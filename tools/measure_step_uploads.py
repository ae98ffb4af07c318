#!/usr/bin/env python
"""What a decode step's host arguments cost on their way to the device, alone:
no model, no scheduler, one tiny jitted program that reads every argument.

Three ways to hand a step's plan (64 slots, a table 128 pages wide) to a
jitted program, each timed on the host clock over ``--iters`` calls with one
call in flight (the call before is read back before the next is made, as the
serving loop does):

  (a) ``separate``: ``jnp.asarray`` of five ``[64]`` vectors (int32 x 3,
      uint32, float32) and of one ``[64, 128]`` int32 table, a ``[64]`` bool
      vector passed as numpy, as ``DecodeScheduler._plan_step`` did before
      PR 59;
  (b) ``packed_asarray``: one ``jnp.asarray`` of a ``[64, 134]`` int32 buffer;
  (c) ``packed_numpy``: the same buffer passed as numpy straight into the
      jitted call.

and the same for a prefill chunk's eight values against one vector.  Prints
one JSON line: the median and the quartiles of a call's host time in
microseconds, a way, and the device it ran on.  Run it on the chip
(``chiprun -- python tools/measure_step_uploads.py``): a CPU run says how
fast the CPU client is, which nobody serves with.
"""
import argparse
import json
import statistics
import time

import numpy as np

SLOTS, PAGES, WIDTH = 64, 128, 256


def _timed(make_call, iters):
    """Host microseconds of ``make_call()`` (returns a device array), a call,
    with the call before read back first."""
    out = make_call()
    for _ in range(20):
        np.asarray(out)
        out = make_call()
    times = []
    for _ in range(iters):
        np.asarray(out)
        t0 = time.perf_counter()
        out = make_call()
        times.append((time.perf_counter() - t0) * 1e6)
    np.asarray(out)
    q1, med, q3 = statistics.quantiles(times, n=4)
    return {"median_us": round(med, 1), "q1_us": round(q1, 1),
            "q3_us": round(q3, 1)}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--iters", type=int, default=2000)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    i32 = [rng.randint(0, 1000, SLOTS).astype(np.int32) for _ in range(3)]
    seeds = rng.randint(0, 2 ** 31, SLOTS).astype(np.uint32)
    temps = rng.rand(SLOTS).astype(np.float32)
    flags = rng.rand(SLOTS) > 0.5
    table = rng.randint(0, 4096, (SLOTS, PAGES)).astype(np.int32)
    previous = jnp.zeros((SLOTS,), jnp.int32)

    @jax.jit
    def separate(tokens, positions, table, kv_lens, seeds, temps, previous,
                 flags):
        tokens = jnp.where(flags, previous, tokens)
        return (tokens + positions + kv_lens + table.sum(axis=1)
                + seeds.astype(jnp.int32) + temps.astype(jnp.int32))

    @jax.jit
    def packed(buf, previous):
        tokens, positions, kv_lens = buf[:, 0], buf[:, 1], buf[:, 2]
        seeds = jax.lax.bitcast_convert_type(buf[:, 3], jnp.uint32)
        temps = jax.lax.bitcast_convert_type(buf[:, 4], jnp.float32)
        tokens = jnp.where(buf[:, 5] != 0, previous, tokens)
        return (tokens + positions + kv_lens + buf[:, 6:].sum(axis=1)
                + seeds.astype(jnp.int32) + temps.astype(jnp.int32))

    def fill():
        # the host's own packing is part of what (b) and (c) pay
        buf = np.empty((SLOTS, 6 + PAGES), np.int32)
        buf[:, 0], buf[:, 1], buf[:, 2] = i32
        buf[:, 3] = seeds.view(np.int32)
        buf[:, 4] = temps.view(np.int32)
        buf[:, 5] = flags
        buf[:, 6:] = table
        return buf

    ways = {
        "separate": lambda: separate(
            jnp.asarray(i32[0]), jnp.asarray(i32[1]),
            jnp.asarray(table.copy()), jnp.asarray(i32[2]),
            jnp.asarray(seeds), jnp.asarray(temps), previous, flags),
        "packed_asarray": lambda: packed(jnp.asarray(fill()), previous),
        "packed_numpy": lambda: packed(fill(), previous),
    }

    # the chunk: tokens[width], start, valid, written[width / 16], gathered
    # [pages], slot, seed, temp against one vector
    tokens = rng.randint(0, 1000, WIDTH).astype(np.int32)
    written = rng.randint(0, 4096, WIDTH // 16).astype(np.int32)
    gathered = table[3]

    @jax.jit
    def chunk_separate(tokens, start, valid, written, gathered, slot, seed,
                       temp):
        return (tokens.sum() + start + valid + written.sum() + gathered.sum()
                + slot + seed.astype(jnp.int32) + temp.astype(jnp.int32))

    @jax.jit
    def chunk_packed(vec):
        temp = jax.lax.bitcast_convert_type(vec[WIDTH + 4], jnp.float32)
        return vec[:WIDTH + 4].sum() + vec[WIDTH + 5:].sum() + temp.astype(
            jnp.int32)

    def fill_chunk():
        vec = np.empty((WIDTH + 5 + len(written) + PAGES,), np.int32)
        vec[:WIDTH] = tokens
        vec[WIDTH:WIDTH + 4] = (128, 77, 3, 12345)
        vec[WIDTH + 4] = np.float32(0.7).view(np.int32)
        vec[WIDTH + 5:WIDTH + 5 + len(written)] = written
        vec[WIDTH + 5 + len(written):] = gathered
        return vec

    ways.update({
        "chunk_separate": lambda: chunk_separate(
            jnp.asarray(tokens), jnp.int32(128), jnp.int32(77),
            jnp.asarray(written), jnp.asarray(gathered), np.int32(3),
            np.uint32(12345), np.float32(0.7)),
        "chunk_packed_asarray": lambda: chunk_packed(
            jnp.asarray(fill_chunk())),
        "chunk_packed_numpy": lambda: chunk_packed(fill_chunk()),
    })

    # both forms compute the same thing from the same values
    assert np.array_equal(np.asarray(ways["separate"]()),
                          np.asarray(ways["packed_numpy"]()))
    assert int(ways["chunk_separate"]()) == int(ways["chunk_packed_numpy"]())

    dev = jax.devices()[0]
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind},
              "iters": args.iters, "slots": SLOTS, "table_pages": PAGES,
              "chunk_width": WIDTH}
    for name, call in ways.items():
        result[name] = _timed(call, args.iters)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
