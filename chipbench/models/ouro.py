"""Ouro (a looped language model) behind ``serving.InferenceEngine`` ->
``DecodeScheduler`` (``paddle_tpu/models/ouro.py``): the builders, the checks
against the plain reference at the configuration's own shapes, and what the
standing loop (``drivers/serve_standing_ut.py``) calls.  Every size comes from
the configuration's file (the family's own key names).  The bytes a perfect
step must move are in ``chipbench/ouro_decode.py``."""
from __future__ import annotations

import gc

import numpy as np

# THE LIMITS OF ``correct``, each with what it holds and its two readings (my
# chip runs, PR 57; PERF.md section 6 has them by run): the largest the served
# path gave over its seeds, and what the precision below the configuration's
# (K and V rows in 8 bits, float8 e4m3's grid by ``lax.reduce_precision``)
# gives.  WHICH LIMIT FAILS THE 8-BIT CACHE: ``kv_rows`` and ``kv_rows_deep``
# (``SERVED_STATE_TOL`` below, read from the engine's own programs on its own
# cache).  ``LOGIT_TOL`` does not tell it (its 8-bit reading stands under the
# limit, and says so); it fails a WRONG MECHANISM: a cache that keeps ONE loop
# step's rows for all four reads thirty times past it.
#
# The two paged kernels stand-alone at the published shapes ([8, 16, 128]
# queries and a 512-row chunk over 2048-lane pages, the K/V layer given as a
# TRACED scalar) against float32 attention at the highest precision over the
# same bfloat16 rows, max |a - b| / max |b|.  They hold a wrong kernel (a
# page or a layer misaddressed reads 0.3 or more); the statistics a lower
# precision would touch live inside the Pallas kernels, where nothing outside
# can round them, so neither has a lower-precision reading:
#   walk_decode: the plain walk works on exact bfloat16 parts of its float32
#     operands: 2.0e-4 to 2.8e-4 served over nine seeds.
#   walk_prefill: the chunk kernel's float32 dots are one bfloat16 pass of the
#     MXU: 2.4e-3 to 3.5e-3 served.
#   walk_*_static: the same calls with the layer as a Python int, judged to
#     be EQUAL to the traced form's (0.0 in every run).
MECHANISM_RTOL = {"walk_decode": 2e-3, "walk_prefill": 1.2e-2,
                  "walk_decode_static": 0.0, "walk_prefill_static": 0.0}
NOT_JUDGED = ("kv_rows_8bit", "kv_rows_deep_8bit_min", "kv_rows_deep_median",
              "kv_rows_deep_max", "logits_8bit_rows",
              "logits_shared_step_rows")
# next-token LOGITS of the step FUNCTIONS (a second ``jax.jit`` of
# ``prefill_chunk`` / ``decode_step`` that also returns the gates, on a cache
# of the cell's size) against the float32 reference, max |a - b| over the
# vocabulary in standard deviations of the reference's logits, at the last
# rows of the last whole chunk, the one-token chunk and N_DECODE decode steps:
# 48 layer applications of bfloat16 weights on bfloat16-rounded activations
# (the residual stream and the norms are float32).  Served 0.045 to 0.106 over
# 84 readings of the final tree's seven runs (0.062 to 0.080 over 48 of four
# earlier ones).  The same replay over a cache in which every loop step reads
# the LAST step's rows (``logits_shared_step_rows``) reads 4.4 to 5.6: the
# limit, 2.4 times the largest served reading and 17 times under the smallest
# of those, holds the whole path (cache, chunking, the loop's carry, the K/V
# layer's numbering) against a wrong mechanism.  It does NOT tell a lower
# precision: over a cache rounded to 8 bits after every program
# (``logits_8bit_rows``) it reads 0.085 to 0.112, inside the served range,
# bfloat16 weights and activations being most of the error; the 8-bit cache
# fails by ``kv_rows`` / ``kv_rows_deep``.
LOGIT_TOL = 0.25
# exit-gate logits of the same rows against the reference's, max |g - g_ref|
# (a gate's logit has a spread of about 0.3 over rows): 0.004 to 0.013 served.
GATE_TOL = 0.05
# a served token may differ from the reference's choice only where the
# reference puts it within TIE_TOL standard deviations of its own top logit:
# two logits each off by up to 0.106 can swap when they are 0.21 apart.  Every
# gap read so far is at most 0.053, and every one of 128 tokens a request
# agreed.
TIE_TOL = 0.2
CHECKED_TOKENS = 128
TOKENS_AGREE = 0.7
ROUTING_AGREE = 1.0        # every replayed row's gates within GATE_TOL
N_DECODE = 4


def make_params(cfg, seed):
    from paddle_tpu import observability as obs
    from paddle_tpu.models import ouro as M

    with obs.span("serving.model_load", model="ouro-weights"):
        import jax

        params = M.params(cfg, seed, dtype=cfg["weights_dtype"])
        jax.block_until_ready(params)
    return params, {"cfg": cfg}


def decode_config(cfg, max_new_tokens):
    from paddle_tpu import serving

    return serving.DecodeConfig(
        num_slots=cfg["slots"], page_size=cfg["page"],
        max_seq_len=cfg["max_seq_len"], num_pages=cfg["num_pages"],
        prefill_buckets=tuple(cfg["buckets"]),
        prefill_chunk_tokens=cfg["chunk"], prefix_cache=cfg["prefix_cache"],
        max_new_tokens=max_new_tokens, queue_capacity=cfg["queue_capacity"],
        kv_dtype=cfg["kv_dtype"])


def build_engine(cfg, params, meta, max_new_tokens):
    """The front door, warmed up (the constructor compiles the decode step
    and every prefill chunk width)."""
    from paddle_tpu import serving
    from paddle_tpu.models import ouro as M

    return serving.InferenceEngine(
        decode_model=M.build_decode_model(params, cfg),
        decode_config=decode_config(cfg, max_new_tokens))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    if not np.all(np.isfinite(a)):
        return float("inf")
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def mechanism_errors(cfg, params, seed, reference):
    """The two paged kernels as the step programs call them (the K/V layer a
    traced scalar, and once more as a Python int) against float32 attention
    over the same rows, at the configuration's heads, page and chunk."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import flash_attention as FA

    gc.collect()
    H, Dh = cfg["num_attention_heads"], cfg["head_dim"]
    ps, C, S = cfg["page"], cfg["chunk"], cfg["slots"]
    L, at = 4, 3                              # a small stack, its last layer
    T = min(3 * C + 5 * ps + 7, cfg["max_seq_len"] - C)
    npg = -(-(T + C) // ps)
    ks = jax.random.split(jax.random.PRNGKey((seed + 5) % (2 ** 31)), 6)
    kv_dt = jnp.dtype(cfg["kv_dtype"])
    k, v = (jax.random.normal(kk, (npg * ps, H * Dh), jnp.float32).astype(kv_dt)
            for kk in ks[:2])
    perm = 1 + jax.random.permutation(ks[2], npg).astype(jnp.int32)
    noise = jax.random.normal(ks[3], (L, npg + 1, ps, H * Dh),
                              jnp.float32).astype(kv_dt)
    pool_k = noise.at[at, perm].set(k.reshape(npg, ps, H * Dh))
    pool_v = noise.at[at, perm].set(v.reshape(npg, ps, H * Dh))
    scale = Dh ** -0.5

    def dense(q, limit):
        """``q [R, H, Dh]``, row ``r`` over keys ``0 .. limit[r] - 1``."""
        with jax.default_matmul_precision("highest"):
            kf = k.astype(jnp.float32).reshape(-1, H, Dh)
            vf = v.astype(jnp.float32).reshape(-1, H, Dh)
            s = jnp.einsum("rhd,khd->rhk", q.astype(jnp.float32), kf) * scale
            ok = jnp.arange(kf.shape[0])[None, :] < limit[:, None]
            p = jax.nn.softmax(jnp.where(ok[:, None, :], s, -1e30), axis=-1)
            return jnp.einsum("rhk,khd->rhd", p, vf)

    errs = {}
    lens = np.linspace(1, T, S).astype(np.int32)
    lens[-1] = 0
    q = jax.random.normal(ks[4], (S, H, Dh), jnp.float32).astype(kv_dt)
    tables = jnp.broadcast_to(perm[None, :], (S, npg))
    walk = jax.jit(lambda q, pk, pv, n, l: FA.paged_decode_attention(
        q, pk, pv, tables, n, layer=l, sm_scale=scale))
    got = np.asarray(walk(q, pool_k, pool_v, jnp.asarray(lens), jnp.int32(at))
                     .astype(jnp.float32))
    fixed = np.asarray(jax.jit(lambda q, pk, pv, n: FA.paged_decode_attention(
        q, pk, pv, tables, n, layer=at, sm_scale=scale))(
            q, pool_k, pool_v, jnp.asarray(lens)).astype(jnp.float32))
    want = np.asarray(jax.jit(dense)(q, jnp.asarray(np.maximum(lens, 1))))
    live = lens > 0
    errs["walk_decode"] = _rel(got[live], want[live])
    errs["walk_decode_static"] = float(np.abs(got - fixed).max())
    if got[~live].any():
        errs["walk_decode_empty_slot_not_zero"] = float("inf")

    start = ((T - C) // ps) * ps
    qc = jax.random.normal(ks[5], (C, H, Dh), jnp.float32).astype(kv_dt)
    chunk = jax.jit(lambda q, pk, pv, l: FA.paged_prefill_attention(
        q, pk, pv, perm, jnp.int32(start), layer=l, sm_scale=scale))
    got = np.asarray(chunk(qc, pool_k, pool_v, jnp.int32(at))
                     .astype(jnp.float32))
    fixed = np.asarray(jax.jit(lambda q, pk, pv: FA.paged_prefill_attention(
        q, pk, pv, perm, jnp.int32(start), layer=at, sm_scale=scale))(
            qc, pool_k, pool_v).astype(jnp.float32))
    want = np.asarray(jax.jit(dense)(
        qc, start + 1 + jnp.arange(C, dtype=jnp.int32)))
    errs["walk_prefill"] = _rel(got, want)
    errs["walk_prefill_static"] = float(np.abs(got - fixed).max())
    return errs


def checked_layers(cfg):
    """The K/V layers whose rows are held to the reference: the first and the
    last layer of each loop step, as ``(u, l)``."""
    L = cfg["num_hidden_layers"]
    return [(u, l) for u in range(cfg["total_ut_steps"])
            for l in sorted({0, L - 1})]


_REFERENCE_FN = {}
_CONTROL = {}           # the control replays' logits, then their errors
LAST = {}               # the compiled decode program's text, for the driver


def reference_logits(cfg, params, sequence, positions, reference,
                     forced=None):
    """The reference's logits ``[P, V]`` at ``positions`` of ``sequence``, its
    gate logits there ``[P, U]`` (in the slot of the loop's routing
    comparison) and the K and V rows ``[P, 2, H * Dh]`` of each of
    :func:`checked_layers`.  The sequence is padded to the configuration's
    ``max_seq_len`` (whole blocks) and the positions to whole chunks: one
    compiled program for most lengths."""
    import jax
    import jax.numpy as jnp

    block = 512
    T = -(-cfg["max_seq_len"] // block) * block
    seq = np.zeros(T, np.int32)
    seq[:len(sequence)] = sequence
    n, C = len(positions), cfg["chunk"]
    positions = list(positions) + [positions[-1]] * (-n % C)
    key = (id(reference), len(positions))
    fn = _REFERENCE_FN.get(key)
    if fn is None:
        def run(p, s, q):
            logits, gates, kv = reference.forward(
                p, cfg, s, q, block=block, rows=checked_layers(cfg))
            return logits, gates, [jnp.stack([k[q], v[q]], axis=1)
                                   for k, v in kv]
        fn = _REFERENCE_FN[key] = jax.jit(run)
    logits, gates, rows = fn(params, jnp.asarray(seq),
                             jnp.asarray(positions, jnp.int32))
    logits = np.asarray(logits, np.float64)[:n]
    for name in ("logits_8bit", "logits_shared_step"):
        low = _CONTROL.pop(name, None)
        if low is not None:
            # the loop asks for the replayed positions first: the control's
            # error in the loop's own measure
            _CONTROL[name + "_rows"] = max(
                float(np.max(np.abs(a - b)) / b.std())
                if np.all(np.isfinite(a)) else float("inf")
                for a, b in zip(low, logits))
    return (logits, [np.asarray(gates, np.float64).T[:n]],
            [np.asarray(r, np.float64)[:n] for r in rows])


def gap(logits, token):
    """How far ``token`` sits below the top of ``logits``, in their standard
    deviations (0 where it is the top)."""
    return float((logits.max() - logits[int(token)]) / logits.std())


# ONE SCHEDULE, RUN TWICE over a checked sequence (as ``models/mellum.py``
# does): through the engine's OWN compiled step programs into the engine's OWN
# cache after the drain (:func:`served_state_errors`, which reads the rows
# they leave), and through the step FUNCTIONS under a ``jax.jit`` that also
# returns the gates (:func:`replay`), on a cache of the cell's size.
# ``sequence[:n]`` (``n`` = ``split`` floored to a page) in chunks of
# ``chunk``, token ``n`` through the narrowest chunk program, then
# ``N_DECODE`` tokens decoded in slot 0 while every other slot decodes random
# ids on a page of its own.
def _schedule(cfg, cache, sequence, split, seed, chunk, decode):
    """``chunk(width, tokens, start, valid, pages written, table row)`` and
    ``decode(tokens, positions, tables, kv_lens)`` are the two programs.
    Returns ``(release, first, end, chunk results, decode results, pages)``:
    rows ``first .. end - 1`` are the last whole-width chunk's, the narrow
    chunk's and the decoded ones."""
    import jax.numpy as jnp

    S, ps, C = cfg["slots"], cfg["page"], cfg["chunk"]
    narrow = min(b for b in list(cfg["buckets"]) + [C] if b <= C)
    n = (min(split, len(sequence) - 1 - N_DECODE) // ps) * ps
    assert n > 0, "a checked sequence is a page and %d tokens" % (N_DECODE + 1)
    end = n + 1 + N_DECODE
    pages = cache.alloc(cache.pages_for(end))
    rest = [cache.alloc(1)[0] for _ in range(S - 1)]
    tables = np.zeros((S, cache.max_pages_per_seq), np.int32)
    tables[0] = cache.table_row(pages)
    tables[1:, 0] = rest
    rng = np.random.RandomState(seed % (2 ** 32))

    def one(w, start, valid):
        tokens = np.zeros(w, np.int32)
        tokens[:valid] = sequence[start:start + valid]
        vec = np.zeros(max(1, w // ps), np.int32)
        m = max(0, min(len(vec), len(pages) - start // ps))
        vec[:m] = pages[start // ps:start // ps + m]
        return chunk(w, jnp.asarray(tokens), jnp.int32(start),
                     jnp.int32(valid), jnp.asarray(vec),
                     jnp.asarray(tables[0]))

    chunks = [one(C, start, min(C, n - start)) for start in range(0, n, C)]
    chunks.append(one(narrow, n, 1))
    steps = []
    for pos in range(n + 1, end):
        tokens = rng.randint(0, cfg["vocab_size"], S).astype(np.int32)
        tokens[0] = sequence[pos]
        positions = np.full(S, pos - n, np.int32)
        positions[0] = pos
        steps.append(decode(jnp.asarray(tokens), jnp.asarray(positions),
                            jnp.asarray(tables), jnp.asarray(positions + 1)))
    return (lambda: cache.free(pages + rest), max(0, ((n - 1) // C) * C), end,
            chunks, steps, pages)


def replay_fns(cfg):
    """The two step functions under a ``jax.jit`` of their own that also
    returns the gates: made once a run, so that every checked request replays
    through the same executables."""
    import jax

    from paddle_tpu.models import ouro as M

    donate = () if jax.default_backend() == "cpu" else (1,)
    return (jax.jit(lambda p, c, *a: M.prefill_chunk(
                p, *a[:3], c, *a[3:], cfg=cfg, with_gates=True),
                donate_argnums=donate),
            jax.jit(lambda p, c, *a: M.decode_step(
                p, *a[:2], c, *a[2:], cfg=cfg, with_gates=True),
                donate_argnums=donate))


def fresh_cache(cfg):
    """A cache of the cell's size, as the scheduler builds it."""
    from paddle_tpu import serving
    from paddle_tpu.models import ouro as M

    layout = M.cache_layout(cfg)
    return serving.PagedKVCache(
        layout["num_layers"], cfg["num_pages"], cfg["page"],
        layout["num_heads"], layout["head_dim"], cfg["max_seq_len"],
        dtype=cfg["kv_dtype"], num_slots=cfg["slots"])


def _controls(cfg):
    """``{name: pools -> pools}``, each applied after every program of a
    control replay.  ``logits_8bit``: every row of the cache rounded to 8 bits
    (float8 e4m3's grid) by ``reduce_precision``, which the compiler keeps (a
    conversion pair inside one program it removes on the chip: PR 49).
    ``logits_shared_step``: the rows of the last loop step's K/V layers
    written over every other step's - a cache that kept ONE step's rows."""
    import jax

    donate = () if jax.default_backend() == "cpu" else (0,)
    L, U = cfg["num_hidden_layers"], cfg["total_ut_steps"]

    def shared(pools):
        out = {}
        for name, leaf in pools.items():
            for u in range(U - 1):
                leaf = jax.lax.dynamic_update_slice_in_dim(
                    leaf, leaf[(U - 1) * L:], u * L, axis=0)
            out[name] = leaf
        return out

    return {
        "logits_8bit": jax.jit(lambda pools: {
            name: jax.lax.reduce_precision(leaf, exponent_bits=4,
                                           mantissa_bits=3)
            for name, leaf in pools.items()}, donate_argnums=donate),
        "logits_shared_step": jax.jit(shared, donate_argnums=donate)}


def replay(cfg, params, sequence, split, seed, fns):
    """The step functions' own LOGITS and gates on the schedule above (``fns``
    from :func:`replay_fns`, a fresh cache of the cell's size).  Returns
    ``(logits [2 + N_DECODE, V] at positions n - 1 .. end - 1, [gates [end -
    first, U]], first, end)``.  The run's FIRST replay is made twice more,
    once a control of :func:`_controls`; each one's distance from the
    reference's logits is a reading beside ``LOGIT_TOL``
    (:func:`reference_logits` takes them, :func:`deep_row_errors` reports
    them)."""
    import jax.numpy as jnp

    def run(after=None):
        cache = fresh_cache(cfg)
        pools = [cache.pools]
        gates = []

        def keep(new):
            pools[0] = after(new) if after else new

        def chunk(width, tokens, start, valid, written, row):
            logits, new, g = fns[0](params, pools[0], tokens, start, valid,
                                    written, row, jnp.int32(0))
            keep(new)
            gates.append(np.asarray(g, np.float64).T[:int(valid)])
            return np.asarray(logits, np.float64)

        def decode(tokens, positions, tables, lens):
            logits, new, _, g = fns[1](params, pools[0], tokens, positions,
                                       tables, lens)
            keep(new)
            gates.append(np.asarray(g, np.float64).T[:1])
            return np.asarray(logits[0], np.float64)

        _, first, end, chunks, steps, _ = _schedule(
            cfg, cache, sequence, split, seed, chunk, decode)
        rows = np.concatenate(gates)
        del pools[:], cache
        return (np.stack(chunks[-2:] + steps), rows[len(rows) - (end - first):],
                first, end)

    logits, gates, first, end = run()
    if "logits_8bit_rows" not in _CONTROL:
        for name, after in _controls(cfg).items():
            gc.collect()
            _CONTROL[name] = run(after)[0]
    gc.collect()
    return logits, [gates], first, end


def routing_agreement(served, reference_gates):
    """The loop's routing slot carries the exit gates: ``(share of replayed
    rows whose gate logits all lie within GATE_TOL of the reference's, the
    largest distance)``."""
    assert served.shape == reference_gates.shape
    far = np.abs(served - reference_gates).max(axis=-1)
    return float((far <= GATE_TOL).mean()), float(far.max())


# THE ROWS HELD ON THE OBJECT THAT IS TIMED (the engine's own executables on
# the engine's own cache, the schedule above; readings: my chip runs, PR 57):
#   kv_rows: K/V layer 0's K and V rows (loop step 0, layer 0) at every
#     position of the schedule.  Such a row depends on its token and position
#     alone, so the reference gives it without the cache, in float32: max |row
#     - reference| / max |reference| over K and V.  bfloat16 rows read 3.3e-3
#     to 3.9e-3; the same rows kept in 8 bits (``kv_rows_8bit``) 4.7e-2 to
#     5.5e-2; the limit sits between them in the logarithm.
#   kv_rows_deep: the rows of the OTHER checked K/V layers (the last layer of
#     every loop step and the first of steps 1 .. U - 1: inputs that passed
#     through up to 47 layer applications and, from step 1 on, through the
#     loop-end norm) at positions ``first .. end - 1``: the share of (row,
#     layer, K | V) entries whose distance from the reference's row, in the
#     row's own norm, is past ``DEEP_ROW_TOL``; the median and largest
#     distance beside it (6.9e-3 to 8.6e-3 and 1.2e-2 to 1.7e-2), and the
#     NEAREST such row kept in 8 bits (``kv_rows_deep_8bit_min``: 2.5e-2 to
#     2.6e-2).  ``DEEP_ROW_TOL`` is their geometric middle, and a hundredth of
#     the entries may pass it: every 8-bit row does.
SERVED_STATE_TOL = {"kv_rows": 1.2e-2, "kv_rows_deep": 1e-2}
DEEP_ROW_TOL = 0.021


def served_state_errors(cfg, scheduler, sequence, split, seed, params,
                        reference):
    """``kv_rows`` from ``scheduler``'s own programs and cache (stopped, every
    page free), and for :func:`deep_row_errors` the rows they left in each of
    :func:`checked_layers` at positions ``first .. end - 1``: ``(errs,
    (first, [(k rows, v rows) per checked layer]))``.  Also keeps the compiled
    decode program's text for the driver."""
    import jax
    import jax.numpy as jnp

    cache, ps = scheduler.cache, cfg["page"]
    L = cfg["num_hidden_layers"]
    zeros = (jnp.zeros((cfg["slots"],), jnp.uint32),
             jnp.zeros((cfg["slots"],), jnp.float32))

    def chunk(width, *args):
        scheduler.run_step(("chunk", width), *args, np.int32(0), np.uint32(0),
                           np.float32(0))

    def decode(*args):
        scheduler.run_step(("decode",), *args, *zeros)

    LAST["program_text"] = scheduler.decode_program_text()
    release, errs = None, {}
    try:
        release, first, end, _, _, pages = _schedule(
            cfg, cache, sequence, split, seed, chunk, decode)
        ids = jnp.asarray(pages)

        def rows(leaf, at, lo):
            got = cache.pools[leaf][at, ids].reshape(len(pages) * ps, -1)
            return got[lo:end]

        served = [tuple(np.asarray(rows(leaf, u * L + l, first).astype(
            jnp.float32), np.float64) for leaf in ("k", "v"))
            for u, l in checked_layers(cfg)]
        got = [rows(leaf, 0, 0) for leaf in ("k", "v")]
        want = jax.jit(lambda p, t: reference.first_layer_rows(
            p, cfg, t, jnp.arange(t.shape[0], dtype=jnp.int32)))(
                params, jnp.asarray(np.asarray(sequence[:end], np.int32)))
        errs["kv_rows"] = max(_rel(g.astype(jnp.float32), w)
                              for g, w in zip(got, want))
        errs["kv_rows_8bit"] = max(
            _rel(jax.lax.reduce_precision(g.astype(jnp.float32), 4, 3), w)
            for g, w in zip(got, want))
    finally:
        if release is not None:
            release()
    return errs, (first, served)


def deep_row_errors(cfg, first, served, reference_rows):
    """``kv_rows_deep`` from ``served = (first, [(k, v) per checked layer])``
    and the reference's K and V rows of the same layers at positions ``lo ..
    end - 1`` (``[n, 2, width]``, ``lo = max(0, first - chunk)``), and the
    control replays' readings."""
    import ml_dtypes

    at, layers = served
    lo = max(0, first - cfg["chunk"])
    far_rows, far_8bit = [], []

    def far(got, want):
        return np.linalg.norm(got - want, axis=-1) / np.maximum(
            np.linalg.norm(want, axis=-1), 1e-30)

    for index, ((k, v), want) in enumerate(zip(layers, reference_rows)):
        if not index:
            continue                # K/V layer 0 is ``kv_rows``
        for which, g in enumerate((k, v)):
            if not np.all(np.isfinite(g)):
                return {"kv_rows_deep": float("inf")}
            w = np.asarray(want, np.float64)[at - lo:at - lo + len(g), which]
            far_rows.append(far(g, w))
            far_8bit.append(far(g.astype(ml_dtypes.float8_e4m3fn).astype(
                np.float64), w))
    errs = {name: _CONTROL[name] for name in (
        "logits_8bit_rows", "logits_shared_step_rows") if name in _CONTROL}
    far_all = np.concatenate(far_rows) if far_rows else np.zeros(1)
    if far_8bit:
        errs["kv_rows_deep_8bit_min"] = float(np.concatenate(far_8bit).min())
    errs.update({"kv_rows_deep": float((far_all > DEEP_ROW_TOL).mean()),
                 "kv_rows_deep_median": float(np.median(far_all)),
                 "kv_rows_deep_max": float(far_all.max())})
    return errs
