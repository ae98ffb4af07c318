"""The quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py            # one TPU chip: device, train, conv, serve
    python chip_smoke.py --chips 4  # four chips: ONLY the across-chip phase

One process, no children that need the chip.  It drives the two paths users
depend on through the normal entry points at Transformer-base widths (depth
and widths as published; weights random, from ``--seed``), checks what comes
out, and exits non-zero at the first phase that fails with that phase's
traceback.  Nothing here or below it selects a platform: ``main`` refuses
anything that is not a TPU, and no exception in a phase is turned into a
result.  The last line of stdout is one JSON object and nothing follows it.

The phase functions take their sizes as an argument (``SIZES`` below is the
only table) and the place to run on, so a tier-1 test can call the train and
serve phases at toy widths on the CPU; what only a chip can show (compiled
kernels in the executable, TPU residency) is asserted by ``main`` on the
reports the phases return.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

# Transformer-base (Vaswani et al. 2017, "base"): 6 layers, 8 heads,
# d_model 512, d_inner 2048, vocabulary 30000 — the one model the repo both
# trains (models/transformer.py graph) and serves (lm_params ->
# build_decode_model).
_BASE = dict(n_layer=6, n_head=8, d_model=512, d_inner=2048, vocab=30000)

SIZES = {
    # bf16 is asked for the way a user asks: contrib.mixed_precision.decorate
    # (bf16 matmuls, f32 master weights and f32 everything else).  lr is a
    # constant so that 8 steps on one repeated batch have to lower the loss.
    "train": dict(_BASE, batch=64, seq=256, dropout=0.1, lr=5e-4,
                  steps=8, prefetched=4),
    # the flash kernel alone at the model's shape, against mha_reference
    "kernel": dict(batch=64, n_head=8, seq=256, head_dim=64),
    # ResNet-50; bf16 by the default matmul/conv policy (bf16 data meets f32
    # master weights, ops/common.py computes in the narrower type)
    "conv": dict(batch=128, depth=50, class_dim=1000, image=224, steps=4),
    "serve": dict(_BASE, slots=16, max_seq_len=2048, page=16,
                  chunk=512, buckets=(128, 512, 2048), new_tokens=16,
                  # 8 prompts; #1 (1024) is longer than `chunk`, #6 and #7
                  # share their first `shared_prefix` tokens
                  prompt_lens=(32, 1024, 100, 300, 64, 700, 200, 260),
                  shared_prefix=192),
    # --chips 4 only: dp x tp training against one device, then 4 replicas
    "mesh": dict(_BASE, batch=64, seq=256, dropout=0.0, lr=5e-4, steps=4,
                 mesh_shape=(2, 2)),
    "pool": dict(_BASE, replicas=4, slots=8, max_seq_len=512, page=16,
                 chunk=128, buckets=(32, 128, 512), new_tokens=16,
                 prompt_lens=(32, 200, 64, 300, 48, 150, 90, 260),
                 shared_prefix=0),
}

# bf16 has 8 significand bits (relative step 2^-8 ~ 0.4%).  The flash kernel
# rounds p to the input dtype before p.v and its backward rounds ds likewise,
# so kernel and reference differ by a few bf16 steps of the largest value.
KERNEL_RTOL = 2e-2   # max |kernel - ref| <= KERNEL_RTOL * max |ref|, per tensor
# Paged (Pallas) vs gather-reference serving engines: NOT bitwise.  The
# reference contracts q.k on the MXU at default precision (operands rounded
# to bf16) while the decode kernel's products are f32-exact (the MXU over
# exact bf16 parts of q and p), and the online softmax sums a turn of pages
# at a time.  Claimed instead: (a) each paged kernel
# alone matches its reference within PAGED_RTOL on the chip; (b) generated
# tokens match the reference engine's exactly, except that a request may
# part ways at a step where an independent f32 forward (lm_prefill,
# mha_reference, highest matmul precision) puts BOTH candidate tokens within
# TIE_TOL standard deviations (of the logits) of its own top logit — a near
# tie for first place that either engine may break either way.  From that
# step on the two engines decode different contexts, so the rest of that
# request is not compared.
PAGED_RTOL = 2e-2
TIE_TOL = 0.05
# ParallelExecutor (dp x tp) vs one device.  First step (identical
# parameters): __graft_entry__.py's dry-run bound.  Later steps: Adam's first
# updates are lr * m / sqrt(v) ~ lr * sign(g), so a bf16-level difference in
# a near-zero gradient (the tp split changes the order of f32 partial sums)
# moves that parameter by up to 2 * lr, and the two runs' parameters part by
# more than rounding; measured 1e-4 .. 5e-4 on a toy CPU mesh.  The bound
# below is still far inside the loss's own step-to-step change.
MESH_LOSS_RTOL = 2e-5
MESH_LATER_RTOL = 5e-3


def log(msg):
    print(msg, flush=True)


def _xla_compiles():
    """jax's compile requests as the program counts them itself
    (``obs.watch_compiles()``, armed by ``enable_compilation_cache()`` and
    so by the first ``Executor()``): ``requests`` (one a backend compile,
    whether the persistent cache answered or XLA did), ``cache_hits`` and
    ``cache_misses``, each summed over every ``within``."""
    from paddle_tpu import observability as obs

    out = {"requests": 0, "cache_hits": 0, "cache_misses": 0}
    for key, cell in obs.get_telemetry().counters().items():
        kind = obs.split_labels(key)[0].rpartition("xla.compile.")[2]
        if kind in out:
            out[kind] += cell.value
    return out


def _xla_compile_requests():
    return _xla_compiles()["requests"]


def _finite(x):
    return bool(np.all(np.isfinite(np.asarray(x, np.float32))))


# ---------------------------------------------------------------------------
# phase 2: train
# ---------------------------------------------------------------------------


def _build_transformer(sz):
    """Transformer-base training graph: flash kernels on, bf16 through
    ``mixed_precision.decorate``, built with ``optimizer.minimize``."""
    import paddle_tpu as fluid
    from paddle_tpu import layers, optimizer
    from paddle_tpu.contrib import mixed_precision
    from paddle_tpu.models import transformer as T

    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        words = [layers.data(name=n, shape=[sz["seq"]], dtype="int64")
                 for n in ("src_word", "trg_word", "lbl_word")]
        loss, _, _, _ = T.transformer(
            *words, sz["vocab"], sz["vocab"], sz["seq"], sz["n_layer"],
            sz["n_head"], sz["d_model"], sz["d_inner"], sz["dropout"],
            use_flash=True)
        mixed_precision.decorate(optimizer.AdamOptimizer(
            learning_rate=sz["lr"], beta1=0.9, beta2=0.98, epsilon=1e-9)
        ).minimize(loss)
    return main, startup, loss


def _word_batch(sz, seed):
    rng = np.random.RandomState(seed)
    return {n: rng.randint(1, sz["vocab"], size=(sz["batch"], sz["seq"])
                           ).astype(np.int64)
            for n in ("src_word", "trg_word", "lbl_word")}


def phase_train(sz, place, seed=0):
    """Transformer through Executor.run, flash kernels on, bf16 via
    ``mixed_precision.decorate``; the second half of the steps fed through
    the device prefetcher the way ``Trainer.train`` feeds."""
    import paddle_tpu as fluid
    from paddle_tpu import executor as executor_mod
    from paddle_tpu.observability import xla_stats
    from paddle_tpu.reader import device_prefetch

    np.random.seed(seed)
    main, startup, loss = _build_transformer(sz)
    main.random_seed = startup.random_seed = seed + 1
    feed = _word_batch(sz, seed)
    exe = fluid.Executor(place)
    device = place.jax_device()
    scope = fluid.Scope()
    losses, times = [], []
    xla_stats.enable()   # captures the compiled step's analyses once
    try:
        with fluid.scope_guard(scope):
            t0 = time.perf_counter()
            exe.run(startup)
            log("train: startup program ran in %.1f s" % (time.perf_counter() - t0))

            def step(f):
                t0 = time.perf_counter()
                out = exe.run(main, feed=f, fetch_list=[loss])
                losses.append(float(np.ravel(np.asarray(out[0]))[0]))
                times.append(time.perf_counter() - t0)

            step(feed)
            stats = xla_stats.program_stats()
            compiles = executor_mod.compile_count()
            xla_compiles = _xla_compile_requests()
            for _ in range(sz["steps"] - sz["prefetched"] - 1):
                step(feed)
            # Trainer.train's feed path: DataFeeder conversion + device_put
            # on a background thread, committed device arrays to exe.run
            feeder = fluid.DataFeeder(
                feed_list=[main.global_block().var(n) for n in feed],
                place=place, program=main)
            rows = list(zip(*(feed[n] for n in feed)))
            prefetched = device_prefetch.decorate_device_feed(
                lambda: (rows for _ in range(sz["prefetched"])), feeder, exe,
                main, buffer_size=2)()
            for f in prefetched:
                assert all(d == device for v in f.values()
                           for d in v.devices()), "prefetched feed off-device"
                step(f)
            recompiles = executor_mod.compile_count() - compiles
            xla_recompiles = _xla_compile_requests() - xla_compiles
            state = {n: v for n, v in scope.vars.items()
                     if hasattr(v, "devices")}
    finally:
        xla_stats.disable()
    assert len(losses) == sz["steps"]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], "loss did not fall: %s" % losses
    assert recompiles == 0, "%d compiles after the first step" % recompiles
    # jax's own count (only moves while the persistent cache is on): the
    # host-fed and the prefetched steps must be ONE executable underneath
    assert xla_recompiles == 0, (
        "%d XLA compile requests after the first step" % xla_recompiles)
    off = {n: v.devices() for n, v in state.items() if v.devices() != {device}}
    assert state and not off, "state not on %s: %s" % (device, off)
    warm = sorted(times[2:])
    log("train: losses %s" % " ".join("%.4f" % v for v in losses))
    log("train: step_ms median after warm-up %.2f (first step incl. compile "
        "%.1f s)" % (1e3 * warm[len(warm) // 2], times[0]))
    return {"losses": losses, "kernel_calls": stats.kernel_calls,
            "temp_bytes": stats.temp_bytes, "n_state": len(state),
            "platforms": sorted({d.platform for v in state.values()
                                 for d in v.devices()})}


def phase_kernel(sz, seed=0):
    """flash_attention alone (causal, kv_lens): output and the three
    gradients against mha_reference, both in bf16 on the default device."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel.flash_attention import (flash_attention,
                                                     mha_reference)

    shape = (sz["batch"], sz["n_head"], sz["seq"], sz["head_dim"])
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in ks[:4])
    lens = jax.random.randint(ks[4], (sz["batch"],), sz["seq"] // 4,
                              sz["seq"] + 1).astype(jnp.int32)

    def run(attn):
        # everything is an argument: an array closed over would be baked
        # into the executable as a constant (tens of MB in the compile cache)
        def f(q, k, v, w, lens):
            out = attn(q, k, v, causal=True, kv_lens=lens)
            return jnp.sum(out.astype(jnp.float32) * w.astype(jnp.float32)), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            f, argnums=(0, 1, 2), has_aux=True))(q, k, v, w, lens)
        return (out,) + grads

    errs = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), run(flash_attention),
                          run(mha_reference)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert _finite(a), name
        errs[name] = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
    log("kernel: flash vs mha_reference at %s, max err / max |ref|: %s "
        "(bound %g)" % (list(shape), errs, KERNEL_RTOL))
    assert max(errs.values()) <= KERNEL_RTOL, errs
    return errs


# ---------------------------------------------------------------------------
# phase 3: train, conv
# ---------------------------------------------------------------------------


def phase_conv(sz, place, seed=0):
    import paddle_tpu as fluid
    from paddle_tpu.models import resnet

    np.random.seed(seed)
    with fluid.unique_name.guard():
        model = resnet.get_model(
            batch_size=sz["batch"], class_dim=sz["class_dim"],
            depth=sz["depth"], image_shape=(3, sz["image"], sz["image"]),
            lr=0.1, dtype="bfloat16")
    rng = np.random.RandomState(seed)
    feed = {"data": rng.randn(sz["batch"], 3, sz["image"], sz["image"]
                              ).astype(np.float32),
            "label": rng.randint(0, sz["class_dim"], size=(sz["batch"], 1)
                                 ).astype(np.int64)}
    exe = fluid.Executor(place)
    losses = []
    with fluid.scope_guard(fluid.Scope()):
        exe.run(model["startup"])
        for _ in range(sz["steps"]):
            out = exe.run(model["main"], feed=feed, fetch_list=[model["loss"]])
            losses.append(float(np.ravel(np.asarray(out[0]))[0]))
    log("conv: losses %s" % " ".join("%.4f" % v for v in losses))
    assert all(np.isfinite(losses)), losses
    return {"losses": losses}


# ---------------------------------------------------------------------------
# phase 4: serve
# ---------------------------------------------------------------------------


def _prompts(sz, seed):
    rng = np.random.RandomState(seed + 7)
    prompts = [rng.randint(1, sz["vocab"], size=n).astype(np.int32)
               for n in sz["prompt_lens"]]
    if sz["shared_prefix"]:
        prompts[-1][:sz["shared_prefix"]] = prompts[-2][:sz["shared_prefix"]]
    return prompts


def _decode_model(sz, seed, attn_impl=None):
    from paddle_tpu.models import transformer as T

    params, meta = T.lm_params(
        seed=seed, vocab_size=sz["vocab"], n_layer=sz["n_layer"],
        n_head=sz["n_head"], d_model=sz["d_model"], d_inner=sz["d_inner"],
        max_length=sz["max_seq_len"])
    return params, T.build_decode_model(params, meta, attn_impl=attn_impl)


def _decode_config(sz):
    from paddle_tpu import serving

    return serving.DecodeConfig(
        num_slots=sz["slots"], page_size=sz["page"],
        max_seq_len=sz["max_seq_len"], prefill_buckets=sz["buckets"],
        prefill_chunk_tokens=sz["chunk"], prefix_cache=True,
        max_new_tokens=sz["new_tokens"], kv_dtype="bfloat16")


def _generate_all(front, prompts, sz):
    """Submit the prompts at once (continuous batching) and wait for all —
    except that a last prompt sharing a prefix goes in after the others are
    done, so that it meets the prefix it shares in the cache."""
    late = 1 if sz["shared_prefix"] else 0
    futs = [front.generate_async(p, max_new_tokens=sz["new_tokens"])
            for p in prompts[:len(prompts) - late]]
    out = [np.asarray(f.result(timeout=600)) for f in futs]
    for p in prompts[len(prompts) - late:]:
        out.append(np.asarray(front.generate(
            p, max_new_tokens=sz["new_tokens"], timeout=600)))
    return out


def _paged_kernels_vs_reference(sz, seed):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import flash_attention as FA

    H, Dh = sz["n_head"], sz["d_model"] // sz["n_head"]
    S, ps, C = sz["slots"], sz["page"], sz["chunk"]
    mp = sz["max_seq_len"] // ps
    P = S * mp + 1
    ks = jax.random.split(jax.random.PRNGKey(seed + 3), 5)
    k_pool = jax.random.normal(ks[0], (P, ps, H, Dh), jnp.bfloat16)
    v_pool = jax.random.normal(ks[1], (P, ps, H, Dh), jnp.bfloat16)
    tables = 1 + jax.random.permutation(ks[2], S * mp).reshape(S, mp).astype(
        jnp.int32)
    lens = jnp.asarray(
        [0] + [int(x) for x in np.linspace(1, mp * ps, S - 1)], jnp.int32)
    q = jax.random.normal(ks[3], (S, H, Dh), jnp.float32)
    qc = jax.random.normal(ks[4], (C, H, Dh), jnp.float32)
    start = jnp.int32(((mp * ps - C) // 2 // ps) * ps)
    calls = {
        "decode": (FA.paged_decode_attention,
                   (q, k_pool, v_pool, tables, lens)),
        "prefill": (FA.paged_prefill_attention,
                    (qc, k_pool, v_pool, tables[1], start)),
    }
    errs = {}
    for name, (fn, args) in calls.items():
        a, b = (np.asarray(jax.jit(
            lambda *xs, fn=fn, impl=impl: fn(*xs, impl=impl))(*args),
            np.float32) for impl in ("pallas", "reference"))
        assert _finite(a), name
        errs[name] = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        if name == "decode":
            assert not a[0].any(), "kv_lens == 0 slot is not exact zeros"
    log("serve: paged kernels vs reference, max err / max |ref|: %s "
        "(bound %g)" % (errs, PAGED_RTOL))
    assert max(errs.values()) <= PAGED_RTOL, errs
    return errs


def _reference_logits(params, sz, prompt, accepted):
    """Next-token logits after ``prompt + accepted`` under an independent
    f32 forward (lm_prefill, mha_reference, highest matmul precision)."""
    import jax

    from paddle_tpu.models import transformer as T

    seq = np.concatenate([prompt, accepted]).astype(np.int32)
    padded = np.zeros(sz["max_seq_len"], np.int32)
    padded[:len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        logits, _, _ = jax.jit(
            lambda t, n: T.lm_prefill(params, t, n,
                                      n_head=sz["n_head"]))(padded, len(seq))
    return np.asarray(logits, np.float64)


def _near_tie(logits, tok_a, tok_b):
    """How far the worse of two candidate next tokens sits below the top of
    the reference ``logits``, in standard deviations of the logits.  Small
    only when BOTH candidates all but tie with the reference's own choice —
    two engines agreeing on the same wrong token is not a tie."""
    return (logits.max() - min(logits[tok_a], logits[tok_b])) / logits.std()


def phase_serve(sz, seed=0):
    """The paged kernels alone against their reference, then the LM behind
    DecodeScheduler through InferenceEngine.generate, paged attention engine
    left at its default (the Pallas kernels on a chip, the gather reference
    on the CPU backend), against a second engine with
    ``attn_impl="reference"``.  See PAGED_RTOL / TIE_TOL for what is
    claimed."""
    from paddle_tpu import executor as executor_mod
    from paddle_tpu import serving

    _paged_kernels_vs_reference(sz, seed)
    prompts = _prompts(sz, seed)
    results = {}
    recompiles = {}
    for impl in (None, "reference"):
        params, dm = _decode_model(sz, seed, attn_impl=impl)
        t0 = time.perf_counter()
        engine = serving.InferenceEngine(decode_model=dm,
                                         decode_config=_decode_config(sz))
        warm = time.perf_counter() - t0
        try:
            compiles = executor_mod.compile_count()
            t0 = time.perf_counter()
            results[impl] = _generate_all(engine, prompts, sz)
            took = time.perf_counter() - t0
            recompiles[impl] = executor_mod.compile_count() - compiles
            health = engine.health()["decode"]
        finally:
            engine.stop()
        log("serve[%s]: warm-up %.1f s, %d requests in %.2f s, compiles after "
            "warm-up %d, prefix-cache hit pages %s"
            % (impl or "default", warm, len(prompts), took, recompiles[impl],
               health.get("prefix", {}).get("kv_hit_pages")))
        assert all(len(r) == sz["new_tokens"] for r in results[impl]), \
            [len(r) for r in results[impl]]
        assert recompiles[impl] == 0, recompiles
        assert health["kv_pages_used"] == 0, health
        hit = health.get("prefix", {}).get("kv_hit_pages", 0)
        assert hit > 0 or not sz["shared_prefix"], "shared prefix never hit"

    exact, ties = 0, []
    for i, (a, b) in enumerate(zip(results[None], results["reference"])):
        diff = np.nonzero(a != b)[0]
        if not len(diff):
            exact += 1
            continue
        at = int(diff[0])
        gap = _near_tie(_reference_logits(params, sz, prompts[i], a[:at]),
                        int(a[at]), int(b[at]))
        ties.append((i, at, round(float(gap), 4)))
        assert gap <= TIE_TOL, (
            "request %d: engines part at generated token %d (%d vs %d) where "
            "the f32 reference puts one of them %.3f logit std below its top "
            "logit (> %g)" % (i, at, a[at], b[at], gap, TIE_TOL))
    log("serve: %d/%d requests token-for-token equal to the reference engine "
        "over %d tokens; near-tie partings (request, token, gap below the "
        "f32 top logit / std): %s"
        % (exact, len(prompts), sz["new_tokens"], ties))
    return {"exact": exact, "ties": ties, "tokens": results[None]}


def serve_lowers_to_kernels(sz, seed=0):
    """Whether decode_fn / prefill_chunk_fn at their default engine lower to
    compiled Pallas kernels here (lowering only — nothing compiles or runs)."""
    import jax
    import jax.numpy as jnp

    _, dm = _decode_model(sz, seed)
    L, H, Dh = sz["n_layer"], sz["n_head"], sz["d_model"] // sz["n_head"]
    S, ps, C = sz["slots"], sz["page"], sz["chunk"]
    mp = sz["max_seq_len"] // ps
    pool = jax.ShapeDtypeStruct((L, S * mp + 1, ps, H * Dh), jnp.bfloat16)
    cache = {"k": pool, "v": pool}
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    texts = {
        "decode": jax.jit(dm.decode_fn).lower(
            dm.params, i32(S), i32(S), cache, i32(S, mp), i32(S)).as_text(),
        "prefill": jax.jit(dm.prefill_chunk_fn).lower(
            dm.params, i32(C), i32(), i32(), cache, i32(C // ps), i32(mp),
            i32()).as_text(),
    }
    return {k: t.count("tpu_custom_call") for k, t in texts.items()}


# ---------------------------------------------------------------------------
# --chips 4: across chips
# ---------------------------------------------------------------------------


def phase_mesh(sz, place, seed=0):
    """dp x tp ParallelExecutor against the single-device Executor: same
    seed, same batch, losses within MESH_LOSS_RTOL / MESH_LATER_RTOL; weights
    and batch spread over every device; collectives in the compiled step."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as fluid
    from paddle_tpu.observability import xla_stats
    from paddle_tpu.parallel_executor import build_mesh

    feed = _word_batch(sz, seed)
    n_dev = int(np.prod(sz["mesh_shape"]))
    devices = jax.devices()[:n_dev]

    def run(parallel):
        np.random.seed(seed)
        main, startup, loss = _build_transformer(sz)
        main.random_seed = startup.random_seed = seed + 1
        scope = fluid.Scope()
        losses = []
        with fluid.scope_guard(scope):
            exe = fluid.Executor(place)
            exe.run(startup)
            f = feed
            if parallel:
                exe = fluid.ParallelExecutor(
                    loss_name=loss.name, main_program=main, scope=scope,
                    mesh_shape=sz["mesh_shape"], devices=devices)
                mesh = build_mesh(sz["mesh_shape"], devices)
                f = {n: jax.device_put(v.astype(np.int32),
                                       NamedSharding(mesh, P("dp")))
                     for n, v in feed.items()}
                for n, v in f.items():
                    held = {s.device for s in v.addressable_shards}
                    assert held == set(devices), (n, held)
                    assert all(s.data.shape[0] == sz["batch"] // sz["mesh_shape"][0]
                               for s in v.addressable_shards), n
            for _ in range(sz["steps"]):
                if parallel:
                    out = exe.run([loss], feed=f)
                else:
                    out = exe.run(main, feed=f, fetch_list=[loss])
                losses.append(float(np.ravel(np.asarray(out[0]))[0]))
        return losses, scope

    ref, _ = run(False)
    xla_stats.enable()
    try:
        got, scope = run(True)
        stats = xla_stats.program_stats()
    finally:
        xla_stats.disable()
    log("mesh: one device  %s" % " ".join("%.6f" % v for v in ref))
    log("mesh: dp x tp %s %s" % (sz["mesh_shape"],
                                 " ".join("%.6f" % v for v in got)))
    for i, (a, b) in enumerate(zip(got, ref)):
        rtol = MESH_LOSS_RTOL if i == 0 else MESH_LATER_RTOL
        assert abs(a - b) <= rtol * max(1.0, abs(b)), (i, got, ref)
    log("mesh: max relative loss difference, first step %.2e (bound %g), "
        "later steps %.2e (bound %g)"
        % (abs(got[0] - ref[0]) / abs(ref[0]), MESH_LOSS_RTOL,
           max(abs(a - b) / abs(b) for a, b in zip(got[1:], ref[1:])),
           MESH_LATER_RTOL))
    split = {}
    for n, v in scope.vars.items():
        shards = getattr(v, "addressable_shards", None)
        if shards and v.ndim == 2 and shards[0].data.shape != v.shape:
            assert {s.device for s in shards} == set(devices), n
            split[n] = shards[0].data.shape
    assert split, "no tp-split weight in scope"
    log("mesh: %d tp-split weights, each with a shard on all %d devices "
        "(e.g. %s); all-reduce etc. in the compiled step: %d; kernel calls %d"
        % (len(split), n_dev, next(iter(split.items())), stats.collectives,
           stats.kernel_calls))
    assert stats.collectives > 0, "no collective in the compiled mesh step"
    return {"losses": got, "ref": ref, "kernel_calls": stats.kernel_calls,
            "collectives": stats.collectives}


def phase_pool(sz, seed=0):
    """ReplicaPool with one replica per device answers the requests with the
    tokens a one-replica pool gives."""
    from paddle_tpu import serving

    prompts = _prompts(sz, seed)
    answers = {}
    for n in (1, sz["replicas"]):
        _, dm = _decode_model(sz, seed)
        pool = serving.ReplicaPool(None, replicas=n, decode_model=dm,
                                   decode_config=_decode_config(sz))
        try:
            answers[n] = _generate_all(pool, prompts, sz)
            stats = pool.replica_stats()
            pools = [{str(d) for d in r.decoder._cache.k_pool.devices()}
                     for r in pool._replicas]
        finally:
            pool.stop()
        log("pool[%d]: replica devices %s, pools on %s, sequences completed "
            "per replica %s"
            % (n, [s["device"] for s in stats], pools,
               [s["decode"]["completed"] for s in stats]))
        assert all(len(p) == 1 for p in pools), pools
        assert len(set.union(*pools)) == n, pools
    for i, (a, b) in enumerate(zip(answers[sz["replicas"]], answers[1])):
        assert np.array_equal(a, b), (i, a, b)
    log("pool: %d requests, %d replicas, tokens equal to the one-replica "
        "answers" % (len(prompts), sz["replicas"]))
    return {"tokens": answers[sz["replicas"]]}


# ---------------------------------------------------------------------------
# main: the only place that knows about the chip
# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    import jax

    import paddle_tpu as fluid

    t_start = time.perf_counter()
    # phase 1: device
    devices = jax.devices()
    d0 = devices[0]
    log("device: platform=%s kind=%s count=%d jax=%s"
        % (d0.platform, d0.device_kind, len(devices), jax.__version__))
    if d0.platform != "tpu" or len(devices) != args.chips:
        log("device: need %d TPU device(s), found %s"
            % (args.chips, [str(d) for d in devices]))
        return 1
    fluid.enable_compilation_cache()   # arms obs.watch_compiles() too
    log("cache: dir=%s (JAX_COMPILATION_CACHE_DIR %s)"
        % (jax.config.jax_compilation_cache_dir,
           "set" if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "unset"))
    place = fluid.TPUPlace()

    def timed(name, fn, *a):
        t0 = time.perf_counter()
        log("phase %s: start %s" % (name, SIZES.get(name, "")))
        out = fn(*a)   # an exception ends the run: traceback, exit code 1
        n = _xla_compiles()
        log("phase %s: ok in %.1f s (persistent compile cache so far: %d hits, "
            "%d misses)" % (name, time.perf_counter() - t0, n["cache_hits"],
                            n["cache_misses"]))
        return out

    if args.chips == 1:
        rep = timed("train", phase_train, SIZES["train"], place, args.seed)
        assert rep["platforms"] == ["tpu"], rep["platforms"]
        assert rep["kernel_calls"] > 0, \
            "no tpu_custom_call in the compiled train step"
        log("train: %d tpu_custom_call in the compiled step, %.2f GB of "
            "temporaries, %d state arrays on the TPU"
            % (rep["kernel_calls"], rep["temp_bytes"] / 1e9, rep["n_state"]))
        timed("kernel", phase_kernel, SIZES["kernel"], args.seed)
        timed("conv", phase_conv, SIZES["conv"], place, args.seed)
        low = serve_lowers_to_kernels(SIZES["serve"], args.seed)
        log("serve: tpu_custom_call in lowered decode / prefill-chunk: %s" % low)
        assert min(low.values()) > 0, low
        timed("serve", phase_serve, SIZES["serve"], args.seed)
    else:
        rep = timed("mesh", phase_mesh, SIZES["mesh"], place, args.seed)
        assert rep["kernel_calls"] > 0, \
            "no tpu_custom_call in the compiled mesh step"
        timed("pool", phase_pool, SIZES["pool"], args.seed)
    from paddle_tpu import observability as obs

    log("cache: the program's xla.compile.* counters: %s" % json.dumps(
        {k: c.value for k, c in sorted(obs.get_telemetry().counters().items())
         if k.startswith("xla.compile.") and c.value}))
    log("total: %.1f s" % (time.perf_counter() - t_start))
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
