"""Headline benchmarks on one chip: ResNet-50 ImageNet training throughput
(primary metric) and Transformer-base WMT training throughput (extra metric).

Prints ONE JSON line:
  {"metric": "resnet50_images_per_sec_per_chip", "value": N, "unit": "images/sec",
   "vs_baseline": R, "mfu": F, "extra_metrics": [{"metric":
   "transformer_tokens_per_sec_per_chip", ...}]}

Baselines (reference = PaddlePaddle Fluid 0.15, benchmark/fluid README era):
ResNet-50 ~340 images/sec on a V100 (batch 128, best config) and
Transformer-base ~4.5k tokens/sec/GPU.  vs_baseline = ours / baseline.

Runs on a TPU only: without one it exits 1 before any leg (a number from
another backend must never appear under these metric names).  Every result
carries the device it ran on (platform, device_kind, count).  A leg that
raises is recorded with an "error" field, the remaining legs still run, and
the exit code is 1.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

BASELINE_IMAGES_PER_SEC = 340.0
BASELINE_TOKENS_PER_SEC = 4500.0
V5E_PEAK_BF16_FLOPS = 197e12  # per chip


def _device():
    """The device every result is labelled with; exits 1 without a TPU."""
    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        sys.exit("bench.py needs a TPU; jax found %s" % [str(x) for x in jax.devices()])
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _time_steps(jitted, state, feeds, iters, warmup=3):
    for _ in range(warmup):
        fetches, state = jitted(state, feeds)
    np.asarray(fetches[0])
    t0 = time.perf_counter()
    for _ in range(iters):
        fetches, state = jitted(state, feeds)
    np.asarray(fetches[0])  # device->host read: a true sync
    dt = time.perf_counter() - t0
    return dt, state


def bench_resnet():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.jax_bridge import init_state, program_to_fn
    from paddle_tpu.models import resnet

    batch = 128
    dtype = "bfloat16"
    image_shape = (3, 224, 224)

    with fluid.unique_name.guard():
        model = resnet.get_model(
            batch_size=batch, class_dim=1000, depth=50, image_shape=image_shape,
            lr=0.1, dtype=dtype,
        )
    state = init_state(model["startup"])
    step = program_to_fn(model["main"], [model["loss"]], return_state=True)
    jitted = jax.jit(step, donate_argnums=(0,))

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, *image_shape), dtype=jnp.bfloat16)
    y = rng.randint(0, 1000, size=(batch, 1)).astype(np.int64)
    feeds = {"data": jax.device_put(x), "label": jax.device_put(y)}

    iters = 30
    dt, _ = _time_steps(jitted, state, feeds, iters)
    ips = batch * iters / dt

    # ResNet-50 fwd ≈ 3.8 GFLOPs/img @224²; training (fwd + dgrad + wgrad) ≈ 3×
    train_flops_per_img = 3 * 3.8e9
    return {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(ips / BASELINE_IMAGES_PER_SEC, 3),
        "mfu": round(ips * train_flops_per_img / V5E_PEAK_BF16_FLOPS, 4),
    }


def bench_resnet_real_input(synthetic_ips):
    """ResNet-50 fed by the REAL input path (jpeg corpus -> pre-decoded
    uint8 recordio -> C++ shuffling loader -> crop/flip -> normalize
    on-device), vs the synthetic-feed number: proves whether input is the
    bottleneck.

    Normalization/cast runs inside the jitted step (fuses into the first
    conv) so the host ships uint8 — 4x less host RAM and host->device
    bandwidth."""
    import tempfile

    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.jax_bridge import init_state, program_to_fn
    from paddle_tpu.models import resnet
    from paddle_tpu.reader.image_pipeline import (
        convert_decoded_to_recordio,
        decoded_pipeline,
        batched_images,
        synthesize_jpeg_corpus,
        IMG_MEAN,
        IMG_STD,
    )

    batch = 128
    dtype = "bfloat16"
    n_corpus = 512
    iters = 24

    d = tempfile.mkdtemp(prefix="bench_imgs_")
    samples = synthesize_jpeg_corpus(d, n=n_corpus, size=256, classes=1000, seed=0)
    shards = convert_decoded_to_recordio(samples, os.path.join(d, "dec"), num_shards=4)

    with fluid.unique_name.guard():
        model = resnet.get_model(
            batch_size=batch, class_dim=1000, depth=50, image_shape=(3, 224, 224),
            lr=0.1, dtype=dtype,
        )
    state = init_state(model["startup"])
    raw_step = program_to_fn(model["main"], [model["loss"]], return_state=True)
    mean = jnp.asarray(IMG_MEAN)
    std = jnp.asarray(IMG_STD)

    def step(state, feeds):
        x = feeds["data"].astype(jnp.float32) / 255.0
        x = ((x - mean[None]) / std[None]).astype(jnp.bfloat16)
        return raw_step(state, {"data": x, "label": feeds["label"]})

    jitted = jax.jit(step, donate_argnums=(0,))

    # infinite-epoch pipeline through the shared async device-feed
    # machinery (reader.device_prefetch).  Several transfer threads keep
    # device_put ahead of the compute stream.  The prefetcher serializes
    # next() on the source (host-side decode/slice is not thread-safe)
    # while transfers run unlocked, and close() drains/joins the threads
    # deadline-capped so later (memory-hungry) legs never run with ~7
    # batches still pinned on device.
    from paddle_tpu.reader.device_prefetch import DevicePrefetcher

    reader = decoded_pipeline(shards, mode="train", image_size=224,
                              epochs=10_000, output="uint8")
    batches = batched_images(reader, batch)()

    def to_device(pair):
        imgs, labels = pair
        # int64 labels, same as the synthetic leg: a differing label
        # dtype would trace a second program and the two legs would
        # no longer measure the same compiled step
        return {"data": jax.device_put(imgs),
                "label": jax.device_put(labels.astype(np.int64))}

    feeds = DevicePrefetcher(batches, to_device, buffer_size=4,
                             transfer_threads=3)
    try:
        for _ in range(3):  # warmup/compile
            fetches, state = jitted(state, next(feeds))
        np.asarray(fetches[0])
        t0 = time.perf_counter()
        for _ in range(iters):
            fetches, state = jitted(state, next(feeds))
        np.asarray(fetches[0])
        dt = time.perf_counter() - t0
        ips = batch * iters / dt
    finally:
        # release the transfer threads and their pinned device batches on
        # the error path too
        feeds.close()

    return {
        "metric": "resnet50_real_input_images_per_sec_per_chip",
        "value": round(ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(ips / BASELINE_IMAGES_PER_SEC, 3),
        "input_fraction_of_synthetic": round(ips / synthetic_ips, 3) if synthetic_ips else None,
    }


def bench_resnet_inference():
    """ResNet-50 forward-only throughput, bf16 vs int8 execution
    (contrib.quantize.Int8InferenceTranspiler): the MXU's int8 path runs
    2x the bf16 MAC rate on v5e, so int8 inference is the perf ceiling
    check for the quantized stack."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.contrib.quantize import Int8InferenceTranspiler
    from paddle_tpu.jax_bridge import init_state, program_to_fn
    from paddle_tpu.models.resnet import resnet_imagenet

    batch = 256
    dtype = "float32"  # weights f32; activations cast per mode below

    with fluid.unique_name.guard():
        main = fluid.Program()
        startup = fluid.Program()
        with fluid.program_guard(main, startup):
            image = fluid.layers.data(name="data", shape=[3, 224, 224], dtype=dtype)
            predict = resnet_imagenet(image, class_dim=1000, depth=50, is_train=False)
        infer = main.clone(for_test=True)
    state = init_state(startup)

    rng = np.random.RandomState(0)
    x = rng.randn(batch, 3, 224, 224).astype(np.float32)
    iters = 30

    def run(prog, st, tag):
        import jax.numpy as jnp

        fn = program_to_fn(prog, [predict], is_test=True)
        # BOTH legs run bf16 activations and bf16 non-quantized params —
        # otherwise the int8 leg pays f32 bandwidth on every
        # BN/relu/pool/residual op and the speedup conflates dtype traffic
        # with the MXU int8 path it is meant to certify (int8 weights and
        # their f32 scales keep their dtypes)
        stc = {k: (jnp.asarray(v, jnp.bfloat16)
                   if hasattr(v, "dtype") and v.dtype == np.float32
                   and not k.endswith(".scale") else v)
               for k, v in st.items()}
        xx = jnp.asarray(x, jnp.bfloat16)
        jitted = jax.jit(fn)
        out = jitted(stc, {"data": xx})
        np.asarray(out[0][0, 0])
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jitted(stc, {"data": xx})
        np.asarray(out[0][0, 0])
        return batch * iters / (time.perf_counter() - t0)

    ips_bf16 = run(infer, dict(state), "bf16")

    class _Scope(dict):
        pass

    s = _Scope(state)
    Int8InferenceTranspiler().transpile(infer, s)
    state_q = dict(state)
    state_q.update({k: np.asarray(v) for k, v in s.items()
                    if k.endswith((".int8", ".scale"))})
    ips_int8 = run(infer, state_q, "int8")

    return {
        "metric": "resnet50_int8_infer_images_per_sec_per_chip",
        "value": round(ips_int8, 2),
        "unit": "images/sec",
        "vs_baseline": None,
        "bf16_infer_images_per_sec": round(ips_bf16, 2),
        "int8_speedup_vs_bf16": round(ips_int8 / ips_bf16, 3) if ips_bf16 else None,
    }


def _transformer_train_flops_per_step(batch, seq, n_layer, d, d_inner, vocab):
    """Analytic matmul FLOPs for one training step (2·m·n·k per matmul,
    backward ≈ 2× forward)."""
    qkvo = 8 * d * d            # 4 projections per attention
    attn = 4 * seq * d          # scores + context per token
    ffn = 4 * d * d_inner
    enc = n_layer * (qkvo + attn + ffn)
    dec = n_layer * (2 * (qkvo + attn) + ffn)   # self + cross attention
    logits = 2 * d * vocab
    fwd = batch * seq * (enc + dec + logits)
    return 3 * fwd


def bench_transformer(batch=64, seq=256, metric="transformer_tokens_per_sec_per_chip", iters=30, baseline=BASELINE_TOKENS_PER_SEC):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.jax_bridge import init_state, program_to_fn
    from paddle_tpu.models import transformer as T

    # Transformer-base, WMT-scale vocab, bf16, flash attention path.
    n_layer, n_head, d_model, d_inner = 6, 8, 512, 2048
    vocab = 30000

    with fluid.unique_name.guard():
        model = T.get_model(
            batch_size=batch, seq_len=seq, src_vocab_size=vocab, trg_vocab_size=vocab,
            max_length=seq, n_layer=n_layer, n_head=n_head, d_model=d_model,
            d_inner=d_inner, dropout=0.1, use_flash=True,
        )
    state = init_state(model["startup"])
    state = {
        k: (jnp.asarray(v, jnp.bfloat16) if hasattr(v, "dtype") and v.dtype == np.float32 else v)
        for k, v in state.items()
    }
    step = program_to_fn(model["main"], [model["loss"]], return_state=True)
    jitted = jax.jit(step, donate_argnums=(0,))

    rng = np.random.RandomState(0)
    feeds = {
        name: jax.device_put(rng.randint(1, vocab, size=(batch, seq)).astype(np.int64))
        for name in ("src_word", "trg_word", "lbl_word")
    }

    dt, _ = _time_steps(jitted, state, feeds, iters)
    tps = batch * seq * iters / dt  # target tokens/sec

    out = {
        "metric": metric,
        "value": round(tps, 2),
        "unit": "tokens/sec",
    }
    if baseline is not None:  # no published reference number for some shapes
        out["vs_baseline"] = round(tps / baseline, 3)
    flops = _transformer_train_flops_per_step(batch, seq, n_layer, d_model, d_inner, vocab)
    out["mfu"] = round((flops / (batch * seq)) * tps / V5E_PEAK_BF16_FLOPS, 4)
    return out


def main():
    device = _device()
    failed = []

    def leg(metric, unit, fn, *args, **kwargs):
        try:
            out = fn(*args, **kwargs)
        except Exception as e:  # noqa: BLE001 — recorded, and the exit code says so
            traceback.print_exc(file=sys.stderr)
            failed.append(metric)
            out = {"metric": metric, "value": 0.0, "unit": unit,
                   "error": "%s: %s" % (type(e).__name__, e)}
        out["device"] = device
        return out

    result = leg("resnet50_images_per_sec_per_chip", "images/sec", bench_resnet)
    extras = [
        leg("resnet50_real_input_images_per_sec_per_chip", "images/sec",
            bench_resnet_real_input, result["value"]),
        leg("resnet50_int8_infer_images_per_sec_per_chip", "images/sec",
            bench_resnet_inference),
    ]
    for kwargs in (
        # Transformer-base headline config (batch 64, seq 256)
        {"metric": "transformer_tokens_per_sec_per_chip"},
        # long-context configs: no reference baseline exists for these
        # shapes (vs_baseline omitted).  The flash backward engine is chosen
        # per shape by parallel/flash_attention.py's "auto" rule.
        {"batch": 16, "seq": 1024, "baseline": None,
         "metric": "transformer_seq1024_tokens_per_sec_per_chip", "iters": 15},
        {"batch": 8, "seq": 2048, "baseline": None,
         "metric": "transformer_seq2048_tokens_per_sec_per_chip", "iters": 12},
        {"batch": 4, "seq": 4096, "baseline": None,
         "metric": "transformer_seq4096_tokens_per_sec_per_chip", "iters": 10},
    ):
        extras.append(leg(kwargs["metric"], "tokens/sec", bench_transformer,
                          **kwargs))
    result["extra_metrics"] = extras

    print(json.dumps(result))
    if failed:
        sys.exit("failed legs: %s" % ", ".join(failed))


if __name__ == "__main__":
    main()
