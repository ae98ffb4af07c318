"""Capture and summarize an xprof op profile of a training step on the
real chip (the round-3 PERF.md methodology, automated).

Usage (healthy TPU, never concurrently with pytest):

    python tools/profile_step.py --model resnet50 --steps 10
    python tools/profile_step.py --model transformer --steps 10

Prints: top HLO-category table (time share, HBM bytes), copy-op count,
and the per-Program-op attribution from profiler.compiled_op_report —
everything PERF.md's breakdown needs, in one run.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _build(model_name, batch, on_tpu):
    import paddle_tpu as fluid
    from paddle_tpu.jax_bridge import init_state, program_to_fn
    from paddle_tpu.models import resnet, transformer as T

    if model_name == "resnet50":
        with fluid.unique_name.guard():
            model = resnet.get_model(batch_size=batch, class_dim=1000, depth=50,
                                     image_shape=(3, 224, 224), lr=0.1,
                                     dtype="bfloat16" if on_tpu else "float32")
        rng = np.random.RandomState(0)
        feeds = {"data": rng.randn(batch, 3, 224, 224).astype(np.float32),
                 "label": rng.randint(0, 1000, (batch, 1)).astype(np.int64)}
    else:
        b, s = (64, 256) if on_tpu else (2, 16)
        dims = (6, 8, 512, 2048, 30000) if on_tpu else (2, 2, 32, 64, 64)
        n_layer, n_head, d_model, d_inner, vocab = dims
        with fluid.unique_name.guard():
            model = T.get_model(batch_size=b, seq_len=s, src_vocab_size=vocab,
                                trg_vocab_size=vocab, max_length=s,
                                n_layer=n_layer, n_head=n_head, d_model=d_model,
                                d_inner=d_inner, dropout=0.1, use_flash=on_tpu)
        rng = np.random.RandomState(0)
        ids = rng.randint(1, vocab, (b, s)).astype(np.int64)
        feeds = {"src_word": ids, "trg_word": ids, "lbl_word": ids}
    state = init_state(model["startup"])
    step = program_to_fn(model["main"], [model["loss"]], return_state=True)
    return model, state, step, feeds


def _summarize_trace(trace_dir):
    """Parse the op-profile tool data out of the captured trace."""
    from xprof.convert import raw_to_tool_data as rtd

    runs = sorted(glob.glob(os.path.join(trace_dir, "plugins/profile/*")))
    if not runs:
        print("no trace runs captured under", trace_dir)
        return
    run = runs[-1]
    xspaces = glob.glob(os.path.join(run, "*.xplane.pb"))
    try:
        data, _ = rtd.xspace_to_tool_data(xspaces, "op_profile", {})
    except Exception as e:  # noqa: BLE001
        print("op_profile conversion failed:", e)
        return
    prof = json.loads(data) if isinstance(data, (str, bytes)) else data

    def walk(node, depth=0, out=None):
        out = out if out is not None else []
        m = node.get("metrics", {})
        out.append((node.get("name", "?"), m.get("time", 0.0),
                    m.get("bandwidthUtils", []), depth))
        for c in node.get("children", []):
            if depth < 2:
                walk(c, depth + 1, out)
        return out

    root = prof.get("byCategory", prof)
    rows = walk(root)
    print("\n== op profile (category tree, time fraction) ==")
    for name, t, bw, depth in rows[:40]:
        print("%s%-44s %6.2f%%  bw=%s" % ("  " * depth, name[:44], 100 * t, bw))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50", choices=["resnet50", "transformer"])
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--trace_dir", default=None)
    args = ap.parse_args()

    import jax

    on_tpu = jax.default_backend() == "tpu"
    batch = args.batch or (128 if on_tpu else 4)
    model, state, step, feeds = _build(args.model, batch, on_tpu)
    feeds = {k: jax.device_put(v) for k, v in feeds.items()}
    jitted = jax.jit(step, donate_argnums=(0,))

    for _ in range(3):
        f, state = jitted(state, feeds)
    np.asarray(f[0])  # sync via readback

    trace_dir = args.trace_dir or tempfile.mkdtemp(prefix="xprof_")
    jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        f, state = jitted(state, feeds)
    np.asarray(f[0])
    dt = time.perf_counter() - t0
    jax.profiler.stop_trace()
    print("steady state: %.2f ms/step (%d steps)" % (dt / args.steps * 1e3, args.steps))
    print("trace dir:", trace_dir)

    _summarize_trace(trace_dir)

    # per-Program-op attribution of the compiled step (instruction counts)
    import paddle_tpu as fluid

    report, _rows = fluid.profiler.compiled_op_report(
        model["main"], {k: np.asarray(v) for k, v in feeds.items()},
        state={k: np.asarray(v) for k, v in state.items()},
        fetch_list=[model["loss"]])
    print("\n== compiled per-op attribution (HLO instructions) ==")
    print("\n".join(report.splitlines()[:30]))


if __name__ == "__main__":
    main()
