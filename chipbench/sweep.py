"""Find the knee of a serving cell: the highest offered rate it sustains.

    python3 chipbench/sweep.py --workload tfbase_lm_chat --seconds 20 --rates 8 12 16 20 24

One process and ONE engine warm-up; each rate is offered for ``--seconds``
through the serve driver's own window (same generator, same reduction), once
for each of ``--seeds`` (where the mix has no ``order_seed`` each seed orders
the schedule its own way, so several seeds at one rate show how far the
numbers swing with the order), the stragglers are drained, and one JSON line
per window is printed.  A rate is sustained while the backlog at the window's
end stays near zero and the drain stays about one request long; past the knee
both grow with the window.  The cell's traffic file then fixes ``rate_rps`` at
about four fifths of the knee by hand.  ``--order-seeds`` reads candidate
ORDERS of one schedule the same way (each window under the mix with that
``order_seed``, the run seeds drawing tokens alone; the engine's weights stay
the first seed's), for a mix that fixes its order.  Each line also says what
share of the loop's iterations carried a chunk, window and drain together.
Like ``run.py`` it refuses anything but a TPU.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import cells  # noqa: E402
from chipbench.registry import Registry  # noqa: E402

REPORTED = ("rate_rps", "attempted", "completed", "failed",
            "serve_tokens_per_s", "ttft_mean_ms", "ttft_p50_ms", "ttft_p90_ms",
            "ttft_p95_ms", "itl_p50_ms", "itl_p95_ms", "generator_lag_p95_ms",
            "backlog_at_end", "active_at_end", "kv_pages_used_at_end",
            "drain_s")
LOOP_CELLS = ("serving.decode.prefill", "serving.decode.iteration")


def _loop_counts():
    return [cells.snapshot(c).count for c in LOOP_CELLS]


def sweep(registry, cell_name, rates, seconds, seeds, log=print,
          order_seeds=(None,)):
    cell = registry.cell(cell_name)
    cfg = registry.config(cell["config"])
    mix = registry.traffic(cell["traffic"])
    model = registry.module("models", cfg["model"])
    serve = registry.module("drivers", cfg["driver"])
    params, meta = model.make_params(cfg, seeds[0])
    engine = model.build_engine(cfg, params, meta, mix["output_len"]["max"])
    rows = []
    try:
        for rate, order, seed in itertools.product(rates, order_seeds, seeds):
            m = mix if order is None else dict(mix, order_seed=order)
            chunks0, turns0 = _loop_counts()
            w = serve.window(engine, m, cfg["vocab"], seconds, seed,
                             rate=rate)
            chunks, turns = _loop_counts()
            rows.append(dict(
                {k: w[k] for k in REPORTED}, seed=seed,
                order_seed=m.get("order_seed"),
                chunk_iteration_share_pct=(
                    100.0 * (chunks - chunks0) / max(1, turns - turns0))))
            log(json.dumps(rows[-1]))
    finally:
        engine.stop()
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", type=int, nargs="+", default=[0])
    ap.add_argument("--order-seeds", type=int, nargs="+", default=[None])
    args = ap.parse_args(argv)

    import jax

    import paddle_tpu as fluid

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("need a TPU, found %s" % [str(d) for d in devices], flush=True)
        return 1
    fluid.enable_compilation_cache()
    sweep(Registry(ROOT), args.workload, args.rates, args.seconds, args.seeds,
          log=lambda s: print(s, flush=True), order_seeds=args.order_seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
