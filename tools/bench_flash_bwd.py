"""Flash backward engine shootout on the real chip (VERDICT r4 item 6).

Times fwd+bwd for scan vs the fused one-grid Pallas backward (and the
two-kernel pair) at long sequence lengths, tokens held constant.  Run on
a healthy TPU:  python tools/bench_flash_bwd.py
Prints a markdown table for PERF.md.  The forward in every figure is the
one the program runs (tiles chosen from the shape, PR 29: under 1 ms at
T=2048 causal where the rounds 3-5 tables carried a ~9 ms forward); the
backward's blocks stay 128 (64 for fused64).
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import flash_attention as FA

    on_tpu = jax.default_backend() == "tpu"
    print("devices:", jax.devices(), "on_tpu:", on_tpu)

    H, D = 8, 64
    tokens = 16384 if on_tpu else 512
    rows = []
    for T in ((2048, 4096, 8192) if on_tpu else (128, 256)):
        B = max(1, tokens // T)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        dt = jnp.bfloat16 if on_tpu else jnp.float32
        q = jax.random.normal(ks[0], (B, H, T, D), dt)
        k = jax.random.normal(ks[1], (B, H, T, D), dt)
        v = jax.random.normal(ks[2], (B, H, T, D), dt)

        times = {}
        # fused64: the fused one-grid backward at BACKWARD-ONLY block_k=64
        # (FLASH_BWD_BLOCK_K; the forward keeps bk=128) — the [T, bk] f32
        # intermediates halve, fitting scoped VMEM up to T=4096 where
        # bk=128 OOMs (PERF.md round-5 calibration); half-width lanes may
        # cost MXU efficiency, hence measured rather than assumed
        for impl in ("scan", "fused", "pallas", "fused64"):
            FA.FLASH_BWD_IMPL = "fused" if impl == "fused64" else impl
            FA.FLASH_BWD_BLOCK_K = 64 if impl == "fused64" else None

            def loss(q, k, v):
                o = FA.flash_attention(q, k, v, None, True, None, None, None,
                                       None if on_tpu else True)
                return (o.astype(jnp.float32) ** 2).sum()

            g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            try:
                out = g(q, k, v)  # compile + warmup
                np.asarray(out[0][0, 0, 0])
                iters = 10 if on_tpu else 2
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = g(q, k, v)
                np.asarray(out[0][0, 0, 0])  # sync via readback
                times[impl] = (time.perf_counter() - t0) / iters * 1e3
            except Exception as e:  # noqa: BLE001
                times[impl] = float("nan")
                print("  %s T=%d failed: %s" % (impl, T, e), file=sys.stderr)
        rows.append((T, B, times))
        print("T=%d B=%d: %s" % (T, B, {k_: round(v_, 2) for k_, v_ in times.items()}))

    print("\n| T | B | scan ms | fused ms | fused-bk64 ms | pair ms | winner |")
    print("|---|---|---|---|---|---|---|")
    for T, B, t in rows:
        finite = [(v, k_) for k_, v in t.items() if v == v]
        best = min(finite)[1] if finite else "all failed"
        print("| %d | %d | %.2f | %.2f | %.2f | %.2f | %s |"
              % (T, B, t.get("scan", float("nan")), t.get("fused", float("nan")),
                 t.get("fused64", float("nan")), t.get("pallas", float("nan")),
                 best))


if __name__ == "__main__":
    main()
