"""Decoder-only Transformer LM behind ``serving.InferenceEngine`` ->
``DecodeScheduler``.  Builders and checks copied from ``chip_smoke.py``
(PR 21), which ran on the chip; the weights are made here, on the device."""
from __future__ import annotations

import numpy as np

# paged kernels against the plain reference, each held to its own arithmetic:
# the prefill kernel's per-head matmuls round their operands like the MXU
# (bf16; 2.0e-3 to 3.2e-3 measured), the decode kernel is f32-exact on the VPU
# (2.7e-7 to 4.9e-7 measured), so one computed in bf16 would not pass
PAGED_RTOL = {"decode": 1e-4, "prefill": 2e-2}
# a generated token may differ from the f32 reference's choice only where the
# reference puts it within TIE_TOL standard deviations (of the logits) of its
# own top logit: a near tie that bf16 operands and bf16 KV may break either way
TIE_TOL = 0.05


def make_params(cfg, seed):
    """The pytree ``T.lm_params`` makes (same shapes, scales and sinusoid
    table), as float32 device arrays from ``seed`` in ONE jitted call."""
    import jax
    import jax.numpy as jnp

    d, di, v = cfg["d_model"], cfg["d_inner"], cfg["vocab"]
    n_layer, max_len = cfg["n_layer"], cfg["max_seq_len"]

    def make(key):
        keys = iter(jax.random.split(key, 2 + 6 * n_layer))

        def w(rows, cols, scale=None):
            s = scale if scale is not None else 1.0 / np.sqrt(rows)
            return jax.random.normal(next(keys), (rows, cols), jnp.float32) * s

        pos = jnp.arange(max_len, dtype=jnp.float32)[:, None]
        inv = 1.0 / jnp.power(10000.0, (jnp.arange(d) // 2 * 2.0) / d)
        ang = pos * inv[None, :]
        table = jnp.where(jnp.arange(d)[None, :] % 2 == 0, jnp.sin(ang),
                          jnp.cos(ang))
        return {
            "tok_emb": w(v, d, 0.02), "pos_table": table, "out_w": w(d, v),
            "layers": [{
                "wq": w(d, d), "wk": w(d, d), "wv": w(d, d), "wo": w(d, d),
                "ln1_s": jnp.ones(d), "ln1_b": jnp.zeros(d),
                "ffn_w1": w(d, di), "ffn_b1": jnp.zeros(di),
                "ffn_w2": w(di, d), "ffn_b2": jnp.zeros(d),
                "ln2_s": jnp.ones(d), "ln2_b": jnp.zeros(d),
            } for _ in range(n_layer)],
        }

    params = jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))
    meta = dict(vocab_size=v, n_layer=n_layer, n_head=cfg["n_head"],
                d_model=d, d_inner=di, max_length=max_len,
                head_dim=d // cfg["n_head"])
    return params, meta


def build_engine(cfg, params, meta, max_new_tokens):
    """The front door, warmed up (the constructor compiles the decode step
    and every prefill chunk width)."""
    from paddle_tpu import serving
    from paddle_tpu.models import transformer as T

    return serving.InferenceEngine(
        decode_model=T.build_decode_model(params, meta),
        decode_config=serving.DecodeConfig(
            num_slots=cfg["slots"], page_size=cfg["page"],
            max_seq_len=cfg["max_seq_len"], num_pages=cfg["num_pages"],
            prefill_buckets=tuple(cfg["buckets"]),
            prefill_chunk_tokens=cfg["chunk"],
            prefix_cache=cfg["prefix_cache"], max_new_tokens=max_new_tokens,
            queue_capacity=cfg["queue_capacity"], kv_dtype=cfg["kv_dtype"]))


def paged_kernel_errors(cfg, seed, reference):
    """Both paged attention kernels (the engine the program picks here)
    against the plain reference at the configuration's own pool shape."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.parallel import flash_attention as FA

    H, Dh = cfg["n_head"], cfg["d_model"] // cfg["n_head"]
    S, ps, C = cfg["slots"], cfg["page"], cfg["chunk"]
    mp = cfg["max_seq_len"] // ps
    P = S * mp + 1
    ks = jax.random.split(jax.random.PRNGKey((seed + 3) % (2 ** 31)), 5)
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
        "decode": (FA.paged_decode_attention, reference.paged_decode,
                   (q, k_pool, v_pool, tables, lens)),
        "prefill": (FA.paged_prefill_attention, reference.paged_prefill,
                    (qc, k_pool, v_pool, tables[1], start)),
    }
    errs = {}
    for name, (fn, ref, args) in calls.items():
        a = np.asarray(jax.jit(fn)(*args), np.float32)
        b = np.asarray(jax.jit(ref)(*args), np.float32).reshape(a.shape)
        errs[name] = (float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
                      if np.all(np.isfinite(a)) else float("inf"))
        if name == "decode" and a[0].any():
            errs["decode_empty_slot_not_zero"] = float("inf")
    return errs


def token_gaps(cfg, params, samples, reference):
    """For each ``(prompt, generated)`` and for the first, the middle and the
    last generated token: how far the served token sits below the top of the
    reference's next-token logits given the prompt and the served tokens
    before it (teacher forcing), in standard deviations of the logits (0
    where the two agree).  The first token comes out of chunked prefill, the
    others out of the decode loop: its KV writes, page tables and lengths."""
    import jax

    fn = jax.jit(reference.next_token_logits, static_argnums=3)
    gaps = []
    for prompt, generated in samples:
        seq = np.zeros(cfg["max_seq_len"], np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + len(generated)] = generated
        for k in sorted({0, len(generated) // 2, len(generated) - 1}):
            logits = np.asarray(fn(params, seq, len(prompt) + k, cfg["n_head"]),
                                np.float64)
            gaps.append(float((logits.max() - logits[int(generated[k])])
                              / logits.std()))
    return gaps
