"""Plain reference for ``evabyte_6_5b``: the forward pass of EvaByte
(``model_type`` ``evabyte``, ``attention_class`` ``eva``) over one whole
sequence in straightforward float32 ``jax.numpy`` at the highest matmul
precision.  No kernel, no cache, no paging, no batching, and no chunking of
the SEQUENCE other than what the equations state: every chunk's summary is
built from the full K and V, and a query's keys are masked by ``b(t)``.  The
parameters are an ARGUMENT (the served pytree, upcast here).  Query rows are
processed in blocks of ``block`` (a divisor of the window, so a block lies in
one window), the feed-forward block in the same blocks, so that 32768
positions of 8 layers fit beside the served weights.

Source: https://huggingface.co/EvaByte/EvaByte/blob/main/config.json and, for
the FORM of the summaries, which the config has no key for, the ``evabyte``
modelling code (``eva.py``, ``eva_agg_kernel.py``, ``eva_prep_kv_kernel.py``,
``eva_pt_ref.py``) and section 4 of Zheng, Yuan, Wang, Kong, "Efficient
Attention via Control Variates", arXiv:2302.04542.

    hidden 4096, 32 layers, 32 heads of 128 (MHA), SwiGLU 11008, vocabulary
    320 (bytes and specials), 8 prediction heads, untied, no bias;
    window_size W = 2048, chunk_size C = 16, rope_theta 100000, no scaling;
    RMSNorm eps 1e-5 with a unit offset; residual stream float32.

    h_0 = E[byte]                                    (no scale)
    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + w)
    a = RMSNorm_1(h);  q_t, k_t, v_t = a W_q, a W_k, a W_v   [32, 128] each
    rotate-half rotary on the whole head of q and k, angle t * theta^(-2i/128)
    chunk c = positions cC .. cC + C - 1, a head i with phi_i, mu_i in R^128:
        alpha_{c,j} = softmax_j(k_j . phi_i)         over the chunk's C keys
        k~_c = sum_j alpha_{c,j} k_j + mu_i;   v~_c = sum_j alpha_{c,j} v_j
    query t, b(t) = floor(t / W) W:  keys k_j for j in b(t) .. t and k~_c for
        c < b(t) / C, logits q_t . key / sqrt(128), ONE softmax over both,
        o_t = sum_j p_j v_j + sum_c p_c v~_c;   h <- h + o W_o
    u = RMSNorm_2(h);  h <- h + (silu(u W_gate) * u W_up) W_down
    logits = RMSNorm_f(h_L) W_head  [8 * 320] float32: head j scores byte
        t + 1 + j

The served pytree's layout (``paddle_tpu/models/evabyte.py:params``): ``w_qkv``
= [W_q | W_k | W_v] and ``w_gu`` = [gate | up] column-wise; ``phi`` / ``mu``
``[L, 32, 128]``; the norms' ``w`` in the offset form (around zero).

What the config does not fix, written here as the source's code has it (each
is listed under ``assumed`` in the configuration's file): softmax pooling of a
chunk's keys against a learned ``phi`` a head; the pooled key shifted by a
learned ``mu``; pooling weights shared by K and V; no ``|k|^2`` term; the
summaries of the query's own window invisible; the window aligned, not
sliding.  ``init_fn`` / ``init_std`` / ``init_cutoff_factor`` / ``lazy_init``
are initialisation: the seeded weights do not follow them.
"""
import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def rotary(x, positions, theta):
    """Rotate-half rotary of ``x [T, H, Dh]`` at ``positions [T]``; the
    inverse frequencies in float64 on the host, rounded once (a float32 power
    on the chip is off by a part in 10^6, which 30 000 positions make 0.02
    radians)."""
    half = x.shape[-1] // 2
    inv = jnp.asarray((float(theta) ** (
        -np.arange(half, dtype=np.float64) / half)).astype(np.float32))
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def summaries(k, v, phi, mu, chunk):
    """``(k~, v~) [T / C, H, Dh]`` of ``k`` / ``v`` ``[T, H, Dh]`` (``T`` whole
    chunks)."""
    T, H, Dh = k.shape
    kc, vc = (a.reshape(T // chunk, chunk, H, Dh) for a in (k, v))
    alpha = jax.nn.softmax(jnp.einsum("nchd,hd->nch", kc, phi), axis=1)
    return (jnp.einsum("nch,nchd->nhd", alpha, kc) + mu,
            jnp.einsum("nch,nchd->nhd", alpha, vc))


def eva_attention(q, k, v, ks, vs, positions, window, chunk):
    """``o [R, H, Dh]`` of queries ``q [R, H, Dh]`` at ``positions [R]``
    against the whole sequence's ``k`` / ``v`` ``[T, H, Dh]`` and summaries
    ``ks`` / ``vs`` ``[T / C, H, Dh]``, masked as the equations say."""
    base = (positions // window) * window
    key_at = jnp.arange(k.shape[0])
    see = (key_at[None] >= base[:, None]) & (key_at[None] <= positions[:, None])
    see_sum = jnp.arange(ks.shape[0])[None] < (base // chunk)[:, None]
    scale = q.shape[-1] ** -0.5
    s = jnp.concatenate([jnp.einsum("rhd,khd->rhk", q, k),
                         jnp.einsum("rhd,khd->rhk", q, ks)], axis=-1) * scale
    ok = jnp.concatenate([see, see_sum], axis=-1)[:, None, :]
    p = jax.nn.softmax(jnp.where(ok, s, NEG), axis=-1)
    return (jnp.einsum("rhk,khd->rhd", p[..., :k.shape[0]], v)
            + jnp.einsum("rhk,khd->rhd", p[..., k.shape[0]:], vs))


def layer_rows(params, cfg, layer, x, positions):
    """``(q, k, v) [T, H, Dh]`` of layer ``layer`` from its input ``x``."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    lp = params["layers"][layer]
    a = rms(x, params["ln1"][layer], cfg["rms_norm_eps"])
    y = (a @ lp["w_qkv"].astype(jnp.float32)).reshape(-1, 3, H, D // H)
    theta = float(cfg["rope_theta"])
    return (rotary(y[:, 0], positions, theta),
            rotary(y[:, 1], positions, theta), y[:, 2])


def forward(params, cfg, tokens, positions, block=256):
    """``tokens [T]`` (``T`` whole blocks) -> ``(logits [P, Hn, V] at
    ``positions [P]``, per layer the K and V rows there ``[P, 2, H * Dh]``,
    per layer every chunk's summary ``[T / C, 2, H * Dh]``)."""
    W, C = int(cfg["window_size"]), int(cfg["chunk_size"])
    F, eps = cfg["intermediate_size"], cfg["rms_norm_eps"]
    T = tokens.shape[0]
    if block % C or (W % block and block % W) or T % block:
        raise ValueError("block %d: whole chunks, inside one window, and a "
                         "divisor of the %d tokens" % (block, T))
    block = min(block, W)
    at = jnp.arange(T, dtype=jnp.int32)
    rows, sums = [], []
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(jnp.float32)[tokens]
        for layer, lp in enumerate(params["layers"]):
            q, k, v = layer_rows(params, cfg, layer, x, at)
            ks, vs = summaries(k, v, params["phi"][layer], params["mu"][layer],
                               C)
            rows.append(jnp.stack([k[positions], v[positions]], axis=1)
                        .reshape(positions.shape[0], 2, -1))
            sums.append(jnp.stack([ks, vs], axis=1).reshape(T // C, 2, -1))
            wo = lp["wo"].astype(jnp.float32)
            w_gu = lp["w_gu"].astype(jnp.float32)
            w_down = lp["w_down"].astype(jnp.float32)

            def rest(args):
                xb, qb, pb = args
                o = eva_attention(qb, k, v, ks, vs, pb, W, C)
                h = xb + o.reshape(block, -1) @ wo
                gu = rms(h, params["ln2"][layer], eps) @ w_gu
                return h + (jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ w_down

            x = jax.lax.map(rest, (
                x.reshape(T // block, block, -1),
                q.reshape((T // block, block) + q.shape[1:]),
                at.reshape(T // block, block))).reshape(T, -1)
        logits = rms(x[positions], params["norm_f"], eps) @ params[
            "head"].astype(jnp.float32)
    return (logits.reshape(positions.shape[0], cfg["num_pred_heads"], -1),
            rows, sums)
