"""Plain reference for ``ouro_2_6b``: the forward pass of Ouro (``model_type``
``ouro``, a looped language model) over one whole sequence in straightforward
float32 ``jax.numpy`` at the highest matmul precision.  A Python loop over the
loop steps ``u`` and the layers ``l``, full ``[T, T]`` causal attention, no
kernel, no cache, no paging, no batching; query rows are processed in blocks
of ``block`` (the scores of a block are ``[H, block, T]``), so that the
published widths fit beside the served weights.  The parameters are an
ARGUMENT (the served pytree, upcast here).

Source: https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json and,
for what the config has no key for, the released ``modeling_ouro.py`` the
config belongs to and Zhu et al., "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741.

    hidden 2048, 48 layers, 16 heads of 128 (multi-head: 16 K/V heads), SwiGLU
    5632, vocabulary 49152 untied, rope_theta 1e6 without scaling, RMS eps
    1e-6, total_ut_steps U = 4, early_exit_threshold 1, no bias.

    h^0 = E[id]                                       (no scale)
    RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w
    for u = 0 .. U - 1:   x <- h^u
        for l = 0 .. L - 1:   (K/V layer u L + l)
            a = RMSNorm_1(x);  q, k, v = a W_q, a W_k, a W_v   [16, 128] each
            rotate-half rotary on the whole head of q and k at the token's
                position t (the same in every loop step), angle t theta^(-2i/128)
            o_t = softmax_{j <= t}(q_t . k_j / sqrt(128)) v_j   over the k, v
                of THIS layer IN THIS loop step
            x <- x + RMSNorm_2(o W_o)
            b = RMSNorm_3(x);  x <- x + RMSNorm_4((silu(b W_gate) * b W_up) W_down)
        h^{u+1} = RMSNorm_f(x)        the model's one final norm, every step
        g_u = w_g . h^{u+1} + b_g     the exit gate
    lambda_u = sigmoid(g_u);  p_u = lambda_u prod_{j<u} (1 - lambda_j) for
        u < U - 1, the last step takes the rest; served step = the first u
        with sum_{j<=u} p_j >= early_exit_threshold, else U - 1
    logits = h^U W_head               (h^U is normed already)

The served pytree's layout (``paddle_tpu/models/ouro.py:params``): ``w_qkv``
= [W_q | W_k | W_v] and ``w_gu`` = [gate | up] column-wise; the four norms of a
layer ``ln_in`` / ``ln_post_attn`` / ``ln_pre_mlp`` / ``ln_post_mlp`` ``[L,
D]``; ``gate_w [D]``, ``gate_b [1]``.

What the config does not fix, written here as the released modelling code has
it (each is listed under ``assumed`` in the configuration's file): no
attention or MLP bias; the four norms' placement (sandwich); the final norm
inside the loop, its output feeding the next step; the gate's form and its
bias; rotate-half rotary on all 128 lanes; K/V layers numbered ``u L + l``.
"""
import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e30


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rotary(x, positions, theta):
    """Rotate-half rotary of ``x [T, H, Dh]`` at ``positions [T]`` (inverse
    frequencies made in float64 on the host, rounded once)."""
    half = x.shape[-1] // 2
    inv = (float(theta) ** (-np.arange(half, dtype=np.float64) / half)
           ).astype(np.float32)
    ang = positions.astype(jnp.float32)[:, None, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def exit_step(gates, threshold):
    """The 0-based loop step each position is served from, ``gates [U, ..]``:
    a plain Python rendering of the exit rule (numpy, float64)."""
    g = np.asarray(gates, np.float64)
    U = g.shape[0]
    lam = 1.0 / (1.0 + np.exp(-g))
    out = np.full(g.shape[1:], U - 1, np.int64)
    done = np.zeros(g.shape[1:], bool)
    stay = np.ones(g.shape[1:])
    total = np.zeros(g.shape[1:])
    for u in range(U - 1):
        total = total + lam[u] * stay
        stay = stay * (1.0 - lam[u])
        now = (total >= threshold) & ~done
        out[now] = u
        done |= now
    return out


def _blocks(T, block):
    block = T if block is None else min(block, T)
    return [(a, min(a + block, T)) for a in range(0, T, block)]


def forward(params, cfg, tokens, positions, *, block=None, rows=(),
            share_last_step=False):
    """``(logits [P, V], gates [U, P], kv)`` of ``tokens [T]`` at ``positions
    [P]``: next-token logits, every loop step's gate logit, and for each ``(u,
    l)`` of ``rows`` the K and V rows ``[T, H * Dh]`` that layer ``l`` keeps
    in loop step ``u`` (rotated keys), as a list of ``(k, v)``.

    ``share_last_step``: NOT the model: the paper's last-step K/V reuse, in
    which every loop step reads the K and V rows that step ``U - 1`` wrote
    (a quarter of the cache).  It is here so that the tests can show the
    comparison tells the two apart."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, cfg, tokens, positions, block, tuple(rows),
                        share_last_step)


def _forward(params, cfg, tokens, positions, block, rows, share_last_step):
    f32 = jnp.float32
    T = tokens.shape[0]
    H, Dh = cfg["num_attention_heads"], cfg["head_dim"]
    F, eps = cfg["intermediate_size"], cfg["rms_norm_eps"]
    L, U = cfg["num_hidden_layers"], cfg["total_ut_steps"]
    pos = jnp.arange(T, dtype=jnp.int32)
    scale = Dh ** -0.5

    def layer(x, l, kv_from=None):
        lp = {k: v.astype(f32) for k, v in params["layers"][l].items()}
        a = rms(x, params["ln_in"][l], eps)
        y = a @ lp["w_qkv"]
        q = rotary(y[:, :H * Dh].reshape(T, H, Dh), pos, cfg["rope_theta"])
        k = rotary(y[:, H * Dh:2 * H * Dh].reshape(T, H, Dh), pos,
                   cfg["rope_theta"])
        v = y[:, 2 * H * Dh:].reshape(T, H, Dh)
        kept = (k.reshape(T, H * Dh), v.reshape(T, H * Dh))
        if kv_from is not None:
            k, v = (r.reshape(T, H, Dh) for r in kv_from)
        outs = []
        for a0, a1 in _blocks(T, block):
            s = jnp.einsum("thd,shd->hts", q[a0:a1], k) * scale
            ok = pos[None, :] <= pos[a0:a1, None]
            p = jax.nn.softmax(jnp.where(ok[None], s, NEG), axis=-1)
            outs.append(jnp.einsum("hts,shd->thd", p, v).reshape(a1 - a0, -1))
        o = jnp.concatenate(outs, axis=0)
        x = x + rms(o @ lp["wo"], params["ln_post_attn"][l], eps)
        ys = []
        for a0, a1 in _blocks(T, block):
            b = rms(x[a0:a1], params["ln_pre_mlp"][l], eps)
            gu = b @ lp["w_gu"]
            ys.append((jax.nn.silu(gu[:, :F]) * gu[:, F:]) @ lp["w_down"])
        x = x + rms(jnp.concatenate(ys, axis=0), params["ln_post_mlp"][l], eps)
        return x, kept

    def steps(h, kv_from):
        gates, kv = [], {}
        for u in range(U):
            x = h
            for l in range(L):
                x, kept = layer(x, l, None if kv_from is None
                                else kv_from[l])
                kv[u, l] = kept
            h = rms(x, params["norm_f"], eps)
            gates.append((h * params["gate_w"]).sum(axis=-1)
                         + params["gate_b"][0])
        return h, jnp.stack(gates), kv

    h0 = params["embed"].astype(f32)[tokens]
    h, gates, kv = steps(h0, None)
    if share_last_step:
        # the fixed point is not sought: one pass that reads, in every step,
        # the rows the exact model's LAST step keeps
        h, gates, kv = steps(h0, [kv[U - 1, l] for l in range(L)])
    logits = h[positions] @ params["head"].astype(f32)
    return logits, gates[:, positions], [kv[u, l] for u, l in rows]


def first_layer_rows(params, cfg, tokens, positions):
    """K/V layer 0's K and V rows ``[T, H * Dh]`` of ``tokens`` at
    ``positions``: layer 0 in loop step 0 reads the embedding alone, so its
    rows depend on a token and its position and on nothing cached."""
    with jax.default_matmul_precision("highest"):
        H, Dh = cfg["num_attention_heads"], cfg["head_dim"]
        T = tokens.shape[0]
        a = rms(params["embed"].astype(jnp.float32)[tokens],
                params["ln_in"][0], cfg["rms_norm_eps"])
        y = a @ params["layers"][0]["w_qkv"].astype(jnp.float32)
        k = rotary(y[:, H * Dh:2 * H * Dh].reshape(T, H, Dh), positions,
                   cfg["rope_theta"])
        return k.reshape(T, H * Dh), y[:, 2 * H * Dh:]
