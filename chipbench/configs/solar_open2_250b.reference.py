"""Plain reference for ``solar_open2_250b``: ONE holder's share of the forward
pass of upstage's Solar-Open2-250B (``model_type`` ``solar_open2``) over one
whole sequence in straightforward float32 ``jax.numpy`` at the highest matmul
precision.  No kernel, no cache, no paging, no chunk-wise form, no sorting or
grouping of experts, no batching; the parameters are an ARGUMENT (the served
pytree, upcast here, one expert at a time).  Rows are processed in blocks so
that 20480 tokens fit beside the served weights; the delta rule itself runs
token by token (``lax.scan``).

Source: https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json
The delta-rule layers are Kimi Delta Attention (arXiv:2510.26692), which the
catalog's ``described_as`` confirms ("gated delta-rule linear (neg.
eigenvalues, conv4)").

    hidden 4096, 48 layers, vocabulary 196608 (untied), RMSNorm eps 1e-5
    x_0 = E[tok];  h = x + Mixer(norm1(x));  x' = h + Experts(norm2(h))
    logits = W_head RMSNorm(x_L)

Softmax layers (``gqa_layers``; 64 query heads, 8 KV heads of 128; query head
i reads KV head i // 8; ``use_rope`` false: NO rotation), u = norm1(x):

    q = u W_q, k = u W_k, v = u W_v;  a = softmax(q k^T / sqrt(128)) v, causal
    y = (sigmoid(u W_g) * a) W_o          (``use_gqa_gate``: elementwise)

Delta-rule layers (every other layer; ``linear_attn_config``: 64 heads of 128,
``short_conv_kernel_size`` 4), u = norm1(x), per head:

    conv(z)_t = sum_{j=0..3} w_j * z_{t-3+j}          (depthwise, causal, zeros
                                                       before the sequence)
    q = l2(silu(conv(u W_q))), k = l2(silu(conv(u W_k))), v = silu(conv(u W_v))
        l2(x) = x / sqrt(sum x^2 + 1e-6)
    g_t = -exp(A_log[head]) softplus(W_f2 (W_f1 u) + dt_bias)   in R^128 a head
    beta_t = 2 sigmoid(u W_b)                       (``kda_allow_neg_eigval``)
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(128)
    y = (rmsnorm_head(o_t) * sigmoid(W_g2 (W_g1 u))) W_o

Experts (every layer; ``first_k_dense_replace`` 0), u = norm2(h): s =
sigmoid(u W_r) over all 320 in float32; the 8 largest of s + bias are chosen
(on a tie the lower expert wins); w = s[chosen] / sum, x
``routed_scaling_factor`` 1; dropless.  THIS HOLDER (``experts_held`` = (lo,
hi)) adds its own experts' terms and the one shared expert:

    Experts(u) = shared(u) + sum_{e in top8, lo <= e < hi} w_e expert_e(u)

What the other seven holders would add is left out, and that partial sum goes
on to the next layer: the share, not the model.  Written as a loop over the
held experts with a mask.

The served pytree's layout (``paddle_tpu/models/solar_open2.py:params``):
softmax layer ``w_in`` = [W_q | W_k | W_v | W_g] column-wise; delta-rule layer
``w_qkv`` = [W_q | W_k | W_v], ``w_low`` = [W_f1 | W_g1 | W_b], ``conv_w [4,
24576]`` (tap j of channel c; channels in ``w_qkv``'s order); ``e_gu [L, 40,
4096, 2560]`` = [gate | up], ``e_down [L, 40, 1280, 4096]``; ``router_w [L,
4096, 320]``, ``router_b [L, 320]``.

What the config cannot settle (each also under ``assumed`` in the JSON):
rank 128 for W_f1 and W_g1 (``kda_use_full_proj`` false); sigmoid scores and a
selection bias; the softmax layer's gate elementwise and no QK-norm; ``A_log``
and ``dt_bias`` from the seed.  ``forced`` lets a caller GIVE the experts of
some rows: top-8 is a discrete choice, and a served row whose eighth and ninth
scores lie closer than bfloat16 rounding of the residual stream takes another
expert than this float32 pass.

CONTROLS.  ``variant`` makes a WRONG mechanism, one at a time, so that the
comparison that decides ``correct`` can be shown to catch it: ``beta1`` (no
factor 2), ``head_decay`` (one decay a head: the channels' mean), ``taps3``
(the oldest tap dropped), ``no_gate`` (either mixer's output gate left out),
``rotary`` (rotate-half rotary, theta ``rope_theta``, on the softmax layer's q
and k).  None is used by ``forward`` unless asked.
"""
import math

import jax
import jax.numpy as jnp

L2_EPS = 1e-6
VARIANTS = ("beta1", "head_decay", "taps3", "no_gate", "rotary")


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kinds(cfg):
    return ["gqa" if i in cfg["gqa_layers"] else "kda"
            for i in range(cfg["num_hidden_layers"])]


def rope(x, positions, theta):
    """Rotate-half rotary (the ``rotary`` control only)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def attention(q, k, v, positions):
    """Masked softmax attention: ``q [R, Hq, d]`` at absolute ``positions
    [R]`` against ``k``, ``v`` ``[T, Hkv, d]`` (key ``s`` at position ``s``;
    query head ``i`` reads KV head ``i // (Hq / Hkv)``), keys ``s <= t``."""
    with jax.default_matmul_precision("highest"):
        R, Hq, d = q.shape
        g = Hq // k.shape[1]
        s = jnp.einsum("rhgd,thd->rhgt", q.reshape(R, k.shape[1], g, d),
                       k) / math.sqrt(d)
        ok = positions[:, None] >= jnp.arange(k.shape[0])[None, :]
        p = jax.nn.softmax(jnp.where(ok[:, None, None, :], s, -1e30), axis=-1)
        return jnp.einsum("rhgt,thd->rhgd", p, v).reshape(R, Hq, d)


def gqa_rows(params, cfg, layer, x, positions, variant=None):
    """The K and V rows ``[T, Hkv * d]`` a softmax layer caches for its input
    rows ``x [T, D]``: a function of the row alone (no rotation)."""
    with jax.default_matmul_precision("highest"):
        H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        w = params["layers"][layer]["w_in"].astype(jnp.float32)
        u = rms_norm(x, params["ln1"][layer], cfg["rms_norm_eps"])
        k = u @ w[:, H * d:(H + Hkv) * d]
        if variant == "rotary":
            k = rope(k.reshape(-1, Hkv, d), positions,
                     float(cfg["rope_theta"])).reshape(k.shape)
        return k, u @ w[:, (H + Hkv) * d:(H + 2 * Hkv) * d]


def gqa_layer(params, cfg, layer, x, positions, k, v, variant=None):
    """``x + Mixer(norm1(x))`` of a softmax layer for rows ``x [R, D]`` at
    ``positions`` against the sequence's ``k``, ``v`` ``[T, Hkv, d]``."""
    with jax.default_matmul_precision("highest"):
        H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        lp = params["layers"][layer]
        w = lp["w_in"].astype(jnp.float32)
        u = rms_norm(x, params["ln1"][layer], cfg["rms_norm_eps"])
        q = (u @ w[:, :H * d]).reshape(-1, H, d)
        if variant == "rotary":
            q = rope(q, positions, float(cfg["rope_theta"]))
        a = attention(q, k, v, positions).reshape(x.shape[0], -1)
        if variant != "no_gate":
            a = a * jax.nn.sigmoid(u @ w[:, (H + 2 * Hkv) * d:])
        return x + a @ lp["wo"].astype(jnp.float32)


def kda_step(S, q, k, v, g, beta):
    """One token of the gated delta rule for one sequence: ``S [H, dk, dv]``,
    ``q, k, g [H, dk]``, ``v [H, dv]``, ``beta [H]``.  Returns ``(o [H, dv],
    S')`` with ``o = S'^T q`` (unscaled)."""
    sd = jnp.exp(g)[:, :, None] * S
    ks = jnp.einsum("hk,hkv->hv", k, sd)
    new = sd + beta[:, None, None] * k[:, :, None] * (v - ks)[:, None, :]
    return jnp.einsum("hk,hkv->hv", q, new), new


def kda_layer(params, cfg, layer, x, state, tail, real, variant=None):
    """``x + Mixer(norm1(x))`` of a delta-rule layer for consecutive rows ``x
    [R, D]`` of one sequence, from the state ``[H, d, d]`` and the
    convolution's last inputs ``tail [K - 1, 3 H d]`` before them; rows where
    ``real [R]`` is false neither decay nor write (and are no convolution
    input to keep: they lie behind every real row).  Returns ``(y [R, D],
    state', tail')``."""
    with jax.default_matmul_precision("highest"):
        lin = cfg["linear_attn_config"]
        H, d, K = lin["num_heads"], lin["head_dim"], lin[
            "short_conv_kernel_size"]
        N, f32 = H * d, jnp.float32
        lp = params["layers"][layer]
        r = (lp["w_low"].shape[1] - H) // 2
        u = rms_norm(x, params["ln1"][layer], cfg["rms_norm_eps"])
        z = jnp.concatenate([tail, u @ lp["w_qkv"].astype(f32)], axis=0)
        R = x.shape[0]
        taps = range(1, K) if variant == "taps3" else range(K)
        y = jax.nn.silu(sum(lp["conv_w"][j] * z[j:j + R] for j in taps))
        q, k, v = (y[:, i * N:(i + 1) * N].reshape(R, H, d) for i in range(3))
        q, k = l2(q), l2(k)
        low = u @ lp["w_low"].astype(f32)
        g = -jnp.exp(lp["A_log"])[None, :, None] * jax.nn.softplus(
            low[:, :r] @ lp["w_f2"].astype(f32) + lp["dt_bias"]
        ).reshape(R, H, d)
        if variant == "head_decay":
            g = jnp.broadcast_to(g.mean(axis=-1, keepdims=True), g.shape)
        beta = (1.0 if variant == "beta1" else 2.0) * jax.nn.sigmoid(
            low[:, 2 * r:])
        assert cfg["kda_allow_neg_eigval"], "beta = 2 sigmoid(.) is written"
        g = jnp.where(real[:, None, None], g, 0.0)
        beta = jnp.where(real[:, None], beta, 0.0)

        def token(S, xs):
            o, S = kda_step(S, *xs)
            return S, o

        state, o = jax.lax.scan(token, state, (q, k, v, g, beta))
        o = rms_norm(o / math.sqrt(d), lp["o_norm"], cfg["rms_norm_eps"]
                     ).reshape(R, N)
        if variant != "no_gate":
            o = o * jax.nn.sigmoid(low[:, r:2 * r] @ lp["w_g2"].astype(f32))
        n = real.sum()
        return (x + o @ lp["wo"].astype(f32), state,
                jax.lax.dynamic_slice_in_dim(z, n, K - 1, axis=0))


def swiglu(x, w_gu, w_down):
    f = w_down.shape[0]
    gu = x @ w_gu.astype(jnp.float32)
    return (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_down.astype(jnp.float32)


def scores(u, router_w):
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(u @ router_w.astype(jnp.float32))


def weights(chosen, s, scale=1.0):
    """The chosen experts' scores, renormalised: ``[T, E]``."""
    w = jnp.where(chosen, s, 0.0)
    return w / (w.sum(axis=-1, keepdims=True) + 1e-20) * scale


def route(u, router_w, bias, top_k, scale=1.0):
    """``(chosen [T, E] bool, weights [T, E])``: sigmoid scores over all
    experts, the ``top_k`` largest of score + bias by rank (ties: the lower
    expert), the weights the chosen SCORES renormalised."""
    s = scores(u, router_w)
    b = s + bias
    e = jnp.arange(s.shape[-1])
    beats = (b[:, None, :] > b[:, :, None]) | (
        (b[:, None, :] == b[:, :, None]) & (e[None, :] < e[:, None]))
    chosen = beats.sum(axis=-1) < top_k
    return chosen, weights(chosen, s, scale)


def moe_layer(u, router_w, bias, e_gu, e_down, shared, top_k, held, scale=1.0,
              forced=None, layer=None):
    """The share ``held = (lo, hi)`` of the expert block on normalised rows
    ``u [T, D]``: every held expert (``e_gu [H, D, 2F]``, ``e_down [H, F,
    D]``, or with ``layer`` the served stacks ``[L, H, ..]`` read at ``[layer,
    i]``) applied to every row and masked, plus ``shared = (w_gu, w_down)``
    once (None: another holder adds it).  ``forced = (rows [T] bool, sets [T,
    E] bool)``: those rows are computed over the GIVEN experts.  Returns ``(y
    [T, D], chosen [T, E])``, ``chosen`` always the router's own choice over
    ALL experts."""
    with jax.default_matmul_precision("highest"):
        chosen, w = route(u, router_w, bias, top_k, scale)
        if forced is not None:
            w = weights(jnp.where(forced[0][:, None], forced[1], chosen),
                        scores(u, router_w), scale)
        lo, hi = held

        def one(y, i):
            at = i if layer is None else (layer, i)
            return y + jax.lax.dynamic_index_in_dim(
                w, lo + i, axis=1, keepdims=True) * swiglu(
                    u, e_gu[at], e_down[at]), None

        y = jnp.zeros_like(u) if shared is None else swiglu(u, *shared)
        y, _ = jax.lax.scan(one, y, jnp.arange(hi - lo))
        return y, chosen


def forward(params, cfg, tokens, positions, block=32, forced=None, upto=None,
            variant=None):
    """Next-token logits ``[P, V]`` at ``positions [P]`` of ``tokens [T]`` (``T``
    a multiple of ``block``; a pad tail is causally invisible), each layer's
    chosen experts at those positions ``[P, E]`` bool, and what each layer's
    cache would keep: a softmax layer's ``(k, v)`` rows at the positions ``[P,
    Hkv * d]``, a delta-rule layer's ``(state [H, d, d], last K - 1
    convolution inputs [K - 1, 3 H d])`` after token ``upto - 1`` (default:
    all of ``tokens``).  ``forced = (rows [F] int32, [sets [F, E] bool per
    layer])``: the rows at those positions are computed over the given
    experts."""
    assert variant is None or variant in VARIANTS, variant
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        T = tokens.shape[0]
        Hkv, d = cfg["num_key_value_heads"], cfg["head_dim"]
        lin = cfg["linear_attn_config"]
        E, eps = cfg["router_experts"], cfg["rms_norm_eps"]
        pos_all = jnp.arange(T, dtype=jnp.int32)
        last = T if upto is None else upto
        # rows a block of the delta rule and of the expert loop
        wide = block * math.gcd(T // block, 16)
        x = params["embed"][tokens].astype(f32)
        chosen_at, kept = [], []
        for layer, kind in enumerate(kinds(cfg)):
            lp = params["layers"][layer]
            if kind == "gqa":
                k_rows, v_rows = gqa_rows(params, cfg, layer, x, pos_all,
                                          variant)
                kept.append((k_rows[positions], v_rows[positions]))
                k = k_rows.reshape(T, Hkv, d)
                v = v_rows.reshape(T, Hkv, d)
                h = jax.lax.map(
                    lambda xb, layer=layer, k=k, v=v: gqa_layer(
                        params, cfg, layer, xb[0], xb[1], k, v, variant),
                    (x.reshape(T // block, block, -1),
                     pos_all.reshape(T // block, block))).reshape(T, -1)
            else:
                n = lin["num_heads"] * lin["head_dim"]

                def rows(carry, xb, layer=layer):
                    full, snap = carry
                    xr, pr = xb
                    y, S, tail = kda_layer(params, cfg, layer, xr, *full,
                                           jnp.ones(pr.shape, bool), variant)
                    # the leaves as they stand after token ``upto - 1``: the
                    # one block that straddles it runs once more, cut there
                    snap = jax.lax.cond(
                        pr[-1] < last, lambda: (S, tail),
                        lambda: jax.lax.cond(
                            pr[0] < last,
                            lambda: kda_layer(params, cfg, layer, xr, *full,
                                              pr < last, variant)[1:],
                            lambda: snap))
                    return ((S, tail), snap), y

                start = (jnp.zeros((lin["num_heads"],) + (lin["head_dim"],) * 2,
                                   f32),
                         jnp.zeros((lin["short_conv_kernel_size"] - 1, 3 * n),
                                   f32))
                (_, leaves), h = jax.lax.scan(
                    rows, (start, start), (x.reshape(T // wide, wide, -1),
                                           pos_all.reshape(T // wide, wide)))
                h = h.reshape(T, -1)
                kept.append(leaves)
            u = rms_norm(h, params["ln2"][layer], eps)
            given = (jnp.zeros((T,), bool), jnp.zeros((T, E), bool))
            if forced is not None:
                given = (given[0].at[forced[0]].set(True),
                         given[1].at[forced[0]].set(forced[1][layer]))

            def experts(ub, layer=layer, lp=lp):
                return moe_layer(
                    ub[0], params["router_w"][layer],
                    params["router_b"][layer], params["e_gu"],
                    params["e_down"], (lp["s_gu"], lp["s_down"]),
                    cfg["num_experts_per_tok"], tuple(cfg["experts_held"]),
                    float(cfg["routed_scaling_factor"]), forced=ub[1:],
                    layer=layer)

            y, chosen = jax.lax.map(experts, (
                u.reshape(T // wide, wide, -1),
                given[0].reshape(T // wide, wide),
                given[1].reshape(T // wide, wide, E)))
            chosen_at.append(chosen.reshape(T, E)[positions])
            x = h + y.reshape(T, -1)
        xn = rms_norm(x[positions], params["norm_f"], eps)
        return xn @ params["head"].astype(f32), chosen_at, kept
