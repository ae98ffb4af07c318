"""Plain reference for ``mellum2_12b_a2_5b``: the forward pass of JetBrains'
Mellum2-12B-A2.5B-Instruct (``model_type`` ``mellum``) over one whole sequence
in straightforward float32 ``jax.numpy`` at the highest matmul precision.  No
kernel, no cache, no paging, no sorting or grouping of experts, no batching;
the parameters are an ARGUMENT (the served pytree, upcast here, one expert at
a time).  Query rows are processed in blocks of ``block``, the expert loop in
blocks of rows and the head in blocks of the vocabulary, so that 36864 tokens
fit beside the served weights.

Source: https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json
Every equation is fixed by its keys:

    hidden 2304, 28 layers, vocabulary 98304 (untied), RMSNorm eps 1e-6, no bias
    x_0 = E[tok];  h = x + Attn(norm1(x));  x' = h + MoE(norm2(h))
    logits = W_head RMSNorm(x_L)

Attention (32 query heads, 4 KV heads, ``head_dim`` 128; query head i reads KV
head i // 8), u = norm1(x):

    q = u W_q [32, 128], k = u W_k [4, 128], v = u W_v [4, 128]
    rotate-half rotary on the whole head of q and k, angle position * inv_freq_i
    scores q_h . k_(h // 8) / sqrt(128), softmax over the keys the layer sees
    ``layer_types[l]`` = sliding_attention: keys s with 0 <= t - s <= 1023
        (``sliding_window`` 1024, the query's own position counted);
        inv_freq_i = 500000 ** (-2i / 128)       (``rope_type`` default)
    ``layer_types[l]`` = full_attention: keys s <= t; YaRN (``rope_parameters
        .full_attention``: factor s = 16, original context 8192, beta_fast 32,
        beta_slow 1, theta 500000): f_i = theta ** (-2i / 128);
        dim(b) = 128 ln(8192 / (2 pi b)) / (2 ln theta);
        low = max(floor(dim(32)), 0), high = min(ceil(dim(1)), 63);
        r_i = clip((i - low) / (high - low), 0, 1);
        inv_freq_i = (f_i / s) r_i + f_i (1 - r_i);
        cos and sin multiplied by ``attention_factor`` 1.2772588722239782
        (= 0.1 ln 16 + 1) on q and on k.
    o = concat_h(P_h v_(h // 8)) W_o                 (4096 -> 2304)

Experts (``mlp_layer_types`` all sparse; ``intermediate_size`` 7168 is used by
no layer), u = norm2(h): p = softmax(u W_g) over all 64 in float32; the 8
largest are chosen (on a tie the lower expert wins); w = p[chosen] / sum
(``norm_topk_prob``); no bias, no scaling factor, no shared expert;

    MoE(u) = sum_{e in top8} w_e (silu(u W_gate,e) * u W_up,e) W_down,e

Dropless: every chosen (token, expert) pair is computed.  Written here as a
loop over ALL experts with a mask.

The served pytree's layout (``paddle_tpu/models/mellum.py:params``): ``w_qkv``
= [W_q | W_k | W_v] column-wise; ``e_gu [L, 64, 2304, 1792]`` = [gate | up]
column-wise and ``e_down [L, 64, 896, 2304]``; ``router_w [L, 2304, 64]``.

Departures from the published description, each because the config cannot
settle it:
* NO QK-norm and no attention gate: the config has no key for either (the
  family's code may normalise q and k per head; without a key there is no
  shape or epsilon to write it from).
* NO MTP head: the catalog's ``described_as.other`` says "MTP head", the
  config has no key for one, and the guide says to trust the config.
* ``forced`` lets a caller GIVE the experts of some rows: top-8 is a discrete
  choice, and a served row whose eighth and ninth probabilities lie closer
  than bfloat16 rounding of the residual stream takes another expert than
  this float32 pass; its logits and the K/V rows it caches are then
  comparable only over the same experts, and the choice itself is compared
  apart.
Nothing else.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def inverse_frequencies(rope, d):
    """``(inv_freq [d / 2] float32, attention_factor)`` of one entry of
    ``rope_parameters``, the formula of the docstring in float64."""
    theta = float(rope["rope_theta"])
    f = theta ** (-2.0 * np.arange(d // 2, dtype=np.float64) / d)
    if rope["rope_type"] == "default":
        return f.astype(np.float32), 1.0
    assert rope["rope_type"] == "yarn", rope
    s = float(rope["factor"])
    ctx = float(rope["original_max_position_embeddings"])

    def dim(beta):
        return d * math.log(ctx / (2 * math.pi * beta)) / (2 * math.log(theta))

    low = max(math.floor(dim(rope["beta_fast"])), 0)
    high = min(math.ceil(dim(rope["beta_slow"])), d // 2 - 1)
    r = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return ((f / s) * r + f * (1 - r)).astype(np.float32), float(
        rope["attention_factor"])


def rope(x, positions, inv_freq, factor):
    """Rotate-half rotary: pairs ``(x[i], x[i + d / 2])`` of the last axis of
    ``x [T, H, d]`` at ``positions [T]``."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, positions, window=None, sm_scale=None):
    """Masked softmax attention: ``q [R, Hq, d]`` at absolute ``positions
    [R]`` against ``k``, ``v`` ``[T, Hkv, d]`` (key ``s`` at position ``s``;
    query head ``i`` reads KV head ``i // (Hq / Hkv)``); a key is seen where
    ``0 <= t - s`` and, with ``window``, ``t - s <= window - 1``."""
    with jax.default_matmul_precision("highest"):
        R, Hq, d = q.shape
        g = Hq // k.shape[1]
        scale = 1.0 / math.sqrt(d) if sm_scale is None else sm_scale
        qg = q.reshape(R, k.shape[1], g, d)
        s = jnp.einsum("rhgd,thd->rhgt", qg, k) * scale
        back = positions[:, None] - jnp.arange(k.shape[0])[None, :]
        ok = back >= 0
        if window is not None:
            ok = ok & (back <= window - 1)
        p = jax.nn.softmax(jnp.where(ok[:, None, None, :], s, -1e30), axis=-1)
        return jnp.einsum("rhgt,thd->rhgd", p, v).reshape(R, Hq, d)


def swiglu(x, w_gu, w_down):
    f = w_down.shape[0]
    gu = x @ w_gu.astype(jnp.float32)
    return (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_down.astype(jnp.float32)


def scores(u, router_w):
    with jax.default_matmul_precision("highest"):
        return jax.nn.softmax(u @ router_w.astype(jnp.float32), axis=-1)


def weights(chosen, s):
    """The chosen experts' probabilities, renormalised: ``[T, E]``."""
    w = jnp.where(chosen, s, 0.0)
    return w / (w.sum(axis=-1, keepdims=True) + 1e-20)


def route(u, router_w, top_k):
    """``(chosen [T, E] bool, weights [T, E])``: softmax over all experts, the
    ``top_k`` largest by rank (ties: the lower expert), renormalised."""
    s = scores(u, router_w)
    e = jnp.arange(s.shape[-1])
    beats = (s[:, None, :] > s[:, :, None]) | (
        (s[:, None, :] == s[:, :, None]) & (e[None, :] < e[:, None]))
    chosen = beats.sum(axis=-1) < top_k
    return chosen, weights(chosen, s)


def moe_layer(u, router_w, e_gu, e_down, top_k, held=None, forced=None,
              layer=None):
    """The expert block on normalised rows ``u [T, D]``: every expert of
    ``held`` (default all; ``e_gu [H, D, 2F]``, ``e_down [H, F, D]`` hold
    exactly those, or with ``layer`` the served stacks ``[L, H, ..]`` read at
    ``[layer, i]``: a layer sliced out first is a 0.8 GB copy beside the
    served weights) applied to every row and masked.  ``forced = (rows [T]
    bool, sets [T, E] bool)``: those rows are computed over the GIVEN experts
    (weights from this router's own probabilities).  Returns ``(y [T, D],
    chosen [T, E])``, ``chosen`` always the router's own choice."""
    with jax.default_matmul_precision("highest"):
        chosen, w = route(u, router_w, top_k)
        if forced is not None:
            w = weights(jnp.where(forced[0][:, None], forced[1], chosen),
                        scores(u, router_w))
        lo = 0 if held is None else held[0]

        def one(y, i):
            at = i if layer is None else (layer, i)
            y_i = swiglu(u, e_gu[at], e_down[at])
            return y + jax.lax.dynamic_index_in_dim(
                w, lo + i, axis=1, keepdims=True) * y_i, None

        y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                            jnp.arange(e_gu.shape[-3]))
        return y, chosen


def layer_rows(params, cfg, layer, x, positions):
    """The rotated K rows and the V rows ``[T, Hkv * d]`` layer ``layer``
    caches for its input rows ``x [T, D]`` at ``positions``."""
    with jax.default_matmul_precision("highest"):
        H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        inv_freq, factor = inverse_frequencies(
            cfg["rope_parameters"][cfg["layer_types"][layer]], d)
        w = params["layers"][layer]["w_qkv"].astype(jnp.float32)
        u = rms_norm(x, params["ln1"][layer], cfg["rms_norm_eps"])
        k = rope((u @ w[:, H * d:(H + Hkv) * d]).reshape(-1, Hkv, d),
                 positions, inv_freq, factor)
        return k.reshape(-1, Hkv * d), u @ w[:, (H + Hkv) * d:]


def forward(params, cfg, tokens, positions, block=128, forced=None):
    """Next-token logits ``[P, V]`` at ``positions [P]`` of ``tokens [T]``
    (``T`` a multiple of ``block``; a pad tail is causally invisible), each
    layer's chosen experts at those positions ``[P, E]`` bool, and each
    layer's ``(k, v)`` rows there ``[P, Hkv * d]`` (what a cache would keep
    of the token).  ``forced = (rows [F] int32, [sets [F, E] bool per
    layer])``: the rows at those positions are computed over the given
    experts (see :func:`moe_layer`); what is returned is the router's own
    choice."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        T = tokens.shape[0]
        H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        eps, E = cfg["rms_norm_eps"], cfg["num_experts"]
        pos_all = jnp.arange(T, dtype=jnp.int32)
        # rows a block of the expert loop: a few attention blocks
        wide = block * math.gcd(T // block, 16)
        x = params["embed"][tokens].astype(f32)
        chosen_at, rows_at = [], []
        for layer, lp in enumerate(params["layers"]):
            kind = cfg["layer_types"][layer]
            window = (cfg["sliding_window"] if kind == "sliding_attention"
                      else None)
            inv_freq, factor = inverse_frequencies(
                cfg["rope_parameters"][kind], d)
            w_qkv, wo = lp["w_qkv"].astype(f32), lp["wo"].astype(f32)
            k_rows, v_rows = layer_rows(params, cfg, layer, x, pos_all)
            rows_at.append((k_rows[positions], v_rows[positions]))
            k = k_rows.reshape(T, Hkv, d)
            v = v_rows.reshape(T, Hkv, d)

            def rows(xb, w_qkv=w_qkv, wo=wo, k=k, v=v, window=window,
                     inv_freq=inv_freq, factor=factor,
                     ln1=params["ln1"][layer]):
                xr, pr = xb
                q = rope((rms_norm(xr, ln1, eps) @ w_qkv[:, :H * d]).reshape(
                    -1, H, d), pr, inv_freq, factor)
                return xr + attention(q, k, v, pr, window).reshape(
                    xr.shape[0], -1) @ wo

            h = jax.lax.map(rows, (x.reshape(T // block, block, -1),
                                   pos_all.reshape(T // block, block))
                            ).reshape(T, -1)
            u = rms_norm(h, params["ln2"][layer], eps)
            given = (jnp.zeros((T,), bool), jnp.zeros((T, E), bool))
            if forced is not None:
                given = (given[0].at[forced[0]].set(True),
                         given[1].at[forced[0]].set(forced[1][layer]))

            def experts(ub, layer=layer):
                return moe_layer(
                    ub[0], params["router_w"][layer], params["e_gu"],
                    params["e_down"], cfg["num_experts_per_tok"],
                    forced=ub[1:], layer=layer)

            y, chosen = jax.lax.map(experts, (
                u.reshape(T // wide, wide, -1),
                given[0].reshape(T // wide, wide),
                given[1].reshape(T // wide, wide, E)))
            chosen_at.append(chosen.reshape(T, E)[positions])
            x = h + y.reshape(T, -1)
        # the head in eight blocks of the vocabulary: its float32 copy whole
        # is 0.9 GB beside the served weights
        xn = rms_norm(x[positions], params["norm_f"], eps)
        head = params["head"]
        step = -(-head.shape[1] // 8)
        logits = jnp.concatenate(
            [xn @ head[:, at:at + step].astype(f32)
             for at in range(0, head.shape[1], step)], axis=1)
        return logits, chosen_at, rows_at
