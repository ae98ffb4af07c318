"""Plain reference for ``trinity_large_preview``: the forward pass of arcee-ai's
Trinity-Large-Preview (``model_type`` ``afmoe``) over one whole sequence in
straightforward float32 ``jax.numpy`` at the highest matmul precision.  No
kernel, no cache, no paging, no sorting or grouping of experts, no batching;
the parameters are an ARGUMENT (the served pytree, upcast here, one expert at
a time).  Query rows are processed in blocks of ``block``, the expert loop in
blocks of rows and the head in blocks of the vocabulary, so that 17408 tokens
fit beside the served weights.

Source: https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json

    hidden 3072, 60 layers, vocabulary 200192 (untied), RMSNorm eps 1e-5, no bias
    x_0 = E[tok] * sqrt(3072)                                  (``mup_enabled``)
    h = x + norm_post_attn(Attn(norm_in(x)))
    x' = h + norm_post_mlp(FFN(norm_pre_mlp(h)))               (sandwich norms)
    logits = W_head RMSNorm(x_L)

Attention (48 query heads, 8 KV heads, ``head_dim`` 128; query head i reads KV
head i // 6), a = norm_in(x):

    q = a W_q [48, 128], k = a W_k [8, 128], v = a W_v [8, 128], g = a W_g [6144]
    q <- RMSNorm_q(q), k <- RMSNorm_k(k): over the head's 128, a weight [128]
    ``layer_types[l]`` = sliding_attention: rotate-half rotary on the whole head
        of q and k, angle position * 10000 ** (-2i / 128) (``rope_theta`` 10000,
        ``rope_scaling`` null); keys s with 0 <= t - s <= 4095
        (``sliding_window`` 4096, the query's own position counted)
    ``layer_types[l]`` = full_attention: NO rotation of q or k; keys s <= t
    scores q_h . k_(h // 6) / sqrt(128), softmax in float32 over the keys seen
    o = (sigmoid(g) * concat_h(P_h v_(h // 6))) W_o            (6144 -> 3072)

Feed-forward, b = norm_pre_mlp(h).  Layers 0 .. ``num_dense_layers`` - 1:
``W_d (silu(W_g b) * W_u b)`` of width 12288.  Every later layer: s =
sigmoid(b W_r) over all 256 in float32 (``score_func`` sigmoid); chosen = the 4
largest of s + expert_bias (the bias selects only; on a tie the lower expert
wins); w = s[chosen] / (sum s[chosen] + 1e-20) * 2.448 (``route_norm``,
``route_scale``); ``n_group`` = ``topk_group`` = 1: no group limit;

    FFN(b) = Shared(b) + sum_{e in chosen} w_e (silu(b W_gate,e) * b W_up,e) W_down,e

Dropless: every chosen (token, expert) pair is computed.  Written here as a
loop over the experts HELD (``held``: the holder's ``lo .. hi - 1`` of 256)
with a mask: the terms of the experts held elsewhere are left out, here as in
the served program, and that partial sum goes on to the next layer.

The served pytree's layout (``paddle_tpu/models/afmoe.py:params``): ``w_in`` =
[W_q | W_k | W_v | W_g] column-wise; ``d_gu`` / ``s_gu`` = [gate | up]
column-wise; ``e_gu [Le, held, 3072, 6144]`` and ``e_down [Le, held, 3072,
3072]`` over the expert layers; ``router_w [Le, 3072, 256]``, ``router_b [Le,
256]``.

Departures from the published description, each because the config cannot
settle it:
* The QK-norm, the gate's shape (elementwise, projected from the mixer's
  normalised input at the query width), the four norms a layer and rotary on
  the sliding layers ONLY have no key in the config: they are the ``afmoe``
  modelling code's, unconditional there, and the catalog's ``described_as``
  bears out "gated" and "sandwich norm".
* "Depth-scaled" (``described_as.other``) is an initialisation, not an
  equation: seeded weights need not follow it.  ``load_balance_coeff`` and
  ``use_grouped_mm`` are training's and the checkpoint loader's: unused.
* ``forced`` lets a caller GIVE the experts of some rows: top-4 is a discrete
  choice, and a served row whose fourth and fifth biased scores lie closer
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


def rope(x, positions, theta):
    """Rotate-half rotary: pairs ``(x[i], x[i + d / 2])`` of the last axis of
    ``x [T, H, d]`` at ``positions [T]``, angle ``position * theta ** (-2i /
    d)`` (the frequencies in float64 on the host, rounded once)."""
    d = x.shape[-1]
    inv_freq = (float(theta) ** (-2.0 * np.arange(d // 2, dtype=np.float64)
                                 / d)).astype(np.float32)
    ang = positions.astype(jnp.float32)[:, None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
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
        return jax.nn.sigmoid(u @ router_w.astype(jnp.float32))


def weights(chosen, s, scale):
    """The chosen experts' SCORES (no bias), renormalised and scaled: ``[T,
    E]``."""
    w = jnp.where(chosen, s, 0.0)
    return w / (w.sum(axis=-1, keepdims=True) + 1e-20) * scale


def route(u, router_w, router_b, top_k, scale):
    """``(chosen [T, E] bool, weights [T, E])``: sigmoid scores over all
    experts, the ``top_k`` largest of score + bias by rank (ties: the lower
    expert), the weights from the scores alone."""
    s = scores(u, router_w)
    b = s + router_b
    e = jnp.arange(s.shape[-1])
    beats = (b[:, None, :] > b[:, :, None]) | (
        (b[:, None, :] == b[:, :, None]) & (e[None, :] < e[:, None]))
    chosen = beats.sum(axis=-1) < top_k
    return chosen, weights(chosen, s, scale)


def moe_layer(u, router_w, router_b, e_gu, e_down, shared, top_k, scale,
              held=None, forced=None, layer=None):
    """The expert block on normalised rows ``u [T, D]``: the shared expert
    (``shared = (s_gu, s_down)``, or None to leave it out) plus every expert
    of ``held = (lo, hi)`` (default all; ``e_gu [H, D, 2F]``, ``e_down [H, F,
    D]`` hold exactly those, or with ``layer`` the served stacks ``[Le, H,
    ..]`` read at ``[layer, i]``) applied to every row and masked.  ``forced =
    (rows [T] bool, sets [T, E] bool)``: those rows are computed over the
    GIVEN experts (weights from this router's own scores).  Returns ``(y [T,
    D], chosen [T, E])``, ``chosen`` always the router's own choice."""
    with jax.default_matmul_precision("highest"):
        chosen, w = route(u, router_w, router_b, top_k, scale)
        if forced is not None:
            w = weights(jnp.where(forced[0][:, None], forced[1], chosen),
                        scores(u, router_w), scale)
        lo = 0 if held is None else held[0]

        def one(y, i):
            at = i if layer is None else (layer, i)
            y_i = swiglu(u, e_gu[at], e_down[at])
            return y + jax.lax.dynamic_index_in_dim(
                w, lo + i, axis=1, keepdims=True) * y_i, None

        y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                            jnp.arange(e_gu.shape[-3]))
        if shared is not None:
            y = y + swiglu(u, *shared)
        return y, chosen


def _split(cfg):
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return H * d, Hkv * d


def layer_rows(params, cfg, layer, x, positions):
    """The K rows (normalised a head; rotated in a sliding layer, NOT in a full
    one) and the V rows ``[T, Hkv * d]`` layer ``layer`` caches for its input
    rows ``x [T, D]`` at ``positions``."""
    with jax.default_matmul_precision("highest"):
        n_q, n_kv = _split(cfg)
        Hkv, d = cfg["num_key_value_heads"], cfg["head_dim"]
        w = params["layers"][layer]["w_in"].astype(jnp.float32)
        a = rms_norm(x, params["ln_in"][layer], cfg["rms_norm_eps"])
        k = rms_norm((a @ w[:, n_q:n_q + n_kv]).reshape(-1, Hkv, d),
                     params["kn"][layer], cfg["rms_norm_eps"])
        if cfg["layer_types"][layer] == "sliding_attention":
            k = rope(k, positions, cfg["rope_theta"])
        return k.reshape(-1, n_kv), a @ w[:, n_q + n_kv:n_q + 2 * n_kv]


def forward(params, cfg, tokens, positions, block=128, forced=None):
    """Next-token logits ``[P, V]`` at ``positions [P]`` of ``tokens [T]``
    (``T`` a multiple of ``block``; a pad tail is causally invisible), each
    EXPERT layer's chosen experts at those positions ``[P, E]`` bool, and
    each layer's ``(k, v)`` rows there ``[P, Hkv * d]`` (what a cache would
    keep of the token).  ``forced = (rows [F] int32, [sets [F, E] bool per
    expert layer])``: the rows at those positions are computed over the given
    experts (see :func:`moe_layer`); what is returned is the router's own
    choice."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        T = tokens.shape[0]
        H, d = cfg["num_attention_heads"], cfg["head_dim"]
        Hkv = cfg["num_key_value_heads"]
        n_q, n_kv = _split(cfg)
        eps, E = cfg["rms_norm_eps"], cfg["router_experts"]
        n_dense, F = cfg["num_dense_layers"], cfg["intermediate_size"]
        pos_all = jnp.arange(T, dtype=jnp.int32)
        # rows a block of the expert loop: a few attention blocks
        wide = block * math.gcd(T // block, 16)
        x = params["embed"][tokens].astype(f32) * math.sqrt(cfg["hidden_size"])
        chosen_at, rows_at = [], []
        for layer, lp in enumerate(params["layers"]):
            sliding = cfg["layer_types"][layer] == "sliding_attention"
            window = cfg["sliding_window"] if sliding else None
            w_in, wo = lp["w_in"].astype(f32), lp["wo"].astype(f32)
            k_rows, v_rows = layer_rows(params, cfg, layer, x, pos_all)
            rows_at.append((k_rows[positions], v_rows[positions]))
            k = k_rows.reshape(T, Hkv, d)
            v = v_rows.reshape(T, Hkv, d)

            def rows(xb, layer=layer, w_in=w_in, wo=wo, k=k, v=v,
                     window=window, sliding=sliding):
                xr, pr = xb
                a = rms_norm(xr, params["ln_in"][layer], eps)
                q = rms_norm((a @ w_in[:, :n_q]).reshape(-1, H, d),
                             params["qn"][layer], eps)
                if sliding:
                    q = rope(q, pr, cfg["rope_theta"])
                gate = jax.nn.sigmoid(a @ w_in[:, n_q + 2 * n_kv:])
                o = attention(q, k, v, pr, window).reshape(xr.shape[0], -1)
                return xr + rms_norm((gate * o) @ wo,
                                     params["ln_post_attn"][layer], eps)

            h = jax.lax.map(rows, (x.reshape(T // block, block, -1),
                                   pos_all.reshape(T // block, block))
                            ).reshape(T, -1)
            b = rms_norm(h, params["ln_pre_mlp"][layer], eps)
            if layer < n_dense:
                m = jax.lax.map(
                    lambda bb, lp=lp: swiglu(bb, lp["d_gu"], lp["d_down"]),
                    b.reshape(T // wide, wide, -1)).reshape(T, -1)
                assert lp["d_down"].shape[0] == F
            else:
                row = layer - n_dense
                given = (jnp.zeros((T,), bool), jnp.zeros((T, E), bool))
                if forced is not None:
                    given = (given[0].at[forced[0]].set(True),
                             given[1].at[forced[0]].set(forced[1][row]))

                def experts(ub, row=row, lp=lp):
                    return moe_layer(
                        ub[0], params["router_w"][row],
                        params["router_b"][row], params["e_gu"],
                        params["e_down"], (lp["s_gu"], lp["s_down"]),
                        cfg["num_experts_per_tok"], cfg["route_scale"],
                        held=tuple(cfg["experts_held"]), forced=ub[1:],
                        layer=row)

                m, chosen = jax.lax.map(experts, (
                    b.reshape(T // wide, wide, -1),
                    given[0].reshape(T // wide, wide),
                    given[1].reshape(T // wide, wide, E)))
                chosen_at.append(chosen.reshape(T, E)[positions])
                m = m.reshape(T, -1)
            x = h + rms_norm(m, params["ln_post_mlp"][layer], eps)
        # the head in blocks of the vocabulary
        xn = rms_norm(x[positions], params["norm_f"], eps)
        head = params["head"]
        step = -(-head.shape[1] // 8)
        logits = jnp.concatenate(
            [xn @ head[:, at:at + step].astype(f32)
             for at in range(0, head.shape[1], step)], axis=1)
        return logits, chosen_at, rows_at
