"""Plain reference for ``kanana2_30b_a3b``: the forward pass of kakaocorp's
kanana-2-30b-a3b (``model_type`` ``deepseek_v3``) over one whole sequence in
straightforward float32 ``jax.numpy`` at the highest matmul precision.  No
kernel, no cache, no paging, no absorbed attention, no sorting or grouping of
experts, no batching; the parameters are an ARGUMENT (the served pytree,
upcast here, one expert at a time).  Query rows are processed in blocks of
``block`` so that 20480 tokens fit beside the served weights.

Source: https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json
Every equation is fixed by its keys:

    hidden 2048, 48 layers, vocabulary 128256 (untied), RMSNorm eps 1e-6
    x_0 = E[tok];  h = x + MLA(norm1(x));  x' = h + FFN(norm2(h))
    logits = W_head RMSNorm(x_L)

MLA (``q_lora_rank`` null; 32 heads; ``qk_nope_head_dim`` 128,
``qk_rope_head_dim`` 64, ``v_head_dim`` 128, ``kv_lora_rank`` 512), u = norm1(x):

    q = u W_q            -> [32, 192] = [q_nope 128 | q_pe 64] a head
    u W_kva              -> [c' 512 | k_pe 64];  c = RMSNorm(c')  (kv_a_layernorm)
    c W_kvb              -> [32, k_nope 128 | v 128]
    rotary on q_pe and on the ONE shared k_pe: theta 1e6, on interleaved pairs
      (x_2i, x_2i+1) (``rope_interleave``), no scaling (``rope_scaling`` null,
      so no mscale)
    k_h = [k_nope_h | k_pe];  scores q_h . k_h / sqrt(192), causal softmax
    o = concat_h(P_h v_h) W_o                         (4096 -> 2048)

Layer 0 (``first_k_dense_replace`` 1): SwiGLU 2048 -> 6144 -> 2048.
Layers 1..: u = norm2(h); router s = sigmoid(u W_g) (128 scores, float32);
choose the top 6 of s + b (``e_score_correction_bias``; ``n_group`` =
``topk_group`` = 1, so no group limit; on a tie the lower expert wins);
weights w = s[chosen] (WITHOUT b), w /= sum(w) + 1e-20 (``norm_topk_prob``),
w *= 2.448 (``routed_scaling_factor``);

    FFN(u) = sum_i w_i E_i(u) + S(u)
    E_i: SwiGLU 2048 -> 768 -> 2048;  S: SwiGLU 2048 -> 1536 -> 2048
                                       (``n_shared_experts`` 2 x 768)

Dropless: every chosen (token, expert) pair is computed.  Written here as a
loop over ALL experts with a mask.

The served pytree's layout (``paddle_tpu/models/deepseek_v3.py:params``):
``w_in`` = [W_q | W_kva] column-wise; ``wkvb [32, 256, 512]`` holds head h's
slice of W_kvb transposed (rows 0..127 give k_nope, 128..255 give v);
``w_gu`` = [gate | up] column-wise (the dense block's, or S's); ``e_gu [5,
128, 2048, 1536]`` / ``e_down [5, 128, 768, 2048]`` the routed experts of the
expert layers in order, gate | up fused the same way; ``router_w``,
``router_b`` stacked over the expert layers.

Departures and readings: ``forced`` lets a caller GIVE the experts of some
rows: top-6 is a discrete choice, and a served row whose sixth and seventh
scores lie closer than bfloat16 rounding of the residual stream takes another
expert than this float32 pass; its logits and the latent rows it caches are
then comparable only over the same experts, and the choice itself is compared
apart.  The expert loop runs over blocks of rows too.  Nothing else.
"""
import math

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def rope_interleaved(x, positions, theta):
    """Rotary on the pairs ``(x[2i], x[2i + 1])`` of the last axis, angle
    ``position * theta ** (-2i / d)``; ``positions`` matches ``x``'s leading
    axis (further axes of ``x`` broadcast)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, positions):
    """Causal softmax attention: ``q [R, H, dk]`` at absolute ``positions
    [R]`` against ``k [T, H, dk]``, ``v [T, H, dv]`` (key ``j`` at position
    ``j``) -> ``[R, H, dv]``."""
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("rhd,thd->rht", q, k) / math.sqrt(q.shape[-1])
        ok = jnp.arange(k.shape[0])[None, :] <= positions[:, None]
        p = jax.nn.softmax(jnp.where(ok[:, None, :], s, -1e30), axis=-1)
        return jnp.einsum("rht,thd->rhd", p, v)


def expand_latent(c, k_pe, wkvb, dn):
    """Per-head keys and values of cached rows: ``c [T, R]``, rotated ``k_pe
    [T, dr]``, ``wkvb [H, dn + dv, R]`` -> ``(k [T, H, dn + dr], v [T, H,
    dv])``."""
    with jax.default_matmul_precision("highest"):
        kv = jnp.einsum("tc,hdc->thd", c, wkvb.astype(jnp.float32))
    k_rot = jnp.broadcast_to(k_pe[:, None, :], kv.shape[:2] + k_pe.shape[-1:])
    return jnp.concatenate([kv[..., :dn], k_rot], axis=-1), kv[..., dn:]


def swiglu(x, w_gu, w_down):
    f = w_down.shape[0]
    gu = x @ w_gu.astype(jnp.float32)
    return (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ w_down.astype(jnp.float32)


def scores(u, router_w):
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(u @ router_w.astype(jnp.float32))


def weights(chosen, s, scale):
    """The chosen experts' scores, normalised and scaled: ``[T, E]``."""
    w = jnp.where(chosen, s, 0.0)
    return w / (w.sum(axis=-1, keepdims=True) + 1e-20) * scale


def route(u, router_w, router_b, top_k, scale):
    """``(chosen [T, E] bool, weights [T, E])``: sigmoid scores, the top
    ``top_k`` of score + bias by rank (ties: the lower expert), the chosen
    scores normalised and scaled."""
    s = scores(u, router_w)
    b = s + router_b
    e = jnp.arange(s.shape[-1])
    beats = (b[:, None, :] > b[:, :, None]) | (
        (b[:, None, :] == b[:, :, None]) & (e[None, :] < e[:, None]))
    chosen = beats.sum(axis=-1) < top_k
    return chosen, weights(chosen, s, scale)


def moe_layer(u, router_w, router_b, e_gu, e_down, shared, top_k, scale,
              held=None, forced=None):
    """The expert block on normalised rows ``u [T, D]``: every expert of
    ``held`` (default all; ``e_gu [H, D, 2F]``, ``e_down [H, F, D]`` hold
    exactly those) applied to every row and masked, plus ``shared = (w_gu,
    w_down)`` or None.  ``forced = (rows [T] bool, sets [T, E] bool)``: those
    rows are computed over the GIVEN experts (weights from this router's own
    scores).  Returns ``(y [T, D], chosen [T, E])``, ``chosen`` always the
    router's own choice."""
    with jax.default_matmul_precision("highest"):
        chosen, w = route(u, router_w, router_b, top_k, scale)
        if forced is not None:
            w = weights(jnp.where(forced[0][:, None], forced[1], chosen),
                        scores(u, router_w), scale)
        lo = 0 if held is None else held[0]

        def one(y, i):
            y_i = swiglu(u, e_gu[i], e_down[i])
            return y + jax.lax.dynamic_index_in_dim(
                w, lo + i, axis=1, keepdims=True) * y_i, None

        y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                            jnp.arange(e_gu.shape[0]))
        if shared is not None:
            y = y + swiglu(u, *shared)
        return y, chosen


def forward(params, cfg, tokens, positions, block=128, forced=None):
    """Next-token logits ``[P, V]`` at ``positions [P]`` of ``tokens [T]``
    (``T`` a multiple of ``block``; a pad tail is causally invisible), each
    expert layer's chosen experts at those positions ``[P, E]`` bool, and
    each layer's latent rows there ``[P, 512 + 64]`` = ``[c | rotated
    k_pe]`` (what a cache would keep of the token).
    ``forced = (rows [F] int32, [sets [F, E] bool per expert layer])``: the
    rows at those positions are computed over the given experts (see
    :func:`moe_layer`); what is returned is the router's own choice."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        T = tokens.shape[0]
        H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
        dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        R, theta = cfg["kv_lora_rank"], float(cfg["rope_theta"])
        n_dense = cfg["first_k_dense_replace"]
        pos_all = jnp.arange(T, dtype=jnp.int32)
        a = H * (dn + dr)
        # rows a block of the expert loop: a few attention blocks
        wide = block * math.gcd(T // block, 16)
        x = params["embed"][tokens].astype(f32)
        chosen_at, rows_at = [], []
        for layer, lp in enumerate(params["layers"]):
            w_in, wo = lp["w_in"].astype(f32), lp["wo"].astype(f32)
            u = rms_norm(x, params["ln1"][layer], eps)
            kva = u @ w_in[:, a:]
            c = rms_norm(kva[:, :R], params["kvn"][layer], eps)
            k_pe = rope_interleaved(kva[:, R:], pos_all, theta)
            rows_at.append(jnp.concatenate([c, k_pe], axis=1)[positions])
            k, v = expand_latent(c, k_pe, lp["wkvb"], dn)

            def rows(xb, w_in=w_in, wo=wo, k=k, v=v, ln1=params["ln1"][layer]):
                xr, pr = xb
                q = (rms_norm(xr, ln1, eps) @ w_in[:, :a]).reshape(
                    -1, H, dn + dr)
                q = jnp.concatenate([q[..., :dn], rope_interleaved(
                    q[..., dn:], pr, theta)], axis=-1)
                return xr + attention(q, k, v, pr).reshape(
                    xr.shape[0], -1) @ wo

            h = jax.lax.map(rows, (x.reshape(T // block, block, -1),
                                   pos_all.reshape(T // block, block))
                            ).reshape(T, -1)
            u = rms_norm(h, params["ln2"][layer], eps)
            if layer < n_dense:
                x = h + swiglu(u, lp["w_gu"], lp["w_down"])
                continue
            m = layer - n_dense
            E = params["router_w"][m].shape[-1]
            given = (jnp.zeros((T,), bool), jnp.zeros((T, E), bool))
            if forced is not None:
                given = (given[0].at[forced[0]].set(True),
                         given[1].at[forced[0]].set(forced[1][m]))

            def experts(ub, m=m, lp=lp):
                return moe_layer(
                    ub[0], params["router_w"][m], params["router_b"][m],
                    params["e_gu"][m], params["e_down"][m],
                    (lp["w_gu"], lp["w_down"]), cfg["num_experts_per_tok"],
                    cfg["routed_scaling_factor"], forced=ub[1:])

            y, chosen = jax.lax.map(experts, (
                u.reshape(T // wide, wide, -1),
                given[0].reshape(T // wide, wide),
                given[1].reshape(T // wide, wide, E)))
            chosen_at.append(chosen.reshape(T, E)[positions])
            x = h + y.reshape(T, -1)
        logits = rms_norm(x[positions], params["norm_f"], eps) @ params[
            "head"].astype(f32)
        return logits, chosen_at, rows_at
