"""Plain reference for ``glm5_744b_a40b``: the forward pass of zai-org's GLM-5
(``model_type`` ``glm_moe_dsa``) over one whole sequence in straightforward
float32 ``jax.numpy`` at the highest matmul precision.  No kernel, no cache, no
paging, no absorbed attention, no threshold search, no sorting or grouping of
experts, no batching; the parameters are an ARGUMENT (the served pytree, upcast
here, one expert at a time).  Query rows are processed in blocks of ``block``.

Source: https://huggingface.co/zai-org/GLM-5/blob/main/config.json
What its keys fix:

    hidden 6144, 78 layers, vocabulary 154880 (untied), RMSNorm eps 1e-5
    x_0 = E[tok];  h = x + Attn(norm1(x));  x' = h + FFN(norm2(h))
    logits = W_head RMSNorm(x_L)

MLA with a compressed query (``q_lora_rank`` 2048, ``kv_lora_rank`` 512; 64
heads; ``qk_nope_head_dim`` 192, ``qk_rope_head_dim`` 64, ``v_head_dim``
256), a = norm1(x):

    c_q = RMSNorm(a W_qa)   (q_a_layernorm)       -> [2048]
    q = c_q W_qb            -> [64, 256] = [q_nope 192 | q_pe 64] a head
    a W_kva                 -> [c' 512 | k_pe 64];  c = RMSNorm(c')
    c W_kvb                 -> [64, k_nope 192 | v 256]
    rotary on q_pe and on the ONE shared k_pe: theta 1e6, on interleaved pairs
      (``rope_interleave``), ``rope_type`` default: no scaling, no mscale
    k_h = [k_nope_h | k_pe];  scores q_h . k_h / sqrt(256)

DeepSeek sparse attention (``index_n_heads`` 32, ``index_head_dim`` 128,
``index_topk`` 2048, ``indexer_rope_interleave`` true):

    q^I = c_q W_qb^I        -> [32, 128]
    k^I = LayerNorm(a W_k^I) (weight and bias, eps 1e-6) -> [128], ONE a token
    rotary on the FIRST 64 lanes of each q^I_j and of k^I (interleaved pairs,
      theta and positions as above); the other 64 lanes are not rotated
    w = a W_w               -> [32]
    I_{t,s} = 32^-1/2 128^-1/2 sum_j w_{t,j} relu(q^I_{t,j} . k^I_s),  s <= t
    S_t = the min(t + 1, 2048) positions s <= t of largest I_{t,s}; on a tie
      the lower position wins; no position is selected by rule
    softmax over S_t only;  o = concat_h(P_h v_h) W_o        (16384 -> 6144)

Dense layers (``first_k_dense_replace``): SwiGLU 6144 -> 12288 -> 6144.
Expert layers: u = norm2(h); s = sigmoid(u W_g) (256 scores, float32); choose
the top 8 of s + b (``e_score_correction_bias``; ``n_group`` = ``topk_group`` =
1; on a tie the lower expert wins); w = s[chosen] / (sum + 1e-20) x 2.5;

    FFN(u) = sum_i w_i E_i(u) + S(u);   E_i, S: SwiGLU 6144 -> 2048 -> 6144

Dropless.  ``experts_held = [lo, hi)``: the terms of the other experts are
left out (they are their holders'), the router still scores all 256 and the
shared expert is added.  ``vocab_size`` is the held slice's: ``E`` and
``W_head`` have that many rows / columns.

The served pytree's layout (``paddle_tpu/models/deepseek_v3.py:params``):
``w_in`` = [W_qa | W_kva | W_k^I | W_w] column-wise; ``w_qb`` = [W_qb |
W_qb^I]; ``qn`` the query latent's norm; ``ikn_w`` / ``ikn_b`` the indexer
key's LayerNorm; ``wkvb [64, 448, 512]`` holds head h's slice of W_kvb
transposed (rows 0..191 give k_nope, 192..447 give v); ``w_gu`` = [gate | up]
(the dense block's, or S's); ``e_gu`` / ``e_down`` the HELD routed experts of
the expert layers in order; ``router_w``, ``router_b`` over all 256.

What the config does not fix, and how it is read here (the configuration
file's ``assumed`` repeats each): the indexer's form beyond its three sizes —
the query from ``c_q`` and not from ``a``, LayerNorm WITH bias on the key,
rotary on the first 64 lanes, the head weights from ``a`` with the two scales
``32^-1/2`` and ``128^-1/2``, ReLU, no always-selected set, ties to the lower
position — follows DeepSeek-V3.2-Exp's released modelling code, which
``glm_moe_dsa`` carries.  Departures: the released kernels rotate ``q^I`` and
``k^I`` by a Hadamard matrix and score in FP8; the rotation is orthogonal and
changes no dot product, and this configuration is served in bf16, so neither is
here.  The multi-token-prediction layer (``num_nextn_predict_layers``) is no
part of the next-token forward and is left out.

``forced`` lets a caller GIVE the experts of some rows and ``selected`` the
attended SETS of some rows: top-8 and top-2048 are discrete choices, and a
served row whose k-th and (k+1)-th scores lie closer than bfloat16 rounding
takes another expert or token than this float32 pass; its logits are then
comparable only over the same choice, and the choice itself is compared apart.
"""
import math

import jax
import jax.numpy as jnp


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def layer_norm(x, weight, bias, eps=1e-6):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


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


def rope_first(x, positions, theta, n):
    """Rotary on the first ``n`` lanes of the last axis only."""
    return jnp.concatenate([rope_interleaved(x[..., :n], positions, theta),
                            x[..., n:]], axis=-1)


def index_scores(q, w, k, positions):
    """``I [R, T]``: ``q [R, Hi, Di]`` at absolute ``positions [R]``, ``w [R,
    Hi]``, ``k [T, Di]`` (key ``s`` at position ``s``); ``-inf`` where ``s >
    t``."""
    with jax.default_matmul_precision("highest"):
        dots = jnp.einsum("rhd,td->rht", q, k)
        s = (jax.nn.relu(dots) * w[:, :, None]).sum(axis=1) / math.sqrt(
            q.shape[1] * q.shape[2])
    ok = jnp.arange(k.shape[0])[None, :] <= positions[:, None]
    return jnp.where(ok, s, -jnp.inf)


def select(scores, positions, top_k):
    """``S [R, T]`` bool: the ``min(t + 1, top_k)`` visible positions of
    largest score, by a stable sort (on a tie the lower position is first)."""
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return rank < jnp.minimum(positions + 1, top_k)[:, None]


def attention(q, k, v, sets):
    """Softmax attention over each row's own set: ``q [R, H, dk]``, ``k [T, H,
    dk]``, ``v [T, H, dv]``, ``sets [R, T]`` bool -> ``[R, H, dv]``."""
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("rhd,thd->rht", q, k) / math.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(sets[:, None, :], s, -1e30), axis=-1)
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
    router's own choice over ALL experts."""
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


def forward(params, cfg, tokens, positions, block=128, forced=None,
            selected=None):
    """Next-token logits ``[P, V]`` at ``positions [P]`` of ``tokens [T]``
    (``T`` a multiple of ``block``; a pad tail is causally invisible); each
    expert layer's chosen experts at those positions ``[P, E]`` bool; each
    layer's latent rows there ``[P, 512 + 64]`` = ``[c | rotated k_pe]``; and
    each layer's indexer there, ``{"k": [P, 128]`` rotated ``k^I`` (what
    ``index_k`` would keep), ``"scores": [P, T]`` float32 (``-inf`` past a
    row's own position), ``"sets": [P, T]`` bool the indexer's OWN
    selection``}``.
    ``forced = (rows [F] int32, [sets [F, E] bool per expert layer])``: the
    rows at those positions are computed over the given experts;
    ``selected = (rows [F] int32, [sets [F, T] bool per layer])``: the rows at
    those positions attend to the given sets.  What is returned is always
    the router's and the indexer's own choice."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        T = tokens.shape[0]
        H, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
        dn, dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
        R, Rq = cfg["kv_lora_rank"], cfg["q_lora_rank"]
        Hi, Di = cfg["index_n_heads"], cfg["index_head_dim"]
        top = cfg["index_topk"]
        theta = float(cfg["rope_theta"])
        n_dense = cfg["first_k_dense_replace"]
        held = tuple(cfg.get("experts_held", (0, cfg["n_routed_experts"])))
        pos_all = jnp.arange(T, dtype=jnp.int32)
        n_q = H * (dn + dr)
        at = Rq + R + dr                       # where [W_k^I | W_w] start
        wide = block * math.gcd(T // block, 16)
        x = params["embed"][tokens].astype(f32)
        chosen_at, rows_at, index_at = [], [], []
        for layer, lp in enumerate(params["layers"]):
            w_in, wo = lp["w_in"].astype(f32), lp["wo"].astype(f32)
            w_qb = lp["w_qb"].astype(f32)
            a = rms_norm(x, params["ln1"][layer], eps)
            kva = a @ w_in[:, Rq:at]
            c = rms_norm(kva[:, :R], params["kvn"][layer], eps)
            k_pe = rope_interleaved(kva[:, R:], pos_all, theta)
            rows_at.append(jnp.concatenate([c, k_pe], axis=1)[positions])
            k, v = expand_latent(c, k_pe, lp["wkvb"], dn)
            k_idx = rope_first(layer_norm(
                a @ w_in[:, at:at + Di], params["ikn_w"][layer],
                params["ikn_b"][layer]), pos_all, theta, dr)
            given = (jnp.zeros((T,), bool), jnp.zeros((T, T), bool))
            if selected is not None:
                given = (given[0].at[selected[0]].set(True),
                         given[1].at[selected[0]].set(selected[1][layer]))

            def rows(xb, w_in=w_in, w_qb=w_qb, wo=wo, k=k, v=v, k_idx=k_idx,
                     layer=layer):
                xr, pr, use, sets = xb
                ar = rms_norm(xr, params["ln1"][layer], eps)
                c_q = rms_norm(ar @ w_in[:, :Rq], params["qn"][layer], eps)
                qb = c_q @ w_qb
                q = qb[:, :n_q].reshape(-1, H, dn + dr)
                q = jnp.concatenate([q[..., :dn], rope_interleaved(
                    q[..., dn:], pr, theta)], axis=-1)
                q_idx = rope_first(qb[:, n_q:].reshape(-1, Hi, Di), pr, theta,
                                   dr)
                score = index_scores(q_idx, ar @ w_in[:, at + Di:at + Di + Hi],
                                     k_idx, pr)
                own = select(score, pr, top)
                o = attention(q, k, v, jnp.where(use[:, None], sets, own))
                return xr + o.reshape(xr.shape[0], -1) @ wo, score, own

            h, score, own = jax.lax.map(rows, (
                x.reshape(T // block, block, -1),
                pos_all.reshape(T // block, block),
                given[0].reshape(T // block, block),
                given[1].reshape(T // block, block, T)))
            h = h.reshape(T, -1)
            index_at.append({"k": k_idx[positions],
                             "scores": score.reshape(T, T)[positions],
                             "sets": own.reshape(T, T)[positions]})
            u = rms_norm(h, params["ln2"][layer], eps)
            if layer < n_dense:
                x = h + swiglu(u, lp["w_gu"], lp["w_down"])
                continue
            m = layer - n_dense
            E = params["router_w"][m].shape[-1]
            forced_m = (jnp.zeros((T,), bool), jnp.zeros((T, E), bool))
            if forced is not None:
                forced_m = (forced_m[0].at[forced[0]].set(True),
                            forced_m[1].at[forced[0]].set(forced[1][m]))

            def experts(ub, m=m, lp=lp):
                return moe_layer(
                    ub[0], params["router_w"][m], params["router_b"][m],
                    params["e_gu"][m], params["e_down"][m],
                    (lp["w_gu"], lp["w_down"]), cfg["num_experts_per_tok"],
                    cfg["routed_scaling_factor"], held=held, forced=ub[1:])

            y, chosen = jax.lax.map(experts, (
                u.reshape(T // wide, wide, -1),
                forced_m[0].reshape(T // wide, wide),
                forced_m[1].reshape(T // wide, wide, E)))
            chosen_at.append(chosen.reshape(T, E)[positions])
            x = h + y.reshape(T, -1)
        logits = rms_norm(x[positions], params["norm_f"], eps) @ params[
            "head"].astype(f32)
        return logits, chosen_at, rows_at, index_at
