"""Plain reference for ``sdar_30b_a3b``: the forward pass of JetLM's
SDAR-30B-A3B-Chat (``model_type`` ``sdar_moe``) over one whole sequence under
the BLOCK MASK, and its generation by diffusion over blocks, in straightforward
float32 ``jax.numpy`` at the highest matmul precision.  No kernel, no cache,
no paging, no sorting or grouping of experts, no batching; the parameters are
an ARGUMENT (the served pytree, upcast here, one expert at a time).  Query rows
are processed in blocks of ``rows``, the expert loop in blocks of rows and the
head in blocks of the vocabulary, so that a context of 10752 tokens fits beside
the served weights.

Source: https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json
(with the release's ``modeling_sdar_moe.py`` and ``generate.py``).  The keys
fix every equation of the forward:

    hidden 2048, 48 layers, vocabulary 151936 (untied), RMSNorm eps 1e-6, no bias
    x_0 = E[tok];  h = x + Attn(norm1(x));  x' = h + MoE(norm2(h))
    logits = W_head RMSNorm(x_L)

Attention (32 query heads, 4 KV heads, ``head_dim`` 128; query head i reads KV
head i // 8), u = norm1(x):

    q = u W_q [32, 128], k = u W_k [4, 128], v = u W_v [4, 128]
    q <- RMSNorm_q(q), k <- RMSNorm_k(k): over the 128 lanes of each head, one
        weight [128] each a layer (the family's per-head QK norm, BEFORE rotary)
    rotate-half rotary on the whole head of q and k, angle position * inv_freq_i,
        inv_freq_i = 1e6 ** (-2i / 128)  (``rope_theta`` 1e6, no scaling)
    scores q_h . k_(h // 8) / sqrt(128), softmax in float32 over the keys the
        BLOCK MASK lets the query see: with B = ``block_length``, position i
        sees position j iff j // B <= i // B (blocks counted from position 0):
        causal between blocks, bidirectional inside one, for the prompt as for
        generated text
    o = concat_h(P_h v_(h // 8)) W_o                 (4096 -> 2048)

Experts (``decoder_sparse_step`` 1, ``mlp_only_layers`` []: every layer routes;
``intermediate_size`` 6144 is used by no layer), u = norm2(h): p = softmax(u
W_g) over all 128 in float32; the 8 largest are chosen (on a tie the lower
expert wins); w = p[chosen] / sum (``norm_topk_prob``); no bias, no shared
expert;

    MoE(u) = sum_{e in top8} w_e (silu(u W_gate,e) * u W_up,e) W_down,e

Dropless: every chosen (token, expert) pair is computed.  Written here as a
loop over ALL experts with a mask.

**Logits are unshifted**: the row of position i predicts the id AT position i
(a masked position predicts itself), not the id after it.

**Generation** (``block_diffusion_generate``, the release's function of that
name): the sequence is ceil((P + max_new_tokens) / B) blocks; the first P // B
are the prompt's whole blocks; every further block starts as the prompt's
leftover ids (if any) followed by the mask id.  For a block, repeat, at most
``denoising_steps + 1`` times (the release's loop): if it holds no mask id, or
has had its ``denoising_steps`` denoising forwards, ONE forward over it fixes
its K/V (here: nothing to keep, the whole sequence is recomputed every forward)
and the next block starts; else a forward gives every masked position its
candidate (argmax: greedy) and its confidence c_i = softmax(logits_i)[candidate]
in float32, and unmasks a set U of the masked positions: with n_t the count of
denoising step t (B spread over ``denoising_steps`` forwards, the remainder to
the first ones), H = {i masked: c_i > threshold}; U = H if |H| >= n_t, else the
n_t masked positions of highest confidence (ties to the lower position; all
that are left if fewer).  Unmasked ids never change again.  Every id is a
candidate, the mask id too: a position "unmasked" to it stays masked, and the
block closes holding it when its forwards are spent.  ``threshold >= 1`` is the
release's ``low_confidence_static``; its default is ``low_confidence_dynamic``.

The served pytree's layout (``paddle_tpu/models/sdar.py:params``): ``w_qkv`` =
[W_q | W_k | W_v] column-wise; ``e_gu [L, 128, 2048, 1536]`` = [gate | up]
column-wise and ``e_down [L, 128, 768, 2048]``; ``router_w [L, 2048, 128]``;
``q_norm``, ``k_norm`` ``[L, 128]``.

What the config does not fix (``assumed`` in the configuration's file):
``block_length`` 4, ``denoising_steps`` 4, the rule and its threshold 0.9 (the
release's generation defaults), ``mask_token_id``; that the QK norm is a head's
and comes before rotary, that nothing has a bias, that logits are unshifted and
that the router's softmax is float32 before top-k are the release's modelling
code.
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


def rope(x, positions, theta):
    """Rotate-half rotary: pairs ``(x[i], x[i + d / 2])`` of the last axis of
    ``x [T, H, d]`` at ``positions [T]``."""
    d = x.shape[-1]
    inv_freq = (float(theta) ** (-2.0 * np.arange(d // 2, dtype=np.float64)
                                 / d)).astype(np.float32)
    ang = positions.astype(jnp.float32)[:, None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v, positions, block_length):
    """Softmax attention under the block mask: ``q [R, Hq, d]`` at absolute
    ``positions [R]`` against ``k``, ``v`` ``[T, Hkv, d]`` (key ``s`` at
    position ``s``; query head ``i`` reads KV head ``i // (Hq / Hkv)``); key
    ``s`` is seen by the query at ``t`` iff ``s // B <= t // B``."""
    with jax.default_matmul_precision("highest"):
        R, Hq, d = q.shape
        g = Hq // k.shape[1]
        qg = q.reshape(R, k.shape[1], g, d)
        s = jnp.einsum("rhgd,thd->rhgt", qg, k) / math.sqrt(d)
        ok = (jnp.arange(k.shape[0])[None, :] // block_length
              <= positions[:, None] // block_length)
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


def moe_layer(u, router_w, e_gu, e_down, top_k, layer, forced=None):
    """The expert block on normalised rows ``u [T, D]``: every expert of the
    served stacks ``[L, E, ..]`` (read at ``[layer, i]``) applied to every row
    and masked.  ``forced = (rows [T] bool, sets [T, E] bool)``: those rows
    are computed over the GIVEN experts (weights from this router's own
    probabilities).  Returns ``(y [T, D], chosen [T, E])``, ``chosen`` always
    the router's own choice."""
    with jax.default_matmul_precision("highest"):
        chosen, w = route(u, router_w, top_k)
        if forced is not None:
            w = weights(jnp.where(forced[0][:, None], forced[1], chosen),
                        scores(u, router_w))

        def one(y, i):
            y_i = swiglu(u, e_gu[layer, i], e_down[layer, i])
            return y + jax.lax.dynamic_index_in_dim(
                w, i, axis=1, keepdims=True) * y_i, None

        y, _ = jax.lax.scan(one, jnp.zeros_like(u),
                            jnp.arange(e_gu.shape[1]))
        return y, chosen


def forward(params, cfg, tokens, positions, rows=128, forced=None):
    """Logits ``[P, V]`` at ``positions [P]`` of ``tokens [T]`` under the block
    mask (``T`` a multiple of ``rows`` and of ``block_length``; a pad tail of
    whole blocks is invisible to the blocks before it), row ``i`` predicting
    the id AT ``positions[i]``; each layer's chosen experts at those positions
    ``[P, E]`` bool; and each layer's ``(k, v)`` rows there ``[P, Hkv * d]``
    (what a cache keeps of the token once its block is whole).  ``forced =
    (rows [F] int32, [sets [F, E] bool per layer])``: the rows at those
    positions are computed over the given experts (see :func:`moe_layer`);
    what is returned is the router's own choice."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        T = tokens.shape[0]
        H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
        eps, E, B = (cfg["rms_norm_eps"], cfg["num_experts"],
                     cfg["block_length"])
        theta = cfg["rope_theta"]
        pos_all = jnp.arange(T, dtype=jnp.int32)
        wide = rows * math.gcd(T // rows, 16)
        x = params["embed"][tokens].astype(f32)
        chosen_at, rows_at = [], []
        for layer, lp in enumerate(params["layers"]):
            w_qkv, wo = lp["w_qkv"].astype(f32), lp["wo"].astype(f32)
            u = rms_norm(x, params["ln1"][layer], eps)
            k = rope(rms_norm((u @ w_qkv[:, H * d:(H + Hkv) * d]).reshape(
                T, Hkv, d), params["k_norm"][layer], eps), pos_all, theta)
            v = (u @ w_qkv[:, (H + Hkv) * d:]).reshape(T, Hkv, d)
            rows_at.append((k.reshape(T, -1)[positions],
                            v.reshape(T, -1)[positions]))

            def some(xb, w_qkv=w_qkv, wo=wo, k=k, v=v, layer=layer):
                xr, pr = xb
                q = rope(rms_norm(
                    (rms_norm(xr, params["ln1"][layer], eps)
                     @ w_qkv[:, :H * d]).reshape(-1, H, d),
                    params["q_norm"][layer], eps), pr, theta)
                return xr + attention(q, k, v, pr, B).reshape(
                    xr.shape[0], -1) @ wo

            h = jax.lax.map(some, (x.reshape(T // rows, rows, -1),
                                   pos_all.reshape(T // rows, rows))
                            ).reshape(T, -1)
            u = rms_norm(h, params["ln2"][layer], eps)
            given = (jnp.zeros((T,), bool), jnp.zeros((T, E), bool))
            if forced is not None:
                given = (given[0].at[forced[0]].set(True),
                         given[1].at[forced[0]].set(forced[1][layer]))

            def experts(ub, layer=layer):
                return moe_layer(
                    ub[0], params["router_w"][layer], params["e_gu"],
                    params["e_down"], cfg["num_experts_per_tok"], layer,
                    forced=ub[1:])

            y, chosen = jax.lax.map(experts, (
                u.reshape(T // wide, wide, -1),
                given[0].reshape(T // wide, wide),
                given[1].reshape(T // wide, wide, E)))
            chosen_at.append(chosen.reshape(T, E)[positions])
            x = h + y.reshape(T, -1)
        # the head in eight blocks of the vocabulary: its float32 copy whole
        # is 1.2 GB beside the served weights
        xn = rms_norm(x[positions], params["norm_f"], eps)
        head = params["head"]
        step = -(-head.shape[1] // 8)
        logits = jnp.concatenate(
            [xn @ head[:, at:at + step].astype(f32)
             for at in range(0, head.shape[1], step)], axis=1)
        return logits, chosen_at, rows_at


def transfer_counts(block_length, steps):
    """``get_num_transfer_tokens``: positions denoising forward ``t`` unmasks
    at least: the block spread over ``steps`` forwards, the remainder to the
    first ones."""
    return [block_length // steps + (t < block_length % steps)
            for t in range(steps)]


def unmask(ids, logits, t, cfg):
    """One denoising forward's decision over one block, greedy: ``ids [B]``
    (numpy), ``logits [B, V]`` float32, ``t`` the block's denoising forwards so
    far.  Returns ``(ids', U, candidates [B], confidences [B])``, ``U`` the
    sorted positions it unmasks."""
    B, mask = cfg["block_length"], cfg["mask_token_id"]
    logits = np.array(logits, np.float32)
    cand = logits.argmax(axis=-1)
    z = logits - logits.max(axis=-1, keepdims=True)
    conf = np.exp(z[np.arange(B), cand]) / np.exp(z).sum(axis=-1)
    masked = [i for i in range(B) if ids[i] == mask]
    n = transfer_counts(B, cfg["denoising_steps"])[t]
    high = [i for i in masked if conf[i] > cfg["confidence_threshold"]]
    if len(high) >= n:
        U = high
    else:
        U = sorted(sorted(masked, key=lambda i: (-conf[i], i))[:n])
    out = np.array(ids)
    out[U] = cand[U]
    return out, U, cand, conf


def block_diffusion_generate(params, cfg, prompt, max_new_tokens, rows=None,
                             fwd=None):
    """Greedy generation by diffusion over blocks, in plain Python over
    :func:`forward`, recomputing the WHOLE sequence every forward.  Returns
    ``(ids [max_new_tokens], forwards)``; ``forwards`` has one entry a
    forward, in order: ``dict(block=, ids=the block going in [B], logits=[B,
    V], unmasked=[positions in the block], kv=bool)``, ``kv`` the forward that
    found its block whole or out of denoising forwards (it unmasks nothing; a
    cache would keep its K/V)."""
    B, mask = cfg["block_length"], cfg["mask_token_id"]
    prompt = np.asarray(prompt, np.int32)
    P = len(prompt)
    blocks = -(-(P + max_new_tokens) // B)
    rows = rows or B
    T = -(-blocks * B // rows) * rows
    x = np.full((T,), mask, np.int32)
    x[:P] = prompt
    if fwd is None:
        fwd = jax.jit(lambda p, tokens, positions: forward(
            p, cfg, tokens, positions, rows=rows)[0])
    forwards = []
    for b in range(P // B, blocks):
        at = np.arange(b * B, (b + 1) * B)
        for t in range(cfg["denoising_steps"] + 1):
            ids = x[at].copy()
            # what lies behind the block is the mask id: invisible to it
            seen = np.where(np.arange(T) < (b + 1) * B, x, mask)
            logits = np.asarray(fwd(params, jnp.asarray(seen),
                                    jnp.asarray(at, jnp.int32)))
            if t == cfg["denoising_steps"] or not (ids == mask).any():
                forwards.append(dict(block=b, ids=ids, logits=logits,
                                     unmasked=[], kv=True))
                break
            x[at], U, _, _ = unmask(ids, logits, t, cfg)
            forwards.append(dict(block=b, ids=ids, logits=logits, unmasked=U,
                                 kv=False))
    return x[P:P + max_new_tokens].copy(), forwards
