"""Plain reference for ``minicpm_sala_9b``: MiniCPM-SALA's forward pass over one
whole sequence in straightforward float32 ``jax.numpy`` at the highest matmul
precision.  No kernel, no cache, no paging, no chunk-wise scan, no batching;
the parameters are an ARGUMENT (the served pytree, upcast here).  Rows are
processed in blocks of ``block`` queries so that 38912 tokens fit beside the
served weights; nothing else is blocked.

Source: https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json
(``model_type`` ``minicpm_sala``); the two mixers are the published mechanisms
the config names.  The equations:

    x_0 = scale_emb * E[tok]
    for layer l, with r = scale_depth / sqrt(mup_denominator)   (= 1.4/sqrt(32),
                                   the PUBLISHED depth, whatever depth is run):
        h  = x + r * W_o( Mixer_l(RMSNorm(x)) * sigmoid(W_g RMSNorm(x)) )
        x' = h + r * W_down( silu(W_gate u) * W_up u ),   u = RMSNorm(h)
    logits = W_head RMSNorm(x_L) / (hidden_size / dim_model_base)
    RMSNorm(x) = x / sqrt(mean(x^2) + rms_norm_eps) * weight,  eps 1e-6

``lightning-attn`` (Lightning Attention-2, arXiv:2401.04658), per head h of
``lightning_nh`` = 32 with q, k, v in R^128: RMSNorm on q and k per head
(``qk_norm``); rotary, theta ``rope_theta``, rotate-half, on q and k
(``lightning_use_rope``);

    S_t = lambda_h S_{t-1} + k_t^T v_t      (S in R^{128x128}, S_{-1} = 0)
    o_t = q_t S_t / sqrt(128)               (``lightning_scale`` 1/sqrt(d))

RMSNorm on o per head (``use_output_norm``), the output gate
(``use_output_gate``), W_o.  It is written here as exactly that recurrence.

``minicpm4`` (InfLLM-V2: MiniCPM4 report arXiv:2506.07900, arXiv:2509.24663):
32 query heads in 2 groups of 16 over 2 KV heads of 128; RMSNorm on q and k per
head (``qk_norm``); no rotary (``attn_use_rope`` false); output gate
(``attn_use_output_gate``); W_o.  A query at position t sees n = t + 1 keys:

* n <= dense_len: causal softmax attention over all of them;
* otherwise: pooled keys Kbar_j = mean(k[s j .. s j + l - 1]) over the kernels
  that lie wholly inside the visible range; per query head
  p = softmax_j(q . Kbar_j / sqrt(128)); a block's score is the max of p over
  the kernels that overlap it, summed over the 16 query heads of its group (a
  group shares one selection); blocks < init_blocks and the blocks that cover
  the last ``window_size`` tokens are always selected, the ``topk`` best of the
  other visible blocks are added (ties: the lower block wins); every query
  head attends causally, with ONE softmax, over exactly the tokens of its
  group's selected blocks.  The selection has no parameters.

Written here as mask-and-softmax over all keys.

Departures and readings, each the served model's too:
* the config has ONE ``qk_norm`` key: read as holding for both mixers;
* ``mup_denominator`` (32) is read as the depth in r: it equals the published
  ``num_hidden_layers`` and stays when the depth is cut;
* lightning decay rates are not in the config: ``lambda_h = exp(-s_h)`` with
  the ALiBi-style slopes s_h = 2^(-8 (h+1) / H) the Lightning Attention family
  builds, the same in every layer (ASSUMED; ``assumed.lightning_slopes``);
* the selection's sizes are not in the catalog row's config: ``kernel_size``
  32, ``kernel_stride`` 16, ``block_size`` 64, ``topk`` 64, ``init_blocks`` 1,
  ``window_size`` 2048, ``dense_len`` 8192, the ``sparse_config`` of the
  MiniCPM4 family the mixer is named after (ASSUMED; the row's
  ``described_as`` confirms "block top-64");
* weights are seeded random, in the served dtype, upcast to float32 here.
"""
import math

import numpy as np

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def slopes(n_head):
    return np.asarray([2.0 ** (-8.0 * (h + 1) / n_head)
                       for h in range(n_head)], np.float32)


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, positions, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def lightning_recurrence(q, k, v, state, n_valid=None):
    """The plain recurrence: ``q, k, v [T, H, d]``, ``state [H, d, d]`` before
    row 0; rows at or past ``n_valid`` leave the state alone.  Returns
    ``(o [T, H, d], state')``."""
    with jax.default_matmul_precision("highest"):
        T, H, d = q.shape
        lam = jnp.exp(-jnp.asarray(slopes(H)))[:, None, None]
        n_valid = T if n_valid is None else n_valid

        def step(S, xs):
            qt, kt, vt, t = xs
            S1 = lam * S + kt[:, :, None] * vt[:, None, :]
            S1 = jnp.where(t < n_valid, S1, S)
            return S1, jnp.einsum("hd,hde->he", qt, S1) / math.sqrt(d)

        state, o = jax.lax.scan(step, state, (q, k, v, jnp.arange(T)))
        return o, state


def _kernel_tables(T, sp):
    """Static tables of the pooling geometry over ``T`` tokens: the token ids
    of every kernel ``[NK, l]``, and for every block the kernels that overlap
    it ``[NB, W]`` with their validity."""
    l, s, B = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    NK = (T - l) // s + 1
    NB = -(-T // B)
    tok = np.arange(NK)[:, None] * s + np.arange(l)[None, :]
    over = [[j for j in range(NK) if j * s <= b * B + B - 1
             and j * s + l - 1 >= b * B] for b in range(NB)] if NK * NB < 2e5 \
        else None
    if over is None:       # the same sets, from the bounds (long sequences)
        lo = np.maximum(-(-(np.arange(NB) * B - l + 1) // s), 0)
        hi = np.minimum((np.arange(NB) * B + B - 1) // s, NK - 1)
        over = [list(range(a, b + 1)) for a, b in zip(lo, hi)]
    W = max(1, max(len(o) for o in over))
    idx = np.zeros((NB, W), np.int32)
    ok = np.zeros((NB, W), bool)
    for b, o in enumerate(over):
        idx[b, :len(o)] = o
        ok[b, :len(o)] = True
    return tok, idx, ok


def select(q, k, positions, sp):
    """The selection of the queries ``q [Q, Hq, d]`` at ``positions [Q]``
    against ALL keys ``k [T, Hkv, d]``: ``[Q, Hkv, NB]`` bool (every visible
    block where ``n <= dense_len``)."""
    with jax.default_matmul_precision("highest"):
        Q, Hq, d = q.shape
        T, Hkv = k.shape[:2]
        g = Hq // Hkv
        l, s, B = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
        tok, idx, ok = _kernel_tables(T, sp)
        NB = idx.shape[0]
        n = positions + 1
        kbar = k[tok].mean(axis=1)                               # [NK,Hkv,d]
        sc = jnp.einsum("qkgd,jkd->qkgj", q.reshape(Q, Hkv, g, d),
                        kbar) / math.sqrt(d)
        whole = (jnp.arange(tok.shape[0])[None, :] * s + l - 1
                 < n[:, None])[:, None, None, :]                 # inside range
        p = jnp.where(whole, jax.nn.softmax(
            jnp.where(whole, sc, NEG_INF), axis=-1), 0.0)
        score = jnp.where(ok, p[..., idx], 0.0).max(axis=-1).sum(axis=2)
        blocks = jnp.arange(NB)[None, :]
        cur = (positions // B)[:, None]
        visible = blocks <= cur
        forced = (blocks < sp["init_blocks"]) | (
            blocks >= (jnp.maximum(n - sp["window_size"], 0) // B)[:, None])
        cand = (visible & ~forced)[:, None, :]
        # rank of every block among the candidates, best first, lower id
        # first on ties
        order = jnp.argsort(jnp.where(cand, -score, jnp.inf), axis=-1,
                            stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        sparse = (visible & forced)[:, None, :] | (cand & (rank < sp["topk"]))
        dense = (n <= sp["dense_len"])[:, None, None]
        return jnp.where(dense, visible[:, None, :], sparse)


def sparse_attention(q, k, v, positions, blocks, block_size):
    """Mask-and-softmax over all keys: ``q [Q, Hq, d]`` at ``positions``,
    ``k, v [T, Hkv, d]``, ``blocks [Q, Hkv, NB]`` the selected blocks; a key is
    read iff it is causal and its block is selected.  One softmax."""
    with jax.default_matmul_precision("highest"):
        Q, Hq, d = q.shape
        T, Hkv = k.shape[:2]
        g = Hq // Hkv
        key = jnp.arange(T)
        ok = (key[None, None, :] <= positions[:, None, None]) & jnp.take(
            blocks, key // block_size, axis=-1)                  # [Q,Hkv,T]
        s = jnp.einsum("qkgd,tkd->qkgt", q.reshape(Q, Hkv, g, d),
                       k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(ok[:, :, None, :], s, NEG_INF), axis=-1)
        p = jnp.where(ok[:, :, None, :], p, 0.0)
        return jnp.einsum("qkgt,tkd->qkgd", p, v).reshape(Q, Hq, d)


def _layer_io(cfg, kind):
    Hq, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    if kind == "minicpm4":
        return (Hq, Hkv, Hkv), Dh
    return (cfg["lightning_nh"],) * 3, cfg["lightning_head_dim"]


def forward(params, cfg, tokens, positions, block=128):
    """Next-token logits ``[P, V]`` at ``positions [P]`` of ``tokens [T]``
    (``T`` a multiple of ``block``; a pad tail is causally invisible), and
    each sparse layer's selected blocks at those positions ``[P, Hkv, NB]``."""
    with jax.default_matmul_precision("highest"):
        f32 = jnp.float32
        T = tokens.shape[0]
        sp = cfg["sparse_config"]
        eps = cfg["rms_norm_eps"]
        r = cfg["scale_depth"] / math.sqrt(cfg["mup_denominator"])
        F = cfg["intermediate_size"]
        nblk = T // block
        pos_all = jnp.arange(T, dtype=jnp.int32)
        x = params["embed"][tokens].astype(f32) * cfg["scale_emb"]
        si = li = 0
        selected = []
        for layer, kind in enumerate(cfg["mixer_types"]):
            lp = {n: w.astype(f32) for n, w in params["layers"][layer].items()}
            heads, hd = _layer_io(cfg, kind)
            cuts = np.cumsum([h * hd for h in heads])
            ln1, ln2 = params["ln1"][layer], params["ln2"][layer]

            def project(xb, lp=lp, heads=heads, hd=hd, cuts=cuts, ln1=ln1):
                y = rms_norm(xb, ln1, eps) @ lp["w_in"]
                q, k, v = (y[:, lo:hi].reshape(-1, h, hd) for lo, hi, h in
                           zip([0, cuts[0], cuts[1]], cuts, heads))
                return q, k, v, y[:, cuts[2]:]

            def finish(xb, o, gate, lp=lp, ln2=ln2):
                h = xb + r * ((o.reshape(o.shape[0], -1)
                               * jax.nn.sigmoid(gate)) @ lp["wo"])
                gu = rms_norm(h, ln2, eps) @ lp["w_gu"]
                return h + r * ((jax.nn.silu(gu[:, :F]) * gu[:, F:])
                                @ lp["w_down"])

            xs = (x.reshape(nblk, block, -1), pos_all.reshape(nblk, block))
            if kind == "minicpm4":
                qn, kn = params["sparse"]["qn"][si], params["sparse"]["kn"][si]
                k_all, v_all = jax.lax.map(
                    lambda a: project(a)[1:3], xs[0])
                k_all = rms_norm(k_all.reshape(T, heads[1], hd), kn, eps)
                v_all = v_all.reshape(T, heads[2], hd)

                def sparse_block(_, a, k_all=k_all, v_all=v_all, qn=qn):
                    xb, pb = a
                    q, _, _, gate = project(xb)
                    q = rms_norm(q, qn, eps)
                    o = sparse_attention(q, k_all, v_all, pb,
                                         select(q, k_all, pb, sp),
                                         sp["block_size"])
                    return None, finish(xb, o, gate)

                # the selected sets at the asked positions, from the same q
                qp = rms_norm(project(x[positions])[0], qn, eps)
                selected.append(select(qp, k_all, positions, sp))
                _, x = jax.lax.scan(sparse_block, None, xs)
                si += 1
            else:
                qn, kn, on = (params["lin"][n][li] for n in ("qn", "kn", "on"))

                def lin_block(S, a, qn=qn, kn=kn, on=on):
                    xb, pb = a
                    q, k, v, gate = project(xb)
                    q = rope(rms_norm(q, qn, eps), pb, cfg["rope_theta"])
                    k = rope(rms_norm(k, kn, eps), pb, cfg["rope_theta"])
                    o, S = lightning_recurrence(q, k, v, S)
                    return S, finish(xb, rms_norm(o, on, eps), gate)

                _, x = jax.lax.scan(
                    lin_block, jnp.zeros((heads[0], hd, hd), f32), xs)
                li += 1
            x = x.reshape(T, -1)
        logits = (rms_norm(x[positions], params["norm_f"], eps)
                  @ params["head"].astype(f32)) / (
                      cfg["hidden_size"] / cfg["dim_model_base"])
        return logits, selected
