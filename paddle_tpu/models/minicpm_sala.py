"""MiniCPM-SALA (openbmb, ``model_type`` ``minicpm_sala``) as a served
``DecodeModel``: a pre-norm RMSNorm / SwiGLU decoder with µP scalings whose
layers mix TWO kinds of sequence mixer,

* ``minicpm4`` — InfLLM-V2 block-sparse attention (MiniCPM4, arXiv:2506.07900):
  grouped query heads over a few KV heads, QK-norm, no rotary, output gate;
  past ``dense_len`` visible tokens a query reads block 0, the blocks of its
  sliding window and the ``topk`` best of the rest, scored against mean-pooled
  keys.  Its K/V live in the paged pools and its pooled keys in a further
  page-indexed pool (one row per ``kernel_stride`` tokens: the float32 mean of
  that half-kernel's keys as the K pool holds them; a pooled key is the mean
  of two neighbours), so the selection never re-reads K.
* ``lightning-attn`` — Lightning Attention-2 (arXiv:2401.04658): per head a
  decayed outer-product state ``S_t = lambda_h S_{t-1} + k_t^T v_t``,
  ``o_t = q_t S_t / sqrt(d)``, with QK-norm, rotary, output norm and gate.  Its
  state is slot-indexed in the cache (``[L_lin, slots, H, d, d]`` float32),
  taken as zero by a sequence's first chunk and carried from chunk to chunk.

The equations, sources and every assumed size are in the plain reference,
``chipbench/configs/minicpm_sala_9b.reference.py``.  ``cfg`` is the
configuration in the family's own key names (that file's JSON twin).

Precision: weights and the activations between matmuls in the dtype of the
weights (bfloat16 on the chip, float32 in the CPU tests), matmuls accumulate
in float32; norms, softmax, rotary, the selection scores (highest matmul
precision) and the recurrent state in float32.

Weights as a pytree of a few dozen arrays: the four matrices of a layer are
one array each (projections fused column-wise: ``w_in`` = q | k | v | gate,
``w_gu`` = gate | up), per-layer vectors are stacked by kind.  A stack of
matrices is NOT used: the chip's compiler copies a matrix sliced out of a
stack before a dot can read it (134 MB a slice at these widths).
"""
from __future__ import annotations

import functools
import math

import numpy as np

__all__ = ["sala_params", "sala_prefill_chunk", "sala_decode_step",
           "build_decode_model", "cache_layout", "lightning_slopes",
           "select_blocks", "STEP_COUNTERS"]

LIGHTNING_BLOCK = 128       # tokens of one step of the chunk-wise scan
STEP_COUNTERS = ("sparse.selected_tokens", "sparse.visible_tokens",
                 "sparse.dense_rows")


# -- static description -------------------------------------------------------

def _dims(cfg):
    sp = cfg["sparse_config"]
    d = dict(
        D=cfg["hidden_size"], F=cfg["intermediate_size"],
        V=cfg["vocab_size"], Hq=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], Dh=cfg["head_dim"],
        Hl=cfg["lightning_nh"], Dl=cfg["lightning_head_dim"],
        kinds=tuple(cfg["mixer_types"]), eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_theta"]),
        resid=cfg["scale_depth"] / math.sqrt(cfg["mup_denominator"]),
        scale_emb=float(cfg["scale_emb"]),
        logit_div=cfg["hidden_size"] / cfg["dim_model_base"],
        l=sp["kernel_size"], s=sp["kernel_stride"], B=sp["block_size"],
        topk=sp["topk"], init=sp["init_blocks"], window=sp["window_size"],
        dense_len=sp["dense_len"])
    if cfg["lightning_nkv"] != d["Hl"]:
        raise ValueError("lightning_nkv != lightning_nh is not written here")
    if d["l"] % d["s"] or d["B"] % d["s"]:
        raise ValueError("kernel_size and block_size must be multiples of "
                         "kernel_stride")
    d["n_sparse"] = sum(k == "minicpm4" for k in d["kinds"])
    d["n_lin"] = sum(k == "lightning-attn" for k in d["kinds"])
    if d["n_sparse"] + d["n_lin"] != len(d["kinds"]):
        raise ValueError("unknown mixer in %s" % (d["kinds"],))
    # pages a selection may list: the forced blocks and the top-k, or every
    # block of a context that still runs dense
    d["n_listed"] = max(-(-d["dense_len"] // d["B"]),
                        d["init"] + d["topk"] + d["window"] // d["B"] + 1)
    return d


def cache_layout(cfg):
    """What the model keeps in the cache, as ``DecodeModel`` states it: the
    sparse layers' paged K/V, their pooled-key pool, the lightning state."""
    d = _dims(cfg)
    return dict(
        num_layers=d["n_sparse"], num_heads=d["Hkv"], head_dim=d["Dh"],
        page_pools={"kbar": dict(layers=d["n_sparse"], tokens_per_row=d["s"],
                                 width=d["Hkv"] * d["Dh"], dtype="float32")},
        slot_state={"lin": dict(layers=d["n_lin"],
                                shape=(d["Hl"], d["Dl"], d["Dl"]),
                                dtype="float32")})


def lightning_slopes(n_head):
    """Decay rates ``s_h`` (``lambda_h = exp(-s_h)``): the ALiBi-style
    geometric slopes ``2 ** (-8 (h + 1) / H)`` the Lightning Attention family
    builds (assumed: the config has no key for them)."""
    return np.asarray([2.0 ** (-8.0 * (h + 1) / n_head)
                       for h in range(n_head)], np.float32)


def sala_params(cfg, seed, dtype="bfloat16"):
    """Seeded random weights as device arrays of ``dtype`` (vectors float32),
    made in ONE jitted call: normal(0, 1/fan_in) matrices, norm weights
    around one."""
    import jax
    import jax.numpy as jnp

    d = _dims(cfg)
    dt = jnp.dtype(dtype)
    D, F, V = d["D"], d["F"], d["V"]
    n_in = {"minicpm4": 2 * d["Hq"] * d["Dh"] + 2 * d["Hkv"] * d["Dh"],
            "lightning-attn": 4 * d["Hl"] * d["Dl"]}
    n_out = {"minicpm4": d["Hq"] * d["Dh"], "lightning-attn": d["Hl"] * d["Dl"]}

    def make(key):
        keys = iter(jax.random.split(key, 16 + 4 * len(d["kinds"])))

        def w(rows, cols, std=None):
            std = 1.0 / math.sqrt(rows) if std is None else std
            return (jax.random.normal(next(keys), (rows, cols), jnp.float32)
                    * std).astype(dt)

        def vec(*shape):
            return 1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                 jnp.float32)

        return {
            "embed": w(V, D, 1.0 / d["scale_emb"]), "head": w(D, V),
            "norm_f": vec(D),
            "ln1": vec(len(d["kinds"]), D), "ln2": vec(len(d["kinds"]), D),
            "sparse": {"qn": vec(d["n_sparse"], d["Dh"]),
                       "kn": vec(d["n_sparse"], d["Dh"])},
            "lin": {"qn": vec(d["n_lin"], d["Dl"]),
                    "kn": vec(d["n_lin"], d["Dl"]),
                    "on": vec(d["n_lin"], d["Dl"])},
            "layers": [{"w_in": w(D, n_in[k]), "wo": w(n_out[k], D),
                        "w_gu": w(D, 2 * F), "w_down": w(F, D)}
                       for k in d["kinds"]],
        }

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


# -- shared pieces ------------------------------------------------------------

def _rms(x, weight, eps):
    """RMSNorm over the last axis in float32 (returns float32)."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * weight


def _mm(x, w):
    """``x @ w`` with operands in the weights' dtype, float32 accumulation."""
    import jax.numpy as jnp

    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _rope(x, positions, theta):
    """Rotary embedding, rotate-half convention, ``x [..., H, d]`` float32 at
    absolute ``positions [...]``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None, None] * inv   # [..,1,half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _ffn(d, lp, h, ln2, act):
    """``h + r * W_down(silu(W_gate u) * W_up u)``, ``u = RMSNorm(h)``."""
    import jax

    gu = _mm(_rms(h, ln2, d["eps"]), lp["w_gu"])
    gate, up = gu[..., :d["F"]], gu[..., d["F"]:]
    y = _mm(jax.nn.silu(gate) * up, lp["w_down"])
    return (h.astype(y.dtype) + d["resid"] * y).astype(act)


def _mix_and_ffn(d, lp, x, o, gate, ln2, act):
    """The rest of a layer after its mixer: output gate, ``W_o``, residual,
    then the feed-forward block.  ``o [rows, ..]`` the mixer's output."""
    import jax
    import jax.numpy as jnp

    o = o.reshape(x.shape[0], -1).astype(jnp.float32) * jax.nn.sigmoid(gate)
    h = (x.astype(jnp.float32) + d["resid"] * _mm(o, lp["wo"])).astype(act)
    return _ffn(d, lp, h, ln2, act)


def _logits(d, params, x):
    return _mm(_rms(x, params["norm_f"], d["eps"]), params["head"]) / d[
        "logit_div"]


# -- block selection (parameter-free) ----------------------------------------

def select_blocks(d, q, hb, n):
    """InfLLM-V2 selection.  ``q [Bt, R, Hq, Dh]`` float32 query rows (after
    QK-norm), ``hb [Bt, NHB, Hkv, Dh]`` float32 half-kernel key means of each
    batch entry's whole page-table span in cache order, ``n [Bt, R]`` visible
    keys per row (0 = no row).  Returns ``mask [Bt, R, Hkv, NB]`` bool: the
    blocks each KV head's group reads (every visible block where
    ``n <= dense_len``).  The scores (``flash_attention.block_scores``) and
    the pick from them are two steps: a decode step takes its scores from the
    kernel that walks the pooled keys where they lie, and picks the same."""
    from ..parallel.flash_attention import block_scores

    return _pick_blocks(d, block_scores(
        q, hb, n, kernel_size=d["l"], stride=d["s"], block_size=d["B"]), n)


def _pick_blocks(d, score, n):
    """The selection from its scores: ``score [Bt, R, Hkv, NB]`` float32,
    ``n [Bt, R]`` -> ``mask [Bt, R, Hkv, NB]`` bool (block 0, the window's
    blocks and the ``topk`` best of the other visible ones; every visible
    block where ``n <= dense_len``)."""
    import jax.numpy as jnp

    blocks = jnp.arange(score.shape[-1])
    cur = (n - 1) // d["B"]                                      # [Bt, R]
    visible = (blocks[None, None, :] <= cur[..., None]) & (n[..., None] > 0)
    forced = (blocks[None, None, :] < d["init"]) | (
        blocks[None, None, :] >= (jnp.maximum(n - d["window"], 0)
                                  // d["B"])[..., None])
    cand = (visible & ~forced)[:, :, None, :]
    # the topk best candidates, the lower block first on a tie: a block's
    # rank is the number of candidates that beat it (counted, not sorted:
    # a sort of NB scores a row was 5 of a 40 ms prefill chunk, PERF.md)
    sc = jnp.where(cand, score, -1.0)
    beats = (sc[..., None, :] > sc[..., :, None]) | (
        (sc[..., None, :] == sc[..., :, None])
        & (blocks[None, :] < blocks[:, None]))
    picked = cand & (beats.sum(axis=-1) < d["topk"])
    sparse = (visible & forced)[:, :, None, :] | picked
    dense = (n <= d["dense_len"])[:, :, None, None]
    return jnp.where(dense, visible[:, :, None, :], sparse)


def _listed(d, mask, n, tables):
    """A decode step's selection as the kernel takes it.  ``mask [S, Hkv,
    NB]``, ``n [S]``, ``tables [S, MP]`` -> ``(pages [S, Hkv, NS], tokens
    [S, Hkv])``: the selected pages in cache order and how many of their
    tokens are valid (all but the last page are whole)."""
    import jax.numpy as jnp

    NB = mask.shape[-1]
    # compaction without a sort: block b is entry (selected blocks before
    # it) of the list
    at = jnp.cumsum(mask, axis=-1, dtype=jnp.int32) - 1
    entry = jnp.arange(min(d["n_listed"], NB), dtype=jnp.int32)
    blocks = jnp.sum(jnp.where(
        mask[..., None] & (at[..., None] == entry), jnp.arange(
            NB, dtype=jnp.int32)[:, None], 0), axis=-2)           # [S,Hkv,NS]
    pages = jnp.take_along_axis(tables[:, None, :], blocks, axis=-1)
    count = mask.sum(axis=-1).astype(jnp.int32)
    tokens = jnp.where(count > 0, (count - 1) * d["B"]
                       + ((n - 1) % d["B"] + 1)[:, None], 0)
    return pages, tokens


# -- the two mixers, prefill and decode ---------------------------------------

def _split(d, kind, y):
    """q, k, v, gate out of the fused input projection's columns."""
    if kind == "minicpm4":
        a, b = d["Hq"] * d["Dh"], d["Hkv"] * d["Dh"]
        cuts = (a, a + b, a + 2 * b)
        heads = (d["Hq"], d["Hkv"], d["Hkv"])
        hd = d["Dh"]
    else:
        a = d["Hl"] * d["Dl"]
        cuts = (a, 2 * a, 3 * a)
        heads = (d["Hl"],) * 3
        hd = d["Dl"]
    lead = y.shape[:-1]
    q, k, v = (y[..., lo:hi].reshape(lead + (h, hd)) for lo, hi, h in zip(
        (0,) + cuts[:2], cuts, heads))
    return q, k, v, y[..., cuts[2]:]


def _as_stored(x, dtype):
    """Float32 ``x`` rounded to what a pool of ``dtype`` keeps of it, still
    float32 (``reduce_precision``: the compiler may drop a convert pair).
    The half-kernel means are means of the keys AS THE CACHE HOLDS THEM, so
    ``kbar`` can be checked against ``k`` on the served cache itself."""
    import jax
    import jax.numpy as jnp

    info = jnp.finfo(dtype)
    if info.bits >= 32:
        return x
    return jax.lax.reduce_precision(x, info.nexp, info.nmant)


def _hb_rows(d, k, valid_rows):
    """Half-kernel means of a chunk's keys: ``k [C, Hkv*Dh]`` float32 with
    rows past ``valid_rows`` zeroed -> ``[C // s, Hkv*Dh]``."""
    import jax.numpy as jnp

    C = k.shape[0]
    k = jnp.where(jnp.arange(C)[:, None] < valid_rows, k, 0.0)
    return k.reshape(C // d["s"], d["s"], -1).sum(axis=1) / d["s"]


def _lightning_chunk(d, slopes, q, k, v, state, valid):
    """Chunk-wise Lightning recurrence over one sequence's chunk: ``q, k, v
    [C, H, d]`` float32, ``state [H, d, d]`` float32 before row 0, rows at or
    past ``valid`` are padding (no decay, no update).  Returns ``(o [C, H,
    d], state')``: intra-block masked products with decay, inter-block through
    the state, ``LIGHTNING_BLOCK`` rows a scan step."""
    import jax
    import jax.numpy as jnp

    C, H, dh = q.shape
    B = math.gcd(C, LIGHTNING_BLOCK)
    nb = C // B
    scale = 1.0 / math.sqrt(dh)
    s_h = jnp.asarray(slopes)[:, None, None]                     # [H,1,1]
    i = jnp.arange(B)

    def step(S, xs):
        qb, kb, vb, first = xs                                    # [B,H,d]
        cnt = jnp.clip(valid - first, 0, B)
        c = jnp.minimum(i + 1, cnt)                               # tokens in
        live = (i[None, :] <= i[:, None]) & (i[None, :] < cnt)    # [B,B] j<=i
        decay = jnp.where(live[None], jnp.exp(
            -s_h * (c[:, None] - c[None, :])[None].astype(jnp.float32)), 0.0)
        a = jnp.einsum("ihd,jhd->hij", qb, kb) * decay            # [H,B,B]
        o = jnp.einsum("hij,jhd->ihd", a, vb)
        carry = jnp.exp(-s_h[:, :, 0] * c[None, :].astype(jnp.float32))
        o = o + jnp.einsum("ihd,hde->ihe", qb, S) * carry.T[:, :, None]
        tail = jnp.where(i[None, :] < cnt, jnp.exp(
            -s_h[:, :, 0] * (cnt - c)[None, :].astype(jnp.float32)), 0.0)
        S = (S * jnp.exp(-s_h * cnt.astype(jnp.float32))
             + jnp.einsum("jhd,jhe,hj->hde", kb, vb, tail))
        return S, o * scale

    split = lambda x: x.reshape(nb, B, H, dh)  # noqa: E731
    state, o = jax.lax.scan(
        step, state, (split(q), split(k), split(v), jnp.arange(nb) * B))
    return o.reshape(C, H, dh), state


def _lightning_step(slopes, q, k, v, state, live):
    """One token of the recurrence for every slot: ``q, k, v [S, H, d]``
    float32, ``state [S, H, d, d]`` float32.  The update is elementwise
    float32 (exact to rounding); slots that do not decode (``live`` false)
    keep their state.  Returns ``(o [S, H, d], state')``."""
    import jax.numpy as jnp

    s1 = (state * jnp.exp(-slopes)[None, :, None, None]
          + k[..., :, None] * v[..., None, :])
    s1 = jnp.where(live[:, None, None, None], s1, state)
    return jnp.einsum("shd,shde->she", q, s1) / math.sqrt(q.shape[-1]), s1


def sala_prefill_chunk(params, tokens, start, valid, cache, chunk_pages,
                       gather_pages, slot, *, cfg, attn_impl=None,
                       with_selection=False):
    """One chunk of one sequence's prefill (the ``DecodeModel`` contract):
    ``tokens [C]`` at absolute positions ``start ..``, ``valid`` real rows.
    Sparse layers scatter K, V and the half-kernel key means into
    ``chunk_pages`` and attend over ``gather_pages`` through the block mask;
    lightning layers read the state at ``slot`` (ZERO where ``start == 0``:
    the reset of a reused slot), scan the chunk and write it back.  Returns
    ``(last_logits [V], cache')``; with ``with_selection`` also each sparse
    layer's block mask ``[C, Hkv, NB]``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_prefill_attention

    d = _dims(cfg)
    k_pool, v_pool, kbar, lin = (cache[n] for n in ("k", "v", "kbar", "lin"))
    C = tokens.shape[0]
    ps = k_pool.shape[2]
    if ps != d["B"]:
        raise ValueError("page_size %d must equal the selection's block_size "
                         "%d (a block is a page)" % (ps, d["B"]))
    act = params["embed"].dtype
    slopes = lightning_slopes(d["Hl"])
    positions = start + jnp.arange(C, dtype=jnp.int32)
    n_vis = jnp.where(jnp.arange(C) < valid, positions + 1, 0)[None]  # [1,C]
    x = (params["embed"][tokens].astype(jnp.float32) * d["scale_emb"]
         ).astype(act)
    si = li = 0
    masks = []
    for layer, kind in enumerate(d["kinds"]):
        lp = params["layers"][layer]
        u = _rms(x, params["ln1"][layer], d["eps"])
        q, k, v, gate = _split(d, kind, _mm(u, lp["w_in"]))
        if kind == "minicpm4":
            q = _rms(q, params["sparse"]["qn"][si], d["eps"])
            k = _rms(k, params["sparse"]["kn"][si], d["eps"])
            kf = _as_stored(k.reshape(C, -1), k_pool.dtype)
            k_pool = k_pool.at[si, chunk_pages].set(
                kf.reshape(C // ps, ps, -1).astype(k_pool.dtype))
            v_pool = v_pool.at[si, chunk_pages].set(
                v.reshape(C // ps, ps, -1).astype(v_pool.dtype))
            kbar = kbar.at[si, chunk_pages].set(
                _hb_rows(d, kf, valid).reshape(C // ps, ps // d["s"], -1))
            hb = kbar[si, gather_pages].reshape(1, -1, d["Hkv"], d["Dh"])
            mask = select_blocks(d, q[None], hb, n_vis)[0]        # [C,Hkv,NB]
            masks.append(mask)
            o = paged_prefill_attention(
                q, k_pool, v_pool, gather_pages, start, impl=attn_impl,
                layer=si, block_mask=mask.transpose(1, 0, 2))
            si += 1
        else:
            q = _rope(_rms(q, params["lin"]["qn"][li], d["eps"]), positions,
                      d["theta"])
            k = _rope(_rms(k, params["lin"]["kn"][li], d["eps"]), positions,
                      d["theta"])
            s0 = jnp.where(start == 0, 0.0, lin[li, slot])
            o, s1 = _lightning_chunk(d, slopes, q, k, v, s0, valid)
            lin = lin.at[li, slot].set(s1)
            o = _rms(o, params["lin"]["on"][li], d["eps"])
            li += 1
        x = _mix_and_ffn(d, lp, x, o, gate, params["ln2"][layer], act)
    last = jax.lax.dynamic_index_in_dim(x, valid - 1, axis=0, keepdims=False)
    out = (_logits(d, params, last),
           dict(cache, k=k_pool, v=v_pool, kbar=kbar, lin=lin))
    return out + (masks,) if with_selection else out


def sala_decode_step(params, tokens, positions, cache, page_tables, kv_lens,
                     *, cfg, attn_impl=None, with_selection=False):
    """One token per slot (the ``DecodeModel`` contract).  Sparse layers write
    K, V and fold the key into its half-kernel mean, select blocks on the
    device and attend over the selected pages only; lightning layers apply
    one step of the recurrence to the slots that decode (``kv_lens > 0``) and
    leave the others' state as it is.  Returns ``(logits [S, V], cache',
    counts [3])`` — ``STEP_COUNTERS``: selected and visible tokens summed over
    slots, sparse layers and KV heads, and the rows that ran dense."""
    import jax.numpy as jnp

    from ..parallel.flash_attention import (paged_block_scores,
                                            paged_decode_attention)

    d = _dims(cfg)
    k_pool, v_pool, kbar, lin = (cache[n] for n in ("k", "v", "kbar", "lin"))
    S = tokens.shape[0]
    ps = k_pool.shape[2]
    if ps != d["B"]:
        raise ValueError("page_size %d must equal the selection's block_size "
                         "%d (a block is a page)" % (ps, d["B"]))
    act = params["embed"].dtype
    slopes = jnp.asarray(lightning_slopes(d["Hl"]))
    live = kv_lens > 0
    pages = page_tables[jnp.arange(S), positions // ps]
    offsets = positions % ps
    hb_row = offsets // d["s"]
    opens_row = (positions % d["s"] == 0)[:, None]
    x = (params["embed"][tokens].astype(jnp.float32) * d["scale_emb"]
         ).astype(act)
    si = li = 0
    masks = []
    selected = jnp.int32(0)
    for layer, kind in enumerate(d["kinds"]):
        lp = params["layers"][layer]
        u = _rms(x, params["ln1"][layer], d["eps"])
        q, k, v, gate = _split(d, kind, _mm(u, lp["w_in"]))
        if kind == "minicpm4":
            q = _rms(q, params["sparse"]["qn"][si], d["eps"])
            k = _rms(k, params["sparse"]["kn"][si], d["eps"])
            kf = _as_stored(k.reshape(S, -1), k_pool.dtype)
            k_pool = k_pool.at[si, pages, offsets].set(kf.astype(k_pool.dtype))
            v_pool = v_pool.at[si, pages, offsets].set(
                v.reshape(S, -1).astype(v_pool.dtype))
            # the token's share of its half-kernel mean: the first token of
            # a half-kernel overwrites whatever the page held before
            old = jnp.where(opens_row, 0.0, kbar[si, pages, hb_row])
            kbar = kbar.at[si, pages, hb_row].set(old + kf / d["s"])
            score = paged_block_scores(
                q, kbar, page_tables, kv_lens, layer=si, kernel_size=d["l"],
                stride=d["s"], impl=attn_impl)
            mask = _pick_blocks(d, score[:, None], kv_lens[:, None])[:, 0]
            masks.append(mask)
            sel_pages, sel_tokens = _listed(d, mask, kv_lens, page_tables)
            selected = selected + sel_tokens.sum()
            o = paged_decode_attention(
                q, k_pool, v_pool, page_tables, kv_lens, impl=attn_impl,
                layer=si, selection=(sel_pages, sel_tokens))
            si += 1
        else:
            q = _rope(_rms(q, params["lin"]["qn"][li], d["eps"]), positions,
                      d["theta"])
            k = _rope(_rms(k, params["lin"]["kn"][li], d["eps"]), positions,
                      d["theta"])
            o, s1 = _lightning_step(slopes, q, k, v, lin[li], live)
            lin = lin.at[li].set(s1)
            o = _rms(o, params["lin"]["on"][li], d["eps"])
            li += 1
        x = _mix_and_ffn(d, lp, x, o, gate, params["ln2"][layer], act)
    counts = jnp.stack([
        selected, kv_lens.sum() * (d["Hkv"] * d["n_sparse"]),
        (live & (kv_lens <= d["dense_len"])).sum().astype(jnp.int32)
    ]).astype(jnp.int32)
    out = (_logits(d, params, x),
           dict(cache, k=k_pool, v=v_pool, kbar=kbar, lin=lin), counts)
    return out + (masks,) if with_selection else out


def build_decode_model(params, cfg, eos_id=None, attn_impl=None):
    """MiniCPM-SALA behind ``InferenceEngine`` -> ``DecodeScheduler``.  Serve
    it with ``DecodeConfig(page_size=sparse_config.block_size,
    prefill_chunk_tokens=...)``; ``prefix_cache`` and sessions are refused
    (slot state has no snapshot per page boundary)."""
    from ..serving.decode_scheduler import DecodeModel

    _dims(cfg)
    return DecodeModel(
        functools.partial(sala_decode_step, cfg=cfg, attn_impl=attn_impl),
        functools.partial(sala_prefill_chunk, cfg=cfg, attn_impl=attn_impl),
        params=params, vocab_size=cfg["vocab_size"], eos_id=eos_id,
        name="minicpm-sala", step_counters=STEP_COUNTERS,
        **cache_layout(cfg))
