"""The DeepSeek-V3 family (``model_type`` ``deepseek_v3``, e.g. kakaocorp's
kanana-2-30b-a3b; and ``glm_moe_dsa``, zai-org's GLM-5, which adds DeepSeek
sparse attention to it) as a served ``DecodeModel``: a pre-norm RMSNorm decoder
whose attention is multi-head LATENT attention and whose feed-forward blocks,
after ``first_k_dense_replace`` dense ones, are sparse experts.

* **MLA.**  With ``q_lora_rank`` null ``x W_q`` gives each head ``[q_nope |
  q_pe]``; with a rank the query is compressed first: ``c_q = RMSNorm(x
  W_qa)``, ``q = c_q W_qb``.  ``x W_kva`` gives ``[c' | k_pe]``, ``c =
  RMSNorm(c')`` is the compressed KV all heads share and ``k_pe`` the one
  rotary key (rotary on interleaved pairs: de-interleaved here, then the
  rotate-half form, the same on ``q_pe`` and ``k_pe``, so every score is the
  interleaved one's).  The cache holds ONE row ``[c | k_pe | 0]`` a token a
  layer (``cache["latent"]``; no K / V pools).  Both step programs attend in
  the ABSORBED form: ``q_lat = q_nope W_uk`` carries a head's query into the
  latent space, scores are ``(q_lat . c + q_pe . k_pe) / sqrt(d_nope +
  d_rope)``, and ``(P c) W_uv`` is the head's output, with ``W_uk``, ``W_uv``
  the two halves of the head's slice of ``W_kvb`` — the same function as
  expanding every cached row through ``W_kvb``, with each row read once for
  all heads (``parallel/flash_attention.py``: ``paged_mla_*_attention``).
* **DeepSeek sparse attention**, iff the config has ``index_topk``: a
  lightning indexer (``index_n_heads`` queries ``q^I = c_q W_qb^I`` of
  ``index_head_dim``, ONE key ``k^I = LayerNorm(x W_k^I)`` a token, rotary on
  the first ``qk_rope_head_dim`` lanes of both, head weights ``w = x W_w``)
  scores every visible token, ``I_s = (Hi Di)^-1/2 sum_j w_j relu(q^I_j .
  k^I_s)``; the ``index_topk`` best are the query's SET (exact; ties: the
  lower position) and latent attention reads those rows only.  The cache
  holds a SECOND page-indexed leaf, ``cache["index_k"]`` (``Di`` lanes a
  token a layer), that only the indexer reads.  A decode step scores through
  the page table, makes the set's row list (``dsa_select``: on the chip one
  kernel) and attends over the gathered rows; a chunk attends over every
  visible row under the set as a mask (``parallel/flash_attention.py``:
  ``paged_index_scores*``, ``dsa_*``, ``paged_mla_rows_attention``).  Without
  ``index_topk`` none of this exists and the step programs are dense MLA's.
* **Experts** (``parallel/moe.py``: ``moe_topk``): sigmoid scores over ALL
  ``router_experts`` (``n_routed_experts`` where the config has no such key),
  top-k of score + ``e_score_correction_bias`` (``n_group`` = ``topk_group``
  = 1: no group limit), weights normalised and scaled by
  ``routed_scaling_factor``, dropless; computed are the pairs of the experts
  ``experts_held = [lo, hi)`` (all of them where the config has no such key:
  kanana-2; a sixteenth: GLM-5 under EP16), the others' terms are left to
  their holders; plus the shared experts (one SwiGLU of ``n_shared_experts``
  x the expert width), added once.

The equations and every assumed size are in the plain references,
``chipbench/configs/kanana2_30b_a3b.reference.py`` and
``glm5_744b_a40b.reference.py``; ``cfg`` is the configuration in the family's
own key names.  Precision, the shared pieces (``_rms``, ``_mm``, ``_rope``,
``_ffn``, ``_logits``) and the weights-as-arguments contract are
``models/minicpm_sala.py``'s; the router's and the indexer's scores, norms,
rotary and softmax are float32.

Weights: the matrices of a layer are an array each (``w_in`` = everything
projected from the normed input, fused column-wise: q | kv_a, or q_a | kv_a |
k^I | w; ``w_qb`` = q_b | q_b^I where the query is compressed; ``wkvb`` ``[H,
d_nope + d_v, rank]``, ``wo``, and ``w_gu`` / ``w_down``: the dense block's,
or the shared experts'); the routed experts are two stacks ``[expert layers,
experts held, ...]`` that the grouped matrix product addresses in place;
vectors and routers are stacked by kind.
"""
from __future__ import annotations

import functools
import math

from .minicpm_sala import _ffn, _logits, _mm, _rms, _rope

__all__ = ["params", "prefill_chunk", "decode_step", "build_decode_model",
           "cache_layout", "step_counters", "take_share", "STEP_COUNTERS"]

STEP_COUNTERS = ("moe.pairs", "moe.experts_touched", "moe.max_load",
                 "latent.tokens_read")
# what a model with a share of the experts and an indexer counts besides
DSA_COUNTERS = ("moe.pairs_elsewhere", "sparse.selected_tokens",
                "sparse.visible_tokens", "index.rows_scored")
INDEX_LN_EPS = 1e-6


def step_counters(cfg):
    """Names of what :func:`decode_step` returns behind its cache."""
    return STEP_COUNTERS + (DSA_COUNTERS if "index_topk" in cfg else ())


def _dims(cfg):
    for key, want in (("rope_scaling", None),
                      ("n_group", 1), ("topk_group", 1),
                      ("moe_layer_freq", 1), ("scoring_func", "sigmoid"),
                      ("norm_topk_prob", True)):
        if cfg.get(key, want) != want:
            raise ValueError("%s = %r is not written here (only %r)"
                             % (key, cfg[key], want))
    E = cfg["n_routed_experts"]
    lo, hi = (int(e) for e in cfg.get("experts_held", (0, E)))
    router = int(cfg.get("router_experts", E))
    if not 0 <= lo < hi <= router or hi - lo != E:
        raise ValueError(
            "experts_held %s must be n_routed_experts = %d of the router's %d"
            % ((lo, hi), E, router))
    d = dict(
        D=cfg["hidden_size"], F=cfg["intermediate_size"],
        Fm=cfg["moe_intermediate_size"], V=cfg["vocab_size"],
        H=cfg["num_attention_heads"], L=cfg["num_hidden_layers"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], R=cfg["kv_lora_rank"],
        Rq=cfg.get("q_lora_rank"),
        E=E, router=router, held=(lo, hi), k=cfg["num_experts_per_tok"],
        n_shared=cfg["n_shared_experts"],
        n_dense=min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"]),
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        scale=float(cfg["routed_scaling_factor"]),
        topk=cfg.get("index_topk"),
        resid=1.0, logit_div=1.0)
    if d["topk"] is not None:
        if d["Rq"] is None:
            raise ValueError("the indexer's queries come from the compressed "
                             "query: index_topk needs q_lora_rank")
        d.update(Hi=cfg["index_n_heads"], Di=cfg["index_head_dim"])
        d["index_scale"] = 1.0 / math.sqrt(d["Hi"] * d["Di"])
    # a cached row in whole lane tiles (an HBM row is padded to them anyway)
    d["W"] = -(-(d["R"] + d["dr"]) // 128) * 128
    d["sm_scale"] = 1.0 / math.sqrt(d["dn"] + d["dr"])
    return d


def cache_layout(cfg):
    """What the model keeps in the cache, as ``DecodeModel`` states it: one
    latent row a token a layer, with an indexer one indexer key beside it,
    and NO K / V layers."""
    d = _dims(cfg)
    pools = {"latent": dict(layers=d["L"], tokens_per_row=1, width=d["W"],
                            dtype=None)}
    if d["topk"] is not None:
        pools["index_k"] = dict(layers=d["L"], tokens_per_row=1,
                                width=d["Di"], dtype=None)
    return dict(page_pools=pools)


def params(cfg, seed, dtype="bfloat16"):
    """Seeded random weights as device arrays of ``dtype`` (vectors and the
    routers float32): normal(0, 1 / fan_in) matrices, norm weights around
    one, the selection bias normal(0, 0.02), the indexer LayerNorm's bias
    normal(0, 0.1).  Made on the device; the expert stacks a layer at a time
    into a donated buffer, so nothing larger than a layer's experts in
    float32 is ever a temporary."""
    import jax
    import jax.numpy as jnp

    from ..core import cpu_backend

    d = _dims(cfg)
    dt = jnp.dtype(dtype)
    D, H, L = d["D"], d["H"], d["L"]
    n_moe = L - d["n_dense"]
    n_q = H * (d["dn"] + d["dr"])
    n_in = (n_q if d["Rq"] is None else d["Rq"]) + d["R"] + d["dr"]
    if d["topk"] is not None:
        n_in += d["Di"] + d["Hi"]

    def mat(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    def make(key):
        keys = iter(jax.random.split(key, 16 + 5 * L))
        # what query compression and the indexer add draws from a stream of
        # its own: a config without them keeps the weights it always had
        more = iter(jax.random.split(jax.random.fold_in(key, 7), 4 + L))

        def vec(*shape, keys=keys):
            return 1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                 jnp.float32)

        def layer(i):
            F = d["F"] if i < d["n_dense"] else d["n_shared"] * d["Fm"]
            out = {"w_in": mat(next(keys), (D, n_in), D),
                   "wkvb": mat(next(keys), (H, d["dn"] + d["dv"], d["R"]),
                               d["R"]),
                   "wo": mat(next(keys), (H * d["dv"], D), H * d["dv"]),
                   "w_gu": mat(next(keys), (D, 2 * F), D),
                   "w_down": mat(next(keys), (F, D), F)}
            if d["Rq"] is not None:
                n_qb = n_q + (d["Hi"] * d["Di"] if d["topk"] is not None
                              else 0)
                out["w_qb"] = mat(next(more), (d["Rq"], n_qb), d["Rq"])
            return out

        out = {
            "embed": mat(next(keys), (d["V"], D), 1.0),
            "head": mat(next(keys), (D, d["V"]), D),
            "norm_f": vec(D), "ln1": vec(L, D), "ln2": vec(L, D),
            "kvn": vec(L, d["R"]),
            "router_w": jax.random.normal(
                next(keys), (n_moe, D, d["router"]), jnp.float32)
            / math.sqrt(D),
            "router_b": 0.02 * jax.random.normal(
                next(keys), (n_moe, d["router"]), jnp.float32),
            "layers": [layer(i) for i in range(L)],
        }
        if d["Rq"] is not None:
            out["qn"] = vec(L, d["Rq"], keys=more)
        if d["topk"] is not None:
            out["ikn_w"] = vec(L, d["Di"], keys=more)
            out["ikn_b"] = 0.1 * jax.random.normal(
                next(more), (L, d["Di"]), jnp.float32)
        return out

    root = jax.random.PRNGKey(seed % (2 ** 31))
    out = jax.jit(make)(root)
    donate = () if cpu_backend() else (0,)
    for name, shape, fan_in, salt in (
            ("e_gu", (d["E"], D, 2 * d["Fm"]), D, 1),
            ("e_down", (d["E"], d["Fm"], D), d["Fm"], 2)):
        put = jax.jit(lambda stack, key, i, shape=shape, fan_in=fan_in:
                      jax.lax.dynamic_update_index_in_dim(
                          stack, mat(key, shape, fan_in), i, 0),
                      donate_argnums=donate)
        stack = jnp.zeros((n_moe,) + shape, dt)
        for i in range(n_moe):
            stack = put(stack, jax.random.fold_in(root, 16 * salt + i), i)
        out[name] = stack
    return out


def take_share(weights, cfg, experts_held, vocab=None):
    """``(weights', cfg')`` of one holder of an expert-parallel,
    vocabulary-parallel split of ``weights`` (made for ``cfg``): the experts
    ``lo .. hi - 1`` of every layer and, with ``vocab = (lo, hi)``, those rows
    of the embedding and columns of the head.  The router keeps its whole
    width: every holder scores all experts."""
    lo, hi = experts_held
    d = _dims(cfg)
    at = d["held"][0]
    out = dict(weights, e_gu=weights["e_gu"][:, lo - at:hi - at],
               e_down=weights["e_down"][:, lo - at:hi - at])
    cut = dict(cfg, experts_held=[lo, hi], n_routed_experts=hi - lo,
               router_experts=d["router"])
    if vocab is not None:
        out.update(embed=weights["embed"][vocab[0]:vocab[1]],
                   head=weights["head"][:, vocab[0]:vocab[1]])
        cut["vocab_size"] = vocab[1] - vocab[0]
    return out, cut


# -- the layer ----------------------------------------------------------------

def _deinterleave(x):
    """``[x0, x1, x2, ..]`` -> ``[x0, x2, .. | x1, x3, ..]`` on the last axis:
    the pairs ``(2i, 2i + 1)`` that ``rope_interleave`` rotates become the
    pairs ``(i, i + half)`` of the rotate-half form."""
    import jax.numpy as jnp

    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _projections(d, p, lp, layer, x):
    """What a layer projects from its normed input: ``(y, qb)`` float32 —
    ``y = norm1(x) w_in`` (every head's query, or the query latent; ``[c' |
    k_pe]``; with an indexer its key and head weights) and, where the query
    is compressed, ``qb = RMSNorm(query latent) w_qb`` (the heads' queries
    and the indexer's), else None."""
    y = _mm(_rms(x, p["ln1"][layer], d["eps"]), lp["w_in"])
    if d["Rq"] is None:
        return y, None
    return y, _mm(_rms(y[:, :d["Rq"]], p["qn"][layer], d["eps"]), lp["w_qb"])


def _latent_rows(d, p, lp, layer, x, positions, projected=None):
    """A layer's absorbed queries ``[T, H, W]`` (the activations' dtype) and
    the rows ``[T, W]`` (float32) its tokens add to the cache."""
    import jax.numpy as jnp

    T = x.shape[0]
    H, dn, dr, R = d["H"], d["dn"], d["dr"], d["R"]
    act = x.dtype
    y, qb = projected or _projections(d, p, lp, layer, x)
    a = H * (dn + dr) if qb is None else d["Rq"]
    qh = (y if qb is None else qb)[:, :H * (dn + dr)].reshape(T, H, dn + dr)
    c = _rms(y[:, a:a + R], p["kvn"][layer], d["eps"])
    q_pe = _rope(_deinterleave(qh[..., dn:]), positions, d["theta"])
    k_pe = _rope(_deinterleave(y[:, a + R:a + R + dr])[:, None, :], positions,
                 d["theta"])[:, 0]
    q_lat = jnp.einsum("thd,hdc->thc", qh[..., :dn].astype(act),
                       lp["wkvb"][:, :dn, :],
                       preferred_element_type=jnp.float32)
    pad = d["W"] - R - dr
    q = jnp.concatenate([q_lat, q_pe, jnp.zeros((T, H, pad), jnp.float32)],
                        axis=-1).astype(act)
    row = jnp.concatenate([c, k_pe, jnp.zeros((T, pad), jnp.float32)],
                          axis=-1)
    return q, row


def _indexer_rows(d, p, layer, projected, positions, act):
    """The lightning indexer's part of a layer: ``(q^I [T, Hi, Di]`` in the
    activations' dtype, ``k^I [T, Di]`` float32 — the row ``index_k`` takes —,
    ``w [T, Hi]`` float32)``; None for a model without one."""
    import jax
    import jax.numpy as jnp

    if d["topk"] is None:
        return None
    y, qb = projected
    Hi, Di, dr = d["Hi"], d["Di"], d["dr"]
    at = d["Rq"] + d["R"] + dr

    def rotated(v):
        """Rotary on the first ``dr`` lanes of ``v [T, n, Di]``."""
        return jnp.concatenate([
            _rope(_deinterleave(v[..., :dr]), positions, d["theta"]),
            v[..., dr:]], axis=-1)

    k = y[:, at:at + Di]
    mean = k.mean(axis=-1, keepdims=True)
    k = ((k - mean) * jax.lax.rsqrt(((k - mean) ** 2).mean(
        axis=-1, keepdims=True) + INDEX_LN_EPS)
        * p["ikn_w"][layer] + p["ikn_b"][layer])
    q = qb[:, d["H"] * (d["dn"] + dr):].reshape(-1, Hi, Di)
    return (rotated(q).astype(act), rotated(k[:, None, :])[:, 0],
            y[:, at + Di:at + Di + Hi])


def _attn_out(d, lp, x, o):
    """``x + concat_h((P c) W_uv) W_o``: ``o [T, H, R]`` float32."""
    import jax.numpy as jnp

    act = x.dtype
    heads = jnp.einsum("thc,hdc->thd", o.astype(act),
                       lp["wkvb"][:, d["dn"]:, :],
                       preferred_element_type=jnp.float32)
    return (x.astype(jnp.float32)
            + _mm(heads.reshape(x.shape[0], -1), lp["wo"])).astype(act)


def _feed_forward(d, p, lp, layer, h, token_mask):
    """The block after attention: ``(h + FFN(norm2(h)), counts, chosen)``,
    the expert layer's counts ``[3]`` and chosen experts ``[T, k]`` as
    ``moe_topk`` returns them (None for a dense block)."""
    import jax
    import jax.numpy as jnp

    from ..parallel.moe import moe_topk

    act = h.dtype
    if layer < d["n_dense"]:
        return _ffn(d, lp, h, p["ln2"][layer], act), None, None
    m = layer - d["n_dense"]
    with jax.named_scope("moe_experts"):
        u = _rms(h, p["ln2"][layer], d["eps"]).astype(act)
        y, counts, chosen = moe_topk(
            u, {"w": p["router_w"][m], "bias": p["router_b"][m]},
            {"w_gu": p["e_gu"], "w_down": p["e_down"]},
            {"w_gu": lp["w_gu"], "w_down": lp["w_down"]},
            top_k=d["k"], experts_held=d["held"], scale=d["scale"],
            token_mask=token_mask, layer=m)
        return (h.astype(jnp.float32) + y).astype(act), counts, chosen


def prefill_chunk(p, tokens, start, valid, cache, chunk_pages, gather_pages,
                  slot, *, cfg, with_routing=False, with_selection=False):
    """One chunk of one sequence's prefill (the ``DecodeModel`` contract):
    every layer scatters the chunk's latent rows (and indexer keys) into
    ``chunk_pages`` and attends, absorbed, over ``gather_pages`` (its own rows
    included) causally by position — with an indexer, over the rows of each
    query's own selection, given to the same walk as a mask; padding rows
    route to no expert.  Returns ``(last_logits [V], cache')``; with
    ``with_routing`` also the experts each expert layer chose ``[C, k]`` (the
    choice ``moe_topk`` computed with); with ``with_selection`` also every
    layer's ``(index scores [C, keys] float32, selected [C, keys] bool)``."""
    import jax
    import jax.numpy as jnp

    from ..parallel import flash_attention as FA

    d = _dims(cfg)
    cache = dict(cache)
    ps = cache["latent"].shape[2]
    C = tokens.shape[0]
    positions = start + jnp.arange(C, dtype=jnp.int32)
    real = jnp.arange(C) < valid
    visible = jnp.where(real, positions + 1, 0)
    x = p["embed"][tokens]
    routing, selection = [], []

    def put(leaf, rows):
        return leaf.at[layer, chunk_pages].set(
            rows.reshape(C // ps, ps, -1).astype(leaf.dtype))

    for layer, lp in enumerate(p["layers"]):
        with jax.named_scope("mla_attention"):
            projected = _projections(d, p, lp, layer, x)
            q, row = _latent_rows(d, p, lp, layer, x, positions, projected)
            index = _indexer_rows(d, p, layer, projected, positions, x.dtype)
            cache["latent"] = put(cache["latent"], row)
            keep = None
            if index is not None:
                cache["index_k"] = put(cache["index_k"], index[1])
                with jax.named_scope("dsa_index"):
                    scores = FA.paged_index_scores_prefill(
                        index[0], index[2], cache["index_k"], gather_pages,
                        start, valid, layer=layer, scale=d["index_scale"])
                with jax.named_scope("dsa_select"):
                    keep = FA.dsa_keep(scores, visible, d["topk"])
                selection.append((scores, keep))
            o = FA.paged_mla_prefill_attention(
                q, cache["latent"], gather_pages, start, valid,
                v_width=d["R"], sm_scale=d["sm_scale"], layer=layer,
                keep=keep)
            h = _attn_out(d, lp, x, o)
        x, _, chosen = _feed_forward(d, p, lp, layer, h, real)
        if chosen is not None:
            routing.append(chosen)
    last = jax.lax.dynamic_index_in_dim(x, valid - 1, axis=0, keepdims=False)
    out = (_logits(d, p, last), cache)
    if with_routing:
        out += (routing,)
    return out + (selection,) if with_selection else out


def decode_step(p, tokens, positions, cache, page_tables, kv_lens, *, cfg,
                with_routing=False, with_selection=False):
    """One token per slot (the ``DecodeModel`` contract): every layer writes
    the token's latent row (and indexer key) and attends, absorbed, over the
    slot's first ``kv_lens`` rows — with an indexer, over the
    ``min(kv_lens, index_topk)`` rows its scores select, gathered as a list;
    slots that do not decode (``kv_lens == 0``) write to scratch, select
    nothing and route to no expert.  Returns ``(logits [S, V], cache',
    counts)`` — :func:`step_counters`: the (token, expert) pairs computed,
    the held experts that took one, the largest expert's pairs (each summed
    over the expert layers) and the latent rows read (summed over slots and
    layers); with an indexer also the chosen pairs whose experts are held
    elsewhere, the selected and the visible tokens and the indexer keys
    scored (summed over slots and layers).  ``with_routing``: also the
    experts each expert layer chose ``[S, k]``; ``with_selection``: also every
    layer's ``(index scores [S, keys], rows [S, index_topk], n [S])``."""
    import jax
    import jax.numpy as jnp

    from ..parallel import flash_attention as FA

    d = _dims(cfg)
    cache = dict(cache)
    ps = cache["latent"].shape[2]
    S = tokens.shape[0]
    live = kv_lens > 0
    pages = page_tables[jnp.arange(S), positions // ps]
    offsets = positions % ps
    x = p["embed"][tokens]
    counts = jnp.zeros((3,), jnp.int32)
    routing, selection = [], []
    for layer, lp in enumerate(p["layers"]):
        with jax.named_scope("mla_attention"):
            projected = _projections(d, p, lp, layer, x)
            q, row = _latent_rows(d, p, lp, layer, x, positions, projected)
            index = _indexer_rows(d, p, layer, projected, positions, x.dtype)
            cache["latent"] = cache["latent"].at[layer, pages, offsets].set(
                row.astype(cache["latent"].dtype))
            if index is None:
                o = FA.paged_mla_decode_attention(
                    q, cache["latent"], page_tables, kv_lens,
                    v_width=d["R"], sm_scale=d["sm_scale"], layer=layer)
            else:
                cache["index_k"] = cache["index_k"].at[
                    layer, pages, offsets].set(
                        index[1].astype(cache["index_k"].dtype))
                with jax.named_scope("dsa_index"):
                    scores = FA.paged_index_scores(
                        index[0], index[2], cache["index_k"], page_tables,
                        kv_lens, layer=layer, scale=d["index_scale"])
                with jax.named_scope("dsa_select"):
                    rows, n = FA.dsa_select(
                        scores, kv_lens, d["topk"])    # scores to row list
                with jax.named_scope("mla_rows"):
                    o = FA.paged_mla_rows_attention(
                        q, cache["latent"], page_tables, rows, n,
                        v_width=d["R"], sm_scale=d["sm_scale"], layer=layer)
                selection.append((scores, rows, n))
            h = _attn_out(d, lp, x, o)
        x, c, chosen = _feed_forward(d, p, lp, layer, h, live)
        if c is not None:
            counts = counts + c
            routing.append(chosen)
    visible = kv_lens.sum() * d["L"]
    if d["topk"] is None:
        more = [visible]
    else:
        chosen_pairs = live.sum() * (d["k"] * (d["L"] - d["n_dense"]))
        selected = jnp.minimum(kv_lens, d["topk"]).sum() * d["L"]
        more = [selected, chosen_pairs - counts[0], selected, visible,
                visible]
    counts = jnp.concatenate([counts, jnp.stack(more).astype(jnp.int32)])
    out = (_logits(d, p, x), cache, counts)
    if with_routing:
        out += (routing,)
    return out + (selection,) if with_selection else out


def build_decode_model(weights, cfg, eos_id=None):
    """A DeepSeek-V3-family model behind ``InferenceEngine`` ->
    ``DecodeScheduler``: ``weights`` from :func:`params` (or a checkpoint in
    its form).  The cache is pages only (one leaf, or two with an indexer),
    so the prefix cache, sessions and roles take it as they take any paged
    model."""
    from ..serving.decode_scheduler import DecodeModel

    _dims(cfg)
    return DecodeModel(
        functools.partial(decode_step, cfg=cfg),
        functools.partial(prefill_chunk, cfg=cfg),
        params=weights, vocab_size=cfg["vocab_size"], eos_id=eos_id,
        name="deepseek-v3", step_counters=step_counters(cfg),
        **cache_layout(cfg))
