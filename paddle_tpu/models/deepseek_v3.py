"""The DeepSeek-V3 family (``model_type`` ``deepseek_v3``; e.g. kakaocorp's
kanana-2-30b-a3b) as a served ``DecodeModel``: a pre-norm RMSNorm decoder whose
attention is multi-head LATENT attention and whose feed-forward blocks, after
``first_k_dense_replace`` dense ones, are sparse experts.

* **MLA** (no query compression: ``q_lora_rank`` null).  ``x W_q`` gives each
  head ``[q_nope | q_pe]``; ``x W_kva`` gives ``[c' | k_pe]``, ``c =
  RMSNorm(c')`` is the compressed KV all heads share and ``k_pe`` the one
  rotary key (rotary on interleaved pairs: de-interleaved here, then the
  rotate-half form, the same on ``q_pe`` and ``k_pe``, so every score is the
  interleaved one's).  The cache holds ONE row ``[c | k_pe | 0]`` a token a
  layer (``cache["latent"]``, the model's only page-indexed leaf: no K / V
  pools).  Both step programs attend in the ABSORBED form: ``q_lat = q_nope
  W_uk`` carries a head's query into the latent space, scores are ``(q_lat .
  c + q_pe . k_pe) / sqrt(d_nope + d_rope)``, and ``(P c) W_uv`` is the head's
  output, with ``W_uk``, ``W_uv`` the two halves of the head's slice of
  ``W_kvb`` — the same function as expanding every cached row through
  ``W_kvb``, with each row read once for all heads
  (``parallel/flash_attention.py``: ``paged_mla_*_attention``).
* **Experts** (``parallel/moe.py``: ``moe_topk``): sigmoid scores, top-k of
  score + ``e_score_correction_bias`` (``n_group`` = ``topk_group`` = 1: no
  group limit), weights normalised and scaled by ``routed_scaling_factor``,
  dropless, every expert held here (``experts_held`` = all of them), plus
  the shared experts (one SwiGLU of ``n_shared_experts`` x the expert width).

The equations and every assumed size are in the plain reference,
``chipbench/configs/kanana2_30b_a3b.reference.py``; ``cfg`` is the
configuration in the family's own key names.  Precision, the shared pieces
(``_rms``, ``_mm``, ``_rope``, ``_ffn``, ``_logits``) and the
weights-as-arguments contract are ``models/minicpm_sala.py``'s; the router's
scores, norms, rotary and softmax are float32.

Weights: the matrices of a layer are an array each (``w_in`` = q | kv_a fused
column-wise, ``wkvb`` ``[H, d_nope + d_v, rank]``, ``wo``, and ``w_gu`` /
``w_down``: the dense block's, or the shared experts'); the routed experts are
two stacks ``[expert layers, experts, ...]`` that the grouped matrix
product addresses in place; vectors and routers are stacked by kind.
"""
from __future__ import annotations

import functools
import math

from .minicpm_sala import _ffn, _logits, _mm, _rms, _rope

__all__ = ["params", "prefill_chunk", "decode_step", "build_decode_model",
           "cache_layout", "STEP_COUNTERS"]

STEP_COUNTERS = ("moe.pairs", "moe.experts_touched", "moe.max_load",
                 "latent.tokens_read")


def _dims(cfg):
    for key, want in (("q_lora_rank", None), ("rope_scaling", None),
                      ("n_group", 1), ("topk_group", 1),
                      ("moe_layer_freq", 1), ("scoring_func", "sigmoid"),
                      ("norm_topk_prob", True)):
        if cfg.get(key, want) != want:
            raise ValueError("%s = %r is not written here (only %r)"
                             % (key, cfg[key], want))
    d = dict(
        D=cfg["hidden_size"], F=cfg["intermediate_size"],
        Fm=cfg["moe_intermediate_size"], V=cfg["vocab_size"],
        H=cfg["num_attention_heads"], L=cfg["num_hidden_layers"],
        dn=cfg["qk_nope_head_dim"], dr=cfg["qk_rope_head_dim"],
        dv=cfg["v_head_dim"], R=cfg["kv_lora_rank"],
        E=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
        n_shared=cfg["n_shared_experts"],
        n_dense=min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"]),
        eps=cfg["rms_norm_eps"], theta=float(cfg["rope_theta"]),
        scale=float(cfg["routed_scaling_factor"]),
        resid=1.0, logit_div=1.0)
    # a cached row in whole lane tiles (an HBM row is padded to them anyway)
    d["W"] = -(-(d["R"] + d["dr"]) // 128) * 128
    d["sm_scale"] = 1.0 / math.sqrt(d["dn"] + d["dr"])
    return d


def cache_layout(cfg):
    """What the model keeps in the cache, as ``DecodeModel`` states it: one
    latent row a token a layer and NO K / V layers."""
    d = _dims(cfg)
    return dict(page_pools={"latent": dict(
        layers=d["L"], tokens_per_row=1, width=d["W"], dtype=None)})


def params(cfg, seed, dtype="bfloat16"):
    """Seeded random weights as device arrays of ``dtype`` (vectors and the
    routers float32): normal(0, 1 / fan_in) matrices, norm weights around
    one, the selection bias normal(0, 0.02).  Made on the device; the expert
    stacks a layer at a time into a donated buffer, so nothing larger than a
    layer's experts in float32 is ever a temporary."""
    import jax
    import jax.numpy as jnp

    from ..core import cpu_backend

    d = _dims(cfg)
    dt = jnp.dtype(dtype)
    D, H, L = d["D"], d["H"], d["L"]
    n_moe = L - d["n_dense"]
    n_in = H * (d["dn"] + d["dr"]) + d["R"] + d["dr"]

    def mat(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    def make(key):
        keys = iter(jax.random.split(key, 16 + 5 * L))

        def vec(*shape):
            return 1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                 jnp.float32)

        def layer(i):
            F = d["F"] if i < d["n_dense"] else d["n_shared"] * d["Fm"]
            return {"w_in": mat(next(keys), (D, n_in), D),
                    "wkvb": mat(next(keys), (H, d["dn"] + d["dv"], d["R"]),
                                d["R"]),
                    "wo": mat(next(keys), (H * d["dv"], D), H * d["dv"]),
                    "w_gu": mat(next(keys), (D, 2 * F), D),
                    "w_down": mat(next(keys), (F, D), F)}

        return {
            "embed": mat(next(keys), (d["V"], D), 1.0),
            "head": mat(next(keys), (D, d["V"]), D),
            "norm_f": vec(D), "ln1": vec(L, D), "ln2": vec(L, D),
            "kvn": vec(L, d["R"]),
            "router_w": jax.random.normal(
                next(keys), (n_moe, D, d["E"]), jnp.float32) / math.sqrt(D),
            "router_b": 0.02 * jax.random.normal(
                next(keys), (n_moe, d["E"]), jnp.float32),
            "layers": [layer(i) for i in range(L)],
        }

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


# -- the layer ----------------------------------------------------------------

def _deinterleave(x):
    """``[x0, x1, x2, ..]`` -> ``[x0, x2, .. | x1, x3, ..]`` on the last axis:
    the pairs ``(2i, 2i + 1)`` that ``rope_interleave`` rotates become the
    pairs ``(i, i + half)`` of the rotate-half form."""
    import jax.numpy as jnp

    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _latent_rows(d, p, lp, layer, x, positions):
    """A layer's absorbed queries ``[T, H, W]`` (the activations' dtype) and
    the rows ``[T, W]`` (float32) its tokens add to the cache."""
    import jax.numpy as jnp

    T = x.shape[0]
    H, dn, dr, R = d["H"], d["dn"], d["dr"], d["R"]
    act = x.dtype
    y = _mm(_rms(x, p["ln1"][layer], d["eps"]), lp["w_in"])
    a = H * (dn + dr)
    qh = y[:, :a].reshape(T, H, dn + dr)
    c = _rms(y[:, a:a + R], p["kvn"][layer], d["eps"])
    q_pe = _rope(_deinterleave(qh[..., dn:]), positions, d["theta"])
    k_pe = _rope(_deinterleave(y[:, a + R:])[:, None, :], positions,
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
            top_k=d["k"], experts_held=(0, d["E"]), scale=d["scale"],
            token_mask=token_mask, layer=m)
        return (h.astype(jnp.float32) + y).astype(act), counts, chosen


def prefill_chunk(p, tokens, start, valid, cache, chunk_pages, gather_pages,
                  slot, *, cfg, with_routing=False):
    """One chunk of one sequence's prefill (the ``DecodeModel`` contract):
    every layer scatters the chunk's latent rows into ``chunk_pages`` and
    attends, absorbed, over ``gather_pages`` (its own rows included) causally
    by position; padding rows route to no expert.  Returns ``(last_logits
    [V], cache')``; with ``with_routing`` also the experts each expert layer
    chose ``[C, k]`` (the choice ``moe_topk`` computed with)."""
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_mla_prefill_attention

    d = _dims(cfg)
    latent = cache["latent"]
    C, ps = tokens.shape[0], latent.shape[2]
    positions = start + jnp.arange(C, dtype=jnp.int32)
    real = jnp.arange(C) < valid
    x = p["embed"][tokens]
    routing = []
    for layer, lp in enumerate(p["layers"]):
        with jax.named_scope("mla_attention"):
            q, row = _latent_rows(d, p, lp, layer, x, positions)
            latent = latent.at[layer, chunk_pages].set(
                row.reshape(C // ps, ps, -1).astype(latent.dtype))
            o = paged_mla_prefill_attention(
                q, latent, gather_pages, start, valid, v_width=d["R"],
                sm_scale=d["sm_scale"], layer=layer)
            h = _attn_out(d, lp, x, o)
        x, _, chosen = _feed_forward(d, p, lp, layer, h, real)
        if chosen is not None:
            routing.append(chosen)
    last = jax.lax.dynamic_index_in_dim(x, valid - 1, axis=0, keepdims=False)
    out = (_logits(d, p, last), dict(cache, latent=latent))
    return out + (routing,) if with_routing else out


def decode_step(p, tokens, positions, cache, page_tables, kv_lens, *, cfg,
                with_routing=False):
    """One token per slot (the ``DecodeModel`` contract): every layer writes
    the token's latent row and attends, absorbed, over the slot's first
    ``kv_lens`` rows; slots that do not decode (``kv_lens == 0``) write to
    scratch and route to no expert.  Returns ``(logits [S, V], cache', counts
    [4])`` — ``STEP_COUNTERS``: the (token, expert) pairs computed, the
    experts that took one, the largest expert's pairs (each summed over the
    expert layers) and the latent rows read (summed over slots and layers);
    with ``with_routing`` also the experts each expert layer chose ``[S, k]``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_mla_decode_attention

    d = _dims(cfg)
    latent = cache["latent"]
    S, ps = tokens.shape[0], latent.shape[2]
    live = kv_lens > 0
    pages = page_tables[jnp.arange(S), positions // ps]
    offsets = positions % ps
    x = p["embed"][tokens]
    counts = jnp.zeros((3,), jnp.int32)
    routing = []
    for layer, lp in enumerate(p["layers"]):
        with jax.named_scope("mla_attention"):
            q, row = _latent_rows(d, p, lp, layer, x, positions)
            latent = latent.at[layer, pages, offsets].set(
                row.astype(latent.dtype))
            o = paged_mla_decode_attention(
                q, latent, page_tables, kv_lens, v_width=d["R"],
                sm_scale=d["sm_scale"], layer=layer)
            h = _attn_out(d, lp, x, o)
        x, c, chosen = _feed_forward(d, p, lp, layer, h, live)
        if c is not None:
            counts = counts + c
            routing.append(chosen)
    counts = jnp.concatenate([
        counts, (kv_lens.sum() * d["L"]).astype(jnp.int32)[None]])
    out = (_logits(d, p, x), dict(cache, latent=latent), counts)
    return out + (routing,) if with_routing else out


def build_decode_model(weights, cfg, eos_id=None):
    """A DeepSeek-V3-family model behind ``InferenceEngine`` ->
    ``DecodeScheduler``: ``weights`` from :func:`params` (or a checkpoint in
    its form).  The cache is pages only, so the prefix cache, sessions and
    roles take it as they take any paged model."""
    from ..serving.decode_scheduler import DecodeModel

    _dims(cfg)
    return DecodeModel(
        functools.partial(decode_step, cfg=cfg),
        functools.partial(prefill_chunk, cfg=cfg),
        params=weights, vocab_size=cfg["vocab_size"], eos_id=eos_id,
        name="deepseek-v3", step_counters=STEP_COUNTERS,
        **cache_layout(cfg))
