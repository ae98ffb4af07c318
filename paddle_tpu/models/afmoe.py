"""The AFMoE family (``model_type`` ``afmoe``; arcee-ai's Trinity-Large-Preview)
as a served ``DecodeModel``: a SANDWICH-norm RMSNorm decoder (a norm before and
a norm after every mixer and every feed-forward block) with gated, QK-normed
grouped-query attention of two kinds, leading dense layers, and a SHARE of a
routed expert layer with one shared expert in every layer after them.

* **Embedding**: ``E[token] * sqrt(hidden_size)`` (``mup_enabled``).
* **Attention**: ``Hq`` query heads over ``Hkv`` KV heads (query head ``i``
  reads KV head ``i // g``); q and k normalised a head (RMSNorm over
  ``head_dim`` with a weight ``[head_dim]``); scale ``1 / sqrt(head_dim)``,
  causal; the output times ``sigmoid(W_g a)`` elementwise before ``W_o``.  A
  ``sliding_attention`` layer rotates q and k (rotate-half rotary on the whole
  head, ``rope_theta``, no scaling) and sees keys ``t - sliding_window + 1 ..
  t``; a ``full_attention`` layer rotates NOTHING and sees ``0 .. t``: the
  cached K rows of the two page groups differ in kind, not only in reach.
* **The cache** is ``models/mellum.py``'s: two page GROUPS, ``k_full`` /
  ``v_full`` for the life of the sequence, ``k_win`` / ``v_win`` a ring whose
  pages go back to the allocator as they fall out of the window.
* **Feed-forward**: layers ``0 .. num_dense_layers - 1`` a SwiGLU of
  ``intermediate_size``; every later layer ``moe_topk``: sigmoid scores over
  ALL ``router_experts`` in float32, ``expert_bias`` for the choice only, the
  ``num_experts_per_tok`` best, weights renormalised (``route_norm``) times
  ``route_scale``; this holder computes the pairs of ``experts_held = (lo,
  hi)`` and adds the shared expert; what the other holders would add is left
  out, and that partial sum goes on.

The equations and every assumed size are in the plain reference,
``chipbench/configs/trinity_large_preview.reference.py``; ``cfg`` is the
configuration in the family's own key names plus ``router_experts`` (the
router's width) and ``experts_held`` (``num_experts = hi - lo``).  Precision,
``_rms``, ``_mm``, ``_logits`` and the weights-as-arguments contract are
``models/minicpm_sala.py``'s, the rotary, the groups and the leaves
``models/mellum.py``'s, ``take_share`` ``models/solar_open2.py``'s; the
router, norms, rotary and softmax are float32.

Weights: a layer's attention matrices are ``w_in`` = q | k | v | gate fused
column-wise and ``wo``; a dense layer adds ``d_gu`` / ``d_down``, an expert
layer ``s_gu`` / ``s_down`` (the shared expert); the routed experts are two
stacks ``[expert layers, held, ...]`` that the grouped matrix product
addresses in place; vectors and routers are stacked by kind.
"""
from __future__ import annotations

import functools
import math

from .mellum import (GROUPS, KINDS, _rotary, _scope, group_layout,
                     rope_inverse_frequencies)
from .minicpm_sala import _logits, _mm, _rms
from .solar_open2 import take_share as _take_share

__all__ = ["params", "prefill_chunk", "decode_step", "build_decode_model",
           "cache_layout", "take_share", "STEP_COUNTERS"]

STEP_COUNTERS = ("moe.pairs", "moe.pairs_held", "moe.experts_touched",
                 "kv.full_tokens_read", "kv.window_tokens_read")


def _dims(cfg):
    for key, want in (("hidden_act", "silu"), ("score_func", "sigmoid"),
                      ("route_norm", True), ("mup_enabled", True),
                      ("rope_scaling", None), ("n_group", 1),
                      ("topk_group", 1), ("tie_word_embeddings", False)):
        if cfg.get(key, want) != want:
            raise ValueError("%s = %r is not written here (only %r)"
                             % (key, cfg[key], want))
    L = cfg["num_hidden_layers"]
    kinds = list(cfg["layer_types"])
    n_dense = int(cfg["num_dense_layers"])
    if len(kinds) != L or set(kinds) - set(KINDS) or not 0 <= n_dense <= L:
        raise ValueError("layer_types names %d layers of %s, num_dense_layers "
                         "of them dense; got %s / %d"
                         % (L, KINDS, kinds, n_dense))
    lo, hi = (int(e) for e in cfg["experts_held"])
    E = int(cfg["router_experts"])
    if not 0 <= lo < hi <= E or hi - lo != cfg["num_experts"]:
        raise ValueError(
            "experts_held %s must be num_experts = %d of the router's %d"
            % ((lo, hi), cfg["num_experts"], E))
    d = dict(
        D=cfg["hidden_size"], F=cfg["intermediate_size"],
        Fm=cfg["moe_intermediate_size"], V=cfg["vocab_size"],
        Fs=cfg["num_shared_experts"] * cfg["moe_intermediate_size"],
        H=cfg["num_attention_heads"], Hkv=cfg["num_key_value_heads"],
        Dh=cfg["head_dim"], L=L, n_dense=n_dense, E=E, held=(lo, hi),
        k=cfg["num_experts_per_tok"], scale=float(cfg["route_scale"]),
        eps=cfg["rms_norm_eps"], W=int(cfg["sliding_window"]), kinds=kinds,
        emb=math.sqrt(cfg["hidden_size"]), resid=1.0, logit_div=1.0)
    d["sm_scale"] = 1.0 / math.sqrt(d["Dh"])
    # a layer's index among the layers of its kind (its row of the leaf)
    d["row"] = [kinds[:i].count(kind) for i, kind in enumerate(kinds)]
    d["inv_freq"], _ = rope_inverse_frequencies(
        {"rope_type": "default", "rope_theta": cfg["rope_theta"]}, d["Dh"])
    return d


def cache_layout(cfg):
    """What the model keeps in the cache, as ``DecodeModel`` states it:
    ``models/mellum.py``'s two groups and four leaves, the sliding layers'
    with the window."""
    d = _dims(cfg)
    return group_layout(d["kinds"], d["W"], d["Hkv"] * d["Dh"])


def take_share(weights, cfg, experts_held, vocab=None):
    """``models/solar_open2.py:take_share`` in this family's key names: one
    holder's experts ``lo .. hi - 1`` of every expert layer (``num_experts``
    then counts them) and, with ``vocab``, its rows of the embedding and
    columns of the head; the router keeps its whole width."""
    out, cut = _take_share(weights, cfg, experts_held, vocab)
    cut["num_experts"] = cut.pop("n_routed_experts")
    return out, cut


def params(cfg, seed, dtype="bfloat16"):
    """Seeded random weights as device arrays of ``dtype`` (vectors and the
    routers float32): normal(0, 1 / fan_in) matrices, the embedding normal(0,
    1 / hidden_size) (so that ``E sqrt(D)`` has unit rows), norm weights
    around one, ``expert_bias`` normal(0, 0.02): small beside the scores'
    spread, so that it changes the choice of a known share of rows and no
    more.  The published initialisation is depth-scaled; that is how a
    checkpoint was drawn, not an equation, and seeded weights do not follow
    it.  Made on the device; the expert stacks a layer at a time into a
    donated buffer."""
    import jax
    import jax.numpy as jnp

    from ..core import cpu_backend

    d = _dims(cfg)
    dt = jnp.dtype(dtype)
    D, L = d["D"], d["L"]
    Le = L - d["n_dense"]
    n_q, n_kv = d["H"] * d["Dh"], d["Hkv"] * d["Dh"]
    n_held = d["held"][1] - d["held"][0]
    f32 = jnp.float32

    def mat(key, shape, fan_in):
        return (jax.random.normal(key, shape, f32)
                / math.sqrt(fan_in)).astype(dt)

    def make(key):
        keys = iter(jax.random.split(key, 16 + 4 * L))

        def vec(*shape):
            return 1.0 + 0.1 * jax.random.normal(next(keys), shape, f32)

        def layer(i):
            lp = {"w_in": mat(next(keys), (D, 2 * n_q + 2 * n_kv), D),
                  "wo": mat(next(keys), (n_q, D), n_q)}
            wide, names = ((d["F"], ("d_gu", "d_down")) if i < d["n_dense"]
                           else (d["Fs"], ("s_gu", "s_down")))
            lp[names[0]] = mat(next(keys), (D, 2 * wide), D)
            lp[names[1]] = mat(next(keys), (wide, D), wide)
            return lp

        return {
            "embed": mat(next(keys), (d["V"], D), D),
            "head": mat(next(keys), (D, d["V"]), D),
            "norm_f": vec(D), "ln_in": vec(L, D), "ln_post_attn": vec(L, D),
            "ln_pre_mlp": vec(L, D), "ln_post_mlp": vec(L, D),
            "qn": vec(L, d["Dh"]), "kn": vec(L, d["Dh"]),
            "router_w": jax.random.normal(next(keys), (Le, D, d["E"]), f32)
            / math.sqrt(D),
            "router_b": 0.02 * jax.random.normal(next(keys), (Le, d["E"]),
                                                 f32),
            "layers": [layer(i) for i in range(L)],
        }

    root = jax.random.PRNGKey(seed % (2 ** 31))
    out = jax.jit(make)(root)
    donate = () if cpu_backend() else (0,)
    for name, shape, fan_in, salt in (
            ("e_gu", (n_held, D, 2 * d["Fm"]), D, 1),
            ("e_down", (n_held, d["Fm"], D), d["Fm"], 2)):
        put = jax.jit(lambda stack, key, i, shape=shape, fan_in=fan_in:
                      jax.lax.dynamic_update_index_in_dim(
                          stack, mat(key, shape, fan_in), i, 0),
                      donate_argnums=donate)
        stack = jnp.zeros((Le,) + shape, dt)
        for i in range(Le):
            stack = put(stack, jax.random.fold_in(root, 64 * salt + i), i)
        out[name] = stack
    return out


# -- the layer ----------------------------------------------------------------

def _embed(d, p, tokens):
    import jax.numpy as jnp

    rows = p["embed"][tokens]
    return (rows.astype(jnp.float32) * d["emb"]).astype(rows.dtype)


def _attn_in(d, p, lp, layer, x, positions):
    """A layer's queries ``[T, Hq, Dh]`` (the activations' dtype; normalised a
    head, rotated in a sliding layer), the K rows (normalised, rotated
    likewise) and V rows ``[T, Hkv * Dh]`` float32 its tokens add to the
    cache, and the output gate ``[T, Hq * Dh]`` float32."""
    import jax

    T = x.shape[0]
    H, Hkv, Dh = d["H"], d["Hkv"], d["Dh"]
    n_q, n_kv = H * Dh, Hkv * Dh
    y = _mm(_rms(x, p["ln_in"][layer], d["eps"]), lp["w_in"])
    q = _rms(y[:, :n_q].reshape(T, H, Dh), p["qn"][layer], d["eps"])
    k = _rms(y[:, n_q:n_q + n_kv].reshape(T, Hkv, Dh), p["kn"][layer],
             d["eps"])
    if d["kinds"][layer] == "sliding_attention":
        q = _rotary(q, positions, d["inv_freq"], 1.0)
        k = _rotary(k, positions, d["inv_freq"], 1.0)
    return (q.astype(x.dtype), k.reshape(T, n_kv),
            y[:, n_q + n_kv:n_q + 2 * n_kv],
            jax.nn.sigmoid(y[:, n_q + 2 * n_kv:]))


def _attn_out(d, p, lp, layer, x, o, gate):
    """``x + norm_post_attn((gate * o) W_o)``."""
    import jax.numpy as jnp

    y = _mm(o.reshape(x.shape[0], -1) * gate, lp["wo"])
    return (x.astype(jnp.float32)
            + _rms(y, p["ln_post_attn"][layer], d["eps"])).astype(x.dtype)


def _ffn(d, p, lp, layer, h, token_mask):
    """``(h + norm_post_mlp(FFN(norm_pre_mlp(h))), counts [3] or None, chosen
    [T, k] or None)``: the dense SwiGLU in a leading layer, the shared expert
    and the held experts' part after them."""
    import jax
    import jax.numpy as jnp

    from ..parallel.moe import moe_topk

    act = h.dtype
    u = _rms(h, p["ln_pre_mlp"][layer], d["eps"]).astype(act)
    counts = chosen = None
    if layer < d["n_dense"]:
        with jax.named_scope("dense_ffn"):
            gu = _mm(u, lp["d_gu"])
            m = _mm(jax.nn.silu(gu[:, :d["F"]]) * gu[:, d["F"]:],
                    lp["d_down"])
    else:
        row = layer - d["n_dense"]
        with jax.named_scope("experts"):
            m, counts, chosen = moe_topk(
                u, {"w": p["router_w"][row], "bias": p["router_b"][row]},
                {"w_gu": p["e_gu"], "w_down": p["e_down"]},
                {"w_gu": lp["s_gu"], "w_down": lp["s_down"]}, top_k=d["k"],
                experts_held=d["held"], scale=d["scale"], scoring="sigmoid",
                token_mask=token_mask, layer=row)
    out = (h.astype(jnp.float32)
           + _rms(m, p["ln_post_mlp"][layer], d["eps"])).astype(act)
    return out, counts, chosen


def _step_counts(d, counts, n_rows, full_tokens, window_tokens):
    """``STEP_COUNTERS`` of one program: the pairs its ``n_rows`` real rows
    were routed to over all experts, the pairs of the experts held here and
    the held experts that took one (each summed over the expert layers), and
    the cached positions a full and a sliding layer
    are entitled to read, times the layers of the kind."""
    import jax.numpy as jnp

    n_full = d["kinds"].count("full_attention")
    return jnp.stack([
        n_rows * (d["k"] * (d["L"] - d["n_dense"])), counts[0], counts[1],
        full_tokens * n_full,
        window_tokens * (d["L"] - n_full)]).astype(jnp.int32)


def prefill_chunk(p, tokens, start, valid, cache, chunk_pages, gather_pages,
                  slot, *, cfg, with_routing=False):
    """One chunk of one sequence's prefill (the ``DecodeModel`` contract of a
    model with page groups whose chunk program counts too): every layer
    scatters the chunk's K and V rows into its group's ``chunk_pages`` and
    attends over the group's ``gather_pages`` (its own rows included) causally by
    position, a sliding layer no further back than its window through the
    group's ring; padding rows route to no expert.  Returns ``(last_logits
    [V], cache', counts [5])`` — ``STEP_COUNTERS`` of the chunk (its rows
    share the keys they read: a full layer reads positions ``0 .. start +
    valid - 1`` ONCE, a sliding one the last ``valid + W - 1`` of them); with
    ``with_routing`` also the experts each expert layer chose ``[C, k]``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_gqa_prefill_attention

    d = _dims(cfg)
    cache = dict(cache)
    C = tokens.shape[0]
    positions = start + jnp.arange(C, dtype=jnp.int32)
    real = jnp.arange(C) < valid
    x = _embed(d, p, tokens)
    counts = jnp.zeros((3,), jnp.int32)
    routing = []
    for layer, lp in enumerate(p["layers"]):
        kind = d["kinds"][layer]
        group, kn, vn = GROUPS[kind]
        row = d["row"][layer]
        with jax.named_scope(_scope(kind)):
            q, k, v, gate = _attn_in(d, p, lp, layer, x, positions)
            ps = cache[kn].shape[2]
            cache[kn] = cache[kn].at[row, chunk_pages[group]].set(
                k.reshape(C // ps, ps, -1).astype(cache[kn].dtype))
            cache[vn] = cache[vn].at[row, chunk_pages[group]].set(
                v.reshape(C // ps, ps, -1).astype(cache[vn].dtype))
            o = paged_gqa_prefill_attention(
                q, cache[kn], cache[vn], gather_pages[group], start, valid,
                layer=row, sm_scale=d["sm_scale"],
                window=d["W"] if kind == "sliding_attention" else None)
            h = _attn_out(d, p, lp, layer, x, o, gate)
        x, c, chosen = _ffn(d, p, lp, layer, h, real)
        if c is not None:
            counts = counts + c
            routing.append(chosen)
    last = jax.lax.dynamic_index_in_dim(x, valid - 1, axis=0, keepdims=False)
    end = start + valid
    out = (_logits(d, p, last), cache, _step_counts(
        d, counts, valid, end, jnp.minimum(end, valid + d["W"] - 1)))
    return out + (routing,) if with_routing else out


def decode_step(p, tokens, positions, cache, page_tables, kv_lens, *, cfg,
                with_routing=False):
    """One token per slot (the ``DecodeModel`` contract of a model with page
    groups: ``page_tables`` is ``{group: [S, width]}``, the window group's a
    ring): every layer writes the token's K and V row on its group's page of
    ``positions`` and attends over the slot's first ``kv_lens`` rows, a
    sliding layer over the last ``sliding_window`` of them; slots that do not
    decode (``kv_lens == 0``) write to scratch and route to no expert.
    Returns ``(logits [S, V], cache', counts [5])`` — ``STEP_COUNTERS``; with
    ``with_routing`` also the experts each expert layer chose ``[S, k]``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_gqa_decode_attention

    d = _dims(cfg)
    cache = dict(cache)
    S = tokens.shape[0]
    live = kv_lens > 0
    x = _embed(d, p, tokens)
    counts = jnp.zeros((3,), jnp.int32)
    routing = []
    where = {}
    for kind in (k for k in KINDS if k in d["kinds"]):
        group, kn, _ = GROUPS[kind]
        ps, table = cache[kn].shape[2], page_tables[group]
        # the group's page of the token: logical page p in column p % width
        where[kind] = (table[jnp.arange(S), (positions // ps)
                             % table.shape[1]], positions % ps)
    for layer, lp in enumerate(p["layers"]):
        kind = d["kinds"][layer]
        group, kn, vn = GROUPS[kind]
        row = d["row"][layer]
        pages, offsets = where[kind]
        with jax.named_scope(_scope(kind)):
            q, k, v, gate = _attn_in(d, p, lp, layer, x, positions)
            cache[kn] = cache[kn].at[row, pages, offsets].set(
                k.astype(cache[kn].dtype))
            cache[vn] = cache[vn].at[row, pages, offsets].set(
                v.astype(cache[vn].dtype))
            o = paged_gqa_decode_attention(
                q, cache[kn], cache[vn], page_tables[group], kv_lens,
                layer=row, sm_scale=d["sm_scale"],
                window=d["W"] if kind == "sliding_attention" else None)
            h = _attn_out(d, p, lp, layer, x, o, gate)
        x, c, chosen = _ffn(d, p, lp, layer, h, live)
        if c is not None:
            counts = counts + c
            routing.append(chosen)
    out = (_logits(d, p, x), cache, _step_counts(
        d, counts, live.sum(), kv_lens.sum(),
        jnp.minimum(kv_lens, d["W"]).sum()))
    return out + (routing,) if with_routing else out


def build_decode_model(weights, cfg, eos_id=None):
    """An AFMoE share behind ``InferenceEngine`` -> ``DecodeScheduler``:
    ``weights`` from :func:`params` (or :func:`take_share`).  Its cache is two
    page groups, one with a window, so the prefix cache, sessions and roles
    refuse it (``DecodeScheduler``); its chunk program counts what its decode
    step counts (the third value both return)."""
    from ..serving.decode_scheduler import DecodeModel

    _dims(cfg)
    return DecodeModel(
        functools.partial(decode_step, cfg=cfg),
        functools.partial(prefill_chunk, cfg=cfg),
        params=weights, vocab_size=cfg["vocab_size"], eos_id=eos_id,
        name="afmoe", step_counters=STEP_COUNTERS,
        **cache_layout(cfg))
