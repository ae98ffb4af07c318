"""The Mellum family (``model_type`` ``mellum``; JetBrains'
Mellum2-12B-A2.5B-Instruct) as a served ``DecodeModel``: a pre-norm RMSNorm
decoder with grouped-query attention of TWO kinds, layer by layer
(``layer_types``), and a sparse expert block in every layer.

* **Attention**: ``Hq`` query heads over ``Hkv`` KV heads (query head ``i``
  reads KV head ``i // g``), rotate-half rotary on the whole head, scale
  ``1 / sqrt(head_dim)``, causal.  A ``sliding_attention`` layer's query at
  position ``t`` sees keys ``t - sliding_window + 1 .. t`` and uses plain
  rotary; a ``full_attention`` layer sees ``0 .. t`` and uses YaRN: the
  inverse frequencies blended between ``f_i / factor`` and ``f_i`` by a ramp
  over the pair index, and ``cos`` / ``sin`` multiplied by
  ``attention_factor`` on q and on k (:func:`rope_inverse_frequencies`;
  computed once on the host in float64 from ``rope_parameters``).  The config
  names no QK-norm and no attention gate: none is written.
* **The cache** is in two page GROUPS (``serving/kv_cache.py``): ``k_full`` /
  ``v_full`` hold the full layers' rows for the life of the sequence,
  ``k_win`` / ``v_win`` the sliding layers' last ``sliding_window`` positions
  on pages that go back to the allocator as they fall out of it, so the step
  functions take a page table a group and the window group's is a ring
  (``parallel/flash_attention.py``: ``paged_gqa_*_attention``).
* **Experts** (``parallel/moe.py``: ``moe_topk``): ``softmax`` over all
  ``num_experts`` router logits in float32, the ``num_experts_per_tok``
  largest chosen, their probabilities renormalised (``norm_topk_prob``), no
  bias, no scaling factor, no shared expert, dropless, every expert held here.

The equations and every assumed size are in the plain reference,
``chipbench/configs/mellum2_12b_a2_5b.reference.py``; ``cfg`` is the
configuration in the family's own key names.  Precision, the shared pieces
(``_rms``, ``_mm``, ``_logits``) and the weights-as-arguments contract are
``models/minicpm_sala.py``'s; the router, norms, rotary and softmax are
float32.

Weights: a layer's matrices are an array each (``w_qkv`` = q | k | v fused
column-wise, ``wo``); the experts are two stacks ``[layers, experts, ...]``
that the grouped matrix product addresses in place; vectors and routers are
stacked by kind.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from .minicpm_sala import _logits, _mm, _rms

__all__ = ["params", "prefill_chunk", "decode_step", "build_decode_model",
           "cache_layout", "group_layout", "rope_inverse_frequencies",
           "STEP_COUNTERS"]

STEP_COUNTERS = ("moe.pairs", "moe.experts_touched", "moe.max_load",
                 "kv.full_tokens_read", "kv.window_tokens_read")
KINDS = ("full_attention", "sliding_attention")
# the page group and the leaves of each kind of layer
GROUPS = {"full_attention": ("full", "k_full", "v_full"),
          "sliding_attention": ("window", "k_win", "v_win")}


def rope_inverse_frequencies(rope, head_dim):
    """``(inv_freq [head_dim / 2] float32, attention_factor)`` of one entry of
    ``rope_parameters``: ``default`` is ``theta ** (-2i / d)``; ``yarn``
    blends ``f_i / factor`` (pairs that turn fewer than ``beta_slow`` times
    over the original context) with ``f_i`` (more than ``beta_fast`` times)
    by a linear ramp over the pair index between them.  Float64 on the host,
    rounded once."""
    d, theta = int(head_dim), float(rope["rope_theta"])
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return f.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError("rope_type %r is not written here (default, yarn)"
                         % (kind,))
    s = float(rope["factor"])
    ctx = float(rope["original_max_position_embeddings"])

    def dim(beta):
        return d * math.log(ctx / (2 * math.pi * beta)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(rope["beta_slow"]))), d // 2 - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    factor = rope.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(s) + 1.0
    return (f / s * ramp + f * (1 - ramp)).astype(np.float32), float(factor)


def _dims(cfg):
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("norm_topk_prob", True),
                      ("tie_word_embeddings", False)):
        if cfg.get(key, want) != want:
            raise ValueError("%s = %r is not written here (only %r)"
                             % (key, cfg[key], want))
    L = cfg["num_hidden_layers"]
    kinds = list(cfg["layer_types"])
    if len(kinds) != L or set(kinds) - set(KINDS) or set(
            cfg["mlp_layer_types"]) != {"sparse"} or len(
                cfg["mlp_layer_types"]) != L:
        raise ValueError(
            "layer_types / mlp_layer_types name %d layers of %s / sparse; "
            "got %s / %s" % (L, KINDS, kinds, cfg["mlp_layer_types"]))
    d = dict(
        D=cfg["hidden_size"], Fm=cfg["moe_intermediate_size"],
        V=cfg["vocab_size"], H=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], Dh=cfg["head_dim"], L=L,
        E=cfg["num_experts"], k=cfg["num_experts_per_tok"],
        eps=cfg["rms_norm_eps"], W=int(cfg["sliding_window"]),
        kinds=kinds, resid=1.0, logit_div=1.0)
    d["sm_scale"] = 1.0 / math.sqrt(d["Dh"])
    # a layer's index among the layers of its kind (its row of the leaf)
    d["row"] = [kinds[:i].count(kind) for i, kind in enumerate(kinds)]
    d["rope"] = {kind: rope_inverse_frequencies(cfg["rope_parameters"][kind],
                                                d["Dh"])
                 for kind in KINDS if kind in kinds}
    return d


def group_layout(kinds, window, width):
    """The page groups and leaves of a model whose ``kinds`` of layers keep K
    and V rows of ``width`` values: each kind's in a group of its own, the
    sliding layers' with ``window`` (``DecodeModel``'s ``page_groups`` and
    ``page_pools``)."""
    groups, pools = {}, {}
    for kind in KINDS:           # the full group first: the cache's own
        n = list(kinds).count(kind)
        if not n:
            continue
        group, k, v = GROUPS[kind]
        groups[group] = dict(
            window=window if kind == "sliding_attention" else None)
        for leaf in (k, v):
            pools[leaf] = dict(layers=n, tokens_per_row=1, width=width,
                               dtype=None, group=group)
    if "full" not in groups:
        raise ValueError("a model of sliding layers alone is not written "
                         "here: the cache's first group keeps every position")
    return dict(page_groups=groups, page_pools=pools)


def cache_layout(cfg):
    """What the model keeps in the cache, as ``DecodeModel`` states it: K and
    V rows of each kind of layer in a page group of its own, the sliding
    layers' with the window."""
    d = _dims(cfg)
    return group_layout(d["kinds"], d["W"], d["Hkv"] * d["Dh"])


def params(cfg, seed, dtype="bfloat16"):
    """Seeded random weights as device arrays of ``dtype`` (vectors and the
    routers float32): normal(0, 1 / fan_in) matrices, norm weights around
    one.  Made on the device; the expert stacks a layer at a time into a
    donated buffer, so nothing larger than a layer's experts in float32 is
    ever a temporary."""
    import jax
    import jax.numpy as jnp

    from ..core import cpu_backend

    d = _dims(cfg)
    dt = jnp.dtype(dtype)
    D, L = d["D"], d["L"]
    n_qkv = (d["H"] + 2 * d["Hkv"]) * d["Dh"]

    def mat(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    def make(key):
        keys = iter(jax.random.split(key, 8 + 2 * L))

        def vec(*shape):
            return 1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                 jnp.float32)

        return {
            "embed": mat(next(keys), (d["V"], D), 1.0),
            "head": mat(next(keys), (D, d["V"]), D),
            "norm_f": vec(D), "ln1": vec(L, D), "ln2": vec(L, D),
            "router_w": jax.random.normal(
                next(keys), (L, D, d["E"]), jnp.float32) / math.sqrt(D),
            "layers": [{"w_qkv": mat(next(keys), (D, n_qkv), D),
                        "wo": mat(next(keys), (d["H"] * d["Dh"], D),
                                  d["H"] * d["Dh"])} for _ in range(L)],
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
        stack = jnp.zeros((L,) + shape, dt)
        for i in range(L):
            stack = put(stack, jax.random.fold_in(root, 64 * salt + i), i)
        out[name] = stack
    return out


# -- the layer ----------------------------------------------------------------

def _rotary(x, positions, inv_freq, factor):
    """Rotate-half rotary of ``x [T, H, Dh]`` float32 at ``positions [T]``
    with the kind's inverse frequencies, ``cos`` / ``sin`` times ``factor``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _qkv(d, p, lp, layer, x, positions):
    """A layer's rotated queries ``[T, Hq, Dh]`` (the activations' dtype) and
    the K and V rows ``[T, Hkv * Dh]`` (float32) its tokens add to the
    cache."""
    T = x.shape[0]
    H, Hkv, Dh = d["H"], d["Hkv"], d["Dh"]
    inv_freq, factor = d["rope"][d["kinds"][layer]]
    y = _mm(_rms(x, p["ln1"][layer], d["eps"]), lp["w_qkv"])
    q = _rotary(y[:, :H * Dh].reshape(T, H, Dh), positions, inv_freq, factor)
    k = _rotary(y[:, H * Dh:(H + Hkv) * Dh].reshape(T, Hkv, Dh), positions,
                inv_freq, factor)
    return q.astype(x.dtype), k.reshape(T, Hkv * Dh), y[:, (H + Hkv) * Dh:]


def _experts(d, p, layer, h, token_mask):
    """``(h + MoE(norm2(h)), counts [3], chosen [T, k])``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.moe import moe_topk

    act = h.dtype
    with jax.named_scope("moe_experts"):
        u = _rms(h, p["ln2"][layer], d["eps"]).astype(act)
        y, counts, chosen = moe_topk(
            u, {"w": p["router_w"][layer], "bias": None},
            {"w_gu": p["e_gu"], "w_down": p["e_down"]}, None,
            top_k=d["k"], experts_held=(0, d["E"]), scoring="softmax",
            token_mask=token_mask, layer=layer)
        return (h.astype(jnp.float32) + y).astype(act), counts, chosen


def _attn_out(lp, x, o):
    import jax.numpy as jnp

    return (x.astype(jnp.float32) + _mm(
        o.reshape(x.shape[0], -1), lp["wo"])).astype(x.dtype)


def _scope(kind):
    return "full_attention" if kind == "full_attention" else "window_attention"


def prefill_chunk(p, tokens, start, valid, cache, chunk_pages, gather_pages,
                  slot, *, cfg, with_routing=False):
    """One chunk of one sequence's prefill (the ``DecodeModel`` contract of a
    model with page groups: ``chunk_pages`` and ``gather_pages`` are ``{group:
    ..}``): every layer scatters the chunk's K and V rows into its group's
    ``chunk_pages`` and attends over the group's ``gather_pages`` (its own
    rows included) causally by position, a sliding layer no further back than
    its window through the group's ring; padding rows route to no expert.
    Returns ``(last_logits [V], cache')``; with ``with_routing`` also the
    experts each layer chose ``[C, k]``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_gqa_prefill_attention

    d = _dims(cfg)
    cache = dict(cache)
    C = tokens.shape[0]
    positions = start + jnp.arange(C, dtype=jnp.int32)
    real = jnp.arange(C) < valid
    x = p["embed"][tokens]
    routing = []
    for layer, lp in enumerate(p["layers"]):
        kind = d["kinds"][layer]
        group, kn, vn = GROUPS[kind]
        row = d["row"][layer]
        with jax.named_scope(_scope(kind)):
            q, k, v = _qkv(d, p, lp, layer, x, positions)
            ps = cache[kn].shape[2]
            cache[kn] = cache[kn].at[row, chunk_pages[group]].set(
                k.reshape(C // ps, ps, -1).astype(cache[kn].dtype))
            cache[vn] = cache[vn].at[row, chunk_pages[group]].set(
                v.reshape(C // ps, ps, -1).astype(cache[vn].dtype))
            o = paged_gqa_prefill_attention(
                q, cache[kn], cache[vn], gather_pages[group], start, valid,
                layer=row, sm_scale=d["sm_scale"],
                window=d["W"] if kind == "sliding_attention" else None)
            h = _attn_out(lp, x, o)
        x, _, chosen = _experts(d, p, layer, h, real)
        routing.append(chosen)
    last = jax.lax.dynamic_index_in_dim(x, valid - 1, axis=0, keepdims=False)
    out = (_logits(d, p, last), cache)
    return out + (routing,) if with_routing else out


def decode_step(p, tokens, positions, cache, page_tables, kv_lens, *, cfg,
                with_routing=False):
    """One token per slot (the ``DecodeModel`` contract of a model with page
    groups: ``page_tables`` is ``{group: [S, width]}``, the window group's a
    ring): every layer writes the token's K and V row on its group's page of
    ``positions`` and attends over the slot's first ``kv_lens`` rows, a
    sliding layer over the last ``sliding_window`` of them; slots that do not
    decode (``kv_lens == 0``) write to scratch and route to no expert.
    Returns ``(logits [S, V], cache', counts [5])`` — ``STEP_COUNTERS``: the
    (token, expert) pairs computed, the experts that took one, the largest
    expert's pairs (each summed over the layers), and the cached positions a
    step's attention is entitled to read in the full and in the sliding
    layers (summed over slots and the kind's layers); with ``with_routing``
    also the experts each layer chose ``[S, k]``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_gqa_decode_attention

    d = _dims(cfg)
    cache = dict(cache)
    S = tokens.shape[0]
    live = kv_lens > 0
    x = p["embed"][tokens]
    counts = jnp.zeros((3,), jnp.int32)
    routing = []
    where = {}
    for kind in d["rope"]:
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
            q, k, v = _qkv(d, p, lp, layer, x, positions)
            cache[kn] = cache[kn].at[row, pages, offsets].set(
                k.astype(cache[kn].dtype))
            cache[vn] = cache[vn].at[row, pages, offsets].set(
                v.astype(cache[vn].dtype))
            o = paged_gqa_decode_attention(
                q, cache[kn], cache[vn], page_tables[group], kv_lens,
                layer=row, sm_scale=d["sm_scale"],
                window=d["W"] if kind == "sliding_attention" else None)
            h = _attn_out(lp, x, o)
        x, c, chosen = _experts(d, p, layer, h, live)
        counts = counts + c
        routing.append(chosen)
    n_full = d["kinds"].count("full_attention")
    read = jnp.stack([kv_lens.sum() * n_full,
                      jnp.minimum(kv_lens, d["W"]).sum() * (d["L"] - n_full)])
    out = (_logits(d, p, x), cache,
           jnp.concatenate([counts, read.astype(jnp.int32)]))
    return out + (routing,) if with_routing else out


def build_decode_model(weights, cfg, eos_id=None):
    """A Mellum-family model behind ``InferenceEngine`` -> ``DecodeScheduler``:
    ``weights`` from :func:`params` (or a checkpoint in its form).  Its cache
    is two page groups, one with a window, so the prefix cache, sessions and
    roles refuse it (``DecodeScheduler``)."""
    from ..serving.decode_scheduler import DecodeModel

    _dims(cfg)
    return DecodeModel(
        functools.partial(decode_step, cfg=cfg),
        functools.partial(prefill_chunk, cfg=cfg),
        params=weights, vocab_size=cfg["vocab_size"], eos_id=eos_id,
        name="mellum", step_counters=STEP_COUNTERS, **cache_layout(cfg))
