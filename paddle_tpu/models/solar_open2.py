"""The Solar Open 2 family (``model_type`` ``solar_open2``; upstage's
Solar-Open2-250B) as a served ``DecodeModel``: a pre-norm RMSNorm decoder whose
layers mix TWO sequence mixers and whose every feed-forward block is a SHARE of
a routed expert layer.

* ``gqa_layers`` — softmax attention: ``Hq`` query heads over ``Hkv`` KV heads
  (query head ``i`` reads KV head ``i // g``), NO positional rotation
  (``use_rope`` false), scale ``1 / sqrt(head_dim)``, causal, an elementwise
  sigmoid output gate (``use_gqa_gate``).  K and V rows in the paged pools
  (``parallel/flash_attention.py``: ``paged_gqa_*_attention``).
* every other layer — Kimi Delta Attention (arXiv:2510.26692;
  ``linear_attn_config``): q, k, v through a depthwise causal convolution of
  ``short_conv_kernel_size`` taps and SiLU, q and k L2-normalised a head, a
  log-decay a key CHANNEL ``g = -exp(A_log) softplus(W_f2 W_f1 h + dt_bias)``,
  a write strength ``beta = 2 sigmoid(W_b h)`` (``kda_allow_neg_eigval``), the
  gated delta rule on a ``[d_k, d_v]`` float32 state a head, a per-head RMSNorm
  and a low-rank sigmoid gate on the way out (``parallel/kda.py``).  The state
  and the convolution's last ``K - 1`` inputs are TWO slot-indexed leaves of
  the cache (``kda [L_kda, slots, H, d, d]`` float32, ``conv [L_kda, slots, K -
  1, 3 H d]``), taken as zero by a sequence's first chunk and carried from
  chunk to chunk and step to step.
* experts (``parallel/moe.py``: ``moe_topk``): sigmoid scores over ALL
  ``router_experts`` in float32, ``e_score_correction_bias`` for the choice
  only, the ``num_experts_per_tok`` best, weights renormalised, times
  ``routed_scaling_factor``; this holder computes the pairs of
  ``experts_held = (lo, hi)`` and adds the shared expert; what the other
  holders would add is left out, and that partial sum goes on.

The equations and every assumed size are in the plain reference,
``chipbench/configs/solar_open2_250b.reference.py``; ``cfg`` is the
configuration in the family's own key names plus ``router_experts`` (the
router's width) and ``experts_held``.  Precision, ``_rms``, ``_mm``, ``_logits``
and the weights-as-arguments contract are ``models/minicpm_sala.py``'s; the
router, norms, softmax, decay and the delta-rule state are float32.
"""
from __future__ import annotations

import functools
import math

from .minicpm_sala import _logits, _mm, _rms

__all__ = ["params", "prefill_chunk", "decode_step", "build_decode_model",
           "cache_layout", "take_share", "STEP_COUNTERS"]

STEP_COUNTERS = ("kda.slot_updates", "kv.full_tokens_read", "moe.pairs",
                 "moe.experts_touched", "moe.max_load", "moe.pairs_elsewhere")
L2_EPS = 1e-6


def _dims(cfg):
    for key, want in (("use_rope", False), ("use_gqa_gate", True),
                      ("kda_use_full_proj", False), ("norm_topk_prob", True),
                      ("first_k_dense_replace", 0),
                      ("tie_word_embeddings", False)):
        if cfg.get(key, want) != want:
            raise ValueError("%s = %r is not written here (only %r)"
                             % (key, cfg[key], want))
    lin = cfg["linear_attn_config"]
    L = cfg["num_hidden_layers"]
    gqa = sorted(int(i) for i in cfg["gqa_layers"])
    if not gqa or gqa[0] < 0 or gqa[-1] >= L:
        raise ValueError("gqa_layers %s must name at least one of the %d "
                         "layers" % (gqa, L))
    lo, hi = (int(e) for e in cfg["experts_held"])
    E = int(cfg["router_experts"])
    if not 0 <= lo < hi <= E or hi - lo != cfg["n_routed_experts"]:
        raise ValueError(
            "experts_held %s must be n_routed_experts = %d of the router's %d"
            % ((lo, hi), cfg["n_routed_experts"], E))
    kinds = ["gqa" if i in gqa else "kda" for i in range(L)]
    d = dict(
        D=cfg["hidden_size"], L=L, V=cfg["vocab_size"],
        eps=cfg["rms_norm_eps"], H=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], Dh=cfg["head_dim"],
        Hl=lin["num_heads"], Dl=lin["head_dim"],
        K=lin["short_conv_kernel_size"], Fm=cfg["moe_intermediate_size"],
        k=cfg["num_experts_per_tok"], E=E, held=(lo, hi),
        Fs=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        scale=float(cfg["routed_scaling_factor"]), kinds=kinds,
        rank=int(cfg.get("kda_gate_rank", lin["head_dim"])),
        beta=2.0 if cfg["kda_allow_neg_eigval"] else 1.0,
        resid=1.0, logit_div=1.0)
    d["N"] = d["Hl"] * d["Dl"]
    d["n_kda"] = kinds.count("kda")
    d["n_gqa"] = L - d["n_kda"]
    # a layer's index among the layers of its kind (its row of the leaves)
    d["row"] = [kinds[:i].count(kind) for i, kind in enumerate(kinds)]
    return d


def cache_layout(cfg):
    """What the model keeps in the cache, as ``DecodeModel`` states it: the
    softmax layers' paged K/V and the two slot-indexed leaves of the
    delta-rule layers."""
    d = _dims(cfg)
    out = dict(num_layers=d["n_gqa"], num_heads=d["Hkv"], head_dim=d["Dh"])
    if d["n_kda"]:
        out["slot_state"] = {
            "kda": dict(layers=d["n_kda"], shape=(d["Hl"], d["Dl"], d["Dl"]),
                        dtype="float32"),
            "conv": dict(layers=d["n_kda"], shape=(d["K"] - 1, 3 * d["N"]),
                         dtype=cfg.get("conv_state_dtype", "bfloat16"))}
    return out


def params(cfg, seed, dtype="bfloat16"):
    """Seeded random weights as device arrays of ``dtype`` (vectors, routers
    and the convolution's taps float32): normal(0, 1 / fan_in) matrices, norm
    weights around one, the selection bias normal(0, 0.02); ``A_log`` = log of
    uniform(1, 16) a head and ``dt_bias`` the inverse softplus of a step
    log-uniform in 0.001 .. 0.1 a channel (the ranges the KDA paper
    initialises).  Made on the device; the expert stacks a layer at a time
    into a donated buffer."""
    import jax
    import jax.numpy as jnp

    from ..core import cpu_backend

    d = _dims(cfg)
    dt = jnp.dtype(dtype)
    D, L, N, r = d["D"], d["L"], d["N"], d["rank"]
    n_held = d["held"][1] - d["held"][0]
    f32 = jnp.float32

    def mat(key, shape, fan_in):
        return (jax.random.normal(key, shape, f32)
                / math.sqrt(fan_in)).astype(dt)

    def make(key):
        keys = iter(jax.random.split(key, 16 + 16 * L))

        def vec(*shape):
            return 1.0 + 0.1 * jax.random.normal(next(keys), shape, f32)

        def layer(kind):
            lp = {"s_gu": mat(next(keys), (D, 2 * d["Fs"]), D),
                  "s_down": mat(next(keys), (d["Fs"], D), d["Fs"])}
            if kind == "gqa":
                n_q, n_kv = d["H"] * d["Dh"], d["Hkv"] * d["Dh"]
                lp.update(w_in=mat(next(keys), (D, 2 * n_q + 2 * n_kv), D),
                          wo=mat(next(keys), (n_q, D), n_q))
                return lp
            step = jnp.exp(jax.random.uniform(
                next(keys), (N,), f32, math.log(1e-3), math.log(1e-1)))
            lp.update(
                w_qkv=mat(next(keys), (D, 3 * N), D),
                w_low=mat(next(keys), (D, 2 * r + d["Hl"]), D),
                w_f2=mat(next(keys), (r, N), r),
                w_g2=mat(next(keys), (r, N), r),
                wo=mat(next(keys), (N, D), N),
                conv_w=jax.random.normal(next(keys), (d["K"], 3 * N), f32)
                / math.sqrt(d["K"]),
                A_log=jnp.log(jax.random.uniform(
                    next(keys), (d["Hl"],), f32, 1.0, 16.0)),
                dt_bias=step + jnp.log(-jnp.expm1(-step)),
                o_norm=vec(d["Dl"]))
            return lp

        return {
            "embed": mat(next(keys), (d["V"], D), 1.0),
            "head": mat(next(keys), (D, d["V"]), D),
            "norm_f": vec(D), "ln1": vec(L, D), "ln2": vec(L, D),
            "router_w": jax.random.normal(next(keys), (L, D, d["E"]), f32)
            / math.sqrt(D),
            "router_b": 0.02 * jax.random.normal(next(keys), (L, d["E"]),
                                                 f32),
            "layers": [layer(kind) for kind in d["kinds"]],
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
        stack = jnp.zeros((L,) + shape, dt)
        for i in range(L):
            stack = put(stack, jax.random.fold_in(root, 64 * salt + i), i)
        out[name] = stack
    return out


def take_share(weights, cfg, experts_held, vocab=None):
    """``(weights', cfg')`` of one holder of an expert-parallel,
    vocabulary-parallel split of ``weights`` (made for ``cfg``, which holds
    every expert): the experts ``lo .. hi - 1`` of every layer and, with
    ``vocab = (lo, hi)``, those rows of the embedding and columns of the head.
    The router keeps its whole width: every holder scores all experts."""
    lo, hi = experts_held
    at = cfg["experts_held"][0]
    out = dict(weights, e_gu=weights["e_gu"][:, lo - at:hi - at],
               e_down=weights["e_down"][:, lo - at:hi - at])
    cut = dict(cfg, experts_held=[lo, hi], n_routed_experts=hi - lo)
    if vocab is not None:
        out.update(embed=weights["embed"][vocab[0]:vocab[1]],
                   head=weights["head"][:, vocab[0]:vocab[1]])
        cut["vocab_size"] = vocab[1] - vocab[0]
    return out, cut


# -- the layer ----------------------------------------------------------------

def _experts(d, p, lp, layer, h, token_mask):
    """``(h + shared(u) + held experts(u), counts [3], chosen [T, k])``, ``u =
    norm2(h)``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.moe import moe_topk

    act = h.dtype
    with jax.named_scope("experts"):
        u = _rms(h, p["ln2"][layer], d["eps"]).astype(act)
        y, counts, chosen = moe_topk(
            u, {"w": p["router_w"][layer], "bias": p["router_b"][layer]},
            {"w_gu": p["e_gu"], "w_down": p["e_down"]},
            {"w_gu": lp["s_gu"], "w_down": lp["s_down"]}, top_k=d["k"],
            experts_held=d["held"], scale=d["scale"], scoring="sigmoid",
            token_mask=token_mask, layer=layer)
        return (h.astype(jnp.float32) + y).astype(act), counts, chosen


def _gqa_in(d, p, lp, layer, x):
    """The softmax layer's queries ``[T, Hq, Dh]`` (the activations' dtype),
    the K and V rows ``[T, Hkv * Dh]`` float32 and the output gate ``[T, Hq *
    Dh]`` float32: no rotation of any of them."""
    import jax

    n_q, n_kv = d["H"] * d["Dh"], d["Hkv"] * d["Dh"]
    y = _mm(_rms(x, p["ln1"][layer], d["eps"]), lp["w_in"])
    q = y[:, :n_q].reshape(-1, d["H"], d["Dh"]).astype(x.dtype)
    return (q, y[:, n_q:n_q + n_kv], y[:, n_q + n_kv:n_q + 2 * n_kv],
            jax.nn.sigmoid(y[:, n_q + 2 * n_kv:]))


def _mixer_out(lp, x, o):
    """``x + o W_o``: the residual around a mixer, ``o [T, width]`` float32."""
    import jax.numpy as jnp

    return (x.astype(jnp.float32) + _mm(o, lp["wo"])).astype(x.dtype)


def _kda_in(d, p, lp, layer, x, conv_dtype):
    """What a delta-rule layer projects from its input rows ``x [T, D]``: the
    convolution's inputs ``[T, 3N]`` (q | k | v, rounded to what the
    convolution state keeps), the log-decay ``g [T, H, d]``, ``beta [T, H]``
    and the output gate ``[T, N]``, float32."""
    import jax
    import jax.numpy as jnp

    r, Hl, Dl = d["rank"], d["Hl"], d["Dl"]
    u = _rms(x, p["ln1"][layer], d["eps"])
    xp = _mm(u, lp["w_qkv"]).astype(conv_dtype).astype(jnp.float32)
    low = _mm(u, lp["w_low"])
    decay = jax.nn.softplus(_mm(low[:, :r], lp["w_f2"]) + lp["dt_bias"])
    g = -jnp.exp(lp["A_log"])[None, :, None] * decay.reshape(-1, Hl, Dl)
    beta = d["beta"] * jax.nn.sigmoid(low[:, 2 * r:])
    gate = jax.nn.sigmoid(_mm(low[:, r:2 * r], lp["w_g2"]))
    return xp, g, beta, gate


def _l2(x):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _kda_qkv(d, y):
    """q, k (L2-normalised a head) and v ``[T, H, d]`` from the convolution's
    outputs ``y [T, 3N]``."""
    import jax

    N = d["N"]
    y = jax.nn.silu(y)
    q, k, v = (y[:, i * N:(i + 1) * N].reshape(-1, d["Hl"], d["Dl"])
               for i in range(3))
    return _l2(q), _l2(k), v


def _kda_out(d, lp, x, o, gate):
    """The read-out ``o [T, H, d]`` scaled, normalised a head and gated, then
    ``W_o`` and the residual."""
    o = _rms(o / math.sqrt(d["Dl"]), lp["o_norm"], d["eps"])
    return _mixer_out(lp, x, o.reshape(x.shape[0], -1) * gate)


def _gqa_chunk_layer(d, p, lp, layer, x, cache, chunk_pages, gather_pages,
                     start, valid):
    """A softmax layer over one chunk: ``(x + mixer, cache')``."""
    import jax

    from ..parallel.flash_attention import paged_gqa_prefill_attention

    row, C = d["row"][layer], x.shape[0]
    with jax.named_scope("gqa"):
        q, k, v, gate = _gqa_in(d, p, lp, layer, x)
        ps = cache["k"].shape[2]
        for name, rows in (("k", k), ("v", v)):
            cache[name] = cache[name].at[row, chunk_pages].set(
                rows.reshape(C // ps, ps, -1).astype(cache[name].dtype))
        o = paged_gqa_prefill_attention(
            q, cache["k"], cache["v"], gather_pages, start, valid, layer=row)
        return _mixer_out(lp, x, o.reshape(C, -1) * gate), cache


def _gqa_decode_layer(d, p, lp, layer, x, cache, page_tables, kv_lens, pages,
                      offsets):
    """A softmax layer over one token a slot: ``(x + mixer, cache')``."""
    import jax

    from ..parallel.flash_attention import paged_gqa_decode_attention

    row = d["row"][layer]
    with jax.named_scope("gqa"):
        q, k, v, gate = _gqa_in(d, p, lp, layer, x)
        for name, rows in (("k", k), ("v", v)):
            cache[name] = cache[name].at[row, pages, offsets].set(
                rows.astype(cache[name].dtype))
        o = paged_gqa_decode_attention(
            q, cache["k"], cache["v"], page_tables, kv_lens, layer=row)
        return _mixer_out(lp, x, o.reshape(x.shape[0], -1) * gate), cache


def _kda_chunk_layer(d, p, lp, layer, x, cache, slot, fresh, valid):
    """A delta-rule layer over one chunk of the sequence seated in ``slot``
    (``fresh``: its first chunk, both leaves taken as zero): ``(x + mixer,
    cache')`` with both leaves as they stand after row ``valid - 1``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.kda import kda_chunk

    row, C, K = d["row"][layer], x.shape[0], d["K"]
    with jax.named_scope("kda"):
        conv = cache["conv"]
        xp, g, beta, gate = _kda_in(d, p, lp, layer, x, conv.dtype)
        before = jnp.where(fresh, 0.0, conv[row, slot].astype(jnp.float32))
        ext = jnp.concatenate([before, xp], axis=0)              # [K-1+C, 3N]
        y = sum(lp["conv_w"][j] * ext[j:j + C] for j in range(K))
        cache["conv"] = conv.at[row, slot].set(
            jax.lax.dynamic_slice_in_dim(ext, valid, K - 1, axis=0)
            .astype(conv.dtype))
        q, k, v = _kda_qkv(d, y)
        s0 = jnp.where(fresh, 0.0, cache["kda"][row, slot])
        o, s1 = kda_chunk(q, k, v, g, beta, s0, valid)
        cache["kda"] = cache["kda"].at[row, slot].set(s1)
        return _kda_out(d, lp, x, o, gate), cache


def _kda_decode_layer(d, p, lp, layer, x, cache, live):
    """A delta-rule layer over one token a slot: ``(x + mixer, cache')``;
    slots that are not ``live`` keep both leaves."""
    import jax
    import jax.numpy as jnp

    from ..parallel.kda import kda_state_decode

    row, K = d["row"][layer], d["K"]
    with jax.named_scope("kda"):
        conv = cache["conv"]
        xp, g, beta, gate = _kda_in(d, p, lp, layer, x, conv.dtype)
        old = conv[row]                                          # [S, K-1, 3N]
        ext = jnp.concatenate([old.astype(jnp.float32), xp[:, None]], axis=1)
        y = sum(lp["conv_w"][j] * ext[:, j] for j in range(K))
        cache["conv"] = conv.at[row].set(jnp.where(
            live[:, None, None], ext[:, 1:].astype(conv.dtype), old))
        q, k, v = _kda_qkv(d, y)
        o, cache["kda"] = kda_state_decode(
            cache["kda"], q, k, v, g, beta, live, layer=row)
        return _kda_out(d, lp, x, o, gate), cache


def prefill_chunk(p, tokens, start, valid, cache, chunk_pages, gather_pages,
                  slot, *, cfg, with_routing=False):
    """One chunk of one sequence's prefill (the ``DecodeModel`` contract): a
    softmax layer scatters the chunk's K and V rows into ``chunk_pages`` and
    attends over ``gather_pages`` causally by position; a delta-rule layer
    reads the state and the convolution's last inputs at ``slot`` (ZERO where
    ``start == 0``: the reset of a reused slot), runs the chunk-wise
    recurrence and writes both back as they stand after row ``valid - 1``;
    padding rows route to no expert.  Returns ``(last_logits [V], cache')``;
    with ``with_routing`` also the experts each layer chose ``[C, k]``."""
    import jax
    import jax.numpy as jnp

    d = _dims(cfg)
    cache = dict(cache)
    real = jnp.arange(tokens.shape[0]) < valid
    x = p["embed"][tokens]
    routing = []
    for layer, lp in enumerate(p["layers"]):
        if d["kinds"][layer] == "gqa":
            h, cache = _gqa_chunk_layer(d, p, lp, layer, x, cache, chunk_pages,
                                        gather_pages, start, valid)
        else:
            h, cache = _kda_chunk_layer(d, p, lp, layer, x, cache, slot,
                                        start == 0, valid)
        x, _, chosen = _experts(d, p, lp, layer, h, real)
        routing.append(chosen)
    last = jax.lax.dynamic_index_in_dim(x, valid - 1, axis=0, keepdims=False)
    out = (_logits(d, p, last), cache)
    return out + (routing,) if with_routing else out


def decode_step(p, tokens, positions, cache, page_tables, kv_lens, *, cfg,
                with_routing=False):
    """One token per slot (the ``DecodeModel`` contract): a softmax layer
    writes the token's K and V row on the page of ``positions`` and attends
    over the slot's first ``kv_lens`` rows; a delta-rule layer shifts the
    token into the convolution's inputs and applies one step of the gated
    delta rule to the state, in place (``kda_state_decode``); slots that do
    not decode (``kv_lens == 0``) write K/V to scratch, keep both state
    leaves and route to no expert.  Returns ``(logits [S, V], cache', counts
    [6])`` — ``STEP_COUNTERS``: slot x layer state updates, the cached
    positions the softmax layers are entitled to read, the (token, expert)
    pairs computed HERE, the held experts that took one, the largest held
    expert's pairs (each summed over the layers) and the pairs left to the
    other holders; with ``with_routing`` also the experts each layer chose
    ``[S, k]``."""
    import jax.numpy as jnp

    d = _dims(cfg)
    cache = dict(cache)
    S = tokens.shape[0]
    live = kv_lens > 0
    x = p["embed"][tokens]
    counts = jnp.zeros((3,), jnp.int32)
    routing = []
    ps = cache["k"].shape[2]
    pages = page_tables[jnp.arange(S), positions // ps]
    for layer, lp in enumerate(p["layers"]):
        if d["kinds"][layer] == "gqa":
            h, cache = _gqa_decode_layer(d, p, lp, layer, x, cache,
                                         page_tables, kv_lens, pages,
                                         positions % ps)
        else:
            h, cache = _kda_decode_layer(d, p, lp, layer, x, cache, live)
        x, c, chosen = _experts(d, p, lp, layer, h, live)
        counts = counts + c
        routing.append(chosen)
    n_live = live.sum().astype(jnp.int32)
    step = jnp.stack([
        n_live * d["n_kda"], kv_lens.sum().astype(jnp.int32) * d["n_gqa"],
        counts[0], counts[1], counts[2],
        n_live * (d["k"] * d["L"]) - counts[0]]).astype(jnp.int32)
    out = (_logits(d, p, x), cache, step)
    return out + (routing,) if with_routing else out


def build_decode_model(weights, cfg, eos_id=None):
    """A Solar Open 2 share behind ``InferenceEngine`` -> ``DecodeScheduler``:
    ``weights`` from :func:`params` (or :func:`take_share`).  It keeps two
    slot-indexed leaves, so the prefix cache, sessions and roles refuse it
    (``DecodeScheduler``)."""
    from ..serving.decode_scheduler import DecodeModel

    _dims(cfg)
    return DecodeModel(
        functools.partial(decode_step, cfg=cfg),
        functools.partial(prefill_chunk, cfg=cfg),
        params=weights, vocab_size=cfg["vocab_size"], eos_id=eos_id,
        name="solar-open2", step_counters=STEP_COUNTERS, **cache_layout(cfg))
