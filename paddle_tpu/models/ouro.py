"""The Ouro family (``model_type`` ``ouro``; ByteDance's Ouro-2.6B, "Scaling
Latent Reasoning via Looped Language Models", arXiv:2510.25741) as a served
``DecodeModel``: a LOOPED language model.  One stack of ``L`` sandwich-norm
decoder layers is applied ``U = total_ut_steps`` times to every token; the
model's one final norm closes every loop step and its output opens the next;
an exit gate reads each step's output.

* **The loop** is a loop IN the step programs (``lax.scan`` over ``u``, a body
  of ``L`` layers traced once), not ``U * L`` unrolled layers: a step program
  is traced, lowered and compiled at every process start, and its size is what
  set-up pays (PERF.md section 6, PR 57).  No parameter knows ``u``.
* **The cache** holds ``U * L`` K/V layers for ``L`` layers of weights: layer
  ``l``'s rows in step ``u`` are read only by layer ``l`` in step ``u`` of
  later tokens (K/V layer ``u * L + l``, as the release numbers them), so
  ``DecodeModel.num_layers = U * L``.  Inside the loop that index is TRACED:
  the paged walks take it as a prefetched scalar
  (``parallel/flash_attention.py``: ``_layer_prefetch``) and the rows are
  scattered at a traced layer; the two pools are the loop's carry, updated in
  place.  Plain ``k`` / ``v`` leaves in the one page group, so the prefix
  cache, sessions and roles accept the model.
* **Attention**: plain multi-head (``num_key_value_heads`` =
  ``num_attention_heads``), rotate-half rotary on the whole head at the
  token's position (the same in every loop step), causal, scale
  ``head_dim ** -0.5``; the plain paged kernels (one grid step a slot, whole
  ``H * Dh``-lane rows).
* **Exit**: ``lambda_u = sigmoid(g_u)``, ``p_u = lambda_u prod_{j<u} (1 -
  lambda_j)``, the last step takes the rest; the served step is the first at
  which the running sum reaches ``early_exit_threshold``, else the last
  (:func:`served_step`).  At the published threshold 1 every token runs all
  ``U`` steps; the gates are computed and the served step is COUNTED, never
  acted on: a threshold under 1 is a depth a token, which the scheduler
  cannot run (ROADMAP R1) and ``_dims`` refuses.

The equations and every assumed point are in the plain reference,
``chipbench/configs/ouro_2_6b.reference.py``; ``cfg`` is the configuration in
the family's own key names.  Sandwich norms are ``models/afmoe.py``'s form,
the fused ``W_qkv`` and rotary ``models/mellum.py``'s, ``_rms`` / ``_mm`` and
the weights-as-arguments contract ``models/minicpm_sala.py``'s.  The residual
stream, norms, rotary, softmax and the gate are float32.
"""
from __future__ import annotations

import functools
import math

import numpy as np

from .mellum import _rotary
from .minicpm_sala import _mm, _rms

__all__ = ["params", "prefill_chunk", "decode_step", "build_decode_model",
           "cache_layout", "served_step", "STEP_COUNTERS"]

STEP_COUNTERS = ("ut.layer_applications", "ut.kv_rows_read",
                 "ut.served_step_sum")


def _dims(cfg):
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("rope_scaling", None), ("use_sliding_window", False)):
        if cfg.get(key, want) != want:
            raise ValueError("%s = %r is not written here (only %r)"
                             % (key, cfg[key], want))
    L, U = cfg["num_hidden_layers"], cfg["total_ut_steps"]
    kinds = list(cfg.get("layer_types", ["full_attention"] * L))
    if kinds != ["full_attention"] * L:
        raise ValueError("layer_types names %d full_attention layers; got %s"
                         % (L, kinds))
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("grouped K/V heads are not written here (the plain "
                         "paged kernels: num_key_value_heads = "
                         "num_attention_heads)")
    if U < 1 or float(cfg["early_exit_threshold"]) < 1.0:
        raise ValueError(
            "total_ut_steps %r, early_exit_threshold %r: a threshold under 1 "
            "lets tokens leave the loop at different steps (a depth a token), "
            "which the step programs do not run" % (
                U, cfg["early_exit_threshold"]))
    Dh = cfg["head_dim"]
    inv_freq = (float(cfg["rope_theta"]) ** (
        -np.arange(0, Dh, 2, dtype=np.float64) / Dh)).astype(np.float32)
    return dict(D=cfg["hidden_size"], F=cfg["intermediate_size"],
                V=cfg["vocab_size"], H=cfg["num_attention_heads"], Dh=Dh,
                L=L, U=U, eps=cfg["rms_norm_eps"],
                threshold=float(cfg["early_exit_threshold"]),
                sm_scale=1.0 / math.sqrt(Dh), inv_freq=inv_freq)


def cache_layout(cfg):
    """What the model keeps in the cache, as ``DecodeModel`` states it:
    ``total_ut_steps * num_hidden_layers`` K/V layers of all heads."""
    d = _dims(cfg)
    return dict(num_layers=d["U"] * d["L"], num_heads=d["H"],
                head_dim=d["Dh"])


def params(cfg, seed, dtype="bfloat16"):
    """Seeded random weights as device arrays of ``dtype`` (norms and the gate
    float32): normal(0, 1 / fan_in) matrices, norm weights around one, the
    gate's weights small enough that ``sigmoid(g_u)`` lies in about 0.2 .. 0.8
    (its input is a normed row of unit mean square: ``g ~ normal(b, 0.3)``,
    the bias drawn in -0.4 .. 0.4).  Made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp

    d = _dims(cfg)
    dt = jnp.dtype(dtype)
    D, L, F = d["D"], d["L"], d["F"]
    HD = d["H"] * d["Dh"]

    def mat(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                / math.sqrt(fan_in)).astype(dt)

    def make(key):
        keys = iter(jax.random.split(key, 9 + 4 * L))

        def vec(*shape):
            return 1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                 jnp.float32)

        return {
            "embed": mat(next(keys), (d["V"], D), 1.0),
            "head": mat(next(keys), (D, d["V"]), D),
            "norm_f": vec(D), "ln_in": vec(L, D), "ln_post_attn": vec(L, D),
            "ln_pre_mlp": vec(L, D), "ln_post_mlp": vec(L, D),
            "gate_w": 0.3 * jax.random.normal(next(keys), (D,), jnp.float32)
            / math.sqrt(D),
            "gate_b": jax.random.uniform(next(keys), (1,), jnp.float32,
                                         -0.4, 0.4),
            "layers": [{"w_qkv": mat(next(keys), (D, 3 * HD), D),
                        "wo": mat(next(keys), (HD, D), HD),
                        "w_gu": mat(next(keys), (D, 2 * F), D),
                        "w_down": mat(next(keys), (F, D), F)}
                       for _ in range(L)],
        }

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


# -- the layer ----------------------------------------------------------------

def _qkv(d, p, lp, layer, x, positions):
    """A layer's rotated queries ``[T, H, Dh]`` (the weights' dtype) and the K
    and V rows ``[T, H * Dh]`` (float32) its tokens add to the cache."""
    T = x.shape[0]
    H, Dh = d["H"], d["Dh"]
    y = _mm(_rms(x, p["ln_in"][layer], d["eps"]), lp["w_qkv"])
    q = _rotary(y[:, :H * Dh].reshape(T, H, Dh), positions, d["inv_freq"], 1.0)
    k = _rotary(y[:, H * Dh:2 * H * Dh].reshape(T, H, Dh), positions,
                d["inv_freq"], 1.0)
    return (q.astype(lp["w_qkv"].dtype), k.reshape(T, H * Dh),
            y[:, 2 * H * Dh:])


def _attn_out(d, p, lp, layer, x, o):
    """``x + norm_post_attn(o W_o)`` (float32)."""
    y = _mm(o.reshape(x.shape[0], -1), lp["wo"])
    return x + _rms(y, p["ln_post_attn"][layer], d["eps"])


def _mlp(d, p, lp, layer, x):
    """``x + norm_post_mlp(W_down(silu(W_gate b) * W_up b))``, ``b =
    norm_pre_mlp(x)`` (float32)."""
    import jax

    gu = _mm(_rms(x, p["ln_pre_mlp"][layer], d["eps"]), lp["w_gu"])
    y = _mm(jax.nn.silu(gu[..., :d["F"]]) * gu[..., d["F"]:], lp["w_down"])
    return x + _rms(y, p["ln_post_mlp"][layer], d["eps"])


def _loop_end(d, p, x):
    """``(h, g)``: the model's one final norm, applied at the end of every
    loop step, and the exit gate's logit of each row."""
    h = _rms(x, p["norm_f"], d["eps"])
    # elementwise, not a matmul: a float32 dot is one bfloat16 pass on the MXU
    return h, (h * p["gate_w"]).sum(axis=-1) + p["gate_b"][0]


def _loop_steps(step, carry, n):
    """``step(u, carry) -> (carry', g_u)`` for ``u = 0 .. n - 1`` as ONE loop
    of the program; returns ``(carry, gates [n, ..])``.  (The structure test
    replaces this by the Python loop to hold both forms to the same bits.)"""
    import jax
    import jax.numpy as jnp

    return jax.lax.scan(lambda c, u: step(u, c), carry,
                        jnp.arange(n, dtype=jnp.int32))


def served_step(gates, threshold):
    """The exit rule on gate logits ``gates [U, ..]``: the 0-based loop step
    each row is served from: the first ``u`` whose running exit probability
    ``sum_{j <= u} lambda_j prod_{i < j} (1 - lambda_i)`` reaches
    ``threshold`` (the last step takes what is left), else the last."""
    import jax
    import jax.numpy as jnp

    lam = jax.nn.sigmoid(gates.astype(jnp.float32))
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    cdf = jnp.cumsum(lam * before, axis=0)
    # the last step takes the rest: it is reached whatever the gates say
    reached = jnp.concatenate(
        [cdf[:-1] >= threshold, jnp.ones_like(cdf[:1], bool)], axis=0)
    return jnp.argmax(reached, axis=0).astype(jnp.int32)


def prefill_chunk(p, tokens, start, valid, cache, chunk_pages, gather_pages,
                  slot, *, cfg, with_gates=False):
    """One chunk of one sequence's prefill (the ``DecodeModel`` contract): in
    every loop step every layer scatters the chunk's K and V rows into
    ``chunk_pages`` of ITS K/V layer ``u * L + l`` and attends over
    ``gather_pages`` (its own rows included) causally by position.  Returns
    ``(last_logits [V], cache')``; with ``with_gates`` also every row's gate
    logit after each loop step ``[U, C]``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_prefill_attention

    d = _dims(cfg)
    L = d["L"]
    C = tokens.shape[0]
    ps = cache["k"].shape[2]
    positions = start + jnp.arange(C, dtype=jnp.int32)

    def step(u, carry):
        x, k_pool, v_pool = carry
        for layer, lp in enumerate(p["layers"]):
            at = u * L + layer
            with jax.named_scope("ouro.attn"):
                q, k, v = _qkv(d, p, lp, layer, x, positions)
                k_pool = k_pool.at[at, chunk_pages].set(
                    k.reshape(C // ps, ps, -1).astype(k_pool.dtype))
                v_pool = v_pool.at[at, chunk_pages].set(
                    v.reshape(C // ps, ps, -1).astype(v_pool.dtype))
                o = paged_prefill_attention(
                    q, k_pool, v_pool, gather_pages, start,
                    sm_scale=d["sm_scale"], layer=at)
                x = _attn_out(d, p, lp, layer, x, o)
            with jax.named_scope("ouro.mlp"):
                x = _mlp(d, p, lp, layer, x)
        with jax.named_scope("ouro.loop_end"):
            h, g = _loop_end(d, p, x)
        return (h, k_pool, v_pool), g

    x = p["embed"][tokens].astype(jnp.float32)
    with jax.named_scope("ouro.loop"):
        (h, k_pool, v_pool), gates = _loop_steps(
            step, (x, cache["k"], cache["v"]), d["U"])
    last = jax.lax.dynamic_index_in_dim(h, valid - 1, axis=0, keepdims=False)
    out = (_mm(last, p["head"]), dict(cache, k=k_pool, v=v_pool))
    return out + (gates,) if with_gates else out


def decode_step(p, tokens, positions, cache, page_tables, kv_lens, *, cfg,
                with_gates=False):
    """One token per slot (the ``DecodeModel`` contract): in every loop step
    every layer writes the token's K and V row on its page of ``positions``
    in ITS K/V layer ``u * L + l`` and attends over the slot's first
    ``kv_lens`` rows of that layer; slots that do not decode (``kv_lens ==
    0``) write to scratch.  Returns ``(logits [S, V], cache', counts [3])``
    - ``STEP_COUNTERS``: layer applications (live slots x ``U * L``), cached
    rows the step's attention is entitled to read (sum of ``kv_lens`` x ``U *
    L``) and the served loop steps of the live slots, 1-based, summed (``U``
    a slot at the published threshold); with ``with_gates`` also the gate
    logits ``[U, S]``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_decode_attention

    d = _dims(cfg)
    L, U = d["L"], d["U"]
    S = tokens.shape[0]
    ps = cache["k"].shape[2]
    pages = page_tables[jnp.arange(S), positions // ps]
    offsets = positions % ps

    def step(u, carry):
        x, k_pool, v_pool = carry
        for layer, lp in enumerate(p["layers"]):
            at = u * L + layer
            with jax.named_scope("ouro.attn"):
                q, k, v = _qkv(d, p, lp, layer, x, positions)
                k_pool = k_pool.at[at, pages, offsets].set(
                    k.astype(k_pool.dtype))
                v_pool = v_pool.at[at, pages, offsets].set(
                    v.astype(v_pool.dtype))
                o = paged_decode_attention(
                    q, k_pool, v_pool, page_tables, kv_lens,
                    sm_scale=d["sm_scale"], layer=at)
                x = _attn_out(d, p, lp, layer, x, o)
            with jax.named_scope("ouro.mlp"):
                x = _mlp(d, p, lp, layer, x)
        with jax.named_scope("ouro.loop_end"):
            h, g = _loop_end(d, p, x)
        return (h, k_pool, v_pool), g

    x = p["embed"][tokens].astype(jnp.float32)
    with jax.named_scope("ouro.loop"):
        (h, k_pool, v_pool), gates = _loop_steps(
            step, (x, cache["k"], cache["v"]), U)
    live = kv_lens > 0
    served = jnp.where(live, served_step(gates, d["threshold"]) + 1, 0)
    counts = jnp.stack([live.sum() * (U * L), kv_lens.sum() * (U * L),
                        served.sum()]).astype(jnp.int32)
    out = (_mm(h, p["head"]), dict(cache, k=k_pool, v=v_pool), counts)
    return out + (gates,) if with_gates else out


def build_decode_model(weights, cfg, eos_id=None):
    """An Ouro-family model behind ``InferenceEngine`` -> ``DecodeScheduler``:
    ``weights`` from :func:`params` (or a checkpoint in its form).  Its cache
    is ``total_ut_steps * num_hidden_layers`` K/V layers deep for
    ``num_hidden_layers`` layers of weights."""
    from ..serving.decode_scheduler import DecodeModel

    _dims(cfg)
    return DecodeModel(
        functools.partial(decode_step, cfg=cfg),
        functools.partial(prefill_chunk, cfg=cfg),
        params=weights, vocab_size=cfg["vocab_size"], eos_id=eos_id,
        name="ouro", step_counters=STEP_COUNTERS, **cache_layout(cfg))
