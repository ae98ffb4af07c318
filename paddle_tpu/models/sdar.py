"""The SDAR family (``model_type`` ``sdar_moe``; JetLM's SDAR-30B-A3B-Chat,
"Synergy of Diffusion and AutoRegression") as a served ``DecodeModel``: a
pre-norm RMSNorm decoder with QK-normed grouped-query attention and a sparse
expert block in every layer, which generates by DIFFUSION OVER BLOCKS.

* **The block mask**: position ``i`` sees position ``j`` iff ``j // B <= i //
  B`` (``B`` = ``block_length``, blocks counted from position 0): causal
  between blocks, bidirectional inside one, for the prompt as for generated
  text.  So a token's K/V from layer 1 on depends on the LATER tokens of its
  block: a block's rows enter the cache only when the block is whole.
* **Logits are unshifted**: the row of position ``i`` predicts the id AT ``i``
  (a masked position predicts itself).
* **A decode step** carries each slot's current block: ``B`` ids from the
  block's start on, the mask id where a position is still masked.  Every
  forward writes the block's ``B`` K and V rows at ``start ..`` (past the
  slot's ``kv_len``, where nobody else reads them) and attends over ``start +
  B`` rows with no stagger (``paged_gqa_decode_attention(block=B)``: ``B * g``
  query rows a KV head).  The step program (``serving/step_programs.py``)
  unmasks by the model's rule; the forward that closes the block (finds it
  whole, or out of its denoising forwards) is the one whose rows stay (it overwrites what the denoising forwards left there),
  and only then does the scheduler move ``kv_len``, by ``B``.
* **Attention**: ``Hq`` query heads over ``Hkv`` KV heads (query head ``i``
  reads KV head ``i // g``); q and k normalised a head (RMSNorm over
  ``head_dim``, one weight a layer each) BEFORE rotate-half rotary on the whole
  head at the token's absolute position; scale ``1 / sqrt(head_dim)``.
* **Experts** (``parallel/moe.py``: ``moe_topk``): ``softmax`` over all
  ``num_experts`` router logits in float32, the ``num_experts_per_tok`` largest
  chosen, their probabilities renormalised (``norm_topk_prob``), no bias, no
  shared expert, dropless, every expert held here.
* **The cache**: plain ``k`` / ``v`` pools in the first page group, so the
  prefix cache and sessions take the model as they take a causal one: a page
  holds whole blocks (``page_size % B == 0``), and a whole block's rows depend
  on nothing behind it.

The equations and every assumed size are in the plain reference,
``chipbench/configs/sdar_30b_a3b.reference.py``; ``cfg`` is the configuration
in the family's own key names plus ``block_length``, ``denoising_steps``,
``confidence_threshold`` and ``mask_token_id`` (the release's generation
defaults: the config file does not fix them).  Precision, the shared pieces
(``_rms``, ``_mm``, ``_logits``) and the weights-as-arguments contract are
``models/minicpm_sala.py``'s, the rotary and the weights' layout
``models/mellum.py``'s; the router, norms, rotary and softmax are float32.
"""
from __future__ import annotations

import functools
import math

from .mellum import _attn_out, _rotary, rope_inverse_frequencies
from .minicpm_sala import _logits, _mm, _rms

__all__ = ["params", "prefill_chunk", "decode_step", "build_decode_model",
           "block", "STEP_COUNTERS"]

# the model's own step counters (the step program adds ``BLOCK_COUNTERS``):
# the three of ``moe_topk`` and the cached rows a step's attention reads
STEP_COUNTERS = ("moe.pairs", "moe.experts_touched", "moe.max_load",
                 "diffusion.kv_rows_read")


def _dims(cfg):
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("norm_topk_prob", True),
                      ("tie_word_embeddings", False),
                      ("use_sliding_window", False), ("rope_scaling", None),
                      ("decoder_sparse_step", 1), ("mlp_only_layers", [])):
        if cfg.get(key, want) != want:
            raise ValueError("%s = %r is not written here (only %r)"
                             % (key, cfg[key], want))
    d = dict(
        D=cfg["hidden_size"], Fm=cfg["moe_intermediate_size"],
        V=cfg["vocab_size"], H=cfg["num_attention_heads"],
        Hkv=cfg["num_key_value_heads"], Dh=cfg["head_dim"],
        L=cfg["num_hidden_layers"], E=cfg["num_experts"],
        k=cfg["num_experts_per_tok"], eps=cfg["rms_norm_eps"],
        B=int(cfg["block_length"]), resid=1.0, logit_div=1.0)
    d["sm_scale"] = 1.0 / math.sqrt(d["Dh"])
    d["rope"] = rope_inverse_frequencies(
        {"rope_theta": cfg["rope_theta"]}, d["Dh"])
    return d


def block(cfg):
    """What ``DecodeModel.block`` states: the block, as the model's own
    facts."""
    return dict(length=cfg["block_length"], mask_id=cfg["mask_token_id"],
                steps=cfg["denoising_steps"],
                threshold=cfg["confidence_threshold"])


def params(cfg, seed, dtype="bfloat16"):
    """Seeded random weights as device arrays of ``dtype`` (vectors and the
    routers float32): normal(0, 1 / fan_in) matrices, the embedding normal(0,
    1 / hidden_size) (rows of unit norm, as ``models/afmoe.py`` has them and
    as a trained model's are beside its layers' outputs: under entries of
    unit variance the embedding IS the residual stream, every masked position
    of every slot routes like every other, and a fresh block's forward touches
    a third of the experts), norm weights around one.  Made on the device; the
    expert stacks a layer at a time into a donated buffer (``models/
    mellum.py``'s way), so nothing larger than a layer's experts in float32 is
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
        keys = iter(jax.random.split(key, 10 + 2 * L))

        def vec(*shape):
            return 1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                 jnp.float32)

        return {
            "embed": mat(next(keys), (d["V"], D), D),
            "head": mat(next(keys), (D, d["V"]), D),
            "norm_f": vec(D), "ln1": vec(L, D), "ln2": vec(L, D),
            "q_norm": vec(L, d["Dh"]), "k_norm": vec(L, d["Dh"]),
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

def _qkv(d, p, lp, layer, x, positions):
    """A layer's queries ``[T, Hq, Dh]`` (the activations' dtype; normalised a
    head, then rotated) and the K (normalised, rotated) and V rows ``[T, Hkv *
    Dh]`` (float32) its tokens add to the cache."""
    T = x.shape[0]
    H, Hkv, Dh = d["H"], d["Hkv"], d["Dh"]
    inv_freq, factor = d["rope"]
    y = _mm(_rms(x, p["ln1"][layer], d["eps"]), lp["w_qkv"])
    q = _rotary(_rms(y[:, :H * Dh].reshape(T, H, Dh), p["q_norm"][layer],
                     d["eps"]), positions, inv_freq, factor)
    k = _rotary(_rms(y[:, H * Dh:(H + Hkv) * Dh].reshape(T, Hkv, Dh),
                     p["k_norm"][layer], d["eps"]), positions, inv_freq,
                factor)
    return q.astype(x.dtype), k.reshape(T, Hkv * Dh), y[:, (H + Hkv) * Dh:]


def _experts(d, p, layer, h, token_mask):
    """``(h + MoE(norm2(h)), counts [3], chosen [T, k])``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.moe import moe_topk

    act = h.dtype
    with jax.named_scope("sdar.experts"):
        u = _rms(h, p["ln2"][layer], d["eps"]).astype(act)
        y, counts, chosen = moe_topk(
            u, {"w": p["router_w"][layer], "bias": None},
            {"w_gu": p["e_gu"], "w_down": p["e_down"]}, None,
            top_k=d["k"], experts_held=(0, d["E"]), scoring="softmax",
            token_mask=token_mask, layer=layer)
        return (h.astype(jnp.float32) + y).astype(act), counts, chosen


def prefill_chunk(p, tokens, start, valid, cache, chunk_pages, gather_pages,
                  slot, *, cfg, with_routing=False):
    """One chunk of one sequence's prefill UNDER THE BLOCK MASK: ``start`` and
    ``valid`` are multiples of the block length (the scheduler prefills a
    prompt's whole blocks only), every layer scatters the chunk's K and V
    rows into ``chunk_pages`` and attends over ``gather_pages`` (its own rows
    included), a row seeing the keys up to the end of its own block; padding
    rows route to no expert.  Returns ``(last_logits [V], cache')``, the row
    of position ``start + valid - 1`` (which predicts the id AT that
    position: nobody samples from it); with ``with_routing`` also the experts
    each layer chose ``[C, k]``."""
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
    ps = cache["k"].shape[2]
    for layer, lp in enumerate(p["layers"]):
        with jax.named_scope("sdar.attn"):
            q, k, v = _qkv(d, p, lp, layer, x, positions)
            for name, rows in (("k", k), ("v", v)):
                cache[name] = cache[name].at[layer, chunk_pages].set(
                    rows.reshape(C // ps, ps, -1).astype(cache[name].dtype))
            o = paged_gqa_prefill_attention(
                q, cache["k"], cache["v"], gather_pages, start, valid,
                layer=layer, sm_scale=d["sm_scale"], block=d["B"])
            h = _attn_out(lp, x, o)
        x, _, chosen = _experts(d, p, layer, h, real)
        routing.append(chosen)
    last = jax.lax.dynamic_index_in_dim(x, valid - 1, axis=0, keepdims=False)
    with jax.named_scope("sdar.head"):
        out = (_logits(d, p, last), cache)
    return out + (routing,) if with_routing else out


def decode_step(p, tokens, positions, cache, page_tables, kv_lens, *, cfg,
                with_routing=False):
    """One BLOCK per slot (the ``DecodeModel`` contract of a model that states
    a ``block``): ``tokens [S, B]`` the blocks' ids (the mask id where a
    position is masked), ``positions [S]`` their starts, ``kv_lens = start +
    B`` (0: the slot does not decode, writes to scratch and routes to no
    expert).  Every layer writes the block's ``B`` K and V rows at ``start
    ..`` and attends over the slot's first ``kv_lens`` rows, every row of the
    block seeing all of them.  Returns ``(logits [S, B, V], cache', counts
    [4])`` — ``STEP_COUNTERS``: the (token, expert) pairs computed, the
    experts that took one, the largest expert's pairs (each summed over the
    layers), and the cached rows the step's attention reads (``kv_lens``
    summed over slots and layers); with ``with_routing`` also the experts
    each layer chose ``[S * B, k]``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_gqa_decode_attention

    d = _dims(cfg)
    cache = dict(cache)
    S, B = tokens.shape
    live = kv_lens > 0
    at = positions[:, None] + jnp.arange(B, dtype=jnp.int32)[None, :]
    ps = cache["k"].shape[2]
    # the page of every row of the block (a block lies inside one page)
    pages = jnp.where(live[:, None], jnp.take_along_axis(
        page_tables, (at // ps) % page_tables.shape[1], axis=1), 0)
    pages, offsets = pages.reshape(-1), (at % ps).reshape(-1)
    at = at.reshape(-1)
    rows = jnp.repeat(live, B)
    x = p["embed"][tokens.reshape(-1)]
    counts = jnp.zeros((3,), jnp.int32)
    routing = []
    for layer, lp in enumerate(p["layers"]):
        with jax.named_scope("sdar.attn"):
            q, k, v = _qkv(d, p, lp, layer, x, at)
            cache["k"] = cache["k"].at[layer, pages, offsets].set(
                k.astype(cache["k"].dtype))
            cache["v"] = cache["v"].at[layer, pages, offsets].set(
                v.astype(cache["v"].dtype))
            o = paged_gqa_decode_attention(
                q.reshape(S, B, d["H"], d["Dh"]), cache["k"], cache["v"],
                page_tables, kv_lens, layer=layer, sm_scale=d["sm_scale"],
                block=B)
            h = _attn_out(lp, x, o.reshape(S * B, d["H"], d["Dh"]))
        x, c, chosen = _experts(d, p, layer, h, rows)
        counts = counts + c
        routing.append(chosen)
    with jax.named_scope("sdar.head"):
        logits = _logits(d, p, x).reshape(S, B, -1)
    read = (kv_lens.sum() * d["L"]).astype(jnp.int32)
    out = (logits, cache, jnp.concatenate([counts, read[None]]))
    return out + (routing,) if with_routing else out


def build_decode_model(weights, cfg, eos_id=None):
    """An SDAR-family model behind ``InferenceEngine`` -> ``DecodeScheduler``:
    ``weights`` from :func:`params` (or a checkpoint in its form).  It states
    its block (``block``), so a decode step carries a block a slot; its cache
    is the plain ``k`` / ``v`` pools."""
    from ..serving.decode_scheduler import DecodeModel

    d = _dims(cfg)
    return DecodeModel(
        functools.partial(decode_step, cfg=cfg),
        functools.partial(prefill_chunk, cfg=cfg),
        params=weights, num_layers=d["L"], num_heads=d["Hkv"],
        head_dim=d["Dh"], vocab_size=cfg["vocab_size"], eos_id=eos_id,
        name="sdar", step_counters=STEP_COUNTERS, block=block(cfg))
