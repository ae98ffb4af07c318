"""The EvaByte family (``model_type`` ``evabyte``, ``attention_class`` ``eva``;
EvaByte/EvaByte, HKU NLP / SambaNova) as a served ``DecodeModel``: a BYTE-level
pre-norm decoder (vocabulary 320) with ``num_pred_heads`` prediction heads,
RMSNorm with a unit offset, a float32 residual stream, SwiGLU feed-forward
blocks, and EVA attention in every layer.

* **EVA** (Zheng, Yuan, Wang, Kong, arXiv:2302.04542, in the release's causal,
  chunk-summarised form).  ``W`` = ``window_size``, ``C`` = ``chunk_size``.  A
  query at position ``t`` reads, with ``b = (t // W) * W``, the exact K and V
  rows of positions ``b .. t`` (its own ALIGNED window so far) and one SUMMARY
  row ``(k~_c, v~_c)`` for every chunk ``c < b / C`` (all chunks of all earlier
  windows), under ONE softmax at scale ``1 / sqrt(head_dim)``.  A chunk's
  summary, a head: ``alpha = softmax_j(k_j . phi)`` over its ``C`` rotated
  keys (float32), ``k~ = sum_j alpha_j k_j + mu``, ``v~ = sum_j alpha_j v_j``,
  with two learned vectors a head and layer (``phi``, ``mu``).
* **The cache** is in two page GROUPS (``serving/kv_cache.py``).  The FIRST,
  ``summary``, keeps every position and holds no K or V at all: its leaves
  ``ksum`` / ``vsum`` hold one row for every ``C`` tokens, on pages of
  ``summary_page_rows`` rows (a page size of the group's own, ``rows * C``
  tokens).  The further group ``window`` holds the K and V rows (``k`` /
  ``v``) under an ALIGNED window: a slot's table fills to ``W / page`` pages,
  all given back when its position reaches the next multiple of ``W``.
  A summary row is WRITTEN by the step in which its chunk's last byte arrives
  (a decode step, or the chunk program for every chunk a prefill chunk
  completes) and becomes VISIBLE only when its window has closed: the step
  programs read ``b / C`` rows, fewer than are written.
* **Heads**: ``W_head [hidden, num_pred_heads * vocab]`` in float32; head
  ``j`` scores byte ``t + 1 + j``.  The engine serves head 0's byte; the step
  functions return every head's logits on request (``with_heads``).

The equations and every assumed point are in the plain reference,
``chipbench/configs/evabyte_6_5b.reference.py``.  Rotary and the projections
are ``models/mellum.py``'s, norm, products and the feed-forward block
``models/minicpm_sala.py``'s; norms, rotary, pooling weights, softmax and the
residual stream are float32, K, V and summary rows are kept in the cache's
dtype (a summary is pooled from the rows as the cache holds them, so a decode
step and the chunk program write the same row for the same chunk).
"""
from __future__ import annotations

import functools
import math

from .mellum import _qkv, rope_inverse_frequencies
from .minicpm_sala import _ffn, _mm, _rms

__all__ = ["params", "prefill_chunk", "decode_step", "build_decode_model",
           "cache_layout", "summarise", "STEP_COUNTERS"]

# what a step's attention is entitled to read and what the step wrote, summed
# over its slots (rows a layer: every layer reads and writes the same)
STEP_COUNTERS = ("eva.window_rows_read", "eva.summary_rows_read",
                 "eva.chunks_summarised", "eva.windows_closed")
_KIND = "full_attention"       # the one rotary ``mellum._qkv`` is asked for


def _dims(cfg):
    for key, want in (("attention_bias", False), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False), ("rope_scaling", None),
                      ("norm_add_unit_offset", True), ("num_chunks", None)):
        if cfg.get(key, want) != want:
            raise ValueError("%s = %r is not written here (only %r)"
                             % (key, cfg[key], want))
    H = cfg["num_attention_heads"]
    if cfg.get("num_key_value_heads", H) != H:
        raise ValueError("EVA keeps a summary a head: num_key_value_heads "
                         "must equal num_attention_heads")
    d = dict(
        D=cfg["hidden_size"], F=cfg["intermediate_size"],
        V=cfg["vocab_size"], Hn=cfg["num_pred_heads"], H=H, Hkv=H,
        Dh=cfg["hidden_size"] // H, L=cfg["num_hidden_layers"],
        W=int(cfg["window_size"]), C=int(cfg["chunk_size"]),
        eps=cfg["rms_norm_eps"], resid=1.0)
    if d["W"] % d["C"]:
        raise ValueError("window_size %d is not whole chunks of %d"
                         % (d["W"], d["C"]))
    d["sm_scale"] = 1.0 / math.sqrt(d["Dh"])
    d["kinds"] = [_KIND] * d["L"]
    d["rope"] = {_KIND: rope_inverse_frequencies(
        {"rope_theta": cfg["rope_theta"]}, d["Dh"])}
    return d


def cache_layout(cfg):
    """What the model keeps in the cache, as ``DecodeModel`` states it: the
    summary rows in the first group (every position kept, one row a chunk,
    on pages of ``summary_page_rows`` rows: a serving geometry the caller
    states beside the published keys, since the decode kernel walks both
    lists in tiles of whole pages and a page of the window's tokens would be
    ``page / C`` rows, under the chip's tile of 8), K and V in a further
    group under an aligned window."""
    d = _dims(cfg)
    if not cfg.get("summary_page_rows"):
        raise ValueError("summary_page_rows (the summary group's rows a "
                         "page) is not stated")
    rows = int(cfg["summary_page_rows"])
    width = d["H"] * d["Dh"]

    def leaf(group, per_row):
        return dict(layers=d["L"], tokens_per_row=per_row, width=width,
                    dtype=None, group=group)

    return dict(
        page_groups={
            "summary": dict(window=None, page_size=rows * d["C"]),
            "window": dict(window=d["W"], aligned=True)},
        page_pools={"ksum": leaf("summary", d["C"]),
                    "vsum": leaf("summary", d["C"]),
                    "k": leaf("window", 1), "v": leaf("window", 1)})


def params(cfg, seed, dtype="bfloat16"):
    """Seeded random weights as device arrays of ``dtype`` (vectors and the
    head float32), made in one jitted call: normal(0, 1 / fan_in) matrices,
    norm offsets around zero, ``phi`` and ``mu`` as the source initialises
    them in scale (normal(0, 1) clipped to +-1, times ``head_dim ** -0.5``)."""
    import jax
    import jax.numpy as jnp

    d = _dims(cfg)
    dt = jnp.dtype(dtype)
    D, F, L, H, Dh = d["D"], d["F"], d["L"], d["H"], d["Dh"]

    def make(key):
        keys = iter(jax.random.split(key, 8 + 4 * L))

        def mat(rows, cols, std=None, to=dt):
            std = 1.0 / math.sqrt(rows) if std is None else std
            return (jax.random.normal(next(keys), (rows, cols), jnp.float32)
                    * std).astype(to)

        def offset(*shape):
            return 0.1 * jax.random.normal(next(keys), shape, jnp.float32)

        def pooling():
            return jnp.clip(jax.random.normal(next(keys), (L, H, Dh),
                                              jnp.float32), -1.0, 1.0
                            ) * Dh ** -0.5

        return {
            "embed": mat(d["V"], D, 1.0),
            "head": mat(D, d["Hn"] * d["V"], to=jnp.float32),
            "norm_f": offset(D), "ln1": offset(L, D), "ln2": offset(L, D),
            "phi": pooling(), "mu": pooling(),
            "layers": [{"w_qkv": mat(D, 3 * D), "wo": mat(D, D),
                        "w_gu": mat(D, 2 * F), "w_down": mat(F, D)}
                       for _ in range(L)],
        }

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


# -- the layer ----------------------------------------------------------------

def summarise(k, v, phi, mu):
    """The summary rows of whole chunks: ``k`` / ``v`` ``[n, C, H, Dh]``
    float32 (rotated keys), ``phi`` / ``mu`` ``[H, Dh]``.  Pooling weights
    ``softmax_j(k_j . phi)`` over a chunk's rows, shared by K and V; the
    pooled key shifted by ``mu``.  Returns ``(k~, v~)`` ``[n, H * Dh]``."""
    import jax
    import jax.numpy as jnp

    # float32 products: the chip's default would round phi and the pooling
    # weights to bfloat16, which is pooling in bfloat16
    n, exact = k.shape[0], jax.lax.Precision.HIGHEST
    alpha = jax.nn.softmax(
        jnp.einsum("nchd,hd->nch", k, phi, precision=exact), axis=1)
    return ((jnp.einsum("nch,nchd->nhd", alpha, k, precision=exact)
             + mu).reshape(n, -1),
            jnp.einsum("nch,nchd->nhd", alpha, v,
                       precision=exact).reshape(n, -1))


def _project(d, p, lp, layer, x, positions):
    """A layer's rotated queries ``[T, H, Dh]`` (the weights' dtype: the
    source's mixed-precision attention rounds them so) and the K and V rows
    ``[T, H * Dh]`` float32 its tokens add to the cache."""
    q, k, v = _qkv(d, {"ln1": 1.0 + p["ln1"]}, lp, layer, x, positions)
    return q.astype(lp["w_qkv"].dtype), k, v


def _head(d, p, x):
    """Every prediction head's logits ``[.., Hn * V]``, float32 operands."""
    import jax
    import jax.numpy as jnp

    return jnp.dot(_rms(x, 1.0 + p["norm_f"], d["eps"]), p["head"],
                   precision=jax.lax.Precision.HIGHEST)


def _served(d, logits, cache, counts, with_heads):
    """What a step function returns: head 0's logits for the sampler, the
    decode step's counters, and on request every head's ``[.., Hn, V]``."""
    out = (logits[..., :d["V"]], cache)
    if counts is not None:
        out += (counts.astype("int32"),)
    if with_heads:
        out += (logits.reshape(logits.shape[:-1] + (d["Hn"], d["V"])),)
    return out


def prefill_chunk(p, tokens, start, valid, cache, chunk_pages, gather_pages,
                  slot, *, cfg, with_heads=False):
    """One chunk of one sequence's prefill (the ``DecodeModel`` contract of a
    model with page groups): positions ``start .. start + T - 1``, none across
    a multiple of the window.  Every layer scatters the chunk's K and V rows
    into the window group's ``chunk_pages``, writes the summary of every chunk
    of ``chunk_size`` that the ``valid`` rows complete (the others' go to
    scratch) and attends over the window's and the summaries' ``gather_pages``.
    Returns ``(head 0's last logits [V], cache')``: the step counters are the
    decode steps' alone."""
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_eva_prefill_attention

    d = _dims(cfg)
    cache = dict(cache)
    T, C, W, H, Dh = tokens.shape[0], d["C"], d["W"], d["H"], d["Dh"]
    positions = start + jnp.arange(T, dtype=jnp.int32)
    psw, rpp = cache["k"].shape[2], cache["ksum"].shape[2]
    kv_dt = cache["k"].dtype
    # the summary rows this chunk's tokens belong to: their page of
    # ``chunk_pages`` (which starts at the page that holds ``start``) and row
    local = (start % (rpp * C)) // C + jnp.arange(T // C, dtype=jnp.int32)
    complete = (start // C + jnp.arange(T // C) + 1) * C <= start + valid
    sum_pages = jnp.where(complete, chunk_pages["summary"][local // rpp], 0)
    x = p["embed"][tokens].astype(jnp.float32)
    for layer, lp in enumerate(p["layers"]):
        with jax.named_scope("evabyte.attn.window+summary"):
            q, k, v = _project(d, p, lp, layer, x, positions)
            k, v = k.astype(kv_dt), v.astype(kv_dt)
            cache["k"] = cache["k"].at[layer, chunk_pages["window"]].set(
                k.reshape(T // psw, psw, -1))
            cache["v"] = cache["v"].at[layer, chunk_pages["window"]].set(
                v.reshape(T // psw, psw, -1))
        with jax.named_scope("evabyte.attn.summarise"):
            ks, vs = summarise(
                k.astype(jnp.float32).reshape(T // C, C, H, Dh),
                v.astype(jnp.float32).reshape(T // C, C, H, Dh),
                p["phi"][layer], p["mu"][layer])
            cache["ksum"] = cache["ksum"].at[layer, sum_pages,
                                             local % rpp].set(ks.astype(kv_dt))
            cache["vsum"] = cache["vsum"].at[layer, sum_pages,
                                             local % rpp].set(vs.astype(kv_dt))
        with jax.named_scope("evabyte.attn.window+summary"):
            o = paged_eva_prefill_attention(
                q, cache["k"], cache["v"], cache["ksum"], cache["vsum"],
                gather_pages["window"], gather_pages["summary"], start, W, C,
                layer=layer, sm_scale=d["sm_scale"])
            h = x + _mm(o.reshape(T, -1), lp["wo"])
        with jax.named_scope("evabyte.mlp"):
            x = _ffn(d, lp, h, 1.0 + p["ln2"][layer], jnp.float32)
    last = jax.lax.dynamic_index_in_dim(x, valid - 1, axis=0, keepdims=False)
    return _served(d, _head(d, p, last), cache, None, with_heads)


def decode_step(p, tokens, positions, cache, page_tables, kv_lens, *, cfg,
                with_heads=False):
    """One byte per slot (the ``DecodeModel`` contract of a model with page
    groups: ``page_tables`` is ``{group: [S, width]}``, the window group's
    filled from column 0 in every window).  Every layer writes the token's K
    and V row on the window's page of ``positions``; a slot whose byte
    completes a chunk pools that chunk's rows (read back from the page they
    share) into its summary row; then the slot attends over its window's rows
    so far and the summaries of the windows that have closed.  Slots that do
    not decode (``kv_lens == 0``) write to scratch and read nothing.
    Returns ``(head 0's logits [S, V], cache', counts [4])`` —
    ``STEP_COUNTERS``: the window rows and the summary rows the step's
    attention is entitled to read, the chunks it summarised and the windows
    it closed, summed over the slots."""
    import jax
    import jax.numpy as jnp

    from ..parallel.flash_attention import paged_eva_decode_attention

    d = _dims(cfg)
    cache = dict(cache)
    S, C, W, H, Dh = tokens.shape[0], d["C"], d["W"], d["H"], d["Dh"]
    live = kv_lens > 0
    at = jnp.arange(S)
    tw, ts = page_tables["window"], page_tables["summary"]
    psw, rpp = cache["k"].shape[2], cache["ksum"].shape[2]
    kv_dt = cache["k"].dtype
    base = (positions // W) * W
    win_pages = tw[at, (positions // psw) % tw.shape[1]]
    win_rows = positions % psw
    win_lens = jnp.where(live, positions - base + 1, 0)
    sum_lens = jnp.where(live, base // C, 0)         # visible, not written
    # the chunk a slot's byte completes: its rows lie on the byte's own page
    done = live & ((positions + 1) % C == 0)
    chunk = positions // C
    sum_pages = jnp.where(done, ts[at, chunk // rpp], 0)
    chunk_rows = jnp.maximum(win_rows[:, None] - (C - 1)
                             + jnp.arange(C, dtype=jnp.int32), 0)
    x = p["embed"][tokens].astype(jnp.float32)
    for layer, lp in enumerate(p["layers"]):
        with jax.named_scope("evabyte.attn.window+summary"):
            q, k, v = _project(d, p, lp, layer, x, positions)
            cache["k"] = cache["k"].at[layer, win_pages, win_rows].set(
                k.astype(kv_dt))
            cache["v"] = cache["v"].at[layer, win_pages, win_rows].set(
                v.astype(kv_dt))
        with jax.named_scope("evabyte.attn.summarise"):
            ks, vs = summarise(
                *(cache[leaf][layer, win_pages[:, None], chunk_rows]
                  .astype(jnp.float32).reshape(S, C, H, Dh)
                  for leaf in ("k", "v")), p["phi"][layer], p["mu"][layer])
            cache["ksum"] = cache["ksum"].at[layer, sum_pages,
                                             chunk % rpp].set(ks.astype(kv_dt))
            cache["vsum"] = cache["vsum"].at[layer, sum_pages,
                                             chunk % rpp].set(vs.astype(kv_dt))
        with jax.named_scope("evabyte.attn.window+summary"):
            o = paged_eva_decode_attention(
                q, cache["k"], cache["v"], cache["ksum"], cache["vsum"],
                tw, ts, win_lens, sum_lens, layer=layer,
                sm_scale=d["sm_scale"])
            h = x + _mm(o.reshape(S, -1), lp["wo"])
        with jax.named_scope("evabyte.mlp"):
            x = _ffn(d, lp, h, 1.0 + p["ln2"][layer], jnp.float32)
    counts = jnp.stack([win_lens.sum(), sum_lens.sum(), done.sum(),
                        (live & ((positions + 1) % W == 0)).sum()])
    return _served(d, _head(d, p, x), cache, counts, with_heads)


def build_decode_model(weights, cfg, eos_id=None):
    """An EvaByte-family model behind ``InferenceEngine`` ->
    ``DecodeScheduler``: ``weights`` from :func:`params` (or a checkpoint in
    its form).  Its K and V live in a further page group, so the prefix cache,
    sessions and roles refuse it (``DecodeScheduler``)."""
    from ..serving.decode_scheduler import DecodeModel

    d = _dims(cfg)
    return DecodeModel(
        functools.partial(decode_step, cfg=cfg),
        functools.partial(prefill_chunk, cfg=cfg),
        params=weights, vocab_size=d["V"], eos_id=eos_id, name="evabyte",
        step_counters=STEP_COUNTERS, **cache_layout(cfg))
