"""The two step programs of a decode-capable model: what the scheduler
dispatches every iteration, as functions of the model and of nothing else
of the scheduler.

A step program is the model's ``decode_fn`` / ``prefill_chunk_fn`` with the
sampling around it, jitted.  What it is depends on the model, on ``top_k``
(static: ``lax.top_k`` needs a compile-time k) and on whether the cache's
pytree is donated; the width of the decode step and of a chunk are shapes of
its arguments.  ``DecodeModel.step_programs`` builds the pair once a
``(top_k, donate)`` and keeps it, so every scheduler over one model object
dispatches the SAME jitted callable and jax traces a shape once, however many
schedulers, engines or replicas are built over that model.  (A compile is a
device's: replicas on different devices still compile one executable each.)

**One host upload a dispatch** (ISSUE 59).  What the host decides for a
dispatch reaches the device as ONE int32 buffer, which the program takes
apart: a transfer of a few hundred bytes costs the host what one of a hundred
kilobytes does, and a step had seven of them.  The buffer's layout is this
module's (``split_step``, ``split_chunk``: the same function cuts the host's
numpy buffer into the views it fills and the traced buffer into the slices
the model receives), and is a function of shapes alone:

* a decode step: ``[slots, step_columns(widths)]``.  A slot's row holds its row
  of every page group's table, each from a lane multiple on (the first at
  column 0), then ``STEP_COLUMNS``: ``tokens``, ``positions``, ``kv_lens``,
  ``seeds`` (the uint32's bits), ``temps`` (the float32's bits) and
  ``from_previous`` (0 / 1).
* a chunk: ``[chunk_length(width, sizes)]``: ``tokens[width]``, then
  ``CHUNK_SCALARS`` (``start``, ``valid``, ``slot``, ``seed``, ``temp``, the
  last two as bits), then a group its ``written`` and ``gathered`` vectors.
"""
from __future__ import annotations

import numpy as np

__all__ = ["StepPrograms", "sample_token", "STEP_COLUMNS", "CHUNK_SCALARS",
           "step_columns", "split_step", "chunk_length", "split_chunk",
           "int32_bits"]

STEP_COLUMNS = ("tokens", "positions", "kv_lens", "seeds", "temps",
                "from_previous")
CHUNK_SCALARS = ("start", "valid", "slot", "seed", "temp")
# a table's slice out of a row starts where a vector register does, so that it
# is a copy of whole tiles
_LANES = 128


def _table_spans(widths):
    """``[(first, end)]`` columns of each table in a decode step's row."""
    spans, at = [], 0
    for width in widths:
        spans.append((at, at + width))
        at = -(-(at + width) // _LANES) * _LANES
    return spans


def step_columns(widths):
    """Columns of a decode step's buffer over tables ``widths`` wide."""
    return _table_spans(widths)[-1][1] + len(STEP_COLUMNS)


def split_step(buf, widths=None):
    """``(tables, columns)`` of a decode step's buffer ``[slots, columns]``,
    numpy (views, to fill) or traced (slices, to read): a table a width and
    ``STEP_COLUMNS``' int32 columns in order.  ``widths`` None: one table, as
    wide as the row leaves."""
    if widths is None:
        widths = (buf.shape[1] - len(STEP_COLUMNS),)
    spans = _table_spans(widths)
    at = spans[-1][1]
    return ([buf[:, a:b] for a, b in spans],
            [buf[:, at + i] for i in range(len(STEP_COLUMNS))])


def chunk_length(width, sizes):
    """Length of a chunk's buffer: ``width`` tokens, the scalars, and a page
    group ``(written, gathered)`` entries (``sizes``)."""
    return width + len(CHUNK_SCALARS) + sum(w + g for w, g in sizes)


def split_chunk(vec, sizes):
    """``(tokens, scalars, [(written, gathered)])`` of a chunk's buffer,
    numpy (views) or traced (slices); ``scalars`` is ``CHUNK_SCALARS``' int32
    entries as one vector."""
    width = vec.shape[0] - chunk_length(0, sizes)
    at = width + len(CHUNK_SCALARS)
    groups = []
    for written, gathered in sizes:
        groups.append((vec[at:at + written],
                       vec[at + written:at + written + gathered]))
        at += written + gathered
    return vec[:width], vec[width:width + len(CHUNK_SCALARS)], groups


def int32_bits(value, dtype):
    """The int32 that holds ``value``'s 32 bits as ``dtype``: how a uint32
    seed and a float32 temperature ride an int32 buffer."""
    return np.asarray(value, dtype).view(np.int32)


def sample_token(logits, key, temp, top_k):
    """One sampled token id: greedy argmax when ``temp <= 0``, else
    temperature-scaled (optionally top-k-truncated) categorical draw
    with ``key``.  Shape-stable and branch-free (``where``, not
    ``cond``) so greedy and sampling requests share ONE compiled decode
    step — a slot's sampling mode never changes the dispatched shape."""
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    z = logits / jnp.maximum(temp, 1e-6)
    if top_k is not None:
        # static k (a DecodeConfig knob): lax.top_k needs a compile-time
        # k, so the menu of sampling truncations is fixed per scheduler
        kth = jax.lax.top_k(z, top_k)[0][..., -1]
        z = jnp.where(z < kth, -jnp.inf, z)
    sampled = jax.random.categorical(key, z).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)


class StepPrograms:
    """The jitted ``decode`` and ``chunk`` programs of one model for one
    ``(top_k, donate)``, and ``chunk_counts``: whether the chunk program
    returns the model's step counters beside its token.  That is a fact of
    the model's ``prefill_chunk_fn`` that only running it shows, so it is
    None until the chunk program has been traced once — by whichever
    scheduler got there first — and True or False for every holder after.

    Every step takes ``(params, pools, packed, ...)``: the weights as an
    argument that is never donated, the cache's whole pytree donated where
    ``donate``, and the host's ONE buffer of the dispatch (the module
    docstring has its layout).  ``decode(params, pools, packed, previous,
    widths=None)``: ``previous`` is the step before's output, still on the
    device; ``widths`` (static) the width of every page group's table where
    the model has several, and otherwise left out: one table is as wide as
    the row leaves.  ``chunk(params, pools, packed, sizes=...)``: ``sizes``
    (static) a page group's ``(written, gathered)`` lengths; the chunk's
    width is what the buffer's length leaves, so there is one executable a
    width as before.  The model's functions get what they always got: the
    arrays themselves, or ``{group: array}`` where it states page groups.
    """

    def __init__(self, model, top_k, donate):
        import jax
        import jax.numpy as jnp

        self.chunk_counts = None
        pools_arg = (1,) if donate else ()
        groups = tuple(model.page_groups)

        def by_group(arrays):
            return dict(zip(groups, arrays)) if groups else arrays[0]

        bits_as = jax.lax.bitcast_convert_type

        def decode(params, pools, packed, previous, widths=None):
            tables, (tokens, positions, kv_lens, seeds, temps,
                     from_previous) = split_step(packed, widths)
            # a slot that decoded in the step before takes its token
            # from that step's output, still on the device; one whose
            # token the host holds (a prefill's first token, a hand-off,
            # a step already read) takes ``tokens``
            tokens = jnp.where(from_previous != 0,
                               previous[:tokens.shape[0]], tokens)
            logits, pools, *counts = model.decode_fn(
                params, tokens, positions, pools, by_group(tables), kv_lens)

            def samp(logit, seed, pos, temp):
                # the carried per-request key, folded with the
                # sampled token's ABSOLUTE position (kv_lens = the
                # new token's index) — identical between continuous
                # batching and solo serving, whatever the slot mix
                k = jax.random.fold_in(jax.random.PRNGKey(seed), pos)
                return sample_token(logit, k, temp, top_k)

            toks = jax.vmap(samp)(logits, bits_as(seeds, jnp.uint32),
                                  kv_lens, bits_as(temps, jnp.float32))
            if counts:
                # the model's step counters ride the tokens' readback
                toks = jnp.concatenate([toks, counts[0].astype(jnp.int32)])
            return toks, pools

        def chunk(params, pools, packed, sizes):
            tokens, scalars, vecs = split_chunk(packed, sizes)
            start, valid, slot, seed, temp = scalars
            logits, pools, *counts = model.prefill_chunk_fn(
                params, tokens, start, valid, pools,
                by_group([w for w, _ in vecs]),
                by_group([g for _, g in vecs]), slot)
            # the first generated token sits at absolute position
            # start + valid = the prompt's length at the FINAL chunk,
            # the only one whose sample is used: the same logits row
            # and the same key however the prompt was cut, so chunked
            # and monolithic first tokens match bitwise
            kk = jax.random.fold_in(
                jax.random.PRNGKey(bits_as(seed, jnp.uint32)), start + valid)
            tok = sample_token(logits, kk, bits_as(temp, jnp.float32), top_k)
            self.chunk_counts = bool(counts)
            if counts:
                # the model's chunk counters ride the token's readback
                tok = jnp.concatenate(
                    [tok[None], counts[0].astype(jnp.int32)])
            return tok, pools

        self.decode = jax.jit(decode, donate_argnums=pools_arg,
                              static_argnames=("widths",))
        # one callable for every chunk width: the width is what the buffer's
        # length leaves of ``sizes``
        self.chunk = jax.jit(chunk, donate_argnums=pools_arg,
                             static_argnames=("sizes",))
