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

**A step that carries a BLOCK a slot** (ISSUE 60: a model that states
``DecodeModel.block``, generation by diffusion over blocks of ``B``
positions).  The same buffer with ``B`` columns of ``tokens`` (the block's
ids, some of them the mask id) and one more column behind ``STEP_COLUMNS``,
``forwards`` (the denoising forwards the block has had); ``positions`` is the
block's START and ``kv_lens`` the sequence's END (a multiple of ``B``; 0: the
slot is not in the step).  How many positions a forward unmasks is decided by
its logits, so with a step in flight the host cannot say what the next step's
block is: a slot's state ``(ids [B], start, forwards)`` is carried ON THE
DEVICE from step to step (``from_previous``, as the one token is), and the
step's output is the next state with a report: ``block_state`` is its layout.
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["StepPrograms", "sample_token", "STEP_COLUMNS", "CHUNK_SCALARS",
           "step_columns", "split_step", "chunk_length", "split_chunk",
           "int32_bits", "BLOCK_COUNTERS", "block_state", "block_state_length",
           "transfer_counts", "block_candidates", "unmask_rule",
           "unmask_block"]

STEP_COLUMNS = ("tokens", "positions", "kv_lens", "seeds", "temps",
                "from_previous")
CHUNK_SCALARS = ("start", "valid", "slot", "seed", "temp")
# what a block step counts itself, behind the model's own step counters: the
# live slots of the step, those whose forward closed the block (and kept
# its K/V), and the positions unmasked
BLOCK_COUNTERS = ("diffusion.forwards", "diffusion.kv_forwards",
                  "diffusion.unmasked")
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


def step_columns(widths, block=0):
    """Columns of a decode step's buffer over tables ``widths`` wide; with
    ``block = B`` the ``B`` columns of a block's ids and ``forwards``."""
    return _table_spans(widths)[-1][1] + len(STEP_COLUMNS) + block


def split_step(buf, widths=None, block=0):
    """``(tables, columns)`` of a decode step's buffer ``[slots, columns]``,
    numpy (views, to fill) or traced (slices, to read): a table a width and
    ``STEP_COLUMNS``' int32 columns in order.  ``widths`` None: one table, as
    wide as the row leaves.  ``block = B``: ``tokens`` is ``[slots, B]`` and
    ``forwards`` follows ``from_previous``."""
    if widths is None:
        widths = (buf.shape[1] - len(STEP_COLUMNS) - block,)
    spans = _table_spans(widths)
    at = spans[-1][1]
    tables = [buf[:, a:b] for a, b in spans]
    if not block:
        return tables, [buf[:, at + i] for i in range(len(STEP_COLUMNS))]
    return tables, [buf[:, at:at + block]] + [
        buf[:, at + block + i] for i in range(len(STEP_COLUMNS))]


def block_state_length(slots, block):
    """Length of a block step's state and report, before the counters."""
    return slots * (block + 3)


def block_state(vec, slots, block):
    """``(ids [slots, B], start, forwards, flags, counters)`` of a block
    step's output (numpy or traced): the state each slot's NEXT forward starts
    from, and ``flags``, what this forward did: bit ``i`` set where it unmasked
    position ``i`` of the block it was given, bit ``B`` where it found that
    block whole or out of denoising forwards (``unmask_rule``), kept its K/V
    and moved on (``start`` is then the next block's and ``ids`` are all the
    mask id)."""
    n = slots * block
    return (vec[:n].reshape(slots, block), vec[n:n + slots],
            vec[n + slots:n + 2 * slots], vec[n + 2 * slots:n + 3 * slots],
            vec[n + 3 * slots:])


def transfer_counts(block, steps):
    """Positions denoising forward ``t`` of a block must unmask at least:
    ``block`` spread over ``steps`` forwards, the remainder to the first."""
    return [block // steps + (t < block % steps) for t in range(steps)]


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


def block_candidates(logits, keys, temps, top_k=None):
    """Every row's candidate and its confidence: ``logits [R, V]`` float32
    (unshifted: a row predicts the id AT its position), ``keys [R]`` and
    ``temps [R]`` a row's PRNG key and temperature.  The candidate is
    ``sample_token``'s, the confidence its softmax probability in float32.
    Every id is a candidate, the mask id too: a position "unmasked" to it
    stays masked, and its block closes by the count of its forwards
    (:func:`unmask_rule`).  Rows are a step's ``slots x B`` positions as ONE
    axis: ``[S, B, V]`` with ``B`` under a tile's eight sublanes would be a
    padded copy of the logits."""
    import jax
    import jax.numpy as jnp

    cand = jax.vmap(lambda l, k, t: sample_token(l, k, t, top_k))(
        logits, keys, temps)
    picked = jnp.take_along_axis(logits, cand[:, None], axis=1)[:, 0]
    return cand, jnp.exp(picked - jax.scipy.special.logsumexp(logits, axis=-1))


def unmask_rule(ids, cand, conf, forwards, *, mask_id, steps, threshold):
    """One forward's decision over one block: ``ids [B]`` with the mask id at
    the masked positions, ``cand`` / ``conf [B]`` every position's candidate
    and confidence, ``forwards`` the denoising forwards the block has had.
    With ``n`` = ``transfer_counts``' entry of ``forwards`` a denoising
    forward unmasks every masked position whose confidence is above
    ``threshold`` if those are at least ``n``, else the ``n`` masked positions
    of highest confidence (ties to the lower position; all that are left if
    fewer).  Returns ``(ids', unmasked [B] bool, whole)``; ``whole``: the
    block is CLOSED (nothing is unmasked, this forward's K/V is the block's):
    ``ids`` held no mask id, or the block has had its ``steps`` denoising
    forwards (it then holds the mask id wherever that was a position's
    candidate: the release's loop runs ``steps + 1`` forwards a block at
    most).  Branch-free: one program whatever the block holds."""
    import jax.numpy as jnp

    B = ids.shape[0]
    masked = ids == mask_id
    whole = jnp.logical_not(masked.any()) | (forwards >= steps)
    conf = jnp.where(masked, conf, -jnp.inf)
    counts = jnp.asarray(transfer_counts(B, steps), jnp.int32)
    n = counts[jnp.clip(forwards, 0, steps - 1)]
    high = conf > threshold
    at = jnp.arange(B)
    ahead = ((conf[None, :] > conf[:, None])
             | ((conf[None, :] == conf[:, None]) & (at[None, :] < at[:, None])))
    best = masked & (ahead.sum(axis=1) < n)
    unmasked = jnp.where(high.sum() >= n, high, best) & ~whole
    return jnp.where(unmasked, cand, ids).astype(jnp.int32), unmasked, whole


def unmask_block(ids, logits, keys, temp, forwards, *, mask_id, steps,
                 threshold, top_k=None):
    """:func:`block_candidates` and :func:`unmask_rule` over ONE block:
    ``ids [B]``, ``logits [B, V]`` float32, ``keys [B]``, one ``temp``."""
    import jax.numpy as jnp

    cand, conf = block_candidates(
        logits, keys, jnp.broadcast_to(temp, ids.shape), top_k)
    return unmask_rule(ids, cand, conf, forwards, mask_id=mask_id,
                       steps=steps, threshold=threshold)


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

    A model that states a ``block`` gets the block form of ``decode`` (the
    module docstring): its ``decode_fn`` receives ``ids [S, B]``, the blocks'
    starts and ``kv_lens = start + B`` (0: out of the step) and returns
    ``logits [S, B, V]``; the unmasking rule (``block_candidates``, then
    ``unmask_rule`` a slot) runs in the
    program, under the named scope ``<model.name>.unmask``; the output is
    ``block_state``'s vector, then the model's step counters, then
    ``BLOCK_COUNTERS``.  Its chunk program is the plain one (whose sampled
    token nobody reads: a block model's logits are unshifted).
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

        blk = model.block

        def decode_block(params, pools, packed, previous, widths=None):
            B, mask_id = blk["length"], blk["mask_id"]
            tables, (ids, start, end, seeds, temps, from_previous,
                     forwards) = split_step(packed, widths, B)
            S = ids.shape[0]
            # a slot with a step in flight takes its block from that step's
            # output, still on the device: the host learns a step late what
            # a forward unmasked and whether it finished the block
            p_ids, p_start, p_forwards, _, _ = block_state(previous, S, B)
            take = from_previous != 0
            ids = jnp.where(take[:, None], p_ids, ids)
            start = jnp.where(take, p_start, start)
            forwards = jnp.where(take, p_forwards, forwards)
            # past its last block a slot is out of the step, like an empty
            # one (the host may have sent one step more than the sequence had)
            live = (end > 0) & (start < end)
            logits, pools, *counts = model.decode_fn(
                params, ids, start, pools, by_group(tables),
                jnp.where(live, start + B, 0))

            def key(seed, pos, forwards):
                # a position's key: the request's, folded with the ABSOLUTE
                # position and then with the block's forward
                return jax.random.fold_in(jax.random.fold_in(
                    jax.random.PRNGKey(seed), pos), forwards)

            def rows(a):
                return jnp.repeat(a, B)

            with jax.named_scope(model.name + ".unmask"):
                at = (start[:, None] + jnp.arange(B)[None, :]).reshape(-1)
                cand, conf = block_candidates(
                    logits.reshape(S * B, -1).astype(jnp.float32),
                    jax.vmap(key)(rows(bits_as(seeds, jnp.uint32)), at,
                                  rows(forwards)),
                    rows(bits_as(temps, jnp.float32)), top_k)
                new_ids, unmasked, whole = jax.vmap(functools.partial(
                    unmask_rule, mask_id=mask_id, steps=blk["steps"],
                    threshold=blk["threshold"]))(
                        ids, cand.reshape(S, B), conf.reshape(S, B), forwards)
                whole = whole & live
                denoised = live & ~whole
                unmasked = unmasked & denoised[:, None]
                flags = ((unmasked.astype(jnp.int32)
                          << jnp.arange(B)[None, :]).sum(axis=1)
                         + (whole.astype(jnp.int32) << B))
                state = [
                    jnp.where(whole[:, None], mask_id, jnp.where(
                        denoised[:, None], new_ids, ids)).reshape(-1),
                    jnp.where(whole, start + B, start),
                    jnp.where(whole, 0, forwards + denoised),
                    flags]
                own = jnp.stack([live.sum(), whole.sum(), unmasked.sum()])
            out = jnp.concatenate(
                [x.astype(jnp.int32) for x in state]
                + [c.astype(jnp.int32) for c in counts] + [
                    own.astype(jnp.int32)])
            return out, pools

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

        # the block form keeps the name: a device trace's ``jit_decode``
        # is the decode step of every model
        decode_block.__name__ = decode_block.__qualname__ = "decode"
        self.decode = jax.jit(decode_block if blk else decode,
                              donate_argnums=pools_arg,
                              static_argnames=("widths",))
        # one callable for every chunk width: the width is what the buffer's
        # length leaves of ``sizes``
        self.chunk = jax.jit(chunk, donate_argnums=pools_arg,
                             static_argnames=("sizes",))
