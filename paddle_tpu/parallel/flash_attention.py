"""Flash attention: Pallas TPU kernels, forward AND backward.

Reference analog: the reference computes attention as separate
matmul/softmax/matmul ops (nets.py scaled_dot_product_attention,
operators/math/softmax.cu) — O(T²) HBM traffic.  Here the forward is a
single Pallas kernel (online softmax, O(T) HBM per row block, q·kᵀ and p·v
tiles in VMEM).  Two backward engines exist, chosen from the shape
(``_bwd_engine``): a fused one-grid Pallas kernel wherever its query side
fits the VMEM it may ask for, from ``_BWD_MIN_T`` = 256 query rows on (every
shape a cell runs), and the lax.scan-over-key-blocks formulation in plain XLA
elsewhere.  Neither materializes a [T, S] tensor.

LAYOUT (PR 43).  The kernels read and write the projections' OWN rows: q
``[B, T, H * D]``, k and v ``[B, S, H * D]``, head ``h`` the lanes ``h * D :
(h + 1) * D`` — what ``fc`` gives and what the output ``fc`` takes
(``flash_attention_rows``).  Until then they took ``[B * H, T, D]``, a layout
XLA cannot change for a Mosaic call, so every head split and merge around
them was a real pass over the tensor in HBM: 180 copies and casts a training
step, 18.8 ms of 136.9 at T = 2048 (PERF.md section 5).  A BLOCK of rows is
the fewest heads whose lanes are whole 128-lane tiles (``_lane_heads``: two
heads at D = 64, which a ``[.., 64]`` block padded to twice their bytes in
VMEM and moved in 256-byte DMA rows; one at D = 128; all of ``H * D`` where
no count of heads makes whole tiles).  A head meets the MXU as the block's
full lane width with the other heads' lanes zeroed on ONE operand (q in the
forward, k and v in the backward): the contraction then sums its own lanes
only, at no more MXU passes than a depth-64 product took, and every store
(out, dq, dk, dv) is one lane-dense row of all the block's heads.  The
softmax statistics travel as lane-dense ROWS too: the forward emits ``lse``
as ``[B, H, T]`` floats (one ``[1, block_q]`` row a head and query block; it
used to ship a lane-replicated ``[.., T, 128]``), and the backward makes
``delta = rowsum(do * out)`` itself from the resident ``do`` and ``out`` —
both through a tiny f32-precision MXU product (``_lane_sums_as_rows``), so
no XLA glue stands between the two kernels.  ``flash_attention`` on ``[B, H,
T, D]`` is the same kernels behind a transpose at its edge.

The forward's tiles are CHOSEN from the shape (``_fwd_tiles``; PR 29).  Until
then every call walked a grid of 128 x 128 tiles, and on v5e such a grid step
cost 0.65-0.73 µs whatever it held (ledger, PR 28: 10.7 ms a non-causal call
on f32[64,2048,64], 3% of its roofline); July's kernel-only sweeps at blocks
of 128, which the choices below used to quote, measured that overhead and
not the kernels.  Now a step takes a query block of up to 512 rows against
the K and V of its (batch, head group) held WHOLE in VMEM (fetched once),
walks them 512 keys a turn inside the step up to the last key a row of the
block can see, and at T <= 512 takes several batch rows: 1.08 ms for the
same call (traced chip run, PR 29; PERF.md sections 5 and 6 have what a step
and a turn cost).
The backward's tiles are chosen from the shape too (``_bwd_tiles``; PR 34):
a grid step is one 512-key block against the query side of its (batch, head
group), RESIDENT in VMEM and walked inside the step 512 rows a turn, several
batch rows a step at T <= 512; tiles a causal mask or ``kv_lens`` hides take
no turn.  Until then a step was one 128-key block against ALL of T at once,
four [T, 128] intermediates a step: 3.91 ms a call on f32[64,2048,64], and
past the scoped VMEM limit at T = 4096, which ran the scan at 16.8 ms a call;
then 2.03 and 3.94 ms (kernel-only chip runs, PR 34; PERF.md section 6).
Both kernels are compiled with the VMEM limit their residency models ask for
(``_fwd_vmem_bytes``, ``_bwd_vmem_bytes``: calibrated against the compiler,
their docstrings have the points); the models are the only selectors.

Supports causal masking and per-sequence key lengths (`kv_lens`) — the
padding-mask case of the Fluid transformer — without materializing any
[T, S] bias tensor.  TPU-lowering notes:

* `kv_lens` rides the scalar-prefetch path (`pltpu.PrefetchScalarGridSpec`,
  SMEM) — a (1, 1)-blocked VMEM operand is not a legal Mosaic block for a
  [B]-shaped array.
* m/l scratch are lane-replicated (block_q, 128) and stay so through the
  softmax update (``_lanes``): a [block_q, 1] statistic costs a lane
  broadcast at every use, which was 40% of a turn.
* causal masking matches ``mha_reference``'s ``tril(k=S-T)`` — query row t
  attends keys up to ``t + S - T`` — and key chunks no row of the query
  block sees are neither stepped over, computed nor (where S is not held
  whole) fetched; chunks every row sees whole skip the mask arithmetic.
* an f32 ``jnp.dot`` in a kernel is ONE bf16 MXU pass at default precision
  (the chosen tiles differ from blocks of 128 by 1e-3, blocks of 128 from
  the parent's by 0): operands narrower than f32 would save DMA bytes, not
  MXU passes.

On CPU (tests) the same kernel runs under ``interpret=True``; the mode is
inferred from the *input arrays'* platform when they are concrete, falling
back to the default backend under tracing.
"""
from __future__ import annotations

import functools
import math

import jax
import numpy as np

from ..core import cpu_backend

__all__ = ["flash_attention", "flash_attention_rows", "mha_reference",
           "paged_decode_attention", "paged_prefill_attention",
           "paged_mla_decode_attention", "paged_mla_prefill_attention",
           "paged_gqa_decode_attention", "paged_gqa_prefill_attention",
           "paged_eva_decode_attention", "paged_eva_prefill_attention",
           "block_scores", "paged_block_scores", "paged_kv_finite"]

# what tools and tests pass explicitly, and the scan backward's key block;
# the kernels choose their own from the shape (_fwd_tiles, _bwd_tiles)
DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def mha_reference(q, k, v, causal=False, sm_scale=None, kv_lens=None):
    """Plain XLA attention (for testing / tiny shapes). [B, H, T, D].

    Decode contract: a row whose ``kv_lens`` entry is 0 (fully masked —
    an inactive decode slot) yields ZEROS, matching the flash kernels
    (whose online softmax accumulates nothing over skipped blocks)
    instead of the degenerate uniform-mean a plain softmax over an
    all-masked row would produce."""
    import jax.numpy as jnp

    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * sm_scale
    T, S = s.shape[-2], s.shape[-1]
    if causal:
        mask = jnp.tril(jnp.ones((T, S), bool), k=S - T)
        s = jnp.where(mask, s, NEG_INF)
    if kv_lens is not None:
        mask = jnp.arange(S)[None, :] < kv_lens[:, None]  # [B, S]
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if kv_lens is not None:
        p = jnp.where(kv_lens[:, None, None, None] > 0, p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _lanes(x, n):
    """A lane-replicated ``[rows, 128]`` statistic at ``n`` lanes, without a
    lane broadcast where ``n`` is whole vregs or a part of one."""
    import jax.numpy as jnp

    if n % x.shape[1] == 0:
        return jnp.tile(x, (1, n // x.shape[1]))
    if n < x.shape[1]:
        return x[:, :n]
    return jnp.broadcast_to(x[:, 0:1], (x.shape[0], n))


def _lane_heads(H, D):
    """Heads a block of ``[B, T, H * D]`` rows holds in its lanes: the fewest
    whose lanes make whole 128-lane tiles and that divide H (two at D = 64,
    one at D = 128), all H where no such count exists (an odd H at D = 64, or
    ``H * D`` under 128: a block as wide as the array is always a legal one)."""
    for n in range(1, H):
        if H % n == 0 and (n * D) % 128 == 0:
            return n
    return H


def _head_lanes(shape, h, D):
    """Which lanes of a ``[rows, heads * D]`` tile are head ``h``'s."""
    import jax.numpy as jnp

    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= h * D) & (lane < (h + 1) * D)


def _merge_heads(tiles, D):
    """One ``[rows, heads * D]`` tile that takes head ``h``'s lanes from
    ``tiles[h]``: what makes the stores of a step lane-dense."""
    import jax.numpy as jnp

    out = tiles[0]
    for h in range(1, len(tiles)):
        out = jnp.where(_head_lanes(out.shape, h, D), tiles[h], out)
    return out


def _lane_sums_as_rows(x, weights):
    """``weights [8, lanes] . x [rows, lanes]^T -> [8, rows]``: sums over the
    lanes of every row of ``x``, delivered as lane-dense ROWS (what the
    backward's ``[keys, query rows]`` tiles broadcast over their sublanes)
    with no transpose and no ``[rows, 1]`` statistic.  Through the MXU, and
    exact in f32: ``weights`` are 0 or 1 and ``x`` goes in as its three bf16
    parts (8 + 8 + 8 significand bits), one pass each, so every product is
    exact and the sum is f32's (the compiler's own f32 product takes six
    passes for weights it cannot know are exact)."""
    import jax.numpy as jnp

    w = weights.astype(jnp.bfloat16)
    out = None
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        x = x - part.astype(jnp.float32)
        term = jax.lax.dot_general(w, part, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        out = term if out is None else out + term
    return out


def _to_rows(x):
    """``[B, H, T, D]`` as the projections' rows ``[B, T, H * D]``."""
    B, H, T, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B, T, H * D)


def _from_rows(x, H):
    B, T, HD = x.shape
    return x.reshape(B, T, H, HD // H).transpose(0, 2, 1, 3)


def _lens_per_batch(kv_lens, B, S):
    """``[B]`` int32 key lengths for the scalar-prefetch path (S where the
    caller gave none)."""
    import jax.numpy as jnp

    if kv_lens is None:
        return jnp.full((B,), S, jnp.int32)
    return kv_lens.astype(jnp.int32)


def _fwd_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, sm_scale, causal, batches, heads, head_dim, block_q, block_k,
                chunks, num_k_blocks, q_len, kv_len):
    """One grid step = ``batches`` batch rows x the ``heads`` heads whose
    lanes make one block of the ``[B, T, H * D]`` rows x one query block x
    one RESIDENT key span of ``chunks * block_k`` rows (all of S where it fits
    VMEM: then K and V are fetched once a (batch, head group) and the grid has
    no key axis to step over).  A head is a lane slice of the block and meets
    the MXU as the block's FULL lane width: its q has the other heads' lanes
    zeroed, so ``q . k^T`` contracts over all the lanes and sums only its own
    (at D = 64 a depth-64 product was half an MXU pass anyway), ``p . v``
    gives every lane and the head's own are taken at the end, where the
    heads' tiles are merged into ONE lane-dense store.  The key loop runs
    INSIDE the step, ``block_k`` rows a turn (K and V loaded once a turn for
    all the heads), and is bounded by the last chunk a row of this query
    block can see — a masked chunk is neither stepped over nor computed — and
    the chunks every row sees whole take the turn without the mask
    arithmetic."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    g = pl.program_id(0)
    q0 = pl.program_id(2) * block_q
    kj = pl.program_id(3)
    k0 = kj * (chunks * block_k)
    shift = kv_len - q_len  # causal: row t sees keys [0, t + shift] — tril(k=S-T)
    width = acc_scr.shape[2]

    def chunk(n, kvl, qs, last, c, masked):
        start = pl.multiple_of(c * block_k, block_k)
        k = k_ref[n, pl.ds(start, block_k), :].astype(jnp.float32)  # [bk, width]
        v = v_ref[n, pl.ds(start, block_k), :].astype(jnp.float32)
        if masked:
            # zero invalid v rows: 0·NaN from OOB-padded tail tiles would
            # poison the p·v accumulation even where p is 0 (a score off
            # such a k row is replaced below, whatever it is)
            kcol = k0 + start + jax.lax.broadcasted_iota(jnp.int32, (block_k, 1), 0)
            v = jnp.where(kcol < kvl, v, 0.0)
            # one compare a score: the chunk's own column index against each
            # row's last visible one (lane-replicated, as m and l are)
            col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            seen = col <= _lanes(last - (k0 + start), block_k)
        for h, q in enumerate(qs):
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)  # [bq, bk]
            if masked:
                s = jnp.where(seen, s, NEG_INF)
            # m, l and alpha stay lane-replicated [bq, 128] through the
            # update: the only lane traffic a turn is the two row reductions
            m_prev = m_scr[h]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, block_k))
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = l_scr[h] * alpha + p.sum(axis=1, keepdims=True)
            acc_scr[h] = acc_scr[h] * _lanes(alpha, width) + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    def batch(n, carry):
        kvl = lens_ref[g * batches + n]  # valid key length of this batch row

        # m/l/acc are one query block's, a set a head; several batch rows a
        # step take turns at them (the chooser gives batches > 1 only with
        # the whole of S resident)
        @pl.when(kj == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # keys [0, some) are seen by SOME row of the block, [0, every) by
        # EVERY row: chunks under `every` need no mask, chunks past `some`
        # no turn.  Correctness rests on the mask alone (NEG_INF zeroes a
        # masked score); the bounds only save the work — except for the
        # kv_lens == 0 contract, where no turn is taken and _finish emits 0.
        some = every = kvl
        if causal:
            some = jnp.minimum(kvl, q0 + block_q + shift)
            every = jnp.minimum(kvl, q0 + shift + 1)
        n_some = jnp.clip((some - k0 + block_k - 1) // block_k, 0, chunks)
        n_every = jnp.clip((every - k0) // block_k, 0, n_some)

        # each row's last visible key, [bq, 128] lane-replicated: query row t
        # sees keys [0, t + S - T] — tril(k=S-T), matching mha_reference for
        # T != S (bottom-right aligned) — and none from kv_lens on
        last = jnp.full(m_scr.shape[1:], kvl - 1, jnp.int32)
        if causal:
            row = q0 + jax.lax.broadcasted_iota(jnp.int32, last.shape, 0)
            last = jnp.minimum(last, row + shift)

        # the softmax scale goes into q once a step, not into every score
        # tile, and so do the zeros in the other heads' lanes
        q = q_ref[n].astype(jnp.float32) * sm_scale  # [bq, width]
        qs = [q] if heads == 1 else [
            jnp.where(_head_lanes(q.shape, h, head_dim), q, 0.0) for h in range(heads)]
        jax.lax.fori_loop(
            0, n_every, lambda c, _: chunk(n, kvl, qs, last, c, False), None)
        jax.lax.fori_loop(
            n_every, n_some, lambda c, _: chunk(n, kvl, qs, last, c, True), None)

        @pl.when(kj == num_k_blocks - 1)
        def _finish():
            # lane 0 of a lane-replicated [bq, 128] statistic, as a row
            lane0 = (jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1) == 0
                     ).astype(jnp.float32)
            outs = []
            for h in range(heads):
                denom = jnp.maximum(l_scr[h], 1e-30)
                outs.append(acc_scr[h] / _lanes(denom, width))
                # lse leaves as the backward reads it: one lane-dense [1, bq]
                # row a (head, query block), T floats a head where a lane-
                # replicated [T, 128] block was 128 times the bytes
                lse_ref[n, h, 0] = _lane_sums_as_rows(
                    m_scr[h] + jnp.log(denom), lane0)[0:1]
            o_ref[n] = _merge_heads(outs, head_dim).astype(o_ref.dtype)

        return carry

    jax.lax.fori_loop(0, batches, batch, None)


# What a forward step may ask of VMEM.  The kernel is compiled with the limit
# its residency model asks for (``_vmem_limit``), as the backward is: K and V
# of S = 4096 whole beside two heads' tiles are 13.0 MB, too near the
# compiler's default of 16 MB to leave to it.  The only selector: where the
# model is wrong at some shape the step's compile error says so.
_FWD_VMEM_BUDGET = 24 * 1024 * 1024
# A turn of the key loop is one [block_q, block_k] score tile a head; 512 x
# 512 is where a turn's fixed costs (the m/l/acc read-modify-write, the loop)
# stop showing on v5e (PERF.md section 6, PR 29).
_FWD_BLOCK = 512
# (batch, head) pairs a step at short T: enough that a step holds about a
# 512 x 512 tile
_FWD_MAX_HEADS = 8


def _fwd_vmem_bytes(batches, heads, block_q, block_k, chunks, D, in_itemsize):
    """Scoped-VMEM residency of one forward grid step: ``batches`` batch rows
    of a block of ``heads`` heads' lanes (``_lane_heads``).  A VMEM row is
    whole 128-lane tiles: two heads of D = 64 fill one, where a ``[.., D]``
    block padded each head to twice its bytes.  Terms:
      k, v resident span, double-buffered .... 2 * 2 * span * lanes * isz
      q, out blocks, double-buffered ......... 2 * 2 * block_q * lanes * isz
      lse rows [1 -> 8, block_q] f32 ......... heads * 2 * 8 * block_q * 4
      m, l, acc scratch, a set a head ........ heads * block_q * (2 * 128 + lanes) * 4
      the heads' masked f32 q ................ heads * block_q * lanes * 4
      one key-loop turn: 1.5 [block_q, block_k] f32 tiles a head (s and p,
      partly fused) and the f32 k and v chunks
    Calibrated against the compiler (the least ``vmem_limit_bytes`` at which
    a described v5e compiles the kernel, 36 points forward and backward, PR
    43): at T = S = 2048, D = 64 (two heads a block), f32 it takes 8.93 MB at
    blocks 512 x 512 (7.0 for ONE head of a ``[B * H, T, D]`` array before),
    6.03 at 256 x 256, 13.76 at 1024 x 512, 18.21 at 1024 x 1024; 12.99 at S
    = 4096 whole and 20.92 at 8192; 5.64 at T = 256 with four batch rows a
    step; 7.57 at D = 128 (one head a block).  It reads 6-27% over the
    compiler at every f32 point measured and 5-35% for bf16 inputs."""
    lanes = -(-heads * D // 128) * 128
    span = chunks * block_k
    streamed = batches * (4 * span * lanes * in_itemsize
                          + block_q * (4 * lanes * in_itemsize + heads * 2 * 8 * 4))
    scratch = heads * block_q * (2 * 128 + 2 * lanes) * 4
    turn = heads * 6 * block_q * block_k + 2 * block_k * lanes * 4
    return streamed + scratch + turn


def _fwd_tiles(B, H, T, S, D, in_itemsize):
    """``(batches, heads, block_q, block_k, chunks)`` of the forward, chosen
    from the shape alone (no probe, no fallback): the heads a block of rows
    holds in its lanes (``_lane_heads``), a query block and a key chunk of up
    to ``_FWD_BLOCK`` rows, as much of S resident a step as the budget holds
    (all of it at the cells' shapes), and at one query block a batch row
    several batch rows a step.  ``causal`` does not enter: on v5e 512 x 512
    was the fastest tile with and without it (a smaller query block trims
    the masked diagonal and loses more to the step's fixed costs)."""
    heads = _lane_heads(H, D)
    block_q = min(_FWD_BLOCK, T)
    block_k = min(_FWD_BLOCK, S)

    def fits(batches, chunks):
        return _fwd_vmem_bytes(batches, heads, block_q, block_k, chunks, D,
                               in_itemsize) <= _FWD_VMEM_BUDGET

    chunks = max(1, S // block_k)
    while chunks > 1 and not fits(1, chunks):
        chunks = -(-chunks // 2)
    batches = 1
    if block_q == T and chunks * block_k >= S:
        for n in range(2, _FWD_MAX_HEADS // heads + 1):
            if B % n == 0 and n * heads * block_q * block_k * chunks <= 2 * _FWD_BLOCK ** 2 \
                    and fits(n, chunks):
                batches = n
    return batches, heads, block_q, block_k, chunks


# The name a device trace shows the forward under, as the backward's below:
# the trace readers match custom calls whose name holds "flash_attention".
_FWD_KERNEL_NAME = "flash_attention_fwd"


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_fwd(q, k, v, kv_lens, n_head, causal, sm_scale, block_q, block_k,
               interpret, layout):
    """``(out, lse)``.  A jit of its own, as the backward is: a step's
    eighteen calls are two shapes (causal or not), and each is then traced
    and lowered to Mosaic once, not at every call site (set-up time at every
    process start)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import observability as obs

    B, T, HD = q.shape
    S = k.shape[1]
    H, D = n_head, HD // n_head
    batches, heads, bq, bk, chunks = _fwd_tiles(B, H, T, S, D, q.dtype.itemsize)
    if block_q is not None or block_k is not None:
        # explicit blocks are taken as given: one batch row a step, and as
        # many whole chunks of S resident as the array holds
        bq = min(block_q or bq, T)
        bk = min(block_k or bk, S)
        batches, chunks = 1, max(1, S // bk)
    width = heads * D
    span = chunks * bk
    nq = -(-T // bq)
    nk = -(-S // span)
    assert batches == 1 or nk == 1, (batches, bq, bk, chunks)  # one m/l/acc a step
    lens = _lens_per_batch(kv_lens, B, S)

    # what was chosen, once per compiled shape (this runs at trace time): a
    # reader of a device trace divides the kernel's time by its grid steps
    steps = obs.counter("flash.fwd.grid_steps", labels={
        "T": T, "S": S, "block": "%dx%d" % (bq, bk), "heads": batches * heads,
        "bh": B * H, "causal": int(bool(causal)), "layout": layout})
    if not steps.value:
        steps.inc((B // batches) * (H // heads) * nq * nk)

    def kv_block(g, c, i, j, lens):
        if nk == 1:
            return (g, 0, c)
        # a key span no row of the query block sees keeps the index of the
        # last one seen: the pipeline then issues no copy for it (nk > 1
        # comes with batches == 1, so lens[g] is the step's one length)
        some = lens[g]
        if causal:
            some = jnp.minimum(some, i * bq + bq + (S - T))
        return (g, jnp.minimum(j, jnp.maximum((some - 1) // span, 0)), c)

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, batches=batches,
        heads=heads, head_dim=D, block_q=bq, block_k=bk, chunks=chunks,
        num_k_blocks=nk, q_len=T, kv_len=S,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // batches, H // heads, nq, nk),
        in_specs=[
            pl.BlockSpec((batches, bq, width), lambda g, c, i, j, lens: (g, i, c)),
            pl.BlockSpec((batches, span, width), kv_block),
            pl.BlockSpec((batches, span, width), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((batches, bq, width), lambda g, c, i, j, lens: (g, i, c)),
            pl.BlockSpec((batches, heads, 1, 1, bq),
                         lambda g, c, i, j, lens: (g, c, i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, bq, 128), jnp.float32),    # running max (lane-replicated)
            pltpu.VMEM((heads, bq, 128), jnp.float32),    # running sum (lane-replicated)
            pltpu.VMEM((heads, bq, width), jnp.float32),  # output accumulators
        ],
    )
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, T, HD), q.dtype),
            jax.ShapeDtypeStruct((B, H, nq, 1, bq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(_fwd_vmem_bytes(
                batches, heads, bq, bk, chunks, D, q.dtype.itemsize)),
        ),
        interpret=interpret,
        name=_FWD_KERNEL_NAME,
    )(lens, q, k, v)
    # [B, H, T]: a free reshape where T is whole query blocks (a tail block's
    # rows past T hold anything)
    return out, lse.reshape(B, H, nq * bq)[:, :, :T]


def _flash_bwd_scan(n_head, causal, sm_scale, block_k, res, do):
    """Blockwise flash backward in plain JAX (lax.scan over key blocks) on the
    same rows — what ``_bwd_engine`` picks under ``_BWD_MIN_T`` query rows and
    where the fused kernel's resident query side is past its VMEM budget (no
    shape a cell runs)."""
    import jax.numpy as jnp

    q, k, v, kv_lens, out, lse = res
    B, T, HD = q.shape
    S = k.shape[1]
    H, D = n_head, HD // n_head

    def heads(x):  # [B, T, H * D] -> [B, T, H, D] f32: a view, no pass
        return x.astype(jnp.float32).reshape(x.shape[:2] + (H, D))

    qf, kf, vf, dof = heads(q), heads(k), heads(v), heads(do)
    delta = jnp.einsum("bqhd,bqhd->bhq", dof, heads(out))  # [B,H,T]

    bk = min(block_k, S)
    nk = -(-S // bk)
    pad = nk * bk - S
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = kf.reshape(B, nk, bk, H, D)
    vb = vf.reshape(B, nk, bk, H, D)

    col_base = jnp.arange(nk) * bk
    rows = jnp.arange(T)
    klim = jnp.full((B,), S, jnp.int32) if kv_lens is None else kv_lens.astype(jnp.int32)

    def kblock(dq, it):
        kj, vj, j0 = it  # [B,bk,H,D], [B,bk,H,D], scalar col offset
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kj) * sm_scale
        cols = j0 + jnp.arange(bk)
        valid = cols[None, None, None, :] < klim[:, None, None, None]
        if causal:
            # same bottom-right-aligned tril(k=S-T) as the forward kernel
            valid = valid & (rows[:, None] + (S - T) >= cols[None, :])[None, None]
        p = jnp.where(valid, jnp.exp(s - lse[..., :, None]), 0.0)  # [B,H,T,bk]
        dv_j = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
        dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vj)
        ds = p * (dp - delta[..., :, None]) * sm_scale
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds, kj)
        dk_j = jnp.einsum("bhqk,bqhd->bkhd", ds, qf)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros_like(qf)
    its = (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), col_base)
    dq, (dk_b, dv_b) = jax.lax.scan(kblock, dq0, its)
    dk = jnp.moveaxis(dk_b, 0, 1).reshape(B, nk * bk, HD)[:, :S]
    dv = jnp.moveaxis(dv_b, 0, 1).reshape(B, nk * bk, HD)[:, :S]
    return dq.reshape(B, T, HD).astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


# Two backward engines, chosen from the shape by ``_bwd_engine`` and by
# nothing else.  "fused" is the dq+dkv-in-ONE-grid Pallas kernel (5 matmuls a
# head, p computed once a tile feeds dv, dq and dk; every tensor touches HBM
# once): a grid step holds one key block of ``batches`` batch rows of one
# head group's lanes and walks the query side, which stays resident in VMEM
# for the whole key walk, one query block a turn.  "scan" is lax.scan over
# key blocks in plain XLA: what is left for short sequences (under
# ``_BWD_MIN_T`` query rows) and for a shape whose query side the kernel's
# residency model refuses.
#
# A turn of the backward is one [block_k, block_q] tile of s, p, dp and ds a
# head.
_BWD_BLOCK_Q = 512
_BWD_BLOCK_K = 512
# (batch, head) pairs a step at short T, as the forward's
_BWD_MAX_HEADS = 8
# The kernel is compiled with the scoped-VMEM limit its residency model asks
# for (``_vmem_limit``): the 16 MB default is the compiler's default, not
# the core's VMEM, which is 128 MiB on v5e.  A shape may ask for up to this
# budget (and a quarter more as the limit: under half the core's); past it
# ``_bwd_engine`` answers "scan".  The only selector: where the model is
# wrong at some shape the step's compile error says so (no probe, no fallback).
_BWD_VMEM_BUDGET = 48 * 1024 * 1024
# Query rows from which the kernel runs: the least T the chip has measured it
# at.  PR 34 found the scan as fast at T = 256 ([512, 256, 64]: 156.6 ms a
# step with the kernel against 155.2), "the glue the whole difference": the
# gradients' casts and relayouts, which XLA fused into the scan's last turn
# and could not fuse into a custom call.  On rows there is no such glue, and
# tfbase_train_s256 reads 83.1 ms a step with the kernel against 102.8 with
# the scan, 194.8 k items/s against 157.5 k (chip runs, PR 43: PERF.md
# section 6).  Under 256 rows nothing is measured (at 64 the chip's compiler
# refuses the forward's 64-key turn, before PR 43 as after it).
_BWD_MIN_T = 256


def _bwd_vmem_bytes(batches, heads, block_q, block_k, T, D, in_itemsize):
    """Scoped-VMEM residency of one backward grid step (``T`` already a
    multiple of ``block_q``): ``batches`` batch rows of a block of ``heads``
    heads' lanes.  A VMEM row is whole 128-lane tiles.  Terms:
      q, do, out resident, double-buffered ... 3 * 2 * T * lanes * isz
      dq output block, f32, double-buffered .. 2 * T * lanes * 4
      lse rows [T / bq, 1 -> 8, bq], double-buffered, and the delta scratch
                                               heads * 3 * 8 * T * 4
      k, v, dk, dv blocks, double-buffered ... 4 * 2 * block_k * lanes * isz
      dk, dv scratch and the masked f32 k, v, a set a head
                                               heads * 4 * block_k * lanes * 4
      one turn: 2.5 [block_k, block_q] f32 tiles a head (s, p, dp, ds and
      the transposed ds, partly fused), the f32 q and do blocks and the
      turn's dq
    Calibrated against the compiler as the forward's: at D = 64, f32, tiles of
    512 x 512 it takes 14.73 / 22.85 / 39.09 MB at T = 2048 / 4096 / 8192 (4
    KB a query row: eight [T, 128-lane] f32 buffers, two heads'), 17.05 MB at
    T = 2048 with 1024 query rows a turn, 10.67 at 256 x 256, 9.89 at T = 256
    with four batch rows a step, 12.99 at D = 128.  It reads 9-42% over the
    compiler at every f32 point measured and 11-50% for bf16 inputs."""
    lanes = -(-heads * D // 128) * 128
    resident = batches * T * (6 * lanes * in_itemsize + 2 * lanes * 4
                              + heads * 3 * 8 * 4)
    streamed = batches * 8 * block_k * lanes * in_itemsize
    scratch = heads * 4 * block_k * lanes * 4
    turn = heads * 10 * block_q * block_k + 3 * block_q * lanes * 4
    return resident + streamed + scratch + turn


def _vmem_limit(need):
    """``vmem_limit_bytes`` a kernel is compiled with: what its model says the
    shape needs and a quarter more, never under the compiler's default."""
    return max(16 * 1024 * 1024, need + need // 4)


def _bwd_tiles(B, H, T, S, D, in_itemsize):
    """``(batches, heads, block_q, block_k)`` of the fused backward, from the
    shape alone: the heads a block of rows holds in its lanes, a tile of up to
    ``_BWD_BLOCK_K`` keys x ``_BWD_BLOCK_Q`` query rows a turn and, where one
    tile holds all of T and S, as many batch rows a step as make about one
    full tile and fit the budget.  ``causal`` does not enter: it decides which
    tiles are visited.  On v5e 512 x 512 is within 5% of every larger tile
    (1024 x 1024 needs twice the VMEM for 3%), and 8 (batch, head) pairs a
    step the fastest at T = 256 (PERF.md section 6, PR 34)."""
    heads = _lane_heads(H, D)
    block_q = min(_BWD_BLOCK_Q, T)
    block_k = min(_BWD_BLOCK_K, S)
    batches = 1
    if block_q == T and block_k == S:
        for n in range(2, _BWD_MAX_HEADS // heads + 1):
            if B % n == 0 and n * heads * T * S <= 2 * _BWD_BLOCK_Q * _BWD_BLOCK_K \
                    and _bwd_vmem_bytes(n, heads, T, S, T, D,
                                        in_itemsize) <= _BWD_VMEM_BUDGET:
                batches = n
    return batches, heads, block_q, block_k


def _bwd_blocks(B, H, T, S, D, in_itemsize, block_q=None, block_k=None):
    """``(batches, heads, block_q, block_k)`` as the kernel runs them: the
    chooser's, or a caller's explicit blocks taken as given, one batch row a
    step."""
    batches, heads, bq, bk = _bwd_tiles(B, H, T, S, D, in_itemsize)
    if block_q is not None or block_k is not None:
        batches, bq, bk = 1, min(block_q or bq, T), min(block_k or bk, S)
    return batches, heads, bq, bk


def _bwd_engine(B, H, T, S, D, in_itemsize, block_q=None, block_k=None):
    """The backward engine for a shape: "fused" from ``_BWD_MIN_T`` query rows
    on wherever the kernel's residency fits what it may ask of the core's
    VMEM, "scan" elsewhere.  The one place the decision lives."""
    batches, heads, bq, bk = _bwd_blocks(B, H, T, S, D, in_itemsize, block_q, block_k)
    need = _bwd_vmem_bytes(batches, heads, bq, bk, -(-T // bq) * bq, D, in_itemsize)
    return "fused" if T >= _BWD_MIN_T and need <= _BWD_VMEM_BUDGET else "scan"


def _flash_bwd(n_head, causal, sm_scale, block_q, block_k, interpret, layout,
               res, do):
    from .. import observability as obs

    q, k = res[0], res[1]
    B, T, HD = q.shape
    S = k.shape[1]
    H = n_head
    shape = (B, H, T, S, HD // H, q.dtype.itemsize, block_q, block_k)
    engine = _bwd_engine(*shape)
    if engine == "fused":
        batches, heads, bq, bk = _bwd_blocks(*shape)
    else:
        # a turn of the scan is every (batch, head)'s [T, block_k] strip
        batches, heads, bq, bk = B, H, T, min(block_k or DEFAULT_BLOCK_K, S)
    # what was chosen, once per compiled shape (this runs at trace time): a
    # reader of a device trace divides a backward call's time by its grid
    # steps (the scan's turns), and sees which engine the shape took
    steps = obs.counter("flash.bwd.grid_steps", labels={
        "T": T, "S": S, "block": "%dx%d" % (bq, bk), "heads": batches * heads,
        "bh": B * H, "causal": int(bool(causal)), "engine": engine,
        "layout": layout})
    if not steps.value:
        steps.inc((B // batches) * (H // heads) * -(-S // bk))
    if engine == "fused":
        return _flash_bwd_fused(n_head, causal, sm_scale, batches, bq, bk,
                                interpret, res, do)
    return _flash_bwd_scan(n_head, causal, sm_scale, bk, res, do)


def _fused_bwd_kernel(lens_ref, q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                      dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, delta_scr, *, sm_scale,
                      causal, batches, heads, head_dim, block_q, block_k,
                      num_q_blocks, q_len, kv_len):
    """One grid step = ``batches`` batch rows x the ``heads`` heads whose
    lanes make one block of the rows x one key block against the query side,
    which is RESIDENT (its blocks' index maps pin them on the key axis, so it
    is fetched once a (batch, head group)) and is walked INSIDE the step,
    ``block_q`` rows a turn, q and do loaded once a turn for all the heads.  A
    head is a lane slice of the block and meets the MXU as the block's full
    lane width, as in the forward: its k and v have the other heads' lanes
    zeroed, so ``k . q^T`` and ``v . do^T`` sum only its own lanes and ``ds .
    k`` leaves exact zeros in the others (dq is the heads' sum, one
    read-modify-write a turn); ``p^T . do`` and ``ds^T . q`` give every lane,
    accumulate in a scratch a head, and the heads' own lanes are merged into
    ONE lane-dense store of dk and dv at the end.  A turn is one ``[block_k,
    block_q]`` tile a head, keys on the sublanes and query rows on the lanes:
    s and dp come out that way round, p^T . do and ds^T . q need no
    transpose, and a query row's lse and delta are a lane-dense ``[1,
    block_q]`` row that broadcasts over sublanes (no ``[rows, 1]`` statistic
    exists): lse arrives so from the forward, and delta = rowsum(do * out)
    over each head's lanes is made so at the first key block, from the
    resident do and out, and kept in scratch for the key walk (XLA made it
    from a relayout of a whole activation).  Only dq = ds . k transposes its
    tile.  dq accumulates straight
    in its f32 output block across the key walk; dk and dv in scratch across
    the turns.  Query blocks no key of the block can be seen from take no
    turn, and those every row of which sees every key skip the mask."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    g = pl.program_id(0)
    kj = pl.program_id(2)
    k0 = kj * block_k
    shift = kv_len - q_len  # causal: row t sees keys [0, t + shift] — tril(k=S-T)
    div = jax.lax.div       # never-negative operands (see _paged_decode_kernel)

    @pl.when(kj == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    def turn(n, kvl, ks, vs, i, masked):
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q = q_ref[n, rows, :].astype(jnp.float32)    # [bq, width]
        do = do_ref[n, rows, :].astype(jnp.float32)
        if masked:
            # one compare a score: the tile's own key index against each
            # query row's last visible one (a [1, bq] row)
            last = jnp.full((1, block_q), kvl - 1, jnp.int32)
            if causal:
                last = jnp.minimum(last, i * block_q + shift + jax.lax.broadcasted_iota(
                    jnp.int32, (1, block_q), 1))
            key = k0 + jax.lax.broadcasted_iota(jnp.int32, (block_k, block_q), 0)
            seen = key <= last
        dq = None
        for h, (k, v) in enumerate(zip(ks, vs)):
            s = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)  # [bk, bq]
            p = jnp.exp(s - lse_ref[n, h, i])            # a [1, bq] row
            if masked:
                p = jnp.where(seen, p, 0.0)
            dv_scr[h] += jnp.dot(p, do, preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)  # [bk, bq]
            ds = p * (dp - delta_scr[n, h, i][0:1])
            dk_scr[h] += jnp.dot(ds, q, preferred_element_type=jnp.float32)
            d = jax.lax.dot_general(
                ds, k, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            dq = d if dq is None else dq + d
        dq_ref[n, rows, :] += dq

    def batch(n, carry):
        kvl = lens_ref[g * batches + n]  # valid key length of this batch row

        @pl.when(kj == 0)
        def _delta():
            def block(i, _):
                rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
                prod = (do_ref[n, rows, :].astype(jnp.float32)
                        * o_ref[n, rows, :].astype(jnp.float32))  # [bq, width]
                for h in range(heads):
                    mine = _head_lanes((8, prod.shape[1]), h, head_dim)
                    delta_scr[n, h, i] = _lane_sums_as_rows(
                        prod, mine.astype(jnp.float32))      # [8, bq], rows alike
            jax.lax.fori_loop(0, num_q_blocks, block, None)

        # key rows from kv_len on are zeroed (an OOB-padded tail tile holds
        # anything: 0 * NaN would poison dq) and so are the other heads'
        # lanes, and the softmax scale goes into k once a step: s = q .
        # (scale k), dq = ds . (scale k), dk is scaled once at the end
        k = k_ref[n].astype(jnp.float32) * sm_scale
        v = v_ref[n].astype(jnp.float32)
        live = k0 + jax.lax.broadcasted_iota(jnp.int32, k.shape, 0) < kvl
        ks, vs = [], []
        for h in range(heads):
            mine = live if heads == 1 else live & _head_lanes(k.shape, h, head_dim)
            ks.append(jnp.where(mine, k, 0.0))
            vs.append(jnp.where(mine, v, 0.0))
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

        # query blocks [some, n) hold a row that sees SOME key of this block,
        # [every, n) only rows that see EVERY key: blocks under `some` take
        # no turn, blocks from `every` on no mask.  A key block that kv_len
        # cuts masks every turn; one past kv_len takes none (kv_len == 0:
        # exact zeros in dq, dk and dv).
        nq = num_q_blocks
        some = every = 0
        if causal:
            some = jnp.minimum(div(jnp.maximum(k0 - shift, 0), block_q), nq)
            every = jnp.minimum(div(jnp.maximum(
                k0 + block_k - 1 - shift, 0) + block_q - 1, block_q), nq)
        some = jnp.where(k0 < kvl, some, nq)
        every = jnp.where(k0 + block_k <= kvl, every, nq)
        jax.lax.fori_loop(
            some, every, lambda i, _: turn(n, kvl, ks, vs, i, True), None)
        jax.lax.fori_loop(  # every >= some, whichever way they were cut
            every, nq, lambda i, _: turn(n, kvl, ks, vs, i, False), None)
        dk = _merge_heads([dk_scr[h] for h in range(heads)], head_dim)
        dv = _merge_heads([dv_scr[h] for h in range(heads)], head_dim)
        dk_ref[n] = (dk * sm_scale).astype(dk_ref.dtype)
        dv_ref[n] = dv.astype(dv_ref.dtype)
        return carry

    jax.lax.fori_loop(0, batches, batch, None)


# The name a device trace has always shown this kernel under (the sanitized
# name stack of a custom_vjp's backward): stated, so that it stays what the
# trace readers match on now that the call sits under a jit of its own.
_BWD_KERNEL_NAME = "transpose_jvp_flash_attention__"


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5, 6))
def _flash_bwd_fused(n_head, causal, sm_scale, batches, block_q, block_k,
                     interpret, res, do):
    """dq + dk + dv in ONE Pallas grid (see ``_bwd_engine``).  A jit of its
    own: a step's eighteen calls are two shapes (causal or not), and each is
    then traced and lowered to Mosaic once, not at every call site (set-up
    time at every process start)."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, kv_lens, out, lse = res
    B, T, HD = q.shape
    S = k.shape[1]
    H, D = n_head, HD // n_head
    heads = _lane_heads(H, D)
    width = heads * D
    bq, bk = block_q, block_k
    nq = -(-T // bq)
    nk = -(-S // bk)
    Tp = nq * bq

    # the query side is sliced inside a resident block, so it is padded to
    # whole query blocks: a zero do row adds nothing to dk and dv (p^T . do,
    # and ds = p * (0 - 0)), and its dq row is cut off again
    def padded(x, axis):
        if Tp == T:
            return x
        return jnp.pad(x, [(0, Tp - T if a == axis else 0) for a in range(x.ndim)])

    # each query block's lse as a lane-dense row: a free reshape where T is
    # whole query blocks
    lse_rows = padded(lse, 2).reshape(B, H, nq, 1, bq)
    lens = _lens_per_batch(kv_lens, B, S)

    q_side = pl.BlockSpec((batches, Tp, width), lambda g, c, j, lens: (g, 0, c))
    k_side = pl.BlockSpec((batches, bk, width), lambda g, c, j, lens: (g, j, c))
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _fused_bwd_kernel, sm_scale=sm_scale, causal=causal, batches=batches,
            heads=heads, head_dim=D, block_q=bq, block_k=bk, num_q_blocks=nq,
            q_len=T, kv_len=S),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // batches, H // heads, nk),
            in_specs=[
                q_side,                                                   # q
                k_side,                                                   # k
                k_side,                                                   # v
                q_side,                                                   # do
                q_side,                                                   # out
                pl.BlockSpec((batches, heads, nq, 1, bq),
                             lambda g, c, j, lens: (g, c, 0, 0, 0)),
            ],
            out_specs=[q_side, k_side, k_side],                           # dq, dk, dv
            scratch_shapes=[pltpu.VMEM((heads, bk, width), jnp.float32),
                            pltpu.VMEM((heads, bk, width), jnp.float32),
                            pltpu.VMEM((batches, heads, nq, 8, bq), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, HD), jnp.float32),
            jax.ShapeDtypeStruct((B, S, HD), k.dtype),
            jax.ShapeDtypeStruct((B, S, HD), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(_bwd_vmem_bytes(
                batches, heads, bq, bk, Tp, D, q.dtype.itemsize)),
        ),
        interpret=interpret,
        name=_BWD_KERNEL_NAME,
    )(lens, padded(q, 1), k, v, padded(do, 1), padded(out, 1), lse_rows)
    return dq[:, :T].astype(q.dtype), dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def flash_attention_rows(q, k, v, kv_lens=None, n_head=1, causal=False,
                         sm_scale=None, block_q=None, block_k=None,
                         interpret=None, layout="rows"):
    """Fused attention on the projections' own rows: q ``[B, T, H * D]``, k
    and v ``[B, S, H * D]`` → ``[B, T, H * D]``, head ``h`` the lanes ``h * D
    : (h + 1) * D`` (``n_head`` = H).  No head is split off or merged back in
    HBM on either side of the kernels, forward or backward.  ``kv_lens``
    ([B] int32) masks keys past each sequence's length (padding mask).
    ``block_q`` / ``block_k`` of None mean "chosen from the shape"
    (``_fwd_tiles`` and, for the backward, ``_bwd_tiles``); a given value is
    taken as given by both.  ``layout`` only labels the grid-step counters
    with the entry a compiled shape came through."""
    out, _ = _flash_impl(q, k, v, kv_lens, n_head, causal, sm_scale, block_q,
                         block_k, interpret, layout)
    return out


def flash_attention(q, k, v, kv_lens=None, causal=False, sm_scale=None,
                    block_q=None, block_k=None, interpret=None):
    """Fused attention, [B, H, T, D] → [B, H, T, D]: ``flash_attention_rows``
    behind a transpose at its edge, so the kernels a caller of this entry
    checks are the kernels a caller of the rows entry runs."""
    out = flash_attention_rows(_to_rows(q), _to_rows(k), _to_rows(v), kv_lens,
                               q.shape[1], causal, sm_scale, block_q, block_k,
                               interpret, "bhtd")
    return _from_rows(out, q.shape[1])


def _flash_impl(q, k, v, kv_lens, n_head, causal, sm_scale, block_q, block_k,
                interpret, layout):
    if q.shape[2] % n_head:
        raise ValueError("flash_attention_rows: rows of %d lanes do not hold "
                         "n_head=%d whole heads" % (q.shape[2], n_head))
    if causal and q.shape[1] > k.shape[1]:
        # Bottom-right-aligned tril(k=S-T) leaves rows t < T-S with zero
        # visible keys; the online softmax has no meaningful value there
        # (the reference degenerates to a uniform mean over masked keys).
        raise ValueError(
            "causal flash_attention requires T <= S, got T=%d S=%d"
            % (q.shape[1], k.shape[1])
        )
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[2] // n_head))
    if interpret is None:
        interpret = cpu_backend()
    return _flash_fwd(q, k, v, kv_lens, n_head, causal, sm_scale, block_q,
                      block_k, interpret, layout)


def _flash_vjp_fwd(q, k, v, kv_lens, n_head, causal, sm_scale, block_q, block_k,
                   interpret, layout):
    out, lse = _flash_impl(q, k, v, kv_lens, n_head, causal, sm_scale, block_q,
                           block_k, interpret, layout)
    return out, (q, k, v, kv_lens, out, lse)


def _flash_vjp_bwd(n_head, causal, sm_scale, block_q, block_k, interpret, layout,
                   res, do):
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(res[0].shape[2] // n_head))
    if interpret is None:
        interpret = cpu_backend()
    dq, dk, dv = _flash_bwd(n_head, causal, sm_scale, block_q, block_k,
                            interpret, layout, res, do)
    kv_lens = res[3]
    dlens = None
    if kv_lens is not None:
        dlens = np.zeros(kv_lens.shape, jax.dtypes.float0)
    return dq, dk, dv, dlens


flash_attention_rows.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# Decode-shaped attention: single-token queries against a PAGED KV cache.
#
# The serving decode runtime (paddle_tpu/serving/decode_scheduler.py) keeps
# every sequence's keys/values in fixed-size pages of a preallocated pool
# (vLLM/PagedAttention, Kwon et al. SOSP'23); one decode iteration asks,
# for each of S slots, "this slot's ONE new query token against its first
# kv_lens cached tokens".
#
# The pools are STORED heads-folded and layer-stacked, ``[L, P, ps, H*Dh]``
# (head h is lanes ``h*Dh:(h+1)*Dh``): that is the shape whose default
# device layout is row-major with unpadded ``(ps, H*Dh)`` page tiles, so a
# step program that aliases the pools input->output never re-lays them out
# (a trailing ``[H, Dh]`` = 8 x 64 would pad in a bf16 tile, and the
# compiler then picks a pages-minor layout the kernels cannot read).  Both
# engines take the WHOLE stack plus a ``layer`` and touch only the pages the
# page table names — no ``pool[layer]`` slice ever exists.  ``layer`` is a
# Python int where a step program unrolls its layers (closed into the kernel:
# every family but one) or a TRACED int32 scalar where the program applies
# its layers inside a ``lax`` loop (``models/ouro.py``: K/V layer ``u * L +
# l`` with ``u`` the loop's counter): it then rides the scalar-prefetch path
# as a third operand beside the page table and ``kv_lens``
# (``_layer_prefetch``), and the static form lowers to what it always did:
#
# * reference (CPU / tests): gather the slot's pages out of the stack
#   (``pool[layer, page_tables]``), unfold the heads and run the
#   masked-softmax formulation — the same arithmetic shape as
#   ``mha_reference`` with T_q=1, so tier-1 stays green without Pallas
#   interpret overhead.
# * pallas (TPU): the page table and ``kv_lens`` ride the SCALAR-PREFETCH
#   path (``PrefetchScalarGridSpec``) and the stack stays in HBM: one grid
#   step a slot, which copies the slot's OWN ``ceil(kv_len / ps)`` pages
#   ``(layer, page)`` (the layer a constant or a prefetched scalar, the page
#   from the prefetched table) into a VMEM tile, many pages a
#   turn (``_decode_turn_pages``: 32 of the chat cell's 16-token pages), the
#   next turn's copies in flight — no gathered [S, max_kv, H, D]
#   intermediate ever exists in HBM, and no page past ``kv_len`` is copied,
#   stepped over or computed.  A tile carries ALL heads of its pages in its
#   lanes; one online-softmax update a turn serves every head.
#
# The public entry points also accept ONE layer's unfolded
# ``[P, ps, H, Dh]`` pool (``layer=None``): it is folded into a one-layer
# stack and runs the SAME kernel, so what the smokes and the benchmark's
# ``correct`` check is what the step programs serve.
#
# Contract (shared by both engines, tested in test_flash_decode.py):
# ``kv_lens[s] == 0`` (inactive slot) yields EXACT ZEROS for that slot.
# ---------------------------------------------------------------------------


def _layer_index(layer):
    """``layer`` as the walks take it: a Python int (closed into the kernel,
    as every unrolled step program gives it) stays one; anything else is a
    TRACED int32 scalar (a step program that applies its layers inside a
    ``lax`` loop numbers the K/V layer ``u * L + l`` with ``u`` the loop's
    counter) and rides the scalar-prefetch path beside the page table."""
    import jax.numpy as jnp

    if isinstance(layer, (int, np.integer)):
        return int(layer)
    return jnp.asarray(layer, jnp.int32).reshape(())


def _layer_prefetch(kernel, layer, n, **kw):
    """``(kernel', operands)`` of a paged kernel whose first ``n`` operands
    are prefetched scalars and which takes ``layer=``: a static layer is
    closed in and adds no operand (the program is the one it always was); a
    traced one is one more prefetched scalar behind the ``n``, read in the
    kernel where the static form has a constant.  Either way the custom call
    keeps the kernel's name."""
    if isinstance(layer, int):
        return functools.partial(kernel, layer=layer, **kw), ()

    def traced(*refs):
        return kernel(*refs[:n], *refs[n + 1:], layer=refs[n][0], **kw)

    traced.__name__ = kernel.__name__
    traced.__qualname__ = kernel.__qualname__
    return traced, (layer.reshape(1),)


def _stacked_pools(q, k_pool, v_pool, layer):
    """``(k_stack, v_stack, layer, n_kv)`` in the stored form
    ``[L, P, ps, Hkv*Dh]``.  ``layer=None`` means ONE layer's unfolded
    ``[P, ps, Hkv, Dh]`` pool: a free reshape of a row-major array into a
    one-layer stack.  ``q`` has ``Hq = g * Hkv`` heads of the pool's width
    (query head ``i`` reads KV head ``i // g``)."""
    Hq, Dh = q.shape[-2:]
    if layer is None:
        if k_pool.ndim != 4 or k_pool.shape[3] != Dh:
            raise ValueError(
                "without layer= the pool is one layer's [P, ps, Hkv, Dh] = "
                "[.., .., .., %d]; got %s" % (Dh, k_pool.shape))
        n_kv = k_pool.shape[2]
        fold = (1,) + k_pool.shape[:2] + (n_kv * Dh,)
        k_pool, v_pool, layer = k_pool.reshape(fold), v_pool.reshape(fold), 0
    else:
        # an UNFOLDED [P, ps, H, Dh] pool would read as a one-KV-head stack
        # of page size H: refused (a real stack of that shape does not
        # occur: its page size would equal the number of query heads)
        if k_pool.ndim != 4 or k_pool.shape[3] % Dh or (
                k_pool.shape[3] == Dh and k_pool.shape[2] == Hq > 1):
            raise ValueError(
                "with layer= the pool is the stored stack [L, P, ps, Hkv*Dh] "
                "with Dh = %d; got %s" % (Dh, k_pool.shape))
        n_kv = k_pool.shape[3] // Dh
    if Hq % n_kv:
        raise ValueError("%d query heads do not group over %d KV heads"
                         % (Hq, n_kv))
    return k_pool, v_pool, _layer_index(layer), n_kv


def _paged_reference(q, k_pool, v_pool, page_tables, kv_lens, sm_scale, layer):
    import jax.numpy as jnp

    S, H, Dh = q.shape
    ps = k_pool.shape[2]
    mp = page_tables.shape[1]
    # unfold the heads BEFORE the einsums: their reduction shapes are part
    # of the bitwise contract (continuous batching == per-sequence)
    k = k_pool[layer, page_tables].reshape(
        S, mp * ps, H, Dh).astype(jnp.float32)
    v = v_pool[layer, page_tables].reshape(
        S, mp * ps, H, Dh).astype(jnp.float32)
    s = jnp.einsum("shd,skhd->shk", q.astype(jnp.float32), k) * sm_scale
    ok = jnp.arange(mp * ps)[None, :] < kv_lens[:, None]  # [S, K]
    s = jnp.where(ok[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(kv_lens[:, None, None] > 0, p, 0.0)  # inactive slot -> 0
    return jnp.einsum("shk,skhd->shd", p, v).astype(q.dtype)


# A turn of the walk is one online-softmax update over ``pages * ps`` keys
# gathered into a VMEM tile.  On v5e 256 / 512 / 1024 keys read 0.093 /
# 0.095 / 0.105 ms a call on the chat cell's caches (most fit one 512-key
# turn) and 0.59 / 0.51 / 0.47 with every cache full (PERF.md section 6,
# PR 31): 512 is near the best of both.
_DECODE_TURN_KEYS = 512
# Scoped-VMEM budget of the walk: as the forward's.
_DECODE_VMEM_BUDGET = 13 * 1024 * 1024


def _decode_vmem_bytes(pages, ps, lanes, kv_itemsize, n_head):
    """Scoped-VMEM residency of one slot's walk at ``pages`` pages a turn:
      k, v tiles, double-buffered ............ 2 * 2 * turn * lanes * isz
      one turn's operands: the bf16 parts of a tile that is not bf16 (three
      a tile) and the masked v tile ........... up to 4 * turn * lanes * 4
      scores and probabilities [3 * Hp, turn] . 4 * 3 * Hp * turn * 4
      query rows, their parts, acc and the output 16 * Hp * lanes * 4
    An upper estimate, not calibrated against the compiler as the forward's
    is: every turn it lets through at the shapes tried compiles for a
    described v5e under the default scoped limit.  A VMEM row is 128 lanes
    wide whatever ``lanes`` is."""
    turn = pages * ps
    lanes = -(-lanes // 128) * 128
    hp = -(-n_head // 8) * 8
    return (4 * turn * lanes * kv_itemsize + 4 * turn * lanes * 4
            + 12 * hp * turn * 4 + 16 * hp * lanes * 4)


def _decode_turn_pages(ps, lanes, mp, kv_itemsize, n_head):
    """Pages a turn of the decode walk, from the shapes alone (no probe, no
    fallback): as many as make ``_DECODE_TURN_KEYS`` keys, no more than the
    table has, halved until the VMEM model fits its budget."""
    pages = max(1, min(mp, _DECODE_TURN_KEYS // ps))
    while pages > 1 and _decode_vmem_bytes(
            pages, ps, lanes, kv_itemsize, n_head) > _DECODE_VMEM_BUDGET:
        pages = -(-pages // 2)
    return pages


def _bf16_parts(x):
    """bf16 arrays whose sum is ``x`` to f32's last bit: ``x`` itself where
    it is bf16, else three (8 + 8 + 8 bits of an f32 mantissa).  A product
    of two bf16 values is exact in f32 and the MXU accumulates in f32, so a
    matmul over the parts is the f32 result — where a bare ``jnp.dot`` of
    f32 operands in a Mosaic kernel is ONE bf16 pass (PERF.md, PR 29)."""
    import jax.numpy as jnp

    if x.dtype == jnp.bfloat16:
        return [x]
    x = x.astype(jnp.float32)
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    return [hi, mid, (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)]


def _part_rows(x):
    """``(rows, n)``: the ``n`` exact bf16 parts of ``x [r, c]`` stacked ``[n *
    r, c]`` (bf16 ``x`` is its own one part; f32 gives three, stacked as f32,
    whose sublane tile ``r`` is a multiple of, then cast: each part is a bf16
    value already)."""
    import jax.numpy as jnp

    parts = _bf16_parts(x)
    if len(parts) == 1:
        return parts[0], 1
    return jnp.concatenate([p.astype(jnp.float32) for p in parts],
                           axis=0).astype(jnp.bfloat16), len(parts)


def _parts_dot(a_rows, n, b, dims):
    """``a . b`` in f32 on the MXU: ``a_rows, n = _part_rows(a)``, ``b`` split
    in its own exact parts; the parts' products summed least part first."""
    r = a_rows.shape[0] // n
    out = None
    for part in _bf16_parts(b):
        y = jax.lax.dot_general(a_rows, part, (dims, ((), ())),
                                preferred_element_type=jax.numpy.float32)
        if n > 1:
            rows = y[(n - 1) * r:]
            for i in range(n - 2, -1, -1):
                rows = rows + y[i * r:(i + 1) * r]
            y = rows
        out = y if out is None else out + y
    return out


def _walk_pages(kvl, counts, *, page_size, pages, rows, width, copies,
                tiles, scores, limit=None, first=None, pv=None, carry=None,
                finish=True, keep=None):
    """The walk of ONE slot's own pages that the paged decode kernels share:
    ``pages`` pages a turn are copied whole into tile ``slot`` of two (the
    next turn's copies in flight while this turn computes), and a turn is one
    online-softmax update of ``rows`` query rows, keys on the lanes.  No page
    past ``kvl`` is copied, stepped over or computed; ``kvl == 0`` takes no
    turn and gives zeros.

    counts: ``(n_pages, n_turns, n_whole)`` — the slot's pages, its turns,
        and the first turns in which every row sees every key (unmasked).
    copies(t, slot, i): the async copies of the ``i``-th page of turn ``t``.
    tiles(slot): ``(k, v)`` of the turn, ``v [turn, width]``.
    scores(k): ``[rows, turn]`` float32.
    limit: keys each row sees ``[rows, 1]`` where rows differ (a chunk's
        tokens); every row sees ``kvl`` otherwise.
    first: None, or ``(base, lo, n_head)`` for a walk that does not start at
        the sequence's first key (a WINDOW): the walk's first key is the
        sequence's key ``base`` (``kvl``, ``limit`` and ``lo`` count from the
        sequence's start; ``counts`` from the walk's), a row sees no key
        before ``lo`` (``[rows, 1]`` or a scalar), and the first ``n_head``
        turns hold such keys: they are masked, on both sides, as the last
        ones are.
    pv: None (``p . v`` of all rows against the whole ``v`` tile), or
        ``pv(p, v) -> [rows, width]``.
    carry / finish: a walk over SEVERAL page lists under one softmax: with
        ``finish=False`` the walk returns its running ``(m, l, acc)`` in
        place of the normalised result, and the next list's walk takes it as
        ``carry`` (lists of another page size, pool or mask; an empty list
        passes it on unchanged).
    keep: None, or ``keep(t) -> [rows, turn]`` bool: the keys of turn ``t``
        each row attends to at all (a learned selection given as a mask);
        the others' scores are replaced as those past a row's keys are.  A
        row whose first turns keep nothing carries weights of masked keys
        until its first kept key's turn scales them to exact zero
        (``exp(NEG_INF - m)``): every row must keep at least one key it sees.
    A masked turn replaces the scores past a row's keys whatever they are
    and zeroes the value rows past ``kvl`` (they hold what an earlier turn
    or nobody left: 0 * garbage must stay finite).  Returns the normalised
    ``[rows, width]`` float32; m, l and alpha stay ``[rows, 128]``
    lane-replicated."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    n_pages, n_turns, n_whole = counts
    turn = pages * page_size

    def each_page(t, slot, what):
        def one(i, _):
            for c in copies(t, slot, i):
                what(c)
        jax.lax.fori_loop(0, jnp.minimum(pages, n_pages - t * pages), one,
                          None)

    def update(t, carry, masked):
        m_prev, l_prev, acc = carry
        slot = jax.lax.rem(t, 2)

        @pl.when(t + 1 < n_turns)
        def _next():
            each_page(t + 1, 1 - slot, lambda c: c.start())

        each_page(t, slot, lambda c: c.wait())
        k, v = tiles(slot)
        s = scores(k)                                      # [rows, turn]
        if masked and first is None:
            left = kvl - t * turn
            seen = left if limit is None else limit - t * turn
            s = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, (rows, turn), 1) < seen, s, NEG_INF)
            v = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, (turn, 1), 0) < left, v, jnp.zeros_like(v))
        elif masked:
            key0 = first[0] + t * turn
            left = kvl - key0
            seen = left if limit is None else limit - key0
            col = jax.lax.broadcasted_iota(jnp.int32, (rows, turn), 1)
            s = jnp.where((col < seen) & (col >= first[1] - key0), s, NEG_INF)
            v = jnp.where(jax.lax.broadcasted_iota(
                jnp.int32, (turn, 1), 0) < left, v, jnp.zeros_like(v))
        if keep is not None:
            s = jnp.where(keep(t), s, NEG_INF)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, turn))
        alpha = jnp.exp(m_prev - m_new)
        return (m_new, l_prev * alpha + p.sum(axis=1, keepdims=True),
                acc * _lanes(alpha, width)
                + (_parts_dot(*_part_rows(p), v, ((1,), (0,)))
                   if pv is None else pv(p, v)))

    @pl.when(n_turns > 0)
    def _first():
        each_page(0, 0, lambda c: c.start())

    if carry is None:
        carry = (jnp.full((rows, 128), NEG_INF, jnp.float32),
                 jnp.zeros((rows, 128), jnp.float32),
                 jnp.zeros((rows, width), jnp.float32))
    if first is not None:
        n_head = jnp.minimum(first[2], n_turns)
        carry = jax.lax.fori_loop(
            0, n_head, lambda t, c: update(t, c, True), carry)
        n_whole = jnp.maximum(n_whole, n_head)
        carry = jax.lax.fori_loop(
            n_head, n_whole, lambda t, c: update(t, c, False), carry)
    else:
        carry = jax.lax.fori_loop(
            0, n_whole, lambda t, c: update(t, c, False), carry)
    carry = jax.lax.fori_loop(
        n_whole, n_turns, lambda t, c: update(t, c, True), carry)
    if not finish:
        return carry
    _, l, acc = carry
    return acc / _lanes(jnp.maximum(l, 1e-30), width)


def _paged_decode_kernel(pt_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
                         k_buf, v_buf, sem, *, layer, page_size, pages,
                         num_pages_per_seq, n_head, head_dim, sm_scale):
    """One grid step = one SLOT, and inside it the walk (``_walk_pages``)
    over the slot's OWN ``ceil(kv_len / ps)`` pages, ``pages`` a turn: each is
    copied from the stored stack (left in HBM) into its rows of a
    ``[turn, H*Dh]`` VMEM tile by the prefetched page table, the next turn's
    copies in flight while this turn computes.  No page past ``kv_len`` is
    copied, stepped over or computed; ``kv_len == 0`` takes no turn and
    emits zeros.

    A turn is one online-softmax update for all heads at once, keys on the
    lanes: scores ``[H, turn]`` = the block-diagonal query rows ``[H, H*Dh]``
    (row h holds head h's lanes) against the tile, p.v ``[H, H*Dh]`` of which
    row h's own lanes are kept at the end; m, l and alpha stay ``[H, 128]``
    lane-replicated.  Both products run on the MXU over exact bf16 parts of
    their operands (``_bf16_parts``): f32 results, the bf16 page the only
    precision lost."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_idx = pl.program_id(0)
    ps, turn = page_size, pages * page_size
    lanes = n_head * head_dim
    hp = -(-n_head // 8) * 8            # query rows, whole f32 sublane tiles
    kvl = lens_ref[s_idx]
    # lax.div / lax.rem, not // and %: the operands are never negative, and
    # the floor forms trace to a nested jit apiece (six kernels a step
    # program are traced and lowered at every process start: set-up time)
    div = jax.lax.div
    n_pages = div(kvl + (ps - 1), ps)
    n_turns = div(kvl + (turn - 1), turn)
    n_whole = div(kvl, turn)            # turns with every key visible

    def copies(t, slot, i):
        page = pt_ref[s_idx * num_pages_per_seq + t * pages + i]
        rows = pl.ds(pl.multiple_of(i * ps, ps), ps)
        return (pltpu.make_async_copy(k_hbm.at[layer, page],
                                      k_buf.at[slot, rows], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, page],
                                      v_buf.at[slot, rows], sem.at[1, slot]))

    # row h of q_rows is head h's lanes of the (scaled) query, zero elsewhere
    own = (div(jax.lax.broadcasted_iota(jnp.int32, (hp, lanes), 1), head_dim)
           == jax.lax.broadcasted_iota(jnp.int32, (hp, lanes), 0))
    q = q_ref[...].astype(jnp.float32) * sm_scale          # [1, lanes]
    q_rows, nq = _part_rows(
        jnp.where(own, jnp.broadcast_to(q, (hp, lanes)), 0.0))  # [3 * hp, lanes]

    out = _walk_pages(
        kvl, (n_pages, n_turns, n_whole), page_size=ps, pages=pages, rows=hp,
        width=lanes, copies=copies,
        tiles=lambda slot: (k_buf[slot], v_buf[slot]),     # [turn, lanes]
        scores=lambda k: _parts_dot(q_rows, nq, k, ((1,), (1,))))
    o_ref[...] = jnp.sum(jnp.where(own, out, 0.0), axis=0,
                         keepdims=True).astype(o_ref.dtype)


def _paged_pallas(q, k_pool, v_pool, page_tables, kv_lens, sm_scale, interpret,
                  layer):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import observability as obs

    S, H, Dh = q.shape
    ps = k_pool.shape[2]
    mp = page_tables.shape[1]
    pages = _decode_turn_pages(ps, H * Dh, mp, k_pool.dtype.itemsize, H)
    # flat [S*mp] so the prefetched table indexes with one scalar read
    pt_flat = page_tables.astype(jnp.int32).reshape(S * mp)
    lens = kv_lens.astype(jnp.int32)

    # what was chosen, once per compiled shape (this runs at trace time): a
    # reader of a device trace divides the kernel's time by its grid steps
    steps = obs.counter("paged.decode.grid_steps", labels={
        "S": S, "mp": mp, "ps": ps, "turn": pages * ps})
    if not steps.value:
        steps.inc(S)

    kernel, layer_op = _layer_prefetch(
        _paged_decode_kernel, layer, 2, page_size=ps, pages=pages,
        num_pages_per_seq=mp, n_head=H, head_dim=Dh, sm_scale=sm_scale)
    # [S, 1, H*Dh]: the block's last two dims equal the array's own (the
    # only blocking of a one-row query the TPU lowering accepts)
    row = pl.BlockSpec((None, 1, H * Dh), lambda s, *_: (s, 0, 0))
    # the stacked pools stay where they are: the walk copies pages out
    stack = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + len(layer_op),
        grid=(S,),
        in_specs=[row, stack, stack],
        out_specs=[row],
        scratch_shapes=[
            pltpu.VMEM((2, pages * ps, H * Dh), k_pool.dtype),  # k tiles
            pltpu.VMEM((2, pages * ps, H * Dh), v_pool.dtype),  # v tiles
            pltpu.SemaphoreType.DMA((2, 2)),                    # [k|v, tile]
        ],
    )
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, 1, H * Dh), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(pt_flat, lens, *layer_op, q.reshape(S, 1, H * Dh), k_pool, v_pool)
    return out.reshape(S, H, Dh)


# ---------------------------------------------------------------------------
# LATENT (multi-head latent attention, MLA) rows in the paged cache.  A token
# keeps ONE row ``[c | k_pe | 0]`` for all heads (the normalised compressed
# KV, the one shared rotary key, zeros up to ``W`` lanes: on the chip ``W`` is
# whole lane tiles, because an HBM row is padded to them anyway and a page
# copy must take whole tiles: 512 + 64 -> 640), the page pool is
# ``[L, P, ps, W]`` and there is no V pool: a value is the first ``v_width``
# lanes of the same row.  In the ABSORBED form every head's query
# is carried into the latent space (``[q_nope W_uk | q_pe]``, ``W`` wide), so
# all ``H`` heads score against the same row and ``P . c`` comes back
# ``v_width`` wide a head for the caller's ``W_uv``: each page is read ONCE
# for all heads.  The kernel is the plain walk above (one grid step a slot,
# the slot's own pages copied whole, many to a turn, the next turn's copies
# in flight) with ``H`` plain query rows in place of the block-diagonal ones
# and V a lane slice of the K tile.  A grid step may carry ``q_tokens``
# consecutive tokens of one sequence (``q_tokens * H`` rows; token ``j`` sees
# ``kv_len - (q_tokens - 1 - j)`` keys): that is a prefill chunk, whose rows
# are slots of one page table, ``q_tokens`` to a step.  The walk runs from
# row 0 to ``kv_lens`` over every page between; a model that SELECTS rows a
# query (DeepSeek sparse attention, the section after this one) gives a chunk
# its selection as a mask over the same walk (``keep``) and a decode step a
# gathered row list, which the same kernel reads as pages of its own.
# ---------------------------------------------------------------------------

_MLA_PREFILL_TOKENS = 8     # chunk rows a grid step of the prefill form


def _mla_limits(kv_lens, n_rows, n_head, q_tokens):
    """``[.., n_rows]`` keys each query row sees: row ``r`` is token
    ``r // n_head`` of its step's ``q_tokens``."""
    import jax.numpy as jnp

    tok = jnp.minimum(jnp.arange(n_rows) // n_head, q_tokens - 1)
    return kv_lens[..., None] - (q_tokens - 1 - tok)


def _paged_mla_reference(q, pool, page_tables, kv_lens, v_width, n_head,
                         q_tokens, sm_scale, layer, keep=None):
    import jax.numpy as jnp

    S, R, W = q.shape
    ps = pool.shape[2]
    mp = page_tables.shape[-1]
    # one page-table row for every slot (a prefill chunk's rows) or one each
    keys = "kw" if page_tables.ndim == 1 else "skw"
    lat = pool[layer, page_tables].reshape(
        page_tables.shape[:-1] + (mp * ps, W)).astype(jnp.float32)
    s = jnp.einsum("srw,%s->srk" % keys, q.astype(jnp.float32), lat) * sm_scale
    ok = (jnp.arange(mp * ps)[None, None, :]
          < _mla_limits(kv_lens, R, n_head, q_tokens)[:, :, None])
    if keep is not None:                   # [S, q_tokens, keys]: a selection
        ok = ok & jnp.repeat(keep != 0, n_head, axis=1)[:, :R]
    p = jax.nn.softmax(jnp.where(ok, s, NEG_INF), axis=-1)
    p = jnp.where(ok, p, 0.0)              # a row with no key -> zeros
    return jnp.einsum("srk,%s->srv" % keys.replace("w", "v"), p,
                      lat[..., :v_width])


def _mla_turn_pages(ps, lanes, mp, itemsize, rows):
    """Pages a turn of the latent walk: ``_DECODE_TURN_KEYS`` keys, halved
    until tiles (double-buffered), scores, probabilities and their parts,
    query rows and the accumulator fit the walk's VMEM budget."""
    lanes = -(-lanes // 128) * 128
    pages = max(1, min(mp, _DECODE_TURN_KEYS // ps))
    while pages > 1 and (2 * pages * ps * lanes * (itemsize + 4)
                         + 24 * rows * pages * ps
                         + 16 * rows * lanes) > _DECODE_VMEM_BUDGET:
        pages = -(-pages // 2)
    return pages


def _paged_mla_kernel(pt_ref, lens_ref, q_ref, *refs, layer, page_size, pages,
                      num_pages_per_seq, n_head, q_tokens, v_width, sm_scale,
                      selected):
    """One grid step = one slot's ``q_tokens * H`` query rows against the
    slot's own ``ceil(kv_len / ps)`` latent pages, ``pages`` a turn
    (``_walk_pages``).  A turn: scores ``[rows, turn]``
    = the rows against the tile over all ``W`` lanes, an online-softmax
    update, ``p . tile[:, :v_width]``.  Products run on the MXU over exact
    bf16 parts of an operand that is not bf16 (``_bf16_parts``).  With
    ``selected`` a further input ``keep [q_tokens, keys]`` (nonzero = the
    token attends to the key) masks every turn: a token's row of it is
    spread over the token's ``H`` rows by a one-hot product."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if selected:
        keep_ref, lat_hbm, o_ref, buf, sem = refs
    else:
        lat_hbm, o_ref, buf, sem = refs
    s_idx = pl.program_id(0)
    ps, turn = page_size, pages * page_size
    hp = q_ref.shape[0]
    kvl = lens_ref[s_idx]
    div = jax.lax.div
    n_pages = div(kvl + (ps - 1), ps)
    n_turns = div(kvl + (turn - 1), turn)
    # turns in which every row sees every key
    n_whole = div(jnp.maximum(kvl - (q_tokens - 1), 0), turn)
    tok = jnp.minimum(div(jax.lax.broadcasted_iota(jnp.int32, (hp, 1), 0),
                          n_head), q_tokens - 1)
    limit = kvl - (q_tokens - 1 - tok)                       # [hp, 1]

    def copies(t, slot, i):
        page = pt_ref[s_idx * num_pages_per_seq + t * pages + i]
        return (pltpu.make_async_copy(
            lat_hbm.at[layer, page],
            buf.at[slot, pl.ds(pl.multiple_of(i * ps, ps), ps)],
            sem.at[slot]),)

    q_rows, nq = _part_rows(q_ref[...])                      # [nq * hp, W]

    def tiles(slot):
        tile = buf[slot]                                     # [turn, W]
        return tile, tile[:, :v_width]

    keep = None
    if selected:
        # row r is token ``tok[r]``: [hp, q_tokens] one-hot, exact in bf16
        spread = (tok == jax.lax.broadcasted_iota(
            jnp.int32, (hp, q_tokens), 1)).astype(jnp.bfloat16)

        def keep(t):
            mine = keep_ref[:, pl.ds(pl.multiple_of(t * turn, turn), turn)]
            return jax.lax.dot_general(
                spread, (mine != 0).astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) > 0.5

    o_ref[...] = _walk_pages(
        kvl, (n_pages, n_turns, n_whole), page_size=ps, pages=pages, rows=hp,
        width=v_width, copies=copies, tiles=tiles, limit=limit, keep=keep,
        scores=lambda k: _parts_dot(q_rows, nq, k, ((1,), (1,))) * sm_scale
    ).astype(o_ref.dtype)


_MLA_KERNEL_NAME = "paged_mla_attention"


def _paged_mla_pallas(q, pool, page_tables, kv_lens, v_width, n_head,
                      q_tokens, sm_scale, interpret, layer, keep=None,
                      name=_MLA_KERNEL_NAME):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, R, W = q.shape
    ps = pool.shape[2]
    mp = page_tables.shape[1]
    hp = -(-R // 16) * 16               # whole sublane tiles of either dtype
    if hp != R:
        q = jnp.pad(q, ((0, 0), (0, hp - R), (0, 0)))
    pages = _mla_turn_pages(ps, W, mp, pool.dtype.itemsize, hp)
    kernel = functools.partial(
        _paged_mla_kernel, layer=layer, page_size=ps, pages=pages,
        num_pages_per_seq=mp, n_head=n_head, q_tokens=q_tokens,
        v_width=v_width, sm_scale=sm_scale, selected=keep is not None)
    in_specs = [pl.BlockSpec((None, hp, W), lambda s, pt, kl: (s, 0, 0))]
    operands = [q]
    if keep is not None:
        # [S, q_tokens, keys] float32, the keys in whole turns
        turn = pages * ps
        keys = -(-mp * ps // turn) * turn
        keep = jnp.pad(keep.astype(jnp.float32),
                       ((0, 0), (0, 0), (0, keys - keep.shape[2])))
        in_specs.append(pl.BlockSpec((None, q_tokens, keys),
                                     lambda s, pt, kl: (s, 0, 0)))
        operands.append(keep)
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=in_specs + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((None, hp, v_width),
                                    lambda s, pt, kl: (s, 0, 0))],
            scratch_shapes=[
                pltpu.VMEM((2, pages * ps, W), pool.dtype),     # latent tiles
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((S, hp, v_width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=name,
    )(page_tables.astype(jnp.int32).reshape(S * mp),
      kv_lens.astype(jnp.int32), *operands, pool)
    return out[:, :R]


def _mla_impl(impl, interpret):
    if impl in (None, "auto"):
        impl = "reference" if cpu_backend() else "pallas"
    if impl not in ("reference", "pallas"):
        raise ValueError("impl must be auto|reference|pallas, got %r" % impl)
    return impl, cpu_backend() if interpret is None else interpret


def paged_mla_decode_attention(q, latent_pool, page_tables, kv_lens, *,
                               v_width, sm_scale, layer, impl=None,
                               interpret=None):
    """Absorbed MLA decode: one query token per slot against its latent rows.

    q: ``[S, H, W]`` — every head's query in the latent space, ``[q_nope
        W_uk | q_pe]`` (bf16 on the chip: one MXU pass; f32 is split in
        exact bf16 parts).
    latent_pool: the stored stack ``[L, num_pages, page_size, W]``, one row
        ``[c | k_pe | 0]`` a token, addressed in place by ``(layer, page)``;
        ``q`` carries zeros on the padding lanes too.
    page_tables / kv_lens: as :func:`paged_decode_attention`; ``kv_lens[s]
        == 0`` gives exact zeros and reads no page.
    Returns ``[S, H, v_width]`` float32: ``softmax(q . row * sm_scale) .
    row[:v_width]`` a head, for the caller's ``W_uv``.
    """
    S, H, W = q.shape
    impl, interpret = _mla_impl(impl, interpret)
    if impl == "reference":
        return _paged_mla_reference(q, latent_pool, page_tables, kv_lens,
                                    v_width, H, 1, sm_scale, layer)
    return _paged_mla_pallas(q, latent_pool, page_tables, kv_lens, v_width,
                             H, 1, sm_scale, interpret, layer)


def paged_mla_prefill_attention(q, latent_pool, pages, start, valid, *,
                                v_width, sm_scale, layer, keep=None,
                                impl=None, interpret=None):
    """Absorbed MLA attention of one prefill chunk: ``q [C, H, W]`` at
    absolute positions ``start ..`` against the sequence's ``pages [MP]``
    (the chunk's own rows already scattered in), causal by position; rows at
    or past ``valid`` are padding (garbage out).  ``keep [C, MP * ps]``
    (nonzero = attended), where given, is a selection a query: a key is read
    iff it is visible AND kept (:func:`dsa_keep`).  Returns ``[C, H,
    v_width]`` float32.  The reference reduces every row over the full
    page-table span (chunked == one bucket, bitwise); the kernel is the
    decode walk with ``_MLA_PREFILL_TOKENS`` rows of the chunk a grid step."""
    import jax.numpy as jnp

    C, H, W = q.shape
    impl, interpret = _mla_impl(impl, interpret)
    if impl == "reference":
        lens = jnp.where(jnp.arange(C) < valid,
                         start + jnp.arange(C, dtype=jnp.int32) + 1, 0)
        return _paged_mla_reference(
            q, latent_pool, pages, lens, v_width, H, 1, sm_scale, layer,
            keep=None if keep is None else keep[:, None, :])
    nt = math.gcd(C, _MLA_PREFILL_TOKENS)
    first = jnp.arange(C // nt, dtype=jnp.int32) * nt
    lens = jnp.where(first < valid, start + first + nt, 0)
    out = _paged_mla_pallas(
        q.reshape(C // nt, nt * H, W), latent_pool,
        jnp.broadcast_to(pages[None, :], (C // nt, pages.shape[0])), lens,
        v_width, H, nt, sm_scale, interpret, layer,
        keep=None if keep is None else keep.reshape(C // nt, nt, -1))
    return out.reshape(C, H, v_width)


# ---------------------------------------------------------------------------
# DeepSeek SPARSE attention over latent rows (DeepSeek-V3.2-Exp's lightning
# indexer, as ``glm_moe_dsa`` carries it): beside the latent row a token keeps
# ONE indexer key ``k^I`` (``Di`` lanes, a page leaf of its own, ``[L, P, ps,
# Di]``), a query scores every visible token ``I_s = scale * sum_j w_j *
# relu(q^I_j . k^I_s)`` over its ``Hi`` indexer heads, the ``k`` best are its
# SET (ties: the lower position), and latent attention reads those rows only.
# Three stages:
#
# * ``paged_index_scores``: the walk of ``paged_block_scores`` (one grid step a
#   slot, the slot's live pages copied whole, many to a turn on one semaphore
#   and ONE wait for the tile's bytes, the next turn's copies in flight), a
#   turn one ``[rows, Di] x [Di, turn]`` product, ReLU, the head weights and
#   the sum over a token's heads; a grid step may carry ``q_tokens`` tokens of
#   one sequence (a prefill chunk, as the latent kernel does).  Scores past a
#   token's visible keys are ``NEG_INF`` whatever the rows hold.
# * ``dsa_threshold`` / ``dsa_keep`` / ``dsa_rows``: the selection is exact and
#   is no sort.  A float32 score's bit pattern, sign-folded, orders as the
#   score does; the ``k``-th largest is found bit by bit from the top (32
#   counting passes), the ties at it are filled by position with a second
#   search over the position's bits, and the set is a MASK (a chunk: the
#   latent walk takes it as ``keep``) or, compacted by prefix counts in two
#   levels, a ROW LIST (a decode step).  ``dsa_select`` is the decode step's
#   entry, scores to list: the plain form is ``dsa_rows(dsa_keep())``, the
#   kernel (``paged_dsa_select``, one grid step a slot) does the same search
#   and the same two levels with the slot's keys resident in VMEM, the prefix
#   counts and the block look-up as 0/1 products on the MXU.
# * ``paged_mla_rows_attention``: the listed rows are resolved through the
#   page table, gathered ONCE for all heads into ``[S, k, W]`` and read by the
#   latent walk above as a slot's own ``k / ps`` pages.
# ---------------------------------------------------------------------------

_INDEX_KERNEL_NAME = "paged_index_scores"
_ROWS_KERNEL_NAME = "paged_mla_rows_attention"
_SELECT_KERNEL_NAME = "paged_dsa_select"
_SELECT_CHUNK = 32          # rows of 128 positions a turn of a counting pass
_INDEX_TURN_KEYS = 8192      # at most; fewer where a step has many rows
_INDEX_DOTS_BYTES = 2 * 1024 * 1024


def _index_turn_pages(mp, ps, rows):
    """Pages a turn of the indexer's walk, from the shapes alone: as many as
    keep one turn's ``[rows, turn]`` float32 products inside
    ``_INDEX_DOTS_BYTES``, at most ``_INDEX_TURN_KEYS`` keys or the table, in
    whole 128-lane tiles of keys where a page allows it."""
    keys = min(_INDEX_TURN_KEYS, max(128, _INDEX_DOTS_BYTES // (4 * rows)))
    return max(1, min(mp, keys // ps))


def _index_scores_reference(q, w, keys, limits, scale):
    """``q [N, R, Di]``, ``w [N, R]``, ``keys [N | 1, K, Di]``, ``limits [N,
    T]`` visible keys of each of the ``T`` tokens (``R / T`` rows a token) ->
    ``[N, T, K]`` float32."""
    import jax.numpy as jnp

    N, R, _ = q.shape
    T = limits.shape[1]
    dots = jnp.einsum("nrd,nkd->nrk" if keys.shape[0] == N else "nrd,okd->nrk",
                      q.astype(jnp.float32), keys.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    s = (jax.nn.relu(dots) * w.astype(jnp.float32)[..., None]).reshape(
        N, T, R // T, -1).sum(axis=2) * scale
    seen = jnp.arange(s.shape[-1])[None, None, :] < limits[..., None]
    return jnp.where(seen, s, NEG_INF)


def _index_scores_kernel(pt_ref, lens_ref, q_ref, w_ref, k_hbm, o_ref, buf,
                         sem, *, layer, pages, q_tokens, scale):
    """One grid step = one slot's ``q_tokens`` tokens (``rows / q_tokens``
    indexer heads each) against the slot's live pages of indexer keys
    (``buf [2, pages, ps, Di]``, a page an entry; entry ``i`` of turn ``t``
    is the slot's page ``t * pages + i``, or its last live page again where
    the turn runs past them: no page past ``kv_len`` is read and no trip
    count depends on the slot).  ``o_ref [q_tokens, width]``: every lane
    past a token's visible keys is ``NEG_INF``."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_idx = pl.program_id(0)
    ps, lanes = buf.shape[2:]
    rows = q_ref.shape[0]
    heads = rows // q_tokens
    turn = pages * ps
    kvl = lens_ref[s_idx]
    div = jax.lax.div
    n_pages = div(kvl + (ps - 1), ps)
    n_turns = div(n_pages + (pages - 1), pages)
    exact = (jax.lax.Precision.HIGHEST if buf.dtype == jnp.float32
             else jax.lax.Precision.DEFAULT)

    def start(t, slot):
        for i in range(pages):
            page = pt_ref[s_idx, jnp.minimum(t * pages + i, n_pages - 1)]
            pltpu.make_async_copy(k_hbm.at[layer, page], buf.at[slot, i],
                                  sem.at[slot]).start()

    o_ref[...] = jnp.full(o_ref.shape, NEG_INF, jnp.float32)

    @pl.when(n_turns > 0)
    def _first():
        start(0, 0)

    q = q_ref[...].astype(buf.dtype)
    w = w_ref[...]                                           # [rows, 1]
    tok = jax.lax.broadcasted_iota(jnp.int32, (q_tokens, turn), 0)
    limit = kvl - (q_tokens - 1 - tok)

    def one_turn(t, _):
        slot = jax.lax.rem(t, 2)

        @pl.when(t + 1 < n_turns)
        def _next():
            start(t + 1, 1 - slot)

        # the tile's bytes = the turn's copies: one wait for all of them
        pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()
        x = buf[slot].reshape(turn, lanes)
        dots = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                                   precision=exact,
                                   preferred_element_type=jnp.float32)
        s = jnp.maximum(dots, 0.0) * w                       # [rows, turn]
        if q_tokens == 1:
            s = s.sum(axis=0, keepdims=True)
        else:
            s = s.reshape(q_tokens, heads, turn).sum(axis=1)
        pos = t * turn + jax.lax.broadcasted_iota(
            jnp.int32, (q_tokens, turn), 1)
        o_ref[:, pl.ds(pl.multiple_of(t * turn, turn), turn)] = jnp.where(
            pos < limit, s * scale, NEG_INF)
        return _

    jax.lax.fori_loop(0, n_turns, one_turn, None)


def _paged_index_scores_pallas(q, w, pool, page_tables, kv_lens, q_tokens,
                               scale, interpret, layer):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import observability as obs

    S, R, Di = q.shape
    ps = pool.shape[2]
    mp = page_tables.shape[1]
    pages = _index_turn_pages(mp, ps, R)
    width = -(-mp // pages) * pages * ps
    steps = obs.counter("paged.index.grid_steps", labels={
        "S": S, "rows": R, "mp": mp, "ps": ps, "turn": pages * ps})
    if not steps.value:
        steps.inc(S)
    kernel = functools.partial(_index_scores_kernel, layer=layer, pages=pages,
                               q_tokens=q_tokens, scale=scale)
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[pl.BlockSpec((None, R, Di), lambda s, pt, kl: (s, 0, 0)),
                      pl.BlockSpec((None, R, 1), lambda s, pt, kl: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((None, q_tokens, width),
                                    lambda s, pt, kl: (s, 0, 0))],
            scratch_shapes=[
                pltpu.VMEM((2, pages, ps, Di), pool.dtype),     # key tiles
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=[jax.ShapeDtypeStruct((S, q_tokens, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=_INDEX_KERNEL_NAME,
    )(page_tables.astype(jnp.int32), kv_lens.astype(jnp.int32), q,
      w.astype(jnp.float32)[..., None], pool)
    return out[:, :, :mp * ps]


def paged_index_scores(q, w, index_pool, page_tables, kv_lens, *, layer,
                       scale, impl=None, interpret=None):
    """The lightning indexer's scores of one decode token a slot.

    q: ``[S, Hi, Di]`` the token's indexer queries (rotated); w: ``[S, Hi]``
        its head weights; index_pool: the stored stack ``[L, num_pages,
        page_size, Di]`` of indexer keys, addressed in place by ``(layer,
        page)``; page_tables / kv_lens as :func:`paged_decode_attention`.
    Returns ``[S, MP * ps]`` float32: ``scale * sum_j w_j relu(q_j . k_s)``
    at the slot's position ``s``, ``NEG_INF`` at and past ``kv_lens``
    whatever those rows hold (a reseated slot's last occupant, NaN)."""
    impl, interpret = _mla_impl(impl, interpret)
    if impl == "reference":
        S = q.shape[0]
        keys = index_pool[int(layer), page_tables].reshape(
            S, -1, index_pool.shape[-1])
        return _index_scores_reference(q, w, keys, kv_lens[:, None],
                                       scale)[:, 0]
    return _paged_index_scores_pallas(
        q, w, index_pool, page_tables, kv_lens, 1, scale, interpret,
        int(layer))[:, 0]


def paged_index_scores_prefill(q, w, index_pool, pages, start, valid, *,
                               layer, scale, impl=None, interpret=None):
    """The indexer's scores of one prefill chunk: ``q [C, Hi, Di]``, ``w [C,
    Hi]`` at absolute positions ``start ..`` against the sequence's ``pages
    [MP]`` (the chunk's own keys already scattered in), causal by position.
    Returns ``[C, MP * ps]`` float32, ``NEG_INF`` past a token's own position
    (rows at or past ``valid``: all of it)."""
    import jax.numpy as jnp

    C, Hi, Di = q.shape
    impl, interpret = _mla_impl(impl, interpret)
    if impl == "reference":
        lens = jnp.where(jnp.arange(C) < valid,
                         start + jnp.arange(C, dtype=jnp.int32) + 1, 0)
        keys = index_pool[int(layer), pages].reshape(1, -1, Di)
        return _index_scores_reference(q, w, keys, lens[:, None], scale)[:, 0]
    nt = math.gcd(C, _MLA_PREFILL_TOKENS)
    first = jnp.arange(C // nt, dtype=jnp.int32) * nt
    lens = jnp.where(first < valid, start + first + nt, 0)
    out = _paged_index_scores_pallas(
        q.reshape(C // nt, nt * Hi, Di), w.reshape(C // nt, nt * Hi),
        index_pool, jnp.broadcast_to(pages[None, :],
                                     (C // nt, pages.shape[0])),
        lens, nt, scale, interpret, int(layer))
    out = out.reshape(C, -1)
    return jnp.where((jnp.arange(C) < valid)[:, None], out, NEG_INF)


def _order_key(scores):
    """uint32 keys that order as the float32 ``scores`` do (no score is
    NaN)."""
    import jax.numpy as jnp

    scores = scores.astype(jnp.float32)
    # -0.0 ties with +0.0, as a comparison of the floats has it
    bits = jax.lax.bitcast_convert_type(
        jnp.where(scores == 0, jnp.float32(0), scores), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def dsa_threshold(scores, n_visible, k):
    """The exact ``min(n_visible, k)``-best set of each row of ``scores [N,
    K]`` (positions at or past ``n_visible [N]`` are no candidates), as a
    threshold: ``(key [N, K] uint32, tau [N] uint32, last_tie [N] int32)`` —
    position ``s`` is in the set iff ``key > tau``, or ``key == tau`` and ``s
    <= last_tie`` (ties at the threshold: the lower position wins).  ``tau``
    is built bit by bit from the top: the largest value that at least ``k``
    candidates reach; ``last_tie`` likewise over the position's bits."""
    import jax.numpy as jnp

    N, K = scores.shape
    pos = jnp.arange(K, dtype=jnp.int32)[None, :]
    # a candidate's key is at least 2 ** 23 - 1 > 0 (that of -inf)
    key = jnp.where(pos < n_visible[:, None], _order_key(scores),
                    jnp.uint32(0))
    want = jnp.minimum(n_visible, k).astype(jnp.int32)

    def bit(i, tau):
        cand = tau | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = (key >= cand[:, None]).sum(axis=1, dtype=jnp.int32)
        return jnp.where(n >= want, cand, tau)

    tau = jax.lax.fori_loop(0, 32, bit, jnp.zeros((N,), jnp.uint32))
    above = (key > tau[:, None]).sum(axis=1, dtype=jnp.int32)
    tie = key == tau[:, None]
    need = want - above                    # ties to take, lowest first
    bits = max(1, (K - 1).bit_length())

    def pbit(i, p):
        # the largest p with fewer than ``need`` ties before it
        cand = p | (jnp.int32(1) << (bits - 1 - i))
        n = (tie & (pos < cand[:, None])).sum(axis=1, dtype=jnp.int32)
        return jnp.where(n < need, cand, p)

    last = jax.lax.fori_loop(0, bits, pbit, jnp.zeros((N,), jnp.int32))
    return key, tau, jnp.where(need > 0, last, -1)


def dsa_keep(scores, n_visible, k):
    """The exact selection as a mask ``[N, K]`` bool (:func:`dsa_threshold`)."""
    import jax.numpy as jnp

    key, tau, last = dsa_threshold(scores, n_visible, k)
    pos = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
    return (key > tau[:, None]) | ((key == tau[:, None])
                                   & (pos <= last[:, None]))


def dsa_rows(keep, k):
    """A mask ``[N, K]`` with at most ``k`` positions a row -> ``(rows [N, k]
    int32 ascending, n [N] int32)``; entries at and past ``n`` are 0.  In two
    levels, over blocks of 128 positions: list entry ``j`` lies in the last
    block with at most ``j`` kept positions before it (a count over the
    blocks' prefix sums, no search), and inside the block it is the kept
    position of that rank (a prefix count over the block's 128 bits).  A
    binary search a list entry over the whole row's prefix counts, the form
    this replaced, was sixteen dependent element gathers an entry: 8.2 ms a
    layer at ``[24, 53248] -> [24, 2048]`` against 0.92 (PERF.md section 6,
    PR 52).  Since PR 53 the decode step goes through :func:`dsa_select`,
    whose plain form this stays."""
    import jax.numpy as jnp

    N, K = keep.shape
    B = math.gcd(K, 128)
    blocks = keep.reshape(N, K // B, B)
    count = blocks.sum(axis=2, dtype=jnp.int32)
    before = jnp.cumsum(count, axis=1) - count
    n = count.sum(axis=1)
    j = jnp.arange(k, dtype=jnp.int32)
    blk = (before[:, None, :] <= j[None, :, None]).sum(
        axis=2, dtype=jnp.int32) - 1                            # [N, k]
    rank = j[None, :] - jnp.take_along_axis(before, blk, axis=1)
    bits = jnp.take_along_axis(blocks, blk[:, :, None], axis=1)  # [N, k, B]
    inside = jnp.cumsum(bits, axis=2, dtype=jnp.int32) - 1
    lane = jnp.argmax(bits & (inside == rank[:, :, None]), axis=2)
    rows = blk * B + lane.astype(jnp.int32)
    return jnp.where(j[None, :] < n[:, None], rows, 0), n


def _dsa_select_kernel(nv_ref, s_ref, tri_ref, o_ref, key_ref, *, k, width):
    """One grid step = one slot: ``s_ref [B, 128]`` its scores, a block of
    128 positions a row, -> ``o_ref [k / 128, 128]`` its row list.  The
    sign-folded keys (``key_ref`` int32: :func:`_order_key` with the top bit
    flipped, so that a SIGNED compare orders them; a position at or past
    ``n_visible`` holds the least value) stay in VMEM from here to the list,
    and every loop runs over what the slot holds: the counting passes over
    its ``ceil(n_visible / 4096)`` turns of ``_SELECT_CHUNK`` rows, count and
    threshold kept as broadcast vectors; the list over its ``ceil(n_visible /
    16384)`` spans of 128 blocks, each span writing the list tiles (128
    entries) that its kept positions fall in."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    B, Bp = s_ref.shape[0], key_ref.shape[0]
    R, C = _SELECT_CHUNK, 128
    low = jnp.int32(-2 ** 31)
    nv = jnp.clip(nv_ref[pl.program_id(0)], 0, width)
    want = jnp.minimum(nv, k)
    div = jax.lax.div
    n_chunks = div(nv + (R * 128 - 1), R * 128)
    n_spans = div(nv + (C * 128 - 1), C * 128)
    at = jax.lax.broadcasted_iota

    def positions(first_row, rows):
        return ((first_row + at(jnp.int32, (rows, 128), 0)) * 128
                + at(jnp.int32, (rows, 128), 1))

    def chunk(c):
        return pl.ds(pl.multiple_of(c * R, R), R)

    def fold(c, _):
        x = s_ref[chunk(c), :]
        bits = jax.lax.bitcast_convert_type(
            jnp.where(x == 0, jnp.float32(0), x), jnp.int32)
        key = jnp.where(bits >= 0, bits, bits ^ jnp.int32(0x7FFFFFFF))
        key_ref[chunk(c), :] = jnp.where(positions(c * R, R) < nv, key, low)
        return _

    # every row a span below reads, and no more
    jax.lax.fori_loop(0, jnp.minimum(B // R, n_spans * (C // R)), fold, None)
    if Bp > B:
        @pl.when(n_spans * C > B)
        def _past_the_table():
            key_ref[B:, :] = jnp.full((Bp - B, 128), low)

    def count(tests, m):
        """How many of the slot's positions pass each of the ``m`` tests of
        ``tests(keys, first_row)``, each as a ``[1, 128]`` vector of one
        value."""
        def one(c, accs):
            hits = tests(key_ref[chunk(c), :], c * R)
            return tuple(a + jnp.where(h, 1.0, 0.0)
                         for a, h in zip(accs, hits))

        accs = jax.lax.fori_loop(
            0, n_chunks, one,
            tuple(jnp.zeros((R, 128), jnp.float32) for _ in range(m)))
        return tuple(jnp.broadcast_to(jnp.sum(
            jnp.sum(a, axis=0, keepdims=True), axis=1, keepdims=True),
            (1, 128)).astype(jnp.int32) for a in accs)

    # the threshold as :func:`dsa_threshold` builds it, TWO bits a pass (the
    # three candidates' counts are independent reductions; a pass waits for
    # its reduction, so half the passes is half the wait)
    def two_bits(i, tau):
        c1, c2, c3 = (tau ^ (jnp.int32(m) << (30 - 2 * i)) for m in (1, 2, 3))
        n1, n2, n3 = count(
            lambda key, _: (key >= c1, key >= c2, key >= c3), 3)
        return jnp.where(n3 >= want, c3, jnp.where(
            n2 >= want, c2, jnp.where(n1 >= want, c1, tau)))

    tau = jax.lax.fori_loop(0, 16, two_bits, jnp.full((1, 128), low))
    above, reach = count(lambda key, _: (key > tau, key >= tau), 2)
    need = want - above
    pairs = (max(1, (B * 128 - 1).bit_length()) + 1) // 2

    def two_position_bits(i, p):
        c1, c2, c3 = (p | (jnp.int32(m) << (2 * (pairs - 1 - i)))
                      for m in (1, 2, 3))

        def ties_before(key, first_row):
            tie, pos = key == tau, positions(first_row, R)
            return tie & (pos < c1), tie & (pos < c2), tie & (pos < c3)

        t1, t2, t3 = count(ties_before, 3)
        return jnp.where(t3 < need, c3, jnp.where(
            t2 < need, c2, jnp.where(t1 < need, c1, p)))

    # every tie at the threshold is in the set (the common case: one, the
    # ``want``-th best itself): nothing to cut and no pass
    cut = reach > want
    last = jax.lax.fori_loop(
        0, jnp.where(jnp.max(reach) > want, pairs, 0), two_position_bits,
        jnp.zeros((1, 128), jnp.int32))
    last = jnp.where(cut, last, B * 128)

    # the list, in two levels as :func:`dsa_rows`; 0/1 and counts of at most
    # 128 are exact in bfloat16, their sums in the float32 they add up in
    lower = tri_ref[...]
    ones = jnp.ones((C, 128), jnp.bfloat16)
    o_ref[...] = jnp.zeros(o_ref.shape, jnp.int32)

    def span(c, kept):
        """Blocks ``c * 128 ..``; ``kept [1, 128]``: the set's size before."""
        key = key_ref[pl.ds(pl.multiple_of(c * C, C), C), :]
        pos = positions(c * C, C)
        keep = (key > tau) | ((key == tau) & (pos <= last))
        keep = jnp.where(keep, 1.0, 0.0).astype(jnp.bfloat16)
        # kept positions up to each lane of each block, a block a LANE
        inside = jax.lax.dot_general(
            lower, keep, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.bfloat16)
        per_block = jnp.dot(keep, ones, preferred_element_type=jnp.float32)
        through = kept + jnp.dot(lower, per_block.astype(jnp.bfloat16),
                                 preferred_element_type=jnp.float32)
        before = through - per_block          # [128 blocks, 128] a block a ROW
        first = (pos - at(jnp.int32, (C, 128), 1)).astype(jnp.float32)
        total = through[C - 1:, :]

        def tile(t, _):
            """List entries ``t * 128 ..``, an entry a lane: its block is the
            one with ``before <= j < through``, its lane there the count of
            lanes that hold at most ``j - before`` kept positions."""
            j = (t * 128 + at(jnp.int32, (1, 128), 1)).astype(jnp.float32)
            here = (before <= j) & (through > j)
            start = jnp.sum(jnp.where(here, first, 0.0), axis=0,
                            keepdims=True)
            rank = j - jnp.sum(jnp.where(here, before, 0.0), axis=0,
                               keepdims=True)
            counts = jnp.dot(inside, jnp.where(here, 1.0, 0.0).astype(
                jnp.bfloat16), preferred_element_type=jnp.float32)
            lane = jnp.sum(jnp.where(counts <= rank, 1.0, 0.0), axis=0,
                           keepdims=True)
            mine = (j >= kept) & (j < total)
            o_ref[pl.ds(t, 1), :] += jnp.where(mine, start + lane,
                                               0.0).astype(jnp.int32)
            return _

        lo, hi = (jnp.max(x).astype(jnp.int32) for x in (kept, total))
        jax.lax.fori_loop(div(lo, 128), div(hi + 127, 128), tile, None)
        return total

    jax.lax.fori_loop(0, n_spans, span, jnp.zeros((1, 128), jnp.float32))


def _dsa_select_pallas(scores, n_visible, k, interpret):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import observability as obs

    S, K = scores.shape
    steps = obs.counter("paged.dsa_select.grid_steps",
                        labels={"S": S, "K": K, "k": k})
    if not steps.value:
        steps.inc(S)
    turn = _SELECT_CHUNK * 128
    Kp, kp = -(-K // turn) * turn, -(-k // 128) * 128
    if Kp != K:         # what lies past n_visible is never read as a score
        scores = jnp.pad(scores, ((0, 0), (0, Kp - K)))
    B = Kp // 128
    lower = jnp.tri(128, dtype=jnp.bfloat16)    # [r, c] = 1 where c <= r
    (rows,) = pl.pallas_call(
        functools.partial(_dsa_select_kernel, k=k, width=K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S,),
            in_specs=[pl.BlockSpec((None, B, 128), lambda s, nv: (s, 0, 0)),
                      pl.BlockSpec((128, 128), lambda s, nv: (0, 0))],
            out_specs=[pl.BlockSpec((None, kp // 128, 128),
                                    lambda s, nv: (s, 0, 0))],
            scratch_shapes=[pltpu.VMEM((-(-B // 128) * 128, 128),
                                       jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((S, kp // 128, 128), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=_SELECT_KERNEL_NAME,
    )(n_visible.astype(jnp.int32), scores.astype(jnp.float32).reshape(
        S, B, 128), lower)
    return rows.reshape(S, kp)[:, :k]


def dsa_select(scores, n_visible, k, *, impl=None, interpret=None):
    """A decode step's selection, scores to row list: ``scores [S, K]``
    float32, ``n_visible [S]`` -> ``(rows [S, k] int32 ascending, n [S]
    int32)``, to the bit what ``dsa_rows(dsa_keep(scores, n_visible, k), k)``
    gives, which is what ``impl="reference"`` calls: the exact ``min(n_visible,
    k)``-best set, ``-0.0`` tied with ``+0.0``, ties at the threshold to the
    lower position, entries at and past ``n`` zero, whatever lies at and past
    ``n_visible``.  ``impl="pallas"`` is one kernel that keeps a slot's scores
    in VMEM from the order key to the finished list
    (:func:`_dsa_select_kernel`)."""
    import jax.numpy as jnp

    impl, interpret = _mla_impl(impl, interpret)
    if impl == "reference":
        return dsa_rows(dsa_keep(scores, n_visible, k), k)
    if k > scores.shape[1]:
        raise ValueError("a list of %d rows out of %d positions"
                         % (k, scores.shape[1]))
    n = jnp.clip(jnp.minimum(n_visible, k), 0).astype(jnp.int32)
    return _dsa_select_pallas(scores, n_visible, k, interpret), n


def paged_mla_rows_attention(q, latent_pool, page_tables, rows, n_rows, *,
                             v_width, sm_scale, layer, impl=None,
                             interpret=None):
    """Absorbed MLA decode over a ROW LIST: slot ``s`` attends to its token
    positions ``rows[s, :n_rows[s]]`` and to nothing else.

    q, latent_pool, page_tables: as :func:`paged_mla_decode_attention`.
    rows ``[S, NS]`` int32 token positions (resolved here through the page
    table to ``(page, row)``), ``n_rows [S]`` how many of them count
    (``0`` gives exact zeros).  Each listed row is gathered ONCE for all
    heads into ``[S, NS, W]``, which the latent walk then reads as the slot's
    own ``NS / ps`` pages.  Returns ``[S, H, v_width]`` float32."""
    import jax.numpy as jnp

    S, H, W = q.shape
    ps = latent_pool.shape[2]
    impl, interpret = _mla_impl(impl, interpret)
    NS = -(-rows.shape[1] // ps) * ps
    if NS != rows.shape[1]:
        rows = jnp.pad(rows, ((0, 0), (0, NS - rows.shape[1])))
    pages = jnp.take_along_axis(page_tables, rows // ps, axis=1)
    listed = latent_pool[int(layer), pages, rows % ps]       # [S, NS, W]
    listed = listed.reshape(1, S * (NS // ps), ps, W)
    tables = jnp.arange(S * (NS // ps), dtype=jnp.int32).reshape(S, NS // ps)
    if impl == "reference":
        return _paged_mla_reference(q, listed, tables, n_rows, v_width, H, 1,
                                    sm_scale, 0)
    return _paged_mla_pallas(q, listed, tables, n_rows, v_width, H, 1,
                             sm_scale, interpret, 0, name=_ROWS_KERNEL_NAME)


# ---------------------------------------------------------------------------
# GROUPED query heads on the walk, with an optional WINDOW.  ``Hq = g * Hkv``
# query heads read the stored ``[L, P, ps, Hkv*Dh]`` stacks (query head ``i``
# reads KV head ``i // g``; ``layer`` an int or a traced scalar, as in the
# plain kernel): the same walk as the plain kernel (one grid step a
# slot, the slot's own pages copied whole, many to a turn, the next turn's
# copies in flight), with each KV head's ``g`` query rows scored against that
# head's ``Dh`` lanes of the tile and ``p . v`` taken over the same lanes, so
# no product is wasted on another head's lanes.  A grid step may carry
# ``q_tokens`` consecutive tokens of one sequence (a prefill chunk's rows, as
# the latent kernel does), staggered by ``block``: token ``t`` of the step sees
# the keys up to the end of its own BLOCK of ``block`` tokens (``limit = kv_len
# - (q_tokens - (t // block + 1) * block)``; the step's first token stands at
# a multiple of ``block``).  ``block = 1`` is the causal rule, one key more a
# token; ``block = q_tokens`` is no stagger at all, every row of the step sees
# all ``kv_len`` keys: a decode step that carries a slot's whole block under
# attention that is bidirectional inside it (``models/sdar.py``).  With
# ``window = W`` a query at position ``t`` sees
# keys ``t - W + 1 .. t``: the walk starts at the page that holds the oldest
# visible key and masks that page's rows before it, and the table is read as
# a RING (logical page ``p`` in column ``p % width``; a table as wide as the
# sequence is the same thing), because the cache has freed the pages before
# the window (``serving/kv_cache.py``, "Page groups").  The custom call is
# named by kind, ``paged_gqa_full_attention`` / ``paged_gqa_window_attention``,
# so that a device trace tells a model's two kinds of layer apart.
# ---------------------------------------------------------------------------

_GQA_PREFILL_TOKENS = 8     # chunk rows a grid step of the prefill form


def _gqa_row_tokens(n_rows, per_head, group, q_tokens):
    """Token of its step's ``q_tokens`` that each query row belongs to: rows
    are KV-head-major, ``per_head`` a head (token-major, ``group`` rows a
    token, then padding that follows the last token)."""
    import jax.numpy as jnp

    return jnp.minimum((jnp.arange(n_rows) % per_head) // group, q_tokens - 1)


def _paged_gqa_walk_reference(q, k_pool, v_pool, page_tables, kv_lens, n_kv,
                              q_tokens, window, sm_scale, layer, block=1):
    """``q [S, Hkv * q_tokens * g, Dh]`` (KV-head-major, then token, then
    group member) against each slot's pages; ``page_tables [S, MP]`` or one
    row ``[MP]`` for every slot.  Gathers only the pages a window can reach."""
    import jax.numpy as jnp

    S, R, Dh = q.shape
    ps = k_pool.shape[2]
    mp = page_tables.shape[-1]
    per = R // n_kv
    g = per // q_tokens
    tok = _gqa_row_tokens(R, per, g, q_tokens)
    # a row sees the keys up to the end of its token's block
    limit = kv_lens[:, None] - (
        q_tokens - (tok // block + 1) * block)[None, :]             # [S, R]
    if window is None:
        first = jnp.zeros((S,), jnp.int32)
        n_walk = mp
        lo = jnp.zeros_like(limit)
    else:
        lo = jnp.maximum(limit - window, 0)
        first = jnp.maximum(kv_lens - (q_tokens - block) - window, 0) // ps
        n_walk = min(mp, (window + q_tokens + ps - 2) // ps + 1)
    cols = (first[:, None] + jnp.arange(n_walk)[None, :]) % mp      # [S, NW]
    pages = (page_tables[cols] if page_tables.ndim == 1
             else jnp.take_along_axis(page_tables, cols, axis=1))
    pos = (first[:, None] * ps + jnp.arange(n_walk * ps)[None, :])  # [S, K]
    ok = ((pos[:, None, :] < limit[:, :, None])
          & (pos[:, None, :] >= lo[:, :, None]))                    # [S, R, K]
    outs = []
    for h in range(n_kv):
        lanes = slice(h * Dh, (h + 1) * Dh)
        rows = slice(h * per, (h + 1) * per)
        k = k_pool[layer, pages][..., lanes].reshape(
            S, n_walk * ps, Dh).astype(jnp.float32)
        v = v_pool[layer, pages][..., lanes].reshape(
            S, n_walk * ps, Dh).astype(jnp.float32)
        s = jnp.einsum("srd,skd->srk", q[:, rows].astype(jnp.float32), k,
                       precision=jax.lax.Precision.HIGHEST) * sm_scale
        okh = ok[:, rows]
        p = jax.nn.softmax(jnp.where(okh, s, NEG_INF), axis=-1)
        p = jnp.where(okh, p, 0.0)          # a row with no key -> zeros
        seen = okh.any(axis=1)[:, :, None]  # keys no row reads may be garbage
        outs.append(jnp.einsum("srk,skd->srd", p, jnp.where(seen, v, 0.0),
                               precision=jax.lax.Precision.HIGHEST))
    return jnp.concatenate(outs, axis=1)


def _paged_gqa_walk_kernel(pt_ref, lens_ref, q_ref, k_hbm, v_hbm, o_ref,
                           k_buf, v_buf, sem, *, layer, page_size, pages,
                           table_width, n_kv, per_head, group, q_tokens,
                           head_dim, window, sm_scale, listed=False, block=1):
    """One grid step = one slot's ``n_kv * per_head`` query rows against the
    slot's live pages, ``pages`` a turn (``_walk_pages``): the pages up to
    ``kv_len``, from the sequence's first or, with a ``window``, from the one
    that holds the oldest key any row of the step sees.

    ``listed``: one grid step = one (slot, KV head) of a ``(S, n_kv)`` grid,
    which walks its OWN row of the table (``table_width`` listed pages, of
    which ``lens_ref``'s tokens count, page by page) and copies that head's
    ``head_dim`` lanes of each page alone."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_idx = pl.program_id(0)
    lanes = None                        # the whole row of a page
    if listed:
        lanes = pl.ds(pl.multiple_of(pl.program_id(1) * head_dim, head_dim),
                      head_dim)
        s_idx, n_kv = s_idx * n_kv + pl.program_id(1), 1
    ps, turn = page_size, pages * page_size
    rows = n_kv * per_head
    kvl = lens_ref[s_idx]
    div = jax.lax.div
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    tok = jnp.minimum(div(jax.lax.rem(r, per_head), group), q_tokens - 1)
    if block == 1:
        limit = kvl - (q_tokens - 1 - tok)                   # [rows, 1]
    else:
        # to the end of the token's block: ``block == q_tokens`` is ``kvl``
        limit = kvl - (q_tokens - (div(tok, block) + 1) * block)
    least = jnp.maximum(kvl - (q_tokens - block), 0)         # the first token's
    if window is None:
        first_page, first = 0, None
    else:
        first_page = div(jnp.maximum(least - window, 0), ps)
        base = first_page * ps
        lo = jnp.maximum(limit - window, 0)
        first = (base, lo,
                 div(jnp.maximum(kvl - window, 0) - base, turn) + 1)
    n_pages = jnp.maximum(div(kvl + (ps - 1), ps) - first_page, 0)
    n_turns = div(n_pages + (pages - 1), pages)
    # turns in which every row sees every key up to the tile's end
    n_whole = div(jnp.maximum(least - first_page * ps, 0), turn)

    def copies(t, slot, i):
        col = first_page + t * pages + i
        if window is not None:
            col = jax.lax.rem(col, table_width)
        page = pt_ref[s_idx * table_width + col]
        at = pl.ds(pl.multiple_of(i * ps, ps), ps)
        src = ((layer, page) if lanes is None
               else (layer, page, slice(None), lanes))
        return (pltpu.make_async_copy(k_hbm.at[src],
                                      k_buf.at[slot, at], sem.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[src],
                                      v_buf.at[slot, at], sem.at[1, slot]))

    heads = [(slice(h * per_head, (h + 1) * per_head),
              slice(h * head_dim, (h + 1) * head_dim)) for h in range(n_kv)]
    q_parts = [_part_rows(q_ref[rs, :]) for rs, _ in heads]

    def scores(k):
        return jnp.concatenate([
            _parts_dot(qr, nq, k[:, ls], ((1,), (1,)))
            for (qr, nq), (_, ls) in zip(q_parts, heads)], axis=0) * sm_scale

    def pv(p, v):
        return jnp.concatenate([
            _parts_dot(*_part_rows(p[rs, :]), v[:, ls], ((1,), (0,)))
            for rs, ls in heads], axis=0)

    o_ref[...] = _walk_pages(
        kvl, (n_pages, n_turns, n_whole), page_size=ps, pages=pages,
        rows=rows, width=head_dim, copies=copies,
        tiles=lambda slot: (k_buf[slot], v_buf[slot]), scores=scores,
        limit=limit, first=first, pv=pv).astype(o_ref.dtype)


def _gqa_turn_pages(ps, lanes, mp, itemsize, rows, keys=None):
    """Pages a turn of the grouped walk: ``keys`` (``_DECODE_TURN_KEYS``)
    keys, halved until the K and V tiles (double-buffered, and a float32 copy
    of a tile that is not bfloat16), the scores, the probabilities and their
    parts fit the walk's VMEM budget."""
    pages = max(1, min(mp, (keys or _DECODE_TURN_KEYS) // ps))
    while pages > 1 and (4 * pages * ps * lanes * (itemsize + 2)
                         + 24 * rows * pages * ps
                         + 16 * rows * 128) > _DECODE_VMEM_BUDGET:
        pages = -(-pages // 2)
    return pages


def _paged_gqa_walk_pallas(q, k_pool, v_pool, page_tables, kv_lens, n_kv,
                           q_tokens, window, sm_scale, interpret, layer,
                           block=1):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import observability as obs

    S, R, Dh = q.shape
    ps = k_pool.shape[2]
    lanes = k_pool.shape[3]
    mp = page_tables.shape[1]
    per = R // n_kv
    g = per // q_tokens
    # a KV head's rows in whole sublane tiles of either dtype
    per_pad = -(-per // 16) * 16
    if per_pad != per:
        q = jnp.pad(q.reshape(S, n_kv, per, Dh),
                    ((0, 0), (0, 0), (0, per_pad - per), (0, 0))
                    ).reshape(S, n_kv * per_pad, Dh)
    rows = n_kv * per_pad
    pages = _gqa_turn_pages(ps, lanes, mp, k_pool.dtype.itemsize, rows)
    steps = obs.counter("paged.gqa.grid_steps", labels={
        "S": S, "mp": mp, "ps": ps, "turn": pages * ps,
        "window": window or 0})
    if not steps.value:
        steps.inc(S)
    kernel, layer_op = _layer_prefetch(
        _paged_gqa_walk_kernel, layer, 2, page_size=ps, pages=pages,
        table_width=mp, n_kv=n_kv, per_head=per_pad, group=g,
        q_tokens=q_tokens, head_dim=Dh, window=window, sm_scale=sm_scale,
        **({} if block == 1 else {"block": block}))
    slot_rows = pl.BlockSpec((None, rows, Dh), lambda s, *_: (s, 0, 0))
    stack = pl.BlockSpec(memory_space=pl.ANY)
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(layer_op),
            grid=(S,),
            in_specs=[slot_rows, stack, stack],
            out_specs=[slot_rows],
            scratch_shapes=[
                pltpu.VMEM((2, pages * ps, lanes), k_pool.dtype),   # k tiles
                pltpu.VMEM((2, pages * ps, lanes), v_pool.dtype),   # v tiles
                pltpu.SemaphoreType.DMA((2, 2)),                    # [k|v, tile]
            ]),
        out_shape=[jax.ShapeDtypeStruct((S, rows, Dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=("paged_gqa_full_attention" if window is None
              else "paged_gqa_window_attention"),
    )(page_tables.astype(jnp.int32).reshape(S * mp),
      kv_lens.astype(jnp.int32), *layer_op, q, k_pool, v_pool)
    return out.reshape(S, n_kv, per_pad, Dh)[:, :, :per].reshape(S, R, Dh)


def _gqa_heads(q, k_pool):
    """KV heads of the stored stack ``[L, P, ps, Hkv*Dh]`` under ``q [.., Hq,
    Dh]``."""
    Hq, Dh = q.shape[-2:]
    if k_pool.ndim != 4 or k_pool.shape[3] % Dh or Hq % (
            k_pool.shape[3] // Dh):
        raise ValueError(
            "the pool is the stored stack [L, P, ps, Hkv*Dh] with Dh = %d "
            "and Hkv dividing %d query heads; got %s"
            % (Dh, Hq, k_pool.shape))
    return k_pool.shape[3] // Dh


def _gqa_walk(q, k_pool, v_pool, page_tables, kv_lens, n_kv, q_tokens,
              window, sm_scale, impl, interpret, layer, block=1,
              one_table=False):
    """``one_table``: every row of ``page_tables`` is the same sequence's (a
    chunk's grid steps)."""
    impl, interpret = _mla_impl(impl, interpret)
    if window is not None and int(window) < 1:
        raise ValueError("window must be >= 1, got %r" % (window,))
    block = int(block)
    if block < 1 or q_tokens % block:
        raise ValueError(
            "a grid step's %d tokens are whole blocks of `block` tokens; got "
            "block = %r" % (q_tokens, block))
    if block > 1 and window is not None:
        raise ValueError("a window under blocks of %d tokens is not written "
                         "here" % block)
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    layer = _layer_index(layer)
    if impl == "reference":
        tables = (page_tables[0] if one_table and q_tokens > 1
                  else page_tables)
        return _paged_gqa_walk_reference(
            q, k_pool, v_pool, tables, kv_lens, n_kv, q_tokens, window,
            sm_scale, layer, block)
    return _paged_gqa_walk_pallas(
        q, k_pool, v_pool, page_tables, kv_lens, n_kv, q_tokens, window,
        sm_scale, interpret, layer, block)


def paged_gqa_decode_attention(q, k_pool, v_pool, page_tables, kv_lens, *,
                               layer, window=None, sm_scale=None, impl=None,
                               interpret=None, block=1):
    """Grouped-query decode on the walk: one query token per slot, or a
    slot's ``T`` newest tokens.

    q: ``[S, Hq, Dh]``, or ``[S, T, Hq, Dh]`` for the ``T`` tokens at
        positions ``kv_len - T .. kv_len - 1`` (their rows already in the
        pool); k_pool / v_pool: the stored stacks ``[L, num_pages,
        page_size, Hkv*Dh]`` addressed in place by ``(layer, page)``
        (``layer`` a Python int or a traced int32 scalar), ``Hq = g * Hkv``
        (query head ``i`` reads KV head ``i // g``).
    page_tables ``[S, MP]`` / kv_lens ``[S]``: as
        :func:`paged_decode_attention`; ``kv_lens[s] == 0`` gives exact zeros
        and reads no page.
    window: None (keys ``0 .. kv_len - 1``) or ``W``: keys ``kv_len - W ..
        kv_len - 1``, the table read as a RING — the sequence's logical page
        ``p`` in column ``p % MP`` — of which only the columns of the pages
        that hold those keys are read: a column of an older page may name
        any page, or scratch.
    block: how the ``T`` tokens are staggered: 1 causally, ``T`` not at all
        (every token sees all ``kv_len`` keys: one block under attention that
        is bidirectional inside it); ``kv_len - T`` is a multiple of it.
    Returns ``q``'s shape, float32.
    """
    n_kv = _gqa_heads(q, k_pool)
    if q.ndim == 3:
        return _gqa_walk(q, k_pool, v_pool, page_tables, kv_lens, n_kv, 1,
                         window, sm_scale, impl, interpret, layer, block)
    S, T, Hq, Dh = q.shape
    g = Hq // n_kv
    # rows of a slot KV-head-major: [S, T, Hkv, g, Dh] -> [S, Hkv, T, g, Dh]
    rows = q.reshape(S, T, n_kv, g, Dh).transpose(0, 2, 1, 3, 4)
    out = _gqa_walk(rows.reshape(S, n_kv * T * g, Dh), k_pool, v_pool,
                    page_tables, kv_lens, n_kv, T, window, sm_scale, impl,
                    interpret, layer, block)
    return out.reshape(S, n_kv, T, g, Dh).transpose(0, 2, 1, 3, 4).reshape(
        S, T, Hq, Dh)


def paged_gqa_prefill_attention(q, k_pool, v_pool, pages, start, valid, *,
                                layer, window=None, sm_scale=None, impl=None,
                                interpret=None, block=1):
    """Grouped-query attention of one prefill chunk on the walk: ``q [C, Hq,
    Dh]`` at absolute positions ``start ..`` against the sequence's ``pages
    [MP]`` (the chunk's own rows already scattered in), causal by position
    and, with ``window = W``, no further back than ``W - 1`` (``pages`` then
    a ring, as in :func:`paged_gqa_decode_attention`); rows at or past
    ``valid`` are padding (garbage out).  ``_GQA_PREFILL_TOKENS`` rows of the
    chunk a grid step.  With ``block = B`` a row sees the keys up to the end
    of its own block of ``B`` positions (causal between blocks, bidirectional
    inside one; ``start`` a multiple of ``B``, and a grid step carries whole
    blocks).  Returns ``[C, Hq, Dh]`` float32."""
    import jax.numpy as jnp

    C, Hq, Dh = q.shape
    n_kv = _gqa_heads(q, k_pool)
    g = Hq // n_kv
    nt = math.gcd(C, math.lcm(_GQA_PREFILL_TOKENS, int(block)))
    first = jnp.arange(C // nt, dtype=jnp.int32) * nt
    lens = jnp.where(first < valid, start + first + nt, 0)
    # rows of a step KV-head-major: [C/nt, nt, Hkv, g, Dh] -> [.., Hkv, nt, g]
    rows = q.reshape(C // nt, nt, n_kv, g, Dh).transpose(0, 2, 1, 3, 4)
    out = _gqa_walk(
        rows.reshape(C // nt, n_kv * nt * g, Dh), k_pool, v_pool,
        jnp.broadcast_to(pages[None, :], (C // nt, pages.shape[0])), lens,
        n_kv, nt, window, sm_scale, impl, interpret, layer, block,
        one_table=True)
    return out.reshape(C // nt, n_kv, nt, g, Dh).transpose(
        0, 2, 1, 3, 4).reshape(C, Hq, Dh)


# ---------------------------------------------------------------------------
# Grouped query heads and a SELECTION of pages (block-sparse attention), in
# decode.  ``Hq = g * Hkv`` query heads read ``Hkv``-head pools, and each
# (slot, KV head) may bring its own short list of pages in place of the
# slot's whole page-table row: ``sel_pages [S, Hkv, NS]`` in cache order, of
# which the first ``sel_tokens [S, Hkv]`` tokens, counted page by page, are
# valid (so every listed page but the last is whole, no entry past the count
# is read, and a count of 0 gives exact zeros and reads no page).  Without a
# selection the list is the slot's row and the count ``kv_lens``, for every
# KV head.  The kernel is the grouped walk above over that LIST: one grid
# step = one (slot, KV head), whose ``g`` query rows meet its listed pages
# many to a turn, the next turn's copies in flight (``_walk_pages``).  A
# copy takes that head's ``[ps, Dh]`` lanes of a page out of HBM, and a copy
# is whole lane tiles, so ``Dh`` must be a multiple of 128 on the chip.
# ``g = 1`` without a selection is NOT routed here: it is the plain kernel,
# which copies a page's whole ``[ps, H*Dh]`` row for all heads at once and so
# serves 64-lane heads too (ROADMAP D17 has what is left of the fold).
# ---------------------------------------------------------------------------


def _head_lists(page_tables, kv_lens, n_kv, selection):
    """``(pages [S, Hkv, NS], tokens [S, Hkv])``: the selection, or the
    slot's whole row for every KV head."""
    import jax.numpy as jnp

    if selection is not None:
        pages, tokens = selection
        return pages.astype(jnp.int32), tokens.astype(jnp.int32)
    S, mp = page_tables.shape
    return (jnp.broadcast_to(page_tables.astype(jnp.int32)[:, None, :],
                             (S, n_kv, mp)),
            jnp.broadcast_to(kv_lens.astype(jnp.int32)[:, None], (S, n_kv)))


def _paged_gqa_reference(q, k_pool, v_pool, pages, tokens, sm_scale, layer):
    import jax.numpy as jnp

    S, Hq, Dh = q.shape
    n_kv, ns = pages.shape[1:]
    g = Hq // n_kv
    ps = k_pool.shape[2]
    outs = []
    for h in range(n_kv):
        lanes = slice(h * Dh, (h + 1) * Dh)
        k = k_pool[layer, pages[:, h]][..., lanes].reshape(
            S, ns * ps, Dh).astype(jnp.float32)
        v = v_pool[layer, pages[:, h]][..., lanes].reshape(
            S, ns * ps, Dh).astype(jnp.float32)
        qh = q[:, h * g:(h + 1) * g].astype(jnp.float32)
        s = jnp.einsum("sgd,skd->sgk", qh, k,
                       precision=jax.lax.Precision.HIGHEST) * sm_scale
        ok = (jnp.arange(ns * ps)[None, :] < tokens[:, h, None])[:, None, :]
        p = jax.nn.softmax(jnp.where(ok, s, NEG_INF), axis=-1)
        p = jnp.where(ok, p, 0.0)          # a slot with no token -> zeros
        outs.append(jnp.einsum("sgk,skd->sgd", p, jnp.where(
            ok[:, 0, :, None], v, 0.0), precision=jax.lax.Precision.HIGHEST))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def _listed_turn_pages(ps, head_dim, ns, itemsize, rows):
    """Pages a turn of the LISTED walk, from the shapes alone.  A tile row
    is one head's ``head_dim`` lanes, not the page's whole row, so
    ``_DECODE_TURN_KEYS`` keys (tuned on rows of 512 to 1024 lanes) would be
    a tile of an eighth of those bytes and a turn's fixed cost most of it.
    The turn grows until a tile holds what 512 keys of a 1024-lane row do:
    4096 keys at 128 lanes, where 512 / 1024 / 2048 / 4096 / 8192 keys read
    1.15 / 0.92 / 0.84 / 0.80 / 0.86 ms a call at MiniCPM-SALA's shapes
    (PERF.md section 6, PR 39).  No longer than the list, and under the
    grouped walk's VMEM model (``_gqa_turn_pages``)."""
    lanes = -(-head_dim // 128) * 128
    return _gqa_turn_pages(ps, lanes, ns, itemsize, rows,
                           keys=_DECODE_TURN_KEYS * max(1, 1024 // lanes))


def _paged_gqa_pallas(q, k_pool, v_pool, pages, tokens, sm_scale, interpret,
                      layer):
    """The grouped walk (``_paged_gqa_walk_kernel``) over a LIST a (slot, KV
    head): a grid of ``(S, Hkv)`` walks, each the group's ``g`` query rows
    against its own listed pages, that head's lanes of them, many pages a
    turn.  The custom call is ``paged_gqa_decode_attention``."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import observability as obs

    S, Hq, Dh = q.shape
    n_kv, ns = pages.shape[1:]
    g = Hq // n_kv
    ps = k_pool.shape[2]
    if not interpret and Dh % 128:
        raise ValueError(
            "the listed walk copies one KV head's lanes of a page out of "
            "HBM: head_dim must be whole lane tiles (128), got %d" % Dh)
    q = q.reshape(S, n_kv, g, Dh)
    # a KV head's rows in whole sublane tiles of either dtype
    g_pad = -(-g // 16) * 16
    if g_pad != g:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    turn_pages = _listed_turn_pages(ps, Dh, ns, k_pool.dtype.itemsize, g_pad)
    steps = obs.counter("paged.gqa.grid_steps", labels={
        "S": S, "heads": n_kv, "listed": ns, "ps": ps,
        "turn": turn_pages * ps})
    if not steps.value:
        steps.inc(S * n_kv)
    kernel, layer_op = _layer_prefetch(
        _paged_gqa_walk_kernel, layer, 2, page_size=ps, pages=turn_pages,
        table_width=ns, n_kv=n_kv, per_head=g_pad, group=g, q_tokens=1,
        head_dim=Dh, window=None, sm_scale=sm_scale, listed=True)
    rows = pl.BlockSpec((None, None, g_pad, Dh),
                        lambda s, h, *_: (s, h, 0, 0))
    stack = pl.BlockSpec(memory_space=pl.ANY)
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(layer_op),
            grid=(S, n_kv),
            in_specs=[rows, stack, stack],
            out_specs=[rows],
            scratch_shapes=[
                pltpu.VMEM((2, turn_pages * ps, Dh), k_pool.dtype),  # k tiles
                pltpu.VMEM((2, turn_pages * ps, Dh), v_pool.dtype),  # v tiles
                pltpu.SemaphoreType.DMA((2, 2)),                     # [k|v, tile]
            ]),
        out_shape=[jax.ShapeDtypeStruct((S, n_kv, g_pad, Dh), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="paged_gqa_decode_attention",
    )(pages.reshape(S * n_kv * ns), tokens.reshape(S * n_kv), *layer_op, q,
      k_pool, v_pool)
    return out[:, :, :g].reshape(S, Hq, Dh)


# ---------------------------------------------------------------------------
# The SCORES a selection is picked from (InfLLM-V2, arXiv:2506.07900 section
# 2.2, as MiniCPM-SALA's sparse layers use it).  Beside K and V the cache
# keeps, a page, ``per = page_size / stride`` float32 rows: the mean of the
# keys of each ``stride`` tokens (a "half-kernel").  Pooled key ``j`` is the
# mean of rows ``j .. j + span - 1`` (``span = kernel_size / stride``), a
# query row's softmax runs over the pooled keys that lie whole inside its
# visible tokens, a block's (= page's) score is the largest probability among
# the ``per + span - 1`` pooled keys that overlap it, and a KV head's score is
# the sum over its group's query rows.  ``block_scores`` is that on rows
# already gathered (a prefill chunk: many query rows, one sequence's pages);
# in decode ``paged_block_scores`` walks each slot's page table over the
# pooled-row pool IN PLACE: one grid step a slot, the slot's
# ``ceil(kv_len / page_size)`` live pages copied whole (``per`` rows of all KV
# heads' lanes: 4 KB at MiniCPM-SALA's widths), many to a turn, the next turn's
# copies in flight (the discipline of ``_walk_pages``), and the whole row of
# scores finished in VMEM.  What XLA made of the same step was a gather of
# every slot's whole table span, its relayout and a contraction a KV head:
# 159 MB written and read back a layer, 2.16 ms of an 18.2 ms decode step
# (PERF.md section 6, PR 50).
#
# A page's rows land in VMEM as they lie in HBM (``[per, lanes]`` tiles of
# their own: the stored stack's device layout, which a copy cannot change),
# and a turn's tile is read as ``[pages * per, lanes]`` rows in cache order:
# half-kernel ``j`` of the slot is lane ``j`` of the products.  Pooling, the
# softmax and the overlap maximum are then elementwise over one ``[query
# rows, half-kernels]`` array and its rolls by a few lanes, every lane holds
# the score of a block that WOULD start there, and the caller keeps each
# ``per``-th lane: no strided access inside the kernel.  (Reading row ``r`` of
# every page apart, to keep ``per`` arrays a lane a page, made Mosaic load,
# rotate and select page by page: 3000 operations a turn, 0.5 of a 1.1 ms
# call; PERF.md section 6, PR 50.)
# ---------------------------------------------------------------------------


def block_scores(q, hb, n, *, kernel_size, stride, block_size):
    """``q [Bt, R, Hq, Dh]`` float32 query rows, ``hb [Bt, NHB, Hkv, Dh]``
    float32 half-kernel key means of each batch entry's whole page-table span
    in cache order, ``n [Bt, R]`` visible keys per row (0 = no row).  Returns
    ``score [Bt, R, Hkv, NB]`` float32, ``NB = NHB * stride / block_size``;
    rows past ``n``, incomplete half-kernels and whatever else the span holds
    count for nothing.  The contraction is float32 (highest precision)."""
    import jax.numpy as jnp

    Bt, R, Hq, Dh = q.shape
    NHB, Hkv = hb.shape[1:3]
    g = Hq // Hkv
    per = block_size // stride             # half-kernels a block
    NB = NHB // per
    span = kernel_size // stride           # half-kernels a pooled key averages
    NK = NHB - span + 1
    # one batched q . hb^T per KV head: the pooled rows stay in the order
    # the gather left them (a k-major einsum made the compiler transpose
    # the whole gathered span first)
    qg = q.reshape(Bt, R, Hkv, g, Dh)
    dots = jnp.stack([
        jnp.einsum("brgd,bjd->brgj", qg[:, :, k], hb[:, :, k],
                   precision=jax.lax.Precision.HIGHEST)
        for k in range(Hkv)], axis=2)
    # pooled key j = mean of half-kernels j .. j + span - 1 (linear in q)
    kscore = sum(dots[..., o:o + NK] for o in range(span)) / (
        span * math.sqrt(Dh))
    # complete kernels inside the visible range: s j + l - 1 <= n - 1
    nk = jnp.where(n >= kernel_size, (n - kernel_size) // stride + 1, 0)
    live = jnp.arange(NK)[None, None, :] < nk[..., None]          # [Bt,R,NK]
    live = live[:, :, None, None, :]
    p = jax.nn.softmax(jnp.where(live, kscore, NEG_INF), axis=-1)
    p = jnp.where(live, p, 0.0)
    # a block's score: max of p over the kernels that overlap it, summed
    # over the group.  kernel j overlaps block b iff
    # per * b - (span - 1) <= j <= per * b + per - 1
    blocks = jnp.arange(NB)
    score = None
    for o in range(per + span - 1):
        j = per * blocks - (span - 1) + o
        col = jnp.where((j >= 0) & (j < NK),
                        jnp.take(p, jnp.clip(j, 0, NK - 1), axis=-1), 0.0)
        score = col if score is None else jnp.maximum(score, col)
    return score.sum(axis=3)                                     # [Bt,R,Hkv,NB]


# Pages a turn of the scoring walk.  A turn's products are stored at lane
# ``turn * pages * per`` of the row, so a turn is whole 128-lane tiles (or the
# whole table where it is shorter).  At 4 KB a page the two tiles are 1 MB.
_SCORES_TURN_PAGES = 128
_SCORES_KERNEL_NAME = "paged_block_scores"


def _scores_turn_pages(mp):
    """Pages a turn of the scoring walk, from the table's width alone."""
    return min(mp, _SCORES_TURN_PAGES)


def _block_scores_kernel(pt_ref, lens_ref, q_ref, hb_hbm, o_ref, buf, dots,
                         sem, *, layer, pages, n_kv, group, per_head,
                         head_dim, kernel_size, stride):
    """One grid step = one slot: the walk over its live pages' pooled rows
    (``buf [2, pages, per, lanes]``, a page an entry), each turn's ``q . row``
    products stored in cache order in ``dots [n_kv * per_head, width]``
    (half-kernel ``j`` at lane ``j``), then the whole row finished from
    there: pooled keys, the softmax over the live ones, for every lane the
    largest probability among the pooled keys that overlap a block STARTING
    there, and the group's sum into ``o_ref [n_kv, width]`` (the caller keeps
    the lanes where a block does start).

    A turn is ``pages`` copies on one semaphore and ONE wait for the tile's
    bytes: entry ``i`` of turn ``t`` is the slot's page ``t * pages + i``, or
    its last live page again where the turn runs past them, so no page past
    ``kv_len`` is read and no trip count depends on the slot.  A live pooled
    key reads complete half-kernels of copied pages alone; the lanes past
    them hold whatever the tiles held and are dropped by a ``where`` on the
    live mask before any reduction."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_idx = pl.program_id(0)
    per, lanes = buf.shape[2:]
    rows, width = dots.shape
    span = kernel_size // stride
    ps = per * stride
    turn = pages * per                  # half-kernels a turn
    kvl = lens_ref[s_idx]
    div = jax.lax.div
    n_pages = div(kvl + (ps - 1), ps)
    n_turns = div(n_pages + (pages - 1), pages)

    def start(t, slot):
        for i in range(pages):
            page = pt_ref[s_idx, jnp.minimum(t * pages + i, n_pages - 1)]
            pltpu.make_async_copy(hb_hbm.at[layer, page], buf.at[slot, i],
                                  sem.at[slot]).start()

    heads = [(slice(h * per_head, (h + 1) * per_head),
              slice(h * head_dim, (h + 1) * head_dim)) for h in range(n_kv)]

    @pl.when(n_turns > 0)
    def _first():
        start(0, 0)

    def one_turn(t, _):
        slot = jax.lax.rem(t, 2)

        @pl.when(t + 1 < n_turns)
        def _next():
            start(t + 1, 1 - slot)

        # the tile's bytes = the turn's copies: one wait for all of them
        pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()
        x = buf[slot].reshape(turn, lanes)          # cache order, all heads
        at = pl.ds(pl.multiple_of(t * turn, turn), turn)
        for rs, ls in heads:
            # float32 operands at the highest precision: Mosaic takes the
            # product as XLA's HIGHEST takes it (the same bits on the chip)
            dots[rs, at] = jax.lax.dot_general(
                q_ref[rs, :], x[:, ls], (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        return _

    jax.lax.fori_loop(0, n_turns, one_turn, None)

    def ahead(x, by):
        """``x`` read ``by`` lanes on (``out[j] = x[j + by]``); the lanes
        that wrap are never live (see the wrapper)."""
        shift = (-by) % width
        return pltpu.roll(x, shift, 1) if shift else x

    nk = jnp.where(kvl >= kernel_size, div(kvl - kernel_size, stride) + 1, 0)
    live = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1) < nk
    d = dots[...]
    pooled = d
    for o in range(1, span):
        pooled = pooled + ahead(d, o)
    scaled = jnp.where(live, pooled / (span * math.sqrt(head_dim)), NEG_INF)
    e = jnp.exp(scaled - scaled.max(axis=1, keepdims=True))
    p = jnp.where(live, e / e.sum(axis=1, keepdims=True), 0.0)
    # a block that starts at half-kernel j meets pooled keys j - (span - 1)
    # .. j + per - 1
    best = None
    for o in range(-(span - 1), per):
        col = ahead(p, o)
        best = col if best is None else jnp.maximum(best, col)
    for h, (rs, _) in enumerate(heads):
        o_ref[h:h + 1, :] = best[rs][:group].sum(axis=0, keepdims=True)


def _paged_block_scores_pallas(q, hb_pool, page_tables, kv_lens, kernel_size,
                               stride, interpret, layer):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import observability as obs

    S, Hq, Dh = q.shape
    per, lanes = hb_pool.shape[2:]
    n_kv = lanes // Dh
    g = Hq // n_kv
    mp = page_tables.shape[1]
    # a KV head's rows in whole float32 sublane tiles
    g_pad = -(-g // 8) * 8
    q = q.reshape(S, n_kv, g, Dh).astype(jnp.float32)
    if g_pad != g:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, g_pad - g), (0, 0)))
    pages = _scores_turn_pages(mp)
    # half-kernels of the whole table, in whole turns.  A roll wraps only
    # lanes at or past the last pooled key into a live lane's sum (they are
    # masked first) and only into lanes past the last block's start
    width = -(-mp // pages) * pages * per
    steps = obs.counter("paged.select.grid_steps", labels={
        "S": S, "heads": n_kv, "mp": mp, "ps": per * stride,
        "turn": pages * per * stride})
    if not steps.value:
        steps.inc(S)
    kernel = functools.partial(
        _block_scores_kernel, layer=layer, pages=pages, n_kv=n_kv, group=g,
        per_head=g_pad, head_dim=Dh, kernel_size=kernel_size, stride=stride)
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[pl.BlockSpec((None, n_kv * g_pad, Dh),
                                   lambda s, pt, kl: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[pl.BlockSpec((None, n_kv, width),
                                    lambda s, pt, kl: (s, 0, 0))],
            scratch_shapes=[
                pltpu.VMEM((2, pages, per, lanes), hb_pool.dtype),  # tiles
                pltpu.VMEM((n_kv * g_pad, width), jnp.float32),     # q . rows
                pltpu.SemaphoreType.DMA((2,)),                      # [tile]
            ]),
        out_shape=[jax.ShapeDtypeStruct((S, n_kv, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=_SCORES_KERNEL_NAME,
    )(page_tables.astype(jnp.int32), kv_lens.astype(jnp.int32),
      q.reshape(S, n_kv * g_pad, Dh), hb_pool)
    # a block starts every ``per`` half-kernels
    return out[:, :, :mp * per:per]


def paged_block_scores(q, hb_pool, page_tables, kv_lens, *, layer,
                       kernel_size, stride, impl=None, interpret=None):
    """Block scores of one decode token a slot against the slot's own pages.

    q: ``[S, Hq, Dh]`` float32 (after QK-norm); hb_pool: the stored stack
        ``[L, num_pages, page_size / stride, Hkv*Dh]`` float32 of half-kernel
        key means, addressed in place by ``(layer, page)``; ``Hq = g * Hkv``.
    page_tables ``[S, MP]`` / kv_lens ``[S]``: as
        :func:`paged_decode_attention`.  The kernel reads no page past
        ``kv_len`` and no row past the last complete half-kernel into a
        score: unused table entries, unlisted pages and stale rows of a
        reused page may hold anything.  ``kv_lens[s] == 0`` gives zeros.
    impl: None/"auto" (pallas on TPU, reference elsewhere), "reference"
        (gather of the table span, then :func:`block_scores`), or "pallas".
    Returns ``[S, Hkv, MP]`` float32: :func:`block_scores` of the gathered
    span, to float32 rounding.
    """
    impl, interpret = _mla_impl(impl, interpret)
    n_kv = _gqa_heads(q, hb_pool)
    if kernel_size % stride:
        raise ValueError("a kernel of %d tokens is not whole strides of %d"
                         % (kernel_size, stride))
    if impl == "reference":
        hb = hb_pool[int(layer), page_tables].reshape(
            q.shape[0], -1, n_kv, q.shape[2])
        return block_scores(
            q[:, None], hb, kv_lens[:, None], kernel_size=kernel_size,
            stride=stride, block_size=hb_pool.shape[2] * stride)[:, 0]
    return _paged_block_scores_pallas(q, hb_pool, page_tables, kv_lens,
                                      kernel_size, stride, interpret,
                                      int(layer))


# ---------------------------------------------------------------------------
# Prefill-shaped attention over the SAME paged pool: a CHUNK of query tokens
# (absolute positions ``start .. start + C - 1`` of one sequence) against the
# sequence's pages.  This is the kernel half of chunked prefill (ISSUE 15):
# a long prompt is prefilled in fixed-size chunks interleaved with decode
# iterations, each chunk writing its k/v into the sequence's pages and then
# attending causally over EVERYTHING cached so far (earlier chunks, shared
# prefix-cache pages, and itself).
#
# Bitwise discipline: every prefill path (monolithic single-chunk, chunked,
# and prefix-cache resume) runs THIS attention at ONE fixed key width —
# the full page-table span ``max_pages * page_size`` — because the key
# width is part of the floating-point reduction shape: XLA's CPU backend
# produces different last-bit sums for different reduction widths, so
# "chunked == monolithic, bitwise" only holds when both sides reduce over
# identically shaped (masked) key tensors.  Row count (the chunk length)
# is NOT part of that contract — per-row results are row-independent, the
# same property the serving bucket ladder already leans on.
#
# Engines mirror paged_decode_attention: a gather + masked-softmax
# reference (CPU / tests), and a Pallas kernel whose k/v blocks are DMA'd
# straight from the pool via the scalar-prefetched page table.
# ---------------------------------------------------------------------------


def _paged_prefill_reference(q, k_pool, v_pool, pages, start, sm_scale,
                             layer):
    import jax.numpy as jnp

    C, H, Dh = q.shape
    ps = k_pool.shape[2]
    mp = pages.shape[0]
    # heads unfolded before the einsums, as in _paged_reference
    k = k_pool[layer, pages].reshape(mp * ps, H, Dh).astype(jnp.float32)
    v = v_pool[layer, pages].reshape(mp * ps, H, Dh).astype(jnp.float32)
    s = jnp.einsum("chd,khd->chk", q.astype(jnp.float32), k) * sm_scale
    # causal over CACHE order: query row i (absolute position start + i)
    # sees keys [0, start + i] — its own prefix, itself included
    lens = start + jnp.arange(C, dtype=jnp.int32) + 1
    ok = jnp.arange(mp * ps)[None, :] < lens[:, None]  # [C, K]
    s = jnp.where(ok[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("chk,khd->chd", p, v).astype(q.dtype)


def _paged_prefill_kernel(pt_ref, start_ref, q_ref, k_ref, v_ref, o_ref,
                          m_scr, l_scr, acc_scr, *, page_size,
                          num_pages_per_seq, chunk, n_head, head_dim,
                          sm_scale, layer=None):
    """One grid step = one page against the whole chunk, ALL heads (``layer``
    is the page blocks' index maps' to read, not the kernel's).  Heads
    are folded into the lane dimension (``[C, H*Dh]`` queries, ``[ps,
    H*Dh]`` pages — the pool's stored form), which is what
    makes the blocks legal on the chip; the head loop runs inside the
    kernel over static lane slices, one ``[C, Dh] x [Dh, ps]`` matmul
    each, with per-head online-softmax state in the leading scratch dim."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    j = pl.program_id(0)  # page walk
    start = start_ref[0]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # pages wholly past the LAST query row's visibility (key index >=
    # start + chunk) contribute nothing; skipping them is the whole point
    # of walking pages instead of the padded max_seq_len rectangle
    visible = j * page_size < start + chunk

    @pl.when(visible)
    def _body():
        kcol = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0)
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, page_size), 0)
        col = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (chunk, page_size), 1)
        ok = col <= start + row  # causal by absolute position
        for h in range(n_head):
            lanes = slice(h * head_dim, (h + 1) * head_dim)
            q = q_ref[:, lanes].astype(jnp.float32)     # [C, Dh]
            k = k_ref[:, lanes].astype(jnp.float32)     # [ps, Dh]
            v = v_ref[:, lanes].astype(jnp.float32)
            # zero key/value rows past the chunk's visibility so stale page
            # tails can't poison the p·v accumulation (0·garbage stays 0)
            k = jnp.where(kcol < start + chunk, k, 0.0)
            v = jnp.where(kcol < start + chunk, v, 0.0)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(ok, s, NEG_INF)

            m_prev = m_scr[h, :, 0:1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = jnp.broadcast_to(
                l_scr[h, :, 0:1] * alpha + p.sum(axis=1, keepdims=True),
                l_scr.shape[1:])
            acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])

    @pl.when(j == num_pages_per_seq - 1)
    def _finish():
        for h in range(n_head):
            denom = jnp.maximum(l_scr[h, :, 0:1], 1e-30)
            o_ref[:, h * head_dim:(h + 1) * head_dim] = (
                acc_scr[h] / denom).astype(o_ref.dtype)


def _paged_prefill_pallas(q, k_pool, v_pool, pages, start, sm_scale,
                          interpret, layer):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, H, Dh = q.shape
    ps = k_pool.shape[2]
    mp = pages.shape[0]
    pt = pages.astype(jnp.int32)
    start_arr = jnp.reshape(jnp.asarray(start, jnp.int32), (1,))

    # one page of this layer out of the stacked pool, as in _paged_pallas:
    # the kernel sees [ps, H*Dh].  A static layer is a constant of the index
    # map; a traced one is a third prefetched scalar that only the map reads
    kernel, layer_op = _layer_prefetch(
        _paged_prefill_kernel, layer, 2, page_size=ps, num_pages_per_seq=mp,
        chunk=C, n_head=H, head_dim=Dh, sm_scale=sm_scale)
    page = pl.BlockSpec(
        (None, None, ps, H * Dh),
        lambda j, pt, st, *ly: (ly[0][0] if ly else layer, pt[j], 0, 0))
    rows = pl.BlockSpec((C, H * Dh), lambda j, *_: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + len(layer_op),
        grid=(mp,),
        in_specs=[rows, page, page],
        out_specs=[rows],
        scratch_shapes=[
            pltpu.VMEM((H, C, 128), jnp.float32),  # running max (lane-replicated)
            pltpu.VMEM((H, C, 128), jnp.float32),  # running sum
            pltpu.VMEM((H, C, Dh), jnp.float32),   # output accumulator
        ],
    )
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((C, H * Dh), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(pt, start_arr, *layer_op, q.reshape(C, H * Dh), k_pool, v_pool)
    return out.reshape(C, H, Dh)


# ---------------------------------------------------------------------------
# Grouped query heads and a per-row BLOCK MASK, in chunked prefill.  Every
# query row of a chunk selects its own pages, so the kernel still walks the
# sequence's pages in order and ``block_mask [Hkv, C, MP]`` says, for the
# rows of each KV head's group, which pages a row may read (causality by
# absolute position still applies inside them).  The grid is (KV head,
# row block, ``PREFILL_PAGES_PER_STEP`` pages); the mask block ``[cb, MP]``
# stays in VMEM over the page walk and each page's column is picked out of
# its lanes.  A row must have
# the FIRST page it can see selected (block-sparse attention forces block
# 0), so its running max is finite before any masked page.
# ---------------------------------------------------------------------------

PREFILL_ROW_BLOCK = 128


def _paged_gqa_prefill_reference(q, k_pool, v_pool, pages, start, sm_scale,
                                 layer, n_kv, block_mask):
    import jax.numpy as jnp

    C, Hq, Dh = q.shape
    g = Hq // n_kv
    ps = k_pool.shape[2]
    mp = pages.shape[0]
    lens = start + jnp.arange(C, dtype=jnp.int32) + 1
    ok = jnp.arange(mp * ps)[None, :] < lens[:, None]            # [C, K]
    outs = []
    for h in range(n_kv):
        lanes = slice(h * Dh, (h + 1) * Dh)
        k = k_pool[layer, pages][..., lanes].reshape(
            mp * ps, Dh).astype(jnp.float32)
        v = v_pool[layer, pages][..., lanes].reshape(
            mp * ps, Dh).astype(jnp.float32)
        okh = ok if block_mask is None else (
            ok & jnp.repeat(block_mask[h], ps, axis=1))
        qh = q[:, h * g:(h + 1) * g].astype(jnp.float32)
        s = jnp.einsum("cgd,kd->cgk", qh, k,
                       precision=jax.lax.Precision.HIGHEST) * sm_scale
        p = jax.nn.softmax(jnp.where(okh[:, None, :], s, NEG_INF), axis=-1)
        p = jnp.where(okh[:, None, :], p, 0.0)
        outs.append(jnp.einsum("cgk,kd->cgd", p, v,
                               precision=jax.lax.Precision.HIGHEST))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


PREFILL_PAGES_PER_STEP = 4


def _paged_gqa_prefill_kernel(pt_ref, start_ref, q_ref, *refs, page_size,
                              n_steps, per_step, rows, group, head_dim,
                              sm_scale, masked):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    k_refs, v_refs = refs[:per_step], refs[per_step:2 * per_step]
    rest = refs[2 * per_step:]
    if masked:
        mask_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    c, j = pl.program_id(1), pl.program_id(2)
    first = start_ref[0] + c * rows       # absolute position of row 0
    width = per_step * page_size          # keys a grid step covers

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j * width < first + rows)
    def _body():
        kcol = j * width + jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0)
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
        local = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
        ok = j * width + local <= first + row   # causal by absolute position
        if masked:
            lane = jax.lax.broadcasted_iota(jnp.int32, mask_ref.shape, 1)
            for i in range(per_step):
                picked = jnp.sum(
                    jnp.where(lane == j * per_step + i, mask_ref[...], 0.0),
                    axis=1, keepdims=True) > 0.5                 # [rows, 1]
                ok = ok & (picked | (local // page_size != i))
        k = jnp.concatenate([r[...] for r in k_refs], axis=0)    # [width, Dh]
        v = jnp.concatenate([r[...] for r in v_refs], axis=0)
        k = jnp.where(kcol < first + rows, k.astype(jnp.float32), 0.0)
        v = jnp.where(kcol < first + rows, v.astype(jnp.float32), 0.0)
        for i in range(group):
            lanes = slice(i * head_dim, (i + 1) * head_dim)
            q = q_ref[:, lanes].astype(jnp.float32)              # [rows, Dh]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_scr[i, :, 0:1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[i] = jnp.broadcast_to(
                l_scr[i, :, 0:1] * alpha + p.sum(axis=1, keepdims=True),
                l_scr.shape[1:])
            acc_scr[i] = acc_scr[i] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_scr[i] = jnp.broadcast_to(m_new, m_scr.shape[1:])

    @pl.when(j == n_steps - 1)
    def _finish():
        for i in range(group):
            denom = jnp.maximum(l_scr[i, :, 0:1], 1e-30)
            o_ref[:, i * head_dim:(i + 1) * head_dim] = (
                acc_scr[i] / denom).astype(o_ref.dtype)


def _paged_gqa_prefill_pallas(q, k_pool, v_pool, pages, start, sm_scale,
                              interpret, layer, n_kv, block_mask):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    C, Hq, Dh = q.shape
    g = Hq // n_kv
    ps = k_pool.shape[2]
    mp = pages.shape[0]
    cb = PREFILL_ROW_BLOCK if C % PREFILL_ROW_BLOCK == 0 else C
    per = next(p for p in (PREFILL_PAGES_PER_STEP, 2, 1) if mp % p == 0)
    masked = block_mask is not None
    kernel = functools.partial(
        _paged_gqa_prefill_kernel, page_size=ps, n_steps=mp // per,
        per_step=per, rows=cb, group=g, head_dim=Dh, sm_scale=sm_scale,
        masked=masked)

    def page_of(i):
        def index(h, c, j, pt, st):
            # pages past the row block's last visible key are skipped: stay
            # on the last visible one (in VMEM already; no DMA for a skip)
            last = jnp.minimum((st[0] + (c + 1) * cb - 1) // ps, mp - 1)
            return (layer, pt[jnp.minimum(j * per + i, last)], 0, h)
        return pl.BlockSpec((None, None, ps, Dh), index)

    page_specs = [page_of(i) for i in range(per)]
    rows = pl.BlockSpec((cb, g * Dh), lambda h, c, j, pt, st: (c, h))
    in_specs = [rows] + page_specs + page_specs
    args = [q.reshape(C, Hq * Dh)] + [k_pool] * per + [v_pool] * per
    if masked:
        lanes = -(-mp // 128) * 128
        in_specs.append(pl.BlockSpec((None, cb, lanes),
                                     lambda h, c, j, pt, st: (h, c, 0)))
        args.append(jnp.pad(block_mask.astype(jnp.float32),
                            ((0, 0), (0, 0), (0, lanes - mp))))
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_kv, C // cb, mp // per),
            in_specs=in_specs,
            out_specs=[rows],
            scratch_shapes=[
                pltpu.VMEM((g, cb, 128), jnp.float32),   # running max
                pltpu.VMEM((g, cb, 128), jnp.float32),   # running sum
                pltpu.VMEM((g, cb, Dh), jnp.float32),    # output accumulator
            ]),
        out_shape=[jax.ShapeDtypeStruct((C, Hq * Dh), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_gqa_prefill_attention",
    )(pages.astype(jnp.int32),
      jnp.reshape(jnp.asarray(start, jnp.int32), (1,)), *args)
    return out.reshape(C, Hq, Dh)


def paged_prefill_attention(q, k_pool, v_pool, pages, start, sm_scale=None,
                            impl=None, interpret=None, layer=None,
                            block_mask=None):
    """Chunk-of-prompt attention against one sequence's paged KV.

    q: [C, Hq, Dh] — one prefill chunk's query tokens, absolute positions
        ``start .. start + C - 1`` (pad tail rows allowed; their outputs
        are garbage the caller ignores).  ``Hq = g * Hkv`` heads group
        over the pool's ``Hkv`` (query head ``i`` reads KV head ``i // g``).
    block_mask: None, or ``[Hkv, C, max_pages]`` bool — the pages each row
        of a KV head's group may read (block-sparse attention; causality
        still applies inside a page).  Every row must have page 0
        selected.  ``g = 1`` without a mask is the kernel it always was.
    k_pool / v_pool: with ``layer=li`` (the step programs) the STORED
        stack ``[L, num_pages, page_size, H*Dh]``, addressed in place
        (``li`` a Python int; the plain kernel also takes a traced int32
        scalar, a third prefetched operand that its page blocks' index map
        reads); with ``layer=None`` ONE layer's ``[num_pages, page_size, H,
        Dh]`` pool, folded into a one-layer stack for the same kernel.  The
        chunk's OWN k/v must already be scattered in.
    pages: [max_pages] int32 — the sequence's full page-table row in
        order; unused entries must point at a valid (scratch) page.
    start: int32 scalar — absolute position of the chunk's first row.
        Row i attends keys ``[0, start + i]`` (causal over cache order).
    impl: None/"auto" (pallas on TPU, reference elsewhere), "reference",
        or "pallas" (tests drive the kernel under interpret=True on CPU).

    The key width is ALWAYS the full ``max_pages * page_size`` span —
    fixed per cache geometry — so monolithic, chunked, and prefix-cache-
    resumed prefill reduce over identically shaped key tensors and stay
    bitwise interchangeable (see the section comment above).
    """
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    if impl in (None, "auto"):
        impl = "reference" if cpu_backend() else "pallas"
    k_pool, v_pool, layer, n_kv = _stacked_pools(q, k_pool, v_pool, layer)
    plain = n_kv == q.shape[1] and block_mask is None
    if impl not in ("reference", "pallas"):
        raise ValueError("impl must be auto|reference|pallas, got %r" % impl)
    if impl == "reference":
        if plain:
            return _paged_prefill_reference(q, k_pool, v_pool, pages, start,
                                            sm_scale, layer)
        return _paged_gqa_prefill_reference(q, k_pool, v_pool, pages, start,
                                            sm_scale, layer, n_kv, block_mask)
    if interpret is None:
        interpret = cpu_backend()
    if plain:
        return _paged_prefill_pallas(q, k_pool, v_pool, pages, start,
                                     sm_scale, interpret, layer)
    if not isinstance(layer, int):
        raise ValueError("the grouped / block-masked chunk kernel takes a "
                         "static layer (its page blocks' index maps hold it)")
    return _paged_gqa_prefill_pallas(q, k_pool, v_pool, pages, start,
                                     sm_scale, interpret, layer, n_kv,
                                     block_mask)


def paged_decode_attention(q, k_pool, v_pool, page_tables, kv_lens,
                           sm_scale=None, impl=None, interpret=None,
                           layer=None, selection=None):
    """Single-token-query attention against a paged KV pool.

    q: [S, Hq, Dh] — one query token per decode slot; ``Hq = g * Hkv``
        heads group over the pool's ``Hkv`` (query head ``i`` reads KV
        head ``i // g``).
    selection: None, or ``(sel_pages [S, Hkv, NS], sel_tokens [S, Hkv])``
        — per slot and KV head a short list of pages in cache order in
        place of the slot's whole row, of which the first ``sel_tokens``
        tokens (page by page) are valid; ``page_tables`` / ``kv_lens`` are
        then not read.  ``g = 1`` without a selection is the plain kernel:
        one grid step a slot, the slot's own pages many to a turn.
    k_pool / v_pool: with ``layer=li`` (the step programs; a Python int, or
        a traced int32 scalar inside a program's loop over its layers) the
        STORED stack ``[L, num_pages, page_size, H*Dh]``, addressed in
        place; with ``layer=None`` ONE layer's ``[num_pages, page_size, H,
        Dh]`` pool, folded into a one-layer stack for the same kernel.
    page_tables: [S, max_pages] int32 — slot s's kv lives in pages
        ``page_tables[s, :ceil(kv_lens[s]/page_size)]`` in order; unused
        entries must point at a valid (scratch) page id.
    kv_lens: [S] int32 — tokens of valid kv per slot; 0 = inactive slot,
        whose output row is exactly zero.  The plain kernel reads no page,
        and no row of a page, past it: a slot's output depends on its own
        query, pages and length alone.
    impl: None/"auto" (pallas on TPU, reference elsewhere), "reference",
        or "pallas" (tests drive the kernel under interpret=True on CPU).
    """
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    if impl in (None, "auto"):
        impl = "reference" if cpu_backend() else "pallas"
    k_pool, v_pool, layer, n_kv = _stacked_pools(q, k_pool, v_pool, layer)
    plain = n_kv == q.shape[1] and selection is None
    if impl not in ("reference", "pallas"):
        raise ValueError("impl must be auto|reference|pallas, got %r" % impl)
    if interpret is None:
        interpret = cpu_backend()
    if plain and impl == "reference":
        return _paged_reference(q, k_pool, v_pool, page_tables, kv_lens,
                                sm_scale, layer)
    if plain:
        return _paged_pallas(q, k_pool, v_pool, page_tables, kv_lens,
                             sm_scale, interpret, layer)
    pages, tokens = _head_lists(page_tables, kv_lens, n_kv, selection)
    if impl == "reference":
        return _paged_gqa_reference(q, k_pool, v_pool, pages, tokens,
                                    sm_scale, layer)
    return _paged_gqa_pallas(q, k_pool, v_pool, pages, tokens, sm_scale,
                             interpret, layer)


# ---------------------------------------------------------------------------
# EVA (Zheng et al. 2023, arXiv:2302.04542, in EvaByte's causal, chunk-
# summarised form): a query reads the exact K and V rows of its own ALIGNED
# window so far and one summary row for every chunk of every earlier window,
# under ONE softmax.  The two kinds of row live in two page groups of the
# cache (``serving/kv_cache.py``): the window's in ``k_pool`` / ``v_pool`` by
# ``win_tables`` (a window's ``j``-th page in column ``j``), the summaries in
# ``ks_pool`` / ``vs_pool`` by ``sum_tables``, each with a page size of its
# own.  Heads are folded ``[rows, H * Dh]`` as in the plain walk, and the
# decode kernel IS the plain walk taken twice with the softmax's running state
# carried from the first list into the second (``_walk_pages(carry=)``).
# ---------------------------------------------------------------------------

# Keys a turn of either list: 4096-lane bf16 tiles of 256 rows are 2 MB, two
# lists x (k, v) x two tiles 16 MB of a v5e's 128 MiB (the kernel states its
# own limit).
_EVA_TURN_KEYS = 256
_EVA_KERNEL_NAME = "eva_window_summary_decode"


def _eva_lists_reference(q, pools, tables, lens, sm_scale, layer):
    """The XLA form of one softmax over both lists: ``q [S, R, H, Dh]`` (``R``
    query rows a slot), ``tables`` / ``lens`` the two lists' ``[S, MP]`` pages
    and visible rows (``[S]``, or ``[S, R]`` where a slot's rows differ); a
    row that sees nothing gives zeros."""
    import jax.numpy as jnp

    S, R, H, Dh = q.shape
    ks, vs, oks = [], [], []
    for (k_pool, v_pool), table, n in zip(pools, tables, lens):
        rows = table.shape[1] * k_pool.shape[2]
        ks.append(k_pool[layer, table].reshape(S, rows, H, Dh))
        vs.append(v_pool[layer, table].reshape(S, rows, H, Dh))
        n = jnp.broadcast_to(n.reshape(S, -1), (S, R))
        oks.append(jnp.arange(rows)[None, None, :] < n[:, :, None])
    k, v = jnp.concatenate(ks, axis=1), jnp.concatenate(vs, axis=1)
    ok = jnp.concatenate(oks, axis=2)                       # [S, R, K]
    # operands in their common dtype, float32 results: a bfloat16 product is
    # exact in float32, and a chunk's 4096 keys are not copied as float32
    dt = jnp.promote_types(q.dtype, k.dtype)
    s = jnp.einsum("srhd,skhd->srhk", q.astype(dt), k.astype(dt),
                   preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(ok[:, :, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(ok.any(axis=2)[:, :, None, None], p, 0.0)
    # float32 probabilities stay float32 (the chip's default would round them
    # to bfloat16: 2e-3 of the result where the kernel's exact parts lose none)
    return jnp.einsum("srhk,skhd->srhd", p, v.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def _paged_eva_kernel(ptw_ref, pts_ref, lw_ref, ls_ref, q_ref, k_hbm, v_hbm,
                      ks_hbm, vs_hbm, o_ref, kw_buf, vw_buf, ks_buf, vs_buf,
                      sem, *, layer, win, summ, n_head, head_dim, sm_scale):
    """One grid step = one SLOT: the plain walk (``_paged_decode_kernel``:
    block-diagonal query rows, all heads a turn) over the slot's window pages,
    masked past its ``lw`` rows, then over its summary pages up to ``ls``
    rows, the running max, sum and accumulator carried across.  ``win`` /
    ``summ`` = ``(page rows, pages a turn, table columns)`` of each list."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s_idx = pl.program_id(0)
    lanes = n_head * head_dim
    hp = -(-n_head // 8) * 8
    div = jax.lax.div
    own = (div(jax.lax.broadcasted_iota(jnp.int32, (hp, lanes), 1), head_dim)
           == jax.lax.broadcasted_iota(jnp.int32, (hp, lanes), 0))
    q = q_ref[...].astype(jnp.float32) * sm_scale          # [1, lanes]
    q_rows, nq = _part_rows(
        jnp.where(own, jnp.broadcast_to(q, (hp, lanes)), 0.0))

    def walk(kvl, pt_ref, geom, pools, bufs, sem_row, carry, finish):
        ps, pages, mp = geom
        turn = ps * pages

        def copies(t, slot, i):
            page = pt_ref[s_idx * mp + t * pages + i]
            rows = pl.ds(pl.multiple_of(i * ps, ps), ps)
            return tuple(pltpu.make_async_copy(
                pool.at[layer, page], buf.at[slot, rows],
                sem.at[sem_row + j, slot])
                for j, (pool, buf) in enumerate(zip(pools, bufs)))

        return _walk_pages(
            kvl, (div(kvl + (ps - 1), ps), div(kvl + (turn - 1), turn),
                  div(kvl, turn)),
            page_size=ps, pages=pages, rows=hp, width=lanes, copies=copies,
            tiles=lambda slot: (bufs[0][slot], bufs[1][slot]),
            scores=lambda k: _parts_dot(q_rows, nq, k, ((1,), (1,))),
            carry=carry, finish=finish)

    carry = walk(lw_ref[s_idx], ptw_ref, win, (k_hbm, v_hbm),
                 (kw_buf, vw_buf), 0, None, False)
    out = walk(ls_ref[s_idx], pts_ref, summ, (ks_hbm, vs_hbm),
               (ks_buf, vs_buf), 2, carry, True)
    o_ref[...] = jnp.sum(jnp.where(own, out, 0.0), axis=0,
                         keepdims=True).astype(o_ref.dtype)


def _eva_turn_pages(ps, mp):
    return max(1, min(mp, _EVA_TURN_KEYS // ps))


def _paged_eva_pallas(q, pools, tables, lens, sm_scale, interpret, layer):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import observability as obs

    S, H, Dh = q.shape
    geoms, need = [], 0
    for (k_pool, _), table in zip(pools, tables):
        ps, mp = k_pool.shape[2], table.shape[1]
        pages = _eva_turn_pages(ps, mp)
        geoms.append((ps, pages, mp))
        need += _decode_vmem_bytes(pages, ps, H * Dh, k_pool.dtype.itemsize,
                                   H)
    steps = obs.counter("paged.eva.grid_steps", labels={
        "S": S, "window": "%dx%d" % geoms[0][:2],
        "summary": "%dx%d" % geoms[1][:2]})
    if not steps.value:
        steps.inc(S)

    kernel = functools.partial(
        _paged_eva_kernel, layer=layer, win=geoms[0], summ=geoms[1],
        n_head=H, head_dim=Dh, sm_scale=sm_scale)
    row = pl.BlockSpec((None, 1, H * Dh), lambda s, *_: (s, 0, 0))
    stack = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S,),
        in_specs=[row, stack, stack, stack, stack],
        out_specs=[row],
        scratch_shapes=[
            pltpu.VMEM((2, ps * pages, H * Dh), pool.dtype)
            for (ps, pages, _), pair in zip(geoms, pools) for pool in pair
        ] + [pltpu.SemaphoreType.DMA((4, 2))],   # [list x (k | v), tile]
    )
    (out,) = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((S, 1, H * Dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(need),
        ),
        interpret=interpret,
        name=_EVA_KERNEL_NAME,
    )(tables[0].astype(jnp.int32).reshape(-1),
      tables[1].astype(jnp.int32).reshape(-1),
      lens[0].astype(jnp.int32), lens[1].astype(jnp.int32),
      q.reshape(S, 1, H * Dh), *pools[0], *pools[1])
    return out.reshape(S, H, Dh)


def _eva_pools(q, k_pool, v_pool, ks_pool, vs_pool):
    width = q.shape[-2] * q.shape[-1]
    for pool in (k_pool, v_pool, ks_pool, vs_pool):
        if pool.ndim != 4 or pool.shape[3] != width:
            raise ValueError(
                "EVA attention reads the stored stacks [L, pages, rows, H * "
                "Dh] with H * Dh = %d (every head has its own K and V); got "
                "%s" % (width, pool.shape))
    return (k_pool, v_pool), (ks_pool, vs_pool)


def paged_eva_decode_attention(q, k_pool, v_pool, ks_pool, vs_pool,
                               win_tables, sum_tables, win_lens, sum_lens, *,
                               layer, sm_scale=None, impl=None,
                               interpret=None):
    """EVA decode: one query token a slot against its window's exact rows and
    the visible summaries, one softmax over both.

    q: ``[S, H, Dh]``; k_pool / v_pool ``[L, Pw, psw, H * Dh]`` and ks_pool /
        vs_pool ``[L, Ps, pss, H * Dh]``: the stored stacks of the two page
        groups, addressed in place by ``(layer, page)``.
    win_tables ``[S, MPw]`` / win_lens ``[S]``: the window's pages in order
        from column 0 and its rows written so far (the query's own included);
        sum_tables ``[S, MPs]`` / sum_lens ``[S]``: the summary pages from the
        sequence's first on and the rows VISIBLE to the query (fewer than are
        written).  No page or row past a length is read; a slot with both at
        0 gives exact zeros.
    Returns ``[S, H, Dh]`` float32.
    """
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    if impl in (None, "auto"):
        impl = "reference" if cpu_backend() else "pallas"
    if impl not in ("reference", "pallas"):
        raise ValueError("impl must be auto|reference|pallas, got %r" % impl)
    pools = _eva_pools(q, k_pool, v_pool, ks_pool, vs_pool)
    tables, lens = (win_tables, sum_tables), (win_lens, sum_lens)
    if impl == "reference":
        return _eva_lists_reference(q[:, None], pools, tables, lens,
                                    sm_scale, layer)[:, 0]
    if interpret is None:
        interpret = cpu_backend()
    return _paged_eva_pallas(q, pools, tables, lens, sm_scale, interpret,
                             layer)


def paged_eva_prefill_attention(q, k_pool, v_pool, ks_pool, vs_pool,
                                win_pages, sum_pages, start, window, chunk, *,
                                layer, sm_scale=None):
    """EVA over one prefill chunk: ``q [C, H, Dh]`` at positions ``start ..
    start + C - 1``, none of which crosses a multiple of ``window`` (the
    chunk's own K and V already scattered into the window's pages).  Row ``i``
    reads the window's rows ``0 .. start + i - b`` with ``b = (start //
    window) * window`` and the ``b / chunk`` summaries of the windows before.
    The XLA form over the gathered pages (set-up's path: the decode kernel's
    twin in Pallas is not written); the key width is the table's own, whatever
    the chunk.  Returns ``[C, H, Dh]`` float32."""
    import jax.numpy as jnp

    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    pools = _eva_pools(q, k_pool, v_pool, ks_pool, vs_pool)
    base = (start // window) * window
    rows = start - base + 1 + jnp.arange(q.shape[0], dtype=jnp.int32)
    return _eva_lists_reference(
        q[None], pools, (win_pages[None], sum_pages[None]),
        (rows[None], jnp.reshape(base // chunk, (1, 1))), sm_scale, layer)[0]


def paged_kv_finite(k_pool, v_pool, pages):
    """Fused per-page isfinite sweep over freshly written KV pages.

    The decode analog of the trainer's ``nan_guard``: the scheduler runs
    this (opt-in, ``DecodeConfig(kv_guard=True)``) over the pages a
    prefill chunk or decode step just wrote, so a non-finite k/v
    projection fails exactly the owning sequence typed instead of
    parking NaNs in pages a prefix-sharing sequence will read later.

    k_pool / v_pool: the cache's stacked ``[L, num_pages, ps, H*D]``
    pools (all layers — a bad write in ANY layer must trip).
    pages: ``[N]`` int32 page ids to check (per-slot decode tail pages,
    or the pages a chunk wrote; padding entries may aim at scratch
    page 0, whose writes are always finite model outputs).

    Returns ``[N]`` bool — ``False`` marks a page holding a non-finite
    value.  One gather + one reduction, fused under the caller's jit;
    everything reduces on device and only N booleans cross to host.
    """
    import jax.numpy as jnp

    k = k_pool[:, pages]        # [L, N, ps, H*D]
    v = v_pool[:, pages]
    axes = (0, 2, 3)
    return (jnp.isfinite(k).all(axis=axes)
            & jnp.isfinite(v).all(axis=axes))
