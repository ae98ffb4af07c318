"""Continuous-batching decode scheduler: iteration-level sequence serving.

The throughput problem with naive autoregressive serving is REQUEST-level
scheduling: a batch decodes in lockstep until its *longest* sequence
finishes, and new arrivals wait for the whole batch to retire — almost
all of the accelerator's decode capacity burns on padding and requeue
latency.  This module implements iteration-level scheduling in the style
of Orca (Yu et al., OSDI'22): the decode step is ONE fixed-shape compiled
program over ``num_slots`` slots, and the scheduler admits new sequences
into free slots and retires finished ones *between* iterations — the
batch composition changes every step, the compiled shape never does.

Shape discipline (the TPU-native part, same philosophy as the predict
path's bucket ladder):

* **prefill** runs per sequence as a series of CHUNK steps over the paged
  pool: each chunk scatters its page-multiple k/v window into the
  sequence's pages, then attends (causally, by absolute position) over
  everything cached so far through the page table.  With
  ``DecodeConfig.prefill_chunk_tokens`` unset, a prompt is ONE chunk
  padded to a page-multiple length-bucket ladder (the monolithic
  behavior, one warmed program per bucket); set, prefill is split into
  fixed-budget chunks and the scheduler runs AT MOST ONE chunk per
  iteration, fewest-remaining-chunks first (admission order on ties),
  interleaved with the decode step — so a long prompt no longer
  head-of-line-blocks active decodes or short prompts behind it: TTFT
  and inter-token latency are bounded by the chunk size, not the
  longest prompt.  The chunk step is
  one compiled program per chunk width, so the zero-recompile contract
  holds with chunking on.
* **prefix caching** (``DecodeConfig.prefix_cache=True``): admission
  probes the KV cache's content-hash page index with the prompt's chain
  hashes and maps any cached leading full pages read-only (refcounted —
  see :mod:`~paddle_tpu.serving.kv_cache`); only the uncached tail is
  prefilled, resuming chunk steps mid-prompt.  Repeated system prompts /
  few-shot templates stop being recomputed; reuse shows up on
  ``serving.decode.kv_hit_pages`` and prefilled work on
  ``serving.decode.prefill_tokens``.
* **decode** is a single ``[num_slots]`` program: embed one token per
  slot, scatter its k/v into the paged pool, attend over each slot's own
  pages (``paged_decode_attention``), greedy-sample the next token.
  Inactive slots ride along with ``kv_lens == 0`` — fully masked, exact
  zeros, scratch-page writes — so admission/retirement never changes the
  dispatched shape.  Zero recompiles after warmup is asserted against
  ``executor.compile_count()`` (every dispatch goes through a
  :class:`~paddle_tpu.executor.JitStepCache`).
* **bitwise per-sequence equality**: a sequence's tokens depend only on
  its own slot's row — matmul rows, layer norm, attention-over-own-pages
  and argmax are all row-independent — so continuous batching returns
  bit-identical tokens to serving the same request alone
  (``max_active=1``), which is what tools/check_decode.py gates.

**One decode step in flight** (ISSUE 36): the loop dispatches step n+1
before it reads step n.  The sampled tokens stay on the device — the decode
program takes the previous step's output beside the host's ``tokens`` and a
per-slot mask, and feeds ``where(mask, previous, tokens)`` — while
positions, ``kv_lens``, seeds and page tables depend on LENGTHS alone,
which the host knows ahead.  So the host's build, dispatch and commit run
under the device's step instead of between two of them.  Decisions that
need token VALUES run one step late and change no served token: a slot
that hit EOS in step n rode step n+1, and that token is dropped at commit
(``serving.decode.tokens_discarded``).  A commit matches a result to the
``_Slot`` OBJECT captured at its plan, never to the slot index.  Page reuse
stays safe by the device's program order: a page freed on the host is only
rewritten by a program dispatched later; a prefill chunk or a hand-off's
scatter simply goes out behind the step in flight.  Where a step must be
read before the next may go it is — ``kv_guard`` (the sweep must see the
page), a lost readback (both unread steps are dropped, the cache is put back
and they are planned again from the journal), a fatal fault, ``stop()`` —
and that is the same code with nothing left in flight.  An iteration that
finds nothing in flight plans and sends TWO steps before it reads the first
(the pipeline is full from its first iteration), one that finds no slot to
decode reads what is in flight and sends nothing: every iteration with a
decode step opens one ``serving.decode.step`` span, and there is one a step.

Admission reuses the serving contracts: bounded queue with typed
``ServingQueueFull`` backpressure, per-request deadlines shed with
``ServingTimeout`` (in queue AND mid-decode), ``ServingClosed`` after
stop.  Everything reports as ``serving.decode.*`` telemetry.

**Durability** (ISSUE 17): every request carries a host-side
:class:`DecodeJournal` — prompt, sampling knobs, and the accepted
tokens so far, O(tokens) memory and no KV — which makes a sequence's
full decode state portable: a failed replica's in-flight sequences are
EVICTED (:meth:`DecodeScheduler.evict_inflight`, pages freed, futures
untouched) and re-admitted elsewhere by re-prefilling
``prompt + accepted`` and decoding the remainder.  Because every token
at absolute position ``i`` is sampled with the same
``fold_in(PRNGKey(seed), i)`` key whether it came from prefill or
decode, the resumed output is BITWISE identical to the uninterrupted
run (gated by tools/check_decode_resilience.py).  Transient
decode-step faults retry in place (``decode_retries`` — the pools are
functional, so a failed attempt left them intact), the opt-in
``kv_guard`` sweeps freshly written pages for non-finite values and
fails exactly the owning sequence typed (``KVCorruption``) with the
pages scrubbed, and ``GenerateRequest.cancel()`` retires a sequence at
the next iteration boundary instead of decoding to max_len for nobody.
"""
from __future__ import annotations

import collections
import functools
import threading
import time

import numpy as np

from .. import observability as _obs
from .. import resilience as _resilience
from ..core import cpu_backend
from ..executor import JitStepCache
from .errors import (
    KVCorruption,
    ServingCancelled,
    ServingClosed,
    ServingDegraded,
    ServingError,
    ServingTimeout,
)
from .kv_cache import PagedKVCache
from .request_queue import Request, RequestQueue
from .step_programs import (
    BLOCK_COUNTERS,
    StepPrograms,
    block_state,
    block_state_length,
    chunk_length,
    int32_bits,
    split_chunk,
    split_step,
    step_columns,
)
from .worker import RestartableWorker

__all__ = ["DecodeModel", "DecodeConfig", "DecodeJournal",
           "GenerateRequest", "DecodeScheduler", "HandoffPacket"]

_requests = _obs.counter("serving.decode.requests")
_tokens = _obs.counter("serving.decode.tokens")
_prefills = _obs.counter("serving.decode.prefills")
_steps = _obs.counter("serving.decode.steps")
# steps dispatched while the one before was still unread (over ``steps``: the
# share of steps the one-step pipeline engaged), and tokens computed for a
# slot that EOS, a cancel or a deadline had already ended (never served)
_steps_overlapped = _obs.counter("serving.decode.steps_overlapped")
_chunks_overlapped = _obs.counter("serving.decode.chunks_overlapped")
_tokens_discarded = _obs.counter("serving.decode.tokens_discarded")
# a block model (``DecodeModel.block``): tokens a commit delivered to a slot
# (0 .. B: what a forward unmasked IN ORDER behind what the request had; a
# forward that wrote K/V delivers none), a cell a model's block length
_tokens_delivered = _obs.histogram("serving.decode.tokens_delivered")
# host arrays handed to the device a dispatch: ONE, the packed buffer
# (``step_programs.py``), counted where it is made so that the next argument
# someone adds is seen
_host_uploads = {p: _obs.counter("serving.decode.host_uploads", {"program": p})
                 for p in ("decode", "chunk")}
_retired = _obs.counter("serving.decode.retired")
_state_resets = _obs.counter("serving.cache.state_resets")
_window_released = _obs.counter("serving.cache.window.pages_released")
# pages a decode step's slots hold against the pages its tables span: the
# share of the whole-table walk that the slot-bounded one still takes
_walked_pages = _obs.counter("serving.decode.paged.walked_pages")
_table_pages = _obs.counter("serving.decode.paged.table_pages")
_expired = _obs.counter("serving.decode.expired")
_expired_mid_decode = _obs.counter("serving.decode.expired_mid_decode")
_queue_full = _obs.counter("serving.decode.queue_full")
_queue_depth = _obs.gauge("serving.decode.queue_depth")
_active_slots = _obs.gauge("serving.decode.active_slots")
# tail-latency histograms (log-bucketed, SLO-grade quantiles): decode
# queue wait and time-to-first-token (admission -> first sampled token,
# the interactive-latency number).  The worker's phases (iteration,
# admit, chunk, prefill, step, ...) are spans: each observes into the
# cell of its own name, see docs/observability.md "Phases"
_queue_wait_hist = _obs.histogram("serving.decode.queue_wait")
_ttft_hist = _obs.histogram("serving.decode.ttft")
# two observations per iteration, both from the worker's frame
# (``observability.Frame``: what the spans that closed on the thread add up
# to): its duration less the ``*.wait`` spans inside it — host time during
# which this scheduler has nothing queued on the device — and its duration
# less its direct children: what lies between spans
_iteration_host = _obs.histogram("serving.decode.iteration.host")
_iteration_unspanned = _obs.histogram("serving.decode.iteration.unspanned")
# a scheduler's construction less its three named parts (the cache's
# allocation, the weights' placement, warm-up): what it does under no name.
# Kept here because the cells' difference cannot say it: ``serving.model_load``
# is also the name of the model store's load and of a caller's own loading
_build_unspanned = _obs.histogram("serving.decode.build.unspanned")
# commit to commit, by whether a prefill chunk rode the interval; and of the
# intervals judged a stall (``DecodeScheduler._note_commit``) the excess
# over the kind's baseline, with ``serving.decode.stall_seconds{where=...}``
# putting the same seconds down to a phase
_interval = tuple(_obs.histogram("serving.decode.interval", {"chunk": c})
                  for c in (0, 1))
_stall = _obs.histogram("serving.decode.stall")
# a stall is an interval above max(floor, factor x its kind's baseline); the
# baseline is a mean of the first 1 / weight quiet intervals and
# exponential from there, and nothing is judged before ``MIN_SAMPLES``
STALL_FLOOR_S = 0.05
STALL_FACTOR = 3.0
STALL_WEIGHT = 1.0 / 64
STALL_MIN_SAMPLES = 32
STALL_RING = 64
STALL_CPU_EVERY_S = 0.02
# cells that per-layer metrics read exist from the import on, so that a
# reader tells "nothing ran" (0) from "this program has no such span"
for _cell in ("prefill.chunk", "step.build", "step.dispatch",
              "step.commit"):
    _obs.histogram("serving.decode." + _cell)
_prefill_retries = _obs.counter("serving.decode.prefill_retries")
_prefill_tokens = _obs.counter("serving.decode.prefill_tokens")
_expired_mid_prefill = _obs.counter("serving.decode.expired_mid_prefill")
_step_retries = _obs.counter("serving.decode.step_retries")
_cancelled = _obs.counter("serving.decode.cancelled")
_replays = _obs.counter("serving.decode.replays")
_kv_guard_trips = _obs.counter("serving.decode.kv_guard_trips")
# sessions / disaggregated prefill (PR 20): affinity honored counts
# admissions whose pool-stamped preferred replica was this one; the
# handoff family counts prefill->decode KV transfers in roles mode
_affinity_honored = _obs.counter("serving.affinity.honored")
_handoff_packets = _obs.counter("serving.handoff.packets")
_handoff_pages = _obs.counter("serving.handoff.pages")
_handoff_bytes = _obs.counter("serving.handoff.bytes")
_handoff_injected = _obs.counter("serving.handoff.injected")
_handoff_failed = _obs.counter("serving.handoff.failed")
_session_parked_pages = _obs.counter("serving.session.pinned")


class DecodeModel:
    """The pure-jax callables a decode-capable model exposes, its weights,
    and what it keeps in the cache.

    ``params`` is the model's weights as ONE pytree of arrays.  Every
    callable takes it first and the scheduler passes it to every jitted
    step as an ARGUMENT (never donated): a step executable holds no
    weights, so it is small enough for the compile cache and a model of
    any size is on the device once.  Keep the pytree to few arrays (a
    dispatch on the chip pays about 13 us an argument): stack by kind
    what is small, keep a matrix per layer where slicing a stack would
    copy a layer-sized matrix (docs/serving.md, "The cache contract").

    ``prefill_chunk_fn(params, tokens[C], start, valid, cache,
    chunk_pages[C // page_size], gather_pages[MP], slot) ->
    (last_logits[V], cache')`` — one resumable prefill CHUNK of the
    sequence seated in ``slot``: scatter the window's k/v (and further
    page-indexed rows) into ``chunk_pages``, attend over the sequence's
    ``gather_pages`` causally by absolute position (``start + row``);
    ``last_logits`` sits at row ``valid - 1``.  Slot-indexed state is read
    at ``slot`` and written back there; a chunk with ``start == 0`` opens a
    sequence and must take the state as ZERO whatever the slot held (that
    is the reset of a reused slot: it costs no dispatch of its own).  The
    scheduler prefills EVERY prompt through this step (monolithic = one
    bucket-wide chunk), which is what makes chunked, monolithic, and
    prefix-cache-resumed prefill bitwise interchangeable.

    ``decode_fn(params, tokens[S], positions[S], cache, page_tables[S,MP],
    kv_lens[S]) -> (logits[S,V], cache')`` — one token per slot: write its
    k/v at ``positions`` into the paged pools, attend over each slot's
    first ``kv_lens`` cached tokens.  ``kv_lens[s] == 0`` marks a slot that
    does not decode (masked, scratch writes, slot state left as it is).
    A model with ``step_counters`` returns a third value, one int32 per
    name: the scheduler reads them with the tokens (one array a step) and
    adds each to the counter ``serving.decode.<name>``.  Its
    ``prefill_chunk_fn`` may return the same third value (seen when the chunk
    program is first traced, ``StepPrograms.chunk_counts``): it is read with
    the chunk's token,
    and the counters are then told apart by the label the loop's intervals
    have: ``serving.decode.<name>{chunk="0"}`` the decode steps',
    ``{chunk="1"}`` the chunk programs'.

    ``block``: None, or ``dict(length=B, mask_id=, steps=, threshold=)`` for a
    model that generates by DIFFUSION OVER BLOCKS: a step carries each slot's
    current block of ``B`` positions, some of them the mask id.  ``decode_fn``
    then receives ``tokens [S, B]``, ``positions [S]`` the blocks' STARTS
    (multiples of ``B``) and ``kv_lens = start + B`` (0: the slot does not
    decode): it writes the block's ``B`` rows at ``start ..`` and attends over
    the slot's first ``kv_lens`` rows with NO stagger inside the block
    (``paged_gqa_decode_attention(..., block=B)``), and returns ``logits [S,
    B, V]``, row ``i`` predicting the id AT position ``start + i``
    (unshifted).  The step program (``step_programs.py``) draws a candidate
    and its confidence a masked position and unmasks by the rule the model
    states (``steps`` denoising forwards a block at most, every candidate
    above ``threshold`` at once, every id a candidate); a forward that finds
    its block whole, or out of its ``steps`` denoising forwards, is the one
    whose K/V rows stay: the slot's ``kv_len`` moves by ``B`` there and
    nowhere else.  A step delivers 0 to ``B`` tokens a slot.
    ``prefill_chunk_fn`` runs under the same mask (position ``i`` sees ``j``
    iff ``j // B <= i // B``, so a prompt's K/V is not causal): only the
    prompt's whole blocks are prefilled, its last ``len % B`` ids are seated
    in the first decoded block as known positions, and nobody reads the
    chunk's logits.  ``page_size`` is a multiple of ``B``, so chunks and
    pages hold whole blocks and a prefix hit stays exact.

    ``cache`` is the cache's pytree, a dict of arrays
    (``kv_cache.PagedKVCache.pools``): ``"k"`` and ``"v"`` in the stored
    shape ``[num_layers, num_pages, page_size, num_heads * head_dim]``
    (heads folded head-major into the last axis, so a token's k is one
    row and a page a ``[page_size, H*D]`` tile), each of ``page_pools``
    ``{name: dict(layers=, tokens_per_row=, width=, dtype=)}`` as
    ``[layers, num_pages, page_size // tokens_per_row, width]``, and each
    of ``slot_state`` ``{name: dict(layers=, shape=, dtype=)}`` as
    ``[layers, num_slots, *shape]``.  ``num_layers`` / ``num_heads`` /
    ``head_dim`` describe the layers that hold paged K/V (their count,
    KV heads and head width), not the model's depth and not its layers of
    weights (a looped model keeps one K/V layer a (loop step, layer):
    ``models/ouro.py`` states 4 x 12 = 48 for 12); left out (0) the
    cache has NO ``"k"`` / ``"v"`` leaf and every page-indexed leaf is one
    of ``page_pools`` (an MLA model's one latent row a token).  A model scatters
    rows into a leaf and attends through
    ``paged_*_attention(..., layer=li)`` (``li`` a Python int, or a traced
    scalar inside a program's loop); it must not slice a layer out
    (``cache["k"][li]`` is a layer-sized copy in every step on the chip).

    ``page_groups``: None, or an ordered ``{group: dict(window=None | W,
    aligned=False, page_size=None)}`` for a model whose layers do not all keep
    the same positions (``kv_cache.py``, "Page groups"); each ``page_pools``
    leaf then names its ``group``.  The first group keeps every position; a
    group with a ``window`` keeps a sequence's last ``W`` (a query at position
    ``t`` reads ``t - W + 1 .. t``) or, ``aligned``, the positions from the
    last multiple of ``W`` on (``(t // W) * W .. t``), and its pages return to
    the allocator as they fall out of it: one at a time, or a whole window at
    once.  A group may state a ``page_size`` of its own in tokens (the
    config's otherwise): ``chunk_pages[group]`` then holds ``max(1, C //
    page_size)`` pages from the one that holds ``start`` on.  Such a model's
    step functions receive ``page_tables``, ``chunk_pages`` and
    ``gather_pages`` as ``{group: array}``; a window group's table is a RING
    (logical page ``p`` of a sequence in column ``p % width``, released
    entries at scratch; an aligned window's ``j``-th page is column ``j``).
    ``DecodeConfig.num_pages`` is then ``{group: pages}``.  Without it a model
    has one group and receives the arrays themselves, as every model did.

    Both are jitted once a model object (``step_programs``, the cache
    donated on TPU) and every scheduler over it dispatches those callables;
    they must be shape-stable in everything but values.
    ``models.transformer.build_decode_model``,
    ``models.minicpm_sala.build_decode_model``,
    ``models.deepseek_v3.build_decode_model``,
    ``models.mellum.build_decode_model``,
    ``models.solar_open2.build_decode_model``,
    ``models.afmoe.build_decode_model``,
    ``models.evabyte.build_decode_model``,
    ``models.ouro.build_decode_model`` and
    ``models.sdar.build_decode_model`` are the in-repo producers.
    """

    def __init__(self, decode_fn, prefill_chunk_fn, *, params=None,
                 num_layers=0, num_heads=0, head_dim=0, vocab_size,
                 eos_id=None, name="decode-model", page_pools=None,
                 slot_state=None, step_counters=(), page_groups=None,
                 block=None):
        self.decode_fn = decode_fn
        self.prefill_chunk_fn = prefill_chunk_fn
        self.params = params
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.vocab_size = int(vocab_size)
        self.eos_id = eos_id
        self.name = name
        self.page_pools = dict(page_pools or {})
        self.slot_state = dict(slot_state or {})
        self.page_groups = dict(page_groups or {})
        if block is not None:
            block = dict(length=int(block["length"]),
                         mask_id=int(block["mask_id"]),
                         steps=int(block["steps"]),
                         threshold=float(block["threshold"]))
            if (block["length"] < 1 or block["steps"] < 1
                    or not 0 <= block["mask_id"] < self.vocab_size):
                raise ValueError(
                    "block: length and steps are >= 1 and mask_id a row of "
                    "the vocabulary; got %r" % (block,))
        self.block = block
        # a block step counts its own three behind the model's
        self.step_counters = tuple(step_counters) + (
            BLOCK_COUNTERS if block else ())
        self._programs = {}
        self._programs_lock = threading.Lock()

    def step_programs(self, top_k, donate):
        """This model's jitted step programs (``step_programs.py``), built
        once a ``(top_k, donate)`` and kept: every scheduler over this model
        object dispatches the same callables, so a shape is traced once."""
        if top_k is not None:
            # static truncation menu; never wider than the vocabulary
            top_k = min(top_k, self.vocab_size)
        with self._programs_lock:
            programs = self._programs.get((top_k, donate))
            if programs is None:
                programs = self._programs[top_k, donate] = StepPrograms(
                    self, top_k, donate)
        return programs


class DecodeConfig:
    """Decode-runtime knobs (all shapes derive from these).

    num_slots: decode-step width — concurrent sequences at full load.
    page_size / max_seq_len: KV paging geometry; ``max_seq_len`` caps
        ``prompt_len + max_new_tokens`` per sequence.
    num_pages: pool size (+1 scratch).  Default reserves full worst-case
        occupancy for every slot — raise/lower to trade HBM for the
        admission-blocking rate.  ``{group: pages}`` for a model with
        ``page_groups`` (a group left out gets its worst case).
    prefill_buckets: page-multiple prompt-length ladder; default doubles
        from ``page_size`` up to ``max_seq_len``.
    max_new_tokens: default per-request generation cap (requests may pass
        their own, bounded by ``max_seq_len``).
    max_active: admission cap on concurrently decoding sequences
        (default ``num_slots``); ``1`` is the naive per-sequence-serving
        baseline the benchmark compares against.
    queue_capacity / default_deadline_ms: the PR-5 admission contract.
    kv_dtype: pool dtype (bf16 on chip halves KV HBM).
    warmup: compile the decode step + every prefill bucket up front.
    default_temperature: sampling temperature for requests that don't
        carry their own; ``0`` (the default) is greedy argmax.
    top_k: restrict sampling to the k highest logits (None = the full
        vocabulary).  STATIC — compiled into the decode step — because
        ``lax.top_k`` needs a compile-time k; per-request knobs are
        ``temperature``/``seed`` on :meth:`DecodeScheduler.submit`.
    prefill_retries: transient prefill-dispatch faults are retried this
        many times before the request fails typed.  The prefill leg is
        REPLAYABLE — its KV-pool inputs are untouched by a failed
        attempt (functional writes) — unlike the in-place decode step;
        forced to 0 when pool donation is active (TPU), where a failed
        dispatch consumes the pools.
    prefill_chunk_tokens: per-iteration prefill token budget.  None
        (default) prefills each prompt as ONE chunk padded to the bucket
        ladder — the monolithic behavior, where a long prompt
        head-of-line-blocks the decode step for its whole prefill.  Set
        to a page-size multiple to split prefill into fixed-budget
        chunks run at most one per iteration, fewest remaining chunks
        first (admission order on ties), interleaved with decode — TTFT
        of short prompts and inter-token latency of active decodes
        become bounded by the chunk size.  One compiled chunk program per width, so the
        zero-recompile contract holds.
    prefix_cache: probe the KV pool's content-hash page index at
        admission and map cached prompt-prefix pages read-only instead
        of recomputing them (refcounted sharing, LRU eviction of
        refcount-zero pages — see kv_cache.py); a hit resumes prefill
        mid-prompt.  Generated tokens are bitwise identical warm vs cold.
    decode_retries: transient DECODE-step dispatch faults retry this
        many times before failing the active sequences typed.  The
        decode step is replayable for the same reason prefill is — the
        pool updates are functional, a failed attempt leaves the
        current buffers intact — so forced to 0 under pool donation
        (TPU), where a failed donated dispatch consumed them.
    replay_budget: times a sequence may be re-admitted after a replica
        death before failing typed (``ServingDegraded``).  Replay
        re-prefills ``prompt + accepted-so-far`` on a sibling and
        continues bitwise-identically (absolute-position PRNG folding);
        the budget bounds the work a crash-looping fleet can re-burn
        per request.
    kv_guard: opt-in KV integrity sweep — after every prefill chunk and
        decode step, a fused isfinite reduction over the pages just
        written.  A non-finite write fails exactly the owning sequence
        with :class:`~.errors.KVCorruption` and scrubs its pages
        (zeroed + dropped from the prefix index) instead of silently
        poisoning shared prefix pages.  Costs one small device
        reduction + a host sync per step; off by default.
    """

    def __init__(self, num_slots=4, page_size=16, max_seq_len=256,
                 num_pages=None, prefill_buckets=None, max_new_tokens=64,
                 max_active=None, queue_capacity=128,
                 default_deadline_ms=None, kv_dtype="float32", warmup=True,
                 default_temperature=0.0, top_k=None, prefill_retries=2,
                 prefill_chunk_tokens=None, prefix_cache=False,
                 decode_retries=2, replay_budget=2, kv_guard=False):
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.max_seq_len = int(max_seq_len)
        self.num_pages = num_pages
        self.prefill_buckets = prefill_buckets
        self.max_new_tokens = int(max_new_tokens)
        self.max_active = (self.num_slots if max_active is None
                           else int(max_active))
        self.queue_capacity = int(queue_capacity)
        self.default_deadline_ms = default_deadline_ms
        self.kv_dtype = kv_dtype
        self.warmup = bool(warmup)
        self.default_temperature = float(default_temperature)
        self.top_k = None if top_k is None else int(top_k)
        self.prefill_retries = int(prefill_retries)
        self.prefill_chunk_tokens = (None if prefill_chunk_tokens is None
                                     else int(prefill_chunk_tokens))
        self.prefix_cache = bool(prefix_cache)
        self.decode_retries = int(decode_retries)
        self.replay_budget = int(replay_budget)
        self.kv_guard = bool(kv_guard)
        if self.decode_retries < 0 or self.replay_budget < 0:
            raise ValueError("decode_retries and replay_budget must be >= 0")
        if self.prefill_chunk_tokens is not None:
            if (self.prefill_chunk_tokens < self.page_size
                    or self.prefill_chunk_tokens % self.page_size):
                raise ValueError(
                    "prefill_chunk_tokens must be a positive multiple of "
                    "page_size %d, got %r"
                    % (self.page_size, prefill_chunk_tokens))
        if self.default_temperature < 0:
            raise ValueError("default_temperature must be >= 0")
        if self.top_k is not None and self.top_k < 1:
            raise ValueError("top_k must be >= 1 (or None for full vocab)")
        if self.num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if self.max_active < 1 or self.max_active > self.num_slots:
            raise ValueError("max_active must be in [1, num_slots]")
        if self.max_seq_len < self.page_size:
            raise ValueError("max_seq_len must be >= page_size")


class DecodeJournal:
    """Host-side durable record of one generation — the replay unit.

    Holds the ORIGINAL prompt and generation cap plus every accepted
    token, O(tokens) host memory and no KV: together with the request's
    pinned sampling knobs (seed/temperature) this is a sequence's
    complete decode state.  On a replica death the pool re-admits the
    request with ``prompt + accepted`` as the resume prompt and
    ``remaining()`` as the new cap; absolute-position PRNG folding then
    reproduces the uninterrupted run bitwise.  ``replays`` counts
    re-admissions against ``DecodeConfig.replay_budget``.
    """

    __slots__ = ("prompt0", "max_new0", "accepted", "replays")

    def __init__(self, prompt, max_new_tokens):
        self.prompt0 = prompt
        self.max_new0 = int(max_new_tokens)
        self.accepted = []           # every token the client will receive
        self.replays = 0

    def remaining(self):
        return self.max_new0 - len(self.accepted)

    def resume_prompt(self):
        """``prompt + accepted`` — what a replay re-prefills.  The chain
        hashes of the shared prefix are identical to the original
        prompt's, so surviving prefix-cache pages answer warm."""
        return np.concatenate(
            [np.asarray(self.prompt0, np.int32),
             np.asarray(self.accepted, np.int32)])

    def tokens(self):
        """The accepted tokens as the client-facing int32 array."""
        return np.asarray(self.accepted, np.int32)


class GenerateRequest(Request):
    """One admitted generation request; doubles as the caller's future.

    ``result(timeout)`` returns the generated token ids as an int32 array
    (includes the EOS token when one stopped the sequence).
    ``token_times`` carries a ``time.perf_counter()`` stamp per
    generated token — the inter-token-latency record the benchmark
    reads.  ``temperature``/``seed`` select the sampling mode:
    temperature ``<= 0`` (or None with a greedy default config) is
    argmax; positive temperature draws from the (optionally
    top-k-truncated) softmax with a PRNG key derived from ``seed``,
    folded with each token's absolute sequence position — the carried
    key makes generation deterministic per ``(seed, prompt)`` and
    independent of batch composition.  ``seed=None`` defaults to the
    request's admission seq (stable within a scheduler run; pass an
    explicit seed for cross-run determinism — the replica pool PINS one
    at admission, because replay re-enqueues the request and a
    seq-derived seed would change mid-generation).

    ``journal`` is the request's :class:`DecodeJournal`; ``prompt`` /
    ``max_new_tokens`` are the CURRENT incarnation's (rewritten by
    replay), the journal keeps the originals and the accepted tokens.
    """

    __slots__ = ("prompt", "max_new_tokens", "token_times", "temperature",
                 "seed", "journal", "cancelled", "session", "affinity",
                 "affinity_ts", "handoff_origin")

    def __init__(self, prompt, max_new_tokens, deadline=None, priority=None,
                 temperature=None, seed=None, session=None):
        super().__init__(feed=None, rows=1, deadline=deadline,
                         priority=priority)
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.token_times = []
        self.temperature = temperature
        self.seed = seed
        self.journal = DecodeJournal(prompt, max_new_tokens)
        self.cancelled = False
        # conversational session key (opaque; router-scoped): on a
        # SUCCESSFUL retirement the owning scheduler parks the finished
        # history's KV pages pinned and records them in the pool's
        # SessionStore — see serving/sessions.py
        self.session = session
        # pool-stamped dispatch hint: preferred replica index + stamp
        # time.  A HINT with a staleness bound, never a requirement —
        # gates strip it when the target can't take the work
        self.affinity = None
        self.affinity_ts = None
        # roles mode: the prefill replica that staged this request's KV
        # handoff (None outside roles mode) — the session's sticky
        # replica, since that is where the prompt's prefix pages live
        self.handoff_origin = None

    @property
    def prompt_len(self):
        return int(self.prompt.shape[0])

    def cancel(self):
        """Ask the runtime to drop this request: an active sequence is
        retired (pages freed) at the next iteration boundary, a queued
        or parked one is dropped at its next admission touch — either
        way the future fails with ``ServingCancelled`` and the
        ``serving.decode.cancelled`` counter ticks.  Safe from any
        thread; returns False when the request already finished."""
        if self.done():
            return False
        self.cancelled = True
        return True


class _Slot:
    """Worker-private state of one active sequence.

    A chunk-prefilled sequence enters in the PREFILLING state:
    ``prefill_pos`` tracks prompt tokens already cached (starting past
    any prefix-cache hit) and advances one chunk per scheduled
    iteration; the first sampled token (produced by the final chunk)
    flips it to decoding.  A slot made without ``prefill_pos`` (a
    handed-off sequence) is already past prefill.

    Under a block model (``block``: ``DecodeModel.block``) the slot prefills
    the prompt's whole blocks (``prefill_end``) and then holds its CURRENT
    block as the last committed forward left it: ``block`` (``B`` ids, the
    mask id where a position is still masked; the first one opens with the
    prompt's leftover ids), ``forwards`` (denoising forwards it has had),
    ``kv_len`` its start (the rows of the blocks before it are in the cache)
    and ``end`` the sequence's last block's end.
    """

    __slots__ = ("req", "pages", "prompt_len", "kv_len", "generated",
                 "prefill_pos", "hashes", "more", "inflight", "prefill_end",
                 "block", "forwards", "end", "mask_id", "block_since")

    def __init__(self, req, pages, prefill_pos=None, hashes=None, block=None):
        self.req = req
        self.pages = pages
        self.prompt_len = self.prefill_end = req.prompt_len
        self.block = self.block_since = None
        if block is not None:
            B = block["length"]
            self.prefill_end = req.prompt_len // B * B
            self.end = -(-(req.prompt_len + req.max_new_tokens) // B) * B
            self.mask_id, self.forwards = block["mask_id"], 0
            self.block = np.full((B,), self.mask_id, np.int32)
            left = req.prompt_len - self.prefill_end
            self.block[:left] = req.prompt[self.prefill_end:]
        # tokens written to the paged cache so far
        self.kv_len = (req.prompt_len if prefill_pos is None
                       else int(prefill_pos))
        self.generated = []            # sampled tokens (last one not yet fed)
        self.prefill_pos = (req.prompt_len if prefill_pos is None
                            else int(prefill_pos))
        self.hashes = hashes           # prompt chain hashes (prefix cache)
        self.more = {}                 # {further page group: _HeldPages}
        # decode steps dispatched for this slot and not yet committed: the
        # DISPATCHED length is ``kv_len + inflight``
        self.inflight = 0

    @property
    def prefilling(self):
        """True until the final chunk has produced the first token (under a
        block model: until the prompt's whole blocks are in the cache)."""
        return self.prefill_pos < self.prefill_end or (
            self.block is None and not self.generated)

    def next_block(self):
        """The block's rows are in the cache: the next one opens, all
        masked."""
        self.kv_len += len(self.block)
        self.block = np.full_like(self.block, self.mask_id)
        self.forwards = 0


class _Step:
    """One decode step from its plan to its commit.  ``entries`` are the
    ``(index, _Slot)`` pairs that decode in it — the OBJECTS, because the
    index may be reseated before the commit; ``args`` the program's
    packed buffer (gone once dispatched); ``out`` the
    dispatched step's output, still on the device (tokens, then the model's
    step counters); ``pools_before`` the cache pytree it took — what a retry
    rolls back to when the readback is lost, None under donation (consumed)
    and once another program has written the cache behind it."""

    __slots__ = ("entries", "args", "out", "pools_before", "sampled",
                 "read_at", "sent_at")

    def __init__(self, entries, args):
        self.entries = entries
        self.args = args
        self.out = self.sent_at = None
        self.pools_before = None
        self.sampled = None            # ``out`` read back, until committed
        self.read_at = None            # when it was: the device's next start


class _Chunk:
    """One prefill chunk from its dispatch to its commit, inside ONE
    iteration: the ``_Slot`` it prefills at ``idx`` (the object: the index
    may be vacated by a failed decode step in between), the window
    ``[start, start + valid)`` of the prompt in a program ``width`` wide,
    the ``pages`` it writes; ``out`` its output on the device (the sampled
    token, then the model's chunk counters); ``pools_before`` the cache
    pytree it took, None under donation; ``span`` the iteration's
    ``prefill`` span, held between its two parts; ``wall`` / ``t0`` when it
    was built (wall clock, ``perf_counter``)."""

    __slots__ = ("idx", "slot", "start", "valid", "width", "pages", "out",
                 "pools_before", "span", "wall", "t0")

    def __init__(self, idx, slot, start, valid, width, pages, span):
        self.idx, self.slot = idx, slot
        self.start, self.valid, self.width = start, valid, width
        self.pages, self.span = pages, span
        self.out = self.pools_before = None
        self.wall, self.t0 = time.time(), time.perf_counter()


class _HeldPages:
    """A slot's pages in one further page group: ``pages`` hold its logical
    pages ``first ..`` in order (the ones before fell out of the group's
    window and went back), under a reservation of ``reserved`` pages."""

    __slots__ = ("first", "pages", "reserved")

    def __init__(self, reserved):
        self.first = 0
        self.pages = collections.deque()
        self.reserved = int(reserved)


class HandoffPacket:
    """Host-staged KV of one fully prefilled sequence in transit
    between a prefill-role replica and a decode-role one (roles mode).

    ``pages_host`` holds, for each page-indexed leaf of the cache, a numpy
    ``[L, max_pages_per_seq, ...]`` gather of the origin cache (rows past
    ``n_pages`` hold scratch content and scatter back into scratch);
    ``first`` is the first
    sampled token (already journaled on the origin); ``hashes`` the
    prompt chain hashes so the destination can re-register the prefix.
    """

    __slots__ = ("req", "pages_host", "n_pages", "kv_len",
                 "hashes", "origin", "first")

    def __init__(self, req, pages_host, n_pages, kv_len, hashes,
                 origin, first):
        self.req = req
        self.pages_host = pages_host
        self.n_pages = int(n_pages)
        self.kv_len = int(kv_len)
        self.hashes = hashes
        self.origin = int(origin)
        self.first = int(first)


class DecodeScheduler:
    """Continuous-batching generation over a :class:`DecodeModel`.

    One worker thread owns the loop (admit -> decode step -> retire);
    clients only touch the bounded queue and their request futures —
    the same single-dispatcher discipline as the predict batcher.  The
    loop keeps ONE decode step in flight: it dispatches step n+1, then
    reads and commits step n (the module docstring says what that moves
    and what it cannot).

    Pool mode (ReplicaPool): ``queue=`` injects the SHARED admission
    queue (the scheduler then never closes or drains it — the pool
    owns its lifecycle), ``gate=`` a claim predicate consulted before
    every shared-queue pull (least-loaded dispatch / breaker / replica
    quiesce), ``name=`` a distinct worker-thread name so the
    supervisor and the chaos injectors can address one replica's
    decoder, and ``evict_on_death=True`` switches the worker-death
    path from fail-the-sequences to LEAVE them harvestable: the pool's
    restart wrapper calls :meth:`evict_inflight` while the worker is
    provably dead and re-admits the journals to sibling replicas.
    ``breaker=`` (a :class:`~.resilient.CircuitBreaker`) records decode
    dispatch outcomes; the pool's gate consults it for admission.
    ``device=`` commits this scheduler's weights and cache to one device
    (a pool gives each replica its own).
    """

    def __init__(self, model, config=None, autostart=True, queue=None,
                 gate=None, name=None, evict_on_death=False, breaker=None,
                 sessions=None, replica_index=0, role="both",
                 on_handoff=None, claim=None, device=None):
        self.model = model
        cfg = self.config = config or DecodeConfig()
        # set-up accounts for its own time: construction is one span (its
        # self time is what it does under no name), and what compiles
        # under it is put down to it or to the part that caused it
        _obs.watch_compiles()
        with _obs.setup_span("serving.decode.build",
                             model=model.name) as build:
            if role not in ("both", "prefill", "decode"):
                raise ServingError(
                    "role must be 'both', 'prefill', or 'decode', got %r"
                    % (role,))
            if model.slot_state and (cfg.prefix_cache or sessions is not None
                                     or role != "both"):
                raise ServingError(
                    "%r keeps slot-indexed state (%s): prefix_cache, sessions "
                    "and prefill/decode roles map or move PAGES, and a page "
                    "says nothing of the state at its boundary. A state "
                    "snapshot per checkpointed boundary is missing; serve it "
                    "with prefix_cache=False, no sessions and role='both'"
                    % (model.name, ", ".join(sorted(model.slot_state))))
            if len(model.page_groups) > 1 and (
                    cfg.prefix_cache or sessions is not None or role != "both"
                    or cfg.kv_guard):
                raise ServingError(
                    "%r keeps its pages in groups (%s): prefix_cache, sessions, "
                    "prefill/decode roles and kv_guard map, move or sweep the "
                    "FIRST group's pages, and a window group has freed the "
                    "pages at a hit's boundary. Pinning a window's pages with a "
                    "prefix is missing; serve it with prefix_cache=False, no "
                    "sessions, role='both' and kv_guard=False"
                    % (model.name, ", ".join(model.page_groups)))
            if sessions is not None and not cfg.prefix_cache:
                raise ServingError(
                    "sessions require prefix_cache=True: a session pin is an "
                    "extra refcount on the prompt's prefix-index chain")
            blk = self._block = model.block
            if blk and (model.slot_state or len(model.page_groups) > 1
                        or role != "both" or cfg.page_size % blk["length"]):
                raise ServingError(
                    "%r decodes by blocks of %d positions: pages (and so "
                    "chunks and prefix hits) hold whole blocks, so page_size "
                    "(%d) is a multiple of it; a block's state is not part of "
                    "a hand-off between roles and has no rule for slot state "
                    "or a windowed page group: serve it with role='both'"
                    % (model.name, blk["length"], cfg.page_size))
            B = blk["length"] if blk else 0
            # conversational sessions (serving/sessions.py): the store is
            # SHARED across a pool's replicas; each scheduler only parks
            # into and releases pins against its OWN cache
            self._sessions = sessions
            self._replica_index = int(replica_index)
            self._role = role
            self._on_handoff = on_handoff
            # cross-thread pin-release + handoff-injection queues: the cache
            # allocator is worker-owned, so other threads (session TTL
            # sweeps, a sibling's handoff dispatch) only ever ENQUEUE here;
            # the worker drains at each loop iteration — or the enqueuer
            # applies directly under the life lock once the worker is
            # provably dead (stop/give-up cleanup must still land)
            self._pending_lock = threading.Lock()
            self._pending_release = []
            self._pending_handoffs = collections.deque()
            def worst(page_size):
                return cfg.num_slots * -(-cfg.max_seq_len // page_size) + 1

            if model.page_groups:
                sizes = cfg.num_pages if isinstance(cfg.num_pages, dict) else {}
                unknown = set(sizes) - set(model.page_groups)
                if unknown or (cfg.num_pages and not sizes):
                    raise ServingError(
                        "%r keeps its pages in groups %s: num_pages is {group: "
                        "pages} over them, got %r"
                        % (model.name, list(model.page_groups), cfg.num_pages))
                # a group that states no pool holds every slot's longest
                # sequence, counted in its OWN page size
                groups = {g: dict(spec, num_pages=sizes[g] if g in sizes
                                  else worst(spec.get("page_size")
                                             or cfg.page_size))
                          for g, spec in model.page_groups.items()}
                num_pages = None
            else:
                groups, num_pages = None, cfg.num_pages or worst(cfg.page_size)
            # the page pools and state leaves, zeros on the device
            with _obs.span("serving.cache.allocate") as part:
                self._cache = PagedKVCache(
                    model.num_layers, num_pages,
                    cfg.page_size, model.num_heads, model.head_dim,
                    cfg.max_seq_len, dtype=cfg.kv_dtype,
                    page_pools=model.page_pools,
                    slot_state=model.slot_state,
                    num_slots=cfg.num_slots, device=device,
                    page_groups=groups)
            named_s = part.duration
            self._admit_waits = {
                g: _obs.counter("serving.decode.admit_waits_for_pages",
                                {"group": g})
                for g in self._cache.group_names}
            # the counters' cells by program
            self._count_cells = {}
            # decode steps dispatched and not yet read, oldest first: one
            # between iterations, two for a moment inside one (step n+1 goes
            # out, then step n is read); and what the decode program takes in
            # its ``previous`` place when nothing is in flight (no slot's
            # ``from_previous`` is set then: every slot feeds the host's token)
            self._unread = collections.deque()
            self._planned = []             # planned and not yet sent (None: replan)
            self._no_previous = np.zeros(
                ((block_state_length(cfg.num_slots, B) if B
                  else cfg.num_slots) + len(model.step_counters),), np.int32)
            # this scheduler's copy of the weights, on the device once: every
            # step takes it as an argument.  ``device`` (a pool's replica)
            # COMMITS weights and cache there, which is what keeps the worker
            # thread's dispatches on that device
            import jax

            with _obs.setup_span("serving.model_load",
                                 model=model.name) as part:
                if device is None:
                    self._params = jax.tree_util.tree_map(jax.numpy.asarray,
                                                          model.params)
                else:
                    self._params = jax.device_put(model.params, device)
                jax.block_until_ready(self._params)
            named_s += part.duration
            if cfg.prefill_buckets:
                buckets = sorted(set(int(b) for b in cfg.prefill_buckets))
                bad = [b for b in buckets
                       if b % cfg.page_size or b < 1 or b > cfg.max_seq_len]
                if bad:
                    raise ServingError(
                        "prefill_buckets must be page_size multiples within "
                        "max_seq_len; bad: %s" % bad)
            else:
                buckets, b = [], cfg.page_size
                while b < cfg.max_seq_len:
                    buckets.append(b)
                    b *= 2
                buckets.append(-(-cfg.max_seq_len // cfg.page_size)
                               * cfg.page_size)
                buckets = sorted(set(buckets))
            self.prefill_buckets = tuple(buckets)
            self._owns_queue = queue is None
            self._queue = queue if queue is not None else RequestQueue(
                cfg.queue_capacity, depth_gauge=_queue_depth,
                full_counter=_queue_full,
                shed_counter=_obs.counter("serving.decode.shed_admission"),
                gauge_prefix="serving.decode.queue_depth")
            self._gate = gate
            # claim predicate: evaluated by the shared queue UNDER ITS LOCK
            # against the head actually popped — closes the peek-then-pop
            # window where two replicas approve different heads and pop
            # crosswise, stealing each other's affinity-tagged requests
            self._claim = claim
            self._breaker = breaker
            self._evict_on_death = bool(evict_on_death)
            # reset_pools safety: the cache refuses to zero pages under
            # these sequences unless the caller says force=True
            self._cache.live_seqs = lambda: [
                s.req.seq for s in self._slots if s is not None]
            self._telemetry = _obs.get_telemetry()
            # pool donation saves an HBM copy per step on chip; CPU jax has no
            # donation and would warn every dispatch
            donate = not cpu_backend()
            self._donated = donate
            # the prefill leg is replayable (its pool inputs survive a failed
            # attempt — KV writes are functional), so transient dispatch
            # faults retry instead of fail-typing the request.  NOT with
            # donation: a failed donated dispatch already consumed the pools,
            # so there is nothing valid to replay against.
            self._prefill_policy = _resilience.RetryPolicy(
                max_retries=0 if self._donated else cfg.prefill_retries,
                base_delay=0.02, max_delay=0.25,
                classify=_resilience.is_transient_error)
            # the decode step is replayable for the same reason (functional
            # pool updates: a failed attempt never touched the current
            # buffers) — and NOT replayable under donation, identically
            self._decode_policy = _resilience.RetryPolicy(
                max_retries=0 if self._donated else cfg.decode_retries,
                base_delay=0.02, max_delay=0.25,
                classify=_resilience.is_transient_error)
            self._programs = model.step_programs(cfg.top_k, donate)
            self._jit = JitStepCache(
                lambda key: self._build_step(key, donate),
                cap=2 * len(self.prefill_buckets) + 12, name="decode-steps")
            self._slots = [None] * cfg.num_slots
            # a table a page group: the first's holds the whole sequence's
            # pages, a further group's too or, with a window, a RING as wide as
            # the most a slot holds live at once (logical page p in column
            # p % width; released entries at scratch).  They are column VIEWS
            # of one standing decode-step buffer (``step_programs.py``) whose
            # other columns stay zero: a step's plan is one copy of it
            widest = max(self._chunk_widths())
            self._check_group_geometry()
            widths = (self._cache.max_pages_per_seq,) + tuple(
                grp.table_width(cfg.max_seq_len, widest)
                for grp in self._cache.groups.values())
            # what the programs are told of the layout (static): a table's
            # width where there are several (one is as wide as the row
            # leaves), and a chunk width's ``(written, gathered)`` lengths
            self._widths = widths if len(widths) > 1 else None
            self._chunk_sizes = {
                w: tuple((max(1, w // self._cache.group_page_size(g)), width)
                         for g, width in zip(self._cache.group_names, widths))
                for w in self._chunk_widths()}
            self._standing = np.zeros(
                (cfg.num_slots, step_columns(widths, B)), np.int32)
            self._group_tables = self._split_step(self._standing)[0]
            self._tables, *more = self._group_tables
            self._more_tables = dict(zip(self._cache.groups, more))
            self._widest_chunk = widest
            self._hol = None               # head-of-line request awaiting pages
            # the loop's own account (``_note_commit``): the worker's frame and
            # the collector's watcher while the loop runs; where the last commit
            # ended (None: nothing to measure an interval from); what rode the
            # interval under way; a baseline and its samples per kind of
            # interval (without / with a prefill chunk); the stalls
            self._frame = None
            self._gc = None
            self._last_commit = None
            self._cpu_at = (0.0, 0.0)      # newest reading of the CPU clock
            self._committed = False
            self._chunk_rode = False
            self._retried = False
            self._baseline = [0.0, 0.0]
            self._samples = [0, 0]
            self._stall_run = [[0, 0.0], [0, 0.0]]   # stalls in a row: n, seconds
            self._stalls = collections.deque(maxlen=STALL_RING)
            self._stall_count = 0
            self._stall_seconds = 0.0
            # serializes _hol handoff between the worker (_admit/_fail_all)
            # and a stop() that timed out joining a wedged-but-alive worker
            # — an unsynchronized claim could fail AND decode one request
            self._hol_lock = threading.Lock()
            self._drain = True
            self._completed = 0
            self._retired_total = 0        # SERVED slot retirements only: the
            # service-rate EMA must not count queue-expiry sheds, mid-decode
            # sheds, or fault mass-retires as served work, or overload and
            # failure inflate the rate and disable shed-at-admission exactly
            # when it matters
            # thread lifecycle (single-use Thread re-arming, life lock
            # against start/restart/fail_pending races, BaseException death
            # choke) lives in the shared RestartableWorker — see worker.py
            self._worker = RestartableWorker(
                self._serve_loop, name or "paddle-tpu-decode-scheduler",
                label=name or "decoder")
            if cfg.warmup:
                t0 = time.perf_counter()
                self.warmup()
                named_s += time.perf_counter() - t0
            if autostart:
                self.start()
        _build_unspanned.observe(build.duration - named_s)

    # -- compiled steps ------------------------------------------------------
    def _build_step(self, key, donate):
        """The callable behind a key of ``_jit``.  ``decode`` and ``chunk``
        are the MODEL's (``DecodeModel.step_programs``: one jitted callable
        a kind for every scheduler over the model, so an evicted key asked
        for again gets the same callable back and nothing recompiles); the
        rest are programs over this scheduler's cache."""
        import jax

        cache = self._cache
        if key[0] == "kvguard":
            # fused isfinite sweep over the pages a step just wrote;
            # one compiled program per page-vector length (key[1])
            return jax.jit(cache.pages_finite)
        if key[0] == "hgather":
            # roles mode, prefill side: pull one sequence's pages to the
            # host for handoff.  Fixed shape [L, max_pages_per_seq, ...]
            # whatever the prompt length — pad index entries point at
            # scratch page 0, whose gathered rows are simply ignored
            return jax.jit(cache.gather_pages)
        if key[0] == "hscatter":
            # roles mode, decode side: land a handoff packet's staged
            # pages into this cache.  Pad target entries aim at scratch
            # page 0 (duplicate scatter indices all write scratch —
            # whichever lands, scratch content is don't-care).  The cache
            # donated on TPU like every other in-place update.
            return jax.jit(cache.scatter_pages,
                           donate_argnums=(0,) if donate else ())
        if key[0] == "decode":
            return self._programs.decode
        if key[0] == "chunk":
            return self._programs.chunk
        raise KeyError(key)

    def _step_counters(self, chunk):
        """The cells of the model's step counters for a decode step
        (``chunk`` 0) or a chunk program (1).  Where the chunk program
        counts too, the label ``chunk`` tells the two apart; a model whose
        decode step alone counts keeps the unlabelled cells.  A slot decodes
        only behind its own chunk, so the chunk program has been traced (by
        this scheduler or by another over the same model) before the first
        counts are read."""
        cells = self._count_cells.get(chunk)
        if cells is None:
            labels = ({"chunk": chunk} if self._programs.chunk_counts
                      else None)
            cells = self._count_cells[chunk] = [
                _obs.counter("serving.decode." + name, labels)
                for name in self.model.step_counters]
        return cells

    def _chunk_widths(self):
        """The prefill-chunk widths this config can dispatch.
        Monolithic (no chunk budget): the bucket ladder — a prompt uses
        its bucket, a prefix-cache resume the smallest bucket covering
        the uncached tail.  Chunked: the budget width plus every SMALLER
        ladder bucket — a remaining prefill under the budget dispatches
        at its own bucket instead of padding to the full budget (a
        10-token prompt must not pay a 256-wide chunk), so the menu
        stays a small fixed warmed set either way."""
        if self.config.prefill_chunk_tokens is None:
            return self.prefill_buckets
        ct = self.config.prefill_chunk_tokens
        return tuple(sorted({b for b in self.prefill_buckets if b < ct}
                            | {ct}))

    def _split_step(self, buf):
        """``split_step`` of a buffer of this scheduler's decode step."""
        return split_step(buf, self._widths,
                          self._block["length"] if self._block else 0)

    def _step_buffer(self, standing):
        """The buffer of one decode dispatch, the ONE host array it hands the
        device (it goes into the jitted call as numpy: no transfer of its
        own): a copy of the standing one, which holds the tables as they
        are, or zeros of its shape."""
        _host_uploads["decode"].inc()
        return (self._standing.copy() if standing
                else np.zeros_like(self._standing))

    def _chunk_buffer(self, width):
        """``(buf, sizes, split_chunk(buf, sizes))``: the buffer of one
        dispatch of the chunk program ``width`` wide (zeros), the ONE host
        array it hands the device, its static layout and its parts."""
        _host_uploads["chunk"].inc()
        sizes = self._chunk_sizes[width]
        buf = np.zeros((chunk_length(width, sizes),), np.int32)
        return buf, sizes, split_chunk(buf, sizes)

    def _pack_step(self, tokens, positions, tables, kv_lens, seeds, temps,
                   from_previous=None, forwards=None):
        """A decode step's buffer from the step's values a vector; ``tables``
        as the model receives them.  The loop (``_plan_step``) writes the
        same views in place."""
        buf = self._step_buffer(standing=False)
        views, columns = self._split_step(buf)
        for view, table in zip(views, self._group_list(tables)):
            view[:] = np.asarray(table)
        for column, vector, dtype in zip(
                columns,
                (tokens, positions, kv_lens, seeds, temps, from_previous,
                 forwards),
                (np.int32, np.int32, np.int32, np.uint32, np.float32,
                 np.int32, np.int32)):
            if vector is not None:
                column[:] = int32_bits(vector, dtype)
        return buf

    def _pack_chunk(self, width, tokens, start, valid, written, gathered,
                    slot, seed, temp):
        """``(buf, sizes)`` of a chunk from its values; ``written`` and
        ``gathered`` as the model receives them."""
        buf, sizes, (row, scalars, vecs) = self._chunk_buffer(width)
        row[:] = np.asarray(tokens)
        scalars[:] = (int(start), int(valid), int(slot),
                      int32_bits(seed, np.uint32), int32_bits(temp, np.float32))
        for (w, g), pages, table in zip(vecs, self._group_list(written),
                                        self._group_list(gathered)):
            w[:], g[:] = np.asarray(pages), np.asarray(table)
        return buf, sizes

    def _group_list(self, arg):
        """A per-group argument as the model receives it (the array itself,
        or ``{group: array}``) as a list in the cache's group order."""
        if not self.model.page_groups:
            return [arg]
        return [arg[g] for g in self._cache.group_names]

    def decode_program_text(self):
        """The compiled decode program as text: every device instruction's
        name beside the ``op_name`` its metadata carries (the model's
        ``jax.named_scope`` path), which is how a reader of a device trace,
        where an instruction has its name alone, tells the stages of a step
        apart.  Lowers and compiles the warmed program's shapes once more
        (the persistent cache answers where there is one)."""
        return self._jit.get(("decode",)).lower(
            self._params, self._cache.pools, np.zeros_like(self._standing),
            self._no_previous, widths=self._widths).compile().as_text()

    def warmup(self):
        """Compile the decode step and every prefill width against the
        scratch page, so no live sequence ever pays a compile."""
        import jax
        import jax.numpy as jnp

        cfg = self.config
        cache, params = self._cache, self._params
        with _obs.setup_span("serving.decode.warmup", slots=cfg.num_slots):
            step = self._jit.get(("decode",))
            # twice: ``previous`` is a host array when nothing is in flight
            # and the step before's own output when one is, and jax keys an
            # executable on where an argument lives as well as on its shape
            toks = self._no_previous
            for _ in range(2):
                toks, cache.pools = step(
                    params, cache.pools, self._step_buffer(standing=False),
                    toks, widths=self._widths)
            np.asarray(toks)
            for w in self._chunk_widths():
                # every page at scratch, one valid token
                buf, sizes, (_, scalars, _) = self._chunk_buffer(w)
                scalars[1] = 1
                toks, cache.pools = self._jit.get(("chunk", w))(
                    params, cache.pools, buf, sizes=sizes)
                np.asarray(toks)
            if cfg.kv_guard:
                # one guard program per page-vector length the runtime
                # dispatches: the decode tail sweep ([num_slots]) and
                # each prefill width's written-page sweep
                for n in sorted({cfg.num_slots}
                                | {max(1, w // cache.page_size)
                                   for w in self._chunk_widths()}):
                    np.asarray(self._jit.get(("kvguard", n))(
                        cache.pools, jnp.zeros((n,), jnp.int32)))
            # roles mode: compile the handoff leg this replica
            # dispatches (all-scratch indices — real pages see the same
            # program), so the first conversation never pays a compile
            mp = cache.max_pages_per_seq
            if self._role == "prefill" and self._on_handoff is not None:
                jax.block_until_ready(self._jit.get(("hgather",))(
                    cache.pools, jnp.zeros((mp,), jnp.int32)))
            if self._role == "decode":
                idx = jnp.zeros((mp,), jnp.int32)
                zero = jax.tree_util.tree_map(
                    jnp.zeros_like, jax.eval_shape(
                        cache.gather_pages, cache.pools, idx))
                cache.pools = self._jit.get(("hscatter",))(
                    cache.pools, zero, idx)
                jax.block_until_ready(cache.pools)
        return self

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        # a collection stops every thread: the loop counts them as its own
        self._gc = _obs.watch_gc()
        self._worker.start()
        return self

    def restart(self):
        """Re-arm a DEAD worker with a fresh thread (the supervisor's
        recovery path); queue, slots, and KV state carry over — a kill
        lands between state updates, so resuming the loop continues
        every live sequence.  No-op (False) while stopping or alive."""
        return self._worker.restart()

    @property
    def started(self):
        return self._worker.started

    @property
    def alive(self):
        return self._worker.alive

    @property
    def stopping(self):
        return self._worker.stopping

    @property
    def cache(self):
        """This scheduler's :class:`PagedKVCache`.  The worker owns it
        while alive; anyone else reads it only after :meth:`stop`."""
        return self._cache

    # -- page groups ---------------------------------------------------------
    def _check_group_geometry(self):
        """A chunk lies on whole pages of every group or inside one page of
        it, and never straddles a multiple of an aligned window: chunks
        start at multiples of the widest, so every width and every group's
        page size divide one another, and the widest divides the window."""
        widths = self._chunk_widths()
        for g in self._cache.group_names:
            ps = self._cache.group_page_size(g)
            grp = self._cache.groups.get(g)
            aligned = grp is not None and grp.aligned
            bad = [w for w in widths if w % ps and ps % w]
            if aligned and grp.window % max(widths):
                bad.append(max(widths))
            if bad:
                raise ServingError(
                    "page group %r (page_size %d%s): chunk widths %s neither "
                    "fill whole pages nor fit inside one%s; choose "
                    "prefill_chunk_tokens / prefill_buckets that do"
                    % (g, ps, ", aligned window %d" % grp.window
                       if aligned else "", sorted(set(bad)),
                       ", or straddle a multiple of the window"
                       if aligned else ""))

    def _chunk_page_vec(self, vec, group, start, column):
        """Fill ``vec`` with the pages of ``group`` that a chunk from
        ``start`` on writes: ``width // page_size`` of them in order (one
        where the page is wider than the chunk), ``column(p)`` the page that
        holds logical page ``p`` (0: none, the rows scatter to scratch)."""
        ps = self._cache.group_page_size(group)
        for i in range(len(vec)):
            vec[i] = column(start // ps + i)

    def _group_needs(self, req):
        """Pages ``req`` reserves in each further group."""
        return {g: grp.slot_bound(req.prompt_len + req.max_new_tokens,
                                  self._widest_chunk)
                for g, grp in self._cache.groups.items()}

    def _ensure_pages(self, idx, slot, end):
        """Hand the slot the further groups' pages that positions below
        ``end`` reach (under its reservation: this cannot fail)."""
        for g, held in slot.more.items():
            table = self._more_tables[g]
            ps = self._cache.groups[g].page_size
            for p in range(held.first + len(held.pages), -(-end // ps)):
                page = self._cache.groups[g].alloc(1)[0]
                held.pages.append(page)
                table[idx, p % table.shape[1]] = page

    def _release_window(self, idx, slot):
        """Give back each window group's pages on which every position is
        out of the window of the slot's NEXT position (and of every later
        one): the table names them no more, and the next ``alloc`` may hand
        them to another slot."""
        released = 0
        for g, held in slot.more.items():
            grp = self._cache.groups[g]
            live = grp.first_live_page(slot.kv_len)
            if live <= held.first or not held.pages:
                continue
            with self._telemetry.span("serving.decode.window.release"):
                table = self._more_tables[g]
                dead = [held.pages.popleft() for _ in range(
                    min(live - held.first, len(held.pages)))]
                for p, page in enumerate(dead, held.first):
                    # an aligned window's first column may already name the
                    # next window's first page (a step in flight past the
                    # boundary took it)
                    if table[idx, p % table.shape[1]] == page:
                        table[idx, p % table.shape[1]] = 0
                held.first += len(dead)
                grp.free(dead, released=True)
                released += len(dead)
        if released:
            _window_released.inc(released)

    def _free_slot_pages(self, idx, slot):
        """Every page and reservation of a slot that leaves, in every
        group."""
        self._tables[idx] = 0
        self._cache.free(slot.pages)
        for g, held in slot.more.items():
            self._more_tables[g][idx] = 0
            self._cache.groups[g].free(held.pages)
            self._cache.groups[g].unreserve(held.reserved)
        slot.more = {}

    def run_step(self, key, *args):
        """One dispatch of this scheduler's OWN compiled step program
        ``key`` (``("decode",)`` or ``("chunk", width)``, as warmed up and
        served) on its own weights and cache, the cache updated in place as
        the loop does.  ``args`` are the step's values one by one, host or
        device arrays, as the model's own function names them: a decode
        step's ``(tokens, positions, page_tables, kv_lens, seeds, temps)``
        (a block model's: ``(ids [S, B], starts, page_tables, ends, seeds,
        temps, forwards)``, ``step_programs.py``),
        then ``previous, from_previous`` or neither (every slot feeds
        ``tokens``: no step in flight before it); a chunk's ``(tokens, start,
        valid, chunk_pages, gather_pages, slot, seed, temp)``.  They are
        packed into the one buffer the program takes, as the loop packs its
        own.  Returns the program's first output (the tokens).  For a check
        or a tool that must read what the SERVED executables leave in the
        SERVED cache; refused while the worker, which owns the cache, is
        alive."""
        if self.alive:
            raise ServingError(
                "run_step: the worker thread owns the cache; stop() first")
        key = tuple(key)
        if key == ("decode",):
            n = 7 if self._block else 6
            previous, from_previous = args[n:] or (self._no_previous, None)
            out, self._cache.pools = self._jit.get(key)(
                self._params, self._cache.pools,
                self._pack_step(*args[:6], from_previous, *args[6:n]),
                previous, widths=self._widths)
        else:
            packed, sizes = self._pack_chunk(key[1], *args)
            out, self._cache.pools = self._jit.get(key)(
                self._params, self._cache.pools, packed, sizes=sizes)
        return out

    def fail_pending(self, exc):
        """Fail every queued and active request with ``exc`` — the
        supervisor's give-up path for a worker that is dead past its
        restart budget.  ``_fail_all`` mutates worker-owned slot/KV
        state, so this ENFORCES the dead-worker precondition instead of
        trusting the caller: a supervisor give-up tick racing an
        operator ``engine.start()`` revive must not free pages under a
        live worker (returns False; the next tick sees the live thread
        and skips).  The worker's life lock serializes the aliveness
        check with any concurrent restart/start spawn."""
        with self._worker.life_lock:
            if self._worker.alive:
                return False
            self._fail_all(exc)
        return True

    def stop(self, drain=True, timeout=None):
        """Stop generating.  ``drain=True`` finishes every admitted and
        queued sequence first; ``drain=False`` fails them with
        ``ServingClosed`` after the in-flight iteration.  A worker that
        is still wedged when the join times out gets its QUEUED requests
        failed fast (the queue is lock-safe to drain; active slots stay
        worker-owned — if the worker ever resumes it sees ``stopping``
        and fails them itself)."""
        self._drain = bool(drain)
        self._worker.request_stop()
        if self._owns_queue:
            self._queue.close()
        stopped = self._worker.join(timeout)
        if stopped:
            # leftovers exist only when the worker never ran (or was
            # asked not to drain): fail them rather than hang futures.
            # Under the life lock: a supervisor give-up tick's
            # fail_pending must not race this into double-retiring a
            # slot (double cache.free would alias KV pages)
            with self._worker.life_lock:
                self._fail_all(ServingClosed("decode scheduler stopped"))
        elif timeout is not None:
            # the head-of-line request parked awaiting KV pages is in
            # neither the queue nor a slot — a wedged worker will never
            # admit it, so fail it here or its future hangs forever
            # (the hol lock makes the claim exclusive: a resuming
            # drain=True worker would otherwise decode the request this
            # thread just failed)
            hol = self._take_hol()
            if hol is not None:
                # fail the future only: the wedged-but-alive worker still
                # owns the cache, so the pinned prefix refs are leaked
                # deliberately rather than freed from this thread (the
                # scheduler is terminally wedged either way)
                hol[0].fail(ServingClosed(
                    "engine stopped before request ran (decode worker "
                    "wedged)"))
            if self._owns_queue:
                self._queue.drain_remaining(lambda r: ServingClosed(
                    "engine stopped before request ran (decode worker "
                    "wedged)"))
        return stopped

    # -- client API ----------------------------------------------------------
    def submit(self, prompt, max_new_tokens=None, deadline_ms=None,
               priority=None, temperature=None, seed=None, session=None):
        """Admit one prompt; returns its :class:`GenerateRequest` future.
        Raises ``ServingClosed`` when stopped, ``ServingQueueFull`` under
        backpressure, ``ServingError`` for malformed prompts.
        ``priority`` is a :data:`~.request_queue.PRIORITY_CLASSES` lane
        (admission order; decode slots themselves are shared).
        ``temperature`` (default: the config's, normally 0 = greedy) and
        ``seed`` select per-request sampling — see
        :class:`GenerateRequest`."""
        cfg = self.config
        tokens = np.asarray(prompt)
        if tokens.ndim != 1 or tokens.shape[0] < 1:
            raise ServingError(
                "prompt must be a non-empty 1-D token array, got shape %s"
                % (tokens.shape,))
        tokens = tokens.astype(np.int32, copy=False)
        n_new = int(cfg.max_new_tokens if max_new_tokens is None
                    else max_new_tokens)
        if n_new < 1:
            raise ServingError("max_new_tokens must be >= 1")
        plen = int(tokens.shape[0])
        if plen > self.prefill_buckets[-1]:
            raise ServingError(
                "prompt length %d exceeds the largest prefill bucket %d"
                % (plen, self.prefill_buckets[-1]))
        if plen + n_new > cfg.max_seq_len:
            raise ServingError(
                "prompt %d + max_new_tokens %d exceeds max_seq_len %d"
                % (plen, n_new, cfg.max_seq_len))
        if temperature is not None and float(temperature) < 0:
            raise ServingError("temperature must be >= 0, got %r"
                               % (temperature,))
        ms = deadline_ms if deadline_ms is not None else cfg.default_deadline_ms
        deadline = None if ms is None else time.perf_counter() + ms / 1e3
        req = self._queue.put(
            GenerateRequest(tokens, n_new, deadline=deadline,
                            priority=priority, temperature=temperature,
                            seed=seed, session=session))
        _requests.inc()
        return req

    def generate(self, prompt, max_new_tokens=None, deadline_ms=None,
                 timeout=None, temperature=None, seed=None, session=None):
        """Synchronous generate: the generated int32 token ids."""
        return self.submit(prompt, max_new_tokens=max_new_tokens,
                           deadline_ms=deadline_ms, temperature=temperature,
                           seed=seed, session=session).result(timeout=timeout)

    def stats(self):
        active = sum(1 for s in self._slots if s is not None)
        st = {
            "num_slots": self.config.num_slots,
            "max_active": self.config.max_active,
            "active": active,
            "prefilling": sum(1 for s in self._slots
                              if s is not None and s.prefilling),
            "queue_depth": self._queue.depth(),
            "admitted": self._queue.last_seq(),
            "completed": self._completed,
            "kv_pages_free": self._cache.free_pages,
            "kv_pages_used": self._cache.used_pages,
            "kv_occupancy": self._cache.occupancy(),
            "prefill_buckets": list(self.prefill_buckets),
            "prefill_chunk_tokens": self.config.prefill_chunk_tokens,
            "prefix_cache": self.config.prefix_cache,
            "role": self._role,
            # commit-to-commit intervals judged a stall (docs/
            # observability.md, "Debugging a stalled replica")
            "stalls": {"count": self._stall_count,
                       "seconds": self._stall_seconds,
                       "last": self._stalls[-1] if self._stalls else None},
        }
        if self.config.prefix_cache:
            st["prefix"] = self._cache.prefix_stats()
        if self._cache.groups:
            # the ``kv_*`` keys above are the first group's; each further
            # group under its own name
            st["kv_groups"] = {
                g: {"pages_free": grp.free_pages, "pages_used": grp.used_pages,
                    "pages_reserved": grp.reserved,
                    "occupancy": grp.occupancy()}
                for g, grp in self._cache.groups.items()}
        return st

    def stalls(self):
        """The journal: the last ``STALL_RING`` stalls, oldest first."""
        return list(self._stalls)

    def cache_stats(self):
        """The cache allocator snapshot incl. the leaked-refcount sweep
        (``PagedKVCache.stats()``) — the gate's no-leak assertion reads
        this after session expiry."""
        return self._cache.stats()

    # -- worker --------------------------------------------------------------
    def _sampling_params(self, req):
        """(temperature float32, seed uint32) for one request: request
        overrides, else the config default; a seedless sampling request
        gets its admission seq (stable within this scheduler run)."""
        temp = (req.temperature if req.temperature is not None
                else self.config.default_temperature)
        seed = req.seed if req.seed is not None else (req.seq or 0)
        return np.float32(temp), np.uint32(int(seed) & 0xFFFFFFFF)

    def _active_count(self):
        return sum(1 for s in self._slots if s is not None)

    def free_slots(self):
        """Seats this scheduler could fill right now — the pool's
        least-loaded-dispatch signal.  Read cross-thread (a snapshot
        under the GIL; staleness only skews one claim decision)."""
        return self.config.max_active - self._active_count()

    def _recover_pools(self, exc):
        """After a failed dispatch with donation enabled (TPU), the pool
        buffers passed in were already consumed — every sequence's cached
        KV is gone.  Retire all actives with the error and reallocate
        zeroed pools so the scheduler keeps serving new requests instead
        of wedging on deleted arrays."""
        if not self._donated:
            return
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._retire(i, error=exc)
        # force: every owner was just retired above — the live-sequence
        # guard would otherwise refuse the recovery zeroing itself
        self._cache.reset_pools(force=True)

    def _take_hol(self):
        """Exclusively claim the parked head-of-line entry — a
        ``(request, pinned prefix pages, chain hashes)`` triple — or
        None: the worker, a wedged-timeout stop(), and _fail_all all
        hand off through here so exactly one owner ever fails/serves
        it."""
        with self._hol_lock:
            entry, self._hol = self._hol, None
            return entry

    def _park_hol(self, req, cached_pages, hashes):
        """Park the head-of-line request WITH its prefix-probe result:
        the hit pages stay rc-PINNED while parked, so the request isn't
        re-probed (and the hit/miss counters not re-counted) every
        iteration the pool stays exhausted, and its prefix can't be
        evicted out from under the admission it is queued for."""
        with self._hol_lock:
            self._hol = (req, cached_pages, hashes)

    # -- sessions & handoff (cross-thread entry points) ----------------------
    def release_session_pins(self, pages):
        """Release session-pinned pages back to this scheduler's cache.
        Safe from ANY thread (it is the SessionStore's release callback,
        fired by TTL sweeps, capacity evictions, and end_session on
        arbitrary callers): the pages are queued and freed ON the worker
        at its next loop iteration.  When the worker is provably dead
        (stop/give-up/cold-demotion cleanup), the queue is drained
        directly under the life lock instead — a dead worker never
        races, and the lock blocks a concurrent restart spawn."""
        with self._pending_lock:
            self._pending_release.extend(int(p) for p in pages)
        self.drain_pending_releases()

    def drain_pending_releases(self):
        """Apply queued pin releases if the worker is provably dead;
        no-op otherwise (the live worker drains its own queue).  The
        pool calls this after stopping a replica so ``SessionStore.
        clear()``'s releases land even with every worker gone."""
        with self._worker.life_lock:
            if self._worker.alive:
                return False
            self._drain_pending()
        return True

    def _drain_pending(self):
        """Free queued session-pin releases (worker thread, or any
        thread holding the dead-worker proof)."""
        with self._pending_lock:
            pages, self._pending_release = self._pending_release, []
        if pages:
            self._cache.free(pages)

    def inject_handoff(self, packet):
        """Queue a prefilled sequence's staged KV for seating on this
        (decode-role) replica — called by the pool's handoff dispatch
        from the ORIGIN replica's worker thread.  Returns False when
        this scheduler is stopping (the caller re-routes or fails the
        request)."""
        if self._worker.stopping:
            return False
        with self._pending_lock:
            self._pending_handoffs.append(packet)
        return True

    def _fail_all(self, exc):
        self._drain_pending()
        with self._pending_lock:
            packets = list(self._pending_handoffs)
            self._pending_handoffs.clear()
        for pk in packets:
            pk.req.fail(exc)
        hol = self._take_hol()
        if hol is not None:
            req, cached_pages, _ = hol
            if cached_pages:
                # safe here: _fail_all runs on the worker thread or with
                # the worker provably dead (fail_pending/stop enforce it)
                self._cache.release_prefix(cached_pages)
            req.fail(exc)
        if self._owns_queue:
            # a SHARED (pool) queue holds sibling replicas' work too;
            # its drain is the pool's call, never one replica's
            self._queue.drain_remaining(lambda r: exc)
        for i, slot in enumerate(self._slots):
            if slot is not None:
                self._retire(i, error=exc)
        # whatever was in flight was computed for slots that are gone
        self._unread.clear()

    def _serve_loop(self):
        # (BaseException escaping this loop is the death path: the
        # RestartableWorker choke counts it, emits the worker_death
        # record/trace event, and the supervisor restarts the thread —
        # slots and KV carry over — or fails pending requests fast.)
        # While the loop runs every span that closes on this thread also
        # lands in its frame: the loop's account of its own time
        self._frame = _obs.open_frame()
        self._last_commit = None
        try:
            # said once, not every turn: a compile request on this thread
            # is a shape that escaped the warmed menu
            with _obs.compiles_within("serving.decode.iteration"):
                self._serve_turns(self._frame)
        finally:
            self._frame = None
            _obs.close_frame()

    def _serve_turns(self, frame):
        # anchors for the queue's service-rate EMA (deadline-aware
        # admission): retirements per second of BUSY wall time
        self._note_ts = time.perf_counter()
        self._note_retired = self._retired_total
        tel = self._telemetry
        while True:
            if (self._active_count() or self._unread
                    or self._has_admissible()):
                # one turn: admit, then one iteration over the active
                # slots (or over the step still in flight for slots that
                # have all left: it is read and dropped).  Its phases are
                # children of this span on the worker's line of a profiler
                # trace; a turn that could seat nothing is no iteration and
                # closes into no cell
                wait0, children0 = frame.wait_s, frame.children_s
                self._committed = False
                with tel.span("serving.decode.iteration") as turn:
                    with tel.span("serving.decode.admit") as admit:
                        # queued session-pin releases first: freed pages
                        # may be exactly what this admission needs
                        self._drain_pending()
                        self._admit()
                        iterated = (self._active_count() > 0
                                    or bool(self._unread))
                        if not iterated:
                            turn.name = admit.name = None
                    if iterated:
                        if self._worker.stopping and not self._drain:
                            # non-drain stop: fail the actives after the
                            # in-flight iteration instead of decoding
                            # every sequence to completion (unbounded
                            # shutdown); the step in flight is read and
                            # committed first, so the cache and the
                            # journals agree
                            self._settle()
                            self._fail_all(
                                ServingClosed("decode scheduler stopped"))
                            return
                        self._iterate()
                        self._note_throughput()
                if iterated:
                    _iteration_host.observe(
                        turn.duration - (frame.wait_s - wait0))
                    _iteration_unspanned.observe(
                        turn.duration - (frame.children_s - children0))
                    # out here, behind the turn: nothing of the loop's
                    # account lies between two spans of an iteration
                    if self._committed:
                        self._note_commit(frame)
                    else:
                        self._lose_anchor()
                    continue
            else:
                self._await_request()
                if self._has_admissible():
                    continue
            # idle: re-anchor so idle gaps don't dilute the rate
            self._lose_anchor()
            self._note_ts = time.perf_counter()
            self._note_retired = self._retired_total
            if self._worker.stopping and (not self._drain
                                          or (self._queue.depth() == 0
                                              and not self._has_admissible())):
                if not self._drain:
                    self._fail_all(ServingClosed("decode scheduler stopped"))
                return

    def _has_admissible(self):
        """A parked head-of-line request or a staged hand-off packet:
        work ``_admit`` can seat without pulling from the queue."""
        return self._hol is not None or bool(self._pending_handoffs)

    def _await_request(self):
        """Nothing to decode and nothing parked: wait up to 50 ms for a
        request so the loop doesn't spin, and park what arrives head of
        line for the next turn's ``_admit``.  The wait is the cell
        ``serving.decode.idle``: it is no part of ``admit`` or of an
        iteration."""
        self._lose_anchor()
        with self._telemetry.span("serving.decode.idle"):
            self._drain_pending()
            if self._worker.stopping and not self._drain:
                return
            req = self._pull(0.05)
            if req is not None:
                self._park_hol(req, [], None)

    def _pull(self, timeout):
        """Claim the next request off the queue, or None."""
        # the pool's claim gate (least-loaded dispatch, breaker, replica
        # quiesce) applies to SHARED-queue pulls only — a parked HOL
        # request already belongs to this replica (its prefix pages are
        # pinned here)
        if self._gate is not None and not self._gate():
            if timeout:
                time.sleep(0.002)  # don't spin while gated out
            return None
        req = self._queue.get(timeout=timeout, accept=self._claim)
        if (req is not None
                and getattr(req, "affinity", None) == self._replica_index):
            _affinity_honored.inc()
        return req

    def _note_throughput(self):
        """Feed retired-sequences-per-second into the queue's EMA so
        decode admission can shed deadline-doomed requests up front
        (every GenerateRequest is rows=1, so the queue's rows/s IS
        requests/s here).  Only REAL retirements count — a shed of an
        already-expired queued request costs ~0 and must not look like
        served throughput."""
        done = self._retired_total - self._note_retired
        if done <= 0:
            return
        now = time.perf_counter()
        self._queue.note_service(done, now - self._note_ts)
        self._note_ts = now
        self._note_retired = self._retired_total

    def _admit_handoffs(self):
        """Seat injected handoff packets (sequences a prefill-role
        sibling already prefilled) ahead of fresh queue work — their
        KV is staged on the host and their callers are further along.
        Returns False when a packet is blocked on pages (fresh
        admission must also wait: the packet is effectively this
        replica's head of line)."""
        cache = self._cache
        while self._active_count() < self.config.max_active:
            with self._pending_lock:
                packet = (self._pending_handoffs[0]
                          if self._pending_handoffs else None)
            if packet is None:
                return True
            req = packet.req
            if req.cancelled or req.expired():
                with self._pending_lock:
                    self._pending_handoffs.popleft()
                if req.cancelled:
                    _cancelled.inc()
                    req.fail(ServingCancelled(
                        "request cancelled during prefill->decode "
                        "handoff"))
                else:
                    _expired.inc()
                    _expired_mid_decode.inc()
                    req.fail(ServingTimeout(
                        "deadline expired during prefill->decode "
                        "handoff"))
                self._completed += 1
                continue
            need = cache.pages_for(req.prompt_len + req.max_new_tokens)
            if need > cache.num_pages - 1:
                with self._pending_lock:
                    self._pending_handoffs.popleft()
                req.fail(ServingError(
                    "handed-off sequence needs %d pages but the pool "
                    "has %d" % (need, cache.num_pages - 1)))
                self._completed += 1
                continue
            pages = cache.alloc(need)
            if pages is None:
                # wait for a retirement; don't admit fresh work past a
                # staged packet (it holds host copies, not pool pages,
                # so waiting leaks nothing)
                return False
            with self._pending_lock:
                self._pending_handoffs.popleft()
            self._seat_handoff(packet, pages)
        return not self._pending_handoffs

    def _seat_handoff(self, packet, pages):
        """Land one handoff packet: scatter the staged KV into our
        freshly reserved pages and seat the slot already DECODING (the
        origin sampled the first token; it is journaled there)."""
        import jax.numpy as jnp

        req = packet.req
        idx = next(i for i, s in enumerate(self._slots) if s is None)
        idxvec = np.zeros((self._cache.max_pages_per_seq,), np.int32)
        idxvec[:packet.n_pages] = pages[:packet.n_pages]
        fn = self._jit.get(("hscatter",))
        with self._telemetry.span("serving.handoff.stage"):
            self._wrote_cache(fn(
                self._cache.pools,
                {name: jnp.asarray(a)
                 for name, a in packet.pages_host.items()},
                jnp.asarray(idxvec)))
        slot = _Slot(req, pages, hashes=packet.hashes)
        slot.kv_len = packet.kv_len
        slot.generated.append(packet.first)
        self._slots[idx] = slot
        self._tables[idx] = self._cache.table_row(pages)
        if self.config.prefix_cache and packet.hashes:
            # re-register the prompt's full pages HERE: the next turn's
            # prefix probe (and its session pin) must find them in the
            # replica that will actually serve the decode
            for pi in range(min(packet.kv_len // self._cache.page_size,
                                len(packet.hashes), len(pages))):
                self._cache.register_prefix(packet.hashes, pi, pages[pi])
        _handoff_injected.inc()
        _active_slots.set(self._active_count())
        tel = self._telemetry
        if tel.recording:
            tel.emit({
                "type": "decode_handoff", "ts": time.time(),
                "source": "serving", "seq": req.seq, "leg": "inject",
                "origin": packet.origin, "dest": self._replica_index,
                "pages": packet.n_pages, "kv_len": packet.kv_len,
            })
        self._finish_if_done(idx)

    def _admit(self):
        """Fill free slots from the queue (iteration-level admission).
        Never blocks: the idle wait is ``_await_request``'s."""
        cache, cfg = self._cache, self.config
        if not self._admit_handoffs():
            return                 # blocked on pages for a staged packet
        while self._active_count() < cfg.max_active:
            if self._worker.stopping and not self._drain:
                return
            hol = self._take_hol()
            if hol is not None:
                req, cached_pages, hashes = hol
            else:
                req = self._pull(0.0)
                cached_pages, hashes = [], None
            if req is None:
                return
            if req.cancelled:
                if cached_pages:
                    cache.release_prefix(cached_pages)
                _cancelled.inc()
                req.fail(ServingCancelled(
                    "request cancelled before decode started"))
                self._completed += 1
                continue
            if req.expired():
                if cached_pages:
                    cache.release_prefix(cached_pages)
                _expired.inc()
                req.fail(ServingTimeout(
                    "deadline expired after %.3fs in decode queue"
                    % (time.perf_counter() - req.enqueue_ts)))
                self._completed += 1
                continue
            need = cache.pages_for(req.prompt_len + req.max_new_tokens)
            # (a block model's last block may end past that: inside the same
            # page, which holds whole blocks)
            if cfg.prefix_cache and hashes is None:
                # probe ONCE, before the fresh alloc: hits shrink the
                # fresh reservation and stay rc-pinned (a re-parked
                # request carries its probe result instead of
                # re-counting hits every exhausted iteration)
                cached_pages, hashes = cache.lookup_prefix(req.prompt)
            # one admission waits for every group: a further group short of
            # its reservation parks the head as a short first group does
            more = self._group_needs(req)
            short = [g for g, n in more.items()
                     if not cache.groups[g].can_reserve(n)]
            pages = (None if short
                     else cache.alloc(need - len(cached_pages)))
            if pages is None:
                # pinned hit pages are NOT in free_pages — count them
                # toward what this reservation can ever assemble
                if (not self._active_count()
                        and (need > cache.free_pages + len(cached_pages)
                             or short)):
                    # nothing will ever free enough: the reservation is
                    # larger than the whole (idle) pool
                    if cached_pages:
                        cache.release_prefix(cached_pages)
                    req.fail(ServingError(
                        "sequence needs %d pages but the pool has %d "
                        "usable%s; raise num_pages or shrink the request"
                        % (need, cache.free_pages, "".join(
                            " (and %d of group %r's %d)" % (
                                more[g], g, cache.groups[g].num_pages - 1)
                            for g in short))))
                    self._completed += 1
                    continue
                # pool exhausted: hold the head (FIFO) until a retirement
                # frees its reservation; an admission is counted once, against
                # each group that was short when it first waited
                if hol is None:
                    for g in short or [cache.primary_group]:
                        self._admit_waits[g].inc()
                self._park_hol(req, cached_pages, hashes)
                return
            self._place(req, cached_pages + pages,
                        len(cached_pages) * cache.page_size, hashes, more)

    def _place(self, req, pages, cached_tokens, hashes, more=None):
        """Seat one admitted request in a free slot in the PREFILLING
        state: pages are reserved (``cached_tokens`` of
        them already hold a shared prompt prefix), but no model compute
        happens here — chunks run one per iteration in ``_iterate``,
        so a burst of long-prompt admissions can't stall active
        decodes behind back-to-back prefills."""
        idx = next(i for i, s in enumerate(self._slots) if s is None)
        now = time.perf_counter()
        wait = now - req.enqueue_ts
        _queue_wait_hist.observe(wait)
        req.dispatch_ts = now
        tel = self._telemetry
        if tel.span_active() and req.trace is not None:
            tel.record_span(
                "serving.queue_wait", req.enqueue_wall, wait,
                tags=req.trace.child().tags(priority=req.priority,
                                            seq=req.seq))
        slot = _Slot(req, pages, prefill_pos=cached_tokens, hashes=hashes,
                     block=self._block)
        for g, n in (more or {}).items():
            self._cache.groups[g].reserve(n)
            slot.more[g] = _HeldPages(n)
        if self._cache.slot_leaf_names:
            # a reused slot's state is void from here on: the sequence's
            # first chunk (start == 0) takes it as zero inside the chunk
            # program, so the reset is no dispatch and has no time of its
            # own: a counter, not a span
            _state_resets.inc()
        self._slots[idx] = slot
        self._tables[idx] = self._cache.table_row(pages)
        _active_slots.set(self._active_count())

    def _note_prefill_retry(self, req):
        """The on_retry callback of a prefill chunk's dispatch: count,
        record, and trace one retried transient fault."""
        def note_retry(exc, attempt_n, delay):
            _prefill_retries.inc()
            self._retried = True
            tel = self._telemetry
            if tel.recording:
                tel.emit({
                    "type": "serving_retry", "ts": time.time(),
                    "source": "serving", "leg": "decode_prefill",
                    "error": repr(exc)[:200], "attempt": attempt_n,
                    "delay_s": delay, "seq": req.seq,
                })
            if tel.span_active() and req.trace is not None:
                tel.record_span(
                    "serving.retry", time.time(), 0.0,
                    tags=req.trace.child().tags(leg="decode_prefill",
                                                attempt=attempt_n,
                                                error=repr(exc)[:120]))
        return note_retry

    def _chunk_width_for(self, remaining):
        """Dispatch width for a chunk with ``remaining`` prompt tokens
        left: the chunk budget, except a smaller remainder rides its
        own (warmed) bucket — see :meth:`_chunk_widths`."""
        ct = self.config.prefill_chunk_tokens
        if ct is None:
            # a replay's resume prompt can reach max_seq_len, which may
            # sit between the last two ladder rungs — fall back to the
            # largest bucket (>= max_seq_len by construction) and loop
            return next((b for b in self.prefill_buckets if b >= remaining),
                        self.prefill_buckets[-1])
        if remaining >= ct:
            return ct
        b = next((b for b in self.prefill_buckets if b >= remaining), ct)
        return min(ct, b)

    def _chunks_left(self, slot):
        remaining = slot.prefill_end - slot.prefill_pos
        return -(-remaining // self._chunk_width_for(remaining))

    def _send_chunk(self):
        """Dispatch ONE prefill chunk, for the prefilling slot with the
        fewest chunks left (admission order on ties): scatter the next
        page-multiple token window's k/v, attend over everything cached so
        far, and — on the final chunk — sample the first token.  The cache
        is the chunk's from here (what is dispatched next runs behind its
        writes); its token is read and the slot moved on in
        :meth:`_read_chunk`, in the same iteration.  Returns the chunk in
        flight, None where its dispatch failed for good."""
        tel = self._telemetry
        self._chunk_rode = True
        with tel.span("serving.decode.chunk.build"):
            idx = min((i for i, s in enumerate(self._slots)
                       if s is not None and s.prefilling),
                      key=lambda i: (self._chunks_left(self._slots[i]),
                                     self._slots[i].req.seq))
            slot = self._slots[idx]
            req = slot.req
            start = slot.prefill_pos
            remaining = slot.prefill_end - start
            width = self._chunk_width_for(remaining)
            valid = min(remaining, width)
            packed, sizes, (tokens, scalars, vecs) = self._chunk_buffer(
                width)
            tokens[:valid] = req.prompt[start:start + valid]
            # pages this chunk writes, a group: the prompt's pages covering
            # [start, start + width), by the group's own table and page size
            # (the further groups' are handed out now); the tail past the
            # prompt's pages scatters to scratch, like the monolithic pad tail
            cache = self._cache
            self._ensure_pages(idx, slot, start + valid)

            def held(g, p):
                """The slot's page of ``g`` that holds logical page ``p``."""
                if p >= -(-req.prompt_len // cache.group_page_size(g)):
                    return 0
                if g == cache.primary_group:
                    return slot.pages[p]
                table = self._more_tables[g]
                return table[idx, p % table.shape[1]]

            for g, table, (written, gathered) in zip(
                    cache.group_names, self._group_tables, vecs):
                self._chunk_page_vec(written, g, start,
                                     functools.partial(held, g))
                gathered[:] = table[idx]
            temp, seed = self._sampling_params(req)
            scalars[:] = (start, valid, idx, int32_bits(seed, np.uint32),
                          int32_bits(temp, np.float32))
            fn = self._jit.get(("chunk", width))

        def attempt():
            # the chaos choke point is consulted per ATTEMPT (a retry is
            # a fresh dispatch, exactly like the predict path's)
            serve_fault = _resilience._serve_fault
            if serve_fault is not None:
                serve_fault([req])
            with tel.span("serving.decode.prefill.dispatch"):
                return fn(self._params, self._cache.pools, packed,
                          sizes=sizes)

        # what the iteration pays for the chunk: its dispatch (retries
        # included) here and the block on its token in ``_read_chunk``, one
        # span in two parts
        sent = _Chunk(idx, slot, start, valid, width, vecs[0][0], tel.span(
            "serving.decode.prefill", bucket=width, rows=valid, start=start,
            seq=req.seq))
        try:
            with sent.span:
                sent.span.hold()
                sent.out, pools = _resilience.call_with_retry(
                    attempt, policy=self._prefill_policy,
                    on_retry=self._note_prefill_retry(req))
                # the donated pytree always belongs to the newest dispatch
                # (in here: the unread steps let go of the cache they took)
                sent.pools_before = (None if self._donated
                                     else self._cache.pools)
                self._wrote_cache(pools)
        except Exception as exc:  # noqa: BLE001 — worker must survive
            self._fail_chunk(sent, exc)
            return None
        except BaseException:
            self._chunk_died(sent)
            raise
        return sent

    def _read_chunk(self, sent, since=None):
        """Read the token of the chunk in flight (ONE blocking transfer: the
        worker parks in the device's wait) and commit it: the slot moves on
        and, behind its final chunk, holds its first token.  ``since`` is
        when the decode step that was ahead of the chunk was read back: where
        the host waited for that step, the device started the chunk then."""
        tel = self._telemetry
        idx, slot, start, valid = sent.idx, sent.slot, sent.start, sent.valid
        req = slot.req
        behind = bool(self._unread)
        try:
            with sent.span:
                with tel.span("serving.decode.prefill.wait"):
                    read = np.asarray(sent.out).reshape(-1)
                # the chunk program's time where the DEVICE sets the pace
                # (the host had to wait for the step ahead, so the chunk
                # started at ``since``; that step's commit lies inside: an
                # upper bound).  Where the host reads the step ahead late the
                # chunk is under way by then, and this is what was left of it
                t0 = sent.t0 if since is None else since
                tel.observe_span("serving.decode.prefill.chunk",
                                 sent.wall + (t0 - sent.t0), t0)
                first = int(read[0])
        except Exception as exc:  # noqa: BLE001 — worker must survive
            # every decode step still unread went out behind the chunk and
            # consumed its cache: they are forgotten (and planned again from
            # the journals' tokens), and the loop stands on the cache as the
            # chunk found it (gone under donation: ``_recover_pools``)
            self._abandon(*self._unread)
            if sent.pools_before is not None:
                self._cache.pools = sent.pools_before
            self._fail_chunk(sent, exc)
            return
        except BaseException:
            self._chunk_died(sent)
            raise
        if self._slots[idx] is not slot:
            # the decode step sent behind the chunk failed and, where it had
            # consumed the cache, took every sequence with it
            return
        with tel.span("serving.decode.chunk.commit"):
            done = time.perf_counter()
            if tel.span_active() and req.trace is not None:
                tel.record_span(
                    "serving.execute", sent.wall, sent.span.duration,
                    tags=req.trace.child().tags(
                        phase="prefill", bucket=sent.width, rows=valid,
                        start=start))
            sent.out = sent.pools_before = None
            if behind:
                _chunks_overlapped.inc()
            if self._breaker is not None:
                self._breaker.record_success()
            if self.config.kv_guard and self._guard_pages(
                    [idx] * len(sent.pages), sent.pages, phase="prefill"):
                return
            slot.prefill_pos = start + valid
            slot.kv_len = slot.prefill_pos
            if slot.more:
                self._release_window(idx, slot)
            # the model's chunk counters came back behind the token
            for c, n in zip(self._step_counters(1), read[1:]):
                c.inc(int(n))
            _prefills.inc()
            _prefill_tokens.inc(valid)
            ps = self._cache.page_size
            if self.config.prefix_cache and slot.hashes:
                # publish every full REAL page this chunk completed: its
                # content is now immutable (decode appends only past the
                # prompt), so later identical prefixes can map it
                # read-only
                for pi in range(start // ps, (start + valid) // ps):
                    if pi < len(slot.hashes):
                        self._cache.register_prefix(slot.hashes, pi,
                                                    slot.pages[pi])
            if slot.block is None and slot.prefill_pos >= req.prompt_len:
                # final chunk: the sampled token at position
                # prompt_len - 1 is the sequence's first generated token
                # (a block model's chunk samples nothing anyone reads: its
                # first token comes out of the first block's forwards)
                slot.generated.append(first)
                req.journal.accepted.append(first)
                req.token_times.append(time.perf_counter())
                # TTFT: admission -> first sampled token, the number an
                # interactive-decode SLO is written against
                _ttft_hist.observe(done - req.enqueue_ts)
                _tokens.inc()
                if not self._finish_if_done(idx):
                    self._maybe_handoff(idx)

    def _fail_chunk(self, sent, exc):
        """A chunk failed for good, at its dispatch or at its readback: its
        sequence alone is failed typed; under donation the failed dispatch
        consumed the cache, and every sequence goes with it."""
        if self._slots[sent.idx] is sent.slot:
            self._retire(sent.idx, error=exc)
        self._recover_pools(exc)
        if self._breaker is not None:
            self._breaker.record_fatal()

    def _chunk_died(self, sent):
        """The worker is being killed with a chunk not yet committed.  Solo
        mode: fail the sequence and release its reservation before the death
        propagates — ServingDegraded (not ServingError): the engine is sick,
        the request was fine, same error class as the batcher death.  Pool
        mode (evict_on_death): leave the slot INTACT — nothing of the chunk
        was committed, so the slot's state is consistent, and the pool
        harvests it via evict_inflight and replays it on a sibling."""
        if not self._evict_on_death and self._slots[sent.idx] is sent.slot:
            self._retire(sent.idx, error=ServingDegraded(
                "decode worker died mid-prefill; request aborted"))

    def _maybe_handoff(self, idx):
        """Roles mode, prefill side: a freshly prefilled (and not yet
        finished) sequence leaves for a decode-role sibling — gather
        its prompt pages to the host, release the local seat (the full
        prompt pages stay REGISTERED here, rc=0-parked, so the next
        turn's affinity probe still finds this replica warm), and hand
        the packet to the pool.  Returns True when the slot was
        exported (the caller must not keep using ``idx``)."""
        if self._role != "prefill" or self._on_handoff is None:
            return False
        import jax.numpy as jnp

        slot = self._slots[idx]
        req = slot.req
        n_pages = self._cache.pages_for(slot.kv_len)
        idxvec = np.zeros((self._cache.max_pages_per_seq,), np.int32)
        idxvec[:n_pages] = slot.pages[:n_pages]
        fn = self._jit.get(("hgather",))
        with self._telemetry.span("serving.handoff.stage"):
            pages_host = {
                name: np.asarray(a) for name, a in fn(
                    self._cache.pools, jnp.asarray(idxvec)).items()}
        packet = HandoffPacket(
            req, pages_host, n_pages=n_pages, kv_len=slot.kv_len,
            hashes=slot.hashes, origin=self._replica_index,
            first=slot.generated[-1])
        req.handoff_origin = self._replica_index
        self._slots[idx] = None
        self._free_slot_pages(idx, slot)
        _active_slots.set(self._active_count())
        _handoff_packets.inc()
        _handoff_pages.inc(n_pages)
        _handoff_bytes.inc(sum(a.nbytes for a in pages_host.values()))
        tel = self._telemetry
        if tel.recording:
            tel.emit({
                "type": "decode_handoff", "ts": time.time(),
                "source": "serving", "seq": req.seq, "leg": "export",
                "origin": self._replica_index, "pages": n_pages,
                "kv_len": slot.kv_len,
            })
        try:
            ok = self._on_handoff(packet)
        except Exception as exc:  # noqa: BLE001 — worker must survive
            ok = False
            exc_repr = repr(exc)[:200]
        else:
            exc_repr = None
        if not ok and not req.done():
            _handoff_failed.inc()
            req.fail(ServingDegraded(
                "prefill->decode KV handoff failed%s"
                % ("" if exc_repr is None else (": " + exc_repr))))
            self._completed += 1
        return True

    def _guard_pages(self, owners, page_vec, phase):
        """KV integrity sweep over ``page_vec`` (``owners[j]`` = the slot
        that wrote entry j; scratch-page entries are skipped).  A
        non-finite page fails its owning slot typed (``KVCorruption``)
        and scrubs the bad pages — zeroed and dropped from the prefix
        index — so the poison can't outlive the sequence into a future
        page owner or a prefix hit.  Returns the set of tripped slot
        indices (empty = clean)."""
        import jax.numpy as jnp

        fn = self._jit.get(("kvguard", len(page_vec)))
        ok = np.asarray(fn(self._cache.pools,
                           jnp.asarray(page_vec, np.int32)))
        bad = [j for j in range(len(page_vec))
               if page_vec[j] and not ok[j]]
        if not bad:
            return set()
        tripped = {}
        for j in bad:
            tripped.setdefault(owners[j], []).append(int(page_vec[j]))
        for idx, pages in tripped.items():
            slot = self._slots[idx]
            _kv_guard_trips.inc()
            self._retire(idx, error=KVCorruption(
                "non-finite KV write in page(s) %s during %s (seq %s, "
                "%d/%d tokens); sequence failed, pages scrubbed"
                % (pages, phase, slot.req.seq, len(slot.generated),
                   slot.req.max_new_tokens)))
            # after the retire's free the pages are rc=0 (the guard only
            # ever trips on privately written pages): zero them and drop
            # any index entries before the allocator reuses them
            self._cache.scrub_pages(pages)
        return set(tripped)

    def evict_inflight(self):
        """Harvest every in-flight sequence for replay elsewhere: clear
        the slots and the parked HOL entry, free their pages and pinned
        prefix references, and return the (unfailed) requests — futures
        untouched, journals intact.  The pool's supervisor calls this
        between a replica death and the worker restart, while the
        worker is provably dead (the caller holds that proof via the
        supervisor's is-alive check), then re-admits each request to a
        sibling replica.  With donation the pools are also reset — the
        dying dispatch may have consumed them."""
        harvested = []
        # queued pin releases apply now (the worker is provably dead);
        # staged handoff packets are harvestable work — their KV copies
        # die with this replica but their journals replay anywhere
        self._drain_pending()
        with self._pending_lock:
            packets = list(self._pending_handoffs)
            self._pending_handoffs.clear()
        for pk in packets:
            if not pk.req.done():
                harvested.append(pk.req)
        hol = self._take_hol()
        if hol is not None:
            req, cached_pages, _ = hol
            if cached_pages:
                self._cache.release_prefix(cached_pages)
            if not req.done():
                harvested.append(req)
        for i, slot in enumerate(self._slots):
            if slot is None:
                continue
            self._slots[i] = None
            self._free_slot_pages(i, slot)
            if not slot.req.done():
                harvested.append(slot.req)
        # a token still in flight was never journalled: the sibling that
        # replays the journal computes it again
        self._unread.clear()
        if self._donated:
            self._cache.reset_pools(force=True)
        _active_slots.set(0)
        return harvested

    def evict_if_dead(self):
        """:meth:`evict_inflight` under the dead-worker proof — the
        pool's supervisor paths call this so a racing operator
        ``start()`` can never land a revived worker on top of an
        eviction in progress (the worker's life lock serializes the
        aliveness check with any spawn).  Returns None (no-op) while
        the worker is alive."""
        with self._worker.life_lock:
            if self._worker.alive:
                return None
            return self.evict_inflight()

    def idle(self):
        """No active sequence and no parked head-of-line request (the
        pool's decode-drain probe)."""
        return (self._active_count() == 0 and self._hol is None
                and not self._unread)

    def _finish_if_done(self, idx):
        slot = self._slots[idx]
        eos = self.model.eos_id
        if (len(slot.generated) >= slot.req.max_new_tokens
                or (eos is not None and slot.generated
                    and slot.generated[-1] == eos)):
            self._retire(idx)
            return True
        return False

    def _iterate(self):
        tel = self._telemetry
        with tel.span("serving.decode.sweep"):
            # shed actives whose deadline passed before burning a step on
            # them — checked BETWEEN chunks too, so a doomed long prompt
            # frees its budget early instead of prefilling to completion
            now0 = time.perf_counter()
            # cancellation reaps at the iteration boundary: the slot retires
            # and its pages free before the next step dispatches, so an
            # abandoned future stops burning decode capacity immediately
            for i, slot in enumerate(self._slots):
                if slot is not None and slot.req.cancelled:
                    _cancelled.inc()
                    self._retire(i, error=ServingCancelled(
                        "request cancelled after %d/%d generated tokens"
                        % (len(slot.generated), slot.req.max_new_tokens)))
            for i, slot in enumerate(self._slots):
                if slot is not None and slot.req.expired(now0):
                    req = slot.req
                    queued_s = ((req.dispatch_ts or now0) - req.enqueue_ts
                                if req.enqueue_ts is not None else 0.0)
                    running_s = (now0 - req.dispatch_ts
                                 if req.dispatch_ts is not None else 0.0)
                    _expired.inc()
                    if slot.prefilling:
                        _expired_mid_prefill.inc()
                        err = ServingTimeout(
                            "deadline expired mid-prefill after %d/%d prompt "
                            "tokens (%.3fs in queue, %.3fs in prefill)"
                            % (slot.prefill_pos, slot.prompt_len,
                               max(0.0, queued_s), max(0.0, running_s)))
                    else:
                        _expired_mid_decode.inc()
                        err = ServingTimeout(
                            "deadline expired mid-decode after %d/%d generated "
                            "tokens (%.3fs in queue, %.3fs decoding)"
                            % (len(slot.generated), req.max_new_tokens,
                               max(0.0, queued_s), max(0.0, running_s)))
                    self._retire(i, error=err)
        # chunked prefill phase: AT MOST ONE chunk per iteration, so
        # prefill work interleaves with (never starves) the decode step
        # below.  Pick order: FEWEST REMAINING CHUNKS first, admission
        # order (seq) on ties — a short prompt's single chunk runs ahead
        # of a long prompt's many, which is exactly what bounds short
        # TTFT by the chunk size instead of the longest neighbor.  With
        # monolithic prefill every slot has exactly one chunk left, so
        # the tiebreak degrades to pure admission-order FIFO (the PR-6
        # behavior).  A sustained flood of shorter prefills can delay a
        # longer one (bounded by the seat cap: each shorter request
        # holds a slot and runs exactly one winning chunk per iteration);
        # admission stays FIFO-per-priority-lane either way.
        # The chunk's token is read BEHIND the decode step dispatched after
        # it: with step n in flight the order is dispatch the chunk, plan
        # and dispatch step n+1 behind it, read and commit step n, read and
        # commit the chunk, so the device has step n+1 queued while the host
        # commits.  (The slot whose final chunk this is still prefills when
        # n+1 is planned, and joins n+2 with its first token read here.)
        # With nothing in flight ahead (nobody decodes yet; ``kv_guard``,
        # whose sweep must see the chunk's pages before another write
        # lands) the chunk is read before the step is planned: the same two
        # calls in the other order.
        sent = (self._send_chunk() if any(
            s is not None and s.prefilling for s in self._slots) else None)
        if sent is not None and not self._unread:
            self._read_chunk(sent)
            sent = None
        try:
            step = self._decode_step()
        except BaseException:
            if sent is not None:
                self._chunk_died(sent)
            raise
        if sent is not None:
            self._read_chunk(sent, since=step and step.read_at)

    def _wrote_cache(self, pools):
        """Another program than a decode step (a prefill chunk, a hand-off's
        scatter) wrote the cache: a decode step still unread can no longer
        be rolled back past it."""
        self._cache.pools = pools
        for sent in self._unread:
            sent.pools_before = None

    def _plan_step(self):
        """The next decode step's slots and arguments, from LENGTHS alone
        (None when no slot decodes).  A slot with a step in flight stands at
        its dispatched length ``kv_len + inflight`` and takes its token from
        that step's output on the device; a slot whose committed and
        in-flight tokens reach ``max_new_tokens`` is not in the step.  The
        planned step counts as in flight for its slots from here on.

        The plan is ONE buffer (``step_programs.py``): a copy of the
        standing one, which holds every group's table (the copy the step
        needs anyway: the program may run behind the host, which rewrites
        the tables while it is in flight), with the slots' values written
        into its other columns.

        Under a block model a step carries a slot's current BLOCK (``B`` ids
        from ``kv_len`` on) and may deliver 0 to ``B`` tokens, which only its
        logits decide: a slot is in the step until what it has DELIVERED
        reaches ``max_new_tokens`` (so one step more than it needed may go
        out: ``tokens_discarded``), a slot with a step in flight takes its
        block, its start and its forwards from that step's output on the
        device, and the host's columns hold the block as the last COMMITTED
        forward left it (what a replan after a lost readback starts from).
        ``kv_lens`` carries the sequence's end: past it the program takes the
        slot out of the step itself."""
        blk = self._block
        with self._telemetry.span("serving.decode.step.build") as build:
            entries = [(i, s) for i, s in enumerate(self._slots)
                       if s is not None and not s.prefilling
                       and (len(s.generated) + (0 if blk else s.inflight)
                            < s.req.max_new_tokens)]
            if not entries:
                build.name = None      # no step: the span closes into no cell
                return None
            if self._more_tables:
                # the page the new token lands on, in every further group
                for i, slot in entries:
                    self._ensure_pages(i, slot, slot.kv_len + slot.inflight + 1)
            buf = self._step_buffer(standing=True)
            (tokens, positions, kv_lens, seeds, temps, from_previous,
             *forwards) = self._split_step(buf)[1]
            seeds, temps = seeds.view(np.uint32), temps.view(np.float32)
            for i, slot in entries:
                if slot.inflight:
                    from_previous[i] = 1         # its token is on the device
                if blk:
                    kv_lens[i] = slot.end        # the sequence's last row
                    if not slot.inflight:
                        tokens[i] = slot.block       # the block as committed
                        positions[i] = slot.kv_len   # ... its start
                        forwards[0][i] = slot.forwards
                else:
                    at = slot.kv_len + slot.inflight
                    positions[i] = at            # ... at the next cache index
                    kv_lens[i] = at + 1          # visible kv incl. this token
                    if not slot.inflight:
                        tokens[i] = slot.generated[-1]   # the last sampled
                temps[i], seeds[i] = self._sampling_params(slot.req)
                slot.inflight += 1
            # rows a slot's walk reads, in pages (a block model's ``kv_lens``
            # holds the sequence's end: its rows are its block's end)
            rows = (np.asarray([s.kv_len + blk["length"] for _, s in entries])
                    if blk else kv_lens)
            _walked_pages.inc(int(np.sum(-(-rows // self._cache.page_size))))
            _table_pages.inc(self._tables.size)
            # The decode step scatters EVERY slot's token k/v at
            # page_tables[s, 0] offset 0 when positions[s] == 0 — a seated
            # slot that does not decode in this step (prefilling, or at its
            # length with its last token in flight) points at real (possibly
            # SHARED prefix) pages, so its dispatch row must aim at scratch
            # like an empty slot's or the write corrupts position 0 of its
            # (or a prefix neighbor's) cache.  (Its other columns are zero.)
            idle = [i for i, s in enumerate(self._slots)
                    if s is not None and not kv_lens[i]]
            buf[idle] = 0
            return _Step(entries, buf)

    def _plan_steps(self):
        """What this iteration dispatches: the next step, and with nothing
        in flight (and no ``kv_guard``) the one after it as well, so that
        from the first iteration on a step runs while the one before it is
        read."""
        plans = []
        while len(plans) + len(self._unread) < (1 if self.config.kv_guard
                                                else 2):
            plan = self._plan_step()
            if plan is None:
                break
            plans.append(plan)
        return plans

    def _dispatch_step(self, plan):
        """Send one planned step behind whatever is in flight."""
        with self._telemetry.span("serving.decode.step.dispatch"):
            previous = (self._unread[-1].out if self._unread
                        else self._no_previous)
            before = self._cache.pools
            plan.out, pools = self._jit.get(("decode",))(
                self._params, before, plan.args, previous,
                widths=self._widths)
            # the donated pytree always belongs to the newest dispatch
            self._cache.pools = pools
            plan.args = None
            if self._block:
                # a block's span opens with its first forward's dispatch
                plan.sent_at = (time.time(), time.perf_counter())
                for _, slot in plan.entries:
                    if slot.block_since is None:
                        slot.block_since = plan.sent_at
            plan.pools_before = None if self._donated else before
            if self._unread:
                _steps_overlapped.inc()
            self._unread.append(plan)

    def _decode_step(self, dispatch=True):
        """Dispatch the next decode step, THEN read and commit the one in
        flight before it: the host's work for step n+1 runs while the device
        runs step n.  With ``kv_guard`` the step just dispatched is the one
        read (the sweep must see the page before another write lands): the
        same code with nothing left in flight.  When no slot decodes next
        (or ``dispatch`` is False), what is in flight is read with nothing
        behind it.  Returns the step it committed, if any."""
        planned = self._planned = self._plan_steps() if dispatch else []
        if not planned and not self._unread:
            self._cache.publish_gauges(
                sum(s.kv_len for s in self._slots if s is not None))
            return None
        try:
            # the dispatch of step n+1 to the readback of step n (retries
            # included): the per-iteration step time, and the cell
            # ``decode_step_ms`` reads
            with self._telemetry.span(
                    "serving.decode.step",
                    active=len((planned or self._unread)[0].entries)):
                done = _resilience.call_with_retry(
                    self._send_then_read, policy=self._decode_policy,
                    on_retry=self._note_step_retry)
        except Exception as exc:  # noqa: BLE001 — worker must survive
            # fatal (or transient past the retry budget): fail the
            # decoding sequences typed, un-retried — replay can't fix a
            # deterministic fault
            self._fail_decoding(exc)
            return None
        except BaseException:
            # the worker is being killed: what was planned and never sent
            # must not count as in flight when the loop is resumed
            self._abandon(*(self._planned or ()))
            self._planned = []
            raise
        if done is not None:
            self._commit_step(done)
        return done

    def _send_then_read(self):
        """One attempt of the iteration's decode phase: send what is
        planned, then read the oldest unread step.  Returns that step for
        the commit (None with nothing to read)."""
        if self._planned is None:
            # the readback of the attempt before was lost: every unread
            # step was dropped, and they are planned again from the
            # journal's tokens
            self._planned = self._plan_steps()
        planned = self._planned
        if planned:
            # the chaos choke point is consulted per ATTEMPT (a retry
            # is a fresh dispatch, exactly like the prefill legs')
            serve_fault = _resilience._serve_fault
            if serve_fault is not None:
                serve_fault([s.req for _, s in planned[0].entries])
        while planned:
            self._dispatch_step(planned[0])
            del planned[0]
        if not self._unread:
            return None
        # two are unread now (or one, where nobody decodes behind it):
        # the older is read while the newer runs
        oldest = self._unread[0]
        try:
            oldest.sampled = self._read_step(oldest)
        except BaseException as exc:
            # what was to be read is lost, and every step behind it was
            # computed from it: drop them all, stand on the cache as the
            # lost step found it, and let the retry plan them anew
            rollback = oldest.pools_before
            self._abandon(*self._unread)
            if rollback is None:
                if (self._decode_policy.max_retries
                        and self._decode_policy.classify(exc)):
                    raise ServingDegraded(
                        "a decode step's tokens were lost after the "
                        "cache had moved on: nothing to retry against"
                    ) from exc
                raise
            self._cache.pools = rollback
            self._planned = None
            raise
        return self._unread.popleft()

    def _note_step_retry(self, exc, attempt_n, delay):
        _step_retries.inc()
        self._retried = True
        tel = self._telemetry
        if tel.recording:
            tel.emit({
                "type": "serving_retry", "ts": time.time(),
                "source": "serving", "leg": "decode_step",
                "error": repr(exc)[:200], "attempt": attempt_n,
                "delay_s": delay,
                "active": sum(1 for s in self._slots
                              if s is not None and not s.prefilling),
            })

    def _read_step(self, sent):
        """The readback of one dispatched step: its tokens, and behind them
        the model's step counters.  Blocks until the device has run it."""
        with self._telemetry.span("serving.decode.step.wait"):
            sampled = np.asarray(sent.out)
        sent.read_at = time.perf_counter()
        return sampled

    def _abandon(self, *steps):
        """Forget steps, planned or dispatched, that will not be committed."""
        for step in steps:
            for _, slot in step.entries:
                slot.inflight -= 1
            if step in self._unread:
                self._unread.remove(step)

    def _settle(self):
        """Read and commit whatever is in flight and dispatch nothing: what
        a non-drain ``stop()`` does before it fails the actives."""
        while self._unread:
            self._decode_step(dispatch=False)

    def _fail_decoding(self, exc):
        """A decode step failed for good: retire every decoding sequence
        typed, drop what is in flight for them, and (under donation, where
        the failed dispatch consumed the pools) start from zeroed pools."""
        self._abandon(*self._unread, *(self._planned or ()))
        self._planned = []
        for i, slot in enumerate(self._slots):
            if slot is not None and not slot.prefilling:
                self._retire(i, error=exc)
        self._recover_pools(exc)
        if self._breaker is not None:
            self._breaker.record_fatal()

    def _commit_blocks(self, sent, out, live, tripped, now):
        """A block model's part of :meth:`_commit_step`: ``out`` is the
        step's ``block_state``.  A slot whose forward closed its block (found
        it whole, or out of denoising forwards) moves ``kv_len`` by ``B``
        (nowhere else does it move) and opens the next block; any other takes
        the block as the forward left it.  Either delivers the block's ids IN
        ORDER behind what the request has (0 to ``B`` of them, one stamp each,
        all of this commit's instant; none past ``max_new_tokens`` or behind
        an EOS): a denoising forward as far as they are unmasked, a closing
        one what is left, as it is (nothing, unless a position's candidate
        was the mask id).  What the forward of a slot that has left unmasked
        is discarded."""
        B = self._block["length"]
        ids, _, _, flags, _ = block_state(out, self.config.num_slots, B)
        eos = self.model.eos_id
        # the step still in flight (a slot is in it where ``inflight`` is
        # left over once this step's count is taken off)
        ahead = self._unread[0] if self._unread else None
        delivered = 0
        for i, slot in live:
            if i in tripped:
                continue               # retired typed by the guard
            req, n = slot.req, len(slot.generated)
            closed = flags[i] >> B
            if not closed:
                slot.block = ids[i].copy()
                slot.forwards += 1
            at = slot.prompt_len + n - slot.kv_len
            while (0 <= at < B and len(slot.generated) < req.max_new_tokens
                   and (closed or slot.block[at] != slot.mask_id)):
                tok = int(slot.block[at])
                slot.generated.append(tok)
                req.journal.accepted.append(tok)
                req.token_times.append(now)
                at += 1
                if tok == eos:
                    break
            if not n and slot.generated:
                # admission -> first delivered token
                _ttft_hist.observe(now - req.enqueue_ts)
            if closed:
                # the forward that wrote the block's K/V: the block's span
                # runs from its first forward's dispatch to here, and the
                # next block's first forward is the step in flight
                if slot.block_since is not None:
                    self._telemetry.observe_span(
                        "serving.decode.block", *slot.block_since)
                slot.block_since = ahead.sent_at if slot.inflight else None
                slot.next_block()
            _tokens_delivered.observe(len(slot.generated) - n)
            delivered += len(slot.generated) - n
        _tokens.inc(delivered)
        gone = [i for i, slot in sent.entries if self._slots[i] is not slot]
        if gone:
            _tokens_discarded.inc(int(sum(
                bin(int(flags[i]) & ((1 << B) - 1)).count("1")
                for i in gone)))

    def _commit_step(self, sent):
        """Take one read step's tokens into the slots that decoded in it —
        the ``_Slot`` objects captured at its plan: a slot that left in
        between (EOS seen a step late, a cancel, a deadline) drops its
        token, whoever sits at its index now — then run the decisions that
        need token values.  (Under a block model a slot takes 0 to ``B``
        tokens and its ``kv_len`` moves by ``B`` or not at all:
        :meth:`_commit_blocks`.)"""
        cfg = self.config
        with self._telemetry.span("serving.decode.step.commit"):
            # the cache as this step found it and the step's output are
            # let go inside this span (where pools are not donated it is a
            # whole pytree of buffers to free, and the step behind this one
            # is still reading the output)
            sampled = np.array(sent.sampled)
            sent.pools_before = sent.out = sent.sampled = None
            # the model's step counters came back behind the tokens
            for c, n in zip(self._step_counters(0),
                            sampled[len(sampled) - len(
                                self.model.step_counters):]):
                c.inc(int(n))
            if self._breaker is not None:
                self._breaker.record_success()
            for _, slot in sent.entries:
                slot.inflight -= 1
            live = [(i, slot) for i, slot in sent.entries
                    if self._slots[i] is slot]
            tripped = ()
            if cfg.kv_guard:
                # sweep each slot's TAIL page — the one this step's token
                # write landed in (position = pre-step kv_len)
                guard_vec = np.zeros((cfg.num_slots,), np.int32)
                owners = list(range(cfg.num_slots))
                for i, slot in live:
                    guard_vec[i] = slot.pages[
                        slot.kv_len // self._cache.page_size]
                tripped = self._guard_pages(owners, guard_vec,
                                            phase="decode")
            now = time.perf_counter()
            if self._block:
                self._commit_blocks(sent, sampled, live, tripped, now)
            else:
                for i, slot in live:
                    if i in tripped:
                        continue           # retired typed by the guard
                    slot.kv_len += 1
                    if slot.more:
                        self._release_window(i, slot)
                    tok = int(sampled[i])
                    slot.generated.append(tok)
                    slot.req.journal.accepted.append(tok)
                    slot.req.token_times.append(now)
                _tokens.inc(len(live) - len(tripped))
                if len(live) < len(sent.entries):
                    _tokens_discarded.inc(len(sent.entries) - len(live))
            _steps.inc()
            for i, slot in live:
                if self._slots[i] is slot:
                    self._finish_if_done(i)
            _active_slots.set(self._active_count())
            self._cache.publish_gauges(
                sum(s.kv_len for s in self._slots if s is not None))
            self._committed = True

    # -- the loop's account of its own time ------------------------------------
    def _lose_anchor(self):
        """Nothing to measure the next interval from (an idle wait, a turn
        that committed no step): the next commit observes nothing, and what
        the frame gathered belongs to no interval."""
        self._last_commit = None
        self._chunk_rode = self._retried = False
        if self._frame is not None:
            self._frame.cut()

    def _note_commit(self, frame):
        """The turn that just closed committed a decode step (worker thread;
        behind a commit there is nothing of a turn but the throughput note):
        observe the time since the turn before it did into
        ``serving.decode.interval{chunk}``, and judge it.  The frame is cut
        HERE, so that what it holds is the interval's own extent: the loop's
        conditions between two turns, and this turn."""
        now, gc_s = time.perf_counter(), self._gc.seconds
        last, self._last_commit = self._last_commit, (now, gc_s)
        phases = frame.cut()
        chunk, self._chunk_rode = int(self._chunk_rode), False
        retried, self._retried = self._retried, False
        if last is None:
            self._cpu_at = (now, time.thread_time())
            return
        interval = now - last[0]
        _interval[chunk].observe(interval)
        n, base = self._samples[chunk], self._baseline[chunk]
        run = self._stall_run[chunk]
        stalled = (n >= STALL_MIN_SAMPLES
                   and interval > max(STALL_FLOOR_S, STALL_FACTOR * base))
        # the thread's CPU clock is a system call, and on a sandboxed host
        # a dear one (tens of microseconds of a 3 ms iteration): read at a
        # commit, at most once in ``STALL_CPU_EVERY_S``, and at every stall
        cpu_at = self._cpu_at
        if stalled or now - cpu_at[0] >= STALL_CPU_EVERY_S:
            self._cpu_at = (now, time.thread_time())
        if stalled:
            self._note_stall(interval, base, phases, chunk,
                             self._cpu_at[1] - cpu_at[1], now - cpu_at[0],
                             gc_s - last[1])
            # a stall never feeds the baseline, but a kind that has done
            # nothing else for as long as it took to trust the baseline is
            # in a new regime (a batch or a context many times larger):
            # the run's mean is the baseline from here
            run[0] += 1
            run[1] += interval
            if run[0] >= STALL_MIN_SAMPLES:
                self._baseline[chunk] = run[1] / run[0]
                run[:] = 0, 0.0
            return
        run[:] = 0, 0.0
        if not retried:
            self._samples[chunk] = n = n + 1
            self._baseline[chunk] = base + (interval - base) * max(
                STALL_WEIGHT, 1.0 / n)

    def _note_stall(self, interval, base, phases, chunk, cpu_s, cpu_over_s,
                    gc_s):
        """One stall: its excess over the baseline into the cell and, under
        the phase it is put down to, into the counter; an entry into the
        journal (and to the record sinks).  ``cpu_s`` is the worker's CPU
        time over the last ``cpu_over_s`` seconds: the interval, and at
        most ``STALL_CPU_EVERY_S`` and one quiet interval before it."""
        hists = self._telemetry.histograms()

        def above_its_mean(name, seconds, spans):
            # the cell's mean over the process, this frame's own spans apart
            # (they have closed, so the cell holds them)
            cell = hists.get(name)
            if cell is None or cell.count <= spans:
                return seconds
            return seconds - spans * (cell.sum - seconds) / (cell.count - spans)

        # the phases below the turn, the collector's own span apart
        below = {name: e for name, e in phases.items()
                 if e[2] >= 1 and not name.startswith("host.gc")}
        unspanned = interval - sum(e[0] for e in below.values() if e[2] == 1)
        over = {name: above_its_mean(name, e[0], e[1])
                for name, e in below.items()}
        most = max(over.values(), default=0.0)
        between = _iteration_unspanned.snapshot().mean or 0.0
        if gc_s > 0.5 * interval:
            where = "gc"
        elif not over or unspanned - between > most:
            where = "outside"
        else:
            # a parent stands as far above its mean as the child that was
            # slow: of those close to the most, the innermost
            where = max((n for n, o in over.items()
                         if o >= most - 0.1 * abs(most)),
                        key=lambda n: below[n][2])
        excess = interval - base
        entry = {
            "ts": time.time(), "interval_s": interval, "baseline_s": base,
            "excess_s": excess, "where": where, "chunk": bool(chunk),
            "frame": {name: e[0] for name, e in below.items()},
            "unspanned_s": unspanned,
            "wait_s": sum(e[0] for name, e in below.items()
                          if name.endswith(".wait")),
            "cpu_s": cpu_s, "cpu_over_s": cpu_over_s, "gc_s": gc_s,
            "active": self._active_count(), "in_flight": len(self._unread),
        }
        _stall.observe(excess)
        _obs.counter("serving.decode.stall_seconds",
                     {"where": where}).inc(excess)
        self._stalls.append(entry)
        self._stall_count += 1
        self._stall_seconds += excess
        tel = self._telemetry
        if tel.recording:
            tel.emit(dict(entry, type="serving_stall", source="serving"))

    def _retire(self, idx, error=None):
        slot = self._slots[idx]
        self._slots[idx] = None
        if (error is None and self._sessions is not None
                and getattr(slot.req, "session", None) is not None):
            # pin BEFORE the free below: every history page stays
            # rc >= 1 throughout, so nothing can evict it in between
            self._park_session(slot)
        self._free_slot_pages(idx, slot)
        self._completed += 1
        if error is None:
            # only SERVED sequences feed the rate EMA: a fault or
            # mid-decode shed can mass-retire N slots in one instant,
            # and counting those would spike the estimated service rate
            # and disable shed-at-admission exactly while the decoder
            # is failing or drowning
            self._retired_total += 1
        req = slot.req
        if error is not None:
            req.fail(error)
        else:
            # the journal, not the slot: after a replay the slot only
            # holds this incarnation's tokens, the journal all of them
            req.complete(req.journal.tokens())
        _retired.inc()
        _active_slots.set(self._active_count())
        tel = self._telemetry
        if tel.span_active():
            seq_tags = {"seq": req.seq, "prompt": slot.prompt_len,
                        "generated": len(slot.generated),
                        "shed": error is not None}
            if req.trace is not None:
                seq_tags = req.trace.child().tags(**seq_tags)
            tel.record_span(
                "serving.decode.sequence", req.enqueue_wall,
                time.time() - req.enqueue_wall, tags=seq_tags)
        if tel.recording:
            tel.emit({
                "type": "decode_sequence", "ts": time.time(),
                "source": "serving", "seq": req.seq,
                "prompt_len": slot.prompt_len,
                "generated": len(slot.generated),
                "shed": error is not None,
                "kv_pages_used": self._cache.used_pages,
                "queue_depth": self._queue.depth(),
            })

    def _park_session(self, slot):
        """Park a successfully retired conversational turn (worker
        thread, called by :meth:`_retire` BEFORE the slot's pages are
        freed).  Registers every full history page in the prefix index
        and takes a session pin (one extra refcount per page) so LRU
        eviction can't reclaim the chain between turns, then records
        the conversation in the shared :class:`SessionStore`.

        Roles mode: when the turn was handed off here from a prefill
        replica, the sticky replica stays the ORIGIN — that is where
        the next turn's prefill (and its prefix probe) will run — so no
        local pin is taken; the origin's warmth is its rc=0-parked
        registered prompt pages (evictable, but the bitwise contract
        never depends on warmth: a cold miss just re-prefills)."""
        req = slot.req
        history = req.journal.resume_prompt()
        origin = getattr(req, "handoff_origin", None)
        sticky = origin if origin is not None else self._replica_index
        pinned = []
        if sticky == self._replica_index:
            ps = self._cache.page_size
            hashes = self._cache.prefix_hashes(history)
            # publish the history's full pages: prefill registered the
            # PROMPT'S full pages already (idempotent), decode appended
            # the generated tokens' pages that only this path publishes
            n_full = min(slot.kv_len // ps, len(slot.pages), len(hashes))
            for pi in range(n_full):
                self._cache.register_prefix(hashes, pi, slot.pages[pi])
            pinned = self._cache.pin_prefix(history, limit=n_full)
            _session_parked_pages.inc(len(pinned))
        self._sessions.park(req.session, replica=sticky,
                            history_len=len(history), pages=pinned,
                            release=self.release_session_pins)
