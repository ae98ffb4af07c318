"""Executor: compiles a whole Program block to ONE XLA computation and runs it.

Reference: python/paddle/fluid/executor.py + paddle/fluid/framework/executor.cc.
The reference Executor interprets the block op-by-op, dispatching a CUDA/CPU
kernel per op.  On TPU that model wastes the compiler: here `Executor.run`
*traces* the block once (each op's registered lowering rule emits JAX ops),
closes over autodiff (the ``backward`` meta-op differentiates the traced
forward prefix with ``jax.value_and_grad``), jits the resulting pure
``step(state, feed, key) -> (fetches, new_state, key)`` function, and caches
the executable keyed on (program version, feed signature, fetch list).
Subsequent runs with the same shapes replay the compiled binary — per-op
dispatch cost is zero and XLA fuses across the entire block.

State (parameters, optimizer accumulators, BN running stats, step counters)
lives in a ``Scope`` as device arrays and is threaded functionally through the
step with buffer donation, so updates are in-place at the XLA level.

Fast-path dispatch: once a (program, scope, fetch list) triple reaches
steady state, ``run()`` replays a ``_BoundProgram`` entry — pre-resolved
owner scopes, a per-feed shape/dtype plan, the compiled runner — instead
of re-deriving the step from the Program.  State stays on device
end-to-end, read-only state is neither donated nor returned, and
``return_numpy=True`` fetches come back as ``LazyFetch`` values that pay
the device->host copy on first access, so step N+1's dispatch never waits
on step N's transfer.  Feeds that are already committed jax arrays (the
async device-feed pipeline, ``reader.device_prefetch``) skip host-side
conversion entirely — shape/dtype validated from metadata, placement
conformed only when it disagrees with the compiled step's shardings — so
a prefetched batch costs zero host copies at dispatch
(``feed_host_copy_count`` instruments the contract).
Invalidation: ``program.version`` bump, any public
scope mutation, feed shape/dtype drift.
A persistent XLA compile cache (``$JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``) lets warm-up survive process restarts
(enable_compilation_cache).
"""
from __future__ import annotations

import contextlib
import logging
import os
import time
import warnings
import weakref

import numpy as np

from . import core
from . import observability as _obs
from . import profiler as _prof
from .observability import xla_stats as _xla_stats
from . import resilience
from .framework import (
    GRAD_SUFFIX,
    Block,
    Parameter,
    Program,
    Variable,
    default_main_program,
    default_startup_program,
    grad_var_name,
)
from .lod import LoDArray
from .registry import get_rule

logger = logging.getLogger(__name__)

__all__ = ["Executor", "Scope", "global_scope", "scope_guard", "as_numpy",
           "LazyFetch", "enable_compilation_cache", "cache_eviction_count",
           "compile_count", "JitStepCache"]


# ---------------------------------------------------------------------------
# Scope
# ---------------------------------------------------------------------------


class _TensorShim:
    """Minimal shim mimicking the reference's Tensor handle so code written
    against ``scope.find_var(n).get_tensor()`` works."""

    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def __array__(self, dtype=None):
        a = np.asarray(self._scope.vars[self._name])
        return a.astype(dtype) if dtype is not None else a

    def set(self, value, place=None):
        self._scope.vars[self._name] = np.asarray(value)
        self._scope._bump()

    def shape(self):
        return list(np.shape(self._scope.vars[self._name]))


class _VarShim:
    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def get_tensor(self):
        return _TensorShim(self._scope, self._name)


class Scope:
    """Host-side variable store: name -> device array (reference
    framework/scope.h, but flat — block locals never escape the jit trace)."""

    def __init__(self):
        self.vars: dict[str, object] = {}
        self.kids: list["Scope"] = []
        self._parent: "Scope | None" = None
        # Mutation counter for the executor's fast-path bound cache: any
        # mutation through the public surface (setitem, shim set, var
        # creation, drop) bumps it, invalidating bound entries whose owner
        # resolution walked through this scope.  The executor's own step
        # write-back intentionally does NOT bump — value updates from the
        # compiled step are what the bound entry exists to serve.
        self._version = 0

    def _bump(self):
        self._version += 1

    def new_scope(self) -> "Scope":
        """Child scope: lookups fall back to this scope (reference
        Scope::NewScope / FindVar ancestor search)."""
        kid = Scope()
        kid._parent = self
        self.kids.append(kid)
        return kid

    def drop_kids(self):
        # detach first: kid.drop() would otherwise remove itself from
        # self.kids mid-iteration and skip every other kid
        kids, self.kids = self.kids, []
        for kid in kids:
            kid._parent = None
            kid.drop()

    def _owner(self, name):
        scope = self
        while scope is not None:
            if name in scope.vars:
                return scope
            scope = scope._parent
        return None

    def find_var(self, name):
        owner = self._owner(name)
        return _VarShim(owner, name) if owner is not None else None

    def var(self, name):
        if name not in self.vars:
            self.vars[name] = None
            self._bump()  # a new local can shadow an ancestor's binding
        return _VarShim(self, name)

    def __contains__(self, name):
        return self._owner(name) is not None

    def __getitem__(self, name):
        owner = self._owner(name)
        if owner is None:
            raise KeyError(name)
        return owner.vars[name]

    def __setitem__(self, name, value):
        self.vars[name] = value
        self._bump()

    def keys(self):
        return self.vars.keys()

    def drop(self):
        """Release this scope's vars and its whole subtree (reference Scope
        destructor semantics); a dropped kid also detaches from its parent
        — both directions, so stale handles stop resolving parent names and
        the parent's kids list doesn't retain dead scopes."""
        self.vars.clear()
        self._bump()
        for kid in self.kids:
            kid._parent = None  # avoid double-detach walk
            kid.drop()
        self.kids.clear()
        if self._parent is not None and self in self._parent.kids:
            self._parent.kids.remove(self)
        self._parent = None


_global_scope = Scope()
_scope_stack = [_global_scope]


def global_scope() -> Scope:
    return _scope_stack[-1]


@contextlib.contextmanager
def scope_guard(scope: Scope):
    _scope_stack.append(scope)
    try:
        yield
    finally:
        _scope_stack.pop()


def as_numpy(tensor):
    if isinstance(tensor, (list, tuple)):
        return [as_numpy(t) for t in tensor]
    if isinstance(tensor, _TensorShim):
        return np.asarray(tensor)
    return np.asarray(tensor)


# ---------------------------------------------------------------------------
# Lazy fetches + fast-path dispatch support
# ---------------------------------------------------------------------------


class LazyFetch:
    """A fetched value that stays on device until first host access.

    The executor fast path hands these back for ``return_numpy=True`` so
    dispatch of step N+1 is not blocked behind step N's device->host copy —
    the copy happens lazily, the first time the caller actually touches the
    value.  Any numpy-style access (``np.asarray``, indexing, arithmetic,
    attribute reads) materializes the host array and from then on behaves
    exactly like the eagerly converted result.  Shape/dtype metadata is
    served from the device array without forcing a sync.
    """

    __slots__ = ("_device_value", "_np")

    def __init__(self, device_value):
        self._device_value = device_value
        self._np = None

    def materialize(self):
        if self._np is None:
            with _obs.span("executor.fetch_materialize"):
                self._np = np.asarray(self._device_value)
            self._device_value = None
        return self._np

    @property
    def shape(self):
        v = self._np if self._np is not None else self._device_value
        return tuple(v.shape)

    @property
    def dtype(self):
        v = self._np if self._np is not None else self._device_value
        return v.dtype

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    def __array__(self, dtype=None, copy=None):
        a = self.materialize()
        if dtype is not None:
            a = a.astype(dtype)
        elif copy:
            a = a.copy()
        return a

    def __repr__(self):
        return repr(self.materialize())

    def __str__(self):
        return str(self.materialize())

    def __getattr__(self, name):
        if name in ("_np", "_device_value"):  # guard copy/pickle recursion
            raise AttributeError(name)
        if name in ("__array_interface__", "__array_struct__"):
            # numpy prefers these to __array__, and they cannot describe an
            # extension dtype: a bfloat16 fetch would come back as raw '|V2'
            raise AttributeError(name)
        # anything not handled above delegates to the materialized array
        return getattr(self.materialize(), name)

    # like ndarray: __eq__ is elementwise, so not hashable
    __hash__ = None
    # numpy defers binary ops to us instead of broadcasting the wrapper
    __array_priority__ = 100.0


def _lazy_unary(name):
    def op(self):
        return getattr(self.materialize(), name)()

    op.__name__ = name
    return op


def _lazy_binary(name):
    def op(self, other):
        return getattr(self.materialize(), name)(other)

    op.__name__ = name
    return op


for _name in ("__len__", "__iter__", "__float__", "__int__", "__bool__",
              "__index__", "__neg__", "__pos__", "__abs__", "__invert__",
              "__complex__"):
    setattr(LazyFetch, _name, _lazy_unary(_name))
for _name in ("__getitem__", "__eq__", "__ne__", "__lt__", "__le__",
              "__gt__", "__ge__", "__add__", "__radd__", "__sub__",
              "__rsub__", "__mul__", "__rmul__", "__truediv__",
              "__rtruediv__", "__floordiv__", "__rfloordiv__", "__mod__",
              "__rmod__", "__pow__", "__rpow__", "__matmul__",
              "__rmatmul__", "__and__", "__rand__", "__or__", "__ror__",
              "__xor__", "__rxor__", "__contains__"):
    setattr(LazyFetch, _name, _lazy_binary(_name))
del _name


class _BoundProgram:
    """A (program, scope, fetch list) binding resolved once, replayed every
    step.  Caches everything ``run()`` otherwise re-derives per call: the
    compiled runner, persistable-var owner scopes (direct references instead
    of a ``list_vars()`` walk + ``_owner()`` chain search per var), the
    write-back owner map, the RNG-key owner, and a per-feed plan (expected
    shape/dtype + the cast, if any) so the hot loop only compares feed
    shapes/dtypes instead of rebuilding the full signature tuple.

    Invalidation: ``program.version`` bump, any public mutation of a scope
    on the owner chain (``Scope._version``), a feed shape/dtype change, a
    state var going missing/None, or NaN-debug toggling — each falls back
    to the slow path, which re-derives and rebinds.

    Scope references (scope, chain, owners) are WEAK: a bound entry must
    never keep a dropped/abandoned scope's device arrays (a whole model's
    parameters) alive — a dead weakref is just one more validation miss,
    and the miss evicts the entry.  The program ref stays strong (host-side
    metadata only; it is what keeps the id()-based cache key stable).
    """

    __slots__ = ("program", "scope", "version", "chain", "feed_plan",
                 "state_owners", "wb_owners", "key_owner", "entry",
                 "fetch_names", "eager_idx", "alias_cell", "nan_debug",
                 "guard")


def _scope_chain_token(scope):
    chain = []
    s = scope
    while s is not None:
        chain.append((s, s._version))
        s = s._parent
    return chain


_BOUND_MISS = object()  # sentinel: bound validation failed, take slow path

# Host-side feed conversions (asarray/astype passes over feed values)
# performed by the executor, across all instances — a telemetry-registry
# counter so step records report it without a second source of truth.
# The on-device feed fast path's contract is that committed device feeds
# never touch this counter — tests assert a zero delta (ISSUE 3
# acceptance).  Counters always count (observability.registry), so the
# value is identical with telemetry on or off.
_feed_copies = _obs.counter("executor.feed_host_copy")
# the async feed pipeline's transfer counter, read here for step records
# (same registry cell reader.device_prefetch increments)
_prefetch_transfers = _obs.counter("prefetch.transfer")


def feed_host_copy_count():
    """Process-wide count of host-side feed conversions the executor has
    performed.  Feeding committed jax arrays (reader.device_prefetch)
    must leave it unchanged — the instrumentation behind the zero-copy
    assertion in tests/unittests/test_device_prefetch.py.  A view of the
    ``executor.feed_host_copy`` telemetry counter."""
    return _feed_copies.value


# LRU evictions from the compiled-entry and bound-program caches.  The
# caches are bounded (env-tunable, see Executor.__init__) so a caller
# feeding ever-new shapes — a misconfigured serving batcher skipping its
# bucket ladder is the canonical case — turns into cache churn visible on
# the telemetry registry instead of an executable leak that OOMs hours in.
_cache_evicts = _obs.counter("executor.cache_evict")
_bound_evicts = _obs.counter("executor.bound_evict")


def cache_eviction_count():
    """(compiled-entry evictions, bound-entry evictions) across the
    process — views of the ``executor.cache_evict`` /
    ``executor.bound_evict`` telemetry counters.  A steadily climbing
    value in steady state means the working set of (program, feed-shape)
    pairs exceeds the caps: raise PADDLE_TPU_EXECUTOR_CACHE_CAP /
    PADDLE_TPU_EXECUTOR_BOUND_CACHE_CAP, or fix the feed-shape churn
    (e.g. a serving batcher padding to its bucket ladder)."""
    return _cache_evicts.value, _bound_evicts.value


def _env_cap(name, default):
    try:
        return max(1, int(os.environ.get(name, "") or default))
    except ValueError:
        warnings.warn("ignoring non-integer %s=%r" % (name, os.environ[name]))
        return default


# Process-wide count of fresh step compilations: every time a runner has
# to BUILD an executable — an Executor (program, feed-shape) cache miss,
# or a JitStepCache key miss — instead of replaying one.  This is the
# no-recompile assert the serving runtimes lean on: warm the shape menu,
# snapshot compile_count(), serve, assert the delta is zero (see
# tools/check_decode.py).
_compiles = _obs.counter("executor.compile")


def compile_count():
    """Fresh executor ENTRIES built across the process — a view of the
    ``executor.compile`` telemetry counter: one per ``Executor`` cache
    miss (program x feed shapes x fetch list x guard) and one per
    ``JitStepCache`` key miss.  Replays of cached/bound entries don't
    count; a nonzero delta across a steady-state serving window means a
    shape escaped the warmed menu.  It is NOT a count of XLA compiles:
    jax keys executables underneath an entry (on argument committed-ness
    and sharding, say), so one entry can compile more than once and a
    persistent-cache hit compiles nothing.  Those are the program's own
    cells since ``obs.watch_compiles()`` (armed by
    :func:`enable_compilation_cache`): counters
    ``xla.compile.requests{within}``, ``.cache_hits{within}`` and
    ``.cache_misses{within}``, spans ``xla.compile.trace`` / ``.lower`` /
    ``.backend`` (docs/observability.md, "Set-up")."""
    return _compiles.value


class JitStepCache:
    """Key-addressed cache of jit-compiled step callables — the
    bound-program idiom (pre-resolved once, replayed thereafter) for
    jax-level functions that live OUTSIDE a Program, with the same
    telemetry contract as the executor's own caches: a key miss counts on
    ``executor.compile`` (the no-recompile assert), an LRU eviction on
    ``executor.bound_evict``.

    The decode runtime (serving/decode_scheduler.py) keys its prefill
    buckets and its one fixed-shape decode step here; because every
    dispatch goes through :meth:`get`, "zero misses after warmup" is
    exactly "zero recompiles after warmup".  Its ``decode`` and ``chunk``
    keys hand back the callables the ``DecodeModel`` holds: a miss there
    (the first sight, or a key evicted and asked for again) still counts
    here, but gets the model's callable back and jax recompiles nothing.
    """

    def __init__(self, build, cap=64, name="jit-step"):
        self._build = build          # key -> compiled/jitted callable
        self._entries = {}
        self._cap = int(cap)
        self.name = name

    def __len__(self):
        return len(self._entries)

    def keys(self):
        return list(self._entries)

    def get(self, key):
        """The callable for ``key``, building (and counting a compile) on
        first sight; hits are LRU-touched replays."""
        fn = self._entries.get(key)
        if fn is not None:
            del self._entries[key]   # LRU touch: re-insert young
            self._entries[key] = fn
            return fn
        _compiles.inc()
        fn = self._build(key)
        while len(self._entries) >= self._cap:
            self._entries.pop(next(iter(self._entries)))
            _bound_evicts.inc()
        self._entries[key] = fn
        return fn


# where the persistent compile cache lives when nobody says otherwise: one
# fixed path inside the checkout (the path is part of the cache key, so a
# directory that moves — a tmp name, a pid, the time — would never hit)
_DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def enable_compilation_cache():
    """Persistent XLA compilation cache, so warm-up compiles survive process
    restarts.  ``$JAX_COMPILATION_CACHE_DIR`` is how a caller places it: jax
    reads the variable itself and this function sets no directory at all;
    where it is unset the cache goes to ``<checkout>/.jax_cache``.  Returns
    True if a cache directory is in use.  Called by the first
    ``Executor()``; from here on the process accounts for its compile
    requests (``obs.watch_compiles()``)."""
    from .core import safe_import_jax

    jax = safe_import_jax()
    _obs.watch_compiles()
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    cache_dir = from_env or _DEFAULT_COMPILE_CACHE_DIR
    # a corrupt/unwritable cache dir (a file squatting on the path, a dead
    # mount, bad permissions) must degrade to running uncached — warm-up
    # persistence is an optimization, never a reason executor setup fails
    try:
        os.makedirs(cache_dir, exist_ok=True)
        # per-process probe name: concurrent startups sharing the cache
        # dir must not race on each other's probe write/remove
        probe = os.path.join(cache_dir,
                             ".paddle_tpu_cache_probe.%d" % os.getpid())
        with open(probe, "w") as f:
            f.write("ok")
        try:
            os.remove(probe)
        except FileNotFoundError:
            pass
    except OSError as e:
        warnings.warn(
            "persistent compilation cache dir %r is unusable (%s); "
            "continuing without a compile cache" % (cache_dir, e))
        return False
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # default thresholds skip tiny/fast compiles; persist everything —
    # dispatch-bound training loops are exactly the small-program regime
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return True


_compile_cache_checked = [False]


def _step_dtype(dtype):
    """A feed dtype as the compiled step sees it: with x64 off jax narrows
    an int64 host array to int32 on the way in, so a numpy int64 feed and
    the int32 device array the prefetcher made from it are ONE signature
    (and one executable), not two."""
    import jax

    return str(jax.dtypes.canonicalize_dtype(dtype))


def _retry_fresh_entry(entry, state_in, feed_arrays, key):
    """First call of a freshly built entry is the compile: transient XLA
    status codes there (RESOURCE_EXHAUSTED from a probe compile racing
    real allocations, UNAVAILABLE during a runtime blip) retry with
    backoff.  A failure AFTER execution started may have consumed the
    donated state buffers — retrying would mask the real error with
    'Array has been deleted' — so retry only while every state input is
    still live."""

    def classify(exc):
        if not resilience.is_transient_xla_error(exc):
            return False
        return not any(
            getattr(v, "is_deleted", lambda: False)()
            for v in state_in.values())

    policy = resilience.RetryPolicy(max_retries=2, base_delay=0.2,
                                    max_delay=2.0, classify=classify)
    # what this call traces, lowers and compiles is ``executor.first_run``'s
    with _obs.compiles_within("executor.first_run"):
        return resilience.call_with_retry(entry, state_in, feed_arrays, key,
                                          policy=policy)

_DONATION_WARNING_MSG = "Some donated buffers were not usable"


def _filter_donation_warning_once():
    """Suppress jax's per-dispatch 'Some donated buffers were not usable'
    UserWarning (platforms without donation support) with a process-wide
    filter instead of a per-call catch_warnings block — entering/exiting
    that context dominated small-step dispatch time.  Re-checked on each
    (cold) _build rather than latched once: a ``warnings.catch_warnings``
    context (pytest wraps every test in one) pops filters registered
    inside it, so the filter must self-heal; the presence check keeps the
    filter list from growing one duplicate per compiled runner."""
    for f in warnings.filters:
        if f[0] == "ignore" and getattr(f[1], "pattern", None) == _DONATION_WARNING_MSG:
            return
    warnings.filterwarnings(
        "ignore", message=_DONATION_WARNING_MSG, category=UserWarning)


# ---------------------------------------------------------------------------
# Lowering context + block interpreter
# ---------------------------------------------------------------------------


class LoweringContext:
    """Carries the symbolic environment while a block is traced."""

    def __init__(self, program, env, base_key, is_test=False, mesh=None):
        self.program = program
        self.env = env  # var name -> traced jax value
        self._base_key = base_key
        self._key_counter = [0]
        self.is_test = is_test
        self.mesh = mesh  # set by ParallelExecutor for sharded lowering

    # RNG --------------------------------------------------------------------
    def op_key(self, op, seed: int = 0):
        """Deterministic PRNG key for an op instance: keyed on the op's stable
        position, so a replay of the same op (e.g. inside value_and_grad)
        draws the *same* randomness.  A nonzero ``seed`` attr pins the op's
        stream across steps (reference ops' ``seed`` attribute)."""
        import jax

        uid = op.block.idx * 100003 + _op_index(op)
        base = jax.random.PRNGKey(seed) if seed else self._base_key
        return jax.random.fold_in(base, uid)

    def next_key(self, seed: int = 0):
        import jax

        self._key_counter[0] += 1
        k = jax.random.fold_in(self._base_key, 7777 + self._key_counter[0])
        if seed:
            k = jax.random.fold_in(jax.random.PRNGKey(seed), self._key_counter[0])
        return k

    # env access -------------------------------------------------------------
    def get(self, name: str):
        try:
            return self.env[name]
        except KeyError:
            raise KeyError(
                "variable %r read before it was written — not in feed, scope, "
                "or produced by an earlier op" % name
            ) from None

    def has(self, name: str) -> bool:
        return name in self.env

    def set(self, name: str, value):
        self.env[name] = value

    def var(self, name: str, block=None):
        block = block or self.program.global_block()
        try:
            return block.var_recursive(name)
        except KeyError:
            return None

    # op-slot helpers --------------------------------------------------------
    def get_input(self, op, slot, default=None):
        names = op.inputs.get(slot) or []
        if not names:
            return default
        return self.get(names[0])

    def get_inputs(self, op, slot):
        return [self.get(n) for n in (op.inputs.get(slot) or [])]

    def set_output(self, op, slot, value):
        names = op.outputs.get(slot) or []
        if not names:
            return
        name = names[0]
        self._bind(name, value, op)

    def set_outputs(self, op, slot, values):
        names = op.outputs.get(slot) or []
        for n, v in zip(names, values):
            self._bind(n, v, op)

    def _bind(self, name, value, op):
        import jax

        var = self.var(name, op.block)
        if var is not None and var.stop_gradient and _is_float(value):
            value = jax.lax.stop_gradient(value)
        self.env[name] = value

    # lengths companions (ragged sequences) ----------------------------------
    def get_lengths(self, name: str, default=None):
        ln = name + "@LENGTHS"
        return self.env.get(ln, default)

    def set_lengths(self, name: str, lengths):
        self.env[name + "@LENGTHS"] = lengths

    def copy_lengths(self, src: str, dst: str):
        ln = src + "@LENGTHS"
        if ln in self.env:
            self.env[dst + "@LENGTHS"] = self.env[ln]
        sln = src + "@SUBLENGTHS"
        if sln in self.env:
            self.env[dst + "@SUBLENGTHS"] = self.env[sln]

    # outer-level (lod level 0) companions for nested LoD: counts of rows
    # per outer group (lod.py nested convention)
    def get_sub_lengths(self, name: str, default=None):
        return self.env.get(name + "@SUBLENGTHS", default)

    def set_sub_lengths(self, name: str, sub_lengths):
        self.env[name + "@SUBLENGTHS"] = sub_lengths

    def child(self, env):
        c = LoweringContext.__new__(LoweringContext)
        c.program = self.program
        c.env = env
        c._base_key = self._base_key
        c._key_counter = self._key_counter  # shared: deterministic key sequence
        c.is_test = self.is_test
        c.mesh = self.mesh
        return c


def _op_index(op):
    for i, o in enumerate(op.block.ops):
        if o is op:
            return i
    return len(op.block.ops) + id(op) % 1000


def _is_float(v):
    try:
        return np.issubdtype(np.asarray(v).dtype if not hasattr(v, "dtype") else v.dtype, np.floating) or str(getattr(v, "dtype", "")) == "bfloat16"
    except Exception:
        return False


# Ops whose lowering rules manage the lengths companion themselves (set it,
# or deliberately drop it — e.g. sequence_pool collapses the time axis).
# Generic propagation must not second-guess them.
_LENGTH_AWARE_OPS = frozenset(
    {
        "sequence_pool",
        "sequence_softmax",
        "sequence_conv",
        "sequence_expand",
        "sequence_expand_as",
        "sequence_concat",
        "sequence_reshape",
        "sequence_enumerate",
        "sequence_scatter",
        "sequence_slice",
        "sequence_pad",
        "sequence_unpad",
        "sequence_mask",
        "sequence_erase",
        "lod_reset",
        "row_conv",
        "lstm",
        "lstmp",
        "gru",
        "im2sequence",
    }
)


def _propagate_lengths(ctx: LoweringContext, op):
    """Generic ragged-metadata flow: if an op didn't set lengths on an output
    but some input carries them and the output preserves the [batch, time]
    leading dims, the output inherits the input's lengths.  Keeps every
    elementwise/matmul rule oblivious to the LoD companion convention."""
    if op.type in _LENGTH_AWARE_OPS:
        return
    src = None
    src_name = None
    for names in op.inputs.values():
        for n in names:
            lens = ctx.env.get(n + "@LENGTHS")
            if lens is not None:
                v = ctx.env.get(n)
                if v is not None and getattr(v, "ndim", 0) >= 2:
                    src = (v.shape[:2], lens)
                    src_name = n
                    break
        if src:
            break
    if not src:
        return
    lead, lens = src
    sub = ctx.env.get(src_name + "@SUBLENGTHS")
    for names in op.outputs.values():
        for n in names:
            if n + "@LENGTHS" in ctx.env:
                continue
            v = ctx.env.get(n)
            if v is not None and getattr(v, "ndim", 0) >= 2 and tuple(v.shape[:2]) == tuple(lead):
                ctx.env[n + "@LENGTHS"] = lens
                if sub is not None and n + "@SUBLENGTHS" not in ctx.env:
                    ctx.env[n + "@SUBLENGTHS"] = sub


_NAN_DEBUG = {"on": False}


def set_nan_debug(enable=True):
    """Executor NaN/Inf debug mode (reference: the per-op CheckNanInf pass
    enabled by FLAGS_check_nan_inf).  When on, every float op output gets a
    ``jax.debug.callback`` probe that reports the producing op and variable
    the moment a non-finite value appears — inside jit, on device."""
    _NAN_DEBUG["on"] = bool(enable)


def _nan_probe(op_type, var_name, value):
    import numpy as np_

    arr = np_.asarray(value)
    if not np_.isfinite(arr).all():
        bad = "nan" if np_.isnan(arr).any() else "inf"
        raise FloatingPointError(
            "non-finite (%s) value in output %r of op %r" % (bad, var_name, op_type)
        )


def interpret_ops(ctx: LoweringContext, ops):
    """Straight-line trace of an op list (no backward meta-op).

    Every op's lowering is wrapped in ``jax.named_scope(op.type)`` so the
    XLA/HLO metadata carries the Program op that produced each fused
    instruction — the analog of the reference profiler's per-op device
    attribution (paddle/fluid/platform/profiler.cc), but on the REAL
    compiled step: xprof traces and compiled-HLO dumps map fusions back to
    op types by scope name."""
    import functools

    import jax

    for op in ops:
        rule = get_rule(op.type)
        with jax.named_scope(op.type):
            rule(ctx, op)
            _propagate_lengths(ctx, op)
        if _NAN_DEBUG["on"]:
            import jax
            import jax.numpy as jnp

            for outs in op.outputs.values():
                for name in outs:
                    v = ctx.env.get(name)
                    if v is not None and hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.inexact):
                        jax.debug.callback(
                            functools.partial(_nan_probe, op.type, name), v
                        )


_COMPANION_SUFFIXES = ("@LENGTHS", "@SUBLENGTHS", "@ARRAY", "@ARRAYLEN")


# Ops whose lowering reads ambient env state through OUTPUT-name
# spellings: while/conditional snapshot their carried vars (listed only as
# outputs), array writers read-extend `<out>@ARRAY`.  Liveness must keep
# those names alive across recompute segment boundaries.
_READS_VIA_OUTPUTS = frozenset(
    {"while", "conditional_block", "array_write", "write_to_array",
     "array_read", "array_length", "increment", "assign"}
)


def _ops_read_names(ops):
    """Every env name an op list may read: declared inputs (recursing into
    control-flow sub-blocks, whose bodies read outer names not listed on
    the parent op), output names of ops that read ambient state through
    their output spelling, plus the ragged/array companion spellings."""
    names = set()

    def walk(op):
        for ns in op.inputs.values():
            names.update(ns)
        if op.type in _READS_VIA_OUTPUTS or getattr(op, "sub_block", None) is not None:
            for ns in op.outputs.values():
                names.update(ns)
        # sub-block bodies close over outer env names
        sub = getattr(op, "sub_block", None)
        if sub is not None:
            for o in sub.ops:
                walk(o)

    for op in ops:
        walk(op)
    out = set(names)
    for n in names:
        for suf in _COMPANION_SUFFIXES:
            out.add(n + suf)
    return out


def _run_recompute_segments(ctx, env0, pre, n_segments, keep):
    """Forward prefix as ``n_segments`` jax.checkpoint segments
    (Program.enable_recompute).  Each segment's boundary env is pruned to
    the names later segments / the keep-set can read, so the residuals
    jax.checkpoint stores shrink from every activation to the segment
    boundaries; interiors are recomputed during the backward sweep.

    Safe under retracing: op RNG is positional (LoweringContext.op_key),
    so the recompute replay draws identical randomness."""
    import jax

    # keep companions of kept names too (fetch reconstruction reads them)
    keep = set(keep)
    for n in list(keep):
        for suf in _COMPANION_SUFFIXES:
            keep.add(n + suf)

    bounds = [len(pre) * i // n_segments for i in range(n_segments + 1)]
    segments = [pre[bounds[i]: bounds[i + 1]] for i in range(n_segments)]
    segments = [s for s in segments if s]

    # live-after set per segment, computed back-to-front
    live_after = [None] * len(segments)
    acc = set(keep)
    for i in range(len(segments) - 1, -1, -1):
        live_after[i] = set(acc)
        acc |= _ops_read_names(segments[i])

    env = env0
    for i, seg in enumerate(segments):
        def run_seg(env_in, _seg=seg):
            c2 = ctx.child(dict(env_in))
            interpret_ops(c2, _seg)
            return c2.env

        if i < len(segments) - 1:
            run_seg = jax.checkpoint(run_seg)
        env = run_seg(env)
        live = live_after[i]
        env = {n: v for n, v in env.items() if n in live}
    return env


def lower_block(ctx: LoweringContext, block: Block):
    """Trace a block, handling the single ``backward`` meta-op if present.

    Reference analog: Executor::Run + the grad ops that append_backward
    inserted.  Here the forward prefix is differentiated *functionally*: it is
    replayed as a pure function of the trainable parameters and
    ``jax.value_and_grad(..., has_aux=True)`` yields both every forward
    binding (so fetches and post-ops see identical values — XLA computes the
    forward once) and the parameter gradients, which are bound to the
    ``<param>@GRAD`` names that clip/regularizer/optimizer ops reference.
    """
    import jax

    bw_idx = None
    for i, op in enumerate(block.ops):
        if op.type in ("backward", "calc_gradient"):
            if bw_idx is not None:
                raise ValueError("multiple backward/calc_gradient ops in one block")
            bw_idx = i
    if bw_idx is None:
        interpret_ops(ctx, block.ops)
        return

    pre, bop, post = block.ops[:bw_idx], block.ops[bw_idx], block.ops[bw_idx + 1:]
    no_grad = set(bop.attrs.get("no_grad_set") or ())
    if bop.type == "backward":
        target_names = [bop.inputs["Loss"][0]]
        wrt_names = [p for p in bop.attrs["parameter_list"] if p not in no_grad]
        missing = [p for p in wrt_names if p not in ctx.env]
        if missing:
            raise KeyError("parameters not initialized (run startup program first): %s" % missing)
    else:  # calc_gradient: arbitrary targets / wrt vars (feeds included)
        target_names = list(bop.inputs["Targets"])
        wrt_names = [w for w in bop.inputs["Inputs"] if w not in no_grad]
        produced = {n for o in pre for ns in o.outputs.values() for n in ns}
        missing = [w for w in wrt_names if w not in ctx.env and w not in produced]
        if missing:
            raise KeyError("calc_gradient inputs not available (feed or initialize them): %s" % missing)
        bad_targets = [t for t in target_names if t not in ctx.env and t not in produced]
        if bad_targets:
            raise KeyError("calc_gradient targets not produced by the program: %s" % bad_targets)
    tg_names = list(bop.inputs.get("TargetGradients") or []) if bop.type == "calc_gradient" else []

    outer_env = ctx.env
    wrt_set = set(wrt_names)

    n_segments = int(getattr(ctx.program, "_recompute_segments", 0) or 0)

    def fwd(wrt_vals):
        env2 = dict(outer_env)
        env2.update(wrt_vals)
        c2 = ctx.child(env2)
        if bop.type == "backward":
            if n_segments > 1 and len(pre) >= n_segments:
                env3 = _run_recompute_segments(
                    ctx, env2, pre, n_segments,
                    keep=set(target_names) | set(tg_names)
                    | _ops_read_names(post)
                    | set(getattr(ctx, "keep_names", ()) or ())
                    | ctx.program.persistable_names())
                env2.clear()
                env2.update(env3)
            else:
                interpret_ops(c2, pre)
        else:
            # calc_gradient may target grads w.r.t. *intermediate* vars: the
            # graph is cut at each wrt name — its producer still runs (for
            # side outputs) but downstream consumers see the seeded tracer,
            # otherwise the recomputation shadows the seed and its grad is
            # silently zero
            for op2 in pre:
                interpret_ops(c2, [op2])
                for ns in op2.outputs.values():
                    for nm in ns:
                        if nm in wrt_set:
                            env2[nm] = wrt_vals[nm]
        import jax.numpy as jnp

        total = 0.0
        for i, t in enumerate(target_names):
            tv = env2[t].astype(jnp.float32)
            if i < len(tg_names):  # explicit cotangent, constant w.r.t. the wrt vars
                tv = tv * jax.lax.stop_gradient(env2[tg_names[i]].astype(jnp.float32))
            total = total + jnp.sum(tv)
        return total, env2

    p0 = {p: outer_env[p] for p in wrt_names if p in outer_env}
    # intermediate wrt vars have no ambient value yet: materialize one by
    # replaying the prefix once (values only, no grad)
    if len(p0) < len(wrt_names):
        probe_env = dict(outer_env)
        interpret_ops(ctx.child(probe_env), pre)
        for w in wrt_names:
            if w not in p0:
                p0[w] = probe_env[w]
    (loss_val, env_after), grads = jax.value_and_grad(fwd, has_aux=True)(p0)
    del loss_val
    ctx.env = env_after
    import jax.numpy as jnp

    for i, t in enumerate(target_names):
        if i < len(tg_names):  # the supplied cotangent IS the target's grad
            ctx.env[grad_var_name(t)] = env_after[tg_names[i]]
        else:
            ctx.env[grad_var_name(t)] = jnp.ones_like(env_after[t])
    for p in wrt_names:
        g = grads[p]
        pv = ctx.var(p)
        if pv is not None and g.dtype != np.dtype("float32") and core.canonical_dtype(str(pv.dtype)) == "float32":
            g = g.astype(jnp.float32)
        ctx.env[grad_var_name(p)] = g
    interpret_ops(ctx, post)
    # splice mutated env back (ctx.env was rebound)
    outer_env.clear()
    outer_env.update(ctx.env)
    ctx.env = outer_env


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------


class Executor:
    """exe = Executor(TPUPlace()); exe.run(program, feed=..., fetch_list=...)"""

    # Default LRU bounds — generous for training (a handful of programs x
    # a few feed shapes), and >> any sane serving bucket ladder.  Env-
    # tunable per process; evictions count on the telemetry registry
    # (executor.cache_evict / executor.bound_evict), so a shape-churning
    # workload shows up as a climbing counter, never an executable leak.
    _CACHE_CAP = 64  # compiled (program, shapes) entries kept per executor
    _BOUND_CAP = 64  # fast-path bound (program, scope, fetches, shapes)

    def __init__(self, place=None):
        from .core import default_place, safe_import_jax

        safe_import_jax()  # first jax import eats np.random state otherwise
        if not _compile_cache_checked[0]:
            _compile_cache_checked[0] = True
            enable_compilation_cache()
        # no place given = jax's default device, resolved once and visible
        self.place = place if place is not None else default_place()
        self.place.jax_device()  # an explicit place that names no device raises here
        self._cache: dict = {}
        self._bound: dict = {}
        self._cache_cap = _env_cap("PADDLE_TPU_EXECUTOR_CACHE_CAP",
                                   self._CACHE_CAP)
        self._bound_cap = _env_cap("PADDLE_TPU_EXECUTOR_BOUND_CACHE_CAP",
                                   self._BOUND_CAP)
        # step telemetry: records flow only when the global registry is
        # enabled AND a sink is attached (telemetry.recording — one
        # attribute read per run otherwise)
        self._telemetry = _obs.get_telemetry()
        self._run_id = "exe-%08x" % (id(self) & 0xFFFFFFFF)
        self._run_seq = 0
        # device-side result of the last nan_guard finiteness check; None
        # when the last run had no guard (see last_step_ok)
        self._last_guard_flag = None
        # fast-path dispatch (bound-program cache + lazy fetches); tests
        # turn one off to compare with the rebind path
        self.fast_path = True
        self.lazy_fetches = True
        # set by ParallelExecutor: jax.sharding.Mesh for data-parallel SPMD;
        # a 2-D ("dp","tp") mesh additionally Megatron-shards parameters
        # (see parallel/tp.py), optionally refined by _sharding_rules
        # ([(regex, PartitionSpec)]).
        self._mesh = None
        self._sharding_rules = None
        self._zero_stage = 0

    def attach_mesh(self, mesh_spec, sharding_rules=None, zero_stage=0,
                    devices=None):
        """Attach a device mesh (True = 1-D dp mesh over every device, or
        a (dp, tp[, sp]) tuple / {axis: size} dict — parallel_executor.
        build_mesh) so runs execute SPMD; the single entry point used by
        ParallelExecutor, Trainer, and Inferencer."""
        from .parallel_executor import build_mesh

        self._mesh = build_mesh(mesh_spec, devices)
        self._sharding_rules = sharding_rules
        self._zero_stage = int(zero_stage or 0)
        # compiled runners bake the mesh/shardings in, but the cache
        # signature (program, feeds, fetches, state) doesn't carry them —
        # drop anything compiled under the previous mesh config
        self._cache.clear()
        self._bound.clear()
        return self._mesh

    # -- public API ----------------------------------------------------------
    def run(
        self,
        program: Program | None = None,
        feed: dict | None = None,
        fetch_list=None,
        feed_var_name="feed",
        fetch_var_name="fetch",
        scope: Scope | None = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
        nan_guard: bool = False,
    ):
        """``nan_guard=True`` arms the on-device step guard: one fused
        finiteness reduction over loss/gradients is compiled into the step
        and a non-finite step's whole state update is skipped inside the
        executable (parameters come back bitwise-unchanged).  The verdict
        is readable afterwards via :meth:`last_step_ok`; guarded and
        unguarded executables are cached separately, with the guard off
        the compiled step has zero extra outputs, and a step that writes
        no state (eval/inference) compiles identically guarded or not —
        there is no update to skip, so last_step_ok stays None.

        The whole call is one span: it closes into the cell
        ``executor.run`` when a compiled entry was replayed and into
        ``executor.first_run`` when this call built one (the start-up
        program and each shape's first step: trace, lower, compile or
        cache look-up), so compile steps never sit among the replays."""
        # the body stays in this frame (no wrapper around a ``_run``): one
        # Python frame more between the caller and a fresh entry's trace
        # cost the first Transformer-base step 1.5-2 s of tracing and
        # lowering on the chip's host (PERF.md section 6, PR 24)
        with self._telemetry.span("executor.run") as whole:
            program = program or default_main_program()
            scope = scope or global_scope()
            feed = feed or {}
            nan_guard = bool(nan_guard)

            # step-record gate: one attribute read; when no sink is attached
            # (or PADDLE_TPU_TELEMETRY=0) the whole telemetry path below is
            # two cheap boolean checks
            recording = self._telemetry.recording
            t_run0 = time.perf_counter() if recording else 0.0

            fetch_names = [f.name if isinstance(f, Variable) else str(f) for f in (fetch_list or [])]

            # fast path: a prior run of this (program, scope, fetch list) bound
            # the compiled runner to pre-resolved owner scopes and a feed plan;
            # on a hit the whole per-step re-derivation below is skipped
            bound_key = None
            if use_program_cache and self.fast_path:
                # the key carries each feed's shape so workloads that alternate
                # among a fixed set of feed shapes — a serving batcher cycling
                # its bucket ladder — keep one bound entry PER shape instead of
                # thrashing rebind on every size change; the per-entry plan
                # still validates dtype/kind before replay.  Sorted so feed
                # dicts built in different key orders share one entry.
                bound_key = (id(program), id(scope), tuple(fetch_names),
                             nan_guard,
                             tuple(sorted((n, getattr(v, "shape", None))
                                          for n, v in feed.items())))
                bound = self._bound.get(bound_key)
                if type(bound) is _BoundProgram:
                    out = self._run_bound(bound, program, scope, feed,
                                          return_numpy, recording, t_run0)
                    if out is not _BOUND_MISS:
                        # LRU touch: keep concurrently hot bindings resident
                        del self._bound[bound_key]
                        self._bound[bound_key] = bound
                        return out
                    # a missed entry is stale; drop it now so it cannot pin
                    # anything until the slow path rebinds (or never, if this
                    # scope is on its way out)
                    self._bound.pop(bound_key, None)

            # last_step_ok must never report a previous run's verdict: clear
            # before any slow-path branch (distributed early returns, reader
            # EOF, a raising entry) can skip the guarded set below
            self._last_guard_flag = None

            # started py_reader pipelines feed the step when the caller passes
            # no feed (the reference's in-graph reader semantics); an exhausted
            # pipeline raises core.EOFException out of run().  Items are pulled
            # from EVERY reader before any is consumed so one reader hitting
            # EOF pushes the others' items back instead of desynchronizing.
            reader_fed = False
            if not feed:
                from .layers.io import program_readers

                # every registered reader is consulted: an unstarted one raises
                # the diagnostic EOF instead of the step failing on missing vars
                started = program_readers(program)
                if started:
                    pulled = []
                    try:
                        for reader in started:
                            pulled.append((reader, reader.feed_dict()))
                    except Exception:
                        for reader, item_feed in reversed(pulled):
                            reader._pushback.appendleft(
                                tuple(item_feed[n] for n in reader.names))
                        raise
                    feed = {}
                    for _, item_feed in pulled:
                        feed.update(item_feed)
                    reader_fed = True

            # distributed programs: listen_and_serv blocks serving; send/recv
            # trainer programs run compute as one XLA step + host-side RPC round
            op_types = {op.type for op in program.global_block().ops}
            if "listen_and_serv" in op_types:
                from .transpiler import pserver_runtime

                whole.name = None  # serves until shut down: no step's time
                return pserver_runtime.serve(self, program, scope)
            if "send" in op_types or "recv" in op_types:
                from .transpiler import pserver_runtime

                clients = self._pserver_clients(program)
                return pserver_runtime.run_trainer_step(self, program, feed, fetch_list, scope, clients)

            tel = self._telemetry
            with tel.span("executor.prepare_feed"):
                feed_arrays = self._prepare_feed(program, feed)
            if resilience._feed_fault is not None:  # fault-injection harness
                feed_arrays = resilience._feed_fault(feed_arrays)
            with tel.span("executor.bind"):
                state_in = self._collect_state(program, scope)
                key = self._rng_key(program, scope)

                sig = (
                    program.fingerprint(),
                    tuple(sorted((n, tuple(np.shape(v)), _step_dtype(v.dtype if hasattr(v, "dtype") else np.asarray(v).dtype)) for n, v in feed_arrays.items())),
                    tuple(fetch_names),
                    tuple(sorted(state_in)),
                    _NAN_DEBUG["on"],  # probes are baked into the executable
                    int(getattr(program, "_recompute_segments", 0) or 0),
                    nan_guard,  # guard reductions/gating are baked in too
                )
                entry = self._cache.get(sig) if use_program_cache else None
                call_entry = entry
                compiled_fresh = False
                if entry is not None:
                    # LRU touch: re-inserting keeps hot entries at the young end
                    del self._cache[sig]
                    self._cache[sig] = entry
                if entry is None:
                    compiled_fresh = True
                    whole.name = "executor.first_run"
                    _compiles.inc()
                    entry = self._build(program, sorted(feed_arrays), fetch_names,
                                        sorted(state_in), nan_guard=nan_guard)
                    if use_program_cache:
                        while len(self._cache) >= self._cache_cap:
                            self._cache.pop(next(iter(self._cache)))  # oldest entry
                            _cache_evicts.inc()
                        self._cache[sig] = entry
                    # first call compiles: retry transient XLA setup failures
                    call_entry = lambda *a: _retry_fresh_entry(entry, *a)  # noqa: E731

            # a fresh entry's call traces, lowers and compiles: it has a cell
            # of its own, so compile steps never land in the dispatch cell
            profiling = _prof.is_profiling()
            with tel.span("executor.compile" if compiled_fresh
                          else "executor.dispatch") as call:
                fetches, new_state, new_key = call_entry(state_in, feed_arrays, key)
                if profiling:
                    # the profiler's report wants the step's device time too
                    import jax

                    jax.block_until_ready(fetches)
            execute_s = call.duration
            if profiling:
                _prof.record("executor.run[prog@%x v%d]" % (id(program), program.version), execute_s)
            with tel.span("executor.writeback"):
                if nan_guard and getattr(entry, "_guard_cell", {}).get("emits"):
                    # the guard verdict rides as an extra trailing pseudo-fetch;
                    # peel it off before anything sees the fetch list (guard
                    # off, or a no-state step: the flag stays None from the
                    # reset above)
                    self._last_guard_flag = fetches[-1][0]
                    fetches = fetches[:-1]
                # write each updated var back to the scope that owns it (param
                # updates through a child scope must mutate the parent's param,
                # as in the reference); new names land in the local scope
                wb_owners = {}
                for name, val in new_state.items():
                    owner = scope._owner(name) or scope
                    owner.vars[name] = val
                    wb_owners[name] = owner
                key_owner = scope._owner("__rng_key__") or scope
                key_owner.vars["__rng_key__"] = new_key

                if bound_key is not None:
                    self._bind(bound_key, program, scope, feed, feed_arrays,
                               state_in, new_state, wb_owners, key_owner, entry,
                               fetch_names, reader_fed, nan_guard)
            if recording:
                self._emit_step(program, time.perf_counter() - t_run0,
                                execute_s, fast_path=False,
                                compiled=compiled_fresh, nan_guard=nan_guard)
            # slow path converts eagerly — exactly the pre-fast-path contract
            return self._finalize_fetches(fetches, return_numpy, lazy=False,
                                          eager_idx=())

    def last_step_ok(self):
        """After a ``nan_guard=True`` run: the on-device finiteness verdict
        for the last step (True = loss/grads finite, update applied;
        False = non-finite, update skipped).  Materializing the scalar is
        the caller's one host sync; returns None when the last run had no
        guard."""
        flag = self._last_guard_flag
        if flag is None:
            return None
        return bool(np.asarray(flag))

    def _emit_step(self, program, duration_s, execute_s, fast_path,
                   compiled, nan_guard):
        """One structured step record to the telemetry sinks (caller gates
        on ``self._telemetry.recording``).  ``nan_ok`` is None here by
        design: materializing the on-device verdict would force a host
        sync per step — Trainer records carry the real verdict because
        the guard loop reads it anyway (see observability.STEP_SCHEMA)."""
        seq = self._run_seq
        self._run_seq = seq + 1
        self._telemetry.emit({
            "type": "step",
            "ts": time.time(),
            "source": "executor",
            "run_id": self._run_id,
            "program": "%x:v%d" % (id(program), getattr(program, "version", 0)),
            "step": seq,
            "duration_s": duration_s,
            "steps_per_s": (1.0 / duration_s) if duration_s > 0 else None,
            "feed_host_copies": _feed_copies.value,
            "prefetch_transfers": _prefetch_transfers.value,
            "nan_ok": None,
            "nan_guard": nan_guard,
            "fast_path": fast_path,
            "compile": compiled,
            "execute_s": execute_s,
        })

    def _finalize_fetches(self, fetches, return_numpy, lazy, eager_idx):
        if return_numpy:
            if not lazy:
                return [np.asarray(v) for v, _ln, _sln in fetches]
            # lazy: dispatch of the next step is not blocked on this step's
            # device->host copies; fetches that may alias donated state
            # buffers (persistable names, or values the trace saw aliasing
            # new_state) are materialized eagerly so a later step's buffer
            # donation can never invalidate a value already handed out.
            return [np.asarray(v) if i in eager_idx else LazyFetch(v)
                    for i, (v, _ln, _sln) in enumerate(fetches)]
        # return_numpy=False: plain fetches stay DEVICE arrays; fetches
        # carrying ragged companions come back as host-side LoDArray (the
        # reference's fetched LoDTensors are host-side too) — that implies
        # a device->host copy for exactly those fetches.
        out = []
        for v, ln, sln in fetches:
            if ln is not None:
                out.append(LoDArray(
                    np.asarray(v), np.asarray(ln),
                    None if sln is None else np.asarray(sln)))
            else:
                out.append(v)
        return out

    # -- fast-path dispatch --------------------------------------------------
    @staticmethod
    def _is_plain_array(v):
        """ndarray or jax device array — the feed kinds the fast path can
        hand to the compiled runner without conversion."""
        return isinstance(v, (np.ndarray, np.generic)) or (
            type(v).__module__.split(".", 1)[0] in ("jax", "jaxlib"))

    @staticmethod
    def _is_device_array(v):
        """A jax array: already on device, so feed preparation must never
        pull it back to host (the async feed pipeline's whole point)."""
        return type(v).__module__.split(".", 1)[0] in ("jax", "jaxlib")

    def plan_feed_shardings(self, program, feeds):
        """The sharding each feed will carry under the attached mesh —
        ``NamedSharding(mesh, P('dp'))`` for declared data vars whose
        batch divides the dp axis, replicated otherwise; ``None`` when no
        mesh is attached (single-device placement).  This is the SAME
        decision the compiled runner bakes into its jit ``in_shardings``,
        factored out so the async device-feed pipeline
        (``reader.device_prefetch``) can ``device_put`` batches with
        matching placement and the step never re-shards them."""
        mesh = self._mesh
        if mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp_size = int(axis_sizes.get("dp", int(np.prod(mesh.devices.shape))))
        has_dp = "dp" in mesh.axis_names
        repl = NamedSharding(mesh, P())
        # only declared data vars batch-shard on dp: a coincidentally
        # batch-divisible non-data feed (e.g. a [ndev*k, d] constant
        # table) must stay replicated
        data_names = {v.name for v in program.list_vars()
                      if getattr(v, "is_data", False)}
        return {
            n: NamedSharding(mesh, P("dp"))
            if has_dp and n in data_names and np.ndim(v) >= 1
            and np.shape(v)[0] % dp_size == 0
            else repl
            for n, v in feeds.items()
        }

    def _bind(self, bound_key, program, scope, feed, feed_arrays, state_in,
              new_state, wb_owners, key_owner, entry, fetch_names,
              reader_fed, nan_guard=False):
        """Create/refresh the fast-path binding after a successful slow run.

        Only steady-state runs bind: reader-driven feeds can't be replayed,
        non-array feeds need per-step conversion, and a step that CREATED a
        persistable (a new_state key absent from the incoming state) hasn't
        settled — the next run's state set differs, so binding now would
        replay a stale one."""
        if reader_fed or not set(new_state) <= set(state_in):
            return
        plan = {}
        for name, val in feed.items():
            if isinstance(val, (LoDArray, tuple, list)) or not self._is_plain_array(val):
                return
            prepared = feed_arrays.get(name)
            if prepared is None:
                return
            cast = prepared.dtype if str(prepared.dtype) != str(val.dtype) else None
            plan[name] = (tuple(val.shape), val.dtype, cast)
        if len(plan) != len(feed_arrays):  # ragged companions present
            return

        b = _BoundProgram()
        b.program = program  # strong ref keeps the id()-based key stable
        b.scope = weakref.ref(scope)
        b.version = program.version
        b.chain = [(weakref.ref(s), v) for s, v in _scope_chain_token(scope)]
        b.feed_plan = plan
        b.state_owners = [(n, weakref.ref(scope._owner(n))) for n in state_in]
        b.wb_owners = {n: weakref.ref(o) for n, o in wb_owners.items()}
        b.key_owner = weakref.ref(key_owner)
        b.entry = entry
        b.fetch_names = tuple(fetch_names)
        persistable = program.persistable_names()
        b.eager_idx = frozenset(
            i for i, f in enumerate(fetch_names) if f in persistable)
        b.alias_cell = getattr(entry, "_alias_cell", None)
        b.nan_debug = _NAN_DEBUG["on"]
        b.guard = bool(nan_guard
                       and getattr(entry, "_guard_cell", {}).get("emits"))
        self._bound.pop(bound_key, None)  # re-insert at the young end
        while len(self._bound) >= self._bound_cap:
            self._bound.pop(next(iter(self._bound)))  # oldest entry
            _bound_evicts.inc()
        self._bound[bound_key] = b

    def _run_bound(self, bound, program, scope, feed, return_numpy,
                   recording=False, t_run0=0.0):
        """One step through the bound fast path; returns _BOUND_MISS when
        any precondition drifted (program edited, scope mutated or died,
        feed shape/dtype changed, state var gone) — caller evicts the
        entry and falls back to the slow path, which re-derives everything
        and rebinds.  ``recording``/``t_run0`` come from run()'s entry so
        a fast-path step record reports the same dispatch-side wall
        duration the slow path does."""
        if bound.version != program.version or bound.nan_debug != _NAN_DEBUG["on"]:
            return _BOUND_MISS
        if bound.scope() is not scope:  # dead ref, or id() reuse after GC
            return _BOUND_MISS
        for sref, v in bound.chain:
            s = sref()
            if s is None or s._version != v:
                return _BOUND_MISS
        if _prof.is_profiling():
            return _BOUND_MISS  # keep the slow path's instrumentation
        tel = self._telemetry
        with tel.span("executor.prepare_feed"):
            plan = bound.feed_plan
            if len(feed) != len(plan):
                return _BOUND_MISS
            feed_arrays = {}
            for name, val in feed.items():
                p = plan.get(name)
                shape = getattr(val, "shape", None)
                dtype = getattr(val, "dtype", None)
                if (p is None or shape is None or dtype is None
                        or tuple(shape) != p[0] or dtype != p[1]
                        # non-plain feeds (LoDArray whose .shape/.dtype
                        # delegate to .data, a LazyFetch fed back in, ...)
                        # go through the slow path's full _prepare_feed,
                        # never a blind asarray
                        or not self._is_plain_array(val)):
                    return _BOUND_MISS
                if p[2] is not None:
                    # ndarray: one astype, no asarray round-trip
                    # (copy=False is a no-op here since p[2] != the feed
                    # dtype by plan construction, but keeps an accidental
                    # same-dtype plan from copying); device array: cast
                    # stays on device
                    if isinstance(val, (np.ndarray, np.generic)):
                        val = val.astype(p[2], copy=False)
                        _feed_copies.inc()
                    else:
                        val = val.astype(p[2])
                feed_arrays[name] = val
        with tel.span("executor.bind"):
            state_in = {}
            for name, oref in bound.state_owners:
                owner = oref()
                if owner is None:
                    return _BOUND_MISS
                v = owner.vars.get(name)
                if v is None:
                    return _BOUND_MISS
                state_in[name] = v
            key_owner = bound.key_owner()
            if key_owner is None:
                return _BOUND_MISS
            key = key_owner.vars.get("__rng_key__")
            if key is None:
                return _BOUND_MISS

        if resilience._feed_fault is not None:  # fault-injection harness
            feed_arrays = resilience._feed_fault(feed_arrays)
        self._last_guard_flag = None  # never report a previous run's verdict
        with tel.span("executor.dispatch") as call:
            fetches, new_state, new_key = bound.entry(state_in, feed_arrays, key)
        with tel.span("executor.writeback"):
            if bound.guard:
                self._last_guard_flag = fetches[-1][0]
                fetches = fetches[:-1]

            wb = bound.wb_owners
            for name, val in new_state.items():
                oref = wb.get(name)
                owner = oref() if oref is not None else None
                if owner is None:  # defensive: retrace surfaced a new name
                    owner = scope._owner(name) or scope
                    wb[name] = weakref.ref(owner)
                owner.vars[name] = val
            key_owner.vars["__rng_key__"] = new_key

        eager = bound.eager_idx
        cell = bound.alias_cell
        if cell is not None and cell.get("idx"):
            eager = eager | cell["idx"]
        if recording:
            self._emit_step(bound.program, time.perf_counter() - t_run0,
                            call.duration, fast_path=True, compiled=False,
                            nan_guard=bound.guard)
        return self._finalize_fetches(fetches, return_numpy,
                                      lazy=self.lazy_fetches, eager_idx=eager)

    # -- internals -----------------------------------------------------------
    def _pserver_clients(self, program):
        from .transpiler.pserver_runtime import PSClient

        if not hasattr(self, "_ps_clients"):
            self._ps_clients = {}
        for op in program.global_block().ops:
            if op.type in ("send", "recv"):
                for ep in op.attrs.get("endpoints", []):
                    if ep not in self._ps_clients:
                        self._ps_clients[ep] = PSClient(ep)
        return self._ps_clients

    def _prepare_feed(self, program, feed):
        out = {}
        blk = program.global_block()
        for name, val in feed.items():
            if isinstance(val, LoDArray):
                arr = np.asarray(val.data)
                if blk.has_var(name):
                    self._check_feed_shape(name, blk.var(name), arr)
                out[name] = arr
                out[name + "@LENGTHS"] = np.asarray(val.lengths)
                if val.sub_lengths is not None:
                    out[name + "@SUBLENGTHS"] = np.asarray(val.sub_lengths)
                _feed_copies.inc()
            elif isinstance(val, tuple) and len(val) == 2:
                arr = np.asarray(val[0])
                if blk.has_var(name):
                    self._check_feed_shape(name, blk.var(name), arr)
                out[name] = arr
                out[name + "@LENGTHS"] = np.asarray(val[1], dtype=np.int32)
                _feed_copies.inc()
            elif self._is_device_array(val):
                # already-on-device feed (reader.device_prefetch, a fetch
                # fed back in): validate shape by metadata and, if the
                # dtype drifted from the declared var, cast ON DEVICE —
                # this branch must never pull the array back to host
                if blk.has_var(name):
                    var = blk.var(name)
                    want = var.dtype
                    if want is not None and str(val.dtype) != _step_dtype(
                            core.np_dtype(want)):
                        val = val.astype(core.np_dtype(want))
                    self._check_feed_shape(name, var, val)
                out[name] = val
            else:
                arr = np.asarray(val)
                if blk.has_var(name):
                    var = blk.var(name)
                    want = var.dtype
                    if want is not None and arr.dtype != core.np_dtype(want):
                        arr = arr.astype(core.np_dtype(want), copy=False)
                    self._check_feed_shape(name, var, arr)
                out[name] = arr
                _feed_copies.inc()
        return out

    @staticmethod
    def _check_feed_shape(name, var, arr):
        """Match the feed against the declared var shape (dynamic dims are
        -1) so shape mistakes fail HERE, by name, instead of as a raw XLA
        dot/conv shape error deep in the traced step.

        Right-aligned comparison honoring the fluid feeding conventions:
        leading dynamic dims may be omitted (a dense [batch, d] feed to a
        lod-declared (-1, -1, d) var), a declared trailing unit dim may be
        squeezed (int label sequences), but the feed may never have MORE
        dims than declared and every static dim must agree."""
        declared = var.shape
        if not declared:
            return

        def matches(decl):
            if len(arr.shape) > len(decl):
                return False
            for d, a in zip(reversed(decl), reversed(arr.shape)):
                if d != -1 and int(d) != int(a):
                    return False
            # only DYNAMIC leading dims may be omitted
            return all(d == -1 for d in decl[: len(decl) - len(arr.shape)])

        ok = matches(declared)
        if not ok and declared[-1] == 1:
            ok = matches(declared[:-1])
        if not ok:
            raise ValueError(
                "feed %r has shape %s but the program declares %s "
                "(-1 = any); check the data layer's shape"
                % (name, tuple(arr.shape), tuple(declared))
            )

    def _collect_state(self, program, scope):
        """Persistable vars resolved through the scope's ancestor chain
        (reference Scope::FindVar), so a new_scope() child sees the
        parent's parameters."""
        state = {}
        for name in program.persistable_names():
            owner = scope._owner(name)
            if owner is not None and owner.vars[name] is not None:
                state[name] = owner.vars[name]
        return state

    def _rng_key(self, program, scope):
        # core.safe_import_jax: the FIRST `import jax` in a process consumes
        # ambient np.random state during import, which would make the very
        # first run's seed draw differ from every later run's under the
        # same np.random.seed (observed: first-call init != later-call
        # init).  The guarded import keeps `np.random.seed(N)` pinning the
        # startup draw regardless of import timing.
        from .core import safe_import_jax

        jax = safe_import_jax()
        owner = scope._owner("__rng_key__")
        k = owner.vars["__rng_key__"] if owner is not None else None
        if k is None:
            seed = program.random_seed or np.random.randint(1, 2**31 - 1)
            k = jax.random.PRNGKey(seed)
        return k

    def _build(self, program, feed_names, fetch_names, state_names,
               nan_guard=False):
        import jax

        # compute-introspection capture: one analysis per built ENTRY,
        # registered under the same program tag step records carry;
        # armed/disarmed per call so enabling the plane mid-run captures
        # on the next step
        prog_tag = "%x:v%d" % (id(program), getattr(program, "version", 0))
        cap_cell = {"done": False}

        persistable_names = program.persistable_names()
        # a fetch that aliases a state output (fetching a param directly, or
        # an assign of one) must not be handed out lazily: the next step
        # donates the state buffer and would invalidate the fetch before the
        # caller reads it.  Tracer identity at trace time records exactly
        # which fetch indices alias; the fast path materializes those
        # eagerly.  Populated on (re)trace, so the cell is shared with the
        # runner via an attribute.
        alias_cell = {"idx": None}
        # whether the guarded step actually emits a verdict pseudo-fetch
        # (False for steps that write no state — nothing to skip, so the
        # guard compiles to a no-op); populated at trace time
        guard_cell = {"emits": False}

        def trace_step(state, feeds, key):
            """One symbolic step.  Returns, beyond the fetches/state/key, the
            set of persistable names the block actually WROTE (tracer
            identity vs the input) — pass-through state can then stay out of
            the jit outputs entirely, which is what makes eval/inference
            loops dispatch in O(1) instead of O(params)."""
            use_key, next_key = jax.random.split(key)
            env = {}
            env.update(state)
            env.update(feeds)
            ctx = LoweringContext(program, env, use_key, mesh=self._mesh)
            # names the step must surface even under recompute pruning
            ctx.keep_names = tuple(fetch_names)
            lower_block(ctx, program.global_block())
            fetches = []
            for f in fetch_names:
                if f not in env:
                    raise KeyError("fetch target %r was not produced by the program" % f)
                # carry the ragged companions out so run() can hand back a
                # structured LoDArray (reference: fetched LoDTensors keep
                # their lod when return_numpy=False)
                fetches.append(
                    (env[f], env.get(f + "@LENGTHS"), env.get(f + "@SUBLENGTHS")))
            new_state = {n: v for n, v in env.items() if n in persistable_names}
            written = {n for n, v in new_state.items() if v is not state.get(n)}
            # a fetch aliasing a state OUTPUT shares the buffer a later
            # step donates; one aliasing a state INPUT (assign of a param,
            # the param itself in an eval step) may share the scope-held
            # buffer a later *training* step donates.  Both must be
            # materialized eagerly by the fast path.
            state_vals = list(new_state.values()) + list(state.values())
            alias = frozenset(
                i for i, (v, _ln, _sln) in enumerate(fetches)
                if any(v is sv for sv in state_vals))
            prev = alias_cell["idx"]
            alias_cell["idx"] = alias if prev is None else (prev | alias)
            if nan_guard:
                # Step guard: ONE fused finiteness reduction over the
                # parameter gradients + float fetches (the loss), then the
                # whole state update is gated on-device — a bad step's
                # parameters/optimizer state pass through bitwise-unchanged
                # and no host sync happens unless the caller reads the
                # verdict (last_step_ok).  The verdict rides as a trailing
                # pseudo-fetch so the runner plumbing (mesh shardings,
                # donation, lazy fetches) needs no second output structure.
                # A step that writes NO state (eval/inference) has nothing
                # to skip: the guard emits nothing and the executable is
                # identical to the unguarded one (guard_cell records that,
                # so run() knows not to pop a verdict).
                import jax.numpy as jnp

                gated = {}
                gated_any = False
                probes = None
                for n, v in new_state.items():
                    old = state.get(n)
                    if (n in written and old is not None
                            and getattr(old, "shape", None) == getattr(v, "shape", None)
                            and getattr(old, "dtype", None) == getattr(v, "dtype", None)):
                        if probes is None:
                            probes = []
                            for pname in persistable_names:
                                g = env.get(grad_var_name(pname))
                                if (g is not None and hasattr(g, "dtype")
                                        and jnp.issubdtype(g.dtype, jnp.inexact)):
                                    probes.append(jnp.sum(g.astype(jnp.float32)))
                            for fv, _ln, _sln in fetches:
                                if (hasattr(fv, "dtype")
                                        and jnp.issubdtype(fv.dtype, jnp.inexact)):
                                    probes.append(jnp.sum(fv.astype(jnp.float32)))
                            good = (jnp.isfinite(jnp.stack(probes).sum())
                                    if probes else jnp.asarray(True))
                        gated[n] = jnp.where(good, v, old)
                        gated_any = True
                    else:
                        gated[n] = v
                guard_cell["emits"] = gated_any
                if gated_any:
                    new_state = gated
                    fetches = fetches + [(good, None, None)]
            return fetches, new_state, written, next_key

        mesh = self._mesh
        if mesh is None:
            # Non-mesh runner: state is split into the MUTATED subset
            # (donated, returned) and the READ-ONLY rest (plain inputs,
            # never donated — donating them would let XLA recycle their
            # buffers for same-shaped outputs and kill the scope's copy,
            # and returning them would pay one output ArrayImpl per var per
            # step for values that never change).  The written set is
            # discovered exactly, by one abstract trace (no compile) on the
            # first call.
            cells = {"mut": None, "mut_set": None}

            def probe(state, feeds, key):
                _, _, written, _ = trace_step(state, feeds, key)
                cells["mut"] = tuple(sorted(written))
                cells["mut_set"] = frozenset(written)
                return 0

            def split_step(mut, ro, feeds, key):
                state = dict(ro)
                state.update(mut)
                fetches, new_state, written, next_key = trace_step(state, feeds, key)
                out_names = cells["mut"]
                extra = [n for n in written if n not in cells["mut_set"]]
                if extra:
                    raise RuntimeError(
                        "internal: retrace wrote persistables %s not seen by "
                        "the discovery trace" % extra)
                new_mut = {n: new_state[n] for n in out_names if n in new_state}
                return fetches, new_mut, next_key

            # everything the step takes and returns lives on the place's
            # device, said explicitly: jax keys its executables on whether
            # each argument is committed, so leaving placement to "wherever
            # the arrays happen to be" compiled the same step up to four
            # times (host vs prefetched feeds x fresh vs stepped state).
            # jit re-places a committed argument that sits whole on another
            # device by itself; what it refuses is one SPLIT over devices
            # (state a ParallelExecutor tp-sharded in the same scope), so
            # the runner gathers those onto the place's device first.
            device = self.place.jax_device()
            home = jax.sharding.SingleDeviceSharding(device)
            jitted = jax.jit(split_step, donate_argnums=(0,),
                             in_shardings=home, out_shardings=home)
            _filter_donation_warning_once()

            def runner(state, feeds, key):
                mut_set = cells["mut_set"]
                if mut_set is None:
                    jax.eval_shape(probe, state, feeds, key)
                    mut_set = cells["mut_set"]
                mut = {}
                ro = {}
                for n, v in state.items():
                    sh = getattr(v, "sharding", home)
                    if sh is not home and not sh.is_fully_replicated:
                        v = jax.device_put(v, home)
                    if n in mut_set:
                        mut[n] = v
                    else:
                        ro[n] = v
                if not cap_cell["done"] and _xla_stats.active():
                    # capture BEFORE the first real call so the gauges are
                    # live by the time the step returns; lower+compile is
                    # pure (no state/RNG effects), so the step itself is
                    # bitwise-unaffected
                    cap_cell["done"] = True
                    _xla_stats.capture_jitted(
                        prog_tag, jitted, (mut, ro, feeds, key))
                return jitted(mut, ro, feeds, key)

            runner._alias_cell = alias_cell
            runner._guard_cell = guard_cell
            return runner

        def step(state, feeds, key):
            fetches, new_state, _written, next_key = trace_step(state, feeds, key)
            return fetches, new_state, next_key

        # SPMD: feeds batch-sharded on 'dp'; state replicated on a 1-D mesh,
        # or Megatron tp-sharded (parallel/tp.py) when the mesh carries a
        # 'tp' axis.  XLA's partitioner inserts the gradient psum / tp
        # collectives over ICI automatically (the reference built NCCL
        # all-reduce ops by hand: framework/details/multi_devices_graph_builder.cc).
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp_size = int(axis_sizes.get("dp", int(np.prod(mesh.devices.shape))))
        tp_size = int(axis_sizes.get("tp", 1))
        repl = NamedSharding(mesh, P())
        cell = {}
        rules = self._sharding_rules

        def runner(state, feeds, key):
            jitted = cell.get("jit")
            if jitted is None:
                # same decision the device-feed prefetcher uses, so a
                # batch it committed ahead of time already matches the
                # in_shardings baked in here
                feed_shardings = cell["feed_sh"] = self.plan_feed_shardings(
                    program, feeds)
                if tp_size > 1:
                    from .parallel.tp import make_param_shardings

                    state_shardings = make_param_shardings(state, mesh, rules=rules)
                else:
                    state_shardings = {n: repl for n in state}
                # pipeline-stacked params (layers.Pipeline) shard their
                # leading stage axis over 'pp' — each device holds ONE
                # stage's slice; optimizer accumulators follow their param
                # (name-prefixed, same leading dim)
                pp_size = int(axis_sizes.get("pp", 1))
                if pp_size > 1:
                    stacked = {
                        v.name for v in program.list_vars()
                        if getattr(v, "pp_stacked", False)
                    }
                    if stacked:
                        # leading dim == pp (plain GPipe) or a multiple of
                        # it (circular: L = pp * repeats rows, device-major
                        # layout — each device's slices are contiguous)
                        pp_shard = NamedSharding(mesh, P("pp"))
                        for n, v in state.items():
                            if (np.ndim(v) < 1
                                    or np.shape(v)[0] < pp_size
                                    or np.shape(v)[0] % pp_size):
                                continue
                            if n in stacked or any(
                                    n.startswith(s + "_") for s in stacked):
                                state_shardings[n] = pp_shard
                # ZeRO (BuildStrategy.zero_stage): partition optimizer
                # accumulators (stage>=1) and parameters (stage>=3) over
                # 'dp' — each dp rank then holds 1/dp of the state and
                # computes 1/dp of the update; XLA's partitioner inserts
                # the use-site all-gathers and turns the gradient
                # psum+slice into a reduce-scatter.  Stage 2 (gradient
                # partitioning) has no separate lever here: gradients are
                # not persistent state under jit, their sharding follows
                # the update site.
                zero = int(getattr(self, "_zero_stage", 0) or 0)
                if zero >= 1 and "dp" in mesh.axis_names and dp_size > 1:
                    tagged = {
                        v.name for v in program.list_vars()
                        if getattr(v, "is_optimizer_state", False)
                    }
                    if zero >= 3:
                        tagged |= {
                            v.name for v in program.list_vars()
                            if isinstance(v, Parameter)
                        }

                    def with_dp(n, v):
                        # largest dim divisible by dp that the current spec
                        # leaves free; None when nothing divides (tiny /
                        # scalar state stays replicated)
                        cur = tuple(state_shardings.get(n, repl).spec)
                        shape = np.shape(v)
                        cur = cur + (None,) * (len(shape) - len(cur))
                        for i in sorted(range(len(shape)),
                                        key=lambda i: -shape[i]):
                            if (shape[i] >= dp_size
                                    and shape[i] % dp_size == 0
                                    and cur[i] is None):
                                spec = list(cur)
                                spec[i] = "dp"
                                return NamedSharding(mesh, P(*spec))
                        return None

                    for n, v in state.items():
                        if n in tagged:
                            s = with_dp(n, v)
                            if s is not None:
                                state_shardings[n] = s
                # pin state OUT-shardings too: the partitioner would
                # otherwise hand state out however propagation landed (a
                # ZeRO-updated param emerges dp-sharded) and the reshard
                # back to the declared sharding would run as a host-issued
                # device_put after every step; pinned, it folds into the
                # compiled step.  new_state's keys normally equal state's;
                # a program whose step CREATES a persistable (keys differ
                # -> pytree structure error on first call) falls back to
                # unpinned outputs + the explicit conform below.
                cell["in_sh"] = (state_shardings, feed_shardings, repl)
                jitted = jax.jit(
                    step,
                    in_shardings=cell["in_sh"],
                    out_shardings=(None, dict(state_shardings), None),
                    donate_argnums=(0,),
                )
                cell["jit"] = jitted
                cell["out_pinned"] = True
                cell["state_shardings"] = state_shardings
            # XLA's partitioner may hand state OUT in different shardings
            # than the declared in_shardings (e.g. a bias left tp-sharded
            # after propagation, or a ZeRO-updated param emerging
            # dp-sharded); jit refuses committed args that disagree, so
            # reshard drifted entries explicitly (no-op when they match).
            # Incoming state is normalized too for externally loaded
            # arrays (checkpoint restore, host numpy).
            state_shardings = cell["state_shardings"]

            def conform(d):
                return {
                    n: v
                    if n not in state_shardings
                    or getattr(v, "sharding", None) == state_shardings[n]
                    else jax.device_put(v, state_shardings[n])
                    for n, v in d.items()
                }

            state = conform(state)
            # committed device FEEDS that disagree with the baked
            # in_shardings (a prefetcher running under a since-changed
            # mesh, a user device_put to one device) are re-placed here
            # instead of tripping jit's committed-argument check; host
            # feeds pass straight through — jit shards them itself
            feed_sh = cell["feed_sh"]
            conformed = None
            for n, v in feeds.items():
                want_sh = feed_sh.get(n)
                if (want_sh is not None and self._is_device_array(v)
                        and getattr(v, "sharding", None) != want_sh):
                    if conformed is None:
                        conformed = dict(feeds)
                    conformed[n] = jax.device_put(v, want_sh)
            if conformed is not None:
                feeds = conformed
            if not cap_cell["done"] and _xla_stats.active():
                cap_cell["done"] = True
                _xla_stats.capture_jitted(
                    prog_tag, cell["jit"], (state, feeds, key),
                    num_devices=int(np.prod(mesh.devices.shape)))
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                try:
                    fetches, new_state, next_key = cell["jit"](state, feeds, key)
                except (TypeError, ValueError):
                    if not cell.get("out_pinned"):
                        raise
                    # Only the documented structure-change case falls back
                    # (the step CREATES a persistable, so new_state's keys
                    # differ from state's and the pinned out_shardings
                    # pytree no longer matches).  Verify by abstract
                    # evaluation — cheap, no compile — and re-raise
                    # genuine user errors instead of silently re-jitting
                    # down the unpinned path.
                    try:
                        _, ns_aval, _ = jax.eval_shape(step, state, feeds, key)
                        structure_changed = set(ns_aval) != set(state)
                    except Exception:
                        structure_changed = False  # original error stands
                    if not structure_changed:
                        raise
                    cell["jit"] = jax.jit(
                        step, in_shardings=cell["in_sh"], donate_argnums=(0,))
                    cell["out_pinned"] = False
                    fetches, new_state, next_key = cell["jit"](state, feeds, key)
            if cell.get("out_pinned"):
                return fetches, new_state, next_key
            # unpinned fallback: keep the AT-REST contract explicitly —
            # scope state between runs conforms to the declared shardings
            return fetches, conform(new_state), next_key

        runner._alias_cell = alias_cell
        runner._guard_cell = guard_cell
        return runner

    def close(self):
        """Drop compiled executables and notify pservers this trainer is done
        (reference: Executor.close sends the barrier/exit RPC)."""
        self._cache.clear()
        self._bound.clear()
        for c in getattr(self, "_ps_clients", {}).values():
            c.shutdown_server()
            c.close()
        self._ps_clients = {}
