"""Paged KV cache: preallocated page pools + per-sequence page tables, and
whatever else a model keeps per page or per slot, as ONE pytree.

**One cache, described by the model.**  ``PagedKVCache.pools`` is a dict of
arrays by leaf name that the scheduler threads whole through every step
program (donated on a TPU).  *Page-indexed* leaves share ONE allocator and
ONE page table and have the page axis at 1: ``"k"`` and ``"v"`` (the layers
that hold paged K/V; a model with ``num_layers == 0`` has neither) and a
model's further ``page_pools`` (one row per ``tokens_per_row`` tokens of a
page, e.g. pooled keys for block selection, or an MLA model's one latent row
a token: its ONLY page-indexed leaf).
*Slot-indexed* leaves (``slot_state``, the slot axis at 1) hold recurrent
state of the sequence seated in a slot; the cache only allocates and resets
them — a sequence's first prefill chunk takes its slot's state as zero inside
the chunk program, so nothing is zeroed per admission here.  The text below is
about the K/V leaves; every page-indexed leaf follows the same allocator.

**Page groups.**  A model whose layers do not all keep the same positions
states ``page_groups``: an ordered ``{name: dict(window=None | W,
aligned=False, page_size=None, num_pages=N)}``, and for each ``page_pools``
leaf the ``group`` it belongs to.  The FIRST group is the cache's own
allocator (everything below; it keeps every position, and leaves that name no
group are its: ``k`` / ``v`` where the model states ``num_layers``, or, for a
model whose K and V rows live in a further group, only derived rows such as
the summaries of EvaByte's closed windows, the K and V leaves then being
``page_pools`` of the windowed group).  Every further group
(:class:`PageGroup`) has its own pool length, scratch page 0, free list,
refcounts and RESERVATION count, and the scheduler keeps a page table a group.
A group may state a ``page_size`` of its own, in TOKENS (a leaf of one row
for every 16 tokens fills a page of 64 rows with 1024 tokens); a group that
states none has the cache's.  A group with a ``window`` keeps a bounded span of
a sequence's positions, in one of two forms.  SLIDING (the default): a query
at ``t`` reads ``t - W + 1 .. t``; pages are handed out as positions reach
them and go back to the group's free list the moment every position on them is
out of every later query's window, so its table is a RING of the bound's width
(logical page ``p`` in column ``p % width``).  ALIGNED (``aligned=True``): a
query at ``t`` reads ``(t // W) * W .. t``; the window fills to ``W /
page_size`` pages, ALL of which go back at once when ``t`` reaches the next
multiple of ``W``, and the table, ``W / page_size`` columns wide, is filled
again from column 0.  Either way a slot reserves a bound that does not grow
with the sequence (or its own pages, where it is shorter than the bound:
``slot_bound``).  The prefix cache, sessions and handoff address the first
group's pages only and are refused for a model with a further group
(``decode_scheduler.py``).  One group and no window is every model that states
nothing: the same leaves, table and programs as before.

The memory half of the continuous-batching decode runtime (vLLM /
PagedAttention, Kwon et al. SOSP'23): instead of one contiguous
``[B, max_seq_len, ...]`` cache slab per sequence — whose worst-case
reservation is what caps batch size long before compute does — keys and
values live in fixed-size PAGES of a pool preallocated once,
``[L, num_pages, page_size, H*D]``, and each sequence owns an ordered list
of page ids (its page table).  Admission allocates, retirement frees, and
the pool's occupancy — not a worst-case rectangle — is what bounds how
many sequences decode concurrently.

**Stored shape of K and V.**  A page row holds all heads FOLDED into its last axis,
head-major (head ``h`` is ``[..., h*D:(h+1)*D]``), and the layers are
stacked in front.  That is the shape whose default device layout is
row-major with unpadded ``(page_size, H*D)`` tiles (``H*D`` a multiple of
128 at every width that matters), which is what the paged kernels read:
a trailing ``[H, D]`` = 8 x 64 pads in a bf16 tile, the compiler then
keeps the pool pages-minor, and every step program re-lays the WHOLE
pool out twice.  The step programs scatter folded rows into the stack
and the kernels address it in place by ``(layer, page)``
(``parallel/flash_attention.py``), so nothing pool-sized or layer-sized
is ever materialised.  The page axis is axis 1: host offload, sessions
and migration index ``[:, idx]``.

Allocation discipline (decode_scheduler.py is the only caller):

* **allocate-on-admit**: a sequence reserves ``ceil((prompt_len +
  max_new_tokens) / page_size)`` pages up front, so decode can never hit
  mid-flight pool exhaustion — a request that doesn't fit simply waits in
  the admission queue.  The cost is internal fragmentation (reserved but
  not-yet-written slots), published as a gauge rather than hidden.
* **free-on-retire**: the whole reservation returns the moment the
  sequence finishes/sheds.  Freed pages are NOT scrubbed — stale values
  are unreachable because every read masks by the owning sequence's
  ``kv_lens``.
* **page 0 is the scratch page**: never allocated.  Inactive decode slots
  point their whole page table at it, so the fixed-shape decode step can
  unconditionally scatter its per-slot k/v write — inactive slots write
  garbage to scratch instead of needing a ragged dispatch.

**Prefix caching** (ISSUE 15) layers block-level KV *sharing* on top —
the vLLM move of treating the page pool as a content-addressed cache:

* every page is REFCOUNTED; ``alloc`` hands out rc=1 pages, a prefix hit
  increfs, ``free`` decrefs, and a page is reusable only at rc=0.
* a **content-hash index** maps a chain hash — hashed over whole
  page-size token blocks, each link folding in the previous page's hash,
  so a hit certifies the entire prefix, not just one block — to the page
  holding that block's K/V.  Only FULL pages are ever indexed: a partial
  page still has decode tokens appended, a full prefix page is immutable
  (append-only while shared), so copy-on-write is never needed.
* ``lookup_prefix`` walks a prompt's leading full pages through the
  index and increfs the hits; the scheduler maps them read-only and
  prefills only the tail.  ``register_prefix`` publishes freshly
  written full pages.
* rc=0 pages whose content is indexed are not freed — they park in an
  **LRU** list and keep answering hits until capacity pressure evicts
  them (``alloc`` evicts least-recently-used rc=0 pages after the plain
  free list runs dry, dropping their index entries).

Reuse is observable: ``serving.decode.kv_hit_pages`` /
``kv_miss_pages`` count probe outcomes, ``kv_evictions`` counts
capacity evictions, ``kv_shared_pages`` gauges pages live in 2+ page
tables right now, and ``kv_cached_pages`` gauges the rc=0 LRU pool.

The pools are jax arrays updated FUNCTIONALLY (``x.at[...].set``) by the
pure helpers below, which the scheduler jits into its prefill/decode
steps; the cache object holds the current buffers plus the host-side
allocator state and telemetry gauges (``serving.decode.kv_*``).
"""
from __future__ import annotations

import collections
import hashlib

import numpy as np

from .. import observability as _obs
from .errors import ServingError

__all__ = ["PagedKVCache", "PageGroup", "write_token_kv"]

_pages_total = _obs.gauge("serving.decode.kv_pages_total")
_pages_used = _obs.gauge("serving.decode.kv_pages_used")
_occupancy = _obs.gauge("serving.decode.kv_occupancy")
_fragmentation = _obs.gauge("serving.decode.kv_fragmentation")
_hit_pages = _obs.counter("serving.decode.kv_hit_pages")
_miss_pages = _obs.counter("serving.decode.kv_miss_pages")
_evictions = _obs.counter("serving.decode.kv_evictions")
_shared_pages = _obs.gauge("serving.decode.kv_shared_pages")
_cached_pages = _obs.gauge("serving.decode.kv_cached_pages")
_page_bytes = _obs.gauge("serving.cache.page_bytes")
_state_bytes = _obs.gauge("serving.cache.state_bytes")


def _group_counters(group):
    """``(pages_taken, pages_released)`` of one page group: pages its
    allocator handed out, and pages a LIVE sequence gave back because they
    fell out of the group's window (a retirement's pages are in neither)."""
    return tuple(_obs.counter("serving.cache." + name, {"group": group})
                 for name in ("pages_taken", "pages_released"))


def write_token_kv(k_pool, v_pool, k_tok, v_tok, pages, offsets):
    """Scatter one decode step's per-slot token k/v into the pools.

    k_tok/v_tok: ``[L, S, H, D]``; ``pages``/``offsets``: ``[S]`` int32 —
    slot s's token lands at ``pool[:, pages[s], offsets[s]]``.  Inactive
    slots aim at the scratch page (duplicate scratch writes are fine:
    nothing ever reads it).  Returns the updated ``(k_pool, v_pool)``.
    """
    L, S = k_tok.shape[:2]
    HD = k_pool.shape[3]
    return (k_pool.at[:, pages, offsets].set(k_tok.reshape(L, S, HD)),
            v_pool.at[:, pages, offsets].set(v_tok.reshape(L, S, HD)))


class PageGroup:
    """The allocator of one FURTHER group of page-indexed leaves (module
    docstring, "Page groups"): ``num_pages`` pages with scratch page 0, a free
    list, a refcount a page, and a count of pages RESERVED to seated
    sequences.  A sequence reserves at admission (``reserve``; admission
    waits while ``can_reserve`` is false), takes pages one at a time as its
    positions reach them (``alloc``, which cannot fail under a reservation)
    and, in a group with a ``window``, gives back each page that fell out of
    it (``free``); retirement frees the rest and ``unreserve``s.
    ``page_size`` is the group's own, in tokens.  ``aligned`` is the window's
    form: False, the last ``window`` positions (a ring that rotates); True,
    the positions from the last multiple of ``window`` on (a table that is
    emptied whole at each multiple and filled again from its first column)."""

    def __init__(self, name, num_pages, page_size, window=None,
                 aligned=False):
        if num_pages < 2:
            raise ServingError(
                "page group %r: num_pages must be >= 2 (page 0 is the "
                "scratch page), got %d" % (name, num_pages))
        if window is not None and int(window) < 1:
            raise ServingError("page group %r: window must be >= 1" % name)
        if aligned and (window is None or int(window) % int(page_size)):
            raise ServingError(
                "page group %r: an aligned window is whole pages (window %r, "
                "page_size %d)" % (name, window, page_size))
        self.name = name
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.window = None if window is None else int(window)
        self.aligned = bool(aligned)
        self._free = collections.deque(range(1, self.num_pages))
        self._rc = [0] * self.num_pages
        self._used = 0
        self.reserved = 0
        self.released = 0       # pages given back by a window, ever
        self.taken = 0          # pages handed out, ever
        self._taken, self._released = _group_counters(name)

    @property
    def free_pages(self):
        return len(self._free)

    @property
    def used_pages(self):
        return self._used

    def occupancy(self):
        usable = self.num_pages - 1
        return self._used / usable if usable else 0.0

    def slot_bound(self, tokens, widest_chunk):
        """Pages a sequence of ``tokens`` positions reserves: every page of
        them, and with a window no more than a slot can hold live at once —
        the window plus a chunk in flight, unaligned — however long it is (a
        sequence shorter than that never holds more than its own pages).  An
        ALIGNED window holds its ``window / page_size`` pages and, while the
        decode steps that end it are still unread, the first page of the next
        (two steps in flight at most: one page, two at a page size of 1); a
        chunk never straddles a multiple of the window (the scheduler refuses
        a chunk width that does not divide it)."""
        whole = -(-int(tokens) // self.page_size)
        if self.window is None:
            return whole
        if self.aligned:
            return min(whole, self.window // self.page_size
                       + -(-2 // self.page_size))
        return min(whole, -(-(self.window + int(widest_chunk))
                            // self.page_size) + 1)

    def table_width(self, tokens, widest_chunk):
        """Columns of the group's page table for sequences of ``tokens``
        positions: logical page ``p`` stands in column ``p % width``.  The
        slot's bound, but for an aligned window: its pages alone, so that a
        window's ``j``-th page is column ``j``."""
        if self.aligned:
            return min(-(-int(tokens) // self.page_size),
                       self.window // self.page_size)
        return self.slot_bound(tokens, widest_chunk)

    def first_live_page(self, next_pos):
        """The first logical page that a query at ``next_pos`` or later can
        still read: every position on the pages before it is more than
        ``window - 1`` behind ``next_pos`` (sliding), or before the last
        multiple of the window at or under ``next_pos`` (aligned)."""
        if self.window is None:
            return 0
        if self.aligned:
            return (int(next_pos) // self.window) * (
                self.window // self.page_size)
        return max(0, int(next_pos) - self.window + 1) // self.page_size

    def can_reserve(self, n):
        return self.reserved + int(n) <= self.num_pages - 1

    def reserve(self, n):
        if not self.can_reserve(n):
            raise ServingError(
                "page group %r: %d reserved + %d > %d usable pages"
                % (self.name, self.reserved, n, self.num_pages - 1))
        self.reserved += int(n)

    def unreserve(self, n):
        self.reserved -= int(n)

    def alloc(self, n=1):
        n = int(n)
        if n > len(self._free):
            return None
        pages = [self._free.popleft() for _ in range(n)]
        for p in pages:
            self._rc[p] = 1
        self._used += n
        self.taken += n
        self._taken.inc(n)
        return pages

    def free(self, pages, released=False):
        for p in pages:
            if p == 0:
                raise ServingError("page 0 is the scratch page; never owned")
            if self._rc[p] != 1:
                raise ServingError("page group %r: double free of page %d"
                                   % (self.name, p))
            self._rc[p] = 0
            self._free.append(p)
        self._used -= len(pages)
        if released:
            self.released += len(pages)
            self._released.inc(len(pages))

    def stats(self):
        """The group's allocator snapshot with the same partition sweep as
        :meth:`PagedKVCache.stats` (a page is referenced or free, never
        both, never neither)."""
        free = set(self._free)
        errors = [(p, self._rc[p], "referenced page also in free list"
                   if self._rc[p] else "leaked: rc=0 but not in free list")
                  for p in range(1, self.num_pages)
                  if (self._rc[p] > 0) == (p in free)]
        return {"num_pages": self.num_pages, "window": self.window,
                "aligned": self.aligned, "page_size": self.page_size,
                "used_pages": self._used, "free_pages": len(self._free),
                "reserved_pages": self.reserved,
                "released_pages": self.released, "taken_pages": self.taken,
                "rc_errors": errors,
                "rc_sum_matches": sum(self._rc) == self._used}


class PagedKVCache:
    """Preallocated paged pools + the host-side refcounting allocator.

    Parameters
    ----------
    num_layers / num_heads / head_dim: model dims; the pools are
        ``[L, num_pages, page_size, H*D]`` (k and v; ``pool_shape``), heads
        folded head-major into the last axis — see the module docstring.
        ``num_layers == 0``: no layer holds per-head K/V, and the cache has
        no ``"k"`` / ``"v"`` leaf at all (every page-indexed leaf is one of
        ``page_pools``; there must be one).
    num_pages: pool size INCLUDING the reserved scratch page 0.
    page_size: tokens per page.
    max_seq_len: longest sequence the runtime will hold; fixes the
        per-slot page-table width ``max_pages_per_seq``.
    dtype: pool dtype (bf16 halves HBM on chip; f32 default for the
        bitwise CPU contract).
    page_pools: further page-indexed leaves, ``{name: dict(layers=,
        tokens_per_row=, width=, dtype=)}`` -> ``[layers, num_pages,
        page_size // tokens_per_row, width]`` (``dtype`` None = ``dtype``);
        with ``page_groups`` a leaf may name its ``group`` (whose
        ``num_pages`` its page axis then has).
    page_groups: None (one group, every position kept: ``num_pages`` is its
        length), or an ordered ``{name: dict(window=, aligned=, page_size=,
        num_pages=)}`` — the first is this cache's own allocator
        (``num_pages`` is then read from it, and :attr:`page_size` is ITS page
        size where it states one), each further one a :class:`PageGroup` in
        :attr:`groups` with the page size it states, else ``page_size``.
    slot_state / num_slots: slot-indexed leaves, ``{name: dict(layers=,
        shape=, dtype=)}`` -> ``[layers, num_slots, *shape]``.
    device: commit every leaf there (None: jax's default placement).

    ``pools`` is the whole cache as ONE pytree (a dict of arrays by leaf
    name): ONE allocator and ONE page table address all page-indexed
    leaves of a group (one group unless ``page_groups`` says otherwise), the
    scheduler threads the dict through every step, donated.
    """

    def __init__(self, num_layers, num_pages, page_size, num_heads,
                 head_dim, max_seq_len, dtype="float32", page_pools=None,
                 slot_state=None, num_slots=0, device=None, page_groups=None):
        import jax.numpy as jnp

        page_groups = dict(page_groups or {})
        self.primary_group = next(iter(page_groups), "pages")
        first = page_groups.pop(self.primary_group, None)
        if first is not None:
            if first.get("window") is not None:
                raise ServingError(
                    "the first page group (%r) is the cache's own allocator "
                    "and keeps every position; state the window group after "
                    "it" % self.primary_group)
            num_pages = first["num_pages"]
        # the page size of a group that states none; ``self.page_size`` is
        # the FIRST group's own (tables, ``pages_for``, the prefix hashes)
        shared_page_size = int(page_size)
        if first is not None and first.get("page_size"):
            page_size = int(first["page_size"])
        if num_pages < 2:
            raise ServingError(
                "num_pages must be >= 2 (page 0 is the reserved scratch "
                "page), got %d" % num_pages)
        if page_size < 1 or max_seq_len < 1:
            raise ServingError("page_size and max_seq_len must be >= 1")
        self.num_layers = int(num_layers)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.max_seq_len = int(max_seq_len)
        self.num_slots = int(num_slots)
        self.device = device
        self.max_pages_per_seq = -(-self.max_seq_len // self.page_size)
        self.dtype = jnp.dtype(dtype)
        # every leaf's (shape, dtype) by name; the page-indexed ones have
        # the page axis at 1, the slot-indexed ones the slot axis at 1
        self._page_leaves = {}
        # the further groups, and the leaves of each (the first group's are
        # the rest of ``_page_leaves``)
        self.groups = {
            name: PageGroup(name, spec["num_pages"],
                            spec.get("page_size") or shared_page_size,
                            spec.get("window"), spec.get("aligned", False))
            for name, spec in page_groups.items()}
        # the first group's own count of pages handed out (it releases none)
        self.taken = 0
        self._taken = _group_counters(self.primary_group)[0]
        self._group_of = {}
        if self.num_layers:
            self._page_leaves = {"k": (self.pool_shape, self.dtype),
                                 "v": (self.pool_shape, self.dtype)}
        elif not page_pools:
            raise ServingError(
                "a cache with no K/V layers (num_layers == 0) needs a "
                "page_pools leaf: it would hold nothing a page addresses")
        for name, spec in (page_pools or {}).items():
            group = spec.get("group", self.primary_group)
            if group != self.primary_group and group not in self.groups:
                raise ServingError(
                    "page pool %r names group %r; the cache has %s"
                    % (name, group,
                       [self.primary_group] + sorted(self.groups)))
            ps = self.group_page_size(group)
            pages = (self.num_pages if group == self.primary_group
                     else self.groups[group].num_pages)
            if ps % int(spec["tokens_per_row"]):
                raise ServingError(
                    "page pool %r keeps one row per %d tokens, which does "
                    "not divide page_size %d"
                    % (name, spec["tokens_per_row"], ps))
            self._group_of[name] = group
            self._page_leaves[name] = (
                (int(spec["layers"]), pages,
                 ps // int(spec["tokens_per_row"]),
                 int(spec["width"])),
                jnp.dtype(spec.get("dtype") or self.dtype))
        self._slot_leaves = {
            name: ((int(spec["layers"]), self.num_slots)
                   + tuple(int(d) for d in spec["shape"]),
                   jnp.dtype(spec["dtype"]))
            for name, spec in (slot_state or {}).items()}
        if set(self._page_leaves) & set(self._slot_leaves):
            raise ServingError("a cache leaf is page-indexed or slot-indexed, "
                               "not both: %s" % sorted(
                                   set(self._page_leaves)
                                   & set(self._slot_leaves)))
        if self._slot_leaves and self.num_slots < 1:
            raise ServingError("slot-indexed state needs num_slots >= 1")
        self.pools = self._zeros()
        _page_bytes.set(self.page_bytes)
        _state_bytes.set(self.state_bytes)
        # by leaf where there is one: a model may keep several kinds of
        # slot state, of shapes and dtypes of their own
        for name, n in self.state_leaf_bytes().items():
            _obs.gauge("serving.cache.state_leaf_bytes",
                       labels={"leaf": name}).set(n)
        # by group where there is more than one: ``kv_*`` are the first's
        self._group_used = {}
        for name in (self.group_names if self.groups else ()):
            _obs.gauge("serving.cache.group_bytes",
                       labels={"group": name}).set(self.group_bytes(name))
            self._group_used[name] = _obs.gauge(
                "serving.cache.group_pages_used", labels={"group": name})
        # page 0 = scratch; everything else starts free
        self._free = collections.deque(range(1, self.num_pages))
        self._used = 0
        self._rc = [0] * self.num_pages
        # prefix-cache state: chain hash -> page id, its inverse, and the
        # rc=0-but-still-indexed pages in least-recently-used order
        self._index = {}
        self._hash_of_page = {}
        self._lru = collections.OrderedDict()
        # per-INSTANCE probe accounting (the serving.decode.kv_* counters
        # are process-wide and would cross-contaminate co-hosted caches)
        self._hits = 0
        self._misses = 0
        self._evicted = 0
        # incrementally maintained rc>=2 count: shared_pages is read on
        # every admission, and an O(num_pages) scan there would put a
        # pool-sized interpreted loop on the serving hot path
        self._shared = 0
        # scheduler-installed callback: () -> iterable of live seq ids.
        # reset_pools consults it so nothing can zero pages out from
        # under a running scheduler without saying force=True.
        self.live_seqs = None
        _pages_total.set(self.num_pages - 1)
        self._publish(0)

    def _zeros(self):
        import jax
        import jax.numpy as jnp

        pools = {name: jnp.zeros(shape, dtype) for name, (shape, dtype) in
                 list(self._page_leaves.items())
                 + list(self._slot_leaves.items())}
        # committed to ``device`` where one was given (a pool's replica)
        return (pools if self.device is None
                else jax.device_put(pools, self.device))

    @property
    def page_leaf_names(self):
        """Names of the page-indexed leaves of :attr:`pools` (page axis 1):
        ``k`` and ``v`` where the model has K/V layers, and its further
        page pools."""
        return tuple(self._page_leaves)

    def group_leaf_names(self, group):
        """The page-indexed leaves of ``group``."""
        return tuple(n for n in self._page_leaves
                     if self._group_of.get(n, self.primary_group) == group)

    @property
    def group_names(self):
        """Every page group's name, the cache's own first."""
        return (self.primary_group,) + tuple(self.groups)

    def group_page_size(self, group):
        """Tokens a page of ``group`` holds."""
        return (self.page_size if group == self.primary_group
                else self.groups[group].page_size)

    @property
    def slot_leaf_names(self):
        """Names of the slot-indexed leaves of :attr:`pools` (slot axis 1)."""
        return tuple(self._slot_leaves)

    @staticmethod
    def _nbytes(leaves):
        return int(sum(int(np.prod(shape)) * dtype.itemsize
                       for shape, dtype in leaves.values()))

    @property
    def page_bytes(self):
        """Bytes of all page-indexed leaves together."""
        return self._nbytes(self._page_leaves)

    def group_bytes(self, group):
        """Bytes of ``group``'s leaves."""
        return self._nbytes({n: self._page_leaves[n]
                             for n in self.group_leaf_names(group)})

    @property
    def state_bytes(self):
        """Bytes of all slot-indexed leaves together."""
        return self._nbytes(self._slot_leaves)

    def state_leaf_bytes(self):
        """Bytes of each slot-indexed leaf, by name."""
        return {name: self._nbytes({name: leaf})
                for name, leaf in self._slot_leaves.items()}

    # the two leaves of a model with K/V layers, by name (tools, tests and
    # the fault injectors read and poke them; the scheduler threads
    # ``pools`` whole)
    @property
    def k_pool(self):
        return self.pools["k"]

    @k_pool.setter
    def k_pool(self, value):
        self.pools["k"] = value

    @property
    def v_pool(self):
        return self.pools["v"]

    @v_pool.setter
    def v_pool(self, value):
        self.pools["v"] = value

    @property
    def pool_shape(self):
        """``(L, num_pages, page_size, H*D)`` — the stored shape of the
        ``k`` and ``v`` pools."""
        return self.pages_shape(self.num_pages)

    def pages_shape(self, n):
        """Shape of ``n`` pages of every K/V layer, ``pool[:, idx]``."""
        return (self.num_layers, int(n), self.page_size,
                self.num_heads * self.head_dim)

    def gather_pages(self, pools, idx):
        """``{name: leaf[:, idx]}`` over the page-indexed leaves of
        ``pools`` (the first group's: ``idx`` are its pages): what a handoff
        packet or a scrub holds (pure; jitted by the scheduler)."""
        return {name: pools[name][:, idx]
                for name in self.group_leaf_names(self.primary_group)}

    def scatter_pages(self, pools, pages, idx):
        """``pools`` with ``pages`` (a :meth:`gather_pages` tree) written at
        page ids ``idx``; slot-indexed leaves pass through."""
        out = dict(pools)
        for name in self.group_leaf_names(self.primary_group):
            out[name] = pools[name].at[:, idx].set(pages[name])
        return out

    def pages_finite(self, pools, idx):
        """``[N]`` bool: page ``idx[j]`` holds only finite values in every
        page-indexed leaf and layer (the ``kv_guard`` sweep; pure)."""
        import jax.numpy as jnp

        ok = None
        for name in self.group_leaf_names(self.primary_group):
            fin = jnp.isfinite(pools[name][:, idx]).all(axis=(0, 2, 3))
            ok = fin if ok is None else ok & fin
        return ok

    def reset_pools(self, force=False):
        """Reallocate every leaf zeroed, slot state included (allocator
        state untouched).  The
        recovery path after a failed DONATED dispatch, whose consumed
        input buffers are gone either way.  The prefix index is FLUSHED —
        its entries describe page contents that no longer exist.

        Zeroing pages under sequences that still decode from them would
        silently corrupt their output, so when the owning scheduler has
        installed a ``live_seqs`` callback and it reports active
        sequences (or, with no callback, when any page is still rc>=1),
        this raises a typed :class:`ServingError` listing them unless
        ``force=True`` — recovery paths that have already evicted or
        failed their sequences pass ``force=True``."""
        if not force:
            live = (sorted(self.live_seqs())
                    if self.live_seqs is not None else None)
            if live:
                raise ServingError(
                    "reset_pools would zero KV under %d live sequence(s) "
                    "(seq %s); retire or evict them first, or pass "
                    "force=True from a recovery path"
                    % (len(live), ", ".join(str(s) for s in live)))
            if live is None and self._used:
                raise ServingError(
                    "reset_pools would zero %d allocated page(s) with no "
                    "live_seqs callback installed; pass force=True if "
                    "their owners are already failed" % self._used)
        self.pools = self._zeros()
        self._index.clear()
        self._hash_of_page.clear()
        for p in self._lru:
            self._free.append(p)
        self._lru.clear()
        _cached_pages.set(0)

    def scrub_pages(self, pages):
        """Zero the given pages in every page-indexed leaf and drop their prefix-index
        entries — the hygiene step after the KV integrity sweep trips.
        Unlike normal retirement (where stale values are unreachable
        because reads mask by ``kv_lens``), a NON-FINITE stale value is
        reachable arithmetic: the reference paged attention multiplies
        masked positions by probability 0, and ``0 * nan = nan`` would
        poison every future owner of the page.  Pages still shared
        (rc >= 2) are skipped — they predate the corrupt write and other
        readers depend on them; only their index entries stay (their
        content is intact)."""
        import jax.numpy as jnp

        scrub = [int(p) for p in pages if p != 0 and self._rc[p] <= 1]
        if not scrub:
            return
        idx = jnp.asarray(scrub, jnp.int32)
        for name in self.group_leaf_names(self.primary_group):
            shape, dtype = self._page_leaves[name]
            zero = jnp.zeros((shape[0], len(scrub)) + shape[2:], dtype)
            self.pools[name] = self.pools[name].at[:, idx].set(zero)
        for p in scrub:
            h = self._hash_of_page.pop(p, None)
            if h is not None:
                self._index.pop(h, None)
            if p in self._lru:
                del self._lru[p]
                self._free.append(p)
        _cached_pages.set(len(self._lru))

    # -- allocator -----------------------------------------------------------
    @property
    def free_pages(self):
        """Pages an ``alloc`` could hand out right now: the plain free
        list plus the rc=0 indexed pages eviction would reclaim."""
        return len(self._free) + len(self._lru)

    @property
    def used_pages(self):
        """Pages referenced by at least one live page table (rc >= 1)."""
        return self._used

    @property
    def cached_pages(self):
        """rc=0 pages retained for prefix reuse (evictable)."""
        return len(self._lru)

    @property
    def shared_pages(self):
        """Pages live in two or more page tables right now."""
        return self._shared

    def pages_for(self, tokens):
        """Pages a ``tokens``-long sequence reserves (ceil)."""
        return -(-int(tokens) // self.page_size)

    def alloc(self, n):
        """Reserve ``n`` fresh rc=1 pages; returns their ids or None when
        the pool can't cover the reservation (the caller queues the
        sequence).  The plain free list is consumed first; only then are
        least-recently-used rc=0 prefix pages evicted (index entries
        dropped, ``kv_evictions`` counted)."""
        n = int(n)
        if n > self.free_pages:
            return None
        pages = []
        for _ in range(n):
            if self._free:
                p = self._free.popleft()
            else:
                p, _ = self._lru.popitem(last=False)  # least recently used
                h = self._hash_of_page.pop(p)
                del self._index[h]
                self._evicted += 1
                _evictions.inc()
            self._rc[p] = 1
            pages.append(p)
        self._used += n
        self.taken += n
        self._taken.inc(n)
        _cached_pages.set(len(self._lru))
        return pages

    def free(self, pages):
        """Drop one reference per page of a retired sequence's
        reservation.  A page at rc=0 returns to the free list — unless
        its content is indexed for prefix reuse, in which case it parks
        in the LRU (most-recently-used end) and keeps answering hits
        until evicted."""
        dropped_shared = 0
        for p in pages:
            if p == 0:
                raise ServingError("page 0 is the scratch page; never owned")
            rc = self._rc[p]
            if rc < 1:
                raise ServingError("double free of page %d" % p)
            if rc == 2:
                dropped_shared += 1
                self._shared -= 1
            self._rc[p] = rc - 1
            if rc == 1:
                self._used -= 1
                if p in self._hash_of_page:
                    # fresh insertion lands at the MRU end (a page is
                    # never already parked while rc >= 1)
                    self._lru[p] = None
                else:
                    self._free.append(p)
        if dropped_shared:
            _shared_pages.set(self.shared_pages)
        _cached_pages.set(len(self._lru))

    # -- prefix cache --------------------------------------------------------
    @staticmethod
    def _chain_hashes(tokens, page_size):
        """Chain hash per FULL page of ``tokens``: link i certifies token
        blocks ``0 .. i`` (each digest folds in the previous), so an
        index hit on link i proves the whole prefix matches."""
        toks = np.ascontiguousarray(np.asarray(tokens, dtype=np.int32))
        hashes = []
        h = b"kv-prefix-v1"
        for i in range(len(toks) // page_size):
            block = toks[i * page_size:(i + 1) * page_size]
            h = hashlib.sha1(h + block.tobytes()).digest()
            hashes.append(h)
        return hashes

    def prefix_hashes(self, tokens):
        """Public wrapper: one chain hash per full page of ``tokens``."""
        return self._chain_hashes(tokens, self.page_size)

    def lookup_prefix(self, tokens):
        """Probe the index for ``tokens``' longest cached page prefix.

        Returns ``(pages, hashes)``: ``hashes`` is the full chain (one
        per full page — pass it back to :meth:`register_prefix` as pages
        get written), ``pages`` the already-cached leading run, each
        INCREF'd (map them read-only; ``free`` drops the references at
        retirement).  Reuse is capped at ``len(tokens) - 1`` so at least
        one token always goes through prefill — the model's last-position
        logits (the first sampled token) exist in no cache.
        """
        ps = self.page_size
        hashes = self._chain_hashes(tokens, ps)
        reusable = (len(tokens) - 1) // ps
        pages = []
        for i in range(min(reusable, len(hashes))):
            p = self._index.get(hashes[i])
            if p is None:
                break
            pages.append(p)
        for p in pages:
            if self._rc[p] == 0:       # parked in the LRU: revive
                del self._lru[p]
                self._used += 1
            elif self._rc[p] == 1:     # 1 -> 2: newly shared
                self._shared += 1
            self._rc[p] += 1
        misses = max(0, min(reusable, len(hashes)) - len(pages))
        self._hits += len(pages)
        self._misses += misses
        _hit_pages.inc(len(pages))
        _miss_pages.inc(misses)
        _shared_pages.set(self.shared_pages)
        _cached_pages.set(len(self._lru))
        return pages, hashes

    def release_prefix(self, pages):
        """Undo a :meth:`lookup_prefix` whose admission could not finish
        (pool exhausted for the tail): drop the probe's references."""
        self.free(pages)

    def peek_hashes(self, hashes, limit=None):
        """How many LEADING links of ``hashes`` are indexed right now —
        read-only (no increfs, no hit/miss accounting, no LRU touch).
        The pool's prefix-affinity probe: called from the admission
        thread against every replica's cache, so it must not mutate
        worker-owned allocator state (dict reads are safe under the
        GIL; a stale answer only skews one placement decision)."""
        n = len(hashes) if limit is None else min(int(limit), len(hashes))
        count = 0
        for i in range(n):
            if hashes[i] not in self._index:
                break
            count += 1
        return count

    def peek_prefix(self, tokens):
        """:meth:`peek_hashes` over ``tokens``' own chain — leading
        indexed full pages, capped like :meth:`lookup_prefix` (at least
        one token always prefills)."""
        ps = self.page_size
        return self.peek_hashes(self._chain_hashes(tokens, ps),
                                limit=(len(tokens) - 1) // ps)

    def pin_prefix(self, tokens, limit=None):
        """Take one EXTRA reference on each indexed page of ``tokens``'
        leading chain — the session-pin primitive: a pinned page can't
        be LRU-evicted until :meth:`free` drops the pin.  Unlike
        :meth:`lookup_prefix` this is not a read-mapping probe: no
        hit/miss accounting, no ``len - 1`` cap (the LAST full page is
        exactly what the next turn's longer prompt wants warm).
        Returns the pinned page ids (leading indexed run only)."""
        hashes = self._chain_hashes(tokens, self.page_size)
        n = len(hashes) if limit is None else min(int(limit), len(hashes))
        pages = []
        for i in range(n):
            p = self._index.get(hashes[i])
            if p is None:
                break
            pages.append(p)
        for p in pages:
            if self._rc[p] == 0:       # parked in the LRU: revive
                del self._lru[p]
                self._used += 1
            elif self._rc[p] == 1:     # 1 -> 2: newly shared
                self._shared += 1
            self._rc[p] += 1
        _shared_pages.set(self.shared_pages)
        _cached_pages.set(len(self._lru))
        return pages

    def register_prefix(self, hashes, page_index, page):
        """Publish one freshly WRITTEN full page: ``page`` holds the K/V
        of token block ``page_index`` under chain hash
        ``hashes[page_index]``.  First writer wins — a hash already
        indexed (a concurrent identical prompt) keeps its existing page
        and this one stays private."""
        h = hashes[page_index]
        if h in self._index or page in self._hash_of_page:
            return False
        self._index[h] = page
        self._hash_of_page[page] = h
        return True

    def prefix_stats(self):
        """Per-INSTANCE snapshot (the registry counters sum across every
        cache in the process; these don't)."""
        return {
            "kv_hit_pages": self._hits,
            "kv_miss_pages": self._misses,
            "kv_evictions": self._evicted,
            "kv_shared_pages": self.shared_pages,
            "kv_cached_pages": len(self._lru),
            "indexed_pages": len(self._index),
        }

    def stats(self):
        """Full allocator snapshot WITH the leaked-refcount sweep.

        Every non-scratch page must be in exactly one state: rc >= 1
        (used), rc = 0 and parked in the reuse LRU (indexed content), or
        rc = 0 and on the plain free list.  ``rc_errors`` lists every
        page that violates the partition — a page at rc > 0 that is
        also free/parked (double accounting), or an rc = 0 page in
        neither pool (a LEAKED reference: some early-exit path dropped
        a page without freeing it).  The tier-1 sessions gate asserts
        ``rc_errors == []`` and ``used_pages == 0`` after session
        expiry, so any new release path that forgets a pin fails CI
        instead of slowly eating the pool.  Aggregate invariants
        (``rc_sum_matches``): #{rc>=1} == used_pages and #{rc>=2} ==
        shared_pages, catching drift in the incremental counters."""
        free = set(self._free)
        errors = []
        n_used = n_shared = 0
        for p in range(1, self.num_pages):
            rc = self._rc[p]
            in_free, in_lru = p in free, p in self._lru
            if rc < 0:
                errors.append((p, rc, "negative refcount"))
            elif rc > 0:
                n_used += 1
                if rc >= 2:
                    n_shared += 1
                if in_free or in_lru:
                    errors.append((p, rc, "referenced page also in %s"
                                   % ("free list" if in_free else "LRU")))
            elif in_free and in_lru:
                errors.append((p, rc, "page in free list AND LRU"))
            elif not in_free and not in_lru:
                errors.append((p, rc, "leaked: rc=0 but in neither "
                               "free list nor LRU"))
        st = {
            "num_pages": self.num_pages,
            "used_pages": self._used,
            "free_pages": self.free_pages,
            "cached_pages": len(self._lru),
            "shared_pages": self._shared,
            "rc_errors": errors,
            "rc_sum_matches": (n_used == self._used
                               and n_shared == self._shared),
        }
        st.update(self.prefix_stats())
        if self.groups:
            # one entry a group, this allocator's own under its name: a
            # number over both would mean neither
            own = {k: st[k] for k in ("num_pages", "used_pages", "free_pages",
                                      "rc_errors", "rc_sum_matches")}
            # the first group keeps every position: it releases nothing
            own.update(window=None, aligned=False, page_size=self.page_size,
                       taken_pages=self.taken, released_pages=0)
            st["groups"] = dict({self.primary_group: own},
                                **{n: g.stats() for n, g in
                                   self.groups.items()})
        return st

    # -- telemetry -----------------------------------------------------------
    def _publish(self, live_tokens):
        usable = self.num_pages - 1
        _pages_used.set(self._used)
        _occupancy.set(self._used / usable if usable else 0.0)
        cap = self._used * self.page_size
        # internal fragmentation: reserved-but-unwritten fraction of the
        # allocated capacity (allocate-on-admit's rent).  Clamped at 0:
        # shared prefix pages count once in cap but once per OWNER in
        # the scheduler's live-token sum, so sharing can push the naive
        # ratio negative
        _fragmentation.set(max(0.0, 1.0 - live_tokens / cap) if cap
                           else 0.0)
        for name, gauge in self._group_used.items():
            gauge.set(self._used if name == self.primary_group
                      else self.groups[name].used_pages)

    def publish_gauges(self, live_tokens):
        """Refresh occupancy/fragmentation gauges; the scheduler calls this
        once per iteration with the total live (written) token count."""
        self._publish(int(live_tokens))

    def fragmentation(self, live_tokens):
        cap = self._used * self.page_size
        return max(0.0, 1.0 - int(live_tokens) / cap) if cap else 0.0

    def occupancy(self):
        usable = self.num_pages - 1
        return self._used / usable if usable else 0.0

    def table_row(self, pages):
        """A fixed-width ``[max_pages_per_seq]`` int32 page-table row for
        ``pages`` (tail entries -> scratch page 0)."""
        row = np.zeros((self.max_pages_per_seq,), np.int32)
        row[:len(pages)] = pages
        return row
