"""Page GROUPS in the cache manager (``serving/kv_cache.py``: ``PageGroup``;
``serving/decode_scheduler.py``): a group with a window hands pages out as a
sequence's positions reach them and takes them back as they fall out of the
window, under a reservation that does not grow with the sequence; one
admission waits for every group; retirement, cancellation and pool recovery
free every group; a released page that another slot took, poisoned, changes
nothing for the slot that gave it back; what a window group cannot do is
refused.  A toy model whose logits depend on every K row its window should
see, and on nothing else, makes each of those visible on the CPU."""
import functools
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.serving import kv_cache
from paddle_tpu.serving.errors import ServingError
from paddle_tpu.serving.step_programs import STEP_COLUMNS, split_step

PS, W, V = 4, 6, 32     # page, window, vocabulary


# -- the allocator of a further group ----------------------------------------

def test_a_group_reserves_allocates_frees_and_sweeps():
    g = kv_cache.PageGroup("window", num_pages=6, page_size=PS, window=W)
    assert g.slot_bound(10 ** 6, widest_chunk=8) == -(-(W + 8) // PS) + 1 == 5
    assert g.slot_bound(2 * PS - 1, widest_chunk=8) == 2    # its own pages
    assert g.can_reserve(5) and not g.can_reserve(6)
    g.reserve(5)
    assert not g.can_reserve(1)
    with pytest.raises(ServingError):
        g.reserve(1)
    pages = g.alloc(3)
    assert len(set(pages)) == 3 and 0 not in pages and g.used_pages == 3
    assert g.alloc(3) is None                      # only two are left
    g.free(pages[:1], released=True)
    with pytest.raises(ServingError):
        g.free(pages[:1])                          # double free
    with pytest.raises(ServingError):
        g.free([0])                                # scratch is never owned
    st = g.stats()
    assert st["rc_errors"] == [] and st["rc_sum_matches"]
    assert (st["used_pages"], st["free_pages"], st["released_pages"],
            st["reserved_pages"]) == (2, 3, 1, 5)
    g.free(pages[1:])
    g.unreserve(5)
    assert g.free_pages == 5 and g.reserved == 0


@pytest.mark.parametrize("next_pos,first", [
    (0, 0), (W - 1, 0), (W + PS - 2, 0), (W + PS - 1, 1), (W + 2 * PS - 1, 2)])
def test_the_first_live_page_is_the_one_the_next_query_still_reads(next_pos,
                                                                   first):
    """A query at ``next_pos`` reads keys ``next_pos - W + 1 ..``: the pages
    before the one holding that key are dead, and no other."""
    g = kv_cache.PageGroup("w", 9, PS, window=W)
    assert g.first_live_page(next_pos) == first
    assert kv_cache.PageGroup("f", 9, PS).first_live_page(next_pos) == 0
    assert kv_cache.PageGroup("f", 9, PS).slot_bound(9, 100) == 3


def test_a_cache_in_groups_sizes_each_leaf_by_its_group():
    c = serving.PagedKVCache(
        0, None, PS, 0, 0, 64, num_slots=2,
        page_pools={"a": dict(layers=2, tokens_per_row=1, width=8, dtype=None,
                              group="full"),
                    "b": dict(layers=3, tokens_per_row=1, width=8, dtype=None,
                              group="window")},
        page_groups={"full": dict(window=None, num_pages=11),
                     "window": dict(window=W, num_pages=5)})
    assert c.group_names == ("full", "window") and c.primary_group == "full"
    assert c.pools["a"].shape == (2, 11, PS, 8)
    assert c.pools["b"].shape == (3, 5, PS, 8)
    assert c.group_leaf_names("window") == ("b",)
    assert c.group_bytes("full") + c.group_bytes("window") == c.page_bytes
    # the cache's own allocator is the first group's; handoff gathers its
    # leaves alone (the ids are its pages)
    assert c.num_pages == 11 and c.free_pages == 10
    assert set(c.gather_pages(c.pools, jnp.asarray([1, 2]))) == {"a"}
    st = c.stats()
    assert set(st["groups"]) == {"full", "window"}
    assert st["groups"]["window"]["window"] == W
    with pytest.raises(ServingError, match="names group"):
        serving.PagedKVCache(
            0, 9, PS, 0, 0, 64,
            page_pools={"a": dict(layers=1, tokens_per_row=1, width=8,
                                  dtype=None, group="nowhere")})
    with pytest.raises(ServingError, match="keeps every position"):
        serving.PagedKVCache(
            0, None, PS, 0, 0, 64,
            page_pools={"a": dict(layers=1, tokens_per_row=1, width=8,
                                  dtype=None, group="w")},
            page_groups={"w": dict(window=W, num_pages=9)})


def test_one_group_and_no_window_is_the_cache_every_model_had():
    c = serving.PagedKVCache(2, 9, PS, 2, 4, 32)
    assert c.groups == {} and c.group_names == ("pages",)
    assert "groups" not in c.stats()
    assert c.group_leaf_names("pages") == ("k", "v")


# -- a toy model in two groups ------------------------------------------------
#
# A token's K row is its one-hot (in both groups).  The logits at a position
# are ``full + 100 * window``: the histogram of the tokens at positions ``0 ..
# t`` (read through the full group's table) plus a hundred times the histogram
# of ``t - W + 1 .. t`` (read through the window group's RING), so a page that
# is missing, stale, freed too early or read though it is dead moves a logit by
# a whole number, and a NaN anywhere the walk touches shows.

def _histogram(leaf, table, lens, lo, PS=PS):
    """``[S, V]``: the sum of layer 0's rows of ``leaf`` at each slot's
    positions ``lo[s] .. lens[s] - 1``, logical page ``p`` in column ``p %
    width`` of ``table [S, width]``; pages wholly outside that range are not
    read at all (a dead column may name a poisoned page)."""
    S, width = table.shape
    n_walk = width
    first = lo // PS
    cols = (first[:, None] + jnp.arange(n_walk)[None, :]) % width
    pages = jnp.take_along_axis(table, cols, axis=1)
    pos = (first[:, None] * PS + jnp.arange(n_walk * PS)[None, :])
    live_page = ((first[:, None] + jnp.arange(n_walk)[None, :]) * PS
                 < lens[:, None])
    rows = leaf[0, jnp.where(live_page, pages, 0)]          # [S, NW, PS, V]
    ok = ((pos >= lo[:, None]) & (pos < lens[:, None])
          & jnp.repeat(live_page, PS, axis=1))
    return jnp.where(ok[:, :, None], rows.reshape(S, n_walk * PS, V),
                     0.0).sum(axis=1)


def _toy_decode(params, tokens, positions, cache, tables, kv_lens):
    cache = dict(cache)
    S = tokens.shape[0]
    row = jax.nn.one_hot(tokens, V, dtype=jnp.float32)
    for leaf, g in (("kf", "full"), ("kw", "window")):
        t = tables[g]
        page = t[jnp.arange(S), (positions // PS) % t.shape[1]]
        cache[leaf] = cache[leaf].at[0, page, positions % PS].set(row)
    full = _histogram(cache["kf"], tables["full"], kv_lens,
                      jnp.zeros_like(kv_lens))
    win = _histogram(cache["kw"], tables["window"], kv_lens,
                     jnp.maximum(kv_lens - W, 0))
    logits = full + 100.0 * win
    return logits, cache


def _toy_chunk(params, tokens, start, valid, cache, chunk_pages, gather_pages,
               slot):
    cache = dict(cache)
    C = tokens.shape[0]
    rows = jax.nn.one_hot(tokens, V, dtype=jnp.float32).reshape(C // PS, PS, V)
    for leaf, g in (("kf", "full"), ("kw", "window")):
        cache[leaf] = cache[leaf].at[0, chunk_pages[g]].set(rows)
    n = (start + valid)[None]
    full = _histogram(cache["kf"], gather_pages["full"][None], n,
                      jnp.zeros_like(n))
    win = _histogram(cache["kw"], gather_pages["window"][None], n,
                     jnp.maximum(n - W, 0))
    return (full + 100.0 * win)[0], cache


@functools.lru_cache(maxsize=None)
def _model(window=W):
    """One model object a window: its schedulers share its step programs."""
    leaf = dict(layers=1, tokens_per_row=1, width=V, dtype=None)
    return serving.DecodeModel(
        _toy_decode, _toy_chunk, params={"unused": np.zeros((1,), np.float32)},
        vocab_size=V, name="toy-groups",
        page_groups={"full": dict(window=None),
                     "window": dict(window=window)},
        page_pools={"kf": dict(leaf, group="full"),
                    "kw": dict(leaf, group="window")})


def _config(**over):
    kw = dict(num_slots=2, page_size=PS, max_seq_len=64,
              num_pages={"full": 33, "window": 11},
              prefill_buckets=(4, 8, 32), prefill_chunk_tokens=8,
              max_new_tokens=8, kv_dtype="float32")
    kw.update(over)
    return serving.DecodeConfig(**kw)


def _expected(prompt, n_new):
    """The tokens the toy model serves: argmax of ``full + 100 x window``."""
    seq, out = list(prompt), []
    for _ in range(n_new):
        full = np.bincount(seq, minlength=V)
        win = np.bincount(seq[-W:], minlength=V)
        out.append(int(np.argmax(full + 100.0 * win)))
        seq.append(out[-1])
    return out


def test_the_scheduler_serves_a_window_group_through_many_releases():
    """Prompts of several chunks and answers of several pages, two slots,
    three requests: every token is the one a whole-history count and a
    last-``W`` count give, so no page the window needs was ever missing and
    none outside it was read; the slots' window pages stayed under the bound
    all along; everything is free at the end, in both groups."""
    released0 = obs.counter("serving.cache.window.pages_released").value
    sched = serving.DecodeScheduler(_model(), _config())
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, V, size=n).astype(np.int32)
               for n in (29, 3, 18)]
    bound = sched.cache.groups["window"].slot_bound(64, 8)
    assert bound == -(-(W + 8) // PS) + 1 == 5
    assert sched._more_tables["window"].shape == (2, bound)
    seen = []
    real = sched._release_window

    def watch(idx, slot):
        seen.append(len(slot.more["window"].pages))
        real(idx, slot)

    sched._release_window = watch
    outs = [sched.submit(p, max_new_tokens=14) for p in prompts]
    for p, f in zip(prompts, outs):
        assert list(f.result(timeout=120)) == _expected(p, 14)
    sched.stop()
    assert seen and max(seen) <= bound
    assert obs.counter("serving.cache.window.pages_released"
                       ).value - released0 >= 3 + 8 + 5
    st = sched.cache_stats()
    for g in ("full", "window"):
        assert st["groups"][g]["used_pages"] == 0, st
        assert st["groups"][g]["rc_errors"] == []
    assert st["groups"]["window"]["reserved_pages"] == 0
    assert not sched._more_tables["window"].any() and not sched._tables.any()
    health = sched.stats()
    assert health["kv_groups"]["window"]["pages_used"] == 0
    assert obs.gauge("serving.cache.group_bytes",
                     labels={"group": "window"}).value == 11 * PS * V * 4


def test_pages_one_step_ahead_stay_under_the_bound_and_the_reservation():
    """With a step in flight ``_ensure_pages`` is called with the DISPATCHED
    length, one past the committed one, and a release acts on the committed
    length while the step behind it runs: a slot never holds more window
    pages than its reservation (= the ring's width), at every alloc and at
    every release, and releases and re-uses are what a loop that reads each
    step before the next one does."""
    runs = {}
    for loop in ("in flight", "in order"):
        c0 = {c: obs.counter(c).value for c in (
            "serving.cache.window.pages_released",
            "serving.decode.steps_overlapped")}
        sched = serving.DecodeScheduler(_model(), _config())
        if loop == "in order":
            def plans(sched=sched):
                plan = None if sched._unread else sched._plan_step()
                return [] if plan is None else [plan]
            sched._plan_steps = plans
        bound = sched._more_tables["window"].shape[1]
        grp, held, handed = sched.cache.groups["window"], [], []
        ensure, alloc = sched._ensure_pages, grp.alloc

        def watch(idx, slot, end, ensure=ensure, held=held):
            ensure(idx, slot, end)
            held.append(len(slot.more["window"].pages))
            assert len(slot.more["window"].pages) <= slot.more[
                "window"].reserved

        sched._ensure_pages = watch
        grp.alloc = lambda n=1, alloc=alloc, handed=handed: (
            handed.extend(alloc(n) or ()) or handed[-n:])
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, V, size=n).astype(np.int32)
                   for n in (29, 3, 18)]
        outs = [sched.submit(p, max_new_tokens=30) for p in prompts]
        for p, f in zip(prompts, outs):
            assert list(f.result(timeout=120)) == _expected(p, 30)
        sched.stop()
        assert held and max(held) <= bound
        runs[loop] = dict(
            released=obs.counter("serving.cache.window.pages_released").value
            - c0["serving.cache.window.pages_released"],
            handed=len(handed), reused=len(handed) - len(set(handed)),
            overlapped=obs.counter("serving.decode.steps_overlapped").value
            - c0["serving.decode.steps_overlapped"])
    assert runs["in flight"].pop("overlapped") > 0 == runs["in order"].pop(
        "overlapped")
    assert runs["in flight"] == runs["in order"]
    assert runs["in flight"]["reused"] > 0


def test_a_released_page_poisoned_in_anothers_hands_changes_nothing():
    """Slot 0 decodes far past its window; every page it has released is
    poisoned with NaN the moment it is released (another slot's write could
    put anything there).  Its tokens are still the ones a clean run gives,
    and finite: neither the walk nor a copy touches a released page."""
    sched = serving.DecodeScheduler(_model(), _config(num_slots=1),
                                    autostart=False)
    grp = sched.cache.groups["window"]
    real = grp.free

    def poison(pages, released=False):
        if released:
            idx = jnp.asarray(list(pages))
            sched.cache.pools["kw"] = sched.cache.pools["kw"].at[
                :, idx].set(jnp.nan)
        real(pages, released=released)

    grp.free = poison
    prompt = np.random.RandomState(3).randint(1, V, size=21).astype(np.int32)
    sched.start()
    got = list(sched.submit(prompt, max_new_tokens=20).result(timeout=120))
    sched.stop()
    assert got == _expected(prompt, 20)
    assert grp.released >= (21 + 20 - W) // PS - 1
    # and the pool did hand released pages out again: 10 usable pages served
    # 41 positions
    assert grp.stats()["rc_errors"] == []


def _slowed(sched, nap=0.005):
    """``sched`` with a nap at every step's readback: a request stays seated
    long enough for another thread to see it there."""
    read = sched._read_step
    sched._read_step = lambda sent: (time.sleep(nap), read(sent))[1]
    return sched


def test_admission_waits_while_either_group_is_short():
    """The window group holds ONE slot's bound: the second request parks at
    the head of the line though the full group has room, and is admitted when
    the first retires; then the other way round."""
    sched = _slowed(serving.DecodeScheduler(
        _model(), _config(num_pages={"full": 33, "window": 6})))
    a = sched.submit(np.arange(1, 10, dtype=np.int32), max_new_tokens=30)
    b = sched.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
    deadline = time.time() + 60
    while not a.token_times and time.time() < deadline:
        time.sleep(0.01)
    assert sched.stats()["active"] == 1 and not b.done()
    assert sched.stats()["kv_groups"]["window"]["pages_reserved"] == 5
    assert len(a.result(timeout=120)) == 30
    assert len(b.result(timeout=120)) == 2
    sched.stop()
    # the full group short: 40 + 4 positions need 11 pages of its 12 usable
    sched = _slowed(serving.DecodeScheduler(
        _model(), _config(num_pages={"full": 13, "window": 11})))
    a = sched.submit(np.arange(1, 31, dtype=np.int32), max_new_tokens=14)
    b = sched.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
    while not a.token_times and time.time() < deadline:
        time.sleep(0.01)
    assert sched.stats()["active"] == 1 and not b.done()
    assert len(a.result(timeout=120)) == 14 and len(b.result(timeout=120)) == 2
    sched.stop()
    assert sched.cache_stats()["groups"]["window"]["reserved_pages"] == 0


def test_a_request_no_group_can_ever_hold_fails_at_once():
    sched = serving.DecodeScheduler(
        _model(), _config(num_pages={"full": 33, "window": 4}))
    with pytest.raises(ServingError, match="group 'window'"):
        sched.submit(np.arange(1, 6, dtype=np.int32),
                     max_new_tokens=20).result(timeout=60)
    # one that fits its own pages under the bound reserves those alone
    assert len(sched.submit(np.arange(1, 6, dtype=np.int32),
                            max_new_tokens=2).result(timeout=60)) == 2
    sched.stop()


@pytest.mark.parametrize("how", ["cancel", "recover_pools", "evict"])
def test_every_way_out_leaves_both_free_lists_whole(how):
    sched = _slowed(serving.DecodeScheduler(_model(), _config()))
    futs = [sched.submit(np.arange(1, 20, dtype=np.int32) % V,
                         max_new_tokens=40) for _ in range(2)]
    deadline = time.time() + 60
    while (any(len(f.token_times) < 3 for f in futs)
           and time.time() < deadline):
        time.sleep(0.01)
    assert sched.stats()["kv_groups"]["window"]["pages_used"] > 0
    if how == "cancel":
        for f in futs:
            f.cancel()
        while sched.stats()["active"] and time.time() < deadline:
            time.sleep(0.01)
        sched.stop()
    elif how == "recover_pools":
        sched.stop(drain=False)
        for i, slot in enumerate(sched._slots):     # what a failed donated
            if slot is not None:                    # dispatch does
                sched._retire(i, error=ServingError("dispatch failed"))
        sched.cache.reset_pools(force=True)
    else:
        sched.stop(drain=False)
        sched.evict_inflight()
    st = sched.cache_stats()
    for g in ("full", "window"):
        assert st["groups"][g]["used_pages"] == 0, (how, st)
        assert st["groups"][g]["rc_errors"] == []
        assert st["groups"][g]["rc_sum_matches"]
    assert st["groups"]["window"]["reserved_pages"] == 0
    assert not sched._more_tables["window"].any()


@pytest.mark.parametrize("what", ["prefix_cache", "sessions", "role",
                                  "kv_guard"])
def test_what_a_window_group_cannot_do_is_refused(what):
    kw, cfg = {}, {}
    if what == "prefix_cache":
        cfg = dict(prefix_cache=True)
    elif what == "sessions":
        cfg = dict(prefix_cache=True)
        kw = dict(sessions=serving.SessionStore())
    elif what == "role":
        kw = dict(role="prefill")
    else:
        cfg = dict(kv_guard=True)
    with pytest.raises(ServingError, match="groups"):
        serving.DecodeScheduler(_model(), _config(**cfg), autostart=False,
                                **kw)


def test_num_pages_of_a_model_in_groups_is_given_by_group():
    with pytest.raises(ServingError, match="num_pages is"):
        serving.DecodeScheduler(_model(), _config(num_pages=33),
                                autostart=False)
    with pytest.raises(ServingError, match="num_pages is"):
        serving.DecodeScheduler(
            _model(), _config(num_pages={"full": 33, "ring": 9}),
            autostart=False)
    # a group left out gets its worst case
    sched = serving.DecodeScheduler(_model(), _config(num_pages={"full": 33}),
                                    autostart=False)
    assert sched.cache.groups["window"].num_pages == 2 * 16 + 1


def test_a_model_that_states_no_groups_gets_the_arrays_themselves():
    """The step programs of every model that was there: tables and page
    vectors are arrays, not dicts (their jaxprs do not change)."""
    seen = {}

    def decode(params, tokens, positions, cache, tables, kv_lens):
        seen["tables"] = tables
        return jnp.zeros((tokens.shape[0], V)), cache

    def chunk(params, tokens, start, valid, cache, written, gathered, slot):
        seen["written"], seen["gathered"] = written, gathered
        return jnp.zeros((V,)), cache

    model = serving.DecodeModel(decode, chunk, params={}, num_layers=1,
                                num_heads=1, head_dim=4, vocab_size=V)
    sched = serving.DecodeScheduler(
        model, _config(num_pages=17), autostart=False)
    assert sched._more_tables == {} and sched.cache.groups == {}
    assert seen["tables"].shape == (2, 16)
    assert seen["written"].shape == (1,) or seen["written"].shape == (2,)
    assert seen["gathered"].shape == (16,)
    assert "kv_groups" not in sched.stats()


# -- an ALIGNED window, and a first group with a page size of its own ---------
#
# The same toy, with the window group aligned (a query at ``t`` reads ``(t //
# AW) * AW .. t``: two pages that fill, go back together and fill again) and
# the first group on pages of ``APS`` = 8 tokens where the cache's are 4.

AW, APS = 8, 8
_LEAVES = (("kf", "all", APS), ("kw", "window", PS))


def _aligned_logits(cache, tables, lens):
    full = _histogram(cache["kf"], tables["all"], lens, jnp.zeros_like(lens),
                      PS=APS)
    win = _histogram(cache["kw"], tables["window"], lens,
                     (jnp.maximum(lens - 1, 0) // AW) * AW)
    return full + 100.0 * win


def _aligned_decode(params, tokens, positions, cache, tables, kv_lens):
    cache = dict(cache)
    S = tokens.shape[0]
    row = jax.nn.one_hot(tokens, V, dtype=jnp.float32)
    for leaf, g, ps in _LEAVES:
        t = tables[g]
        page = t[jnp.arange(S), (positions // ps) % t.shape[1]]
        cache[leaf] = cache[leaf].at[0, page, positions % ps].set(row)
    return _aligned_logits(cache, tables, kv_lens), cache


def _aligned_chunk(params, tokens, start, valid, cache, chunk_pages,
                   gather_pages, slot):
    cache = dict(cache)
    C = tokens.shape[0]
    rows = jax.nn.one_hot(tokens, V, dtype=jnp.float32)
    for leaf, g, ps in _LEAVES:
        # whole pages, or a part of the one page that holds ``start``
        local = start % ps + jnp.arange(C)
        cache[leaf] = cache[leaf].at[
            0, chunk_pages[g][local // ps], local % ps].set(rows)
    return _aligned_logits(
        cache, {g: t[None] for g, t in gather_pages.items()},
        (start + valid)[None])[0], cache


@functools.lru_cache(maxsize=None)
def _aligned_model(window=AW):
    leaf = dict(layers=1, tokens_per_row=1, width=V, dtype=None)
    return serving.DecodeModel(
        _aligned_decode, _aligned_chunk,
        params={"unused": np.zeros((1,), np.float32)}, vocab_size=V,
        name="toy-aligned",
        page_groups={"all": dict(window=None, page_size=APS),
                     "window": dict(window=window, aligned=True)},
        page_pools={"kf": dict(leaf, group="all"),
                    "kw": dict(leaf, group="window")})


def _aligned_config(**over):
    return _config(**dict(dict(num_pages={"all": 17, "window": 7}), **over))


def _aligned_expected(prompt, n_new):
    seq, out = list(prompt), []
    for _ in range(n_new):
        t = len(seq) - 1
        full = np.bincount(seq, minlength=V)
        win = np.bincount(seq[(t // AW) * AW:], minlength=V)
        out.append(int(np.argmax(full + 100.0 * win)))
        seq.append(out[-1])
    return out


@pytest.mark.parametrize("next_pos,first", [
    (0, 0), (AW - 1, 0), (AW, 2), (2 * AW - 1, 2), (2 * AW, 4)])
def test_an_aligned_window_lives_from_the_last_multiple_on(next_pos, first):
    g = kv_cache.PageGroup("w", 9, PS, window=AW, aligned=True)
    assert g.first_live_page(next_pos) == first
    # its pages and the next window's first; a table of its pages alone
    assert g.slot_bound(10 ** 6, widest_chunk=8) == AW // PS + 1 == 3
    assert g.table_width(10 ** 6, widest_chunk=8) == AW // PS
    assert g.slot_bound(PS + 1, 8) == 2 == g.table_width(PS + 1, 8)
    assert g.stats()["aligned"] is True
    with pytest.raises(ServingError, match="whole pages"):
        kv_cache.PageGroup("w", 9, PS, window=AW + 1, aligned=True)


def test_every_group_has_a_page_size_of_its_own():
    c = serving.PagedKVCache(
        0, None, PS, 0, 0, 64, num_slots=2,
        page_pools={"s": dict(layers=1, tokens_per_row=4, width=8, dtype=None,
                              group="all"),
                    "k": dict(layers=2, tokens_per_row=1, width=8, dtype=None,
                              group="window")},
        page_groups={"all": dict(window=None, page_size=16, num_pages=5),
                     "window": dict(window=AW, aligned=True, num_pages=7)})
    # the first group's page is 16 tokens = 4 rows of 4; the window's the
    # cache's own 4
    assert c.page_size == 16 and c.max_pages_per_seq == 4
    assert c.pages_for(17) == 2 and c.group_page_size("all") == 16
    assert c.groups["window"].page_size == c.group_page_size("window") == PS
    assert c.pools["s"].shape == (1, 5, 4, 8)
    assert c.pools["k"].shape == (2, 7, PS, 8)
    st = c.stats()["groups"]
    assert (st["all"]["page_size"], st["window"]["page_size"]) == (16, PS)
    assert (st["all"]["aligned"], st["window"]["aligned"]) == (False, True)
    with pytest.raises(ServingError, match="does not divide"):
        serving.PagedKVCache(
            0, None, PS, 0, 0, 64,
            page_pools={"s": dict(layers=1, tokens_per_row=3, width=8,
                                  dtype=None, group="all")},
            page_groups={"all": dict(window=None, page_size=16, num_pages=5)})


def test_the_scheduler_fills_an_aligned_window_and_gives_it_back_whole():
    """Three requests over two slots through six and more boundaries each,
    one step in flight: every token is the one a whole-history count (pages
    of 8) and a count from the last multiple of 8 on give; a window's two
    pages go back in ONE call, at the step that reaches the multiple and none
    before, poisoned as they go; a planned step's live columns name only
    pages its slot still holds; a slot never holds more than its bound; both
    free lists are whole at the end."""
    sched = serving.DecodeScheduler(_aligned_model(), _aligned_config(),
                                    autostart=False)
    grp = sched.cache.groups["window"]
    bound = grp.slot_bound(64, 8)
    assert bound == 3 and sched._more_tables["window"].shape == (2, 2)
    assert sched._tables.shape == (2, 64 // APS)
    releases, held_most = [], [0]
    release, plan, free = sched._release_window, sched._plan_step, grp.free

    def watch_release(idx, slot):
        before = grp.released
        release(idx, slot)
        if grp.released > before:
            releases.append((slot.kv_len, grp.released - before))

    def watch_plan():
        step = plan()
        if step is not None:
            (_, table), columns = split_step(step.args, sched._widths)
            lens = columns[STEP_COLUMNS.index("kv_lens")]
            for i, slot in step.entries:
                held_most[0] = max(held_most[0],
                                   len(slot.more["window"].pages))
                live = table[i, :((lens[i] - 1) % AW) // PS + 1]
                assert set(live) <= set(slot.more["window"].pages), (
                    live, slot.more["window"].pages)
        return step

    def poison(pages, released=False):
        if released:
            sched.cache.pools["kw"] = sched.cache.pools["kw"].at[
                :, jnp.asarray(list(pages))].set(jnp.nan)
        free(pages, released=released)

    sched._release_window, sched._plan_step = watch_release, watch_plan
    grp.free = poison
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, V, size=n).astype(np.int32)
               for n in (29, 3, 16)]
    sched.start()
    outs = [sched.submit(p, max_new_tokens=30) for p in prompts]
    for p, f in zip(prompts, outs):
        assert list(f.result(timeout=120)) == _aligned_expected(p, 30)
    sched.stop()
    assert obs.counter("serving.decode.steps_overlapped").value > 0
    # every release is a whole window, at a multiple of the window
    assert releases and all(at % AW == 0 and n == AW // PS
                            for at, n in releases), releases
    assert len(releases) == sum((len(p) + 29) // AW for p in prompts)
    assert 0 < held_most[0] <= bound
    st = sched.cache_stats()
    for g in ("all", "window"):
        assert st["groups"][g]["used_pages"] == 0, st
        assert st["groups"][g]["rc_errors"] == []
        assert st["groups"][g]["rc_sum_matches"]
    assert st["groups"]["window"]["reserved_pages"] == 0
    assert not sched._more_tables["window"].any() and not sched._tables.any()


def test_a_chunk_that_would_straddle_an_aligned_window_is_refused():
    with pytest.raises(ServingError, match="straddle"):
        serving.DecodeScheduler(_aligned_model(window=12), _aligned_config(),
                                autostart=False)
    # and a page size that a chunk width neither fills nor fits inside
    with pytest.raises(ServingError, match="neither"):
        serving.DecodeScheduler(
            _aligned_model(),
            _aligned_config(page_size=2, prefill_buckets=(6, 8, 64)),
            autostart=False)


# -- ONE group on pages of its own size, with everything a first group can do -

def _wide_decode(params, tokens, positions, cache, tables, kv_lens):
    t = tables["all"]
    page = t[jnp.arange(tokens.shape[0]), positions // APS]
    cache = dict(cache, kf=cache["kf"].at[0, page, positions % APS].set(
        jax.nn.one_hot(tokens, V, dtype=jnp.float32)))
    return _histogram(cache["kf"], t, kv_lens, jnp.zeros_like(kv_lens),
                      PS=APS), cache


def _wide_chunk(params, tokens, start, valid, cache, chunk_pages,
                gather_pages, slot):
    local = start % APS + jnp.arange(tokens.shape[0])
    cache = dict(cache, kf=cache["kf"].at[
        0, chunk_pages["all"][local // APS], local % APS].set(
            jax.nn.one_hot(tokens, V, dtype=jnp.float32)))
    n = (start + valid)[None]
    return _histogram(cache["kf"], gather_pages["all"][None], n,
                      jnp.zeros_like(n), PS=APS)[0], cache


def test_a_first_group_on_wider_pages_keeps_the_guard_and_the_prefix_cache():
    """One group that states ``page_size`` 8 under a ``DecodeConfig`` whose
    own is 4: the default pool, the guard's tail page, a hit's cached tokens
    and the pages a chunk publishes are all counted in the GROUP's pages (in
    the config's, ``slot.pages[kv_len // 4]`` runs off the slot's list and a
    hit of two pages would stand for 8 tokens)."""
    leaf = dict(layers=1, tokens_per_row=1, width=V, dtype=None, group="all")
    model = serving.DecodeModel(
        _wide_decode, _wide_chunk,
        params={"unused": np.zeros((1,), np.float32)}, vocab_size=V,
        name="toy-wide", page_groups={"all": dict(window=None,
                                                  page_size=APS)},
        page_pools={"kf": leaf})
    sched = serving.DecodeScheduler(
        model, _config(num_pages=None, prefix_cache=True, kv_guard=True,
                       max_new_tokens=24), autostart=False)
    cache = sched.cache
    assert cache.page_size == APS and cache.num_pages == 2 * 64 // APS + 1
    placed, place = [], sched._place

    def watch_place(req, pages, cached_tokens, *rest):
        placed.append((len(pages), cached_tokens))
        return place(req, pages, cached_tokens, *rest)

    sched._place = watch_place
    rng = np.random.RandomState(3)
    shared = rng.randint(1, V, size=2 * APS + 3).astype(np.int32)
    prompts = [np.concatenate([shared, rng.randint(1, V, size=n)]).astype(
        np.int32) for n in (2, 9)]
    sched.start()
    for p in prompts:       # one after the other: the second finds the first
        out = sched.submit(p, max_new_tokens=24).result(timeout=120)
        seq = list(p)
        for tok in out:
            assert tok == int(np.argmax(np.bincount(seq, minlength=V)))
            seq.append(tok)
    sched.stop()
    # the second request maps the two whole pages of the shared prefix
    assert [c for _, c in placed] == [0, 2 * APS], placed
    assert obs.counter("serving.decode.kv_guard_trips").value == 0
    st = sched.cache_stats()
    assert st["rc_errors"] == [] and st["rc_sum_matches"], st
