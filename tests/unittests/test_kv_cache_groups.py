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

def _histogram(leaf, table, lens, lo):
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


def test_admission_waits_while_either_group_is_short():
    """The window group holds ONE slot's bound: the second request parks at
    the head of the line though the full group has room, and is admitted when
    the first retires; then the other way round."""
    sched = serving.DecodeScheduler(
        _model(), _config(num_pages={"full": 33, "window": 6}))
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
    sched = serving.DecodeScheduler(
        _model(), _config(num_pages={"full": 13, "window": 11}))
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
    sched = serving.DecodeScheduler(_model(), _config())
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
