"""The EvaByte family (``models/evabyte.py``) through the serving path against
its plain reference (``chipbench/configs/evabyte_6_5b.reference.py``) on the
CPU at toy sizes with seeded float32 weights: the logits of all 8 prediction
heads after chunked prefill (two chunk widths) and through decode across two
window boundaries, from a cache in two page GROUPS (summaries on pages of
their own size in the first, K and V under an ALIGNED window in the second),
the window's pages given back whole and poisoned as they go; the structure
(``W >= T`` is plain causal attention; ``phi`` and ``mu`` both matter; a decode
step and the chunk program write the same summary); a reseated slot over a
poisoned pool; the engine's served bytes and counters; the kernel against its
XLA form.

At these sizes the model runs in float32 end to end, so the system differs
from the reference only by the ORDER of float32 operations.
"""
import collections
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models import evabyte as M
from paddle_tpu.parallel import flash_attention as FA

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REFERENCE = os.path.join(ROOT, "chipbench/configs/evabyte_6_5b.reference.py")

CFG = dict(
    attention_bias=False, hidden_act="silu", hidden_size=64,
    intermediate_size=96, vocab_size=320, num_pred_heads=8,
    num_attention_heads=2, num_key_value_heads=2, num_hidden_layers=2,
    rms_norm_eps=1e-5, norm_add_unit_offset=True, tie_word_embeddings=False,
    window_size=32, chunk_size=4, num_chunks=None, rope_theta=100000,
    rope_scaling=None, summary_page_rows=4)
W, C = CFG["window_size"], CFG["chunk_size"]
PAGE, SLOTS, MAX_LEN = 8, 3, 128
SUM_PAGE = CFG["summary_page_rows"] * C          # 16 tokens a summary page
CHUNK, BUCKETS = 16, (8, 16, 128)
LOGIT_TOL = 2e-4        # max |a - b| / std(b): float32 reordering only
POISON = 3e4            # what a page that was given back holds


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("evabyte_reference",
                                                  REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return M.params(CFG, 0, dtype="float32")


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(1).randint(0, 320, size=MAX_LEN).astype(
        np.int32)


@pytest.fixture(scope="module")
def truth(reference, params, tokens):
    """The reference's logits ``[T, 8, 320]`` at every position."""
    logits, _, sums = jax.jit(lambda p, t: reference.forward(
        p, CFG, t, jnp.arange(MAX_LEN), block=16))(params, jnp.asarray(tokens))
    return np.asarray(logits, np.float64), [np.asarray(s) for s in sums]


@pytest.fixture(scope="module")
def fns():
    return (jax.jit(lambda p, c, *a: M.prefill_chunk(
                p, *a[:3], c, *a[3:], cfg=CFG, with_heads=True)),
            jax.jit(lambda p, c, *a: M.decode_step(
                p, *a[:2], c, *a[2:], cfg=CFG, with_heads=True)))


def _cache(poisoned=False):
    layout = M.cache_layout(CFG)
    groups = {g: dict(spec, num_pages=n) for (g, spec), n in zip(
        layout["page_groups"].items(), (1 + SLOTS * MAX_LEN // SUM_PAGE,
                                        1 + 8))}
    cache = serving.PagedKVCache(
        0, None, PAGE, 0, 0, MAX_LEN, dtype="float32", num_slots=SLOTS,
        page_pools=layout["page_pools"], page_groups=groups)
    if poisoned:
        cache.pools = {n: jnp.full_like(a, POISON)
                       for n, a in cache.pools.items()}
    return cache


def _err(got, want):
    return float(np.max(np.abs(got - want)) / want.std())


def _through_the_cache(fns, params, tokens, prompt, steps, cache, slot=0,
                       poison=True):
    """Prefill ``tokens[:prompt]`` in chunks of ``CHUNK`` and a narrow tail,
    then decode ``steps`` tokens in ``slot``, the pages handed out and given
    back as the scheduler does it (a window's pages back, and poisoned, when
    the next position reaches a multiple of the window).  Returns the logits
    ``[1 + steps, 8, 320]`` at positions ``prompt - 1 ..``, the counters of
    every decode step and the window pages released."""
    grp = cache.groups["window"]
    width = grp.table_width(MAX_LEN, CHUNK)
    assert width == W // PAGE and grp.slot_bound(MAX_LEN, CHUNK) == width + 1
    pages = cache.alloc(cache.pages_for(prompt + steps))
    table = np.zeros((SLOTS, cache.max_pages_per_seq), np.int32)
    table[slot] = cache.table_row(pages)
    ring = np.zeros((SLOTS, width), np.int32)
    held, base, released = collections.deque(), [0], [0]

    def reach(upto):
        for p in range(base[0] + len(held), -(-upto // PAGE)):
            held.append(grp.alloc(1)[0])
            ring[slot, p % width] = held[-1]
        assert len(held) <= width + 1

    def leave(next_pos):
        live = grp.first_live_page(next_pos)
        dead = [held.popleft() for _ in range(min(live - base[0], len(held)))]
        for p, page in enumerate(dead, base[0]):
            if ring[slot, p % width] == page:
                ring[slot, p % width] = 0
        if dead and poison:
            idx = jnp.asarray(dead)
            for leaf in ("k", "v"):
                cache.pools[leaf] = cache.pools[leaf].at[:, idx].set(POISON)
        grp.free(dead, released=True)
        base[0] += len(dead)
        released[0] += len(dead)

    logits = []
    start = 0
    while start < prompt:
        left = prompt - start
        w = min(b for b in BUCKETS if b >= min(left, CHUNK))
        valid = min(left, w)
        reach(start + valid)
        toks = np.zeros(w, np.int32)
        toks[:valid] = tokens[start:start + valid]
        vec_w = np.asarray([ring[slot, (start // PAGE + i) % width]
                            if start // PAGE + i < -(-prompt // PAGE) else 0
                            for i in range(w // PAGE)], np.int32)
        vec_s = np.asarray([pages[start // SUM_PAGE + i]
                            for i in range(max(1, w // SUM_PAGE))], np.int32)
        _, cache.pools, heads = fns[0](
            params, cache.pools, jnp.asarray(toks), jnp.int32(start),
            jnp.int32(valid),
            {"summary": jnp.asarray(vec_s), "window": jnp.asarray(vec_w)},
            {"summary": jnp.asarray(table[slot]),
             "window": jnp.asarray(ring[slot].copy())}, jnp.int32(slot))
        start += valid
        leave(start)
    logits.append(np.asarray(heads, np.float64))
    counts = []
    for pos in range(prompt, prompt + steps):
        reach(pos + 1)
        toks, positions = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
        lens = np.zeros(SLOTS, np.int32)
        toks[slot], positions[slot], lens[slot] = tokens[pos], pos, pos + 1
        _, cache.pools, count, heads = fns[1](
            params, cache.pools, jnp.asarray(toks), jnp.asarray(positions),
            {"summary": jnp.asarray(table), "window": jnp.asarray(ring.copy())},
            jnp.asarray(lens))
        logits.append(np.asarray(heads[slot], np.float64))
        counts.append(np.asarray(count))
        leave(pos + 1)
    where = dict(pages=pages, held=list(held), first=base[0])
    cache.free(pages)
    grp.free(list(held))
    return np.stack(logits), np.stack(counts), released[0], where


# prompts whose lengths are 0, 1, C - 1 and C + 1 modulo C and that end inside
# a window, on a boundary and one past it
@pytest.mark.parametrize("prompt", [24, 32, 33, 27, 37])
def test_all_eight_heads_follow_the_reference_through_two_boundaries(
        fns, params, tokens, truth, prompt):
    """Chunks of 16 and a tail of 8 or 16, then decode to position 100: past
    64 and 96, so summaries written by decode steps, summaries written by the
    chunk program, a window given back (poisoned) and a window begun from one
    row are all read before the comparison ends."""
    steps = 100 - prompt
    logits, counts, released, _ = _through_the_cache(
        fns, params, tokens, prompt, steps, _cache())
    want = truth[0][prompt - 1:prompt + steps]
    assert logits.shape == want.shape == (steps + 1, 8, 320)
    assert np.isfinite(logits).all()
    assert _err(logits, want) <= LOGIT_TOL
    assert released == (100 // W) * (W // PAGE)
    # the step's counters: rows of the window so far, summaries of the
    # windows before, a chunk every C steps, a window every W
    pos = np.arange(prompt, prompt + steps)
    assert counts[:, 0].tolist() == (pos % W + 1).tolist()
    assert counts[:, 1].tolist() == (pos // W * (W // C)).tolist()
    assert counts[:, 2].tolist() == ((pos + 1) % C == 0).astype(int).tolist()
    assert counts[:, 3].tolist() == ((pos + 1) % W == 0).astype(int).tolist()


def test_a_reseated_slot_reads_nothing_of_its_last_occupant(
        fns, params, tokens, truth):
    """Every page of both pools holds 3e4 before the sequence is seated (what
    a last occupant, or anyone, may have left): the visible COUNTS keep the
    slot from reading a row it did not write, nothing is zeroed for it."""
    logits, _, _, _ = _through_the_cache(
        fns, params, tokens, 37, 40, _cache(poisoned=True), slot=1)
    assert _err(logits, truth[0][36:77]) <= LOGIT_TOL


def test_a_decode_step_and_the_chunk_program_write_the_same_summary(
        fns, params, tokens, truth):
    """Chunks 4 and 5 (positions 16 .. 23) summarised by the chunk program
    (prompt 24) and by decode steps (prompt 16, 8 steps): the same rows, and
    the reference's."""
    got = []
    for prompt in (24, 16):
        cache = _cache()
        *_, where = _through_the_cache(fns, params, tokens, prompt,
                                       24 - prompt + 1, cache, poison=False)
        page = where["pages"][16 // SUM_PAGE]
        got.append([np.asarray(cache.pools[leaf][:, page, :2])
                    for leaf in ("ksum", "vsum")])
    for a, b in zip(*got):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    for layer in range(CFG["num_hidden_layers"]):
        for which in range(2):
            np.testing.assert_allclose(
                got[0][which][layer], truth[1][layer][4:6, which],
                rtol=1e-4, atol=1e-5)


def test_a_window_no_shorter_than_the_sequence_is_plain_causal_attention():
    """``W >= T``: no summary is ever visible, and EVA over the pages is what
    ``mha_reference`` gives on the same rows."""
    T, H, Dh = 24, 2, 32
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.standard_normal((T, H, Dh)), jnp.float32)
               for _ in range(3))
    pages = jnp.arange(1, 1 + T // PAGE)
    pool = lambda rows: jnp.zeros((1, 1 + T // PAGE, PAGE, H * Dh)).at[
        0, pages].set(rows.reshape(T // PAGE, PAGE, H * Dh))
    sums = jnp.full((1, 3, 4, H * Dh), POISON)
    got = FA.paged_eva_prefill_attention(
        q, pool(k), pool(v), sums, sums, pages, jnp.asarray([1, 2]),
        jnp.int32(0), T, C, layer=0)
    want = FA.mha_reference(*(a.transpose(1, 0, 2)[None] for a in (q, k, v)),
                            causal=True)[0].transpose(1, 0, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("which", ["phi", "mu"])
def test_the_pooling_vectors_both_move_the_output(fns, params, tokens, which):
    """Zeroing ``phi`` (uniform pooling) or ``mu`` (no shift of the pooled
    key) changes the logits once a summary is visible, and nothing before."""
    zeroed = dict(params, **{which: jnp.zeros_like(params[which])})
    a, b = (_through_the_cache(fns, p, tokens, 24, 16, _cache())[0]
            for p in (params, zeroed))
    before, after = slice(0, 8), slice(9, None)        # positions 23..30, 32..
    assert _err(a[before], b[before]) == 0.0
    assert _err(a[after], b[after]) > 1e-3


# -- the kernel against its XLA form -------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_decode_kernel_is_one_softmax_over_both_lists(dtype):
    """``paged_eva_decode_attention`` in interpret mode against the XLA form:
    slots with a window of one row, a full window, no summary, many summaries
    and nothing at all; two pages a turn, pages of two sizes; the pools'
    unread rows poisoned."""
    S, H, Dh, psw, pss = 6, 2, 64, 8, 4
    rng = np.random.RandomState(5)
    dt = jnp.dtype(dtype)

    def pool(n, ps):
        return jnp.asarray(rng.standard_normal((2, n, ps, H * Dh)), dt)

    k, v, ks, vs = pool(33, psw), pool(33, psw), pool(41, pss), pool(41, pss)
    tw = jnp.asarray(rng.permutation(np.arange(1, 33))[:S * 4].reshape(S, 4))
    ts = jnp.asarray(rng.permutation(np.arange(1, 41))[:S * 6].reshape(S, 6))
    lw = jnp.asarray([1, 32, 9, 17, 0, 8])
    ls = jnp.asarray([0, 24, 8, 3, 0, 16])
    q = jnp.asarray(rng.standard_normal((S, H, Dh)), dt)
    args = (q, k, v, ks, vs, tw, ts, lw, ls)
    want = FA.paged_eva_decode_attention(*args, layer=1, impl="reference")
    orig = FA._EVA_TURN_KEYS
    FA._EVA_TURN_KEYS = 16         # two window pages, four summary pages a turn
    try:
        got = FA.paged_eva_decode_attention(*args, layer=1, impl="pallas",
                                            interpret=True)
    finally:
        FA._EVA_TURN_KEYS = orig
    assert not np.asarray(got[4]).any() and not np.asarray(want[4]).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # and it is NOT the two lists' softmaxes taken apart and averaged
    apart = 0.5 * (
        FA.paged_eva_decode_attention(q, k, v, ks, vs, tw, ts, lw, 0 * ls,
                                      layer=1, impl="reference")
        + FA.paged_eva_decode_attention(q, k, v, ks, vs, tw, ts, 0 * lw, ls,
                                        layer=1, impl="reference"))
    assert float(jnp.abs(apart[1] - want[1]).max()) > 1e-2


# -- through the engine ---------------------------------------------------------

def test_the_engine_serves_head_zero_through_window_boundaries(
        reference, params):
    """``InferenceEngine.generate`` over the model's own ``cache_layout``:
    three prompts over two slots, 70 new bytes each (two boundaries), one
    step in flight.  Every served byte is the top of the reference's head 0
    over the same bytes; every page of both groups comes back; the counters
    count what the contexts imply."""
    names = ["serving.decode.eva." + n for n in (
        "window_rows_read", "summary_rows_read", "chunks_summarised",
        "windows_closed")] + ["serving.cache.window.pages_released"]
    before = {n: obs.counter(n).value for n in names}
    engine = serving.InferenceEngine(
        decode_model=M.build_decode_model(params, CFG),
        decode_config=serving.DecodeConfig(
            num_slots=2, page_size=PAGE, max_seq_len=MAX_LEN,
            num_pages={"summary": 17, "window": 11},
            prefill_buckets=BUCKETS, prefill_chunk_tokens=CHUNK,
            prefix_cache=False, max_new_tokens=70, kv_dtype="float32"))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 320, size=n).astype(np.int32)
               for n in (33, 5, 48)]
    try:
        sched = engine.decoder
        assert sched.cache.page_size == SUM_PAGE
        assert sched.cache.groups["window"].page_size == PAGE
        assert sched._more_tables["window"].shape == (2, W // PAGE)
        futures = [engine.generate_async(p, max_new_tokens=70)
                   for p in prompts]
        outs = [np.asarray(f.result(timeout=300)) for f in futures]
    finally:
        engine.stop()
    fwd = jax.jit(lambda p, t: reference.forward(
        p, CFG, t, jnp.arange(MAX_LEN), block=16)[0])
    for prompt, out in zip(prompts, outs):
        seq = np.zeros(MAX_LEN, np.int32)
        seq[:len(prompt) + 70] = np.concatenate([prompt, out])
        head0 = np.asarray(fwd(params, jnp.asarray(seq)))[:, 0]
        at = len(prompt) - 1 + np.arange(70)
        top = head0[at].max(axis=1)
        gaps = (top - head0[at, out]) / head0[at].std(axis=1)
        assert gaps.max() <= 1e-3, gaps.max()
    st = sched.cache_stats()
    for g in ("summary", "window"):
        assert st["groups"][g]["used_pages"] == 0, st
        assert st["groups"][g]["rc_errors"] == []
    assert st["groups"]["window"]["reserved_pages"] == 0
    moved = {n.rsplit(".", 1)[1]: obs.counter(n).value - before[n]
             for n in names}
    ends = [len(p) + 70 for p in prompts]
    closed = sum(e // W - len(p) // W for e, p in zip(ends, prompts))
    assert moved["pages_released"] == sum(
        (e - 1) // W for e in ends) * (W // PAGE)
    # the last sampled byte of a request is never fed: 69 steps each
    assert moved["windows_closed"] in (closed, closed - 1, closed - 2,
                                       closed - 3)
    assert moved["chunks_summarised"] >= sum(
        (e - 1) // C - len(p) // C for e, p in zip(ends, prompts))
    assert moved["summary_rows_read"] > 0 < moved["window_rows_read"]


def test_the_summary_groups_rows_a_page_have_to_be_stated():
    layout = M.cache_layout(CFG)
    assert layout["page_groups"]["summary"]["page_size"] == SUM_PAGE
    cfg = {k: v for k, v in CFG.items() if k != "summary_page_rows"}
    with pytest.raises(ValueError, match="summary_page_rows"):
        M.cache_layout(cfg)
