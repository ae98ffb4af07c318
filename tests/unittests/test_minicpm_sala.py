"""MiniCPM-SALA through the serving path against its plain reference
(``chipbench/configs/minicpm_sala_9b.reference.py``), on the CPU at tiny
widths with seeded float32 weights: logits of chunked prefill and of decode
through the cache, the selected block sets, the lightning scan, slot reuse and
continuous batching, the grouped / selected-page kernels in interpret mode,
the depth cut, weights as arguments, and the refusals of a slot-state model.

Every tolerance says why it is what it is.  At these sizes the model runs in
float32 end to end (activations follow the weights' dtype), so the system
differs from the reference only by the ORDER of float32 operations (online
softmax against one softmax, the chunk-wise scan against the recurrence,
half-kernel means against kernel means): 1e-4 of the logits' spread holds
that, and anything computed in bfloat16 where float32 is stated (2 to 3
decimal digits) breaks it — ``test_a_bfloat16_state_would_fail`` shows it.
"""
import functools
import importlib.util
import json
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import serving
from paddle_tpu.models import minicpm_sala as M
from paddle_tpu.models import transformer as T
from paddle_tpu.parallel import flash_attention as FA
from paddle_tpu.serving import step_programs

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIG = os.path.join(ROOT, "chipbench/configs/minicpm_sala_9b.json")

CFG = dict(
    hidden_size=64, intermediate_size=128, vocab_size=100,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    rms_norm_eps=1e-6, rope_theta=10000, scale_emb=12, scale_depth=1.4,
    mup_denominator=32, dim_model_base=16,
    # contexts cross dense_len (32) and hold more blocks (12) than the 1 + 2
    # forced and the top 2: the selection really cuts
    sparse_config=dict(kernel_size=4, kernel_stride=2, block_size=8, topk=2,
                       init_blocks=1, window_size=16, dense_len=32))
PAGE, SLOTS, MAX_LEN, T_PAD = 8, 3, 96, 96
LOGIT_TOL = 1e-4        # max |a - b| / std(b): float32 reordering only


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "sala_reference", os.path.splitext(CONFIG)[0] + ".reference.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return M.sala_params(CFG, 0, dtype="float32")


@pytest.fixture(scope="module")
def decode_model(params):
    """One model object for the module: every scheduler over it dispatches
    the model's own step programs, so a shape is traced once."""
    return M.build_decode_model(params, CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(1).randint(1, 100, size=T_PAD).astype(np.int32)


# jitted once for the module: eager dispatch of a whole step is what takes
# the time at these sizes
_CHUNK = jax.jit(functools.partial(M.sala_prefill_chunk, cfg=CFG,
                                   with_selection=True))
# the decode step by engine: None is the backend's choice (on the CPU the XLA
# scores over the gathered span and the reference attention), "pallas" the
# two kernels a chip runs, interpreted
_DECODE = {engine: jax.jit(functools.partial(
    M.sala_decode_step, cfg=CFG, with_selection=True, attn_impl=engine))
    for engine in (None, "pallas")}
ENGINES = pytest.mark.parametrize("engine", [None, "pallas"],
                                  ids=["xla", "pallas"])


def _cache():
    lay = M.cache_layout(CFG)
    return serving.PagedKVCache(
        lay["num_layers"], SLOTS * (MAX_LEN // PAGE) + 1, PAGE,
        lay["num_heads"], lay["head_dim"], MAX_LEN, dtype="float32",
        page_pools=lay["page_pools"], slot_state=lay["slot_state"],
        num_slots=SLOTS)


def _through_the_cache(params, tokens, prompt_len, steps, chunk, slot=1,
                       between=None, engine=None):
    """Prefill ``tokens[:prompt_len]`` in chunks of ``chunk`` into ``slot``,
    then decode ``steps`` tokens (teacher forced) through the cache with the
    decode step of ``engine``.  Returns the logits at positions ``prompt_len
    - 1 ..`` and the selections there."""
    cache = _cache()
    pages = cache.alloc(cache.pages_for(prompt_len + steps))
    row = cache.table_row(pages)
    pools = cache.pools
    start, logits, sels = 0, [], []
    while start < prompt_len:
        valid = min(chunk, prompt_len - start)
        window = np.zeros(chunk, np.int32)
        window[:valid] = tokens[start:start + valid]
        vec = np.zeros(chunk // PAGE, np.int32)
        n = min(len(vec), len(pages) - start // PAGE)
        vec[:n] = pages[start // PAGE:start // PAGE + n]
        lg, pools, masks = _CHUNK(
            params, jnp.asarray(window), jnp.int32(start), jnp.int32(valid),
            pools, jnp.asarray(vec), jnp.asarray(row), jnp.int32(slot))
        start += valid
    logits.append(np.asarray(lg))
    sels.append([np.asarray(m)[valid - 1] for m in masks])
    tables = np.zeros((SLOTS, cache.max_pages_per_seq), np.int32)
    tables[slot] = row
    for t in range(prompt_len, prompt_len + steps):
        toks, pos, lens = (np.zeros(SLOTS, np.int32) for _ in range(3))
        toks[slot], pos[slot], lens[slot] = tokens[t], t, t + 1
        lg, pools, counts, masks = _DECODE[engine](
            params, jnp.asarray(toks), jnp.asarray(pos), pools,
            jnp.asarray(tables), jnp.asarray(lens))
        if between is not None:
            pools = between(pools)
        logits.append(np.asarray(lg)[slot])
        sels.append([np.asarray(m)[slot] for m in masks])
    return np.stack(logits), sels, np.asarray(counts)


def _err(a, b):
    return float(np.max(np.abs(a - b)) / np.std(b))


@pytest.fixture(scope="module")
def truth(reference, params, tokens):
    """The reference's one full forward pass: logits and selected sets at
    positions 59 .. 79 (prompt 60, 20 decoded tokens)."""
    pos = jnp.arange(59, 80, dtype=jnp.int32)
    logits, sel = jax.jit(lambda p, s, q: reference.forward(
        p, CFG, s, q, block=16))(params, jnp.asarray(tokens), pos)
    return np.asarray(logits), [np.asarray(s) for s in sel]


# 1. system = reference, in logits ------------------------------------------

@pytest.mark.parametrize("chunk", [8, 16, 64], ids=["page", "chunk", "bucket"])
def test_chunked_prefill_then_decode_equals_the_reference(params, tokens, truth,
                                                          chunk):
    logits, _, counts = _through_the_cache(params, tokens, 60, 20, chunk)
    assert logits.shape == truth[0].shape
    for got, want in zip(logits, truth[0]):
        assert _err(got, want) < LOGIT_TOL
    # the last step's counters: 2 sparse layers x 2 KV heads over 80 visible
    # tokens, of which block 0, the window's blocks 8 and 9 and the top 2 are
    # read (5 whole pages of 8)
    assert counts[1] == 80 * 4 and counts[2] == 0
    assert counts[0] == 4 * (5 * 8)


def test_a_bfloat16_state_would_fail(params, tokens, truth):
    """The bound is tight enough: the same path with the lightning state
    rounded to bfloat16 after every step is over 10 times outside it (21 x
    measured)."""
    def rounded(pools):
        return dict(pools, lin=pools["lin"].astype(jnp.bfloat16).astype(
            jnp.float32))

    logits, _, _ = _through_the_cache(params, tokens, 60, 20, 16,
                                      between=rounded)
    assert _err(logits[-1], truth[0][-1]) > 10 * LOGIT_TOL


# 2. the selected sets --------------------------------------------------------

@ENGINES
def test_selected_sets_equal_the_reference(params, tokens, truth, engine):
    """Whichever engine scores the pooled keys (the XLA contraction over the
    gathered span, or the kernel that walks them in place), the pick is the
    reference's: the two engines' masks are bit-equal."""
    _, sels, _ = _through_the_cache(params, tokens, 60, 20, 16, engine=engine)
    for step, per_layer in enumerate(sels):
        for layer, mask in enumerate(per_layer):
            want = truth[1][layer][step]
            np.testing.assert_array_equal(mask[:, :want.shape[-1]], want)
            assert not mask[:, want.shape[-1]:].any()
            n = 60 + step
            first, cur = (n - 16) // 8, (n - 1) // 8
            # forced blocks are always in; the selection really cuts
            assert mask[:, 0].all() and mask[:, first:cur + 1].all()
            assert (mask.sum(axis=-1) == 1 + (cur - first + 1) + 2).all()
            assert mask.sum(axis=-1).max() < cur + 1


def _select(q, hb, n):
    return np.asarray(M.select_blocks(M._dims(CFG), q, hb, n))


def test_below_dense_len_every_visible_block_is_read():
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 3, 4, 16), jnp.float32)
    hb = jnp.asarray(rng.randn(1, 48, 2, 16), jnp.float32)
    mask = _select(q, hb, jnp.asarray([[32, 9, 0]], jnp.int32))[0]
    assert mask[0].sum(axis=-1).tolist() == [4, 4]      # n = dense_len: dense
    assert mask[1, :, :2].all() and mask[1].sum() == 4
    assert not mask[2].any()                            # no row


def test_a_near_tie_changes_at_most_the_tied_blocks():
    """Three candidate blocks for the top 2: block 3 clearly first, blocks 2
    and 5 EXACTLY tied behind it: the lower one wins; a 1% nudge to block 5
    swaps exactly those two, everything else stands."""
    rng = np.random.RandomState(4)
    hit = rng.randn(2, 16).astype(np.float32)               # one per KV head
    q = jnp.asarray(np.repeat(hit, 2, axis=0)[None, None])  # [1,1,4,16]
    n = jnp.asarray([[88]], jnp.int32)     # 11 blocks; the window = 9 and 10

    def selected(b5):
        hb = np.zeros((1, 48, 2, 16), np.float32)
        hb[0, 4 * 3 + 1], hb[0, 4 * 2 + 1], hb[0, 4 * 5 + 1] = (
            hit * 0.5, hit * 0.4, hit * b5)
        return _select(q, jnp.asarray(hb), n)[0, 0]

    base = selected(0.4)
    assert base[:, [0, 2, 3, 9, 10]].all() and base.sum() == 2 * 5
    nudged = selected(0.404)
    assert nudged[:, [0, 3, 5, 9, 10]].all() and nudged.sum() == 2 * 5
    assert (nudged != base)[:, [2, 5]].all() and (nudged != base).sum() == 4


def test_the_split_left_select_blocks_as_it_was():
    """``select_blocks`` is the scores and the pick from them since PR 50;
    its mask on seeded inputs is the one the unsplit function gave (digest
    taken on the parent commit)."""
    import hashlib

    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(2, 3, 4, 16), jnp.float32)
    hb = jnp.asarray(rng.randn(2, 48, 2, 16), jnp.float32)
    n = jnp.asarray([[88, 41, 0], [96, 33, 70]], jnp.int32)
    mask = _select(q, hb, n)
    assert mask.shape == (2, 3, 2, 12) and mask.sum() == 54
    assert hashlib.sha256(np.packbits(mask).tobytes()).hexdigest() == (
        "e2aefcfbfb3dfd432b1ecfc5334b4f8d208cf6ed5e119547f224ad96e052b04f")
    d = M._dims(CFG)
    score = FA.block_scores(q, hb, n, kernel_size=d["l"], stride=d["s"],
                            block_size=d["B"])
    np.testing.assert_array_equal(np.asarray(M._pick_blocks(d, score, n)),
                                  mask)


# the scoring kernel's edges: a context that ends mid half-kernel (37), at a
# page boundary (48), at the table's last token (96), an idle slot (0), one
# shorter than a kernel (3), one kernel exactly (4), mid page (61)
_SCORE_LENS = (37, 48, 96, 0, 3, 4, 61)


def _score_case(seed, garbage):
    """``(q, clean pool, dirty pool, tables, lens)``: PERMUTED page tables of
    12 pages a slot; ``dirty`` holds ``garbage`` wherever no score may come
    from: pages no table lists, the scratch page unused entries name, and
    every row past a context's last complete half-kernel."""
    rng = np.random.RandomState(seed)
    d = M._dims(CFG)
    S, MP, per = len(_SCORE_LENS), MAX_LEN // PAGE, PAGE // d["s"]
    P = S * MP + 1
    lens = np.asarray(_SCORE_LENS, np.int32)
    clean = rng.randn(2, P, per, d["Hkv"] * d["Dh"]).astype(np.float32)
    dirty = np.full_like(clean, garbage)
    free = rng.permutation(P - 1) + 1
    tables = np.zeros((S, MP), np.int32)        # 0 = the scratch page
    for s, n in enumerate(lens):
        pages = free[s * MP:s * MP + -(-int(n) // PAGE)]
        tables[s, :len(pages)] = pages
        rows = int(n) // d["s"]                 # complete half-kernels
        for i, page in enumerate(pages):
            keep = max(0, min(per, rows - i * per))
            dirty[:, page, :keep] = clean[:, page, :keep]
    q = rng.randn(S, d["Hq"], d["Dh"]).astype(np.float32)
    return tuple(jnp.asarray(x) for x in (q, clean, dirty, tables, lens))


@pytest.mark.parametrize("garbage", [1e30, np.nan], ids=["huge", "nan"])
@pytest.mark.parametrize("turn_pages", [None, 4, 5],
                         ids=["one-turn", "turns-of-4", "turns-of-5"])
@pytest.mark.parametrize("layer", [0, 1])
def test_the_scoring_kernel_is_the_xla_scores(monkeypatch, layer, turn_pages,
                                              garbage):
    """``paged_block_scores`` (interpreted) against ``block_scores`` of the
    gathered CLEAN span: equal to float32 rounding (the products are summed in
    another order: 1e-6 of a probability), although the kernel's pool holds
    garbage in every row and page it must not read into a score."""
    d = M._dims(CFG)
    if turn_pages:
        monkeypatch.setattr(FA, "_scores_turn_pages", lambda mp: turn_pages)
    q, clean, dirty, tables, lens = _score_case(11 + layer, garbage)
    hb = clean[layer, tables].reshape(len(_SCORE_LENS), -1, d["Hkv"], d["Dh"])
    want = FA.block_scores(q[:, None], hb, lens[:, None], kernel_size=d["l"],
                           stride=d["s"], block_size=d["B"])[:, 0]
    got = FA.paged_block_scores(
        q, dirty, tables, lens, layer=layer, kernel_size=d["l"],
        stride=d["s"], impl="pallas", interpret=True)
    assert got.shape == want.shape == (len(_SCORE_LENS), d["Hkv"],
                                       MAX_LEN // PAGE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)
    # a probability a pooled key, summed over the group's two rows
    assert float(want.max()) > 0.1
    got = np.asarray(got)
    assert not got[[3, 4]].any()            # no complete kernel: no score
    assert got[5, :, 0].tolist() == [2.0, 2.0] and not got[5, :, 1:].any()
    # and the reference engine of the same entry point reads the same
    ref = FA.paged_block_scores(
        q, clean, tables, lens, layer=layer, kernel_size=d["l"],
        stride=d["s"], impl="reference")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(want))


def test_a_traced_decode_step_says_it_scores_with_the_kernel(params):
    """``paged.select.grid_steps`` is set at trace time, once a compiled
    shape (two sparse layers trace the kernel twice): one walk a slot."""
    from paddle_tpu import observability as obs

    slots = 5                       # a shape no other test of the module has
    d = M._dims(CFG)
    mp = MAX_LEN // PAGE
    cell = obs.counter("paged.select.grid_steps", labels={
        "S": slots, "heads": d["Hkv"], "mp": mp, "ps": PAGE,
        "turn": mp * PAGE})
    assert cell.value == 0
    lay = M.cache_layout(CFG)
    pools = serving.PagedKVCache(
        lay["num_layers"], slots * mp + 1, PAGE, lay["num_heads"],
        lay["head_dim"], MAX_LEN, dtype="float32",
        page_pools=lay["page_pools"], slot_state=lay["slot_state"],
        num_slots=slots).pools
    ints = jnp.zeros((slots,), jnp.int32)
    step = jax.jit(functools.partial(M.sala_decode_step, cfg=CFG,
                                     attn_impl="pallas"))
    for _ in range(2):
        step.lower(params, ints, ints, pools,
                   jnp.zeros((slots, mp), jnp.int32), ints)
        assert cell.value == slots


# 3. the lightning scan -------------------------------------------------------

@pytest.mark.parametrize("width", [1, 8, 64])
def test_chunked_lightning_is_the_recurrence(reference, width):
    rng = np.random.RandomState(5)
    d = M._dims(CFG)
    q, k, v = (jnp.asarray(rng.randn(64, 4, 16), jnp.float32) for _ in "qkv")
    s0 = jnp.asarray(rng.randn(4, 16, 16), jnp.float32)
    want_o, want_s = reference.lightning_recurrence(q, k, v, s0, 59)
    S, outs = s0, []
    for a in range(0, 64, width):
        o, S = M._lightning_chunk(d, M.lightning_slopes(4), q[a:a + width],
                                  k[a:a + width], v[a:a + width], S,
                                  jnp.int32(59 - a))
        outs.append(o)
    # float32 sums in another order; the slopes reach exp(-0.84 x 8) per block
    np.testing.assert_allclose(np.concatenate(outs)[:59],
                               np.asarray(want_o)[:59], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(S, want_s, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(M.lightning_slopes(4), reference.slopes(4))


# 4. slots and batching -------------------------------------------------------

def _scheduler(model, **over):
    cfg = dict(num_slots=SLOTS, page_size=PAGE, max_seq_len=MAX_LEN,
               prefill_chunk_tokens=16, prefill_buckets=(8, 16, MAX_LEN),
               max_new_tokens=8)
    cfg.update(over)
    return serving.DecodeScheduler(model, serving.DecodeConfig(**cfg))


def test_a_reused_slot_and_mixed_batches_serve_what_a_fresh_engine_serves(
        decode_model, tokens):
    prompts = [tokens[:70], tokens[5:25], tokens[30:79], tokens[2:50]]
    solo = _scheduler(decode_model, max_active=1, num_slots=1)
    want = [solo.generate(p, max_new_tokens=8, timeout=300) for p in prompts]
    # slot 0 of the one-slot engine served four sequences in turn: each must
    # equal a FRESH engine's answer (the state reset)
    for p, w in list(zip(prompts, want))[-1:]:
        fresh = _scheduler(decode_model, max_active=1, num_slots=1)
        np.testing.assert_array_equal(
            fresh.generate(p, max_new_tokens=8, timeout=300), w)
        fresh.stop()
    solo.stop()
    batch = _scheduler(decode_model)
    futures = [batch.submit(p, max_new_tokens=8) for p in prompts]
    for f, w in zip(futures, want):
        np.testing.assert_array_equal(f.result(timeout=300), w)   # bitwise
    assert batch.stats()["kv_pages_used"] == 0
    batch.stop()


@ENGINES
def test_run_step_is_the_served_program_on_the_served_cache(
        decode_model, params, tokens, engine):
    """What the benchmark's check reads the cache's guarantees from: a
    stopped scheduler runs its own warmed programs on its own cache, with no
    compile, and gives the token it served: on either engine (``pallas``:
    the step built on the kernels, as a chip serves it)."""
    from paddle_tpu import executor

    sched = _scheduler(decode_model if engine is None else
                       M.build_decode_model(params, CFG, attn_impl=engine))
    served = sched.generate(tokens[:16], max_new_tokens=2, timeout=300)
    with pytest.raises(serving.ServingError, match="owns the cache"):
        sched.run_step(("decode",))
    sched.stop()
    cache = sched.cache
    pages = cache.alloc(cache.pages_for(17))
    compiles = executor.compile_count()
    tok = sched.run_step(
        ("chunk", 16), jnp.asarray(tokens[:16]), jnp.int32(0), jnp.int32(16),
        jnp.asarray(np.asarray(pages[:2], np.int32)),
        jnp.asarray(cache.table_row(pages)), np.int32(0), np.uint32(0),
        np.float32(0))
    assert int(tok) == served[0]
    tables = np.zeros((SLOTS, cache.max_pages_per_seq), np.int32)
    tables[0] = cache.table_row(pages)
    feed = np.zeros(SLOTS, np.int32)
    feed[0] = served[0]
    at = np.zeros(SLOTS, np.int32)
    at[0] = 16
    out = sched.run_step(
        ("decode",), jnp.asarray(feed), jnp.asarray(at), jnp.asarray(tables),
        jnp.asarray(np.where(np.arange(SLOTS) == 0, 17, 0).astype(np.int32)),
        jnp.zeros((SLOTS,), jnp.uint32), jnp.zeros((SLOTS,), jnp.float32))
    assert int(np.asarray(out)[0]) == served[1]
    assert executor.compile_count() == compiles
    cache.free(pages)


# 5. the grouped / selected-page kernels in interpret mode ----------------------

@pytest.mark.parametrize("g", [1, 16])
@pytest.mark.parametrize("selected", [False, True], ids=["rows", "selection"])
def test_grouped_kernels_in_interpret_mode(reference, g, selected):
    rng = np.random.RandomState(6)
    Hkv, Dh, ps, mp, S, C = 2, 16, 8, 6, 3, 16
    kp = jnp.asarray(rng.randn(40, ps, Hkv, Dh), jnp.float32)
    vp = jnp.asarray(rng.randn(40, ps, Hkv, Dh), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(S * mp).reshape(S, mp), jnp.int32)
    lens = np.asarray([0, 17, 48], np.int32)
    q = jnp.asarray(rng.randn(S, g * Hkv, Dh), jnp.float32)
    blocks = rng.rand(S, Hkv, mp) > 0.4
    blocks[:, :, 0] = True
    blocks &= (np.arange(mp)[None, None, :] * ps < lens[:, None, None])
    if not selected:
        blocks = np.arange(mp)[None, None, :] * ps < lens[:, None, None]
        blocks = np.broadcast_to(blocks, (S, Hkv, mp))
    sel = None
    if selected:
        # each slot's last visible page is selected (as the window forces)
        for s in range(S):
            if lens[s]:
                blocks[s, :, (lens[s] - 1) // ps] = True
        d = dict(M._dims(CFG), n_listed=mp, B=ps)
        sel = M._listed(d, jnp.asarray(blocks), jnp.asarray(lens), tables)
    got = FA.paged_decode_attention(q, kp, vp, tables, jnp.asarray(lens),
                                    impl="pallas", selection=sel)
    # the plain reference over the slot's own keys, gathered by hand
    for s in range(S):
        k = kp[tables[s]].reshape(mp * ps, Hkv, Dh)
        v = vp[tables[s]].reshape(mp * ps, Hkv, Dh)
        if not lens[s]:
            assert not np.asarray(got[s]).any()
            continue
        want = reference.sparse_attention(
            q[s:s + 1], k, v, jnp.asarray([lens[s] - 1]),
            jnp.asarray(blocks[s:s + 1]), ps)
        np.testing.assert_allclose(got[s], want[0], rtol=2e-5, atol=2e-6)
    # chunked prefill: 16 queries at 16 .. 31 of slot 2, per-row block masks
    qc = jnp.asarray(rng.randn(C, g * Hkv, Dh), jnp.float32)
    bm = rng.rand(C, Hkv, mp) > 0.4
    bm[:, :, 0] = True
    bm = bm if selected else np.ones_like(bm)
    got = FA.paged_prefill_attention(
        qc, kp, vp, tables[2], jnp.int32(16), impl="pallas",
        block_mask=jnp.asarray(bm).transpose(1, 0, 2) if selected else None)
    k = kp[tables[2]].reshape(mp * ps, Hkv, Dh)
    v = vp[tables[2]].reshape(mp * ps, Hkv, Dh)
    want = reference.sparse_attention(qc, k, v, 16 + jnp.arange(C),
                                      jnp.asarray(bm), ps)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("turn_pages", [1, 2, 4])
def test_a_decode_selection_walks_its_list_in_turns(reference, monkeypatch,
                                                    turn_pages):
    """The model's own list (``_listed``: the selected pages in cache order,
    the count over them) through the listed walk at a turn shorter than the
    list, as long and longer: the f32 reference's attention over the
    selected blocks, and a slot with nothing selected exact zeros.  Pages
    that no list names are NaN."""
    monkeypatch.setattr(FA, "_listed_turn_pages", lambda *a: turn_pages)
    rng = np.random.RandomState(8)
    Hkv, Dh, ps, mp, S, g = 2, 16, 8, 6, 4, 16
    lens = np.asarray([0, 17, 48, 33], np.int32)
    tables = 1 + rng.permutation(S * mp).reshape(S, mp)
    blocks = rng.rand(S, Hkv, mp) > 0.5
    blocks[:, :, 0] = True
    blocks &= (np.arange(mp)[None, None, :] * ps < lens[:, None, None])
    for s in range(S):
        if lens[s]:
            blocks[s, :, (lens[s] - 1) // ps] = True
    pool = rng.randn(2, 40, ps, Hkv, Dh).astype(np.float32)
    named = np.zeros((40, Hkv), bool)
    for s in range(S):
        named[tables[s]] |= blocks[s].T
    pool.transpose(0, 1, 3, 2, 4)[:, ~named] = np.nan
    kp, vp = jnp.asarray(pool[0]), jnp.asarray(pool[1])
    tables = jnp.asarray(tables, jnp.int32)
    q = jnp.asarray(rng.randn(S, g * Hkv, Dh), jnp.float32)
    d = dict(M._dims(CFG), n_listed=mp, B=ps)
    sel = M._listed(d, jnp.asarray(blocks), jnp.asarray(lens), tables)
    got = np.asarray(FA.paged_decode_attention(
        q, kp, vp, tables, jnp.asarray(lens), impl="pallas", selection=sel))
    assert np.isfinite(got).all() and not got[0].any()
    for s in range(1, S):
        k = jnp.nan_to_num(kp[tables[s]].reshape(mp * ps, Hkv, Dh))
        v = jnp.nan_to_num(vp[tables[s]].reshape(mp * ps, Hkv, Dh))
        want = reference.sparse_attention(
            q[s:s + 1], k, v, jnp.asarray([lens[s] - 1]),
            jnp.asarray(blocks[s:s + 1]), ps)
        np.testing.assert_allclose(got[s], want[0], rtol=2e-5, atol=2e-6)


def test_one_head_a_group_without_a_selection_is_the_kernel_it_was(monkeypatch):
    rng = np.random.RandomState(7)
    kp = jnp.asarray(rng.randn(20, 8, 2, 16), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(12).reshape(2, 6), jnp.int32)
    lens = jnp.asarray([30, 7], jnp.int32)
    q = jnp.asarray(rng.randn(2, 2, 16), jnp.float32)
    qc = jnp.asarray(rng.randn(8, 2, 16), jnp.float32)

    def boom(*a, **k):
        raise AssertionError("routed to the grouped kernel")

    for name in ("_paged_gqa_pallas", "_paged_gqa_reference",
                 "_paged_gqa_prefill_pallas", "_paged_gqa_prefill_reference"):
        monkeypatch.setattr(FA, name, boom)
    fold = kp.reshape(1, 20, 8, 32)
    for impl in ("pallas", "reference"):
        a = FA.paged_decode_attention(q, kp, kp, tables, lens, impl=impl)
        b = (FA._paged_pallas(q, fold, fold, tables, lens, 0.25, True, 0)
             if impl == "pallas" else
             FA._paged_reference(q, fold, fold, tables, lens, 0.25, 0))
        np.testing.assert_array_equal(a, b)                       # bitwise
        a = FA.paged_prefill_attention(qc, kp, kp, tables[0], jnp.int32(8),
                                       impl=impl)
        b = (FA._paged_prefill_pallas(qc, fold, fold, tables[0], 8, 0.25,
                                      True, 0) if impl == "pallas" else
             FA._paged_prefill_reference(qc, fold, fold, tables[0], 8, 0.25, 0))
        np.testing.assert_array_equal(a, b)


# 6. the depth cut ------------------------------------------------------------

def test_the_depth_cut_keeps_the_published_residual_scale_and_layers():
    with open(CONFIG) as f:
        cfg = json.load(f)
    d = M._dims(cfg)
    assert cfg["num_hidden_layers"] == 8 == len(cfg["mixer_types"])
    assert d["resid"] == pytest.approx(1.4 / math.sqrt(32))   # NOT sqrt(8)
    assert d["logit_div"] == 16 and d["scale_emb"] == 12
    pub = cfg["published"]["mixer_types"]
    assert [pub[i] for i in cfg["kept_layers"]] == cfg["mixer_types"]
    assert cfg["kept_layers"] == [0, 1, 2, 3, 9, 10, 11, 12]
    assert (d["n_sparse"], d["n_lin"], d["n_listed"]) == (2, 6, 128)
    lay = M.cache_layout(cfg)
    assert (lay["num_layers"], lay["num_heads"], lay["head_dim"]) == (2, 2, 128)
    assert lay["slot_state"]["lin"]["shape"] == (32, 128, 128)
    assert lay["page_pools"]["kbar"]["tokens_per_row"] == 16


# 7. weights as arguments -----------------------------------------------------

def _largest_constant_bytes(lowered_text):
    """Bytes of the largest ``stablehlo.constant`` of a lowered program."""
    sizes = {"f32": 4, "bf16": 2, "i32": 4, "ui32": 4, "i1": 1, "f16": 2,
             "i64": 8, "ui8": 1, "i8": 1, "f64": 8}
    worst = 0
    for m in re.finditer(r"stablehlo\.constant[^\n]*?tensor<([0-9x]*)x?(\w+)>",
                         lowered_text):
        dims, dtype = m.groups()
        n = int(np.prod([int(x) for x in dims.split("x") if x] or [1]))
        worst = max(worst, n * sizes.get(dtype, 4))
    return worst


def _lowered_steps(sched, chunk):
    c, p, cfg = sched._cache, sched._params, sched.config
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    S, sizes = cfg.num_slots, sched._chunk_sizes[chunk]
    yield sched._jit.get(("decode",)).lower(
        p, c.pools, i32(*sched._standing.shape),
        i32(S + len(sched.model.step_counters))).as_text()
    yield sched._jit.get(("chunk", chunk)).lower(
        p, c.pools, i32(step_programs.chunk_length(chunk, sizes)),
        sizes=sizes).as_text()


def test_step_programs_hold_no_weights():
    """Both models, weights of 2 MB and more: the lowered decode and chunk
    programs hold no constant over 1 MB (they held every weight before)."""
    big = dict(CFG, vocab_size=8192)          # 2 MB embedding, 2 MB head
    sala = serving.DecodeScheduler(
        M.build_decode_model(M.sala_params(big, 0, dtype="float32"), big),
        serving.DecodeConfig(num_slots=2, page_size=PAGE, max_seq_len=32,
                             prefill_chunk_tokens=16, warmup=False),
        autostart=False)
    lm_params, meta = T.lm_params(seed=7, vocab_size=8192, n_layer=2, n_head=2,
                                  d_model=64, d_inner=128, max_length=64)
    lm = serving.DecodeScheduler(
        T.build_decode_model(lm_params, meta),
        serving.DecodeConfig(num_slots=2, page_size=PAGE, max_seq_len=32,
                             prefill_chunk_tokens=16, warmup=False),
        autostart=False)
    for sched in (sala, lm):
        assert len(jax.tree_util.tree_leaves(sched._params)) < 64
        for text in _lowered_steps(sched, 16):
            assert _largest_constant_bytes(text) < 2 ** 20


def test_transformer_base_serves_the_tokens_it_served_before():
    """Golden tokens of the commit before weights became arguments (same
    seed, prompts, chunked prefill; greedy and one sampled request)."""
    params, meta = T.lm_params(seed=7, vocab_size=50, n_layer=2, n_head=2,
                               d_model=32, d_inner=64, max_length=128)
    s = serving.DecodeScheduler(
        T.build_decode_model(params, meta),
        serving.DecodeConfig(num_slots=3, page_size=8, max_seq_len=96,
                             prefill_chunk_tokens=16, max_new_tokens=12))
    got = [s.generate(np.asarray(p, np.int32), max_new_tokens=12,
                      timeout=120).tolist()
           for p in ([3, 9, 27, 31, 4], list(range(1, 40)), [7] * 20)]
    got.append(s.submit(np.asarray([5, 6, 7, 8], np.int32), max_new_tokens=12,
                        temperature=0.8, seed=11).result(timeout=120).tolist())
    s.stop()
    assert got == [
        [47, 47, 23, 23, 23, 23, 23, 23, 23, 31, 31, 6],
        [40, 9, 9, 47, 9, 9, 40, 9, 40, 40, 40, 40],
        [47, 6, 6, 23, 23, 31, 31, 31, 3, 3, 3, 3],
        [36, 22, 6, 49, 47, 34, 29, 47, 0, 22, 14, 4]]


# 8. what a slot-state model refuses ------------------------------------------

@pytest.mark.parametrize("how", ["prefix_cache", "sessions", "role", "pool"])
def test_a_slot_state_model_refuses_pages_without_state(params, how):
    model = M.build_decode_model(params, CFG)
    base = dict(num_slots=2, page_size=PAGE, max_seq_len=32,
                prefill_chunk_tokens=16, warmup=False)
    with pytest.raises(serving.ServingError, match="state snapshot"):
        if how == "prefix_cache":
            serving.DecodeScheduler(
                model, serving.DecodeConfig(prefix_cache=True, **base),
                autostart=False)
        elif how == "sessions":
            serving.DecodeScheduler(
                model, serving.DecodeConfig(**base), autostart=False,
                sessions=serving.SessionStore())
        elif how == "role":
            serving.DecodeScheduler(
                model, serving.DecodeConfig(**base), autostart=False,
                role="decode")
        else:
            serving.ReplicaPool(
                None, replicas=2, decode_model=model,
                decode_config=serving.DecodeConfig(**base),
                roles=("prefill", "decode"))
