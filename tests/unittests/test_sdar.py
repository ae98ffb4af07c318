"""The SDAR family (``models/sdar.py``: generation by DIFFUSION OVER BLOCKS)
through the serving path against its plain reference
(``chipbench/configs/sdar_30b_a3b.reference.py``) on the CPU at toy sizes with
seeded float32 weights: chunked prefill under the block mask, then every
denoising forward and every K/V-writing forward through the cache (logits,
unmasked sets, delivered ids, K/V rows), a run in which forwards unmask 0, 1
and several positions and slots finish their blocks on different steps, the
structure tests (``block`` = 1 is today's walk, ``B`` = 1 is a causal decoder,
one step in flight is the unpipelined loop), the shortcuts that must fail,
leftover prompt ids, cancel and EOS with a step in flight, a reseated slot,
and the prefix cache.

At these sizes the model runs in float32 end to end, so the system differs
from the reference only by the ORDER of float32 operations: 1e-4 of the
logits' spread holds that.
"""
import functools
import importlib.util
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models import sdar as M
from paddle_tpu.models.mellum import _attn_out
from paddle_tpu.models.minicpm_sala import _logits
from paddle_tpu.parallel import flash_attention as FA
from paddle_tpu.serving import step_programs as SP
from paddle_tpu.serving.errors import ServingCancelled, ServingError

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REFERENCE = os.path.join(ROOT, "chipbench/configs/sdar_30b_a3b.reference.py")

MASK = 95
CFG = dict(
    attention_bias=False, hidden_act="silu", hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, vocab_size=96,
    num_attention_heads=4, num_key_value_heads=2, head_dim=128,
    num_experts=16, num_experts_per_tok=4, norm_topk_prob=True,
    num_hidden_layers=3, rms_norm_eps=1e-6, tie_word_embeddings=False,
    rope_theta=1e6, rope_scaling=None, use_sliding_window=False,
    sliding_window=None, decoder_sparse_step=1, mlp_only_layers=[],
    block_length=4, denoising_steps=4, confidence_threshold=0.9,
    mask_token_id=MASK)
PAGE, SLOTS, MAX_LEN, CHUNK = 8, 3, 96, 16
LOGIT_TOL = 1e-4        # max |a - b| / std(b): float32 reordering only
# a head scaled so that some candidates' probabilities pass the threshold and
# some do not: the rule's two branches in one run
SHARP = 12.0


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("sdar_reference", REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return M.params(CFG, 0, dtype="float32")


@pytest.fixture(scope="module")
def sharp(params):
    return dict(params, head=params["head"] * SHARP)


def _prompts(lengths, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, MASK, size=n).astype(np.int32) for n in lengths]


def _engine(weights, cfg=CFG, eos_id=None, autostart=True, **kw):
    config = dict(num_slots=SLOTS, page_size=PAGE, max_seq_len=MAX_LEN,
                  prefill_chunk_tokens=CHUNK, prefix_cache=False)
    config.update(kw)
    return serving.InferenceEngine(
        decode_model=M.build_decode_model(weights, cfg, eos_id=eos_id),
        decode_config=serving.DecodeConfig(**config), autostart=autostart)


def _bursts(request):
    """Tokens a commit delivered, in order (stamps of one commit are equal)."""
    stamps = np.asarray(request.token_times)
    assert np.all(np.diff(stamps) >= 0)
    return [int(n) for n in np.unique(stamps, return_counts=True)[1]]


# -- the walk ------------------------------------------------------------------

def _pools(rng, layers=2, pages=14):
    shape = (layers, pages, 16, 2 * 128)
    return (jnp.asarray(rng.normal(size=shape), jnp.bfloat16),
            jnp.asarray(rng.normal(size=shape), jnp.bfloat16))


def _dense(q, k_pool, v_pool, table, layer, pos, B, g=2):
    """One token's attention against the first ``(pos // B + 1) * B`` rows."""
    keys = np.asarray(k_pool[layer, table].astype(jnp.float32)).reshape(
        -1, 2, 128)
    vals = np.asarray(v_pool[layer, table].astype(jnp.float32)).reshape(
        -1, 2, 128)
    n = (pos // B + 1) * B
    out = []
    for h in range(2 * g):
        s = keys[:n, h // g] @ np.asarray(q[h], np.float32) / np.sqrt(128)
        p = np.exp(s - s.max())
        out.append((p / p.sum()) @ vals[:n, h // g])
    return np.stack(out)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("B", [4, 8])
def test_walk_with_a_block_against_dense_attention(impl, B):
    """Decode form (a slot's whole block, no stagger) and chunk form (whole
    blocks a grid step) of the grouped walk under the block rule."""
    rng = np.random.default_rng(0)
    kp, vp = _pools(rng)
    tables = jnp.asarray(rng.permutation(np.arange(1, 14))[:9].reshape(3, 3),
                         jnp.int32)
    q = jnp.asarray(rng.normal(size=(3, B, 4, 128)), jnp.bfloat16)
    lens = jnp.asarray([2 * B, 0, 40], jnp.int32)
    got = FA.paged_gqa_decode_attention(q, kp, vp, tables, lens, layer=1,
                                        impl=impl, interpret=True, block=B)
    assert not np.asarray(got[1]).any()
    for s, n in ((0, 2 * B), (2, 40)):
        want = np.stack([_dense(q[s, t], kp, vp, tables[s], 1, n - B + t, B)
                         for t in range(B)])
        np.testing.assert_allclose(np.asarray(got[s]), want, atol=2e-6)
    qc = jnp.asarray(rng.normal(size=(32, 4, 128)), jnp.bfloat16)
    got = FA.paged_gqa_prefill_attention(qc, kp, vp, tables[0], 16, 24,
                                         layer=0, impl=impl, interpret=True,
                                         block=B)
    want = np.stack([_dense(qc[t], kp, vp, tables[0], 0, 16 + t, B)
                     for t in range(24)])
    np.testing.assert_allclose(np.asarray(got[:24]), want, atol=2e-6)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("layer", [0, 1])
def test_block_of_one_is_the_causal_walk_bit_for_bit(impl, layer):
    """``block`` = 1 states today's rule: the chunk form and the one-token
    decode form give the bits they give without the argument, and a slot's
    ``T`` newest tokens in one call are ``T`` one-token calls."""
    rng = np.random.default_rng(3)
    kp, vp = _pools(rng)
    tables = jnp.asarray(rng.permutation(np.arange(1, 14))[:9].reshape(3, 3),
                         jnp.int32)
    kw = dict(layer=layer, impl=impl, interpret=True)
    q = jnp.asarray(rng.normal(size=(3, 4, 4, 128)), jnp.bfloat16)
    lens = jnp.asarray([9, 0, 37], jnp.int32)
    one = FA.paged_gqa_decode_attention(q[:, -1], kp, vp, tables, lens, **kw)
    assert np.array_equal(one, FA.paged_gqa_decode_attention(
        q[:, -1], kp, vp, tables, lens, block=1, **kw))
    several = FA.paged_gqa_decode_attention(q, kp, vp, tables, lens, block=1,
                                            **kw)
    for t in range(4):
        each = FA.paged_gqa_decode_attention(
            q[:, t], kp, vp, tables, jnp.maximum(lens - (3 - t), 0) * (
                lens > 0), **kw)
        np.testing.assert_allclose(np.asarray(several[:, t]),
                                   np.asarray(each), atol=1e-6)
    qc = jnp.asarray(rng.normal(size=(32, 4, 128)), jnp.bfloat16)
    assert np.array_equal(
        FA.paged_gqa_prefill_attention(qc, kp, vp, tables[0], 16, 20, **kw),
        FA.paged_gqa_prefill_attention(qc, kp, vp, tables[0], 16, 20,
                                       block=1, **kw))
    with pytest.raises(ValueError, match="whole blocks"):
        FA.paged_gqa_decode_attention(q, kp, vp, tables, lens, block=3, **kw)


# -- the step functions through the cache --------------------------------------

def _cache(cfg=CFG):
    return serving.PagedKVCache(
        cfg["num_hidden_layers"], SLOTS * (MAX_LEN // PAGE) + 1, PAGE,
        cfg["num_key_value_heads"], cfg["head_dim"], MAX_LEN,
        dtype="float32", num_slots=SLOTS)


@functools.lru_cache(maxsize=None)
def _fns(steps, B, threshold=0.9):
    cfg = dict(CFG, denoising_steps=steps, block_length=B,
               confidence_threshold=threshold)
    blk = M.block(cfg)

    def unmask(ids, logits, forwards):
        keys = jax.random.split(jax.random.PRNGKey(0), ids.shape[0])
        return SP.unmask_block(ids, logits, keys, jnp.float32(0.0), forwards,
                               mask_id=blk["mask_id"], steps=blk["steps"],
                               threshold=blk["threshold"])

    return (cfg, jax.jit(functools.partial(M.prefill_chunk, cfg=cfg)),
            jax.jit(functools.partial(M.decode_step, cfg=cfg)),
            jax.jit(unmask))


def _through_the_cache(weights, prompt, max_new, steps=4, B=4, slot=1,
                       keep_stale=False, threshold=0.9):
    """Prefill ``prompt``'s whole blocks in chunks of ``CHUNK`` into ``slot``,
    then denoise block after block through the cache as the scheduler's step
    program does.  Returns ``(ids, forwards, rows)``: a forward's ``dict(block,
    ids, logits, unmasked, kv)`` as the reference records it, and the K and V
    rows ``[L, 2, n, width]`` the cache holds at the end.  ``keep_stale``: a
    shortcut, the block's rows are kept from the LAST DENOISING forward (no
    forward over the whole block)."""
    cfg, chunk, decode, unmask = _fns(steps, B, threshold)
    cache = _cache(cfg)
    P = len(prompt)
    blocks = -(-(P + max_new) // B)
    pages = cache.alloc(cache.pages_for(blocks * B))
    row = cache.table_row(pages)
    pools = cache.pools
    start = 0
    while start < P // B * B:
        valid = min(CHUNK, P // B * B - start)
        window = np.zeros(CHUNK, np.int32)
        window[:valid] = prompt[start:start + valid]
        vec = np.zeros(CHUNK // PAGE, np.int32)
        n = min(len(vec), len(pages) - start // PAGE)
        vec[:n] = pages[start // PAGE:start // PAGE + n]
        _, pools = chunk(weights, jnp.asarray(window), jnp.int32(start),
                         jnp.int32(valid), pools, jnp.asarray(vec),
                         jnp.asarray(row), jnp.int32(slot))
        start += valid
    x = np.full(blocks * B, MASK, np.int32)
    x[:P] = prompt
    tables = np.zeros((SLOTS, len(row)), np.int32)
    tables[slot] = row
    forwards = []
    for b in range(P // B, blocks):
        t = 0
        while True:
            ids = x[b * B:(b + 1) * B].copy()
            tokens = np.zeros((SLOTS, B), np.int32)
            starts, lens = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
            tokens[slot], starts[slot], lens[slot] = ids, b * B, (b + 1) * B
            before = pools
            logits, pools, _ = decode(
                weights, jnp.asarray(tokens), jnp.asarray(starts), pools,
                jnp.asarray(tables), jnp.asarray(lens))
            new, unmasked, whole = unmask(jnp.asarray(ids), logits[slot],
                                          jnp.int32(t))
            forwards.append(dict(
                block=b, ids=ids, logits=np.asarray(logits[slot]),
                unmasked=[int(i) for i in np.flatnonzero(unmasked)],
                kv=bool(whole)))
            if whole:
                if keep_stale:
                    pools = before      # what the last denoising forward left
                break
            x[b * B:(b + 1) * B] = np.asarray(new)
            t += 1
    rows = np.stack([np.stack([
        np.asarray(pools[leaf][layer, jnp.asarray(pages)]).reshape(
            len(pages) * PAGE, -1)[:blocks * B] for leaf in ("k", "v")])
        for layer in range(cfg["num_hidden_layers"])])
    return x[P:P + max_new].copy(), forwards, rows


def _reference_rows(reference, weights, cfg, seq):
    """The reference's K and V rows of every position ``[L, 2, n, width]``."""
    T = -(-len(seq) // 8) * 8
    tokens = np.full(T, MASK, np.int32)
    tokens[:len(seq)] = seq
    _, _, rows = reference.forward(
        weights, cfg, jnp.asarray(tokens),
        jnp.arange(len(seq), dtype=jnp.int32), rows=8)
    return np.stack([np.stack([np.asarray(k), np.asarray(v)])
                     for k, v in rows])


def _same_forwards(got, want, tol=LOGIT_TOL):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        assert (a["block"], a["kv"], a["unmasked"]) == (
            b["block"], b["kv"], b["unmasked"])
        assert np.array_equal(a["ids"], b["ids"])
        assert np.max(np.abs(a["logits"] - b["logits"])) / b[
            "logits"].std() < tol


@pytest.mark.parametrize("steps,B", [(1, 4), (2, 4), (4, 4), (4, 8), (3, 8)])
def test_chunk_and_every_forward_match_the_reference(reference, params, steps,
                                                     B):
    """A prompt that crosses pages and chunks and is no whole number of
    blocks, ``max_new_tokens`` none either: every forward's block going in,
    its logits and the set it unmasks, the ids, and the rows chunk and steps
    leave in the cache."""
    cfg = dict(CFG, denoising_steps=steps, block_length=B)
    prompt, max_new = _prompts([37])[0], 14
    ids, forwards, rows = _through_the_cache(params, prompt, max_new, steps, B)
    want_ids, want = reference.block_diffusion_generate(
        params, cfg, prompt, max_new, rows=8)
    assert np.array_equal(ids, want_ids)
    _same_forwards(forwards, want)
    # no confidence passes 0.9 over these weights: the fallback's count a
    # denoising forward (fewer where fewer are left), one K/V forward a block
    counts = SP.transfer_counts(B, steps)
    assert all(0 < len(f["unmasked"]) <= max(counts)
               for f in forwards if not f["kv"])
    assert sum(f["kv"] for f in forwards) == len({f["block"]
                                                  for f in forwards})
    seq = np.concatenate([prompt, want_ids])
    n = len(seq) // B * B
    want_rows = _reference_rows(reference, params, cfg, seq[:n])
    assert np.max(np.abs(rows[:, :, :n] - want_rows)) / np.abs(
        want_rows).max() < 1e-5


def test_a_forward_unmasks_none_one_or_several(reference, sharp):
    """Confidences on both sides of the threshold: in one run some denoising
    forwards take the fallback's one position and some every position above
    the threshold, blocks take 2 to 5 forwards, and the forward that finds a
    block whole unmasks nothing: all as the reference has it."""
    prompt, max_new = _prompts([21])[0], 24
    ids, forwards, _ = _through_the_cache(sharp, prompt, max_new)
    want_ids, want = reference.block_diffusion_generate(
        sharp, CFG, prompt, max_new, rows=8)
    assert np.array_equal(ids, want_ids)
    _same_forwards(forwards, want)
    sizes = {len(f["unmasked"]) for f in forwards}
    assert {0, 1} <= sizes and max(sizes) >= 3
    per_block = np.unique([f["block"] for f in forwards], return_counts=True)[1]
    assert per_block.min() < 5 and per_block.max() >= 4


@pytest.mark.parametrize("weights", ["params", "sharp"])
def test_a_mask_id_candidate_closes_the_block_by_count(request, reference,
                                                       weights):
    """The published candidates: the mask id is one (a head whose mask column
    wins at some positions).  A position "unmasked" to it stays masked, so its
    block is closed by the count of its forwards, ``steps`` denoising ones
    and the one that keeps K/V, and is delivered holding the mask id: through
    the cache and through the scheduler as the reference's loop has it, where
    a rule that takes the mask id out of the candidates gives other ids."""
    weights = request.getfixturevalue(weights)
    prompt, max_new = _prompts([18])[0], 22
    plain, _ = reference.block_diffusion_generate(
        weights, CFG, prompt, max_new, rows=8)
    # the mask column just above the column of the id generated most often:
    # where that id would win, the mask id does
    head = np.array(weights["head"])
    head[:, MASK] = 1.02 * head[:, np.bincount(plain).argmax()]
    weights = dict(weights, head=jnp.asarray(head))
    want_ids, want = reference.block_diffusion_generate(
        weights, CFG, prompt, max_new, rows=8)
    ids, forwards, _ = _through_the_cache(weights, prompt, max_new)
    assert np.array_equal(ids, want_ids)
    _same_forwards(forwards, want)
    assert MASK in want_ids and not np.all(want_ids == MASK)
    per_block = np.unique([f["block"] for f in want], return_counts=True)[1]
    assert per_block.max() == CFG["denoising_steps"] + 1
    # a block closed by count went into its last forward holding the mask id
    assert any(f["kv"] and MASK in f["ids"] for f in want)
    engine = _engine(weights)
    try:
        f = engine.generate_async(prompt, max_new_tokens=max_new)
        assert np.array_equal(f.result(300), want_ids)
        assert len(f.token_times) == max_new
    finally:
        engine.stop()
    # the rule without the mask id among its candidates is another model
    excluded = dict(weights, head=jnp.asarray(
        np.where(np.arange(head.shape[1]) == MASK, 0.0, head)))
    other, _ = reference.block_diffusion_generate(
        excluded, CFG, prompt, max_new, rows=8)
    assert not np.array_equal(other, want_ids)


# -- the scheduler --------------------------------------------------------------

LENGTHS, NEW = (21, 3, 40, 18), (13, 9, 20, 16)


@pytest.fixture(scope="module")
def served(sharp):
    """Four requests over three slots (one waits for a seat and takes a
    retired slot's) under the dynamic rule, a scheduler with a step in
    flight; what every commit saw."""
    names = SP.BLOCK_COUNTERS + M.STEP_COUNTERS
    count0 = {n: obs.counter("serving.decode." + n).value for n in names}
    engine = _engine(sharp)
    sched = engine.decoder
    moves, commit = [], sched._commit_blocks

    def watched(sent, out, live, tripped, now):
        before = {id(s): (s.kv_len, len(s.generated)) for _, s in live}
        commit(sent, out, live, tripped, now)
        for _, s in live:
            moves.append((s.req.seq, s.kv_len - before[id(s)][0],
                          len(s.generated) - before[id(s)][1]))

    sched._commit_blocks = watched
    block0 = obs.histogram("serving.decode.block").snapshot()
    delivered0 = obs.histogram("serving.decode.tokens_delivered").snapshot()
    prompts = _prompts(LENGTHS)
    futures = [engine.generate_async(p, max_new_tokens=n)
               for p, n in zip(prompts, NEW)]
    outs = [f.result(300) for f in futures]
    engine.stop()
    return dict(
        prompts=prompts, futures=futures, outs=outs, moves=moves,
        overlapped=obs.counter("serving.decode.steps_overlapped").value,
        blocks=obs.histogram("serving.decode.block").snapshot() - block0,
        delivered=(obs.histogram("serving.decode.tokens_delivered").snapshot()
                   - delivered0),
        counters={n: obs.counter("serving.decode." + n).value - count0[n]
                  for n in names})


def test_served_ids_and_bursts_match_the_reference(reference, sharp, served):
    """Through ``InferenceEngine`` -> ``DecodeScheduler`` -> ``PagedKVCache``
    with one step in flight: the ids, and the tokens every forward delivered,
    are the reference's; a prompt shorter than a block is never prefilled and
    leftover ids are seated in the first block."""
    assert served["overlapped"] > 0
    for prompt, n, out, f in zip(served["prompts"], NEW, served["outs"],
                                 served["futures"]):
        want, forwards = reference.block_diffusion_generate(
            sharp, CFG, prompt, n, rows=8)
        assert np.array_equal(out, want)
        assert len(f.token_times) == n
        # what a commit delivers: the unmasked positions that are now in order
        x = np.full(-(-(len(prompt) + n) // 4) * 4, MASK, np.int32)
        x[:len(prompt)] = prompt
        have, bursts = len(prompt), []
        for fw in forwards:
            for i in fw["unmasked"]:
                x[fw["block"] * 4 + i] = 0
            upto = have
            while upto < len(prompt) + n and x[upto] != MASK:
                upto += 1
            if upto > have:
                bursts.append(upto - have)
            have = upto
        assert _bursts(f) == bursts
    sizes = [b for f in served["futures"] for b in _bursts(f)]
    assert 1 in sizes and max(sizes) >= 3


def test_kv_len_moves_by_a_block_and_only_on_a_whole_one(served):
    moves = served["moves"]
    assert {kv for _, kv, _ in moves} == {0, 4}
    # the forward that wrote K/V delivered nothing; a commit delivers 0 .. 4
    assert all(n == 0 for _, kv, n in moves if kv)
    assert {n for _, _, n in moves} >= {0, 1, 4}
    assert all(0 <= n <= 4 for _, _, n in moves)
    # slots finish their blocks on different steps: the requests' K/V commits
    # fall at different places of their own sequences
    at = {}
    for seq, kv, _ in moves:
        at.setdefault(seq, []).append(kv)
    assert len({tuple(v[:8]) for v in at.values()}) > 1


def test_counters_histogram_and_span(served):
    c = served["counters"]
    # the device counts every forward it ran, the commits the ones whose slot
    # was still seated: a retired slot's last step in flight is the difference
    committed = sum(1 for _, kv, _ in served["moves"] if kv)
    assert committed <= c["diffusion.kv_forwards"] < c["diffusion.forwards"]
    assert len(served["moves"]) <= c["diffusion.forwards"]
    assert c["diffusion.unmasked"] >= sum(NEW)
    assert c["diffusion.kv_rows_read"] > 0 and c["moe.pairs"] > 0
    # one observation a (live slot, commit); its sum is what was delivered
    assert served["delivered"].count == len(served["moves"])
    assert served["delivered"].sum == sum(NEW)
    assert served["blocks"].count == sum(1 for _, kv, _ in served["moves"]
                                         if kv)
    assert served["blocks"].sum > 0


def _in_order(sched):
    """The unpipelined loop: a step is planned only when nothing is in
    flight."""
    def plan_one():
        step = None if sched._unread else sched._plan_step()
        return [] if step is None else [step]

    sched._plan_steps = plan_one


@pytest.mark.parametrize("weights", ["params", "sharp"])
def test_one_step_in_flight_is_the_unpipelined_loop(request, served, weights):
    """The same requests with no step in flight: the ids and every commit's
    deliveries bit for bit, under the fallback and under the dynamic rule."""
    w = request.getfixturevalue(weights)
    runs = []
    for pipelined in (True, False):
        engine = _engine(w)
        if not pipelined:
            _in_order(engine.decoder)
        before = obs.counter("serving.decode.steps_overlapped").value
        futures = [engine.generate_async(p, max_new_tokens=n, seed=7,
                                         temperature=t)
                   for p, n, t in zip(_prompts(LENGTHS), NEW,
                                      (0.0, 0.8, 0.0, 0.8))]
        outs = [f.result(300) for f in futures]
        engine.stop()
        overlapped = obs.counter("serving.decode.steps_overlapped").value
        assert (overlapped > before) == pipelined
        runs.append((outs, [_bursts(f) for f in futures]))
    for a, b in zip(runs[0][0], runs[1][0]):
        assert np.array_equal(a, b)
    assert runs[0][1] == runs[1][1]


def _causal_step(weights, tokens, positions, cache, tables, lens, *, cfg):
    """A causal one-token-a-step decoder over the family's weights, written
    with the UNBLOCKED walk: what ``B`` = 1 must equal."""
    d = M._dims(cfg)
    cache = dict(cache)
    S = tokens.shape[0]
    ps = cache["k"].shape[2]
    pages = jnp.where(lens > 0, tables[jnp.arange(S), positions // ps], 0)
    x = weights["embed"][tokens]
    for layer, lp in enumerate(weights["layers"]):
        q, k, v = M._qkv(d, weights, lp, layer, x, positions)
        cache["k"] = cache["k"].at[layer, pages, positions % ps].set(k)
        cache["v"] = cache["v"].at[layer, pages, positions % ps].set(v)
        o = FA.paged_gqa_decode_attention(q, cache["k"], cache["v"], tables,
                                          lens, layer=layer,
                                          sm_scale=d["sm_scale"])
        x, _, _ = M._experts(d, weights, layer, _attn_out(lp, x, o), lens > 0)
    return _logits(d, weights, x), cache


def test_a_block_of_one_is_a_causal_decoder_bit_for_bit(params):
    """``B`` = 1, ``steps`` = 1: the chunk is a causal prefill and a step's
    forward is a causal one-token step over the same weights, logits and rows
    bit for bit."""
    cfg, chunk, decode, _ = _fns(1, 1)
    plain = dict(CFG, block_length=1)
    with pytest.MonkeyPatch.context() as patch:
        # the chunk's walk called as a causal family calls it: no ``block``
        patch.setattr(
            FA, "paged_gqa_prefill_attention",
            lambda *a, block=1, _f=FA.paged_gqa_prefill_attention, **kw:
            _f(*a, **kw))
        causal_chunk = jax.jit(functools.partial(M.prefill_chunk, cfg=plain))
        causal_chunk.lower(
            params, jnp.zeros(CHUNK, jnp.int32), jnp.int32(0), jnp.int32(1),
            _cache(cfg).pools, jnp.zeros(2, jnp.int32),
            jnp.zeros(MAX_LEN // PAGE, jnp.int32), jnp.int32(0))
    causal_step = jax.jit(functools.partial(_causal_step, cfg=plain))
    prompt = _prompts([23])[0]
    cache = _cache(cfg)
    pages = cache.alloc(cache.pages_for(40))
    row = cache.table_row(pages)
    both = []
    for blocked in (True, False):
        pools = jax.tree_util.tree_map(jnp.copy, cache.pools)
        for start in (0, 16):
            valid = min(16, len(prompt) - 1 - start)
            window = np.zeros(CHUNK, np.int32)
            window[:valid] = prompt[start:start + valid]
            vec = np.asarray(pages[start // PAGE:start // PAGE + 2], np.int32)
            if blocked:
                logits, pools = chunk(params, jnp.asarray(window),
                                      jnp.int32(start), jnp.int32(valid),
                                      pools, jnp.asarray(vec),
                                      jnp.asarray(row), jnp.int32(0))
            else:
                logits, pools = causal_chunk(
                    params, jnp.asarray(window), jnp.int32(start),
                    jnp.int32(valid), pools, jnp.asarray(vec),
                    jnp.asarray(row), jnp.int32(0))
        tables = np.zeros((SLOTS, len(row)), np.int32)
        tables[0] = row
        tokens = np.asarray([prompt[22], 0, 0], np.int32)
        positions = np.asarray([22, 0, 0], np.int32)
        lens = np.asarray([23, 0, 0], np.int32)
        if blocked:
            step, pools, _ = decode(params, jnp.asarray(tokens[:, None]),
                                    jnp.asarray(positions), pools,
                                    jnp.asarray(tables), jnp.asarray(lens))
            step = step[:, 0]
        else:
            step, pools = causal_step(params, tokens=jnp.asarray(tokens),
                                      positions=jnp.asarray(positions),
                                      cache=pools, tables=jnp.asarray(tables),
                                      lens=jnp.asarray(lens))
        both.append((np.asarray(logits), np.asarray(step[0]),
                     np.asarray(pools["k"][:, jnp.asarray(pages[:3])]),
                     np.asarray(pools["v"][:, jnp.asarray(pages[:3])])))
    for a, b in zip(*both):
        assert np.array_equal(a, b)


# -- the shortcuts fail ---------------------------------------------------------

def _worst(got, want):
    return max(np.max(np.abs(a["logits"] - b["logits"])) / b["logits"].std()
               for a, b in zip(got, want))


def test_the_shortcuts_fail(reference, params, monkeypatch):
    """A causal mask inside the block, a prompt prefilled causally, a block's
    K/V kept from a denoising forward, logits read shifted by one: each is
    further from the reference than the tolerance, a thousand times and
    more."""
    prompt, max_new = _prompts([30])[0], 10
    _, want = reference.block_diffusion_generate(params, CFG, prompt, max_new,
                                                 rows=8)
    _, sound, _ = _through_the_cache(params, prompt, max_new)
    assert _worst(sound, want) < LOGIT_TOL
    # logits shifted by one: row i read as the prediction of position i + 1
    shifted = max(np.max(np.abs(a["logits"][:-1] - b["logits"][1:]))
                  / b["logits"].std() for a, b in zip(sound, want))
    assert shifted > 1000 * LOGIT_TOL
    # a block's rows kept from its last denoising forward
    _, stale, _ = _through_the_cache(params, prompt, max_new, keep_stale=True)
    later = [i for i, f in enumerate(want) if f["block"] > want[0]["block"]]
    assert _worst([stale[i] for i in later],
                  [want[i] for i in later]) > 1000 * LOGIT_TOL
    decode, prefill = (FA.paged_gqa_decode_attention,
                       FA.paged_gqa_prefill_attention)
    # a prompt prefilled causally (the decode forwards sound)
    _fns.cache_clear()
    monkeypatch.setattr(FA, "paged_gqa_prefill_attention",
                        lambda *a, block=1, **kw: prefill(*a, **kw))
    _, got, _ = _through_the_cache(params, prompt, max_new)
    assert _worst(got, want) > 1000 * LOGIT_TOL
    # a causal mask inside the block (the prefill sound)
    _fns.cache_clear()
    monkeypatch.setattr(FA, "paged_gqa_prefill_attention", prefill)
    monkeypatch.setattr(FA, "paged_gqa_decode_attention",
                        lambda *a, block=1, **kw: decode(*a, block=1, **kw))
    _, got, _ = _through_the_cache(params, prompt, max_new)
    assert _worst(got, want) > 1000 * LOGIT_TOL
    _fns.cache_clear()


# -- slots that leave and are taken again -----------------------------------------

def test_cancel_and_eos_with_a_step_in_flight(reference, sharp):
    """An EOS inside a block ends the request there (ids behind it in the
    block are not delivered) and a cancel retires a slot while its next
    forward is on the device: what those forwards unmasked is discarded."""
    prompt = _prompts([21])[0]
    want, _ = reference.block_diffusion_generate(sharp, CFG, prompt, 24,
                                                 rows=8)
    eos = int(want[9])
    cut = int(np.flatnonzero(want == eos)[0])
    before = obs.counter("serving.decode.tokens_discarded").value
    engine = _engine(sharp, eos_id=eos)
    out = engine.generate(prompt, max_new_tokens=24)
    assert np.array_equal(out, want[:cut + 1])
    slow = engine.generate_async(_prompts([40], seed=5)[0], max_new_tokens=40)
    while len(slow.token_times) < 6:
        time.sleep(0.005)
    slow.cancel()
    with pytest.raises(ServingCancelled):
        slow.result(60)
    engine.stop()
    assert engine.health()["decode"]["kv_pages_used"] == 0
    assert obs.counter("serving.decode.tokens_discarded").value > before


def test_a_reseated_slot_reads_nothing_of_its_last_occupant(reference, params):
    """One slot, two requests in turn, every page POISONED between them: the
    second's ids are the reference's (rows past ``kv_lens`` and the last
    occupant's block on the device are never read)."""
    engine = _engine(params, num_slots=1, num_pages=14, autostart=False)
    sched = engine.decoder
    # every row of every page holds a NaN before anyone is seated ...
    sched.cache.pools = {k: jnp.full_like(v, jnp.nan)
                         for k, v in sched.cache.pools.items()}
    engine.start()
    first, second = _prompts([33, 10], seed=9)
    got_first = engine.generate(first, max_new_tokens=12)
    # ... and the second request takes the first's slot, pages (their rows
    # and the rows its last forwards in flight wrote) and place on the device
    got = engine.generate(second, max_new_tokens=11)
    engine.stop()
    for prompt, n, out in ((first, 12, got_first), (second, 11, got)):
        want, _ = reference.block_diffusion_generate(params, CFG, prompt, n,
                                                     rows=8)
        assert np.array_equal(out, want)


def test_prefix_cache_reuses_whole_blocks(reference, params):
    """The prefix cache takes the model: a second request with the same
    first 24 ids maps three whole pages (six whole blocks) and gives the ids
    of a cold run."""
    engine = _engine(params, prefix_cache=True)
    a, b = _prompts([29, 29], seed=11)
    b[:24] = a[:24]
    first = engine.generate(a, max_new_tokens=9)
    before = engine.decoder.cache.stats()["kv_hit_pages"]
    second = engine.generate(b, max_new_tokens=9)
    hit = engine.decoder.cache.stats()["kv_hit_pages"] - before
    engine.stop()
    cold = _engine(params)
    assert np.array_equal(cold.generate(b, max_new_tokens=9), second)
    cold.stop()
    for prompt, out in ((a, first), (b, second)):
        want, _ = reference.block_diffusion_generate(params, CFG, prompt, 9,
                                                     rows=8)
        assert np.array_equal(out, want)
    assert hit == 3


def test_what_a_block_model_is_refused():
    model = M.build_decode_model(None, CFG)
    with pytest.raises(ServingError, match="whole blocks"):
        serving.DecodeScheduler(model, serving.DecodeConfig(
            num_slots=2, page_size=6, max_seq_len=48), autostart=False)
    with pytest.raises(ValueError, match="mask_id"):
        serving.DecodeModel(None, None, vocab_size=10, block=dict(
            length=4, mask_id=10, steps=4, threshold=0.9))
    # every id is a candidate, the mask id too; a block closes when it is
    # whole or has had its denoising forwards, and a closing forward unmasks
    # nothing
    logits = jnp.zeros((2, 10)).at[:, 7].set(9.0).at[:, 3].set(5.0)
    cand, conf = SP.block_candidates(
        logits, jax.random.split(jax.random.PRNGKey(0), 2), jnp.zeros(2))
    assert list(np.asarray(cand)) == [7, 7] and float(conf[0]) > 0.9
    rule = functools.partial(SP.unmask_rule, mask_id=7, steps=2,
                             threshold=2.0)
    ids = jnp.asarray([7, 1], jnp.int32)
    for forwards, closed in ((0, False), (1, False), (2, True)):
        new, unmasked, whole = rule(ids, cand, conf, jnp.int32(forwards))
        assert bool(whole) == closed
        assert list(np.asarray(unmasked)) == [not closed, False]
        assert list(np.asarray(new)) == [7, 1]
    assert bool(rule(jnp.asarray([2, 1], jnp.int32), cand, conf,
                     jnp.int32(0))[2])
    assert SP.transfer_counts(4, 4) == [1, 1, 1, 1]
    assert SP.transfer_counts(8, 3) == [3, 3, 2]
    assert SP.transfer_counts(4, 1) == [4]
