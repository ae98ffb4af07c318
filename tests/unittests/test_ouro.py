"""The Ouro family (``models/ouro.py``: a LOOPED language model) through the
serving path against its plain reference
(``chipbench/configs/ouro_2_6b.reference.py``) on the CPU at toy sizes with
seeded float32 weights: logits, exit gates and the K and V rows of every (loop
step, layer) after chunked prefill and through decode, from a cache that is
``total_ut_steps x num_hidden_layers`` K/V layers deep; the structure (one
loop step IS a plain sandwich-norm decoder; the rolled loop IS the unrolled
one; a paged walk given its layer as a traced scalar IS the walk given an
int); the shortcut that must fail (one loop step's rows read by all); the exit
rule; the engine's served tokens, counters and scopes; the prefix cache; a
reseated slot over a poisoned pool.

At these sizes the model runs in float32 end to end, so the system differs
from the reference only by the ORDER of float32 operations.
"""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models import ouro as M
from paddle_tpu.parallel import flash_attention as FA

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REFERENCE = os.path.join(ROOT, "chipbench/configs/ouro_2_6b.reference.py")

# 4 heads of 128 lanes (the chip's tiles), U = 4 loop steps of L = 3 layers
CFG = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    head_dim=128, intermediate_size=96, hidden_act="silu",
    num_hidden_layers=3, layer_types=["full_attention"] * 3,
    max_window_layers=3, sliding_window=None, use_sliding_window=False,
    rope_theta=1000000, rope_scaling=None, rms_norm_eps=1e-6,
    tie_word_embeddings=False, vocab_size=97, max_position_embeddings=65536,
    total_ut_steps=4, early_exit_threshold=1)
U, L = CFG["total_ut_steps"], CFG["num_hidden_layers"]
PAGE, SLOTS, MAX_LEN, PAGES = 8, 3, 64, 40
CHUNK, BUCKETS = 16, (8, 16, 64)
T0 = 45                 # the prefilled context: 3 chunks (one ragged), 6 pages
N_DECODE = 6
LOGIT_TOL = 2e-4        # max |a - b| / std(b): float32 reordering only
POISON = 3e4


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("ouro_reference", REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return M.params(CFG, 0, dtype="float32")


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(1).randint(1, 97, size=MAX_LEN).astype(
        np.int32)


@pytest.fixture(scope="module")
def truth(reference, params, tokens):
    """The reference's logits ``[T, V]``, gates ``[U, T]`` and the K and V
    rows of every (u, l) at every position."""
    every = [(u, l) for u in range(U) for l in range(L)]
    logits, gates, kv = jax.jit(lambda p, t: reference.forward(
        p, CFG, t, jnp.arange(MAX_LEN), block=32, rows=every))(
            params, jnp.asarray(tokens))
    return (np.asarray(logits, np.float64), np.asarray(gates, np.float64),
            [(np.asarray(k), np.asarray(v)) for k, v in kv])


def _jitted(module=M, cfg=CFG):
    return (jax.jit(lambda p, c, *a: module.prefill_chunk(
                p, *a[:3], c, *a[3:], cfg=cfg, with_gates=True)),
            jax.jit(lambda p, c, *a: module.decode_step(
                p, *a[:2], c, *a[2:], cfg=cfg, with_gates=True)))


@pytest.fixture(scope="module")
def fns():
    return _jitted()


def _cache(cfg=CFG, fill=0.0):
    layout = M.cache_layout(cfg)
    cache = serving.PagedKVCache(
        layout["num_layers"], PAGES, PAGE, layout["num_heads"],
        layout["head_dim"], MAX_LEN, dtype="float32", num_slots=SLOTS)
    if fill:
        cache.pools = {n: jnp.full_like(a, fill)
                       for n, a in cache.pools.items()}
    return cache


def _through_the_cache(fns, params, tokens, cache, n_decode=N_DECODE,
                       context=T0):
    """Prefill ``tokens[:context]`` in chunks into slot 0, then decode
    ``n_decode`` tokens; returns ``(logits by position, gates by position,
    pools, pages)``."""
    chunk, decode = fns
    pages = cache.alloc(cache.pages_for(context + n_decode))
    row = cache.table_row(pages)
    pools = cache.pools
    logits, gates = {}, {}
    start = 0
    while start < context:
        valid = min(CHUNK, context - start)
        toks = np.zeros(CHUNK, np.int32)
        toks[:valid] = tokens[start:start + valid]
        vec = np.zeros(CHUNK // PAGE, np.int32)
        n = min(len(vec), len(pages) - start // PAGE)
        vec[:n] = pages[start // PAGE:start // PAGE + n]
        out, pools, g = chunk(params, pools, jnp.asarray(toks),
                              jnp.int32(start), jnp.int32(valid),
                              jnp.asarray(vec), jnp.asarray(row), jnp.int32(0))
        for i in range(valid):
            gates[start + i] = np.asarray(g)[:, i]
        start += valid
        logits[start - 1] = np.asarray(out)
    tables = np.zeros((SLOTS, cache.max_pages_per_seq), np.int32)
    tables[0] = row
    counts = []
    for pos in range(context, context + n_decode):
        toks, at, lens = (np.zeros(SLOTS, np.int32) for _ in range(3))
        toks[0], at[0], lens[0] = tokens[pos], pos, pos + 1
        out, pools, c, g = decode(params, pools, jnp.asarray(toks),
                                  jnp.asarray(at), jnp.asarray(tables),
                                  jnp.asarray(lens))
        logits[pos], gates[pos] = np.asarray(out[0]), np.asarray(g)[:, 0]
        counts.append(np.asarray(c))
    return logits, gates, pools, pages, counts


def _err(got, want):
    return float(np.max(np.abs(got - want)) / want.std())


# -- against the reference -----------------------------------------------------

@pytest.fixture(scope="module")
def served(fns, params, tokens):
    return _through_the_cache(fns, params, tokens, _cache())


def test_logits_and_gates_through_chunks_and_decode(served, truth):
    logits, gates, _, _, counts = served
    ref_logits, ref_gates, _ = truth
    assert sorted(logits) == [15, 31, 44] + list(range(T0, T0 + N_DECODE))
    for pos, got in logits.items():
        assert _err(got, ref_logits[pos]) < LOGIT_TOL, pos
    for pos, got in gates.items():
        assert np.abs(got - ref_gates[:, pos]).max() < 2e-5, pos
    # the gates are not flat: sigmoid(g) lies in about 0.2 .. 0.8 and moves
    lam = 1 / (1 + np.exp(-ref_gates))
    assert 0.15 < lam.min() and lam.max() < 0.85 and lam.std() > 0.01
    # one live slot: U x L layer applications, kv_len rows a K/V layer, and
    # at the published threshold the last step serves
    for i, c in enumerate(counts):
        assert list(c) == [U * L, (T0 + i + 1) * U * L, U]


def test_rows_of_every_loop_step_and_layer(served, truth):
    _, _, pools, pages, _ = served
    end = T0 + N_DECODE
    for leaf, which in (("k", 0), ("v", 1)):
        got = np.asarray(pools[leaf])[:, np.asarray(pages)].reshape(
            U * L, -1, CFG["num_attention_heads"] * CFG["head_dim"])[:, :end]
        for u in range(U):
            for l in range(L):
                want = truth[2][u * L + l][which][:end]
                assert np.abs(got[u * L + l] - want).max() < 2e-5 * max(
                    1.0, np.abs(want).max()), (leaf, u, l)
    # a loop step's rows are its own: step 1's differ from step 0's
    k = np.asarray(pools["k"])[:, np.asarray(pages)]
    assert np.abs(k[L] - k[0]).max() > 0.1


def test_the_shortcut_fails(reference, params, tokens, truth):
    """A cache that kept ONE loop step's rows (the paper's last-step reuse:
    every step reads step U - 1's K and V) gives other logits, far past the
    tolerance the served path is held to."""
    at = jnp.asarray([T0 - 1, T0 + N_DECODE - 1])
    shared, _, _ = jax.jit(lambda p, t: reference.forward(
        p, CFG, t, at, block=32, share_last_step=True))(
            params, jnp.asarray(tokens))
    for got, pos in zip(np.asarray(shared, np.float64), np.asarray(at)):
        assert _err(got, truth[0][pos]) > 1000 * LOGIT_TOL


# -- structure -----------------------------------------------------------------

def _step_by_step(step, carry, n):
    """The loop over the loop steps as a Python loop: each step a program of
    its own with ``u`` STATIC (its K/V layers ``u * L + l`` are Python ints,
    as an unrolled program has them), the carry handed on between them as the
    program's loop hands it on.  (Unrolled inside ONE program the compiler
    fuses a step's closing norm into the next step's first reduction: other
    last bits, not another model; ``_proof/unrolled.py`` is that form, for the
    chip's set-up times.)"""
    gates = []
    for u in range(n):
        carry, g = jax.jit(step, static_argnums=0)(u, carry)
        gates.append(g)
    return carry, jnp.stack(gates)


def _same(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def test_rolled_loop_equals_the_unrolled_one(params, tokens, monkeypatch):
    """The loop of the program (``lax.scan``, a traced K/V layer) against the
    Python loop over the same step function with static layers: logits, gates
    and every row of the cache, bit for bit, through chunks and decode."""
    # (a short schedule: the step-by-step form compiles every step anew)
    short = dict(n_decode=2, context=20)
    want = _through_the_cache(_jitted(), params, tokens, _cache(), **short)
    monkeypatch.setattr(M, "_loop_steps", _step_by_step)
    eager = (lambda p, c, *a: M.prefill_chunk(p, *a[:3], c, *a[3:], cfg=CFG,
                                              with_gates=True),
             lambda p, c, *a: M.decode_step(p, *a[:2], c, *a[2:], cfg=CFG,
                                            with_gates=True))
    got = _through_the_cache(eager, params, tokens, _cache(), **short)
    for a, b in zip(want[:3], got[:3]):
        assert _same(a, b)


def test_one_loop_step_is_a_plain_sandwich_norm_decoder(params, tokens):
    """With ``total_ut_steps`` 1 the model is a plain decoder of L layers with
    four norms a layer and one final norm: the unlooped functions (static
    layers, no loop) written out here give the same bits."""
    cfg = dict(CFG, total_ut_steps=1)
    d = M._dims(cfg)

    def plain_decode(p, c, tokens, positions, tables, lens):
        k_pool, v_pool = c["k"], c["v"]
        S = tokens.shape[0]
        pages = tables[jnp.arange(S), positions // PAGE]
        x = p["embed"][tokens].astype(jnp.float32)
        for l, lp in enumerate(p["layers"]):
            q, k, v = M._qkv(d, p, lp, l, x, positions)
            k_pool = k_pool.at[l, pages, positions % PAGE].set(k)
            v_pool = v_pool.at[l, pages, positions % PAGE].set(v)
            o = FA.paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                          sm_scale=d["sm_scale"], layer=l)
            x = M._mlp(d, p, lp, l, M._attn_out(d, p, lp, l, x, o))
        h, g = M._loop_end(d, p, x)
        return M._mm(h, p["head"]), {"k": k_pool, "v": v_pool}, g[None]

    def plain_chunk(p, c, tokens, start, valid, written, row, slot):
        k_pool, v_pool = c["k"], c["v"]
        C = tokens.shape[0]
        positions = start + jnp.arange(C, dtype=jnp.int32)
        x = p["embed"][tokens].astype(jnp.float32)
        for l, lp in enumerate(p["layers"]):
            q, k, v = M._qkv(d, p, lp, l, x, positions)
            k_pool = k_pool.at[l, written].set(k.reshape(C // PAGE, PAGE, -1))
            v_pool = v_pool.at[l, written].set(v.reshape(C // PAGE, PAGE, -1))
            o = FA.paged_prefill_attention(q, k_pool, v_pool, row, start,
                                           sm_scale=d["sm_scale"], layer=l)
            x = M._mlp(d, p, lp, l, M._attn_out(d, p, lp, l, x, o))
        h, g = M._loop_end(d, p, x)
        return M._mm(h[valid - 1], p["head"]), {"k": k_pool, "v": v_pool}, g[None]

    def plain(fn, n):
        def run(*a):
            out = fn(*a)
            return out[:2] + (None,) * (n - 3) + out[2:]
        return jax.jit(run)

    looped = _through_the_cache(_jitted(cfg=cfg), params, tokens, _cache(cfg))
    unlooped = _through_the_cache(
        (plain(plain_chunk, 3), plain(plain_decode, 4)), params, tokens,
        _cache(cfg))
    for a, b in zip(looped[:3], unlooped[:3]):
        assert _same(a, b)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("form", ["decode", "chunk", "grouped_decode",
                                  "grouped_chunk", "listed_decode"])
def test_a_traced_layer_is_the_static_layer(form, impl):
    """Each paged walk given its layer as a traced scalar equals the same walk
    given it as an int, bit for bit, at every layer of a 6-layer pool (the
    kernels interpreted, and their ``jax.numpy`` fallbacks)."""
    layers, pages, H, Dh, S, C = 6, 9, 4, 128, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    kp, vp = (jax.random.normal(k, (layers, pages, PAGE, H * Dh), jnp.float32)
              .astype(jnp.bfloat16) for k in ks[:2])
    tables = jnp.asarray(np.array([[1, 2, 3, 4], [5, 6, 0, 0], [7, 8, 0, 0]],
                                  np.int32))
    lens = jnp.asarray([29, 11, 0], jnp.int32)
    kw = dict(impl=impl, interpret=True)
    if form.endswith("decode"):
        q = jax.random.normal(ks[2], (S, H, Dh), jnp.float32).astype(
            jnp.bfloat16)
        sel = FA._head_lists(tables, lens, H, None)
        call = {"decode": lambda l: FA.paged_decode_attention(
                    q, kp, vp, tables, lens, layer=l, **kw),
                "grouped_decode": lambda l: FA.paged_gqa_decode_attention(
                    q, kp, vp, tables, lens, layer=l, **kw),
                "listed_decode": lambda l: FA.paged_decode_attention(
                    q, kp, vp, tables, lens, layer=l, selection=sel, **kw)
                }[form]
    else:
        q = jax.random.normal(ks[3], (C, H, Dh), jnp.float32).astype(
            jnp.bfloat16)
        call = {"chunk": lambda l: FA.paged_prefill_attention(
                    q, kp, vp, tables[0], jnp.int32(8), layer=l, **kw),
                "grouped_chunk": lambda l: FA.paged_gqa_prefill_attention(
                    q, kp, vp, tables[0], jnp.int32(8), jnp.int32(C),
                    layer=l, **kw)}[form]
    traced = jax.jit(call)
    outs = []
    for layer in range(layers):
        a = np.asarray(call(layer).astype(jnp.float32))
        b = np.asarray(traced(jnp.int32(layer)).astype(jnp.float32))
        assert np.array_equal(a, b), layer
        outs.append(a)
    assert np.abs(outs[0] - outs[5]).max() > 0.01     # the layers differ


def test_a_traced_layer_is_refused_where_an_index_map_holds_it():
    q = jnp.zeros((16, 4, 128), jnp.float32)
    pool = jnp.zeros((2, 4, 8, 2 * 128), jnp.float32)     # 2 KV heads: grouped
    with pytest.raises(ValueError, match="static layer"):
        jax.jit(lambda l: FA.paged_prefill_attention(
            q, pool, pool, jnp.zeros((4,), jnp.int32), jnp.int32(0), layer=l,
            impl="pallas", interpret=True))(jnp.int32(1))


# -- the exit rule --------------------------------------------------------------

def test_served_step_follows_the_exit_rule(reference, truth):
    gates = truth[1]                                        # [U, T]
    # the published threshold: nothing exits before the last step
    assert np.all(np.asarray(M.served_step(jnp.asarray(gates), 1.0)) == U - 1)
    # a threshold of the test's own, handed to the reference's rule
    for threshold in (0.5, 0.8):
        want = reference.exit_step(gates, threshold)
        got = np.asarray(M.served_step(jnp.asarray(gates), threshold))
        assert np.array_equal(got, want)
        assert len(set(want.tolist())) > 1 or threshold == 0.5
    # one loop step: there is only the last
    assert np.all(np.asarray(M.served_step(jnp.asarray(gates[:1]), 0.5)) == 0)


def test_what_is_not_written_is_refused():
    with pytest.raises(ValueError, match="depth a token"):
        M.build_decode_model({}, dict(CFG, early_exit_threshold=0.5))
    with pytest.raises(ValueError, match="grouped K/V heads"):
        M.build_decode_model({}, dict(CFG, num_key_value_heads=2))
    with pytest.raises(ValueError, match="use_sliding_window"):
        M.build_decode_model({}, dict(CFG, use_sliding_window=True))


# -- a reseated slot ------------------------------------------------------------

def test_a_reseated_slot_reads_no_row_of_its_last_occupant(fns, params,
                                                           tokens, served):
    """Every row of every K/V layer that the sequence does not own holds
    POISON (what a last occupant or nobody left): the logits are those of the
    clean pool, bit for bit."""
    got = _through_the_cache(fns, params, tokens, _cache(fill=POISON))
    assert _same(served[0], got[0]) and _same(served[1], got[1])


# -- the engine -----------------------------------------------------------------

def _engine(params, **more):
    return serving.InferenceEngine(
        decode_model=M.build_decode_model(params, CFG),
        decode_config=serving.DecodeConfig(
            num_slots=SLOTS, page_size=PAGE, max_seq_len=MAX_LEN,
            num_pages=PAGES, prefill_buckets=BUCKETS,
            prefill_chunk_tokens=CHUNK, kv_dtype="float32", **more))


@pytest.fixture(scope="module")
def engine(params):
    eng = _engine(params, prefix_cache=True)
    yield eng
    eng.stop()


def test_the_cache_is_deeper_than_the_weights(engine, params):
    cache = engine.decoder.cache
    assert cache.pools["k"].shape[0] == U * L == 12
    assert len(params["layers"]) == L
    # a page holds its tokens' rows in every K/V layer, K and V
    assert cache.pools["k"].shape == (U * L, PAGES, PAGE, 4 * 128)


def test_engine_serves_the_references_tokens_and_counts(engine, tokens, truth):
    names = ["serving.decode." + n for n in M.STEP_COUNTERS]
    before = [obs.counter(n).value for n in names]
    steps0 = obs.counter("serving.decode.steps").value
    out = engine.generate(tokens[:T0], max_new_tokens=N_DECODE)
    assert len(out) == N_DECODE
    # greedy over the reference's logits, on the tokens the engine chose
    seq = np.concatenate([tokens[:T0], out])
    assert out[0] == int(np.argmax(truth[0][T0 - 1]))
    steps = obs.counter("serving.decode.steps").value - steps0
    moved = [obs.counter(n).value - b for n, b in zip(names, before)]
    assert steps >= N_DECODE - 1
    # one live slot a step: U x L applications and U served steps each
    assert moved[0] == steps * U * L and moved[2] == steps * U
    assert moved[1] >= U * L * sum(range(T0 + 1, T0 + steps))
    assert len(seq) == T0 + N_DECODE


def test_the_prefix_cache_brings_every_loop_steps_rows(engine, params, tokens):
    """A hit skips the prefill of the cached pages: the tokens equal a cold
    engine's only if the pages bring the rows of all ``U x L`` K/V layers."""
    prompt = tokens[:T0 - 2]
    hits = obs.counter("serving.decode.kv_hit_pages")
    first = engine.generate(prompt, max_new_tokens=N_DECODE)
    h0 = hits.value
    again = engine.generate(prompt, max_new_tokens=N_DECODE)
    assert hits.value - h0 >= (len(prompt) - 1) // PAGE
    cold = _engine(params, prefix_cache=False)
    try:
        want = cold.generate(prompt, max_new_tokens=N_DECODE)
    finally:
        cold.stop()
    assert np.array_equal(first, want) and np.array_equal(again, want)


def test_the_decode_programs_scopes_sit_in_the_loops_body(engine):
    from chipbench import ouro_decode

    text = engine.decoder.decode_program_text()
    names = ouro_decode.stage_names(text)
    assert names["attn"] and names["mlp"] and names["loop_end"]
    # (a reduction's scalar region carries the bare scope; every instruction
    # of the program proper carries its whole path)
    scoped = [line for line in text.splitlines()
              if "ouro.attn" in line and "jit(decode)" in line]
    assert scoped and all("ouro.loop/while/body" in line for line in scoped)
