"""The Solar Open 2 family (``paddle_tpu/models/solar_open2.py``) at toy widths
on the CPU: chunked prefill then decode through the cache and BOTH slot-state
leaves against the plain reference's full forward; the scheduler with slots
joining and leaving, a step in flight and a lost readback; the eight shares of
an expert layer; the vocabulary slice; the state kernel in interpret mode; the
refusals."""
import functools
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models import solar_open2 as M
from paddle_tpu.parallel import kda, moe
from paddle_tpu.serving.errors import ServingError
from paddle_tpu.testing import faults

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CFG = dict(
    model_type="solar_open2", hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, vocab_size=96,
    moe_intermediate_size=32, rms_norm_eps=1e-5, rope_theta=10000,
    gqa_layers=[0], use_rope=False, use_gqa_gate=True, kda_use_full_proj=False,
    kda_allow_neg_eigval=True, n_routed_experts=16, router_experts=16,
    experts_held=[0, 16], n_shared_experts=1, norm_topk_prob=True,
    routed_scaling_factor=1, num_experts_per_tok=4, first_k_dense_replace=0,
    tie_word_embeddings=False, conv_state_dtype="float32", kda_gate_rank=8,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                            num_heads=4, num_kv_heads=None))
PAGE, SLOTS, MAX_LEN, STEPS, T_PAD = 8, 3, 128, 6, 96
LOGIT_TOL = 2e-5


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 16 of the chunk-wise form, so that a toy chunk holds two."""
    monkeypatch.setattr(kda, "KDA_BLOCK", 16)


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location(
        "solar_reference", os.path.join(
            ROOT, "chipbench/configs/solar_open2_250b.reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return M.params(CFG, 3, dtype="float32")


@pytest.fixture(scope="module")
def decode_model(params):
    """One model object for the module: every scheduler over it dispatches
    the model's own step programs, so a shape is traced once."""
    return M.build_decode_model(params, CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(0).randint(0, CFG["vocab_size"], T_PAD
                                            ).astype(np.int32)


def _cache(cfg=CFG, pages=40):
    lay = M.cache_layout(cfg)
    return serving.PagedKVCache(
        lay["num_layers"], pages, PAGE, lay["num_heads"], lay["head_dim"],
        MAX_LEN, dtype="float32", slot_state=lay["slot_state"],
        num_slots=SLOTS)


def _steps(cfg):
    return (jax.jit(lambda p, c, *a: M.prefill_chunk(
                p, *a[:3], c, *a[3:], cfg=cfg, with_routing=True)),
            jax.jit(lambda p, c, *a: M.decode_step(
                p, *a[:2], c, *a[2:], cfg=cfg, with_routing=True)))


# the module's configuration is jitted once: its cases trace a shape once.  A
# case that patches what a trace reads passes ``steps_fn=_steps(CFG)``
_STEPS = _steps(CFG)


def _through_the_cache(params, tokens, prompt_len, steps, chunk, slot=1,
                       cfg=CFG, pools=None, steps_fn=None):
    """Prefill ``tokens[:prompt_len]`` in chunks of ``chunk``, decode
    ``steps`` more in ``slot``: ``(logits [1 + steps, V], routing, pools)``."""
    pools = _cache(cfg).pools if pools is None else pools
    table = np.zeros(MAX_LEN // PAGE, np.int32)
    table[:12] = np.arange(1, 13)
    run_chunk, run_step = steps_fn or (
        _STEPS if cfg is CFG else _steps(cfg))
    logits, routing = [], []
    for start in range(0, prompt_len, chunk):
        valid = min(chunk, prompt_len - start)
        toks = np.zeros(chunk, np.int32)
        toks[:valid] = tokens[start:start + valid]
        lg, pools, chosen = run_chunk(
            params, pools, jnp.asarray(toks), jnp.int32(start),
            jnp.int32(valid),
            jnp.asarray(table[start // PAGE:(start + chunk) // PAGE]),
            jnp.asarray(table), jnp.int32(slot))
        routing.append([np.asarray(c)[:valid] for c in chosen])
    logits.append(np.asarray(lg))
    tables = np.zeros((SLOTS, MAX_LEN // PAGE), np.int32)
    tables[slot] = table
    for pos in range(prompt_len, prompt_len + steps):
        vec = np.zeros(SLOTS, np.int32)
        toks, positions, lens = vec.copy(), vec.copy(), vec.copy()
        toks[slot], positions[slot], lens[slot] = tokens[pos], pos, pos + 1
        lg, pools, _, chosen = run_step(
            params, pools, jnp.asarray(toks), jnp.asarray(positions),
            jnp.asarray(tables), jnp.asarray(lens))
        logits.append(np.asarray(lg[slot]))
        routing.append([np.asarray(c)[slot:slot + 1] for c in chosen])
    sets = [np.concatenate(layer) for layer in zip(*routing)]
    return np.stack(logits), sets, pools


def _truth(reference, params, tokens, n, cfg=CFG, variant=None):
    seq = np.zeros(T_PAD, np.int32)
    seq[:n] = tokens[:n]
    fwd = jax.jit(lambda p, s, q: reference.forward(
        p, cfg, s, q, block=8, upto=n, variant=variant))
    return fwd(params, jnp.asarray(seq), jnp.arange(n))


PROMPT = 77


@pytest.fixture(scope="module")
def served(params, tokens):
    """The sound side of the controls, once: what the system serves for the
    module's prompt (chunks of 32, blocks of 16 as ``small_blocks`` sets them
    for every test)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(kda, "KDA_BLOCK", 16)
        return _through_the_cache(params, tokens, PROMPT, STEPS, 32)


# 1. the model ----------------------------------------------------------------

# chunks of 24 split a block of 16 and, with 77 = 3 x 24 + 5, a last chunk
# of 5 rows; chunks of 8 put the convolution's 4 taps across a boundary at
# every chunk
@pytest.mark.parametrize("chunk", [8, 24, 32, 80],
                         ids=["page", "splits-a-block", "two-blocks",
                              "one-chunk"])
def test_chunked_prefill_then_decode_equals_the_reference(
        reference, params, tokens, chunk):
    n = PROMPT + STEPS
    logits, sets, pools = _through_the_cache(params, tokens, PROMPT, STEPS,
                                             chunk)
    want, chosen, kept = _truth(reference, params, tokens, n)
    want = np.asarray(want)[PROMPT - 1:]
    assert np.abs(logits - want).max() <= LOGIT_TOL * np.abs(want).max()
    # the routed sets of every row of every layer
    for layer, got in enumerate(sets):
        mask = np.zeros((n, CFG["router_experts"]), bool)
        np.put_along_axis(mask, got, True, axis=1)
        assert (mask == np.asarray(chosen[layer])).all()
    # both state leaves in the slot, and nothing in the others
    for row in range(3):
        state, tail = kept[row + 1]
        assert np.abs(pools["kda"][row, 1] - state).max() <= 1e-5
        assert np.abs(pools["conv"][row, 1] - tail).max() <= 1e-5
    assert not np.asarray(pools["kda"][:, [0, 2]]).any()
    assert not np.asarray(pools["conv"][:, [0, 2]]).any()


@pytest.mark.parametrize("variant", ["beta1", "head_decay", "taps3",
                                     "no_gate", "rotary"])
def test_a_wrong_mechanism_fails_the_model_test(reference, params, tokens,
                                                served, variant):
    """The controls: the reference with ONE mechanism wrong lies hundreds of
    tolerances from the served logits."""
    n = PROMPT + STEPS
    logits = served[0]
    want = np.asarray(_truth(reference, params, tokens, n, variant=variant)[0]
                      )[PROMPT - 1:]
    assert np.abs(logits - want).max() > 100 * LOGIT_TOL * np.abs(want).max()


def test_the_kernel_in_interpret_mode_serves_the_same_logits(params, tokens,
                                                             monkeypatch):
    a = _through_the_cache(params, tokens, PROMPT, STEPS, 32)
    monkeypatch.setattr(kda, "kda_state_decode", functools.partial(
        kda.kda_state_decode, impl="pallas"))
    b = _through_the_cache(params, tokens, PROMPT, STEPS, 32,
                           steps_fn=_steps(CFG))
    assert np.abs(a[0] - b[0]).max() <= LOGIT_TOL
    for name in ("kda", "conv"):
        assert np.abs(a[2][name] - b[2][name]).max() <= 1e-5


def test_a_reseated_slot_starts_from_zero_state(params, tokens):
    """A sequence seated where another one sat reads what it would read in a
    fresh cache: its first chunk takes both leaves as zero."""
    other = np.random.RandomState(5).randint(0, CFG["vocab_size"], T_PAD
                                             ).astype(np.int32)
    _, _, used = _through_the_cache(params, other, 50, 3, 32)
    assert np.asarray(used["kda"][:, 1]).any()
    fresh = _through_the_cache(params, tokens, 40, STEPS, 32)
    again = _through_the_cache(params, tokens, 40, STEPS, 32, pools=used)
    assert np.array_equal(fresh[0], again[0])
    for name in ("kda", "conv"):
        assert np.array_equal(fresh[2][name][:, 1], again[2][name][:, 1])


# 2. shares -------------------------------------------------------------------

def test_the_eight_shares_of_a_layer_sum_to_the_uncut_layer(reference,
                                                            params):
    """``experts_held`` = (0, 2), (2, 4), .., (14, 16) under the router of 16,
    the shared expert passed to the first only: the parts add up to the
    reference's uncut layer, and each part is the reference's share."""
    u = jax.random.normal(jax.random.PRNGKey(1), (24, CFG["hidden_size"]))
    router = {"w": params["router_w"][1], "bias": params["router_b"][1]}
    lp = params["layers"][1]
    shared = {"w_gu": lp["s_gu"], "w_down": lp["s_down"]}
    whole = reference.moe_layer(
        u, router["w"], router["bias"], params["e_gu"][1], params["e_down"][1],
        (lp["s_gu"], lp["s_down"]), 4, (0, 16))[0]
    total, pairs = 0.0, 0
    for lo in range(0, 16, 2):
        held = (lo, lo + 2)
        y, counts, _ = moe.moe_topk(
            u, router, {"w_gu": params["e_gu"][1, lo:lo + 2],
                        "w_down": params["e_down"][1, lo:lo + 2]},
            shared if lo == 0 else None, top_k=4, experts_held=held,
            scoring="sigmoid")
        part = reference.moe_layer(
            u, router["w"], router["bias"], params["e_gu"][1, lo:lo + 2],
            params["e_down"][1, lo:lo + 2],
            (lp["s_gu"], lp["s_down"]) if lo == 0 else None, 4, held)[0]
        assert np.abs(y - part).max() <= 1e-5
        total, pairs = total + y, pairs + int(counts[0])
    assert np.abs(total - whole).max() <= 1e-5
    assert pairs == 24 * 4          # every chosen pair computed exactly once


def test_a_share_of_the_model_is_the_references_share(reference, params,
                                                      tokens):
    """``take_share``: experts 4-7 of 16 and ids 32-63 of the vocabulary.  The
    logits over the slice are the slice of the whole head's logits for the
    same share of experts, and the counters tell held pairs from the rest."""
    weights, cfg = M.take_share(params, CFG, (4, 8), vocab=(32, 64))
    assert cfg["n_routed_experts"] == 4 and cfg["vocab_size"] == 32
    toks = 32 + tokens % 32                # ids of the slice, as traffic's are
    local = (toks - 32).astype(np.int32)
    logits, _, _ = _through_the_cache(weights, local, 40, STEPS, 32, cfg=cfg)
    # the same share of experts under the WHOLE embedding and head
    wide, wide_cfg = M.take_share(params, CFG, (4, 8))
    full, _, _ = _through_the_cache(wide, toks, 40, STEPS, 32, cfg=wide_cfg)
    assert np.abs(logits - full[:, 32:64]).max() <= LOGIT_TOL
    want = np.asarray(_truth(reference, weights, local, 40 + STEPS,
                             cfg=cfg)[0])[39:]
    assert np.abs(logits - want).max() <= LOGIT_TOL * np.abs(want).max()


def test_step_counters_tell_held_pairs_from_the_rest(params, tokens):
    weights, cfg = M.take_share(params, CFG, (4, 8))
    pools = _cache(cfg).pools
    tables = np.tile(np.arange(1, 17, dtype=np.int32), (SLOTS, 1))
    lens = np.asarray([5, 0, 9], np.int32)
    _, _, counts, chosen = M.decode_step(
        weights, jnp.asarray(tokens[:SLOTS]), jnp.asarray(np.maximum(
            lens - 1, 0)), pools, jnp.asarray(tables), jnp.asarray(lens),
        cfg=cfg, with_routing=True)
    chosen = np.stack([np.asarray(c) for c in chosen])[:, lens > 0]
    held = ((chosen >= 4) & (chosen < 8)).sum()
    named = dict(zip(M.STEP_COUNTERS, np.asarray(counts)))
    assert named["kda.slot_updates"] == 2 * 3
    assert named["kv.full_tokens_read"] == 14
    assert named["moe.pairs"] == held
    assert named["moe.pairs_elsewhere"] == 2 * 4 * 4 - held
    assert 0 < named["moe.experts_touched"] <= 4 * 4


# 3. the scheduler ------------------------------------------------------------

def _scheduler(model, **over):
    kw = dict(num_slots=SLOTS, page_size=PAGE, max_seq_len=MAX_LEN,
              num_pages=41, prefill_buckets=(8, 32, 96),
              prefill_chunk_tokens=32, max_new_tokens=STEPS,
              kv_dtype="float32")
    kw.update(over)
    return serving.DecodeScheduler(model, serving.DecodeConfig(**kw))


def _read_each_step_before_the_next(sched):
    def plans():
        plan = None if sched._unread else sched._plan_step()
        return [] if plan is None else [plan]

    sched._plan_steps = plans


def test_the_scheduler_serves_the_references_tokens(
        reference, params, decode_model, tokens):
    """Five requests over three slots: sequences join while others decode and
    a slot is seated a second time.  Every served token is the reference's
    argmax given the tokens before it, so a reseated slot started from zero
    state and a step in flight never read a state behind its own."""
    before = {c: obs.counter("serving.decode." + c).value
              for c in M.STEP_COUNTERS + ("steps_overlapped",)}
    resets = obs.counter("serving.cache.state_resets").value
    sched = _scheduler(decode_model)
    prompts = [tokens[:n] for n in (77, 5, 40, 61, 13)]
    futs = [sched.submit(p, max_new_tokens=STEPS) for p in prompts]
    outs = [f.result(timeout=300) for f in futs]
    sched.stop()
    fwd = jax.jit(lambda p, s, q: reference.forward(p, CFG, s, q, block=8))
    for prompt, out in zip(prompts, outs):
        seq = np.zeros(T_PAD, np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + STEPS] = out
        pos = jnp.arange(len(prompt) - 1, len(prompt) + STEPS - 1)
        logits = np.asarray(fwd(params, jnp.asarray(seq), pos)[0])
        top2 = np.sort(logits, axis=-1)[:, -2:]
        sure = (top2[:, 1] - top2[:, 0]) > 1e-3 * logits.std()
        assert (logits.argmax(-1) == out)[sure].all()
    after = {c: obs.counter("serving.decode." + c).value for c in before}
    assert after["steps_overlapped"] > before["steps_overlapped"]
    assert after["kda.slot_updates"] > before["kda.slot_updates"]
    assert after["moe.pairs_elsewhere"] == before["moe.pairs_elsewhere"]
    assert obs.counter("serving.cache.state_resets").value == resets + 5
    assert sched.stats()["kv_pages_used"] == 0


def test_a_step_in_flight_serves_what_the_in_order_loop_did(
        decode_model, tokens):
    runs = {}
    for loop in ("in flight", "in order"):
        sched = _scheduler(decode_model)
        if loop == "in order":
            _read_each_step_before_the_next(sched)
        futs = [sched.submit(tokens[:n], max_new_tokens=STEPS)
                for n in (77, 5, 40, 61)]
        runs[loop] = [f.result(timeout=300).tobytes() for f in futs]
        sched.stop()
    assert runs["in flight"] == runs["in order"]


def test_a_lost_readback_puts_both_state_leaves_back(decode_model, tokens):
    """The readback of step n is lost with step n + 1 dispatched behind it:
    both are dropped and the cache — pages AND both slot-state leaves, which
    the dropped steps had already moved — is what step n took; the retry
    serves a clean run's tokens."""
    clean = _scheduler(decode_model)
    want = clean.generate(tokens[:40], max_new_tokens=12, timeout=300)
    clean.stop()
    sched = _scheduler(decode_model)
    read, fired = sched._read_step, [0]

    def lossy(sent):
        if (not fired[0] and len(sched._unread) == 2
                and len(sent.entries[0][1].generated) >= 4):
            fired[0] += 1
            raise faults.FaultInjected("injected lost readback")
        return read(sent)

    sched._read_step = lossy
    retries = obs.counter("serving.decode.step_retries").value
    got = sched.generate(tokens[:40], max_new_tokens=12, timeout=300)
    sched.stop()
    assert fired[0] == 1
    assert obs.counter("serving.decode.step_retries").value == retries + 1
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("what", ["prefix_cache", "sessions", "role"])
def test_slot_state_refuses_what_it_cannot_do(params, what):
    kw, cfg = {}, {}
    if what == "prefix_cache":
        cfg = dict(prefix_cache=True)
    elif what == "sessions":
        cfg, kw = dict(prefix_cache=True), dict(
            sessions=serving.SessionStore())
    else:
        kw = dict(role="decode")
    with pytest.raises(ServingError, match="conv, kda"):
        serving.DecodeScheduler(
            M.build_decode_model(params, CFG),
            serving.DecodeConfig(num_slots=SLOTS, page_size=PAGE,
                                 max_seq_len=MAX_LEN, num_pages=41,
                                 warmup=False, **cfg),
            autostart=False, **kw)


def test_the_cache_states_its_bytes_by_leaf():
    cache = _cache()
    kda_bytes = 3 * SLOTS * 4 * 16 * 16 * 4
    conv_bytes = 3 * SLOTS * 3 * (3 * 4 * 16) * 4
    assert cache.state_leaf_bytes() == {"kda": kda_bytes, "conv": conv_bytes}
    assert cache.state_bytes == kda_bytes + conv_bytes
    for leaf, n in (("kda", kda_bytes), ("conv", conv_bytes)):
        assert obs.gauge("serving.cache.state_leaf_bytes",
                         labels={"leaf": leaf}).value == n
    assert cache.slot_leaf_names == ("kda", "conv")
    assert cache.pools["conv"].dtype == jnp.float32
    assert M.cache_layout(dict(CFG, conv_state_dtype="bfloat16"))[
        "slot_state"]["conv"]["dtype"] == "bfloat16"


@pytest.mark.parametrize("key,value", [
    ("use_rope", True), ("use_gqa_gate", False), ("kda_use_full_proj", True),
    ("first_k_dense_replace", 1), ("norm_topk_prob", False)])
def test_a_key_the_model_does_not_write_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        M.cache_layout(dict(CFG, **{key: value}))


def test_a_share_that_is_not_the_stated_count_is_refused():
    with pytest.raises(ValueError, match="experts_held"):
        M.cache_layout(dict(CFG, experts_held=[0, 8]))
    with pytest.raises(ValueError, match="gqa_layers"):
        M.cache_layout(dict(CFG, gqa_layers=[]))


# 4. the state kernel and the chunk-wise form ---------------------------------

def _inputs(rng, rows, H=4, d=16):
    def vec(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    return (unit(vec(rows, H, d)), unit(vec(rows, H, d)), vec(rows, H, d),
            -jnp.exp(vec(rows, H, d)), jnp.asarray(
                2 * rng.rand(rows, H), jnp.float32))


@pytest.mark.parametrize("live", [
    [1, 0, 1, 1, 0, 0], [0, 0, 1, 0, 1, 1], [0] * 6, [1] * 6],
    ids=["ends-dead", "starts-dead", "none", "all"])
def test_the_state_kernel_is_the_recurrence_in_place(reference, live):
    rng = np.random.RandomState(0)
    S = len(live)
    stack = jnp.asarray(rng.randn(3, S, 4, 16, 16), jnp.float32)
    q, k, v, g, beta = _inputs(rng, S)
    live = jnp.asarray(live, bool)
    o, new = kda.kda_state_decode(stack, q, k, v, g, beta, live, layer=1,
                                  impl="pallas", interpret=True)
    want_o, want_s = jax.vmap(reference.kda_step)(stack[1], q, k, v, g, beta)
    if live.any():
        assert np.abs(o[live] - want_o[live]).max() <= 1e-5
        assert np.abs(new[1][live] - want_s[live]).max() <= 1e-5
    # dead slots and the other layers untouched, bit for bit; they read zeros
    assert np.array_equal(new[1][~live], stack[1][~live])
    assert np.array_equal(new[0], stack[0]) and np.array_equal(new[2], stack[2])
    assert not np.asarray(o[~live]).any()
    # and the plain engine is the same function
    o2, new2 = kda.kda_state_decode(stack, q, k, v, g, beta, live, layer=1,
                                    impl="reference")
    assert np.abs(o - o2).max() <= 1e-5 and np.abs(new - new2).max() <= 1e-5


def test_the_state_kernel_counts_its_grid_steps_and_refuses_bf16():
    rng = np.random.RandomState(1)
    q, k, v, g, beta = _inputs(rng, 2)
    state = jnp.zeros((2, 4, 16, 16), jnp.float32)
    cell = obs.counter("kda.decode.grid_steps", labels={
        "slots": 2, "heads": 4, "dk": 16, "dv": 16, "heads_a_step": 4})
    n = cell.value
    kda.kda_state_decode(state, q, k, v, g, beta, jnp.ones(2, bool),
                         impl="pallas", interpret=True)
    assert cell.value == n + 2
    with pytest.raises(ValueError, match="float32"):
        kda.kda_state_decode(state.astype(jnp.bfloat16), q, k, v, g, beta,
                             jnp.ones(2, bool))


@pytest.mark.parametrize("valid", [160, 150, 17, 1])
def test_the_chunk_wise_form_is_the_recurrence(reference, valid):
    """Ten blocks of 16, a ragged end, a fast channel (``exp(-G)`` of it would
    overflow float32 inside the chunk: the form keeps differences)."""
    rng = np.random.RandomState(2)
    C = 160
    q, k, v, g, beta = _inputs(rng, C)
    g = g.at[:, 0, 0].set(-1.6)              # 160 x 1.6: exp(256) overflows
    s0 = jnp.asarray(rng.randn(4, 16, 16), jnp.float32)
    o, s1 = jax.jit(kda.kda_chunk)(q, k, v, g, beta, s0, jnp.int32(valid))
    S, outs = s0, []
    for t in range(valid):
        ot, S = reference.kda_step(S, q[t], k[t], v[t], g[t], beta[t])
        outs.append(ot)
    assert np.isfinite(np.asarray(o[:valid])).all()
    assert np.abs(o[:valid] - jnp.stack(outs)).max() <= 2e-5
    assert np.abs(s1 - S).max() <= 2e-5
