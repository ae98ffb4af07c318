"""The Mellum family (``models/mellum.py``) through the serving path against its
plain reference (``chipbench/configs/mellum2_12b_a2_5b.reference.py``) on the
CPU at toy sizes with seeded float32 weights: logits of chunked prefill and of
decode through a cache in two page GROUPS (the sliding layers' window smaller
than the sequence and no multiple of the page, pages released on the way and
poisoned as they go), the routed sets, the served tokens of the scheduler, the
step counters, YaRN's inverse frequencies against the formula, and what a
window group is refused.

At these sizes the model runs in float32 end to end, so the system differs
from the reference only by the ORDER of float32 operations: 1e-4 of the
logits' spread holds that.
"""
import collections
import functools
import importlib.util
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models import mellum as M
from paddle_tpu.serving.errors import ServingError

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REFERENCE = os.path.join(ROOT,
                         "chipbench/configs/mellum2_12b_a2_5b.reference.py")

YARN = {"rope_type": "yarn", "rope_theta": 10000, "factor": 4,
        "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 0.1 * math.log(4) + 1}
CFG = dict(
    attention_bias=False, hidden_act="silu", hidden_size=48,
    intermediate_size=64, moe_intermediate_size=24, vocab_size=100,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    num_hidden_layers=4, rms_norm_eps=1e-6, sliding_window=21,
    tie_word_embeddings=False,
    layer_types=["sliding_attention", "sliding_attention", "full_attention",
                 "sliding_attention"],
    mlp_layer_types=["sparse"] * 4,
    rope_parameters={"full_attention": YARN,
                     "sliding_attention": {"rope_type": "default",
                                           "rope_theta": 10000}})
PAGE, SLOTS, MAX_LEN, T_PAD = 8, 3, 96, 96
PROMPT, STEPS = 60, 12
LOGIT_TOL = 1e-4        # max |a - b| / std(b): float32 reordering only


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("mellum_reference", REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return M.params(CFG, 0, dtype="float32")


@pytest.fixture(scope="module")
def decode_model(params):
    """One model object for the module: every scheduler over it dispatches
    the model's own step programs, so a shape is traced once."""
    return M.build_decode_model(params, CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(1).randint(1, 100, size=T_PAD).astype(np.int32)


_CHUNK = jax.jit(functools.partial(M.prefill_chunk, cfg=CFG,
                                   with_routing=True))
_DECODE = jax.jit(functools.partial(M.decode_step, cfg=CFG,
                                    with_routing=True))


def _cache(window_pages):
    layout = M.cache_layout(CFG)
    sizes = {"full": SLOTS * (MAX_LEN // PAGE) + 1, "window": window_pages}
    return serving.PagedKVCache(
        0, None, PAGE, 0, 0, MAX_LEN, num_slots=SLOTS,
        page_pools=layout["page_pools"],
        page_groups={g: dict(spec, num_pages=sizes[g])
                     for g, spec in layout["page_groups"].items()})


def _through_the_cache(params, tokens, prompt_len, steps, chunk, slot=1,
                       steps_fn=(_CHUNK, _DECODE)):
    """Prefill ``tokens[:prompt_len]`` in chunks of ``chunk`` into ``slot``,
    then decode ``steps`` tokens (teacher forced), the window group's pages
    handed out and given back as the scheduler does it — from a pool of just
    the slot's bound, each page POISONED with NaN as it is given back.
    Returns the logits at positions ``prompt_len - 1 ..``, each layer's
    chosen experts there, the last step's counters and the pages released."""
    run_chunk, run_decode = steps_fn
    grp_bound = -(-(CFG["sliding_window"] + chunk) // PAGE) + 1
    cache = _cache(grp_bound + 1)
    grp = cache.groups["window"]
    assert grp.slot_bound(MAX_LEN, chunk) == grp_bound
    pages = cache.alloc(cache.pages_for(prompt_len + steps))
    row = cache.table_row(pages)
    ring = np.zeros((SLOTS, grp_bound), np.int32)
    held, base, released = collections.deque(), [0], [0]
    pools = cache.pools

    def reach(upto):
        for p in range(base[0] + len(held), -(-upto // PAGE)):
            held.append(grp.alloc(1)[0])        # never None: the bound holds
            ring[slot, p % grp_bound] = held[-1]

    def leave(next_pos, pools):
        while base[0] < grp.first_live_page(next_pos) and held:
            page = held.popleft()
            ring[slot, base[0] % grp_bound] = 0
            grp.free([page], released=True)
            pools = dict(pools)
            for leaf in ("k_win", "v_win"):
                pools[leaf] = pools[leaf].at[:, page].set(jnp.nan)
            base[0] += 1
            released[0] += 1
        return pools

    start, logits, chosen = 0, [], []
    while start < prompt_len:
        valid = min(chunk, prompt_len - start)
        reach(start + valid)
        window = np.zeros(chunk, np.int32)
        window[:valid] = tokens[start:start + valid]
        vec, vec_w = (np.zeros(chunk // PAGE, np.int32) for _ in range(2))
        n = min(len(vec), len(pages) - start // PAGE)
        vec[:n] = pages[start // PAGE:start // PAGE + n]
        for i in range(-(-(start + valid) // PAGE) - start // PAGE):
            vec_w[i] = ring[slot, (start // PAGE + i) % grp_bound]
        lg, pools, routes = run_chunk(
            params, jnp.asarray(window), jnp.int32(start), jnp.int32(valid),
            pools, {"full": jnp.asarray(vec), "window": jnp.asarray(vec_w)},
            # copies: the programs run behind the host, which rewrites the
            # ring (the CPU backend reads a numpy buffer in place)
            {"full": jnp.asarray(row), "window": jnp.asarray(ring[slot].copy())},
            jnp.int32(slot))
        start += valid
        pools = leave(start, pools)
    logits.append(np.asarray(lg))
    chosen.append([np.sort(np.asarray(r)[valid - 1]) for r in routes])
    tables = np.zeros((SLOTS, cache.max_pages_per_seq), np.int32)
    tables[slot] = row
    for t in range(prompt_len, prompt_len + steps):
        reach(t + 1)
        toks, pos, lens = (np.zeros(SLOTS, np.int32) for _ in range(3))
        toks[slot], pos[slot], lens[slot] = tokens[t], t, t + 1
        lg, pools, counts, routes = run_decode(
            params, jnp.asarray(toks), jnp.asarray(pos), pools,
            {"full": jnp.asarray(tables), "window": jnp.asarray(ring.copy())},
            jnp.asarray(lens))
        logits.append(np.asarray(lg)[slot])
        chosen.append([np.sort(np.asarray(r)[slot]) for r in routes])
        pools = leave(t + 1, pools)
    return np.stack(logits), chosen, np.asarray(counts), released[0]


def _err(a, b):
    return float(np.max(np.abs(a - b)) / np.std(b))


@pytest.fixture(scope="module")
def truth(reference, params, tokens):
    """The reference's one full forward pass: logits and chosen experts at
    positions ``PROMPT - 1 .. PROMPT + STEPS - 1``."""
    pos = jnp.arange(PROMPT - 1, PROMPT + STEPS, dtype=jnp.int32)
    logits, chosen, _ = jax.jit(lambda p, s, q: reference.forward(
        p, CFG, s, q, block=16))(params, jnp.asarray(tokens), pos)
    return np.asarray(logits), [np.asarray(c) for c in chosen]


# 1. system = reference, in logits and in routed sets -------------------------

@pytest.mark.parametrize("chunk", [8, 16, 64], ids=["page", "chunk", "bucket"])
def test_chunked_prefill_then_decode_equals_the_reference(params, tokens, truth,
                                                          chunk):
    """A window of 21 over pages of 8 and a sequence of 72: the window's
    first key falls inside a page at nearly every position, and five or six
    pages go back (poisoned) on the way, whatever the chunking."""
    logits, chosen, counts, released = _through_the_cache(
        params, tokens, PROMPT, STEPS, chunk)
    want, want_chosen = truth
    assert np.isfinite(logits).all()
    assert _err(logits, want) <= LOGIT_TOL
    assert released >= (PROMPT + STEPS - CFG["sliding_window"]) // PAGE
    for i, sets in enumerate(chosen):
        for layer, got in enumerate(sets):
            assert list(got) == list(np.flatnonzero(want_chosen[layer][i]))
    # the step's counters: one live slot, k pairs a layer; positions read
    n = PROMPT + STEPS
    assert list(counts) == [
        4 * CFG["num_experts_per_tok"], 4 * CFG["num_experts_per_tok"], 4,
        n * 1, min(n, CFG["sliding_window"]) * 3]


@pytest.mark.parametrize("variant", ["plain_rotary", "no_attention_factor",
                                     "window_20", "window_22"])
def test_a_wrong_mechanism_fails_the_model_test(params, tokens, truth,
                                                monkeypatch, variant):
    """A full layer that used the sliding layers' plain rotary, or YaRN
    without the ``attention_factor``, or a window one short or one long, is
    another model: the logits leave the reference's by far more than float32
    reordering."""
    if variant.startswith("window"):
        # the cache keeps what this model reads; the reference reads 21
        monkeypatch.setitem(CFG, "sliding_window", int(variant[-2:]))
    else:
        real = M.rope_inverse_frequencies

        def wrong(rope, head_dim):
            inv, factor = real(rope, head_dim)
            if rope.get("rope_type") != "yarn":
                return inv, factor
            if variant == "no_attention_factor":
                return inv, 1.0
            return real(CFG["rope_parameters"]["sliding_attention"], head_dim)

        monkeypatch.setattr(M, "rope_inverse_frequencies", wrong)
    cfg = dict(CFG)
    fns = (jax.jit(functools.partial(M.prefill_chunk, cfg=cfg,
                                     with_routing=True)),
           jax.jit(functools.partial(M.decode_step, cfg=cfg,
                                     with_routing=True)))
    logits = _through_the_cache(params, tokens, PROMPT, STEPS, 16,
                                steps_fn=fns)[0]
    assert np.isfinite(logits).all()
    assert _err(logits, truth[0]) > 100 * LOGIT_TOL


def test_chunked_prefill_serves_the_one_bucket_prefills_tokens(params, tokens):
    a = _through_the_cache(params, tokens, PROMPT, 4, 8)[0]
    b = _through_the_cache(params, tokens, PROMPT, 4, 64)[0]
    assert _err(a, b) <= LOGIT_TOL
    assert (a.argmax(-1) == b.argmax(-1)).all()


# 2. the scheduler ------------------------------------------------------------

def _scheduler(model, **over):
    kw = dict(num_slots=SLOTS, page_size=PAGE, max_seq_len=MAX_LEN,
              num_pages={"full": 37, "window": 16},
              prefill_buckets=(8, 16, 96), prefill_chunk_tokens=16,
              max_new_tokens=STEPS, kv_dtype="float32")
    kw.update(over)
    return serving.DecodeScheduler(model, serving.DecodeConfig(**kw))


def test_the_scheduler_serves_the_references_tokens(
        reference, params, decode_model, tokens):
    """Four requests over three slots and a window pool of fifteen pages (two
    bounds and a half: the third seat waits for the window group), contexts
    from under the window to four times it: every served token is the
    reference's argmax given the tokens before it."""
    before = {c: obs.counter("serving.decode." + c).value
              for c in M.STEP_COUNTERS}
    sched = _scheduler(decode_model)
    prompts = [tokens[:n] for n in (77, 5, 40, 61)]
    futs = [sched.submit(p, max_new_tokens=STEPS) for p in prompts]
    outs = [f.result(timeout=300) for f in futs]
    sched.stop()
    fwd = jax.jit(lambda p, s, q: reference.forward(p, CFG, s, q, block=16))
    for prompt, out in zip(prompts, outs):
        seq = np.zeros(T_PAD, np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + STEPS] = out
        pos = jnp.arange(len(prompt) - 1, len(prompt) + STEPS - 1)
        logits = np.asarray(fwd(params, jnp.asarray(seq), pos)[0])
        top2 = np.sort(logits, axis=-1)[:, -2:]
        sure = (top2[:, 1] - top2[:, 0]) > 1e-3 * logits.std()
        assert (logits.argmax(-1) == out)[sure].all()
    st = sched.cache_stats()["groups"]
    assert st["window"]["released_pages"] > 10
    assert st["window"]["used_pages"] == st["full"]["used_pages"] == 0
    after = {c: obs.counter("serving.decode." + c).value
             for c in M.STEP_COUNTERS}
    assert after["kv.full_tokens_read"] > before["kv.full_tokens_read"]
    assert (after["kv.window_tokens_read"] - before["kv.window_tokens_read"]
            < 3 * (after["kv.full_tokens_read"]
                   - before["kv.full_tokens_read"]))


def _read_each_step_before_the_next(sched):
    """The loop as it ran before a step stayed in flight: plan one step only
    when nothing is unread, so each is read before the next is built."""
    def plans():
        plan = None if sched._unread else sched._plan_step()
        return [] if plan is None else [plan]

    sched._plan_steps = plans


def test_a_step_in_flight_reads_and_reuses_what_the_in_order_loop_did(
        decode_model, tokens):
    """``_ensure_pages`` runs one step ahead and a window page is released
    while the step behind the commit is in flight: the served tokens, what
    both kinds of layer read (``window_read_share_pct``'s two counters), the
    pages released and the pages handed out again are the in-order loop's."""
    names = ("kv.full_tokens_read", "kv.window_tokens_read",
             "steps_overlapped")
    runs = {}
    for loop in ("in flight", "in order"):
        before = {c: obs.counter("serving.decode." + c).value for c in names}
        released0 = obs.counter("serving.cache.window.pages_released").value
        sched = _scheduler(decode_model)
        if loop == "in order":
            _read_each_step_before_the_next(sched)
        grp, handed = sched.cache.groups["window"], []
        real = grp.alloc
        grp.alloc = lambda n=1: handed.extend(real(n) or ()) or handed[-n:]
        prompts = [tokens[:n] for n in (77, 5, 40)]
        futs = [sched.submit(p, max_new_tokens=STEPS) for p in prompts]
        outs = [f.result(timeout=300).tobytes() for f in futs]
        sched.stop()
        st = sched.cache_stats()["groups"]["window"]
        assert st["used_pages"] == 0 and st["rc_errors"] == []
        runs[loop] = dict(
            outs=outs, released=st["released_pages"],
            counted=obs.counter("serving.cache.window.pages_released").value
            - released0,
            handed=len(handed), reused=len(handed) - len(set(handed)),
            **{c: obs.counter("serving.decode." + c).value - before[c]
               for c in names})
    a, b = runs["in flight"], runs["in order"]
    assert a.pop("steps_overlapped") > 0 == b.pop("steps_overlapped")
    assert a == b
    assert a["released"] == a["counted"] > 10 and a["reused"] > 0


@pytest.mark.parametrize("what", ["prefix_cache", "sessions", "role"])
def test_a_window_group_refuses_what_it_cannot_do(params, what):
    kw, cfg = {}, {}
    if what == "prefix_cache":
        cfg = dict(prefix_cache=True)
    elif what == "sessions":
        cfg, kw = dict(prefix_cache=True), dict(
            sessions=serving.SessionStore())
    else:
        kw = dict(role="decode")
    with pytest.raises(ServingError, match="window group has freed"):
        serving.DecodeScheduler(
            M.build_decode_model(params, CFG),
            serving.DecodeConfig(
                num_slots=SLOTS, page_size=PAGE, max_seq_len=MAX_LEN,
                num_pages={"full": 37, "window": 16}, warmup=False, **cfg),
            autostart=False, **kw)


# 3. rotary -------------------------------------------------------------------

def test_yarn_inverse_frequencies_are_the_formulas(reference):
    """The published ``rope_parameters.full_attention`` at ``head_dim`` 128:
    the pair-by-pair blend, its ramp's ends and the ``attention_factor``."""
    rope = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}
    inv, factor = M.rope_inverse_frequencies(rope, 128)
    assert factor == rope["attention_factor"]
    assert factor == pytest.approx(0.1 * math.log(16) + 1, abs=1e-15)
    d, theta, s, ctx = 128, 500000.0, 16.0, 8192.0
    f = [theta ** (-2 * i / d) for i in range(d // 2)]

    def dim(beta):
        return d * math.log(ctx / (2 * math.pi * beta)) / (2 * math.log(theta))

    low, high = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), d // 2 - 1)
    assert (low, high) == (18, 35)
    want = []
    for i in range(d // 2):
        r = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(f[i] / s * r + f[i] * (1 - r))
    np.testing.assert_allclose(inv, np.asarray(want, np.float32), rtol=1e-7)
    # fast pairs are left alone, slow pairs are interpolated by the factor
    assert inv[0] == np.float32(1.0) and inv[low] == np.float32(f[low])
    assert inv[-1] == pytest.approx(f[-1] / 16, rel=1e-6)
    # the reference's own formula agrees, and so does the default kind
    ref_inv, ref_factor = reference.inverse_frequencies(rope, 128)
    np.testing.assert_array_equal(inv, ref_inv)
    assert ref_factor == factor
    plain, one = M.rope_inverse_frequencies(
        {"rope_type": "default", "rope_theta": 500000}, 128)
    np.testing.assert_allclose(plain, np.asarray(f, np.float32), rtol=1e-7)
    assert one == 1.0
    with pytest.raises(ValueError, match="rope_type"):
        M.rope_inverse_frequencies({"rope_type": "llama3",
                                    "rope_theta": 1e4}, 128)


# 4. the layout ---------------------------------------------------------------

def test_the_cache_layout_is_two_groups_by_kind():
    layout = M.cache_layout(CFG)
    assert list(layout["page_groups"]) == ["full", "window"]
    assert layout["page_groups"]["window"]["window"] == 21
    assert layout["page_groups"]["full"]["window"] is None
    pools = layout["page_pools"]
    assert {n: (p["layers"], p["group"]) for n, p in pools.items()} == {
        "k_full": (1, "full"), "v_full": (1, "full"),
        "k_win": (3, "window"), "v_win": (3, "window")}
    assert all(p["width"] == 2 * 16 for p in pools.values())
    with pytest.raises(ValueError, match="sliding layers alone"):
        M.cache_layout(dict(CFG, layer_types=["sliding_attention"] * 4))


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("norm_topk_prob", False),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("mlp_layer_types", ["sparse", "dense", "sparse", "sparse"]),
    ("layer_types", ["full_attention"] * 3)])
def test_a_key_the_model_does_not_write_is_refused(key, value):
    with pytest.raises(ValueError):
        M._dims(dict(CFG, **{key: value}))


def test_weights_are_few_arrays_and_the_step_programs_hold_none(params):
    leaves = jax.tree_util.tree_leaves(params)
    assert len(leaves) == 8 + 2 * CFG["num_hidden_layers"]
    assert params["e_gu"].shape == (4, 8, 48, 48)
    assert params["e_down"].shape == (4, 8, 24, 48)
    assert params["router_w"].dtype == jnp.float32
    cache = _cache(9)
    tables = {"full": jnp.zeros((SLOTS, cache.max_pages_per_seq), jnp.int32),
              "window": jnp.zeros((SLOTS, 6), jnp.int32)}
    jaxpr = jax.make_jaxpr(functools.partial(M.decode_step, cfg=CFG))(
        params, jnp.zeros((SLOTS,), jnp.int32), jnp.zeros((SLOTS,), jnp.int32),
        cache.pools, tables, jnp.zeros((SLOTS,), jnp.int32))
    # the only constants are the two kinds' inverse frequencies
    assert all(np.asarray(c).size <= CFG["head_dim"] // 2
               for c in jaxpr.consts)
