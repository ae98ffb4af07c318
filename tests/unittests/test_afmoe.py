"""The AFMoE family (``models/afmoe.py``) through the serving path against its
plain reference (``chipbench/configs/trinity_large_preview.reference.py``) on
the CPU at toy sizes with seeded float32 weights: logits of chunked prefill and
of decode through a cache in two page GROUPS (a context past the window, whose
ring wrapped and released pages that are poisoned as they go, and one under
it), each named wrong reading of the block, the router's bias, the eight
holders' shares against the uncut layer, and a scheduler in which a request
retires and another takes its slot and its released window pages.

At these sizes the model runs in float32 end to end, so the system differs
from the reference only by the ORDER of float32 operations: ``LOGIT_TOL`` = 1e-4
of the logits' spread holds that (read here: 3.8e-6 to 4.9e-6), and every wrong
reading below, a router in bfloat16 among them (the one LOWER PRECISION a toy in
float32 can still show), reads a hundred times the limit or more (the least: the
bias weighing, 0.038; a bfloat16 router 0.075; every other 1.6 to 4.0).
"""
import collections
import functools
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models import afmoe as A
from paddle_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REFERENCE = os.path.join(ROOT,
                         "chipbench/configs/trinity_large_preview.reference.py")

# the cut's own pattern at toy widths: a last leading dense layer (sliding),
# then a whole period of expert layers; a holder of experts 4 .. 7 of 16
CFG = dict(
    hidden_act="silu", hidden_size=48, intermediate_size=64,
    moe_intermediate_size=24, vocab_size=100, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, num_hidden_layers=5,
    layer_types=["sliding_attention", "sliding_attention", "full_attention",
                 "sliding_attention", "sliding_attention"],
    num_dense_layers=1, num_experts=4, router_experts=16,
    experts_held=[4, 8], num_experts_per_tok=2, num_shared_experts=1,
    score_func="sigmoid", route_norm=True, route_scale=2.448, n_group=1,
    topk_group=1, rms_norm_eps=1e-5, sliding_window=21, rope_theta=10000,
    rope_scaling=None, mup_enabled=True, tie_word_embeddings=False)
PAGE, SLOTS, MAX_LEN, T_PAD = 8, 3, 96, 96
PROMPT, STEPS = 60, 12
LOGIT_TOL = 1e-4        # max |a - b| / std(b): float32 reordering only


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("afmoe_reference", REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return A.params(CFG, 0, dtype="float32")


@pytest.fixture(scope="module")
def decode_model(params):
    """One model object for the module: every scheduler over it dispatches
    the model's own step programs, so a shape is traced once."""
    return A.build_decode_model(params, CFG)


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(1).randint(1, 100, size=T_PAD).astype(np.int32)


def _steps(cfg=CFG):
    return (jax.jit(functools.partial(A.prefill_chunk, cfg=cfg,
                                      with_routing=True)),
            jax.jit(functools.partial(A.decode_step, cfg=cfg,
                                      with_routing=True)))


_STEPS = _steps()


def _cache(window_pages):
    layout = A.cache_layout(CFG)
    sizes = {"full": SLOTS * (MAX_LEN // PAGE) + 1, "window": window_pages}
    return serving.PagedKVCache(
        0, None, PAGE, 0, 0, MAX_LEN, num_slots=SLOTS,
        page_pools=layout["page_pools"],
        page_groups={g: dict(spec, num_pages=sizes[g])
                     for g, spec in layout["page_groups"].items()})


def _through_the_cache(params, tokens, prompt_len, steps, chunk, slot=1,
                       steps_fn=_STEPS):
    """Prefill ``tokens[:prompt_len]`` in chunks of ``chunk`` into ``slot``,
    then decode ``steps`` tokens (teacher forced), the window group's pages
    handed out and given back as the scheduler does it — from a pool of just
    the slot's bound, each page POISONED with NaN as it is given back.
    Returns the logits at positions ``prompt_len - 1 ..``, each expert
    layer's chosen experts there, the last chunk's and the last step's
    counters and the pages released."""
    run_chunk, run_decode = steps_fn
    bound = -(-(CFG["sliding_window"] + chunk) // PAGE) + 1
    cache = _cache(bound + 1)
    grp = cache.groups["window"]
    assert grp.slot_bound(MAX_LEN, chunk) == bound
    pages = cache.alloc(cache.pages_for(prompt_len + steps))
    row = cache.table_row(pages)
    ring = np.zeros((SLOTS, bound), np.int32)
    held, base, released = collections.deque(), [0], [0]
    pools = cache.pools

    def reach(upto):
        for p in range(base[0] + len(held), -(-upto // PAGE)):
            held.append(grp.alloc(1)[0])        # never None: the bound holds
            ring[slot, p % bound] = held[-1]

    def leave(next_pos, pools):
        # what the CACHE keeps is the configuration's window, whatever the
        # model under test reads
        while base[0] < grp.first_live_page(next_pos) and held:
            page = held.popleft()
            ring[slot, base[0] % bound] = 0
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
        rows = np.zeros(chunk, np.int32)
        rows[:valid] = tokens[start:start + valid]
        vec, vec_w = (np.zeros(chunk // PAGE, np.int32) for _ in range(2))
        n = min(len(vec), len(pages) - start // PAGE)
        vec[:n] = pages[start // PAGE:start // PAGE + n]
        for i in range(-(-(start + valid) // PAGE) - start // PAGE):
            vec_w[i] = ring[slot, (start // PAGE + i) % bound]
        lg, pools, chunk_counts, routes = run_chunk(
            params, jnp.asarray(rows), jnp.int32(start), jnp.int32(valid),
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
    counts = None
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
    return (np.stack(logits), chosen, np.asarray(chunk_counts),
            None if counts is None else np.asarray(counts), released[0])


def _err(a, b):
    return float(np.max(np.abs(a - b)) / np.std(b))


def _truth(reference, params, tokens, prompt, steps):
    pos = jnp.arange(prompt - 1, prompt + steps, dtype=jnp.int32)
    logits, chosen, _ = jax.jit(lambda p, s, q: reference.forward(
        p, CFG, s, q, block=16))(params, jnp.asarray(tokens), pos)
    return np.asarray(logits), [np.asarray(c) for c in chosen]


@pytest.fixture(scope="module")
def truth(reference, params, tokens):
    """The reference's one full forward pass: logits and chosen experts at
    positions ``PROMPT - 1 .. PROMPT + STEPS - 1``."""
    return _truth(reference, params, tokens, PROMPT, STEPS)


# 1. system = reference, in logits and in routed sets -------------------------

@pytest.mark.parametrize("chunk", [8, 16, 64], ids=["page", "chunk", "bucket"])
def test_chunked_prefill_then_decode_equals_the_reference(params, tokens, truth,
                                                          chunk):
    """A window of 21 over pages of 8 and a sequence of 72: the ring has
    wrapped, the window's first key falls inside a page at nearly every
    position, and five or six pages go back (poisoned) on the way, whatever
    the chunking."""
    logits, chosen, chunk_counts, counts, released = _through_the_cache(
        params, tokens, PROMPT, STEPS, chunk)
    want, want_chosen = truth
    assert np.isfinite(logits).all()
    assert _err(logits, want) <= LOGIT_TOL
    assert released >= (PROMPT + STEPS - CFG["sliding_window"]) // PAGE
    for i, sets in enumerate(chosen):
        for layer, got in enumerate(sets):
            assert list(got) == list(np.flatnonzero(want_chosen[layer][i]))
    # the step's counters: one live slot, k pairs an expert layer over all 16;
    # the held experts' pairs are a part of them; positions read
    n, k = PROMPT + STEPS, CFG["num_experts_per_tok"]
    held = sum(int(((want_chosen[layer][-1]).nonzero()[0] // 4 == 1).sum())
               for layer in range(4))
    assert list(counts[:2]) == [4 * k, held]
    assert counts[2] <= counts[1] and bool(counts[2]) == bool(counts[1])
    assert list(counts[3:]) == [n * 1, min(n, CFG["sliding_window"]) * 4]
    # the last chunk's: its real rows share the keys they read, once a layer
    valid = PROMPT - (PROMPT - 1) // chunk * chunk
    assert chunk_counts[0] == valid * 4 * k and chunk_counts[1] <= valid * 4 * k
    assert list(chunk_counts[3:]) == [
        PROMPT, min(PROMPT, valid + CFG["sliding_window"] - 1) * 4]


def test_a_context_under_the_window_equals_the_reference(reference, params,
                                                         tokens):
    """12 + 5 positions against a window of 21: nothing is released, the ring
    never wraps, and both kinds of layer read every key."""
    logits, _, _, counts, released = _through_the_cache(
        params, tokens, 12, 5, 8)
    want, _ = _truth(reference, params, tokens, 12, 5)
    assert _err(logits, want) <= LOGIT_TOL
    assert released == 0 and list(counts[3:]) == [17, 17 * 4]


def _flipped(which):
    """``_attn_in`` seeing other kinds of layer than the configuration's (it
    reads them for the rotation alone)."""
    real = A._attn_in

    def wrong(d, p, lp, layer, x, positions):
        kinds = [which.get(kind, kind) for kind in d["kinds"]]
        return real(dict(d, kinds=kinds), p, lp, layer, x, positions)

    return wrong


def _no_gate(real=A._attn_in):
    def wrong(*args):
        q, k, v, gate = real(*args)
        return q, k, v, jnp.ones_like(gate)

    return wrong


def _no_qk_norm(real=A._rms):
    def wrong(x, weight, eps):
        if x.ndim == 3:                       # [T, heads, head_dim]: q or k
            return x.astype(jnp.float32)
        return real(x, weight, eps)

    return wrong


def _no_post_attn_norm(d, p, lp, layer, x, o, gate):
    y = A._mm(o.reshape(x.shape[0], -1) * gate, lp["wo"])
    return (x.astype(jnp.float32) + y).astype(x.dtype)


def _route(weigh_bias=False, bf16=False):
    """``moe.route_topk`` with the bias in the weights, or with logits from
    bfloat16 operands kept in bfloat16."""
    def wrong(x, router_w, router_bias, *, top_k, scale=1.0,
              scoring="sigmoid"):
        if bf16:
            logits = jax.lax.reduce_precision(jnp.dot(
                x.astype(jnp.bfloat16), router_w.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32), 8, 7)
        else:
            logits = jnp.dot(x.astype(jnp.float32), router_w,
                             precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)
        biased = scores + router_bias
        _, experts = jax.lax.top_k(biased, top_k)
        w = jnp.take_along_axis(biased if weigh_bias else scores, experts,
                                axis=-1)
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), w * scale

    return wrong


WRONG = {
    "window_20": dict(cfg=dict(sliding_window=20)),
    "window_22": dict(cfg=dict(sliding_window=22)),
    "rotary_on_a_full_layer": dict(patch=(A, "_attn_in", _flipped(
        {"full_attention": "sliding_attention"}))),
    "no_rotary_on_a_sliding_layer": dict(patch=(A, "_attn_in", _flipped(
        {"sliding_attention": "full_attention"}))),
    "no_gate": dict(patch=(A, "_attn_in", _no_gate())),
    "no_qk_norm": dict(patch=(A, "_rms", _no_qk_norm())),
    "post_attn_norm_dropped": dict(patch=(A, "_attn_out", _no_post_attn_norm)),
    "no_sqrt_d_on_the_embedding": dict(patch=(
        A, "_embed", lambda d, p, tokens: p["embed"][tokens])),
    "bias_weighs": dict(patch=(moe, "route_topk", _route(weigh_bias=True))),
    "bf16_router": dict(patch=(moe, "route_topk", _route(bf16=True))),
}


@pytest.mark.parametrize("variant", sorted(WRONG))
def test_a_wrong_reading_of_the_block_fails_the_model_test(
        params, tokens, truth, monkeypatch, variant):
    """Each is another model (or, the last, a lower precision): the logits
    leave the reference's by far more than float32 reordering."""
    how = WRONG[variant]
    if "patch" in how:
        monkeypatch.setattr(*how["patch"])
    logits = _through_the_cache(
        params, tokens, PROMPT, STEPS, 16,
        steps_fn=_steps(dict(CFG, **how.get("cfg", {}))))[0]
    assert np.isfinite(logits).all()
    assert _err(logits, truth[0]) > 100 * LOGIT_TOL


# 2. the router and the shares ------------------------------------------------

def test_the_bias_selects_and_does_not_weigh(reference, params):
    """Over 512 rows the seeded ``expert_bias`` changes the chosen set of a
    share of the rows (so a router that ignored it would be seen), the served
    choice is the reference's, and the weights are the chosen experts' SCORES
    renormalised times ``route_scale``: the bias is in none of them."""
    u = jax.random.normal(jax.random.PRNGKey(3), (512, 48), jnp.float32)
    w, b = params["router_w"][0], params["router_b"][0]
    k, scale = CFG["num_experts_per_tok"], CFG["route_scale"]
    experts, weights = moe.route_topk(u, w, b, top_k=k, scale=scale)
    plain, _ = moe.route_topk(u, w, None, top_k=k, scale=scale)
    moved = (np.sort(experts, -1) != np.sort(plain, -1)).any(-1).mean()
    assert 0.02 < moved < 0.5
    chosen, want = reference.route(u, w, b, k, scale)
    got = np.zeros(chosen.shape, np.float32)
    np.put_along_axis(got, np.asarray(experts), np.asarray(weights), axis=1)
    assert ((got > 0) == np.asarray(chosen)).all()
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-7)
    s = np.asarray(jax.nn.sigmoid(u @ w))
    picked = np.take_along_axis(s, np.asarray(experts), axis=1)
    np.testing.assert_allclose(
        np.asarray(weights), picked / picked.sum(-1, keepdims=True) * scale,
        rtol=1e-5)


def test_the_eight_shares_sum_to_the_uncut_layer(reference):
    """One expert layer's rows through ``moe_topk`` once a holder (experts ``2
    i, 2 i + 1`` of 16), the shared expert passed by the first holder alone:
    the eight parts add up to the reference's WHOLE layer (every expert held,
    the shared expert once)."""
    whole_cfg = dict(CFG, num_experts=16, experts_held=[0, 16])
    whole = A.params(whole_cfg, 5, dtype="float32")
    lp = whole["layers"][2]
    u = jax.random.normal(jax.random.PRNGKey(4), (40, 48), jnp.float32)
    router = {"w": whole["router_w"][1], "bias": whole["router_b"][1]}
    total, pairs = 0.0, 0
    for i in range(8):
        share, cut = A.take_share(whole, whole_cfg, (2 * i, 2 * i + 2))
        assert cut["num_experts"] == 2 and share["e_gu"].shape[1] == 2
        y, counts, _ = moe.moe_topk(
            u, router, {"w_gu": share["e_gu"], "w_down": share["e_down"]},
            {"w_gu": lp["s_gu"], "w_down": lp["s_down"]} if i == 0 else None,
            top_k=2, experts_held=(2 * i, 2 * i + 2), scale=CFG["route_scale"],
            layer=1)
        total = total + y
        pairs += int(counts[0])
    assert pairs == 40 * 2
    want, _ = reference.moe_layer(
        u, router["w"], router["bias"], whole["e_gu"][1], whole["e_down"][1],
        (lp["s_gu"], lp["s_down"]), 2, CFG["route_scale"])
    assert float(np.max(np.abs(total - want)) / np.max(np.abs(want))) < 1e-5


def test_take_share_cuts_the_vocabulary_too():
    whole_cfg = dict(CFG, num_experts=16, experts_held=[0, 16])
    whole = A.params(whole_cfg, 5, dtype="float32")
    share, cut = A.take_share(whole, whole_cfg, (4, 8), vocab=(25, 50))
    assert cut["vocab_size"] == 25 and cut["experts_held"] == [4, 8]
    assert share["embed"].shape == (25, 48) and share["head"].shape == (48, 25)
    assert share["router_w"].shape == whole["router_w"].shape
    A.build_decode_model(share, cut)


# 3. the scheduler ------------------------------------------------------------

def _scheduler(model, **over):
    kw = dict(num_slots=2, page_size=PAGE, max_seq_len=MAX_LEN,
              num_pages={"full": 25, "window": 13},
              prefill_buckets=(8, 16, 96), prefill_chunk_tokens=16,
              max_new_tokens=STEPS, kv_dtype="float32")
    kw.update(over)
    return serving.DecodeScheduler(model, serving.DecodeConfig(**kw))


def _cells(names, labels):
    return {n: obs.counter(n, labels).value for n in names}


def test_one_retires_and_a_third_takes_its_slot_and_its_window_pages(
        reference, params, decode_model, tokens):
    """Two slots and a window group of twelve pages (two bounds): the third
    request waits for a seat, takes the slot of the one that retired and
    window pages that a LIVE sequence released, and every served token of all
    three is the reference's argmax given the tokens before it.  The chunk
    programs count apart from the decode steps; each group counts the pages
    it handed out and took back."""
    step_names = ["serving.decode." + c for c in A.STEP_COUNTERS]
    before = {c: _cells(step_names, {"chunk": c}) for c in (0, 1)}
    groups = ("full", "window")
    taken0 = {g: obs.counter("serving.cache.pages_taken", {"group": g}).value
              for g in groups}
    released0 = obs.counter("serving.cache.pages_released",
                            {"group": "window"}).value
    sched = _scheduler(decode_model)
    grp, handed = sched.cache.groups["window"], []
    real = grp.alloc
    grp.alloc = lambda n=1: handed.extend(real(n) or ()) or handed[-n:]
    prompts = [tokens[:n] for n in (77, 30, 61)]
    news = (STEPS, 4, STEPS)
    futs = [sched.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    outs = [f.result(timeout=300) for f in futs]
    sched.stop()
    fwd = jax.jit(lambda p, s, q: reference.forward(p, CFG, s, q, block=16))
    for prompt, out in zip(prompts, outs):
        seq = np.zeros(T_PAD, np.int32)
        seq[:len(prompt)] = prompt
        seq[len(prompt):len(prompt) + len(out)] = out
        pos = jnp.arange(len(prompt) - 1, len(prompt) + len(out) - 1)
        logits = np.asarray(fwd(params, jnp.asarray(seq), pos)[0])
        top2 = np.sort(logits, axis=-1)[:, -2:]
        sure = (top2[:, 1] - top2[:, 0]) > 1e-3 * logits.std()
        assert (logits.argmax(-1) == out)[sure].all()
    st = sched.cache_stats()["groups"]
    assert st["window"]["used_pages"] == st["full"]["used_pages"] == 0
    assert st["window"]["reserved_pages"] == 0
    assert st["window"]["rc_errors"] == st["full"]["rc_errors"] == []
    # released and taken, symmetrically, in the group's stats and its counters
    assert st["window"]["released_pages"] > 8
    assert st["window"]["taken_pages"] == len(handed) > 12
    assert len(handed) > len(set(handed))           # pages went round
    assert st["full"]["released_pages"] == 0 < st["full"]["taken_pages"]
    assert (obs.counter("serving.cache.pages_released", {"group": "window"})
            .value - released0 == st["window"]["released_pages"])
    for g in groups:
        assert (obs.counter("serving.cache.pages_taken", {"group": g}).value
                - taken0[g] == st[g]["taken_pages"])
    # the third request waited for a SLOT, not for pages
    after = {c: _cells(step_names, {"chunk": c}) for c in (0, 1)}
    moved = {c: {n: after[c][n] - before[c][n] for n in step_names}
             for c in (0, 1)}
    k, rows = CFG["num_experts_per_tok"], sum(len(p) for p in prompts)
    assert moved[1]["serving.decode.moe.pairs"] == rows * 4 * k
    assert 0 < moved[1]["serving.decode.moe.pairs_held"] < rows * 4 * k
    assert moved[0]["serving.decode.moe.pairs"] == (sum(news) - 3) * 4 * k
    # chunks of 16 over prompts of 77, 30 and 61: every full-layer key once a
    # chunk that can see it; a sliding layer's no further back than 21 + 15
    ends = [min(at + 16, n) for n in (77, 30, 61) for at in range(0, n, 16)]
    assert moved[1]["serving.decode.kv.full_tokens_read"] == sum(ends)
    assert (moved[1]["serving.decode.kv.window_tokens_read"]
            < 4 * moved[1]["serving.decode.kv.full_tokens_read"])


def test_an_admission_that_finds_the_window_group_short_is_counted(
        decode_model, tokens):
    """A window group of one bound and a half: the second request has a free
    slot and waits for the WINDOW group's reservation; it is counted once,
    against that group, however many iterations it stays parked."""
    cells = {g: obs.counter("serving.decode.admit_waits_for_pages",
                            {"group": g}) for g in ("full", "window")}
    before = {g: c.value for g, c in cells.items()}
    sched = _scheduler(decode_model, num_pages={"full": 25, "window": 9})
    futs = [sched.submit(tokens[:n], max_new_tokens=6) for n in (40, 33)]
    outs = [f.result(timeout=300) for f in futs]
    sched.stop()
    assert [len(o) for o in outs] == [6, 6]
    assert cells["window"].value - before["window"] == 1
    assert cells["full"].value == before["full"]


def test_a_window_group_refuses_the_prefix_cache(decode_model):
    with pytest.raises(serving.errors.ServingError):
        _scheduler(decode_model, prefix_cache=True)
