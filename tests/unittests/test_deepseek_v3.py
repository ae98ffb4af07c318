"""The DeepSeek-V3 family (``models/deepseek_v3.py``) through the serving path
against its plain reference (``chipbench/configs/kanana2_30b_a3b.reference.py``)
on the CPU at toy sizes with seeded float32 weights: logits of chunked prefill
and of decode through the LATENT cache, the routed sets, the served tokens of
the scheduler, absorbed against expanded attention, ``moe_topk`` against the
reference's masked loop (ties, the selection bias, the scaling factor), the
shares of disjoint ``experts_held`` ranges, a cache with no K / V leaf under
the allocator and the prefix index, the step counters, and both Pallas
kernels in interpret mode.

Every tolerance says why it is what it is.  At these sizes the model runs in
float32 end to end, so the system differs from the reference only by the
ORDER of float32 operations (absorbed against expanded products, one softmax
against another reduction order, sorted pairs against a masked loop): 1e-4 of
the logits' spread holds that.
"""
import functools
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models import deepseek_v3 as M
from paddle_tpu.parallel import flash_attention as FA
from paddle_tpu.parallel import moe

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REFERENCE = os.path.join(ROOT, "chipbench/configs/kanana2_30b_a3b.reference.py")

# toy sizes under the family's own key names (a latent + rotary row of 40
# values pads to one lane tile in the cache)
CFG = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    vocab_size=100, num_attention_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, q_lora_rank=None,
    n_routed_experts=16, num_experts_per_tok=3, n_shared_experts=2,
    first_k_dense_replace=1, num_hidden_layers=3, n_group=1, topk_group=1,
    moe_layer_freq=1, norm_topk_prob=True, scoring_func="sigmoid",
    routed_scaling_factor=2.448, rms_norm_eps=1e-6, rope_theta=1000000,
    rope_scaling=None)
PAGE, SLOTS, MAX_LEN, T_PAD = 8, 3, 96, 96
PROMPT, STEPS = 60, 12
LOGIT_TOL = 1e-4        # max |a - b| / std(b): float32 reordering only


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("kanana_reference", REFERENCE)
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


def _cache(dtype="float32"):
    return serving.PagedKVCache(
        0, SLOTS * (MAX_LEN // PAGE) + 1, PAGE, 0, 0, MAX_LEN, dtype=dtype,
        num_slots=SLOTS, **M.cache_layout(CFG))


def _through_the_cache(params, tokens, prompt_len, steps, chunk, slot=1):
    """Prefill ``tokens[:prompt_len]`` in chunks of ``chunk`` into ``slot``'s
    pages, then decode ``steps`` tokens (teacher forced) through the cache.
    Returns the logits at positions ``prompt_len - 1 ..``, each expert
    layer's chosen experts there, and the last step's counters."""
    cache = _cache()
    pages = cache.alloc(cache.pages_for(prompt_len + steps))
    row = cache.table_row(pages)
    pools = cache.pools
    start, logits, chosen = 0, [], []
    while start < prompt_len:
        valid = min(chunk, prompt_len - start)
        window = np.zeros(chunk, np.int32)
        window[:valid] = tokens[start:start + valid]
        vec = np.zeros(chunk // PAGE, np.int32)
        n = min(len(vec), len(pages) - start // PAGE)
        vec[:n] = pages[start // PAGE:start // PAGE + n]
        lg, pools, routes = _CHUNK(
            params, jnp.asarray(window), jnp.int32(start), jnp.int32(valid),
            pools, jnp.asarray(vec), jnp.asarray(row), jnp.int32(slot))
        start += valid
    logits.append(np.asarray(lg))
    chosen.append([np.sort(np.asarray(r)[valid - 1]) for r in routes])
    tables = np.zeros((SLOTS, cache.max_pages_per_seq), np.int32)
    tables[slot] = row
    for t in range(prompt_len, prompt_len + steps):
        toks, pos, lens = (np.zeros(SLOTS, np.int32) for _ in range(3))
        toks[slot], pos[slot], lens[slot] = tokens[t], t, t + 1
        lg, pools, counts, routes = _DECODE(
            params, jnp.asarray(toks), jnp.asarray(pos), pools,
            jnp.asarray(tables), jnp.asarray(lens))
        logits.append(np.asarray(lg)[slot])
        chosen.append([np.sort(np.asarray(r)[slot]) for r in routes])
    return np.stack(logits), chosen, np.asarray(counts)


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
    logits, chosen, counts = _through_the_cache(params, tokens, PROMPT, STEPS,
                                                chunk)
    assert _err(logits, truth[0]) < LOGIT_TOL
    n_moe = CFG["num_hidden_layers"] - CFG["first_k_dense_replace"]
    for step, sets in enumerate(chosen):
        assert len(sets) == n_moe
        for layer, got in enumerate(sets):
            np.testing.assert_array_equal(
                got, np.flatnonzero(truth[1][layer][step]))
    # one live slot: k pairs an expert layer, each on its own expert; the
    # slot's visible rows in every layer
    k = CFG["num_experts_per_tok"]
    np.testing.assert_array_equal(
        counts, [n_moe * k, n_moe * k, n_moe,
                 (PROMPT + STEPS) * CFG["num_hidden_layers"]])


def test_chunked_prefill_serves_the_one_bucket_prefills_tokens_bitwise(
        decode_model, tokens):
    prompts = [tokens[:70], tokens[5:25], tokens[30:79], tokens[2:50]]
    outs = {}
    for name, kw in (("bucket", {"prefill_chunk_tokens": MAX_LEN}),
                     ("chunked", {"prefill_chunk_tokens": 8})):
        sched = _scheduler(decode_model, **kw)
        futures = [sched.submit(p, max_new_tokens=8) for p in prompts]
        outs[name] = [f.result(timeout=300) for f in futures]
        assert sched.stats()["kv_pages_used"] == 0
        sched.stop()
    for a, b in zip(outs["bucket"], outs["chunked"]):
        assert a.tobytes() == b.tobytes()


def test_a_bfloat16_latent_would_fail(params, tokens, truth, monkeypatch):
    """The control of ``LOGIT_TOL``: the same path over latent rows rounded
    to bfloat16 (everything else float32) is orders of magnitude outside."""
    real = M._latent_rows

    def rounded(*args):
        q, row = real(*args)
        return q, row.astype(jnp.bfloat16).astype(jnp.float32)

    monkeypatch.setattr(M, "_latent_rows", rounded)
    cache = _cache()
    pages = cache.alloc(cache.pages_for(64))
    window = np.zeros(64, np.int32)
    window[:PROMPT] = tokens[:PROMPT]
    lg, _ = jax.jit(functools.partial(M.prefill_chunk, cfg=CFG))(
        params, jnp.asarray(window), jnp.int32(0), jnp.int32(PROMPT),
        cache.pools, jnp.asarray(np.asarray(pages, np.int32)),
        jnp.asarray(cache.table_row(pages)), jnp.int32(0))
    assert _err(np.asarray(lg), truth[0][0]) > 20 * LOGIT_TOL


def test_the_cache_keeps_the_references_latent_rows(reference, params, tokens):
    """Every layer's cached row is the reference's ``[c | k_pe]`` of its
    token (the rotary pairs de-interleaved: evens, then odds), with zeros on
    the lanes past it."""
    cache = _cache()
    pages = cache.alloc(cache.pages_for(64))
    window = np.zeros(64, np.int32)
    window[:PROMPT] = tokens[:PROMPT]
    _, pools, _ = _CHUNK(
        params, jnp.asarray(window), jnp.int32(0), jnp.int32(PROMPT),
        cache.pools, jnp.asarray(np.asarray(pages, np.int32)),
        jnp.asarray(cache.table_row(pages)), jnp.int32(0))
    L, R = CFG["num_hidden_layers"], CFG["kv_lora_rank"]
    width = R + CFG["qk_rope_head_dim"]
    got = np.asarray(pools["latent"][:, jnp.asarray(pages)]).reshape(
        L, 64, -1)[:, :PROMPT]
    rows = jax.jit(lambda p, s, q: reference.forward(p, CFG, s, q,
                                                     block=16)[2])(
        params, jnp.asarray(tokens), jnp.arange(PROMPT, dtype=jnp.int32))
    assert len(rows) == L
    for layer, want in enumerate(np.asarray(r) for r in rows):
        want = np.concatenate([want[:, :R], want[:, R::2], want[:, R + 1::2]],
                              axis=1)
        np.testing.assert_allclose(got[layer, :, :width], want, rtol=2e-5,
                                   atol=2e-5)
    assert not got[:, :, width:].any()


# 2. through the scheduler ----------------------------------------------------

def _scheduler(model, **over):
    cfg = dict(num_slots=SLOTS, page_size=PAGE, max_seq_len=MAX_LEN,
               prefill_chunk_tokens=16, prefill_buckets=(8, 16, MAX_LEN),
               max_new_tokens=8)
    cfg.update(over)
    return serving.DecodeScheduler(model, serving.DecodeConfig(**cfg))


def test_the_scheduler_serves_the_references_tokens(
        reference, params, decode_model, tokens):
    """Prefill-then-decode through ``DecodeScheduler``: every served token is
    the top of the reference's logits given the tokens served before it (to a
    near tie: float32 reordering), for a batch of prompts at once; and the
    program's counters reach the registry."""
    prompts = [tokens[:70], tokens[5:25], tokens[30:79]]
    before = {c: obs.counter("serving.decode." + c).value
              for c in M.STEP_COUNTERS}
    sched = _scheduler(decode_model)
    futures = [sched.submit(p, max_new_tokens=8) for p in prompts]
    served = [np.asarray(f.result(timeout=300), np.int32) for f in futures]
    assert sched.stats()["kv_pages_used"] == 0
    sched.stop()
    forward = jax.jit(lambda p, s, q: reference.forward(p, CFG, s, q,
                                                        block=16)[0])
    for prompt, out in zip(prompts, served):
        assert len(out) == 8
        seq = np.zeros(T_PAD, np.int32)
        seq[:len(prompt) + 8] = np.concatenate([prompt, out])
        at = np.arange(len(prompt) - 1, len(prompt) + 7, dtype=np.int32)
        logits = np.asarray(forward(params, jnp.asarray(seq), jnp.asarray(at)))
        for lg, tok in zip(logits, out):
            assert (lg.max() - lg[tok]) / lg.std() < LOGIT_TOL
    moved = {c: obs.counter("serving.decode." + c).value - before[c]
             for c in M.STEP_COUNTERS}
    n_moe = CFG["num_hidden_layers"] - CFG["first_k_dense_replace"]
    # 7 decoded tokens a request (the first comes from the prefill), each k
    # pairs an expert layer
    assert moved["moe.pairs"] == 3 * 7 * n_moe * CFG["num_experts_per_tok"]
    assert 0 < moved["moe.experts_touched"] <= moved["moe.pairs"]
    assert moved["moe.max_load"] >= 1
    assert moved["latent.tokens_read"] == CFG["num_hidden_layers"] * sum(
        len(p) + j for p in prompts for j in range(1, 8))


def test_a_reused_slot_and_mixed_batches_serve_what_a_lone_request_gets(
        decode_model, tokens):
    prompts = [tokens[:70], tokens[5:25], tokens[30:79], tokens[2:50]]
    solo = _scheduler(decode_model, max_active=1, num_slots=1)
    want = [solo.generate(p, max_new_tokens=8, timeout=300) for p in prompts]
    solo.stop()
    batch = _scheduler(decode_model)
    futures = [batch.submit(p, max_new_tokens=8) for p in prompts]
    for f, w in zip(futures, want):
        np.testing.assert_array_equal(f.result(timeout=300), w)
    assert batch.stats()["kv_pages_used"] == 0
    batch.stop()


# 3. the latent cache: no K / V leaf ------------------------------------------

def test_a_cache_with_no_kv_leaves_allocates_frees_and_shares_prefixes():
    c = serving.PagedKVCache(0, num_pages=9, page_size=4, num_heads=0,
                             head_dim=0, max_seq_len=32,
                             **M.cache_layout(CFG))
    assert c.page_leaf_names == ("latent",) and set(c.pools) == {"latent"}
    lanes = c.pools["latent"].shape[-1]
    assert c.pools["latent"].shape == (CFG["num_hidden_layers"], 9, 4, lanes)
    assert lanes >= CFG["kv_lora_rank"] + CFG["qk_rope_head_dim"]
    assert c.page_bytes == c.pools["latent"].nbytes
    toks = np.arange(100, 113, dtype=np.int32)      # 13 tokens: 3 full pages
    pages, hashes = c.lookup_prefix(toks)
    assert pages == [] and len(hashes) == 3
    owned = c.alloc(4)
    assert c.used_pages == 4 and 0 not in owned
    for i in range(3):
        assert c.register_prefix(hashes, i, owned[i])
    c.free(owned)
    assert c.used_pages == 0 and c.cached_pages == 3
    again, _ = c.lookup_prefix(toks)
    assert again == owned[:3] and c.used_pages == 3
    c.free(again)
    # the page helpers move every page-indexed leaf, here the one
    got = c.gather_pages(c.pools, jnp.asarray(owned[:2], jnp.int32))
    assert set(got) == {"latent"}
    assert np.asarray(c.pages_finite(c.pools, jnp.asarray(owned, jnp.int32))).all()
    assert len(c.alloc(8)) == 8                     # evicts the parked pages
    with pytest.raises(serving.ServingError, match="page_pools"):
        serving.PagedKVCache(0, 9, 4, 0, 0, 32)


def test_the_prefix_cache_serves_a_latent_model_the_same_tokens(
        decode_model, tokens):
    rng = np.random.RandomState(3)
    prefix = tokens[:40]
    prompts = [np.concatenate([prefix, rng.randint(1, 100, size=n).astype(
        np.int32)]) for n in (9, 17, 5)]
    hit = obs.counter("serving.decode.kv_hit_pages")
    outs = {}
    for name, kw in (("cold", {}), ("warm", {"prefix_cache": True})):
        sched = _scheduler(decode_model, **kw)
        h0 = hit.value
        outs[name] = [sched.generate(p, max_new_tokens=6, timeout=300)
                      for p in prompts]
        if kw:
            assert hit.value - h0 >= 2 * (len(prefix) // PAGE)
        sched.stop()
    for cold, warm in zip(outs["cold"], outs["warm"]):
        np.testing.assert_array_equal(cold, warm)


# 4. absorbed = expanded ------------------------------------------------------

def _latent_case(seed, dtype, n_slots=5, heads=4, rank=32, rope=8, nope=16):
    """Seeded latent rows in a shuffled pool and queries of every head."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    pages, ps = 14, 8
    width = -(-(rank + rope) // 128) * 128
    rows = jnp.concatenate([
        jax.random.normal(ks[0], (pages * ps, rank + rope), jnp.float32),
        jnp.zeros((pages * ps, width - rank - rope), jnp.float32)], axis=1)
    perm = 1 + jax.random.permutation(ks[1], pages).astype(jnp.int32)
    pool = jnp.zeros((2, pages + 1, ps, width), dtype).at[1, perm].set(
        rows.reshape(pages, ps, width).astype(dtype))
    wkvb = jax.random.normal(ks[2], (heads, 2 * nope, rank), jnp.float32) / 6
    q = jax.random.normal(ks[3], (n_slots, heads, nope + rope), jnp.float32)
    return rows, perm, pool, wkvb, q, dict(rank=rank, rope=rope, nope=nope,
                                           width=width, ps=ps)


def _absorb(q, wkvb, c):
    q_lat = jnp.einsum("thd,hdc->thc", q[..., :c["nope"]],
                       wkvb[:, :c["nope"]], precision="highest")
    pad = jnp.zeros(q.shape[:2] + (c["width"] - c["rank"] - c["rope"],))
    return jnp.concatenate([q_lat, q[..., c["nope"]:], pad], axis=-1)


def _heads(o, wkvb, c):
    return jnp.einsum("thc,hdc->thd", o, wkvb[:, c["nope"]:],
                      precision="highest")


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_absorbed_decode_is_the_expanded_attention(reference, impl):
    rows, perm, pool, wkvb, q, c = _latent_case(0, jnp.float32)
    lens = np.asarray([0, 1, 8, 29, 112], np.int32)
    tables = jnp.broadcast_to(perm[None, :], (len(lens), perm.shape[0]))
    got = _heads(FA.paged_mla_decode_attention(
        _absorb(q, wkvb, c), pool, tables, jnp.asarray(lens),
        v_width=c["rank"], sm_scale=1 / np.sqrt(c["nope"] + c["rope"]),
        layer=1, impl=impl), wkvb, c)
    k, v = reference.expand_latent(rows[:, :c["rank"]],
                                   rows[:, c["rank"]:c["rank"] + c["rope"]],
                                   wkvb, c["nope"])
    want = reference.attention(q, k, v, jnp.asarray(np.maximum(lens - 1, 0)))
    # float32 on both sides; the absorbed form sums over the latent first
    np.testing.assert_allclose(np.asarray(got)[1:], np.asarray(want)[1:],
                               rtol=2e-4, atol=2e-5)
    assert not np.asarray(got)[0].any()             # an empty slot: zeros


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("start,valid", [(0, 16), (16, 11), (96, 16)])
def test_absorbed_prefill_is_the_expanded_attention(reference, impl, start,
                                                    valid):
    rows, perm, pool, wkvb, _, c = _latent_case(1, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(9), (16, 4, c["nope"] + c["rope"]))
    got = _heads(FA.paged_mla_prefill_attention(
        _absorb(q, wkvb, c), pool, perm, jnp.int32(start), jnp.int32(valid),
        v_width=c["rank"], sm_scale=1 / np.sqrt(c["nope"] + c["rope"]),
        layer=1, impl=impl), wkvb, c)
    k, v = reference.expand_latent(rows[:, :c["rank"]],
                                   rows[:, c["rank"]:c["rank"] + c["rope"]],
                                   wkvb, c["nope"])
    want = reference.attention(q, k, v, start + jnp.arange(16, dtype=jnp.int32))
    np.testing.assert_allclose(np.asarray(got)[:valid],
                               np.asarray(want)[:valid], rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_latent_kernel_in_interpret_mode_is_the_plain_form(dtype):
    """The Pallas walk (interpreted) against the gather-and-softmax form on
    the same pool and queries, in the pool's dtype: the kernel's products run
    over exact bfloat16 parts, so float32 differs by reordering alone and a
    bfloat16 pool by nothing more (both read the same rounded rows)."""
    _, perm, pool, wkvb, q, c = _latent_case(2, jnp.dtype(dtype))
    qa = _absorb(q, wkvb, c).astype(dtype)
    lens = jnp.asarray([0, 1, 8, 29, 112], jnp.int32)
    tables = jnp.broadcast_to(perm[None, :], (5, perm.shape[0]))
    kw = dict(v_width=c["rank"], sm_scale=0.2, layer=1)
    a = FA.paged_mla_decode_attention(qa, pool, tables, lens,
                                      impl="reference", **kw)
    b = FA.paged_mla_decode_attention(qa, pool, tables, lens, impl="pallas",
                                      interpret=True, **kw)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    qc = jnp.tile(qa, (4, 1, 1))[:16]
    for start, valid in ((0, 16), (16, 11), (88, 16)):
        a = FA.paged_mla_prefill_attention(
            qc, pool, perm, jnp.int32(start), jnp.int32(valid),
            impl="reference", **kw)
        b = FA.paged_mla_prefill_attention(
            qc, pool, perm, jnp.int32(start), jnp.int32(valid), impl="pallas",
            interpret=True, **kw)
        np.testing.assert_allclose(a[:valid], b[:valid], rtol=1e-5, atol=1e-5)


# 5. the expert layer ---------------------------------------------------------

def _expert_case(seed, rows=40, width=32, experts=16, inner=24):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = jax.random.normal(ks[0], (rows, width))
    router = {"w": jax.random.normal(ks[1], (width, experts)) * 0.3,
              "bias": jax.random.normal(ks[2], (experts,)) * 0.1}
    ex = {"w_gu": jax.random.normal(ks[3], (experts, width, 2 * inner)) * 0.2,
          "w_down": jax.random.normal(ks[4], (experts, inner, width)) * 0.2}
    sh = {"w_gu": jax.random.normal(ks[5], (width, 2 * inner)) * 0.2,
          "w_down": jax.random.normal(ks[6], (inner, width)) * 0.2}
    return x, router, ex, sh


def _plain(reference, x, router, ex, sh, k, scale=2.448):
    return reference.moe_layer(x, router["w"], router["bias"], ex["w_gu"],
                               ex["w_down"], (sh["w_gu"], sh["w_down"]), k,
                               scale)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_moe_topk_is_the_references_loop(reference, impl):
    x, router, ex, sh = _expert_case(0)
    want, chosen = _plain(reference, x, router, ex, sh, 3)
    got, counts, ids = moe.moe_topk(
        x, router, ex, sh, top_k=3, experts_held=(0, 16), scale=2.448,
        impl=impl)
    # float32 both sides; the loop sums 16 masked terms, the layer 3 sorted
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    load = np.asarray(chosen).sum(axis=0)
    np.testing.assert_array_equal(counts, [load.sum(), (load > 0).sum(),
                                           load.max()])
    picked, weights = moe.route_topk(x, router["w"], router["bias"], top_k=3,
                                     scale=2.448)
    np.testing.assert_array_equal(np.sort(picked, axis=1),
                                  np.nonzero(np.asarray(chosen))[1].reshape(-1, 3))
    # what the layer returns is the choice it computed with
    np.testing.assert_array_equal(ids, picked)
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 2.448,
                               rtol=1e-6)


def test_the_bias_steers_the_choice_and_stays_out_of_the_weights(reference):
    x, router, ex, sh = _expert_case(1)
    plain, _ = moe.route_topk(x, router["w"], None, top_k=3)
    router["bias"] = jnp.zeros((16,)).at[5].set(10.0)     # 5 always chosen
    picked, weights = moe.route_topk(x, router["w"], router["bias"], top_k=3)
    assert (np.asarray(picked) == 5).any(axis=1).all()
    assert not (np.asarray(plain) == 5).any(axis=1).all()
    scores = jax.nn.sigmoid(jnp.dot(x, router["w"], precision="highest"))
    chosen = jnp.take_along_axis(scores, picked, axis=1)
    np.testing.assert_allclose(
        weights, chosen / chosen.sum(axis=1, keepdims=True), rtol=1e-6)
    want, _ = _plain(reference, x, router, ex, sh, 3)
    got, _, _ = moe.moe_topk(x, router, ex, sh, top_k=3,
                             experts_held=(0, 16), scale=2.448)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_on_a_tie_the_lower_expert_wins_as_in_the_reference(reference):
    x, router, ex, sh = _expert_case(2)
    # experts 3, 7 and 11 score the same on every row, and lead: with two
    # places, 3 and 7 take them
    col = router["w"][:, 3] + 1.0
    w = router["w"].at[:, 7].set(col).at[:, 11].set(col).at[:, 3].set(col)
    router = {"w": w, "bias": jnp.zeros((16,))}
    picked, _ = moe.route_topk(x, w, router["bias"], top_k=2)
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(x, w, precision="highest")))
    tied = scores[:, 3] >= np.delete(scores, [3, 7, 11], axis=1).max(axis=1)
    assert tied.any()
    np.testing.assert_array_equal(np.sort(np.asarray(picked)[tied], axis=1),
                                  np.tile([3, 7], (tied.sum(), 1)))
    want, chosen = _plain(reference, x, router, ex, sh, 2)
    np.testing.assert_array_equal(
        np.sort(picked, axis=1), np.nonzero(np.asarray(chosen))[1].reshape(-1, 2))
    got, _, _ = moe.moe_topk(x, router, ex, sh, top_k=2,
                             experts_held=(0, 16), scale=2.448)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_shares_of_disjoint_ranges_sum_to_the_layer(reference, impl):
    """Four holders of a quarter of the experts each, the shared experts with
    ONE of them: their parts add up to the whole layer, and their pairs to
    every chosen pair."""
    x, router, ex, sh = _expert_case(3)
    want, _ = _plain(reference, x, router, ex, sh, 3)
    whole, counts, ids = moe.moe_topk(
        x, router, ex, sh, top_k=3, experts_held=(0, 16), scale=2.448,
        impl=impl)
    total, pairs, touched = 0.0, 0, 0
    for i, (lo, hi) in enumerate(((0, 4), (4, 8), (8, 12), (12, 16))):
        part, c, held_ids = moe.moe_topk(
            x, router, {n: a[lo:hi] for n, a in ex.items()},
            sh if i == 2 else None, top_k=3, experts_held=(lo, hi),
            scale=2.448, impl=impl)
        # every holder routes over ALL the experts
        np.testing.assert_array_equal(held_ids, ids)
        total, pairs, touched = total + part, pairs + int(c[0]), touched + int(c[1])
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    assert pairs == int(counts[0]) == x.shape[0] * 3
    assert touched == int(counts[1])
    # a holder's part is what the reference computes over its range alone
    held, _ = reference.moe_layer(
        x, router["w"], router["bias"], ex["w_gu"][4:8], ex["w_down"][4:8],
        None, 3, 2.448, (4, 8))
    part, _, _ = moe.moe_topk(x, router, {n: a[4:8] for n, a in ex.items()},
                              None, top_k=3, experts_held=(4, 8), scale=2.448,
                              impl=impl)
    np.testing.assert_allclose(part, held, rtol=2e-5, atol=2e-5)


def test_padding_rows_route_nowhere_and_a_stack_is_addressed_in_place():
    x, router, ex, sh = _expert_case(4)
    mask = jnp.arange(x.shape[0]) < 33
    want, c_want, _ = moe.moe_topk(x[:33], router, ex, sh, top_k=3,
                                   experts_held=(0, 16), scale=2.448)
    stack = {n: jnp.stack([a * 0, a]) for n, a in ex.items()}
    for impl in ("reference", "pallas"):
        got, c, _ = moe.moe_topk(x, router, stack, sh, top_k=3,
                                 experts_held=(0, 16), scale=2.448, impl=impl,
                                 layer=1, token_mask=mask)
        np.testing.assert_allclose(got[:33], want, rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(c, c_want)


@pytest.mark.parametrize("sizes", [
    [0, 5, 0, 1, 30, 0, 0, 4], [0, 0, 0, 0, 0, 0, 0, 0],
    [40, 0, 0, 0, 0, 0, 0, 0], [3, 3, 3, 3, 3, 3, 3, 3]],
    ids=["ragged", "empty", "one", "even"])
def test_grouped_matmul_in_interpret_mode_is_ragged_dot(sizes):
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    x = jax.random.normal(ks[0], (40, 32))
    w = jax.random.normal(ks[1], (8, 32, 48)) * 0.2
    sizes = jnp.asarray(sizes, jnp.int32)
    a = moe.grouped_matmul(x, w, sizes, impl="reference")
    b = moe.grouped_matmul(x, w, sizes, impl="pallas", interpret=True)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    assert not np.asarray(b)[int(sizes.sum()):].any()   # rows of no group
    with pytest.raises(ValueError, match="layer="):
        moe.grouped_matmul(x, w[None], sizes)


def test_a_row_tile_boundary_inside_a_group(monkeypatch):
    """Groups that straddle the kernel's row tiles, and tiles no group
    reaches, at tile sizes small enough to have several."""
    monkeypatch.setattr(moe, "_GMM_ROWS", 16)
    monkeypatch.setattr(moe, "_GMM_TILE_M", 32)
    monkeypatch.setattr(moe, "_GMM_BLOCK_BYTES", 32 * 128 * 4)
    assert moe._gmm_tile_n(32, 256, 4) == 128       # two column tiles
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    x = jax.random.normal(ks[0], (160, 32))
    w = jax.random.normal(ks[1], (6, 32, 256)) * 0.2
    for sizes in ([50, 0, 7, 33, 1, 10], [0, 0, 97, 0, 0, 0], [1, 1, 1, 1, 1, 1]):
        sizes = jnp.asarray(sizes, jnp.int32)
        a = moe.grouped_matmul(x, w, sizes, impl="reference")
        b = moe.grouped_matmul(x, w, sizes, impl="pallas", interpret=True)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


# (K, N) of gate-and-up and down at the benchmark's four expert
# configurations, bf16: the column tile the 4 MiB block budget gives each
_COLUMN_TILES = {
    "mellum2-gate-up": (2304, 1792, 896), "mellum2-down": (896, 2304, 2304),
    "kanana2-gate-up": (2048, 1536, 768), "kanana2-down": (768, 2048, 2048),
    # 4096 x 512 x 2 B is the budget to the byte; 640 would be 5.2 MB
    "solar2-gate-up": (4096, 2560, 512), "solar2-down": (1280, 4096, 1024),
    # 768 would be 4.7 MB: `trinity_open_mixedlen` keeps the programs it had
    "trinity-gate-up": (3072, 6144, 512), "trinity-down": (3072, 3072, 512),
    # no multiple of 128 divides N: the whole width, one column tile
    "toy-48": (32, 48, 48), "toy-64": (64, 64, 64), "toy-200": (16, 200, 200),
}


@pytest.mark.parametrize("case", sorted(_COLUMN_TILES))
def test_the_column_tile_follows_the_weight_blocks_bytes(case):
    K, N, tn = _COLUMN_TILES[case]
    assert moe._gmm_tile_n(K, N, 2) == tn
    assert N % tn == 0
    assert tn % 128 or K * tn * 2 <= moe._GMM_BLOCK_BYTES


@pytest.mark.parametrize("tiles", ["several", "one"])
@pytest.mark.parametrize("N", [7 * 128, 9 * 128])
def test_wide_column_tiles_are_ragged_dot(monkeypatch, N, tiles):
    """Mellum2's widths in 128s (7 and 9 column tiles at the narrowest, one
    at the widest), groups that straddle row tiles, a tile no group reaches:
    the same product whatever the budget makes of the columns."""
    K = 32
    monkeypatch.setattr(moe, "_GMM_ROWS", 16)
    monkeypatch.setattr(moe, "_GMM_TILE_M", 32)
    monkeypatch.setattr(moe, "_GMM_BLOCK_BYTES",
                        K * 4 * (128 if tiles == "several" else N))
    assert moe._gmm_tile_n(K, N, 4) == (128 if tiles == "several" else N)
    ks = jax.random.split(jax.random.PRNGKey(N), 2)
    x = jax.random.normal(ks[0], (96, K))
    w = jax.random.normal(ks[1], (5, K, N)) * 0.2
    sizes = jnp.asarray([37, 0, 3, 29, 1], jnp.int32)
    a = moe.grouped_matmul(x, w, sizes, impl="reference")
    b = moe.grouped_matmul(x, w, sizes, impl="pallas", interpret=True)
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    assert not np.asarray(b)[70:].any()


def test_the_grid_steps_counter_says_which_tile_a_shape_got():
    from paddle_tpu import observability as obs

    labels = {"K": 32, "N": 384, "tn": 384, "tile_m": 32, "rows": 24}
    x, w = jnp.zeros((24, 32)), jnp.zeros((5, 32, 384))
    sizes = jnp.asarray([4, 0, 9, 1, 2], jnp.int32)
    for _ in range(2):      # a second trace of the shape counts nothing more
        jax.make_jaxpr(lambda x, w, s: moe.grouped_matmul(
            x, w, s, impl="pallas", interpret=True))(x, w, sizes)
    assert obs.counter("moe.gmm.grid_steps", labels=labels).value == 5


# 6. the family's refusals and the weights' form ------------------------------

@pytest.mark.parametrize("key,value", [("index_topk", 2048), ("n_group", 8),
                                       ("rope_scaling", {"type": "yarn"})])
def test_a_key_the_model_does_not_write_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        M.cache_layout(dict(CFG, **{key: value}))


def test_weights_are_few_arrays_and_the_step_programs_hold_none(params):
    leaves = jax.tree_util.tree_leaves(params)
    # eight arrays stacked by kind, two expert stacks, five matrices a layer
    assert len(leaves) == 10 + 5 * CFG["num_hidden_layers"]
    n_moe = CFG["num_hidden_layers"] - CFG["first_k_dense_replace"]
    assert params["e_gu"].shape[:2] == (n_moe, CFG["n_routed_experts"])
    assert params["router_w"].dtype == jnp.float32
    model = M.build_decode_model(params, CFG)
    assert model.num_layers == 0 and set(model.page_pools) == {"latent"}
    assert tuple(model.step_counters) == M.STEP_COUNTERS
    text = jax.jit(functools.partial(M.decode_step, cfg=CFG)).lower(
        params, jnp.zeros(SLOTS, jnp.int32), jnp.zeros(SLOTS, jnp.int32),
        _cache().pools, jnp.zeros((SLOTS, MAX_LEN // PAGE), jnp.int32),
        jnp.zeros(SLOTS, jnp.int32)).as_text()
    assert len(text) < 2 ** 20                  # no weight is a constant


# 8. the second form of the router: softmax over all experts (the Mellum
# family's; ``chipbench/configs/mellum2_12b_a2_5b.reference.py`` is its
# plain form) -----------------------------------------------------------------

@pytest.fixture(scope="module")
def softmax_reference():
    spec = importlib.util.spec_from_file_location(
        "mellum_reference",
        os.path.join(ROOT, "chipbench/configs/mellum2_12b_a2_5b.reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_softmax_router_is_softmax_then_top_k_then_renormalise(
        softmax_reference):
    x, router, ex, _ = _expert_case(5, experts=16)
    picked, weights = moe.route_topk(x, router["w"], None, top_k=8,
                                     scoring="softmax")
    p = np.asarray(jax.nn.softmax(jnp.dot(x, router["w"],
                                          precision="highest"), axis=-1))
    order = np.argsort(-p, axis=1, kind="stable")[:, :8]
    np.testing.assert_array_equal(np.sort(picked, axis=1),
                                  np.sort(order, axis=1))
    chosen = np.take_along_axis(p, np.asarray(picked), axis=1)
    np.testing.assert_allclose(
        weights, chosen / chosen.sum(axis=1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(weights).sum(axis=1), 1.0,
                               rtol=1e-6)
    # another function than the sigmoid form over the same logits: the
    # weights differ though the chosen sets (both monotone in the logit,
    # no bias) are the same
    same, other = moe.route_topk(x, router["w"], None, top_k=8)
    np.testing.assert_array_equal(np.sort(same, axis=1),
                                  np.sort(picked, axis=1))
    assert np.abs(np.asarray(other) - np.asarray(weights)).max() > 1e-3
    mask, w = softmax_reference.route(x, router["w"], 8)
    np.testing.assert_array_equal(
        np.sort(picked, axis=1), np.nonzero(np.asarray(mask))[1].reshape(-1, 8))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), np.asarray(picked), axis=1),
        weights, rtol=1e-6)
    with pytest.raises(ValueError, match="scoring"):
        moe.route_topk(x, router["w"], None, top_k=8, scoring="tanh")


def test_the_softmax_router_breaks_a_tie_for_the_lower_expert(
        softmax_reference):
    x, router, _, _ = _expert_case(6)
    col = router["w"][:, 3] + 1.0
    w = router["w"].at[:, 7].set(col).at[:, 11].set(col).at[:, 3].set(col)
    picked, _ = moe.route_topk(x, w, None, top_k=2, scoring="softmax")
    mask, _ = softmax_reference.route(x, w, 2)
    np.testing.assert_array_equal(
        np.sort(picked, axis=1), np.nonzero(np.asarray(mask))[1].reshape(-1, 2))
    p = np.asarray(jax.nn.softmax(jnp.dot(x, w, precision="highest"), axis=-1))
    tied = p[:, 3] >= np.delete(p, [3, 7, 11], axis=1).max(axis=1)
    assert tied.any()
    np.testing.assert_array_equal(np.sort(np.asarray(picked)[tied], axis=1),
                                  np.tile([3, 7], (tied.sum(), 1)))


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_shares_of_disjoint_ranges_sum_to_the_softmax_layer(softmax_reference,
                                                            impl):
    """The same as for the sigmoid form, with no shared expert: four holders
    of a quarter of the experts each add up to the whole layer, which is the
    plain loop over all experts with a mask."""
    x, router, ex, _ = _expert_case(7)
    kw = dict(top_k=8, scoring="softmax", impl=impl)
    want, mask = softmax_reference.moe_layer(x, router["w"], ex["w_gu"],
                                             ex["w_down"], 8)
    whole, counts, ids = moe.moe_topk(x, {"w": router["w"], "bias": None}, ex,
                                      None, experts_held=(0, 16), **kw)
    np.testing.assert_allclose(whole, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        np.sort(ids, axis=1), np.nonzero(np.asarray(mask))[1].reshape(-1, 8))
    total, pairs, touched = 0.0, 0, 0
    for lo, hi in ((0, 4), (4, 8), (8, 12), (12, 16)):
        part, c, held_ids = moe.moe_topk(
            x, {"w": router["w"], "bias": None},
            {n: a[lo:hi] for n, a in ex.items()}, None,
            experts_held=(lo, hi), **kw)
        np.testing.assert_array_equal(held_ids, ids)
        held, _ = softmax_reference.moe_layer(
            x, router["w"], ex["w_gu"][lo:hi], ex["w_down"][lo:hi], 8,
            (lo, hi))
        np.testing.assert_allclose(part, held, rtol=2e-5, atol=2e-5)
        total, pairs, touched = total + part, pairs + int(c[0]), touched + int(c[1])
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)
    assert pairs == int(counts[0]) == x.shape[0] * 8
    assert touched == int(counts[1])


def test_the_sigmoid_routers_program_is_the_one_it_was():
    """``scoring`` is a Python-level choice: the sigmoid form traces to the
    same jaxpr whether or not it is named."""
    x, router, _, _ = _expert_case(8)
    named = jax.make_jaxpr(lambda x, w, b: moe.route_topk(
        x, w, b, top_k=3, scale=2.448, scoring="sigmoid"))(
            x, router["w"], router["bias"])
    plain = jax.make_jaxpr(lambda x, w, b: moe.route_topk(
        x, w, b, top_k=3, scale=2.448))(x, router["w"], router["bias"])
    assert str(named) == str(plain)
    assert "logistic" in str(plain) and "logistic" not in str(jax.make_jaxpr(
        lambda x, w: moe.route_topk(x, w, None, top_k=3, scoring="softmax"))(
            x, router["w"]))


# sha256[:16] of ``moe_topk``'s jaxpr with every expert held, at the parent
# commit (7cceb06), made by the same function on a checkout of it
_WHOLE_LAYER_DIGESTS = {
    ("sigmoid", "float32"): "da857afa94481356",
    ("sigmoid", "bfloat16"): "39703a16a0a7bced",
    ("softmax", "float32"): "377b82bc5a6f18e5",
    ("softmax", "bfloat16"): "49f95ca8d4655be7",
}


@pytest.mark.parametrize("scoring,dtype", sorted(_WHOLE_LAYER_DIGESTS))
def test_a_layer_that_holds_every_expert_traces_as_it_did(scoring, dtype):
    """PR 40 serves a TRUE share through ``moe_topk`` and gave
    ``grouped_matmul`` a stated VMEM limit at contractions of 4096: a caller
    that holds every expert of a narrower model (``models/deepseek_v3.py``
    with a sigmoid router, a bias and shared experts; ``models/mellum.py``
    with a softmax router and neither) traces to the jaxpr it traced to, the
    kernel's included."""
    import hashlib
    import re

    from paddle_tpu.parallel import moe

    E, D, F, T, k = 8, 64, 32, 12, 3
    sig = scoring == "sigmoid"
    x = jnp.zeros((T, D), dtype)
    router = {"w": jnp.zeros((D, E), jnp.float32),
              "bias": jnp.zeros((E,), jnp.float32) if sig else None}
    experts = {"w_gu": jnp.zeros((2, E, D, 2 * F), dtype),
               "w_down": jnp.zeros((2, E, F, D), dtype)}
    shared = {"w_gu": jnp.zeros((D, 2 * F), dtype),
              "w_down": jnp.zeros((F, D), dtype)} if sig else None
    jaxpr = jax.make_jaxpr(lambda x, r, e, s: moe.moe_topk(
        x, r, e, s, top_k=k, experts_held=(0, E), scale=2.5 if sig else 1.0,
        token_mask=jnp.arange(T) < 10, layer=1, impl="pallas",
        interpret=True, scoring=scoring))(x, router, experts, shared)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    assert (hashlib.sha256(text.encode()).hexdigest()[:16]
            == _WHOLE_LAYER_DIGESTS[scoring, dtype])
