"""DeepSeek sparse attention in the DeepSeek-V3 family file
(``models/deepseek_v3.py`` with ``index_topk``: GLM-5's ``glm_moe_dsa``)
through the serving path against its plain reference
(``chipbench/configs/glm5_744b_a40b.reference.py``) on the CPU at toy sizes
with seeded float32 weights: logits of chunked prefill and of decode through
BOTH cache leaves at contexts that start below ``index_topk`` and grow past
it and at contexts many times it, the selected SETS of chunk and decode, the
two structure tests (``index_topk`` >= the span is dense MLA bit for bit;
kanana-2's logits are the parent's), the exact selection on adversarial
scores, the row list through a shuffled page table, both leaves' rows, a
reseated slot, the prefix cache, the 16 shares of an expert layer, and every
new kernel in interpret mode.

At these sizes the model runs in float32 end to end, so the system differs
from the reference only by the ORDER of float32 operations: 1e-4 of the
logits' spread holds that, and the selected sets are EQUAL (no near tie at
these sizes and seeds).
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

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REFERENCE = os.path.join(ROOT, "chipbench/configs/glm5_744b_a40b.reference.py")

# toy sizes under the family's own key names: index_topk 16 against contexts
# of up to 200, 4 indexer heads, a compressed query, a third of the experts
CFG = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    vocab_size=100, num_attention_heads=4, kv_lora_rank=32, q_lora_rank=48,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    index_n_heads=4, index_head_dim=16, index_topk=16,
    n_routed_experts=8, router_experts=24, experts_held=[8, 16],
    num_experts_per_tok=3, n_shared_experts=1,
    first_k_dense_replace=1, num_hidden_layers=3, n_group=1, topk_group=1,
    moe_layer_freq=1, norm_topk_prob=True, scoring_func="sigmoid",
    routed_scaling_factor=2.5, rms_norm_eps=1e-5, rope_theta=1000000)
PAGE, SLOTS, MAX_LEN = 8, 3, 208
LOGIT_TOL = 1e-4        # max |a - b| / std(b): float32 reordering only


@pytest.fixture(scope="module")
def reference():
    spec = importlib.util.spec_from_file_location("glm5_reference", REFERENCE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def params():
    return M.params(CFG, 0, dtype="float32")


@pytest.fixture(scope="module")
def tokens():
    return np.random.RandomState(1).randint(1, 100, size=MAX_LEN).astype(np.int32)


@functools.lru_cache(maxsize=None)
def _fns(items):
    cfg = dict(items)
    cfg["experts_held"] = list(cfg["experts_held"])
    kw = dict(cfg=cfg, with_routing=True, with_selection=True)
    return (jax.jit(functools.partial(M.prefill_chunk, **kw)),
            jax.jit(functools.partial(M.decode_step, **kw)))


def _key(cfg):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items()))


def _cache(cfg=CFG, dtype="float32", max_len=MAX_LEN):
    return serving.PagedKVCache(
        0, SLOTS * (max_len // PAGE) + 1, PAGE, 0, 0, max_len, dtype=dtype,
        num_slots=SLOTS, **M.cache_layout(cfg))


def _through_the_cache(params, tokens, prompt_len, steps, chunk, cfg=CFG,
                       slot=1, shuffle=None, poison=False):
    """Prefill ``tokens[:prompt_len]`` in chunks of ``chunk`` into ``slot``'s
    pages, then decode ``steps`` tokens (teacher forced) through the cache.
    Returns the logits at positions ``prompt_len - 1 ..``, each layer's
    selected set there (sorted positions), the pools and the page row."""
    chunk_fn, decode_fn = _fns(_key(cfg))
    cache = _cache(cfg)
    pages = cache.alloc(cache.pages_for(prompt_len + steps))
    if shuffle is not None:
        pages = list(np.random.RandomState(shuffle).permutation(pages))
    row = cache.table_row(pages)
    pools = cache.pools
    if poison:
        # whatever a last occupant or nobody left: every row of every page
        # (finite: the plain forms multiply a masked row by an exact zero;
        # the kernels are held to NaN rows in their own tests)
        pools = {k: jnp.full_like(v, 1e4) for k, v in pools.items()}
    start, logits, sets = 0, [], []
    while start < prompt_len:
        valid = min(chunk, prompt_len - start)
        window = np.zeros(chunk, np.int32)
        window[:valid] = tokens[start:start + valid]
        vec = np.zeros(chunk // PAGE, np.int32)
        n = min(len(vec), len(pages) - start // PAGE)
        vec[:n] = pages[start // PAGE:start // PAGE + n]
        lg, pools, _, sel = chunk_fn(
            params, jnp.asarray(window), jnp.int32(start), jnp.int32(valid),
            pools, jnp.asarray(vec), jnp.asarray(row), jnp.int32(slot))
        start += valid
    logits.append(np.asarray(lg))
    sets.append([np.flatnonzero(np.asarray(k)[valid - 1]) for _, k in sel])
    tables = np.zeros((SLOTS, cache.max_pages_per_seq), np.int32)
    tables[slot] = row
    for t in range(prompt_len, prompt_len + steps):
        toks, pos, lens = (np.zeros(SLOTS, np.int32) for _ in range(3))
        toks[slot], pos[slot], lens[slot] = tokens[t], t, t + 1
        lg, pools, counts, _, sel = decode_fn(
            params, jnp.asarray(toks), jnp.asarray(pos), pools,
            jnp.asarray(tables), jnp.asarray(lens))
        logits.append(np.asarray(lg)[slot])
        sets.append([np.sort(np.asarray(r)[slot][:int(np.asarray(n)[slot])])
                     for _, r, n in sel])
    return np.stack(logits), sets, pools, row, np.asarray(counts)


def _err(a, b):
    return float(np.max(np.abs(a - b)) / np.std(b))


@pytest.fixture(scope="module")
def truth(reference, params, tokens):
    """The reference's one full forward pass at every position."""
    pos = jnp.arange(MAX_LEN, dtype=jnp.int32)
    logits, _, rows, index = jax.jit(lambda p, s, q: reference.forward(
        p, CFG, s, q, block=16))(params, jnp.asarray(tokens), pos)
    return (np.asarray(logits), [np.asarray(r) for r in rows],
            [{k: np.asarray(v) for k, v in layer.items()} for layer in index])


# 1. system = reference, in logits and in selected sets ----------------------

@pytest.mark.parametrize("prompt,steps,chunk", [
    (10, 30, 16),      # starts below index_topk, grows past it in decode
    (56, 12, 8),       # a few times index_topk, page-wide chunks
    (176, 24, 64),     # many times index_topk, a ragged last chunk
], ids=["grows-past-topk", "pages", "long"])
def test_chunked_prefill_then_decode_equals_the_reference(params, tokens, truth,
                                                          prompt, steps, chunk):
    logits, sets, _, _, counts = _through_the_cache(params, tokens, prompt,
                                                    steps, chunk)
    at = slice(prompt - 1, prompt + steps)
    assert _err(logits, truth[0][at]) < LOGIT_TOL
    for i, per_layer in enumerate(sets):
        t = prompt - 1 + i
        for layer, got in enumerate(per_layer):
            want = np.flatnonzero(truth[2][layer]["sets"][t])
            np.testing.assert_array_equal(got, want)
            assert len(got) == min(t + 1, CFG["index_topk"])
    # one live slot: k pairs chosen an expert layer, some of them held here;
    # min(visible, index_topk) rows read and every visible key scored a layer
    L, k = CFG["num_hidden_layers"], CFG["num_experts_per_tok"]
    n_moe = L - CFG["first_k_dense_replace"]
    visible = prompt + steps
    assert counts[0] + counts[4] == n_moe * k
    np.testing.assert_array_equal(
        counts[[3, 5, 6, 7]],
        [min(visible, 16) * L, min(visible, 16) * L, visible * L, visible * L])


def test_the_selection_is_neither_a_window_nor_a_prefix(truth):
    """Or the cell would measure a sliding window: a late query's set shares
    less than half with "the last k" and with "the first k"."""
    k = CFG["index_topk"]
    for layer in truth[2]:
        for t in (120, 160, 200):
            got = set(np.flatnonzero(layer["sets"][t]))
            assert len(got) == k
            assert len(got & set(range(t + 1 - k, t + 1))) < k / 2
            assert len(got & set(range(k))) < k / 2
        s = layer["scores"][200, :201]
        assert s.std() > 0.05 * np.abs(s).max()         # far from flat


def test_both_leaves_keep_the_references_rows(params, tokens, truth):
    prompt, steps = 40, 8
    _, _, pools, row, _ = _through_the_cache(params, tokens, prompt, steps, 16,
                                             shuffle=3)
    n = prompt + steps
    R, dr = CFG["kv_lora_rank"], CFG["qk_rope_head_dim"]
    for layer in range(CFG["num_hidden_layers"]):
        lat = np.asarray(pools["latent"][layer, row]).reshape(-1, 128)[:n]
        want = truth[1][layer][:n]
        want = np.concatenate([want[:, :R], want[:, R::2], want[:, R + 1::2]],
                              axis=1)
        np.testing.assert_allclose(lat[:, :R + dr], want, atol=2e-5)
        assert not lat[:, R + dr:].any()
        idx = np.asarray(pools["index_k"][layer, row]).reshape(-1, 16)[:n]
        want = truth[2][layer]["k"][:n]
        want = np.concatenate([want[:, 0:dr:2], want[:, 1:dr:2], want[:, dr:]],
                              axis=1)
        np.testing.assert_allclose(idx, want, atol=2e-5)


def test_a_reseated_slot_never_selects_a_row_of_its_last_occupant(
        params, tokens, truth):
    """Every row nobody of this sequence wrote is 1e4: the scores past
    ``kv_lens`` are masked before the selection, a slot with fewer than
    ``index_topk`` visible tokens selects exactly those, and the logits are
    the clean cache's."""
    prompt, steps = 10, 12
    logits, sets, _, _, _ = _through_the_cache(params, tokens, prompt, steps,
                                               16, poison=True, shuffle=5)
    assert np.isfinite(logits).all()
    assert _err(logits, truth[0][prompt - 1:prompt + steps]) < LOGIT_TOL
    for i, per_layer in enumerate(sets):
        for got in per_layer:
            np.testing.assert_array_equal(
                got, np.arange(min(prompt + i, CFG["index_topk"]))
                if prompt + i <= CFG["index_topk"] else got)
            assert got.max() < prompt + i


# 2. structure ----------------------------------------------------------------

def test_index_topk_of_the_whole_span_is_dense_mla_bit_for_bit(params, tokens):
    """With ``index_topk`` = the page table's span every visible row is
    selected, and the model IS dense MLA: the bits of the dense path on the
    same weights with the indexer's matrices ignored."""
    span = dict(CFG, index_topk=MAX_LEN)
    dense = {k: v for k, v in CFG.items()
             if k not in ("index_topk", "index_n_heads", "index_head_dim")}
    d = M._dims(CFG)
    # the dense model's w_in and w_qb have no indexer columns
    n_q = d["H"] * (d["dn"] + d["dr"])
    cut = dict(params, layers=[
        dict(lp, w_in=lp["w_in"][:, :d["Rq"] + d["R"] + d["dr"]],
             w_qb=lp["w_qb"][:, :n_q]) for lp in params["layers"]])
    cut = {k: v for k, v in cut.items() if k not in ("ikn_w", "ikn_b")}
    prompt, steps = 40, 6
    sparse = _through_the_cache(params, tokens, prompt, steps, 16, cfg=span)
    chunk_fn = jax.jit(functools.partial(M.prefill_chunk, cfg=dense))
    decode_fn = jax.jit(functools.partial(M.decode_step, cfg=dense))
    cache = _cache(dense)
    pages = cache.alloc(cache.pages_for(prompt + steps))
    row, pools, slot = cache.table_row(pages), cache.pools, 1
    logits = []
    for start in range(0, prompt, 16):
        valid = min(16, prompt - start)
        window = np.zeros(16, np.int32)
        window[:valid] = tokens[start:start + valid]
        lg, pools = chunk_fn(
            cut, jnp.asarray(window), jnp.int32(start), jnp.int32(valid),
            pools, jnp.asarray(pages[start // PAGE:start // PAGE + 2]),
            jnp.asarray(row), jnp.int32(slot))
    logits.append(np.asarray(lg))
    tables = np.zeros((SLOTS, cache.max_pages_per_seq), np.int32)
    tables[slot] = row
    for t in range(prompt, prompt + steps):
        toks, pos, lens = (np.zeros(SLOTS, np.int32) for _ in range(3))
        toks[slot], pos[slot], lens[slot] = tokens[t], t, t + 1
        lg, pools, _ = decode_fn(cut, jnp.asarray(toks), jnp.asarray(pos),
                                 pools, jnp.asarray(tables), jnp.asarray(lens))
        logits.append(np.asarray(lg)[slot])
    np.testing.assert_array_equal(sparse[0], np.stack(logits))
    np.testing.assert_array_equal(np.asarray(sparse[2]["latent"]),
                                  np.asarray(pools["latent"]))


# kanana-2's toy logits [13, :8] as the PARENT commit (c45bda9) gave them
# (tests/unittests/test_deepseek_v3.py's CFG, seed 0, prompt 60, 12 steps,
# chunks of 16): float32 bytes, little-endian
PARENT_LOGITS = (
    "56e903be06d8923f1c31aebf83102f3f9abaf4becd5191bfbcb6a33d7d60c9bec6e50dc0"
    "3f0fe7bf6de63f3fd97fdfbd765330c0ead6323f63f3a23e4ebdef3ee6dbf83ef39c0540"
    "06db06bfe1d7283f8898bdbfac44a63ff0f594be986091bf5e58ebbfb01802bfc5e70140"
    "b91b4cbf1af89b3fd743743da2ee64bf6d44dfbf7305753e89a8544064c616c0e416303f"
    "8fe9d1bcd62a1540206426bf8802a53f573aab3e739b353b5b9a0dbf91d9ac3fe01f99bf"
    "a1f5a33dff63c13e3292213f3570a83f5280b03fd813873ea4a6c4bd2cf5833e427753bf"
    "b62253bf049311bfa0e4243f615fd63f1552513f5f1106bf63bd9abdc12dd43f55beb2bf"
    "5035883fc25fc6bed8a97abe19f87e3ffe8184bee263c9bd4a34a4be4d968b3f9887883f"
    "e0dd36bff1c0a43f759e1ac07b4425bfb2523ebf77a02a3d561f453ebb92c9bf3d98ac3d"
    "eaa77d3faf189cbf268ba8be88ea4b3e10bac93f4609dbbfc472b83f0382943f8040bd3f"
    "6b5a973e6079b5bd7fa0f63e7bd428bf5f7567bf9add32bf0813923fcb470c40eccb62bf"
    "cb68d8bac66c3fbebc2c8c3fdddaabbf02e1d4be")


def test_kanana2s_logits_are_the_parents():
    """``q_lora_rank`` null and no ``index_topk``: the same file gives the
    logits it gave before it knew either (the weights' random stream and the
    step programs are untouched)."""
    spec = importlib.util.spec_from_file_location(
        "kanana_tests", os.path.join(ROOT,
                                     "tests/unittests/test_deepseek_v3.py"))
    t = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(t)
    toks = np.random.RandomState(1).randint(1, 100, size=t.T_PAD).astype(
        np.int32)
    logits, _, counts = t._through_the_cache(
        M.params(t.CFG, 0, dtype="float32"), toks, t.PROMPT, t.STEPS, 16)
    want = np.frombuffer(bytes.fromhex(PARENT_LOGITS), "<f4").reshape(13, 8)
    np.testing.assert_array_equal(logits[:, :8], want)
    assert tuple(M.step_counters(t.CFG)) == M.STEP_COUNTERS and len(
        counts) == 4


# 3. the exact selection ------------------------------------------------------

def _argsort_sets(scores, n, k):
    """The stable-argsort selection with the tie rule, in numpy."""
    out = np.zeros(scores.shape, bool)
    for i, (row, m) in enumerate(zip(scores, n)):
        order = np.argsort(-row[:m], kind="stable")
        out[i, order[:min(m, k)]] = True
    return out


def _adversarial(case, rng, N=6, K=96):
    n = rng.randint(1, K + 1, size=N)
    if case == "ties-at-the-threshold":
        s = rng.randint(0, 4, size=(N, K)).astype(np.float32)
    elif case == "all-equal":
        s = np.full((N, K), 0.25, np.float32)
    elif case == "fewer-visible-than-k":
        s = rng.randn(N, K).astype(np.float32)
        n = rng.randint(0, 10, size=N)
    elif case == "signs-and-zeros":
        s = rng.choice(np.asarray([-1.5, -0.0, 0.0, 1e-30, -1e-30, 3.0],
                                  np.float32), size=(N, K))
    elif case == "minus-infinity-past-kv-lens":
        s = rng.randn(N, K).astype(np.float32)
        s[np.arange(K)[None, :] >= n[:, None]] = -np.inf
    else:                                   # garbage past kv_lens
        s = rng.randn(N, K).astype(np.float32)
        s[np.arange(K)[None, :] >= n[:, None]] = 1e30
    return s, n.astype(np.int32)


_CASES = ["ties-at-the-threshold", "all-equal", "fewer-visible-than-k",
          "signs-and-zeros", "minus-infinity-past-kv-lens",
          "garbage-past-kv-lens"]


@pytest.mark.parametrize("case", _CASES)
@pytest.mark.parametrize("k", [1, 16, 96])
def test_the_selection_is_the_argsorts(case, k):
    rng = np.random.RandomState(len(case) + k)
    s, n = _adversarial(case, rng)
    keep = np.asarray(jax.jit(lambda s, n: FA.dsa_keep(s, n, k))(s, n))
    clean = np.where(s == 0, 0.0, s)        # -0.0 ties with +0.0
    np.testing.assert_array_equal(keep, _argsort_sets(clean, n, k))
    rows, m = jax.jit(lambda x: FA.dsa_rows(x, k))(keep)
    rows, m = np.asarray(rows), np.asarray(m)
    np.testing.assert_array_equal(m, np.minimum(n, k))
    for i in range(len(n)):
        np.testing.assert_array_equal(rows[i, :m[i]], np.flatnonzero(keep[i]))
        assert not rows[i, m[i]:].any()
    assert np.isfinite(rows).all()


def _edges(case, rng):
    """Score sets ``(s [N, K], n_visible [N], k)`` that sit on the selection
    kernel's own seams: a counting pass's chunk of 4096 positions, a vreg row
    of 1024, a block of 128, a list tile of 128 entries."""
    K = 8192
    pos = np.arange(K)
    if case == "how-many-are-visible":          # 0, 1, exactly k, all of it
        n, k = np.asarray([0, 1, 160, K, 4096, 4097]), 160
        s = rng.randn(len(n), K).astype(np.float32)
    elif case == "more-visible-than-the-row-is-wide":
        # 200 positions, padded to a turn's 4096 inside: the padding is no
        # candidate whatever n_visible says
        n, k = np.asarray([300, 200, 199, 10 ** 6]), 64
        s = -1.0 - rng.rand(len(n), 200)
    elif case == "ties-straddle-the-seams":
        # ``k - need`` scores above, at the row's end; equal scores over lo ..
        # hi, of which the ``need`` lowest positions are taken: the cut falls
        # before, on and behind a block's, a vreg row's and a chunk's edge
        k = 200
        spec = [(120, 140, 5), (120, 140, 8), (120, 140, 9), (120, 140, 21),
                (1016, 1040, 8), (1016, 1040, 9), (4090, 4100, 6),
                (4090, 4100, 7), (0, 7000, 1), (0, 7000, 200)]
        n = np.full(len(spec), K)
        s = np.full((len(spec), K), -3.0, np.float32)
        for row, (lo, hi, need) in zip(s, spec):
            row[lo:hi + 1] = 0.25
            row[K - (k - need):] = 2.0 + rng.rand(k - need)
    elif case == "a-tile-over-many-blocks":
        # one kept position every 200: a tile of 128 entries spans 200 blocks
        n, k = np.asarray([K, K - 100, 5000]), 40
        s = np.where(pos % 200 == 7, 5.0 + (pos % 3), rng.rand(3, K) - 2.0)
    elif case == "a-tile-inside-one-block":
        # the best 128 are one whole block, the next 128 another
        n, k = np.asarray([K, 6000, 1300]), 256
        s = rng.rand(3, K) - 2.0
        s[:, 1152:1280] = 9.0
        s[:, 384:512] = 8.0
    elif case == "a-full-list-of-a-long-row":
        K = 53248
        n, k = np.asarray([K, 49152, 15970, 2048, 2047]), 2048
        s = np.maximum(rng.randn(len(n), K), 0).astype(np.float32)   # ReLU
    else:
        raise KeyError(case)
    s = s.astype(np.float32)
    s[np.arange(s.shape[1])[None, :] >= n[:, None]] = [
        1e30, -np.inf, FA.NEG_INF][rng.randint(3)]
    return s, n, k


@pytest.mark.parametrize("case,k", [(c, k) for c in _CASES
                                    for k in (1, 16, 96)] + [
    ("ties-at-the-threshold", 2048), ("signs-and-zeros", 2048),
    ("garbage-past-kv-lens", 2048),
    ("how-many-are-visible", None), ("ties-straddle-the-seams", None),
    ("more-visible-than-the-row-is-wide", None),
    ("a-tile-over-many-blocks", None),
    ("a-tile-inside-one-block", None), ("a-full-list-of-a-long-row", None)])
def test_the_selection_kernel_is_the_plain_form_to_the_bit(case, k):
    """``dsa_select(impl="pallas")`` in interpret mode against
    ``dsa_rows(dsa_keep())``: rows and n equal exactly."""
    rng = np.random.RandomState(len(case) + (k or 0))
    if k is None:
        s, n, k = _edges(case, rng)
    elif k == 2048:                         # 2048 of a long row
        s, n = _adversarial(case, rng, N=4, K=6144)
        n[0], n[1] = 6144, 2048
    else:
        s, n = _adversarial(case, rng)
    s, n = jnp.asarray(s), jnp.asarray(n, jnp.int32)
    want = jax.jit(lambda s, n: FA.dsa_rows(FA.dsa_keep(s, n, k), k))(s, n)
    got = jax.jit(lambda s, n: FA.dsa_select(
        s, n, k, impl="pallas", interpret=True))(s, n)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    assert got[0].dtype == want[0].dtype and got[1].dtype == want[1].dtype
    labels = {"S": s.shape[0], "K": s.shape[1], "k": k}
    assert obs.counter("paged.dsa_select.grid_steps",
                       labels=labels).value == s.shape[0]


def test_on_the_cpu_the_selection_is_the_plain_form_through_the_module(
        monkeypatch):
    """``impl`` left to the backend: the CPU takes ``dsa_rows(dsa_keep())``,
    looked up in the module at the call (what a test that replaces
    ``FA.dsa_keep`` relies on)."""
    s = jnp.asarray(np.random.RandomState(0).randn(3, 96).astype(np.float32))
    n = jnp.asarray([96, 10, 0], jnp.int32)
    rows, m = FA.dsa_select(s, n, 16)
    want = FA.dsa_rows(FA.dsa_keep(s, n, 16), 16)
    np.testing.assert_array_equal(np.asarray(rows), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(m), np.asarray(want[1]))
    window = lambda s, n, k: (jnp.arange(96)[None, :] < n[:, None]) & (
        jnp.arange(96)[None, :] >= n[:, None] - k)
    monkeypatch.setattr(FA, "dsa_keep", window)
    rows, m = FA.dsa_select(s, n, 16)
    np.testing.assert_array_equal(np.asarray(rows[0]), np.arange(80, 96))
    with pytest.raises(ValueError, match="impl must be"):
        FA.dsa_select(s, n, 16, impl="sort")


# 4. the kernels against their plain forms ------------------------------------

def _index_case(seed, dtype, n_slots=5, heads=4, width=16, mp=12):
    rng = np.random.RandomState(seed)
    pool = rng.randn(2, n_slots * mp + 1, PAGE, width).astype(np.float32)
    tables = 1 + rng.permutation(n_slots * mp).reshape(n_slots, mp)
    lens = rng.randint(1, mp * PAGE + 1, size=n_slots)
    lens[1], lens[2] = 0, mp * PAGE
    # every row no slot may read is NaN
    for s in range(n_slots):
        flat = pool[1, tables[s]].reshape(-1, width)
        flat[lens[s]:] = np.nan
        pool[1, tables[s]] = flat.reshape(mp, PAGE, width)
    pool[1, 0] = np.nan
    q = rng.randn(n_slots, heads, width).astype(np.float32)
    w = rng.randn(n_slots, heads).astype(np.float32)
    return (jnp.asarray(q, dtype), jnp.asarray(w), jnp.asarray(pool, dtype),
            jnp.asarray(tables, jnp.int32), jnp.asarray(lens, jnp.int32))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("turn_pages", [None, 2, 5])
def test_index_scores_kernel_in_interpret_mode_is_the_plain_form(
        monkeypatch, dtype, turn_pages):
    if turn_pages is not None:
        monkeypatch.setattr(FA, "_index_turn_pages", lambda *a: turn_pages)
    q, w, pool, tables, lens = _index_case(3, dtype)
    kw = dict(layer=1, scale=0.125)
    want = np.asarray(FA.paged_index_scores(q, w, pool, tables, lens,
                                            impl="reference", **kw))
    got = np.asarray(FA.paged_index_scores(q, w, pool, tables, lens,
                                           impl="pallas", interpret=True,
                                           **kw))
    seen = np.arange(want.shape[1])[None, :] < np.asarray(lens)[:, None]
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got[~seen], np.float32(FA.NEG_INF))
    np.testing.assert_allclose(got[seen], want[seen], rtol=2e-5, atol=2e-5)
    labels = {"S": 5, "rows": 4, "mp": 12, "ps": PAGE,
              "turn": (turn_pages or FA._index_turn_pages(12, PAGE, 4)) * PAGE}
    assert obs.counter("paged.index.grid_steps", labels=labels).value == 5


@pytest.mark.parametrize("start,valid", [(0, 16), (40, 11), (80, 16)])
def test_index_scores_of_a_chunk_in_interpret_mode(monkeypatch, start, valid):
    monkeypatch.setattr(FA, "_index_turn_pages", lambda *a: 3)
    q, w, pool, tables, _ = _index_case(4, "float32")
    rng = np.random.RandomState(9)
    C = 16
    qc = jnp.asarray(rng.randn(C, 4, 16).astype(np.float32))
    wc = jnp.asarray(rng.randn(C, 4).astype(np.float32))
    # the sequence of slot 2 holds every row; the others' NaN rows are not its
    kw = dict(layer=1, scale=0.125)
    args = (qc, wc, pool, tables[2], jnp.int32(start), jnp.int32(valid))
    want = np.asarray(FA.paged_index_scores_prefill(*args, impl="reference",
                                                    **kw))
    got = np.asarray(FA.paged_index_scores_prefill(
        *args, impl="pallas", interpret=True, **kw))
    seen = (np.arange(want.shape[1])[None, :] <= start + np.arange(C)[:, None]
            ) & (np.arange(C) < valid)[:, None]
    np.testing.assert_array_equal(got[~seen], np.float32(FA.NEG_INF))
    np.testing.assert_array_equal(want[~seen], np.float32(FA.NEG_INF))
    np.testing.assert_allclose(got[seen], want[seen], rtol=2e-5, atol=2e-5)


def _latent_case(seed, dtype, n_slots=4, heads=4, mp=10, width=128, rank=32):
    rng = np.random.RandomState(seed)
    pool = np.zeros((2, n_slots * mp + 1, PAGE, width), np.float32)
    pool[..., :rank + 8] = rng.randn(2, n_slots * mp + 1, PAGE, rank + 8)
    tables = 1 + rng.permutation(n_slots * mp).reshape(n_slots, mp)
    q = np.zeros((n_slots, heads, width), np.float32)
    q[..., :rank + 8] = rng.randn(n_slots, heads, rank + 8)
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(tables, jnp.int32), rng)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_attention_over_a_row_list_through_a_shuffled_page_table(dtype, impl):
    """The listed rows resolved through the page table: the softmax over
    exactly those rows, computed here from the gathered rows in numpy."""
    k = 24
    q, pool, tables, rng = _latent_case(5, dtype)
    S, mp = tables.shape
    n = np.asarray([k, 5, 0, 17], np.int32)
    rows = np.zeros((S, k), np.int32)
    for s in range(S):
        rows[s, :n[s]] = np.sort(rng.choice(mp * PAGE, n[s], replace=False))
    got = np.asarray(FA.paged_mla_rows_attention(
        q, pool, tables, jnp.asarray(rows), jnp.asarray(n), v_width=32,
        sm_scale=0.2, layer=1, impl=impl,
        interpret=True if impl == "pallas" else None))
    flat = np.asarray(pool.astype(jnp.float32))[1][np.asarray(tables)].reshape(
        S, mp * PAGE, -1)
    qf = np.asarray(q.astype(jnp.float32))
    for s in range(S):
        if n[s] == 0:
            assert not got[s].any()
            continue
        sel = flat[s, rows[s, :n[s]]]
        p = jax.nn.softmax(jnp.asarray(qf[s] @ sel.T * 0.2), axis=-1)
        np.testing.assert_allclose(got[s], np.asarray(p) @ sel[:, :32],
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("start,valid", [(0, 16), (48, 9)])
def test_a_chunks_latent_kernel_under_a_mask_in_interpret_mode(start, valid):
    q1, pool, tables, rng = _latent_case(6, "float32")
    C, mp = 16, tables.shape[1]
    q = jnp.asarray(np.concatenate([
        rng.randn(C, 4, 40), np.zeros((C, 4, 88))], axis=-1).astype(np.float32))
    pos = start + np.arange(C)
    keep = (rng.rand(C, mp * PAGE) < 0.3) & (
        np.arange(mp * PAGE)[None, :] <= pos[:, None])
    # the first kept key of some rows lies turns into the walk
    keep[:, :24] &= (np.arange(C) % 2 == 0)[:, None]
    keep[np.arange(C), pos] = True
    kw = dict(v_width=32, sm_scale=0.2, layer=1, keep=jnp.asarray(keep))
    args = (q, pool, tables[1], jnp.int32(start), jnp.int32(valid))
    want = np.asarray(FA.paged_mla_prefill_attention(*args, impl="reference",
                                                     **kw))
    got = np.asarray(FA.paged_mla_prefill_attention(
        *args, impl="pallas", interpret=True, **kw))
    np.testing.assert_allclose(got[:valid], want[:valid], rtol=2e-5, atol=2e-5)
    # and the mask matters: without it the rows are others
    dense = np.asarray(FA.paged_mla_prefill_attention(
        *args, impl="reference", **dict(kw, keep=None)))
    assert np.abs(dense[:valid] - want[:valid]).max() > 1e-2


# 5. the cache and the scheduler ----------------------------------------------

def test_two_page_leaves_of_different_width_in_one_group():
    cache = _cache()
    L = CFG["num_hidden_layers"]
    assert cache.pools["latent"].shape == (L, cache.num_pages, PAGE, 128)
    assert cache.pools["index_k"].shape == (L, cache.num_pages, PAGE, 16)
    assert set(cache.pools) == {"latent", "index_k"}
    # pools sized from both leaves
    assert cache.page_bytes == L * cache.num_pages * PAGE * (128 + 16) * 4
    model = M.build_decode_model(M.params(CFG, 1, dtype="float32"), CFG)
    assert model.num_layers == 0
    assert set(model.page_pools) == {"latent", "index_k"}
    assert tuple(model.step_counters) == M.STEP_COUNTERS + M.DSA_COUNTERS


@pytest.fixture(scope="module")
def decode_model(params):
    return M.build_decode_model(params, CFG)


def _scheduler(model, **over):
    cfg = dict(num_slots=SLOTS, page_size=PAGE, max_seq_len=MAX_LEN,
               prefill_chunk_tokens=16, prefill_buckets=(8, 16, 128),
               max_new_tokens=8, prefix_cache=False)
    cfg.update(over)
    return serving.DecodeScheduler(model, serving.DecodeConfig(**cfg))


def test_the_scheduler_serves_the_references_tokens_and_counts(
        decode_model, tokens, truth):
    sched = _scheduler(decode_model)
    try:
        names = ["serving.decode." + c for c in M.step_counters(CFG)]
        before = [obs.counter(c).value for c in names]
        prompt = tokens[:50]
        out = sched.submit(prompt, max_new_tokens=1).result(timeout=120)
        assert int(np.asarray(out)[0]) == int(np.argmax(truth[0][49]))
        more = sched.submit(prompt, max_new_tokens=6).result(timeout=120)
        assert len(np.asarray(more)) == 6
        moved = dict(zip(M.step_counters(CFG), (
            obs.counter(c).value - b for c, b in zip(names, before))))
        assert moved["index.rows_scored"] == moved["sparse.visible_tokens"] > 0
        assert 0 < moved["sparse.selected_tokens"] < moved[
            "sparse.visible_tokens"]
        assert moved["moe.pairs"] + moved["moe.pairs_elsewhere"] > 0
        assert sched.cache_stats()["used_pages"] == 0
    finally:
        sched.stop()


def test_the_prefix_cache_accepts_the_model_and_a_hit_reuses_index_k_rows(
        decode_model, tokens):
    cold = _scheduler(decode_model)
    try:
        want = [np.asarray(cold.submit(tokens[:n], max_new_tokens=5)
                           .result(timeout=120)) for n in (70, 90)]
    finally:
        cold.stop()
    warm = _scheduler(decode_model, prefix_cache=True)
    try:
        hits = obs.counter("serving.decode.kv_hit_pages")
        first = np.asarray(warm.submit(tokens[:70], max_new_tokens=5)
                           .result(timeout=120))
        before = hits.value
        # shares 70 tokens = 8 whole pages of BOTH leaves with the first
        second = np.asarray(warm.submit(tokens[:90], max_new_tokens=5)
                            .result(timeout=120))
        assert hits.value - before >= 8
        np.testing.assert_array_equal(first, want[0])
        np.testing.assert_array_equal(second, want[1])
    finally:
        warm.stop()


# 6. the share ----------------------------------------------------------------

def test_sixteen_shares_of_an_expert_layer_sum_to_the_layer(reference):
    """The parts of the 16 disjoint ``experts_held`` ranges, the shared expert
    counted once, add up to the uncut reference's layer (this router, scale
    2.5), and ``take_share`` cuts what each holder holds."""
    from paddle_tpu.parallel import moe

    whole = dict(CFG, n_routed_experts=32, router_experts=32,
                 experts_held=[0, 32])
    p = M.params(whole, 2, dtype="float32")
    d = M._dims(whole)
    u = jax.random.normal(jax.random.PRNGKey(3), (20, d["D"]), jnp.float32)
    lp = p["layers"][1]
    shared = {"w_gu": lp["w_gu"], "w_down": lp["w_down"]}
    router = {"w": p["router_w"][0], "bias": p["router_b"][0]}
    want, chosen = reference.moe_layer(
        u, router["w"], router["bias"], p["e_gu"][0], p["e_down"][0],
        (shared["w_gu"], shared["w_down"]), d["k"], d["scale"])
    total, pairs = 0.0, 0
    for i in range(16):
        part, cut = M.take_share(p, whole, (2 * i, 2 * i + 2),
                                 vocab=(0, 50) if i == 0 else None)
        assert part["e_gu"].shape[1] == 2 and cut["experts_held"] == [
            2 * i, 2 * i + 2] and cut["router_experts"] == 32
        y, counts, _ = moe.moe_topk(
            u, router, {"w_gu": part["e_gu"], "w_down": part["e_down"]},
            shared if i == 0 else None, top_k=d["k"],
            experts_held=(2 * i, 2 * i + 2), scale=d["scale"], layer=0)
        total = total + y
        pairs += int(counts[0])
        if i == 0:
            assert part["embed"].shape[0] == 50 and cut["vocab_size"] == 50
    assert pairs == 20 * d["k"]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # and the reference's own ``held`` is the same part
    part = reference.moe_layer(
        u, router["w"], router["bias"], p["e_gu"][0][4:6], p["e_down"][0][4:6],
        None, d["k"], d["scale"], held=(4, 6))[0]
    y = moe.moe_topk(u, router, {"w_gu": p["e_gu"][:, 4:6],
                                 "w_down": p["e_down"][:, 4:6]}, None,
                     top_k=d["k"], experts_held=(4, 6), scale=d["scale"],
                     layer=0)[0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(part), rtol=2e-5,
                               atol=2e-5)
    assert chosen.shape == (20, 32)


@pytest.mark.parametrize("key,value,match", [
    ("index_topk", 16, "q_lora_rank"), ("experts_held", [0, 4], "experts_held")])
def test_what_the_family_cannot_be_is_refused(key, value, match):
    bad = {k: v for k, v in CFG.items() if k != "q_lora_rank"}
    if key == "experts_held":
        bad = dict(CFG)
    with pytest.raises(ValueError, match=match):
        M.cache_layout(dict(bad, **{key: value}))
