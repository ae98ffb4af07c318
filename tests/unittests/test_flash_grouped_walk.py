"""The grouped walk of the paged kernels (grouped-query heads, a window):
decode and prefill against plain masked attention, bf16 pools, what it
refuses, and the digests that pin the walks it must not change (CPU interpret
mode)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp



# ---------------------------------------------------------------------------
# Grouped query heads on the walk, with an optional WINDOW
# (``paged_gqa_decode_attention`` / ``paged_gqa_prefill_attention``): both
# engines against plain masked attention over the unpaged rows.  Every page
# and row that no query may read is NaN, so a walk that copies or scores a
# released page, a row before the window or a row past ``kv_len`` shows.
# ---------------------------------------------------------------------------

_G_PS, _G_HKV, _G_G, _G_D, _G_T = 8, 2, 4, 128, 100


def _plain_gqa(q, kk, vv, pos, window):
    """``q [R, Hq, D]`` at positions ``pos`` against ``kk``, ``vv`` ``[T,
    Hkv, D]``: keys ``s`` with ``0 <= t - s`` (``<= window - 1``)."""
    g = q.shape[1] // kk.shape[1]
    k, v = jnp.repeat(kk, g, axis=1), jnp.repeat(vv, g, axis=1)
    s = jnp.einsum("rhd,thd->rht", q, k,
                   precision="highest") / np.sqrt(q.shape[-1])
    back = pos[:, None] - jnp.arange(kk.shape[0])[None, :]
    ok = back >= 0
    if window is not None:
        ok = ok & (back <= window - 1)
    p = jax.nn.softmax(jnp.where(ok[:, None, :], s, -1e30), axis=-1)
    return np.asarray(jnp.einsum("rht,thd->rhd", p, v, precision="highest"))


def _paged_rows(rows, pages_needed, width, n_pool, layer=1):
    """A NaN pool ``[2, n_pool, ps, Hkv*D]`` holding ``rows [T, Hkv, D]`` of
    the logical pages in ``pages_needed``, and the table row (a ring of
    ``width``: logical page p in column p % width) that names them."""
    pool = np.full((2, n_pool, _G_PS, _G_HKV * _G_D), np.nan, np.float32)
    row = np.zeros((width,), np.int32)
    for n, pg in enumerate(pages_needed):
        row[pg % width] = n + 1
        hi = min(rows.shape[0], (pg + 1) * _G_PS)
        pool[layer, n + 1, :hi - pg * _G_PS] = rows[pg * _G_PS:hi].reshape(
            hi - pg * _G_PS, -1)
    return pool, row


@pytest.fixture(scope="module")
def gqa_rows():
    rng = np.random.RandomState(0)
    return (rng.randn(_G_T, _G_HKV, _G_D).astype(np.float32),
            rng.randn(_G_T, _G_HKV, _G_D).astype(np.float32))


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("window,width", [(None, 13), (20, 6), (20, 13),
                                          (8, 3)],
                         ids=["full", "ring", "absolute", "one-page"])
def test_grouped_walk_decode_is_plain_masked_attention(gqa_rows, impl, window,
                                                       width):
    """Slots with nothing, under, at, just over and far over the window, at
    aligned and unaligned lengths; with a window the table is a ring as wide
    as the slot's bound (or the whole sequence: the same thing)."""
    from paddle_tpu.parallel.flash_attention import paged_gqa_decode_attention

    kk, vv = gqa_rows
    lens = np.array([0, 5, 8, 19, 20, 21, 24, 37, 100], np.int32)
    if width * _G_PS < _G_T:      # a ring: a slot's live pages must fit it
        lens = lens[lens <= (width - 1) * _G_PS] if window is None else lens
    S = len(lens)
    k_pool = np.full((2, 40, _G_PS, _G_HKV * _G_D), np.nan, np.float32)
    v_pool = k_pool.copy()
    tables, nxt = np.zeros((S, width), np.int32), 1
    for s, n in enumerate(lens):
        first = 0 if window is None else max(n - window, 0) // _G_PS
        for pg in range(first, -(-n // _G_PS)):
            tables[s, pg % width] = nxt
            hi = min(n, (pg + 1) * _G_PS)
            k_pool[1, nxt, :hi - pg * _G_PS] = kk[pg * _G_PS:hi].reshape(
                hi - pg * _G_PS, -1)
            v_pool[1, nxt, :hi - pg * _G_PS] = vv[pg * _G_PS:hi].reshape(
                hi - pg * _G_PS, -1)
            nxt += 1
    q = np.random.RandomState(1).randn(S, _G_HKV * _G_G, _G_D).astype(
        np.float32)
    got = np.asarray(paged_gqa_decode_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(tables), jnp.asarray(lens), layer=1, window=window,
        impl=impl, interpret=True))
    assert np.isfinite(got).all()
    for s, n in enumerate(lens):
        if n == 0:
            assert not got[s].any()     # an empty slot: exact zeros
            continue
        want = _plain_gqa(jnp.asarray(q[s:s + 1]), jnp.asarray(kk),
                          jnp.asarray(vv), jnp.asarray([n - 1]), window)[0]
        np.testing.assert_allclose(got[s], want, atol=2e-5)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("window,width", [(None, 13), (20, 6), (5, 4)],
                         ids=["full", "ring", "window-under-a-page"])
@pytest.mark.parametrize("start,valid", [(0, 16), (16, 16), (40, 11),
                                         (80, 16), (8, 1)])
def test_grouped_walk_prefill_is_plain_masked_attention(gqa_rows, impl, window,
                                                        width, start, valid):
    """A chunk of 16 rows at aligned starts early and late in the sequence,
    whole and ragged: each row causal by position and no further back than
    the window; only the pages some row of the chunk can see are there."""
    from paddle_tpu.parallel.flash_attention import paged_gqa_prefill_attention

    kk, vv = gqa_rows
    C = 16
    first = 0 if window is None else max(start - window + 1, 0) // _G_PS
    pages = range(first, -(-(start + C) // _G_PS))
    k_pool, row = _paged_rows(kk, pages, width, 40)
    v_pool, _ = _paged_rows(vv, pages, width, 40)
    q = np.random.RandomState(2).randn(C, _G_HKV * _G_G, _G_D).astype(
        np.float32)
    got = np.asarray(paged_gqa_prefill_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(row), jnp.int32(start), jnp.int32(valid), layer=1,
        window=window, impl=impl, interpret=True))
    want = _plain_gqa(jnp.asarray(q), jnp.asarray(kk), jnp.asarray(vv),
                      jnp.arange(start, start + C), window)
    assert np.isfinite(got[:valid]).all()
    np.testing.assert_allclose(got[:valid], want[:valid], atol=2e-5)


def test_grouped_walk_reads_bfloat16_pools_and_counts_its_grid():
    """bfloat16 queries and pools (the served dtypes): one MXU pass a
    product, the bf16 page the precision lost; the trace-time counter names
    the walk that was chosen."""
    from paddle_tpu import observability as obs
    from paddle_tpu.parallel.flash_attention import paged_gqa_decode_attention

    rng = np.random.RandomState(3)
    S, n_pages, mp = 4, 9, 4
    kp = jnp.asarray(rng.randn(1, n_pages, _G_PS, _G_HKV * _G_D),
                     jnp.bfloat16)
    vp = jnp.asarray(rng.randn(1, n_pages, _G_PS, _G_HKV * _G_D),
                     jnp.bfloat16)
    q = jnp.asarray(rng.randn(S, _G_HKV * _G_G, _G_D), jnp.bfloat16)
    tables = jnp.asarray(1 + rng.permutation(8).reshape(2, 4)[[0, 1, 0, 1]],
                         jnp.int32)
    lens = jnp.asarray([3, 32, 17, 0], jnp.int32)
    for window in (None, 12):
        ref, got = (np.asarray(paged_gqa_decode_attention(
            q, kp, vp, tables, lens, layer=0, window=window, impl=impl,
            interpret=True)) for impl in ("reference", "pallas"))
        np.testing.assert_allclose(got, ref, atol=2e-2)
        assert got.dtype == np.float32 and not got[3].any()
        assert obs.counter("paged.gqa.grid_steps", labels={
            "S": S, "mp": mp, "ps": _G_PS, "turn": mp * _G_PS,
            "window": window or 0}).value == S


def test_grouped_walk_refuses_what_it_cannot_read():
    from paddle_tpu.parallel.flash_attention import paged_gqa_decode_attention

    q = jnp.zeros((2, 4, 16))
    pool = jnp.zeros((1, 3, 8, 2 * 16))
    args = (jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32))
    with pytest.raises(ValueError, match="window"):
        paged_gqa_decode_attention(q, pool, pool, *args, layer=0, window=0)
    with pytest.raises(ValueError, match="stored stack"):
        paged_gqa_decode_attention(q, pool[0], pool[0], *args, layer=0)


def test_the_walk_of_the_plain_and_the_latent_kernel_is_the_one_it_was():
    """``_walk_pages`` grew a first page and a ``p . v`` of the caller's: with
    neither, the plain and the latent kernel trace to the jaxprs they traced
    to (f32 / bf16 queries x f32 / bf16 pools), which the recorded digests of
    the parent's hold."""
    import hashlib
    import re

    from paddle_tpu.parallel import flash_attention as FA

    def digest(fn, *args):
        text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
        return hashlib.sha256(text.encode()).hexdigest()[:16]

    got = {}
    for qd in ("float32", "bfloat16"):
        for pd in ("float32", "bfloat16"):
            q = jnp.zeros((4, 8, 64), qd)
            pool = jnp.zeros((2, 9, 16, 512), pd)
            t, n = jnp.zeros((4, 8), jnp.int32), jnp.zeros((4,), jnp.int32)
            got["plain", qd, pd] = digest(
                lambda q, k, v, t, n: FA.paged_decode_attention(
                    q, k, v, t, n, impl="pallas", interpret=True, layer=1),
                q, pool, pool, t, n)
            lat = jnp.zeros((2, 9, 16, 128), pd)
            ql = jnp.zeros((4, 8, 128), qd)
            got["latent", qd, pd] = digest(
                lambda q, p, t, n: FA.paged_mla_decode_attention(
                    q, p, t, n, v_width=64, sm_scale=0.1, layer=1,
                    impl="pallas", interpret=True), ql, lat, t, n)
    assert got == _WALK_DIGESTS, got


# sha256[:16] of the kernels' jaxprs at the parent commit (948e369), made by
# the same function on a checkout of it
_WALK_DIGESTS = {
    ("latent", "bfloat16", "bfloat16"): "4f015dc3ec1a3220",
    ("latent", "bfloat16", "float32"): "647ee99e42b0e84f",
    ("latent", "float32", "bfloat16"): "ccac1f6b69425960",
    ("latent", "float32", "float32"): "4512b4642a2fe820",
    ("plain", "bfloat16", "bfloat16"): "f932d30cfc0b06a8",
    ("plain", "bfloat16", "float32"): "db3adbe59d784410",
    ("plain", "float32", "bfloat16"): "e6d7bc5078985a7d",
    ("plain", "float32", "float32"): "f59d85bae1e17ef4",
}


@pytest.mark.parametrize("window", [None, 20], ids=["full", "window"])
@pytest.mark.parametrize("pd", ["float32", "bfloat16"])
@pytest.mark.parametrize("qd", ["float32", "bfloat16"])
@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_the_table_row_grouped_walk_is_the_one_it_was(form, qd, pd, window):
    """``_paged_gqa_walk_kernel`` grew a list a (slot, KV head): without one
    the grouped walk of a slot's table row traces to the jaxpr it traced to
    (the recorded digests are the parent's, 25cc02b, by the same function)."""
    import hashlib
    import re

    from paddle_tpu.parallel import flash_attention as FA

    kw = dict(layer=1, window=window, impl="pallas", interpret=True)
    pool = jnp.zeros((2, 9, 16, 256), pd)
    t, n = jnp.zeros((4, 8), jnp.int32), jnp.zeros((4,), jnp.int32)
    if form == "decode":
        fn, args = (lambda q, k, v, t, n: FA.paged_gqa_decode_attention(
            q, k, v, t, n, **kw), (jnp.zeros((4, 8, 128), qd), pool, pool,
                                   t, n))
    else:
        fn, args = (lambda q, k, v, p, s, vd: FA.paged_gqa_prefill_attention(
            q, k, v, p, s, vd, **kw), (jnp.zeros((16, 8, 128), qd), pool,
                                       pool, t[0], jnp.int32(8),
                                       jnp.int32(16)))
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    assert (hashlib.sha256(text.encode()).hexdigest()[:16]
            == _GQA_WALK_DIGESTS[form, qd, pd, window])


_GQA_WALK_DIGESTS = {
    ("decode", "bfloat16", "bfloat16", 20): "21144cbdbc2940c9",
    ("decode", "bfloat16", "bfloat16", None): "7a44c1b62d8436e0",
    ("decode", "bfloat16", "float32", 20): "e227ad11985a6f2e",
    ("decode", "bfloat16", "float32", None): "12f259ff584169ee",
    ("decode", "float32", "bfloat16", 20): "a3fe7a6074c25397",
    ("decode", "float32", "bfloat16", None): "e1c862e197d77a6f",
    ("decode", "float32", "float32", 20): "ccf78a86db0b84c2",
    ("decode", "float32", "float32", None): "e11bba911e5b70f2",
    ("prefill", "bfloat16", "bfloat16", 20): "05445d242175f5c8",
    ("prefill", "bfloat16", "bfloat16", None): "e6f8f5ebc5ddb3a6",
    ("prefill", "bfloat16", "float32", 20): "e8e458935c349dfe",
    ("prefill", "bfloat16", "float32", None): "0eedc49677cbe5ec",
    ("prefill", "float32", "bfloat16", 20): "26fa71da56ed8102",
    ("prefill", "float32", "bfloat16", None): "d39c9251aa69084a",
    ("prefill", "float32", "float32", 20): "be8a34636ca42e6c",
    ("prefill", "float32", "float32", None): "f8926211dbaff017",
}
