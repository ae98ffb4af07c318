"""The listed walk of the paged decode kernel: a slot reads the pages of its
own list and nothing else, at each edge of the count, a list a turn (CPU
interpret mode)."""
import numpy as np
import pytest
import jax.numpy as jnp

from paddle_tpu.parallel.flash_attention import paged_decode_attention


# ---------------------------------------------------------------------------
# The LISTED walk (PR 39): ``paged_decode_attention(selection=...)`` is the
# grouped walk over a page LIST a (slot, KV head), one walk a (slot, KV
# head).  Interpret mode against ``_paged_gqa_reference``.  Every row of the
# pool that no list counts is NaN — the other head's lanes of a listed page,
# the rows of a last page past the count, every unlisted page — and the
# entries of a list past its count name such pages, so a walk that copies,
# scores or weighs anything it was not given shows.
# ---------------------------------------------------------------------------

_L_PS, _L_HKV, _L_G, _L_D, _L_NS = 8, 2, 4, 128, 6


# tokens a (slot, KV head) counts: none, inside the first page, one whole
# page, mid-page, page boundaries, the whole list; a slot's two heads differ
_L_COUNTS = np.asarray([[0, 3], [_L_PS, 2 * _L_PS + 5],
                        [_L_NS * _L_PS, 2 * _L_PS], [5 * _L_PS + 1, 0]],
                       np.int32)


def _listed_case(counts, ps, n_kv, dh, ns, kv_dtype, seed=11, layers=2,
                 layer=1):
    """NaN pools ``[layers, P, ps, n_kv*dh]`` holding, for each (slot, KV
    head), ``counts`` tokens in that head's lanes of pages of its own, the
    lists that name them (any page past the count) and the query rows."""
    rng = np.random.RandomState(seed)
    S = counts.shape[0]
    n_pool = S * n_kv * ns + 1
    k = np.full((layers, n_pool, ps, n_kv * dh), np.nan, np.float32)
    v = np.full((layers, n_pool, ps, n_kv * dh), np.nan, np.float32)
    own = 1 + rng.permutation(S * n_kv * ns).reshape(S, n_kv, ns)
    pages = rng.randint(0, n_pool, (S, n_kv, ns))       # past the count: any
    for s in range(S):
        for h in range(n_kv):
            n = int(counts[s, h])
            used = -(-n // ps)
            pages[s, h, :used] = own[s, h, :used]
            for j in range(used):
                rows = min(ps, n - j * ps)
                at = (layer, own[s, h, j], slice(0, rows),
                      slice(h * dh, (h + 1) * dh))
                k[at] = rng.randn(rows, dh)
                v[at] = rng.randn(rows, dh)
    q = rng.randn(S, n_kv * _L_G, dh).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(k).astype(kv_dtype),
            jnp.asarray(v).astype(kv_dtype),
            (jnp.asarray(pages, jnp.int32), jnp.asarray(counts)))


def _listed_both(q, k, v, selection, layer=1):
    S = q.shape[0]
    unread = (jnp.zeros((S, 1), jnp.int32), jnp.zeros((S,), jnp.int32))
    return tuple(np.asarray(paged_decode_attention(
        q, k, v, *unread, impl=impl, interpret=True, layer=layer,
        selection=selection)) for impl in ("reference", "pallas"))


@pytest.fixture(params=[1, 2, None], ids=["turn1page", "turn2pages",
                                          "turn-chosen"])
def listed_turn(request, monkeypatch):
    """Pages a turn of the listed walk: one, two (lists of 3 and 6 pages are
    longer than a turn, those of one page shorter) or the chooser's own (the
    whole list)."""
    from paddle_tpu.parallel import flash_attention as FA

    if request.param is not None:
        monkeypatch.setattr(FA, "_listed_turn_pages",
                            lambda *a: request.param)
    return request.param or _L_NS


@pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.bfloat16],
                         ids=["kv-f32", "kv-bf16"])
def test_listed_walk_reads_its_own_list_and_nothing_else(listed_turn,
                                                         kv_dtype):
    q, k, v, sel = _listed_case(_L_COUNTS, _L_PS, _L_HKV, _L_D, _L_NS,
                                kv_dtype)
    ref, got = _listed_both(q, k, v, sel)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)
    # a count of 0 is exact zeros, for that head's rows alone
    for s, h in zip(*np.nonzero(_L_COUNTS == 0)):
        assert not got[s, h * _L_G:(h + 1) * _L_G].any()
    assert np.abs(got[0, _L_G:]).max() > 0


@pytest.mark.parametrize("count", [0, 1, _L_PS - 1, _L_PS, _L_PS + 1,
                                   3 * _L_PS, _L_NS * _L_PS - 1,
                                   _L_NS * _L_PS])
def test_listed_walk_at_each_edge_of_the_count(listed_turn, count):
    """One (slot, KV head) at a time at the count's edges, the slot's other
    head on a full list: the walks of one grid row do not lean on each
    other."""
    counts = np.asarray([[count, _L_NS * _L_PS], [7, count]], np.int32)
    q, k, v, sel = _listed_case(counts, _L_PS, _L_HKV, _L_D, _L_NS,
                                jnp.float32, seed=count)
    ref, got = _listed_both(q, k, v, sel)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)
    if not count:
        assert not got[0, :_L_G].any() and not got[1, _L_G:].any()


def test_listed_walk_is_the_walk_of_each_list_alone(listed_turn):
    """A (slot, KV head)'s rows depend on its own list, count and query
    alone, bitwise: the same list served beside other lists, and with the
    entries past its count renamed."""
    q, k, v, (pages, counts) = _listed_case(
        _L_COUNTS, _L_PS, _L_HKV, _L_D, _L_NS, jnp.float32)
    _, got = _listed_both(q, k, v, (pages, counts))
    used = -(-np.asarray(counts) // _L_PS)
    renamed = np.where(np.arange(_L_NS)[None, None, :] < used[..., None],
                       np.asarray(pages), 0)
    _, again = _listed_both(q, k, v, (jnp.asarray(renamed), counts))
    np.testing.assert_array_equal(got, again)
    _, alone = _listed_both(q[1:2], k, v, (pages[1:2], counts[1:2]))
    np.testing.assert_array_equal(got[1:2], alone)


def test_listed_walk_at_the_cells_head_and_page_in_bfloat16():
    """MiniCPM-SALA's own ``Dh`` = 128, ``ps`` = 64, 16 query rows a KV head,
    bfloat16 pools, float32 queries, and a list longer than the chooser's
    turn (4096 keys at these shapes): the bf16 page is the precision lost,
    and the trace-time counter names the walk that was chosen."""
    from paddle_tpu import observability as obs
    from paddle_tpu.parallel import flash_attention as FA

    ps, n_kv, g, dh, ns = 64, 2, 16, 128, 72
    assert FA._listed_turn_pages(ps, dh, ns, 2, g) * ps == 4096 < ns * ps
    counts = np.asarray([[ns * ps - 17, 65 * ps], [0, 2 * ps + 1]], np.int32)
    rng = np.random.RandomState(5)
    q, k, v, sel = _listed_case(counts, ps, n_kv, dh, ns, jnp.bfloat16)
    q = jnp.asarray(rng.randn(2, n_kv * g, dh), jnp.float32)
    ref, got = _listed_both(q, k, v, sel)
    assert got.dtype == np.float32 and np.isfinite(got).all()
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-6)
    assert not got[1, :g].any()
    assert obs.counter("paged.gqa.grid_steps", labels={
        "S": 2, "heads": n_kv, "listed": ns, "ps": ps,
        "turn": 4096}).value == 2 * n_kv


@pytest.mark.parametrize("ps,dh,ns,itemsize,rows,pages", [
    (64, 128, 128, 2, 16, 64),    # MiniCPM-SALA's decode: 4096 keys, 2 turns
    (64, 128, 128, 4, 16, 32),    # a float32 pool there: the budget halves it
    (64, 128, 8, 2, 16, 8),       # a list shorter than a turn: all of it
    (16, 256, 256, 2, 16, 128),   # 256 lanes a head: 2048 keys
    (16, 512, 128, 2, 16, 64),    # 512 lanes a head: 1024 keys
    (16, 1024, 128, 2, 16, 32),   # a head as wide as a row: the 512 keys
    (8, 16, 6, 4, 16, 6),         # the toy shapes of this file
    (8192, 128, 4, 2, 16, 1),     # a page wider than a turn: one page
    (64, 128, 128, 2, 1024, 4),   # rows so many the budget halves it
])
def test_listed_turn_is_a_pure_function_of_the_shapes(ps, dh, ns, itemsize,
                                                      rows, pages):
    from paddle_tpu.parallel import flash_attention as FA

    assert FA._listed_turn_pages(ps, dh, ns, itemsize, rows) == pages


def test_the_selected_page_grid_is_gone():
    """The kernel that stepped over the list eight pages a grid step went
    with its grid (ROADMAP S9 (1))."""
    from paddle_tpu.parallel import flash_attention as FA

    assert not hasattr(FA, "_paged_gqa_decode_kernel")
    assert not hasattr(FA, "DECODE_PAGES_PER_STEP")
