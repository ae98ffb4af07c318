"""The paged prefill kernel beside its decode twin: the chunk kernel against
the reference, chunk-split and page indirection bitwise, and both kernels over
stacked, heads-folded pools (CPU interpret mode)."""
import numpy as np
import pytest
import jax.numpy as jnp

from paddle_tpu.parallel.flash_attention import (
    mha_reference,
    paged_decode_attention,
    paged_prefill_attention,
)


class TestPagedPrefillAttention:
    """The chunked-prefill attention (ISSUE 15): a chunk of query rows at
    absolute positions ``start..`` against the sequence's paged KV, with
    the properties the scheduler's bitwise contract leans on — per-row
    parity with the reference oracle, engine parity (pallas interpret),
    chunk-split invariance, and page-placement indifference."""

    def _setup(self, seed=0, P=9, ps=4, H=2, Dh=8, MP=4, C=8, start=4):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(C, H, Dh).astype(np.float32))
        kp = jnp.asarray(rng.randn(P, ps, H, Dh).astype(np.float32))
        vp = jnp.asarray(rng.randn(P, ps, H, Dh).astype(np.float32))
        pages = jnp.asarray(np.array([1, 3, 5, 7], np.int32)[:MP])
        return q, kp, vp, pages, start

    def test_reference_matches_mha_per_row(self):
        # row i (absolute position start + i) == T_q=1 attention over
        # the gathered pages with kv_len = start + i + 1
        q, kp, vp, pages, start = self._setup()
        out = np.asarray(paged_prefill_attention(q, kp, vp, pages, start,
                                                 impl="reference"))
        kk = np.asarray(kp)[np.asarray(pages)]
        vv = np.asarray(vp)[np.asarray(pages)]
        MP, ps, H, Dh = kk.shape
        kk = kk.reshape(MP * ps, H, Dh)
        vv = vv.reshape(MP * ps, H, Dh)
        for i in range(q.shape[0]):
            ref = mha_reference(
                np.asarray(q)[i][None, :, None, :],
                jnp.asarray(kk.transpose(1, 0, 2)[None]),
                jnp.asarray(vv.transpose(1, 0, 2)[None]),
                kv_lens=jnp.asarray([start + i + 1]))
            np.testing.assert_allclose(
                out[i], np.asarray(ref)[0, :, 0, :], atol=2e-6)

    def test_pallas_kernel_matches_reference(self):
        q, kp, vp, pages, start = self._setup(seed=1)
        ref = np.asarray(paged_prefill_attention(q, kp, vp, pages, start,
                                                 impl="reference"))
        pal = np.asarray(paged_prefill_attention(
            q, kp, vp, pages, jnp.int32(start), impl="pallas",
            interpret=True))
        np.testing.assert_allclose(pal, ref, atol=2e-6)

    def test_chunk_split_invariance_bitwise(self):
        # one C-row call must equal two C/2-row calls BITWISE (same pool
        # content, fixed key width): the row-independence property that
        # makes chunked == monolithic prefill exact
        q, kp, vp, pages, start = self._setup(seed=2)
        C = q.shape[0]
        full = np.asarray(paged_prefill_attention(q, kp, vp, pages, start,
                                                  impl="reference"))
        lo = np.asarray(paged_prefill_attention(
            q[:C // 2], kp, vp, pages, start, impl="reference"))
        hi = np.asarray(paged_prefill_attention(
            q[C // 2:], kp, vp, pages, start + C // 2, impl="reference"))
        assert np.concatenate([lo, hi]).tobytes() == full.tobytes()

    def test_page_indirection_bitwise(self):
        q, kp, vp, pages, start = self._setup(seed=3)
        out1 = np.asarray(paged_prefill_attention(q, kp, vp, pages, start,
                                                  impl="reference"))
        perm = np.array([0, 8, 7, 6, 5, 4, 3, 2, 1])
        inv = np.argsort(perm)
        out2 = np.asarray(paged_prefill_attention(
            q, jnp.asarray(np.asarray(kp)[perm]),
            jnp.asarray(np.asarray(vp)[perm]),
            jnp.asarray(inv[np.asarray(pages)].astype(np.int32)),
            start, impl="reference"))
        assert out1.tobytes() == out2.tobytes()


class TestStackedFoldedPools:
    """The form the step programs use: the cache's STORED pools
    ``[L, P, ps, H*Dh]`` (heads folded head-major into the lanes) plus a
    static ``layer``, addressed in place by (layer, page) — against the
    reference over that layer's unfolded pool, for every layer of a
    3-layer stack whose layers all differ (a wrong ``layer`` reads another
    layer's pages and fails)."""

    L, P, ps, H, Dh = 3, 11, 4, 2, 8

    def _stack(self, seed, dtype=jnp.float32):
        rng = np.random.RandomState(seed)
        shape = (self.L, self.P, self.ps, self.H, self.Dh)
        k5 = jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(dtype)
        v5 = jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(dtype)
        fold = shape[:3] + (self.H * self.Dh,)
        return k5, v5, k5.reshape(fold), v5.reshape(fold)

    def _decode_args(self, seed, qdtype):
        rng = np.random.RandomState(100 + seed)
        q = jnp.asarray(rng.randn(4, self.H, self.Dh).astype(np.float32))
        pt = jnp.asarray(np.array([[1, 2, 3], [4, 0, 0], [5, 6, 7],
                                   [0, 0, 0]], np.int32))
        lens = jnp.asarray(np.array([11, 3, 12, 0], np.int32))
        return q.astype(qdtype), pt, lens

    def _prefill_args(self, seed, qdtype):
        rng = np.random.RandomState(200 + seed)
        q = jnp.asarray(rng.randn(8, self.H, self.Dh).astype(np.float32))
        return (q.astype(qdtype), jnp.asarray(np.array([1, 3, 5, 7],
                                                       np.int32)), 4)

    @pytest.mark.parametrize("qdtype", [jnp.float32, jnp.bfloat16],
                             ids=["q-f32", "q-bf16"])
    @pytest.mark.parametrize("layer", [0, 1, 2])
    def test_decode_kernel_every_layer(self, layer, qdtype):
        k5, v5, kf, vf = self._stack(seed=layer)
        q, pt, lens = self._decode_args(layer, qdtype)
        ref = np.asarray(paged_decode_attention(
            q.astype(jnp.float32), k5[layer], v5[layer], pt, lens,
            impl="reference"))
        pal = paged_decode_attention(q, kf, vf, pt, lens, impl="pallas",
                                     interpret=True, layer=layer)
        assert pal.dtype == qdtype and pal.shape == q.shape
        pal = np.asarray(pal.astype(jnp.float32))
        tol = 2e-6 if qdtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(pal, ref, atol=tol)
        assert (pal[3] == 0).all()              # kv_lens == 0: exact zeros
        # the layers differ, so another layer's pages do not pass
        other = np.asarray(paged_decode_attention(
            q, kf, vf, pt, lens, impl="pallas", interpret=True,
            layer=(layer + 1) % self.L).astype(jnp.float32))
        assert np.abs(other[:3] - ref[:3]).max() > 0.05

    @pytest.mark.parametrize("qdtype", [jnp.float32, jnp.bfloat16],
                             ids=["q-f32", "q-bf16"])
    @pytest.mark.parametrize("layer", [0, 1, 2])
    def test_prefill_kernel_every_layer(self, layer, qdtype):
        k5, v5, kf, vf = self._stack(seed=10 + layer)
        q, pages, start = self._prefill_args(layer, qdtype)
        ref = np.asarray(paged_prefill_attention(
            q.astype(jnp.float32), k5[layer], v5[layer], pages, start,
            impl="reference"))
        pal = paged_prefill_attention(q, kf, vf, pages, jnp.int32(start),
                                      impl="pallas", interpret=True,
                                      layer=layer)
        assert pal.dtype == qdtype and pal.shape == q.shape
        pal = np.asarray(pal.astype(jnp.float32))
        tol = 2e-6 if qdtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(pal, ref, atol=tol)
        other = np.asarray(paged_prefill_attention(
            q, kf, vf, pages, jnp.int32(start), impl="pallas",
            interpret=True, layer=(layer + 1) % self.L).astype(jnp.float32))
        assert np.abs(other - ref).max() > 0.05

    @pytest.mark.parametrize("impl", ["reference", "pallas"])
    @pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.bfloat16],
                             ids=["kv-f32", "kv-bf16"])
    def test_one_layer_entry_equals_stacked_bitwise_decode(self, kv_dtype,
                                                           impl):
        # the 4-D entry folds into a one-layer stack and runs the SAME
        # engine: what the smokes check is what the step programs serve
        k5, v5, kf, vf = self._stack(seed=20, dtype=kv_dtype)
        q, pt, lens = self._decode_args(0, jnp.float32)
        for layer in range(self.L):
            one = np.asarray(paged_decode_attention(
                q, k5[layer], v5[layer], pt, lens, impl=impl))
            stacked = np.asarray(paged_decode_attention(
                q, kf, vf, pt, lens, impl=impl, layer=layer))
            assert one.tobytes() == stacked.tobytes()

    @pytest.mark.parametrize("impl", ["reference", "pallas"])
    @pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.bfloat16],
                             ids=["kv-f32", "kv-bf16"])
    def test_one_layer_entry_equals_stacked_bitwise_prefill(self, kv_dtype,
                                                            impl):
        k5, v5, kf, vf = self._stack(seed=21, dtype=kv_dtype)
        q, pages, start = self._prefill_args(0, jnp.float32)
        for layer in range(self.L):
            one = np.asarray(paged_prefill_attention(
                q, k5[layer], v5[layer], pages, start, impl=impl))
            stacked = np.asarray(paged_prefill_attention(
                q, kf, vf, pages, start, impl=impl, layer=layer))
            assert one.tobytes() == stacked.tobytes()

    @pytest.mark.parametrize("fn", ["decode", "prefill"])
    def test_pool_shape_must_match_the_form(self, fn):
        k5, v5, kf, vf = self._stack(seed=22)
        if fn == "decode":
            q, pt, lens = self._decode_args(0, jnp.float32)
            call = lambda k, v, **kw: paged_decode_attention(  # noqa: E731
                q, k, v, pt, lens, impl="reference", **kw)
        else:
            q, pages, start = self._prefill_args(0, jnp.float32)
            call = lambda k, v, **kw: paged_prefill_attention(  # noqa: E731
                q, k, v, pages, start, impl="reference", **kw)
        with pytest.raises(ValueError, match="one layer's"):
            call(kf, vf)                       # a stack without layer=
        with pytest.raises(ValueError, match="stored stack"):
            call(k5[0], v5[0], layer=0)        # an unfolded pool with layer=
