"""Flash-attention decode-contract edge cases (CPU interpret mode) + the
paged decode attention engines.

The decode runtime leans on exactly these properties of the attention
stack (ISSUE 6): a fully masked row (``kv_lens == 0``, an inactive decode
slot) is EXACT ZEROS on every engine; ``kv_lens == S`` degrades to
unmasked attention; a single-token query (``T_q=1``, the decode shape)
against a long KV matches the reference; and mixed per-sequence lengths
in one batch mask independently.  Parity oracle: ``mha_reference``.
"""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from paddle_tpu.parallel.flash_attention import (  # noqa: E402
    flash_attention,
    mha_reference,
    paged_decode_attention,
    paged_prefill_attention,
)


def _rand(shape, seed):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape).astype(np.float32))


def _flash(q, k, v, **kw):
    return flash_attention(q, k, v, interpret=True, **kw)


class TestFlashDecodeContract:
    def test_kv_lens_zero_is_exact_zeros(self):
        B, H, T, S, D = 3, 2, 4, 16, 8
        q, k, v = _rand((B, H, T, D), 0), _rand((B, H, S, D), 1), _rand(
            (B, H, S, D), 2)
        lens = jnp.asarray([0, 7, 0], jnp.int32)
        out = np.asarray(_flash(q, k, v, kv_lens=lens))
        ref = np.asarray(mha_reference(q, k, v, kv_lens=lens))
        # the fully masked rows are exact zeros on BOTH engines (not the
        # degenerate uniform mean a plain softmax would give) ...
        assert (out[0] == 0).all() and (out[2] == 0).all()
        assert (ref[0] == 0).all() and (ref[2] == 0).all()
        # ... and the live row still matches the reference
        np.testing.assert_allclose(out[1], ref[1], atol=2e-6)

    def test_kv_lens_full_matches_unmasked(self):
        B, H, T, S, D = 2, 2, 8, 8, 8
        q, k, v = _rand((B, H, T, D), 3), _rand((B, H, S, D), 4), _rand(
            (B, H, S, D), 5)
        lens = jnp.full((B,), S, jnp.int32)
        out = np.asarray(_flash(q, k, v, kv_lens=lens))
        ref = np.asarray(mha_reference(q, k, v))
        np.testing.assert_allclose(out, ref, atol=2e-6)

    def test_single_token_query_long_kv(self):
        # the decode shape: T_q=1 against a long cache, causal and not
        B, H, S, D = 2, 2, 256, 8
        q = _rand((B, H, 1, D), 6)
        k, v = _rand((B, H, S, D), 7), _rand((B, H, S, D), 8)
        lens = jnp.asarray([S, 100], jnp.int32)
        for causal in (False, True):
            out = np.asarray(_flash(q, k, v, kv_lens=lens, causal=causal))
            ref = np.asarray(
                mha_reference(q, k, v, kv_lens=lens, causal=causal))
            np.testing.assert_allclose(out, ref, atol=2e-6)

    def test_mixed_length_batch(self):
        B, H, T, S, D = 5, 2, 16, 64, 8
        q, k, v = _rand((B, H, T, D), 9), _rand((B, H, S, D), 10), _rand(
            (B, H, S, D), 11)
        lens = jnp.asarray([0, 1, 17, 63, 64], jnp.int32)
        out = np.asarray(_flash(q, k, v, kv_lens=lens))
        ref = np.asarray(mha_reference(q, k, v, kv_lens=lens))
        assert (out[0] == 0).all() and (ref[0] == 0).all()
        np.testing.assert_allclose(out, ref, atol=2e-6)

    def test_mixed_length_causal_cross_length(self):
        B, H, T, S, D = 3, 2, 8, 32, 8
        q, k, v = _rand((B, H, T, D), 12), _rand((B, H, S, D), 13), _rand(
            (B, H, S, D), 14)
        lens = jnp.asarray([5, 20, 32], jnp.int32)
        out = np.asarray(_flash(q, k, v, kv_lens=lens, causal=True))
        ref = np.asarray(mha_reference(q, k, v, kv_lens=lens, causal=True))
        np.testing.assert_allclose(out, ref, atol=2e-6)


class TestPagedDecodeAttention:
    def _setup(self, seed=0, S=4, H=2, Dh=8, P=11, ps=4, MP=3):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(S, H, Dh).astype(np.float32))
        kp = jnp.asarray(rng.randn(P, ps, H, Dh).astype(np.float32))
        vp = jnp.asarray(rng.randn(P, ps, H, Dh).astype(np.float32))
        pt = jnp.asarray(np.array([[1, 2, 3], [4, 0, 0], [5, 6, 7],
                                   [0, 0, 0]], np.int32))
        lens = jnp.asarray(np.array([11, 3, 12, 0], np.int32))
        return q, kp, vp, pt, lens

    def test_reference_matches_mha_per_slot(self):
        q, kp, vp, pt, lens = self._setup()
        out = np.asarray(paged_decode_attention(q, kp, vp, pt, lens,
                                                impl="reference"))
        kk = np.asarray(kp)[np.asarray(pt)]
        vv = np.asarray(vp)[np.asarray(pt)]
        S, MP, ps, H, Dh = kk.shape
        kk = kk.reshape(S, MP * ps, H, Dh)
        vv = vv.reshape(S, MP * ps, H, Dh)
        for s in range(S):
            ref = mha_reference(
                np.asarray(q)[s][None, :, None, :],
                jnp.asarray(kk[s].transpose(1, 0, 2)[None]),
                jnp.asarray(vv[s].transpose(1, 0, 2)[None]),
                kv_lens=jnp.asarray([int(lens[s])]))
            np.testing.assert_allclose(
                out[s], np.asarray(ref)[0, :, 0, :], atol=2e-6)
        assert (out[3] == 0).all()  # inactive slot

    def test_pallas_kernel_matches_reference(self):
        # the TPU scalar-prefetch page-table kernel, interpreted on CPU
        q, kp, vp, pt, lens = self._setup(seed=1)
        ref = np.asarray(paged_decode_attention(q, kp, vp, pt, lens,
                                                impl="reference"))
        pal = np.asarray(paged_decode_attention(q, kp, vp, pt, lens,
                                                impl="pallas",
                                                interpret=True))
        np.testing.assert_allclose(pal, ref, atol=2e-6)
        assert (pal[3] == 0).all()

    def test_page_table_indirection(self):
        # same kv content through two different physical page layouts
        # must give identical results: attention reads PAGES, not offsets
        q, kp, vp, pt, lens = self._setup(seed=2)
        out1 = np.asarray(paged_decode_attention(q, kp, vp, pt, lens,
                                                 impl="reference"))
        perm = np.array([0, 8, 9, 10, 1, 2, 3, 4, 5, 6, 7])  # page renames
        inv = np.argsort(perm)
        kp2 = jnp.asarray(np.asarray(kp)[perm])
        vp2 = jnp.asarray(np.asarray(vp)[perm])
        pt2 = jnp.asarray(inv[np.asarray(pt)].astype(np.int32))
        out2 = np.asarray(paged_decode_attention(q, kp2, vp2, pt2, lens,
                                                 impl="reference"))
        assert out1.tobytes() == out2.tobytes()


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


class TestTheDecodeWalk:
    """The plain decode kernel walks a slot's OWN pages, many to a turn
    (PR 31): a slot visits ``ceil(kv_len / ps)`` pages, one online-softmax
    update a turn of ``FA._decode_turn_pages(..) * ps`` keys, and nothing
    past ``kv_len`` is copied or computed.  Interpret mode against
    ``_paged_reference`` on a 3-layer stack whose table spans more than a
    turn, at the chooser's own turn (one or two turns a slot) and at a turn
    patched small (up to 20 turns a slot)."""

    L, S, H, Dh, ps, MP = 3, 8, 2, 8, 8, 80

    @pytest.fixture(params=[None, 32], ids=["turn-chosen", "turn32"])
    def turn(self, request, monkeypatch):
        from paddle_tpu.parallel import flash_attention as FA

        if request.param is not None:
            monkeypatch.setattr(FA, "_DECODE_TURN_KEYS", request.param)
        turn = self.ps * FA._decode_turn_pages(
            self.ps, self.H * self.Dh, self.MP, 4, self.H)
        assert turn == (request.param or 512) < self.MP * self.ps
        return turn

    def _stack(self, seed, kv_dtype=jnp.float32):
        rng = np.random.RandomState(seed)
        P = self.S * self.MP + 1
        shape = (self.L, P, self.ps, self.H * self.Dh)
        k = jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(kv_dtype)
        v = jnp.asarray(rng.randn(*shape).astype(np.float32)).astype(kv_dtype)
        # every slot its own pages, in an order of the seed's
        pt = 1 + rng.permutation(self.S * self.MP).reshape(self.S, self.MP)
        q = jnp.asarray(rng.randn(self.S, self.H, self.Dh).astype(np.float32))
        return q, k, v, jnp.asarray(pt.astype(np.int32))

    def _edge_lens(self, turn):
        ps = self.ps
        return jnp.asarray(np.array(
            [0, 1, ps - 1, ps, turn - 1, turn, turn + 1, self.MP * ps],
            np.int32))

    @staticmethod
    def _kernel(q, k, v, pt, lens, layer):
        return paged_decode_attention(q, k, v, pt, lens, impl="pallas",
                                      interpret=True, layer=layer)

    @staticmethod
    def _reference(q, k, v, pt, lens, layer):
        return np.asarray(paged_decode_attention(
            q.astype(jnp.float32), k, v, pt, lens, impl="reference",
            layer=layer))

    @pytest.mark.parametrize("kv_dtype", [jnp.float32, jnp.bfloat16],
                             ids=["kv-f32", "kv-bf16"])
    @pytest.mark.parametrize("qdtype", [jnp.float32, jnp.bfloat16],
                             ids=["q-f32", "q-bf16"])
    @pytest.mark.parametrize("layer", [0, 2], ids=["layer0", "layerL-1"])
    def test_edge_lengths_mixed_in_one_batch(self, turn, layer, qdtype,
                                             kv_dtype):
        q, k, v, pt = self._stack(seed=layer, kv_dtype=kv_dtype)
        lens = self._edge_lens(turn)
        q = q.astype(qdtype)
        ref = self._reference(q, k, v, pt, lens, layer)
        pal = self._kernel(q, k, v, pt, lens, layer)
        assert pal.dtype == qdtype and pal.shape == q.shape
        pal = np.asarray(pal.astype(jnp.float32))
        # the kernel is f32 whatever the pool holds: only q's own rounding
        np.testing.assert_allclose(
            pal, ref, atol=3e-6 if qdtype == jnp.float32 else 2e-2)
        assert not pal[0].any()                 # kv_lens == 0: exact zeros

    @pytest.mark.parametrize("where", ["past_kv_len", "tail_of_partial_page"])
    @pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf],
                             ids=["nan", "inf", "-inf"])
    def test_garbage_outside_kv_len_is_never_read(self, turn, bad, where):
        q, k, v, pt = self._stack(seed=5)
        ps = self.ps
        lens = np.array([0, 3, ps + 1, turn - 3, turn + 2, 2 * ps, 5,
                         self.MP * ps - 2], np.int32)
        clean = np.asarray(self._kernel(q, k, v, pt, jnp.asarray(lens), 1))
        kn, vn = np.array(k), np.array(v)
        for s, n in enumerate(lens):
            used = -(-int(n) // ps)
            if where == "past_kv_len":
                for arr in (kn, vn):
                    arr[1, np.asarray(pt)[s, used:]] = bad
            elif n % ps:
                for arr in (kn, vn):
                    arr[1, int(pt[s, used - 1]), n % ps:] = bad
        dirty = np.asarray(self._kernel(q, jnp.asarray(kn), jnp.asarray(vn),
                                        pt, jnp.asarray(lens), 1))
        assert np.isfinite(dirty).all()
        assert dirty.tobytes() == clean.tobytes()

    @pytest.mark.parametrize("neighbours", ["empty", "short", "full"])
    @pytest.mark.parametrize("own", ["one_key", "a_turn_and_a_bit", "full"])
    def test_row_independence_bitwise(self, turn, own, neighbours):
        """A slot's output depends on its own query, pages and length only:
        continuous batching equals per-sequence serving."""
        q, k, v, pt = self._stack(seed=9)
        full = self.MP * self.ps
        mine = {"one_key": 1, "a_turn_and_a_bit": turn + 5, "full": full}[own]
        theirs = {"empty": 0, "short": 3, "full": full}[neighbours]
        outs = []
        for others in (theirs, turn // 2 + 1):
            lens = np.full(self.S, others, np.int32)
            lens[3] = mine
            outs.append(np.asarray(
                self._kernel(q, k, v, pt, jnp.asarray(lens), 0))[3])
        assert outs[0].tobytes() == outs[1].tobytes()

    def test_shuffled_tables_read_pages_not_offsets(self, turn):
        q, k, v, pt = self._stack(seed=11)
        lens = self._edge_lens(turn)
        out1 = np.asarray(self._kernel(q, k, v, pt, lens, 2))
        perm = np.random.RandomState(12).permutation(k.shape[1])
        inv = np.argsort(perm)
        out2 = np.asarray(self._kernel(
            q, k[:, perm], v[:, perm],
            jnp.asarray(inv[np.asarray(pt)].astype(np.int32)), lens, 2))
        assert out1.tobytes() == out2.tobytes()

    def test_grid_steps_counter_is_one_step_a_slot(self, turn):
        from paddle_tpu import observability as obs

        q, k, v, pt = self._stack(seed=13)
        self._kernel(q, k, v, pt, self._edge_lens(turn), 0)
        steps = obs.counter("paged.decode.grid_steps", labels={
            "S": self.S, "mp": self.MP, "ps": self.ps, "turn": turn})
        assert steps.value == self.S            # was S * mp = 640



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
