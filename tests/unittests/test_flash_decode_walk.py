"""Flash-attention decode-contract edge cases (CPU interpret mode) + the
paged decode attention engines.

The decode runtime leans on exactly these properties of the attention
stack (ISSUE 6): a fully masked row (``kv_lens == 0``, an inactive decode
slot) is EXACT ZEROS on every engine; ``kv_lens == S`` degrades to
unmasked attention; a single-token query (``T_q=1``, the decode shape)
against a long KV matches the reference; and mixed per-sequence lengths
in one batch mask independently.  Parity oracle: ``mha_reference``.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.parallel.flash_attention import (
    flash_attention,
    mha_reference,
    paged_decode_attention,
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


@functools.lru_cache(maxsize=None)
def _walk(layer, turn_keys):
    """The interpreted decode kernel of one layer at one turn, jitted: cases
    of one static shape trace the ``pallas_call`` once and feed it their
    data.  ``turn_keys`` is ``FA._DECODE_TURN_KEYS`` as the trace will read
    it (the ``turn`` fixture patches it)."""
    return jax.jit(functools.partial(
        paged_decode_attention, impl="pallas", interpret=True, layer=layer))


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
        from paddle_tpu.parallel import flash_attention as FA

        return _walk(layer, FA._DECODE_TURN_KEYS)(q, k, v, pt, lens)

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
        # the counter is the trace's: a trace of this test's own
        paged_decode_attention(q, k, v, pt, self._edge_lens(turn),
                               impl="pallas", interpret=True, layer=0)
        steps = obs.counter("paged.decode.grid_steps", labels={
            "S": self.S, "mp": self.MP, "ps": self.ps, "turn": turn})
        assert steps.value == self.S            # was S * mp = 640
