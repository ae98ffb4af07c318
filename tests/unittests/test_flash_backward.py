"""The training flash kernel's backward engines (scan, fused, auto): gradients
against the reference, kv_lens and cross lengths, both sides of the automatic
choice's boundary, bf16 inputs, the grid-step counter (CPU interpret mode)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.parallel import flash_attention as FA
from paddle_tpu.parallel.flash_attention import flash_attention, mha_reference

from _flash_cases import (
    _CHOSEN_LENS,
    _ENTRIES,
    _assert_out_and_grads_close,
    _force_bwd,
    _out_and_grads,
    _rand_qkv,
    _rand_qkvw,
    _small_chooser,
)


@pytest.mark.parametrize("entry", list(_ENTRIES))
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bwd_impl", ["scan", "fused"])
def test_flash_grads_match(causal, bwd_impl, entry, monkeypatch):
    _force_bwd(monkeypatch, bwd_impl)
    q, k, v = _rand_qkv(T=32, D=8, seed=1)

    def loss_flash(q, k, v):
        return (_ENTRIES[entry](q, k, v, causal=causal, block_q=16, block_k=16,
                                interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v, causal=causal) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


def test_flash_fused_bwd_kv_lens_and_cross_length(monkeypatch):
    """Fused one-grid backward under key padding masks and T != S."""
    _force_bwd(monkeypatch, "fused")
    B, H, T, S, D = 2, 2, 24, 40, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
    lens = jnp.array([17, 40], jnp.int32)

    gf = jax.grad(lambda a, b, c: (
        flash_attention(a, b, c, lens, True, None, 16, 16, True) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: (
        mha_reference(a, b, c, causal=True, kv_lens=lens) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


# (T, S) under _BWD_MIN_T; from it on with the kernel's residency inside the
# budget; and outside it: the chooser's three ways, at toy widths
_BOUNDARY_SHAPES = {"under-min-T": ((24, 40), "scan"),
                    "fits": ((32, 40), "fused"),
                    "over-budget": ((64, 64), "scan")}


@pytest.mark.parametrize("lens", ["full", "ragged"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("shape", list(_BOUNDARY_SHAPES))
def test_flash_bwd_auto_on_both_sides_of_its_boundary(shape, causal, lens,
                                                      monkeypatch):
    """``_bwd_engine`` left to choose, its two constants set small: the engine
    it names is the one that runs, and its gradients match the reference."""
    (T, S), engine = _BOUNDARY_SHAPES[shape]
    B, H, D = 3, 2, 8
    monkeypatch.setattr(FA, "_BWD_MIN_T", 32)
    monkeypatch.setattr(FA, "_BWD_VMEM_BUDGET", 300_000)
    assert FA._bwd_engine(B, H, T, S, D, 4, 16, 16) == engine
    ran = []

    def spy(name):
        inner = getattr(FA, "_flash_bwd_" + name)

        def run(*args):
            ran.append(name)
            return inner(*args)
        monkeypatch.setattr(FA, "_flash_bwd_" + name, run)

    spy("scan")
    spy("fused")
    q, k, v, w = _rand_qkvw(B, H, T, S, D, seed=15)
    kv_lens = _CHOSEN_LENS[lens] and jnp.array(_CHOSEN_LENS[lens](S), jnp.int32)
    kw = dict(kv_lens=kv_lens, causal=causal)
    got = _out_and_grads(flash_attention, q, k, v, w, block_q=16, block_k=16, **kw)
    assert ran == [engine]
    _assert_out_and_grads_close(got, _out_and_grads(mha_reference, q, k, v, w, **kw))


@pytest.mark.parametrize("blocks", [16, None], ids=["16x16", "chosen"])
@pytest.mark.parametrize("bwd_impl", ["scan", "fused"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_grads_match_bf16_inputs(causal, bwd_impl, blocks, monkeypatch):
    """bf16 q, k, v (a caller who casts before the kernel, ROADMAP S2c):
    gradients come back bf16 and within bf16's rounding of the f32
    reference on the same (rounded) values, at explicit blocks and at the
    choosers' own (set small: an uneven T in several query blocks)."""
    _force_bwd(monkeypatch, bwd_impl)
    _small_chooser(monkeypatch)
    T = 32 if blocks else 40
    q, k, v, w = _rand_qkvw(2, 2, T, T, 8, seed=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    lens = jnp.array([T, 19], jnp.int32)
    got = _out_and_grads(flash_attention, qb, kb, vb, w, kv_lens=lens,
                         causal=causal, block_q=blocks, block_k=blocks)
    want = _out_and_grads(mha_reference, *(x.astype(jnp.float32) for x in (qb, kb, vb)),
                          w, kv_lens=lens, causal=causal)
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        # one bf16 rounding of the result (2^-8 relative) on values of order 1
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("entry", list(_ENTRIES))
@pytest.mark.parametrize("engine", ["fused", "scan"])
def test_flash_bwd_grid_steps_recorded_once_per_compiled_shape(engine, entry,
                                                               monkeypatch):
    from paddle_tpu import observability as obs

    _force_bwd(monkeypatch, engine)
    _small_chooser(monkeypatch)
    B, H, T, D = 2, 2, 48, 8
    q, k, v = _rand_qkv(B=B, H=H, T=T, D=D, seed=17)
    batches, heads, bq, bk = FA._bwd_blocks(B, H, T, T, D, 4)
    if engine == "scan":  # a turn is every (batch, head)'s [T, block_k] strip
        batches, heads, bq, bk = B, H, T, FA.DEFAULT_BLOCK_K
    labels = {"T": T, "S": T, "block": "%dx%d" % (bq, min(bk, T)),
              "heads": batches * heads, "bh": B * H, "causal": 1,
              "engine": engine, "layout": entry}
    cell = obs.counter("flash.bwd.grid_steps", labels=labels)
    before = cell.value
    f = jax.jit(jax.grad(lambda q, k, v: _ENTRIES[entry](q, k, v, causal=True).sum(),
                         argnums=(0, 1, 2)))
    for _ in range(3):
        jax.block_until_ready(f(q, k, v))
    steps = (B // batches) * (H // heads) * -(-T // min(bk, T))
    assert steps == {"fused": 6, "scan": 1}[engine]
    assert cell.value == (before or steps) == steps
