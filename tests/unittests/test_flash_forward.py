"""The training flash kernel, forward: against the reference on both
entries, the chosen tiles, how it lowers for the chip, its grid-step counter
(CPU interpret mode).  Cut from test_flash_ring_attention.py along the
kernel families (``_flash_cases.py`` holds what the files share)."""
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
def test_flash_matches_reference(causal, entry):
    q, k, v = _rand_qkv()
    out = _ENTRIES[entry](q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_causal_offset_when_T_ne_S():
    """Causal mask for cross-length attention is bottom-right aligned
    (tril(k=S-T)): decoder-with-cache shapes, T < S."""
    B, H, T, S, D = 2, 2, 24, 56, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
    out = flash_attention(q, k, v, None, True, None, 16, 16, True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)

    gf = jax.grad(lambda a, b, c: (flash_attention(a, b, c, None, True, None, 16, 16, True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda a, b, c: (mha_reference(a, b, c, causal=True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


# explicit 128 x 128 blocks at a toy length, and the chooser's own tiles at the
# longest training cell's [32, 4096, 64] f32, where the backward's query side
# is resident and walked inside the step
_LOWERED = {"T256-bf16-128x128": ((2, 4, 256, 64), jnp.bfloat16, 128),
            "s4096-f32-chosen": ((4, 8, 4096, 64), jnp.float32, None)}


@pytest.mark.parametrize("case", list(_LOWERED))
@pytest.mark.parametrize("causal,with_lens", [(False, False), (True, False), (True, True)])
def test_flash_lowers_for_tpu(causal, with_lens, case):
    """Compile gate: the Pallas kernels must produce a valid Mosaic TPU
    module (block specs, scalar prefetch) — lowered cross-platform from the
    CPU test host via jax.export, no TPU execution."""
    (B, H, T, D), dtype, block = _LOWERED[case]
    q = jax.ShapeDtypeStruct((B, H, T, D), dtype)
    lens = jnp.full((B,), T, jnp.int32) if with_lens else None

    def f(q, k, v):
        return flash_attention(q, k, v, lens, causal, None, block, block, False)

    from jax import export as jax_export  # plain `jax.export` attribute is
    # version-dependent; the submodule import works on every release in use

    exported = jax_export.export(jax.jit(f), platforms=["tpu"])(q, q, q)
    assert "tpu_custom_call" in exported.mlir_module()

    # the fused one-grid backward (dq+dkv in a single kernel) lowers too: it
    # is what the chooser gives both shapes
    assert FA._bwd_engine(B, H, T, T, D, q.dtype.itemsize, block, block) == "fused"

    def g(q, k, v):
        return (flash_attention(q, k, v, lens, causal, None, block, block, False)
                .astype(jnp.float32) ** 2).sum()

    exported_fused = jax.export.export(
        jax.jit(jax.grad(g, argnums=(0, 1, 2))), platforms=["tpu"])(q, q, q)
    # forward + 1 backward pallas_call
    assert exported_fused.mlir_module().count("tpu_custom_call") >= 2


def test_flash_uneven_tail_block():
    q, k, v = _rand_qkv(T=40, D=8, seed=2)  # 40 not divisible by 16
    out = flash_attention(q, k, v, None, False, None, 16, 16, True)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


# (T, S): T = S in several query blocks, T < S (bottom-right-aligned causal),
# an uneven tail in both, and a T of half a query block (several batch rows
# a step, each with the heads its lanes hold)
_CHOSEN_SHAPES = {"T=S": (64, 64), "T<S": (24, 56), "tail": (40, 40),
                  "heads": (8, 8)}


def _check_chosen_tiles(monkeypatch, bwd_impl, B, H, T, S, D, lens, causal, seed):
    """block_q = block_k = None at toy widths, one backward engine: output and
    the three gradients against the plain reference; a sequence with no
    visible key (``lens[b] == 0``) comes out as exact zeros, in the output
    and in dq, dk and dv."""
    _force_bwd(monkeypatch, bwd_impl)
    monkeypatch.setattr(FA, "DEFAULT_BLOCK_K", 16)
    q, k, v, w = _rand_qkvw(B, H, T, S, D, seed)
    kw = dict(kv_lens=lens and jnp.array(lens, jnp.int32), causal=causal)
    got = _out_and_grads(flash_attention, q, k, v, w, **kw)
    _assert_out_and_grads_close(got, _out_and_grads(mha_reference, q, k, v, w, **kw))
    for b, n in enumerate(lens or ()):
        assert n or not any(np.asarray(x)[b].any() for x in got)


@pytest.mark.parametrize("bwd_impl", ["scan", "fused"])
@pytest.mark.parametrize("lens", list(_CHOSEN_LENS))
@pytest.mark.parametrize("shape", list(_CHOSEN_SHAPES))
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_chosen_tiles_match_reference(causal, shape, lens, bwd_impl,
                                            monkeypatch):
    """block_q = block_k = None: the forward's tiles come from the shape
    (``_fwd_tiles``); output and the three
    gradients against the plain reference; the backward's come from the shape
    too (``_bwd_tiles``)."""
    _small_chooser(monkeypatch)
    T, S = _CHOSEN_SHAPES[shape]
    B, H, D = 3, 2, 8
    batches, heads, bq, bk, chunks = FA._fwd_tiles(B, H, T, S, D, 4)
    bwd_batches, bwd_heads, bwd_bq, bwd_bk = FA._bwd_blocks(B, H, T, S, D, 4)
    # H * D = 16 lanes: one block of lanes holds both heads
    assert heads == bwd_heads == H
    if shape == "heads":
        assert batches > 1 and bq == T
        assert bwd_batches > 1 and (bwd_bq, bwd_bk) == (T, S)
    else:
        assert batches == 1 and bq < T and (chunks > 1 or bk * chunks < S)
        # several query blocks x several key blocks, one batch row a step
        assert bwd_batches == 1 and bwd_bq < T and 2 * bwd_bk <= S
    kv_lens = _CHOSEN_LENS[lens] and _CHOSEN_LENS[lens](S)
    _check_chosen_tiles(monkeypatch, bwd_impl, B, H, T, S, D, kv_lens, causal,
                        seed=11)


@pytest.mark.parametrize("bwd_impl", ["scan", "fused"])
@pytest.mark.parametrize("lens", list(_CHOSEN_LENS))
def test_flash_chosen_tiles_cross_attention_T_gt_S(lens, bwd_impl, monkeypatch):
    """A target longer than its source (the encoder-decoder cross attention,
    non-causal): more query rows than keys, in both engines."""
    _small_chooser(monkeypatch)
    B, H, T, S, D = 3, 2, 56, 24, 8
    kv_lens = _CHOSEN_LENS[lens] and _CHOSEN_LENS[lens](S)
    _check_chosen_tiles(monkeypatch, bwd_impl, B, H, T, S, D, kv_lens, False,
                        seed=14)


@pytest.mark.parametrize("bwd_impl", ["scan", "fused"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_flash_chosen_tiles_with_part_of_S_resident(causal, bwd_impl, monkeypatch):
    """A budget that holds only part of S a step: the grid gets its key axis
    back, and a key span no row of the query block sees is clamped to the
    last one seen (no copy, no turn).  The ``lse`` that form leaves feeds
    each backward."""
    _small_chooser(monkeypatch, vmem_budget=170 * 1024)
    B, H, T, S, D = 3, 1, 48, 80, 8
    batches, heads, bq, bk, chunks = FA._fwd_tiles(B, H, T, S, D, 4)
    assert batches == 1 and -(-S // (bk * chunks)) > 2
    _check_chosen_tiles(monkeypatch, bwd_impl, B, H, T, S, D, [S, 21, 0],
                        causal, seed=12)


# the benchmark's three training shapes [B, H, T, D], the backward engine each
# takes and the kernel's tiles (batch rows, heads in a block's lanes, query
# rows, keys) there: ONE kernel, two heads of 64 lanes a block of 128, and
# several batch rows a step where one tile holds all of T
_CELL_SHAPES = [((64, 8, 256, 64), "fused", (4, 2, 256, 256)),
                ((8, 8, 2048, 64), "fused", (1, 2, 512, 512)),
                ((4, 8, 4096, 64), "fused", (1, 2, 512, 512))]


@pytest.mark.parametrize("itemsize", [4, 2], ids=["f32", "bf16"])
@pytest.mark.parametrize("shape,engine,bwd_tiles", _CELL_SHAPES,
                         ids=["s256", "s2048", "s4096"])
def test_flash_chooser_at_the_cells_shapes(shape, engine, bwd_tiles, itemsize):
    B, H, T, D = shape
    batches, heads, bq, bk, chunks = FA._fwd_tiles(B, H, T, T, D, itemsize)
    need = FA._fwd_vmem_bytes(batches, heads, bq, bk, chunks, D, itemsize)
    assert need <= FA._FWD_VMEM_BUDGET and need < FA._vmem_limit(need) <= 32 * 2 ** 20
    # a block of lanes is whole 128-lane tiles of whole heads
    assert heads == FA._lane_heads(H, D) == 2 and heads * D == 128
    assert B % batches == 0 and bq <= T and bk * chunks == T  # all of S resident
    # a step worth taking: at least sixteen of the old 128 x 128 tiles a head
    assert batches * heads * bq * bk * chunks >= 32 * 128 * 128
    # a short sequence does not pay for a long one's tiles
    assert (batches > 1) == (T <= FA._FWD_BLOCK)
    # the backward: the engine, the kernel's tiles, and a residency inside the
    # budget and inside the limit the kernel is compiled with (under half of
    # v5e's 128 MiB of VMEM a core)
    assert FA._bwd_engine(B, H, T, T, D, itemsize) == engine
    assert (engine == "fused") == (T >= FA._BWD_MIN_T)
    assert FA._bwd_blocks(B, H, T, T, D, itemsize) == bwd_tiles
    need = FA._bwd_vmem_bytes(*bwd_tiles, T, D, itemsize)
    assert need <= FA._BWD_VMEM_BUDGET and need < FA._vmem_limit(need) <= 64 * 2 ** 20
    assert B % bwd_tiles[0] == 0 and T % bwd_tiles[2] == 0 == T % bwd_tiles[3]
    # the residency is the query side's: at this many rows the scan takes
    # over, as it does under the least T the chip measured the kernel at
    assert FA._bwd_engine(B, H, 16 * 4096, 16 * 4096, D, itemsize) == "scan"
    assert FA._bwd_engine(4 * B, H, 128, 128, D, itemsize) == "scan"


@pytest.mark.parametrize("H,D,heads", [
    (8, 64, 2), (2, 64, 2), (16, 32, 4), (4, 128, 1), (2, 256, 1),  # whole tiles
    (3, 64, 3), (5, 64, 5), (1, 64, 1),  # no count of heads makes whole tiles
    (2, 8, 2), (8, 8, 8),                # H * D under 128: all of it
])
def test_flash_lane_heads(H, D, heads):
    """A block of rows is the fewest heads whose lanes are whole 128-lane
    tiles, and all the heads where no count dividing H is."""
    assert FA._lane_heads(H, D) == heads
    assert H % heads == 0 and (heads == H or heads * D % 128 == 0)


@pytest.mark.parametrize("entry", list(_ENTRIES))
def test_flash_fwd_grid_steps_recorded_once_per_compiled_shape(entry):
    from paddle_tpu import observability as obs

    B, H, T, D = 2, 2, 32, 8
    q, k, v = _rand_qkv(B=B, H=H, T=T, D=D, seed=13)
    batches, heads, bq, bk, chunks = FA._fwd_tiles(B, H, T, T, D, 4)
    labels = {"T": T, "S": T, "block": "%dx%d" % (bq, bk),
              "heads": batches * heads, "bh": B * H, "causal": 1,
              "layout": entry}
    cell = obs.counter("flash.fwd.grid_steps", labels=labels)
    before = cell.value
    f = jax.jit(lambda q, k, v: _ENTRIES[entry](q, k, v, causal=True))
    for _ in range(3):
        f(q, k, v).block_until_ready()
    steps = (B // batches) * (H // heads) * -(-T // bq) * -(-T // (bk * chunks))
    assert cell.value == (before or steps) == steps


def test_flash_kv_lens_padding_mask():
    q, k, v = _rand_qkv(B=3, H=2, T=32, D=8, seed=5)
    lens = jnp.array([32, 17, 5], jnp.int32)
    out = flash_attention(q, k, v, lens, False, None, 16, 16, True)
    ref = mha_reference(q, k, v, kv_lens=lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, lens, False, None, 16, 16, True) ** 2).sum()

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v, kv_lens=lens) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)
