"""Flash attention (pallas, interpret on cpu) vs reference; ring attention
on the 8-device cpu mesh vs full attention — fwd and grads."""
import functools
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import flash_attention as FA
from paddle_tpu.parallel.flash_attention import (flash_attention,
                                                 flash_attention_rows,
                                                 mha_reference)
from paddle_tpu.parallel.ring_attention import ring_attention_sharded
from paddle_tpu.parallel.collective import make_mesh


def _rand_qkv(B=2, H=2, T=64, D=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, T, D), jnp.float32)
    return q, k, v


def _force_bwd(monkeypatch, engine):
    """The backward is chosen from the shape and from nothing else; a test
    that needs one engine at a toy shape replaces the chooser."""
    monkeypatch.setattr(FA, "_bwd_engine", lambda *a, **kw: engine)


def _through_rows(q, k, v, **kw):
    """``flash_attention_rows`` on ``[B, H, T, D]`` data: the heads folded
    into the rows' lanes by the TEST, so what runs is the rows entry alone."""
    H = q.shape[1]
    out = flash_attention_rows(FA._to_rows(q), FA._to_rows(k), FA._to_rows(v),
                               n_head=H, **kw)
    return FA._from_rows(out, H)


_ENTRIES = {"bhtd": flash_attention, "rows": _through_rows}


def _out_and_grads(attn, q, k, v, w, **kw):
    """``attn``'s output and the gradients of ``sum(out * w)`` in q, k, v."""
    def f(q, k, v):
        out = attn(q, k, v, **kw)
        return jnp.sum(out.astype(jnp.float32) * w), out
    (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (out,) + grads


def _assert_out_and_grads_close(got, want):
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=2e-4, atol=2e-4)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


def _rand_qkvw(B, H, T, S, D, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (B, H, T, D), jnp.float32),
            jax.random.normal(ks[1], (B, H, S, D), jnp.float32),
            jax.random.normal(ks[2], (B, H, S, D), jnp.float32),
            jax.random.normal(ks[3], (B, H, T, D), jnp.float32))


@pytest.mark.parametrize("entry", list(_ENTRIES))
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal, entry):
    q, k, v = _rand_qkv()
    out = _ENTRIES[entry](q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


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


def test_flash_uneven_tail_block():
    q, k, v = _rand_qkv(T=40, D=8, seed=2)  # 40 not divisible by 16
    out = flash_attention(q, k, v, None, False, None, 16, 16, True)
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def _small_chooser(monkeypatch, vmem_budget=None):
    """The choosers at toy widths: blocks of at most 16 rows in the forward
    and in the backward (and, with a small budget, only part of S resident a
    forward step), so the interpret-mode shapes below walk the same forms the
    cells' shapes do: several query blocks x several key blocks a head."""
    monkeypatch.setattr(FA, "_FWD_BLOCK", 16)
    monkeypatch.setattr(FA, "_BWD_BLOCK_Q", 16)
    monkeypatch.setattr(FA, "_BWD_BLOCK_K", 16)
    if vmem_budget is not None:
        monkeypatch.setattr(FA, "_FWD_VMEM_BUDGET", vmem_budget)


# (T, S): T = S in several query blocks, T < S (bottom-right-aligned causal),
# an uneven tail in both, and a T of half a query block (several batch rows
# a step, each with the heads its lanes hold)
_CHOSEN_SHAPES = {"T=S": (64, 64), "T<S": (24, 56), "tail": (40, 40),
                  "heads": (8, 8)}
_CHOSEN_LENS = {"full": None, "ragged": lambda S: [S, S // 2 + 1, 3],
                "zero-row": lambda S: [S - 5, 0, S]}


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


# the rows entry itself, [B, T, H * D] in and out: (H, D) = the widths of the
# rows; the lane geometry each gives is test_flash_lane_heads'
_ROWS_WIDTHS = {"HD128": (2, 64), "HD512": (8, 64), "odd-heads": (3, 64),
                "D128": (2, 128), "HD16": (2, 8)}
# (T, S, causal, lens, dtype): self-attention under a causal mask and ragged
# lengths with a sequence of no visible key; cross-attention with more query
# rows than keys (an uneven tail block on both sides); bf16 inputs
_ROWS_CASES = {
    "causal-lens": (32, 32, True, [32, 0, 19], jnp.float32),
    "cross-tail": (40, 24, False, [24, 11, 3], jnp.float32),
    "T<S-causal": (24, 56, True, None, jnp.float32),
    "bf16": (40, 40, True, [40, 21, 3], jnp.bfloat16),
}


# every case at rows of one 128-lane block of two heads, in both engines; the
# other widths under the causal mask and ragged lengths in both engines, and
# each once more in another case
_ROWS_RUNS = (
    [("HD128", case, bwd) for case in _ROWS_CASES for bwd in ("scan", "fused")]
    + [(width, "causal-lens", bwd) for width in list(_ROWS_WIDTHS)[1:]
       for bwd in ("scan", "fused")]
    + [("HD512", "bf16", "fused"), ("odd-heads", "cross-tail", "fused"),
       ("D128", "T<S-causal", "fused"), ("HD16", "cross-tail", "scan")])


@pytest.mark.parametrize("width,case,bwd_impl", _ROWS_RUNS,
                         ids=["-".join(r) for r in _ROWS_RUNS])
def test_flash_rows_entry(width, case, bwd_impl, monkeypatch):
    """``flash_attention_rows`` on the projections' ``[B, T, H * D]`` rows:
    output and the three gradients against the plain reference on the unfolded
    heads, at 16-row blocks; the ``[B, H, T, D]`` entry gives the same BITS on
    the same data (it is the same kernels behind a transpose); a sequence
    with no visible key is exact zeros in all four."""
    _force_bwd(monkeypatch, bwd_impl)
    H, D = _ROWS_WIDTHS[width]
    T, S, causal, lens, dtype = _ROWS_CASES[case]
    B = 3
    q, k, v, w = _rand_qkvw(B, H, T, S, D, seed=18)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    kw = dict(kv_lens=lens and jnp.array(lens, jnp.int32), causal=causal)

    def rows(q, k, v, **kw):
        return flash_attention_rows(q, k, v, n_head=H, block_q=16, block_k=16,
                                    interpret=True, **kw)

    got = _out_and_grads(rows, *(FA._to_rows(x) for x in (q, k, v, w)), **kw)
    assert all(x.shape == (B, n, H * D) and x.dtype == dtype
               for x, n in zip(got, (T, T, S, S)))
    same = _out_and_grads(flash_attention, q, k, v, w, block_q=16, block_k=16,
                          interpret=True, **kw)
    for a, b in zip(got, same):
        np.testing.assert_array_equal(np.asarray(FA._from_rows(a, H)), np.asarray(b))
    want = _out_and_grads(mha_reference, *(x.astype(jnp.float32) for x in (q, k, v)),
                          w, **kw)
    if dtype == jnp.bfloat16:
        # one bf16 rounding of the result (2^-8 relative) on values of order 1
        for a, b in zip(same, want):
            np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                       rtol=2e-2, atol=2e-2)
    else:
        _assert_out_and_grads_close(same, want)
    for b, n in enumerate(lens or ()):
        assert n or not any(np.asarray(x, np.float32)[b].any() for x in got)


def test_flash_rows_refuses_rows_that_hold_no_whole_heads():
    x = jnp.zeros((2, 16, 24), jnp.float32)
    with pytest.raises(ValueError, match="whole heads"):
        flash_attention_rows(x, x, x, n_head=5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    assert jax.device_count() >= 8, "conftest must force 8 cpu devices"
    mesh = make_mesh({"sp": 8})
    q, k, v = _rand_qkv(B=1, H=2, T=64, D=8, seed=3)
    out = ring_attention_sharded(q, k, v, mesh, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ring_attention_grad():
    mesh = make_mesh({"sp": 4})
    q, k, v = _rand_qkv(B=1, H=1, T=32, D=8, seed=4)

    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel.ring_attention import ring_attention

    spec = P(None, None, "sp", None)

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=P(), check_vma=False)
    def loss_ring(qs, ks, vs):
        o = ring_attention(qs, ks, vs, "sp")
        return jax.lax.psum((o ** 2).sum(), "sp")

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v) ** 2).sum()

    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    ge = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


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


def test_transformer_flash_matches_reference_path():
    """use_flash=True transformer produces the same loss/logits as the
    bias-based attention path (dropout off)."""
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T

    rng = np.random.RandomState(0)
    B, L = 2, 16
    src = rng.randint(1, 50, size=(B, L)).astype("int64")
    trg = rng.randint(1, 50, size=(B, L)).astype("int64")
    lbl = rng.randint(1, 50, size=(B, L)).astype("int64")
    src[0, 12:] = T.PAD_IDX
    trg[0, 10:] = T.PAD_IDX
    lbl[0, 10:] = T.PAD_IDX

    results = {}
    for use_flash in (False, True):
        main = fluid.Program()
        startup = fluid.Program()
        startup.random_seed = 7
        with fluid.program_guard(main, startup):
            sw = fluid.layers.data(name="s", shape=[L], dtype="int64")
            tw = fluid.layers.data(name="t", shape=[L], dtype="int64")
            lw = fluid.layers.data(name="l", shape=[L], dtype="int64")
            avg, s_cost, tok, logits = T.transformer(
                sw, tw, lw, 60, 60, 32, n_layer=2, n_head=2, d_model=32,
                d_inner=64, dropout=0.0, use_flash=use_flash,
            )
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            (lv,) = exe.run(main, feed={"s": src, "t": trg, "l": lbl}, fetch_list=[avg])
        results[use_flash] = float(np.ravel(lv)[0])
    np.testing.assert_allclose(results[True], results[False], rtol=2e-4)


def _attention_program(use_flash, causal=True, n_head=2, d_model=32, L=16):
    """``multi_head_attention`` alone (the flash path under key lengths and,
    with ``causal``, a causal mask) with the gradient of its sum in its
    input: ``(main, startup, fetches)``."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as T

    main, startup = fluid.Program(), fluid.Program()
    startup.random_seed = 7
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[L, d_model], dtype="float32")
        lens = fluid.layers.data(name="lens", shape=[], dtype="int32")
        y = T.multi_head_attention(
            x, None, None, None, d_model // n_head, d_model // n_head, d_model,
            n_head, use_flash=use_flash, flash_causal=causal, kv_lens=lens)
        (dx,) = fluid.backward.calc_gradient(
            fluid.layers.reduce_sum(fluid.layers.square(y)), [x])
    return main, startup, [y.name, dx.name]


def test_flash_path_of_multi_head_attention_has_no_reshape_or_transpose():
    """With ``use_flash`` the kernels read the projections' rows: the graph is
    fc x 3 -> flash_attention(n_head) -> fc and its backward, with no op that
    splits or merges heads; parameter names and shapes are the matmul-softmax
    path's, so a checkpoint written by either (or by the parent) loads."""
    flash, _, _ = _attention_program(True)
    plain, _, _ = _attention_program(False)
    kinds = [op.type for op in flash.global_block().ops]
    assert kinds.count("flash_attention") == 1
    assert not [k for k in kinds if "transpose" in k or "reshape" in k], kinds
    assert any("transpose" in k for k in (op.type for op in plain.global_block().ops))
    (op,) = [op for op in flash.global_block().ops if op.type == "flash_attention"]
    assert op.attrs["n_head"] == 2
    assert all(len(flash.global_block().var(n).shape) == 3
               for n in op.input("Q") + op.input("K") + op.input("V") + op.output("Out"))

    def params(program):
        return sorted((p.name, tuple(p.shape)) for p in program.all_parameters())
    assert params(flash) == params(plain) and len(params(flash)) == 4


def test_flash_op_without_the_head_attribute_runs_and_differentiates():
    """A program saved before ``n_head`` existed holds the op on [B, H, T, D]
    inputs and no such attribute: it lowers as before (the same kernels behind
    a transpose) and gives what the rows op gives on the same data."""
    import paddle_tpu as fluid

    B, H, L, D = 3, 2, 16, 8
    rng = np.random.RandomState(3)
    feed = {n: rng.randn(B, H, L, D).astype("float32") for n in "qkv"}
    feed["lens"] = np.array([16, 9, 0], "int32")
    got = {}
    for form in ("bhtd", "rows"):
        shape = [H, L, D] if form == "bhtd" else [L, H * D]
        main, startup = fluid.Program(), fluid.Program()
        with fluid.unique_name.guard(), fluid.program_guard(main, startup):
            q, k, v = (fluid.layers.data(name=n, shape=shape, dtype="float32")
                       for n in "qkv")
            lens = fluid.layers.data(name="lens", shape=[], dtype="int32")
            out = fluid.layers.flash_attention(
                q, k, v, kv_lens=lens, causal=True,
                n_head=H if form == "rows" else None)
            grads = fluid.backward.calc_gradient(
                fluid.layers.reduce_sum(fluid.layers.square(out)), [q, k, v])
        (op,) = [op for op in main.global_block().ops if op.type == "flash_attention"]
        assert ("n_head" in op.attrs) == (form == "rows")
        fed = dict(feed) if form == "bhtd" else dict(
            feed, **{n: np.asarray(FA._to_rows(jnp.asarray(feed[n]))) for n in "qkv"})
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            vals = exe.run(main, feed=fed, fetch_list=[out] + grads)
        got[form] = [np.asarray(x) for x in vals]
        assert all(np.isfinite(x).all() and x.any() for x in got[form])
    # (not the same bits here: inside one jitted CPU program XLA fuses the
    # interpreted kernel with the transposes around it; test_flash_rows_entry
    # holds the two entries to the same bits call by call)
    for a, b in zip(got["bhtd"], got["rows"]):
        np.testing.assert_allclose(np.asarray(FA._to_rows(jnp.asarray(a))), b,
                                   rtol=1e-5, atol=1e-5)
    ref = mha_reference(*(jnp.asarray(feed[n]) for n in "qkv"), causal=True,
                        kv_lens=jnp.asarray(feed["lens"]))
    np.testing.assert_allclose(got["bhtd"][0], np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_flash_layer_refuses_rows_of_the_wrong_rank():
    import paddle_tpu as fluid

    with fluid.program_guard(fluid.Program(), fluid.Program()):
        q = fluid.layers.data(name="q", shape=[2, 16, 8], dtype="float32")
        with pytest.raises(ValueError, match="n_head"):
            fluid.layers.flash_attention(q, q, q, n_head=2)


def test_flash_and_plain_attention_blocks_agree_with_gradients():
    """The rows path against the matmul-softmax path of the same block on the
    same weights (no mask: full key lengths, not causal): output and the
    gradient that reaches the block's input."""
    import paddle_tpu as fluid

    rng = np.random.RandomState(1)
    feed = {"x": rng.randn(3, 16, 32).astype("float32"),
            "lens": np.array([16, 16, 16], "int32")}
    got = {}
    for use_flash in (False, True):
        main, startup, fetches = _attention_program(use_flash, causal=False)
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup)
            got[use_flash] = [np.asarray(v) for v in exe.run(
                main, feed=feed, fetch_list=fetches)]
    for a, b in zip(got[True], got[False]):
        assert a.shape == (3, 16, 32) and b.any()
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
