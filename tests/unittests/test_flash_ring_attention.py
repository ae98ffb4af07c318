"""Flash attention (pallas, interpret on cpu) vs reference; ring attention
on the 8-device cpu mesh vs full attention — fwd and grads."""
import functools
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import flash_attention as FA
from paddle_tpu.parallel.flash_attention import flash_attention, mha_reference
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


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _rand_qkv()
    out = flash_attention(q, k, v, None, causal, None, 32, 32, True)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bwd_impl", ["scan", "fused"])
def test_flash_grads_match(causal, bwd_impl, monkeypatch):
    _force_bwd(monkeypatch, bwd_impl)
    q, k, v = _rand_qkv(T=32, D=8, seed=1)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, None, causal, None, 16, 16, True) ** 2).sum()

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
def test_flash_lowers_for_tpu(causal, with_lens, case, monkeypatch):
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
    # is what the chooser gives the cell's shape (the toy length gets the
    # scan, which is plain XLA)
    if block is None:
        assert FA._bwd_engine(B * H, T, T, D, q.dtype.itemsize) == "fused"
    _force_bwd(monkeypatch, "fused")

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
# an uneven tail in both, and a T of one query block (several heads a step)
_CHOSEN_SHAPES = {"T=S": (64, 64), "T<S": (24, 56), "tail": (40, 40),
                  "heads": (16, 16)}
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
    heads, bq, bk, chunks = FA._fwd_tiles(B * H, T, S, D, 4)
    bwd_heads, bwd_bq, bwd_bk = FA._bwd_blocks(B * H, T, S, D, 4)
    if shape == "heads":
        assert heads > 1 and bq == T
        assert bwd_heads > 1 and (bwd_bq, bwd_bk) == (T, S)
    else:
        assert bq < T and (chunks > 1 or bk * chunks < S)
        # several query blocks x several key blocks a head, one head a step
        assert bwd_heads == 1 and bwd_bq < T and 2 * bwd_bk <= S
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
    _small_chooser(monkeypatch, vmem_budget=100 * 1024)
    B, H, T, S, D = 3, 1, 48, 80, 8
    heads, bq, bk, chunks = FA._fwd_tiles(B * H, T, S, D, 4)
    assert heads == 1 and -(-S // (bk * chunks)) > 2
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
    monkeypatch.setattr(FA, "_BWD_VMEM_BUDGET", 250_000)
    assert FA._bwd_engine(B * H, T, S, D, 4, 16, 16) == engine
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


# the benchmark's three training shapes [B*H, T, D], the backward engine each
# takes and the kernel's tiles (heads, query rows, keys) there: ONE kernel,
# several heads a step where one tile holds all of T; at T = 256 the chip
# read the scan as fast (PR 34), so the kernel starts at _BWD_MIN_T
_CELL_SHAPES = [((512, 256, 64), "scan", (8, 256, 256)),
                ((64, 2048, 64), "fused", (1, 512, 512)),
                ((32, 4096, 64), "fused", (1, 512, 512))]


@pytest.mark.parametrize("itemsize", [4, 2], ids=["f32", "bf16"])
@pytest.mark.parametrize("shape,engine,bwd_tiles", _CELL_SHAPES,
                         ids=["s256", "s2048", "s4096"])
def test_flash_chooser_at_the_cells_shapes(shape, engine, bwd_tiles, itemsize):
    bh, T, D = shape
    heads, bq, bk, chunks = FA._fwd_tiles(bh, T, T, D, itemsize)
    assert FA._fwd_vmem_bytes(heads, bq, bk, chunks, D, itemsize) <= FA._FWD_VMEM_BUDGET
    assert bh % heads == 0 and bq <= T and bk * chunks == T  # all of S resident
    # a step worth taking: at least sixteen of the old 128 x 128 tiles
    assert heads * bq * bk * chunks >= 16 * 128 * 128
    # a short sequence does not pay for a long one's tiles
    assert (heads > 1) == (T <= FA._FWD_BLOCK)
    # the backward: the engine, the kernel's tiles, and a residency inside the
    # budget and inside the limit the kernel is compiled with (under half of
    # v5e's 128 MiB of VMEM a core)
    assert FA._bwd_engine(bh, T, T, D, itemsize) == engine
    assert (engine == "fused") == (T >= FA._BWD_MIN_T)
    assert FA._bwd_blocks(bh, T, T, D, itemsize) == bwd_tiles
    need = FA._bwd_vmem_bytes(*bwd_tiles, T, D, itemsize)
    limit = FA._bwd_vmem_limit(*bwd_tiles, T, D, itemsize)
    assert need <= FA._BWD_VMEM_BUDGET and need < limit <= 64 * 2 ** 20
    assert bh % bwd_tiles[0] == 0 and T % bwd_tiles[1] == 0 == T % bwd_tiles[2]
    # the residency is the query side's: at this many rows the scan takes over
    assert FA._bwd_engine(bh, 16 * 4096, 16 * 4096, D, itemsize) == "scan"


def test_flash_fwd_grid_steps_recorded_once_per_compiled_shape():
    from paddle_tpu import observability as obs

    B, H, T, D = 2, 2, 32, 8
    q, k, v = _rand_qkv(B=B, H=H, T=T, D=D, seed=13)
    heads, bq, bk, chunks = FA._fwd_tiles(B * H, T, T, D, 4)
    labels = {"T": T, "S": T, "block": "%dx%d" % (bq, bk), "bh": B * H, "causal": 1}
    cell = obs.counter("flash.fwd.grid_steps", labels=labels)
    before = cell.value
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, None, True))
    for _ in range(3):
        f(q, k, v).block_until_ready()
    steps = (B * H // heads) * -(-T // bq) * -(-T // (bk * chunks))
    assert cell.value == (before or steps) == steps


@pytest.mark.parametrize("engine", ["fused", "scan"])
def test_flash_bwd_grid_steps_recorded_once_per_compiled_shape(engine, monkeypatch):
    from paddle_tpu import observability as obs

    _force_bwd(monkeypatch, engine)
    _small_chooser(monkeypatch)
    B, H, T, D = 2, 2, 48, 8
    q, k, v = _rand_qkv(B=B, H=H, T=T, D=D, seed=17)
    heads, bq, bk = FA._bwd_blocks(B * H, T, T, D, 4)
    if engine == "scan":  # a turn is every (batch, head)'s [T, block_k] strip
        heads, bq, bk = B * H, T, FA.DEFAULT_BLOCK_K
    labels = {"T": T, "S": T, "block": "%dx%d" % (bq, min(bk, T)), "bh": B * H,
              "causal": 1, "engine": engine}
    cell = obs.counter("flash.bwd.grid_steps", labels=labels)
    before = cell.value
    f = jax.jit(jax.grad(lambda q, k, v: flash_attention(q, k, v, None, True).sum(),
                         argnums=(0, 1, 2)))
    for _ in range(3):
        jax.block_until_ready(f(q, k, v))
    steps = (B * H // heads) * -(-T // min(bk, T))
    assert steps == {"fused": 12, "scan": 1}[engine]
    assert cell.value == (before or steps) == steps


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
