"""The main path's Pallas kernels compile for a TPU v5e — checked without one.

The TPU compiler is installed with jax and compiles for a chip that is only
described (``jax.experimental.topologies``), with ``JAX_PLATFORMS=cpu`` still
set.  Interpret-mode tests cannot see what the chip's lowering refuses (block
shapes against the (8, 128) tiling, scoped VMEM); these can.  Nothing runs, so
they say nothing about results — ``chip_smoke.py`` does that on the chip.

The kernels decide ``interpret`` from the backend (the CPU here), so the
tests call the kernel entry points with ``interpret=False``.  The topology is
described inside a module-scoped fixture (only the xdist worker that is given
this file loads the TPU library), and everything compiles in this process.
"""
import contextlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import flash_attention as FA


@pytest.fixture(scope="module")
def four_chips():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2").devices
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def one_chip(four_chips):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(four_chips[0])


@contextlib.contextmanager
def _persistent_cache_off():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep it off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture()
def no_persistent_cache():
    with _persistent_cache_off():
        yield


def _kernel_calls(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text().count(
        'custom_call_target="tpu_custom_call"')


# (shape [B, H, T, D], compiled kernels expected in fwd+bwd): the forward is
# always one, and the backward the fused one-grid kernel from T = 256 on (the
# XLA scan, no kernel, under it and past the kernel's VMEM budget); here at
# explicit blocks of 128, the query side resident all the same
@pytest.mark.parametrize("shape,kernels", [
    ((128, 8, 128, 64), 1),    # under the kernel's least T: scan
    ((64, 8, 256, 64), 2),     # Transformer-base training shape
    ((8, 8, 2048, 64), 2),
    ((4, 8, 4096, 64), 2),     # 16.70M of 16.00M scoped before PR 34
], ids=["b128xT128-scan", "b64xT256-fused", "b8xT2048-fused", "b4xT4096-fused"])
def test_flash_fwd_bwd_compiles_for_v5e(one_chip, no_persistent_cache, shape,
                                        kernels):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = FA.flash_attention(q, k, v, None, True, None,
                                 FA.DEFAULT_BLOCK_Q, FA.DEFAULT_BLOCK_K, False)
        return out.astype(jnp.float32).sum()

    assert _kernel_calls(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) == kernels


# What the three training cells hand the kernel: ``decorate`` leaves the
# activations f32 (twice the tile bytes of the bf16 cases above), every call
# brings ``kv_lens``, and the tiles are the choosers' own (``block_q =
# block_k = None``).  A tile that fits VMEM on paper (``_fwd_vmem_bytes`` and
# ``_bwd_vmem_bytes``, each inside the limit its kernel is compiled with,
# ``_vmem_limit``) has to fit it here.
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape,kernels", [
    ((64, 8, 256, 64), 2),     # the least T of the kernel: 4 batch rows a step
    ((32, 8, 512, 64), 2),
    ((8, 8, 2048, 64), 2),
    ((4, 8, 4096, 64), 2),
    ((1, 8, 65536, 64), 1),    # the query side past the budget: scan backward
], ids=["b64xT256-fused", "b32xT512-fused", "b8xT2048-fused", "b4xT4096-fused",
        "b1xT65536-scan"])
def test_flash_chosen_tiles_compile_for_v5e(one_chip, no_persistent_cache,
                                            shape, kernels, dtype, causal):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    lens = jax.ShapeDtypeStruct(shape[:1], jnp.int32, sharding=one_chip)

    def loss(q, k, v, lens):
        out = FA.flash_attention(q, k, v, lens, causal, interpret=False)
        return out.astype(jnp.float32).sum()

    assert _kernel_calls(jax.grad(loss, argnums=(0, 1, 2)), x, x, x, lens) == kernels


def _relayouts(text, B, T, H, D):
    """The compiled program's ``copy`` / ``transpose`` instructions that give
    an activation-sized array: ``[B, T, H, D]``- or ``[B, H, T, D]``-shaped,
    or the ``[B, T, H * D]`` rows in another layout."""
    shapes = "|".join([r"%d,%d,%d,%d" % (B, T, H, D), r"%d,%d,%d,%d" % (B, H, T, D),
                       r"%d,%d,%d" % (B * H, T, D), r"%d,%d,%d" % (B, T, H * D)])
    return re.findall(r"= \w+\[(?:%s)\]\S* (?:copy|transpose)\(" % shapes, text)


# What the cells' step programs call: the rows entry on the projections' own
# f32 [B, T, H * D] rows, every call with ``kv_lens``, the tiles the choosers'
# own.  Beside the custom calls the compiled program moves nothing: a head is
# never split off or merged back in HBM.
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("B,T", [(64, 256), (8, 2048), (4, 4096)],
                         ids=["s256", "s2048", "s4096"])
def test_flash_rows_compile_at_the_cells_shapes(one_chip, no_persistent_cache,
                                                B, T, causal):
    H, D = 8, 64
    x = jax.ShapeDtypeStruct((B, T, H * D), jnp.float32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)

    def loss(q, k, v, w, lens):
        out = FA.flash_attention_rows(q, k, v, lens, H, causal, interpret=False)
        return (out * w).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x, x, lens).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert "flash_attention_fwd" in text and FA._BWD_KERNEL_NAME in text
    assert _relayouts(text, B, T, H, D) == []


def test_flash_rows_compile_under_a_dp_x_tp_mesh(four_chips, no_persistent_cache,
                                                monkeypatch):
    """What ``ParallelExecutor`` on a 2 x 2 mesh hands the op (the smoke's
    ``phase_mesh``): rows split on ``dp`` and their ``H * D`` axis on ``tp``
    by whole head groups, each device its own kernels on its own block, no
    collective and no relayout around them."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.attention_ops import _flash_on_mesh

    monkeypatch.setattr(FA, "cpu_backend", lambda: False)  # compile the kernels
    mesh = Mesh(np.array(four_chips).reshape(2, 2), ("dp", "tp"))
    B, T, H, D = 64, 256, 8, 64
    x = jax.ShapeDtypeStruct((B, T, H * D), jnp.float32,
                             sharding=NamedSharding(mesh, P("dp", None, "tp")))
    lens = jax.ShapeDtypeStruct((B,), jnp.int32,
                                sharding=NamedSharding(mesh, P("dp")))

    def loss(q, k, v, lens):
        return _flash_on_mesh(q, k, v, lens, H, True, mesh).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x, lens).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert not re.findall(r" (?:all-gather|all-to-all|all-reduce|collective-permute)"
                          r"(?:-start)?\(", text)
    assert _relayouts(text, B // 2, T, H // 2, D) == []


def test_attention_block_reads_the_projections_rows_on_v5e(
        one_chip, no_persistent_cache, monkeypatch):
    """Transformer-base's attention block at ``tfbase_train_s2048``'s widths
    under ``decorate``, forward and backward: three projections, the flash
    forward, the output projection, the fused flash backward and the four
    weight gradients, and between the projections and the two custom calls no
    ``copy`` or ``transpose`` of an activation (the parent's block held
    eight: q, k, v split and out merged in f32, their gradients in bf16)."""
    import paddle_tpu as fluid
    from paddle_tpu.contrib import mixed_precision
    from paddle_tpu.jax_bridge import program_to_fn
    from paddle_tpu.models import transformer as T

    monkeypatch.setattr(FA, "cpu_backend", lambda: False)  # compile the kernels
    B, S, D, H = 8, 2048, 512, 8
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[S, D], dtype="float32")
        lens = fluid.layers.data(name="lens", shape=[], dtype="int32")
        y = T.multi_head_attention(x, None, None, None, D // H, D // H, D, H,
                                   use_flash=True, flash_causal=True, kv_lens=lens)
        mixed_precision.decorate(fluid.optimizer.SGD(0.1)).minimize(
            fluid.layers.reduce_mean(fluid.layers.square(y)))
    params = {v.name: jax.ShapeDtypeStruct(v.shape, jnp.float32, sharding=one_chip)
              for v in main.list_vars() if v.persistable and v.shape}
    text = jax.jit(program_to_fn(main, [], return_state=True)).lower(
        params, {"x": jax.ShapeDtypeStruct((B, S, D), jnp.float32, sharding=one_chip),
                 "lens": jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)},
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
    ).compile().as_text()
    calls = re.findall(r"%(\S+) = .* custom-call\(.*tpu_custom_call", text)
    assert sorted(c.rsplit(".", 1)[0] for c in calls) == [
        "flash_attention_fwd", FA._BWD_KERNEL_NAME], calls
    assert _relayouts(text, B, S, H, D // H) == []


# Transformer-base serving widths: 64 slots, 8 heads x 64, 16-token pages,
# bf16 pools holding a 2048-token context per slot
_S, _H, _DH, _PS, _MP = 64, 8, 64, 16, 128


def _pool(sharding):
    return jax.ShapeDtypeStruct((_S * _MP + 1, _PS, _H, _DH), jnp.bfloat16,
                                sharding=sharding)


@pytest.mark.parametrize("qdtype", [jnp.float32, jnp.bfloat16],
                         ids=["q-f32", "q-bf16"])
def test_paged_decode_compiles_for_v5e(one_chip, no_persistent_cache, qdtype):
    def f(q, k_pool, v_pool, tables, lens):
        return FA.paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                         impl="pallas", interpret=False)

    assert _kernel_calls(
        f, jax.ShapeDtypeStruct((_S, _H, _DH), qdtype, sharding=one_chip),
        _pool(one_chip), _pool(one_chip),
        jax.ShapeDtypeStruct((_S, _MP), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((_S,), jnp.int32, sharding=one_chip)) == 1


@pytest.mark.parametrize("qdtype", [jnp.float32, jnp.bfloat16],
                         ids=["q-f32", "q-bf16"])
@pytest.mark.parametrize("layer", [0, 5], ids=["layer0", "layer5"])
def test_paged_decode_walk_compiles_on_the_stored_stack(
        one_chip, no_persistent_cache, layer, qdtype):
    """The chat cell's own call: the stored ``[6, 8193, 16, 512]`` bf16
    stack left in HBM, a static layer, and the turn the chooser picks there
    (32 pages = 512 keys: two double-buffered ``[512, 512]`` tiles)."""
    assert FA._decode_turn_pages(_PS, _H * _DH, _MP, 2, _H) * _PS == 512

    def f(q, k_pool, v_pool, tables, lens):
        return FA.paged_decode_attention(q, k_pool, v_pool, tables, lens,
                                         impl="pallas", interpret=False,
                                         layer=layer)

    stack = jax.ShapeDtypeStruct((_L, _P, _PS, _H * _DH), jnp.bfloat16,
                                 sharding=one_chip)
    assert _kernel_calls(
        f, jax.ShapeDtypeStruct((_S, _H, _DH), qdtype, sharding=one_chip),
        stack, stack,
        jax.ShapeDtypeStruct((_S, _MP), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((_S,), jnp.int32, sharding=one_chip)) == 1


@pytest.mark.parametrize("qdtype", [jnp.float32, jnp.bfloat16],
                         ids=["q-f32", "q-bf16"])
def test_eva_decode_compiles_at_the_published_shape(one_chip,
                                                    no_persistent_cache,
                                                    qdtype):
    """``evabyte_standing_decode``'s own call: 24 slots, 32 heads of 128 =
    4096 lanes, the window group's ``[8, 793, 64, 4096]`` and the summary
    group's ``[8, 481, 64, 4096]`` bf16 stacks left in HBM (pages of 64 rows
    in both, 64 and 1024 tokens), tables of 32 columns each; one kernel, 256
    rows a turn of either list."""
    assert FA._eva_turn_pages(64, 32) * 64 == 256

    def f(q, k, v, ks, vs, tw, ts, lw, ls):
        return FA.paged_eva_decode_attention(
            q, k, v, ks, vs, tw, ts, lw, ls, layer=5, impl="pallas",
            interpret=False)

    def stack(pages):
        return jax.ShapeDtypeStruct((8, pages, 64, 4096), jnp.bfloat16,
                                    sharding=one_chip)

    ints = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                               sharding=one_chip)
    assert _kernel_calls(
        f, jax.ShapeDtypeStruct((24, 32, 128), qdtype, sharding=one_chip),
        stack(793), stack(793), stack(481), stack(481), ints(24, 32),
        ints(24, 32), ints(24), ints(24)) == 1


@pytest.mark.parametrize("ps,lanes,mp,itemsize,n_head,pages", [
    (16, 512, 128, 2, 8, 32),     # the chat cell: 512 keys a turn, 4 turns
    (16, 512, 128, 4, 8, 32),     # an f32 pool of the same shape
    (64, 256, 608, 2, 2, 8),      # 64-token pages: 8 of them
    (16, 512, 8, 2, 8, 8),        # a table shorter than a turn: all of it
    (4, 16, 3, 4, 2, 3),          # the toy shapes of test_flash_decode.py
    (1024, 512, 4, 2, 8, 1),      # a page wider than a turn: one page
    (16, 2048, 128, 4, 16, 8),    # lanes so wide the budget halves it twice
])
def test_decode_turn_is_a_pure_function_of_the_shapes(ps, lanes, mp, itemsize,
                                                      n_head, pages):
    got = FA._decode_turn_pages(ps, lanes, mp, itemsize, n_head)
    assert got == pages and 1 <= got <= mp
    assert got == 1 or FA._decode_vmem_bytes(
        got, ps, lanes, itemsize, n_head) <= FA._DECODE_VMEM_BUDGET
    # 256 keys a turn at least, where the table and the budget allow
    if mp * ps >= 256 and lanes <= 512:
        assert got * ps >= 256


def test_the_grouped_kernel_is_refused_at_heads_of_64_lanes(
        one_chip, no_persistent_cache):
    """Why ``paged_decode_attention`` keeps two kernels: the listed walk
    (``paged_gqa_decode_attention``, one walk a (slot, KV head)) copies ONE
    head's lanes of a page out of HBM, and a copy takes whole lane tiles
    (Mosaic: "Slice shape along dimension 3 must be aligned to tiling
    (128)").  Transformer-base's heads are 64 wide: with one query head a KV
    head and the whole table listed it is refused, in the kernel's own
    words and before Mosaic is asked, so the kernel that copies whole
    ``[ps, H*Dh]`` rows serves it (ROADMAP D17 has what is left of the
    fold)."""
    def grouped(q, k_pool, v_pool, tables, lens):
        k_pool, v_pool, layer, n_kv = FA._stacked_pools(q, k_pool, v_pool, 0)
        pages, tokens = FA._head_lists(tables, lens, n_kv, None)
        return FA._paged_gqa_pallas(q, k_pool, v_pool, pages, tokens,
                                    _DH ** -0.5, False, layer)

    stack = jax.ShapeDtypeStruct((1, _P, _PS, _H * _DH), jnp.bfloat16,
                                 sharding=one_chip)
    with pytest.raises(ValueError, match="whole lane tiles"):
        jax.jit(grouped).lower(
            jax.ShapeDtypeStruct((_S, _H, _DH), jnp.float32, sharding=one_chip),
            stack, stack,
            jax.ShapeDtypeStruct((_S, _MP), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((_S,), jnp.int32, sharding=one_chip))


@pytest.mark.parametrize("qdtype", [jnp.float32, jnp.bfloat16],
                         ids=["q-f32", "q-bf16"])
def test_listed_walk_compiles_for_v5e(one_chip, no_persistent_cache, qdtype):
    """The selected-page decode attention of ``sala_longctx_decode`` at the
    cell's own shapes — 64 slots, 32 query / 2 KV heads of 128, 64-token
    pages, 128 listed pages a (slot, KV head), the stored bf16 stacks
    ``[2, 24577, 64, 256]`` left in HBM, the second layer — is ONE custom
    call, named as the benchmark's reader finds it, at the turn the chooser
    picks there (64 pages = 4096 keys)."""
    S, Hq, Hkv, Dh, ps, NS = 64, 32, 2, 128, 64, 128
    assert FA._listed_turn_pages(ps, Dh, NS, 2, Hq // Hkv) == 64

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def f(q, k_pool, v_pool, pages, tokens):
        return FA.paged_decode_attention(
            q, k_pool, v_pool, None, None, impl="pallas", interpret=False,
            layer=1, selection=(pages, tokens))

    pool = sds((2, 24577, ps, Hkv * Dh), jnp.bfloat16)
    text = jax.jit(f).lower(sds((S, Hq, Dh), qdtype), pool, pool,
                            sds((S, Hkv, NS)), sds((S, Hkv))
                            ).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_gqa_decode_attention" in text
    # the pools are passed once each, and nothing pool-sized is copied
    call = next(line for line in text.splitlines()
                if "tpu_custom_call" in line and " custom-call(" in line)
    assert len(re.findall(r"%[\w.-]+", call.split(" custom-call(")[1]
                          .split(")")[0])) == 5


@pytest.mark.parametrize("layer", [0, 1])
def test_scoring_walk_compiles_for_v5e(one_chip, no_persistent_cache, layer):
    """The selection's scores of ``sala_longctx_decode`` at the cell's own
    shapes — 64 slots, a table of 608 pages of 64 tokens, 32 query / 2 KV
    heads of 128 lanes, the stored float32 stack of half-kernel means ``[2,
    24577, 4, 256]`` left in HBM, either layer of it — is ONE custom call
    under its own name, 128 pages a turn, and nothing pool-sized beside it."""
    S, Hq, Hkv, Dh, MP = 64, 32, 2, 128, 608
    assert FA._scores_turn_pages(MP) == 128

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def f(q, pool, tables, lens):
        return FA.paged_block_scores(
            q, pool, tables, lens, layer=layer, kernel_size=32, stride=16,
            impl="pallas", interpret=False)

    compiled = jax.jit(f).lower(
        sds((S, Hq, Dh), jnp.float32), sds((2, 24577, 4, Hkv * Dh),
                                           jnp.float32),
        sds((S, MP)), sds((S,))).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert FA._SCORES_KERNEL_NAME in text
    # the pool is an operand where it lies: no temporary of any size
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.fixture(scope="module")
def sala_decode_text(one_chip):
    """The compiled decode step of MiniCPM-SALA at the sizes of
    ``sala_longctx_decode`` (``chipbench/configs/minicpm_sala_9b.json``),
    the cache donated as the scheduler donates it."""
    import json
    import os

    from paddle_tpu.models import minicpm_sala as M

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench/configs/minicpm_sala_9b.json")) as f:
        cfg = json.load(f)
    d = M._dims(cfg)
    S, ps, P = cfg["slots"], cfg["page"], cfg["num_pages"]
    mp = -(-cfg["max_seq_len"] // ps)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: M.sala_params(cfg, 0)))
    kv = sds((d["n_sparse"], P, ps, d["Hkv"] * d["Dh"]), jnp.bfloat16)
    cache = {"k": kv, "v": kv,
             "kbar": sds((d["n_sparse"], P, ps // d["s"], d["Hkv"] * d["Dh"]),
                         jnp.float32),
             "lin": sds((d["n_lin"], S, d["Hl"], d["Dl"], d["Dl"]),
                        jnp.float32)}

    def decode(cache, params, tokens, positions, tables, lens):
        return M.sala_decode_step(params, tokens, positions, cache, tables,
                                  lens, cfg=cfg)

    with _persistent_cache_off(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(FA, "cpu_backend", lambda: False)
        return (cfg, jax.jit(decode, donate_argnums=(0,)).lower(
            cache, params, sds((S,)), sds((S,)), sds((S, mp)), sds((S,))
        ).compile().as_text())


def test_sala_decode_step_gathers_no_table_span(sala_decode_text):
    """The decode step scores the pooled keys where they lie: two scoring
    walks and two selected-page walks are its custom calls, and NO
    instruction has a ``slots * MP`` = 38912 dimension — until PR 50 the
    gather of every slot's whole table span ``f32[38912, 4, 256]`` (159 MB a
    layer), its relayout and the scoring fusions were 4.3 ms of an 18.2 ms
    step.  The pools are operands in place: nothing pool-sized is copied."""
    cfg, text = sala_decode_text
    span = cfg["slots"] * -(-cfg["max_seq_len"] // cfg["page"])
    assert span == 38912
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    assert text.count(FA._SCORES_KERNEL_NAME + ".") >= 2
    shapes = re.findall(r"= \(?\w+\[([0-9,]*)\]", text)
    assert len(shapes) > 1000
    assert not [dims for dims in shapes
                if str(span) in dims.split(",")]
    pool = cfg["num_pages"] * (cfg["page"] // 16) * 256     # a layer of kbar
    copies = [m for m in re.finditer(
        r"%(\S+) = \w+\[([0-9,]+)\]\S* (copy|slice|dynamic-slice)\(", text)
        if int(np.prod([int(x) for x in m.group(2).split(",")]))
        in (pool, 2 * pool)]
    assert copies == []


@pytest.mark.parametrize("chunk", [16, 512], ids=["chunk16", "chunk512"])
def test_paged_prefill_compiles_for_v5e(one_chip, no_persistent_cache, chunk):
    def f(q, k_pool, v_pool, pages, start):
        return FA.paged_prefill_attention(q, k_pool, v_pool, pages, start,
                                          impl="pallas", interpret=False)

    assert _kernel_calls(
        f, jax.ShapeDtypeStruct((chunk, _H, _DH), jnp.float32,
                                sharding=one_chip),
        _pool(one_chip), _pool(one_chip),
        jax.ShapeDtypeStruct((_MP,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)) == 1


def _latent_shapes():
    """The latent cell's shapes, from its configuration file."""
    import json
    import os

    from paddle_tpu.models import deepseek_v3 as M

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench/configs/kanana2_30b_a3b.json")) as f:
        cfg = json.load(f)
    return cfg, M._dims(cfg)


@pytest.mark.parametrize("qdtype", [jnp.float32, jnp.bfloat16],
                         ids=["q-f32", "q-bf16"])
@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_latent_attention_compiles_for_v5e(one_chip, no_persistent_cache,
                                           form, qdtype):
    """The absorbed kernel at the cell's slots, heads, page, lanes and page
    table, on the stored stack (a layer past the first), in both forms."""
    cfg, d = _latent_shapes()
    mp = cfg["max_seq_len"] // cfg["page"]

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((d["L"], cfg["num_pages"], cfg["page"], d["W"]), jnp.bfloat16)
    kw = dict(v_width=d["R"], sm_scale=d["sm_scale"], layer=d["L"] - 1,
              impl="pallas", interpret=False)
    if form == "decode":
        calls = _kernel_calls(
            lambda q, pool, t, n: FA.paged_mla_decode_attention(
                q, pool, t, n, **kw),
            sds((cfg["slots"], d["H"], d["W"]), qdtype), pool,
            sds((cfg["slots"], mp)), sds((cfg["slots"],)))
    else:
        calls = _kernel_calls(
            lambda q, pool, pages, start, valid:
            FA.paged_mla_prefill_attention(q, pool, pages, start, valid, **kw),
            sds((cfg["chunk"], d["H"], d["W"]), qdtype), pool, sds((mp,)),
            sds(()), sds(()))
    assert calls == 1


def _grouped_shapes():
    """The sizes of the configuration whose attention is the grouped walk in
    two page groups, from its own file."""
    import json
    import os

    from paddle_tpu.models import mellum as M

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root,
                           "chipbench/configs/mellum2_12b_a2_5b.json")) as f:
        cfg = json.load(f)
    return cfg, M._dims(cfg)


@pytest.mark.parametrize("kind", ["full", "window"])
@pytest.mark.parametrize("form", ["decode", "prefill128", "prefill512"])
def test_grouped_walk_compiles_for_v5e(one_chip, no_persistent_cache, form,
                                       kind):
    """The grouped-query walk at the cell's slots, heads, page and lanes on
    the stored stacks (a layer past the first), full layers over the whole
    page table and sliding layers over the ring of a slot's bound, in both
    forms; the custom call carries the kind's name."""
    cfg, d = _grouped_shapes()
    n = d["kinds"].count(kind + "_attention" if kind == "full"
                         else "sliding_attention")
    window = None if kind == "full" else d["W"]
    mp = (cfg["max_seq_len"] // cfg["page"] if kind == "full"
          else -(-(d["W"] + cfg["chunk"]) // cfg["page"]) + 1)

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((n, cfg["num_pages"][kind], cfg["page"], d["Hkv"] * d["Dh"]),
               jnp.bfloat16)
    kw = dict(layer=n - 1, window=window, sm_scale=d["sm_scale"],
              impl="pallas", interpret=False)
    if form == "decode":
        fn, args = (
            lambda q, k, v, t, n_: FA.paged_gqa_decode_attention(
                q, k, v, t, n_, **kw),
            (sds((cfg["slots"], d["H"], d["Dh"]), jnp.bfloat16), pool, pool,
             sds((cfg["slots"], mp)), sds((cfg["slots"],))))
    else:
        fn, args = (
            lambda q, k, v, pages, start, valid:
            FA.paged_gqa_prefill_attention(q, k, v, pages, start, valid, **kw),
            (sds((int(form[7:]), d["H"], d["Dh"]), jnp.bfloat16), pool, pool,
             sds((mp,)), sds(()), sds(())))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_gqa_%s_attention" % kind in text


@pytest.mark.parametrize("rows", ["decode", "chunk"])
def test_grouped_matmul_compiles_for_v5e(one_chip, no_persistent_cache, rows):
    """Both products of an expert layer (gate-and-up, down) on the stacked
    experts, at a decode step's and at a chunk's sorted pairs."""
    from paddle_tpu.parallel import moe

    cfg, d = _latent_shapes()
    pairs = d["k"] * (cfg["slots"] if rows == "decode" else cfg["chunk"])
    n_moe = d["L"] - d["n_dense"]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(x, w_gu, w_down, sizes):
        gu = moe.grouped_matmul(x, w_gu, sizes, layer=n_moe - 1,
                                impl="pallas", interpret=False)
        act = (jax.nn.silu(gu[:, :d["Fm"]]) * gu[:, d["Fm"]:]).astype(x.dtype)
        return moe.grouped_matmul(act, w_down, sizes, layer=n_moe - 1,
                                  impl="pallas", interpret=False)

    assert _kernel_calls(
        both, sds((pairs, d["D"])), sds((n_moe, d["E"], d["D"], 2 * d["Fm"])),
        sds((n_moe, d["E"], d["Fm"], d["D"])),
        sds((d["E"],), jnp.int32)) == 2


def test_kda_state_decode_compiles_for_v5e(one_chip, no_persistent_cache):
    """The delta-rule state update at the shapes of the benchmark's
    ``solar2_standing_decode`` cell (128 slots, 64 heads of 128 x 128 float32,
    the stack of 3 layers addressed in place): one kernel, the 1.6 GB stack
    aliased in and out with no copy of it and no layer sliced out."""
    from paddle_tpu.parallel import kda

    S, H, d = 128, 64, 128

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(state, q, k, v, g, beta, live):
        return kda.kda_state_decode(state, q, k, v, g, beta, live, layer=1,
                                    impl="pallas", interpret=False)

    vec = sds((S, H, d))
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        sds((3, S, H, d, d)), vec, vec, vec, vec, sds((S, H)),
        sds((S,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "kda_state_decode" in text
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 3 * S * H * d * d * 4
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


# the grouped product where the 4 MiB block budget sets its column tile: a
# decode step's and a chunk's pairs, the layers' weights, the tile, and the
# blocks' bytes the kernel states (beside its 16 MiB margin) at 512-row tiles
_MELLUM_GATE_UP = ((8, 64, 2304, 1792), 896, 20774912)
_MELLUM_DOWN = ((8, 64, 896, 2304), 2304, 23658496)
_SOLAR_DOWN = ((4, 40, 1280, 4096), 1024, 14 * 2 ** 20)
_GMM_AT_THE_BUDGET = {
    "mellum2-gate-up-decode": (512,) + _MELLUM_GATE_UP,
    "mellum2-gate-up-chunk": (4096,) + _MELLUM_GATE_UP,
    "mellum2-down-decode": (512,) + _MELLUM_DOWN,
    "mellum2-down-chunk": (4096,) + _MELLUM_DOWN,
    "solar2-down-decode": (1024,) + _SOLAR_DOWN,
    "solar2-down-chunk": (4096,) + _SOLAR_DOWN,
}


def test_grouped_matmul_compiles_at_a_contraction_of_4096(one_chip,
                                                          no_persistent_cache):
    """A hidden size of 4096 puts the two operand blocks past the compiler's
    own scoped VMEM limit: the kernel states what it needs (and states
    nothing where the blocks fit, so the narrower models' programs are as
    they were)."""
    from paddle_tpu.parallel import moe

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def product(x, w, sizes):
        return moe.grouped_matmul(x, w, sizes, layer=3, impl="pallas",
                                  interpret=False)

    for rows in (1024, 4096):       # a decode step's and a chunk's pairs
        assert _kernel_calls(product, sds((rows, 4096)),
                             sds((4, 40, 4096, 2560)),
                             sds((40,), jnp.int32)) == 1
    # mellum2's gate-and-up at the 896 columns its block budget gives it
    # states 20.8 MB and the margin; a block of 1 MB states nothing
    wide = jax.make_jaxpr(lambda x, w, s: moe.grouped_matmul(
        x, w, s, impl="pallas", interpret=False))(
            jnp.zeros((512, 2304), jnp.bfloat16),
            jnp.zeros((8, 2304, 1792), jnp.bfloat16),
            jnp.zeros((8,), jnp.int32))
    assert ("vmem_limit_bytes=%d" % (_MELLUM_GATE_UP[2] + 16 * 2 ** 20)
            in str(wide))
    narrow = jax.make_jaxpr(lambda x, w, s: moe.grouped_matmul(
        x, w, s, impl="pallas", interpret=False))(
            jnp.zeros((512, 1024), jnp.bfloat16),
            jnp.zeros((8, 1024, 512), jnp.bfloat16),
            jnp.zeros((8,), jnp.int32))
    assert "vmem_limit_bytes=None" in str(narrow)


@pytest.mark.parametrize("case", sorted(_GMM_AT_THE_BUDGET))
def test_grouped_matmul_compiles_at_blocks_of_4_mb(one_chip,
                                                   no_persistent_cache, case):
    """A ``[K, tn]`` block at the budget, three copies of it beside the row
    tile and the float32 output block (the whole ``[512, 2304]`` in mellum2's
    down product): one custom call, compiled with the VMEM it states.
    solar_open2's down product states it for the ``[128, 1024]`` float32
    product and its select alone: its blocks come to 14 MiB to the byte, and
    the compiler allocated 16.5 MB against its own 16."""
    from paddle_tpu.parallel import moe

    pairs, weights, tn, need = _GMM_AT_THE_BUDGET[case]
    K, N = weights[-2:]
    assert moe._gmm_tile_n(K, N, 2) == tn

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(x, w, sizes):
        return moe.grouped_matmul(x, w, sizes, layer=weights[0] - 1,
                                  impl="pallas", interpret=False)

    args = (sds((pairs, K)), sds(weights), sds(weights[1:2], jnp.int32))
    assert _kernel_calls(fn, *args) == 1
    assert ("vmem_limit_bytes=%d" % (need + 16 * 2 ** 20)
            in str(jax.make_jaxpr(fn)(*args)))


def test_ffn_block_draws_each_dropout_mask_once_on_v5e(one_chip,
                                                        no_persistent_cache):
    """Transformer-base's FFN block at the training cells' widths, forward
    and backward: one ``rng-bit-generator`` a mask, and no fusion re-derives
    a mask with a counter hash (a threefry key's 20 rounds over
    ``u32[8,2048,2048]`` sat in five fusions of this block)."""
    import paddle_tpu as fluid
    from paddle_tpu.jax_bridge import program_to_fn
    from paddle_tpu.models import transformer as T

    B, S, D, DI = 8, 2048, 512, 2048
    main, startup = fluid.Program(), fluid.Program()
    with fluid.unique_name.guard(), fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[S, D], dtype="float32")
        y = T.post_process(x, T.positionwise_feed_forward(x, DI, D, 0.1), 0.1)
        fluid.optimizer.SGD(0.1).minimize(fluid.layers.reduce_mean(y))
    params = {v.name: jax.ShapeDtypeStruct(v.shape, jnp.float32, sharding=one_chip)
              for v in main.list_vars() if v.persistable and v.shape}
    text = jax.jit(program_to_fn(main, [], return_state=True)).lower(
        params, {"x": jax.ShapeDtypeStruct((B, S, D), jnp.float32, sharding=one_chip)},
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
    ).compile().as_text()
    drawn = re.findall(r"= u32\[([\d,]+)\]\S* rng-bit-generator\(", text)
    assert sorted(drawn) == ["8,2048,2048", "8,2048,512"], drawn
    assert not re.findall(r"= u32\[8,2048,\d+\]\S* xor\(", text)


def test_interpret_follows_the_backend_in_one_place():
    """On this (CPU) backend the default is interpret / the reference
    engine; a kernel asked to compile here (interpret=False) must fail
    loudly rather than fall back."""
    from paddle_tpu.core import cpu_backend

    assert cpu_backend() is True
    q = jnp.zeros((2, 2, 16), jnp.float32)
    pool = jnp.zeros((3, 16, 2, 16), jnp.float32)
    args = (q, pool, pool, jnp.ones((2, 1), jnp.int32),
            jnp.array([3, 0], jnp.int32))
    ref = FA.paged_decode_attention(*args)                  # reference engine
    got = FA.paged_decode_attention(*args, impl="pallas")   # interpreted
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)
    with pytest.raises(Exception):
        jax.block_until_ready(FA.paged_decode_attention(
            *args, impl="pallas", interpret=False))


# ---------------------------------------------------------------------------
# The REAL step programs at the widths of the benchmark's `tfbase_lm_chat`
# cell: 64 slots, 6 layers, 8193 pages of 16 tokens, H*Dh = 512, bf16 pools,
# donated.  What is checked is that a step never materialises anything
# pool-sized or layer-sized: the pools are stored `[L, P, ps, H*Dh]`, whose
# device layout is the row-major one the kernels read, and the kernels
# address the stack in place by (layer, page).  A 5-D `[.., H, Dh]` pool made
# every step copy both 805 MB pools twice and slice out 12 layers (PERF.md).
# ---------------------------------------------------------------------------
_L, _P, _V, _DM, _DI = 6, _S * _MP + 1, 30000, _H * _DH, 2048
_POOL_ELEMS = _L * _P * _PS * _DM
_STEP_PROGRAMS = ("decode", "chunk128", "chunk512")


@pytest.fixture(scope="module")
def step_programs(one_chip):
    """name -> compiled step program, each compiled once for the module.
    The pools are parameters 0 and 1, donated as the scheduler donates
    them; the kernels compile (no interpret) because the one predicate
    says "not the CPU" while these lower."""
    from paddle_tpu.models import transformer as T

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def decode(cache, params, tokens, positions, tables, kv_lens):
        return T.lm_decode_step(params, tokens, positions, cache, tables,
                                kv_lens, n_head=_H)

    def chunk(cache, params, tokens, start, valid, chunk_pages,
              gather_pages):
        return T.lm_prefill_chunk(params, tokens, start, valid, cache,
                                  chunk_pages, gather_pages, n_head=_H)

    pool = sds((_L, _P, _PS, _DM), jnp.bfloat16)
    cache = {"k": pool, "v": pool}   # flattens to parameters 0 and 1
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        T.lm_serving_params(T.lm_params(
            vocab_size=_V, n_layer=_L, n_head=_H, d_model=_DM, d_inner=_DI,
            max_length=_MP * _PS)[0]))
    args = {"decode": (decode, (sds((_S,)), sds((_S,)), sds((_S, _MP)),
                                sds((_S,))))}
    for c in (128, 512):   # _chunk_widths() of chunk 512, buckets 128/512/2048
        args["chunk%d" % c] = (chunk, (sds((c,)), sds(()), sds(()),
                                       sds((c // _PS,)), sds((_MP,))))
    with _persistent_cache_off(), pytest.MonkeyPatch.context() as mp:
        mp.setattr(FA, "cpu_backend", lambda: False)
        return {name: jax.jit(fn, donate_argnums=(0,)).lower(
                    cache, params, *rest).compile()
                for name, (fn, rest) in args.items()}


def _pool_sized(hlo_text):
    """Instructions that copy or slice something of the whole pool's or one
    layer's element count: `(name, opcode, dims)` each."""
    found = []
    for m in re.finditer(
            r"%(\S+) = \w+\[([0-9,]+)\]\S* ([\w-]+)\(", hlo_text):
        name, dims, opcode = m.groups()
        n = int(np.prod([int(d) for d in dims.split(",")]))
        if n in (_POOL_ELEMS, _POOL_ELEMS // _L) and (
                opcode in ("copy", "slice", "dynamic-slice")
                or "copy" in name or "slice" in name):
            found.append((name, opcode, dims))
    return found


@pytest.mark.parametrize("program", _STEP_PROGRAMS)
def test_step_program_has_no_pool_sized_copy_or_slice(step_programs, program):
    text = step_programs[program].as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == _L
    assert _pool_sized(text) == []


@pytest.mark.parametrize("program", _STEP_PROGRAMS)
def test_step_program_aliases_both_pools(step_programs, program):
    head = step_programs[program].as_text().split("\n", 1)[0]
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", head).group(1)
    assert sorted(int(p) for p in re.findall(r"\((\d+), \{\}", alias)) == [0, 1]


@pytest.mark.parametrize("program", _STEP_PROGRAMS)
def test_step_program_pool_layout_is_row_major(step_programs, program):
    layouts = re.findall(r"bf16\[%d,%d,%d,%d\]\{([0-9,]+)" % (
        _L, _P, _PS, _DM), step_programs[program].as_text())
    # 2 parameters and 2 results at least, and the in-place scatters between
    assert len(layouts) >= 4 and set(layouts) == {"3,2,1,0"}


@pytest.mark.parametrize("program", _STEP_PROGRAMS)
def test_step_program_temporaries_are_small(step_programs, program):
    mem = step_programs[program].memory_analysis()
    assert mem.alias_size_in_bytes == 2 * 2 * _POOL_ELEMS   # both bf16 pools
    assert mem.temp_size_in_bytes < 64 * 2 ** 20


# ---------------------------------------------------------------------------
# The AFMoE family's step programs at the sizes of `trinity_open_mixedlen`
# (chipbench/configs/trinity_large_preview.json): 48 query heads over 8 KV
# heads (6 a group, where the other grouped cells run 8), a window of 4096
# over pages of 64 (a ring of 73 columns), and expert matrices whose
# contraction AND width are 3072: the two operand blocks of the grouped
# product pass the compiler's own scoped VMEM limit, so the kernel states
# what it needs.
# ---------------------------------------------------------------------------

def _afmoe_shapes():
    import json
    import os

    from paddle_tpu.models import afmoe as A

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(
            root, "chipbench/configs/trinity_large_preview.json")) as f:
        cfg = json.load(f)
    return cfg, A._dims(cfg)


def test_grouped_matmul_states_its_vmem_at_a_contraction_of_3072(
        one_chip, no_persistent_cache):
    """Both products of a held expert share at a decode step's 256 pairs
    (13.6 MB of operand and output blocks: under the scoped default of 14
    MiB, nothing stated) and at a chunk's 2048 (512-row tiles, 17.8 MB: the
    kernel states what it needs)."""
    from paddle_tpu.parallel import moe

    cfg, d = _afmoe_shapes()
    held = d["held"][1] - d["held"][0]
    n_moe = d["L"] - d["n_dense"]

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(x, w_gu, w_down, sizes):
        gu = moe.grouped_matmul(x, w_gu, sizes, layer=n_moe - 1,
                                impl="pallas", interpret=False)
        act = (jax.nn.silu(gu[:, :d["Fm"]]) * gu[:, d["Fm"]:]).astype(x.dtype)
        return moe.grouped_matmul(act, w_down, sizes, layer=n_moe - 1,
                                  impl="pallas", interpret=False)

    for rows in (cfg["slots"], cfg["chunk"]):
        assert _kernel_calls(
            both, sds((rows * d["k"], d["D"])),
            sds((n_moe, held, d["D"], 2 * d["Fm"])),
            sds((n_moe, held, d["Fm"], d["D"])),
            sds((held,), jnp.int32)) == 2
    for rows, stated in ((256, False), (2048, True)):
        text = str(jax.make_jaxpr(lambda x, w, s: moe.grouped_matmul(
            x, w, s, impl="pallas", interpret=False))(
                jnp.zeros((rows, 3072), jnp.bfloat16),
                jnp.zeros((2, 3072, 512), jnp.bfloat16),
                jnp.zeros((2,), jnp.int32)))
        assert ("vmem_limit_bytes=None" not in text) == stated


_AFMOE_PROGRAMS = ("decode", "chunk128", "chunk512")


@pytest.fixture(scope="module")
def afmoe_programs(one_chip):
    """name -> the family's compiled step program at the cell's sizes, the
    cache (parameter 1) donated as the scheduler donates it."""
    from paddle_tpu import core
    from paddle_tpu.models import afmoe as A

    cfg, d = _afmoe_shapes()
    bf, f32 = jnp.bfloat16, jnp.float32

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    D, L, Le = d["D"], d["L"], d["L"] - d["n_dense"]
    n_q, n_kv = d["H"] * d["Dh"], d["Hkv"] * d["Dh"]
    held = d["held"][1] - d["held"][0]

    def layer(i):
        wide, names = ((d["F"], ("d_gu", "d_down")) if i < d["n_dense"]
                       else (d["Fs"], ("s_gu", "s_down")))
        return {"w_in": sds((D, 2 * n_q + 2 * n_kv), bf),
                "wo": sds((n_q, D), bf), names[0]: sds((D, 2 * wide), bf),
                names[1]: sds((wide, D), bf)}

    params = dict(
        {n: sds((L, D), f32) for n in ("ln_in", "ln_post_attn", "ln_pre_mlp",
                                       "ln_post_mlp")},
        embed=sds((d["V"], D), bf), head=sds((D, d["V"]), bf),
        norm_f=sds((D,), f32), qn=sds((L, d["Dh"]), f32),
        kn=sds((L, d["Dh"]), f32), router_w=sds((Le, D, d["E"]), f32),
        router_b=sds((Le, d["E"]), f32), layers=[layer(i) for i in range(L)],
        e_gu=sds((Le, held, D, 2 * d["Fm"]), bf),
        e_down=sds((Le, held, d["Fm"], D), bf))
    S, ps, C = cfg["slots"], cfg["page"], cfg["chunk"]
    cache = {leaf: sds((spec["layers"], cfg["num_pages"][spec["group"]], ps,
                        spec["width"]), bf)
             for leaf, spec in A.cache_layout(cfg)["page_pools"].items()}
    mp = {"full": cfg["max_seq_len"] // ps,
          "window": -(-(d["W"] + C) // ps) + 1}

    def by_group(make):
        return {g: make(n) for g, n in mp.items()}

    def decode(p, cache, tokens, positions, tables, kv_lens):
        return A.decode_step(p, tokens, positions, cache, tables, kv_lens,
                             cfg=cfg)

    def chunk(p, cache, tokens, start, valid, written, gathered, slot):
        return A.prefill_chunk(p, tokens, start, valid, cache, written,
                               gathered, slot, cfg=cfg)

    args = {"decode": (decode, (sds((S,)), sds((S,)),
                                by_group(lambda n: sds((S, n))), sds((S,))))}
    for w in (128, 512):    # _chunk_widths() of chunk 512, buckets 128/512/..
        args["chunk%d" % w] = (chunk, (
            sds((w,)), sds(()), sds(()), by_group(lambda n: sds((w // ps,))),
            by_group(lambda n: sds((n,))), sds(())))
    pool_bytes = sum(2 * int(np.prod(a.shape)) for a in cache.values())
    with _persistent_cache_off(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(FA, "cpu_backend", lambda: False)
        patch.setattr(core, "cpu_backend", lambda: False)
        compiled = {name: jax.jit(fn, donate_argnums=(1,)).lower(
                        params, cache, *rest).compile()
                    for name, (fn, rest) in args.items()}
    return compiled, pool_bytes


@pytest.mark.parametrize("program", _AFMOE_PROGRAMS)
def test_afmoe_step_program_compiles_for_v5e(afmoe_programs, program):
    """Five walks (four over the ring, one over the whole table, each named
    after its kind) and two grouped products an expert layer; the cache is
    aliased whole, and what the program adds to weights and cache fits the
    chip beside them."""
    compiled, pool_bytes = afmoe_programs
    text = compiled[program].as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 5 + 2 * 4
    assert "paged_gqa_full_attention" in text
    assert "paged_gqa_window_attention" in text
    mem = compiled[program].memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    assert mem.temp_size_in_bytes < 128 * 2 ** 20
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 14.5e9


# -- DeepSeek sparse attention at glm5_standing_dsactx's shapes ---------------

def _dsa_shapes():
    """The sizes of the configuration with an indexer, from its own file."""
    import json
    import os

    from paddle_tpu.models import deepseek_v3 as M

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench/configs/glm5_744b_a40b.json")) as f:
        cfg = json.load(f)
    return cfg, M._dims(cfg)


@pytest.mark.parametrize("stage", [
    "index_decode", "index_chunk", "select_rows", "select_mask",
    "rows_attention", "chunk_under_a_mask"])
def test_sparse_attention_stages_compile_for_v5e(one_chip, no_persistent_cache,
                                                 stage):
    """Every stage of DeepSeek sparse attention at the cell's own shapes — 24
    slots, a table of 832 pages of 64 rows, 32 indexer heads of 128 lanes
    (``[24, 32, 128]``), 2048 selected rows (``[24, 2048]``) of 640 lanes, a
    chunk of 512, both stored stacks left in HBM at a layer past the first:
    the four kernels are ONE custom call each under their own names — a decode
    step's selection, scores to row list, among them —, the chunk's selection
    (the mask) is no kernel, and neither form is a sort."""
    cfg, d = _dsa_shapes()
    S, C, ps = cfg["slots"], cfg["chunk"], cfg["page"]
    mp = cfg["max_seq_len"] // ps
    Hi, Di, H, W, R, top = d["Hi"], d["Di"], d["H"], d["W"], d["R"], d["topk"]
    assert (S, Hi, Di, top, W, mp) == (24, 32, 128, 2048, 640, 832)
    layer = d["L"] - 1

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ipool = sds((d["L"], cfg["num_pages"], ps, Di), jnp.bfloat16)
    lpool = sds((d["L"], cfg["num_pages"], ps, W), jnp.bfloat16)
    on_chip = dict(impl="pallas", interpret=False)
    att = dict(v_width=R, sm_scale=d["sm_scale"], layer=layer, **on_chip)
    idx = dict(layer=layer, scale=d["index_scale"], **on_chip)
    fn, args, name = {
        "index_decode": (
            lambda q, w, pool, t, n: FA.paged_index_scores(
                q, w, pool, t, n, **idx),
            (sds((S, Hi, Di), jnp.bfloat16), sds((S, Hi), jnp.float32), ipool,
             sds((S, mp)), sds((S,))), FA._INDEX_KERNEL_NAME),
        "index_chunk": (
            lambda q, w, pool, t, s, v: FA.paged_index_scores_prefill(
                q, w, pool, t, s, v, **idx),
            (sds((C, Hi, Di), jnp.bfloat16), sds((C, Hi), jnp.float32), ipool,
             sds((mp,)), sds(()), sds(())), FA._INDEX_KERNEL_NAME),
        "select_rows": (
            lambda s, n: FA.dsa_select(s, n, top, **on_chip),
            (sds((S, mp * ps), jnp.float32), sds((S,))),
            FA._SELECT_KERNEL_NAME),
        "select_mask": (
            lambda s, n: FA.dsa_keep(s, n, top),
            (sds((C, mp * ps), jnp.float32), sds((C,))), None),
        "rows_attention": (
            lambda q, pool, t, r, n: FA.paged_mla_rows_attention(
                q, pool, t, r, n, **att),
            (sds((S, H, W), jnp.bfloat16), lpool, sds((S, mp)),
             sds((S, top)), sds((S,))), FA._ROWS_KERNEL_NAME),
        "chunk_under_a_mask": (
            lambda q, pool, t, s, v, keep: FA.paged_mla_prefill_attention(
                q, pool, t, s, v, keep=keep, **att),
            (sds((C, H, W), jnp.bfloat16), lpool, sds((mp,)), sds(()),
             sds(()), sds((C, mp * ps), jnp.bool_)), FA._MLA_KERNEL_NAME),
    }[stage]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = text.count('custom_call_target="tpu_custom_call"')
    assert calls == (0 if name is None else 1)
    if name is not None:
        assert "%" + name in text
    if stage.startswith("select"):
        assert " sort(" not in text and "approx" not in text.lower()


# ---------------------------------------------------------------------------
# The LOOPED family (models/ouro.py) at the sizes of `ouro_standing_reasoning`
# (chipbench/configs/ouro_2_6b.json): plain multi-head rows of 16 x 128 = 2048
# lanes, 48 K/V layers from 12 layers of weights, and the K/V layer a TRACED
# scalar (the loop over total_ut_steps is a loop in the program): the walk
# takes it as a third prefetched scalar.  The whole step programs: 12 custom
# calls in the loop's body, both pools (5.9 GB each) aliased and updated in
# place across the loop's iterations - a copy of one would not fit the chip.
# ---------------------------------------------------------------------------

def _ouro_cfg():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench/configs/ouro_2_6b.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("layer", ["traced", "static"])
@pytest.mark.parametrize("form", ["decode", "chunk512", "chunk128"])
def test_ouro_walks_compile_for_v5e(one_chip, no_persistent_cache, form,
                                    layer):
    cfg = _ouro_cfg()
    H, Dh, ps = cfg["num_attention_heads"], cfg["head_dim"], cfg["page"]
    S, mp = cfg["slots"], cfg["max_seq_len"] // cfg["page"]
    layers = cfg["total_ut_steps"] * cfg["num_hidden_layers"]

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((layers, cfg["num_pages"], ps, H * Dh), jnp.bfloat16)
    at = sds(()) if layer == "traced" else None

    def pick(l):
        return 37 if l is None else l

    if form == "decode":
        def fn(q, k, v, tables, lens, l=None):
            return FA.paged_decode_attention(
                q, k, v, tables, lens, layer=pick(l), impl="pallas",
                interpret=False)
        args = (sds((S, H, Dh), jnp.bfloat16), pool, pool, sds((S, mp)),
                sds((S,)))
    else:
        C = int(form[5:])

        def fn(q, k, v, pages, start, l=None):
            return FA.paged_prefill_attention(
                q, k, v, pages, start, layer=pick(l), impl="pallas",
                interpret=False)
        args = (sds((C, H, Dh), jnp.bfloat16), pool, pool, sds((mp,)),
                sds(()))
    args = args + ((at,) if at is not None else ())
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    # the traced layer is one more scalar operand of the call, not a slice
    assert "dynamic-slice" not in text


_OURO_PROGRAMS = ("decode", "chunk512")


@pytest.fixture(scope="module")
def ouro_programs(one_chip):
    from paddle_tpu.models import ouro as O

    cfg = _ouro_cfg()
    S, ps = cfg["slots"], cfg["page"]
    mp = cfg["max_seq_len"] // ps
    layers = cfg["total_ut_steps"] * cfg["num_hidden_layers"]

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((layers, cfg["num_pages"], ps, 2048), jnp.bfloat16)
    cache = {"k": pool, "v": pool}
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: O.params(cfg, 0)))

    def decode(cache, params, tokens, positions, tables, lens):
        return O.decode_step(params, tokens, positions, cache, tables, lens,
                             cfg=cfg)

    def chunk(cache, params, tokens, start, valid, written, row):
        return O.prefill_chunk(params, tokens, start, valid, cache, written,
                               row, jnp.int32(0), cfg=cfg)

    args = {"decode": (decode, (sds((S,)), sds((S,)), sds((S, mp)),
                                sds((S,)))),
            "chunk512": (chunk, (sds((512,)), sds(()), sds(()),
                                 sds((512 // ps,)), sds((mp,))))}
    with _persistent_cache_off(), pytest.MonkeyPatch.context() as mp_:
        mp_.setattr(FA, "cpu_backend", lambda: False)
        return cfg, {name: jax.jit(fn, donate_argnums=(0,)).lower(
            cache, params, *rest).compile() for name, (fn, rest) in
            args.items()}


@pytest.mark.parametrize("program", _OURO_PROGRAMS)
def test_ouro_step_program_updates_both_pools_in_place(ouro_programs,
                                                       program):
    cfg, programs = ouro_programs
    compiled = programs[program]
    text = compiled.as_text()
    L = cfg["num_hidden_layers"]
    elems = (cfg["total_ut_steps"] * L * cfg["num_pages"] * cfg["page"]
             * 2048)
    # the loop is rolled: one walk a layer of WEIGHTS, in the loop's body
    assert text.count('custom_call_target="tpu_custom_call"') == L
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert all("ouro.loop/while/body" in line and "ouro.attn" in line
               for line in calls)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * 2 * elems      # both bf16 pools
    assert mem.temp_size_in_bytes < 64 * 2 ** 20
    found = []
    for m in re.finditer(r"%(\S+) = \w+\[([0-9,]+)\]\S* ([\w-]+)\(", text):
        name, dims, opcode = m.groups()
        n = int(np.prod([int(d) for d in dims.split(",")]))
        if n in (elems, elems // (cfg["total_ut_steps"] * L)) and (
                opcode in ("copy", "slice", "dynamic-slice")
                or "copy" in name or "slice" in name):
            found.append((name, opcode, dims))
    assert found == []


# ---------------------------------------------------------------------------
# SDAR (block diffusion): the grouped walk with ``block`` = 4 in both forms at
# the published shapes, the grouped matrix product at a block step's 2048
# pairs over 128 experts of 768, and the two step programs as the scheduler
# dispatches them (the block form of the decode program, unmasking included)
# ---------------------------------------------------------------------------

def _sdar_cfg():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "chipbench/configs/sdar_30b_a3b.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("form", ["decode", "chunk512", "chunk128"])
def test_sdar_walks_compile_for_v5e(one_chip, no_persistent_cache, form):
    """``[64, 4 x 32, 128]`` query rows a step (a slot's whole block: 32 rows
    a K/V head, no stagger) and a chunk's two blocks a grid step, against a
    ``[6, P, 64, 512]`` pool."""
    cfg = _sdar_cfg()
    H, Hkv, Dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    S, ps, B = cfg["slots"], cfg["page"], cfg["block_length"]
    mp = cfg["max_seq_len"] // ps

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((cfg["num_hidden_layers"], cfg["num_pages"], ps, Hkv * Dh),
               jnp.bfloat16)
    kw = dict(layer=5, block=B, impl="pallas", interpret=False)
    if form == "decode":
        fn, args = (
            lambda q, k, v, t, n: FA.paged_gqa_decode_attention(
                q, k, v, t, n, **kw),
            (sds((S, B, H, Dh), jnp.bfloat16), pool, pool, sds((S, mp)),
             sds((S,))))
    else:
        fn, args = (
            lambda q, k, v, pages, start, valid:
            FA.paged_gqa_prefill_attention(q, k, v, pages, start, valid, **kw),
            (sds((int(form[5:]), H, Dh), jnp.bfloat16), pool, pool,
             sds((mp,)), sds(()), sds(())))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_gqa_full_attention" in text


def test_sdar_grouped_matmul_compiles_for_v5e(one_chip, no_persistent_cache):
    """``[2048, 2048] x [128, 2048, 1536]`` and ``[2048, 768] x [128, 768,
    2048]``: a block step's 256 rows x 8 experts, 16 rows an expert."""
    from paddle_tpu.parallel import moe

    cfg = _sdar_cfg()
    D, Fm, E, L = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                   cfg["num_experts"], cfg["num_hidden_layers"])
    pairs = (cfg["slots"] * cfg["block_length"]
             * cfg["num_experts_per_tok"])
    assert (pairs, D, E, 2 * Fm) == (2048, 2048, 128, 1536)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def both(x, w_gu, w_down, sizes):
        gu = moe.grouped_matmul(x, w_gu, sizes, layer=L - 1, impl="pallas",
                                interpret=False)
        act = (jax.nn.silu(gu[:, :Fm]) * gu[:, Fm:]).astype(x.dtype)
        return moe.grouped_matmul(act, w_down, sizes, layer=L - 1,
                                  impl="pallas", interpret=False)

    assert _kernel_calls(
        both, sds((pairs, D)), sds((L, E, D, 2 * Fm)), sds((L, E, Fm, D)),
        sds((E,), jnp.int32)) == 2


_SDAR_PROGRAMS = ("decode", "chunk512")


@pytest.fixture(scope="module")
def sdar_programs(one_chip):
    """The scheduler's own step programs (``StepPrograms``: the packed buffer
    in, the block form's state out) over the published shapes."""
    from paddle_tpu import core
    from paddle_tpu.models import sdar as M
    from paddle_tpu.serving import step_programs as SP

    cfg = _sdar_cfg()
    S, ps, B = cfg["slots"], cfg["page"], cfg["block_length"]
    mp = cfg["max_seq_len"] // ps

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((cfg["num_hidden_layers"], cfg["num_pages"], ps, 512),
               jnp.bfloat16)
    cache = {"k": pool, "v": pool}
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: M.params(cfg, 0)))
    model = M.build_decode_model(None, cfg)
    programs = SP.StepPrograms(model, None, True)
    sizes = ((512 // ps, mp),)
    args = {
        "decode": (programs.decode, (
            sds((S, SP.step_columns((mp,), B))),
            sds((SP.block_state_length(S, B) + len(model.step_counters),))),
            {}),
        "chunk512": (programs.chunk, (
            sds((SP.chunk_length(512, sizes),)),), {"sizes": sizes})}
    with _persistent_cache_off(), pytest.MonkeyPatch.context() as mp_:
        mp_.setattr(FA, "cpu_backend", lambda: False)
        mp_.setattr(core, "cpu_backend", lambda: False)
        return cfg, {name: fn.lower(params, cache, *rest, **kw).compile()
                     for name, (fn, rest, kw) in args.items()}


@pytest.mark.parametrize("program", _SDAR_PROGRAMS)
def test_sdar_step_program_compiles_for_v5e(sdar_programs, program):
    """One walk and two grouped products a layer, both pools updated in
    place, no pool-sized copy, and the program's scopes in the text."""
    cfg, programs = sdar_programs
    compiled = programs[program]
    text = compiled.as_text()
    L = cfg["num_hidden_layers"]
    assert text.count('custom_call_target="tpu_custom_call"') == 3 * L
    for scope in ("sdar.attn", "sdar.experts", "sdar.head") + (
            ("sdar.unmask",) if program == "decode" else ()):
        assert scope in text, scope
    elems = L * cfg["num_pages"] * cfg["page"] * 512
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 2 * 2 * elems      # both bf16 pools
    # a decode step's logits [256, 151936] float32 and their softmax, never
    # as [64, 4, 151936] (four rows under a tile's eight: a padded copy)
    assert mem.temp_size_in_bytes < 1.5 * 2 ** 30
    assert "f32[%d,%d,%d]" % (cfg["slots"], cfg["block_length"],
                              cfg["vocab_size"]) not in text
    found = []
    for m in re.finditer(r"%(\S+) = \w+\[([0-9,]+)\]\S* ([\w-]+)\(", text):
        name, dims, opcode = m.groups()
        n = int(np.prod([int(d) for d in dims.split(",")]))
        if n in (elems, elems // L) and (
                opcode in ("copy", "slice", "dynamic-slice")
                or "copy" in name or "slice" in name):
            found.append((name, opcode, dims))
    assert found == []
