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
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel import flash_attention as FA


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep it off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernel_calls(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text().count(
        'custom_call_target="tpu_custom_call"')


# (shape [B, H, T, D], compiled kernels expected in fwd+bwd): the forward is
# always one; the backward is the XLA scan (no kernel) except where the
# "auto" rule picks the fused one-grid kernel (T >= 2048 and it fits VMEM)
@pytest.mark.parametrize("shape,kernels", [
    ((64, 8, 256, 64), 1),     # Transformer-base training shape
    ((8, 8, 2048, 64), 2),     # fused backward
    ((4, 8, 4096, 64), 1),     # fused would not fit: scan backward
], ids=["b64xT256", "b8xT2048-fused", "b4xT4096-scan"])
def test_flash_fwd_bwd_compiles_for_v5e(one_chip, no_persistent_cache, shape,
                                        kernels):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = FA.flash_attention(q, k, v, None, True, None,
                                 FA.DEFAULT_BLOCK_Q, FA.DEFAULT_BLOCK_K, False)
        return out.astype(jnp.float32).sum()

    assert _kernel_calls(jax.grad(loss, argnums=(0, 1, 2)), x, x, x) == kernels


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
