"""All-to-all (Ulysses) sequence parallelism on the 8-device cpu mesh:
forward vs full attention, gradients, and the head-divisibility guard."""
from __future__ import annotations

import functools
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.parallel.collective import make_mesh
from paddle_tpu.parallel.flash_attention import mha_reference
from paddle_tpu.parallel.ulysses import ulysses_attention, ulysses_attention_sharded


def _qkv(B=1, H=8, T=64, D=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, H, T, D), jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full_attention(causal):
    assert jax.device_count() >= 8, "conftest must force 8 cpu devices"
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv()
    out = ulysses_attention_sharded(q, k, v, mesh, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ulysses_grads_match():
    mesh = make_mesh({"sp": 4})
    q, k, v = _qkv(H=4, T=32, D=8, seed=1)

    from jax.sharding import PartitionSpec as P


    spec = P(None, None, "sp", None)

    @jax.jit
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec), out_specs=P(), check_vma=False)
    def loss_ulysses(qs, ks, vs):
        o = ulysses_attention(qs, ks, vs, "sp")
        return jax.lax.psum((o ** 2).sum(), "sp")

    def loss_ref(q, k, v):
        return (mha_reference(q, k, v) ** 2).sum()

    gu = jax.grad(loss_ulysses, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gu, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)


def test_ulysses_rejects_indivisible_heads():
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(H=4)  # 4 heads cannot split across 8 devices
    with pytest.raises(ValueError, match="axis size"):
        ulysses_attention_sharded(q, k, v, mesh)
