"""Ring attention over a sharded sequence axis against full attention."""
import functools

import numpy as np
import pytest
import jax

from paddle_tpu.parallel.flash_attention import mha_reference
from paddle_tpu.parallel.ring_attention import ring_attention_sharded
from paddle_tpu.parallel.collective import make_mesh

from _flash_cases import _rand_qkv


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
