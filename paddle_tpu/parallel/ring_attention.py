"""Ring attention: sequence/context parallelism over a mesh axis.

Reference analog: the reference caps sequence length by single-GPU memory
(its attention materializes T×T); there is no sequence-parallel path.  This
module is the TPU-native long-context answer: shard the sequence over the
``sp`` mesh axis, keep Q resident, and rotate K/V chunks around the ICI
ring with ``ppermute`` while accumulating the streaming-softmax state
(running max m, denominator l, weighted accumulator) — attention over
sequences p× longer than one chip's HBM, with compute/communication
overlap left to XLA's latency-hiding scheduler.

Use inside ``shard_map`` with sequence-sharded [B, H, T/p, D] blocks
(ring_attention), or call ``ring_attention_sharded`` to wrap jit+shard_map
over a mesh.  Differentiable (autodiff goes through ppermute).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ring_attention", "ring_attention_sharded"]

NEG_INF = -1e30


def _block_attn(q, k, v, sm_scale, mask):
    """One blockwise attention contribution with streaming-softmax stats.
    q [B,H,Tq,D], k/v [B,H,Tk,D], mask [Tq,Tk] or None."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm_scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = s.max(axis=-1)  # [B,H,Tq]
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m, l, o


def ring_attention(q, k, v, axis_name, causal=False, sm_scale=None):
    """Attention over the full (mesh-sharded) sequence.

    q/k/v: this device's sequence shard [B, H, T_local, D] inside shard_map.
    With ``causal``, shards are assumed laid out in sequence order along the
    mesh axis.
    """
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    p = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % p) for i in range(p)]
    Tl = q.shape[2]

    qf = q.astype(jnp.float32)

    def step(carry, r):
        k_cur, v_cur, m_acc, l_acc, o_acc = carry
        src = (idx - r) % p  # which shard's K/V we hold at round r
        if causal:
            rows = jnp.arange(Tl)[:, None] + idx * Tl
            cols = jnp.arange(Tl)[None, :] + src * Tl
            mask = rows >= cols
        else:
            mask = None
        m_blk, l_blk, o_blk = _block_attn(qf, k_cur.astype(jnp.float32), v_cur.astype(jnp.float32), sm_scale, mask)
        m_new = jnp.maximum(m_acc, m_blk)
        a_old = jnp.exp(m_acc - m_new)
        a_blk = jnp.exp(m_blk - m_new)
        l_new = l_acc * a_old + l_blk * a_blk
        o_new = o_acc * a_old[..., None] + o_blk * a_blk[..., None]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m_new, l_new, o_new), None

    B, H, _, D = q.shape
    m0 = jnp.full((B, H, Tl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    o0 = jnp.zeros((B, H, Tl, D), jnp.float32)
    (k_f, v_f, m, l, o), _ = jax.lax.scan(step, (k, v, m0, l0, o0), jnp.arange(p))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False, sm_scale=None):
    """jit + shard_map wrapper: q/k/v are global [B, H, T, D] arrays; the T
    axis is sharded over ``axis_name`` of ``mesh``."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis_name, None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )
    def _run(qs, ks, vs):
        return ring_attention(qs, ks, vs, axis_name, causal=causal, sm_scale=sm_scale)

    return jax.jit(_run)(q, k, v)
