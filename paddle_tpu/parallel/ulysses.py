"""All-to-all sequence parallelism (DeepSpeed-Ulysses style) — the second
long-context engine beside ring attention.

Where ring attention keeps Q resident and rotates K/V shards around the
ICI ring (p rounds of ppermute), the all-to-all scheme re-shards ONCE per
direction: each device trades its sequence shard of every head for the
full sequence of H/p heads (`lax.all_to_all` over the ``sp`` axis),
computes ordinary full-sequence attention locally, and trades back.
Communication is 4 all-to-alls of activation size (q/k/v in, output back)
regardless of sequence length — cheaper than the ring's p ppermute rounds
when heads are plentiful and the interconnect is all-to-all capable (TPU
ICI is); the constraint is that the head count must divide by the axis
size.

Reference analog: none — the reference caps context length at one GPU's
memory.  Differentiable end to end (autodiff through all_to_all).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .flash_attention import mha_reference

__all__ = ["ulysses_attention", "ulysses_attention_sharded"]


def ulysses_attention(q, k, v, axis_name, causal=False, sm_scale=None):
    """Attention over the full mesh-sharded sequence, inside ``shard_map``.

    q/k/v: this device's sequence shard ``[B, H, T_local, D]``; shards are
    laid out in sequence order along the axis.  H must be divisible by the
    axis size.
    """
    p = jax.lax.psum(1, axis_name)
    H = q.shape[1]
    if H % p != 0:
        raise ValueError(
            "ulysses_attention needs head count %% axis size == 0, got H=%d p=%d"
            % (H, p))

    def seq_to_heads(x):
        # [B, H, T/p, D] -> [B, H/p, T, D]: give away H/p-head slices of my
        # sequence shard, receive my heads' shards of the whole sequence
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    qh = seq_to_heads(q)
    kh = seq_to_heads(k)
    vh = seq_to_heads(v)
    out = mha_reference(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    return heads_to_seq(out).astype(q.dtype)


def ulysses_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False, sm_scale=None):
    """jit + shard_map wrapper: q/k/v are global [B, H, T, D]; the T axis is
    sharded over ``axis_name`` of ``mesh``."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis_name, None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=False
    )
    def _run(qs, ks, vs):
        return ulysses_attention(qs, ks, vs, axis_name, causal=causal, sm_scale=sm_scale)

    return jax.jit(_run)(q, k, v)
