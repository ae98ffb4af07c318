"""Fused attention op lowering (pallas flash attention kernel).

No reference analog op: the reference composes matmul+softmax+matmul
(nets.py:233).  ``flash_attention`` is the TPU-native fused path —
O(T) HBM per row block instead of materializing the [T, S] score matrix —
exposed as a first-class op so Programs (transformer, seq2seq) can opt in.
"""
from __future__ import annotations

from ..registry import register


@register("flash_attention")
def _flash_attention(ctx, op):
    import jax.numpy as jnp

    from ..parallel.flash_attention import flash_attention

    q = ctx.get_input(op, "Q")  # [B, H, T, D]
    k = ctx.get_input(op, "K")
    v = ctx.get_input(op, "V")
    kv_lens = ctx.get_input(op, "KVLens", None)  # [B] int, optional
    if kv_lens is not None:
        kv_lens = kv_lens.reshape(-1).astype(jnp.int32)
    causal = bool(op.attrs.get("causal", False))

    # sequence parallelism over the executor mesh's 'sp' axis.  Giving the
    # mesh a non-trivial sp axis IS the opt-in (attr
    # sequence_parallel=False forces the single-shard kernel); falls back
    # when T doesn't divide or kv_lens masking is requested (both sp
    # engines assume dense blocks).  Engine choice ("auto"):
    # - Ulysses (all-to-all head/sequence re-shard, parallel/ulysses.py)
    #   when the head count divides the axis — its communication volume is
    #   constant in sp, vs the ring's p-1 K/V rotations;
    # - ring attention (ppermute K/V rotation) otherwise — no head
    #   constraint and sequences can exceed one device's HBM.
    if bool(op.attrs.get("sequence_parallel", True)) and ctx.mesh is not None:
        mesh = ctx.mesh
        axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        sp = int(axis_sizes.get("sp", 1))
        if sp > 1 and kv_lens is None and q.shape[2] % sp == 0:
            engine = op.attrs.get("sp_engine", "auto")
            if engine == "auto":
                engine = "ulysses" if q.shape[1] % sp == 0 else "ring"
            if engine == "ulysses":
                from ..parallel.ulysses import ulysses_attention_sharded

                out = ulysses_attention_sharded(q, k, v, mesh, axis_name="sp", causal=causal)
            else:
                from ..parallel.ring_attention import ring_attention_sharded

                out = ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=causal)
            ctx.set_output(op, "Out", out)
            return

    if ctx.mesh is not None:
        out = _flash_on_mesh(q, k, v, kv_lens, causal, ctx.mesh)
    else:
        out = flash_attention(q, k, v, kv_lens, causal)
    ctx.set_output(op, "Out", out)


def _flash_on_mesh(q, k, v, kv_lens, causal, mesh):
    """The single-shard kernel under an executor mesh.  XLA's partitioner
    cannot split a Mosaic kernel ("cannot be automatically partitioned"), so
    the call runs inside ``shard_map``: attention is independent across
    batch and heads, so the batch splits on ``dp`` and the heads on ``tp``
    (an axis that does not divide stays replicated, as do all others)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.flash_attention import flash_attention

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def axis(name, dim):
        n = int(sizes.get(name, 1))
        return name if n > 1 and dim % n == 0 else None

    spec = P(axis("dp", q.shape[0]), axis("tp", q.shape[1]), None, None)
    lens = () if kv_lens is None else (kv_lens,)

    def shard(q, k, v, *lens):
        return flash_attention(q, k, v, lens[0] if lens else None, causal)

    return jax.shard_map(
        shard, mesh=mesh, in_specs=(spec,) * 3 + (P(spec[0]),) * len(lens),
        out_specs=spec, check_vma=False)(q, k, v, *lens)
