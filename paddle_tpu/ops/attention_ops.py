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

    from ..parallel.flash_attention import (_from_rows, _to_rows,
                                            flash_attention,
                                            flash_attention_rows)

    # with an ``n_head`` attribute q, k, v and the result are the projections'
    # own rows [B, T, H * D] (head h the lanes h * D : (h + 1) * D) and no
    # head is split off or merged back around the kernels; without it (a
    # program saved before the attribute existed) they are [B, H, T, D]
    n_head = int(op.attrs.get("n_head") or 0)
    q = ctx.get_input(op, "Q")
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
        T = q.shape[1] if n_head else q.shape[2]
        if sp > 1 and kv_lens is None and T % sp == 0:
            if n_head:
                # the sequence-parallel engines keep [B, H, T, D], behind a
                # transpose of their own
                q, k, v = (_from_rows(x, n_head) for x in (q, k, v))
            engine = op.attrs.get("sp_engine", "auto")
            if engine == "auto":
                engine = "ulysses" if q.shape[1] % sp == 0 else "ring"
            if engine == "ulysses":
                from ..parallel.ulysses import ulysses_attention_sharded

                out = ulysses_attention_sharded(q, k, v, mesh, axis_name="sp", causal=causal)
            else:
                from ..parallel.ring_attention import ring_attention_sharded

                out = ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=causal)
            ctx.set_output(op, "Out", _to_rows(out) if n_head else out)
            return

    if ctx.mesh is not None:
        out = _flash_on_mesh(q, k, v, kv_lens, n_head, causal, ctx.mesh)
    elif n_head:
        out = flash_attention_rows(q, k, v, kv_lens, n_head, causal)
    else:
        out = flash_attention(q, k, v, kv_lens, causal)
    ctx.set_output(op, "Out", out)


def _flash_on_mesh(q, k, v, kv_lens, n_head, causal, mesh):
    """The single-shard kernel under an executor mesh.  XLA's partitioner
    cannot split a Mosaic kernel ("cannot be automatically partitioned"), so
    the call runs inside ``shard_map``: attention is independent across
    batch and heads, so the batch splits on ``dp`` and the heads on ``tp``
    (an axis that does not divide stays replicated, as do all others).  Rows
    (``n_head`` > 0) split their ``H * D`` axis where whole head groups — the
    heads one block of lanes holds — divide."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..parallel.flash_attention import (_lane_heads, flash_attention,
                                            flash_attention_rows)

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def axis(name, dim):
        n = int(sizes.get(name, 1))
        return name if n > 1 and dim % n == 0 else None

    if n_head:
        groups = n_head // _lane_heads(n_head, q.shape[2] // n_head)
        spec = P(axis("dp", q.shape[0]), None, axis("tp", groups))
        local_heads = n_head // (int(sizes["tp"]) if spec[2] else 1)
    else:
        spec = P(axis("dp", q.shape[0]), axis("tp", q.shape[1]), None, None)
    lens = () if kv_lens is None else (kv_lens,)

    def shard(q, k, v, *lens):
        lens = lens[0] if lens else None
        if n_head:
            return flash_attention_rows(q, k, v, lens, local_heads, causal)
        return flash_attention(q, k, v, lens, causal)

    return jax.shard_map(
        shard, mesh=mesh, in_specs=(spec,) * 3 + (P(spec[0]),) * len(lens),
        out_specs=spec, check_vma=False)(q, k, v, *lens)
