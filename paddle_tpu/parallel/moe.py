"""Expert parallelism: Switch-style Mixture-of-Experts over an ``ep``
mesh axis.

Reference analog: none — Fluid v0.15 predates MoE.  TPU-native design
(the Switch-Transformer recipe): each device owns ONE expert FFN, tokens
are data-sharded over the same ``ep`` axis, and routing is two
``all_to_all``s around the expert application:

1. gate: softmax(x @ gate_w) per token, top-1 expert choice;
2. dispatch: tokens are packed into per-expert capacity slots
   ([E, C, D] one-hot scatter — dense, XLA-friendly, no dynamic shapes);
   tokens past an expert's capacity are DROPPED (their combine weight is
   zero), the standard Switch overflow rule;
3. all_to_all ships slot buffers so device e holds every source shard's
   slots for expert e; the expert runs one batched FFN; the second
   all_to_all ships results back;
4. combine: each surviving token reads its expert output scaled by its
   gate probability (so gate gradients flow through the combine).
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["switch_moe", "moe_expert_params", "switch_moe_dense_reference"]


def switch_moe_dense_reference(x, gate_w, expert_params, expert_fn):
    """Per-token dense top-1 reference for ``switch_moe`` (no dispatch, no
    capacity): every token runs its argmax expert, scaled by the gate prob.
    Shared by the unit tests and the driver dryrun so the two equivalence
    checks can't silently diverge from the engine's combine semantics."""
    import jax
    import jax.numpy as jnp

    probs = np.asarray(jax.nn.softmax(jnp.asarray(x) @ jnp.asarray(gate_w), axis=-1))
    choice = probs.argmax(-1)
    out = np.zeros_like(np.asarray(x))
    for t in range(x.shape[0]):
        e = int(choice[t])
        p = jax.tree_util.tree_map(lambda a, _e=e: a[_e], expert_params)
        out[t] = probs[t, e] * np.asarray(expert_fn(p, jnp.asarray(x[t:t + 1])))[0]
    return out


def moe_expert_params(per_expert):
    """[pytree per expert] -> stacked pytree (leading E axis; shard on ep)."""
    import jax

    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *per_expert)


def switch_moe(x, gate_w, expert_params, expert_fn, mesh, axis_name="ep",
               capacity_factor=2.0):
    """x [B, D] (sharded over ``axis_name`` on dim 0) -> [B, D].

    gate_w [D, E]; expert_params stacked with leading E == axis size;
    expert_fn(params_slice, tokens [n, D]) -> [n, D].
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    E = int(dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name])
    B = x.shape[0]
    if B % E:
        raise ValueError("token count %d %% ep size %d != 0" % (B, E))
    if gate_w.shape[1] != E:
        # extra gate columns would silently zero every token routed past E
        raise ValueError(
            "gate_w has %d expert columns but the %r axis has %d devices"
            % (gate_w.shape[1], axis_name, E))
    lead = jax.tree_util.tree_leaves(expert_params)[0].shape[0]
    if lead != E:
        raise ValueError(
            "expert_params leading dim %d != ep size %d" % (lead, E))
    t_local = B // E
    C = int(np.ceil(capacity_factor * t_local / E))

    param_specs = jax.tree_util.tree_map(lambda _: P(axis_name), expert_params)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis_name), P(), param_specs),
        out_specs=P(axis_name),
        check_vma=False,
    )
    def run(xs, gw, params):
        # xs: this shard's tokens [t_local, D]
        my_params = jax.tree_util.tree_map(lambda p: p[0], params)
        logits = xs @ gw                                   # [t, E]
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)                # [t]
        gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]

        onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)        # [t, E]
        pos = jnp.cumsum(onehot, axis=0) * onehot - 1              # slot per token
        pos = pos.max(axis=1)                                      # [t]
        keep = (pos >= 0) & (pos < C)

        # dispatch [E, C, D]: one-hot scatter of kept tokens
        slot_onehot = (
            jax.nn.one_hot(expert, E, dtype=xs.dtype)[:, :, None]
            * jax.nn.one_hot(jnp.where(keep, pos, 0), C, dtype=xs.dtype)[:, None, :]
        ) * keep[:, None, None].astype(xs.dtype)                   # [t, E, C]
        dispatch = jnp.einsum("tec,td->ecd", slot_onehot, xs)      # [E, C, D]

        # ship slots: device e ends up with [E_src, C, D] for ITS expert
        recv = jax.lax.all_to_all(dispatch, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)       # [E*C, D]... tiled
        recv = recv.reshape(E, C, xs.shape[-1])
        hidden = expert_fn(my_params, recv.reshape(E * C, -1))
        hidden = hidden.reshape(E, C, -1)

        # ship results back to the token owners
        back = jax.lax.all_to_all(hidden, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)
        back = back.reshape(E, C, -1)                              # per-expert slots

        # combine: token reads (expert, slot), scaled by its gate prob;
        # dropped tokens contribute zero (straight-through Switch rule)
        out = jnp.einsum("tec,ecd->td", slot_onehot, back)
        return out * (gate * keep.astype(gate.dtype))[:, None]

    return run(x, gate_w, expert_params)
