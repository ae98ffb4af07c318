"""Mixture-of-Experts layers.

Two layers live here.  ``moe_topk`` (below ``switch_moe``) is the DROPLESS
top-k layer that knows which experts it holds: it routes every token over ALL
experts, sorts the chosen (token, expert) pairs by expert and computes the
pairs of the experts in ``experts_held`` with a grouped matrix product — no
capacity, no drop; the pairs of experts held elsewhere are left to whoever
holds them, and the parts of disjoint ranges (the shared experts counted
once) add up to the whole layer.  It is what ``models/deepseek_v3.py``
serves.  ``switch_moe`` is the Switch-style layer over an ``ep`` mesh axis that
the Fluid op trains.  Reference analog: none — Fluid v0.15 predates MoE.  TPU-native design
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

__all__ = ["switch_moe", "moe_expert_params", "switch_moe_dense_reference",
           "moe_topk", "route_topk", "grouped_matmul", "MOE_COUNTERS"]


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


# ---------------------------------------------------------------------------
# Dropless top-k experts over the experts held here.
# ---------------------------------------------------------------------------

MOE_COUNTERS = ("pairs", "experts_touched", "max_load")
_GMM_ROWS = 128         # rows of one product inside a grid step
_GMM_TILE_M = 512       # rows of the sorted pairs a grid step holds
_GMM_BLOCK_BYTES = 4 * 2 ** 20     # the most one expert's [K, tn] block holds
_GMM_VMEM_DEFAULT = 14 * 2 ** 20   # what fits the compiler's own scoped limit


SCORINGS = ("sigmoid", "softmax")


def route_topk(x, router_w, router_bias, *, top_k, scale=1.0,
               scoring="sigmoid"):
    """The router: ``(experts [T, k] int32, weights [T, k] float32)``.

    Scores in float32 at the highest matmul precision, in the form the MODEL
    states (``scoring``): ``sigmoid(x W_g)`` an expert, or ``softmax(x W_g)``
    over all experts.  The ``top_k`` experts of ``score + bias`` are chosen
    (the selection bias steers the choice only; on a tie the lower expert
    wins, as ``lax.top_k``), the weights are the chosen experts' SCORES,
    normalised to sum to one and scaled."""
    import jax
    import jax.numpy as jnp

    if scoring not in SCORINGS:
        raise ValueError("scoring is one of %s, got %r" % (SCORINGS, scoring))
    logits = jnp.dot(x.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, axis=-1) if scoring == "softmax"
              else jax.nn.sigmoid(logits))
    biased = scores if router_bias is None else scores + router_bias
    _, experts = jax.lax.top_k(biased, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), weights * scale


def _gmm_items(group_sizes, tile_m, n_tiles):
    """The (group, row tile) pairs a grouped product visits, row tile major:
    ``(item_group, item_tile, offsets [G + 1], n_items)`` with ``G + n_tiles
    - 1`` entries (the most there can be); entries past ``n_items`` repeat
    the last one, so their blocks are already there and they do nothing."""
    import jax.numpy as jnp

    G = group_sizes.shape[0]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(group_sizes).astype(jnp.int32)])
    lo, hi = offsets[:-1], offsets[1:]
    first = lo // tile_m
    count = jnp.where(hi > lo, (hi - 1) // tile_m - first + 1, 0)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(count).astype(jnp.int32)])
    n_items = starts[-1]
    i = jnp.minimum(jnp.arange(G + n_tiles - 1, dtype=jnp.int32),
                    jnp.maximum(n_items - 1, 0))
    group = jnp.clip(jnp.searchsorted(starts, i, side="right") - 1, 0, G - 1)
    tile = jnp.clip(first[group] + i - starts[group], 0, n_tiles - 1)
    return group.astype(jnp.int32), tile.astype(jnp.int32), offsets, n_items


def _gmm_tile_n(K, N, itemsize):
    """Output columns a grid step computes: the widest multiple of 128 that
    divides ``N`` and whose weight block ``K x tn x itemsize`` is at most
    ``_GMM_BLOCK_BYTES``; ``N`` itself where it has no such divisor.

    A grid step costs its block's bytes and a cost beside them that is
    0.3 us up to a block of about 2.6 MB and 0.6 us at 4 MB (``PERF.md``,
    section 5), and the work is the same whatever the tile: an expert's
    weights are read once, in ``N / tn`` blocks.  So the fewest steps whose
    blocks still stream win, up to the budget: from 2 MB a block on every
    product reads 88-91% of its bytes' speed, and 8 MiB bought 0-2% more."""
    fit = [tn for tn in range(128, N + 1, 128)
           if N % tn == 0 and K * tn * itemsize <= _GMM_BLOCK_BYTES]
    return max(fit, default=N)


def _gmm_kernel(group_ref, tile_ref, off_ref, n_ref, x_ref, w_ref, o_ref, *,
                tile_m, rows):
    """One grid step = one (column tile, item): the rows of item's group that
    lie in item's row tile, against that group's ``[K, tn]`` block, ``rows``
    at a time (a product of fewer rows costs the MXU as much: it is the
    block's load that takes the time, and a fixed cost a step beside it,
    which is why ``_gmm_tile_n`` makes the block as wide as its budget
    allows).  Consecutive items of one row tile keep the output block; its
    first item zeroes it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    g, t = group_ref[i], tile_ref[i]
    opens = (i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != t)

    @pl.when(opens)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _item():
        lo = jnp.maximum(off_ref[g], t * tile_m) - t * tile_m
        hi = jnp.minimum(off_ref[g + 1], (t + 1) * tile_m) - t * tile_m
        w = w_ref[...]

        def part(j, _):
            at = pl.ds(pl.multiple_of(j * rows, rows), rows)
            y = jnp.dot(x_ref[at, :], w, preferred_element_type=jnp.float32)
            r = j * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            o_ref[at, :] = jnp.where((r >= lo) & (r < hi), y,
                                     o_ref[at, :]).astype(o_ref.dtype)

        jax.lax.fori_loop(jax.lax.div(lo, rows),
                          jax.lax.div(hi + rows - 1, rows), part, None)


def grouped_matmul(x, w, group_sizes, *, layer=None, impl=None,
                   interpret=None):
    """``out[r] = x[r] @ w[group of row r]`` for rows sorted by group.

    x ``[M, K]``; w ``[G, K, N]``, or a stack ``[L, G, K, N]`` addressed in
    place by the static ``layer`` (no layer-sized slice is made on the chip);
    group_sizes ``[G]`` int32: the first ``group_sizes[0]`` rows belong to
    group 0, and so on.  Rows past ``sum(group_sizes)`` come back ZERO.
    Returns ``[M, N]`` float32.  A group with no row costs nothing: its
    weights are not read.  ``impl``: None/"auto" (the Pallas kernel on a TPU,
    ``lax.ragged_dot`` elsewhere), "reference", "pallas".

    The kernel's grid is ``(N / tn, G + row tiles - 1)``: one expert's ``[K,
    tn]`` block a step, the contraction never tiled.  ``tn`` follows from the
    block's bytes (:func:`_gmm_tile_n`: a step's fixed cost beside its
    block's bytes is what a narrow tile pays for), and counter
    ``moe.gmm.grid_steps{K,N,tn,tile_m,rows}`` (``rows`` = ``M``) says what
    each compiled shape got."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .. import observability as obs
    from ..core import cpu_backend

    if impl in (None, "auto"):
        impl = "reference" if cpu_backend() else "pallas"
    if impl not in ("reference", "pallas"):
        raise ValueError("impl must be auto|reference|pallas, got %r" % impl)
    if (w.ndim == 4) != (layer is not None):
        raise ValueError("w is [G, K, N], or [L, G, K, N] with layer=")
    M, K = x.shape
    G, N = w.shape[-3], w.shape[-1]
    group_sizes = group_sizes.astype(jnp.int32)
    live = (jnp.arange(M) < group_sizes.sum())[:, None]
    if impl == "reference":
        out = jax.lax.ragged_dot(
            x.astype(w.dtype), w if layer is None else w[layer], group_sizes,
            preferred_element_type=jnp.float32)
        return jnp.where(live, out, 0.0)
    if interpret is None:
        interpret = cpu_backend()
    rows = min(_GMM_ROWS, -(-M // 16) * 16)
    tile_m = min(_GMM_TILE_M, -(-M // rows) * rows)
    m_pad = -(-M // tile_m) * tile_m
    item = jnp.dtype(w.dtype).itemsize
    tn = _gmm_tile_n(K, N, item)
    if m_pad != M:
        x = jnp.pad(x, ((0, m_pad - M), (0, 0)))
    n_tiles = m_pad // tile_m
    # what was chosen, once per compiled shape (this runs at trace time): a
    # reader of a device trace divides the call's time by its grid steps
    steps = obs.counter("moe.gmm.grid_steps", labels={
        "K": K, "N": N, "tn": tn, "tile_m": tile_m, "rows": M})
    grid = (N // tn, G + n_tiles - 1)
    if not steps.value:
        steps.inc(grid[0] * grid[1])
    group, tile, offsets, n_items = _gmm_items(group_sizes, tile_m, n_tiles)
    lead = () if layer is None else (None,)
    at = () if layer is None else (int(layer),)
    # both operand blocks twice (the next step's copy in flight), the output
    # block twice, and the weight block once more as the value the products
    # read; stated to the compiler only where it passes its default, beside
    # the [rows, tn] float32 product and its select (any block near the 4 MiB
    # budget does; 3072 x 512 at a decode step's 256 rows, 13.6 MB, and the
    # tests' toy shapes fit as they are).  The widest cases under the budget:
    # mellum2's down product, whose output block is the full [512, 2304],
    # 23.7 MB; a [512, 4096] row tile against a [4096, 512] block, 23.1 MB
    need = (2 * item * (tile_m * K + K * tn) + 2 * 4 * tile_m * tn
            + item * K * tn)
    limit = ({} if need + 2 * 4 * rows * tn <= _GMM_VMEM_DEFAULT
             else dict(vmem_limit_bytes=int(need + 16 * 2 ** 20)))
    (out,) = pl.pallas_call(
        functools.partial(_gmm_kernel, tile_m=tile_m, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((tile_m, K),
                             lambda j, i, g, t, o, n: (t[i], 0)),
                pl.BlockSpec(lead + (None, K, tn),
                             lambda j, i, g, t, o, n: at + (g[i], 0, j))],
            out_specs=[pl.BlockSpec((tile_m, tn),
                                    lambda j, i, g, t, o, n: (t[i], j))]),
        out_shape=[jax.ShapeDtypeStruct((m_pad, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), **limit),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(group, tile, offsets, n_items.reshape(1), x.astype(w.dtype), w)
    # a row tile no group reaches was never written
    return jnp.where(live, out[:M], 0.0)


def moe_topk(x, router, experts, shared, *, top_k, experts_held, scale=1.0,
             token_mask=None, layer=None, impl=None, interpret=None,
             scoring="sigmoid"):
    """Dropless top-k experts, the part of ``experts_held``: ``(y [T, D]
    float32, counts [3] int32, chosen [T, k] int32)``.

    x ``[T, D]``.  router: ``{"w": [D, E], "bias": [E] or None}`` over ALL
    ``E`` experts (:func:`route_topk`, whose ``scoring`` the model states).
    experts: ``{"w_gu": [.., H, D, 2F], "w_down": [.., H, F, D]}``, the ``H = hi - lo`` SwiGLU experts ``lo ..
    hi - 1`` this caller holds (gate | up fused column-wise; a stack of
    layers with ``layer=``).  shared: None, or ``{"w_gu": [D, 2Fs], "w_down":
    [Fs, D]}``, added ONCE by whoever passes it.  ``token_mask [T]`` bool:
    rows that are padding route nowhere.  Every chosen pair of a held expert
    is computed (no capacity); a pair of an expert held elsewhere is left out
    here: the parts of disjoint ranges sum to the layer.  ``counts``
    (``MOE_COUNTERS``): held pairs, held experts with a pair, the most pairs
    one expert took.  ``chosen``: the router's choice over all ``E`` that the
    layer computed with (padding rows' too, though they go nowhere)."""
    import jax
    import jax.numpy as jnp

    lo, hi = experts_held
    T, D = x.shape
    F = experts["w_down"].shape[-2]
    chosen, weights = route_topk(x, router["w"], router.get("bias"),
                                 top_k=top_k, scale=scale, scoring=scoring)
    held = (chosen >= lo) & (chosen < hi)
    if token_mask is not None:
        held = held & token_mask[:, None]
    # pairs sorted by expert, the pairs left out after them
    key = jnp.where(held, chosen - lo, hi - lo).reshape(T * top_k)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=hi - lo + 1)[:hi - lo].astype(jnp.int32)
    xs = x[order // top_k]
    kw = dict(layer=layer, impl=impl, interpret=interpret)
    gu = grouped_matmul(xs, experts["w_gu"], sizes, **kw)
    act = (jax.nn.silu(gu[:, :F]) * gu[:, F:]).astype(x.dtype)
    down = grouped_matmul(act, experts["w_down"], sizes, **kw)
    w_sorted = jnp.where(held, weights, 0.0).reshape(T * top_k)[order]
    # back to (token, choice) order: a gather by the inverse permutation
    back = jnp.argsort(order)
    y = (down * w_sorted[:, None])[back].reshape(T, top_k, D).sum(axis=1)
    if shared is not None:
        sgu = jnp.dot(x.astype(shared["w_gu"].dtype), shared["w_gu"],
                      preferred_element_type=jnp.float32)
        fs = shared["w_down"].shape[0]
        y = y + jnp.dot(
            (jax.nn.silu(sgu[:, :fs]) * sgu[:, fs:]).astype(x.dtype),
            shared["w_down"], preferred_element_type=jnp.float32)
    counts = jnp.stack([sizes.sum(), (sizes > 0).sum(), sizes.max()])
    return y, counts.astype(jnp.int32), chosen
