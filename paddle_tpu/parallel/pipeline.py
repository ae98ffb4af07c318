"""Pipeline parallelism over a mesh axis (GPipe-style, SPMD).

Reference analog: none — Fluid v0.15 scales data-parallel only.  This is
the TPU-native pipeline engine: layer stages are sharded over the ``pp``
mesh axis (each device holds ONE stage's parameters), microbatches
stream through the ring with ``ppermute``, and every device runs the
same SPMD program — no per-stage processes, no send/recv ops.

Schedule: classic GPipe fill-drain.  With S stages and M microbatches
the loop runs T = M + S - 1 ticks; at tick t device s applies its stage
to the activation it received at t-1 and forwards the result to s+1.
Microbatch m leaves the last stage at tick m + S - 1.  Bubble fraction =
(S-1)/(M+S-1), the standard GPipe overhead; gradients flow through the
``ppermute``s (differentiable), so ``jax.grad`` of a pipelined loss is
pipeline-parallel backward for free.

Constraints (the standard homogeneous-pipeline contract): all stages
share one ``stage_fn`` (e.g. a transformer block) with per-stage
parameters stacked on a leading axis, and activations keep one shape
across stages.
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["pipeline_apply", "pipeline_apply_circular",
           "pipeline_stage_params", "circular_stage_index"]


def circular_stage_index(v, n_devices, repeats):
    """Storage row of virtual stage ``v`` in the device-major stacked layout
    used by the circular schedule: device ``v % S`` holds its ``repeats``
    slices contiguously, so a plain P('pp') sharding of the leading dim
    hands each device exactly its rows.  Shared by the sequential
    reference path so both paths read identical weights."""
    return (v % n_devices) * repeats + v // n_devices


def pipeline_stage_params(per_stage_params):
    """[pytree per stage] -> one pytree with a leading n_stages axis
    (shard this axis over 'pp')."""
    import jax

    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *per_stage_params)


def pipeline_apply_circular(stage_fn, stacked_params, x, mesh, n_microbatches,
                            repeats, axis_name="pp", side_inputs=None):
    """Circular (interleaved) pipeline: L = S*repeats virtual stages on S
    devices — device ``d`` hosts virtual stages ``d, d+S, d+2S, ...``
    (praxis-style circular placement), so every stage transition rides the
    same s -> s+1 ``ppermute`` ring, including the round wrap S-1 -> 0.

    Why: GPipe's bubble is (S-1)/(M+S-1) of the schedule.  The circular
    schedule STREAMS waves of S microbatches back to back — wave ``w``
    enters exactly as device 0 finishes its last slice of wave ``w-1`` —
    so the S-1 fill/drain cost is paid ONCE for M*R stage-rounds of work:
    bubble fraction (S-1)/(M*repeats + S-1), the standard interleaved-
    pipeline result, at the same device count.

    Schedule: microbatch g = w*S + m enters device 0 at tick w*L + m.  At
    tick u device s has exactly one job: with q = (u - s) mod L, its local
    slice is j = q // S (virtual stage v = j*S + s), processing microbatch
    m = q mod S of wave w = (u - s - q) / L — unique because the R
    candidate stages a device hosts have tick offsets spaced S apart, and
    only one lands in the S-wide entry window.  mb g leaves stage L-1 on
    device S-1 at tick w*L + m + L - 1; total ticks T = W*L + S - 1.

    ``stacked_params`` leading dim is L in the DEVICE-MAJOR layout of
    ``circular_stage_index`` (virtual stage v at row (v%S)*R + v//S), so
    sharding the leading dim over ``axis_name`` gives each device its own
    R slices contiguously.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    S = int(dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name])
    R = int(repeats)
    L = S * R
    B = x.shape[0]
    M = int(n_microbatches)
    if B % M:
        raise ValueError("batch %d %% microbatches %d != 0" % (B, M))
    if M % S:
        raise ValueError(
            "circular schedule needs microbatches (%d) in waves of the pp "
            "size (%d)" % (M, S))
    lead = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if lead != L:
        # a dim-S stack (the pipeline_apply convention) would shard to one
        # row per device and the dynamic slice index would silently clamp
        raise ValueError(
            "circular stacked_params leading dim %d != S*repeats = %d"
            % (lead, L))
    W = M // S
    T = W * L + S - 1
    mb = B // M
    xs = x.reshape((M, mb) + x.shape[1:])
    sides = None
    if side_inputs is not None and jax.tree_util.tree_leaves(side_inputs):
        sides = jax.tree_util.tree_map(
            lambda a: a.reshape((M, mb) + a.shape[1:]), side_inputs)

    param_specs = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    side_specs = jax.tree_util.tree_map(lambda _: P(), sides)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(param_specs, P(), side_specs),
        out_specs=P(),
        check_vma=False,
    )
    def run(params, xs, sides):
        idx = jax.lax.axis_index(axis_name)
        # this device's R slices: rows [d*R, (d+1)*R) of the device-major
        # layout land here under the P(axis_name) sharding
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(held, u):
            q = jnp.mod(u - idx, L)
            j = q // S                         # local slice index
            m = jnp.mod(q, S)
            w = (u - idx - q) // L             # wave (may be out of range
            g = jnp.clip(w * S + m, 0, M - 1)  # during fill/drain: discarded)
            my = jax.tree_util.tree_map(
                lambda p: jax.lax.dynamic_index_in_dim(
                    p, j, axis=0, keepdims=False),
                params)
            # entry: device 0 at virtual stage 0 (q < S) ingests microbatch
            # g while waves remain; the clamp keeps drain feeds finite
            feed = xs[g]
            inp = jnp.where((idx == 0) & (q < S) & (w < W), feed, held)
            if sides is None:
                out = stage_fn(my, inp)
            else:
                side_mb = jax.tree_util.tree_map(lambda a: a[g], sides)
                out = stage_fn(my, inp, side_mb)
            nxt = jax.lax.ppermute(out, axis_name, perm)
            return nxt, out

        _, outs = jax.lax.scan(tick, xs[0], jnp.arange(T))
        # mb g = w*S + m exits on device S-1 at tick w*L + m + L - 1
        exit_ticks = np.array(
            [w_ * L + m_ + L - 1 for w_ in range(W) for m_ in range(S)])
        mine = outs[exit_ticks]                # [M, mb, ...]
        mine = jnp.where(idx == S - 1, mine, jnp.zeros_like(mine))
        return jax.lax.psum(mine, axis_name)

    ys = run(stacked_params, xs, sides)  # [M, mb, ...]
    return ys.reshape((B,) + ys.shape[2:])


def pipeline_apply(stage_fn, stacked_params, x, mesh, n_microbatches,
                   axis_name="pp", side_inputs=None):
    """Run ``x`` through the S-stage pipeline.

    stage_fn(params_slice, activation[, sides]) -> activation, applied S
    times in sequence semantically; stacked_params has leading dim S
    (sharded over ``axis_name``); x is the full batch [B, ...] with
    B % n_microbatches == 0.  Returns the full output batch.  Call under
    jit (the shard_map is internal).

    ``side_inputs`` (optional pytree of [B, ...] arrays) are batch-aligned
    companions every stage reads but none transforms — e.g. an attention
    bias: each stage must see the SLICE belonging to the microbatch it is
    currently processing (a full-batch closure would shape-mismatch the
    microbatched activation).  When given, stage_fn is called as
    stage_fn(params, h, sides) with sides sliced to the in-flight
    microbatch.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    S = int(dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name])
    B = x.shape[0]
    if B % n_microbatches:
        raise ValueError("batch %d %% microbatches %d != 0" % (B, n_microbatches))
    M = n_microbatches
    mb = B // M
    xs = x.reshape((M, mb) + x.shape[1:])
    sides = None
    # an empty pytree ({} from a programmatically-built dict) means absent:
    # stage_fn keeps its two-arg signature
    if side_inputs is not None and jax.tree_util.tree_leaves(side_inputs):
        sides = jax.tree_util.tree_map(
            lambda a: a.reshape((M, mb) + a.shape[1:]), side_inputs)

    # per-device views: params [1, ...] (its own stage), xs replicated
    param_specs = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    side_specs = jax.tree_util.tree_map(lambda _: P(), sides)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(param_specs, P(), side_specs),
        out_specs=P(),
        check_vma=False,
    )
    def run(params, xs, sides):
        idx = jax.lax.axis_index(axis_name)
        my_params = jax.tree_util.tree_map(lambda p: p[0], params)
        perm = [(i, (i + 1) % S) for i in range(S)]
        T = M + S - 1

        def tick(carry, t):
            held = carry  # activation this device is about to process
            # stage 0 ingests microbatch t; during the drain (t >= M) it
            # re-feeds the LAST microbatch rather than zeros — the output
            # is discarded either way, but zeros would let a stage_fn that
            # is non-finite at 0 (e.g. x/||x||) poison parameter grads via
            # 0 * NaN in the VJP
            feed = xs[jnp.minimum(t, M - 1)]
            inp = jnp.where(idx == 0, feed, held)
            if sides is None:
                out = stage_fn(my_params, inp)
            else:
                # device idx processes microbatch t - idx at tick t (fill
                # ticks clamp to 0: the activation is discarded garbage,
                # the slice just has to be shape-right and finite)
                m = jnp.clip(t - idx, 0, M - 1)
                side_mb = jax.tree_util.tree_map(lambda a: a[m], sides)
                out = stage_fn(my_params, inp, side_mb)
            nxt = jax.lax.ppermute(out, axis_name, perm)
            # the LAST stage's output at tick t is microbatch t-(S-1)
            return nxt, out

        # initial carry is a REAL microbatch for the same reason as the
        # drain feed: fill-phase garbage is discarded, but it must stay
        # finite or it NaN-poisons the VJP
        _, outs = jax.lax.scan(tick, xs[0], jnp.arange(T))
        # outs[t] on device S-1 is microbatch t-(S-1); select those M slices
        last = outs[S - 1:]
        # only stage S-1 holds the real outputs; psum-broadcast them out
        mine = jnp.where(idx == S - 1, last, jnp.zeros_like(last))
        return jax.lax.psum(mine, axis_name)

    ys = run(stacked_params, xs, sides)
    return ys.reshape((B,) + ys.shape[2:])
