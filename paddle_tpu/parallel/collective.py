"""Collective communication surface (the NCCL-equivalent layer).

Reference analog: paddle/fluid/platform/nccl_helper.h + the NCCL all-reduce
inside ParallelExecutor (details/all_reduce_op_handle.cc).  On TPU these are
XLA collectives over ICI — thin wrappers around ``jax.lax`` so framework
code never imports jax directly, plus mesh helpers shared by
ParallelExecutor / ring attention / the dryrun harness.

All functions are *traceable*: call them inside jit/shard_map with a named
mesh axis.  XLA lowers them onto the ICI rings (or DCN when the mesh spans
hosts via jax.distributed).
"""
from __future__ import annotations

import numpy as np

__all__ = [
    "all_reduce",
    "psum",
    "pmean",
    "all_gather",
    "reduce_scatter",
    "ppermute",
    "all_to_all",
    "axis_index",
    "axis_size",
    "make_mesh",
    "device_count",
    "init_distributed",
    "shutdown_distributed",
]


# Tracks whether THIS module initialized jax.distributed, so repeat calls
# and teardown are classified by state rather than by parsing exception
# text (brittle across jax versions; a real failure whose message happens
# to contain "already"/"not initialized" must not be swallowed).
_DIST_STATE = {"initialized": False}


def init_distributed(coordinator_address=None, num_processes=None, process_id=None):
    """Join this host to the multi-host runtime (the analog of the
    reference's trainer/pserver endpoint wiring, but for SPMD: after this,
    ``jax.devices()`` spans every host and mesh axes may cross DCN).

    Arguments default from the reference's trainer environment variables —
    ``PADDLE_CURRENT_ENDPOINT``'s peer list analog ``PADDLE_COORDINATOR``
    (host:port of process 0), ``PADDLE_TRAINERS_NUM`` and
    ``PADDLE_TRAINER_ID`` — so launcher scripts port unchanged.  No-ops on
    repeat calls.
    """
    import os

    import jax

    coordinator_address = coordinator_address or os.environ.get("PADDLE_COORDINATOR")
    if num_processes is None:
        num_processes = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    if process_id is None:
        process_id = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    if not 0 <= process_id < num_processes:
        raise ValueError(
            "process_id %d out of range for %d processes" % (process_id, num_processes))
    if num_processes > 1 and not coordinator_address:
        raise ValueError(
            "multi-process init needs coordinator_address (or PADDLE_COORDINATOR)")
    if num_processes == 1 and not coordinator_address:
        return  # single host, no coordinator requested: nothing to wire up
    if _DIST_STATE["initialized"]:
        return  # repeat initialization is a documented no-op
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _DIST_STATE["initialized"] = True


def shutdown_distributed():
    import jax

    if not _DIST_STATE["initialized"]:
        return  # never initialized (by us): nothing to tear down
    jax.distributed.shutdown()
    _DIST_STATE["initialized"] = False


def psum(x, axis_name):
    import jax

    return jax.lax.psum(x, axis_name)


all_reduce = psum  # reference spelling


def pmean(x, axis_name):
    import jax

    return jax.lax.pmean(x, axis_name)


def all_gather(x, axis_name, axis=0, tiled=True):
    import jax

    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, scatter_dimension=0):
    import jax

    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension, tiled=True)


def ppermute(x, axis_name, perm):
    import jax

    return jax.lax.ppermute(x, axis_name, perm)


def all_to_all(x, axis_name, split_axis, concat_axis, tiled=True):
    import jax

    return jax.lax.all_to_all(x, axis_name, split_axis, concat_axis, tiled=tiled)


def axis_index(axis_name):
    import jax

    return jax.lax.axis_index(axis_name)


def axis_size(axis_name):
    import jax

    return jax.lax.psum(1, axis_name)


def device_count():
    import jax

    return jax.device_count()


def make_mesh(axes, devices=None):
    """Build a ``jax.sharding.Mesh`` from {axis_name: size} (insertion
    ordered).  A -1 size absorbs the remaining devices."""
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    names = list(axes)
    sizes = [axes[n] for n in names]
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        sizes[sizes.index(-1)] = len(devices) // known
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError("mesh %r needs %d devices, have %d" % (axes, total, len(devices)))
    arr = np.array(devices[:total]).reshape(sizes)
    return Mesh(arr, tuple(names))
