"""Sharded (multi-host) checkpointing via Orbax.

Reference analog: the reference checkpoints by gathering every parameter
to one host and writing flat files (io.py save_persistables +
checkpoint_notify between trainers).  That cannot scale to mesh-sharded
state — a tp-split embedding may not even fit one host.  Here each host
writes exactly its own shards and restore re-creates arrays WITH their
shardings, using Orbax (the standard JAX checkpoint layer):

    save_sharded(path, state, step=100)
    state = load_sharded(path, template=state)       # same shardings
    state = load_sharded(path)                       # host arrays

Works transparently for replicated single-chip state too, so
``Trainer``-style checkpoints can point here when the state lives on a
mesh.  Async by default is avoided (deterministic tests);
steps are versioned subdirectories with a ``latest`` resolution rule like
trainer.py's serials.
"""
from __future__ import annotations

import os
import warnings

import numpy as np

from . import resilience

__all__ = ["save_sharded", "load_sharded", "latest_step"]

# shared checkpoint filesystems hiccup; Orbax save/restore calls retry
# transient IO errors before giving up
SHARDED_IO_POLICY = resilience.RetryPolicy(
    max_retries=2, base_delay=0.1, max_delay=1.0,
    classify=resilience.is_transient_io_error)


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.Checkpointer(ocp.PyTreeCheckpointHandler())


def save_sharded(dirname, state, step=0):
    """Write one step-versioned sharded checkpoint of {name: array}."""
    from .core import safe_import_jax

    jax = safe_import_jax()

    path = os.path.abspath(os.path.join(dirname, "step_%d" % int(step)))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    # orbax refuses to overwrite; mirror trainer.py's serial semantics.
    # Multi-host: ONLY process 0 removes (N hosts racing rmtree on one
    # shared path crash on each other's deletions), and everyone barriers
    # before Orbax starts writing into the fresh directory.
    if os.path.exists(path):
        import shutil

        if jax.process_index() == 0:
            shutil.rmtree(path)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("paddle_tpu_ckpt_rm")
    arrays = {k: v if hasattr(v, "dtype") else np.asarray(v) for k, v in state.items()}
    resilience.call_with_retry(
        _checkpointer().save, path, arrays, policy=SHARDED_IO_POLICY)
    return path


def _steps(dirname):
    if not os.path.isdir(dirname):
        return []
    steps = []
    for n in os.listdir(dirname):
        if n.startswith("step_"):
            try:
                steps.append(int(n[5:]))
            except ValueError:
                pass
    return sorted(steps)


def latest_step(dirname):
    steps = _steps(dirname)
    return steps[-1] if steps else None


def load_sharded(dirname, step=None, template=None, fallback=True):
    """Restore {name: array}.  With ``template`` (a state dict of arrays
    whose shardings describe the target layout), each array is restored
    directly INTO that sharding — every host reads only its shards.

    Without an explicit ``step``, candidates are tried newest-first: a
    torn/corrupt step directory (crash mid-save) is skipped with a
    warning and the newest restorable step wins (``fallback=False``
    restores strictly the latest or raises).  An explicit ``step`` never
    falls back."""
    from .core import safe_import_jax

    safe_import_jax()
    import orbax.checkpoint as ocp

    def restore(path):
        if template is None:
            return resilience.call_with_retry(
                _checkpointer().restore, path, policy=SHARDED_IO_POLICY)

        def spec(v):
            if hasattr(v, "sharding"):
                return ocp.ArrayRestoreArgs(sharding=v.sharding, dtype=v.dtype)
            return ocp.RestoreArgs()

        restore_args = {k: spec(v) for k, v in template.items()}
        return resilience.call_with_retry(
            _checkpointer().restore, path,
            args=ocp.args.PyTreeRestore(restore_args=restore_args),
            policy=SHARDED_IO_POLICY)

    if step is not None:
        return restore(os.path.abspath(os.path.join(dirname, "step_%d" % int(step))))
    candidates = list(reversed(_steps(dirname)))
    if not candidates:
        raise IOError("no sharded checkpoints under %r" % dirname)
    failures = []
    for s in candidates:
        path = os.path.abspath(os.path.join(dirname, "step_%d" % s))
        try:
            return restore(path)
        except Exception as e:  # torn/corrupt step dir: try an older one
            if not fallback:
                raise
            failures.append("step %d: %s" % (s, e))
            warnings.warn(
                "skipping unrestorable sharded checkpoint step %d under %r "
                "(%s); falling back to an older step" % (s, dirname, e))
    raise IOError("no restorable sharded checkpoint under %r; tried "
                  "newest-first: %s" % (dirname, "; ".join(failures)))
