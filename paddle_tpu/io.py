"""Model save/load + inference model (reference: python/paddle/fluid/io.py).

Parameters live in the Scope as device arrays; save/load moves them to/from
disk.  ``filename=None`` → one file per variable (reference layout);
``filename=...`` → single combined ``.npz``.  Inference models serialize the
pruned Program as JSON (``__model__``) + params, mirroring the reference's
``__model__`` protobuf + param files.
"""
from __future__ import annotations

import json
import os
from io import BytesIO

import numpy as np

from . import observability as _obs
from . import resilience
from .executor import Executor, global_scope
from .framework import Parameter, Program, Variable, default_main_program

# transient-FS retry for every param file read/write (shared checkpoint
# mounts hiccup; a clean retry beats losing a save)
IO_RETRY_POLICY = resilience.RetryPolicy(
    max_retries=2, base_delay=0.05, max_delay=0.5)

__all__ = [
    "save_vars",
    "save_params",
    "save_persistables",
    "load_vars",
    "load_params",
    "load_persistables",
    "save_inference_model",
    "load_inference_model",
    "load_aot_inference_model",
    "get_inference_program",
    "read_artifact_bytes",
    "is_parameter",
    "is_persistable",
    "get_parameter_value",
    "get_parameter_value_by_name",
]


def is_parameter(var):
    return isinstance(var, Parameter)


def is_persistable(var):
    return bool(var.persistable)


def _var_bytes(scope, name):
    val = scope.vars.get(name)
    if val is None:
        raise KeyError("variable %r has no value in scope (run startup first?)" % name)
    return np.asarray(val)


def _write_npy(path, arr):
    """np.save through the resilience choke point: serialized in memory,
    written with fsync + transient-error retry (fault-injectable)."""
    buf = BytesIO()
    np.save(buf, np.asarray(arr))
    resilience.call_with_retry(
        resilience.fs_write_bytes, path, buf.getvalue(), policy=IO_RETRY_POLICY)


def _write_npz(path, arrays):
    buf = BytesIO()
    np.savez(buf, **arrays)
    resilience.call_with_retry(
        resilience.fs_write_bytes, path, buf.getvalue(), policy=IO_RETRY_POLICY)


def read_artifact_bytes(path):
    """Read a model-artifact file through the resilience choke point
    (``fs_read_bytes`` + transient-error retry).  Inference model loads
    (``__model__``, ``__aot__``, ``__aot_meta__``) share the checkpoint
    layer's fault-injectable read path, so a flaky model mount retries
    instead of killing a serving engine's (re)load — and
    ``testing.faults.flaky_io`` can target exact artifacts in tests."""
    return resilience.call_with_retry(
        resilience.fs_read_bytes, path, policy=IO_RETRY_POLICY)


def _write_artifact_bytes(path, data):
    resilience.call_with_retry(
        resilience.fs_write_bytes, path, data, policy=IO_RETRY_POLICY)


def _read_np(path):
    """np.load (npy or npz) through the resilience choke point."""
    data = read_artifact_bytes(path)
    return np.load(BytesIO(data), allow_pickle=False)


def save_vars(executor, dirname, main_program=None, vars=None, predicate=None, filename=None):
    main_program = main_program or default_main_program()
    if vars is None:
        vars = list(filter(predicate, main_program.list_vars()))
    scope = global_scope()
    os.makedirs(dirname, exist_ok=True)
    with _obs.span("io.save_vars", vars=len(vars)):
        if filename is None:
            for v in vars:
                _write_npy(os.path.join(dirname, v.name + ".npy"), _var_bytes(scope, v.name))
        else:
            if not filename.endswith(".npz"):
                filename += ".npz"  # np.savez appended it; keep the layout
            _write_npz(
                os.path.join(dirname, filename),
                {v.name: _var_bytes(scope, v.name) for v in vars},
            )


def save_params(executor, dirname, main_program=None, filename=None):
    save_vars(executor, dirname, main_program, predicate=is_parameter, filename=filename)


def save_persistables(executor, dirname, main_program=None, filename=None):
    save_vars(executor, dirname, main_program, predicate=is_persistable, filename=filename)


def load_vars(executor, dirname, main_program=None, vars=None, predicate=None, filename=None):
    main_program = main_program or default_main_program()
    if vars is None:
        vars = list(filter(predicate, main_program.list_vars()))
    scope = global_scope()
    if filename is None:
        for v in vars:
            path = os.path.join(dirname, v.name + ".npy")
            if not os.path.exists(path) and os.path.exists(
                    os.path.join(dirname, v.name)):
                # a directory saved by the REFERENCE framework: one binary
                # LoDTensor file per var, no .npy suffix (fluid_format.py)
                from .fluid_format import read_fluid_var_file

                arr, _lod = read_fluid_var_file(os.path.join(dirname, v.name))
                scope[v.name] = arr
                continue
            scope[v.name] = _read_np(path)
    else:
        data = _read_np(os.path.join(dirname, filename) + ("" if filename.endswith(".npz") else ".npz"))
        for v in vars:
            scope[v.name] = data[v.name]


def load_params(executor, dirname, main_program=None, filename=None):
    load_vars(executor, dirname, main_program, predicate=is_parameter, filename=filename)


def load_persistables(executor, dirname, main_program=None, filename=None):
    load_vars(executor, dirname, main_program, predicate=is_persistable, filename=filename)


def get_inference_program(target_vars, main_program=None):
    main_program = main_program or default_main_program()
    if not isinstance(target_vars, list):
        target_vars = [target_vars]
    return main_program.prune(target_vars)


def save_inference_model(
    dirname,
    feeded_var_names,
    target_vars,
    executor,
    main_program=None,
    model_filename=None,
    params_filename=None,
    export_for_deployment=True,
    aot=False,
    aot_feed_shapes=None,
    aot_platforms=None,
):
    """``aot=True`` additionally serializes a compiled executable
    (``__aot__`` StableHLO artifact via jax.export) with the weights baked
    in: a fresh process loads and predicts with NO Program rebuild and no
    re-trace — the deployment story the reference covers with its C++
    predictor (paddle/fluid/inference/api/paddle_inference_api.h,
    api_impl.cc).  The batch dim exports symbolically, so one artifact
    serves any batch size; other dims must be static (override with
    ``aot_feed_shapes={name: shape}``).  ``aot_platforms`` defaults to
    ("cpu", "tpu") — one artifact runs on either.  Ragged (lod_level>=1)
    feeds are not AOT-exportable — their @LENGTHS companions are runtime
    metadata; use the ``load_inference_model`` jit path for those."""
    main_program = main_program or default_main_program()
    if isinstance(feeded_var_names, str):
        feeded_var_names = [feeded_var_names]
    if not isinstance(target_vars, list):
        target_vars = [target_vars]
    os.makedirs(dirname, exist_ok=True)
    inference_program = main_program.prune(target_vars)
    model = {
        "program": inference_program.to_dict(),
        "feed_names": list(feeded_var_names),
        "fetch_names": [v.name if isinstance(v, Variable) else v for v in target_vars],
    }
    _write_artifact_bytes(
        os.path.join(dirname, model_filename or "__model__"),
        json.dumps(model).encode("utf-8"))
    params = [v for v in inference_program.list_vars() if is_persistable(v)]
    save_vars(executor, dirname, vars=params, filename=params_filename)
    if aot:
        _export_aot(
            dirname, inference_program, model["feed_names"],
            model["fetch_names"], aot_feed_shapes, aot_platforms)
    return model["fetch_names"]


def _export_aot(dirname, inference_program, feed_names, fetch_names,
                feed_shapes=None, platforms=None):
    import jax
    from jax import export as jax_export

    from .jax_bridge import program_to_fn
    from .ops.common import to_jdtype

    scope = global_scope()
    state = {
        v.name: np.asarray(scope.vars[v.name])
        for v in inference_program.list_vars()
        if is_persistable(v) and scope.vars.get(v.name) is not None
    }
    fn = program_to_fn(inference_program, fetch_names, is_test=True)

    def predict(*feed_arrays):
        return tuple(fn(state, dict(zip(feed_names, feed_arrays))))

    (b,) = jax_export.symbolic_shape("b")
    specs, dtypes = [], []
    for name in feed_names:
        var = inference_program.global_block().var(name)
        shape = list((feed_shapes or {}).get(name) or var.shape)
        if shape and int(shape[0]) in (-1, 0):
            shape[0] = b
        if any(isinstance(s, int) and s <= 0 for s in shape):
            raise ValueError(
                "AOT export needs static non-batch dims for feed %r, got %s "
                "(pass aot_feed_shapes={%r: full_shape})" % (name, shape, name))
        dt = to_jdtype(var.dtype)
        specs.append(jax.ShapeDtypeStruct(tuple(shape), dt))
        dtypes.append(np.dtype(dt).name)
    platforms = tuple(platforms or ("cpu", "tpu"))
    exported = jax_export.export(jax.jit(predict), platforms=platforms)(*specs)
    _write_artifact_bytes(os.path.join(dirname, "__aot__"),
                          bytes(exported.serialize()))
    _write_artifact_bytes(os.path.join(dirname, "__aot_meta__"), json.dumps({
        "feed_names": list(feed_names),
        "feed_dtypes": dtypes,
        "feed_shapes": [
            [str(d) for d in s.shape] for s in specs],
        "fetch_names": list(fetch_names),
        "platforms": list(platforms),
        "jax_version": jax.__version__,
    }).encode("utf-8"))


def load_aot_inference_model(dirname):
    """Load an ``aot=True`` artifact WITHOUT rebuilding the Program or
    re-tracing: returns ``(predict, feed_names, fetch_names)`` where
    ``predict(feed_dict) -> [fetch arrays]`` runs the deserialized
    compiled executable (weights baked in; batch size free).  The
    standalone CLI ``tools/predict.py`` does the same with only
    jax + numpy on the path."""
    from .core import safe_import_jax

    jax = safe_import_jax()
    from jax import export as jax_export

    meta = json.loads(
        read_artifact_bytes(
            os.path.join(dirname, "__aot_meta__")).decode("utf-8"))
    exported = jax_export.deserialize(
        bytearray(read_artifact_bytes(os.path.join(dirname, "__aot__"))))
    call = jax.jit(exported.call)
    feed_names = meta["feed_names"]
    dtypes = [np.dtype(d) for d in meta["feed_dtypes"]]

    def predict(feed):
        args = [np.asarray(feed[n], dt) for n, dt in zip(feed_names, dtypes)]
        return [np.asarray(o) for o in call(*args)]

    return predict, feed_names, meta["fetch_names"]


def load_inference_model(dirname, executor, model_filename=None, params_filename=None):
    model = json.loads(
        read_artifact_bytes(
            os.path.join(dirname, model_filename or "__model__"))
        .decode("utf-8"))
    program = Program.from_dict(model["program"])
    params = [v for v in program.list_vars() if is_persistable(v)]
    load_vars(executor, dirname, vars=params, filename=params_filename)
    fetch_vars = [program.global_block().var(n) for n in model["fetch_names"]]
    return program, model["feed_names"], fetch_vars


def get_parameter_value(para, executor):
    if not is_parameter(para):
        raise TypeError("expected a Parameter")
    return np.asarray(global_scope()[para.name])


def get_parameter_value_by_name(name, executor, program=None):
    program = program or default_main_program()
    return get_parameter_value(program.global_block().var(name), executor)
