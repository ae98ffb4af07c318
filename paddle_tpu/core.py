"""Device places and dtype utilities.

TPU-native analog of the reference's ``paddle/fluid/platform/place.h`` and
``framework/data_type.h``: a Place selects which jax backend the Executor
compiles for; dtypes are plain strings mapped to numpy/jax dtypes.  Unlike the
reference there is no per-op device dispatch — the whole block is compiled by
XLA for one device (or a mesh of them).
"""
from __future__ import annotations

import numpy as np


class Place:
    """Base device place.  A place names a platform and a device index and
    resolves to exactly that jax device or raises — it never substitutes
    another platform or another index."""

    _platform = "cpu"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self.device_id)

    def __eq__(self, other):
        return type(self) is type(other) and self.device_id == other.device_id

    def __hash__(self):
        return hash((type(self).__name__, self.device_id))

    def jax_device(self):
        """The jax device this place names; RuntimeError if there is none."""
        jax = safe_import_jax()
        try:
            devs = jax.devices(self._platform)
        except RuntimeError:
            devs = []
        if self.device_id >= len(devs):
            raise RuntimeError(
                "%r: jax has %d %r device(s); its default backend is %r with "
                "%s.  Use CPUPlace() to ask for the CPU, or no place at all "
                "for jax's default device."
                % (self, len(devs), self._platform, jax.default_backend(),
                   [str(d) for d in jax.devices()]))
        return devs[self.device_id]


class CPUPlace(Place):
    _platform = "cpu"


class TPUPlace(Place):
    """The native device of this framework (reference: CUDAPlace)."""

    _platform = "tpu"


def default_place():
    """The place "no place given" means: jax's default device (what
    ``JAX_PLATFORMS`` selected), as an explicit Place so the decision is
    visible (``exe.place``)."""
    jax = safe_import_jax()
    platform = jax.devices()[0].platform
    for cls in (TPUPlace, CPUPlace):
        if cls._platform == platform:
            return cls(0)
    raise RuntimeError(
        "jax's default device is a %r device; paddle_tpu runs on TPUs "
        "(TPUPlace) and on the CPU (CPUPlace)" % platform)


def cpu_backend() -> bool:
    """The one predicate behind every "how does this run here" choice: on
    the CPU backend (tests) Pallas kernels run interpreted, paged attention
    defaults to its gather reference and serving pools are not donated;
    on any other backend kernels compile and a refusal is an error."""
    return safe_import_jax().default_backend() == "cpu"


class CUDAPlace(TPUPlace):
    """Compatibility alias so reference scripts run unmodified: maps to the
    accelerator backend (TPU here).  Warns once so ported scripts can find
    leftover CUDA-specific placement."""

    _warned = False

    def __init__(self, device_id=0):
        if not CUDAPlace._warned:
            import warnings

            warnings.warn(
                "CUDAPlace maps to the TPU backend in paddle_tpu; use "
                "TPUPlace() directly", stacklevel=2)
            CUDAPlace._warned = True
        super().__init__(device_id)


class CUDAPinnedPlace(CPUPlace):
    pass


class EOFException(Exception):
    """Raised when a started py_reader pipeline is exhausted (reference:
    fluid.core.EOFException).  Deliberately NOT a StopIteration subclass:
    PEP 479 would mutate that into RuntimeError inside generator frames
    and silently end iterator-driven for-loops."""


# ---------------------------------------------------------------------------
# dtypes
# ---------------------------------------------------------------------------

_DTYPE_ALIASES = {
    "float32": "float32",
    "fp32": "float32",
    "float": "float32",
    "float64": "float64",
    "fp64": "float64",
    "double": "float64",
    "float16": "float16",
    "fp16": "float16",
    "bfloat16": "bfloat16",
    "bf16": "bfloat16",
    "int8": "int8",
    "uint8": "uint8",
    "int16": "int16",
    "int32": "int32",
    "int": "int32",
    "int64": "int64",
    "long": "int64",
    "bool": "bool",
}


def canonical_dtype(dtype) -> str:
    """Normalize a user dtype (str / np.dtype / jnp dtype) to a canonical
    string name."""
    if isinstance(dtype, str):
        name = dtype
    else:
        name = np.dtype(dtype).name if not hasattr(dtype, "name") else dtype.name
    name = str(name)
    if name not in _DTYPE_ALIASES:
        # np.dtype round trip for things like '<f4'
        name = np.dtype(name).name
    if name not in _DTYPE_ALIASES:
        raise ValueError("unsupported dtype: %r" % (dtype,))
    return _DTYPE_ALIASES[name]


def safe_import_jax():
    """Import jax with the ambient np.random state preserved.

    The FIRST ``import jax`` in a process consumes np.random draws during
    import, so a user's ``np.random.seed(N)`` placed before the import
    would pin a DIFFERENT startup draw than the same seed placed after it
    (first-run-vs-later-runs nondeterminism).  Every lazy jax import on a
    user-facing entry path goes through here; tests/unittests/
    test_first_run_determinism.py is the regression."""
    import sys

    if "jax" in sys.modules:
        import jax

        return jax
    state = np.random.get_state()
    import jax

    np.random.set_state(state)
    return jax


def np_dtype(dtype):
    name = canonical_dtype(dtype)
    if name == "bfloat16":
        import jax.numpy as jnp

        return jnp.bfloat16
    return np.dtype(name)


def is_float_dtype(dtype) -> bool:
    return canonical_dtype(dtype) in ("float16", "bfloat16", "float32", "float64")
