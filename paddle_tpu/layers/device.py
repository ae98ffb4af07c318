"""Device util layers (reference: python/paddle/fluid/layers/device.py).

The reference's ``get_places`` fed the deprecated ParallelDo; here the
multi-device path is ParallelExecutor over a mesh, so this is a host-side
shim returning the actual device list — enough for ported scripts that
only count devices or iterate them.
"""
from __future__ import annotations

__all__ = ["get_places"]


def get_places(device_count=None, device_type=None):
    """The visible accelerator (or CPU) devices, optionally truncated to
    ``device_count``.  ``device_type`` filters by platform name
    ("tpu"/"cpu"; "gpu"/"cuda" map to the accelerator backend)."""
    import jax

    devices = list(jax.devices())
    if device_type is not None:
        want = str(device_type).lower()
        if want == "tpu":
            # no silent substitution: scripts branch on this list's length
            devices = [d for d in devices if d.platform == "tpu"]
        elif want in ("gpu", "cuda"):
            # ported CUDA scripts: any accelerator counts (this framework's
            # accelerator backend is the TPU)
            devices = [d for d in devices
                       if d.platform in ("gpu", "cuda", "tpu")]
        elif want == "cpu":
            try:
                devices = list(jax.devices("cpu"))  # explicit backend: the
                # default-backend list omits CPUs on accelerator hosts
            except RuntimeError:
                devices = [d for d in devices if d.platform == "cpu"]
        else:
            raise ValueError("unknown device_type %r" % device_type)
    if device_count is not None:
        if device_count <= 0:
            raise ValueError("device_count must be positive, got %d" % device_count)
        devices = devices[: int(device_count)]
    return devices
