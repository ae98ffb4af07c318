"""Compute-side introspection: per-compiled-program XLA cost/memory capture.

The serving stack got its signal plane in PR 8 (tracing, histograms,
``/metrics``); this module is the same idea one layer DOWN, at the
compiled-executable boundary.  XLA already computes everything a roofline
analysis needs — per-executable flop counts and bytes-accessed
(``cost_analysis()``) and the exact HBM footprint the allocator will
reserve (``memory_analysis()``: argument / output / temp / alias /
generated-code bytes) — but jax leaves it sitting on the ``Compiled``
object.  Here it is captured once per compiled step and published as
static ``compute.*`` registry gauges on the SAME export plane the
serving SLO dashboards already scrape: ``compute.flops_per_step``,
``compute.bytes_per_step`` (bytes accessed), ``compute.peak_hbm_bytes``
(argument+output+temp), ``compute.arg_bytes``, ``compute.temp_bytes``,
``compute.output_bytes``, ``compute.arith_intensity`` (flops/byte) and
``compute.roofline_compute_bound`` (1.0 when the program's intensity
exceeds the device's machine balance, else 0.0 — the roofline verdict).

**No step time here.**  A utilization needs the device's busy time,
which a host clock around an async dispatch does not give: MFU comes
from ``chipbench/layer_metrics/mfu_pct.py`` (model FLOPs counted from
shapes over the traced busy time), and host phases from the always-on
span cells (docs/observability.md "Phases").

**Cost model of the capture itself.**  The plane is OFF by default
(``PADDLE_TPU_XLA_STATS=1`` or :func:`enable` arms it); disabled, an
entry's runner pays one flag read per step and ``Executor.run`` itself
nothing.  Enabled, capture costs one
extra lowering+compile per (program, shapes) entry through the AOT path
— jax exposes no public handle to the executable its C++ jit path built,
so the introspection compile is a second one.  With the persistent
compilation cache on (``executor.enable_compilation_cache``) the second
compile is a cache hit; either way it happens once per entry, never per
step.  Capture never touches program state or RNG (lower+compile is
pure), so training is bitwise-identical with the plane on or off —
tested in test_xla_stats.py.

**Honesty notes.**  The roofline verdict is computed against the PEAK
of the device kind regardless of the dtype mix the program actually
issues; pass explicit ``peak_flops``/``peak_membw`` to :func:`enable` to
measure against a different roof.  Cost/memory analysis values are exact for the executable XLA built, and
deterministic for a fixed (program, shapes, jax/XLA version) — which is
what makes them usable as drift-gate invariants (tools/check_perf_drift.py).
"""
from __future__ import annotations

import os
import re
import threading

from . import registry as _reg

__all__ = [
    "enable",
    "disable",
    "active",
    "configure_peaks",
    "restore_defaults",
    "device_peaks",
    "ProgramStats",
    "capture_compiled",
    "capture_jitted",
    "extract_compiled",
    "program_stats",
    "all_stats",
    "summary",
    "reset",
    "GAUGES",
]

# every gauge the plane publishes, in one place: the export-coverage test
# and docs key off this tuple, so a renamed gauge breaks loudly
GAUGES = (
    "compute.flops_per_step",
    "compute.bytes_per_step",
    "compute.peak_hbm_bytes",
    "compute.arg_bytes",
    "compute.temp_bytes",
    "compute.output_bytes",
    "compute.arith_intensity",
    "compute.roofline_compute_bound",
)

# -- per-device peak table ----------------------------------------------------
# (peak dense flops/s, peak HBM bytes/s) PER JAX DEVICE, keyed by a
# substring of ``device.device_kind``.  v2/v3 expose one device per CORE
# (two cores per chip), v4+ one per chip (megacore) — the numbers below
# are per-jax-device accordingly.  Documentation figures for the bf16/
# dense roof; override with configure_peaks()/enable(peak_flops=...,
# peak_membw=...) when measuring against a different roof (fp8, int8,
# a measured STREAM number, ...).
PEAK_TABLE = (
    ("TPU v2", 22.5e12, 350e9),
    ("TPU v3", 61.25e12, 450e9),
    ("TPU v4", 275e12, 1228e9),
    ("TPU v5 lite", 197e12, 819e9),
    ("TPU v5e", 197e12, 819e9),
    ("TPU v5p", 459e12, 2765e9),
    ("TPU v6", 918e12, 1640e9),
    # named placeholder (not a default for unknown kinds): a roof so the
    # roofline verdict stays defined in the hermetic CPU test mesh; tests
    # pin explicit peaks instead of asserting against these.
    ("cpu", 1e11, 5e10),
)


def device_peaks(device_kind=None):
    """(peak_flops, peak_membw) per device for ``device_kind`` (default:
    the first jax device's kind).  Env overrides
    ``PADDLE_TPU_PEAK_FLOPS`` / ``PADDLE_TPU_PEAK_BW`` win over the
    table; a kind that matches no row is an error, not a default."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    for key, flops, bw in PEAK_TABLE:
        if key.lower() in str(device_kind).lower():
            break
    else:
        raise ValueError(
            "device_kind %r matches no row of PEAK_TABLE (%s); add its "
            "documented peaks there" % (
                device_kind, ", ".join(k for k, _, _ in PEAK_TABLE)))
    env_f = os.environ.get("PADDLE_TPU_PEAK_FLOPS")
    env_b = os.environ.get("PADDLE_TPU_PEAK_BW")
    if env_f:
        flops = float(env_f)
    if env_b:
        bw = float(env_b)
    return flops, bw


class ProgramStats:
    """Static cost/memory analysis of one compiled program entry (keyed
    by the executor's program tag, ``<id-hex>:v<version>``)."""

    __slots__ = ("tag", "flops", "bytes_accessed", "arg_bytes", "out_bytes",
                 "temp_bytes", "alias_bytes", "code_bytes", "peak_hbm_bytes",
                 "num_devices", "device_kind", "kernel_calls", "collectives")

    def __init__(self, tag, flops, bytes_accessed, arg_bytes, out_bytes,
                 temp_bytes, alias_bytes, code_bytes, num_devices,
                 device_kind, kernel_calls=0, collectives=0):
        self.tag = tag
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        self.arg_bytes = arg_bytes
        self.out_bytes = out_bytes
        self.temp_bytes = temp_bytes
        self.alias_bytes = alias_bytes
        self.code_bytes = code_bytes
        # what the allocator must reserve while the step runs: inputs +
        # outputs + scratch (aliased/donated bytes are already netted out
        # of output_size by XLA's accounting)
        self.peak_hbm_bytes = arg_bytes + out_bytes + temp_bytes
        self.num_devices = max(1, int(num_devices))
        self.device_kind = device_kind
        # compiled Pallas kernels in the executable (``tpu_custom_call``
        # instructions); 0 where kernels ran interpreted or not at all
        self.kernel_calls = int(kernel_calls)
        # cross-device instructions the partitioner put in (all-reduce,
        # all-gather, reduce-scatter, all-to-all, collective-permute)
        self.collectives = int(collectives)

    @property
    def arith_intensity(self):
        """Flops per byte accessed — the roofline x-coordinate."""
        if not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed

    def as_dict(self):
        return {
            "tag": self.tag,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "arg_bytes": self.arg_bytes,
            "out_bytes": self.out_bytes,
            "temp_bytes": self.temp_bytes,
            "alias_bytes": self.alias_bytes,
            "code_bytes": self.code_bytes,
            "peak_hbm_bytes": self.peak_hbm_bytes,
            "num_devices": self.num_devices,
            "device_kind": self.device_kind,
            "kernel_calls": self.kernel_calls,
            "collectives": self.collectives,
            "arith_intensity": self.arith_intensity,
        }

    def __repr__(self):
        return ("ProgramStats(%r, flops=%.3g, bytes=%.3g, peak_hbm=%.3g)"
                % (self.tag, self.flops, self.bytes_accessed,
                   self.peak_hbm_bytes))


class _Plane:
    """Module-wide capture state.  ``active`` is read by a compiled
    entry's runner until its one capture is done, so it is a plain
    attribute; everything behind it is lock-protected."""

    def __init__(self):
        self.active = os.environ.get("PADDLE_TPU_XLA_STATS", "0") == "1"
        self.peak_flops = None     # per-device override (None = table)
        self.peak_membw = None
        self.lock = threading.Lock()
        self.programs = {}         # tag -> ProgramStats
        self.last_tag = None


_plane = _Plane()

_captures = _reg.counter("compute.captures")
_capture_errors = _reg.counter("compute.capture_errors")


def active():
    """Whether the plane is armed."""
    return _plane.active


def enable(peak_flops=None, peak_membw=None):
    """Arm the capture plane.  ``peak_flops``/``peak_membw`` override the
    per-device peak table for the roofline verdict.  None arguments leave
    the current setting untouched, and overrides OUTLIVE :func:`disable`
    — call :func:`restore_defaults` to return to the table/env."""
    if peak_flops is not None:
        _plane.peak_flops = float(peak_flops)
    if peak_membw is not None:
        _plane.peak_membw = float(peak_membw)
    _plane.active = True


def disable():
    _plane.active = False


def configure_peaks(peak_flops=None, peak_membw=None):
    """Set (or with None, clear back to the table) the per-device peak
    overrides without toggling the plane."""
    _plane.peak_flops = None if peak_flops is None else float(peak_flops)
    _plane.peak_membw = None if peak_membw is None else float(peak_membw)


def restore_defaults():
    """Clear the peak overrides — ``enable()``'s otherwise persist
    process-wide (``disable`` only disarms), so tools that pin a roof
    for one report call this on the way out."""
    _plane.peak_flops = None
    _plane.peak_membw = None


def _peaks(device_kind):
    f, b = device_peaks(device_kind)
    if _plane.peak_flops is not None:
        f = _plane.peak_flops
    if _plane.peak_membw is not None:
        b = _plane.peak_membw
    return f, b


def _cost_dict(compiled):
    """``cost_analysis()`` normalized to one flat dict — older jax
    returns a one-element list of dicts, newer a dict."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca or {})


_COLLECTIVE_RE = re.compile(
    r"(?<=[\])}] )(?:all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


def extract_compiled(compiled, tag="<adhoc>", num_devices=None):
    """Build a :class:`ProgramStats` from a ``jax.stages.Compiled``
    WITHOUT registering it — the pure extraction, shared by the capture
    path, tools/perf_report.py and contrib.memory_usage.  Raises on a
    backend that implements neither analysis."""
    cost = {}
    try:
        cost = _cost_dict(compiled)
    except Exception:
        pass
    mem = None
    try:
        mem = compiled.memory_analysis()
    except Exception:
        pass
    if not cost and mem is None:
        raise RuntimeError(
            "backend exposes neither cost_analysis nor memory_analysis")
    if num_devices is None:
        try:
            num_devices = len(compiled.input_shardings[0][0].device_set)  # type: ignore[index]
        except Exception:
            num_devices = 1
    import jax

    g = lambda o, a: float(getattr(o, a, 0) or 0)  # noqa: E731
    hlo = compiled.as_text()
    return ProgramStats(
        tag,
        flops=float(cost.get("flops", 0.0) or 0.0),
        bytes_accessed=float(cost.get("bytes accessed", 0.0) or 0.0),
        arg_bytes=g(mem, "argument_size_in_bytes"),
        out_bytes=g(mem, "output_size_in_bytes"),
        temp_bytes=g(mem, "temp_size_in_bytes"),
        alias_bytes=g(mem, "alias_size_in_bytes"),
        code_bytes=g(mem, "generated_code_size_in_bytes"),
        num_devices=num_devices,
        device_kind=jax.devices()[0].device_kind,
        kernel_calls=hlo.count('custom_call_target="tpu_custom_call"'),
        collectives=len(_COLLECTIVE_RE.findall(hlo)),
    )


def capture_compiled(tag, compiled, num_devices=None):
    """Register ``compiled``'s analyses under ``tag`` and publish the
    static ``compute.*`` gauges.  Returns the :class:`ProgramStats` (or
    None when extraction failed — a capture failure must never take the
    step down)."""
    try:
        st = extract_compiled(compiled, tag, num_devices)
    except Exception:
        _capture_errors.inc()
        return None
    with _plane.lock:
        _plane.programs[tag] = st
        _plane.last_tag = tag
    _captures.inc()
    _publish_static(st)
    return st


def capture_jitted(tag, jitted, args, num_devices=None):
    """Lower+compile ``jitted`` on ``args`` through the AOT path and
    capture the result (the executor's hook; see the module docstring
    for the one-extra-compile cost model)."""
    try:
        compiled = jitted.lower(*args).compile()
    except Exception:
        _capture_errors.inc()
        return None
    return capture_compiled(tag, compiled, num_devices)


def _publish_static(st):
    _reg.gauge("compute.flops_per_step").set(st.flops)
    _reg.gauge("compute.bytes_per_step").set(st.bytes_accessed)
    _reg.gauge("compute.peak_hbm_bytes").set(st.peak_hbm_bytes)
    _reg.gauge("compute.arg_bytes").set(st.arg_bytes)
    _reg.gauge("compute.temp_bytes").set(st.temp_bytes)
    _reg.gauge("compute.output_bytes").set(st.out_bytes)
    ai = st.arith_intensity
    if ai is not None:
        _reg.gauge("compute.arith_intensity").set(ai)
        pf, pb = _peaks(st.device_kind)
        balance = pf / pb if pb else None
        if balance is not None:
            _reg.gauge("compute.roofline_compute_bound").set(
                1.0 if ai >= balance else 0.0)


def program_stats(tag=None):
    """The :class:`ProgramStats` for ``tag`` (default: the most recently
    captured program), or None."""
    with _plane.lock:
        if tag is None:
            tag = _plane.last_tag
        return _plane.programs.get(tag)


def all_stats():
    with _plane.lock:
        return dict(_plane.programs)


def summary():
    """One formatted table over every captured program — the quick look
    before reaching for tools/perf_report.py."""
    rows = sorted(all_stats().values(), key=lambda s: -s.flops)
    lines = ["%-22s %12s %12s %12s %10s" % (
        "Program", "GFLOPs", "MB accessed", "peak HBM MB", "intensity")]
    for st in rows:
        ai = st.arith_intensity
        lines.append("%-22s %12.3f %12.3f %12.3f %10s" % (
            st.tag, st.flops / 1e9, st.bytes_accessed / 1e6,
            st.peak_hbm_bytes / 1e6,
            "%.2f" % ai if ai is not None else "-"))
    return "\n".join(lines)


def reset():
    """Forget every captured program and zero the ``compute.*`` cells
    in place (tests, and the drift gate's per-scenario isolation)."""
    with _plane.lock:
        _plane.programs.clear()
        _plane.last_tag = None
    _reg.reset("compute.")
