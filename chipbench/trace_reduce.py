"""From a profiler trace to numbers: busy and idle time of the device, time
per operation, and the idle gaps by what the host was doing.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` alone, into a plain form that a small recorded
trace can be kept in for the tests::

    {"planes": {plane: {line: [[name, start_ns, duration_ns], ...]}}}

A device plane is one whose name starts with ``/device:TPU:``; its ``XLA Ops``
line holds one event per executed HLO operation (events of a ``while`` or a
``call`` enclose those of their bodies, so times per operation are SELF times)
and its ``XLA Modules`` line one event per executed program.  The profiler
names an operation by its whole HLO text (``%copy.92 = bf16[6,8193,...]{...}
copy(...)``); :func:`short_op` cuts that to ``copy.92 copy bf16[6,8193,...]``:
the instruction's own name first, which is what the readers match on (a Pallas
kernel is a ``custom-call`` named after the traced function, e.g.
``jvp_flash_attention_.33``).  Host planes hold one line per thread; kept are
the benchmark's own ``jax.profiler.TraceAnnotation`` spans (``chipbench.*``)
and jax's own host events that say what a thread was doing (``HOST_WORDS``).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
# jax's own host events worth keeping: what the dispatching thread was doing
HOST_WORDS = ("np.asarray", "PjitFunction", "shard_args", "DevicePut")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def short_op(text):
    """``%name = shape{layout} opcode(operands...)`` -> ``name opcode shape``
    (other names pass through)."""
    if not text.startswith("%") or " = " not in text:
        return text
    name, rest = text[1:].split(" = ", 1)
    op = _OPCODE.search(" " + rest)
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return "%s %s %s" % (name, op.group(1) if op else "?", shape)


def op_name(event_name):
    """The instruction's own name, without its ``.<number>`` suffix."""
    name = event_name.split(" ", 1)[0]
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def find_xplane(log_dir):
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % log_dir)
    return found[-1]


def load_xplane(path):
    """The plain form of one ``.xplane.pb``: the device planes' operation and
    program lines (operations by :func:`short_op`), and of the host planes the
    ``chipbench.*`` spans and the ``HOST_WORDS`` events."""
    from jax.profiler import ProfileData

    planes = {}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if plane.name.startswith("/device:") and not device:
            continue
        lines = {}
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[short_op(e.name) if device else e.name,
                       int(e.start_ns), int(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith((SPAN_PREFIX,) + HOST_WORDS)]
            if events:
                lines.setdefault(line.name, []).extend(events)
        if lines:
            planes[plane.name] = lines
    return {"planes": planes}


def load_json(path):
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)


def device_planes(trace):
    return sorted(p for p in trace["planes"] if p.startswith(DEVICE_PREFIX))


def usable(trace):
    """Whether ``trace`` is there and saw a device (a CPU rehearsal's does
    not: its readers then return nothing)."""
    return trace is not None and bool(device_planes(trace))


def line_events(trace, plane, line):
    return trace["planes"].get(plane, {}).get(line, [])


def host_spans(trace, prefix=SPAN_PREFIX):
    """``[name, start_ns, duration_ns]`` of the host events whose names start
    with ``prefix``, over every thread of every host plane, by start."""
    out = []
    for plane, lines in trace["planes"].items():
        if plane.startswith("/device:"):
            continue
        for events in lines.values():
            out.extend(e for e in events if e[0].startswith(prefix))
    return sorted(out, key=lambda e: e[1])


def window(trace):
    """(start_ns, end_ns) of the traced window: the ``chipbench.window`` span
    if the run recorded one, else the extent of the device's operations."""
    spans = [e for e in host_spans(trace) if e[0] == WINDOW_SPAN]
    if spans:
        return spans[0][1], spans[0][1] + spans[0][2]
    ops = [e for p in device_planes(trace)
           for e in line_events(trace, p, OPS_LINE)]
    if not ops:
        raise ValueError("trace holds no device operation")
    return min(e[1] for e in ops), max(e[1] + e[2] for e in ops)


def merged(intervals):
    """Disjoint, sorted union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clipped(events, lo, hi):
    """``(start, end)`` of each event, cut to ``[lo, hi]``; empty ones go."""
    out = []
    for _, s, d in events:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            out.append((s, e))
    return out


def busy_ns(events, lo, hi):
    return sum(e - s for s, e in merged(clipped(events, lo, hi)))


def busy_and_window_s(trace):
    """Seconds in which an operation ran on the device, averaged over the
    device planes, and the length of the traced window."""
    lo, hi = window(trace)
    planes = device_planes(trace)
    if not planes:
        raise ValueError("trace holds no %s* plane" % DEVICE_PREFIX)
    busy = [busy_ns(line_events(trace, p, OPS_LINE), lo, hi) for p in planes]
    return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9


def self_times(events, lo=None, hi=None):
    """Seconds by operation name, each event's time less that of the events
    it encloses (bodies of loops and calls), within ``[lo, hi]``."""
    evs = sorted(((s, s + d, n) for n, s, d in events
                  if (lo is None or s + d > lo) and (hi is None or s < hi)),
                 key=lambda e: (e[0], -e[1]))
    total = {}
    stack = []          # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, name, own = stack.pop()
            total[name] = total.get(name, 0) + own

    for s, e, n in evs:
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, n, e - s])
    close(float("inf"))
    return {n: t / 1e9 for n, t in total.items()}


def top_device_ops(trace, n=10):
    """The ``n`` operations that took most device time (self time, summed
    over the device planes and averaged), as ``[[name, seconds], ...]``."""
    lo, hi = window(trace)
    planes = device_planes(trace)
    total = {}
    for p in planes:
        for name, t in self_times(line_events(trace, p, OPS_LINE), lo, hi).items():
            total[name] = total.get(name, 0.0) + t / len(planes)
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:n]]


def op_time_s(trace, match):
    """Self time of the device operations whose event name (``name opcode
    shape``) ``match`` accepts."""
    lo, hi = window(trace)
    planes = device_planes(trace)
    return sum(t for p in planes for name, t in self_times(
        line_events(trace, p, OPS_LINE), lo, hi).items() if match(name)
    ) / max(1, len(planes))


def module_runs(trace, match=None):
    """``[name, start_ns, duration_ns]`` of the programs the first device ran
    inside the window, optionally only those whose name ``match`` accepts."""
    lo, hi = window(trace)
    planes = device_planes(trace)
    if not planes:
        return []
    return [e for e in line_events(trace, planes[0], MODULES_LINE)
            if e[1] >= lo and e[1] + e[2] <= hi
            and (match is None or match(e[0]))]


def idle_gaps(trace, n=10, min_gap_ns=20_000):
    """The device's idle time inside the window by what the host was doing:
    each gap of the first device longer than ``min_gap_ns`` goes to the
    ``chipbench.*`` span that covers most of it, or where none does to jax's
    own host event that does (``host:<event>``), or to ``host:other``.
    ``[[name, seconds], ...]``, largest first."""
    lo, hi = window(trace)
    planes = device_planes(trace)
    if not planes:
        return []
    busy = merged(clipped(line_events(trace, planes[0], OPS_LINE), lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= min_gap_ns]
    spans = [e for e in host_spans(trace, "") if e[0] != WINDOW_SPAN]
    longest = max([e[2] for e in spans], default=0)
    total = {}
    j = 0
    for gs, ge in gaps:
        while j < len(spans) and spans[j][1] + longest < gs:
            j += 1
        ours, theirs = {}, {}
        for name, s, d in spans[j:]:
            if s >= ge:
                break
            o = min(ge, s + d) - max(gs, s)
            if o > 0:
                into = ours if name.startswith(SPAN_PREFIX) else theirs
                into[name] = into.get(name, 0) + o
        if ours:
            name = max(ours, key=ours.get)[len(SPAN_PREFIX):]
        elif theirs:
            name = "host:" + max(theirs, key=theirs.get)
        else:
            name = "host:other"
        total[name] = total.get(name, 0) + (ge - gs)
    return [[k, v / 1e9] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:n]]
