"""Device time of the paged decode attention kernel per decode step in the
traced part of the window (every layer's call summed).  The kernel is the
Pallas custom call inside the scheduler's jitted ``decode`` program, which the
trace names after that function (``decode.11``); a decode step is one run of
the ``jit_decode`` program on the device."""
from chipbench import trace_reduce


def is_decode_kernel(event_name):
    parts = event_name.split(" ")
    return (len(parts) > 1 and parts[1] == "custom-call"
            and trace_reduce.op_name(event_name) == "decode")


def read(observed):
    if "busy_s" not in observed:
        return None
    trace = observed["trace"]
    steps = len(trace_reduce.module_runs(
        trace, lambda name: name.startswith("jit_decode")))
    if not steps:
        return None
    return 1e3 * trace_reduce.op_time_s(trace, is_decode_kernel) / steps
