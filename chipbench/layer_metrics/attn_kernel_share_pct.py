"""Share of the device's busy time spent in the flash attention kernels'
events: the Pallas custom calls, which the trace names after the traced
function (``jvp_flash_attention_.33`` the forward; a fused backward is a
custom call under the same function's name).  The scan backward (what ``auto``
picks under T = 2048) is plain XLA fusions with names of their own and is NOT
attributed here: at seq 256 this is the forward kernels' share alone."""
from chipbench import trace_reduce


def is_attention_kernel(event_name):
    parts = event_name.split(" ")
    return (len(parts) > 1 and parts[1] == "custom-call"
            and "flash_attention" in parts[0])


def read(observed):
    if "busy_s" not in observed:
        return None
    return (100.0 * trace_reduce.op_time_s(observed["trace"],
                                           is_attention_kernel)
            / observed["busy_s"])
