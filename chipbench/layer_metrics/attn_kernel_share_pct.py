"""Share of the device's busy time spent in the flash attention kernels'
events: the Pallas custom calls, which the trace names after the traced
function (``flash_attention_fwd.33`` the forward, ``jvp_flash_attention_.33``
before PR 43; ``transpose_jvp_flash_attention__.33`` the fused backward).
Since PR 43 every training cell runs the fused backward (from 256 rows on),
so this is forward and backward together at every length.  A scan backward
would be plain XLA fusions with names of their own and would NOT be attributed
here; no cell runs one."""
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
