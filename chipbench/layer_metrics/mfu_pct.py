"""Model FLOPs per step (from shapes, ``chipbench/flops.py`` through the
model's ``flops_per_step``: forward and backward, recomputation and the masked
half of causal attention not counted) over the device's busy time per step,
over the chip's published bf16 peak."""


def read(observed):
    if "busy_s" not in observed or not observed.get("traced_steps"):
        return None
    return (100.0 * observed["flops_per_step"] * observed["traced_steps"]
            / observed["busy_s"] / observed["peak"]("bf16_flops"))
