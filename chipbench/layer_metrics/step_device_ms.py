"""Device busy time per training step: the union of the device's operation
intervals over the traced steps, divided by their number."""


def read(observed):
    if "busy_s" not in observed or not observed.get("traced_steps"):
        return None
    return 1e3 * observed["busy_s"] / observed["traced_steps"]
