"""Share of the traced window in which no operation ran on the device."""


def read(observed):
    if "busy_s" not in observed:
        return None
    return 100.0 * (1.0 - observed["busy_s"] / observed["traced_window_s"])
