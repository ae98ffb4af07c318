"""Windows closed a second of the measured window (the counter
``serving.decode.eva.windows_closed`` between its edges): each gives its
``window_size / page`` pages back at once and begins again from one row."""
from chipbench import eva_decode


def read(observed):
    moved = observed.get("window_counters") or {}
    cell = eva_decode._EVA + "windows_closed"
    if cell not in moved or not observed.get("seconds"):
        return None
    return moved[cell] / observed["seconds"]
