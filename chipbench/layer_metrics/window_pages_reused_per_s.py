"""Pages of the window group that LIVE sequences gave back to its allocator a
second of the window, as they fell out of the sliding window (the counter
``serving.cache.pages_released{group="window"}`` between the window's edges):
each is handed to whichever slot reaches a new page next.  What a cell with
arrivals and retirements has and a closed loop of standing requests has only
at its own decode rate."""
from chipbench import loop_cells


def read(observed):
    moved = observed.get("window_counters") or {}
    cell = loop_cells.labeled("serving.cache.pages_released", group="window")
    if cell not in moved or not observed.get("seconds"):
        return None
    return moved[cell] / observed["seconds"]
