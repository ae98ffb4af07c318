"""jax compile requests (persistent-cache hits + misses) between the window's
start and end; expected 0."""


def read(observed):
    return observed.get("compiles_in_window")
