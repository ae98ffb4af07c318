"""Testing utilities: deterministic fault injection for the resilience
layer (``paddle_tpu.testing.faults``) and the cost of an always-on path in
function calls (``paddle_tpu.testing.calls``)."""
from . import calls, faults  # noqa: F401
