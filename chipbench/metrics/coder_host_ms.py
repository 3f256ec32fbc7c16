"""Host milliseconds per model step in the rANS coder: the window's
``coder.step`` and ``rans.flush_slot`` span seconds from the service's
registry over ``model_steps``."""
from chipbench.spans import span_seconds


def read(rec):
    s = span_seconds(rec, ("coder.step", "rans.flush_slot"))
    steps = rec["counters"]["model_steps"]
    return 1e3 * s / steps if s is not None and steps else None
