"""Backend compiles and compile-cache loads inside service steps in the
window: the delta of the service's ``scheduler.step_compiles``."""


def read(rec):
    c = (rec.get("registry") or {}).get("counters", {})
    return c.get("scheduler.step_compiles")
