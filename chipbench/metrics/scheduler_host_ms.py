"""Host milliseconds per model step in the scheduler's own code: the
window's ``service.step`` span seconds less those of the spans directly
inside it (refill, model step, CDF build, coder, finished slots), over
``model_steps``. None unless every model step opened ``service.step``."""


def read(rec):
    spans = (rec.get("registry") or {}).get("spans", {})
    step = spans.get("service.step")
    steps = rec["counters"]["model_steps"]
    if not step or not steps or step["count"] != steps:
        return None
    child = sum(v["seconds"] for k, v in spans.items()
                if k.startswith("service.step/") and k.count("/") == 1)
    return 1e3 * (step["seconds"] - child) / steps
