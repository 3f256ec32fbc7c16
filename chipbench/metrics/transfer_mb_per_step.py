"""Megabytes moved between host and device per model step: the window
deltas of the service's ``transfer.d2h_bytes`` (logits, ids and CDFs
fetched to the host) and ``transfer.h2d_bytes`` (logits handed to the
CDF program, previous tokens, refill masks) over ``model_steps``."""


def read(rec):
    c = (rec.get("registry") or {}).get("counters", {})
    steps = rec["counters"]["model_steps"]
    if "transfer.d2h_bytes" not in c or not steps:
        return None
    return (c["transfer.d2h_bytes"] + c["transfer.h2d_bytes"]) / steps / 1e6
