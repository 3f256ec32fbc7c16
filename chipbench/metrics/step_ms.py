"""Host milliseconds spent inside ``poll()`` per model step over the run
(``scheduler.model_steps``): the scheduler's whole step as the client
drives it."""


def read(rec):
    steps = rec["counters"]["model_steps"]
    return 1e3 * rec["poll_s"] / steps if steps else None
