"""Share of offered lane-steps that coded a token
(``scheduler.token_steps / scheduler.lane_steps``)."""


def read(rec):
    c = rec["counters"]
    return c["token_steps"] / c["lane_steps"] if c["lane_steps"] else None
