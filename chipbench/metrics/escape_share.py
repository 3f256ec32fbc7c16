"""Share of coded tokens that took the escape slot
(``scheduler.escapes / scheduler.token_steps``)."""


def read(rec):
    c = rec["counters"]
    return c["escapes"] / c["token_steps"] if c["token_steps"] else None
