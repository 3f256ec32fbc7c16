"""Input tokens coded in the window (the delta of the scheduler's
``token_steps`` counter from the window's start to its close) over the
window's seconds, host clock."""


def read(rec):
    return rec["counters"]["token_steps"] / rec["t_close"]
