"""Rows the held experts computed per (lane, expert) pair routed to them:
the window deltas of the service's ``moe.expert_rows`` (experts held x
capacity, summed over layers; dropless dispatch makes the capacity every
lane of the step) over ``moe.routed_local``. The padding of dropless
dispatch: about 64 / (64 x 10 / 72) = 7.2 at 64 lanes. None where the
program counts no such rows."""


def read(rec):
    c = (rec.get("registry") or {}).get("counters", {})
    if not c.get("moe.routed_local") or "moe.expert_rows" not in c:
        return None
    return c["moe.expert_rows"] / c["moe.routed_local"]
