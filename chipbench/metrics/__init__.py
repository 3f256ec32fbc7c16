"""One reader per metric: ``<name>.py`` defines ``read(rec)``, which
returns the metric's value from a run's record, or None where the run
gives it nothing to read. Helpers the readers share live here."""


def program_ms(rec, program):
    """Device milliseconds per execution of the jitted program named
    ``program`` in the run's trace; None without one."""
    t = rec.get("trace")
    p = (t or {}).get("programs", {}).get(program)
    if not p or not p["count"]:
        return None
    return 1e3 * p["seconds"] / p["count"]
