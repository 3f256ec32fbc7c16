"""Device idle milliseconds per service step of the traced stretch while
the host was in the ``scheduler`` bucket of ``chipbench/spans.py`` (the
innermost program span open over each idle piece decides it)."""
from chipbench.spans import idle_ms


def read(rec):
    return idle_ms(rec, "scheduler")
