"""Device milliseconds per execution of the top-K CDF program
(``topk_cdf`` of ``core/cdf.py``), from the profiler trace."""
from chipbench.metrics import program_ms


def read(rec):
    return program_ms(rec, "topk_cdf")
