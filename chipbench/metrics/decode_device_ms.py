"""Device milliseconds per execution of the model's decode-step program
(the jitted ``_decode`` of ``serve/engine.py``, scope
``model_decode_step``), from the profiler trace."""
from chipbench.metrics import program_ms


def read(rec):
    return program_ms(rec, "_decode")
