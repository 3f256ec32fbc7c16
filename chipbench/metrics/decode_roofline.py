"""Share (%) of its roofline the decode-step program reaches: the least
time of one step (the larger of its FLOPs over peak FLOP/s and its bytes
over peak bandwidth, as counted by the reference the configuration
names; ``chipbench/flops.py``) over the program's device time per
execution from the trace."""
from chipbench import flops
from chipbench.metrics import program_ms


def read(rec):
    if not rec["peaks"]:
        return None
    ms = program_ms(rec, "_decode")
    c = rec["counters"]
    if not ms or not c["model_steps"]:
        return None
    lanes = c["token_steps"] / c["model_steps"]
    return 100.0 * flops.decode_step_seconds(rec, lanes) / (ms / 1e3)
