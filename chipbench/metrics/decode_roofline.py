"""Share (%) of its roofline the decode-step program reaches: the least
time of one step (the larger of its FLOPs over peak FLOP/s and its bytes
over peak bandwidth: weights, the cache up to each coding lane's
position, the cache written and the logits; ``chipbench/flops.py``) over
the program's device time per execution from the trace."""
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
    least = flops.decode_step_seconds(rec["model"], rec["peaks"], lanes,
                                      rec["mean_pos"])
    return 100.0 * least / (ms / 1e3)
