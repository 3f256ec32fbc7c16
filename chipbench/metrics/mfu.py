"""The whole step's share (%) of the chip's peak: model FLOPs of the
tokens coded (``flops_per_token`` of the reference the configuration
names, at the mean position; ``chipbench/flops.py``) over the host
seconds spent stepping (inside ``poll()``) times peak bf16 FLOP/s. Time
between arrivals, when the service is idle, is not counted."""
from chipbench import flops


def read(rec):
    if not rec["peaks"]:
        return None
    c = rec["counters"]
    if not c["token_steps"] or rec["poll_s"] <= 0:
        return None
    work = c["token_steps"] * flops.counts(rec).flops_per_token(
        rec["model"], rec["mean_pos"])
    return 100.0 * work / (rec["poll_s"] * rec["peaks"]["bf16_flops"])
