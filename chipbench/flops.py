"""Least time of a run's decode step, from the work counts of the plain
reference its configuration names.

The record carries the reference's name (``rec["reference"]``, set by
``harness.run``); ``chipbench/reference/<name>.py`` gives the step's
operations per token, ``flops_per_token(m, pos)``, and its least bytes,
``decode_step_bytes(m, lanes, pos)``, for the configuration file's
``model`` section ``m``.
"""
from __future__ import annotations

import importlib


def counts(rec: dict):
    """The reference module named by the run's record."""
    return importlib.import_module(f"chipbench.reference.{rec['reference']}")


def decode_step_seconds(rec: dict, lanes: float) -> float:
    """Least time of one decode step over ``lanes`` coding lanes at the
    run's mean position on a chip with the record's peaks: the larger of
    its operations over peak FLOP/s and its bytes over peak bandwidth."""
    ref, m, pos = counts(rec), rec["model"], rec["mean_pos"]
    peaks = rec["peaks"]
    return max(lanes * ref.flops_per_token(m, pos) / peaks["bf16_flops"],
               ref.decode_step_bytes(m, lanes, pos)
               / peaks["hbm_bytes_per_s"])
