"""Seconds from process start to the first measured step: TPU start-up,
weights, the document pool, compiles (or cache loads) and warm-up."""


def read(rec):
    return rec["setup_s"]
