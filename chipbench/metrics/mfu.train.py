"""Whole train step's share of the chips' bf16 peak: forward and
backward FLOPs of the images completed in the window (dense model, no
recomputation), per second of window, over chips x peak."""
from chipbench.metrics._common import step_mfu


def read(run):
    return step_mfu(run)
