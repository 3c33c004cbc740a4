"""Whole serve step's share of the chip's bf16 peak: forward FLOPs that
the valid lanes of the window needed (trunk per lane, attention over
each lane's context, output head per emitted token), per second of
window, over the peak."""
from chipbench.metrics._common import step_mfu


def read(run):
    return step_mfu(run)
