"""Valid (token, slot) lanes fed over the lanes the step program runs
(prefill_chunk x slots x steps) in the window."""
from chipbench.metrics._common import lane_use


def read(run):
    return lane_use(run)
