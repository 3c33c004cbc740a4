"""Fused paged decode-attention kernel's share of its roofline, from its
device time in the trace and the work of the valid positions."""
from chipbench.metrics._common import attn_roofline


def read(run):
    return attn_roofline(run)
