"""Reductions the per-layer readers share."""
from __future__ import annotations

import math


def pct(values, q):
    """q-th percentile (0..100), nearest rank; None for no values."""
    v = sorted(values)
    if not v:
        return None
    k = max(0, min(len(v) - 1, math.ceil(q / 100.0 * len(v)) - 1))
    return float(v[k])


def step_mfu(run):
    """The model FLOPs of the steps that ran inside the traced part of
    the window, per second of it, over the chips' bf16 peak, in %."""
    c = run.get("counters") or {}
    t = run.get("trace") or {}
    if not c.get("traced_model_flops") or not t.get("window_s"):
        return None
    peak = run["chips"] * run["peaks"]["bf16_flops"]
    return 100.0 * c["traced_model_flops"] / t["window_s"] / peak


def lane_use(run):
    c = run.get("counters") or {}
    if not c.get("lanes_total"):
        return None
    return 100.0 * c["lanes_valid"] / c["lanes_total"]


def idle_share(run):
    t = run.get("trace") or {}
    if not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def attn_roofline(run):
    """The fused attention kernel's share of its roofline: the least
    time the chip needs for the q.K and p.V FLOPs and the K/V bytes of
    the valid positions fed in the traced steps, over the kernel's device
    time there. The serve step must hold exactly one kind of Mosaic
    kernel; anything else is a fault of the reading."""
    t = run.get("trace") or {}
    c = run.get("counters") or {}
    kernels = t.get("kernel_s") or {}
    if not kernels or not c.get("traced_steps"):
        return None
    if len(kernels) != 1:
        raise RuntimeError(f"attn_roofline: the serve step holds "
                           f"{sorted(kernels)}, not one Mosaic kernel")
    secs = next(iter(kernels.values()))
    p = run["peaks"]
    least = max(c["traced_attn_flops"] / p["bf16_flops"],
                c["traced_attn_bytes"] / p["hbm_bytes_per_s"])
    return 100.0 * least / secs
