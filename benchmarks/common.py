"""Shared benchmark helpers.

Every benchmark reproduces one paper table/figure (DESIGN.md §6) and
reports two kinds of numbers:

* RT — modeled runtime at PAPER SCALE (ViT-1B, e=8 V100-class ranks),
  from the analytic iteration model. The paper itself simulates
  heterogeneity by sleep injection, so modeled bulk-synchronous times are
  the same epistemics (DESIGN.md §7.4). V100: 112 TFLOP/s tensor peak.
* ACC — REAL training accuracy of the reduced model on CPU with the
  actual ZERO/SEMI machinery in the jitted step.

Output convention (benchmarks/run.py): ``name,us_per_call,derived`` CSV.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from typing import Optional

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT_DIR = os.path.join(ROOT, "experiments", "bench")

# paper-scale constants (Sec. V-A): 8x V100 (112 TFLOPS), ViT-1B
PAPER_E = 8
V100_FLOPS = 112e12
V100_MFU = 0.35


# Non-matmul fraction C/M of the paper's testbed, CALIBRATED from the
# paper's own headline ((8M+C)/(M+C) = 3.5 at χ=8 ⇒ C = 1.8·M): V100s on
# PCIe 3.0 with 1D-TP all-reduces every layer are communication-heavy.
PAPER_COMM_FRAC = 1.8


def paper_scale_model(arch: str = "vit-1b", batch: int = 64, seq: int = 65):
    """IterationModel for the paper's testbed (ViT-1B, bs=64, sql=65)."""
    from repro.config import ShapeConfig, get_config
    from repro.core.hetero import iteration_model
    cfg = get_config(arch)
    shape = ShapeConfig("paper", seq, batch, "train")
    return iteration_model(cfg, shape, PAPER_E, peak_flops=V100_FLOPS,
                           mfu=V100_MFU, comm_frac=PAPER_COMM_FRAC)


def is_dry_run() -> bool:
    """Tiny-shapes smoke mode (CI): set by `benchmarks/run.py --dry-run`.

    Benchmarks consult this to shrink device counts / shapes / iteration
    counts so the whole sweep finishes in seconds, not minutes."""
    return os.environ.get("REPRO_BENCH_DRY", "") == "1"


def save_json(name: str, payload: dict) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    path = os.path.join(OUT_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    return path


def save_bench_json(name: str, config: dict, metrics: dict,
                    trajectory: bool = False) -> str:
    """Write bench output in the STABLE schema shared by the CI smoke job
    and the per-PR trajectory files:

        {"name": <bench id>, "config": {...}, "metrics": {...}}

    Always lands in experiments/bench/<name>.json; with trajectory=True it
    is ALSO written to the repo root as BENCH_<name>.json (committed, so
    perf regressions are visible in per-PR diffs). Dry-run smoke never
    touches trajectory files — tiny-shape numbers must not clobber the
    committed full-scale points."""
    payload = {"name": name, "config": config, "metrics": metrics}
    path = save_json(name, payload)
    if trajectory and not is_dry_run():
        with open(os.path.join(ROOT, f"BENCH_{name}.json"), "w") as f:
            json.dump(payload, f, indent=1, default=float, sort_keys=True)
    return path


def run_subprocess_py(code: str, devices: int = 8, timeout: int = 1200,
                      with_bench_path: bool = False) -> str:
    """Run a snippet under N host devices; returns stdout.

    The child is pinned to the CPU backend: its mesh is N virtual host
    devices by design, and a parent that has imported jax may hold the
    accelerator. ``with_bench_path`` adds the repo root to PYTHONPATH so
    the snippet can import the ``benchmarks`` package itself."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    path = [os.path.join(ROOT, "src")] + ([ROOT] if with_bench_path else [])
    env["PYTHONPATH"] = os.pathsep.join(path)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, cwd=ROOT,
                         timeout=timeout)
    if out.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{out.stdout}\n{out.stderr}")
    return out.stdout


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
