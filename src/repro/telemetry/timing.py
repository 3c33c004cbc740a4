"""Measurement layer: per-rank step timing (DESIGN_TELEMETRY.md §1).

Two clocks feed a :class:`StepSample`:

* **host wall** — :class:`RankTimer` brackets the jitted step call with
  ``time.perf_counter`` around ``jax.block_until_ready``; this is the
  host's real, dispatch-inclusive step time.
* **per-rank segment clock** — each rank's locally measured matmul-path
  time. The jitted gather built by
  :func:`repro.launch.steps.build_rank_time_gather` all-gathers the
  local clocks over the mesh's ``model`` axis once per control interval,
  so every host sees ALL TP ranks' times — not just its own — without an
  all-reduce every iteration (the paper's passive-refresh discipline,
  Sec. III-A).

On the single-host simulator all "ranks" share one wall clock, so the
per-rank structure of the local clocks comes from the simulated
measurement backend (χ-schedule × :class:`IterationModel` × the ACTIVE
plan's work fraction — i.e. the mitigated runtime a real cluster would
observe). On real heterogeneous hardware the same gather carries
genuinely distinct local measurements; nothing downstream changes.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional

import numpy as np

from repro.telemetry.spans import span


@dataclasses.dataclass
class StepSample:
    """One control-interval measurement record (the trace unit).

    rank_times are the times AS MEASURED — i.e. under the plan that was
    active for the step (mitigated). ``work_frac`` records that plan's
    retained-work fraction so the estimator (and trace replay) can
    reconstruct full-workload-equivalent times exactly.
    """

    step: int
    rank_times: np.ndarray               # [e] measured per-rank seconds
    plan_signature: str = ""             # canonical static-plan signature
    work_frac: Optional[np.ndarray] = None   # [e] retained-work fraction
    wall_s: float = 0.0                  # host wall around block_until_ready

    def to_json(self) -> Dict[str, Any]:
        d = {"kind": "sample", "step": int(self.step),
             "rank_times": [float(t) for t in np.asarray(self.rank_times)],
             "plan_signature": self.plan_signature,
             "wall_s": float(self.wall_s)}
        if self.work_frac is not None:
            d["work_frac"] = [float(f) for f in np.asarray(self.work_frac)]
        return d

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "StepSample":
        wf = d.get("work_frac")
        return StepSample(
            step=int(d["step"]),
            rank_times=np.asarray(d["rank_times"], np.float64),
            plan_signature=d.get("plan_signature", ""),
            work_frac=(np.asarray(wf, np.float64) if wf is not None else None),
            wall_s=float(d.get("wall_s", 0.0)))


class RankTimer:
    """Host wall-clock + per-rank gather for the measurement loop.

    ``start``/``stop`` measure the real step wall (``stop`` blocks on the
    step outputs first, so async dispatch cannot hide device time), and
    open and close the profiler span ``repro.device`` around it.
    ``gather`` pushes a per-rank local-clock vector through the jitted
    all-gather — run every ``interval`` steps by ``maybe_gather`` so the
    collective stays off the per-iteration critical path.
    """

    def __init__(self, mesh=None, axis: str = "model", interval: int = 1):
        self.mesh = mesh
        self.axis = axis
        self.interval = max(int(interval), 1)
        self._gather_fn = None
        self._t0: Optional[float] = None
        self._span = None
        self.gather_count = 0

    # -- host wall ---------------------------------------------------------
    def start(self) -> None:
        self._span = span("repro.device")
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def stop(self, outputs=None) -> float:
        """Block on ``outputs`` (if given) and return elapsed seconds."""
        if outputs is not None:
            import jax
            jax.block_until_ready(outputs)
        t0 = self._t0 if self._t0 is not None else time.perf_counter()
        self._t0 = None
        wall = time.perf_counter() - t0
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
        return wall

    # -- per-rank gather ----------------------------------------------------
    def _gather(self):
        if self._gather_fn is None:
            from repro.launch.steps import build_rank_time_gather
            self._gather_fn = build_rank_time_gather(self.mesh, self.axis)
        return self._gather_fn

    def gather(self, local_times: np.ndarray) -> np.ndarray:
        """All-gather per-rank local clocks; returns the replicated [e]
        vector every host ends up holding."""
        if self.mesh is None or self.mesh.shape.get(self.axis, 1) <= 0:
            return np.asarray(local_times, np.float64)
        self.gather_count += 1
        out = self._gather()(np.asarray(local_times, np.float32))
        return np.asarray(out, np.float64)

    def maybe_gather(self, step: int, local_times: np.ndarray) -> np.ndarray:
        """Gather on control-interval boundaries; pass through otherwise."""
        if self.mesh is not None and step % self.interval == 0:
            return self.gather(local_times)
        return np.asarray(local_times, np.float64)


MEASURE_STREAM = 0x7E1E    # SeedSequence domain tag for measurement noise


def measurement_rng(seed: int) -> np.random.Generator:
    """Noise stream for simulated measurements, keyed off the run seed on
    its own SeedSequence domain so it never aliases the data or
    χ-schedule RNG streams."""
    return np.random.default_rng(
        np.random.SeedSequence((int(seed), MEASURE_STREAM)))


def capture_sample(model, chis, work_frac, *, step: int, plan=None,
                   wall: float = 0.0, rng=None, noise: float = 0.0,
                   timer: Optional[RankTimer] = None) -> StepSample:
    """Simulated-measurement backend SHARED by the train and serve
    drivers (so their trace/estimation semantics cannot diverge): what
    each rank would locally observe for this step — per-rank times under
    the ACTIVE plan (mitigated), optional multiplicative measurement
    noise — gathered across ranks once per control interval when a
    ``timer`` is supplied. Pass a timer only when the measurement vector
    is rank-aligned with its mesh axis (sim_ranks == real tp)."""
    meas = model.times(np.asarray(chis, np.float64),
                       np.asarray(work_frac, np.float64))
    if noise and rng is not None:
        meas = meas * (1.0 + rng.uniform(-noise, noise, len(meas)))
    if timer is not None:
        meas = timer.maybe_gather(step, meas)
    return StepSample(
        step=step, rank_times=meas,
        plan_signature=(plan.static.signature_str()
                        if plan is not None else ""),
        work_frac=np.asarray(work_frac, np.float64).copy(), wall_s=wall)
