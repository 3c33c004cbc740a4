"""Closed-loop telemetry: measured per-rank timing, online straggler
estimation, and record/replay traces (DESIGN_TELEMETRY.md).

Four modules, consumed bottom-up by the launch drivers:

* :mod:`repro.telemetry.timing` — measurement. ``RankTimer`` wraps the
  jitted step with a host ``perf_counter`` around ``block_until_ready``
  and owns the in-graph per-rank gather (every host sees all TP ranks'
  clocks, refreshed once per control interval). ``StepSample`` is the
  unit record: ``{step, rank_times, plan_signature, work_frac, wall_s}``.
* :mod:`repro.telemetry.estimator` — estimation. ``StragglerEstimator``
  inverts the iteration-time decomposition under the ACTIVE plan's
  retained-work fraction, smooths with an EWMA, rejects single-sample
  spikes by median/MAD, gates on warmup, and serves the controller
  FULL-workload-equivalent times so the loop is not fooled by its own
  mitigation.
* :mod:`repro.telemetry.spans` — ``span(name, **counters)``, named host
  spans in the profiler's trace (a ``jax.profiler.TraceAnnotation``
  with checked plain-valued counters).
* :mod:`repro.telemetry.trace` — record/replay. Versioned JSONL
  ``TraceWriter``/``TraceReader`` for ``StepSample`` streams and
  ``schedule_from_trace`` which turns a recorded trace into a
  ``HeteroSchedule(kind="trace")`` replay.
"""
from repro.telemetry.estimator import EstimatorConfig, StragglerEstimator
from repro.telemetry.spans import span
from repro.telemetry.timing import (RankTimer, StepSample, capture_sample,
                                    measurement_rng)
from repro.telemetry.trace import (TRACE_SCHEMA, TRACE_VERSION,
                                   TraceFormatError, TraceReader,
                                   TraceWriter, replica_schedules,
                                   schedule_from_trace)

__all__ = [
    "EstimatorConfig", "StragglerEstimator", "RankTimer", "StepSample",
    "capture_sample", "measurement_rng", "span",
    "TRACE_SCHEMA", "TRACE_VERSION", "TraceFormatError", "TraceReader",
    "TraceWriter", "replica_schedules", "schedule_from_trace",
]
