"""End-to-end training driver with the SEMI-migration control loop.

Runs a REAL (reduced-size) model on the host devices: data pipeline →
jitted train step (with the workload-control plan as a runtime input) →
host-side controller (straggler detection / Eq.1-3) → checkpointing.
Heterogeneity is simulated per the paper (Sec. V-A): a χ-schedule feeds
the iteration-time model, whose per-rank times drive the controller; the
*measured* wall-clock of the bulk-synchronous step is then modeled as the
max over ranks (the real cluster behavior the technique removes).

Control threading (plan assembly, signature-keyed compile cache,
mitigation dispatch, telemetry) lives in the unified
:class:`repro.control.ControlPlane` shared with the serve engine
(DESIGN_CONTROL.md) — this driver owns only what is train-specific: the
optimizer, the data pipeline, weight-statistics observation and the
full-state checkpoint.

Checkpoints carry the COMPLETE train state — params, AdamW moments +
step, controller/estimator state and the data-pipeline position — so a
crash-interrupted run resumed with ``--resume`` is bit-identical to an
uninterrupted one (pinned by tests/test_system.py).

    PYTHONPATH=src python -m repro.launch.train --arch yi-6b --steps 50 \
        --tp 4 --control semi --hetero round_robin --chi 4
"""
from __future__ import annotations

# CLI nicety: when invoked as a script with --tp/--dp > 1, request that many
# host devices BEFORE jax initializes (shared jax-free helper).
from repro.launch._bootstrap import (argv_int as _argv_int,
                                     enable_compile_cache,
                                     ensure_host_devices)

ensure_host_devices(_argv_int("--tp") * _argv_int("--dp"))

import argparse
import dataclasses
import functools
import json
import os
from typing import Dict, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import store as ckpt_store
from repro.config import (ModelConfig, ShapeConfig, TrainConfig,
                          resolve_model)
from repro.control import ControlConfig, ControlPlane
from repro.control.plane import make_schedule
from repro.core import geometry as geom_lib
from repro.core import hetero as hetero_lib
from repro.core.workload import WorkloadPlan
from repro.data.pipeline import (PatternImageStream, TokenTaskStream,
                                 patchify, skip_batches)
from repro.launch import steps as steps_lib
from repro.launch.mesh import make_small_mesh
from repro.models import get_api
from repro.optim import adamw
from repro.sharding import ragged_local_width, use_mesh
from repro.telemetry import span


@dataclasses.dataclass
class TrainerState:
    params: object
    opt: object
    step: int = 0


# batches eval_accuracy consumes per eval event — shared by the eval call
# and the resume fast-forward, which must skip exactly this many per past
# event for a resumed run to stay equivalent to an uninterrupted one
EVAL_BATCHES = 4

# FFN pruning granularity the trainer plans at (control_block_size adapts
# it down when d_ff/tp is small); the ragged geometry quantizes to the
# same grid so geometry block counts and plan block counts line up
TRAIN_BLOCK = 8


def _resolve_geometry(spec: Optional[str], cfg, tp: int, *, hetero_kind: str,
                      chi: float, period: int, seed: int,
                      trace_in: Optional[str]):
    """Parse ``--geometry`` into a ShardGeometry (None = classic split).

    ``"chi"`` seeds the static split from the hetero schedule's step-0
    speed ratios (core/geometry.py geometry_from_chi — the steady-state
    χ of a static/persistent schedule); ``"a,b,..."`` gives explicit
    per-rank block counts summing to d_ff/TRAIN_BLOCK. Equal splits
    collapse to None so the geometry-free path stays bit-identical.
    """
    if spec is None or not str(spec).strip() \
            or str(spec).strip().lower() == "none":
        return None
    reason = geom_lib.geometry_unsupported_reason(cfg)
    if reason:
        raise ValueError(f"--geometry unsupported for {cfg.name}: {reason}")
    if cfg.d_ff % TRAIN_BLOCK:
        raise ValueError(
            f"--geometry needs d_ff divisible by {TRAIN_BLOCK} "
            f"(got {cfg.d_ff})")
    nb_total = cfg.d_ff // TRAIN_BLOCK
    if str(spec).strip().lower() == "chi":
        sched = make_schedule(hetero_kind, tp, chi=chi, period=period,
                              seed=seed, trace_in=trace_in)
        if sched is None:
            raise ValueError("--geometry chi needs a hetero schedule "
                             "(--hetero != none)")
        geo = geom_lib.geometry_from_schedule(sched, nb_total, TRAIN_BLOCK)
    else:
        sizes = geom_lib.parse_geometry_arg(str(spec), tp)
        geo = geom_lib.geometry_for_cfg(cfg, sizes, TRAIN_BLOCK)
    return None if geo.is_equal else geo


def stat_weights(params, scopes) -> Dict[str, jax.Array]:
    """The layer-stacked weight each controlled scope's statistics are
    taken over: ffn -> w_down [L, d_ff, d]; qkv -> wq [L, d, H*hd];
    attn_out -> wo [L, H*hd, d] (contraction axis after the layer axis
    in every case)."""
    st = params["stack"] if "stack" in params else params.get("decoder", {})
    scan = st.get("scan") if isinstance(st, dict) else None
    if scan is None:
        return {}
    out = {}
    for grp in (scan if isinstance(scan, tuple) else (scan,)):
        if not isinstance(grp, dict):
            continue
        if "ffn" in grp and "ffn" in scopes:
            out.setdefault("ffn", grp["ffn"]["w_down"])
        attn = grp.get("attn")
        if isinstance(attn, dict):
            if "qkv" in scopes and "wq" in attn:
                out.setdefault("qkv", attn["wq"])
            if "attn_out" in scopes and "wo" in attn:
                out.setdefault("attn_out", attn["wo"])
    return out


def _refresh(weights, snapshots, pruned_last, *, block: int):
    """Alg. 1's statistics refresh for each scope, on the device: the
    float32 mean over layers, its per-block mean |Δw| along the
    contraction axis against the snapshot (``block_variation``), and the
    snapshot advanced on the rows of blocks not pruned last window
    (``update_state``)."""
    fresh, advanced = {}, {}
    for name, w in weights.items():
        mean = w.astype(jnp.float32).mean(axis=0)
        snap = snapshots[name]
        per_row = jnp.abs(mean - snap).mean(axis=tuple(range(1, mean.ndim)))
        fresh[name] = per_row.reshape(-1, block).mean(axis=1)
        keep = jnp.repeat(pruned_last[name], block).reshape(
            (-1,) + (1,) * (mean.ndim - 1))
        advanced[name] = jnp.where(keep, snap, mean)
    return fresh, advanced


class WeightStats:
    """The controller's weight statistics, computed next to the weights.

    One executable, compiled here from the weights' shapes and shardings
    so that nothing compiles when it runs, takes each scope's layer-stacked
    weight, its snapshot and its ``[nb]`` pruned-last mask (``_refresh``).
    Only the ``[nb]`` variations come to the host; the snapshots stay on
    the device, each sharded as its mean over layers. A scope with no
    snapshot yet is measured against zeros, which the controller discards.
    """

    def __init__(self, weights: Dict[str, jax.Array], block: int):
        mesh = next(iter(weights.values())).sharding.mesh
        self.snap_sh = {k: NamedSharding(mesh, P(*tuple(w.sharding.spec)[1:]))
                        for k, w in weights.items()}
        self.nb = {k: w.shape[1] // block for k, w in weights.items()}
        self.blocks = sum(self.nb.values())
        self.nbytes = 4 * self.blocks          # float32 variations
        rep = NamedSharding(mesh, P())
        fn = jax.jit(functools.partial(_refresh, block=block),
                     in_shardings=({k: w.sharding for k, w in weights.items()},
                                   self.snap_sh, {k: rep for k in weights}),
                     out_shardings=({k: rep for k in weights}, self.snap_sh))
        snaps = {k: jax.ShapeDtypeStruct(w.shape[1:], jnp.float32)
                 for k, w in weights.items()}
        self.refresh = fn.lower(
            {k: jax.ShapeDtypeStruct(w.shape, w.dtype)
             for k, w in weights.items()}, snaps,
            {k: jax.ShapeDtypeStruct((n,), jnp.bool_)
             for k, n in self.nb.items()}).compile()
        self.zeros = {k: jax.device_put(np.zeros(s.shape, s.dtype),
                                        self.snap_sh[k])
                      for k, s in snaps.items()}

    def __call__(self, weights, priority):
        """Fresh ``[nb]`` variation per scope (numpy, fetched in the span
        ``repro.control.weight_stats.fetch``) and the advanced snapshots
        (device arrays), given the controller's priority states."""
        snaps, masks = {}, {}
        for k, n in self.nb.items():
            st = priority.get(k)
            have = st is not None and st.snapshot is not None
            snaps[k] = (jax.device_put(st.snapshot, self.snap_sh[k])
                        if have else self.zeros[k])
            masks[k] = st.pruned_last if have else np.zeros((n,), bool)
        fresh, advanced = self.refresh(weights, snaps, masks)
        with span("repro.control.weight_stats.fetch", bytes=self.nbytes):
            fresh = jax.device_get(fresh)
        return fresh, advanced


def run_training(arch: Union[str, ModelConfig], *, steps: int = 50,
                 tp: int = 1, dp: int = 1, control_mode: str = "off",
                 hetero_kind: str = "none",
                 chi: float = 2.0, lr: float = 3e-3, batch: int = 8,
                 seq: int = 64, seed: int = 0, log_every: int = 10,
                 ckpt_dir: Optional[str] = None, resume: bool = False,
                 imputation: str = "zero", selection: str = "priority",
                 hetero_period: int = 10, mig_blocks: int = 0,
                 max_sources: int = 3,
                 eval_every: int = 0, quiet: bool = False,
                 force_gamma: Optional[float] = None,
                 data_noise: float = 0.35,
                 use_kernel: bool = False,
                 psum_chunks: int = 1,
                 times: str = "modeled",
                 trace_in: Optional[str] = None,
                 trace_out: Optional[str] = None,
                 measure_noise: float = 0.0,
                 ckpt_every: int = 50,
                 geometry: Optional[str] = None) -> Dict:
    """Returns a summary dict (loss/acc curves, modeled step times).

    ``arch`` is a registered name (trained at its smoke variant) or a
    :class:`ModelConfig`, trained as given."""
    cfg = resolve_model(arch)
    arch = cfg.name if isinstance(arch, ModelConfig) else arch
    cfg_canonical = cfg
    geo = _resolve_geometry(geometry, cfg, tp, hetero_kind=hetero_kind,
                            chi=chi, period=hetero_period, seed=seed,
                            trace_in=trace_in)
    if geo is not None:
        # static uneven sharding, realized as a zero-padded equal GSPMD
        # split (core/geometry.py): the model config carries the padded
        # d_ff; params are initialized canonically and expanded below
        cfg = geom_lib.apply_geometry_cfg(cfg, geo)
    api = get_api(cfg)
    mesh = make_small_mesh(dp, tp)
    if geo is not None:
        ragged_local_width(geo.padded_width, mesh)
    train_cfg = TrainConfig(learning_rate=lr, steps=steps)
    shape = ShapeConfig("trainer", seq, batch, "train")

    control_cfg = ControlConfig(
        mode=control_mode, hetero_kind=hetero_kind, chi=chi,
        period=hetero_period, block_size=TRAIN_BLOCK,
        max_sources=max_sources, shed_cap=mig_blocks,
        # training default: Eq.(2) balances migration vs. resize cost
        # (the serve engine's ControlConfig default is "lossless")
        beta_policy="eq2",
        imputation=imputation, selection=selection,
        use_kernel=use_kernel, psum_chunks=psum_chunks,
        seed=seed, times=times,
        trace_in=trace_in, trace_out=trace_out,
        measure_noise=measure_noise,
        geometry=geo.sizes if geo is not None else None,
    ).to_workload(
        enabled=control_mode != "off" or force_gamma is not None,
        # legacy CLI contract: --mig-blocks 0 disables migration entirely;
        # otherwise it caps the per-source shed count
        migration_sources=max_sources if mig_blocks > 0 else 0)

    with use_mesh(mesh):
        # Plan-signature compile cache: the controller's multi-straggler
        # plans change the STATIC shed counts, so the step function is
        # (re)built per canonical signature; shed quantization keeps the
        # signature set small and each one compiles at most once.
        def _build_step(static):
            fn_, _, in_sh_, out_sh_ = steps_lib.build_train_step(
                cfg, shape, mesh, train_cfg, static, total_steps=steps,
                use_kernel=control_cfg.use_kernel,
                psum_chunks=control_cfg.psum_chunks)
            jitted = jax.jit(fn_, in_shardings=in_sh_, out_shardings=out_sh_)
            n_slots = max(1, static.num_sources) if static is not None else 0
            return jitted, n_slots, in_sh_

        # -- unified control plane (plan assembly / compile cache /
        # mitigation dispatch / telemetry, shared with the serve engine) --
        # the latency model prices the CANONICAL workload — under a ragged
        # geometry the padded lanes are inert zeros, not extra FLOPs, and
        # work_fraction reports in equal-shard (L_eq) units to match
        it_model = hetero_lib.iteration_model(cfg_canonical, shape,
                                              max(tp, 1),
                                              peak_flops=5e9, mfu=1.0)
        plane = ControlPlane(
            cfg, control_cfg, mesh=mesh, tp=tp, builder=_build_step,
            it_model=it_model, controller_blocks="global",
            hetero_kind=hetero_kind, chi=chi, period=hetero_period,
            seed=seed, trace_in=trace_in, trace_out=trace_out,
            trace_meta={"arch": arch, "hetero": hetero_kind,
                        "control": control_mode, "seed": seed},
            measure_noise=measure_noise,
            geometry=geo.sizes if geo is not None else None)
        step_jit, plan_slots, in_sh = plane.base
        controller = plane.controller
        scopes = plane.scopes

        # real init. Geometry runs initialize CANONICAL params (same RNG
        # draws as the equal-shard run) and expand them into the padded
        # ragged layout — rank r's shard holds its geometry[r] real blocks
        # first, zero padding after (inert fwd/bwd and under AdamW).
        box = {}
        if geo is not None:
            p_host, box["ax"] = api.init(jax.random.PRNGKey(seed),
                                         cfg_canonical,
                                         jnp.dtype(train_cfg.param_dtype))
            params = jax.device_put(
                geom_lib.expand_ffn_params(p_host, geo), in_sh[0])
        else:
            def init_fn():
                p, ax = api.init(jax.random.PRNGKey(seed), cfg,
                                 jnp.dtype(train_cfg.param_dtype))
                box["ax"] = ax
                return p
            params = jax.jit(init_fn, out_shardings=in_sh[0])()
        opt = jax.device_put(adamw.init(params), in_sh[1])

        # -- resume: restore the FULL train state (params + optimizer
        # moments/step + control-plane state + data position), so the
        # resumed run is equivalent to never having stopped. Legacy
        # params-only checkpoints restore what they have.
        start_step = 0
        batches_drawn = 0
        if ckpt_dir and resume:
            last = ckpt_store.latest_step(ckpt_dir)
            if last is not None:
                man = ckpt_store.read_manifest(ckpt_dir, last)
                extra = man.get("extra", {})
                # the checkpointed param layout is geometry-dependent —
                # resuming across geometries would silently misassign
                # blocks to ranks, so mismatches fail loudly (legacy
                # checkpoints carry no key == equal split)
                ck_geo = extra.get("geometry")
                cur_geo = list(geo.sizes) if geo is not None else None
                if (ck_geo or cur_geo) and list(ck_geo or []) != \
                        list(cur_geo or []):
                    raise ValueError(
                        f"checkpoint shard geometry {ck_geo} does not "
                        f"match this run's geometry {cur_geo}; resuming "
                        "across geometries is not supported")
                if extra.get("layout") == ckpt_store.TRAIN_STATE_LAYOUT:
                    params = ckpt_store.restore(ckpt_dir, last, params,
                                                in_sh[0], prefix="params")
                    opt = ckpt_store.restore(ckpt_dir, last, opt, in_sh[1],
                                             prefix="opt")
                    plane.load_state(
                        ckpt_store.load_arrays(ckpt_dir, last, "plane"),
                        extra.get("plane"))
                    start_step = int(extra.get("train_step", last))
                    batches_drawn = int(extra.get("data_batches", start_step))
                else:
                    params = ckpt_store.restore(ckpt_dir, last, params,
                                                in_sh[0])
                    start_step = last
                    batches_drawn = last

        # the statistics refresh compiles here, with the plane's steps,
        # so none compiles inside the loop
        stat_like = stat_weights(params, scopes)
        weight_stats = (WeightStats(stat_like, plane.wc.block_size)
                        if controller is not None and stat_like else None)

        def save_ckpt(step_now: int) -> None:
            tree = {"params": params, "opt": opt}
            plane_arrays = plane.state_arrays()
            if plane_arrays:
                tree["plane"] = plane_arrays
            ckpt_store.save(ckpt_dir, step_now, tree, extra={
                "layout": ckpt_store.TRAIN_STATE_LAYOUT,
                "train_step": step_now,
                "data_batches": batches_drawn,
                "plane": plane.state_meta(),
                "geometry": list(geo.sizes) if geo is not None else None,
                "arch": arch, "tp": tp, "dp": dp, "seed": seed})

        # data
        if cfg.num_classes:
            stream = iter(PatternImageStream(batch_size=batch, seed=seed,
                                             noise=data_noise))
            eval_stream = iter(PatternImageStream(batch_size=batch,
                                                  seed=seed + 777,
                                                  noise=data_noise))
        else:
            stream = iter(TokenTaskStream(cfg.vocab_size, seq, batch,
                                          seed=seed))
            eval_stream = None
        if batches_drawn:
            # re-align the synthetic streams with the checkpointed position
            skip_batches(stream, batches_drawn)
            if eval_stream is not None and eval_every:
                skip_batches(eval_stream,
                             EVAL_BATCHES * (start_step // eval_every))

        def make_batch():
            b = next(stream)
            if cfg.num_classes:
                b = {"patches": patchify(b["images"]), "labels": b["labels"]}
            if cfg.family == "vlm" and cfg.frontend and not cfg.num_classes:
                b["patch_embeds"] = np.random.default_rng(0).standard_normal(
                    (batch, cfg.frontend.num_tokens, cfg.d_model)).astype(
                        np.float32) * 0.02
            if cfg.encdec is not None:
                b["frame_embeds"] = np.random.default_rng(0).standard_normal(
                    (batch, cfg.encdec.encoder_seq_len, cfg.d_model)).astype(
                        np.float32) * 0.02
            return b

        work_frac = np.ones((tp,))
        history = {"loss": [], "acc": [], "modeled_step_s": [],
                   "gammas": [], "mig": [], "mig_shed": [],
                   "buckets": [], "signatures": [], "wall_s": []}

        plan = None
        for it in range(start_step, steps):
            with span("repro.train.step", step=it):
                chis = plane.chis(it)
                plan_arrays = None
                report = None
                plan = None
                step_fn = step_jit
                if controller is not None:
                    if force_gamma is not None:
                        # Figs. 5/6: force a uniform γ on EVERY rank
                        from repro.core.workload import (PlanDynamic,
                                                         bucket_for_gamma)
                        b = bucket_for_gamma(force_gamma,
                                             control_cfg.gamma_buckets)
                        plan = WorkloadPlan(
                            plane.static,
                            PlanDynamic(
                                bucket_by_rank=np.full((tp,), b, np.int32),
                                mig_src=np.array(-1, np.int32),
                                pri_lists=controller.pri_lists()))
                        report = None
                    else:
                        # the controller consumes FULL-workload-equivalent
                        # times — from the χ-oracle, or (measured mode) the
                        # estimator's reconstruction of measured (mitigated)
                        # times of previous steps (Eq. 1 measures the
                        # heterogeneity degree, not the mitigated runtime)
                        times = plane.controller_times(chis)
                        plan, report = plane.decide(times)
                    # pick the executable for this plan's signature and
                    # assemble the dynamic plan arrays (projection is the
                    # identity: the trainer simulates at real-mesh scale)
                    step_fn, plan_arrays, _ = plane.dispatch(plan)
                    work_frac = plane.work_frac(plan)

                with span("repro.train.batch"):
                    b = make_batch()
                    batches_drawn += 1
                    b = {k: jnp.asarray(v) for k, v in b.items()}
                plane.timer.start()
                if plan_arrays is not None:
                    params, opt, metrics = step_fn(params, opt, b, plan_arrays)
                else:
                    params, opt, metrics = step_fn(params, opt, b)
                wall = plane.timer.stop(metrics)
                with span("repro.train.fetch"):
                    metrics = jax.device_get(metrics)

                # modeled bulk-synchronous step time (the paper's RT metric)
                modeled = it_model.step_time(chis, work_frac)

                # -- measurement: what a real cluster would observe THIS
                # step — per-rank times under the ACTIVE plan (mitigated),
                # gathered across ranks once per control interval; feeds
                # the estimator and the trace
                plane.capture(chis, work_frac, step=it, plan=plan, wall=wall)

                history["loss"].append(float(metrics["loss"]))
                history["modeled_step_s"].append(modeled)
                history["wall_s"].append(wall)
                if report is not None:
                    history["gammas"].append(
                        {int(k): float(v) for k, v in report.gammas.items()})
                    history["mig"].append(int(report.mig_src))
                    history["mig_shed"].append(
                        [list(map(int, report.mig_srcs)),
                         list(map(int, report.mig_shed))])
                    history["buckets"].append(
                        [int(x) for x in report.bucket_by_rank])
                    history["signatures"].append(
                        plan.static.signature_str())

                if weight_stats is not None and (it + 1) % 10 == 0:
                    with span("repro.control.weight_stats",
                              bytes=weight_stats.nbytes,
                              blocks=weight_stats.blocks):
                        fresh, snaps = weight_stats(
                            stat_weights(params, scopes),
                            controller.priority)
                    with span("repro.control.observe_weights",
                              scopes=len(fresh)):
                        controller.observe_variation(fresh, snaps)

                if eval_every and (it + 1) % eval_every == 0 \
                        and cfg.num_classes:
                    from repro.data.pipeline import eval_accuracy
                    def predict(bb):
                        return api.forward(params, cfg,
                                           jnp.asarray(patchify(bb["images"])))
                    with span("repro.train.eval"):
                        acc = eval_accuracy(predict, eval_stream,
                                            EVAL_BATCHES)
                    history["acc"].append(acc)
                    if not quiet:
                        print(f"  step {it+1}: eval acc {acc:.3f}")

                if not quiet and (it + 1) % log_every == 0:
                    print(f"step {it+1:4d} loss={metrics['loss']:.4f} "
                          f"wall={wall*1e3:.0f}ms modeled={modeled*1e3:.1f}ms")

                if ckpt_dir and (it + 1) % max(ckpt_every, 1) == 0 \
                        and (it + 1) < steps:
                    with span("repro.train.checkpoint"):
                        save_ckpt(it + 1)

        if ckpt_dir:
            with span("repro.train.checkpoint"):
                save_ckpt(steps)
        plane.close()
        history["final_loss"] = history["loss"][-1] if history["loss"] else None
        history["mean_modeled_step_s"] = float(
            np.mean(history["modeled_step_s"])) if history["modeled_step_s"] else 0
        # compile-cache telemetry: distinct plan signatures built vs reused
        history["plan_compiles"] = plane.cache.compile_count
        history["plan_cache_hits"] = plane.cache.hit_count
        history["times_mode"] = control_cfg.times if control_cfg.enabled else "modeled"
        if geo is not None:
            history["geometry"] = list(geo.sizes)
        if plane.estimator is not None:
            history["chi_hat"] = [float(c) for c in plane.estimator.chi_hat]
            history["estimator_rejected"] = plane.estimator.rejected_total
            history["rank_gathers"] = plane.timer.gather_count
        if plane.writer is not None:
            history["trace_out"] = trace_out
        return history


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="vit-1b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--control", default="off",
                    choices=["off", "zero", "mig", "semi"])
    ap.add_argument("--hetero", default="none",
                    choices=["none", "static", "round_robin", "contention",
                             "trace"])
    ap.add_argument("--chi", type=float, default=2.0)
    ap.add_argument("--times", default="modeled",
                    choices=["modeled", "measured"],
                    help="controller input: modeled χ-oracle, or measured "
                         "times through the online StragglerEstimator "
                         "(DESIGN_TELEMETRY.md)")
    ap.add_argument("--trace-in", default=None,
                    help="telemetry trace to replay (with --hetero trace)")
    ap.add_argument("--trace-out", default=None,
                    help="record a replayable telemetry trace here (JSONL)")
    ap.add_argument("--measure-noise", type=float, default=0.0,
                    help="multiplicative noise on simulated measurements")
    ap.add_argument("--mig-blocks", type=int, default=0,
                    help="per-source migration shed cap; 0 disables migration")
    ap.add_argument("--geometry", default=None,
                    help="static ragged TP shard geometry: 'chi' seeds "
                         "per-rank FFN block counts from the hetero "
                         "schedule's speed ratios; 'a,b,...' gives them "
                         "explicitly (DESIGN_SHARDING.md)")
    ap.add_argument("--max-sources", type=int, default=3,
                    help="max concurrent migration stragglers per TP group")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--imputation", default="zero",
                    choices=["zero", "average", "same"])
    ap.add_argument("--selection", default="priority",
                    choices=["random", "priority", "priority_diff"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="steps between mid-run full-state checkpoints")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--eval-every", type=int, default=0)
    ap.add_argument("--use-kernel", action="store_true",
                    help="route controlled matmuls through the Pallas "
                         "pruned-kernel family (fused FFN + kernel bwd)")
    ap.add_argument("--psum-chunks", type=int, default=1,
                    help="chunk-split the controlled epilogue all-reduce "
                         "into this many async-overlappable psums")
    ap.add_argument("--out", default=None, help="write history JSON here")
    args = ap.parse_args()
    enable_compile_cache()

    hist = run_training(
        args.arch, steps=args.steps, tp=args.tp, dp=args.dp,
        control_mode=args.control, hetero_kind=args.hetero, chi=args.chi,
        lr=args.lr, batch=args.batch, seq=args.seq, seed=args.seed,
        ckpt_dir=args.ckpt_dir, resume=args.resume,
        imputation=args.imputation, selection=args.selection,
        mig_blocks=args.mig_blocks, max_sources=args.max_sources,
        eval_every=args.eval_every, use_kernel=args.use_kernel,
        psum_chunks=args.psum_chunks,
        times=args.times, trace_in=args.trace_in, trace_out=args.trace_out,
        measure_noise=args.measure_noise, ckpt_every=args.ckpt_every,
        geometry=args.geometry)
    print(f"final loss: {hist['final_loss']:.4f}  "
          f"mean modeled step: {hist['mean_modeled_step_s']*1e3:.2f} ms")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(hist, f, indent=1)


if __name__ == "__main__":
    main()
