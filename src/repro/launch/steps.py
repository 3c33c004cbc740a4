"""jit-able train / serve steps with explicit in/out shardings.

``build_train_step`` / ``build_serve_step`` return (fn, arg-SDS tuple,
in_shardings, out_shardings) ready for ``jax.jit(...).lower(...)`` (the
dry-run) or real execution (the trainer).

Workload control: when a WorkloadPlan is supplied, the step takes an extra
``plan`` dict of device arrays (bucket_by_rank, mig_src, pri lists) and
threads a ControlContext into the model — so the controller can retarget
stragglers every iteration without recompiling.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import sharding as sh
from repro.config import ModelConfig, RunConfig, ShapeConfig, TrainConfig
from repro.control import scopes as _scopes
from repro.core.workload import PlanStatic
from repro.layers.tp_linear import ControlContext
from repro.models import get_api
from repro.optim import adamw
from repro.launch import specs as specs_lib

SDS = jax.ShapeDtypeStruct

# Scope discovery / plan-array assembly moved to the unified control plane
# (repro.control.scopes) in PR 5; the module-level aliases that kept old
# imports alive are now deprecation shims — import from
# repro.control.scopes instead (enforced for new code by the ruff TID251
# banned-api rule in pyproject.toml).
_DEPRECATED_SCOPE_EXPORTS = (
    "SCOPE_LAYOUT", "control_block_size", "control_scopes", "per_rank_pri",
    "plan_pri_arrays", "plan_specs", "scope_block_table")


def __getattr__(name: str):
    if name in _DEPRECATED_SCOPE_EXPORTS:
        import warnings
        warnings.warn(
            f"repro.launch.steps.{name} is deprecated; import it from "
            "repro.control.scopes", DeprecationWarning, stacklevel=2)
        return getattr(_scopes, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def _replicated(mesh):
    return NamedSharding(mesh, P())


def make_ctx(mesh: Mesh, static: PlanStatic, plan: Dict[str, Any],
             use_kernel: bool = False,
             psum_chunks: int = 1) -> ControlContext:
    return ControlContext(
        mesh=mesh, axis="model", static=static,
        bucket_by_rank=plan["bucket_by_rank"], mig_src=plan["mig_src"],
        pri=plan.get("pri", {}), use_kernel=use_kernel,
        per_layer=static.per_layer, psum_chunks=psum_chunks)


def build_rank_time_gather(mesh: Mesh, axis: str = "model"):
    """Jitted all-gather of per-rank local clocks (telemetry measurement).

    Input: [e] float32 sharded over ``axis`` — entry r is rank r's locally
    measured segment time (on the single-host simulator the vector comes
    from the simulated measurement backend; on a real cluster each rank
    contributes its own slice). Output: the replicated [e] vector, so
    EVERY host sees ALL TP ranks' times. Run once per control interval by
    telemetry.RankTimer — not every iteration — per the paper's passive
    T_avg refresh discipline (Sec. III-A).
    """
    e = mesh.shape[axis]

    def local_gather(x):                      # x: [1] this rank's clock
        return jax.lax.all_gather(x, axis, tiled=True)

    gathered = jax.shard_map(local_gather, mesh=mesh, in_specs=P(axis),
                             out_specs=P(), check_vma=False)
    return jax.jit(gathered,
                   in_shardings=NamedSharding(mesh, P(axis)),
                   out_shardings=_replicated(mesh)) if e > 1 else \
        jax.jit(lambda x: x)


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------


def build_train_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                     train: TrainConfig = TrainConfig(),
                     control_static: Optional[PlanStatic] = None,
                     total_steps: int = 0, use_kernel: bool = False,
                     psum_chunks: int = 1):
    """Returns (train_step, arg_sds, in_shardings, out_shardings)."""
    cfg = specs_lib.effective_model_cfg(cfg, shape)
    api = get_api(cfg)
    dtype = jnp.dtype(train.param_dtype)
    rules = specs_lib.rules_for(shape, mesh, cfg, fsdp=train.fsdp_layers)

    p_sds, _, p_shards = specs_lib.param_specs(cfg, mesh, rules, dtype)
    opt_sds = adamw.AdamWState(
        step=SDS((), jnp.int32),
        mu=jax.tree.map(lambda s: SDS(s.shape, jnp.float32), p_sds),
        nu=jax.tree.map(lambda s: SDS(s.shape, jnp.float32), p_sds))
    opt_shards = adamw.AdamWState(
        step=_replicated(mesh),
        mu=jax.tree.map(lambda s: s, p_shards),
        nu=jax.tree.map(lambda s: s, p_shards))
    b_sds, b_shards = specs_lib.batch_specs(cfg, shape, mesh, dtype)

    scopes = _scopes.control_scopes(cfg, control_static) \
        if control_static else {}
    if control_static and scopes:
        import dataclasses as _dc
        control_static = _dc.replace(
            control_static,
            scope_blocks=_scopes.scope_block_table(cfg, control_static))
        pl_sds, pl_shards = _scopes.plan_specs(control_static, cfg, mesh,
                                               scopes)
    else:
        control_static = None
        pl_sds = pl_shards = None

    metric_shards = {"loss": _replicated(mesh),
                     "grad_norm": _replicated(mesh), "lr": _replicated(mesh)}

    def train_step(params, opt_state, batch, plan=None):
        with sh.use_rules(rules):
            ctx = (make_ctx(mesh, control_static, plan,
                            use_kernel=use_kernel,
                            psum_chunks=psum_chunks)
                   if control_static is not None else None)

            def lf(p, b):
                loss, metrics = api.loss_fn(p, cfg, b, ctx=ctx,
                                            remat=train.remat)
                return loss, metrics

            n_micro = max(train.microbatch, 1)
            if n_micro > 1:
                # gradient accumulation: scan over micro-batches (memory
                # peak divides by n_micro; grads/loss averaged)
                def split(v):
                    return v.reshape((n_micro, v.shape[0] // n_micro)
                                     + v.shape[1:])
                micro = jax.tree.map(split, batch)
                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)

                def acc_step(carry, mb):
                    g_acc, l_acc = carry
                    (l, _), g = jax.value_and_grad(lf, has_aux=True)(params, mb)
                    g_acc = jax.tree.map(
                        lambda a, b_: a + b_.astype(jnp.float32) / n_micro,
                        g_acc, g)
                    return (g_acc, l_acc + l / n_micro), None

                (grads, loss), _ = jax.lax.scan(
                    acc_step, (zeros, jnp.zeros((), jnp.float32)), micro)
                grads = jax.tree.map(lambda g, p: g.astype(p.dtype),
                                     grads, params)
            else:
                (loss, _), grads = jax.value_and_grad(
                    lf, has_aux=True)(params, batch)
            new_p, new_opt, om = adamw.apply(params, grads, opt_state, train,
                                             total_steps)
            out_metrics = {"loss": loss, "grad_norm": om["grad_norm"],
                           "lr": om["lr"]}
            return new_p, new_opt, out_metrics

    args = (p_sds, opt_sds, b_sds) + ((pl_sds,) if pl_sds else ())
    in_sh = (p_shards, opt_shards, b_shards) + ((pl_shards,) if pl_sds else ())
    out_sh = (p_shards, opt_shards, metric_shards)
    return train_step, args, in_sh, out_sh


# ---------------------------------------------------------------------------
# prefill / serve steps
# ---------------------------------------------------------------------------


def build_prefill_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                       dtype=jnp.bfloat16):
    """Forward over the full sequence producing last-token logits (the
    inference-prefill workload)."""
    cfg = specs_lib.effective_model_cfg(cfg, shape)
    api = get_api(cfg)
    rules = specs_lib.rules_for(shape, mesh, cfg)
    p_sds, _, p_shards = specs_lib.param_specs(cfg, mesh, rules, dtype)
    b_sds, b_shards = specs_lib.batch_specs(cfg, shape, mesh, dtype)
    b_sds.pop("labels", None)
    b_shards.pop("labels", None)

    logits_spec = sh.filter_spec_for_mesh(
        sh.logical_to_spec(("batch", "vocab"), rules), mesh)
    logits_sh = NamedSharding(mesh, sh.fit_spec_to_shape(
        logits_spec, (shape.global_batch, cfg.vocab_size or 1), mesh))

    if cfg.num_classes:
        def prefill(params, batch):
            with sh.use_rules(rules):
                return api.forward(params, cfg, batch["patches"])
        out_sh = _replicated(mesh)
    elif cfg.encdec is not None:
        def prefill(params, batch):
            with sh.use_rules(rules):
                logits = api.forward(params, cfg, batch["tokens"],
                                     batch["frame_embeds"])
                return logits[:, -1]
        out_sh = logits_sh
    else:
        def prefill(params, batch):
            with sh.use_rules(rules):
                logits, _, _ = api.forward(
                    params, cfg, batch["tokens"],
                    patch_embeds=batch.get("patch_embeds"))
                return logits[:, -1]
        out_sh = logits_sh

    return prefill, (p_sds, b_sds), (p_shards, b_shards), out_sh


def build_serve_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                     dtype=jnp.bfloat16,
                     control_static: Optional[PlanStatic] = None,
                     use_kernel: bool = False, fused_attention: bool = False,
                     psum_chunks: int = 1, paging=None):
    """One-token decode against a seq_len KV cache.

    With ``control_static`` the step takes an extra ``plan`` dict (same
    layout as the train step's) and threads a ControlContext into the
    model, so the controller can ZERO-resize the TP decode matmuls of a
    contended rank at serve time without recompiling (signature-keyed
    executables come from the engine's PlanCompileCache).

    ``fused_attention`` routes the decode-attention call through the
    fused Pallas kernel (cfg-level, so the DENSE ctx=None path gets it
    too); ``psum_chunks`` chunk-splits the controlled epilogue psums.
    ``paging`` (core.paging.PagedLayout) swaps the attention cache to
    the block-paged pool and adds a ``pages`` [B, pages_per_slot] arg
    right after ``cur_pos``.
    """
    cfg = specs_lib.effective_model_cfg(cfg, shape)
    if fused_attention:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, fused_decode_attn=True)
    api = get_api(cfg)
    rules = specs_lib.rules_for(shape, mesh, cfg)
    p_sds, _, p_shards = specs_lib.param_specs(cfg, mesh, rules, dtype)
    d_sds, d_shards = specs_lib.decode_specs(cfg, shape, mesh, dtype,
                                             paging=paging)

    logits_spec = sh.filter_spec_for_mesh(
        sh.logical_to_spec(("batch", "vocab"), rules), mesh)
    logits_sh = NamedSharding(mesh, sh.fit_spec_to_shape(
        logits_spec, (shape.global_batch, cfg.vocab_size or 1), mesh))

    scopes = (_scopes.control_scopes(cfg, control_static)
              if control_static and cfg.encdec is None else {})
    if control_static and scopes:
        import dataclasses as _dc
        control_static = _dc.replace(
            control_static,
            scope_blocks=_scopes.scope_block_table(cfg, control_static))
        pl_sds, pl_shards = _scopes.plan_specs(control_static, cfg, mesh,
                                               scopes)
    else:
        control_static = None
        pl_sds = pl_shards = None

    if cfg.encdec is not None:
        if paging is not None:
            raise ValueError("paged decode does not cover encoder-decoder "
                             "models (the serve engine rejects them)")
        def serve_step(params, cache, tokens, cur_pos, encoder_out):
            with sh.use_rules(rules):
                return api.decode_step(params, cfg, cache, tokens, cur_pos,
                                       encoder_out)
        args = (p_sds, d_sds["cache"], d_sds["tokens"], d_sds["cur_pos"],
                d_sds["encoder_out"])
        in_sh = (p_shards, d_shards["cache"], d_shards["tokens"],
                 d_shards["cur_pos"], d_shards["encoder_out"])
    elif control_static is not None and paging is not None:
        def serve_step(params, cache, tokens, cur_pos, pages, plan):
            with sh.use_rules(rules):
                ctx = make_ctx(mesh, control_static, plan,
                               use_kernel=use_kernel,
                               psum_chunks=psum_chunks)
                return api.decode_step(params, cfg, cache, tokens, cur_pos,
                                       ctx=ctx, pages=pages)
        args = (p_sds, d_sds["cache"], d_sds["tokens"], d_sds["cur_pos"],
                d_sds["pages"], pl_sds)
        in_sh = (p_shards, d_shards["cache"], d_shards["tokens"],
                 d_shards["cur_pos"], d_shards["pages"], pl_shards)
    elif control_static is not None:
        def serve_step(params, cache, tokens, cur_pos, plan):
            with sh.use_rules(rules):
                ctx = make_ctx(mesh, control_static, plan,
                               use_kernel=use_kernel,
                               psum_chunks=psum_chunks)
                return api.decode_step(params, cfg, cache, tokens, cur_pos,
                                       ctx=ctx)
        args = (p_sds, d_sds["cache"], d_sds["tokens"], d_sds["cur_pos"],
                pl_sds)
        in_sh = (p_shards, d_shards["cache"], d_shards["tokens"],
                 d_shards["cur_pos"], pl_shards)
    elif paging is not None:
        def serve_step(params, cache, tokens, cur_pos, pages):
            with sh.use_rules(rules):
                return api.decode_step(params, cfg, cache, tokens, cur_pos,
                                       pages=pages)
        args = (p_sds, d_sds["cache"], d_sds["tokens"], d_sds["cur_pos"],
                d_sds["pages"])
        in_sh = (p_shards, d_shards["cache"], d_shards["tokens"],
                 d_shards["cur_pos"], d_shards["pages"])
    else:
        def serve_step(params, cache, tokens, cur_pos):
            with sh.use_rules(rules):
                return api.decode_step(params, cfg, cache, tokens, cur_pos)
        args = (p_sds, d_sds["cache"], d_sds["tokens"], d_sds["cur_pos"])
        in_sh = (p_shards, d_shards["cache"], d_shards["tokens"],
                 d_shards["cur_pos"])

    out_sh = (logits_sh, d_shards["cache"])
    return serve_step, args, in_sh, out_sh


def build_step_for(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                   train: TrainConfig = TrainConfig(),
                   control_static: Optional[PlanStatic] = None,
                   use_kernel: bool = False, fused_attention: bool = False,
                   psum_chunks: int = 1):
    """Dispatch on the shape kind: train_4k -> train_step;
    prefill_32k -> prefill; decode shapes -> serve_step (controlled when
    ``control_static`` is given — decode is a control surface since the
    serve engine). Prefill has no control hook (full-sequence forward is
    not in the paper's per-iteration balancing loop)."""
    if shape.kind == "train":
        return build_train_step(cfg, shape, mesh, train, control_static,
                                use_kernel=use_kernel,
                                psum_chunks=psum_chunks)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, shape, mesh,
                                  jnp.dtype(train.param_dtype))
    return build_serve_step(cfg, shape, mesh, jnp.dtype(train.param_dtype),
                            control_static=control_static,
                            use_kernel=use_kernel,
                            fused_attention=fused_attention,
                            psum_chunks=psum_chunks)


# ---------------------------------------------------------------------------
# static-analysis registration (repro.analysis; see DESIGN_ANALYSIS.md)
# ---------------------------------------------------------------------------

from repro.analysis import registry as _analysis  # noqa: E402


def _an_smoke():
    import numpy as np
    from repro.config import get_config, smoke_variant
    return np, smoke_variant(get_config("yi-6b"))


def _an_mesh(e: int):
    import numpy as np
    return Mesh(np.array(jax.devices()[:e]).reshape(1, e),
                ("data", "model"))


def _an_control_static(e: int, spelling: str) -> PlanStatic:
    """Two spellings of the SAME canonical plan (mig_shed vs the legacy
    mig_blocks scalar) — R1 proves they trace identically, which is what
    makes PlanCompileCache's canonical-signature keying sound."""
    kw = dict(buckets=(0.0, 0.25, 0.5), block_size=8, tp_size=e)
    if spelling == "mig_shed":
        return PlanStatic(mig_shed=(2,), **kw)
    return PlanStatic(mig_blocks=2, **kw)


def _an_train_cases(env):
    np, cfg = _an_smoke()
    shape = ShapeConfig("an_train", 16, 4, "train")
    mesh1 = _an_mesh(1)
    fn, args, in_sh, out_sh = build_train_step(cfg, shape, mesh1,
                                               TrainConfig())
    cases = [_analysis.TraceCase(
        step="train_step", name="dense_tp1", fn=fn, args=args, mesh=mesh1,
        in_shardings=in_sh, out_shardings=out_sh,
        compile_hlo=env.compile_hlo, signature="dense_tp1")]
    e = min(4, env.max_devices)
    if e >= 2:
        mesh = _an_mesh(e)

        def build(spelling):
            st = _an_control_static(e, spelling)
            f, a, _, _ = build_train_step(cfg, shape, mesh, TrainConfig(),
                                          control_static=st)
            return st, f, a

        st_a, fn_a, args_a = build("mig_shed")
        _, fn_b, args_b = build("mig_blocks")
        cases.append(_analysis.TraceCase(
            step="train_step", name=f"controlled_tp{e}", fn=fn_a,
            args=args_a, mesh=mesh,
            signature=st_a.canonical().signature_str(),
            retrace=(("mig_blocks-spelling", fn_b, args_b),)))
    return cases


def _an_prefill_cases(env):
    np, cfg = _an_smoke()
    mesh1 = _an_mesh(1)
    fn, args, in_sh, out_sh = build_prefill_step(
        cfg, ShapeConfig("an_prefill", 32, 4, "prefill"), mesh1)
    return [_analysis.TraceCase(
        step="prefill_step", name="dense_tp1", fn=fn, args=args,
        mesh=mesh1, signature="prefill_tp1")]


def _an_decode_cases(env):
    np, cfg = _an_smoke()
    shape = ShapeConfig("an_decode", 16, 2, "decode")
    mesh1 = _an_mesh(1)
    fn, args, in_sh, out_sh = build_serve_step(cfg, shape, mesh1)
    cases = [_analysis.TraceCase(
        step="serve_decode_step", name="dense_tp1", fn=fn, args=args,
        mesh=mesh1, in_shardings=in_sh, compile_hlo=env.compile_hlo,
        signature="decode_dense_tp1")]
    e = min(4, env.max_devices)
    if e >= 2:
        mesh = _an_mesh(e)

        def build(spelling):
            st = _an_control_static(e, spelling)
            f, a, _, _ = build_serve_step(cfg, shape, mesh,
                                          control_static=st)
            return st, f, a

        st_a, fn_a, args_a = build("mig_shed")
        _, fn_b, args_b = build("mig_blocks")
        cases.append(_analysis.TraceCase(
            step="serve_decode_step", name=f"controlled_tp{e}", fn=fn_a,
            args=args_a, mesh=mesh,
            signature=st_a.canonical().signature_str(),
            retrace=(("mig_blocks-spelling", fn_b, args_b),)))
    return cases


_analysis.register("train_step", _an_train_cases)
_analysis.register("prefill_step", _an_prefill_cases)
_analysis.register("serve_decode_step", _an_decode_cases)
