"""Tensor-parallel linear layers with flexible workload control.

Two execution paths per op:

* **plain** (ctx is None / neutral): einsum + logical-axis sharding
  constraints — GSPMD handles the TP partitioning (used for baseline
  dry-runs and when the controller reports no stragglers).
* **controlled**: a ``jax.shard_map`` block over the TP ("model") axis in
  which each rank applies its γ-bucket (ZERO-resizing ``lax.switch``) and,
  for FFN pairs, each straggler in the CONCURRENT source set sheds its
  slot's `m_s` intermediate blocks to the helpers (migration with
  reduce-merging; see core/migration.py for the multi-source partition).
  Plan semantics per rank, over its local keep-first priority list `pri`:

      [ keep (kc_b - m_s·is_straggler) | migrate m_s (slot source only) | pruned ]

  Branches are duplicated per source slot (keep kc_b − m_s) so migrated
  blocks are truly not computed locally (static shapes, real FLOP cut).
  The per-slot shed counts live in ``PlanStatic.mig_sheds`` (static —
  quantized + compile-cached upstream); the source rank ids arrive as the
  dynamic ``mig_src`` vector, so retargeting stragglers never recompiles.

A ragged static shard geometry (``PlanStatic.geometry``, core/geometry.py)
changes what "the local workload" means: rank r owns ``geometry[r]`` real
blocks of its padded local slice, branch tables are built per distinct
size class, and every keep count quantizes against the rank's own block
count — so statically-small ranks do statically less work before SEMI
splits the residual imbalance.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import resizing
from repro.core.migration import fused_migration_delta
from repro.core.workload import PlanStatic, keep_blocks_for_bucket
from repro.sharding import filter_spec_for_mesh, shard


@dataclasses.dataclass
class ControlContext:
    """Device-side plan handed to controlled layers.

    Arrays may carry a leading layer dimension (scan slices it off):
      bucket_by_rank: [e] or [L, e] int32
      mig_src:        [] or [S] int32 source ranks, aligned with
                      static.mig_sheds (−1 = slot idle / no migration)
      pri:            scope -> [nb] / [e, nb_loc] (+ optional leading L)
    """

    mesh: Mesh
    axis: str
    static: PlanStatic
    bucket_by_rank: jax.Array
    mig_src: jax.Array
    pri: Dict[str, jax.Array]
    use_kernel: bool = False
    per_layer: bool = False      # arrays carry a leading layer dim (PriDiff)
    psum_chunks: int = 1         # chunk-split epilogue all-reduces (>1)

    @property
    def tp(self) -> int:
        return self.static.tp_size

    def layer_slice(self, bucket, pri) -> "ControlContext":
        """Rebind per-layer arrays (used inside scan bodies / unrolled ends)."""
        return dataclasses.replace(self, bucket_by_rank=bucket, pri=pri,
                                   per_layer=False)


def _spec(mesh: Mesh, *parts) -> P:
    return filter_spec_for_mesh(P(*parts), mesh)


def chunked_psum(y: jax.Array, axis: str, n_chunks: int) -> jax.Array:
    """Epilogue all-reduce split into independent per-chunk ``psum``s.

    One fat ``lax.psum`` over the full ``[M, d_out]`` partial serializes
    compute → all-reduce on the decode hot path. Splitting the last dim
    into ``n_chunks`` independent psums gives XLA's latency-hiding
    scheduler (async collectives) chunks it can START while other work
    (the remaining branch compute, the next layer's prologue) is still
    in flight — the "bidirectional chunking" of the ISSUE 7 tentpole,
    expressed at the collective level where the scheduler can see it.
    ``n_chunks`` falls back to the largest divisor of ``d_out`` at or
    below the request (1 ⇒ the classic single psum, byte-identical).
    """
    if n_chunks <= 1:
        return lax.psum(y, axis)
    d = y.shape[-1]
    n = min(n_chunks, d)
    while n > 1 and d % n:
        n -= 1
    if n <= 1:
        return lax.psum(y, axis)
    parts = jnp.split(y, n, axis=-1)
    return jnp.concatenate([lax.psum(p, axis) for p in parts], axis=-1)


# ---------------------------------------------------------------------------
# Plain path
# ---------------------------------------------------------------------------


def dense(x: jax.Array, w: jax.Array, out_axes, *, mesh=None) -> jax.Array:
    """x [..., K] @ w [K, N] with a logical sharding constraint on y."""
    y = jnp.einsum("...k,kn->...n", x, w)
    return shard(y, out_axes, mesh=mesh)


# ---------------------------------------------------------------------------
# Controlled projection (resizing only) — attention/SSM projections
# ---------------------------------------------------------------------------


def controlled_proj(x: jax.Array, w: jax.Array, ctx: Optional[ControlContext],
                    scope: str, *, split: str, out_axes=None) -> jax.Array:
    """TP linear with per-rank ZERO-resizing on the contraction dim.

    split="col": w [K, N] partitioned on N over the TP axis; x replicated
      on TP. Resizing prunes K blocks (the paper's Fig. 2 forward case).
    split="row": w [K, N] partitioned on K; x partitioned on its last dim.
      Resizing prunes local K blocks; output psum'd over the TP axis.
    """
    if ctx is None or scope not in ctx.pri:
        if split == "row":
            y = jnp.einsum("...k,kn->...n", x, w)
            return shard(y, out_axes, mesh=ctx.mesh if ctx else None) \
                if out_axes else y
        return dense(x, w, out_axes, mesh=ctx.mesh if ctx else None) \
            if out_axes else jnp.einsum("...k,kn->...n", x, w)

    mesh, axis = ctx.mesh, ctx.axis
    st = ctx.static
    blk = st.block_for(scope)
    pri = ctx.pri[scope]
    lead = x.shape[:-1]

    if split == "col":
        in_specs = (_spec(mesh, *([None] * len(lead)), None),
                    _spec(mesh, None, axis),
                    _spec(mesh, axis),            # bucket_by_rank [e] -> [1]
                    _spec(mesh, None))            # pri [nb] replicated
        out_spec = _spec(mesh, *([None] * len(lead)), axis)

        def body(x_, w_, bucket_, pri_):
            return resizing.switched_matmul(
                x_, w_, pri_, bucket_[0], buckets=st.buckets,
                block=blk, use_kernel=ctx.use_kernel)

        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_spec, check_vma=False)(
            x, w, ctx.bucket_by_rank, pri)

    # row-split: x last dim and w first dim are sharded; per-rank pri [e, nb]
    in_specs = (_spec(mesh, *([None] * len(lead)), axis),
                _spec(mesh, axis, None),
                _spec(mesh, axis),
                _spec(mesh, axis, None))
    out_spec = _spec(mesh, *([None] * len(lead)), None)

    def body_row(x_, w_, bucket_, pri_):
        y = resizing.switched_matmul(
            x_, w_, pri_[0], bucket_[0], buckets=st.buckets,
            block=blk, use_kernel=ctx.use_kernel)
        return chunked_psum(y, axis, ctx.psum_chunks)

    return jax.shard_map(body_row, mesh=mesh, in_specs=in_specs,
                     out_specs=out_spec, check_vma=False)(
        x, w, ctx.bucket_by_rank, pri)


# ---------------------------------------------------------------------------
# Controlled FFN pair (resizing + migration with reduce-merging)
# ---------------------------------------------------------------------------


def _gather_cols_mat(w, ids, block):
    d, H = w.shape
    return jnp.take(w.reshape(d, H // block, block), ids, axis=1) \
        .reshape(d, ids.shape[0] * block)


def controlled_ffn(x: jax.Array, w_up: jax.Array, w_down: jax.Array,
                   ctx: Optional[ControlContext], scope: str,
                   act_fn: Callable, w_gate: Optional[jax.Array] = None,
                   out_axes=("batch", None, "embed")) -> jax.Array:
    """FFN pair y = act(x@w_up[,·gate]) @ w_down under workload control.

    w_up/w_gate: [d, H] column-split over TP; w_down: [H, d_out] row-split.
    The intermediate H blocks are the controlled workload unit: each rank
    resizes by its bucket; the straggler additionally migrates `m` blocks
    which helpers compute from broadcast slices and merge into the final
    psum (reduce-merging, Sec. IV-A).
    """
    if ctx is None or scope not in ctx.pri:
        h = jnp.einsum("...k,kh->...h", x, w_up)
        mesh = ctx.mesh if ctx else None
        h = shard(h, ("batch", None, "mlp"), mesh=mesh) if h.ndim == 3 else h
        if w_gate is not None:
            h = act_fn(jnp.einsum("...k,kh->...h", x, w_gate)) * h
        else:
            h = act_fn(h)
        y = jnp.einsum("...h,hd->...d", h, w_down)
        return shard(y, out_axes, mesh=mesh) if y.ndim == 3 else y

    mesh, axis = ctx.mesh, ctx.axis
    st = ctx.static
    blk = st.block_for(scope)
    e = st.tp_size
    sheds = st.mig_sheds                       # per-source shed counts (static)
    S = len(sheds)
    # ragged static shard geometry (core/geometry.py): per-rank real block
    # counts under the padded layout. An all-equal geometry is the plain
    # equal split — normalize it away here too so equal-geometry plans
    # trace the exact baseline jaxpr.
    geo = st.geometry if len(set(st.geometry)) > 1 else ()
    pri = ctx.pri[scope]                       # [e, nb_loc]
    lead = x.shape[:-1]
    nl = len(lead)

    in_specs = (_spec(mesh, *([None] * nl), None),       # x replicated on TP
                _spec(mesh, None, axis),                 # w_up col-split
                _spec(mesh, axis, None),                 # w_down row-split
                _spec(mesh, None, axis) if w_gate is not None else None,
                _spec(mesh, axis),                       # bucket [e]
                _spec(mesh, axis, None),                 # pri [e, nb]
                _spec(mesh),                             # mig_src scalar
                )
    if w_gate is None:
        in_specs = in_specs[:3] + in_specs[4:]
    out_spec = _spec(mesh, *([None] * nl), None)

    def body(x_, w_up_, w_down_, *rest):
        if w_gate is not None:
            w_gate_, bucket_, pri_, mig_src_ = rest
        else:
            bucket_, pri_, mig_src_ = rest
            w_gate_ = None
        x2 = x_.reshape(-1, x_.shape[-1])
        pri_ = pri_[0]
        bucket_self = bucket_[0]
        rank = lax.axis_index(axis)
        Hloc = w_up_.shape[1]
        nb = Hloc // blk
        if S > 0 and max(sheds) >= nb:
            raise ValueError(
                f"mig_shed {sheds} must leave each source at least one of "
                f"its {nb} local blocks")
        if geo:
            if len(geo) != e:
                raise ValueError(
                    f"geometry {geo} has {len(geo)} ranks, tp_size={e}")
            if max(geo) != nb:
                raise ValueError(
                    f"geometry {geo}: max size {max(geo)} must equal the "
                    f"padded local block count {nb} (Hloc={Hloc}, blk={blk})")
            if S > 0 and max(sheds) >= min(geo):
                raise ValueError(
                    f"mig_shed {sheds} must leave the smallest-geometry "
                    f"rank (L={min(geo)}) at least one real block")

        # source-slot vector: pad/trim the dynamic mig_src to S entries
        if S > 0:
            srcs = jnp.atleast_1d(mig_src_)[:S]
            if srcs.shape[0] < S:
                srcs = jnp.concatenate(
                    [srcs, jnp.full((S - srcs.shape[0],), -1, srcs.dtype)])
            ranks_v = jnp.arange(e)
            is_src_vec = jnp.any(ranks_v[:, None] == srcs[None, :], axis=1)
            is_straggler = is_src_vec[rank]
            my_slot = jnp.argmax(srcs == rank)
        else:
            is_straggler = jnp.zeros((), bool)
            my_slot = jnp.zeros((), jnp.int32)

        # ---- per-rank local compute: switch over (bucket × source slot) --
        def make_branch(kc: int):
            kc = max(1, min(kc, nb))

            def branch(ops_):
                x2_, wu, wg, wd, pri_b = ops_
                if kc >= nb:
                    # dense shortcut: keeping every block, the gather is an
                    # identity copy — skip it (helpers/buckets at γ=0 run
                    # the true dense pair)
                    h = x2_ @ wu
                    h = act_fn(x2_ @ wg) * h if wg is not None else act_fn(h)
                    return h @ wd
                keep = jnp.sort(pri_b[:kc])
                return resizing.resized_ffn(x2_, wu, wd, keep, act_fn, wg,
                                            block=blk,
                                            use_kernel=ctx.use_kernel)
            return branch

        if geo:
            # one branch table per distinct rank size L ("size class"):
            # keep counts are quantized against L, so a small rank at
            # γ=0 runs exactly its L real blocks — the padding is never
            # gathered and the static FLOP rebalance is real.
            classes = sorted(set(geo))
            branches, kc_rows = [], []
            for L in classes:
                kcs_L = [keep_blocks_for_bucket(g, L) for g in st.buckets]
                branches += [make_branch(kc) for kc in kcs_L]
                for m_s in sheds:
                    branches += [make_branch(kc - m_s) for kc in kcs_L]
                kc_rows.append(kcs_L)
            class_self = jnp.asarray(
                [classes.index(L) for L in geo], jnp.int32)[rank]
            branch_idx = bucket_self + len(st.buckets) * jnp.where(
                is_straggler, 1 + my_slot, 0).astype(jnp.int32) \
                + len(st.buckets) * (1 + S) * class_self
        else:
            kcs = [keep_blocks_for_bucket(g, nb) for g in st.buckets]
            branches = [make_branch(kc) for kc in kcs]
            for m_s in sheds:
                branches += [make_branch(kc - m_s) for kc in kcs]
            branch_idx = bucket_self + len(st.buckets) * jnp.where(
                is_straggler, 1 + my_slot, 0).astype(jnp.int32)
        partial = lax.switch(branch_idx, branches,
                             (x2, w_up_, w_gate_, w_down_, pri_))

        # ---- migration: slot source s exports the m_s blocks right after
        # its (clamped) locally-kept prefix; all slots share ONE fused
        # masked-psum broadcast and helpers fold their partials into the
        # layer's single psum (core/migration.py:fused_migration_delta).
        if S > 0:
            if geo:
                # [n_classes, n_buckets]: this rank's keep count depends on
                # its size class as well as its bucket
                kc_self = jnp.asarray(kc_rows, jnp.int32)[
                    class_self, bucket_self]
            else:
                kc_table = jnp.array(kcs, jnp.int32)
                kc_self = kc_table[bucket_self]
            exports = []
            for s, m_s in enumerate(sheds):
                # start from the CLAMPED keep count max(kc − m_s, 1): the
                # local branch never keeps fewer than 1 block, so the
                # migrated window must start after it to stay disjoint
                # (no double compute even when kc − m_s < 1)
                start = jnp.clip(jnp.maximum(kc_self - m_s, 1), 0, nb - m_s)
                mig_ids = lax.dynamic_slice_in_dim(pri_, start, m_s)
                exp_up = _gather_cols_mat(w_up_, mig_ids, blk)
                exp_down = resizing.gather_rows(w_down_, mig_ids, blk)
                exp_g = (_gather_cols_mat(w_gate_, mig_ids, blk)
                         if w_gate_ is not None else None)
                exports.append((exp_up, exp_down, exp_g))
            partial = partial + fused_migration_delta(
                x2, axis=axis, rank=rank, srcs=srcs, sheds=sheds, block=blk,
                act_fn=act_fn, exports=exports)

        # chunked epilogue: applied AFTER the branch switch/migration
        # merge so every lax.switch branch keeps its uniform shape
        y = chunked_psum(partial, axis, ctx.psum_chunks)
        return y.reshape(*lead, w_down_.shape[1])

    args = (x, w_up, w_down) + ((w_gate,) if w_gate is not None else ()) + (
        ctx.bucket_by_rank, pri, ctx.mig_src)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_spec, check_vma=False)(*args)
