"""Continuous-batching serve engine with slot-based KV cache and
straggler-aware decode control.

The seed served fixed batches in lockstep: requests could only enter and
leave together, and none of the paper's workload-control machinery ran at
inference time. This engine is the first path where the balancing
techniques run outside the training loop:

* **Request queue + admission control** — FIFO queue (bounded via
  ``max_queue``); requests are admitted whenever a KV slot is free and
  their arrival step has passed.
* **Slot-based KV cache** — ONE cache pytree padded to a fixed
  ``num_slots`` batch dim, each slot at its own ``cur_pos`` (the decode
  cache write is a per-row scatter, layers/blocks.py). Completed slots
  return to a free list and are zeroed by a jitted reset before reuse
  (semantics-preserving recycling: attention masks by position, recurrent
  SSM/conv state restarts from zeros). Because every array shape is fixed
  at construction, the jitted ``serve_step`` never re-traces on arrivals
  or completions — asserted by tests via the jit cache size.
* **Prefill-on-admit** — prompts are teacher-forced through the same
  jitted decode step (the ``build_serve_step``/``decode_specs`` path), so
  a newly admitted request prefills while other slots keep decoding.
* **Straggler-aware decode** — a χ-schedule (paper Sec. V-A) feeds the
  iteration-time model; measured-style per-rank decode times drive the
  :class:`SemiController` through the unified
  :class:`repro.control.ControlPlane` (the same plan-assembly /
  compile-cache / dispatch implementation the trainer uses —
  DESIGN_CONTROL.md). ``--control zero`` ZERO-resizes a contended rank's
  TP decode matmuls (fast, lossy); ``--control semi`` opens the paper's
  FULL mitigation space at serve time — Eq.(3) picks the straggler prefix
  that migrates (multi-source, reduce-merged, **lossless**: decode
  outputs are token-exact) and only the remainder resizes. Serving
  defaults to the ``lossless`` β-policy, so a SEMI plan that fits entirely
  in migration changes no tokens. Plans sized on a simulated group larger
  than the real mesh are *projected* (``repro.control.projection``):
  migration slots fold onto real ranks, resize buckets keep the
  critical-path branch. Executables are keyed by the full plan signature
  (shed counts included) in a :class:`PlanCompileCache`, so replanning
  swaps between compiled steps instead of recompiling.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --slots 4 \
        --requests 8 --prompt-len 8 --gen-len 24 [--control semi \
        --hetero contention --chi 4 --tp 4]
"""
from __future__ import annotations

# CLI nicety: when invoked as a script with --tp > 1, request that many
# host devices BEFORE jax initializes (shared jax-free helper).
if __name__ == "__main__":
    from repro.launch._bootstrap import (apply_xla_preset, argv_int,
                                         argv_str, ensure_host_devices)
    ensure_host_devices(argv_int("--tp"))
    apply_xla_preset(argv_str("--xla-preset", "none"))

import argparse
import collections
import dataclasses
import time
import warnings
from typing import Dict, List, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint import store as ckpt_store
from repro.config import ModelConfig, ShapeConfig, resolve_model
from repro.control import ControlConfig, ControlPlane
from repro.core import geometry as geom_lib
from repro.core import hetero as hetero_lib
from repro.core import paging as paging_lib
from repro.launch import steps as steps_lib
from repro.launch._bootstrap import enable_compile_cache
from repro.launch.mesh import make_small_mesh
from repro.models import get_api
from repro.sharding import ragged_local_width, use_mesh
from repro.telemetry import span


# ---------------------------------------------------------------------------
# Requests / completions
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    """One generation request.

    arrival_step: engine step at which the request becomes eligible for
    admission (0 = immediately); lets tests/benchmarks replay staggered
    arrival traces deterministically.
    """

    uid: int
    prompt: np.ndarray                 # [P] int32 prompt tokens
    max_new_tokens: int
    arrival_step: int = 0
    eos_id: Optional[int] = None


@dataclasses.dataclass
class Completion:
    uid: int
    prompt: np.ndarray
    tokens: np.ndarray                 # generated tokens (<= max_new_tokens)
    admitted_step: int
    finished_step: int
    slot: int
    token_latencies: List[float]       # modeled seconds per emitted token
    # first entry includes queue wait + prefill (time-to-first-token)


@dataclasses.dataclass(frozen=True)
class LoadSnapshot:
    """What the cluster router sees of one engine (repro.cluster).

    ``chi`` is the per-rank χ feed — the estimator's χ̂ once the measured
    loop is locked, else the schedule's current oracle (ones when
    homogeneous). ``step_time_s`` prices one engine step under the
    ACTIVE control plan (``ControlPlane.capacity``), so a straggling
    replica whose SEMI loop already migrated its imbalance reads as
    (nearly) full capacity — the two nested control loops share one
    telemetry vocabulary. ``backlog_steps`` counts the token-steps
    still owed: active slots' remaining prefill chunks + decode tokens,
    plus every queued request's full cost.
    """

    step: int
    clock: float
    queue_depth: int
    active: int
    free_slots: int
    free_pages: Optional[int]          # None = fixed (non-paged) cache
    num_slots: int
    chi: np.ndarray
    work_frac: np.ndarray
    step_time_s: float
    dense_step_time_s: float
    backlog_steps: int


@dataclasses.dataclass
class _Slot:
    req: Request
    admitted_step: int
    pos: int = 0                       # NEXT cache position to feed
    next_token: int = 0                # token to feed this step (decode)
    generated: Optional[list] = None
    t_mark: float = 0.0                # engine clock at last token emission
    t_elig: float = 0.0                # clock at TTFT eligibility (fixed;
    #                                    restored on page-pool preemption)
    latencies: Optional[list] = None


# ---------------------------------------------------------------------------
# Control configuration
# ---------------------------------------------------------------------------


class ServeControlConfig(ControlConfig):
    """Deprecated alias of :class:`repro.control.ControlConfig`.

    The serve engine's knobs were collapsed into the shared
    :class:`ControlConfig` (field names are unchanged); this subclass
    exists only so existing callers keep working, and warns on
    construction. Import ``ControlConfig`` from ``repro.control``.
    """

    def __post_init__(self):
        warnings.warn(
            "ServeControlConfig is deprecated; use "
            "repro.control.ControlConfig (same field names)",
            DeprecationWarning, stacklevel=3)
        super().__post_init__()


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------


class ServeEngine:
    """Continuous-batching decode engine over a fixed slot set."""

    def __init__(self, arch: Union[str, ModelConfig], num_slots: int = 4,
                 max_len: int = 64, *,
                 tp: int = 1, ckpt_dir: Optional[str] = None, seed: int = 0,
                 control: Optional[ControlConfig] = None,
                 param_dtype: str = "float32",
                 max_queue: Optional[int] = None,
                 page_size: int = 0, prefill_chunk: int = 1,
                 kv_int8: bool = False,
                 num_pages: Optional[int] = None,
                 trace_tag: Optional[Dict] = None):
        """``page_size`` > 0 switches the KV cache to the block-paged
        pool layout (core/paging.py): attention cache leaves live in a
        shared ``[num_pages, page_size, ...]`` pool (``num_pages``
        defaults to full fixed-cache capacity; pass less to hold more
        resident slots than the pool could serve at max_len — the
        engine preempts on exhaustion). ``prefill_chunk`` teacher-forces
        up to that many prompt tokens per engine step inside ONE jitted
        step (decode slots still advance one token), so a long prompt
        no longer serializes the batch. ``kv_int8`` stores the GQA K/V
        pool in int8 with per-row f32 scales (half the pool HBM; not
        bit-exact, oracle attention path only).

        ``arch`` is a registered name (served at its smoke variant) or a
        :class:`ModelConfig`, served as given."""
        self.cfg = resolve_model(arch)
        arch = self.cfg.name if isinstance(arch, ModelConfig) else arch
        cfg_canonical = self.cfg
        self.api = get_api(self.cfg)
        if not self.api.has_decode or self.cfg.encdec is not None:
            raise ValueError(f"{arch}: the serve engine drives decoder-only "
                             "models (LM/SSM/hybrid/MoE)")
        self.num_slots = num_slots
        self.max_len = max_len
        self.tp = tp
        self.mesh = make_small_mesh(1, tp)
        self.shape = ShapeConfig("serve", max_len, num_slots, "decode")
        self.control = control or ControlConfig()
        self.max_queue = max_queue
        dtype = jnp.dtype(param_dtype)

        # ---- paged KV layout + chunked prefill --------------------------
        if kv_int8 and not page_size:
            raise ValueError("kv_int8 requires the paged cache "
                             "(--page-size > 0)")
        self.paging = (paging_lib.paged_layout(
            max_len, page_size, num_slots, num_pages=num_pages,
            kv_int8=kv_int8) if page_size else None)
        if self.paging is not None and self.control.fused_attention:
            if kv_int8:
                raise ValueError("kv_int8 has no fused-kernel path; drop "
                                 "--fused-attn (oracle dequant attention)")
            if page_size % 8:
                raise ValueError(f"--page-size {page_size} must be a "
                                 "multiple of 8 for the fused paged "
                                 "kernel (f32 sublane tiling)")
        self.alloc = (paging_lib.PageAllocator(self.paging, num_slots)
                      if self.paging is not None else None)
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.preemptions = 0

        # ---- workload control wiring (the unified control plane) --------
        c = self.control
        # static ragged shard geometry (core/geometry.py): the model
        # config carries the padded d_ff; params are initialized
        # canonically and expanded into the padded ragged layout below
        self.geometry = None
        if c.geometry is not None:
            geo = geom_lib.geometry_for_cfg(cfg_canonical, c.geometry,
                                            c.block_size)
            if not geo.is_equal:
                reason = geom_lib.geometry_unsupported_reason(cfg_canonical)
                if reason:
                    raise ValueError(
                        f"geometry unsupported for {arch}: {reason}")
                self.geometry = geo
                self.cfg = geom_lib.apply_geometry_cfg(cfg_canonical, geo)
                ragged_local_width(geo.padded_width, self.mesh)
        wc = c.to_workload()
        self._wc = wc

        # slot clearing runs INSIDE the jitted step (clear is a regular
        # [num_slots] input, zeros on non-admission steps): recycled
        # SSM/conv state restarts from zeros, and the cache array fed to
        # every step is always a previous step's output — a separate reset
        # executable produces different buffer layouts and costs a
        # spurious one-time retrace (observed on the mamba conv cache).
        cache_ax = (self.api.cache_axes(self.cfg, paging=self.paging)
                    if self.paging is not None
                    else self.api.cache_axes(self.cfg))

        def _clear_slots(cache, clear):
            def one(leaf, ax):
                ax_full = (None,) * (leaf.ndim - len(ax)) + tuple(ax)
                if "batch" not in ax_full:
                    # paged pool leaf: recycling is the allocator's job
                    # (reads mask by position; no zeroing needed)
                    return leaf
                b = ax_full.index("batch")
                shp = [1] * leaf.ndim
                shp[b] = num_slots
                return leaf * (1.0 - clear).reshape(shp).astype(leaf.dtype)
            return jax.tree.map(one, cache, cache_ax)

        # chunked-prefill lane merge: a substep's INVALID lanes (idle
        # slots, decode slots past substep 0, prefill lanes past the
        # prompt chunk) must not advance that slot's state. Attention
        # scatters already drop invalid positions; recurrent SSM/conv
        # leaves update unconditionally, so batch-axis leaves are
        # where-merged back to their pre-substep values.
        def _merge_invalid(old, new, valid):
            def one(o, n, ax):
                ax_full = (None,) * (n.ndim - len(ax)) + tuple(ax)
                if "batch" not in ax_full:
                    return n
                b = ax_full.index("batch")
                shp = [1] * n.ndim
                shp[b] = num_slots
                return jnp.where((valid > 0.0).reshape(shp), n, o)
            return jax.tree.map(one, old, new, cache_ax)

        # plan-signature compile cache over serve-step executables: the
        # controller's static shed counts select the executable; dynamic
        # bucket/src arrays change freely without recompiling.
        from jax.sharding import NamedSharding, PartitionSpec
        replicated = NamedSharding(self.mesh, PartitionSpec())

        invalid_pos = jnp.int32(paging_lib.INVALID_POS)

        def _build(static):
            fn, _, in_sh, out_sh = steps_lib.build_serve_step(
                self.cfg, self.shape, self.mesh, dtype,
                control_static=static, use_kernel=wc.use_kernel,
                fused_attention=wc.fused_attention,
                psum_chunks=wc.psum_chunks, paging=self.paging)

            def stepper(params, cache, tokens, pos, valid, clear, *rest):
                # tokens/pos/valid are [C, num_slots] — C chunked-prefill
                # substeps scanned INSIDE the one jitted step (C=1 is the
                # plain decode step). rest = (pages?, plan?). The
                # full-cache sweep only runs on admission steps; the
                # common decode step skips it (clear is all-zeros).
                cache = jax.lax.cond(jnp.any(clear > 0.0),
                                     lambda c: _clear_slots(c, clear),
                                     lambda c: c, cache)

                def substep(c, xs):
                    tok, p, v = xs
                    p_eff = jnp.where(v > 0.0, p, invalid_pos)
                    logits, nc = fn(params, c, tok, p_eff, *rest)
                    nc = _merge_invalid(c, nc, v)
                    # greedy argmax in-graph: only [C, num_slots] token
                    # ids cross the host boundary, not the full logits
                    return nc, jnp.argmax(logits, -1).astype(jnp.int32)

                cache, toks = jax.lax.scan(substep, cache,
                                           (tokens, pos, valid))
                return toks, cache

            jitted = jax.jit(stepper,
                             in_shardings=(in_sh[0], in_sh[1], replicated,
                                           replicated, replicated,
                                           replicated) + in_sh[4:],
                             out_shardings=(replicated, out_sh[1]),
                             donate_argnums=(1,))
            n_plan_slots = (max(1, static.num_sources)
                            if static is not None else 0)
            return jitted, n_plan_slots, in_sh

        # ---- unified control plane (compile cache + controller +
        # telemetry + sim->real dispatch; shared with launch/train.py) ----
        self.sim_ranks = c.sim_ranks or tp
        # the latency model prices the CANONICAL workload — padded lanes
        # under a ragged geometry are inert zeros, not extra FLOPs
        self.it_model = hetero_lib.iteration_model(
            cfg_canonical, ShapeConfig("serve_model", 1, num_slots, "decode"),
            max(self.sim_ranks, 1), peak_flops=c.peak_flops, mfu=c.mfu)
        # decode-overhead pricing (attention cache reads + collective
        # exposure) — opt-in so the classic legs' modeled trajectories
        # stay bit-identical (tests pin them)
        self.overhead = (hetero_lib.decode_overhead_model(
            cfg_canonical, num_slots, max_len, self.it_model,
            peak_flops=c.peak_flops,
            tile=(self.paging.page_size if self.paging is not None
                  else 128))
            if c.model_decode_overheads else None)
        self.plane = ControlPlane(
            self.cfg, wc, mesh=self.mesh, tp=tp, builder=_build,
            it_model=self.it_model, sim_ranks=self.sim_ranks,
            geometry=(self.geometry.sizes
                      if self.geometry is not None else None),
            # the controller reasons in per-rank shard blocks (the paper's
            # L_i) so migration sheds are sized to FIT a source's local
            # shard; projected sheds are additionally clamped to the real
            # mesh's shard when sim_ranks != tp
            controller_blocks="local", clamp_sheds=True,
            hetero_kind=c.hetero_kind, chi=c.chi, period=c.period,
            contention_p=c.contention_p, seed=c.seed,
            trace_in=c.trace_in, trace_rank_offset=c.trace_rank_offset,
            trace_out=c.trace_out,
            # trace_tag: per-replica tagging (repro.cluster) so traces
            # from one cluster run identify their lane in the shared set
            trace_meta={"arch": arch, "engine": "serve", "mode": c.mode,
                        "hetero": c.hetero_kind, "seed": c.seed,
                        **(trace_tag or {})},
            measure_noise=c.measure_noise)
        self._base_step, self._base_plan_slots, in_sh = self.plane.base
        self.schedule = self.plane.schedule
        self.controller = self.plane.controller

        # ---- params + slot cache ----------------------------------------
        # params (and checkpoints) are CANONICAL; a ragged geometry
        # expands them into the zero-padded layout at load time. Fresh
        # params are made on the device under jit, straight into their
        # shardings, so a full-width model never has a second copy.
        def init_params():
            return self.api.init(jax.random.PRNGKey(seed), cfg_canonical,
                                 dtype)[0]

        params = None
        if ckpt_dir:
            # race-tolerant latest-committed load: a warm spare may be
            # promoted while a trainer is mid-save in the same directory
            _, params = ckpt_store.load_latest_params(
                ckpt_dir, jax.eval_shape(init_params))
        if self.geometry is not None:
            params = geom_lib.expand_ffn_params(
                jax.jit(init_params)() if params is None else params,
                self.geometry)
        self.params = (jax.jit(init_params, out_shardings=in_sh[0])()
                       if params is None
                       else jax.device_put(params, in_sh[0]))
        self.cache = jax.device_put(
            self.api.init_cache(self.cfg, num_slots, max_len, dtype,
                                paging=self.paging)
            if self.paging is not None
            else self.api.init_cache(self.cfg, num_slots, max_len, dtype),
            in_sh[1])

        # ---- host-side state ---------------------------------------------
        self.queue: collections.deque = collections.deque()
        self._eligible_clock: Dict[int, float] = {}   # req.uid -> TTFT start
        self._submitted: Dict[int, float] = {}   # req.uid -> host submit time
        self.slots: List[Optional[_Slot]] = [None] * num_slots
        self.free: List[int] = list(range(num_slots))[::-1]
        self.step_count = 0
        self.clock = 0.0                     # modeled seconds
        self.completions: List[Completion] = []
        self.history: List[Dict] = []

    # -- admission ---------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """FIFO admission control; False = queue full, request rejected.

        Raises on requests that can never fit: prefill past ``max_len``
        would silently drop cache writes (jax scatters clip out-of-bounds
        indices) and break token-exactness without an error.
        """
        need = len(req.prompt) + req.max_new_tokens
        if len(req.prompt) == 0 or need > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt {len(req.prompt)} + "
                f"max_new_tokens {req.max_new_tokens} exceeds the engine's "
                f"max_len {self.max_len}")
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            return False
        self.queue.append(req)
        self._submitted[req.uid] = time.perf_counter()
        # time-to-first-token starts when the request becomes ELIGIBLE
        # (arrival), not when a slot frees up — queue wait is part of
        # TTFT. Keyed by req.uid: keying by id(req) handed a NEW request
        # a stale clock whenever CPython recycled a completed request's
        # address (ISSUE 8 bugfix).
        if req.arrival_step <= self.step_count:
            self._eligible_clock.setdefault(req.uid, self.clock)
        return True

    def try_submit(self, req: Request) -> bool:
        """Non-blocking admission: ``False`` means NOTHING was enqueued.

        The cluster router needs a clean can't-take-it signal instead of
        an exception — or, worse, a request silently parked behind a
        bound it can never clear. ``False`` when:

        * the bounded queue is already at ``max_queue``;
        * the request can never be served by this engine: prompt +
          ``max_new_tokens`` past ``max_len``, or a paged pool too small
          to EVER hold the request even running alone (without this
          check the admit loop deadlocks on the queue head and the whole
          run times out, or the pool raises mid-decode).

        :meth:`submit` keeps its raising contract for the standalone
        driver, where a never-fits request is a caller bug.
        """
        need = len(req.prompt) + req.max_new_tokens
        if len(req.prompt) == 0 or need > self.max_len:
            return False
        if self.paging is not None \
                and self.paging.pages_for(need) > self.paging.num_pages:
            return False
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            return False
        return self.submit(req)

    def _admit(self):
        """Returns (admitted uids, slot-clear mask for this step's reset).

        Recycled slots are zeroed inside the step so SSM/conv state
        restarts cleanly; attention correctness never depends on stale
        K/V (positions > cur_pos are masked, <= cur_pos are rewritten by
        prefill), but zeroing keeps recycling uniformly exact."""
        clear = np.zeros((self.num_slots,), np.float32)
        admitted = []
        now = time.perf_counter()
        # mark queue members that just became eligible (TTFT clock start)
        for req in self.queue:
            if req.arrival_step <= self.step_count:
                self._eligible_clock.setdefault(req.uid, self.clock)
        while self.free and self.queue \
                and self.queue[0].arrival_step <= self.step_count:
            if self.alloc is not None \
                    and not self.alloc.can_fit(len(self.queue[0].prompt)):
                break          # pool can't hold the prompt; wait for frees
            req = self.queue.popleft()
            slot = self.free.pop()
            t0 = self._eligible_clock.pop(req.uid, self.clock)
            self.slots[slot] = _Slot(
                req=req, admitted_step=self.step_count, pos=0,
                next_token=int(req.prompt[0]), generated=[],
                t_mark=t0, t_elig=t0, latencies=[])
            clear[slot] = 1.0
            admitted.append(req.uid)
            # the queue wait on the host clock, for the profiler
            wait_ms = 1e3 * (now - self._submitted.get(req.uid, now))
            with span("repro.serve.request_admitted", uid=int(req.uid),
                      queue_wait_ms=wait_ms):
                pass
        return admitted, clear

    # -- page-pool bookkeeping (paged engine only) ---------------------------
    def _planned_feed(self, s: "_Slot") -> int:
        """Positions this slot writes THIS step: a prefill chunk or one
        decode token."""
        P = len(s.req.prompt)
        return min(self.prefill_chunk, P - s.pos) if s.pos < P else 1

    def _preempt(self, slot: int) -> int:
        """Evict a slot back to the FRONT of the queue, returning its
        pages. Deterministic greedy decode regenerates the identical
        tokens on re-admission, so preemption preserves token-exactness;
        the TTFT clock is restored to the original eligibility time so
        queue-wait (including the preemption) stays in TTFT."""
        s = self.slots[slot]
        self.alloc.free_slot(slot)
        self.slots[slot] = None
        self.free.append(slot)
        self.queue.appendleft(s.req)
        self._eligible_clock[s.req.uid] = s.t_elig
        self.preemptions += 1
        return s.req.uid

    def _ensure_pages(self) -> list:
        """Grow each active slot's page list to cover this step's writes,
        preempting the most recently admitted other slot on exhaustion
        (oldest requests keep their pages — FIFO service order). Returns
        the uids preempted this step."""
        preempted = []
        order = sorted(
            (i for i, s in enumerate(self.slots) if s is not None),
            key=lambda i: (self.slots[i].admitted_step, i))
        for i in order:
            s = self.slots[i]
            if s is None:                      # preempted earlier this pass
                continue
            while not self.alloc.ensure(i, s.pos + self._planned_feed(s) - 1):
                victims = [j for j, v in enumerate(self.slots)
                           if v is not None and j != i]
                if not victims:
                    raise RuntimeError(
                        f"page pool exhausted: slot {i} (uid "
                        f"{s.req.uid}) needs a page and no other slot "
                        "can be preempted — the pool is too small for a "
                        "single request")
                victim = max(victims,
                             key=lambda j: (self.slots[j].admitted_step, j))
                preempted.append(self._preempt(victim))
        return preempted

    def kv_cache_bytes(self) -> int:
        """Total bytes of the engine's cache pytree (KV pools/rows plus
        recurrent state) — the equal-HBM axis of serve_bench's
        mixed_lengths capacity gate."""
        return int(sum(l.size * l.dtype.itemsize
                       for l in jax.tree.leaves(self.cache)))

    # -- one decode step -----------------------------------------------------
    def step(self) -> Dict:
        """Admit, run one jitted step over all slots, harvest.

        Each step feeds every active slot either a CHUNK of its prompt
        (up to ``prefill_chunk`` teacher-forced positions, scanned inside
        the one jitted executable) or one greedy decode token — chunked
        prefill and decode interleave freely across slots with no
        retrace. On the paged engine, page lists are grown to cover this
        step's writes first, preempting the newest-admitted slot when the
        pool runs dry.

        The step and its phases are profiler spans (``repro.serve.*``,
        DESIGN_TELEMETRY.md §7); the counters of ``repro.serve.step``
        are set once the step has run."""
        with span("repro.serve.step", step=int(self.step_count)) as sp:
            return self._run_step(sp)

    def _run_step(self, sp) -> Dict:
        with span("repro.serve.admit") as sp_admit:
            admitted, clear = self._admit()
            preempted = (self._ensure_pages() if self.alloc is not None
                         else [])
            sp_admit.count(admitted=len(admitted), preempted=len(preempted))

        C = self.prefill_chunk
        B = self.num_slots
        with span("repro.serve.feed") as sp_feed:
            tokens_cb = np.zeros((C, B), np.int32)
            pos_cb = np.full((C, B), paging_lib.INVALID_POS, np.int32)
            valid_cb = np.zeros((C, B), np.float32)
            feed = np.zeros((B,), np.int32)       # positions fed per slot
            last_pos = np.zeros((B,), np.int32)   # highest position fed
            active = np.zeros((B,), np.float32)
            for i, s in enumerate(self.slots):
                if s is None:
                    continue
                active[i] = 1.0
                P = len(s.req.prompt)
                if s.pos < P:                 # teacher-forced prefill chunk
                    n = min(C, P - s.pos)
                    tokens_cb[:n, i] = np.asarray(
                        s.req.prompt[s.pos:s.pos + n], np.int32)
                    pos_cb[:n, i] = np.arange(s.pos, s.pos + n)
                else:                         # one greedy decode token
                    n = 1
                    tokens_cb[0, i] = s.next_token
                    pos_cb[0, i] = s.pos
                valid_cb[:n, i] = 1.0
                feed[i] = n
                last_pos[i] = s.pos + n - 1
            lanes = int(feed.sum())
            sp_feed.count(lanes=lanes)
            with use_mesh(self.mesh):
                fed = (jnp.asarray(tokens_cb), jnp.asarray(pos_cb),
                       jnp.asarray(valid_cb), jnp.asarray(clear))
                if self.alloc is not None:
                    fed = fed + (jnp.asarray(self.alloc.table()),)

        # chunked prefill feeds MORE than one token per occupied slot;
        # price the extra substep work as extra workload fraction so the
        # modeled clock stays honest (C=1 → scale 1.0, bit-identical to
        # the single-token trajectories the classic legs pin)
        chunk_scale = 1.0 + max(0.0, float(valid_cb.sum())
                                - float(active.sum())) / self.num_slots

        # -- straggler model + plan selection -----------------------------
        step_idx = self.step_count
        chis = self.plane.chis(step_idx)
        dense_latency = self.it_model.step_time(chis, np.ones(self.sim_ranks))
        plan_report = None
        plan = None
        proj = None
        frac = np.ones(self.sim_ranks)
        if self.controller is not None:
            # full-workload-equivalent times (χ-oracle, or the estimator's
            # closed-loop reconstruction in measured mode): Eq.(1)
            # measures the heterogeneity degree, not the mitigated runtime
            times = self.plane.controller_times(chis)
            plan, plan_report = self.plane.decide(times)
            # full SEMI dispatch: the projected plan carries resize
            # buckets AND multi-source migration slots; the executable is
            # keyed on the projected signature in the compile cache
            step_fn, plan_arrays, proj = self.plane.dispatch(plan)
            frac = self.plane.work_frac(plan)
            latency = self.it_model.step_time(chis, frac * chunk_scale)
        else:
            step_fn, plan_arrays = self._base_step, None
            latency = (dense_latency if chunk_scale == 1.0
                       else self.it_model.step_time(
                           chis, np.ones(self.sim_ranks) * chunk_scale))

        self.plane.timer.start()
        with use_mesh(self.mesh):
            args = (self.params, self.cache) + fed
            if plan_arrays is not None:
                args = args + (plan_arrays,)
            tok_ids, self.cache = step_fn(*args)
        wall = self.plane.timer.stop(tok_ids)
        with span("repro.serve.fetch"):
            nxt = np.asarray(jax.device_get(tok_ids))  # [C, num_slots]
        overhead = 0.0
        if self.schedule is None:
            latency = dense_latency = wall       # no simulation: real time
        elif self.overhead is not None:
            # occupancy-priced attention reads + (reduced) collective
            # exposure, from THIS step's actual per-slot positions —
            # masked by `active` so empty slots bill zero tiles
            overhead = self.overhead.overhead_s(
                last_pos, fused=self._wc.fused_attention,
                psum_chunks=self._wc.psum_chunks, active=active)
            latency += overhead

        # -- telemetry: what each simulated rank measured THIS step -------
        self.plane.capture(chis, frac, step=step_idx, plan=plan, wall=wall)

        self.clock += latency
        self.step_count += 1

        # -- harvest per slot ---------------------------------------------
        completed = []
        with span("repro.serve.harvest") as sp_harvest:
            for i, s in enumerate(self.slots):
                if s is None or feed[i] == 0:
                    continue
                n = int(feed[i])
                prev = s.pos
                s.pos = prev + n
                P = len(s.req.prompt)
                if prev < P and s.pos < P:
                    continue                         # still mid-prefill
                # the last fed position's logits carry the next token (chunk
                # end == prompt end for the prefill→decode handoff)
                tok = int(nxt[n - 1, i])
                emitted = False
                if len(s.generated) < s.req.max_new_tokens:
                    s.generated.append(tok)
                    s.latencies.append(self.clock - s.t_mark)
                    s.t_mark = self.clock
                    emitted = True
                done = (len(s.generated) >= s.req.max_new_tokens
                        or (emitted and s.req.eos_id is not None
                            and tok == s.req.eos_id))
                if done or s.pos >= self.max_len:
                    self.completions.append(Completion(
                        uid=s.req.uid, prompt=s.req.prompt,
                        tokens=np.asarray(s.generated, np.int32),
                        admitted_step=s.admitted_step,
                        finished_step=self.step_count, slot=i,
                        token_latencies=list(s.latencies)))
                    completed.append(s.req.uid)
                    self._eligible_clock.pop(s.req.uid, None)
                    self._submitted.pop(s.req.uid, None)
                    with span("repro.serve.request_done",
                              uid=int(s.req.uid), tokens=len(s.generated)):
                        pass
                    self.slots[i] = None
                    self.free.append(i)
                    if self.alloc is not None:
                        self.alloc.free_slot(i)
                else:
                    s.next_token = tok
            sp_harvest.count(completed=len(completed))

        report = {"step": self.step_count, "latency_s": latency,
                  "dense_latency_s": dense_latency, "wall_s": wall,
                  "active": sum(s is not None for s in self.slots),
                  "admitted": admitted, "completed": completed,
                  "queued": len(self.queue)}
        if preempted:
            report["preempted"] = preempted
        if self.overhead is not None:
            report["overhead_s"] = overhead
            # slot-cache occupancy + the minimum (fused, occupied-tiles)
            # attention read time: the roofline terms serve_bench gates
            # on. Both are masked by the ACTIVE slots — an empty slot's
            # pos of 0 is vacancy, not a resident length-1 sequence.
            report["occupancy"] = float(
                ((last_pos + 1.0) * active).sum()
                / (self.num_slots * self.max_len))
            report["attn_bound_s"] = self.overhead.attn_s(
                last_pos, fused=True, active=active)
        sp.count(active=int(active.sum()), lanes=lanes,
                 admitted=len(admitted), completed=len(completed),
                 preempted=len(preempted), queued=len(self.queue))
        if self.alloc is not None:
            sp.count(pages_free=self.alloc.free_pages)
        if plan_report is not None:
            report["stragglers"] = list(plan_report.stragglers)
            report["max_bucket"] = int(plan_report.bucket_by_rank.max())
            # mig_srcs/mig_shed record what EXECUTED on the real mesh
            # (post-projection); the controller's sim-scale intent lands
            # under planned_* — at tp=1 the two legitimately differ
            if proj is not None and proj.mig_srcs:
                report["mig_srcs"] = [int(s) for s in proj.mig_srcs]
                report["mig_shed"] = [int(m) for m in proj.mig_sheds]
            if plan_report.mig_srcs:
                report["planned_mig_srcs"] = [int(s)
                                              for s in plan_report.mig_srcs]
                report["planned_mig_shed"] = [int(m)
                                              for m in plan_report.mig_shed]
        self.history.append(report)
        return report

    # -- cluster-driver API (repro.cluster) ----------------------------------
    @property
    def idle(self) -> bool:
        """No active slots and nothing queued (e.g. a drained replica)."""
        return not self.queue and all(s is None for s in self.slots)

    def tick(self) -> Dict:
        """One cluster-driver step: a full jitted step when any slot is
        occupied or a queued request is admissible, otherwise an IDLE
        tick — the step counter still advances (χ-schedule lanes stay
        aligned with the cluster step across replicas) but the modeled
        clock does not (an idle engine isn't burning time any request
        can observe) and no device work runs. Lets one host loop
        interleave R engines deterministically without paying a jitted
        step per idle replica."""
        admissible = bool(
            self.free and self.queue
            and self.queue[0].arrival_step <= self.step_count
            and (self.alloc is None
                 or self.alloc.can_fit(len(self.queue[0].prompt))))
        if admissible or any(s is not None for s in self.slots):
            return self.step()
        # a queued request blocked from admission still waits: mark its
        # TTFT eligibility so the wait is charged when it lands
        for req in self.queue:
            if req.arrival_step <= self.step_count:
                self._eligible_clock.setdefault(req.uid, self.clock)
        self.step_count += 1
        report = {"step": self.step_count, "idle": True, "latency_s": 0.0,
                  "dense_latency_s": 0.0, "wall_s": 0.0, "active": 0,
                  "admitted": [], "completed": [],
                  "queued": len(self.queue)}
        self.history.append(report)
        return report

    def request_cost_steps(self, prompt_len: int,
                           max_new_tokens: int) -> int:
        """Engine steps a request will occupy a slot for: its prefill
        chunks plus one step per generated token — the cost the
        chi_aware router prices against a replica's capacity."""
        return -(-int(prompt_len) // self.prefill_chunk) \
            + int(max_new_tokens)

    def load_snapshot(self) -> LoadSnapshot:
        """Queue/slot/pool load + plan-adjusted capacity, for routing."""
        backlog = 0
        for s in self.slots:
            if s is None:
                continue
            P = len(s.req.prompt)
            backlog += -(-(P - min(s.pos, P)) // self.prefill_chunk) \
                + (s.req.max_new_tokens - len(s.generated))
        for req in self.queue:
            backlog += self.request_cost_steps(len(req.prompt),
                                               req.max_new_tokens)
        cap = self.plane.capacity(self.step_count)
        return LoadSnapshot(
            step=self.step_count, clock=self.clock,
            queue_depth=len(self.queue),
            active=sum(s is not None for s in self.slots),
            free_slots=len(self.free),
            free_pages=(self.alloc.free_pages if self.alloc is not None
                        else None),
            num_slots=self.num_slots,
            chi=cap.chi, work_frac=cap.work_frac,
            step_time_s=cap.step_time_s,
            dense_step_time_s=cap.dense_step_time_s,
            backlog_steps=backlog)

    def evict_queue(self) -> List[Request]:
        """Pop every queued (not yet admitted) request — the cluster
        manager reassigns them when a replica drains or fails. Their
        TTFT eligibility clocks go with them; the receiving replica
        restarts the wait clock in its own timeline."""
        out = list(self.queue)
        self.queue.clear()
        for req in out:
            self._eligible_clock.pop(req.uid, None)
            self._submitted.pop(req.uid, None)
        return out

    def active_requests(self) -> List[Request]:
        """Requests currently holding a slot, in admission order — what
        a failed replica's manager must re-route (greedy decode is
        deterministic, so a from-scratch re-run is token-identical)."""
        order = sorted((i for i, s in enumerate(self.slots)
                        if s is not None),
                       key=lambda i: (self.slots[i].admitted_step, i))
        return [self.slots[i].req for i in order]

    # -- drivers -------------------------------------------------------------
    def run(self, requests: List[Request],
            max_steps: Optional[int] = None) -> List[Completion]:
        """Replay an arrival trace until every request completes.

        Requests are submitted AT their arrival step (not up front), so a
        bounded queue measures true concurrent occupancy rather than the
        length of the trace."""
        if not requests:
            return []
        pending = collections.deque(sorted(requests,
                                           key=lambda r: r.arrival_step))
        limit = max_steps or (self.max_len * (len(requests) + 1)
                              + pending[-1].arrival_step)
        while (pending or self.queue
               or any(s is not None for s in self.slots)):
            if self.step_count >= limit:
                raise RuntimeError(f"serve loop exceeded {limit} steps")
            while pending and pending[0].arrival_step <= self.step_count:
                r = pending.popleft()
                if not self.submit(r):
                    raise RuntimeError(f"queue full, request {r.uid} "
                                       "rejected")
            self.step()
        return sorted(self.completions, key=lambda c: c.uid)

    def close(self) -> None:
        """Flush/close the telemetry trace (safe to call repeatedly)."""
        self.plane.close()

    # -- introspection (tests / benchmarks) ----------------------------------
    def trace_counts(self) -> Dict[str, int]:
        """Executable-build telemetry: plan signatures compiled vs reused,
        and the base jitted step's trace-cache size (1 = never re-traced
        across arrivals/completions/recycling)."""
        out = dict(self.plane.counts())
        out["base_step_traces"] = (self._base_step._cache_size()
                                   if hasattr(self._base_step, "_cache_size")
                                   else -1)
        return out

    def analysis_cases(self, step: str = "serve_engine_step", *,
                       compile_hlo: bool = True):
        """Static-analysis TraceCases for THIS engine's fused base step
        (repro.analysis): the exact jitted executable ``step()`` drives,
        with the KV cache declared hot state (argnum 1, donated) so R2
        proves the donation actually aliased in the compiled module."""
        from repro.analysis.registry import TraceCase
        sds = jax.ShapeDtypeStruct

        def shape_of(tree):
            return jax.tree.map(lambda a: sds(a.shape, a.dtype), tree)

        B, C = self.num_slots, self.prefill_chunk
        args = (shape_of(self.params), shape_of(self.cache),
                sds((C, B), jnp.int32), sds((C, B), jnp.int32),
                sds((C, B), jnp.float32), sds((B,), jnp.float32))
        if self.paging is not None:
            args += (sds((B, self.paging.pages_per_slot), jnp.int32),)
        if self._base_plan_slots:
            raise NotImplementedError(
                "analysis_cases covers the base (dense) serve step; "
                "controlled plan-slot steps are traced via the "
                "serve_decode_step provider")
        return [TraceCase(
            step=step, name=f"base_tp{self.tp}", fn=self._base_step,
            args=args, mesh=self.mesh, donate_argnums=(1,),
            state_argnums=(1,), compile_hlo=compile_hlo,
            signature=f"serve_base_tp{self.tp}")]


#: The well-defined zero-traffic stats record: what a drained or
#: never-routed replica reports. Every key the non-empty record carries,
#: all-zero — so aggregation code can sum/compare without key checks.
EMPTY_LATENCY_STATS = {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0,
                       "mean_ms": 0.0, "ttft_mean_ms": 0.0, "tokens": 0,
                       "requests": 0, "tok_per_s": 0.0}


def latency_percentiles(completions: List[Completion],
                        total_time_s: Optional[float] = None
                        ) -> Dict[str, float]:
    """p50/p95/p99 per-token latency (ms), mean TTFT + tokens/s.

    Pass the engine's elapsed clock as ``total_time_s`` for true ENGINE
    throughput: concurrently-decoding slots each bill the full step
    latency to their own token, so summing per-token latencies would
    understate throughput by ~the number of active slots. Without it the
    sum-based figure (per-slot serial throughput) is returned.

    A run with no emitted tokens — a drained or zero-traffic replica, or
    completions that are all ``max_new_tokens=0`` — returns a copy of
    :data:`EMPTY_LATENCY_STATS` instead of crashing percentile math on
    an empty vector (pinned by tests/test_serve_engine.py)."""
    lats = np.asarray([l for c in completions for l in c.token_latencies])
    if lats.size == 0:
        return dict(EMPTY_LATENCY_STATS)
    # TTFT = each request's FIRST token latency (queue wait + prefill)
    ttft = [c.token_latencies[0] for c in completions if c.token_latencies]
    span = total_time_s if total_time_s is not None else float(lats.sum())
    return {"p50_ms": float(np.percentile(lats, 50) * 1e3),
            "p95_ms": float(np.percentile(lats, 95) * 1e3),
            "p99_ms": float(np.percentile(lats, 99) * 1e3),
            "mean_ms": float(lats.mean() * 1e3),
            "ttft_mean_ms": float(np.mean(ttft) * 1e3),
            "tokens": int(lats.size),
            "requests": len(completions),
            "tok_per_s": float(lats.size / max(span, 1e-12))}


# ---------------------------------------------------------------------------
# Fixed-batch engine (the seed's lockstep loop, kept as the equivalence
# baseline: tests prove slot recycling is semantics-preserving against it)
# ---------------------------------------------------------------------------


class FixedBatchEngine:
    """Holds params + a jitted single-token step; serves fixed batches."""

    def __init__(self, arch: Union[str, ModelConfig], batch: int,
                 max_len: int, ckpt_dir: Optional[str] = None,
                 seed: int = 0):
        self.cfg = resolve_model(arch)
        self.api = get_api(self.cfg)
        self.batch = batch
        self.max_len = max_len
        # made under jit, as ServeEngine makes its params: the two engines
        # must hold bit-identical weights for token-exact comparisons
        params = jax.jit(lambda: self.api.init(jax.random.PRNGKey(seed),
                                               self.cfg)[0])()
        if ckpt_dir:
            last = ckpt_store.latest_step(ckpt_dir)
            if last is not None:
                params = ckpt_store.load_params(ckpt_dir, last, params)
        self.params = params
        self._step = jax.jit(
            lambda p, c, t, pos: self.api.decode_step(p, self.cfg, c, t, pos),
            donate_argnums=(1,))

    def generate(self, prompts: np.ndarray, gen_len: int,
                 eos_id: Optional[int] = None) -> np.ndarray:
        """prompts [B, P] int32 -> [B, P+gen_len] greedy continuations."""
        B, P = prompts.shape
        assert B == self.batch and P + gen_len <= self.max_len
        cache = self.api.init_cache(self.cfg, B, self.max_len)
        out = [prompts[:, 0]]
        done = np.zeros((B,), bool)
        for t in range(P + gen_len - 1):
            logits, cache = self._step(
                self.params, cache, jnp.asarray(out[-1], jnp.int32),
                jnp.full((B,), t, jnp.int32))
            if t + 1 < P:
                nxt = prompts[:, t + 1]
            else:
                nxt = np.asarray(logits.argmax(-1))
                if eos_id is not None:
                    done |= nxt == eos_id
                    nxt = np.where(done, eos_id or 0, nxt)
            out.append(nxt)
            if eos_id is not None and done.all():
                break
        return np.stack(out, axis=1)


# Backwards-compatible alias (pre-continuous-batching name).
DecodeEngine = FixedBatchEngine


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen-len", type=int, default=24)
    ap.add_argument("--arrival-every", type=int, default=2,
                    help="steps between request arrivals (staggered trace)")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--control", default="off",
                    choices=["off", "zero", "semi"])
    ap.add_argument("--hetero", default="none",
                    choices=["none", "static", "round_robin", "contention",
                             "trace"])
    ap.add_argument("--chi", type=float, default=4.0)
    ap.add_argument("--sim-ranks", type=int, default=0)
    ap.add_argument("--max-sources", type=int, default=3,
                    help="concurrent migration slots (semi mode)")
    ap.add_argument("--beta-policy", default="lossless",
                    choices=["lossless", "eq2"],
                    help="semi mission split: lossless migrates the full "
                         "offset volume (token-exact); eq2 balances "
                         "migration vs resize cost per Eq.(2)")
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--fused-attn", action="store_true",
                    help="fused Pallas decode-attention kernel "
                         "(interpret-mode fallback off-TPU)")
    ap.add_argument("--psum-chunks", type=int, default=1,
                    help="chunk-split the controlled epilogue all-reduce "
                         "into this many async-overlappable psums")
    ap.add_argument("--xla-preset", default="none",
                    choices=["none", "latency-hiding"],
                    help="XLA latency-hiding flag preset (applied before "
                         "jax initializes)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--times", default="modeled",
                    choices=["modeled", "measured"],
                    help="controller input: χ-oracle or the online "
                         "StragglerEstimator over measured decode times")
    ap.add_argument("--trace-in", default=None,
                    help="telemetry trace to replay (with --hetero trace)")
    ap.add_argument("--trace-out", default=None,
                    help="record a replayable telemetry trace here (JSONL)")
    ap.add_argument("--geometry", default=None,
                    help="static ragged TP shard geometry: per-rank FFN "
                         "block counts 'a,b,...' (DESIGN_SHARDING.md)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="block-paged KV cache page size in tokens "
                         "(0 = fixed per-slot cache); with --fused-attn "
                         "must be a multiple of 8")
    ap.add_argument("--prefill-chunk", type=int, default=1,
                    help="prompt positions fed per step during prefill "
                         "(scanned inside the one jitted step)")
    ap.add_argument("--kv-int8", action="store_true",
                    help="int8-quantize the paged K/V pools (per-row "
                         "scales; oracle attention path only)")
    args = ap.parse_args()
    enable_compile_cache()

    control = ControlConfig(
        mode=args.control, hetero_kind=args.hetero, chi=args.chi,
        sim_ranks=args.sim_ranks, max_sources=args.max_sources,
        beta_policy=args.beta_policy, use_kernel=args.use_kernel,
        fused_attention=args.fused_attn, psum_chunks=args.psum_chunks,
        times=args.times, trace_in=args.trace_in, trace_out=args.trace_out,
        geometry=geom_lib.parse_geometry_arg(args.geometry, args.tp))
    eng = ServeEngine(args.arch, num_slots=args.slots,
                      max_len=args.prompt_len + args.gen_len, tp=args.tp,
                      ckpt_dir=args.ckpt_dir, control=control,
                      page_size=args.page_size,
                      prefill_chunk=args.prefill_chunk,
                      kv_int8=args.kv_int8)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, eng.cfg.vocab_size,
                                        (args.prompt_len,)).astype(np.int32),
                    max_new_tokens=args.gen_len,
                    arrival_step=i * args.arrival_every)
            for i in range(args.requests)]
    t0 = time.time()
    comps = eng.run(reqs)
    eng.close()
    wall = time.time() - t0
    stats = latency_percentiles(comps, total_time_s=eng.clock)
    for c in comps[:4]:
        print(f"req {c.uid}: slot {c.slot}, steps "
              f"{c.admitted_step}->{c.finished_step}, "
              f"tokens {c.tokens[:8]}...")
    print(f"{len(comps)} requests, {stats['tokens']} tokens in {wall:.1f}s "
          f"wall; modeled p50/p95/p99 per-token "
          f"{stats['p50_ms']:.2f}/{stats['p95_ms']:.2f}/"
          f"{stats['p99_ms']:.2f} ms, {stats['tok_per_s']:.1f} tok/s")
    print(f"trace counts: {eng.trace_counts()}")


# ---------------------------------------------------------------------------
# static-analysis registration (repro.analysis; see DESIGN_ANALYSIS.md)
# ---------------------------------------------------------------------------

from repro.analysis import registry as _analysis  # noqa: E402


def _an_serve_engine_cases(env):
    if not env.heavy:
        return []
    tp = 2 if env.max_devices >= 2 else 1
    eng = ServeEngine("yi-6b", num_slots=2, max_len=8, tp=tp)
    try:
        return eng.analysis_cases(compile_hlo=env.compile_hlo)
    finally:
        eng.close()


_analysis.register("serve_engine_step", _an_serve_engine_cases)


if __name__ == "__main__":
    main()
