#!/usr/bin/env python3
"""Smoke run of the main paths on TPU chips, at published widths.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the TP=4 legs, on a four-chip host

One chip runs, in order, with weights made from a seed:

1. a device check: the run stops unless JAX sees a TPU and the Pallas
   kernels compile (interpret mode refused);
2. the kernels against their references at yi-6b widths in bf16: fused
   paged decode attention, ``block_pruned_matmul`` and
   ``fused_pruned_ffn`` (forward and gradient, block 128);
3. ``ServeEngine`` on yi-6b (d_model 4096, d_ff 11008, bf16, as many of
   its 32 layers as fit one chip) twice: dense with fused paged attention,
   then ZERO-resized decode through the pruned kernels;
4. ``run_training`` on vit-1b (d_model 2048, d_ff 8192, float32 with
   AdamW), depth cut to fit one chip.

``--chips 4`` runs only what exists across chips: vit-1b at all 24 layers
at TP=4 under SEMI control with the pruned kernels against the dense run,
and yi-6b at TP=4 under lossless SEMI with fused attention against dense
serving, step by step.

Every phase checks its outputs and raises on a failure. Compile time is
reported apart from the steady per-step wall time (host clock around
work that ends in ``block_until_ready``). These are smoke readings of one
run, not benchmark results. The last line of standard output is a JSON
object naming the device; it is printed only when every phase passed.
The persistent compilation cache is on (``JAX_COMPILATION_CACHE_DIR``,
else ``<checkout>/.jax_cache``), so a second run reads what the first
compiled.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench.compilelog import CompileLog  # noqa: E402

# yi-6b serving: 4 slots, 8 requests of a 96-token prompt and 32 new
# tokens, a 16-token page pool and 8-token prefill chunks
SERVE = dict(slots=4, requests=8, prompt=96, gen=32, page_size=16,
             prefill_chunk=8)
# layers that fit one 16 GB chip, from memory_analysis() of the compiled
# programs for a described v5e: yi-6b serving in bf16 needs 12.2 GB of
# weights + 1.3 GB of temporaries at 32 layers; vit-1b training in
# float32 keeps params, AdamW moments and the step's new copies
# (2 x 4.8 GB + 0.9 GB at 8 layers; 12 layers would need 15.8 GB)
SERVE_LAYERS = 32
TRAIN_LAYERS = 8

# Tolerances, as the largest |kernel - reference| over the largest
# |reference|. The references run in float32 at "highest" precision on
# the same bf16 inputs; the kernels multiply bf16 on the MXU with float32
# accumulation, so they differ by bf16 roundings of intermediates and
# outputs (2^-8 relative each). Attention rounds the softmax weights and
# its output; the pruned matmul rounds its output; the fused FFN also
# rounds the hidden activation, and its backward rounds each of its
# chained products, so its gradient gets the widest bound.
TOL = {"paged_decode_attention": 2e-2, "block_pruned_matmul": 1e-2,
       "block_pruned_matmul_grad": 1e-2, "fused_pruned_ffn": 2e-2,
       "fused_pruned_ffn_grad": 4e-2}


class LoweredModules:
    """Every module JAX lowers is written to a scratch directory, so a leg
    can show that the program it ran holds the Mosaic kernels
    (``tpu_custom_call``) — also when the executable came from the
    persistent cache, since lowering precedes the cache lookup."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="chip_smoke_ir_")
        jax.config.update("jax_dump_ir_to", self.dir)
        self.seen = set()

    def new(self, fn_name):
        """Modules of jitted ``fn_name`` lowered since the last call."""
        names = sorted(n for n in os.listdir(self.dir) if n not in self.seen)
        self.seen.update(names)
        return [os.path.join(self.dir, n) for n in names
                if f"jit_{fn_name}" in n]

    def check_kernels(self, leg, fn_name):
        mods = self.new(fn_name)
        if not mods:
            raise RuntimeError(f"{leg}: no lowered {fn_name} module found")
        for m in mods:
            with open(m) as f:
                if "tpu_custom_call" not in f.read():
                    raise RuntimeError(
                        f"{leg}: {os.path.basename(m)} holds no "
                        "tpu_custom_call — the Pallas kernels did not run")
        print(f"{leg}: tpu_custom_call in all {len(mods)} lowered "
              f"{fn_name} module(s)")

    def close(self):
        jax.config.update("jax_dump_ir_to", "")
        shutil.rmtree(self.dir, ignore_errors=True)


def device_check(chips):
    """The chip, or a non-zero exit naming why not."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {d.platform!r}, "
            f"{len(devs)} device(s)); this smoke run is for the chip only")
    from repro.kernels import ops
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if env in ops._TRUTHY:
        raise SystemExit(
            f"chip_smoke: REPRO_PALLAS_INTERPRET={env!r} asks for Pallas "
            "interpret mode; the kernels must compile for the chip")
    if ops.interpret_mode():
        raise SystemExit("chip_smoke: Pallas kernels would run in "
                         "interpret mode on this backend")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX found {len(devs)}")
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_line(tag):
    stats = jax.devices()[0].memory_stats() or {}
    gb = lambda k: stats.get(k, 0) / 1e9
    print(f"{tag}: device 0 bytes_in_use={gb('bytes_in_use'):.2f} GB "
          f"peak={gb('peak_bytes_in_use'):.2f} GB "
          f"limit={gb('bytes_limit'):.2f} GB")


# ---------------------------------------------------------------------------
# kernels against their references
# ---------------------------------------------------------------------------


def _compare(name, got, ref):
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        raise RuntimeError(f"kernel {name}: shape {got.shape} != "
                           f"reference {ref.shape}")
    if not np.isfinite(got).all():
        raise RuntimeError(f"kernel {name}: non-finite output")
    err = float(np.max(np.abs(got - ref)))
    rel = err / max(float(np.max(np.abs(ref))), 1e-30)
    print(f"kernel {name}: max_abs_err={err:.3e} rel_err={rel:.3e} "
          f"tol={TOL[name]:.0e}")
    if not rel <= TOL[name]:
        raise RuntimeError(f"kernel {name}: rel_err {rel:.3e} exceeds "
                           f"{TOL[name]:.0e}")


def _highest(fn):
    """A float32 reference: matmuls at full precision (the TPU default
    would multiply float32 in one bf16 pass)."""
    def run(*a):
        with jax.default_matmul_precision("highest"):
            return fn(*a)
    return jax.jit(run)


def kernel_checks(*, batch, q_heads, kv_heads, head_dim, max_len, page_size,
                  d_model, d_ff, tokens, block=128, seed=0):
    from repro.kernels import ops, ref
    from repro.layers import attention
    rng = np.random.default_rng(seed)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    f32 = lambda a: a.astype(jnp.float32)

    # -- fused paged decode attention vs the layers/attention.py oracle --
    pps = max_len // page_size
    num_pages = batch * pps + 8
    q = bf(rng.standard_normal((batch, q_heads, 1, head_dim)))
    kp = bf(rng.standard_normal((num_pages, kv_heads, page_size, head_dim)))
    vp = bf(rng.standard_normal((num_pages, kv_heads, page_size, head_dim)))
    cur = np.asarray([max_len - 1, max_len * 3 // 4, max_len // 3, 3][:batch]
                     + [max_len // 2] * max(0, batch - 4), np.int32)
    perm = rng.permutation(num_pages)
    pages = perm[:batch * pps].reshape(batch, pps).astype(np.int32)
    pages[np.arange(pps)[None, :] > (cur // page_size)[:, None]] = -1
    pages, cur = jnp.asarray(pages), jnp.asarray(cur)
    got = jax.jit(lambda *a: ops.fused_paged_decode_attention(
        a[0], a[1], a[2], pages=a[3], cur_pos=a[4]))(q, kp, vp, pages, cur)
    want = _highest(lambda *a: attention.paged_decode_attention(
        a[0], a[1], a[2], pages=a[3], cur_pos=a[4]))(
            f32(q), f32(kp), f32(vp), pages, cur)
    _compare("paged_decode_attention", got, want)

    # -- block_pruned_matmul: y = x[:, kept] @ w[kept] (down projection) --
    nb = d_ff // block
    keep = jnp.asarray(np.sort(rng.choice(nb, nb // 2, replace=False))
                       .astype(np.int32))
    x = bf(rng.standard_normal((tokens, d_ff)))
    w = bf(rng.standard_normal((d_ff, d_model)) * 0.02)
    g = bf(rng.standard_normal((tokens, d_model)))

    def mm_loss(fn):
        return lambda x_, w_: jnp.sum(fn(x_, w_).astype(jnp.float32)
                                      * f32(g))

    kern = lambda x_, w_: ops.block_pruned_matmul(x_, w_, keep, block)
    oracle = lambda x_, w_: ref.block_pruned_matmul_ref(x_, w_, keep,
                                                        block=block)
    _compare("block_pruned_matmul", jax.jit(kern)(x, w),
             _highest(oracle)(f32(x), f32(w)))
    got = jax.jit(jax.grad(mm_loss(kern), argnums=(0, 1)))(x, w)
    want = _highest(jax.grad(mm_loss(oracle), argnums=(0, 1)))(f32(x),
                                                               f32(w))
    for a, b in zip(got, want):
        _compare("block_pruned_matmul_grad", a, b)

    # -- fused_pruned_ffn: silu(x @ Wg[:, kept]) * (x @ Wu[:, kept]) @
    #    Wd[kept] — the gated FFN pair ---------------------------------
    x = bf(rng.standard_normal((tokens, d_model)))
    wu = bf(rng.standard_normal((d_model, d_ff)) * 0.02)
    wgt = bf(rng.standard_normal((d_model, d_ff)) * 0.02)
    wd = bf(rng.standard_normal((d_ff, d_model)) * 0.02)
    cols = (keep[:, None] * block + jnp.arange(block)[None, :]).reshape(-1)

    def ffn_ref(x_, wu_, wd_, wg_):
        h = jax.nn.silu(x_ @ wg_[:, cols]) * (x_ @ wu_[:, cols])
        return h @ wd_[cols]

    def ffn_kern(x_, wu_, wd_, wg_):
        return ops.fused_pruned_ffn(x_, wu_, wd_, keep, wg_, jax.nn.silu,
                                    block)

    def ffn_loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * f32(g))

    _compare("fused_pruned_ffn", jax.jit(ffn_kern)(x, wu, wd, wgt),
             _highest(ffn_ref)(f32(x), f32(wu), f32(wd), f32(wgt)))
    got = jax.jit(jax.grad(ffn_loss(ffn_kern), argnums=(0, 1, 2, 3)))(
        x, wu, wd, wgt)
    want = _highest(jax.grad(ffn_loss(ffn_ref), argnums=(0, 1, 2, 3)))(
        f32(x), f32(wu), f32(wd), f32(wgt))
    for a, b in zip(got, want):
        _compare("fused_pruned_ffn_grad", a, b)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def _requests(vocab, *, requests, prompt, gen, seed, **_):
    from repro.launch.serve import Request
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(0, vocab, (prompt,))
                    .astype(np.int32), max_new_tokens=gen)
            for i in range(requests)]


def _engine(cfg, control, tp, *, slots, prompt, gen, page_size,
            prefill_chunk, seed, **_):
    from repro.launch.serve import ServeEngine
    return ServeEngine(cfg, num_slots=slots, max_len=prompt + gen, tp=tp,
                       control=control, param_dtype="bfloat16",
                       page_size=page_size, prefill_chunk=prefill_chunk,
                       seed=seed)


def _tokens_so_far(eng):
    out = {c.uid: list(c.tokens) for c in eng.completions}
    out.update({s.req.uid: list(s.generated) for s in eng.slots
                if s is not None})
    return out


def _step_timed(eng, log, walls, compile_walls):
    """One engine step; its wall time counts as compile time if JAX
    traced, lowered or compiled anything during it."""
    n0 = log.events
    rep = eng.step()
    (compile_walls if log.events != n0 else walls).append(rep["wall_s"])
    return rep


def _check_completions(leg, eng, vocab, *, requests, gen, **_):
    comps = sorted(eng.completions, key=lambda c: c.uid)
    if len(comps) != requests:
        raise RuntimeError(f"{leg}: {len(comps)} of {requests} requests "
                           "completed")
    for c in comps:
        t = np.asarray(c.tokens)
        if t.shape != (gen,):
            raise RuntimeError(f"{leg}: request {c.uid} produced "
                               f"{t.shape[0]} tokens, expected {gen}")
        if t.min() < 0 or t.max() >= vocab:
            raise RuntimeError(f"{leg}: request {c.uid} emitted a token "
                               f"outside [0, {vocab})")
    return {c.uid: list(c.tokens) for c in comps}


def _timing_line(leg, walls, compile_walls):
    w = np.asarray(walls)
    steady = (f"median {np.median(w):.6f} s min {w.min():.6f} s "
              f"max {w.max():.6f} s over {w.size} steps" if w.size
              else "no steady steps")
    print(f"{leg}: {len(compile_walls)} step(s) that compiled took "
          f"{sum(compile_walls):.3f} s wall; steady step wall: {steady}")


def serve_leg(leg, cfg, control, lowered, log, *, tp=1, **sizes):
    memory_line(f"{leg} start")
    c0 = log.seconds
    eng = _engine(cfg, control, tp, **sizes)
    print(f"{leg}: {cfg.name} d_model={cfg.d_model} d_ff={cfg.d_ff} "
          f"layers={cfg.num_layers} heads={cfg.num_heads}/"
          f"{cfg.num_kv_heads} vocab={cfg.vocab_size} params=bfloat16 "
          f"tp={tp} mode={control.mode} use_kernel={control.use_kernel} "
          f"fused_attention={control.fused_attention}")
    for r in _requests(cfg.vocab_size, **sizes):
        eng.submit(r)
    walls, compile_walls = [], []
    while not eng.idle:
        if eng.step_count > 20 * sizes["requests"] * (
                sizes["prompt"] + sizes["gen"]):
            raise RuntimeError(f"{leg}: serve loop did not drain")
        _step_timed(eng, log, walls, compile_walls)
    tokens = _check_completions(leg, eng, cfg.vocab_size, **sizes)
    resized = sum(1 for h in eng.history if h.get("max_bucket", 0) > 0)
    print(f"{leg}: {len(tokens)} requests x {sizes['gen']} tokens in "
          f"{eng.step_count} steps; resized steps={resized}; "
          f"plan compiles={eng.plane.cache.compile_count}")
    print(f"{leg}: compile {log.seconds - c0:.3f} s (engine set-up "
          "included)")
    _timing_line(leg, walls, compile_walls)
    lowered.check_kernels(leg, "stepper")
    memory_line(f"{leg} end")
    eng.close()
    return tokens


def serve_lockstep(cfg, lowered, log, *, tp, **sizes):
    """Dense and lossless-SEMI engines step by step on the same requests;
    reports the first step whose emitted tokens differ."""
    from repro.control import ControlConfig
    legs = {
        "serve_tp4_dense": ControlConfig(mode="off", fused_attention=True),
        "serve_tp4_semi": ControlConfig(mode="semi", hetero_kind="static",
                                        chi=4.0, fused_attention=True,
                                        beta_policy="lossless"),
    }
    c0 = log.seconds
    engs = {k: _engine(cfg, c, tp, **sizes) for k, c in legs.items()}
    print(f"serve_tp4: {cfg.name} d_model={cfg.d_model} d_ff={cfg.d_ff} "
          f"layers={cfg.num_layers} params=bfloat16 tp={tp}, dense vs "
          "lossless semi (hetero static, chi 4), fused attention")
    reqs = _requests(cfg.vocab_size, **sizes)
    for e in engs.values():
        for r in reqs:
            e.submit(r)
    walls = {k: ([], []) for k in engs}
    first_div = None
    while not all(e.idle for e in engs.values()):
        if max(e.step_count for e in engs.values()) > 20 * sizes[
                "requests"] * (sizes["prompt"] + sizes["gen"]):
            raise RuntimeError("serve_tp4: serve loop did not drain")
        for k, e in engs.items():
            _step_timed(e, log, *walls[k])
        a, b = (_tokens_so_far(e) for e in engs.values())
        if first_div is None and a != b:
            uid = min(u for u in set(a) | set(b) if a.get(u) != b.get(u))
            ta, tb = a.get(uid, []), b.get(uid, [])
            idx = next((i for i, (x, y) in enumerate(zip(ta, tb)) if x != y),
                       min(len(ta), len(tb)))
            first_div = (engs["serve_tp4_dense"].step_count, uid, idx)
    toks = {k: _check_completions(k, e, cfg.vocab_size, **sizes)
            for k, e in engs.items()}
    semi = engs["serve_tp4_semi"]
    migrated = sum(1 for h in semi.history if h.get("mig_srcs"))
    if not migrated:
        raise RuntimeError("serve_tp4_semi: no step migrated work")
    print(f"serve_tp4: compile {log.seconds - c0:.3f} s for both engines "
          "(set-up included)")
    for k in engs:
        print(f"{k}: {engs[k].step_count} steps")
        _timing_line(k, *walls[k])
    lowered.check_kernels("serve_tp4", "stepper")
    n_tok = sum(len(t) for t in toks["serve_tp4_dense"].values())
    same = sum(x == y for u in toks["serve_tp4_dense"]
               for x, y in zip(toks["serve_tp4_dense"][u],
                               toks["serve_tp4_semi"][u]))
    print(f"serve_tp4: semi migrated on {migrated} steps; "
          f"{same}/{n_tok} tokens equal to dense; "
          + ("token-exact" if first_div is None else
             f"first divergence at engine step {first_div[0]}, request "
             f"{first_div[1]}, token {first_div[2]}"))
    for e in engs.values():
        e.close()


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def train_leg(leg, cfg, lowered, log, *, steps, tp=1, check_kernels=False,
              **kw):
    from repro.launch.train import run_training
    memory_line(f"{leg} start")
    c0 = log.seconds
    n_params = cfg.param_count()
    print(f"{leg}: {cfg.name} d_model={cfg.d_model} d_ff={cfg.d_ff} "
          f"layers={cfg.num_layers} params~{n_params / 1e9:.3f} B float32 "
          f"+ AdamW; tp={tp} {kw}")
    hist = run_training(cfg, steps=steps, tp=tp, quiet=True, **kw)
    loss = np.asarray(hist["loss"])
    if loss.shape != (steps,) or not np.isfinite(loss).all():
        raise RuntimeError(f"{leg}: losses {loss.tolist()} are not "
                           f"{steps} finite values")
    print(f"{leg}: loss per step {loss.tolist()}")
    print(f"{leg}: wall_s per step {hist['wall_s']} (the first includes "
          f"compile); compile {log.seconds - c0:.3f} s")
    if check_kernels:
        lowered.check_kernels(leg, "train_step")
    else:
        lowered.new("train_step")
    memory_line(f"{leg} end")
    return hist


# ---------------------------------------------------------------------------


def one_chip(lowered, log):
    from repro.config import get_config
    yi = dataclasses.replace(get_config("yi-6b"), num_layers=SERVE_LAYERS)
    vit = dataclasses.replace(get_config("vit-1b"),
                              num_layers=TRAIN_LAYERS)
    print(f"cuts: yi-6b serves {SERVE_LAYERS} of "
          f"{get_config('yi-6b').num_layers} layers; vit-1b trains "
          f"{TRAIN_LAYERS} of {get_config('vit-1b').num_layers} layers")
    kernel_checks(batch=SERVE["slots"], q_heads=yi.num_heads,
                  kv_heads=yi.num_kv_heads, head_dim=yi.resolved_head_dim,
                  max_len=SERVE["prompt"] + SERVE["gen"],
                  page_size=SERVE["page_size"], d_model=yi.d_model,
                  d_ff=yi.d_ff, tokens=256)
    gc.collect()
    from repro.control import ControlConfig
    serve_leg("serve_dense", yi, ControlConfig(fused_attention=True),
              lowered, log, seed=0, **SERVE)
    gc.collect()
    serve_leg("serve_zero_kernel", yi,
              ControlConfig(mode="zero", use_kernel=True,
                            hetero_kind="static", chi=4.0, sim_ranks=4,
                            fused_attention=True),
              lowered, log, seed=0, **SERVE)
    gc.collect()
    train_leg("train", vit, lowered, log, steps=3, seed=0)


def four_chips(lowered, log):
    from repro.config import get_config
    vit = get_config("vit-1b")
    yi = get_config("yi-6b")
    print(f"cuts: none — vit-1b trains all {vit.num_layers} layers and "
          f"yi-6b serves all {yi.num_layers} layers at TP=4")
    off = train_leg("train_tp4_off", vit, lowered, log, steps=5, tp=4,
                    control_mode="off", seed=0)
    semi = train_leg("train_tp4_semi", vit, lowered, log, steps=5, tp=4,
                     check_kernels=True, control_mode="semi",
                     hetero_kind="contention", mig_blocks=2,
                     use_kernel=True, seed=0)
    shed = [s for s in semi["mig_shed"] if s[0]]
    if not shed:
        raise RuntimeError("train_tp4_semi: mig_shed is empty on every "
                           "step — no work migrated")
    print(f"train_tp4_semi: mig_shed {semi['mig_shed']}; final loss "
          f"{semi['loss'][-1]} vs off {off['loss'][-1]}")
    gc.collect()
    serve_lockstep(yi, lowered, log, tp=4, seed=0, **SERVE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: every one-chip phase; 4: only the TP=4 legs")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    device = device_check(args.chips)
    from repro.launch._bootstrap import enable_compile_cache
    cache_dir = enable_compile_cache()
    n_cached = (len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
                else 0)
    print(f"compile cache: {cache_dir} holds {n_cached} entries at start")
    log = CompileLog()
    lowered = LoweredModules()
    (one_chip if args.chips == 1 else four_chips)(lowered, log)
    lowered.close()
    n_after = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"compile cache: {log.cache_hits} hits, {log.cache_misses} "
          f"misses; {n_after} entries at end; total compile "
          f"{log.seconds:.3f} s; run {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
