"""Training through ``repro.launch.train.run_training``.

``run_training`` runs a fixed number of steps and has no hook, so the
driver wraps the calls its loop makes into other layers and leaves the
loop itself alone:

* the image stream: the driver's own feed (``chipbench.traffic``) stands
  in for the program's synthetic stream. Each batch it hands out marks a
  step boundary (the previous step has finished: its metrics were read
  on the host), and once the window has closed it raises
  :class:`WindowClosed`, which ends the run;
* the control plane's ``dispatch``: the step program it returns is
  wrapped to keep what the correctness check needs from the first
  steps, and the kept-block plan of each of them.

``run_training`` compiles its weight initialisation with its seed as a
constant, ~25 s of compile for every new seed at ViT-1B size. So it is
given the fixed seed 0 (under static chi with priority selection nothing
else draws from it; the image stream is the driver's own), and the first
step is handed, in place of those weights, the program's own
initialisation from ``--seed``, compiled once with the key as its
argument and placed as the program placed its own (``seeded_params``).

Set-up is everything up to the batch of step ``warmup_steps + 1``
(weights from the seed on the device, the compile, the first steps).
The window then runs ``--seconds``; samples completed in it count.

After the window, with the program's state freed, the plain reference
(``chipbench.reference.vit``) runs the first three steps from the same
seed on the same images under the same plan, and the program's losses,
first gradient and parameter change are compared with it.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time

import numpy as np

from chipbench import harness
from chipbench import traffic as traffic_lib
from chipbench import work

MODEL_KEYS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
              "num_attention_heads": "num_heads",
              "num_hidden_layers": "num_layers", "rms_norm_eps": "norm_eps"}


class WindowClosed(Exception):
    """Raised from the image feed to end ``run_training`` at the close."""


def model_config(conf: dict):
    from repro.config import get_config
    m = conf["model"]
    cfg = get_config(conf["program_arch"])
    cfg = dataclasses.replace(
        cfg, **{f: m[k] for k, f in MODEL_KEYS.items()},
        num_kv_heads=m["num_attention_heads"],
        head_dim=m["hidden_size"] // m["num_attention_heads"])
    if cfg.act != m["hidden_act"] or cfg.num_classes != m["num_labels"] \
            or cfg.frontend.num_tokens != m["tokens"]:
        raise ValueError("configuration differs from the program's "
                         "architecture")
    return cfg


class Feed:
    """Batches of the traffic's images; a step boundary per batch."""

    def __init__(self, ctx, batch: int):
        self.ctx = ctx
        self.batch = batch
        self.gen = traffic_lib.image_batches(ctx.traffic, seed=ctx.seed,
                                             batch=batch)
        self.given = []           # host time each batch was handed out
        self.kept = []            # the first batches, for the reference
        self.t_open = self.t_close = self.t_traced = None
        self.setup_s = None
        self.warm = int(ctx.traffic["warmup_steps"])

    def __iter__(self):
        return self

    def __next__(self):
        now = time.perf_counter()
        n = len(self.given)
        if n == self.warm:                       # window opens
            self.t_open = now
            self.t_close = now + self.ctx.seconds
            self.setup_s = now - self.ctx.t0
            self.ctx.compiles.mark()
            self.ctx.tracer.start()
        elif self.t_open is not None:
            if now >= self.t_close:
                raise WindowClosed
            if self.t_traced is None and now >= self.t_open + \
                    self.ctx.config.get("trace_seconds", 5.0):
                self.ctx.tracer.stop()
                self.t_traced = now
        self.given.append(now)
        with self.ctx.tracer.span("bench.data"):
            b = next(self.gen)
        if n < 3:
            self.kept.append(b)
        return b

    def ends_in_window(self, until=None) -> list:
        """Host times at which the steps that finished inside the window
        (or before ``until``) ended: each batch handed out after the
        opening one marks the end of the step before it."""
        end = self.t_close if until is None else until
        return [t for t in self.given[self.warm + 1:] if t <= end]

    def done_in_window(self, until=None) -> int:
        return len(self.ends_in_window(until))


class Capture:
    """Wraps each step program the plane dispatches; keeps, from the
    first steps, the initial parameters (until step 4 reads them back
    changed), the first clipped gradient's per-leaf norms, the losses
    and the plans."""

    def __init__(self, ctx, plane_cls, cfg):
        self.ctx = ctx
        self.cfg = cfg
        self.k = 0
        self.p0 = None
        self.g1 = None
        self.change = None
        self.losses = []
        self.plans = []
        self.migrating = []       # per step: did a rank shed blocks
        self.plane = None
        cap = self

        class Plane(plane_cls):
            def __init__(self, *a, **kw):
                super().__init__(*a, **kw)
                cap.plane = self
                fn, slots, aux = self.base
                self.base = (cap.wrap(fn), slots, aux)

            def dispatch(self, plan):
                with ctx.tracer.span("bench.dispatch"):
                    fn, arrays, proj = super().dispatch(plan)
                cap.migrating.append(bool(proj.mig_srcs))
                if len(cap.plans) < 3:
                    cap.plans.append(_host_plan(arrays, self.static, proj))
                return cap.wrap(fn), arrays, proj

        self.Plane = Plane

    def wrap(self, fn):
        import jax
        b1 = self.ctx.config["trainer"]["beta1"]

        def step(params, opt, batch, *rest):
            self.k += 1
            k = self.k
            if k == 1:
                params = seeded_params(params, self.cfg, self.ctx.seed)
                self.p0 = params
            if k == 4:
                self.change = _leaf_norms(jax.tree.map(
                    lambda a, b: a - b, params, self.p0))
                self.p0 = None
            with self.ctx.tracer.span("bench.train_step"):
                out = fn(params, opt, batch, *rest)
            if k == 1:       # AdamW's first moment after one step
                self.g1 = {n: v / (1.0 - b1)
                           for n, v in _leaf_norms(out[1].mu).items()}
            if k <= 3:
                self.losses.append(out[2]["loss"])
            return out
        return step


def seeded_params(like, cfg, seed: int):
    """The weights of ``harness.seeded_init`` in the dtype and on the
    shardings of ``like``, the weights the program made from its own
    seed, whose buffers are then freed."""
    import jax
    params = harness.seeded_init(
        cfg, jax.tree.leaves(like)[0].dtype, seed, like,
        shardings=jax.tree.map(lambda a: a.sharding, like))
    for a in jax.tree.leaves(like):
        a.delete()
    return params


def _leaf_norms(tree) -> dict:
    """Per-leaf 2-norms, keyed by the leaf's own name."""
    import jax
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in flat:
        name = str(getattr(path[-1], "key", path[-1]))
        out[name] = float(jnp.sqrt(jnp.sum(jnp.square(
            leaf.astype(jnp.float32)))))
    return out


def _host_plan(arrays, static, proj) -> dict:
    """The kept-block plan of one step, on the host: each rank's resize
    bucket and the buckets' ratios, the keep-first priority lists, and
    the migration sources with their shed block counts."""
    import jax
    return {"bucket_by_rank": np.asarray(jax.device_get(
                arrays["bucket_by_rank"])),
            "gammas": list(static.buckets),
            "mig_src": [int(r) for r in proj.mig_srcs],
            "mig_shed": [int(m) for m in proj.mig_sheds],
            "pri": {k: np.asarray(jax.device_get(v))
                    for k, v in arrays["pri"].items()}}


def run(ctx) -> dict:
    from repro.launch import train as train_mod
    conf = ctx.config
    t = conf["trainer"]
    m = conf["model"]
    cfg = model_config(conf)
    feeds = []

    def feed_factory(*_, **__):
        f = Feed(ctx, t["batch"])
        feeds.append(f)
        return f

    cap = Capture(ctx, train_mod.ControlPlane, cfg)
    with harness.patched(train_mod, PatternImageStream=feed_factory,
                         ControlPlane=cap.Plane):
        try:
            train_mod.run_training(
                cfg, steps=t["total_steps"], tp=t["tp"],
                control_mode=t["control_mode"],
                hetero_kind=t["hetero_kind"], chi=t["chi"],
                mig_blocks=t["mig_blocks"], use_kernel=t["use_kernel"],
                lr=t["lr"], batch=t["batch"], seed=0, quiet=True,
                log_every=10 ** 9)
            raise RuntimeError("run_training ended before the window "
                               "closed; raise total_steps")
        except WindowClosed:
            pass
    ctx.tracer.stop()
    feed = feeds[0]
    compiles_in_window = ctx.compiles.since_mark
    memory_peak = harness.memory_peak_bytes(ctx.cell["chips"])
    ends = feed.ends_in_window()
    done = len(ends)
    # all the work finished in the window over the time it took: from the
    # opening to the end of the last step finished inside the window
    samples_per_s = (done * t["batch"] / (ends[-1] - feed.t_open)
                     if ends else 0.0)
    warm = feed.warm
    migrating = sum(cap.migrating[warm:warm + done])
    e2e = {"train_samples_per_s": samples_per_s}
    counters = {
        "window_s": ctx.seconds, "steps": done,
        "model_flops": done * t["batch"] * work.vit_train_flops(m),
        "traced_model_flops": (feed.done_in_window(feed.t_traced)
                               * t["batch"] * work.vit_train_flops(m)
                               if feed.t_traced else 0),
        "compiles_in_window": compiles_in_window,
    }
    g = feed.given[feed.warm:]
    gaps_ms = sorted(((b - a) * 1e3 for a, b in zip(g, g[1:])),
                     reverse=True)
    print("train: slowest window steps (ms): "
          + ", ".join(f"{x:.0f}" for x in gaps_ms[:8]) + "; median "
          f"{np.median(gaps_ms) if gaps_ms else 0:.0f}", file=sys.stderr,
          flush=True)
    print(f"train: window {ctx.seconds} s, {done} steps, "
          f"{samples_per_s:.2f} samples/s, set-up {feed.setup_s:.2f} s, "
          f"compiles in window {compiles_in_window}", file=sys.stderr,
          flush=True)

    # -- correctness, after the window, with the program's state freed -----
    import jax
    losses = [float(x) for x in jax.device_get(cap.losses)]
    plans, g1, change = cap.plans, cap.g1, cap.change
    del cap, feeds
    gc.collect()
    jax.clear_caches()
    st = jax.devices()[0].memory_stats() or {}
    print(f"train: {st.get('bytes_in_use', 0) / 1e9:.2f} GB in use on "
          "chip 0 before the reference", file=sys.stderr, flush=True)
    t_ref = time.perf_counter()
    checks = compare(ctx, feed.kept, plans, losses, g1, change)
    print(f"train: reference took {time.perf_counter() - t_ref:.2f} s",
          file=sys.stderr, flush=True)
    checks += [
        {"name": "window_steps_migrating", "value": migrating, "limit": 1,
         "rule": "value >= limit", "ok": migrating >= 1},
        {"name": "compiles_in_window", "value": compiles_in_window,
         "limit": 0, "rule": "value <= limit",
         "ok": compiles_in_window == 0}]
    return {"e2e": e2e, "counters": counters, "checks": checks,
            "attempted": done, "failed": 0,
            "memory_peak_bytes": memory_peak, "setup_s": feed.setup_s,
            "compiles_in_window": compiles_in_window}


def reference(ctx, batches, plans, precision="f32"):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from chipbench.reference import vit as ref
    t = ctx.config["trainer"]
    devs = jax.devices()[:ctx.cell["chips"]]
    mesh = Mesh(np.asarray(devs), ("x",))

    def shardings(shapes):
        # each leaf split on its last axis over the chips where it divides
        def one(a):
            n = len(devs)
            spec = P(*([None] * (len(a.shape) - 1) + ["x"])) \
                if a.shape and a.shape[-1] % n == 0 else P()
            return NamedSharding(mesh, spec)
        return {k: one(v) for k, v in shapes.items()}

    opt = {"lr": t["lr"], "total_steps": t["total_steps"],
           "warmup_steps": t["warmup_steps"], "grad_clip": t["grad_clip"],
           "beta1": t["beta1"], "beta2": t["beta2"], "eps": t["eps"],
           "weight_decay": t["weight_decay"]}
    return ref.run_steps(ctx.seed, ctx.config["model"], opt, batches,
                         plans, t["tp"], precision=precision,
                         shardings=shardings)


def gaps(prog, want):
    """(loss gap, first-gradient gap, change gap, leaves left out).

    Loss: the worst step's |program - reference| / |reference|. Norms:
    the worst leaf's |program norm - reference norm| over the larger of
    the reference's norm of that leaf and of the median leaf. Leaves
    whose reference gradient is under a thousandth of the median leaf's
    move by round-off alone under Adam, and are left out of the change."""
    (pl, pg, pc), (rl, rg, rc) = prog, want
    loss = max(abs(a - b) / abs(b) for a, b in zip(pl, rl))
    med_g = float(np.median(list(rg.values())))
    med_c = float(np.median(list(rc.values())))
    grad = max(abs(pg[k] - rg[k]) / max(rg[k], med_g) for k in rg)
    small = sorted(k for k in rg if rg[k] < 1e-3 * med_g)
    change = max(abs(pc[k] - rc[k]) / max(rc[k], med_c)
                 for k in rc if k not in small)
    return loss, grad, change, small


def _checks(cc, vals):
    return [{"name": name, "value": v, "limit": cc[name + "_limit"],
             "rule": "value <= limit", "ok": v <= cc[name + "_limit"]}
            for name, v in vals.items()]


def compare(ctx, batches, plans, losses, g1, change):
    """The checks. The loss gap is printed and not compared: on the chip
    neither the bfloat16 control nor a fault of the program separates it
    from sound runs (PERF.md gives the readings)."""
    cc = ctx.config["correctness"]
    want = reference(ctx, batches, plans)
    loss, grad, chg, small = gaps((losses, g1, change), want)
    if small:
        print(f"train: leaves left out of the change: {small}",
              file=sys.stderr)
    print(f"train: loss gap {loss} (not compared)", file=sys.stderr)
    out = _checks(cc, {"grad_norm_gap": grad, "change_norm_gap": chg})
    if ctx.control:
        low = reference(ctx, batches, plans, precision="bf16")
        c = gaps(low, want)
        print(f"train: control readings loss {c[0]} grad {c[1]} "
              f"change {c[2]}", file=sys.stderr)
        ctx.control_readings = {"loss_gap": c[0], "grad_norm_gap": c[1],
                                "change_norm_gap": c[2]}
        # the control in the program's place, through the same checks
        ctx.control_correct = all(k["ok"] for k in _checks(
            cc, {"grad_norm_gap": c[1], "change_norm_gap": c[2]}))
    ctx.loss_gap = loss
    return out
