"""Runs one cell of ``BENCHMARK.json`` once and prints its result line.

The cell names a configuration and a traffic mix; the configuration's
``driver`` picks ``chipbench/drivers/<driver>.py``. Set-up, the measured
window and the correctness check are the driver's; this module does what
every cell shares: the device check, the compile cache, the profiler,
the per-layer readers and the result line.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "chipbench")
#: JAX's persistent compilation cache: a fixed path inside the checkout
#: (the path is part of the cache's key). Listed in .gitignore.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_bench(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def resolve_cell(bench: dict, name: str, root: str = ROOT):
    """The cell's entry, its configuration file and its traffic file,
    found by the names in ``BENCHMARK.json``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = load_json(os.path.join(root, confs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "chipbench", "traffic",
                                     cell["traffic"] + ".json"))
    return cell, conf, traffic


def metrics_for(bench: dict, cell_name: str):
    """(end-to-end metrics, per-layer metrics) that this cell reports."""
    def applies(m):
        return "workloads" not in m or cell_name in m["workloads"]
    e2e = [m for m in bench["end_to_end"] if applies(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if applies(m) and m["moves"] in names]
    return e2e, layer


def load_reader(name: str, root: str = ROOT):
    """The per-layer reader module ``chipbench/metrics/<name>.py``."""
    path = os.path.join(root, "chipbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Tracer:
    """Host spans and the profiler, on only in a ``--trace 1`` run.

    ``span(name)`` is a ``jax.profiler.TraceAnnotation`` when tracing and
    does nothing otherwise. ``start``/``stop`` bracket the traced part of
    the window; ``reduce`` (after the run) reduces the trace
    (``chipbench.trace``) and deletes the files."""

    def __init__(self, on: bool, chips: int):
        self.on = on
        self.chips = chips
        self.dir = None
        self._win = None

    def span(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def start(self):
        if not self.on:
            return
        import jax
        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        jax.profiler.start_trace(self.dir)
        self._win = self.span("bench.window")
        self._win.__enter__()

    def stop(self):
        if not self.on or self._win is None:
            return
        import jax
        self._win.__exit__(None, None, None)
        self._win = None
        jax.profiler.stop_trace()

    def reduce(self):
        if self.dir is None:
            return None
        from chipbench import trace as trace_lib
        try:
            return trace_lib.reduce_dir(self.dir, chips=self.chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir = None


@dataclasses.dataclass
class Context:
    """What a driver is handed."""
    root: str
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    tracer: Tracer
    compiles: object          # chipbench.compilelog.CompileLog
    t0: float                 # process start, host clock
    device: dict
    peaks: dict
    control: bool = False     # also read the lower-precision control


def device_check(chips: int) -> dict:
    """The chip, or SystemExit naming why not."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chipbench: JAX found no TPU (platform "
                         f"{d.platform!r}, {len(devs)} device(s))")
    from repro.kernels import ops
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    if env in ops._TRUTHY or ops.interpret_mode():
        raise SystemExit("chipbench: Pallas kernels would run in "
                         "interpret mode; the kernels must compile")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


@contextlib.contextmanager
def patched(module, **attrs):
    """Set ``module``'s attributes for the duration of the block."""
    old = {k: getattr(module, k) for k in attrs}
    for k, v in attrs.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)


def seeded_init(cfg, dtype, seed: int, like, shardings=None):
    """The program's own initialisation, ``api.init(PRNGKey(seed), cfg,
    dtype)``, compiled once with the key as its argument: both entry
    points compile it with the seed as a constant, once for every new
    seed (~25-30 s at the cells' sizes). ``like`` holds the shapes the
    program makes; ``shardings``, where given, places the result."""
    import jax
    from repro.models import get_api
    api = get_api(cfg)
    params = jax.jit(lambda key: api.init(key, cfg, dtype)[0],
                     out_shardings=shardings)(jax.random.PRNGKey(seed))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if got != jax.tree.map(lambda a: (a.shape, a.dtype), like):
        raise ValueError("seeded weights differ in shape from the "
                         "program's own initialisation")
    return params


def enable_compile_cache(cache_dir: str = CACHE_DIR) -> None:
    """Before jax is imported: every program goes to the checkout's cache,
    whatever the environment says, and small programs are cached too."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def format_checks(checks) -> str:
    return "\n".join(f"check {c['name']}: {c['value']!r} limit "
                     f"{c['limit']!r} ({c['rule']}) -> "
                     f"{'ok' if c['ok'] else 'FAIL'}" for c in checks)


def build_line(bench, cell, out, trace_on: bool, device: dict) -> dict:
    """The result line: the cell's end-to-end metrics (``--trace 0``) or
    its per-layer metrics (``--trace 1``), then the checks, last."""
    e2e, layer = metrics_for(bench, cell["name"])
    metrics = {}
    if not trace_on:
        for m in e2e:
            v = out["e2e"].get(m["name"])
            if v is None:
                raise RuntimeError(f"driver reported no {m['name']}")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in layer:
            v = load_reader(m["name"]).read(out)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = dict(device)
    dev["memory_peak_bytes"] = out["memory_peak_bytes"]
    line = {"correct": bool(out["checks"]) and all(c["ok"]
                                                   for c in out["checks"]),
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": metrics, "device": dev}
    if trace_on:
        ts = out.get("trace") or {}
        dev["busy_s"] = ts.get("busy_s", 0.0)
        dev["window_s"] = ts.get("window_s", 0.0)
        if ts:
            line["breakdown"] = {"device_ops": ts["top_ops"],
                                 "idle_gaps": ts["top_gaps"]}
    line["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                      for c in out["checks"]}
    return line


def main(argv=None, t0: Optional[float] = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_bench()
    cell, conf, traffic = resolve_cell(bench, args.workload)

    enable_compile_cache()
    device = device_check(cell["chips"])
    from chipbench.compilelog import CompileLog
    from chipbench.peaks import peaks_for
    compiles = CompileLog()
    ctx = Context(root=ROOT, cell=cell, config=conf, traffic=traffic,
                  seed=args.seed, seconds=args.seconds,
                  tracer=Tracer(bool(args.trace), cell["chips"]),
                  compiles=compiles, t0=t0, device=device,
                  peaks=peaks_for(device["kind"]))
    driver = importlib.import_module("chipbench.drivers." + conf["driver"])
    out = driver.run(ctx)
    ctx.tracer.stop()
    out["trace"] = ctx.tracer.reduce()
    out["peaks"] = ctx.peaks
    out["config"] = conf
    out["chips"] = cell["chips"]
    out["e2e"]["setup_s"] = out["setup_s"]
    line = build_line(bench, cell, out, bool(args.trace), device)
    print(f"compile: {compiles.seconds:.3f} s over {compiles.events} "
          f"events; cache {compiles.cache_hits} hits "
          f"{compiles.cache_misses} misses; in window "
          f"{out['compiles_in_window']}", file=sys.stderr)
    print(format_checks(out["checks"]), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
