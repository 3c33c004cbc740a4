"""XLA latency-hiding flag sweep (ISSUE 7): the compiler-side baseline
for hiding the decode-step TP all-reduce, next to the chunked-psum
epilogue (the kernel-side measure) — so the bench reports
kernel-vs-flags-vs-both instead of conflating the two.

Each leg is a fresh subprocess (XLA flags only apply before jax
initializes): ``repro.launch._bootstrap.apply_xla_preset`` — the exact
production path the serve CLI uses — is called pre-jax, then a TP
matmul + epilogue all-reduce step runs under shard_map on host devices,
with the all-reduce either one fat ``lax.psum`` (chunks=1) or the
``repro.layers.tp_linear.chunked_psum`` split the serve engine uses.

    baseline  preset=none            chunks=1
    flags     preset=latency-hiding  chunks=1
    chunked   preset=none            chunks=4
    both      preset=latency-hiding  chunks=4

Report-only (no gate): on CPU the latency-hiding scheduler is largely
inert — the value of this sweep is the committed MECHANISM (flags are
plumbed, both axes measurable) and the TPU numbers when run there.
A leg whose subprocess fails degrades to {"supported": false} so the
smoke job stays green on backends without these flags.
"""
from __future__ import annotations

import json

from benchmarks.common import csv_row, is_dry_run, run_subprocess_py, \
    save_bench_json

_CHILD = """
import json, time
from repro.launch._bootstrap import apply_xla_preset
applied = apply_xla_preset({preset!r})           # pre-jax, production path
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.layers.tp_linear import chunked_psum

devs = jax.devices()
mesh = jax.sharding.Mesh(np.array(devs), ("x",))
M, K, N = {M}, {K}, {N}
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
w = jnp.asarray(rng.standard_normal((K, N)) * 0.02, jnp.float32)

def step(x_, w_):
    # local partial matmul + epilogue all-reduce: the decode-step TP
    # pattern whose exposure the chunked psum / scheduler flags target
    y = x_ @ w_
    y = y + jax.nn.silu(y)                      # compute to overlap with
    return chunked_psum(y, "x", {chunks})

f = jax.jit(jax.shard_map(step, mesh=mesh,
                      in_specs=(P(None, "x"), P("x", None)),
                      out_specs=P(), check_vma=False))
r = f(x, w); r.block_until_ready()
ts = []
for _ in range({iters}):
    t0 = time.perf_counter()
    r = f(x, w); r.block_until_ready()
    ts.append(time.perf_counter() - t0)
print(json.dumps({{"step_us": min(ts) * 1e6, "flags_applied": applied}}))
"""

LEGS = [
    ("baseline", "none", 1),
    ("flags", "latency-hiding", 1),
    ("chunked", "none", 4),
    ("both", "latency-hiding", 4),
]


def main() -> list:
    dry = is_dry_run()
    devices = 2 if dry else 4
    M, K, N = (64, 256, 256) if dry else (256, 2048, 2048)
    iters = 5 if dry else 20

    rows, legs = [], {}
    for name, preset, chunks in LEGS:
        code = _CHILD.format(preset=preset, chunks=chunks, M=M, K=K, N=N,
                             iters=iters)
        try:
            out = run_subprocess_py(code, devices=devices, timeout=600,
                                    with_bench_path=False)
            rep = json.loads(out.strip().splitlines()[-1])
            legs[name] = {"supported": True, "preset": preset,
                          "psum_chunks": chunks,
                          "step_us": rep["step_us"],
                          "flags_applied": rep["flags_applied"]}
        except Exception as e:                                # noqa: BLE001
            legs[name] = {"supported": False, "preset": preset,
                          "psum_chunks": chunks, "error": repr(e)[:200]}
        d = legs[name]
        rows.append(csv_row(f"xla_flags_{name}",
                            d.get("step_us", 0.0),
                            f"preset={preset},chunks={chunks},"
                            f"supported={d['supported']}"))

    base = legs.get("baseline", {})
    speedups = {}
    if base.get("supported"):
        for name in ("flags", "chunked", "both"):
            if legs.get(name, {}).get("supported"):
                speedups[name] = base["step_us"] / legs[name]["step_us"]
    metrics = {"legs": legs, "speedup_vs_baseline": speedups}
    config = {"devices": devices, "M": M, "K": K, "N": N, "iters": iters,
              "dry_run": dry}
    save_bench_json("xla_flags", config, metrics)
    return rows


if __name__ == "__main__":
    import argparse
    import os
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny shapes, 2 devices (CI smoke)")
    if ap.parse_args().dry_run:
        os.environ["REPRO_BENCH_DRY"] = "1"
    print("\n".join(main()))
