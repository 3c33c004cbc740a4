#!/usr/bin/env python3
"""Record a small profiler trace on the chip, as a fixture for the trace
reduction's tests.

    python3 chipbench/tools/record_trace.py OUT_DIR

Runs, under ``jax.profiler``, a host span around a few calls of one
jitted program that holds a bf16 matmul and the repo's fused paged
decode-attention kernel (a Mosaic custom call), with idle host time
between the calls; on a multi-chip host the program also all-reduces
over the chips. Writes ``OUT_DIR/trace.xplane.pb`` and prints a summary
of its planes and lines. Needs a TPU.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(out_dir):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.kernels import ops

    devs = jax.devices()
    if devs[0].platform != "tpu" or ops.interpret_mode():
        raise SystemExit("record_trace: needs a TPU with compiled kernels")
    n = len(devs)
    mesh = Mesh(np.asarray(devs), ("x",))
    B, H, KV, D, ps, pps = 4, 32, 4, 128, 16, 8
    num_pages = B * pps
    rng = np.random.default_rng(0)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    q = bf(rng.standard_normal((B, H, 1, D)))
    kp = bf(rng.standard_normal((num_pages, KV, ps, D)))
    vp = bf(rng.standard_normal((num_pages, KV, ps, D)))
    pages = jnp.asarray(np.arange(num_pages, dtype=np.int32).reshape(B, pps))
    cur = jnp.asarray(np.array([100, 64, 17, 3], np.int32))
    a = jax.device_put(bf(rng.standard_normal((n * 1024, 2048))),
                       NamedSharding(mesh, P("x", None)))
    w = bf(rng.standard_normal((2048, 2048)))

    def body(a_, w_, q_, k_, v_, pg, cp):
        y = a_ @ w_
        if n > 1:
            y = jax.lax.psum(y, "x")
        o = ops.fused_paged_decode_attention(q_, k_, v_, pages=pg,
                                             cur_pos=cp)
        return y.sum() + o.astype(jnp.float32).sum()

    step = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P("x", None), P(), P(), P(), P(), P(), P()),
        out_specs=P(), check_vma=False))
    args = (a, w, q, kp, vp, pages, cur)
    step(*args).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                step(*args).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.host_wait"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    os.makedirs(out_dir, exist_ok=True)
    dst = os.path.join(out_dir, "trace.xplane.pb")
    shutil.copy(path, dst)
    shutil.rmtree(tmp, ignore_errors=True)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(dst)
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs), "events")
            for e in evs[:6]:
                print("    ", repr(e.name), e.start_ns, e.duration_ns,
                      dict(e.stats))
    print("wrote", dst, os.path.getsize(dst), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
