"""The train driver end to end on four CPU devices at a tiny size: the
SEMI path (migration and resizing on rank 0, pruned kernels in interpret
mode) checks out against the reference, and each fault a training cell
can have, planted in the program, makes the run not correct. Each case
runs in a child process, since the device count is fixed when JAX
starts."""
import json
import os
import subprocess
import sys

import pytest

from chipbench.harness import ROOT

CHILD = r"""
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax, jax.numpy as jnp
FAULT = {fault!r}
if FAULT == "state_unchanged":
    from repro.optim import adamw
    adamw.apply = lambda params, grads, state, cfg, total_steps=0: (
        params, state, {{"grad_norm": jnp.zeros(()), "lr": jnp.zeros(())}})
elif FAULT == "answer_altered":
    # the optimizer's answer, the new parameters: w_down moves double
    from repro.optim import adamw
    orig = adamw.apply
    def apply(params, grads, state, cfg, total_steps=0):
        new, st, m = orig(params, grads, state, cfg, total_steps)
        scan = new["stack"]["scan"][0]
        old = params["stack"]["scan"][0]["ffn"]["w_down"]
        scan["ffn"]["w_down"] = 2 * scan["ffn"]["w_down"] - old
        return new, st, m
    adamw.apply = apply
elif FAULT == "half_batch":
    from repro.models import vit
    orig = vit.loss_fn
    def loss_fn(p, cfg, batch, **kw):
        batch = {{k: v[:v.shape[0] // 2] for k, v in batch.items()}}
        return orig(p, cfg, batch, **kw)
    vit.loss_fn = loss_fn
elif FAULT == "no_exchange":
    from repro.layers import tp_linear
    tp_linear.chunked_psum = lambda y, axis, n_chunks: y
from chipbench.tests import tiny
from chipbench.drivers import train
ctx = tiny.train_context(seed=2 ** 31 + 21, seconds=1.0,
                         control=FAULT == "control")
out = train.run(ctx)
res = {{c["name"]: [c["value"], c["ok"]] for c in out["checks"]}}
if FAULT == "control":
    res = {{"control": [ctx.control_readings, ctx.control_correct]}}
print("RESULT " + json.dumps(res))
"""


def _child(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(root=ROOT, src=os.path.join(ROOT, "src"),
                        fault=fault)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    lines = [l for l in p.stdout.splitlines() if l.startswith("RESULT ")]
    assert p.returncode == 0 and lines, p.stderr[-3000:]
    return json.loads(lines[-1][len("RESULT "):])


def test_tiny_semi_training_matches_the_reference():
    checks = _child(None)
    assert all(ok for _, ok in checks.values()), checks
    for name in ("grad_norm_gap", "change_norm_gap"):
        assert checks[name][0] < 1e-5, checks
    assert checks["window_steps_migrating"][0] >= 1


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange", "answer_altered",
                                   "control"])
def test_fault_is_not_correct(fault):
    """``control``: the reference itself in bfloat16, in the program's
    place, through the cell's checks."""
    checks = _child(fault)
    failed = [n for n, (_, ok) in checks.items() if not ok]
    assert failed, checks


def test_seeded_params_are_the_programs_own_initialisation():
    import jax
    import numpy as np
    from repro.models import get_api
    from chipbench.drivers import train
    from chipbench.tests import tiny
    ctx = tiny.train_context(seed=2 ** 31 + 5)
    cfg = train.model_config(ctx.config)
    api = get_api(cfg)
    own = jax.jit(lambda: api.init(jax.random.PRNGKey(ctx.seed), cfg,
                                   np.float32)[0])()
    want = [np.asarray(a) for a in jax.tree.leaves(own)]
    like = jax.jit(lambda: api.init(jax.random.PRNGKey(0), cfg,
                                    np.float32)[0])()
    got = train.seeded_params(like, cfg, ctx.seed)
    leaves = jax.tree.leaves(got)
    assert len(leaves) == len(want) > 0
    for a, b in zip(leaves, want):
        assert np.array_equal(np.asarray(a), b)
    assert all(a.is_deleted() for a in jax.tree.leaves(like))
