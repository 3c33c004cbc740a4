"""Multi-device semantics tests, run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main pytest
process keeps 1 device per the brief)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 420) -> str:
    # CPU virtual-device meshes by design: the child never competes for
    # an accelerator the parent may hold
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


PREAMBLE = """
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.layers.tp_linear import ControlContext, controlled_ffn, controlled_proj
from repro.core.workload import PlanStatic
e, B, S, d, H, block = 8, 2, 8, 64, 256, 8
nb_loc = (H // e) // block
mesh = Mesh(np.array(jax.devices()).reshape(1, e), ("data", "model"))
rng = np.random.default_rng(0)
x = jnp.array(rng.standard_normal((B, S, d)), jnp.float32)
wg = jnp.array(rng.standard_normal((d, H))*.1, jnp.float32)
wu = jnp.array(rng.standard_normal((d, H))*.1, jnp.float32)
wd = jnp.array(rng.standard_normal((H, d))*.1, jnp.float32)
act = jax.nn.silu
ref = (act(x @ wg) * (x @ wu)) @ wd
buckets = (0.0, 0.25, 0.5)
def make_ctx(m, bucket_vec, src):
    st = PlanStatic(buckets=buckets, block_size=block, mig_blocks=m, tp_size=e)
    pri = jnp.tile(jnp.arange(nb_loc, dtype=jnp.int32)[None], (e, 1))
    return ControlContext(mesh=mesh, axis="model", static=st,
        bucket_by_rank=jnp.array(bucket_vec, jnp.int32),
        mig_src=jnp.array(src, jnp.int32), pri={"ffn": pri})
"""


class TestControlledFFN:
    def test_neutral_equals_dense(self):
        run_py(PREAMBLE + """
ctx = make_ctx(0, [0]*e, -1)
y = controlled_ffn(x, wu, wd, ctx, "ffn", act, w_gate=wg)
assert np.allclose(y, ref, atol=1e-4), np.abs(np.array(y)-ref).max()
print("ok")
""")

    def test_migration_is_lossless_fwd_and_bwd(self):
        """The paper's claim: migration re-balances with NO accuracy loss.
        Forward outputs and all weight gradients must equal the dense run."""
        run_py(PREAMBLE + """
ctx = make_ctx(2, [0]*e, 5)
y = controlled_ffn(x, wu, wd, ctx, "ffn", act, w_gate=wg)
assert np.allclose(y, ref, atol=1e-4)
def loss(wu, wd, wg):
    return jnp.sum(controlled_ffn(x, wu, wd, ctx, "ffn", act, w_gate=wg)**2)
g = jax.grad(loss, (0, 1, 2))(wu, wd, wg)
gr = jax.grad(lambda wu, wd, wg: jnp.sum((((act(x@wg))*(x@wu))@wd)**2), (0,1,2))(wu, wd, wg)
for a, b in zip(g, gr):
    assert np.allclose(a, b, atol=1e-3), np.abs(np.array(a)-np.array(b)).max()
print("ok")
""")

    def test_resizing_matches_masked_oracle(self):
        run_py(PREAMBLE + """
ctx = make_ctx(0, [0,0,0,2,0,0,0,0], -1)
y = controlled_ffn(x, wu, wd, ctx, "ffn", act, w_gate=wg)
mask = np.ones(H//block, bool); mask[3*nb_loc+2:3*nb_loc+4] = False
ref_p = ((act(x @ wg) * (x @ wu)) * np.repeat(mask, block)) @ wd
assert np.allclose(y, ref_p, atol=1e-4)
print("ok")
""")

    def test_semi_resize_plus_migrate(self):
        """SEMI on one straggler: migrated blocks stay exact (computed by
        helpers), pruned blocks are dropped — matches the masked oracle."""
        run_py(PREAMBLE + """
ctx = make_ctx(1, [0,0,0,1,0,0,0,0], 3)
y = controlled_ffn(x, wu, wd, ctx, "ffn", act, w_gate=wg)
mask = np.ones(H//block, bool); mask[3*nb_loc+3] = False
ref_sm = ((act(x @ wg) * (x @ wu)) * np.repeat(mask, block)) @ wd
assert np.allclose(y, ref_sm, atol=1e-4)
print("ok")
""")

    def test_kernel_path_matches_xla_inside_shard_map(self):
        """ISSUE 2: with use_kernel the controlled FFN runs the fused
        pruned-FFN pallas_call (+ kernel-level backward) inside shard_map;
        outputs and gradients must match the XLA gather path and the
        masked oracle — resizing AND migration active together."""
        run_py(PREAMBLE + """
import dataclasses
ctx_x = make_ctx(1, [0,0,0,1,0,0,0,0], 3)
ctx_k = dataclasses.replace(ctx_x, use_kernel=True)
y_x = controlled_ffn(x, wu, wd, ctx_x, "ffn", act, w_gate=wg)
y_k = controlled_ffn(x, wu, wd, ctx_k, "ffn", act, w_gate=wg)
assert np.allclose(y_k, y_x, atol=1e-4), np.abs(np.array(y_k)-np.array(y_x)).max()
mask = np.ones(H//block, bool); mask[3*nb_loc+3] = False
ref_sm = ((act(x @ wg) * (x @ wu)) * np.repeat(mask, block)) @ wd
assert np.allclose(y_k, ref_sm, atol=1e-4)
def loss(ctx, wu_, wd_, wg_):
    return jnp.sum(controlled_ffn(x, wu_, wd_, ctx, "ffn", act, w_gate=wg_)**2)
gk = jax.grad(lambda *a: loss(ctx_k, *a), (0, 1, 2))(wu, wd, wg)
gx = jax.grad(lambda *a: loss(ctx_x, *a), (0, 1, 2))(wu, wd, wg)
for a, b in zip(gk, gx):
    assert np.allclose(a, b, atol=1e-3), np.abs(np.array(a)-np.array(b)).max()
print("ok")
""")

    def test_runtime_straggler_retarget_no_recompile(self):
        """Changing mig_src / buckets must hit the jit cache (plan arrays
        are runtime inputs — the controller retargets for free)."""
        run_py(PREAMBLE + """
ctx = make_ctx(1, [0]*e, 0)
f = jax.jit(lambda bucket, src: controlled_ffn(
    x, wu, wd, ControlContext(mesh=mesh, axis="model", static=ctx.static,
        bucket_by_rank=bucket, mig_src=src, pri=ctx.pri),
    "ffn", act, w_gate=wg))
b0 = jnp.zeros((e,), jnp.int32)
y1 = f(b0, jnp.array(2, jnp.int32))
y2 = f(b0, jnp.array(6, jnp.int32))
y3 = f(b0.at[1].set(2), jnp.array(-1, jnp.int32))
assert f._cache_size() == 1, f._cache_size()
assert np.allclose(y1, ref, atol=1e-4) and np.allclose(y2, ref, atol=1e-4)
print("ok")
""")


class TestMigrationPrimitives:
    def test_broadcast_reduce_and_scatter_gather_agree(self):
        """Table I setup: both comm policies compute identical results."""
        run_py("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import migration
e, T, d, H, block = 8, 16, 32, 128, 4
mesh = Mesh(np.array(jax.devices()).reshape(e), ("model",))
rng = np.random.default_rng(0)
x = jnp.array(rng.standard_normal((T, d)), jnp.float32)
w1 = jnp.array(rng.standard_normal((d, H))*.1, jnp.float32)
w2 = jnp.array(rng.standard_normal((H, d))*.1, jnp.float32)
act = jax.nn.silu
ids = jnp.array([0, 2, 3], jnp.int32)
kw = dict(axis="model", mig_src=jnp.array(4, jnp.int32),
          mig_block_ids=ids, block=block, act_fn=act)
f1 = jax.shard_map(lambda x,a,b: migration.migrated_pair_matmul(x,a,b,**kw),
    mesh=mesh, in_specs=(P(), P(None,"model"), P("model",None)),
    out_specs=P(), check_vma=False)
f2 = jax.shard_map(lambda x,a,b: migration.scatter_gather_pair_matmul(x,a,b,**kw),
    mesh=mesh, in_specs=(P(), P(None,"model"), P("model",None)),
    out_specs=P(), check_vma=False)
y1, y2 = f1(x, w1, w2), f2(x, w1, w2)
ref = act(x @ w1) @ w2
assert np.allclose(y1, ref, atol=1e-3)
assert np.allclose(y2, ref, atol=1e-3)
print("ok")
""")


class TestShardedModel:
    def test_tp_model_matches_single_device(self):
        """Same params, same batch: the (data=2, model=4) sharded train step
        must produce the same loss as the unsharded model."""
        run_py("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.config import get_config, smoke_variant, ShapeConfig, TrainConfig
from repro.launch import steps
from repro.models import get_api
from repro.sharding import use_mesh
cfg = smoke_variant(get_config("yi-6b"))
api = get_api(cfg)
params, _ = api.init(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32),
         "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)), jnp.int32)}
loss_1dev, _ = api.loss_fn(params, cfg, batch)

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
from repro.optim import adamw
with use_mesh(mesh):
    fn, args, in_sh, out_sh = steps.build_train_step(
        cfg, ShapeConfig("s", 32, 8, "train"), mesh, TrainConfig())
    step = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
    p = jax.device_put(params, in_sh[0])
    opt = jax.device_put(adamw.init(params), in_sh[1])
    b = jax.device_put(batch, in_sh[2])
    _, _, metrics = step(p, opt, b)
assert np.allclose(float(metrics["loss"]), float(loss_1dev), atol=1e-3), \
    (float(metrics["loss"]), float(loss_1dev))
print("ok")
""")
