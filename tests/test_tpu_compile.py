"""The Pallas kernels compile for a TPU v5e, at the widths the chip runs.

Interpret mode (the CPU default) lowers every kernel to ordinary HLO, so
a tiling or partitioning rule only Mosaic enforces never fires in the
rest of the suite. These tests compile for a *described* v5e:2x2 — no
chip needed, only the installed TPU compiler — with interpret mode off,
and check that each kernel is in the executable as a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers
import every test file.
"""
import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.kernels import ops

SDS = jax.ShapeDtypeStruct
BF16 = jnp.bfloat16

# yi-6b decode: 4 slots, 32 query / 4 kv heads of 128, a 128-token cache
B, HQ, HKV, D, S, PAGE = 4, 32, 4, 128, 128, 16

# (K, N) contraction x output widths of the pruned down projection:
# yi-6b at TP=1 and vit-1b at TP=4 (its per-rank d_ff is 8192/4)
PRUNED_WIDTHS = {"yi-6b": (11008, 4096), "vit-1b-tp4": (2048, 2048)}
# (d_model, per-rank d_ff, gated) of the controlled FFN pair
FFN_WIDTHS = {"yi-6b": (4096, 11008, True), "vit-1b-tp4": (2048, 2048, False)}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Kernels lower through Mosaic, as on the chip."""
    monkeypatch.setattr(ops, "INTERPRET", False)


def _compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_fused_decode_attention_compiles(compiled_kernels, one_chip):
    sd = lambda shp, dt=BF16: SDS(shp, dt, sharding=one_chip)
    txt = _compile_text(
        lambda q, k, v, p: ops.fused_decode_attention(q, k, v, cur_pos=p),
        sd((B, HQ, 1, D)), sd((B, HKV, S, D)), sd((B, HKV, S, D)),
        sd((B,), jnp.int32))
    assert "tpu_custom_call" in txt


def test_fused_paged_decode_attention_compiles(compiled_kernels, one_chip):
    sd = lambda shp, dt=BF16: SDS(shp, dt, sharding=one_chip)
    pps = S // PAGE
    pool = sd((B * pps, HKV, PAGE, D))
    txt = _compile_text(
        lambda q, k, v, pg, p: ops.fused_paged_decode_attention(
            q, k, v, pages=pg, cur_pos=p),
        sd((B, HQ, 1, D)), pool, pool, sd((B, pps), jnp.int32),
        sd((B,), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("widths", sorted(PRUNED_WIDTHS))
def test_block_pruned_matmul_fwd_grad_compiles(compiled_kernels, one_chip,
                                               widths):
    K, N = PRUNED_WIDTHS[widths]
    sd = lambda shp, dt=BF16: SDS(shp, dt, sharding=one_chip)

    def loss(x, w, keep):
        return jnp.sum(ops.block_pruned_matmul(x, w, keep, 128)
                       .astype(jnp.float32))

    txt = _compile_text(jax.grad(loss, argnums=(0, 1)), sd((256, K)),
                        sd((K, N)), sd((K // 128 // 2,), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("widths", sorted(FFN_WIDTHS))
def test_fused_pruned_ffn_fwd_grad_compiles(compiled_kernels, one_chip,
                                            widths):
    d, h, gated = FFN_WIDTHS[widths]
    sd = lambda shp, dt=BF16: SDS(shp, dt, sharding=one_chip)
    act = jax.nn.silu if gated else jax.nn.gelu

    def loss(x, wu, wd, wg, keep):
        return jnp.sum(ops.fused_pruned_ffn(x, wu, wd, keep, wg, act, 128)
                       .astype(jnp.float32))

    wg = sd((d, h)) if gated else None
    txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), sd((256, d)),
                        sd((d, h)), sd((h, d)), wg,
                        sd((h // 128 // 2,), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("paged", [False, True], ids=["unpaged", "paged"])
def test_tp4_decode_attention_call_compiles(compiled_kernels, topo, paged):
    """GSPMD refuses to partition a Mosaic kernel, so the model's decode
    attention must run it per shard: yi-6b's kv heads split over a
    4-chip model axis."""
    from repro.config import ShapeConfig
    from repro.launch.specs import rules_for
    from repro.layers import blocks
    from repro.sharding import use_mesh, use_rules
    cfg = dataclasses.replace(get_config("yi-6b"), num_layers=1,
                              fused_decode_attn=True)
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    ns = lambda *spec: NamedSharding(mesh, P(*spec))
    d, hd = cfg.d_model, cfg.resolved_head_dim
    params = {
        "wq": SDS((d, HQ * hd), BF16, sharding=ns(None, "model")),
        "wk": SDS((d, HKV * hd), BF16, sharding=ns(None, "model")),
        "wv": SDS((d, HKV * hd), BF16, sharding=ns(None, "model")),
        "wo": SDS((HQ * hd, d), BF16, sharding=ns("model", None)),
    }
    pps = S // PAGE
    if paged:
        pool = SDS((B * pps, HKV, PAGE, hd), BF16,
                   sharding=ns(None, "model"))
        pages = SDS((B, pps), jnp.int32, sharding=ns())
    else:
        pool = SDS((B, HKV, S, hd), BF16, sharding=ns(None, "model"))
        pages = None
    x = SDS((B, 1, d), BF16, sharding=ns())
    pos = SDS((B,), jnp.int32, sharding=ns())

    def step(p, x, k, v, pos, pages):
        y, _ = blocks.apply_attention(
            p, x, cfg, ctx=None, positions=pos[:, None],
            cache={"k": k, "v": v}, cur_pos=pos, pages=pages)
        return y

    rules = rules_for(ShapeConfig("decode", S, B, "decode"), mesh, cfg)
    with use_mesh(mesh), use_rules(rules):
        txt = _compile_text(step, params, x, pool, pool, pos, pages)
    assert "tpu_custom_call" in txt


def test_unaligned_block_refused_before_lowering(compiled_kernels,
                                                 one_chip):
    """Block 8 tiles cannot lower through Mosaic: the wrapper says so
    instead of failing deep in the compiler or falling back to XLA."""
    sd = lambda shp: SDS(shp, BF16, sharding=one_chip)
    with pytest.raises(ValueError, match="multiple of the 128-lane"):
        jax.jit(lambda x, w, k: ops.block_pruned_matmul(x, w, k, 8)).lower(
            sd((16, 64)), sd((64, 128)), SDS((4,), jnp.int32,
                                             sharding=one_chip))
