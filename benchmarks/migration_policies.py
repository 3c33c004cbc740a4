"""Table I reproduction: broadcast-reduce vs scatter-gather migration.

Two artifacts:
1. MEASURED per-device HLO collective bytes + op counts of the two
   shard_map implementations (repro.core.migration) on an 8-rank mesh —
   broadcast-reduce's reduce-merging removes the result-return hop, so its
   collective volume is structurally lower.
2. MODELED epoch times at paper scale: t_comm(SG) ≈ 2·V/BW + (e−1)·t_su
   (serial sends + gather-back), t_comm(BR) ≈ V/BW + t_su (tree broadcast;
   reduce merged into the existing all-reduce). Reproduces the table's
   shape: BR < SG everywhere, gap narrowing as ν grows.
"""
from __future__ import annotations

import json

import numpy as np

from benchmarks.common import csv_row, run_subprocess_py, save_json

HLO_SNIPPET = """
import numpy as np, jax, jax.numpy as jnp, json
from jax.sharding import Mesh, PartitionSpec as P
from repro.core import migration
from repro.analysis.hlo import parse_collectives
e, T, d, H, block = 8, 64, 128, 512, 16
mesh = Mesh(np.array(jax.devices()).reshape(e), ("model",))
x = jnp.zeros((T, d), jnp.float32)
w1 = jnp.zeros((d, H), jnp.float32)
w2 = jnp.zeros((H, d), jnp.float32)
ids = jnp.arange(8, dtype=jnp.int32)   # migrate 8 of 32 local blocks
kw = dict(axis="model", mig_src=jnp.array(0, jnp.int32),
          mig_block_ids=ids, block=block, act_fn=jax.nn.silu)
out = {}
for name, fn in [("broadcast_reduce", migration.migrated_pair_matmul),
                 ("scatter_gather", migration.scatter_gather_pair_matmul)]:
    f = jax.shard_map(lambda x, a, b: fn(x, a, b, **kw), mesh=mesh,
        in_specs=(P(), P(None, "model"), P("model", None)),
        out_specs=P(), check_vma=False)
    txt = jax.jit(f).lower(x, w1, w2).compile().as_text()
    out[name] = parse_collectives(txt)
print("RESULT" + json.dumps(out))
"""

# paper testbed epoch structure: 373 s compute-only epoch (Table I, γ=0)
BASE_EPOCH_S = 373.0
PCIE_BW = 12e9          # effective PCIe 3.0 x16
T_SETUP = 0.8           # per-connection setup+serialization cost (s/epoch)


def modeled_epoch(policy: str, gamma: float, nu: int, e: int = 8,
                  vol_full: float = 80e9) -> float:
    """vol_full: bytes a fully-migrated (γ=1) straggler ships per epoch."""
    v = gamma * vol_full * nu
    helpers = e - nu
    if v == 0:
        return BASE_EPOCH_S
    if policy == "broadcast_reduce":
        comm = v / PCIE_BW + nu * T_SETUP * max(np.log2(max(helpers, 2)), 1)
    else:
        comm = 2 * v / PCIE_BW + nu * helpers * T_SETUP
    return BASE_EPOCH_S + comm


def main() -> list:
    rows = []
    out = run_subprocess_py(HLO_SNIPPET, devices=8, timeout=900)
    hlo = json.loads(out.split("RESULT")[1].strip())
    br, sg = hlo["broadcast_reduce"]["total"], hlo["scatter_gather"]["total"]
    rows.append(csv_row("tab1_hlo_coll_bytes_broadcast_reduce", 0.0,
                        f"bytes={br}"))
    rows.append(csv_row("tab1_hlo_coll_bytes_scatter_gather", 0.0,
                        f"bytes={sg}"))
    rows.append(csv_row("tab1_hlo_br_lt_sg", 0.0,
                        f"ratio={sg / max(br, 1):.2f},holds={br < sg}"))

    table = {}
    for nu in (1, 4):
        for g in (0.0, 0.25, 0.5, 0.75, 1.0):
            for pol in ("broadcast_reduce", "scatter_gather"):
                t = modeled_epoch(pol, g, nu)
                table[f"{pol}({nu})/{g}"] = t
        # the paper's observation: the gap narrows as nu grows
    g1 = (table["scatter_gather(1)/1.0"] - BASE_EPOCH_S) / \
         (table["broadcast_reduce(1)/1.0"] - BASE_EPOCH_S)
    g4 = (table["scatter_gather(4)/1.0"] - BASE_EPOCH_S) / \
         (table["broadcast_reduce(4)/1.0"] - BASE_EPOCH_S)
    for k in ("broadcast_reduce(1)/1.0", "scatter_gather(1)/1.0",
              "broadcast_reduce(4)/1.0", "scatter_gather(4)/1.0"):
        rows.append(csv_row(f"tab1_epoch_{k.replace('/', '_g')}",
                            table[k] * 1e6, f"epoch_s={table[k]:.0f}"))
    rows.append(csv_row("tab1_gap_narrows_with_nu", 0.0,
                        f"gap_nu1={g1:.2f},gap_nu4={g4:.2f},holds={g4 < g1}"))
    save_json("tab1_migration_policies", {"hlo": hlo, "epochs": table})
    return rows


if __name__ == "__main__":
    print("\n".join(main()))
