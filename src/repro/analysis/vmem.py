"""R4: static VMEM budgeting for every ``pallas_call`` in a jaxpr.

TPU cores hold ~16 MiB of VMEM (the Pallas pipeline stages every
BlockSpec tile of the inputs/outputs through it, double-buffered, plus
any explicit scratch). Mosaic reports an over-subscription only at
compile time, deep inside a real lowering, as an opaque OOM — this
module prices the tiles from the traced jaxpr instead, so a bad
``tm``/``tn``/``block`` choice in kernels/pruned_matmul.py or
kernels/decode_attn.py becomes a named pre-compile error.

Estimate per pallas_call::

    est = 2 × Σ block_bytes(inputs + outputs)   # double-buffered pipeline
        +     Σ scratch_bytes                   # resident, single copy

Scalar-prefetch operands live in SMEM and are excluded. The grid_mapping
introspection follows jax 0.9's pallas_call params; failures degrade to an
"unpriced" report rather than a crash — the rule only fires on kernels
it could actually price.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator, List, Optional, Tuple

import numpy as np

#: default per-core budget (bytes): TPU v5e-class VMEM
DEFAULT_VMEM_BUDGET = 16 * 2 ** 20


class VmemBudgetError(RuntimeError):
    """A pallas_call's static tile footprint exceeds the VMEM budget."""


@dataclasses.dataclass
class PallasCallReport:
    name: str
    grid: tuple
    block_bytes: int              # Σ over in/out block tiles (single copy)
    scratch_bytes: int
    est_bytes: Optional[int]      # 2*blocks + scratch; None = unpriced
    detail: List[str] = dataclasses.field(default_factory=list)
    note: str = ""

    def over_budget(self, budget: int) -> bool:
        return self.est_bytes is not None and self.est_bytes > budget


def _dtype_bytes(dtype) -> int:
    return int(np.dtype(dtype).itemsize)


def iter_eqns(jaxpr) -> Iterator[Any]:
    """Every eqn in a (Closed)Jaxpr, recursing into call/control-flow
    sub-jaxprs (pjit, scan, while, cond, custom_vjp, shard_map, ...)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)      # ClosedJaxpr -> Jaxpr
    for eqn in inner.eqns:
        yield eqn
        for key, val in eqn.params.items():
            if key == "branches":
                for b in val:
                    yield from iter_eqns(b)
            elif hasattr(val, "eqns") or hasattr(val, "jaxpr"):
                # pallas_call's own kernel jaxpr is priced separately;
                # still recurse so nested pallas_calls are found
                yield from iter_eqns(val)
            elif isinstance(val, (tuple, list)):
                for v in val:
                    if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                        yield from iter_eqns(v)


def _block_bytes(grid_mapping) -> Tuple[int, List[str]]:
    total = 0
    detail = []
    for i, bm in enumerate(grid_mapping.block_mappings):
        # entries are ints or pallas Blocked(block_size=...) markers;
        # squeezed dims count as 1
        dims = (getattr(d, "block_size", d) for d in bm.block_shape)
        shape = tuple(int(d) if isinstance(d, (int, np.integer)) else 1
                      for d in dims)
        sds = getattr(bm, "array_shape_dtype", None)
        nbytes = int(np.prod(shape or (1,))) * (
            _dtype_bytes(sds.dtype) if sds is not None else 4)
        total += nbytes
        detail.append(f"block[{i}] {shape} = {nbytes} B")
    return total, detail


def _scratch_bytes(eqn) -> Tuple[int, List[str]]:
    gm = eqn.params.get("grid_mapping")
    kernel = eqn.params.get("jaxpr")
    n = int(getattr(gm, "num_scratch_operands", 0) or 0)
    if not n or kernel is None:
        return 0, []
    inner = getattr(kernel, "jaxpr", kernel)
    total = 0
    detail = []
    for v in inner.invars[-n:]:
        aval = v.aval
        shape = tuple(getattr(aval, "shape", ()) or ())
        nbytes = int(np.prod(shape or (1,))) * _dtype_bytes(
            getattr(aval, "dtype", np.float32))
        total += nbytes
        detail.append(f"scratch {shape} = {nbytes} B")
    return total, detail


def pallas_reports(jaxpr) -> List[PallasCallReport]:
    """Price every pallas_call reachable from a (Closed)Jaxpr."""
    out = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        name = str(eqn.params.get("name_and_src_info",
                                  eqn.params.get("name", "pallas_call")))
        name = name.split(" ")[0]
        try:
            gm = eqn.params["grid_mapping"]
            blocks, bdetail = _block_bytes(gm)
            scratch, sdetail = _scratch_bytes(eqn)
            out.append(PallasCallReport(
                name=name, grid=tuple(gm.grid),
                block_bytes=blocks, scratch_bytes=scratch,
                est_bytes=2 * blocks + scratch,
                detail=bdetail + sdetail))
        except Exception as e:                        # noqa: BLE001
            out.append(PallasCallReport(
                name=name, grid=(), block_bytes=0, scratch_bytes=0,
                est_bytes=None, note=f"unpriced: {e!r}"))
    return out


def check_budget(jaxpr, budget: int = DEFAULT_VMEM_BUDGET) -> List[str]:
    """Violation messages for every over-budget pallas_call (R4)."""
    msgs = []
    for r in pallas_reports(jaxpr):
        if r.over_budget(budget):
            msgs.append(
                f"pallas_call '{r.name}' grid={r.grid} needs "
                f"~{r.est_bytes / 2**20:.1f} MiB VMEM "
                f"(2×{r.block_bytes} block + {r.scratch_bytes} scratch) "
                f"> budget {budget / 2**20:.1f} MiB; "
                f"tiles: {'; '.join(r.detail)}")
    return msgs


def assert_fits(fn, *args, budget: int = DEFAULT_VMEM_BUDGET) -> None:
    """Named pre-compile gate: trace ``fn(*args)`` abstractly and raise
    :class:`VmemBudgetError` if any pallas_call oversubscribes VMEM —
    use before handing a new tile configuration to Mosaic."""
    import jax
    msgs = check_budget(jax.make_jaxpr(fn)(*args), budget)
    if msgs:
        raise VmemBudgetError("; ".join(msgs))
