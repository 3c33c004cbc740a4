"""Sharding utilities: logical axis rules, spec builders, constraint helpers.

We follow the MaxText-style pattern: parameters and activations carry
*logical* axis names; a rule table maps logical names to mesh axes. GSPMD
handles non-divisible dimensions by padding (e.g. 28 attention heads on a
16-way model axis), which keeps every assigned architecture lowerable on
the production mesh without per-arch special cases.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Logical axis -> mesh axes. "data"-like axes shard the batch; "model"
# shards the tensor-parallel dimension (the paper's `e` parallel tasks).
# The pod axis extends data parallelism across pods.
DEFAULT_RULES: Tuple[Tuple[str, Optional[Tuple[str, ...]]], ...] = (
    ("batch", ("pod", "data")),
    ("seq", None),                    # activations: sequence replicated by default
    ("decode_seq", ("data",)),        # KV caches at decode: shard sequence over data
    ("embed", None),                  # d_model replicated
    ("vocab", ("model",)),
    ("heads", ("model",)),
    ("kv_heads", ("model",)),
    ("head_dim", None),
    ("mlp", ("model",)),              # FFN hidden dim (column-split)
    ("expert", ("model",)),           # expert-parallel
    ("expert_mlp", None),             # per-expert hidden dim
    ("lru", ("model",)),              # RG-LRU / SSM inner width
    ("kv_lora", None),
    ("classes", None),
    ("layers", None),       # stacked-layer dim; ("data",) under FSDP
)


def make_rules(**overrides):
    rules = dict(DEFAULT_RULES)
    rules.update(overrides)
    return tuple(rules.items())


def logical_to_spec(axes: Sequence[Optional[str]], rules=None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec."""
    rules = dict(rules or DEFAULT_RULES)
    parts = []
    for ax in axes:
        if ax is None:
            parts.append(None)
            continue
        mesh_axes = rules.get(ax)
        if mesh_axes is None:
            parts.append(None)
        elif len(mesh_axes) == 1:
            parts.append(mesh_axes[0])
        else:
            parts.append(tuple(mesh_axes))
    return P(*parts)


def shard(x, axes: Sequence[Optional[str]], mesh: Optional[Mesh] = None, rules=None):
    """Apply a logical-axes sharding constraint inside jit.

    No-op when no mesh is active (single-device smoke tests / unit tests).
    Rules resolve as: explicit arg > ambient use_rules() > DEFAULT_RULES.
    """
    mesh = mesh or current_mesh()
    if mesh is None:
        return x
    rules = rules or current_rules()
    spec = filter_spec_for_mesh(logical_to_spec(axes, rules), mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


_CURRENT_MESH = [None]
_CURRENT_RULES = [None]


def current_mesh() -> Optional[Mesh]:
    return _CURRENT_MESH[0]


def current_rules():
    return _CURRENT_RULES[0]


class use_rules:
    """Scope logical-axis rule overrides (active during jit tracing)."""

    def __init__(self, rules):
        self.rules = rules
        self._prev = None

    def __enter__(self):
        self._prev = _CURRENT_RULES[0]
        _CURRENT_RULES[0] = self.rules
        return self.rules

    def __exit__(self, *exc):
        _CURRENT_RULES[0] = self._prev
        return False


class use_mesh:
    """Context manager recording the active mesh for `shard` helpers."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self._ctx = None

    def __enter__(self):
        _CURRENT_MESH[0] = self.mesh
        self._ctx = self.mesh
        self.mesh.__enter__()
        return self.mesh

    def __exit__(self, *exc):
        self.mesh.__exit__(*exc)
        _CURRENT_MESH[0] = None
        return False


def named_sharding(mesh: Mesh, *axes: Optional[str], rules=None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(axes, rules))


def filter_spec_for_mesh(spec: P, mesh: Mesh) -> P:
    """Drop mesh-axis references that don't exist in `mesh` (e.g. 'pod' on
    the single-pod mesh)."""
    names = set(mesh.axis_names)

    def _f(entry):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            kept = tuple(e for e in entry if e in names)
            return kept if kept else None
        return entry if entry in names else None

    return P(*[_f(e) for e in spec])


def fit_spec_to_shape(spec: P, shape, mesh: Mesh) -> P:
    """Drop mesh axes from dims they don't divide (jit argument shardings
    require exact divisibility, unlike internal constraints)."""
    sizes = dict(mesh.shape)

    def _f(entry, dim):
        if entry is None:
            return None
        axes = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in axes:
            n *= sizes.get(a, 1)
        if n == 0 or dim % n != 0:
            # try progressively shorter prefixes
            for k in range(len(axes) - 1, 0, -1):
                n = 1
                for a in axes[:k]:
                    n *= sizes.get(a, 1)
                if dim % n == 0:
                    return axes[:k] if k > 1 else axes[0]
            return None
        return entry

    entries = list(spec) + [None] * (len(shape) - len(spec))
    return P(*[_f(e, d) for e, d in zip(entries, shape)])


def ragged_local_width(padded_width: int, mesh: Mesh,
                       axis: str = "model") -> int:
    """Per-rank lane count of the padded ragged-FFN layout.

    A ragged shard geometry (core/geometry.py) is realized as zero-padded
    EQUAL GSPMD shards on the "mlp" logical axis — rank r's slice holds
    its geometry[r] real blocks first and inert zero blocks after, so no
    sharding rule changes. This validates that the padded width actually
    equal-splits over the mesh's TP axis and returns the local width."""
    n = int(dict(mesh.shape).get(axis, 1))
    if padded_width % n:
        raise ValueError(
            f"padded FFN width {padded_width} does not equal-split over "
            f"the {n}-way {axis!r} mesh axis — the geometry's padded "
            "layout is malformed")
    return padded_width // n


def param_sharding_tree(abstract_params, mesh: Mesh, logical_axes_tree, rules=None):
    """Build a NamedSharding pytree for params from a logical-axes pytree."""
    def _one(axes):
        spec = filter_spec_for_mesh(logical_to_spec(axes, rules), mesh)
        return NamedSharding(mesh, spec)

    return jax.tree.map(_one, logical_axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            e is None or isinstance(e, str) for e in x))
