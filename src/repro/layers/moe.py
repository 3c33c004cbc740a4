"""Mixture-of-experts FFN with two sharding strategies.

Dispatch is sort-based and grouped (megablocks-style, static shapes):
tokens are argsorted by assigned expert, scattered into a fixed [E, G, d]
buffer (G = capacity), expert matmuls run as grouped einsums, and results
combine back with the router weights. Over-capacity tokens drop (their
residual path still carries them — standard Switch behavior).

Sharding strategies (per MoEConfig.expert_sharding):
* ``expert`` — expert-parallel (DeepSeek-V2: 64 experts over the model
  axis; 4 experts/rank on a 16-way mesh). GSPMD materializes the
  all-to-all between the data-sharded token axis and the expert-sharded
  group axis.
* ``tp`` — tensor-parallel within each expert (Mixtral: 8 big experts,
  d_expert split over the model axis like a dense FFN). No all-to-all;
  the second matmul psums over the model axis.

The router auxiliary load-balance loss (Switch-style) is returned to the
caller. Routed experts are excluded from ZERO-resizing (token→expert
assignment changes every step, so a per-expert lineage is not stable);
shared experts and dense-FFN layers use the controlled path instead.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.config import MoEConfig
from repro.sharding import shard


def router_topk(x: jax.Array, w_router: jax.Array, cfg: MoEConfig
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (expert_idx [T,k], weights [T,k], aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x, w_router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, cfg.top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    # Switch-transformer load-balance aux loss
    T, E = logits.shape
    density = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * cfg.top_k)
    mean_prob = probs.mean(axis=0)
    aux = cfg.router_aux_coef * E * jnp.sum(density * mean_prob)
    return idx, weights.astype(x.dtype), aux


def _grouped_dispatch(idx: jax.Array, weights: jax.Array, T: int,
                      num_experts: int, capacity: int):
    """Sort-based dispatch. idx/weights [T, k].

    Returns gather ids [E, G] (into tokens; ==T for empty slots) and
    combine weights [E, G] (0 for empty slots)."""
    k = idx.shape[1]
    flat_e = idx.reshape(-1)                               # [T*k]
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_w = weights.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    t_sorted = flat_t[order]
    w_sorted = flat_w[order]
    # position within the expert segment
    seg_start = jnp.searchsorted(e_sorted, jnp.arange(num_experts))
    pos = jnp.arange(T * k) - seg_start[e_sorted]
    keep = pos < capacity
    slot = jnp.where(keep, e_sorted * capacity + jnp.clip(pos, 0, capacity - 1),
                     num_experts * capacity)               # OOB -> dropped
    gather_t = jnp.full((num_experts * capacity,), T, jnp.int32)
    gather_t = gather_t.at[slot].set(t_sorted.astype(jnp.int32), mode="drop")
    comb_w = jnp.zeros((num_experts * capacity,), w_sorted.dtype)
    comb_w = comb_w.at[slot].set(w_sorted, mode="drop")
    return (gather_t.reshape(num_experts, capacity),
            comb_w.reshape(num_experts, capacity))


def moe_ffn(x: jax.Array, params: dict, cfg: MoEConfig, act_fn,
            mesh=None, expert_sharding: str = "expert"
            ) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, d] -> (y [B, S, d], aux_loss). Routed experts only; shared
    experts / dense layers are composed by the caller."""
    if expert_sharding == "tp" and mesh is not None and "model" in mesh.axis_names:
        return _moe_tp_local(x, params, cfg, act_fn, mesh)
    B, S, d = x.shape
    T = B * S
    xt = x.reshape(T, d)
    idx, weights, aux = router_topk(xt, params["router"], cfg)

    capacity = max(8, int(T * cfg.top_k * cfg.capacity_factor / cfg.num_experts))
    capacity = -(-capacity // 8) * 8
    gather_t, comb_w = _grouped_dispatch(idx, weights, T, cfg.num_experts, capacity)

    if expert_sharding == "tp":
        xe_axes = (None, "batch", "embed")     # G over data; experts replicated
        h_axes = (None, "batch", "mlp")        # expert hidden over model
    else:
        xe_axes = ("expert", None, "embed")    # experts over model (all-to-all)
        h_axes = ("expert", None, None)

    xpad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    xe = xpad[gather_t]                                    # [E, G, d]
    xe = shard(xe, xe_axes, mesh=mesh)

    wg, wu, wd = params.get("w_gate"), params["w_up"], params["w_down"]
    h = jnp.einsum("egd,edf->egf", xe, wu)
    if wg is not None:
        h = act_fn(jnp.einsum("egd,edf->egf", xe, wg)) * h
    else:
        h = act_fn(h)
    h = shard(h, h_axes, mesh=mesh)
    ye = jnp.einsum("egf,efd->egd", h, wd)                 # [E, G, d]
    ye = shard(ye, xe_axes, mesh=mesh)

    ye = ye * comb_w[..., None].astype(ye.dtype)
    y = jnp.zeros((T + 1, d), ye.dtype).at[gather_t.reshape(-1)].add(
        ye.reshape(-1, d))[:T]
    y = shard(y.reshape(B, S, d), ("batch", None, "embed"), mesh=mesh)
    return y, aux


def _moe_tp_local(x: jax.Array, params: dict, cfg: MoEConfig, act_fn, mesh
                  ) -> Tuple[jax.Array, jax.Array]:
    """TP-sharded experts with DATA-LOCAL dispatch (§Perf iteration).

    The GSPMD gather from data-sharded tokens into the grouped buffer
    forced an all-gather of the full token array every layer (~17 GB × L
    for Mixtral train_4k). Inside shard_map each data shard routes and
    groups only its own tokens; the second expert matmul's partials are
    combined back per-token BEFORE the single psum over the model axis, so
    the collective is tokens_loc×d (reduce-merging, same trick as the
    paper's migration) instead of E×G×d."""
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    E = cfg.num_experts
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    gated = params.get("w_gate") is not None

    def body(x_, router_, wu_, wd_, *maybe_gate):
        wg_ = maybe_gate[0] if maybe_gate else None
        Bl, S_, d_ = x_.shape
        Tl = Bl * S_
        xt = x_.reshape(Tl, d_)
        idx, weights, aux = router_topk(xt, router_, cfg)
        cap = max(8, int(Tl * cfg.top_k * cfg.capacity_factor / E))
        cap = -(-cap // 8) * 8
        gather_t, comb_w = _grouped_dispatch(idx, weights, Tl, E, cap)
        xpad = jnp.concatenate([xt, jnp.zeros((1, d_), xt.dtype)], axis=0)
        xe = xpad[gather_t]                              # [E, G, d] local
        h = jnp.einsum("egd,edf->egf", xe, wu_)          # f model-sharded
        if wg_ is not None:
            h = act_fn(jnp.einsum("egd,edf->egf", xe, wg_)) * h
        else:
            h = act_fn(h)
        ye = jnp.einsum("egf,efd->egd", h, wd_)          # partial over model
        ye = ye * comb_w[..., None].astype(ye.dtype)
        y = jnp.zeros((Tl + 1, d_), ye.dtype).at[gather_t.reshape(-1)].add(
            ye.reshape(-1, d_))[:Tl]
        y = lax.psum(y, "model")                         # combine-then-psum
        aux = lax.pmean(aux, dp_axes) if dp_axes else aux
        return y.reshape(Bl, S_, d_), aux

    dp = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    in_specs = [P(dp, None, None), P(None, None),
                P(None, None, "model"), P(None, "model", None)]
    args = [x, params["router"], params["w_up"], params["w_down"]]
    if gated:
        in_specs.append(P(None, None, "model"))
        args.append(params["w_gate"])
    y, aux = jax.shard_map(
        body, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=(P(dp, None, None), P()), check_vma=False)(*args)
    return y, aux
