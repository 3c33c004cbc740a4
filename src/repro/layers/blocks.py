"""Transformer blocks: init + apply for every assigned family, with
scan-over-layers stacking (O(1) HLO size in depth) and workload-control
hooks on every TP linear.

Parameter pytrees are plain nested dicts; each init function also returns
a matching *logical-axes* pytree consumed by the launcher to build
NamedShardings (MaxText-style logical axis rules, repro/sharding.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.layers import attention as attn_lib
from repro.layers import moe as moe_lib
from repro.layers import rglru as rglru_lib
from repro.layers import ssm as ssm_lib
from repro.layers.tp_linear import ControlContext, controlled_ffn, controlled_proj
from repro.sharding import current_mesh, shard

Params = Dict[str, Any]

# ---------------------------------------------------------------------------
# Small pieces
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps) * (1.0 + scale)
    return y.astype(x.dtype)


def _normal(rng, shape, std=0.02, dtype=jnp.float32):
    return (jax.random.normal(rng, shape, jnp.float32) * std).astype(dtype)


def act_of(name: str) -> Tuple[Callable, bool]:
    """Returns (activation, gated)."""
    if name == "silu":
        return jax.nn.silu, True
    if name == "gelu_glu":
        return jax.nn.gelu, True
    if name == "gelu":
        return jax.nn.gelu, False
    raise ValueError(name)


# ---------------------------------------------------------------------------
# Attention layer (GQA / MLA)
# ---------------------------------------------------------------------------


def init_attention(rng, cfg: ModelConfig, dtype) -> Tuple[Params, Params]:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(rng, 8)
    if cfg.mla is not None:
        m = cfg.mla
        qdim = H * (m.qk_nope_head_dim + m.qk_rope_head_dim)
        p = {
            "wq": _normal(ks[0], (d, qdim), dtype=dtype),
            "w_dkv": _normal(ks[1], (d, m.kv_lora_rank), dtype=dtype),
            "w_kr": _normal(ks[2], (d, m.qk_rope_head_dim), dtype=dtype),
            "w_uk": _normal(ks[3], (m.kv_lora_rank, H * m.qk_nope_head_dim), dtype=dtype),
            "w_uv": _normal(ks[4], (m.kv_lora_rank, H * m.v_head_dim), dtype=dtype),
            "wo": _normal(ks[5], (H * m.v_head_dim, d),
                          std=0.02 / (2 * cfg.num_layers) ** 0.5, dtype=dtype),
        }
        ax = {
            "wq": ("embed", "heads"), "w_dkv": ("embed", "kv_lora"),
            "w_kr": ("embed", None), "w_uk": ("kv_lora", "heads"),
            "w_uv": ("kv_lora", "heads"), "wo": ("heads", "embed"),
        }
        return p, ax
    p = {
        "wq": _normal(ks[0], (d, H * hd), dtype=dtype),
        "wk": _normal(ks[1], (d, KV * hd), dtype=dtype),
        "wv": _normal(ks[2], (d, KV * hd), dtype=dtype),
        "wo": _normal(ks[3], (H * hd, d),
                      std=0.02 / (2 * cfg.num_layers) ** 0.5, dtype=dtype),
    }
    ax = {"wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
          "wv": ("embed", "kv_heads"), "wo": ("heads", "embed")}
    if cfg.qkv_bias:
        p.update(bq=jnp.zeros((H * hd,), dtype), bk=jnp.zeros((KV * hd,), dtype),
                 bv=jnp.zeros((KV * hd,), dtype))
        ax.update(bq=("heads",), bk=("kv_heads",), bv=("kv_heads",))
    return p, ax


def _paged_write_ids(pages: jax.Array, cur_pos: jax.Array,
                     page_size: int, num_pages: int):
    """Map each slot's write position to a (pool page id, in-page offset).

    Invalid positions — the ``INVALID_POS`` lanes of a chunked-prefill
    substep, or a position past the slot's allocated frontier — redirect
    to page id ``num_pages``: POSITIVE out-of-range, which the caller's
    ``mode="drop"`` scatter discards. (A -1 sentinel would not work:
    jax's default scatter WRAPS negative indices, silently corrupting
    the last pool page.)"""
    pps = pages.shape[1]
    pi = cur_pos // page_size
    p = jnp.take_along_axis(pages, jnp.clip(pi, 0, pps - 1)[:, None],
                            axis=1)[:, 0]
    ok = jnp.logical_and(jnp.logical_and(pi >= 0, pi < pps), p >= 0)
    page = jnp.where(ok, p, num_pages)
    return page, cur_pos % page_size


def _fused_decode(kernel: Callable, mesh, n_heads: int, args: Tuple,
                  head_axes: Tuple[Optional[int], ...]) -> jax.Array:
    """Run a fused decode-attention kernel, per TP shard on a mesh.

    GSPMD cannot partition a Mosaic kernel, so on a multi-device mesh the
    call runs inside ``shard_map``: ``args[i]`` is split over "model" on
    its ``head_axes[i]`` axis (None = replicated) when the mesh divides
    ``n_heads`` — the kv-head count, so each rank keeps whole GQA groups —
    and every rank runs all heads otherwise. The output's head axis is 1.
    """
    mesh = mesh or current_mesh()
    if mesh is None or mesh.size == 1:
        return kernel(*args)
    n = dict(mesh.shape).get("model", 0)
    h = "model" if n and n_heads % n == 0 else None

    def spec(a, ax):
        parts = [None] * a.ndim
        if ax is not None:
            parts[ax] = h
        return P(*parts)

    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=tuple(spec(a, ax) for a, ax in zip(args, head_axes)),
        out_specs=P(None, h), check_vma=False)(*args)


def apply_attention(p: Params, x: jax.Array, cfg: ModelConfig, *,
                    ctx: Optional[ControlContext], positions: jax.Array,
                    causal: bool = True, window: int = 0,
                    cache: Optional[Params] = None,
                    cur_pos: Optional[jax.Array] = None,
                    kv_source: Optional[jax.Array] = None,
                    mrope_positions: Optional[jax.Array] = None,
                    pages: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, Optional[Params]]:
    """Self- (or cross-, via kv_source) attention.

    cache None => train/prefill (full sequence). cache given => decode:
    x is [B, 1, d], the cache is updated at cur_pos and attended.
    ``pages`` [B, pages_per_slot] switches the decode cache to the
    block-paged pool layout (core/paging.py).
    """
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads
    mesh = ctx.mesh if ctx else None

    if cfg.mla is not None:
        return _apply_mla(p, x, cfg, ctx=ctx, positions=positions,
                          cache=cache, cur_pos=cur_pos, pages=pages)

    q = controlled_proj(x, p["wq"], ctx, "qkv", split="col")
    src = x if kv_source is None else kv_source
    k = controlled_proj(src, p["wk"], ctx, "qkv", split="col")
    v = controlled_proj(src, p["wv"], ctx, "qkv", split="col")
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    Skv = src.shape[1]
    q = shard(q.reshape(B, S, H, hd), ("batch", None, "heads", None), mesh=mesh)
    k = shard(k.reshape(B, Skv, KV, hd), ("batch", None, "kv_heads", None), mesh=mesh)
    v = shard(v.reshape(B, Skv, KV, hd), ("batch", None, "kv_heads", None), mesh=mesh)

    # positions: [S] (train/prefill) or [B, S=1] (decode, = cur_pos[:, None])
    if cfg.pos_embedding == "rope" and kv_source is None:
        q = attn_lib.apply_rope(q, positions, cfg.rope_theta)
        k = attn_lib.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.pos_embedding == "mrope" and kv_source is None:
        assert mrope_positions is not None
        q = attn_lib.apply_mrope(q, mrope_positions, cfg.rope_theta)
        k = attn_lib.apply_mrope(k, mrope_positions if cache is None else
                                 mrope_positions[:, -1:], cfg.rope_theta)

    q = q.transpose(0, 2, 1, 3)                       # [B, H, S, hd]
    k = k.transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)

    new_cache = None
    if cache is not None and S == 1 and pages is not None:
        # paged decode: scatter into the shared pool through the page
        # table; invalid lanes redirect to the dropped page id
        kc, vc = cache["k"], cache["v"]
        num_pages, ps_len = kc.shape[0], kc.shape[2]
        page, off = _paged_write_ids(pages, cur_pos, ps_len, num_pages)
        k_new, v_new = k[:, :, 0, :], v[:, :, 0, :]           # [B, KV, hd]
        k_scale = v_scale = None
        if "k_scale" in cache:
            # int8 pool: per (slot, kv-head) row scale = max|.|/127
            ksc = jnp.maximum(jnp.abs(k_new).max(axis=-1), 1e-12) / 127.0
            vsc = jnp.maximum(jnp.abs(v_new).max(axis=-1), 1e-12) / 127.0
            k_new = jnp.clip(jnp.round(k_new / ksc[..., None]),
                             -127, 127)
            v_new = jnp.clip(jnp.round(v_new / vsc[..., None]),
                             -127, 127)
            k_scale = cache["k_scale"].at[page, :, off].set(
                ksc, mode="drop")
            v_scale = cache["v_scale"].at[page, :, off].set(
                vsc, mode="drop")
        kc = kc.at[page, :, off, :].set(k_new.astype(kc.dtype),
                                        mode="drop")
        vc = vc.at[page, :, off, :].set(v_new.astype(vc.dtype),
                                        mode="drop")
        kc = shard(kc, (None, "kv_heads", None, None), mesh=mesh)
        vc = shard(vc, (None, "kv_heads", None, None), mesh=mesh)
        if cfg.fused_decode_attn:
            if k_scale is not None:
                raise ValueError(
                    "kv_int8 paging has no fused kernel path — run with "
                    "fused_attention off (oracle dequant)")
            from repro.kernels import ops as _kops
            o = _fused_decode(
                lambda q_, k_, v_, pg, cp:
                    _kops.fused_paged_decode_attention(
                        q_, k_, v_, pages=pg, cur_pos=cp, window=window),
                mesh, KV, (q, kc, vc, pages, cur_pos), (1, 1, 1, None, None))
        else:
            o = attn_lib.paged_decode_attention(
                q, kc, vc, pages=pages, cur_pos=cur_pos, window=window,
                k_scale=k_scale, v_scale=v_scale)
        new_cache = {"k": kc, "v": vc}
        if k_scale is not None:
            new_cache["k_scale"] = k_scale
            new_cache["v_scale"] = v_scale
    elif cache is not None and S == 1:
        # decode: write new K/V at each row's OWN cur_pos (continuous
        # batching runs slots at ragged positions), attend over the cache
        kc, vc = cache["k"], cache["v"]
        b_idx = jnp.arange(B)
        kc = kc.at[b_idx, :, cur_pos, :].set(k[:, :, 0, :].astype(kc.dtype))
        vc = vc.at[b_idx, :, cur_pos, :].set(v[:, :, 0, :].astype(vc.dtype))
        kc = shard(kc, ("batch", "kv_heads", "decode_seq", None), mesh=mesh)
        vc = shard(vc, ("batch", "kv_heads", "decode_seq", None), mesh=mesh)
        if cfg.fused_decode_attn:
            # fused Pallas decode attention (kernels/decode_attn.py):
            # online softmax over the ragged cache, no [B, H, S] scores
            # in HBM; interpret-mode fallback keeps CPU containers green
            from repro.kernels import ops as _kops
            o = _fused_decode(
                lambda q_, k_, v_, cp: _kops.fused_decode_attention(
                    q_, k_, v_, cur_pos=cp, window=window),
                mesh, KV, (q, kc, vc, cur_pos), (1, 1, 1, None))
        else:
            o = attn_lib.decode_attention(q, kc, vc, cur_pos=cur_pos,
                                          window=window)
        new_cache = {"k": kc, "v": vc}
    elif cache is not None:
        # prefill: fill the cache from position 0, attend with flash
        kc = lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), 0, axis=2)
        vc = lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), 0, axis=2)
        kc = shard(kc, ("batch", "kv_heads", "decode_seq", None), mesh=mesh)
        vc = shard(vc, ("batch", "kv_heads", "decode_seq", None), mesh=mesh)
        o = attn_lib.flash_attention(
            q, k, v, q_positions=positions, kv_positions=positions,
            causal=causal, window=window)
        new_cache = {"k": kc, "v": vc}
    elif kv_source is not None:
        # cross-attention is non-causal: positions only gate validity
        o = attn_lib.flash_attention(
            q, k, v, q_positions=jnp.arange(S),
            kv_positions=jnp.arange(Skv), causal=False, window=0)
    else:
        o = attn_lib.flash_attention(
            q, k, v, q_positions=positions, kv_positions=positions,
            causal=causal, window=window)

    o = o.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    y = controlled_proj(o, p["wo"], ctx, "attn_out", split="row",
                        out_axes=("batch", None, "embed"))
    if ctx is None or "attn_out" not in (ctx.pri if ctx else {}):
        y = shard(y, ("batch", None, "embed"), mesh=mesh)
    return y, new_cache


def _apply_mla(p, x, cfg, *, ctx, positions, cache, cur_pos, pages=None):
    m = cfg.mla
    B, S, d = x.shape
    H = cfg.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    mesh = ctx.mesh if ctx else None

    q = controlled_proj(x, p["wq"], ctx, "qkv", split="col")
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    latent = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])      # [B,S,R]
    k_rope = jnp.einsum("bsd,dr->bsr", x, p["w_kr"])       # [B,S,dr]
    # `positions` is [S] (train/prefill) or [B, 1] == cur_pos (decode)
    q_rope = attn_lib.apply_rope(q_rope, positions, cfg.rope_theta)
    k_rope = attn_lib.apply_rope(k_rope[:, :, None, :], positions,
                                 cfg.rope_theta)[:, :, 0]

    if cache is not None and S > 1:
        # prefill: fill the latent cache, then run the expanded-form path
        lc = lax.dynamic_update_slice_in_dim(
            cache["latent"], latent.astype(cache["latent"].dtype), 0, axis=1)
        rc = lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), 0, axis=1)
        cache = None  # fall through to the expanded path below
        prefill_cache = {"latent": shard(lc, ("batch", "decode_seq", None), mesh=mesh),
                         "k_rope": shard(rc, ("batch", "decode_seq", None), mesh=mesh)}
    else:
        prefill_cache = None

    if cache is not None:
        if pages is not None:
            # paged decode: pool scatter through the page table
            lc0, rc0 = cache["latent"], cache["k_rope"]
            num_pages, ps_len = lc0.shape[0], lc0.shape[1]
            page, off = _paged_write_ids(pages, cur_pos, ps_len,
                                         num_pages)
            lc = lc0.at[page, off, :].set(
                latent[:, 0].astype(lc0.dtype), mode="drop")
            rc = rc0.at[page, off, :].set(
                k_rope[:, 0].astype(rc0.dtype), mode="drop")
            lc = shard(lc, (None, None, None), mesh=mesh)
            rc = shard(rc, (None, None, None), mesh=mesh)
        else:
            # decode: per-row ragged write (see the GQA decode path above)
            b_idx = jnp.arange(B)
            lc = cache["latent"].at[b_idx, cur_pos, :].set(
                latent[:, 0].astype(cache["latent"].dtype))
            rc = cache["k_rope"].at[b_idx, cur_pos, :].set(
                k_rope[:, 0].astype(cache["k_rope"].dtype))
            lc = shard(lc, ("batch", "decode_seq", None), mesh=mesh)
            rc = shard(rc, ("batch", "decode_seq", None), mesh=mesh)
        # absorbed decode: q_abs = W_uk^T q_nope per head
        w_uk = p["w_uk"].reshape(m.kv_lora_rank, H, dn)
        q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)
        if pages is not None and cfg.fused_decode_attn:
            from repro.kernels import ops as _kops
            o_lat = _fused_decode(
                lambda qa, qr, l_, r_, pg, cp:
                    _kops.fused_paged_mla_decode_attention(
                        qa, qr, l_, r_, pages=pg, cur_pos=cp,
                        head_dim_for_scale=dn + dr),
                mesh, H, (q_abs, q_rope[:, 0], lc, rc, pages, cur_pos),
                (1, 1, None, None, None, None))
        elif pages is not None:
            o_lat = attn_lib.paged_mla_decode_attention(
                q_abs, q_rope[:, 0], lc, rc, pages=pages,
                cur_pos=cur_pos, head_dim_for_scale=dn + dr)
        elif cfg.fused_decode_attn:
            from repro.kernels import ops as _kops
            o_lat = _fused_decode(
                lambda qa, qr, l_, r_, cp: _kops.fused_mla_decode_attention(
                    qa, qr, l_, r_, cur_pos=cp,
                    head_dim_for_scale=dn + dr),
                mesh, H, (q_abs, q_rope[:, 0], lc, rc, cur_pos),
                (1, 1, None, None, None))                   # [B,H,R]
        else:
            o_lat = attn_lib.mla_decode_attention(
                q_abs, q_rope[:, 0], lc, rc, cur_pos=cur_pos,
                head_dim_for_scale=dn + dr)                # [B,H,R]
        w_uv = p["w_uv"].reshape(m.kv_lora_rank, H, dv)
        o = jnp.einsum("bhr,rhd->bhd", o_lat.astype(x.dtype), w_uv)
        o = o.reshape(B, 1, H * dv)
        y = controlled_proj(o, p["wo"], ctx, "attn_out", split="row",
                            out_axes=("batch", None, "embed"))
        return y, {"latent": lc, "k_rope": rc}

    # train/prefill: expand K/V from the latent
    k_nope = jnp.einsum("bsr,rh->bsh", latent, p["w_uk"]).reshape(B, S, H, dn)
    v = jnp.einsum("bsr,rh->bsh", latent, p["w_uv"]).reshape(B, S, H, dv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))], -1)
    qf = jnp.concatenate([q_nope, q_rope], -1)
    qf = shard(qf, ("batch", None, "heads", None), mesh=mesh).transpose(0, 2, 1, 3)
    k = shard(k, ("batch", None, "heads", None), mesh=mesh).transpose(0, 2, 1, 3)
    v = shard(v, ("batch", None, "heads", None), mesh=mesh).transpose(0, 2, 1, 3)
    o = attn_lib.flash_attention(qf, k, v, q_positions=positions,
                                 kv_positions=positions, causal=True)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, H * dv)
    y = controlled_proj(o, p["wo"], ctx, "attn_out", split="row",
                        out_axes=("batch", None, "embed"))
    return y, prefill_cache


# ---------------------------------------------------------------------------
# FFN (dense, controlled) + MoE wrapper
# ---------------------------------------------------------------------------


def init_ffn(rng, d: int, d_ff: int, gated: bool, num_layers: int, dtype
             ) -> Tuple[Params, Params]:
    ks = jax.random.split(rng, 3)
    p = {"w_up": _normal(ks[0], (d, d_ff), dtype=dtype),
         "w_down": _normal(ks[1], (d_ff, d),
                           std=0.02 / (2 * num_layers) ** 0.5, dtype=dtype)}
    ax = {"w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
    if gated:
        p["w_gate"] = _normal(ks[2], (d, d_ff), dtype=dtype)
        ax["w_gate"] = ("embed", "mlp")
    return p, ax


def apply_ffn(p: Params, x: jax.Array, cfg: ModelConfig,
              ctx: Optional[ControlContext]) -> jax.Array:
    act, gated = act_of(cfg.act)
    return controlled_ffn(x, p["w_up"], p["w_down"], ctx, "ffn", act,
                          w_gate=p.get("w_gate"))


def init_moe(rng, cfg: ModelConfig, dtype) -> Tuple[Params, Params]:
    mo = cfg.moe
    d = cfg.d_model
    _, gated = act_of(cfg.act)
    ks = jax.random.split(rng, 8)
    E, f = mo.num_experts, mo.d_expert
    p = {"router": _normal(ks[0], (d, E), dtype=jnp.float32),
         "w_up": _normal(ks[1], (E, d, f), dtype=dtype),
         "w_down": _normal(ks[2], (E, f, d),
                           std=0.02 / (2 * cfg.num_layers) ** 0.5, dtype=dtype)}
    if mo.expert_sharding == "tp":
        # few big experts (Mixtral): shard d_expert over the model axis —
        # sharding E (8) over a 16-way axis would silently replicate.
        up_ax, down_ax = (None, "embed", "mlp"), (None, "mlp", "embed")
    else:
        up_ax, down_ax = (("expert", "embed", "expert_mlp"),
                          ("expert", "expert_mlp", "embed"))
    ax = {"router": ("embed", None), "w_up": up_ax, "w_down": down_ax}
    if gated:
        p["w_gate"] = _normal(ks[3], (E, d, f), dtype=dtype)
        ax["w_gate"] = up_ax
    if mo.num_shared_experts:
        sh, shax = init_ffn(ks[4], d, mo.num_shared_experts * (mo.d_shared or f),
                            gated, cfg.num_layers, dtype)
        p["shared"], ax["shared"] = sh, shax
    return p, ax


def apply_moe(p: Params, x: jax.Array, cfg: ModelConfig,
              ctx: Optional[ControlContext]) -> Tuple[jax.Array, jax.Array]:
    act, _ = act_of(cfg.act)
    mo = cfg.moe
    sharding = getattr(mo, "expert_sharding", None) or (
        "tp" if mo.num_experts <= 8 else "expert")
    from repro import sharding as sh_mod
    y, aux = moe_lib.moe_ffn(x, p, mo, act,
                             mesh=ctx.mesh if ctx else sh_mod.current_mesh(),
                             expert_sharding=sharding)
    if "shared" in p:
        y = y + controlled_ffn(x, p["shared"]["w_up"], p["shared"]["w_down"],
                               ctx, "ffn", act, w_gate=p["shared"].get("w_gate"))
    return y, aux


# ---------------------------------------------------------------------------
# SSM / RG-LRU inits
# ---------------------------------------------------------------------------


def init_mamba(rng, cfg: ModelConfig, dtype) -> Tuple[Params, Params]:
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    dt_rank = s.dt_rank or -(-d // 16)
    ks = jax.random.split(rng, 8)
    A = jnp.tile(jnp.arange(1, s.d_state + 1, dtype=jnp.float32)[None],
                 (d_in, 1))
    p = {
        "w_in": _normal(ks[0], (d, 2 * d_in), dtype=dtype),
        "conv_w": _normal(ks[1], (s.d_conv, d_in), std=0.1, dtype=dtype),
        "conv_b": jnp.zeros((d_in,), dtype),
        "w_x": _normal(ks[2], (d_in, dt_rank + 2 * s.d_state), dtype=dtype),
        "w_dt": _normal(ks[3], (dt_rank, d_in), std=dt_rank ** -0.5, dtype=dtype),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.clip(jax.random.uniform(ks[4], (d_in,)) * 0.099 + 0.001,
                     1e-4, None))).astype(dtype),
        "A_log": jnp.log(A),
        "D": jnp.ones((d_in,), dtype),
        "w_out": _normal(ks[5], (d_in, d),
                         std=0.02 / (2 * cfg.num_layers) ** 0.5, dtype=dtype),
    }
    ax = {"w_in": ("embed", "lru"), "conv_w": (None, "lru"), "conv_b": ("lru",),
          "w_x": ("lru", None), "w_dt": (None, "lru"), "dt_bias": ("lru",),
          "A_log": ("lru", None), "D": ("lru",), "w_out": ("lru", "embed")}
    return p, ax


def init_rglru(rng, cfg: ModelConfig, dtype) -> Tuple[Params, Params]:
    g = cfg.rglru
    d = cfg.d_model
    W = g.lru_width or d
    ks = jax.random.split(rng, 8)
    p = {
        "w_gate_branch": _normal(ks[0], (d, W), dtype=dtype),
        "w_rec_branch": _normal(ks[1], (d, W), dtype=dtype),
        "conv_w": _normal(ks[2], (g.conv1d_width, W), std=0.1, dtype=dtype),
        "conv_b": jnp.zeros((W,), dtype),
        "w_a": _normal(ks[3], (W, W), std=W ** -0.5, dtype=dtype),
        "b_a": jnp.zeros((W,), dtype),
        "w_x": _normal(ks[4], (W, W), std=W ** -0.5, dtype=dtype),
        "b_x": jnp.zeros((W,), dtype),
        "lam": jax.random.uniform(ks[5], (W,), minval=0.3, maxval=0.9),
        "w_out": _normal(ks[6], (W, d),
                         std=0.02 / (2 * cfg.num_layers) ** 0.5, dtype=dtype),
    }
    ax = {"w_gate_branch": ("embed", "lru"), "w_rec_branch": ("embed", "lru"),
          "conv_w": (None, "lru"), "conv_b": ("lru",),
          "w_a": ("lru", None), "b_a": ("lru",), "w_x": ("lru", None),
          "b_x": ("lru",), "lam": ("lru",), "w_out": ("lru", "embed")}
    return p, ax


# ---------------------------------------------------------------------------
# One block (pre-norm residual) — kind dispatch
# ---------------------------------------------------------------------------


def init_block(rng, cfg: ModelConfig, kind: str, dtype) -> Tuple[Params, Params]:
    ks = jax.random.split(rng, 4)
    d = cfg.d_model
    p: Params = {"norm1": jnp.zeros((d,), jnp.float32)}
    ax: Params = {"norm1": ("embed",)}
    if kind == "mamba":
        p["mixer"], ax["mixer"] = init_mamba(ks[0], cfg, dtype)
        return p, ax
    if kind == "rglru":
        p["mixer"], ax["mixer"] = init_rglru(ks[0], cfg, dtype)
    elif kind in ("attn", "attn_local", "attn_bidir"):
        p["attn"], ax["attn"] = init_attention(ks[0], cfg, dtype)
    elif kind == "attn_cross":
        p["attn"], ax["attn"] = init_attention(ks[0], cfg, dtype)
        p["xattn"], ax["xattn"] = init_attention(ks[1], cfg, dtype)
        p["norm_x"], ax["norm_x"] = jnp.zeros((d,), jnp.float32), ("embed",)
    p["norm2"], ax["norm2"] = jnp.zeros((d,), jnp.float32), ("embed",)
    if kind == "moe":
        p["attn"], ax["attn"] = init_attention(ks[0], cfg, dtype)
        p["moe"], ax["moe"] = init_moe(ks[2], cfg, dtype)
    else:
        _, gated = act_of(cfg.act)
        dff = cfg.d_ff if cfg.moe is None else (cfg.moe.d_ff_dense or cfg.d_ff)
        p["ffn"], ax["ffn"] = init_ffn(ks[3], d, dff, gated, cfg.num_layers, dtype)
    return p, ax


def apply_block(p: Params, x: jax.Array, cfg: ModelConfig, kind: str, *,
                ctx: Optional[ControlContext], positions: jax.Array,
                cache: Optional[Params] = None,
                cur_pos: Optional[jax.Array] = None,
                encoder_out: Optional[jax.Array] = None,
                mrope_positions: Optional[jax.Array] = None,
                causal: bool = True,
                pages: Optional[jax.Array] = None):
    """Returns (x_out, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    eps = cfg.norm_eps
    new_cache: Optional[Params] = None

    if kind == "mamba":
        h, st = ssm_lib.mamba_mixer(
            rms_norm(x, p["norm1"], eps), p["mixer"], cfg.ssm,
            state=None if cache is None else (cache["h"], cache["conv"]))
        new_cache = None if cache is None else {"h": st[0], "conv": st[1]}
        return x + h, new_cache, aux

    if kind == "rglru":
        h, st = rglru_lib.rglru_block(
            rms_norm(x, p["norm1"], eps), p["mixer"], cfg.rglru,
            state=None if cache is None else (cache["h"], cache["conv"]))
        cache_out = None if cache is None else {"h": st[0], "conv": st[1]}
        x = x + h
        h2 = apply_ffn(p["ffn"], rms_norm(x, p["norm2"], eps), cfg, ctx)
        return x + h2, cache_out, aux

    window = 0
    if kind == "attn_local":
        window = cfg.rglru.local_window if cfg.rglru else cfg.sliding_window
    elif cfg.sliding_window:
        window = cfg.sliding_window

    attn_cache = None if cache is None else cache.get("attn", cache)
    h, ac = apply_attention(
        p["attn"], rms_norm(x, p["norm1"], eps), cfg, ctx=ctx,
        positions=positions, causal=causal and kind != "attn_bidir",
        window=window, cache=attn_cache, cur_pos=cur_pos,
        mrope_positions=mrope_positions, pages=pages)
    x = x + h
    if kind == "attn_cross":
        hx, _ = apply_attention(
            p["xattn"], rms_norm(x, p["norm_x"], eps), cfg, ctx=ctx,
            positions=positions, causal=False, cache=None,
            kv_source=encoder_out)
        x = x + hx
    if ac is not None:
        new_cache = {"attn": ac}

    if kind == "moe":
        h2, aux = apply_moe(p["moe"], rms_norm(x, p["norm2"], eps), cfg, ctx)
    else:
        h2 = apply_ffn(p["ffn"], rms_norm(x, p["norm2"], eps), cfg, ctx)
    return x + h2, new_cache, aux


# ---------------------------------------------------------------------------
# Layer-kind schedule + stacked init/apply (scan over layers)
# ---------------------------------------------------------------------------


def layer_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    if cfg.family == "ssm":
        return ("mamba",) * cfg.num_layers
    if cfg.family == "hybrid":
        pat = cfg.rglru.block_pattern
        return tuple(("attn_local" if pat[i % len(pat)] == "attn" else "rglru")
                     for i in range(cfg.num_layers))
    if cfg.moe is not None:
        fd = cfg.moe.first_dense_layers
        return ("attn",) * fd + ("moe",) * (cfg.num_layers - fd)
    return ("attn",) * cfg.num_layers


def split_layers(cfg: ModelConfig):
    """Decompose the layer schedule into (prefix_kinds, pattern, repeat,
    suffix_kinds) so the `repeat` homogeneous pattern groups run under one
    ``lax.scan`` (O(1) HLO in depth) and the ragged ends run unrolled."""
    kinds = layer_kinds(cfg)
    L = len(kinds)
    if cfg.family == "hybrid":
        pat = tuple("attn_local" if k == "attn" else "rglru"
                    for k in cfg.rglru.block_pattern)
        repeat = L // len(pat)
        return (), pat, repeat, kinds[repeat * len(pat):]
    if cfg.moe is not None and cfg.moe.first_dense_layers:
        fd = cfg.moe.first_dense_layers
        return kinds[:fd], ("moe",), L - fd, ()
    return (), (kinds[0],), L, ()


def init_stack(rng, cfg: ModelConfig, dtype, kind_override=None
               ) -> Tuple[Params, Params]:
    """Stacked layer params: {"prefix": [...], "scan": stacked, "suffix": [...]}."""
    prefix, pattern, repeat, suffix = split_layers(cfg)
    if kind_override:
        prefix, pattern, repeat, suffix = (), (kind_override,), cfg.num_layers, ()
    out_p: Params = {}
    out_ax: Params = {}

    def init_list(kinds, key):
        ps, axs = [], []
        for i, kind in enumerate(kinds):
            p, ax = init_block(jax.random.fold_in(key, i), cfg, kind, dtype)
            ps.append(p)
            axs.append(ax)
        return ps, axs

    if prefix:
        out_p["prefix"], out_ax["prefix"] = init_list(
            prefix, jax.random.fold_in(rng, 1000))

    def init_group(key):
        return tuple(init_block(jax.random.fold_in(key, j), cfg, kind, dtype)[0]
                     for j, kind in enumerate(pattern))

    keys = jax.random.split(jax.random.fold_in(rng, 2000), repeat)
    out_p["scan"] = jax.vmap(init_group)(keys)
    axes = []
    for j, kind in enumerate(pattern):
        _, axk = init_block(rng, cfg, kind, dtype)
        axes.append(jax.tree.map(
            lambda t: ("layers",) + tuple(t), axk,
            is_leaf=lambda t: isinstance(t, tuple)
            and all(e is None or isinstance(e, str) for e in t)))
    out_ax["scan"] = tuple(axes)

    if suffix:
        out_p["suffix"], out_ax["suffix"] = init_list(
            suffix, jax.random.fold_in(rng, 3000))
    return out_p, out_ax


def apply_stack(stack: Params, x: jax.Array, cfg: ModelConfig, *,
                ctx=None, positions=None, caches=None, cur_pos=None,
                encoder_out=None, mrope_positions=None, causal=True,
                remat: str = "none", kind_override=None, pages=None):
    """Run all layers. caches: {"prefix": [...], "scan": stacked, ...} or None.

    Returns (x, new_caches, total_aux)."""
    prefix, pattern, repeat, suffix = split_layers(cfg)
    if kind_override:
        prefix, pattern, repeat, suffix = (), (kind_override,), cfg.num_layers, ()
    aux_tot = jnp.zeros((), jnp.float32)
    new_caches: Params = {}
    per_layer = ctx is not None and ctx.per_layer

    def ctx_at(layer_idx):
        if ctx is None or not per_layer:
            return ctx
        return ctx.layer_slice(ctx.bucket_by_rank[layer_idx],
                               {k: v[layer_idx] for k, v in ctx.pri.items()})

    def run_list(x, kinds, plist, clist, aux_tot, base):
        ncs = []
        for i, kind in enumerate(kinds):
            c = None if clist is None else clist[i]
            x, nc, aux = apply_block(
                plist[i], x, cfg, kind, ctx=ctx_at(base + i),
                positions=positions, cache=c, cur_pos=cur_pos,
                encoder_out=encoder_out, mrope_positions=mrope_positions,
                causal=causal, pages=pages)
            aux_tot = aux_tot + aux
            ncs.append(nc)
        return x, ncs, aux_tot

    if prefix:
        x, ncs, aux_tot = run_list(
            x, prefix, stack["prefix"],
            None if caches is None else caches.get("prefix"), aux_tot, 0)
        if caches is not None:
            new_caches["prefix"] = ncs

    # per-layer plan arrays for the scanned region: [repeat, pat, ...]
    ctx_xs = None
    if per_layer:
        lo = len(prefix)
        pl = len(pattern)

        def grp(a):
            return a[lo: lo + repeat * pl].reshape(
                (repeat, pl) + a.shape[1:])
        ctx_xs = (grp(ctx.bucket_by_rank),
                  {k: grp(v) for k, v in ctx.pri.items()})

    def scan_body(carry, xs):
        x, aux_in = carry
        group_params, group_caches, group_ctx = xs
        aux_g = jnp.zeros((), jnp.float32)
        ncs = []
        for j, kind in enumerate(pattern):
            c = None if group_caches is None else group_caches[j]
            if group_ctx is not None:
                b, pr = group_ctx
                ctx_j = ctx.layer_slice(b[j], {k: v[j] for k, v in pr.items()})
            else:
                ctx_j = ctx
            x, nc, aux = apply_block(
                group_params[j], x, cfg, kind, ctx=ctx_j, positions=positions,
                cache=c, cur_pos=cur_pos, encoder_out=encoder_out,
                mrope_positions=mrope_positions, causal=causal, pages=pages)
            aux_g = aux_g + aux
            ncs.append(nc)
        ys = tuple(ncs) if group_caches is not None else None
        return (x, aux_in + aux_g), ys

    body = scan_body
    if remat != "none":
        body = jax.checkpoint(
            scan_body,
            policy=jax.checkpoint_policies.nothing_saveable
            if remat == "full" else
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

    scan_caches = None if caches is None else caches.get("scan")
    (x, aux_tot), ncs = lax.scan(
        body, (x, aux_tot), (stack["scan"], scan_caches, ctx_xs))
    if caches is not None:
        new_caches["scan"] = ncs

    if suffix:
        x, ncs, aux_tot = run_list(
            x, suffix, stack["suffix"],
            None if caches is None else caches.get("suffix"), aux_tot,
            len(prefix) + repeat * len(pattern))
        if caches is not None:
            new_caches["suffix"] = ncs

    return x, (new_caches if caches is not None else None), aux_tot
