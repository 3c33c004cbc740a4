"""jit'd public wrappers for the Pallas kernels.

``block_pruned_matmul`` handles arbitrary leading batch dims, pads M/N up
to tile multiples, and provides a custom VJP whose backward is ALSO
kernel-level: ``pruned_matmul_dx_2d`` / ``pruned_matmul_dw_2d`` write the
dX/dW tiles directly through inverse BlockSpec index maps and zero the
pruned blocks in-kernel — no full-size zeros+scatter temporaries and no
gathered ``wk``/``xk`` copies anywhere in the gradient path.

``fused_pruned_ffn`` is the whole controlled FFN pair
``y = act(x @ Wup[:, keep] [, · gate]) @ Wdown[keep, :]`` as ONE forward
pallas_call (the resized hidden activation never round-trips HBM), with a
custom VJP composed from the out-pruned kernel family plus an elementwise
activation VJP.

Interpret mode: auto-detected per backend (CPU containers interpret, real
TPUs compile) and overridable with ``REPRO_PALLAS_INTERPRET=0|1``.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import decode_attn as _dk
from repro.kernels import pruned_matmul as _pk
from repro.kernels import ref as _ref

# Tri-state: None = auto-detect (non-TPU backends interpret, TPU compiles),
# overridable via env REPRO_PALLAS_INTERPRET or by assigning True/False.
INTERPRET = None

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")

# TPU vector lane width: the pruning block is the last dim of every
# kernel tile that streams a pruned block, so a compiled (non-interpret)
# kernel needs it to be a multiple of this
LANES = 128

# cached env + backend resolution: every kernel wrapper consults
# interpret_mode() per call, and jax.default_backend() is not free —
# resolve once, invalidate explicitly via reset_interpret_cache()
_INTERPRET_CACHE = None


def interpret_mode() -> bool:
    """Resolve whether Pallas kernels run in interpret mode.

    Priority: module override (ops.INTERPRET = True/False) >
    REPRO_PALLAS_INTERPRET env var > backend auto-detection (anything
    but TPU interprets). The override is read live; the env + backend
    resolution is computed once and cached module-wide — call
    :func:`reset_interpret_cache` after mutating the env var or
    swapping the jax backend mid-process (tests do)."""
    if INTERPRET is not None:
        return bool(INTERPRET)
    global _INTERPRET_CACHE
    if _INTERPRET_CACHE is None:
        env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
        if env in _TRUTHY:
            _INTERPRET_CACHE = True
        elif env in _FALSY:
            _INTERPRET_CACHE = False
        else:
            _INTERPRET_CACHE = jax.default_backend() != "tpu"
    return _INTERPRET_CACHE


def reset_interpret_cache() -> None:
    """Drop the cached env/backend interpret-mode resolution."""
    global _INTERPRET_CACHE
    _INTERPRET_CACHE = None


# ---------------------------------------------------------------------------
# shape utilities
# ---------------------------------------------------------------------------


def _pad_to(a: jax.Array, mult: int, axis: int) -> jax.Array:
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _tile(dim: int, pref: int, align: int) -> int:
    """Static tile size: dim rounded up to `align`, capped at `pref` —
    avoids padding tiny benchmark shapes up to the full 256-wide tiles."""
    return min(pref, -(-dim // align) * align)


def _validate(K: int, w_rows: int, keep_idx: jax.Array, block: int,
              what: str) -> int:
    """Satellite guard: readable errors instead of a bare assert deep in
    the kernel (the old silent-truncation hazard). Returns num_blocks."""
    if block <= 0:
        raise ValueError(f"{what}: block size must be positive, got {block}")
    if K != w_rows:
        raise ValueError(
            f"{what}: contraction mismatch — x has K={K} but w has "
            f"{w_rows} rows")
    if K % block != 0:
        raise ValueError(
            f"{what}: contraction dim K={K} is not a multiple of the "
            f"pruning block size {block} (K would be silently truncated); "
            "choose a block via repro.core.workload.adapt_block_size")
    if block % LANES and not interpret_mode():
        raise ValueError(
            f"{what}: pruning block {block} is not a multiple of the "
            f"{LANES}-lane vector width, so Mosaic cannot tile it on the "
            "TPU; plan the controlled scope at a 128-multiple block "
            "(repro.control.scopes.plan_block_size) or run in interpret "
            "mode")
    nb = K // block
    if keep_idx.ndim != 1:
        raise ValueError(
            f"{what}: keep_idx must be a 1-D block-id vector, got shape "
            f"{keep_idx.shape}")
    kb = keep_idx.shape[0]
    if kb < 1 or kb > nb:
        raise ValueError(
            f"{what}: keep_idx has {kb} entries but K={K} / block={block} "
            f"gives only {nb} blocks (need 1 <= kept <= {nb})")
    if not jnp.issubdtype(keep_idx.dtype, jnp.integer):
        raise ValueError(
            f"{what}: keep_idx must be integer block ids, got "
            f"{keep_idx.dtype}")
    return nb


def _inverse_order(keep_idx: jax.Array, nb: int) -> jax.Array:
    """[nb] permutation concat(keep_idx, pruned ids) for the backward
    kernels' inverse index maps. The keep prefix is keep_idx ITSELF (in
    caller order, sorted or not): compact slot k must map to block
    keep_idx[k], or the x_compact/compact_out kernels would pair hidden
    blocks with the wrong weight-gradient tiles. Built scatter-free
    (mask + stable argsort) so the gradient path stays free of
    scatter/gather HLO."""
    keep_idx = keep_idx.astype(jnp.int32)
    ids = jnp.arange(nb, dtype=jnp.int32)
    is_kept = jnp.any(ids[:, None] == keep_idx[None, :], axis=1)
    pruned = jnp.argsort(is_kept.astype(jnp.int32),
                         stable=True)[: nb - keep_idx.shape[0]]
    return jnp.concatenate([keep_idx, pruned.astype(jnp.int32)])


# ---------------------------------------------------------------------------
# block-pruned matmul (contraction pruning) with kernel-level VJP
# ---------------------------------------------------------------------------


def _run_fwd(x2d, w, keep_idx, block, tm, tn):
    M, N = x2d.shape[0], w.shape[1]
    _validate(x2d.shape[1], w.shape[0], keep_idx, block,
              "block_pruned_matmul")
    tm_e, tn_e = _tile(M, tm, 8), _tile(N, tn, 128)
    xp = _pad_to(x2d, tm_e, 0)
    wp = _pad_to(w, tn_e, 1)
    y = _pk.block_pruned_matmul_2d(
        xp, wp, keep_idx, block=block, tm=tm_e, tn=tn_e,
        interpret=interpret_mode())
    return y[:M, :N]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def block_pruned_matmul(x, w, keep_idx, block: int = 128,
                        tm: int = 256, tn: int = 256):
    """y = x[..., keep] @ w[keep, :] via the Pallas kernel.

    x: [..., K]; w: [K, N]; keep_idx: [kb] int32 sorted block ids.
    """
    *lead, K = x.shape
    x2d = x.reshape(-1, K)
    y = _run_fwd(x2d, w, keep_idx, block, tm, tn)
    return y.reshape(*lead, w.shape[1])


def _fwd(x, w, keep_idx, block, tm, tn):
    y = block_pruned_matmul(x, w, keep_idx, block, tm, tn)
    return y, (x, w, keep_idx)


def _bwd(block, tm, tn, res, dy):
    x, w, keep_idx = res
    *lead, K = x.shape
    N = w.shape[1]
    nb = K // block
    kb = keep_idx.shape[0]
    x2d = x.reshape(-1, K)
    dy2d = dy.reshape(-1, N)
    M = x2d.shape[0]
    order = _inverse_order(keep_idx, nb)
    interp = interpret_mode()

    tm_e, tn_e = _tile(M, tm, 8), _tile(N, tn, 128)
    dyp = _pad_to(_pad_to(dy2d, tm_e, 0), tn_e, 1)
    wp = _pad_to(w, tn_e, 1)
    # dX: dy @ w[kept]^T written straight to the kept column-blocks, pruned
    # blocks zeroed in-kernel (inverse index map — no zeros+scatter)
    dx = _pk.pruned_matmul_dx_2d(
        dyp, wp, order, kb=kb, block=block, tm=tm_e, tn=tn_e,
        interpret=interp)[:M]
    dx = dx.reshape(*lead, K).astype(x.dtype)
    # dW: x[:, kept]^T @ dy at kept row-blocks, pruned rows zeroed in-kernel
    xp = _pad_to(x2d, tm_e, 0)
    dw = _pk.pruned_matmul_dw_2d(
        xp, dyp, order, kb=kb, block=block, tm=tm_e, tn=tn_e,
        interpret=interp)[:, :N].astype(w.dtype)
    return dx, dw, None


block_pruned_matmul.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# fused pruned FFN pair with kernel-level VJP
# ---------------------------------------------------------------------------


def _ffn_fwd_2d(x2d, w_up, w_down, w_gate, keep_idx, act_fn, block, tm):
    M = x2d.shape[0]
    D2 = w_down.shape[1]
    tm_e = _tile(M, tm, 8)
    xp = _pad_to(x2d, tm_e, 0)
    y = _pk.fused_ffn_2d(xp, w_up, w_down, keep_idx, w_gate, act_fn=act_fn,
                         block=block, tm=tm_e, interpret=interpret_mode())
    return y[:M, :D2]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def fused_pruned_ffn(x, w_up, w_down, keep_idx, w_gate=None, act_fn=None,
                     block: int = 128, tm: int = 256):
    """Controlled FFN pair y = act(x @ Wup[:, keep] [, · gate]) @
    Wdown[keep, :] as ONE forward pallas_call.

    x: [..., K]; w_up/w_gate: [K, H]; w_down: [H, d_out]; keep_idx: [kb]
    int32 kept H-block ids. The resized hidden activation exists only as a
    VMEM tile (never written to HBM); the backward recomputes it compactly
    through the out-pruned kernel family.
    """
    *lead, K = x.shape
    _validate(w_up.shape[1], w_down.shape[0], keep_idx, block,
              "fused_pruned_ffn")
    x2d = x.reshape(-1, K)
    y = _ffn_fwd_2d(x2d, w_up, w_down, w_gate, keep_idx, act_fn, block, tm)
    return y.reshape(*lead, w_down.shape[1])


def _ffn_fwd(x, w_up, w_down, keep_idx, w_gate, act_fn, block, tm):
    y = fused_pruned_ffn(x, w_up, w_down, keep_idx, w_gate, act_fn, block, tm)
    return y, (x, w_up, w_down, w_gate, keep_idx)


def _ffn_bwd(act_fn, block, tm, res, dy):
    x, w_up, w_down, w_gate, keep_idx = res
    *lead, K = x.shape
    H = w_up.shape[1]
    D2 = w_down.shape[1]
    nb = H // block
    kb = keep_idx.shape[0]
    x2d = x.reshape(-1, K)
    dy2d = dy.reshape(-1, D2)
    M = x2d.shape[0]
    order = _inverse_order(keep_idx, nb)
    interp = interpret_mode()

    tm_e = _tile(M, tm, 8)
    tk_e = _tile(K, 128, 128)
    tn_e = _tile(D2, 256, 128)
    xp = _pad_to(_pad_to(x2d, tm_e, 0), tk_e, 1)
    wup_p = _pad_to(w_up, tk_e, 0)
    wgate_p = _pad_to(w_gate, tk_e, 0) if w_gate is not None else None
    dyp = _pad_to(_pad_to(dy2d, tm_e, 0), tn_e, 1)
    wdown_p = _pad_to(w_down, tn_e, 1)
    Mp = xp.shape[0]

    # compact recompute of the resized hidden pre-activations (out-pruned
    # kernel: the kept Wup columns stream through the index map)
    pre_up = _pk.outpruned_matmul_2d(
        xp, wup_p, keep_idx, block=block, tm=tm_e, tk=tk_e, interpret=interp)
    if w_gate is not None:
        pre_g = _pk.outpruned_matmul_2d(
            xp, wgate_p, keep_idx, block=block, tm=tm_e, tk=tk_e,
            interpret=interp)

        def _comb(pu, pg):
            return act_fn(pg) * pu

        h, act_vjp = jax.vjp(_comb, pre_up, pre_g)
    else:
        h, act_vjp = jax.vjp(act_fn, pre_up)

    # dWdown: compact h^T @ dy at kept rows, pruned rows zeroed in-kernel
    dw_down = _pk.pruned_matmul_dw_2d(
        h.astype(dyp.dtype), dyp, order, kb=kb, block=block, tm=tm_e,
        tn=tn_e, x_compact=True, interpret=interp)[:, :D2].astype(w_down.dtype)

    # dh (compact): dy @ Wdown[kept]^T — grid covers only kept slots
    dh = _pk.pruned_matmul_dx_2d(
        dyp, wdown_p, keep_idx.astype(jnp.int32), kb=kb, block=block,
        tm=tm_e, tn=tn_e, compact_out=True, interpret=interp)
    dpre = act_vjp(dh.astype(h.dtype))
    if w_gate is not None:
        dpre_up, dpre_g = dpre
    else:
        (dpre_up,) = dpre

    # dWup (and dWgate): x^T @ dpre at kept col-blocks, pruned cols zeroed
    dpre_up = dpre_up.astype(xp.dtype)
    dw_up = _pk.outpruned_matmul_dw_2d(
        xp, dpre_up, order, kb=kb, block=block, tm=tm_e, tk=tk_e,
        interpret=interp)[:K].astype(w_up.dtype)

    # dx: dpre @ Wup[:, kept]^T (dense — all K positions receive grads)
    dx2d = _pk.outpruned_matmul_dx_2d(
        dpre_up, wup_p, keep_idx, block=block, tm=tm_e, tk=tk_e,
        interpret=interp)
    if w_gate is not None:
        dpre_g = dpre_g.astype(xp.dtype)
        dw_gate = _pk.outpruned_matmul_dw_2d(
            xp, dpre_g, order, kb=kb, block=block, tm=tm_e, tk=tk_e,
            interpret=interp)[:K].astype(w_gate.dtype)
        dx2d = dx2d + _pk.outpruned_matmul_dx_2d(
            dpre_g, wgate_p, keep_idx, block=block, tm=tm_e, tk=tk_e,
            interpret=interp)
    else:
        dw_gate = None
    dx = dx2d[:M, :K].reshape(*lead, K).astype(x.dtype)
    return dx, dw_up, dw_down, None, dw_gate


fused_pruned_ffn.defvjp(_ffn_fwd, _ffn_bwd)

# re-export the oracle for convenience
block_pruned_matmul_ref = _ref.block_pruned_matmul_ref


# ---------------------------------------------------------------------------
# fused decode attention (inference-only: no VJP is defined — taking a
# gradient through these raises at trace time, which is the contract)
# ---------------------------------------------------------------------------


def _check_decode_attn(q, k_cache, v_cache, cur_pos):
    B, Hq, S1, _ = q.shape
    Hkv = k_cache.shape[1]
    if S1 != 1:
        raise ValueError(
            f"fused_decode_attention: q {q.shape} must carry exactly one "
            "query token (decode step), got seq len "
            f"{S1}")
    if Hq % Hkv != 0:
        raise ValueError(
            f"fused_decode_attention: Hq={Hq} is not a multiple of "
            f"Hkv={Hkv} (GQA groups must divide evenly)")
    if k_cache.shape[0] != B or v_cache.shape[:3] != k_cache.shape[:3]:
        raise ValueError(
            f"fused_decode_attention: cache shapes k {k_cache.shape} / "
            f"v {v_cache.shape} do not match q batch {B}")
    if cur_pos.shape != (B,):
        raise ValueError(
            f"fused_decode_attention: cur_pos {cur_pos.shape} must be "
            f"[{B}] (one ragged position per slot)")


def _decode_attn_padded(q, k_cache, v_cache, cur_pos):
    """Common GQA padding: (qg [B,Hkv,G',D'], k, v, G, Dv, scale)."""
    B, Hq, _, D = q.shape
    Hkv = k_cache.shape[1]
    Dv = v_cache.shape[3]
    G = Hq // Hkv
    scale = float(1.0 / (D ** 0.5))      # ORIGINAL head dim, pre-padding
    qg = q.reshape(B, Hkv, G, D)
    qg = _pad_to(_pad_to(qg, 8, 2), 128, 3)
    k = _pad_to(_pad_to(k_cache, _dk.TILE_S, 2), 128, 3)
    v = _pad_to(_pad_to(v_cache, _dk.TILE_S, 2), 128, 3)
    return qg, k, v, G, Dv, scale


def fused_decode_attention(q, k_cache, v_cache, *, cur_pos,
                           window: int = 0):
    """Fused GQA decode attention (single pallas_call, online softmax).

    Same contract as ``layers.attention.decode_attention``:
    q [B, Hq, 1, D]; caches [B, Hkv, S, D]/[B, Hkv, S, Dv]; cur_pos [B]
    int32 — attends cache positions p <= cur_pos[b] (windowed if set).
    Returns [B, Hq, 1, Dv] in q.dtype. Inference-only (no VJP).
    """
    _check_decode_attn(q, k_cache, v_cache, cur_pos)
    B, Hq = q.shape[0], q.shape[1]
    Hkv = k_cache.shape[1]
    qg, k, v, G, Dv, scale = _decode_attn_padded(q, k_cache, v_cache,
                                                 cur_pos)
    out = _dk.gqa_decode_attn_2d(
        cur_pos.astype(jnp.int32), qg, k, v, scale=scale,
        window=int(window), interpret=interpret_mode())
    return out[:, :, :G, :Dv].reshape(B, Hq, 1, Dv).astype(q.dtype)


def unfused_decode_attention(q, k_cache, v_cache, *, cur_pos,
                             window: int = 0):
    """The matched-layer UNFUSED baseline: three pallas_calls with the
    [B, Hkv, G, S] score matrix round-tripping HBM. Benchmark baseline
    only (kernel_bench's decode_attn leg) — the serve path uses either
    the fused kernel or the native-XLA oracle."""
    _check_decode_attn(q, k_cache, v_cache, cur_pos)
    B, Hq = q.shape[0], q.shape[1]
    qg, k, v, G, Dv, scale = _decode_attn_padded(q, k_cache, v_cache,
                                                 cur_pos)
    out = _dk.unfused_gqa_decode_attn_2d(
        cur_pos.astype(jnp.int32), qg, k, v, scale=scale,
        window=int(window), interpret=interpret_mode())
    return out[:, :, :G, :Dv].reshape(B, Hq, 1, Dv).astype(q.dtype)


def fused_mla_decode_attention(q_nope_abs, q_rope, latent_cache,
                               rope_cache, *, cur_pos,
                               head_dim_for_scale: int):
    """Fused absorbed-MLA decode attention against the compressed latent.

    Same contract as ``layers.attention.mla_decode_attention``:
    q_nope_abs [B, H, R]; q_rope [B, H, Dr]; latent_cache [B, S, R];
    rope_cache [B, S, Dr]; returns f32 [B, H, R]. Inference-only.
    """
    B, H, R = q_nope_abs.shape
    Dr = q_rope.shape[2]
    if q_rope.shape[:2] != (B, H):
        raise ValueError(
            f"fused_mla_decode_attention: q_rope {q_rope.shape} must "
            f"lead with [B={B}, H={H}]")
    if latent_cache.shape[0] != B or rope_cache.shape[:2] != \
            latent_cache.shape[:2]:
        raise ValueError(
            f"fused_mla_decode_attention: caches latent "
            f"{latent_cache.shape} / rope {rope_cache.shape} do not "
            f"match batch {B}")
    if cur_pos.shape != (B,):
        raise ValueError(
            f"fused_mla_decode_attention: cur_pos {cur_pos.shape} must "
            f"be [{B}]")
    scale = float(1.0 / (head_dim_for_scale ** 0.5))
    qa = _pad_to(_pad_to(q_nope_abs, 8, 1), 128, 2)
    qr = _pad_to(_pad_to(q_rope, 8, 1), 128, 2)
    lat = _pad_to(_pad_to(latent_cache, _dk.TILE_S, 1), 128, 2)
    rope = _pad_to(_pad_to(rope_cache, _dk.TILE_S, 1), 128, 2)
    out = _dk.mla_decode_attn_2d(
        cur_pos.astype(jnp.int32), qa, qr, lat, rope, scale=scale,
        interpret=interpret_mode())
    return out[:, :H, :R]


def fused_paged_decode_attention(q, k_pool, v_pool, *, pages, cur_pos,
                                 window: int = 0):
    """Fused GQA decode attention over the block-paged KV pool.

    q [B, Hq, 1, D]; pools [num_pages, Hkv, page_size, D] /
    [num_pages, Hkv, page_size, Dv]; pages int32 [B, pages_per_slot]
    (-1 = unallocated); cur_pos [B]. Same ragged-position contract as
    ``fused_decode_attention`` — the page table rides scalar prefetch,
    so unallocated pages are never streamed. Returns [B, Hq, 1, Dv].
    """
    B, Hq, S1, D = q.shape
    Hkv, ps = k_pool.shape[1], k_pool.shape[2]
    if S1 != 1:
        raise ValueError(
            f"fused_paged_decode_attention: q {q.shape} must carry "
            "exactly one query token")
    if Hq % Hkv != 0:
        raise ValueError(
            f"fused_paged_decode_attention: Hq={Hq} not a multiple of "
            f"Hkv={Hkv}")
    if ps % 8 != 0:
        raise ValueError(
            f"fused_paged_decode_attention: page_size={ps} must be a "
            "multiple of 8 (f32 sublane tiling) — use the oracle path "
            "or pick a multiple-of-8 --page-size")
    if pages.shape[0] != B or cur_pos.shape != (B,):
        raise ValueError(
            f"fused_paged_decode_attention: pages {pages.shape} / "
            f"cur_pos {cur_pos.shape} do not match q batch {B}")
    Dv = v_pool.shape[3]
    G = Hq // Hkv
    scale = float(1.0 / (D ** 0.5))
    qg = _pad_to(_pad_to(q.reshape(B, Hkv, G, D), 8, 2), 128, 3)
    k = _pad_to(k_pool, 128, 3)
    v = _pad_to(v_pool, 128, 3)
    out = _dk.gqa_paged_decode_attn_2d(
        cur_pos.astype(jnp.int32), pages.astype(jnp.int32), qg, k, v,
        scale=scale, window=int(window), interpret=interpret_mode())
    return out[:, :, :G, :Dv].reshape(B, Hq, 1, Dv).astype(q.dtype)


def fused_paged_mla_decode_attention(q_nope_abs, q_rope, latent_pool,
                                     rope_pool, *, pages, cur_pos,
                                     head_dim_for_scale: int):
    """Fused absorbed-MLA decode attention over the paged latent pool.

    q_nope_abs [B, H, R]; q_rope [B, H, Dr]; pools
    [num_pages, page_size, R] / [num_pages, page_size, Dr]; pages
    [B, pages_per_slot]; returns f32 [B, H, R]. Inference-only.
    """
    B, H, R = q_nope_abs.shape
    ps = latent_pool.shape[1]
    if q_rope.shape[:2] != (B, H):
        raise ValueError(
            f"fused_paged_mla_decode_attention: q_rope {q_rope.shape} "
            f"must lead with [B={B}, H={H}]")
    if ps % 8 != 0:
        raise ValueError(
            f"fused_paged_mla_decode_attention: page_size={ps} must be "
            "a multiple of 8 — use the oracle path or a multiple-of-8 "
            "--page-size")
    if pages.shape[0] != B or cur_pos.shape != (B,):
        raise ValueError(
            f"fused_paged_mla_decode_attention: pages {pages.shape} / "
            f"cur_pos {cur_pos.shape} do not match batch {B}")
    scale = float(1.0 / (head_dim_for_scale ** 0.5))
    qa = _pad_to(_pad_to(q_nope_abs, 8, 1), 128, 2)
    qr = _pad_to(_pad_to(q_rope, 8, 1), 128, 2)
    lat = _pad_to(latent_pool, 128, 2)
    rope = _pad_to(rope_pool, 128, 2)
    out = _dk.mla_paged_decode_attn_2d(
        cur_pos.astype(jnp.int32), pages.astype(jnp.int32), qa, qr,
        lat, rope, scale=scale, interpret=interpret_mode())
    return out[:, :H, :R]
