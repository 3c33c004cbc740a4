"""Plain float32 reference of a Llama-style decoder LM (Yi-6B's block):
pre-norm RMSNorm, rotary attention with grouped KV heads, SwiGLU FFN,
untied output head.

Weights are made again from the seed, leaf by leaf, as the program's
initialisation draws them: ``PRNGKey(seed)`` split four ways (stack,
embedding, head); layer ``l`` takes the ``l``-th of 32 keys split from
``fold_in(stack_key, 2000)``, folds in 0, splits four ways (attention,
-, -, FFN); each leaf is ``normal * std`` in float32, rounded to the
served dtype. Norm scales start at zero and multiply as ``1 + scale``.
The forward pass then runs in float32 at ``highest`` matmul precision.

``quant="int8"`` is the control: every matmul takes int8 inputs,
weights per output channel and activations per row, symmetric.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def dims(m: dict) -> dict:
    H = m["num_attention_heads"]
    d = m["hidden_size"]
    return {"d": d, "H": H, "KV": m["num_key_value_heads"],
            "hd": m.get("head_dim") or d // H, "ff": m["intermediate_size"],
            "L": m["num_hidden_layers"], "V": m["vocab_size"],
            "theta": float(m["rope_theta"]), "eps": float(m["rms_norm_eps"])}


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def top_keys(seed: int, L: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    layer = jax.random.split(jax.random.fold_in(ks[0], 2000), L)
    return ks, layer


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _leaf(key, shape, dtype):
    return _normal(key, shape, 0.02, dtype).astype(jnp.float32)


def embed_and_head(seed: int, m: dict, dtype):
    k = dims(m)
    ks, _ = top_keys(seed, k["L"])
    return (_leaf(ks[1], (k["V"], k["d"]), dtype),
            _leaf(ks[2], (k["d"], k["V"]), dtype))


@functools.partial(jax.jit, static_argnames=("mk", "dtype"))
def layer_weights(key_l, mk, dtype):
    """One layer's weights in float32, from its key. ``mk`` is
    ``tuple(sorted(dims(m).items()))``."""
    k = dict(mk)
    d, H, KV, hd, ff, L = k["d"], k["H"], k["KV"], k["hd"], k["ff"], k["L"]
    kb = jax.random.split(jax.random.fold_in(key_l, 0), 4)
    ka = jax.random.split(kb[0], 8)
    kf = jax.random.split(kb[3], 3)
    out_std = 0.02 / math.sqrt(2 * L)
    w = {"wq": _normal(ka[0], (d, H * hd), 0.02, dtype),
         "wk": _normal(ka[1], (d, KV * hd), 0.02, dtype),
         "wv": _normal(ka[2], (d, KV * hd), 0.02, dtype),
         "wo": _normal(ka[3], (H * hd, d), out_std, dtype),
         "w_up": _normal(kf[0], (d, ff), 0.02, dtype),
         "w_down": _normal(kf[1], (ff, d), out_std, dtype),
         "w_gate": _normal(kf[2], (d, ff), 0.02, dtype)}
    return {n: v.astype(jnp.float32) for n, v in w.items()}


def _q8(x, axis):
    """Symmetric int8 rounding along ``axis`` (scale max|x| / 127)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, quant):
    if quant == "int8":
        x = _q8(x, -1)
        w = _q8(w, 0)
    return jnp.einsum("...k,kn->...n", x, w,
                      precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope(x, pos, theta):
    """x [N, T, h, D]; rotates the two halves of D (the program's and
    the published model's convention)."""
    D = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("mk", "quant"))
def layer_apply(w, x, mk, quant=None):
    """One pre-norm block over rows ``x`` [N, T, d], causal from 0."""
    k = dict(mk)
    N, T, d = x.shape
    H, KV, hd = k["H"], k["KV"], k["hd"]
    G = H // KV
    pos = jnp.arange(T)
    h = rms_norm(x, k["eps"])
    q = rope(_mm(h, w["wq"], quant).reshape(N, T, H, hd), pos, k["theta"])
    kk = rope(_mm(h, w["wk"], quant).reshape(N, T, KV, hd), pos, k["theta"])
    v = _mm(h, w["wv"], quant).reshape(N, T, KV, hd)
    q = q.reshape(N, T, KV, G, hd)
    s = jnp.einsum("ntkgd,nskd->nkgts", q, kk,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nkgts,nskd->ntkgd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(N, T, H * hd)
    x = x + _mm(o, w["wo"], quant)
    h = rms_norm(x, k["eps"])
    f = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(f, w["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head_logits(x, head, eps, quant=None):
    return _mm(rms_norm(x, eps), head, quant)


def logits_at(seed: int, m: dict, dtype, seqs, want, *, rows: int = 4,
              quant=None) -> np.ndarray:
    """Logits [sum(len(w)), V] at the positions ``want[i]`` of each token
    sequence ``seqs[i]``, layer by layer, ``rows`` sequences at a time.

    Sequences are padded at the end to one length (a multiple of 128):
    attention is causal, so padding changes no earlier position."""
    k = dims(m)
    mk = tuple(sorted(k.items()))
    T = max(len(s) for s in seqs)
    T = -(-T // 128) * 128
    n = -(-len(seqs) // rows) * rows
    toks = np.zeros((n, T), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    embed, head = embed_and_head(seed, m, dtype)
    xs = [jnp.take(embed, jnp.asarray(toks[i:i + rows]), axis=0)
          for i in range(0, n, rows)]
    del embed
    _, keys = top_keys(seed, k["L"])
    for l in range(k["L"]):
        w = layer_weights(keys[l], mk, dtype)
        xs = [layer_apply(w, x, mk, quant) for x in xs]
        del w
    out = []
    for b, x in enumerate(xs):
        for j in range(rows):
            i = b * rows + j
            if i < len(seqs) and len(want[i]):
                rows_x = x[j, jnp.asarray(want[i])]
                out.append(np.asarray(head_logits(rows_x, head, k["eps"],
                                                  quant)))
    return np.concatenate(out, 0)
