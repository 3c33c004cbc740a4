"""Plain float32 reference of the paper's ViT classifier training step
(AdamW), under a given kept-block plan.

Model: 4x4 patches of a 32x32x3 image projected to ``hidden_size``, a
class token, learned positions; pre-norm blocks (RMSNorm scaled by
``1 + scale``) of bidirectional multi-head attention and a GELU (tanh
form) MLP; a final RMSNorm and a linear head on the class token; mean
cross entropy over the batch.

Weights are made again from the seed as the program's initialisation
draws them: ``PRNGKey(seed)`` split six ways (stack, patch projection,
class token, positions, head); layer ``l`` takes the ``l``-th of the keys
split from ``fold_in(stack_key, 2000)``, folds in 0 and splits four ways
(attention, -, -, MLP); each leaf is ``normal * std`` in float32.

The kept-block plan is what ZERO-resizing computes on purpose: on each
tensor-parallel rank ``r`` of ``tp``, only the kept blocks of a scope's
contraction enter its product: ``qkv`` (the model width, for rank r's
columns of wq/wk/wv), ``attn_out`` (rank r's slice of the heads) and
``ffn`` (rank r's slice of the MLP width). Migrated blocks are computed
by other ranks and count as kept. Optimiser: global-norm clipping, then
AdamW with bias correction, warm-up and cosine schedule as configured.

``precision="bf16"`` is the control: parameters, activations and the
update are held in bfloat16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LEAVES = ("patch_proj", "cls", "pos", "norm1", "wq", "wk", "wv", "wo",
          "norm2", "w_up", "w_down", "norm_f", "head")


def _normal(key, shape, std=0.02):
    return jax.random.normal(key, shape, jnp.float32) * std


def init(key, m: dict):
    """Parameters from ``PRNGKey(seed)`` as a flat dict of float32
    leaves; layer leaves are stacked on a leading axis of
    ``num_hidden_layers``."""
    d, ff, L = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    patch_dim = m["patch_size"] ** 2 * m["num_channels"]
    ks = jax.random.split(key, 6)
    out_std = 0.02 / math.sqrt(2 * L)

    def layer(key):
        kb = jax.random.split(jax.random.fold_in(key, 0), 4)
        ka = jax.random.split(kb[0], 8)
        kf = jax.random.split(kb[3], 3)
        return {"norm1": jnp.zeros((d,)), "norm2": jnp.zeros((d,)),
                "wq": _normal(ka[0], (d, d)), "wk": _normal(ka[1], (d, d)),
                "wv": _normal(ka[2], (d, d)),
                "wo": _normal(ka[3], (d, d), out_std),
                "w_up": _normal(kf[0], (d, ff)),
                "w_down": _normal(kf[1], (ff, d), out_std)}

    keys = jax.random.split(jax.random.fold_in(ks[0], 2000), L)
    p = jax.vmap(layer)(keys)          # the same draws as key by key
    p.update(patch_proj=_normal(ks[1], (patch_dim, d)),
             cls=_normal(ks[2], (1, 1, d)),
             pos=_normal(ks[3], (m["tokens"], d), 0.01),
             norm_f=jnp.zeros((d,)),
             head=_normal(ks[4], (d, m["num_labels"])))
    return p


def patchify(images, patch):
    b, h, w, c = images.shape
    x = images.reshape(b, h // patch, patch, w // patch, patch, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, (h // patch) * (w // patch), patch * patch * c)


def masks(m: dict, plan: dict, tp: int) -> dict:
    """0/1 masks of the kept contraction blocks, from a plan given as
    ``{"bucket_by_rank": [tp], "gammas": [...], "pri": {scope: ...},
    "mig_src": [ranks], "mig_shed": [blocks]}``: rank r keeps
    ``kc = max(1, nb - round(gamma * nb))`` blocks, the first in its
    keep-first priority list. A migration source shedding ``m`` FFN
    blocks computes ``pri[:max(1, kc - m)]`` itself and its helpers
    compute the ``m`` blocks from ``min(max(kc - m, 1), nb - m)`` on,
    which is ``pri[:kc]`` whenever ``kc > m``. ``qkv`` -> [tp, d] (one
    list shared by the ranks, each with its own count), ``attn_out`` ->
    [d] (rank slices of the heads), ``ffn`` -> [ff] (rank slices)."""
    d, ff = m["hidden_size"], m["intermediate_size"]
    out = {}
    widths = {"qkv": d, "attn_out": d // tp, "ffn": ff // tp}
    for scope, width in widths.items():
        if plan is None or scope not in plan["pri"]:          # not a controlled scope
            n = tp * width if scope != "qkv" else width
            out[scope] = (np.ones((tp, d), np.float32) if scope == "qkv"
                          else np.ones((n,), np.float32))
            continue
        pri = np.asarray(plan["pri"][scope])
        per_rank = pri.ndim == 1
        nb = pri.shape[-1]
        blk = width // nb
        rows = []
        for r in range(tp):
            g = plan["gammas"][int(plan["bucket_by_rank"][r])]
            kc = max(1, nb - int(round(g * nb)))
            order = pri if per_rank else pri[r]
            mk = np.zeros((nb,), np.float32)
            shed = dict(zip(plan["mig_src"], plan["mig_shed"]))
            if scope == "ffn" and r in shed:
                mg = shed[r]
                start = min(max(kc - mg, 1), nb - mg)
                mk[order[:max(1, min(kc - mg, nb))]] = 1.0
                mk[order[start:start + mg]] = 1.0
            else:
                mk[order[:kc]] = 1.0
            rows.append(np.repeat(mk, blk))
        out[scope] = (np.stack(rows) if scope == "qkv"
                      else np.concatenate(rows))
    return {k: jnp.asarray(v) for k, v in out.items()}


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def loss_fn(p, patches, labels, mk, m, tp):
    H = m["num_attention_heads"]
    d = m["hidden_size"]
    hd = d // H
    eps = m["rms_norm_eps"]
    prec = jax.lax.Precision.HIGHEST
    mm = functools.partial(jnp.einsum, precision=prec)
    dt = p["wq"].dtype
    B = patches.shape[0]
    x = mm("bpk,kd->bpd", patches.astype(dt), p["patch_proj"])
    x = jnp.concatenate([jnp.broadcast_to(p["cls"], (B, 1, d)), x], 1)
    x = x + p["pos"][None]
    S = x.shape[1]

    def proj_cols(h, w):
        # rank r's output columns see only its kept input features
        hm = h[:, :, None, :] * mk["qkv"].astype(dt)[None, None]
        w4 = w.reshape(d, tp, -1)
        return mm("bsrk,krn->bsrn", hm, w4).reshape(B, S, -1)

    def block(x, ly):
        h = _rms(x, ly["norm1"], eps)
        q = proj_cols(h, ly["wq"]).reshape(B, S, H, hd)
        k = proj_cols(h, ly["wk"]).reshape(B, S, H, hd)
        v = proj_cols(h, ly["wv"]).reshape(B, S, H, hd)
        s = mm("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / math.sqrt(hd)
        a = jax.nn.softmax(s, axis=-1).astype(dt)
        o = mm("bhqk,bkhd->bqhd", a, v).reshape(B, S, d)
        x = x + mm("bsk,kn->bsn", o * mk["attn_out"].astype(dt), ly["wo"])
        h = _rms(x, ly["norm2"], eps)
        u = jax.nn.gelu(mm("bsk,kf->bsf", h, ly["w_up"]))
        u = u * mk["ffn"].astype(dt)
        return x + mm("bsf,fd->bsd", u, ly["w_down"]), None

    layer_keys = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_up", "w_down")
    x, _ = jax.lax.scan(block, x, {k: p[k] for k in layer_keys})
    x = _rms(x, p["norm_f"], eps)
    logits = mm("bd,dc->bc", x[:, 0], p["head"]).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], -1))


def lr_at(step, opt):
    warm = min(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    total, w = opt["total_steps"], opt["warmup_steps"]
    cos = 1.0
    if total > w:
        prog = min(max((step - w) / max(total - w, 1), 0.0), 1.0)
        cos = 0.5 * (1.0 + math.cos(math.pi * prog))
    return opt["lr"] * warm * cos


@functools.partial(jax.jit, static_argnames=("mdef", "tp", "opt_def"))
def train_step(p, mu, nu, step, lr, patches, labels, mk, mdef, tp,
               opt_def):
    m, opt = dict(mdef), dict(opt_def)
    loss, g = jax.value_and_grad(loss_fn)(p, patches, labels, mk, m, tp)
    g = {k: v.astype(jnp.float32) for k, v in g.items()}
    gn = jnp.sqrt(sum(jnp.sum(v * v) for v in g.values()))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (gn + 1e-9))
    g = {k: v * scale for k, v in g.items()}
    b1, b2 = opt["beta1"], opt["beta2"]
    t = (step + 1).astype(jnp.float32)
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    mu = {k: b1 * mu[k] + (1 - b1) * g[k] for k in g}
    nu = {k: b2 * nu[k] + (1 - b2) * g[k] * g[k] for k in g}
    new = {k: (p[k].astype(jnp.float32) - lr * (
        (mu[k] / c1) / (jnp.sqrt(nu[k] / c2) + opt["eps"])
        + opt["weight_decay"] * p[k].astype(jnp.float32))).astype(p[k].dtype)
        for k in p}
    return new, mu, nu, loss, g


def run_steps(seed, m, opt, batches, plans, tp, *, precision="f32",
              shardings=None):
    """Three (or ``len(batches)``) steps from the seed's initial weights.

    Returns (losses, per-leaf norm of the first clipped gradient,
    per-leaf norm of the parameters' change after the last step), each
    a dict of floats keyed by leaf name (losses: a list). ``shardings``
    maps the parameters' shapes to where they are made and kept."""
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    # the key is the program's argument, so one compile serves every seed
    make = lambda key: init(key, m)
    key = jax.random.PRNGKey(seed)
    out_sh = (shardings(jax.eval_shape(make, key)) if shardings is not None
              else None)
    p0 = jax.jit(make, out_shardings=out_sh)(key)
    p = {k: v.astype(dt) for k, v in p0.items()}
    mu = {k: jnp.zeros_like(v, jnp.float32) for k, v in p0.items()}
    nu = {k: jnp.zeros_like(v, jnp.float32) for k, v in p0.items()}
    mdef = tuple(sorted(m.items()))
    odef = tuple(sorted(opt.items()))
    losses, g1 = [], None
    plans = list(plans) + [None] * (len(batches) - len(plans))
    for i, (b, plan) in enumerate(zip(batches, plans)):
        patches = jnp.asarray(patchify(b["images"], m["patch_size"]))
        with jax.default_matmul_precision(
                "highest" if precision == "f32" else "default"):
            p, mu, nu, loss, g = train_step(
                p, mu, nu, jnp.int32(i), jnp.float32(lr_at(i, opt)),
                patches, jnp.asarray(b["labels"]), masks(m, plan, tp),
                mdef, tp, odef)
        losses.append(float(loss))
        if i == 0:
            g1 = {k: float(jnp.linalg.norm(v.ravel())) for k, v in g.items()}
        del g
    change = {k: float(jnp.linalg.norm(
        (p[k].astype(jnp.float32) - p0[k]).ravel())) for k in p}
    return losses, g1, change
