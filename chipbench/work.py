"""Work the models need, counted from their shapes.

These count what the model's mathematics requires, not what an
implementation executes: padded lanes, recomputation, discarded logits
and repeated weight reads do not count. A change that removes wasted
work therefore raises a share of peak and never changes these counts.
A multiply-add is two FLOPs.
"""
from __future__ import annotations


# -- decoder LM (the configuration file's published keys) ---------------------

def lm_dims(m: dict) -> dict:
    H, KV = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"]
    hd = m.get("head_dim") or d // H
    return {"d": d, "H": H, "KV": KV, "hd": hd, "ff": m["intermediate_size"],
            "L": m["num_hidden_layers"], "V": m["vocab_size"]}


def lm_token_flops(m: dict) -> float:
    """Projections and gated FFN of one token through every layer
    (attention over the context is :func:`lm_attn_flops`)."""
    k = lm_dims(m)
    d, H, KV, hd, ff = k["d"], k["H"], k["KV"], k["hd"], k["ff"]
    proj = 2 * d * (H * hd + 2 * KV * hd) + 2 * H * hd * d
    ffn = 3 * 2 * d * ff
    return float(k["L"] * (proj + ffn))


def lm_attn_flops(m: dict, keys: float) -> float:
    """q.K and p.V over ``keys`` attended positions, summed over queries
    (pass the sum of context lengths), through every layer."""
    k = lm_dims(m)
    return float(k["L"] * 2 * 2 * k["H"] * k["hd"] * keys)


def lm_attn_bytes(m: dict, keys: float, kv_bytes: int = 2) -> float:
    """K and V bytes that attention must read for ``keys`` attended
    positions (summed over queries), through every layer."""
    k = lm_dims(m)
    return float(k["L"] * 2 * k["KV"] * k["hd"] * kv_bytes * keys)


def lm_head_flops(m: dict) -> float:
    """Output projection for one token whose logits are used."""
    k = lm_dims(m)
    return float(2 * k["d"] * k["V"])


# -- ViT classifier (paper Sec. V-A) -----------------------------------------

def vit_forward_flops(m: dict) -> float:
    """Forward FLOPs of one image: patch projection, every block's
    projections, attention and MLP at ``tokens`` positions, and the
    class head on the class token."""
    d, ff, L = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    S = m["tokens"]
    patch_dim = m["patch_size"] ** 2 * m["num_channels"]
    per_token_layer = 4 * 2 * d * d + 2 * 2 * d * ff
    attn_layer = 2 * 2 * S * S * d
    return float((S - 1) * 2 * patch_dim * d
                 + L * (S * per_token_layer + attn_layer)
                 + 2 * d * m["num_labels"])


def vit_train_flops(m: dict) -> float:
    """Forward and backward of one image: three times the forward."""
    return 3.0 * vit_forward_flops(m)
