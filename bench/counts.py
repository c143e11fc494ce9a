"""Operations and HBM bytes of the algorithms the cells run, from shapes alone.

Every count is the algorithm's own work at unpadded widths: lane padding,
bucket padding and recomputation are not counted, so they show up as a lower
share of the roofline or of the peak. f32 data is 4 bytes, bf16 2.

The op functions return (flops, bytes); `prefill` returns FLOPs. `least_time`
turns a pair into the least time a chip can take for it, the larger of its
two bounds.
"""
from __future__ import annotations

import json
from pathlib import Path

F32, BF16 = 4, 2


def load_peaks(device_kind: str) -> dict:
    """The peaks of `device_kind` from bench/peaks.json; an unknown device is
    an error, never a default."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(table)})")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Seconds: max(flops / peak FLOP/s, bytes / peak HBM bandwidth). The
    FLOP bound uses the bf16 peak, the only one published for v5e."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


# ------------------------------------------------------------------ CHEF head
def lr_hvp(n: int, d1: int, c: int):
    """H(w) v over all n rows of Xa [n, d1] (d1 = d + 1): the logits x W^T,
    u = x V^T and the contraction S^T x, each 2 n d1 c; Xa is read once,
    the row weights once."""
    return 6 * n * d1 * c, F32 * (n * d1 + n)


def lr_grad(n: int, d1: int, c: int):
    """Full-batch gradient: logits and (P - Y)^T x; reads Xa, Y, weights."""
    return 4 * n * d1 * c, F32 * (n * d1 + n * c + n)


def infl_scores(n: int, d1: int, c: int):
    """Eq. 6 scores from given P: u = x v^T; reads Xa, P and Y, writes S."""
    return 2 * n * d1 * c, F32 * (n * d1 + 3 * n * c)


def minibatch_grad(bs: int, d1: int, c: int):
    """Gathered mini-batch gradient over bs rows: logits and the contraction;
    reads the bs rows of Xa, their labels and weights."""
    return 4 * bs * d1 * c, F32 * (bs * d1 + bs * c + bs)


def replay_correction(r: int, d1: int, c: int):
    """DeltaGrad-L correction over r changed rows: logits and contraction."""
    return 4 * r * d1 * c, F32 * (r * d1 + 2 * r * c + 2 * r)


# ------------------------------------------------------------------- decoder
def decoder_params(cfg: dict) -> dict:
    """Parameter counts of a dense decoder from its configuration file:
    per layer (attention + MLP) and the (tied) vocabulary matrix."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // hq
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    mlp = (3 if cfg["hidden_act"] == "swiglu" else 2) * d * ff
    return {"layer": attn + mlp, "layers": cfg["num_hidden_layers"],
            "vocab": d * cfg["vocab_size"], "d": d, "hq": hq, "hkv": hkv,
            "hd": hd}


def decode_token(cfg: dict, ctx: int):
    """FLOPs of one decoded token at context length `ctx` (the new token
    included): 2 x parameters (layers and the vocabulary projection) plus
    attention, QK^T and PV, 4 x Hq x D x ctx per layer. Bytes are this
    token's own K/V reads (see paged_attention); the weights are read once
    per step for all slots and are not counted per token."""
    p = decoder_params(cfg)
    attn = 4 * p["hq"] * p["hd"] * ctx * p["layers"]
    return 2 * (p["layer"] * p["layers"] + p["vocab"]) + attn, \
        paged_attention(cfg, ctx)[1]


def paged_attention(cfg: dict, ctx: int, dtype_bytes: int = BF16):
    """Paged decode attention of one slot over `ctx` valid positions, summed
    over layers: FLOPs 4 x Hq x D x ctx (QK^T and PV), bytes the K and V of
    the valid context (2 x Hkv x D x ctx)."""
    p = decoder_params(cfg)
    flops = 4 * p["hq"] * p["hd"] * ctx * p["layers"]
    nbytes = dtype_bytes * 2 * p["hkv"] * p["hd"] * ctx * p["layers"]
    return flops, nbytes


def prefill(cfg: dict, length: int):
    """FLOPs of one unpadded prefill of `length` tokens: 2 x layer
    parameters per token, causal attention (QK^T and PV over the lower
    triangle, 2 x Hq x D x L x (L + 1) per layer) and the vocabulary
    projection of the last position, whose logits the engine returns."""
    p = decoder_params(cfg)
    dense = 2 * p["layer"] * p["layers"] * length
    attn = 2 * p["hq"] * p["hd"] * length * (length + 1) * p["layers"]
    return dense + attn + 2 * p["vocab"]
