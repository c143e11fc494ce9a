"""Plain reference of a dense OLMo-style decoder: f32 jax.numpy at HIGHEST
precision, written from the OLMo paper (arXiv:2402.00838) and independent of
the program under test.

  x = embed[tokens]
  per layer:  h = LN(x);  x += Attn(h) W_o;  h = LN(x);  x += (silu(h W_g) * h W_i) W_o
  logits = LN(x) embed^T          (tied embeddings; LN has no scale or bias)

Attention is causal multi-head attention with rotary positions on q and k
(rotate-half form, theta from the configuration) and 1/sqrt(D) scaling.

Weights come in the layout the benchmark makes them (bench/weights.py):
`embed.tok` [V, d], and per layer, stacked on a leading layer axis,
`attn.wq|wk|wv` [d, H, D], `attn.wo` [H, D, d], `mlp.wi|wg` [d, F],
`mlp.wo` [F, d]. They are upcast to f32 one layer at a time, so the
reference holds the served bf16 weights plus one layer in f32.

`dtype` selects the weights' precision: "float32" is the reference, and
"float8_e4m3fn" is the control, each weight matrix quantized per output
column to e4m3 with a scale of max|w| / 448 and then computed in f32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def layer_norm(x, eps: float = 1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def rope(x, pos, theta: float):
    """x [S, H, D] rotated at positions pos [S] (rotate-half pairing)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def quantize_e4m3(w, axis: int):
    """w in f32 after a round trip through e4m3 with one scale per slice
    along every axis but `axis` (the contraction axis)."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def _weights(w, dtype: str, axis: int):
    if dtype == "float32":
        return w.astype(jnp.float32)
    if dtype == "float8_e4m3fn":
        return quantize_e4m3(w, axis)
    raise ValueError(dtype)


def _layer(x, lp, pos, theta: float, dtype: str):
    S, d = x.shape
    wq = _weights(lp["attn"]["wq"], dtype, 0)
    wk = _weights(lp["attn"]["wk"], dtype, 0)
    wv = _weights(lp["attn"]["wv"], dtype, 0)
    wo = _weights(lp["attn"]["wo"], dtype, (0, 1))
    h = layer_norm(x)
    q = rope(jnp.einsum("sd,dhk->shk", h, wq, precision=HIGHEST), pos, theta)
    k = rope(jnp.einsum("sd,dhk->shk", h, wk, precision=HIGHEST), pos, theta)
    v = jnp.einsum("sd,dhk->shk", h, wv, precision=HIGHEST)
    D = q.shape[-1]
    s = jnp.einsum("qhk,shk->hqs", q, k, precision=HIGHEST) * D ** -0.5
    causal = pos[:, None] >= pos[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hqs,shk->qhk", p, v, precision=HIGHEST)
    x = x + jnp.einsum("qhk,hkd->qd", o, wo, precision=HIGHEST)
    h = layer_norm(x)
    wi = _weights(lp["mlp"]["wi"], dtype, 0)
    wg = _weights(lp["mlp"]["wg"], dtype, 0)
    wo2 = _weights(lp["mlp"]["wo"], dtype, 0)
    a = jnp.dot(h, wi, precision=HIGHEST)
    g = jnp.dot(h, wg, precision=HIGHEST)
    return x + jnp.dot(jax.nn.silu(g) * a, wo2, precision=HIGHEST)


@partial(jax.jit, static_argnames=("theta", "dtype"))
def forward(weights, tokens, *, theta: float, dtype: str = "float32"):
    """Logits [S, V] of one sequence `tokens` [S] at every position."""
    S = tokens.shape[0]
    pos = jnp.arange(S)
    tok = weights["embed"]["tok"]
    x = tok[tokens].astype(jnp.float32)

    def body(x, lp):
        return _layer(x, lp, pos, theta, dtype), None

    x, _ = jax.lax.scan(body, x, weights["layers"])
    emb = _weights(tok, dtype, 1)
    return jnp.dot(layer_norm(x), emb.T, precision=HIGHEST)


def served_gaps(logits, served, first: int):
    """Per served token: how far its reference logit lies below the
    reference's best at the position that produced it. `served` [n] are the
    tokens the program emitted after the prompt; position first + j produced
    served[j]."""
    rows = logits[first:first + served.shape[0]]
    return jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, served[:, None], axis=-1)[:, 0]


def control_gaps(ref_logits, ctl_logits, first: int, n: int):
    """Per position: how far below the reference's best lies the token that
    the control puts first."""
    r = ref_logits[first:first + n]
    c = ctl_logits[first:first + n]
    pick = jnp.argmax(c, axis=-1)
    return jnp.max(r, axis=-1) - jnp.take_along_axis(r, pick[:, None],
                                                     axis=-1)[:, 0]
