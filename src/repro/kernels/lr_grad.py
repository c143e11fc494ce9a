"""Pallas kernel: fused LR-head batch gradient.

Per tile of `block_n` samples: logits matmul -> masked softmax -> weighted
residual -> gradient contribution matmul, accumulated into the [C, D] output
across the (sequential) grid. Two MXU dots per tile, nothing materialized in
HBM except the final [C, D] gradient.

The per-row weights arrive as an [N, 1] column (block (block_n, 1)): a 1-D
(block_n,) block has a different tiled layout in Mosaic than in XLA and is
refused by the TPU compiler.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.minibatch_grad import F32


def _kernel(x_ref, y_ref, w8_ref, w_ref, o_ref, *, c_actual: int):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    z = jnp.dot(x, w.T, preferred_element_type=jnp.float32,
                precision=F32)  # [BN, C]
    lane = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    z = jnp.where(lane < c_actual, z, -1e30)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    r = (p - y_ref[...].astype(jnp.float32)) * w8_ref[...].astype(jnp.float32)
    contrib = jnp.dot(r.T, x, preferred_element_type=jnp.float32,
                      precision=F32)  # [C, D]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += contrib


def lr_grad_pallas(
    w: jax.Array,  # [C, D]
    Xa: jax.Array,  # [N, D]
    Y: jax.Array,  # [N, C]
    weights: jax.Array,  # [N]
    l2: float,
    *,
    block_n: int = 512,
    c_actual: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    N, D = Xa.shape
    C = w.shape[0]
    assert N % block_n == 0, (N, block_n)
    kernel = functools.partial(_kernel, c_actual=int(c_actual or C))
    raw = pl.pallas_call(
        kernel,
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
            pl.BlockSpec((block_n, C), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((C, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((C, D), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((C, D), jnp.float32),
        interpret=interpret,
    )(Xa, Y, weights.reshape(N, 1), w)
    return raw / N + l2 * w.astype(jnp.float32)
