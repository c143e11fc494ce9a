"""Pallas kernel: fused Hessian-vector product for the LR head.

Per tile: logits matmul -> softmax -> u = X Vᵀ -> Gauss-Newton middle
(p⊙u − p(p·u)) -> output matmul, accumulated into [C, D]. Three MXU dots per
tile; the Hessian is never materialized. This is the inner loop of both CG
(H⁻¹g) and the power method (Appendices C/D). Weights are an [N, 1] column,
as in lr_grad.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.minibatch_grad import F32


def _kernel(x_ref, w8_ref, w_ref, v_ref, o_ref, *, c_actual: int):
    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    z = jnp.dot(x, w.T, preferred_element_type=jnp.float32,
                precision=F32)
    lane = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    z = jnp.where(lane < c_actual, z, -1e30)
    z = z - jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z)
    p = e / jnp.sum(e, axis=-1, keepdims=True)
    u = jnp.dot(x, v.T, preferred_element_type=jnp.float32,
                precision=F32)
    s = p * u - p * jnp.sum(p * u, axis=-1, keepdims=True)
    s = s * w8_ref[...].astype(jnp.float32)
    contrib = jnp.dot(s.T, x, preferred_element_type=jnp.float32,
                      precision=F32)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += contrib


def lr_hvp_pallas(
    w: jax.Array,  # [C, D]
    v: jax.Array,  # [C, D]
    Xa: jax.Array,  # [N, D]
    weights: jax.Array,  # [N]
    l2: float,
    *,
    block_n: int = 512,
    c_actual: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    N, D = Xa.shape
    C = w.shape[0]
    assert N % block_n == 0
    kernel = functools.partial(_kernel, c_actual=int(c_actual or C))
    raw = pl.pallas_call(
        kernel,
        grid=(N // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
            pl.BlockSpec((C, D), lambda i: (0, 0)),
            pl.BlockSpec((C, D), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((C, D), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((C, D), jnp.float32),
        interpret=interpret,
    )(Xa, weights.reshape(N, 1), w, v)
    return raw / N + l2 * v.astype(jnp.float32)
