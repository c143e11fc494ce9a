"""Pallas kernel: fused INFL (Eq. 6) score matrix.

One MXU matmul per tile (U = X·Vᵀ) + an elementwise epilogue produces the
entire [N, C] score matrix — the sample-selector hot loop that the paper
evaluates per-sample per-class with autodiff.

Tiling: grid over N in blocks of `block_n` rows; X tile [block_n, D] and V
[C, D] live in VMEM (D and C padded to 128-lane multiples by ops.py). The
epilogue reads P/Y tiles [block_n, C].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.minibatch_grad import F32


def _kernel(x_ref, v_ref, p_ref, y_ref, o_ref, *, gamma: float, c_actual: int):
    x = x_ref[...]
    v = v_ref[...]
    u = jnp.dot(
        x.astype(jnp.float32), v.astype(jnp.float32).T,
        preferred_element_type=jnp.float32, precision=F32,
    )  # [BN, C]
    p = p_ref[...].astype(jnp.float32)
    y = y_ref[...].astype(jnp.float32)
    # mask padded classes out of the row reduction
    lane = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    valid = lane < c_actual
    w = jnp.where(valid, y + (1.0 - gamma) * (p - y), 0.0)
    base = jnp.sum(w * u, axis=-1, keepdims=True)
    o_ref[...] = base - u


def infl_scores_pallas(
    v: jax.Array,  # [C, D]
    Xa: jax.Array,  # [N, D]
    P: jax.Array,  # [N, C]
    Y: jax.Array,  # [N, C]
    gamma: float,
    *,
    block_n: int = 512,
    c_actual: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    N, D = Xa.shape
    C = v.shape[0]
    assert N % block_n == 0, (N, block_n)
    grid = (N // block_n,)
    kernel = functools.partial(
        _kernel, gamma=float(gamma), c_actual=int(c_actual or C)
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, D), lambda i: (i, 0)),  # X tile
            pl.BlockSpec((C, D), lambda i: (0, 0)),  # V resident
            pl.BlockSpec((block_n, C), lambda i: (i, 0)),  # P tile
            pl.BlockSpec((block_n, C), lambda i: (i, 0)),  # Y tile
        ],
        out_specs=pl.BlockSpec((block_n, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, C), jnp.float32),
        interpret=interpret,
    )(Xa, v, P, Y)
