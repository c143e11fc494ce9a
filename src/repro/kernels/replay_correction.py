"""Pallas kernel: fused DeltaGrad-L replay correction (paper Eq. 4, right
term, adapted for label cleaning in Section 4.2).

Per replay iteration the updated mini-batch gradient is the cached/estimated
old-batch gradient plus a correction over ONLY the changed samples in B_t:

    (1/|B_t|) Σ_{i in R∩B_t} [ 1·∇F(w, z_i^new) − γ·∇F(w, z_i^old) ]

This kernel fuses the Xa row gather (the r_max changed slots of the
iteration, ids `ci` padded with 0, real entries flagged by `cm`) with ONE
shared logits+softmax and both residual branches — the old/new label pair
reuses p_i, so the whole correction is one [r, D]x[D, C] dot, one softmax,
and one [C, r]x[r, D] dot. The Xa rows are DMA'd from HBM by the same
scalar-prefetched row gather as minibatch_grad.py; the slots' old/new labels
and weights ([r, C] and [r], tiny) arrive already gathered by the caller.

Bit-parity contract: same floating-point program as
`deltagrad.replay_correction_reference` (see minibatch_grad.py for why that
matters); ops.py keeps it unpadded in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.minibatch_grad import F32, gather_rows_dma


def _kernel(ci_ref, x_hbm, yo_ref, yn_ref, wo_ref, wn_ref, cm_ref, w_ref,
            o_ref, xb, sem, *, batch_size: int, c_actual: int):
    r = xb.shape[0]
    gather_rows_dma(ci_ref, x_hbm, xb, sem, 0, r)
    xb_ = xb[...].reshape(r, xb.shape[-1])  # [r, D]
    cm = cm_ref[...]  # [r, 1]
    w = w_ref[...]
    z = jnp.dot(xb_, w.T, precision=F32)
    lane = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    z = jnp.where(lane < c_actual, z, -1e30)
    p = jax.nn.softmax(z.astype(jnp.float32), axis=-1)
    g_new = (p - yn_ref[...]) * (wn_ref[...] * cm)
    g_old = (p - yo_ref[...]) * (wo_ref[...] * cm)
    o_ref[...] = jnp.einsum("nc,nd->cd", g_new - g_old, xb_,
                            precision=F32) / batch_size


def replay_correction_pallas(
    w: jax.Array,  # [C, D]
    Xa: jax.Array,  # [N, D]
    yo: jax.Array,  # [r, C] old probabilistic labels of the slots
    yn: jax.Array,  # [r, C] cleaned labels of the slots
    wo: jax.Array,  # [r] old per-sample weights (γ) of the slots
    wn: jax.Array,  # [r] new per-sample weights (1) of the slots
    ci: jax.Array,  # [r] int32 changed-sample ids (padded with 0)
    cm: jax.Array,  # [r] f32 1 for real entries, 0 for padding
    batch_size: int,
    *,
    c_actual: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused Xa-row gather + correction; returns [C, D] f32. Padded slots
    (cm == 0) contribute exactly zero, so ci row padding is free."""
    C, D = w.shape
    r = ci.shape[0]
    kernel = functools.partial(
        _kernel, batch_size=int(batch_size), c_actual=int(c_actual or C)
    )
    col = pl.BlockSpec((r, 1), lambda i, ids: (0, 0))
    lab = pl.BlockSpec((r, yo.shape[1]), lambda i, ids: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # ci drives the row DMAs
        grid=(1,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # Xa rows stay in HBM
            lab, lab, col, col, col,
            pl.BlockSpec((C, D), lambda i, ids: (0, 0)),
        ],
        out_specs=pl.BlockSpec((C, D), lambda i, ids: (0, 0)),
        scratch_shapes=[pltpu.VMEM((r, 1, D), Xa.dtype),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, D), jnp.float32),
        interpret=interpret,
    )(ci, Xa.reshape(Xa.shape[0], 1, D), yo, yn, wo.reshape(r, 1),
      wn.reshape(r, 1), cm.reshape(r, 1), w)
