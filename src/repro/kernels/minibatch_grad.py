"""Pallas kernel: fused gather + mini-batch LR-head gradient.

The constructor-phase hot op (paper Eq. 4, left term): every SGD training
step and every explicit DeltaGrad-L iteration computes

    g = (1/|B_t|) Σ_{i in B_t} γ_i (p_i − y_i) x̃_iᵀ + λ w

over a *gathered* mini-batch B_t = Xa[idx]. The kernel gathers the batch
rows of Xa itself and runs the logits matmul -> masked softmax -> weighted
residual -> gradient matmul epilogue on them, so the gathered [bs, d+1]
batch never round-trips through HBM between the gather and the two MXU dots.

Row gather: Xa stays in HBM (`memory_space=pl.ANY`) as an [N, 1, D] view
(`ops.row_gather_layout`, built once per scan by the caller) — one row per
leading index, so a single row is a whole (1, D) tile and one DMA moves it.
The batch ids are scalar-prefetched into SMEM; each grid step issues one
row DMA per batch slot of its `block_b`-row chunk into a VMEM
scratch, waits for them, and folds the chunk's contribution into the
resident [C, D] output. Only the batch rows ever enter VMEM, whatever N is.
The labels and weights of the batch ([bs, C] and [bs], tiny) arrive already
gathered by the caller.

Bit-parity contract: with one chunk (`block_b` == bs, what ops.py uses in
interpret mode) the body computes the same floating-point values as
`lr_head.minibatch_grad_reference` (same rows, same softmax algorithm, same
einsum contraction, one chunk summed in the same order, same divide/add
order; the HIGHEST-precision dots are plain f32 dots on CPU), so
reference / pallas / pallas_sharded produce bit-identical SGD trajectories
(asserted in tests/test_backend.py) — the property the DeltaGrad-L replay
parity rests on. Several chunks (the compiled TPU path) sum per-chunk partial gradients,
which reorders the batch sum: equal to the reference within f32 rounding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# f32 contractions at full f32 precision: Mosaic's default contracts f32
# operands in bf16 MXU passes (~2^-8 relative per product)
F32 = jax.lax.Precision.HIGHEST


def gather_rows_dma(idx_ref, x_hbm, xb, sem, base: int | jax.Array,
                    n_rows: int):
    """DMA rows `idx_ref[base : base + n_rows]` of the [N, 1, D] HBM array
    `x_hbm` into the [n_rows, 1, D] VMEM scratch `xb`: start every copy, then
    wait for all of them (the copies run concurrently). Shared by the
    mini-batch gradient and the DeltaGrad replay-correction kernels."""
    def start(r, carry):
        pltpu.make_async_copy(x_hbm.at[idx_ref[base + r]], xb.at[r],
                              sem.at[0]).start()
        return carry

    def wait(r, carry):
        # every copy moves one (1, D) row, so any row-sized descriptor
        # waits for exactly one of them
        pltpu.make_async_copy(x_hbm.at[0], xb.at[r], sem.at[0]).wait()
        return carry

    jax.lax.fori_loop(0, n_rows, start, 0)
    jax.lax.fori_loop(0, n_rows, wait, 0)


def _kernel(idx_ref, x_hbm, y_ref, w8_ref, w_ref, o_ref, xb, sem, *,
            l2: float, n_batch: int, c_actual: int, block_b: int):
    c = pl.program_id(0)
    gather_rows_dma(idx_ref, x_hbm, xb, sem, c * block_b, block_b)
    x = xb[...].reshape(block_b, xb.shape[-1])  # [bb, D]
    w = w_ref[...]
    z = jnp.dot(x, w.T, precision=F32)  # [bb, C]
    # mask padded class lanes out of the softmax (no-op when unpadded:
    # where(True, z, ...) returns z bitwise, preserving reference parity)
    lane = jax.lax.broadcasted_iota(jnp.int32, z.shape, 1)
    z = jnp.where(lane < c_actual, z, -1e30)
    p = jax.nn.softmax(z.astype(jnp.float32), axis=-1)
    g = jnp.einsum("nc,nd->cd", (p - y_ref[...]) * w8_ref[...], x,
                   precision=F32)

    @pl.when(c == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += g

    @pl.when(c == pl.num_programs(0) - 1)
    def _finish():
        o_ref[...] = o_ref[...] / n_batch + l2 * w.astype(jnp.float32)


def minibatch_grad_pallas(
    w: jax.Array,  # [C, D]
    Xr: jax.Array,  # [N, 1, D] row-gather view of Xa
    yb: jax.Array,  # [bs, C] labels of the batch rows (already gathered)
    wb: jax.Array,  # [bs] weights of the batch rows (already gathered)
    idx: jax.Array,  # [bs] int32 row ids into Xr
    l2: float,
    *,
    n_batch: int | None = None,
    c_actual: int | None = None,
    block_b: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused Xa-row gather + batch gradient; returns [C, D] f32.

    `block_b` rows are gathered per grid step (default: the whole batch);
    bs must be a multiple of it. `n_batch` is the true mini-batch size used
    as the 1/|B_t| divisor — it differs from bs only when ops.py padded the
    batch (padded slots carry weight 0, an exact-zero contribution)."""
    C, D = w.shape
    bs = idx.shape[0]
    bb = int(block_b or bs)
    assert bs % bb == 0, (bs, bb)
    kernel = functools.partial(
        _kernel, l2=float(l2), n_batch=int(n_batch or bs),
        c_actual=int(c_actual or C), block_b=bb,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # idx drives the row DMAs
        grid=(bs // bb,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # Xa rows stay in HBM
            pl.BlockSpec((bb, yb.shape[1]), lambda c, ids: (c, 0)),
            pl.BlockSpec((bb, 1), lambda c, ids: (c, 0)),
            pl.BlockSpec((C, D), lambda c, ids: (0, 0)),
        ],
        out_specs=pl.BlockSpec((C, D), lambda c, ids: (0, 0)),
        scratch_shapes=[pltpu.VMEM((bb, 1, D), Xr.dtype),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, D), jnp.float32),
        interpret=interpret,
    )(idx, Xr, yb, wb.reshape(bs, 1), w)
