"""Pallas flash-attention forward (GQA + causal + sliding window + softcap).

Grid (B, Hq, nq, nk) — the KV dim is innermost/sequential ("arbitrary"
semantics on TPU) so the online-softmax running max/denominator live in VMEM
scratch that persists across KV steps; the output block is revisited and
rescaled in place, then normalized on the last KV step.

Block sizes default to (128, 128): MXU-aligned, and the working set
(q, k, v, scores, acc tiles) stays well under VMEM. Compiled for TPU, a
block must be a multiple of 128 or the whole sequence (the kpos row block
sits on lanes); `ops._flash_adapt` pads sequences that need it.

GQA is expressed in the k/v BlockSpec index maps (h // group) — no repeated
K/V materialization.

Bit-parity contract (`Backend.flash_attention`): `_kv_block_step` is the
per-(q-block, kv-block) program of the kernel body, and
`flash_attention_reference` scans the *same* function over the same block
decomposition — reference / pallas(interpret) produce bit-identical outputs
(asserted in tests/test_serving.py), and the head-sharded pallas_sharded
form is exact because every (b, h, q-block) cell is independent.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kv_block_step(carry, q, k, v, qp, kp, *, scale: float, causal: bool,
                   window: int, softcap: float):
    """One online-softmax KV step: q [BQ, D]; k, v [BK, D] -> new carry.

    Shared verbatim by the Pallas kernel body and the jnp reference scan —
    any edit here changes both sides of the bit-parity contract together."""
    m_prev, l_prev, acc_prev = carry
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [BQ, BK]
    if softcap:
        # reciprocal-multiply, not division: jit rewrites x / const to
        # x * (1/const) while eager mode divides — the mul form is the one
        # program both execution modes agree on bitwise
        s = softcap * jnp.tanh(s * (1.0 / softcap))
    mask = jnp.ones(s.shape, bool)
    if causal:
        mask &= qp[:, None] >= kp[None, :]
    if window:
        mask &= qp[:, None] - kp[None, :] < window
    s = jnp.where(mask, s, NEG_INF)

    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    acc = acc_prev * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return m_new, l_new, acc


def _kernel(
    qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int, softcap: float, nk: int,
):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)  # [BQ, D]
    k = k_ref[0, 0].astype(jnp.float32)  # [BK, D]
    v = v_ref[0, 0].astype(jnp.float32)  # [BK, D]
    m_new, l_new, acc = _kv_block_step(
        (m_scr[...], l_scr[...], acc_scr[...]), q, k, v,
        qpos_ref[:, 0], kpos_ref[0, :],
        scale=scale, causal=causal, window=window, softcap=softcap,
    )
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc / jnp.maximum(l_new, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # [B, Hq, Sq, D]
    k: jax.Array,  # [B, Hkv, Skv, D]
    v: jax.Array,  # [B, Hkv, Skv, D]
    qpos: jax.Array,  # [Sq] int32
    kpos: jax.Array,  # [Skv] int32
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused GQA flash-attention forward; returns [B, Hq, Sq, D] in q.dtype."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    nq, nk = Sq // block_q, Skv // block_k
    kernel = functools.partial(
        _kernel, scale=D**-0.5, causal=causal, window=window,
        softcap=float(softcap), nk=nk,
    )
    grid = (B, Hq, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda b, h, qi, ki: (qi, 0)),  # qpos
            pl.BlockSpec((1, block_k), lambda b, h, qi, ki: (0, ki)),  # kpos
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention",
    )(*position_blocks(qpos, kpos), q, k, v)


def position_blocks(qpos, kpos):
    """Positions in the layout the attention kernels block them in: qpos
    as an [Sq, 1] column (block (block_q, 1)), kpos as a [1, Skv] row
    (block (1, block_k)). A 1-D (block,) position block has a different
    tiled layout in Mosaic than in XLA and is refused by the TPU compiler;
    the kernels slice the blocks back to 1-D vectors, so the shared
    `_kv_block_step` program is unchanged."""
    return qpos.reshape(-1, 1), kpos.reshape(1, -1)


def flash_attention_reference(
    q: jax.Array,  # [B, Hq, Sq, D]
    k: jax.Array,  # [B, Hkv, Skv, D]
    v: jax.Array,  # [B, Hkv, Skv, D]
    qpos: jax.Array,
    kpos: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Pure-jnp mirror of the kernel's blocked online-softmax program.

    Same block decomposition, same `_kv_block_step` per (q-block, kv-block),
    same final normalize — the `reference` form of `Backend.flash_attention`
    is therefore bit-identical to the interpret-mode kernel, and exact for
    the head-sharded form too (per-head independence). The GQA head gather
    (`h // G`) is expressed as an exact `jnp.take` instead of BlockSpec
    index maps."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    nq, nk = Sq // block_q, Skv // block_k
    step = functools.partial(_kv_block_step, scale=D**-0.5, causal=causal,
                             window=window, softcap=float(softcap))
    qpos_b = qpos.reshape(nq, block_q)
    kpos_b = kpos.reshape(nk, block_k)

    def head_cell(qh, kh, vh):
        # qh [Sq, D]; kh, vh [Skv, D] — one (b, h) column of the grid
        qb = qh.reshape(nq, block_q, D)
        kb = kh.reshape(nk, block_k, D)
        vb = vh.reshape(nk, block_k, D)

        def q_block(qx):
            qi, qp = qx

            def kv_step(carry, kx):
                ki, vi, kp = kx
                return step(carry, qi, ki, vi, qp, kp), None

            init = (jnp.full((block_q,), NEG_INF, jnp.float32),
                    jnp.zeros((block_q,), jnp.float32),
                    jnp.zeros((block_q, D), jnp.float32))
            (_, l_f, acc), _ = jax.lax.scan(kv_step, init, (kb, vb, kpos_b))
            return (acc / jnp.maximum(l_f, 1e-30)[:, None]).astype(q.dtype)

        return jax.lax.map(q_block, (qb, qpos_b)).reshape(Sq, D)

    # lax.map over the flattened (B, Hkv) grid with an inner map over the G
    # query heads of each kv head — NOT vmap (vmap would batch the per-cell
    # dots into one dot_general, whose XLA lowering can differ by an ulp
    # from the interpreter's per-cell dots for degenerate block shapes; see
    # decode_attention_reference), and NOT a take-expanded [B, Hq, Skv, D]
    # K/V (a G-fold memory blowup the kernel's BlockSpec h // G avoids).
    # Every head_cell call sees the same [Sq, D] x [Skv, D] shapes either
    # way, so the floating-point program is unchanged.
    qg = q.astype(jnp.float32).reshape(B * Hkv, G, Sq, D)
    kf = k.astype(jnp.float32).reshape(B * Hkv, Skv, D)
    vf = v.astype(jnp.float32).reshape(B * Hkv, Skv, D)

    def kv_head_cell(t):
        qh, kh, vh = t  # [G, Sq, D], [Skv, D], [Skv, D]
        return jax.lax.map(lambda qx: head_cell(qx, kh, vh), qh)

    out = jax.lax.map(kv_head_cell, (qg, kf, vf))
    return out.reshape(B, Hkv, G, Sq, D).reshape(B, Hq, Sq, D).astype(q.dtype)
