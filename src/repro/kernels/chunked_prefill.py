"""Chunked (memory-efficient) prefill: the flash-attention fold split into
KV-chunk resumable pieces.

The full flash kernel walks, for each (b, h, q-block) grid cell, every KV
block ki = 0..nk-1 with the online-softmax carry (m, l, acc) living in VMEM
scratch. This module runs THE SAME fold as a sequence of per-chunk
invocations: each chunk call takes the carry as ordinary array inputs,
executes the chunk's KV blocks with the shared `_kv_block_step` program
(verbatim — the same block decomposition the full kernel would use on the
full Skv), and emits the updated carry as outputs. Peak score-block memory
is therefore O(Sq * chunk) instead of O(Sq * Skv): only one chunk's
[block_q, block_k] score tiles are ever live.

Bit-parity structure (kernels/README.md):

* The carry crosses chunk invocations as the SAME (m, l, acc) values the
  full kernel holds in scratch after the same ki steps — chunk boundaries
  are block-aligned (chunk rounds up to a block_k multiple), so the step
  sequence is IDENTICAL to the full kernel's for every chunk size. This is
  the in-kernel flash carry (already validated interpret <-> scan-mirror)
  made resumable, not a new fold.
* The final carry is a SINGLETON split-K partial (page axis of size 1), and
  the caller finishes with the shared `combine_pages` merge in its own
  execution context (parity rule 4). The singleton merge is bitwise the
  full kernel's finalize: M = max over one element = m, w = exp(m - M) =
  exp(0) = 1.0 exactly (even at m = NEG_INF), the 1.0-multiplies and
  singleton-axis sums are IEEE identities, and the closing
  acc / max(l, 1e-30) is the very same division.

The jnp reference mirrors the chunk split literally: one `lax.scan` per
chunk threading the carry — a scan split at block boundaries applies the
identical step sequence, so reference == interpret kernel bitwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (NEG_INF, _kv_block_step,
                                          position_blocks)


def chunk_blocks(chunk: int, block_k: int) -> int:
    """Chunk size rounded UP to a block_k multiple (at least one block).

    Block-aligned chunk boundaries are what make the chunked fold's step
    sequence identical to the full kernel's — shared by the pallas form,
    the reference mirror, and the bench memory model so all three agree on
    the effective chunk."""
    return max(block_k, -(-int(chunk) // block_k) * block_k)


def _chunk_kernel(
    qpos_ref, kpos_ref, q_ref, k_ref, v_ref, m_in_ref, l_in_ref, acc_in_ref,
    m_out_ref, l_out_ref, acc_out_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int, softcap: float, nk: int,
):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _resume():
        # resume the fold: carry-in arrays replace the NEG_INF/0/0 init of
        # the full kernel (the first chunk's carry-in IS that neutral init)
        m_scr[...] = m_in_ref[0, 0, :, 0]
        l_scr[...] = l_in_ref[0, 0, :, 0]
        acc_scr[...] = acc_in_ref[0, 0]

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
    def _emit():
        m_out_ref[0, 0] = m_new[:, None]
        l_out_ref[0, 0] = l_new[:, None]
        acc_out_ref[0, 0] = acc


def _chunk_call(q, k, v, qpos, kpos, m, l, acc, *, scale, causal, window,
                softcap, block_q, block_k, interpret):
    """One resumable chunk of the flash fold: k/v/kpos are ONE chunk's
    slice; (m, l, acc) carry in as arrays and out as updated arrays (m, l
    as [B, Hq, Sq, 1] columns, blocked (block_q, 1) like qpos)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    nq, nk = Sq // block_q, Skv // block_k
    kernel = functools.partial(
        _chunk_kernel, scale=scale, causal=causal, window=window,
        softcap=float(softcap), nk=nk,
    )
    grid = (B, Hq, nq, nk)
    carry2 = pl.BlockSpec((1, 1, block_q, 1), lambda b, h, qi, ki: (b, h, qi, 0))
    carry3 = pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda b, h, qi, ki: (qi, 0)),  # qpos
            pl.BlockSpec((1, block_k), lambda b, h, qi, ki: (0, ki)),  # kpos
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, qi, ki: (b, h // G, ki, 0)),
            carry2, carry2, carry3,
        ],
        out_specs=[carry2, carry2, carry3],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, Sq, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Hq, Sq, D), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(*position_blocks(qpos, kpos), q, k, v, m, l, acc)


def chunked_prefill_partials_pallas(
    q: jax.Array,  # [B, Hq, Sq, D]
    k: jax.Array,  # [B, Hkv, Skv, D]
    v: jax.Array,  # [B, Hkv, Skv, D]
    qpos: jax.Array,  # [Sq] int32
    kpos: jax.Array,  # [Skv] int32
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    chunk: int,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
):
    """Chunked GQA prefill as split-K partials: m, l [B, Hq, 1, Sq] and acc
    [B, Hq, 1, Sq, D] f32, the singleton-page layout `combine_pages`
    finishes in the caller's context. The Python chunk loop is static, so
    one jit trace covers the whole prompt while each `pallas_call` touches
    only O(Sq * chunk) score elements."""
    B, Hq, Sq, D = q.shape
    Skv = k.shape[2]
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    c = chunk_blocks(chunk, block_k)
    scale = D**-0.5
    m = jnp.full((B, Hq, Sq, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((B, Hq, Sq, 1), jnp.float32)
    acc = jnp.zeros((B, Hq, Sq, D), jnp.float32)
    for start in range(0, Skv, c):
        stop = min(start + c, Skv)
        m, l, acc = _chunk_call(
            q,
            jax.lax.slice_in_dim(k, start, stop, axis=2),
            jax.lax.slice_in_dim(v, start, stop, axis=2),
            qpos,
            jax.lax.slice_in_dim(kpos, start, stop, axis=0),
            m, l, acc,
            scale=scale, causal=causal, window=window, softcap=softcap,
            block_q=block_q, block_k=block_k, interpret=interpret,
        )
    return (m.reshape(B, Hq, 1, Sq), l.reshape(B, Hq, 1, Sq),
            acc[:, :, None, :, :])


def chunked_prefill_partials_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    qpos: jax.Array,
    kpos: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    chunk: int,
    block_q: int = 128,
    block_k: int = 128,
):
    """Pure-jnp mirror of the chunked fold: the flash reference's kv scan
    split at the SAME block-aligned chunk boundaries, threading the
    (m, l, acc) carry across one `lax.scan` per chunk — the identical step
    sequence, so bit-identical to the interpret-mode chunk kernels. Same
    partial layout as `chunked_prefill_partials_pallas`."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    nq = Sq // block_q
    c = chunk_blocks(chunk, block_k)
    step = functools.partial(_kv_block_step, scale=D**-0.5, causal=causal,
                             window=window, softcap=float(softcap))
    qpos_b = qpos.reshape(nq, block_q)
    spans = [(s, min(s + c, Skv)) for s in range(0, Skv, c)]

    def head_cell(qh, kh, vh):
        # qh [Sq, D]; kh, vh [Skv, D] — one (b, h) column of the grid
        qb = qh.reshape(nq, block_q, D)

        def q_block(qx):
            qi, qp = qx

            def kv_step(carry, kx):
                ki, vi, kp = kx
                return step(carry, qi, ki, vi, qp, kp), None

            carry = (jnp.full((block_q,), NEG_INF, jnp.float32),
                     jnp.zeros((block_q,), jnp.float32),
                     jnp.zeros((block_q, D), jnp.float32))
            for start, stop in spans:
                nk_c = (stop - start) // block_k
                kb = jax.lax.slice_in_dim(kh, start, stop, axis=0) \
                    .reshape(nk_c, block_k, D)
                vb = jax.lax.slice_in_dim(vh, start, stop, axis=0) \
                    .reshape(nk_c, block_k, D)
                kpb = jax.lax.slice_in_dim(kpos, start, stop, axis=0) \
                    .reshape(nk_c, block_k)
                carry, _ = jax.lax.scan(kv_step, carry, (kb, vb, kpb))
            return carry

        return jax.lax.map(q_block, (qb, qpos_b))

    # same lax.map-not-vmap iteration discipline as flash_attention_reference
    qg = q.astype(jnp.float32).reshape(B * Hkv, G, Sq, D)
    kf = k.astype(jnp.float32).reshape(B * Hkv, Skv, D)
    vf = v.astype(jnp.float32).reshape(B * Hkv, Skv, D)

    def kv_head_cell(t):
        qh, kh, vh = t  # [G, Sq, D], [Skv, D], [Skv, D]
        return jax.lax.map(lambda qx: head_cell(qx, kh, vh), qh)

    m, l, acc = jax.lax.map(kv_head_cell, (qg, kf, vf))
    m = m.reshape(B, Hq, Sq)
    l = l.reshape(B, Hq, Sq)
    acc = acc.reshape(B, Hq, Sq, D)
    return m[:, :, None, :], l[:, :, None, :], acc[:, :, None, :, :]
