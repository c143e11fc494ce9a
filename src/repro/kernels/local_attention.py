"""Banded (sliding-window) and block-sparse flash attention.

Both ops are the flash kernel's program with whole KV blocks SKIPPED when
they can contribute nothing:

* `local` skips blocks that the causal/window band fully masks, with the
  skip predicate derived from the position blocks alone — a sliding-window
  arch prefills without touching the out-of-window history, so the live
  work is O(Sq * window) instead of O(Sq * Skv).
* `block_sparse` skips blocks a caller-supplied [nq, nk] block mask
  disables (0 entries); causal/window still mask ELEMENTS inside enabled
  blocks, so an all-ones mask reproduces flash exactly and a banded mask
  reproduces `local`.

Parity rule 5 (kernels/README.md) is what makes the skip exact: a fully
masked block's `_kv_block_step` is a bitwise no-op on the carry — s is
NEG_INF everywhere, so m_new = m_prev, alpha = exp(0) = 1.0, p = 0,
l_new = l_prev * 1.0 + 0.0 and acc = acc_prev * 1.0 + dot(0, v), all IEEE
identities on the +0-signed accumulators the fold produces. Skipping the
block with `pl.when` therefore leaves the carry bit-identical to computing
it, which is why `local` equals the FULL flash kernel (same window spec)
bitwise, not just numerically. The jnp references mirror the skip with
`lax.cond` on the SAME predicate, keeping reference == interpret kernel
bitwise for block-sparse masks that genuinely drop live blocks too.

The band predicate is conservative-sound: predicate-false implies the
block is fully masked (max(qp) < min(kp) kills every causal pair;
min(qp) - max(kp) >= window kills every window pair). A fully masked block
the predicate misses (mixed corners) is computed — an exact no-op, so
parity is unaffected.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import (NEG_INF, _kv_block_step,
                                          position_blocks)


def _band_live(qp, kp, *, causal: bool, window: int):
    """Whether the (q-block, kv-block) cell can hold ANY unmasked element.

    Shared by the Pallas kernels and the reference `lax.cond` mirrors so
    both sides skip the identical block set."""
    live = jnp.bool_(True)
    if causal:
        live = jnp.logical_and(live, jnp.max(qp) >= jnp.min(kp))
    if window:
        live = jnp.logical_and(live, jnp.min(qp) - jnp.max(kp) < window)
    return live


def _skip_step_body(live, qpos_ref, q_ref, k_ref, v_ref, m_scr, l_scr,
                    acc_scr, kp, *, scale, causal, window, softcap):
    """The shared skip-or-step cell: `pl.when(live)` around the verbatim
    `_kv_block_step` with the carry in scratch. One function for both the
    banded and the block-sparse kernel so the executed program per LIVE
    block is identical to the flash kernel's."""
    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)  # [BQ, D]
        k = k_ref[0, 0].astype(jnp.float32)  # [BK, D]
        v = v_ref[0, 0].astype(jnp.float32)  # [BK, D]
        m_new, l_new, acc = _kv_block_step(
            (m_scr[...], l_scr[...], acc_scr[...]), q, k, v,
            qpos_ref[:, 0], kp,
            scale=scale, causal=causal, window=window, softcap=softcap,
        )
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc


def _init_and_finalize(ki, nk, o_ref, m_scr, l_scr, acc_scr):
    """Neutral-init scratch on the first KV step and normalize on the last.

    Finalize reads SCRATCH, not step outputs — the band may skip a cell's
    last block, and the scratch then already holds the final carry (equal,
    by the exact-no-op argument, to what the flash kernel computes)."""
    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def _local_kernel(
    qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int, softcap: float, nk: int,
):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kp = kpos_ref[0, :]
    live = _band_live(qpos_ref[:, 0], kp, causal=causal, window=window)
    _skip_step_body(live, qpos_ref, q_ref, k_ref, v_ref, m_scr, l_scr,
                    acc_scr, kp, scale=scale, causal=causal, window=window,
                    softcap=softcap)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def _sparse_kernel(
    mask_ref, qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: int, softcap: float, nk: int,
):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kp = kpos_ref[0, :]
    live = jnp.logical_and(
        mask_ref[pl.program_id(2), ki] != 0,
        _band_live(qpos_ref[:, 0], kp, causal=causal, window=window))
    _skip_step_body(live, qpos_ref, q_ref, k_ref, v_ref, m_scr, l_scr,
                    acc_scr, kp, scale=scale, causal=causal, window=window,
                    softcap=softcap)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def _banded_call(kernel_fn, mask, q, k, v, qpos, kpos, *, causal, window,
                 softcap, block_q, block_k, interpret):
    """Shared pallas_call plumbing for the two kernels (mask=None -> local)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    nq, nk = Sq // block_q, Skv // block_k
    kernel = functools.partial(
        kernel_fn, scale=D**-0.5, causal=causal, window=window,
        softcap=float(softcap), nk=nk,
    )
    # the sparse kernel's [nq, nk] block mask rides scalar prefetch (SMEM):
    # index maps then take it as a trailing argument, hence the *_
    args = []
    if mask is not None:
        assert mask.shape == (nq, nk), (mask.shape, nq, nk)
        args.append(mask.astype(jnp.int32))
    args += [*position_blocks(qpos, kpos), q, k, v]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0 if mask is None else 1,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((block_q, 1), lambda b, h, qi, ki, *_: (qi, 0)),
            pl.BlockSpec((1, block_k), lambda b, h, qi, ki, *_: (0, ki)),
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki, *_: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, *_: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki, *_: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki, *_: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(*args)


def local_attention_pallas(
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
    """Banded GQA flash attention: the flash kernel with fully-masked
    causal/window blocks `pl.when`-skipped. Returns [B, Hq, Sq, D] in
    q.dtype, bitwise the full flash kernel's output for the same spec."""
    return _banded_call(_local_kernel, None, q, k, v, qpos, kpos,
                        causal=causal, window=window, softcap=softcap,
                        block_q=block_q, block_k=block_k, interpret=interpret)


def block_sparse_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    qpos: jax.Array,
    kpos: jax.Array,
    *,
    block_mask: jax.Array,  # [nq, nk] int32/bool, 0 = block disabled
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Block-sparse GQA flash attention: KV blocks with a 0 in `block_mask`
    are skipped entirely (treated fully masked); causal/window still mask
    elements inside enabled blocks. An all-ones mask is bitwise
    `flash_attention_pallas`."""
    return _banded_call(_sparse_kernel, block_mask, q, k, v, qpos, kpos,
                        causal=causal, window=window, softcap=softcap,
                        block_q=block_q, block_k=block_k, interpret=interpret)


def _banded_reference(mask, q, k, v, qpos, kpos, *, causal, window, softcap,
                      block_q, block_k):
    """Shared jnp mirror: the flash reference's kv scan with the carry held
    through `lax.cond` on the SAME skip predicate as the kernels."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    nq, nk = Sq // block_q, Skv // block_k
    step = functools.partial(_kv_block_step, scale=D**-0.5, causal=causal,
                             window=window, softcap=float(softcap))
    qpos_b = qpos.reshape(nq, block_q)
    kpos_b = kpos.reshape(nk, block_k)
    if mask is not None:
        assert mask.shape == (nq, nk), (mask.shape, nq, nk)
        mask_b = mask.astype(jnp.int32)
    else:
        mask_b = jnp.ones((nq, nk), jnp.int32)

    def head_cell(qh, kh, vh):
        qb = qh.reshape(nq, block_q, D)
        kb = kh.reshape(nk, block_k, D)
        vb = vh.reshape(nk, block_k, D)

        def q_block(qx):
            qi, qp, mrow = qx

            def kv_step(carry, kx):
                ki, vi, kp, me = kx
                live = jnp.logical_and(
                    me != 0, _band_live(qp, kp, causal=causal, window=window))
                return jax.lax.cond(
                    live, lambda c: step(c, qi, ki, vi, qp, kp),
                    lambda c: c, carry), None

            init = (jnp.full((block_q,), NEG_INF, jnp.float32),
                    jnp.zeros((block_q,), jnp.float32),
                    jnp.zeros((block_q, D), jnp.float32))
            (_, l_f, acc), _ = jax.lax.scan(kv_step, init,
                                            (kb, vb, kpos_b, mrow))
            return (acc / jnp.maximum(l_f, 1e-30)[:, None]).astype(q.dtype)

        return jax.lax.map(q_block, (qb, qpos_b, mask_b)).reshape(Sq, D)

    # same lax.map-not-vmap iteration discipline as flash_attention_reference
    qg = q.astype(jnp.float32).reshape(B * Hkv, G, Sq, D)
    kf = k.astype(jnp.float32).reshape(B * Hkv, Skv, D)
    vf = v.astype(jnp.float32).reshape(B * Hkv, Skv, D)

    def kv_head_cell(t):
        qh, kh, vh = t
        return jax.lax.map(lambda qx: head_cell(qx, kh, vh), qh)

    out = jax.lax.map(kv_head_cell, (qg, kf, vf))
    return out.reshape(B, Hkv, G, Sq, D).reshape(B, Hq, Sq, D).astype(q.dtype)


def local_attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    qpos: jax.Array,
    kpos: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Pure-jnp mirror of `local_attention_pallas` (same skip predicate via
    `lax.cond`) — bit-identical to the interpret-mode kernel AND to the
    full flash reference for the same spec."""
    return _banded_reference(None, q, k, v, qpos, kpos, causal=causal,
                             window=window, softcap=softcap,
                             block_q=block_q, block_k=block_k)


def block_sparse_attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    qpos: jax.Array,
    kpos: jax.Array,
    *,
    block_mask: jax.Array,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """Pure-jnp mirror of `block_sparse_attention_pallas` — bit-identical
    to the interpret-mode kernel for any [nq, nk] block mask."""
    return _banded_reference(block_mask, q, k, v, qpos, kpos, causal=causal,
                             window=window, softcap=softcap,
                             block_q=block_q, block_k=block_k)
