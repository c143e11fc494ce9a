"""Pallas kernel: single-token GQA decode attention against a ring KV cache.

The serving hot loop's inner op: one new query token per sequence attends
over the (ring-bounded) cache of `W` slots. Per (batch, kv-head) grid cell
the whole cache block is resident, so the score matmul, the masked softmax,
and the value matmul fuse into one kernel — the [G, W] score matrix never
round-trips through HBM (W = cache capacity, G = Hq // Hkv query heads per
kv head).

Bit-parity contract: the kernel body *is* `_decode_cell`, the same function
`decode_attention_reference` maps over (B, Hkv) with nested vmap — the
`reference` and `pallas` forms of `Backend.decode_attention` therefore run
the identical floating-point program (asserted bitwise in
tests/test_serving.py). The `pallas_sharded` form shard_maps this kernel
over the mesh model axis; per-head independence makes the head split exact,
so all three backends produce bit-identical decode logits.

Validity is an input, not kernel logic: the caller derives `valid` [W] from
the absolute decode position, the ring capacity, and the sliding window
(`repro.models.attention.ring_valid`), which keeps the position arithmetic
identical across every backend and execution mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _decode_cell(q, k, v, valid, *, scale: float, softcap: float):
    """One (batch, kv-head) cell: q [G, D]; k, v [W, D]; valid [W] -> [G, D].

    Shared verbatim by the kernel body and the vmapped reference — any edit
    here changes both sides of the bit-parity contract together."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [G, W]
    if softcap:
        # multiply by the precomputed reciprocal, NOT s / softcap: XLA
        # rewrites constant division to reciprocal-multiply under jit but
        # not eagerly, which would break bit-parity between execution modes
        s = softcap * jnp.tanh(s * (1.0 / softcap))
    s = jnp.where(valid[None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(valid[None, :], jnp.exp(s - m[:, None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return o / jnp.maximum(l, 1e-30)[:, None]


def _kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, *, scale: float, softcap: float):
    o_ref[0, 0] = _decode_cell(
        q_ref[0, 0].astype(jnp.float32),
        k_ref[0, 0].astype(jnp.float32),
        v_ref[0, 0].astype(jnp.float32),
        valid_ref[0, :] != 0,
        scale=scale, softcap=softcap,
    ).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jax.Array,  # [B, Hkv, G, D] grouped query (one token per sequence)
    k: jax.Array,  # [B, Hkv, W, D] ring cache keys (RoPE pre-applied)
    v: jax.Array,  # [B, Hkv, W, D] ring cache values
    valid: jax.Array,  # [W] bool — slot holds an attendable token
    *,
    softcap: float = 0.0,
    scale: float = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused single-token decode attention; returns [B, Hkv, G, D] in q.dtype.

    Grid (B, Hkv): every cell reads its head's full cache block — decode is
    memory-bound on the cache stream, so there is nothing to tile over W
    until W*D exceeds VMEM. Caches past that regime are NOT handled yet
    (W-chunking the grid is a ROADMAP open item); today's callers keep
    W*D comfortably under VMEM. `scale` overrides the D**-0.5 default when
    the caller lane-padded D."""
    B, Hkv, G, D = q.shape
    W = k.shape[2]
    kernel = functools.partial(_kernel, scale=float(scale or D**-0.5),
                               softcap=float(softcap))
    return pl.pallas_call(
        kernel,
        grid=(B, Hkv),
        in_specs=[
            pl.BlockSpec((1, W), lambda b, h: (0, 0)),  # valid as a row
            pl.BlockSpec((1, 1, G, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, W, D), lambda b, h: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, W, D), lambda b, h: (b, h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(valid.astype(jnp.int32).reshape(1, W), q, k, v)


def decode_attention_reference(q, k, v, valid, *, softcap: float = 0.0) -> jax.Array:
    """Pure-jnp form: `_decode_cell` lax.map'd over the flattened (B, Hkv)
    grid — the identical floating-point program the kernel runs per cell
    (bit-parity oracle for `Backend.decode_attention`).

    lax.map, NOT vmap: vmap batches the per-cell dots into one big
    dot_general, and for G == 1 (MHA) XLA lowers that batched matvec with a
    different accumulation order than the interpreter's per-cell 2D dots —
    a one-ulp break of the parity contract. lax.map keeps the per-cell dot
    shapes identical to the kernel's grid steps."""
    B, Hkv, G, D = q.shape
    cell = functools.partial(_decode_cell, scale=float(D**-0.5),
                             softcap=float(softcap))
    qf = q.astype(jnp.float32).reshape(B * Hkv, G, D)
    kf = k.astype(jnp.float32).reshape(B * Hkv, *k.shape[2:])
    vf = v.astype(jnp.float32).reshape(B * Hkv, *v.shape[2:])
    out = jax.lax.map(lambda t: cell(*t, valid), (qf, kf, vf))
    return out.reshape(B, Hkv, G, D).astype(q.dtype)
