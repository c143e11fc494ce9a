"""Pallas kernel: single-token GQA decode attention over a PAGED KV cache.

The production form of the serving decode op: each batch slot's KV history
lives in fixed-size pages of a shared physical pool ([N_pages, P, Hkv, D]),
indexed through a per-slot block table ([B, n_pages] physical page ids) —
the vLLM layout at miniature scale. Per batch slot the kernel STREAMS the
slot's pages one page per grid step (W-chunking: only a single
[P, Hkv, D] page block — every kv head of one page — is ever resident in
VMEM, so caches far past VMEM work unchanged) and runs the per-head program
on each head's [P, D] slice. The page id for each grid step comes from the
block table via scalar-prefetch BlockSpec index maps, so the gather is a
DMA schedule, not a materialized [B, W, Hkv, D] copy. Streaming whole
pages keeps the block's tiled trailing dims the pool's own (Hkv, D), which
the TPU compiler accepts at any page size; a one-head (P, 1, D) block is
refused by its (8, 128) tiling rule.

Split-softmax structure (flash-decoding's split-K shape): the kernel writes
an INDEPENDENT self-normalized partial softmax per page — (m_j, l_j, acc_j)
= (row max, exp-sum, exp-weighted value sum) — and a separate SHARED jnp
function, `combine_pages`, merges the partials into the final output. The
cross-page merge deliberately lives OUTSIDE the kernel: an in-kernel
online-softmax carry chains exp/mul/add across grid steps, and XLA's CPU
codegen for such chains differs by an ulp between the grid interpreter and
a scanned jnp mirror (fusion-context-dependent transcendental emitters), so
a carried kernel can never honestly promise bit-parity off-TPU. Per-page
partials are single-block programs — the regime where the repo's parity
contract is engineered to hold — and `combine_pages` is executed verbatim
by every backend form on bitwise-identical partials.

Bit-parity contract: the per-page program is `_page_partial`, shared
verbatim with `paged_attention_partials_reference` (which lax.map's the
same function over the same page sequence) — the `reference` and `pallas`
forms of `Backend.paged_decode_attention` therefore run identical
floating-point programs, and the `pallas_sharded` form is exact because
cells are per-head independent (pages head-sharded over the mesh `model`
axis, `repro.dist.sharding.page_pool_spec`).

Unlike the ring kernel (where validity is an input), per-slot validity here
is DERIVED FROM THE PAGE TABLE POSITION ARITHMETIC inside the shared
per-page program: page j of slot b covers absolute positions
[j*P, (j+1)*P), valid iff kpos <= pos_b (written and attendable — a paged
cache never wraps, so there is no ring aliasing) and inside the sliding
window when the arch has one.

Trash-page grid steps are SKIPPED, not masked: a table entry equal to the
reserved trash page 0 means "no data here by construction" (unallocated
slots, right-pad positions, table rows past a slot's allocation), so the
kernel guards the whole per-page program behind `pl.when(page_id != 0)` and
the else-branch writes the neutral partial (m = -inf, l = 0, acc = 0)
directly — no page DMA is issued for the step (consecutive steps whose
index maps resolve to the same page 0 block are also deduplicated by the
pipeline, so a mostly-empty table costs almost nothing). `combine_pages`
weighs the neutral partial to exactly zero, the same value a masked
streamed page produced before, and the reference mirror applies the
identical page_id == 0 -> neutral rule with `jnp.where` — see kernel rule 5
in the package README for why this preserves bit-parity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _page_partial(q, k, v, kpos, pos_b, *, scale: float, window: int,
                  softcap: float):
    """Self-normalized partial softmax of ONE page: q [G, D]; k, v [P, D];
    kpos [P] absolute positions covered by the page; pos_b scalar decode
    position of the slot -> (m [G], l [G], acc [G, D]).

    Shared verbatim by the kernel body and the mapped reference — any edit
    here changes both sides of the bit-parity contract together. No
    cross-page carry: a fully masked page yields (NEG_INF, 0, 0), which
    `combine_pages` weighs to exactly zero."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [G, P]
    if softcap:
        # reciprocal-multiply, not division: jit rewrites x / const to
        # x * (1/const) while eager mode divides — the mul form is the one
        # program both execution modes agree on bitwise
        s = softcap * jnp.tanh(s * (1.0 / softcap))
    valid = kpos <= pos_b
    if window:
        valid &= kpos > pos_b - window
    s = jnp.where(valid[None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)  # [G]
    p = jnp.where(valid[None, :], jnp.exp(s - m[:, None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return m, l, acc


def _dequant_page(codes, scale):
    """Dequantize ONE page of one kv head: int8 codes [P, D] + scalar f32
    page scale -> f32 [P, D]. Shared verbatim by the int8 kernel body and
    the mapped reference (int8 -> f32 is exact and the scalar broadcast
    multiply is elementwise, so the cell is bitwise in any context) — the
    quantized op's half of kernel parity rule 1."""
    return codes.astype(jnp.float32) * scale


def combine_pages(m, l, acc):
    """Merge per-page partial softmaxes into the final attention output:
    m, l [..., n_pages, G]; acc [..., n_pages, G, D] -> [..., G, D].

    Executed VERBATIM by every backend form of the paged op, outside the
    kernel, on partials that are already bitwise identical across backends
    — so backend parity holds for any deterministic merge. The inputs are
    fenced with optimization_barrier to keep this subgraph structurally
    identical in every enclosing program (no producer fusion reaching into
    the merge), which pins its own codegen too. Fully masked pages arrive
    as (NEG_INF, 0, 0) and get merge weight exp(NEG_INF - M) == 0."""
    m, l, acc = jax.lax.optimization_barrier((m, l, acc))
    M = jnp.max(m, axis=-2)  # [..., G]
    w = jnp.exp(m - M[..., None, :])  # [..., n_pages, G]
    l_tot = jnp.sum(l * w, axis=-2)  # [..., G]
    acc_tot = jnp.sum(acc * w[..., None], axis=-3)  # [..., G, D]
    return acc_tot / jnp.maximum(l_tot, 1e-30)[..., None]


def page_tile_rows(dtype) -> int:
    """Rows of one native TPU sublane tile for a page-pool dtype: 8 for
    32-bit, 16 for bf16, 32 for int8. A compiled page's per-head [P, D]
    operand fills whole tiles only when P is a multiple of this — the rule
    `ServeEngine` validates page sizes against."""
    return 8 * (4 // jnp.dtype(dtype).itemsize)


def _emit_neutral(m_ref, l_ref, acc_ref):
    """Trash page: no data by construction — write the neutral partial for
    every head without touching k/v (combine_pages weighs it to exactly 0)."""
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)


def _kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, *,
            scale: float, window: int, softcap: float, page_size: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(pt_ref[b, j] != 0)
    def _compute():
        # absolute positions covered by logical page j of this slot (2D iota
        # — 1D iota does not lower on TPU)
        kpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)[0]
        for h in range(k_ref.shape[2]):
            m, l, acc = _page_partial(
                q_ref[0, h].astype(jnp.float32),
                k_ref[0, :, h, :].astype(jnp.float32),
                v_ref[0, :, h, :].astype(jnp.float32),
                kpos, pos_ref[b],
                scale=scale, window=window, softcap=softcap,
            )
            m_ref[0, 0, h] = m
            l_ref[0, 0, h] = l
            acc_ref[0, 0, h] = acc

    @pl.when(pt_ref[b, j] == 0)
    def _neutral():
        _emit_neutral(m_ref, l_ref, acc_ref)


def _page_partials_call(kernel, q, k_pages, v_pages, page_table, pos, *,
                        name: str, interpret: bool, extra=(),
                        extra_specs=()):
    """Shared pallas_call plumbing of the two paged kernels; `name` is the
    kernel's name in the device trace.

    Grid (B, n_pages) with pages innermost: each step DMAs ONE physical
    page — all kv heads of it, a [P, Hkv, D] block whose tiled trailing
    dims are the pool's own (Hkv, D), so any page size is a legal block —
    through the scalar-prefetched block table, and writes that page's
    independent partial for every head. The partials come out [B, n_pages,
    Hkv, ...] (trailing (Hkv, G) blocks are whole dims, legal for the
    compiler) and are transposed to the [B, Hkv, n_pages, ...] layout
    `combine_pages` and the head-sharded specs read."""
    B, Hkv, G, D = q.shape
    P = k_pages.shape[1]
    n_pages = page_table.shape[1]
    page = pl.BlockSpec((1, P, Hkv, D), lambda b, j, pt, ps: (pt[b, j], 0, 0, 0))
    part = pl.BlockSpec((1, 1, Hkv, G), lambda b, j, pt, ps: (b, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_table, pos feed the index maps
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, D), lambda b, j, pt, ps: (b, 0, 0, 0)),
            page, page, *extra_specs,
        ],
        out_specs=[
            part, part,
            pl.BlockSpec((1, 1, Hkv, G, D),
                         lambda b, j, pt, ps: (b, j, 0, 0, 0)),
        ],
    )
    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n_pages, Hkv, G), jnp.float32),
            jax.ShapeDtypeStruct((B, n_pages, Hkv, G), jnp.float32),
            jax.ShapeDtypeStruct((B, n_pages, Hkv, G, D), jnp.float32),
        ],
        interpret=interpret,
        name=name,
    )(page_table, pos, q, k_pages, v_pages, *extra)
    return (m.transpose(0, 2, 1, 3), l.transpose(0, 2, 1, 3),
            acc.transpose(0, 2, 1, 3, 4))


def paged_attention_partials_pallas(
    q: jax.Array,           # [B, Hkv, G, D] grouped query (one token/slot)
    k_pages: jax.Array,     # [N_pages, P, Hkv, D] physical key page pool
    v_pages: jax.Array,     # [N_pages, P, Hkv, D] physical value page pool
    page_table: jax.Array,  # [B, n_pages] int32 physical page ids per slot
    pos: jax.Array,         # [B] int32 per-slot decode position
    *,
    window: int = 0,
    softcap: float = 0.0,
    scale: float = None,
    interpret: bool = False,
):
    """Per-page partial softmaxes via the paged kernel: returns
    (m [B, Hkv, n_pages, G], l [B, Hkv, n_pages, G],
    acc [B, Hkv, n_pages, G, D]) in f32 — feed `combine_pages`.

    Each grid step DMAs exactly one [P, Hkv, D] page per k/v (index-mapped
    through the scalar-prefetched block table) and writes that page's
    independent per-head partials — cache size never constrains VMEM.
    `scale` overrides the D**-0.5 default when the caller lane-padded D."""
    D = q.shape[3]
    kernel = functools.partial(
        _kernel, scale=float(scale or D**-0.5), window=int(window),
        softcap=float(softcap), page_size=k_pages.shape[1],
    )
    return _page_partials_call(kernel, q, k_pages, v_pages, page_table, pos,
                               name="paged_decode_attention",
                               interpret=interpret)


def _kernel_quant(pt_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  m_ref, l_ref, acc_ref, *, scale: float, window: int,
                  softcap: float, page_size: int):
    """`_kernel` over int8 pages: identical structure, with each head's
    streamed [P, D] code block dequantized in-VMEM by the shared
    `_dequant_page` cell against that head's (1, 1) slice of the page's
    [1, Hkv] scale row, prefetched alongside it. Everything downstream of
    the dequant is `_page_partial` verbatim."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(pt_ref[b, j] != 0)
    def _compute():
        kpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)[0]
        for h in range(k_ref.shape[2]):
            m, l, acc = _page_partial(
                q_ref[0, h].astype(jnp.float32),
                _dequant_page(k_ref[0, :, h, :], ks_ref[0, :, h:h + 1]),
                _dequant_page(v_ref[0, :, h, :], vs_ref[0, :, h:h + 1]),
                kpos, pos_ref[b],
                scale=scale, window=window, softcap=softcap,
            )
            m_ref[0, 0, h] = m
            l_ref[0, 0, h] = l
            acc_ref[0, 0, h] = acc

    @pl.when(pt_ref[b, j] == 0)
    def _neutral():
        _emit_neutral(m_ref, l_ref, acc_ref)


def paged_attention_partials_quant_pallas(
    q: jax.Array,           # [B, Hkv, G, D] grouped query (one token/slot)
    k_pages: jax.Array,     # [N_pages, P, Hkv, D] int8 key code pool
    v_pages: jax.Array,     # [N_pages, P, Hkv, D] int8 value code pool
    k_scale: jax.Array,     # [N_pages, Hkv] f32 per-(page, head) key scales
    v_scale: jax.Array,     # [N_pages, Hkv] f32 value scales
    page_table: jax.Array,  # [B, n_pages] int32 physical page ids per slot
    pos: jax.Array,         # [B] int32 per-slot decode position
    *,
    window: int = 0,
    softcap: float = 0.0,
    scale: float = None,
    interpret: bool = False,
):
    """`paged_attention_partials_pallas` over the int8 page pool: the same
    (B, n_pages) grid streams each [P, Hkv, D] int8 page PLUS its page's
    [1, Hkv] scale row (the [N_pages, Hkv] scales viewed as
    [N_pages, 1, Hkv], so the row is a whole trailing-dims block) through
    the same table-prefetched index map and dequantizes in-VMEM — the pool
    crosses HBM at half the bf16 byte count and is never materialized
    densely in any precision."""
    D = q.shape[3]
    NP, Hkv = k_scale.shape
    kernel = functools.partial(
        _kernel_quant, scale=float(scale or D**-0.5), window=int(window),
        softcap=float(softcap), page_size=k_pages.shape[1],
    )
    srow = pl.BlockSpec((1, 1, Hkv), lambda b, j, pt, ps: (pt[b, j], 0, 0))
    return _page_partials_call(
        kernel, q, k_pages, v_pages, page_table, pos,
        name="quant_paged_decode_attention", interpret=interpret,
        extra=(k_scale.reshape(NP, 1, Hkv), v_scale.reshape(NP, 1, Hkv)),
        extra_specs=(srow, srow))


def paged_attention_partials_quant_reference(
    q: jax.Array,           # [B, Hkv, G, D]
    k_pages: jax.Array,     # [N_pages, P, Hkv, D] int8
    v_pages: jax.Array,     # [N_pages, P, Hkv, D] int8
    k_scale: jax.Array,     # [N_pages, Hkv] f32
    v_scale: jax.Array,     # [N_pages, Hkv] f32
    page_table: jax.Array,  # [B, n_pages] int32
    pos: jax.Array,         # [B] int32
    *,
    window: int = 0,
    softcap: float = 0.0,
):
    """Pure-jnp form of `paged_attention_partials_quant_pallas`: the same
    lax.map cell structure as `paged_attention_partials_reference`, with the
    per-page gather widened to (codes, scale) and dequantized by the SAME
    `_dequant_page` cell the kernel runs — the only difference from the
    bf16 reference is that the f32 conversion happens per streamed page
    under its scale instead of once up front (which is also why the int8
    pool is gathered as int8: no dense f32 copy ever exists)."""
    B, Hkv, G, D = q.shape
    P = k_pages.shape[1]
    n_pages = page_table.shape[1]
    part = functools.partial(_page_partial, scale=float(D**-0.5),
                             window=int(window), softcap=float(softcap))
    kT = k_pages.transpose(2, 0, 1, 3)  # [Hkv, NP, P, D] int8
    vT = v_pages.transpose(2, 0, 1, 3)
    ksT = k_scale.transpose(1, 0)  # [Hkv, NP]
    vsT = v_scale.transpose(1, 0)

    def slot_cell(t):
        qb, ptb, pb = t  # [Hkv, G, D], [n_pages], scalar

        def head_cell(th):
            qh, kh, vh, ksh, vsh = th  # [G,D], [NP,P,D] int8, ..., [NP] f32

            def page(j):
                kj = _dequant_page(jnp.take(kh, ptb[j], axis=0),
                                   jnp.take(ksh, ptb[j]))
                vj = _dequant_page(jnp.take(vh, ptb[j], axis=0),
                                   jnp.take(vsh, ptb[j]))
                kpos = j * P + jnp.arange(P, dtype=jnp.int32)
                m, l, acc = part(qh, kj, vj, kpos, pb)
                trash = ptb[j] == 0
                return (jnp.where(trash, NEG_INF, m),
                        jnp.where(trash, 0.0, l),
                        jnp.where(trash, jnp.zeros_like(acc), acc))

            return jax.lax.map(page, jnp.arange(n_pages, dtype=jnp.int32))

        return jax.lax.map(head_cell,
                           (qb.astype(jnp.float32), kT, vT, ksT, vsT))

    return jax.lax.map(
        slot_cell, (q, page_table.astype(jnp.int32), pos.astype(jnp.int32)))


def paged_attention_partials_reference(
    q: jax.Array,           # [B, Hkv, G, D]
    k_pages: jax.Array,     # [N_pages, P, Hkv, D]
    v_pages: jax.Array,     # [N_pages, P, Hkv, D]
    page_table: jax.Array,  # [B, n_pages] int32
    pos: jax.Array,         # [B] int32
    *,
    window: int = 0,
    softcap: float = 0.0,
):
    """Pure-jnp form of `paged_attention_partials_pallas`: `_page_partial`
    lax.map'd over the (B, Hkv, page) cells with per-step scalar `jnp.take`
    page gathers — the identical floating-point program the kernel runs per
    grid cell (bit-parity oracle for `Backend.paged_decode_attention`).

    lax.map, NOT vmap: vmap batches the per-cell dots into one dot_general
    whose XLA lowering can differ by an ulp for degenerate shapes (G == 1
    MHA matvecs); and the page loop gathers one [P, D] page at a time,
    mirroring the kernel's DMA schedule instead of materializing a
    [B, n_pages, P, ...] copy. Trash entries (page id 0) are forced to the
    neutral partial with `jnp.where`, mirroring the kernel's `pl.when` skip:
    `where(False, neutral, partial)` returns the computed partial bitwise,
    `where(True, neutral, …)` the exact constants the kernel writes."""
    B, Hkv, G, D = q.shape
    P = k_pages.shape[1]
    n_pages = page_table.shape[1]
    part = functools.partial(_page_partial, scale=float(D**-0.5),
                             window=int(window), softcap=float(softcap))
    kT = k_pages.astype(jnp.float32).transpose(2, 0, 1, 3)  # [Hkv, NP, P, D]
    vT = v_pages.astype(jnp.float32).transpose(2, 0, 1, 3)

    def slot_cell(t):
        qb, ptb, pb = t  # [Hkv, G, D], [n_pages], scalar

        def head_cell(th):
            qh, kh, vh = th  # [G, D], [NP, P, D], [NP, P, D]

            def page(j):
                kj = jnp.take(kh, ptb[j], axis=0)  # [P, D]
                vj = jnp.take(vh, ptb[j], axis=0)
                kpos = j * P + jnp.arange(P, dtype=jnp.int32)
                m, l, acc = part(qh, kj, vj, kpos, pb)
                trash = ptb[j] == 0
                return (jnp.where(trash, NEG_INF, m),
                        jnp.where(trash, 0.0, l),
                        jnp.where(trash, jnp.zeros_like(acc), acc))

            return jax.lax.map(page, jnp.arange(n_pages, dtype=jnp.int32))

        return jax.lax.map(head_cell, (qb.astype(jnp.float32), kT, vT))

    return jax.lax.map(
        slot_cell, (q, page_table.astype(jnp.int32), pos.astype(jnp.int32)))
