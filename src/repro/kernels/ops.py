"""Jit'd public wrappers for the Pallas kernels.

Handles padding to TPU-friendly tiles (rows to `block_n` multiples, classes /
feature dims to 128 lanes), backend dispatch (interpret=True on CPU so the
kernels execute and validate in this container; compiled on TPU), and
restores reference semantics (slicing padding back off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.chunked_prefill import (
    chunk_blocks,
    chunked_prefill_partials_pallas,
    chunked_prefill_partials_reference,
)
from repro.kernels.decode_attention import (
    decode_attention_pallas,
    decode_attention_reference,
)
from repro.kernels.flash_attention import (
    flash_attention_pallas,
    flash_attention_reference,
)
from repro.kernels.local_attention import (
    block_sparse_attention_pallas,
    block_sparse_attention_reference,
    local_attention_pallas,
    local_attention_reference,
)
from repro.kernels.infl_scores import infl_scores_pallas
from repro.kernels.paged_attention import (
    combine_pages,
    page_tile_rows,
    paged_attention_partials_pallas,
    paged_attention_partials_quant_pallas,
    paged_attention_partials_quant_reference,
    paged_attention_partials_reference,
)
from repro.kernels.lr_grad import lr_grad_pallas
from repro.kernels.lr_hvp import lr_hvp_pallas
from repro.kernels.minibatch_grad import minibatch_grad_pallas
from repro.kernels.replay_correction import replay_correction_pallas


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_rows(x, mult):
    n = x.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)), n


def _pad_dim(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _block_n_padded(n: int) -> int:
    """Row block when the caller pads rows UP to the block: prefer a LARGE
    block that divides n exactly (no padding), else a full 128-row block
    padding a partial tail tile — never degrade to tiny blocks on awkward N
    (the divisor scan stops at 64: for big N, one padded tail tile beats a
    thousand 8-row grid steps)."""
    for b in (512, 256, 128, 64):
        if n % b == 0:
            return b
    if n >= 128:
        return 128
    b = 8
    while b < n:
        b *= 2
    return b


@functools.partial(jax.jit, static_argnames=("gamma",))
def infl_scores(v, Xa, P, Y, gamma: float):
    """Fused Eq. 6 INFL score matrix [N, C] (pads to TPU tiles, slices back)."""
    C = v.shape[0]
    lane = 128 if not _interpret() else 8
    vp = _pad_dim(_pad_dim(v, 0, lane), 1, lane)
    Xp = _pad_dim(Xa, 1, lane)
    Pp = _pad_dim(P, 1, lane)
    Yp = _pad_dim(Y, 1, lane)
    # pick the block first, then pad rows up to it — padding to a multiple
    # of 1 and deriving the block from the raw row count forced block_n=1
    # (one grid step per row) on odd N
    bn = _block_n_padded(Xp.shape[0])
    Xp, n = _pad_rows(Xp, bn)
    S = infl_scores_pallas(
        vp, Xp, _pad_rows(Pp, bn)[0], _pad_rows(Yp, bn)[0], gamma,
        block_n=bn, c_actual=C, interpret=_interpret(),
    )
    return S[:n, :C]


@functools.partial(jax.jit, static_argnames=("l2",))
def lr_grad(w, Xa, Y, weights, l2: float):
    """Fused Eq. 1 batch gradient [C, d+1] (padded rows carry weight 0)."""
    C = w.shape[0]
    N = Xa.shape[0]
    lane = 128 if not _interpret() else 8
    wp = _pad_dim(_pad_dim(w, 0, lane), 1, lane)
    Xp = _pad_dim(Xa, 1, lane)
    Yp = _pad_dim(Y, 1, lane)
    bn = _block_n_padded(N)
    # padded rows get weight 0 => no contribution
    Xp, _ = _pad_rows(Xp, bn)
    Yp, _ = _pad_rows(Yp, bn)
    w8p, _ = _pad_rows(weights, bn)
    g = lr_grad_pallas(wp, Xp, Yp, w8p, 0.0, block_n=bn,
                       c_actual=C, interpret=_interpret())
    g = g * (Xp.shape[0] / N)  # kernel divided by padded N
    return g[:C, : Xa.shape[1]] + l2 * w.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("l2",))
def lr_hvp(w, v, Xa, weights, l2: float, P=None):
    """Fused Hessian-vector product H(w) v -> [C, d+1] (CG inner loop)."""
    del P  # probs are recomputed inside the fused kernel
    C = w.shape[0]
    N = Xa.shape[0]
    lane = 128 if not _interpret() else 8
    wp = _pad_dim(_pad_dim(w, 0, lane), 1, lane)
    vp = _pad_dim(_pad_dim(v, 0, lane), 1, lane)
    Xp = _pad_dim(Xa, 1, lane)
    bn = _block_n_padded(N)
    Xp, _ = _pad_rows(Xp, bn)
    w8p, _ = _pad_rows(weights, bn)
    h = lr_hvp_pallas(wp, vp, Xp, w8p, 0.0, block_n=bn,
                      c_actual=C, interpret=_interpret())
    h = h * (Xp.shape[0] / N)
    return h[:C, : Xa.shape[1]] + l2 * v.astype(jnp.float32)


# rows gathered per grid step of the compiled mini-batch kernel: the
# [block, 1, d+1] f32 gather scratch is ~2.2 MB at d+1 = 2,176 lanes
GATHER_BLOCK = 256


@jax.jit
def row_gather_layout(Xa):
    """Xa [N, D] -> the [N, 1, Dp] view the row-gather kernels
    (`minibatch_grad`, `replay_correction`) DMA batch rows from: one row
    per leading index, so one row is one whole (1, Dp) tile. On TPU the
    lanes pad with zeros to a multiple of 128; interpret mode keeps D.
    Zero lanes only, no reordering (exact). Laying out is a copy of all of
    Xa, so a scan over steps lays out once before the loop and passes the
    result to every step."""
    Xp = Xa if _interpret() else _pad_dim(Xa, 1, 128)
    return Xp.reshape(Xp.shape[0], 1, Xp.shape[1])


@functools.partial(jax.jit, static_argnames=("l2",))
def minibatch_grad(w, Xr, Y, weights, idx, l2: float):
    """Fused gather + mini-batch gradient (constructor-phase hot op).

    `Xr` is `row_gather_layout(Xa)`. The kernel DMAs the batch rows itself;
    the batch's labels and weights are gathered here (tiny). Interpret mode
    runs the kernel UNPADDED in one chunk: the body is then the same
    floating-point program as the reference scan step, which is what makes
    sgd_train/deltagrad_replay bit-identical across backends. On TPU, w
    pads to the layout's lanes and the batch pads to `GATHER_BLOCK`-row
    chunks whose padded slots point at row 0 with weight 0 (exact-zero
    contribution)."""
    idx = idx.astype(jnp.int32)
    yb, wb = Y[idx], weights[idx]
    if _interpret():
        return minibatch_grad_pallas(w, Xr, yb, wb, idx, l2, interpret=True)
    C, D = w.shape
    bs = idx.shape[0]
    lane = 128
    bb = min(GATHER_BLOCK, -(-bs // 8) * 8)
    pad = (-bs) % bb
    wp = _pad_dim(_pad_dim(w, 0, lane), 1, lane)
    g = minibatch_grad_pallas(
        wp, Xr, jnp.pad(_pad_dim(yb, 1, lane), ((0, pad), (0, 0))),
        jnp.pad(wb, (0, pad)), jnp.pad(idx, (0, pad)), l2,
        n_batch=bs, c_actual=C, block_b=bb, interpret=False)
    return g[:C, :D]


@functools.partial(jax.jit, static_argnames=("batch_size",))
def replay_correction(w, Xr, Y_old, Y_new, w_old, w_new, ci, cm,
                      batch_size: int):
    """Fused gather + DeltaGrad-L replay correction over `Xr =
    row_gather_layout(Xa)`. Same interpret-unpadded bit-parity contract as
    `minibatch_grad`; TPU padding extends ci with pointers to row 0 and cm
    with zeros (exact-zero contribution)."""
    ci = ci.astype(jnp.int32)
    yo, yn, wo, wn = Y_old[ci], Y_new[ci], w_old[ci], w_new[ci]
    if _interpret():
        return replay_correction_pallas(w, Xr, yo, yn, wo, wn, ci, cm,
                                        batch_size, interpret=True)
    C, D = w.shape
    lane = 128
    pad = (-ci.shape[0]) % 8

    def rows(a):
        return jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))

    g = replay_correction_pallas(
        _pad_dim(_pad_dim(w, 0, lane), 1, lane), Xr,
        rows(_pad_dim(yo, 1, lane)), rows(_pad_dim(yn, 1, lane)),
        rows(wo), rows(wn), rows(ci), rows(cm), batch_size, c_actual=C,
        interpret=False)
    return g[:C, :D]


def _attn_blocks(Sq: int, Skv: int) -> tuple:
    """(block_q, block_k) for the flash kernel: the LARGEST divisor of the
    sequence length <= 128. The old `128-or-1` rule degraded every
    non-multiple-of-128 length over 128 (now routine: mid-stream join
    prefills run at arbitrary widths) to 1-row blocks — tens of thousands
    of grid cells per head; a divisor walk caps at 128 comparisons at trace
    time and only primes still fall to 1. Shared by the pallas path and the
    reference mirror so both walk the identical block decomposition — a
    precondition of the serving bit-parity contract."""
    def pick(S: int) -> int:
        for b in range(min(128, S), 0, -1):
            if S % b == 0:
                return b
        return 1

    return pick(Sq), pick(Skv)


# padded key positions: past every real query, so the causal mask drops them
_PAD_POS = 2**30


def _compiled_seq_pad(q, k, v, qpos, kpos, spec, extra):
    """Pad sequences for the compiled (non-interpret) attention kernels.

    Their position blocks sit on lanes, so a block is either the whole
    sequence or a multiple of 128: a sequence over 128 that is not a
    multiple of 128 pads up to one. Padded keys sit at `_PAD_POS`, which
    the causal mask drops; padded query rows are sliced off by the caller.
    The interpret path (and every power-of-two bucket the engine prefills
    at) never pads."""
    def need(S):
        return (-S) % 128 if S > 128 else 0

    pq, pk = need(q.shape[2]), need(k.shape[2])
    if not (pq or pk):
        return q, k, v, qpos, kpos
    if not spec.causal or "block_mask" in extra:
        raise NotImplementedError(
            "compiled attention pads sequences over 128 to a multiple of 128,"
            " which needs a causal mask and no block mask; got "
            f"Sq={q.shape[2]}, Skv={k.shape[2]}, causal={spec.causal}")
    seq = lambda x, n: jnp.pad(x, ((0, 0), (0, 0), (0, n), (0, 0)))
    return (seq(q, pq), seq(k, pk), seq(v, pk),
            jnp.pad(qpos, (0, pq), constant_values=_PAD_POS),
            jnp.pad(kpos, (0, pk), constant_values=_PAD_POS))


def _to_kernel_layout(q, k, v, qpos, kpos, spec, extra):
    """q [B,S,H,D] -> kernel layout [B,H,S,D] (+ compiled-path padding), one
    block-size choice, one position cast."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    qpos, kpos = qpos.astype(jnp.int32), kpos.astype(jnp.int32)
    if extra.get("interpret") is False:
        qt, kt, vt, qpos, kpos = _compiled_seq_pad(qt, kt, vt, qpos, kpos,
                                                   spec, extra)
    return qt, kt, vt, qpos, kpos, _attn_blocks(qt.shape[2], kt.shape[2])


def _flash_adapt(inner, q, k, v, qpos, kpos, spec, **extra):
    """Shared model-layout adapter for both flash forms: q [B,S,H,D] ->
    kernel layout [B,H,S,D], one block-size choice, one position cast. ONE
    function on purpose — if the two forms adapted separately, an edit to
    one side would silently break the bit-parity contract."""
    qt, kt, vt, qp, kp, (bq, bk) = _to_kernel_layout(q, k, v, qpos, kpos,
                                                     spec, extra)
    o = inner(
        qt, kt, vt, qp, kp,
        causal=spec.causal, window=spec.window, softcap=spec.logit_softcap,
        block_q=bq, block_k=bk, **extra,
    )
    return o[:, :, : q.shape[1]].transpose(0, 2, 1, 3)


def flash_attention(q, k, v, qpos, kpos, spec):
    """Model-layer adapter around the Pallas flash kernel."""
    return _flash_adapt(flash_attention_pallas, q, k, v, qpos, kpos, spec,
                        interpret=_interpret())


def flash_attention_ref(q, k, v, qpos, kpos, spec):
    """Reference-backend form of `flash_attention`: the same adapter around
    the pure-jnp blocked mirror (identical block sizes, same per-block
    floating-point program — bit-identical to the kernel)."""
    return _flash_adapt(flash_attention_reference, q, k, v, qpos, kpos, spec)


def local_attention(q, k, v, qpos, kpos, spec):
    """Model-layer adapter around the banded (sliding-window) Pallas kernel:
    the flash program with fully-masked band blocks skipped. Bitwise
    `flash_attention` for the same spec (parity rule 5)."""
    return _flash_adapt(local_attention_pallas, q, k, v, qpos, kpos, spec,
                        interpret=_interpret())


def local_attention_ref(q, k, v, qpos, kpos, spec):
    """Reference-backend form of `local_attention`: the same adapter around
    the `lax.cond`-skipping jnp mirror (identical skipped-block set —
    bit-identical to the kernel and to `flash_attention_ref`)."""
    return _flash_adapt(local_attention_reference, q, k, v, qpos, kpos, spec)


def attn_block_mask_shape(Sq: int, Skv: int) -> tuple:
    """(nq, nk) shape of the block mask `block_sparse_attention` expects for
    a [*, Sq, *, D] x [*, Skv, *, D] attention — derived from the SAME
    `_attn_blocks` decomposition the adapters pick, so callers build masks
    at exactly the kernel's block granularity."""
    bq, bk = _attn_blocks(Sq, Skv)
    return Sq // bq, Skv // bk


def block_sparse_attention(q, k, v, qpos, kpos, block_mask, spec):
    """Model-layer adapter around the block-sparse Pallas kernel: KV blocks
    with a 0 in `block_mask` ([nq, nk], see `attn_block_mask_shape`) are
    skipped; causal/window still mask elements inside enabled blocks. An
    all-ones mask is bitwise `flash_attention`."""
    return _flash_adapt(block_sparse_attention_pallas, q, k, v, qpos, kpos,
                        spec, block_mask=block_mask, interpret=_interpret())


def block_sparse_attention_ref(q, k, v, qpos, kpos, block_mask, spec):
    """Reference-backend form of `block_sparse_attention` (same skipped
    blocks via `lax.cond` — bit-identical to the kernel)."""
    return _flash_adapt(block_sparse_attention_reference, q, k, v, qpos,
                        kpos, spec, block_mask=block_mask)


def _chunked_adapt(inner, q, k, v, qpos, kpos, spec, chunk, **extra):
    """Model-layout adapter for the chunked-prefill partial forms: same
    transpose + `_attn_blocks` choice as `_flash_adapt`, but the output is
    the (m, l, acc) split-K partial triple, left in kernel layout for
    `chunked_prefill_finish` / the head-sharded partials shard_map."""
    qt, kt, vt, qp, kp, (bq, bk) = _to_kernel_layout(q, k, v, qpos, kpos,
                                                     spec, extra)
    m, l, acc = inner(
        qt, kt, vt, qp, kp,
        causal=spec.causal, window=spec.window, softcap=spec.logit_softcap,
        chunk=chunk, block_q=bq, block_k=bk, **extra,
    )
    Sq = q.shape[1]
    return m[..., :Sq], l[..., :Sq], acc[..., :Sq, :]


def chunked_prefill_partials(q, k, v, qpos, kpos, spec, chunk: int):
    """Kernel half of the chunked-prefill op: the flash fold run chunk by
    chunk (chunk rounds up to a kv-block multiple), returning the final
    carry as singleton split-K partials m, l [B, Hq, 1, Sq], acc
    [B, Hq, 1, Sq, D] f32. Split from the merge for the same reason as
    `paged_decode_partials`: the shared `combine_pages` finish must run in
    the CALLER's context on every backend form."""
    return _chunked_adapt(chunked_prefill_partials_pallas, q, k, v, qpos,
                          kpos, spec, chunk, interpret=_interpret())


def chunked_prefill_partials_ref(q, k, v, qpos, kpos, spec, chunk: int):
    """Reference-backend form of `chunked_prefill_partials`: the same
    adapter around the per-chunk `lax.scan` mirror (identical step
    sequence — bit-identical to the chunk kernels)."""
    return _chunked_adapt(chunked_prefill_partials_reference, q, k, v, qpos,
                          kpos, spec, chunk)


def chunked_prefill_finish(m, l, acc, q):
    """Merge half of the chunked-prefill op: the SHARED `combine_pages`
    over the singleton partial (exact — the weights are exp(0) = 1.0), cast
    back to q.dtype and restored to model layout [B, Sq, Hq, D]. Bitwise
    the flash kernel's in-kernel finalize."""
    o = combine_pages(m, l, acc)  # [B, Hq, Sq, D] f32
    return o.astype(q.dtype).transpose(0, 2, 1, 3)


def chunked_prefill(q, k, v, qpos, kpos, spec, chunk: int):
    """Chunked (memory-efficient) GQA prefill: peak score-block memory
    O(Sq * chunk) instead of O(Sq * Skv), output bitwise `flash_attention`
    for ANY chunk size (see kernels/chunked_prefill.py for why)."""
    m, l, acc = chunked_prefill_partials(q, k, v, qpos, kpos, spec, chunk)
    return chunked_prefill_finish(m, l, acc, q)


def chunked_prefill_ref(q, k, v, qpos, kpos, spec, chunk: int):
    """Reference-backend form of `chunked_prefill` (same partials mirror +
    the same caller-context `combine_pages` finish)."""
    m, l, acc = chunked_prefill_partials_ref(q, k, v, qpos, kpos, spec, chunk)
    return chunked_prefill_finish(m, l, acc, q)


def _decode_layout(q, k, v):
    """Model layout -> decode-kernel layout: q [B,1,Hq,D] -> [B,Hkv,G,D];
    k, v [B,W,Hkv,D] -> [B,Hkv,W,D]. Pure transposes/reshapes (exact)."""
    B, _, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, D)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    return qg, kt, vt, G


def decode_attention(q, k, v, valid, spec):
    """Fused single-token decode attention over the ring KV cache.

    q [B,1,Hq,D]; k, v [B,W,Hkv,D] (dense, RoPE/dequant already applied);
    valid [W] slot mask (see `repro.models.attention.ring_valid`). Returns
    [B,1,Hq,D]. Interpret mode runs the kernel unpadded — the same
    floating-point program as `decode_attention_ref` — preserving the
    serving bit-parity contract; on TPU, W pads to sublane multiples with
    valid=False (exact no-ops) and the padded scale is pinned to the true
    head dim."""
    B, _, Hq, D = q.shape
    qg, kt, vt, G = _decode_layout(q, k, v)
    if _interpret():
        o = decode_attention_pallas(qg, kt, vt, valid,
                                    softcap=spec.logit_softcap, interpret=True)
        return o.reshape(B, 1, Hq, D)
    W = kt.shape[2]
    scale = D**-0.5
    qp = _pad_dim(_pad_dim(qg, 2, 8), 3, 128)
    kp = _pad_dim(_pad_dim(kt, 2, 8), 3, 128)
    vp = _pad_dim(_pad_dim(vt, 2, 8), 3, 128)
    vm = jnp.pad(valid, (0, (-W) % 8))  # padded slots masked out
    o = decode_attention_pallas(qp, kp, vp, vm, softcap=spec.logit_softcap,
                                scale=scale, interpret=False)
    return o[:, :, :G, :D].reshape(B, 1, Hq, D)


def decode_attention_ref(q, k, v, valid, spec):
    """Reference-backend form of `decode_attention`: the same layout adapter
    around the vmapped `_decode_cell` (bit-identical to the kernel)."""
    B, _, Hq, D = q.shape
    qg, kt, vt, _ = _decode_layout(q, k, v)
    o = decode_attention_reference(qg, kt, vt, valid,
                                   softcap=spec.logit_softcap)
    return o.reshape(B, 1, Hq, D)


def _check_page_rows(k_pages):
    """Backstop for direct op callers (`ServeEngine` validates at config
    time): compiled pages must be whole sublane tiles of the pool dtype."""
    rows = page_tile_rows(k_pages.dtype)
    assert k_pages.shape[1] % rows == 0, (
        f"TPU paged cache needs page_size % {rows} == 0 for "
        f"{k_pages.dtype} pools, got {k_pages.shape[1]}")


def _paged_layout(q, k_pages):
    """Model layout -> paged-kernel layout: q [B,1,Hq,D] -> [B,Hkv,G,D].
    The page pools already carry the kernel layout ([N_pages, P, Hkv, D] —
    transposing the whole pool per decode step would copy the entire cache,
    which is exactly what the page-table indexing exists to avoid)."""
    B, _, Hq, D = q.shape
    Hkv = k_pages.shape[2]
    G = Hq // Hkv
    return q.reshape(B, Hkv, G, D), G


def paged_decode_partials(q, k_pages, v_pages, pages, pos, spec):
    """Kernel half of the paged decode op: per-page partial softmaxes
    (m, l [B, Hkv, n_pages, Gp]; acc [B, Hkv, n_pages, Gp, Dp] f32; Gp/Dp
    padded on TPU) from the page-streaming Pallas kernel. Split from the
    merge so `Backend`'s pallas_sharded form can shard_map ONLY this half:
    the shared `combine_pages` merge must run in the CALLER's execution
    context for every backend — a merge inside the jitted shard_map would
    compile its transcendentals in a different fusion context than the
    eager reference merge and drift by an ulp (the parity hazard the
    split-softmax structure exists to avoid)."""
    B, _, Hq, D = q.shape
    qg, G = _paged_layout(q, k_pages)
    pages = pages.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    if _interpret():
        return paged_attention_partials_pallas(
            qg, k_pages, v_pages, pages, pos, window=spec.window,
            softcap=spec.logit_softcap, interpret=True)
    _check_page_rows(k_pages)
    scale = D**-0.5
    qp = _pad_dim(_pad_dim(qg, 2, 8), 3, 128)
    kp = _pad_dim(k_pages, 3, 128)
    vp = _pad_dim(v_pages, 3, 128)
    return paged_attention_partials_pallas(
        qp, kp, vp, pages, pos, window=spec.window,
        softcap=spec.logit_softcap, scale=scale, interpret=False)


def paged_decode_finish(m, l, acc, q):
    """Merge half of the paged decode op: the SHARED `combine_pages` over
    the per-page partials, sliced back to the true head dims and restored
    to model layout [B, 1, Hq, D]. Every backend form calls this in the
    same (caller) context on bitwise-identical partials — which is what
    makes the three-backend equality exact."""
    B, _, Hq, D = q.shape
    Hkv = m.shape[1]
    G = Hq // Hkv
    o = combine_pages(m, l, acc)[:, :, :G, :D]
    return o.astype(q.dtype).reshape(B, 1, Hq, D)


def paged_decode_attention(q, k_pages, v_pages, pages, pos, spec):
    """Fused page-table-indexed decode attention over the paged KV cache.

    q [B,1,Hq,D]; k_pages, v_pages [N_pages, P, Hkv, D] physical pools
    (RoPE pre-applied); pages [B, n_pages] int32 block table; pos [B] int32
    per-slot decode positions. Returns [B,1,Hq,D]: the kernel streams one
    page per grid step into independent partial softmaxes
    (`paged_decode_partials`), and the shared `combine_pages` merge
    produces the output (`paged_decode_finish`). Interpret mode runs the
    kernel unpadded — the same floating-point program as
    `paged_decode_attention_ref` — preserving the serving bit-parity
    contract; on TPU, G pads to sublanes and D to 128 lanes with the scale
    pinned to the true head dim (page_size must be a multiple of the pool
    dtype's sublane tile, `page_tile_rows` — `ServeEngine` validates that
    at config time; `paged_decode_partials` carries the backstop assert for
    direct op callers)."""
    m, l, acc = paged_decode_partials(q, k_pages, v_pages, pages, pos, spec)
    return paged_decode_finish(m, l, acc, q)


def paged_decode_attention_ref(q, k_pages, v_pages, pages, pos, spec):
    """Reference-backend form of `paged_decode_attention`: the same layout
    adapter around the mapped `_page_partial` mirror plus the SAME
    `combine_pages` merge (bit-identical to the kernel)."""
    qg, _ = _paged_layout(q, k_pages)
    m, l, acc = paged_attention_partials_reference(
        qg, k_pages, v_pages, pages.astype(jnp.int32), pos.astype(jnp.int32),
        window=spec.window, softcap=spec.logit_softcap)
    return paged_decode_finish(m, l, acc, q)


def quant_paged_decode_partials(q, k_pages, v_pages, k_scale, v_scale,
                                pages, pos, spec):
    """Kernel half of the int8 paged decode op: per-page partials from the
    quantized page-streaming kernel (`paged_attention_partials_quant_pallas`
    — one [P, D] int8 block + one (1, 1) scale block per grid step,
    dequantized in-VMEM by the shared `_dequant_page` cell). Split from the
    merge for the same caller-context reason as `paged_decode_partials`.
    On TPU the code pools pad D to 128 lanes with ZERO codes — a zero code
    dequantizes to exactly 0.0 under any scale, so padding stays a no-op —
    while the scale arrays are never padded (the head axis is gridded, not
    blocked)."""
    B, _, Hq, D = q.shape
    qg, G = _paged_layout(q, k_pages)
    pages = pages.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    if _interpret():
        return paged_attention_partials_quant_pallas(
            qg, k_pages, v_pages, k_scale, v_scale, pages, pos,
            window=spec.window, softcap=spec.logit_softcap, interpret=True)
    _check_page_rows(k_pages)
    scale = D**-0.5
    qp = _pad_dim(_pad_dim(qg, 2, 8), 3, 128)
    kp = _pad_dim(k_pages, 3, 128)
    vp = _pad_dim(v_pages, 3, 128)
    return paged_attention_partials_quant_pallas(
        qp, kp, vp, k_scale, v_scale, pages, pos, window=spec.window,
        softcap=spec.logit_softcap, scale=scale, interpret=False)


def quant_paged_decode_attention(q, k_pages, v_pages, k_scale, v_scale,
                                 pages, pos, spec):
    """Fused int8 paged decode attention: `paged_decode_attention` with the
    page pool held as int8 codes + per-(page, head) f32 scales
    (`repro.models.attention.QuantPagedKVCache`). Same split structure —
    quantized partials, then the SHARED `combine_pages` merge in the
    caller's context — so the three-backend bitwise contract carries over
    unchanged."""
    m, l, acc = quant_paged_decode_partials(q, k_pages, v_pages, k_scale,
                                            v_scale, pages, pos, spec)
    return paged_decode_finish(m, l, acc, q)


def quant_paged_decode_attention_ref(q, k_pages, v_pages, k_scale, v_scale,
                                     pages, pos, spec):
    """Reference-backend form of `quant_paged_decode_attention`: the mapped
    quant mirror (same `_dequant_page` + `_page_partial` cells) plus the
    SAME `combine_pages` merge (bit-identical to the kernel)."""
    qg, _ = _paged_layout(q, k_pages)
    m, l, acc = paged_attention_partials_quant_reference(
        qg, k_pages, v_pages, k_scale, v_scale, pages.astype(jnp.int32),
        pos.astype(jnp.int32), window=spec.window, softcap=spec.logit_softcap)
    return paged_decode_finish(m, l, acc, q)
