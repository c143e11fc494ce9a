"""Backend dispatch for the CHEF hot loops.

One `Backend` object — selected once (from `ChefConfig.backend` or an
explicit override) and passed down through `run_chef` -> `influence_vector`
-> `inverse_hvp` -> `lr_head.grad/hvp` / `infl_scores` — replaces the
boolean kernel flag that used to be threaded through every call site.

Three implementations of the same three ops (identical semantics, validated
against each other in tests/test_backend.py):

  reference       pure-jnp closed forms (XLA-fused); the semantic oracle.
  pallas          fused Pallas TPU kernels (repro.kernels.ops; interpret-mode
                  on CPU so they run and validate anywhere).
  pallas_sharded  the Pallas kernels wrapped in `shard_map` over the mesh's
                  data axes: rows of Xa/P/Y are split across devices, the
                  row-local `X @ vᵀ` epilogue (infl_scores) stays local, and
                  the grad/HVP partial sums are psum'd — so `run_chef` can
                  score N >> single-device memory under BOTH the Full selector
                  and the Increm-INFL bound evaluation (repro.core.increm
                  dispatches through this object too). `chunk_rows`
                  additionally bounds the per-device working set by
                  lax.map-ing the kernel over row chunks.

The ops (all return f32, matching `repro.kernels.ref` oracles):

  lr_grad(w, Xa, Y, weights, l2)        -> [C, d+1]   Eq. (1) batch gradient
  lr_hvp(w, v, Xa, weights, l2, P=None) -> [C, d+1]   H(w) v
  infl_scores(v, Xa, P, Y, gamma)       -> [N, C]     Eq. (6) score matrix
  probs_scores(w, v, Xa, Y, gamma)      -> [N, C]     fused probs + Eq. (6)

Constructor-phase ops (the DeltaGrad-L half of the speed story — every
computation inside `lr_head.sgd_train` and `deltagrad.deltagrad_replay`
dispatches through these, mirroring how the selector phase dispatches the
four scoring ops above):

  row_gather_layout(Xa)                                  -> Xr
      the array both ops below gather batch rows from: Xa itself on
      reference, the kernels' [N, 1, Dp] view on pallas, Xa with its rows
      padded to the data axes on pallas_sharded. A copy of Xa, so the SGD
      and replay scans lay out once before the loop, not per step.
  minibatch_grad(w, Xr, Y, weights, idx, l2)             -> [C, d+1]
      gathered mini-batch gradient over B_t = Xa[idx] (Eq. 4 left term):
      one fused gather+softmax+grad kernel on pallas; on pallas_sharded
      Xa/Y/weights stay row-sharded and ONLY the gathered [bs, d+1] batch
      rows are all-gathered (masked local take + psum) per step.
  replay_correction(w, Xr, Y_old, Y_new, w_old, w_new,
                    corr_idx, corr_mask, batch_size)     -> [C, d+1]
      fused DeltaGrad correction over the changed slots of B_t (Eq. 4
      right term, Section 4.2): one shared softmax feeds both the old- and
      new-label residual branches; same sharded gather story.

Constructor parity contract: the three backends produce BIT-IDENTICAL
`sgd_train` weights/trajectories and `deltagrad_replay` results (not just
allclose) — the kernels run the same floating-point program as the
reference scan step, and the sharded gather is exact (each batch row owned
by exactly one shard, psum adds zeros elsewhere). tests/test_backend.py
asserts exact equality.

Trajectory placement: `trajectory_sharding` / `constrain_trajectory` /
`shard_trajectory` keep the [T, C, d+1] caches row-sharded over the mesh's
data axes on pallas_sharded (rule: repro.dist.sharding.trajectory_spec),
so the constructor phase scales with the selector phase instead of
replicating T*C*(d+1) floats per device.

Serving ops (the "serve the cleaned model" half of the north star — every
attention call in `Model.prefill` / `Model.decode_step` and the ServeEngine
dispatches through these):

  flash_attention(q, k, v, qpos, kpos, spec)   -> [B, Sq, Hq, D]
      prefill / full-sequence GQA attention (causal + sliding window +
      logit softcap). reference = the pure-jnp blocked online-softmax
      mirror of the Pallas kernel; pallas = the flash kernel;
      pallas_sharded = the kernel shard_mapped HEAD-WISE over the mesh
      `model` axis (each device owns Hkv/m kv heads and their G query
      heads — exact, attention is per-head independent).
  decode_attention(q, k, v, valid, spec)       -> [B, 1, Hq, D]
      one new token against the ring-bounded KV cache (k, v [B, W, Hkv, D];
      valid [W] from repro.models.attention.ring_valid). Same three forms;
      on pallas_sharded the CACHE ITSELF stays head-sharded over `model`
      (rule: repro.dist.sharding.kv_cache_spec, committed by
      `shard_kv_cache`), so per-device cache memory — the resource that
      caps continuous-batching concurrency — scales with devices.
  paged_decode_attention(q, k_pages, v_pages, pages, pos, spec)
      -> [B, 1, Hq, D]
      one new token per slot against the PAGED KV cache (physical page
      pools [N_pages, P, Hkv, D] indexed through a per-slot block table
      [B, n_pages] with PER-SLOT positions [B]) — the production decode
      op the ServeEngine's `paged` cache mode rides. The kernel streams
      pages one per grid step (W-chunked online softmax), so cache size
      never constrains VMEM; on pallas_sharded the pools stay head-sharded
      over `model` (rule: repro.dist.sharding.page_pool_spec). Prefix
      sharing rides the same op unchanged: aliased pages are ordinary block
      -table entries, and speculative verification is just this op with the
      k draft rows as the batch dimension (per-row positions mask each row
      to its own causal extent).

Serving parity contract: prefill AND decode logits are BIT-IDENTICAL across
all three backends (exact equality, not allclose) — the reference forms run
the same floating-point program as the interpret-mode kernels
(kernels/flash_attention._kv_block_step, kernels/decode_attention
._decode_cell are shared verbatim), and the head split is exact.
tests/test_serving.py asserts it; `benchmarks.run --only serving`
re-asserts it in CI (BENCH_serving.json).

Which backend to pick: `reference` for debugging and as the oracle (always
correct, XLA-fused, fastest off-TPU); `pallas` on a single TPU (fused
kernels, no collective overhead); `pallas_sharded` when N (cleaning) or
batch x cache (serving) exceeds one device — requires a mesh and pays
psum/all-gather latency that only wins at scale.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Union

import jax
import jax.numpy as jnp

BACKENDS = ("reference", "pallas", "pallas_sharded")


def _gather_rows_psum(rows, idx, axes):
    """All-gather the global rows `idx` from row-sharded arrays, inside
    shard_map: each device takes its local members of idx (masked local
    take), the rest contribute zeros, and one psum over the data axes
    assembles the replicated [bs, ...] batch. Exact, not approximate:
    every batch row is owned by exactly one shard and the psum adds 0.0
    everywhere else — which is why the sharded constructor path stays
    bit-identical to the reference gather Xa[idx]."""
    n_local = rows[0].shape[0]
    flat = jnp.int32(0)
    for a in axes:  # outermost data axis first (matches row-shard order)
        flat = flat * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    lidx = idx - flat * n_local
    ok = (lidx >= 0) & (lidx < n_local)
    li = jnp.clip(lidx, 0, n_local - 1)
    out = []
    for r in rows:
        g = jnp.take(r, li, axis=0)
        g = jnp.where(ok.reshape((-1,) + (1,) * (r.ndim - 1)), g, 0.0)
        out.append(jax.lax.psum(g, axes))
    return out


@functools.lru_cache(maxsize=128)
def _cached_sharded(backend: "Backend", op: str, static):
    """One jitted shard_map callable per (Backend, op, static key) — the
    static key is a scalar for the scoring/constructor ops and the (hashable)
    AttnSpec for the serving ops.

    Building the closure + shard_map wrapper inline on every call would hand
    JAX a fresh function object each time — every eager invocation (each CG
    iteration, each benchmark rep) would re-trace and re-compile. Backend is
    frozen + hashable precisely so it can key this cache; jit's own cache
    then handles shape polymorphism."""
    return jax.jit(backend._build_sharded(op, static))


@dataclass(frozen=True)
class Backend:
    """Dispatch object for the three CHEF hot ops. Frozen + hashable so it
    can ride through `functools.partial`/jit closures unchanged."""

    name: str = "reference"
    mesh: Any = None  # required for pallas_sharded
    chunk_rows: int = 0  # 0 = whole local shard in one kernel call

    def __post_init__(self):
        if self.name not in BACKENDS:
            raise ValueError(f"unknown backend {self.name!r}; expected one of {BACKENDS}")
        if self.name == "pallas_sharded" and self.mesh is None:
            raise ValueError("pallas_sharded backend needs a mesh (see get_backend)")

    # ------------------------------------------------------------- dispatch
    def lr_grad(self, w, Xa, Y, weights, l2: float) -> jax.Array:
        """Eq. 1 batch gradient of the weighted LR objective -> [C, d+1] f32.

        reference = closed-form jnp; pallas = fused softmax+residual+matmul
        kernel; pallas_sharded = per-shard partial sums psum'd over the data
        axes (rows of Xa/Y/weights split across devices)."""
        if self.name == "reference":
            from repro.core import lr_head

            return lr_head.grad_reference(w, Xa, Y, weights, l2)
        if self.name == "pallas":
            from repro.kernels import ops

            return ops.lr_grad(w, Xa, Y, weights, l2)
        return self._sharded_reduce("lr_grad", (Xa, Y, weights), w, None, l2)

    def lr_hvp(self, w, v, Xa, weights, l2: float, P=None) -> jax.Array:
        """Gauss-Newton (== CE Hessian) vector product H(w) v -> [C, d+1]
        f32 — the CG / power-method inner loop. Same three forms as
        `lr_grad`; P optionally carries precomputed probs (reference/pallas
        recompute them fused when None)."""
        if self.name == "reference":
            from repro.core import lr_head

            return lr_head.hvp_reference(w, v, Xa, weights, l2, P=P)
        if self.name == "pallas":
            from repro.kernels import ops

            return ops.lr_hvp(w, v, Xa, weights, l2, P=P)
        return self._sharded_reduce("lr_hvp", (Xa, weights), w, v, l2)

    def infl_scores(self, v, Xa, P, Y, gamma: float) -> jax.Array:
        """Eq. 6 INFL score matrix [N, C] — the selector-phase hot loop.
        Prefer `probs_scores` when P is not already materialized: on the
        sharded backend it saves a full-N pad + reshard per round."""
        if self.name == "reference":
            from repro.core.influence import infl_scores_reference

            return infl_scores_reference(v, Xa, P, Y, gamma)
        if self.name == "pallas":
            from repro.kernels import ops

            return ops.infl_scores(v, Xa, P, Y, gamma)
        return self._sharded_scores(v, Xa, P, Y, gamma)

    def probs_scores(self, w, v, Xa, Y, gamma: float) -> jax.Array:
        """Fused P = softmax(Xa wᵀ) + Eq. 6 scores [N, C].

        For pallas_sharded this is ONE pad + ONE shard_map: probs are computed
        on the local row shard and fed straight into the local score kernel.
        The unfused form (`probs()` then `infl_scores()`) padded/sliced P to
        global [N, C] and then re-padded Xa/P/Y to the same multiple — a
        redundant full-N copy + reshard on every scoring round."""
        if self.name != "pallas_sharded":
            from repro.core import lr_head

            return self.infl_scores(v, Xa, lr_head.probs(w, Xa), Y, gamma)
        _, dp, lead = self._data_axes()
        if lead is None:
            from repro.core import lr_head
            from repro.kernels import ops

            return ops.infl_scores(v, Xa, lr_head.probs(w, Xa), Y, gamma)
        from repro.kernels.ops import _pad_rows

        n = Xa.shape[0]
        mult = self._row_mult(dp, n)
        Xp, Yp = (_pad_rows(a, mult)[0] for a in (Xa, Y))
        return _cached_sharded(self, "probs_scores", float(gamma))(w, v, Xp, Yp)[:n]

    # ------------------------------------------------- constructor-phase ops
    def row_gather_layout(self, Xa) -> jax.Array:
        """Xa laid out for `minibatch_grad` / `replay_correction` (see the
        module docstring). Exact: zero lanes or zero rows only."""
        if self.name == "reference":
            return Xa
        from repro.kernels import ops

        if self.name == "pallas":
            return ops.row_gather_layout(Xa)
        _, dp, lead = self._data_axes()
        if lead is None:
            return ops.row_gather_layout(Xa)
        # each shard gathers from its 2-D rows; the psum'd batch is laid
        # out inside the shard_map (bs rows, not N)
        return ops._pad_rows(Xa, dp)[0]

    def minibatch_grad(self, w, Xr, Y, weights, idx, l2: float) -> jax.Array:
        """Gathered mini-batch gradient over B_t = Xa[idx] (Eq. 4 left term):
        the SGD-scan step of `sgd_train` and DeltaGrad-L's explicit
        iterations, with Xr = `row_gather_layout(Xa)`. Bit-identical across
        backends (see module docstring)."""
        if self.name == "reference":
            from repro.core import lr_head

            return lr_head.minibatch_grad_reference(w, Xr, Y, weights, idx, l2)
        from repro.kernels import ops

        if self.name == "pallas":
            return ops.minibatch_grad(w, Xr, Y, weights, idx, l2)
        _, dp, lead = self._data_axes()
        if lead is None:
            return ops.minibatch_grad(w, Xr, Y, weights, idx, l2)
        Yp, w8p = (ops._pad_rows(a, dp)[0] for a in (Y, weights))
        return _cached_sharded(self, "minibatch_grad", float(l2))(
            w, idx.astype(jnp.int32), Xr, Yp, w8p)

    def replay_correction(self, w, Xr, Y_old, Y_new, w_old, w_new,
                          corr_idx, corr_mask, batch_size: int) -> jax.Array:
        """Fused DeltaGrad-L replay correction over the changed slots of B_t
        (Eq. 4 right term), with Xr = `row_gather_layout(Xa)`: padded slots
        (corr_mask == 0) contribute exactly zero. Bit-identical across
        backends."""
        if self.name == "reference":
            from repro.core import deltagrad

            return deltagrad.replay_correction_reference(
                w, Xr, Y_old, Y_new, w_old, w_new, corr_idx, corr_mask,
                batch_size)
        from repro.kernels import ops

        if self.name == "pallas":
            return ops.replay_correction(w, Xr, Y_old, Y_new, w_old, w_new,
                                         corr_idx, corr_mask, batch_size)
        _, dp, lead = self._data_axes()
        if lead is None:
            return ops.replay_correction(w, Xr, Y_old, Y_new, w_old, w_new,
                                         corr_idx, corr_mask, batch_size)
        Yop, Ynp, wop, wnp = (
            ops._pad_rows(a, dp)[0] for a in (Y_old, Y_new, w_old, w_new))
        return _cached_sharded(self, "replay_correction", float(batch_size))(
            w, corr_idx.astype(jnp.int32), corr_mask, Xr, Yop, Ynp, wop, wnp)

    # ---------------------------------------------------------- serving ops
    def _model_axis_divides(self, n_kv_heads: int) -> bool:
        """True when the mesh has a `model` axis whose size splits the kv
        heads evenly — the precondition for the head-wise sharded serving
        path (Hq = G*Hkv divides automatically). False -> fall back to the
        unsharded kernel, mirroring the rulebook's divisibility fallback."""
        size = dict(self.mesh.shape).get("model", 0) if self.mesh else 0
        return size > 0 and n_kv_heads % size == 0

    def flash_attention(self, q, k, v, qpos, kpos, spec) -> jax.Array:
        """Prefill / full-sequence GQA attention (model layout: q [B,Sq,Hq,D];
        k, v [B,Skv,Hkv,D]; qpos/kpos absolute positions) -> [B,Sq,Hq,D].

        Bit-identical across backends (serving parity contract, module
        docstring). On pallas_sharded the heads are split over the mesh
        `model` axis; q/k/v arrive replicated or batch-sharded and leave in
        the same layout the caller handed in."""
        from repro.kernels import ops

        if self.name == "reference":
            return ops.flash_attention_ref(q, k, v, qpos, kpos, spec)
        if self.name == "pallas" or not self._model_axis_divides(k.shape[2]):
            return ops.flash_attention(q, k, v, qpos, kpos, spec)
        return _cached_sharded(self, "flash_attention", spec)(
            q, k, v, qpos.astype(jnp.int32), kpos.astype(jnp.int32))

    def decode_attention(self, q, k, v, valid, spec) -> jax.Array:
        """Single-token decode attention over the ring KV cache: q
        [B,1,Hq,D]; k, v [B,W,Hkv,D] dense cache contents; valid [W] slot
        mask (repro.models.attention.ring_valid — ring-bounded for
        sliding-window archs) -> [B,1,Hq,D].

        Bit-identical across backends. On pallas_sharded the cache stays
        head-sharded over `model` (see `shard_kv_cache`) and each device
        attends only its own heads — no cache collective on the decode
        critical path."""
        from repro.kernels import ops

        if self.name == "reference":
            return ops.decode_attention_ref(q, k, v, valid, spec)
        if self.name == "pallas" or not self._model_axis_divides(k.shape[2]):
            return ops.decode_attention(q, k, v, valid, spec)
        return _cached_sharded(self, "decode_attention", spec)(q, k, v, valid)

    def paged_decode_attention(self, q, k_pages, v_pages, pages, pos,
                               spec) -> jax.Array:
        """Single-token decode attention over the PAGED KV cache: q
        [B,1,Hq,D]; k_pages, v_pages [N_pages, P, Hkv, D] physical page
        pools; pages [B, n_pages] int32 per-slot block table; pos [B] int32
        per-slot decode positions -> [B,1,Hq,D]. The kernel streams each
        slot's pages one page per grid step through the scalar-prefetched
        block table (W-chunked online softmax — cache size never constrains
        VMEM), and per-slot validity is derived from the page-table position
        arithmetic inside the shared cell program
        (kernels/paged_attention._page_step).

        Bit-identical across backends. On pallas_sharded the page pools
        stay head-sharded over `model` (rule:
        repro.dist.sharding.page_pool_spec, committed by `shard_kv_cache`);
        the block table and positions are replicated host metadata, so no
        page traffic lands on the decode critical path."""
        from repro.kernels import ops

        if self.name == "reference":
            return ops.paged_decode_attention_ref(q, k_pages, v_pages, pages,
                                                  pos, spec)
        if self.name == "pallas" or not self._model_axis_divides(
                k_pages.shape[2]):
            return ops.paged_decode_attention(q, k_pages, v_pages, pages, pos,
                                              spec)
        # shard_map covers ONLY the per-page partials (per-head independent);
        # the combine_pages merge runs here in the caller's context — the
        # same context every other backend form merges in, which is what
        # keeps the three-way equality exact (see ops.paged_decode_partials)
        m, l, acc = _cached_sharded(self, "paged_decode_attention", spec)(
            q, k_pages, v_pages, pages.astype(jnp.int32),
            pos.astype(jnp.int32))
        return ops.paged_decode_finish(m, l, acc, q)

    def quant_paged_decode_attention(self, q, k_pages, v_pages, k_scale,
                                     v_scale, pages, pos, spec) -> jax.Array:
        """`paged_decode_attention` over the int8 page pool: k_pages /
        v_pages are [N_pages, P, Hkv, D] int8 codes and k_scale / v_scale
        [N_pages, Hkv] f32 per-(page, head) scales
        (repro.models.attention.QuantPagedKVCache). The kernel streams each
        page's codes plus its (1, 1) scale block and dequantizes in-VMEM
        via the shared `_dequant_page` cell — KV crosses HBM at half the
        bf16 byte count and no dense f32 copy ever exists.

        Bit-identical across backends, same split structure as the bf16 op:
        the shard_map (pools head-sharded by page_pool_spec, scales by
        page_scale_spec — same divisibility rule, so the pair can never
        shard inconsistently) covers only the per-page partials, and the
        shared `combine_pages` merge runs here in the caller's context."""
        from repro.kernels import ops

        if self.name == "reference":
            return ops.quant_paged_decode_attention_ref(
                q, k_pages, v_pages, k_scale, v_scale, pages, pos, spec)
        if self.name == "pallas" or not self._model_axis_divides(
                k_pages.shape[2]):
            return ops.quant_paged_decode_attention(
                q, k_pages, v_pages, k_scale, v_scale, pages, pos, spec)
        m, l, acc = _cached_sharded(self, "quant_paged_decode_attention",
                                    spec)(
            q, k_pages, v_pages, k_scale, v_scale, pages.astype(jnp.int32),
            pos.astype(jnp.int32))
        return ops.paged_decode_finish(m, l, acc, q)

    def chunked_prefill(self, q, k, v, qpos, kpos, spec,
                        chunk: int) -> jax.Array:
        """Chunked (memory-efficient) prefill attention: same signature and
        model layout as `flash_attention` plus the KV chunk size, and the
        OUTPUT IS BITWISE `flash_attention`'s for any chunk — only the peak
        score-block memory changes, O(Sq * chunk) instead of O(Sq * Skv)
        (kernels/chunked_prefill.py documents why the chunked fold is
        exact). Long prefill buckets route here behind
        `ServeConfig.prefill_chunk`.

        On pallas_sharded the shard_map covers ONLY the per-chunk fold's
        final split-K partials (head-wise, per-head independent); the
        shared `combine_pages` finish runs in the caller's context like
        every other backend form (parity rule 4)."""
        from repro.kernels import ops

        if self.name == "reference":
            return ops.chunked_prefill_ref(q, k, v, qpos, kpos, spec, chunk)
        if self.name == "pallas" or not self._model_axis_divides(k.shape[2]):
            return ops.chunked_prefill(q, k, v, qpos, kpos, spec, chunk)
        m, l, acc = _cached_sharded(self, "chunked_prefill",
                                    (spec, int(chunk)))(
            q, k, v, qpos.astype(jnp.int32), kpos.astype(jnp.int32))
        return ops.chunked_prefill_finish(m, l, acc, q)

    def local_attention(self, q, k, v, qpos, kpos, spec) -> jax.Array:
        """Banded (sliding-window) prefill attention: `flash_attention`'s
        program with fully-masked band blocks skipped (parity rule 5 —
        skipping an exactly-neutral block is a bitwise no-op), so sliding
        -window archs prefill in O(Sq * window) live work with output
        BITWISE `flash_attention`'s for the same spec. Same three forms;
        the head-wise sharded split is identical to flash's."""
        from repro.kernels import ops

        if self.name == "reference":
            return ops.local_attention_ref(q, k, v, qpos, kpos, spec)
        if self.name == "pallas" or not self._model_axis_divides(k.shape[2]):
            return ops.local_attention(q, k, v, qpos, kpos, spec)
        return _cached_sharded(self, "local_attention", spec)(
            q, k, v, qpos.astype(jnp.int32), kpos.astype(jnp.int32))

    def block_sparse_attention(self, q, k, v, qpos, kpos, block_mask,
                               spec) -> jax.Array:
        """Block-sparse prefill attention: KV blocks with a 0 in
        `block_mask` ([nq, nk] at the `ops.attn_block_mask_shape`
        granularity) are skipped entirely; causal/window still mask
        elements inside enabled blocks. An all-ones mask is bitwise
        `flash_attention`; any mask is bitwise-identical across the three
        backends (the reference mirrors the skip with `lax.cond`). On
        pallas_sharded the mask is replicated host metadata — the head
        split never touches it."""
        from repro.kernels import ops

        if self.name == "reference":
            return ops.block_sparse_attention_ref(q, k, v, qpos, kpos,
                                                  block_mask, spec)
        if self.name == "pallas" or not self._model_axis_divides(k.shape[2]):
            return ops.block_sparse_attention(q, k, v, qpos, kpos,
                                              block_mask, spec)
        return _cached_sharded(self, "block_sparse_attention", spec)(
            q, k, v, qpos.astype(jnp.int32), kpos.astype(jnp.int32),
            block_mask.astype(jnp.int32))

    # ------------------------------------------------ KV cache placement
    def kv_cache_sharding(self, shape, head_axis: int):
        """NamedSharding for one serving KV-cache leaf (kv heads over the
        mesh `model` axis; rule: repro.dist.sharding.kv_cache_spec), or None
        on unsharded backends."""
        if self.name != "pallas_sharded":
            return None
        from jax.sharding import NamedSharding

        from repro.dist.sharding import kv_cache_spec

        return NamedSharding(self.mesh, kv_cache_spec(self.mesh, shape, head_axis))

    def page_pool_sharding(self, shape, head_axis: int):
        """NamedSharding for one paged-KV page-pool leaf (kv heads over the
        mesh `model` axis; rule: repro.dist.sharding.page_pool_spec), or
        None on unsharded backends."""
        if self.name != "pallas_sharded":
            return None
        from jax.sharding import NamedSharding

        from repro.dist.sharding import page_pool_spec

        return NamedSharding(self.mesh,
                             page_pool_spec(self.mesh, shape, head_axis))

    def page_scale_sharding(self, shape, head_axis: int):
        """NamedSharding for one int8 page-pool SCALE leaf ([N_pages, Hkv]
        f32; kv heads — the last axis — over the mesh `model` axis in
        lockstep with the code pools; rule:
        repro.dist.sharding.page_scale_spec), or None on unsharded
        backends."""
        if self.name != "pallas_sharded":
            return None
        from jax.sharding import NamedSharding

        from repro.dist.sharding import page_scale_spec

        return NamedSharding(self.mesh,
                             page_scale_spec(self.mesh, shape, head_axis))

    def shard_kv_cache(self, cache):
        """Outside-jit committed placement of a serving cache pytree: every
        KVCache / QuantKVCache / PagedKVCache leaf goes head-sharded over
        the mesh `model` axis (ring k/v and page pools: axis ndim-2; quant
        scales: axis ndim-1); recurrent state (SSM / RG-LRU),
        cross-attention caches, the pos counter, the paged block table,
        and the paged `refcount` mirror stay untouched — refcounts are
        tiny host-authoritative metadata and remain replicated (rule:
        repro.dist.sharding.refcount_spec). No-op on unsharded backends —
        call sites never branch on the backend name. The ServeEngine
        commits the prefill cache through this so continuous batching
        scales cache memory with devices."""
        if self.name != "pallas_sharded" or cache is None:
            return cache
        from repro.models.attention import (KVCache, PagedKVCache,
                                            QuantKVCache, QuantPagedKVCache)

        def put(x, head_axis):
            return jax.device_put(x, self.kv_cache_sharding(x.shape, head_axis))

        def pput(x):
            return jax.device_put(
                x, self.page_pool_sharding(x.shape, x.ndim - 2))

        def sput(x):
            return jax.device_put(
                x, self.page_scale_sharding(x.shape, x.ndim - 1))

        def walk(node):
            if isinstance(node, QuantPagedKVCache):
                return QuantPagedKVCache(
                    pput(node.k), pput(node.v),
                    sput(node.k_scale), sput(node.v_scale))
            if isinstance(node, QuantKVCache):
                return QuantKVCache(
                    put(node.k, node.k.ndim - 2), put(node.v, node.v.ndim - 2),
                    put(node.k_scale, node.k_scale.ndim - 1),
                    put(node.v_scale, node.v_scale.ndim - 1))
            if isinstance(node, PagedKVCache):
                return PagedKVCache(pput(node.k), pput(node.v))
            if isinstance(node, KVCache):
                return KVCache(put(node.k, node.k.ndim - 2),
                               put(node.v, node.v.ndim - 2))
            if isinstance(node, dict):
                return {k: walk(v) for k, v in node.items()}
            # recurse into PLAIN tuples only (the blocks/tail containers):
            # isinstance(…, tuple) would also match the recurrent-state
            # NamedTuples (RGLRUState, SSDState) and rebuild them as bare
            # tuples, crashing the next decode's attribute access
            if type(node) is tuple:
                return tuple(walk(x) for x in node)
            return node

        return walk(cache)

    # ------------------------------------------- trajectory cache placement
    def trajectory_sharding(self, n_steps: int):
        """NamedSharding for a [T, C, d+1] trajectory cache leaf, or None on
        unsharded backends (rule: repro.dist.sharding.trajectory_spec)."""
        if self.name != "pallas_sharded":
            return None
        from jax.sharding import NamedSharding

        from repro.dist.sharding import trajectory_spec

        return NamedSharding(self.mesh, trajectory_spec(self.mesh, n_steps))

    def constrain_trajectory(self, traj):
        """Inside-jit sharding constraint for a (ws, gs) trajectory pytree:
        tells GSPMD to keep the caches row-sharded over the data axes instead
        of replicating them. No-op on unsharded backends / None trajectory."""
        if traj is None:
            return traj
        sh = self.trajectory_sharding(jax.tree_util.tree_leaves(traj)[0].shape[0])
        if sh is None:
            return traj
        return jax.tree.map(lambda t: jax.lax.with_sharding_constraint(t, sh), traj)

    def constrain_replicated(self, x):
        """Inside-jit constraint pinning x fully replicated (the L-BFGS ring
        buffers of deltagrad_replay). No-op on unsharded backends."""
        if self.name != "pallas_sharded":
            return x
        from jax.sharding import NamedSharding, PartitionSpec

        sh = NamedSharding(self.mesh, PartitionSpec())
        return jax.lax.with_sharding_constraint(x, sh)

    def shard_trajectory(self, traj):
        """Outside-jit committed placement of a trajectory pytree onto the
        row-sharded layout (device_put). jit normalizes a 1-device constraint
        spec away; committing here makes the layout visible on the arrays
        (`.sharding.spec`), which checkpoints/restores and the sharding
        asserts in tests and BENCH_constructor rely on."""
        if traj is None:
            return traj
        sh = self.trajectory_sharding(jax.tree_util.tree_leaves(traj)[0].shape[0])
        if sh is None:
            return traj
        return jax.tree.map(lambda t: jax.device_put(t, sh), traj)

    def unsharded(self) -> "Backend":
        """Variant for small-N side computations (e.g. the validation
        gradient) where shard/psum overhead outweighs the win: reference for
        pallas_sharded — equally correct, XLA-fused, and fast off-TPU too —
        self otherwise. Keeps the which-backend decision inside Backend so
        call sites never branch on the name."""
        return Backend("reference") if self.name == "pallas_sharded" else self

    def probs(self, w, Xa) -> jax.Array:
        """softmax(Xa wᵀ) through the backend: row-sharded for pallas_sharded
        (building the [N, C] P matrix unsharded is exactly the full-N
        materialization the sharded backend exists to avoid)."""
        if self.name != "pallas_sharded":
            from repro.core import lr_head

            return lr_head.probs(w, Xa)
        from repro.kernels.ops import _pad_rows

        _, dp, lead = self._data_axes()
        if lead is None:
            from repro.core import lr_head

            return lr_head.probs(w, Xa)
        n = Xa.shape[0]
        Xp = _pad_rows(Xa, self._row_mult(dp, n))[0]
        return _cached_sharded(self, "probs", 0.0)(w, Xp)[:n]

    # ------------------------------------------------- pallas_sharded paths
    def _data_axes(self):
        from repro.dist.sharding import data_axes_info

        return data_axes_info(self.mesh)

    def _chunked(self, kernel, row_args, n_rows: int, reduce: bool = False):
        """Run `kernel(*rows)` over row chunks of <= chunk_rows via lax.map
        (bounds per-device VMEM/HBM working set). The chunk count comes from
        `_chunk_count`: the smallest *divisor* of n_rows giving chunks within
        the cap, or the balanced count with zero row padding when no sane
        divisor exists (prime-ish n_rows). Zero-padded rows are exact no-ops:
        weight 0 for the partial-sum kernels, sliced back off otherwise.
        `reduce=True` sums the per-chunk results instead of restacking rows."""
        ck = self.chunk_rows
        if ck <= 0 or n_rows <= ck:
            return kernel(*row_args)
        k = self._chunk_count(n_rows)
        cs = -(-n_rows // k)
        if k * cs != n_rows:  # balanced-padding fallback
            pad = k * cs - n_rows
            row_args = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
                        for a in row_args]
        parts = [a.reshape((k, cs) + a.shape[1:]) for a in row_args]
        out = jax.lax.map(lambda t: kernel(*t), tuple(parts))
        if reduce:
            return jnp.sum(out, axis=0)
        return out.reshape((k * cs,) + out.shape[2:])[:n_rows]

    def _chunk_count(self, n_rows: int) -> int:
        """Chunk count for _chunked: smallest divisor of n_rows >= the
        balanced count ceil(n_rows / chunk_rows), found by walking the
        divisors of n_rows (sqrt enumeration) — the old `while n % k: k += 1`
        integer walk degenerated to 1-row chunks on prime-ish sizes. Capped
        by the same balanced logic as `_row_mult`: a divisor whose chunks
        shrink below half the balanced size is rejected in favour of the
        balanced count itself (the caller then zero-pads one partial tail)."""
        k_min = -(-n_rows // self.chunk_rows)
        divs = set()
        i = 1
        while i * i <= n_rows:
            if n_rows % i == 0:
                divs.add(i)
                divs.add(n_rows // i)
            i += 1
        k_div = min((d for d in divs if d >= k_min), default=None)
        cs_bal = -(-n_rows // k_min)
        if k_div is not None and n_rows // k_div >= (cs_bal + 1) // 2:
            return k_div
        return k_min

    def _row_mult(self, dp: int, n: int) -> int:
        """Row-padding multiple: shards must be equal and, when the local
        shard will be chunked, divisible into balanced chunks <= chunk_rows.
        Balancing (ceil(shard / n_chunks), not chunk_rows itself) keeps the
        padding bounded: naively padding to dp*chunk_rows nearly doubles the
        scored rows for N just past a chunk boundary (e.g. N = chunk+1)."""
        ck = self.chunk_rows
        if ck <= 0 or n <= dp * ck:
            return dp
        shard = -(-n // dp)
        k = -(-shard // ck)
        return dp * (-(-shard // k))

    def _build_sharded(self, op: str, static: float):
        """Construct the shard_map'd computation for one op. Called only via
        _cached_sharded, so the returned function object is stable and JAX's
        trace/compile caches actually hit."""
        from jax.sharding import PartitionSpec as Pspec

        from repro.dist.compat import shard_map_compat
        from repro.kernels import ops

        ba, _, lead = self._data_axes()
        rep2 = Pspec(None, None)
        row2 = Pspec(lead, None)
        row1 = Pspec(lead)

        if op in ("flash_attention", "decode_attention",
                  "paged_decode_attention", "quant_paged_decode_attention",
                  "chunked_prefill", "local_attention",
                  "block_sparse_attention"):
            # serving ops shard the HEAD axis over `model` (not the data
            # axes): each device runs the unsharded kernel on its own
            # Hkv/m kv heads — exact, attention is per-head independent.
            # heads4 covers q [B,1,Hq,D] (axis 2 = Hq) AND the paged pools
            # [N_pages, P, Hkv, D] (axis 2 = Hkv): consecutive Hq blocks are
            # exactly the G query heads of consecutive kv-head blocks.
            # (specs come from the repro.dist.sharding rulebook)
            from repro.dist.sharding import (attn_activation_spec,
                                             attn_partial_specs)

            heads4 = attn_activation_spec()
            part4, part5 = attn_partial_specs()
            if op == "flash_attention":
                def local(qq, kk, vv, qp, kp):
                    return ops.flash_attention(qq, kk, vv, qp, kp, static)

                return shard_map_compat(
                    local, self.mesh,
                    (heads4, heads4, heads4, Pspec(None), Pspec(None)), heads4)
            if op == "local_attention":
                def local(qq, kk, vv, qp, kp):
                    return ops.local_attention(qq, kk, vv, qp, kp, static)

                return shard_map_compat(
                    local, self.mesh,
                    (heads4, heads4, heads4, Pspec(None), Pspec(None)), heads4)
            if op == "block_sparse_attention":
                # the [nq, nk] block mask is replicated host metadata —
                # every head shard skips the identical block set
                def local(qq, kk, vv, qp, kp, bm):
                    return ops.block_sparse_attention(qq, kk, vv, qp, kp,
                                                      bm, static)

                return shard_map_compat(
                    local, self.mesh,
                    (heads4, heads4, heads4, Pspec(None), Pspec(None),
                     Pspec(None, None)), heads4)
            if op == "chunked_prefill":
                # partials only — the combine_pages finish happens outside
                # the shard_map in the caller's context
                # (Backend.chunked_prefill); partial leaves carry heads on
                # axis 1: m, l [B, Hq, 1, Sq], acc [B, Hq, 1, Sq, D]
                spec, chunk = static

                def local(qq, kk, vv, qp, kp):
                    return ops.chunked_prefill_partials(qq, kk, vv, qp, kp,
                                                        spec, chunk)

                return shard_map_compat(
                    local, self.mesh,
                    (heads4, heads4, heads4, Pspec(None), Pspec(None)),
                    (part4, part4, part5))
            if op == "paged_decode_attention":
                # partials only — the merge happens outside the shard_map in
                # the caller's context (Backend.paged_decode_attention);
                # partial leaves carry heads on axis 1: m, l
                # [B, Hkv, n_pages, G], acc [B, Hkv, n_pages, G, D]
                def local(qq, kk, vv, pt, ps):
                    return ops.paged_decode_partials(qq, kk, vv, pt, ps,
                                                     static)

                return shard_map_compat(
                    local, self.mesh,
                    (heads4, heads4, heads4, Pspec(None, None), Pspec(None)),
                    (part4, part4, part5))
            if op == "quant_paged_decode_attention":
                # same partials-only split as the bf16 paged op; the int8
                # code pools shard like the bf16 pools (heads on axis 2) and
                # the [N_pages, Hkv] scale arrays shard their LAST axis in
                # lockstep (rule: repro.dist.sharding.page_scale_spec)
                scale2 = Pspec(None, "model")

                def local(qq, kk, vv, ks, vs, pt, ps):
                    return ops.quant_paged_decode_partials(
                        qq, kk, vv, ks, vs, pt, ps, static)

                return shard_map_compat(
                    local, self.mesh,
                    (heads4, heads4, heads4, scale2, scale2,
                     Pspec(None, None), Pspec(None)),
                    (part4, part4, part5))

            def local(qq, kk, vv, vm):
                return ops.decode_attention(qq, kk, vv, vm, static)

            return shard_map_compat(
                local, self.mesh, (heads4, heads4, heads4, Pspec(None)), heads4)

        if op == "probs":
            def local(ww, xs):
                from repro.core import lr_head

                return self._chunked(lambda x: lr_head.probs(ww, x), (xs,), xs.shape[0])

            return shard_map_compat(local, self.mesh, (rep2, row2), row2)

        if op == "probs_scores":
            def local(ww, vv, xs, ys):
                from repro.core import lr_head

                def kern(x, y):
                    return ops.infl_scores(vv, x, lr_head.probs(ww, x), y, static)

                return self._chunked(kern, (xs, ys), xs.shape[0])

            return shard_map_compat(local, self.mesh, (rep2, rep2, row2, row2), row2)

        if op == "infl_scores":
            def local(vv, xs, ps, ys):
                return self._chunked(
                    lambda x, p, y: ops.infl_scores(vv, x, p, y, static),
                    (xs, ps, ys), xs.shape[0],
                )

            return shard_map_compat(local, self.mesh, (rep2, row2, row2, row2), row2)

        if op == "minibatch_grad":
            def local(ww, idxg, xs, ys, w8s):
                xb, yb, wb = _gather_rows_psum((xs, ys, w8s), idxg, ba)
                # gather is the identity here (the batch is already
                # assembled), so the fused kernel's take() is exact
                return ops.minibatch_grad(
                    ww, ops.row_gather_layout(xb), yb, wb,
                    jnp.arange(idxg.shape[0], dtype=jnp.int32), static)

            return shard_map_compat(
                local, self.mesh, (rep2, Pspec(None), row2, row2, row1), rep2)

        if op == "replay_correction":
            def local(ww, ci, cm, xs, yos, yns, wos, wns):
                xb, yo, yn, wo, wn = _gather_rows_psum(
                    (xs, yos, yns, wos, wns), ci, ba)
                return ops.replay_correction(
                    ww, ops.row_gather_layout(xb), yo, yn, wo, wn,
                    jnp.arange(ci.shape[0], dtype=jnp.int32), cm, int(static))

            return shard_map_compat(
                local, self.mesh,
                (rep2, Pspec(None), Pspec(None), row2, row2, row2, row1, row1),
                rep2)

        if op == "lr_grad":
            def local(ww, vv, xs, ys, w8s):
                kernel = lambda x, y, w8: ops.lr_grad(ww, x, y, w8, 0.0) * x.shape[0]
                total = self._chunked(kernel, (xs, ys, w8s), xs.shape[0], reduce=True)
                return jax.lax.psum(total, ba)

            in_specs = (rep2, rep2, row2, row2, row1)
        else:  # lr_hvp
            def local(ww, vv, xs, w8s):
                kernel = lambda x, w8: ops.lr_hvp(ww, vv, x, w8, 0.0) * x.shape[0]
                total = self._chunked(kernel, (xs, w8s), xs.shape[0], reduce=True)
                return jax.lax.psum(total, ba)

            in_specs = (rep2, rep2, row2, row1)
        return shard_map_compat(local, self.mesh, in_specs, rep2)

    def _sharded_scores(self, v, Xa, P, Y, gamma: float) -> jax.Array:
        from repro.kernels import ops
        from repro.kernels.ops import _pad_rows

        _, dp, lead = self._data_axes()
        if lead is None:
            return ops.infl_scores(v, Xa, P, Y, gamma)
        n = Xa.shape[0]
        # padded rows produce garbage scores locally and are sliced off here
        mult = self._row_mult(dp, n)
        Xp, Pp, Yp = (_pad_rows(a, mult)[0] for a in (Xa, P, Y))
        return _cached_sharded(self, "infl_scores", float(gamma))(v, Xp, Pp, Yp)[:n]

    def _sharded_reduce(self, op: str, row_args, w, v, l2: float) -> jax.Array:
        """Shared grad/HVP path: per-shard partial sums + psum over data axes.

        The local kernel runs with l2=0 and its 1/N_local normalization is
        undone, so the psum'd total divided by the true N plus the l2 term
        reproduces the reference batch objective exactly. Padded rows carry
        weight 0 => zero contribution."""
        from repro.kernels import ops
        from repro.kernels.ops import _pad_rows

        _, dp, lead = self._data_axes()
        n = row_args[0].shape[0]
        if lead is None:
            if op == "lr_grad":
                return ops.lr_grad(w, *row_args, l2)
            return ops.lr_hvp(w, v, row_args[0], row_args[1], l2)
        mult = self._row_mult(dp, n)
        padded = [_pad_rows(a, mult)[0] for a in row_args]
        vv = w if v is None else v  # placeholder arg keeps one code path
        total = _cached_sharded(self, op, 0.0)(w, vv, *padded)
        target = w if op == "lr_grad" else v
        return total / n + l2 * target.astype(jnp.float32)


def get_backend(spec: Union[Backend, str, None] = None, *, mesh=None,
                chunk_rows: int = 0) -> Backend:
    """Resolve a backend spec (Backend | name | None) to a Backend.

    None -> reference. For pallas_sharded with no mesh given, the locally
    visible devices become a trivial data-parallel mesh (host_mesh).

    An explicit Backend passes through with its fields winning, except that
    unset fields (chunk_rows == 0) are filled from the kwargs — so
    run_chef(backend=get_backend('pallas_sharded', mesh=prod_mesh)) still
    picks up ChefConfig.score_chunk instead of silently disabling chunking.
    """
    if isinstance(spec, Backend):
        if chunk_rows and spec.chunk_rows == 0:
            return Backend(spec.name, spec.mesh, chunk_rows)
        return spec
    name = spec or "reference"
    if name == "pallas_sharded" and mesh is None:
        from repro.launch.mesh import host_mesh

        mesh = host_mesh()
    if name != "pallas_sharded":
        mesh = None  # keep reference/pallas Backends hashable & comparable
    return Backend(name, mesh, chunk_rows)
