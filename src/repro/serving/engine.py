"""Batched serving: jitted prefill / decode steps + a continuous-batching
engine used by examples/serve_model.py and the serve driver.

Every attention call dispatches through the one `repro.core.backend.Backend`
object (`reference` | `pallas` | `pallas_sharded`) — the same dispatch layer
the cleaning loop's scoring and constructor phases ride — with BIT-IDENTICAL
logits across the three backends for both prefill and decode
(tests/test_serving.py; re-asserted by `benchmarks.run --only serving`).
On `pallas_sharded` the KV cache is committed head-sharded over the mesh
`model` axis (`Backend.shard_kv_cache`), so the cache memory that caps
batch-slot concurrency scales with devices.

Two cache disciplines, selected by `ServeConfig.cache`:

* ``paged`` (the default for attention-only decoder archs, sliding-window
  included — the prefill keeps every position's K/V via
  ``Model.prefill(full_cache=True)`` and the window is enforced as
  decode-time page validity) — a block-table + free-list PAGED KV cache
  with PER-SLOT decode positions. Each admitted request gets pages from a
  shared physical pool for exactly ceil((prompt + budget) / page_size)
  tokens, is prefilled SOLO at a power-of-two bucket of its own prompt
  length (right-padded; the causal mask is the pad mask), and decodes at
  its own absolute positions. A
  request's token stream — and its logits, bitwise — is therefore
  INDEPENDENT of batching: a mid-stream join decodes exactly like a solo
  un-padded run (tests/test_serving.py asserts bitwise logit equality on
  all three backends). Prefill widths are bucketed, so the set of traced
  prefill shapes stays O(log max_len) no matter how requests stagger.

* ``ring`` — the seed engine's static ring cache with ONE shared position
  counter, kept for one release as the differential-testing oracle. Joins
  prefill the incoming prompt LEFT-padded to the batch's current position,
  so pad tokens are attended and a joined request decodes under pad context
  at the join position (deterministic given the request stream, but not
  invariant to batching — the wart the paged path removes). Each distinct
  join position also traces a fresh prefill shape; that recompile is
  inherent to the shared counter and is likewise fixed only by `paged`.

``cache="auto"`` resolves to `paged` when the arch supports it (attention
-only decoder — int8-quantized KV included) and `ring` otherwise (SSM /
RG-LRU recurrent state, enc-dec).

With ``Model.kv_dtype = jnp.int8`` the paged pools hold int8 codes plus one
symmetric f32 scale per (page, kv head) (`attention.QuantPagedKVCache`):
prefill commits quantize per page (scale = max|x|/127 over the page's
committed tokens), decode writes fold each token into a RUNNING-MAX page
scale (requantize-on-growth; bit-exact when the scale is unchanged), and
the engine zeroes the scale rows of every page it allocates so a recycled
page cannot leak its previous tenant's scale into the running max. The
int8 path keeps the paged discipline's batching invariance bitwise on all
three backends, but prefix sharing and speculative decode are forced OFF:
a shared tail prefill would attend over dequantized prefix K/V where the
solo run saw full precision, and a rejected draft's write can GROW a page
scale that position truncation cannot shrink back. Ring-int8 stays the
differential oracle at the token level (per-page vs per-token scales make
logits close, not bitwise — the documented deviation; see
serving/README.md).

For sliding-window archs the paged engine also RETIRES pages
(``ServeConfig.retire_pages``, default on): after each decode round, any
block-table entry whose whole page span has slid out of the attention
window is redirected to the trash page and un-pinned — freed for
re-allocation once no other table row or prefix-index entry references it
(an aliased prefix page is only un-pinned, never freed under a sharer).
Out-of-window pages contribute exactly the neutral partial to paged
attention, which is also what the trash-page skip contributes, so
retirement is bitwise invisible in the output while lifting slot
concurrency under long prompts on a shrunk pool.

On top of the paged discipline, two production optimizations (both OFF the
parity hook — outputs stay bitwise identical to the plain paged run):

* **Prefix sharing** (``ServeConfig.share_prefix``, default on): admission
  keys every FULL page a committed prompt covers in a prefix index (exact
  token bytes, no hash collisions possible). A later request whose prompt
  extends an indexed block-aligned prefix ALIASES those physical pages in
  its block table instead of re-prefilling them — only the unshared tail
  runs (`Model.prefill_tail`, at the solo run's kv bucket so the logits are
  bitwise the solo prefill's), so prefill work for a batch of B requests
  sharing an S-token prefix is ~O(B * tail + S) instead of O(B * (S+tail)).
  Page ownership is a host-side refcount array (device mirror
  ``cache["refcount"]``, replicated): index entries and table rows each
  hold a reference, pages free only at refcount zero, and a write aimed at
  a page with refcount > 1 first COPIES it onto a fresh page and redirects
  the slot's table row (copy-on-write — never triggered by the normal
  write paths, which only touch positions past the shared boundary; the
  guard is what makes that an invariant rather than an accident). Index
  entries are evicted LIFO on pool pressure, deepest-page-first, so a
  chain never strands a pinned continuation. Sharing is restricted to
  prompts whose kv bucket falls in the same flash block class (both <= 128
  or both > 128) — the validated bitwise-stability envelope. The prefix
  index and its pinned pages PERSIST across `run()` waves: the physical
  pool + free list survive as the engine's warm pool, so a later wave's
  request whose prompt repeats an earlier wave's aliases those pages
  without re-prefilling (the repeated-annotation serving pattern — e.g.
  `repro.stream.ModelAnnotator`'s fixed task prefix). Work counters
  (`ServeEngine.stats`) still reset per run.

* **Speculative multi-token decode** (``ServeConfig.spec_k`` > 1): each
  step drafts k-1 continuation tokens by prompt-lookup (most recent
  earlier occurrence of the current token in the request's own context),
  then verifies draft+current in ONE paged decode call with the k rows as
  the batch dimension — every row shares the slot's block table and
  carries its own position, so the per-row causal masks make the single
  call an exact multi-token decode. The greedy acceptance rule keeps the
  longest prefix of drafts matching the verified argmaxes (>= 1 token
  always emitted); rejected rows' K/V writes are rolled back by pure
  position truncation (stale rows are masked, then overwritten). Emitted
  tokens AND logits are bitwise identical to plain decode."""
from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass, field, replace
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.timing import span


def make_prefill_step(model, backend=None, cache_len=None):
    """Closure for jitting `model.prefill` (dry-run cells + the engine).
    `cache_len` fixes the allocated KV capacity (the engine passes its
    max_len so decode never wraps the ring); None allocates prompt-sized."""
    def prefill_step(params, batch):
        return model.prefill(params, batch, cache_len=cache_len,
                             backend=backend)

    return prefill_step


def make_decode_step(model, backend=None):
    """Closure for jitting `model.decode_step` (cache donated by callers)."""
    def decode_step(params, cache, batch):
        return model.decode_step(params, cache, batch, backend=backend)

    return decode_step


def greedy(logits: jax.Array) -> jax.Array:
    """Greedy next-token ids [B, 1] from last-position logits."""
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)[:, None]


def check_page_size(page_size: int, pool_dtype, *, compiled: bool) -> None:
    """Validate a paged-cache page size for its pool dtype. Compiled (TPU)
    pages must be whole native sublane tiles of the pool dtype — 8 rows for
    f32, 16 for bf16, 32 for int8 (`kernels.paged_attention.page_tile_rows`)
    — so each head's [P, D] page slice is a full tile operand."""
    from repro.kernels.paged_attention import page_tile_rows

    if page_size < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    rows = page_tile_rows(pool_dtype)
    if compiled and page_size % rows:
        raise ValueError(
            f"TPU paged cache with {jnp.dtype(pool_dtype).name} pools needs "
            f"page_size % {rows} == 0, got {page_size}")


def bucket_len(n: int, lo: int = 8) -> int:
    """Round `n` up to a power-of-two bucket (>= lo): the paged engine
    prefills at bucketed widths so many staggered request lengths trace
    only O(log max_len) distinct prefill shapes."""
    w = max(int(lo), 1)
    while w < n:
        w *= 2
    return w


@dataclass
class ServeConfig:
    """ServeEngine configuration (see the module docstring for the cache
    disciplines). `num_pages=0` sizes the pool to cover every slot's
    worst case plus the reserved trash page — the memory-conservative
    default; production deployments shrink it to oversubscribe slots
    against observed request lengths (admission control blocks until
    enough pages free up)."""

    batch_size: int = 4
    max_len: int = 256          # per-request prompt + decode budget bound
    cache: str = "auto"         # "auto" | "paged" | "ring"
    page_size: int = 16         # tokens per physical page (paged only)
    num_pages: int = 0          # physical pool size; 0 = auto-size
    bucket_min: int = 8         # smallest power-of-two prefill bucket
    trace_logits: bool = False  # record per-request logits on Request.logits
    share_prefix: bool = True   # alias shared prefixes; pool persists runs
    spec_k: int = 0             # speculative rows per decode step (<=1 = off)
    prefill_chunk: int = 0      # chunked-prefill KV span; 0 = full flash
    prefix_cap: int = 0         # max warm prefix-index entries; 0 = unbounded
    retire_pages: bool = True   # free fully-out-of-window pages per round


@dataclass
class Request:
    """One generation request: prompt token ids + a decode budget.

    The engine fills `out` (generated token ids), `entry_width` (the
    prefill width the request entered at: its power-of-two prompt bucket on
    `paged`, the wave/join width on `ring` — what the ring-oracle tests
    replay), and, with `ServeConfig.trace_logits`, `logits` (one [V] row
    per generated token — the bitwise joined==solo evidence)."""

    uid: int
    prompt: np.ndarray  # [S] int32
    max_new: int
    out: list = field(default_factory=list)
    done: bool = False
    entry_width: int = -1
    logits: list = field(default_factory=list)


def _splice_slot(dst: dict, src: dict, slot: int) -> dict:
    """Copy batch slot `slot` of cache pytree `src` into `dst` (a ring-mode
    mid-stream join). Stacked super-block leaves carry batch on axis 1
    (leading layers dim), tail leaves on axis 0; the shared pos counter is
    equal on both sides by construction (the join prefill is left-padded to
    it)."""
    def sub(axis):
        def f(a, b):
            idx = [slice(None)] * a.ndim
            idx[axis] = slot
            return a.at[tuple(idx)].set(b[tuple(idx)])

        return f

    return {
        "blocks": jax.tree.map(sub(1), dst["blocks"], src["blocks"]),
        "tail": jax.tree.map(sub(0), dst["tail"], src["tail"]),
        "pos": dst["pos"],
    }


class ServeEngine:
    """Continuous-batching greedy-decode engine over `batch_size` static
    slots, Backend-dispatched end to end.

    `max_len` bounds each request's prompt + decode budget (and sizes the
    ring capacity / paged block table); the `backend` spec resolves through
    `repro.core.backend.get_backend` and selects the attention
    implementation for prefill AND decode. Cache discipline (paged vs ring)
    comes from `config` — see the module docstring."""

    def __init__(self, model, params, batch_size: Optional[int] = None,
                 max_len: Optional[int] = None, backend=None,
                 config: Optional[ServeConfig] = None):
        from repro.core.backend import get_backend
        from repro.models import transformer as T

        cfg = config or ServeConfig()
        if batch_size is not None:
            cfg = replace(cfg, batch_size=batch_size)
        if max_len is not None:
            cfg = replace(cfg, max_len=max_len)
        self.config = cfg
        self.model = model
        self.params = params
        self.B = cfg.batch_size
        self.max_len = cfg.max_len
        self.backend = get_backend(backend) if backend is not None else None
        paged_ok = T.paged_supported(model.cfg)
        if cfg.cache == "auto":
            self.cache_mode = "paged" if paged_ok else "ring"
        elif cfg.cache == "paged" and not paged_ok:
            raise ValueError(
                f"cache='paged' unsupported for {model.cfg.name} "
                "(recurrent blocks / enc-dec) — use 'ring' or 'auto'")
        elif cfg.cache not in ("paged", "ring"):
            raise ValueError(f"unknown cache mode {cfg.cache!r}")
        else:
            self.cache_mode = cfg.cache
        self._quant = (self.cache_mode == "paged"
                       and model.kv_dtype == jnp.int8)
        if self._quant:
            if cfg.spec_k > 1:
                # a rejected draft row's write can GROW a page's running-max
                # scale; position truncation cannot shrink it back, so spec
                # output would differ bitwise from plain decode
                raise ValueError(
                    "spec_k > 1 is unsupported with int8 KV pools "
                    "(draft rollback cannot undo a grown page scale)")
            # a shared-prefix tail prefill attends over DEQUANTIZED prefix
            # K/V where the solo run saw full precision — not bitwise the
            # solo logits, so the aliasing optimization is forced off
            cfg = replace(cfg, share_prefix=False)
            self.config = cfg
        # sliding-window page retirement is legal only when EVERY block
        # masks beyond the window — one full-attention layer still reads
        # every page. attn_kind is arch-global, so the window is uniform.
        w = model.cfg.sliding_window
        windowed = w > 0 and all(
            k == "local" or model.cfg.attn_kind == "sliding"
            for k in model.cfg.block_pattern)
        self._retire_window = w if (cfg.retire_pages and windowed) else 0
        self.prefill_widths: set = set()  # distinct traced prefill widths
        self._decode = jax.jit(make_decode_step(model, self.backend),
                               donate_argnums=(1,))
        if self.cache_mode == "ring":
            self._prefill = jax.jit(
                make_prefill_step(model, self.backend, cache_len=cfg.max_len))
        else:
            pool_dtype = jnp.int8 if self._quant else model.param_dtype
            # interpret mode (CPU) takes any page size; fail at config time,
            # not on the first decode step after admission+prefill work
            check_page_size(cfg.page_size, pool_dtype,
                            compiled=jax.default_backend() == "tpu")
            self.table_pages = -(-cfg.max_len // cfg.page_size)
            # auto pool: full per-slot coverage + the reserved trash page
            self.num_pages = cfg.num_pages or (
                1 + self.B * self.table_pages)
            self._paged_prefill: dict = {}  # bucket width -> jitted prefill
            self._paged_commit: dict = {}   # bucket width -> jitted commit
            self._tail_prefill: dict = {}   # (tail_w, n_share, kv_len) -> jit
            self._tail_commit: dict = {}    # tail bucket width -> jitted
            self._copy_page = None          # jitted CoW page duplication
            self._reset_scales = None       # jitted int8 scale-row zeroing
            # per-run allocator state, (re)built by _paged_init:
            self.page_refs = np.zeros(self.num_pages, np.int32)
            self._prefix_index: "OrderedDict" = OrderedDict()
            # lifetime count of prefix-index entries evicted (pool-pressure
            # LIFO + prefix_cap LRU) — persists across run() waves, mirrored
            # into the per-run stats dict
            self._prefix_evictions = 0
            self._slot_rows: list = [None] * self.B
            self.stats: dict = {}
            # with share_prefix, the (cache, free-list) pool survives run()
            # waves so index-pinned prefix pages stay resident and a later
            # wave's identical prompt aliases them (set at run end)
            self._pool = None
        if cfg.spec_k > 1 and self.cache_mode != "paged":
            raise ValueError("spec_k needs the paged cache discipline")

    # ------------------------------------------------------------ shared bits
    def _commit_cache(self, cache):
        """Pin KV leaves head-sharded over the mesh model axis (no-op off
        pallas_sharded) so continuous batching scales cache with devices."""
        if self.backend is None:
            return cache
        return self.backend.shard_kv_cache(cache)

    def run(self, requests: list) -> list:
        """Serve `requests` to completion; returns them in finish order."""
        with span("repro.serve.run", n=len(requests)):
            pending, done = [], []
            for r in requests:
                # a zero-budget request never enters a slot: in a wave it
                # would be dropped from the results, and as a mid-stream join
                # it would set remaining = -1 and spin the decode loop forever
                if r.max_new <= 0:
                    r.done = True
                    done.append(r)
                else:
                    pending.append(r)
            if self.cache_mode == "paged":
                if self.config.spec_k > 1:
                    return self._run_paged_spec(pending, done)
                return self._run_paged(pending, done)
            return self._run_ring(pending, done)

    # ------------------------------------------------------------- paged path
    def _bucket(self, n: int) -> int:
        return bucket_len(n, self.config.bucket_min)

    def _get_paged_prefill(self, width: int):
        if width not in self._paged_prefill:
            model, backend = self.model, self.backend

            chunk = self.config.prefill_chunk

            def prefill(params, toks, last_pos):
                # full_cache: keep EVERY position's K/V (no sliding-window
                # ring bound) so the page commit sees the whole prompt —
                # the window is a decode-time validity mask on pages
                return model.prefill(params, {"tokens": toks},
                                     cache_len=width, backend=backend,
                                     last_pos=last_pos, full_cache=True,
                                     prefill_chunk=chunk)

            self._paged_prefill[width] = jax.jit(prefill)
        return self._paged_prefill[width]

    def _get_paged_commit(self, width: int):
        if width not in self._paged_commit:
            from repro.models import attention as attn_lib

            def commit(cache, dense, page_row, length):
                def walk(pool, dn):
                    if isinstance(pool, attn_lib.PagedKVCache):
                        return attn_lib.paged_commit(pool, dn, page_row,
                                                     length, width)
                    if isinstance(pool, attn_lib.QuantPagedKVCache):
                        return attn_lib.quant_paged_commit(pool, dn, page_row,
                                                           length, width)
                    if isinstance(pool, dict):
                        return {k: walk(pool[k], dn[k]) for k in pool}
                    if type(pool) is tuple:
                        return tuple(walk(a, b) for a, b in zip(pool, dn))
                    return pool

                new = dict(cache)
                new["blocks"] = walk(cache["blocks"], dense["blocks"])
                new["tail"] = walk(cache["tail"], dense["tail"])
                return new

            self._paged_commit[width] = jax.jit(commit)
        return self._paged_commit[width]

    def _get_tail_prefill(self, tail_w: int, n_share: int, kv_len: int):
        """Jitted tail-only prefill, keyed on (tail bucket, shared pages,
        solo kv bucket) — all three are static trace parameters: the tail
        bucket shapes the token batch, `n_share` slices the block table,
        and `kv_len` pins the attention kv width to the solo program (the
        bitwise-parity anchor; see Model.prefill_tail)."""
        key = (tail_w, n_share, kv_len)
        if key not in self._tail_prefill:
            model, backend = self.model, self.backend

            chunk = self.config.prefill_chunk

            def prefill(params, toks, cache, page_row, last_pos):
                return model.prefill_tail(
                    params, {"tokens": toks}, cache, page_row=page_row,
                    share_pages=n_share, kv_len=kv_len, last_pos=last_pos,
                    backend=backend, prefill_chunk=chunk)

            self._tail_prefill[key] = jax.jit(prefill)
        return self._tail_prefill[key]

    def _get_tail_commit(self, tail_w: int):
        """Jitted scatter of a tail-only prefill cache into the slot's pages
        at a dynamic offset (`start` = shared-prefix length): the tail
        analogue of `_get_paged_commit`."""
        if tail_w not in self._tail_commit:
            from repro.models import attention as attn_lib

            def commit(cache, dense, page_row, start, length):
                def walk(pool, dn):
                    if isinstance(pool, attn_lib.PagedKVCache):
                        return attn_lib.paged_commit_tail(
                            pool, dn, page_row, start, length, tail_w)
                    if isinstance(pool, attn_lib.QuantPagedKVCache):
                        # unreachable: __init__ forces share_prefix off for
                        # int8 pools, so no tail prefill is ever committed
                        raise TypeError(
                            "tail commit is unsupported for int8 KV pools")
                    if isinstance(pool, dict):
                        return {k: walk(pool[k], dn[k]) for k in pool}
                    if type(pool) is tuple:
                        return tuple(walk(a, b) for a, b in zip(pool, dn))
                    return pool

                new = dict(cache)
                new["blocks"] = walk(cache["blocks"], dense["blocks"])
                new["tail"] = walk(cache["tail"], dense["tail"])
                return new

            self._tail_commit[tail_w] = jax.jit(commit)
        return self._tail_commit[tail_w]

    def _get_copy_page(self):
        """Jitted physical page duplication across every layer pool — the
        device half of copy-on-write (`attention.paged_copy_page`)."""
        if self._copy_page is None:
            from repro.models import attention as attn_lib

            def copy(cache, src, dst):
                def walk(pool):
                    if isinstance(pool, (attn_lib.PagedKVCache,
                                         attn_lib.QuantPagedKVCache)):
                        return attn_lib.paged_copy_page(pool, src, dst)
                    if isinstance(pool, dict):
                        return {k: walk(v) for k, v in pool.items()}
                    if type(pool) is tuple:
                        return tuple(walk(x) for x in pool)
                    return pool

                new = dict(cache)
                new["blocks"] = walk(cache["blocks"])
                new["tail"] = walk(cache["tail"])
                return new

            self._copy_page = jax.jit(copy)
        return self._copy_page

    def _get_reset_scales(self):
        """Jitted zeroing of the int8 pools' per-(page, head) scale rows for
        a fixed-size page-id vector — called on every page allocation so a
        page recycled through the free list cannot leak its previous
        tenant's running-max scale into the new tenant's decode writes
        (outputs must be a pure function of the request, not pool
        history). The id vector is padded to `table_pages` entries with the
        trash page 0 (whose scale row is never read), keeping the traced
        shape unique."""
        if self._reset_scales is None:
            from repro.models import attention as attn_lib

            def reset(cache, page_ids):
                def walk(pool):
                    if isinstance(pool, attn_lib.QuantPagedKVCache):
                        return attn_lib.paged_reset_scales(pool, page_ids)
                    if isinstance(pool, dict):
                        return {k: walk(v) for k, v in pool.items()}
                    if type(pool) is tuple:
                        return tuple(walk(x) for x in pool)
                    return pool

                new = dict(cache)
                new["blocks"] = walk(cache["blocks"])
                new["tail"] = walk(cache["tail"])
                return new

            self._reset_scales = jax.jit(reset)
        return self._reset_scales

    def _reset_page_scales(self, cache, pages: list):
        """Zero the scale rows of freshly allocated `pages` (int8 pools
        only; a bf16 pool has no scales and skips the device call)."""
        if not self._quant or not pages:
            return cache
        ids = np.zeros(self.table_pages, np.int32)  # pad with trash page 0
        ids[:len(pages)] = pages
        return self._get_reset_scales()(cache, jnp.asarray(ids))

    # ------------------------------------------------- sliding-window retirement
    def _retire_window_pages(self, cache, free: list, slot_pages: list,
                             active: list):
        """Release every block-table page whose WHOLE span has slid out of
        the attention window. Page j (tokens [j*P, (j+1)*P)) is dead for
        the next decode at position p+1 once (j+1)*P - 1 <= p - window —
        exactly the pages whose every key fails the kernel's
        `kpos > pos - window` validity test, so their partials are already
        the neutral element and redirecting the table entry to the trash
        page is bitwise invisible. Refcount-aware: an aliased prefix page
        is only un-pinned here and returns to the free list at refcount
        zero, never under a sharer or a prefix-index pin. Returns
        (cache, freed_any)."""
        w = self._retire_window
        if not w:
            return cache, False
        P = self.config.page_size
        freed = False
        for i, r in enumerate(active):
            if r is None:
                continue
            p = len(r.prompt) + len(r.out) - 1  # last written position
            n_dead = (p - w + 1) // P
            if n_dead <= 0:
                continue
            row = self._slot_rows[i]
            for j in range(n_dead):
                pg = int(row[j])
                if pg == 0:
                    continue
                row[j] = 0
                cache["pages"] = cache["pages"].at[i, j].set(0)
                slot_pages[i].remove(pg)
                self.page_refs[pg] -= 1
                if self.page_refs[pg] == 0:
                    free.append(pg)
                self.stats["pages_retired"] += 1
                freed = True
        if freed:
            cache = self._sync_refcount(cache)
        return cache, freed

    # ----------------------------------------------- prefix index + refcounts
    def _class_bit(self, bucket: int) -> bool:
        """Flash kv block class of a prompt bucket. The kernel's kv block
        size is min(width, 128) for power-of-two widths, so K/V rows are
        bitwise width-stable WITHIN each class (<= 128: validated directly;
        > 128: every width runs the same 128-wide blocks and the extra
        blocks are masked exact no-ops) but not across the boundary —
        prefix sharing therefore never crosses it."""
        return bucket > 128

    def _prefix_match(self, prompt, bucket: int):
        """Longest indexed block-aligned prefix of `prompt` (same block
        class): -> (n_share, aliased page ids). Capped at (L-1)//P so at
        least one prompt token always remains for the tail prefill (whose
        last-position logits are the request's first output). Every hit
        touches its entry to the recent end of the (ordered) index, so the
        `prefix_cap` LRU eviction retires cold prefixes first."""
        if not self.config.share_prefix:
            return 0, []
        P = self.config.page_size
        pb = np.asarray(prompt, np.int32)
        cls = self._class_bit(bucket)
        ids = []
        for j in range((len(pb) - 1) // P):
            key = (cls, pb[:(j + 1) * P].tobytes())
            page = self._prefix_index.get(key)
            if page is None:
                break
            self._prefix_index.move_to_end(key)  # LRU touch
            ids.append(page)
        return len(ids), ids

    def _register_prefix(self, prompt, bucket: int, row: np.ndarray,
                         free: Optional[list] = None):
        """Index every FULL page the admitted prompt covers (exact token
        bytes as the key — collisions are impossible). Each NEW entry pins
        its page with one refcount, keeping it alive for future sharers
        after the owning slot releases; existing entries (the aliased
        prefix, or a deeper donor chain this admission stopped short of)
        are left untouched. With `ServeConfig.prefix_cap` set, registering
        past the cap retires least-recently-used whole prefixes (the warm
        pool otherwise grows one pinned chain per distinct prompt,
        forever)."""
        if not self.config.share_prefix:
            return
        P = self.config.page_size
        pb = np.asarray(prompt, np.int32)
        cls = self._class_bit(bucket)
        for j in range(len(pb) // P):
            key = (cls, pb[:(j + 1) * P].tobytes())
            if key not in self._prefix_index:
                pg = int(row[j])
                self._prefix_index[key] = pg
                self.page_refs[pg] += 1
        cap = self.config.prefix_cap
        if cap and free is not None:
            while len(self._prefix_index) > cap:
                if not self._evict_chain(free, last=False):
                    break

    def _evict_chain(self, free: list, *, last: bool) -> bool:
        """Drop one prefix entry PLUS every deeper entry extending it — the
        whole cached prefix — un-pinning each page (freed iff the pin was
        its last reference). `last=True` starts from the most recently
        touched end (pool-pressure eviction: with untouched chains indexed
        shallow-to-deep this is the deepest page of the newest chain, the
        historical LIFO order); `last=False` starts from the
        least-recently-used end (the `prefix_cap` age-out). Taking the
        extensions along is what keeps the index walkable: `_prefix_match`
        stops at the first missing depth, so an evicted entry must never
        leave a deeper continuation behind — it would be unreachable yet
        still pinning its page. Counts every dropped entry in the
        `prefix_evictions` stat."""
        if not self._prefix_index:
            return False
        (cls, pb), pg = self._prefix_index.popitem(last=last)
        dropped = [pg]
        for key in [k for k in self._prefix_index
                    if k[0] == cls and k[1].startswith(pb)]:
            dropped.append(self._prefix_index.pop(key))
        for pg in dropped:
            self.page_refs[pg] -= 1
            if self.page_refs[pg] == 0:
                free.append(pg)
        self._prefix_evictions += len(dropped)
        if self.stats:
            self.stats["prefix_evictions"] = self._prefix_evictions
        return True

    def _evict_one(self, free: list) -> bool:
        """Pool-pressure eviction: retire the most recently touched prefix
        chain (see `_evict_chain`). Kept as the single entry point the
        admission and copy-on-write paths loop on until a page frees."""
        return self._evict_chain(free, last=True)

    def _sync_refcount(self, cache):
        """Refresh the device refcount mirror from the host-authoritative
        array (shape/dtype-stable, so jitted steps never retrace)."""
        cache["refcount"] = jnp.asarray(self.page_refs)
        return cache

    def _cow_page(self, cache, free: list, slot_pages: list, slot: int,
                  pidx: int):
        """Copy-on-write one block-table entry of `slot`: duplicate the
        shared physical page onto a fresh one, drop this slot's reference
        to the original, and redirect the table row. Sharers keep the
        original bytes untouched."""
        row = self._slot_rows[slot]
        old = int(row[pidx])
        while not free:
            if not self._evict_one(free):
                raise RuntimeError(
                    "copy-on-write found no free page and nothing evictable")
        new = free.pop()
        cache = self._get_copy_page()(
            cache, jnp.asarray(old, jnp.int32), jnp.asarray(new, jnp.int32))
        self.page_refs[old] -= 1
        self.page_refs[new] = 1
        row[pidx] = new
        slot_pages[slot][slot_pages[slot].index(old)] = new
        cache["pages"] = cache["pages"].at[slot, pidx].set(new)
        self.stats["cow_copies"] += 1
        return self._sync_refcount(cache)

    def _cow_guard(self, cache, free: list, slot_pages: list, slot: int,
                   wpos: int, count: int = 1):
        """Make the pages behind write positions [wpos, wpos + count) of
        `slot` exclusively owned (refcount 1) before a decode writes them.
        The normal flow never trips this — aliased pages cover only
        positions BEFORE the shared boundary and decode writes only
        positions past the prompt — so the guard is the invariant's
        enforcement point, not a hot path."""
        P = self.config.page_size
        row = self._slot_rows[slot]
        for pidx in range(wpos // P, (wpos + count - 1) // P + 1):
            pg = int(row[pidx])
            if pg != 0 and self.page_refs[pg] > 1:
                cache = self._cow_page(cache, free, slot_pages, slot, pidx)
        return cache

    def _paged_init(self, pending: list, done: list):
        """Validate the request set, build the pool cache, and admit into
        every slot — the decode-ready paged state. Split out of the run
        loop so benchmarks can prime a realistic decode state through the
        REAL admission path instead of re-implementing it. Returns
        (cache, nxt, free, slot_pages, active, remaining)."""
        P = self.config.page_size
        for r in pending:
            if len(r.prompt) + r.max_new > self.max_len:
                raise ValueError(
                    f"request {r.uid}: prompt {len(r.prompt)} + budget "
                    f"{r.max_new} exceeds max_len {self.max_len}")
            if len(r.prompt) == 0:
                raise ValueError(f"request {r.uid}: empty prompt")
        with span("repro.serve.pool_init"):
            if self.config.share_prefix and self._pool is not None:
                # warm pool: the previous run() left every slot parked (trash
                # row, pos 0) and its prefix-index pins still hold their pages —
                # reuse the physical cache + free list so this wave's prompts
                # alias pages prefilled by earlier waves. page_refs and
                # _prefix_index carry over; only the work counters reset.
                cache, free = self._pool
                cache = self._sync_refcount(self._commit_cache(cache))
            else:
                cache = self._commit_cache(self.model.init_paged_cache(
                    self.B, self.num_pages, P, self.table_pages))
                free = list(range(1, self.num_pages))  # page 0 = reserved trash
                # fresh allocator state: host-authoritative page refcounts (page
                # usable iff 0 == free, writable iff 1) and the prefix index
                self.page_refs = np.zeros(self.num_pages, np.int32)
                self._prefix_index = OrderedDict()
        slot_pages: list = [[] for _ in range(self.B)]
        active: list = [None] * self.B
        remaining = [0] * self.B
        self._slot_rows = [None] * self.B  # host block-table mirror
        self.stats = {"prompt_tokens": 0, "prefill_tokens": 0,
                      "prefix_hit_tokens": 0, "prefix_hits": 0,
                      "spec_proposed": 0, "spec_accepted": 0,
                      "cow_copies": 0, "pages_retired": 0,
                      "decode_rounds": 0, "slot_rounds": 0, "joins": 0,
                      "prefix_evictions": self._prefix_evictions}
        nxt = jnp.zeros((self.B, 1), jnp.int32)
        cache, nxt = self._admit_idle_slots(pending, done, cache, nxt,
                                            active, remaining, free,
                                            slot_pages)
        return cache, nxt, free, slot_pages, active, remaining

    def _admit_idle_slots(self, pending, done, cache, nxt, active, remaining,
                          free, slot_pages):
        """Offer admission to EVERY idle slot — not just the one that
        triggered it. A slot that found nothing admittable earlier (pool
        exhausted by its peers) must be retried whenever pages free up, or
        it idles for the engine's whole lifetime and concurrency silently
        shrinks."""
        for i in range(self.B):
            if active[i] is None:
                cache, nxt = self._try_admit(pending, done, cache, nxt,
                                             active, remaining, free,
                                             slot_pages, i)
        return cache, nxt

    def _run_paged(self, pending: list, done: list) -> list:
        cache, nxt, free, slot_pages, active, remaining = self._paged_init(
            pending, done)
        while any(r is not None for r in active):
            n_active = sum(r is not None for r in active)
            with span("repro.serve.decode_round", active=n_active):
                for i, r in enumerate(active):
                    if r is not None:  # CoW a still-shared write-target page
                        cache = self._cow_guard(
                            cache, free, slot_pages, i,
                            len(r.prompt) + len(r.out) - 1)
                self.stats["decode_rounds"] += 1
                self.stats["slot_rounds"] += n_active
                logits, cache = self._decode(self.params, cache,
                                             {"tokens": nxt})
                nxt = greedy(logits)
                nxt_np = np.asarray(nxt)
                log_np = (np.asarray(logits)
                          if self.config.trace_logits else None)
                with span("repro.serve.emit"):
                    freed = False
                    for i, r in enumerate(active):
                        if r is None:
                            continue
                        r.out.append(int(nxt_np[i, 0]))
                        if log_np is not None:
                            r.logits.append(log_np[i, 0].copy())
                        remaining[i] -= 1
                        if remaining[i] == 0:
                            r.done = True
                            done.append(r)
                            active[i] = None
                            cache = self._release_slot(cache, free,
                                                       slot_pages, i)
                            freed = True
                    cache, retired = self._retire_window_pages(
                        cache, free, slot_pages, active)
                    if freed or retired:
                        cache, nxt = self._admit_idle_slots(
                            pending, done, cache, nxt, active, remaining,
                            free, slot_pages)
        if pending:
            # cannot happen with the auto-sized pool (B full tables + trash
            # always admit an empty batch) — but a hand-shrunk num_pages
            # could leave requests no slot can ever hold; fail loud
            raise RuntimeError(
                f"{len(pending)} requests unadmittable with "
                f"{len(free)}/{self.num_pages - 1} pages free")
        if self.config.share_prefix:
            self._pool = (cache, free)  # keep pinned prefix pages for waves
        return done

    # ------------------------------------------------------ speculative path
    def _draft(self, r, n: int) -> np.ndarray:
        """Prompt-lookup draft: propose the continuation of the most recent
        EARLIER occurrence of the request's current last token in its own
        context (prompt + generated so far), zero-padded to exactly `n`
        proposals so the verify batch shape is static. A wrong draft costs
        only the rejected rows' compute — acceptance is exact-match greedy,
        so output never depends on draft quality."""
        out = np.zeros((n,), np.int32)
        if n == 0:
            return out
        ctx = np.concatenate([np.asarray(r.prompt, np.int32),
                              np.asarray(r.out, np.int32)])
        hits = np.nonzero(ctx[:-1] == ctx[-1])[0]
        if hits.size:
            cont = ctx[int(hits[-1]) + 1:int(hits[-1]) + 1 + n]
            out[:cont.size] = cont
        return out

    def _run_paged_spec(self, pending: list, done: list) -> list:
        """Speculative multi-token decode loop (spec_k rows per step, one
        slot at a time): verify the current token plus k-1 drafted
        continuations in ONE paged decode call with the rows as the batch
        dimension — all rows share the slot's block table, each carries its
        own position, and `paged_update_decode` writes every row's K/V at a
        distinct (page, offset) BEFORE attention reads it, so the per-row
        causal masks make the single call an exact multi-token decode.

        Acceptance keeps the longest draft prefix matching the verified
        argmaxes (row 0 is the plain decode step, so >= 1 token is always
        emitted and the worst case degenerates to plain decode one slot at
        a time). Rejected rows need no undo beyond POSITION TRUNCATION:
        their writes sit past the slot's committed position, masked out of
        every later read until overwritten. Rows past the slot's remaining
        budget are parked on the trash row (pages 0, pos 0, token 0) so a
        full-size verify batch never writes past the slot's allocation —
        which also keeps the traced shape unique. Tokens and logits are
        bitwise identical to the plain paged loop's."""
        k = self.config.spec_k
        cache, nxt, free, slot_pages, active, remaining = self._paged_init(
            pending, done)
        while any(r is not None for r in active):
            for i in range(self.B):
                r = active[i]
                if r is None:
                    continue
                k_eff = min(k, remaining[i])
                p = len(r.prompt) + len(r.out) - 1  # next write position
                draft = self._draft(r, k - 1)
                d = np.zeros((k, 1), np.int32)
                d[0, 0] = r.out[-1]  # last emitted token = next input
                d[1:k_eff, 0] = draft[:k_eff - 1]
                pos_k = np.zeros(k, np.int32)
                pos_k[:k_eff] = p + np.arange(k_eff)
                pages_k = np.zeros((k, self.table_pages), np.int32)
                pages_k[:k_eff] = self._slot_rows[i]
                cache = self._cow_guard(cache, free, slot_pages, i, p, k_eff)
                self.stats["decode_rounds"] += 1
                self.stats["slot_rounds"] += 1
                sub = {"blocks": cache["blocks"], "tail": cache["tail"],
                       "pos": jnp.asarray(pos_k),
                       "pages": jnp.asarray(pages_k),
                       "refcount": cache["refcount"]}
                logits, out_sub = self._decode(self.params, sub,
                                               {"tokens": jnp.asarray(d)})
                # the donated sub-cache shared the pool arrays: re-anchor the
                # engine cache on the returned ones before anything else
                # touches it (pages/pos stayed outside the donation)
                cache["blocks"] = out_sub["blocks"]
                cache["tail"] = out_sub["tail"]
                cache["refcount"] = out_sub["refcount"]
                g = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1))
                a = 0  # accepted proposals: longest exact-match draft prefix
                while a + 1 < k_eff and d[a + 1, 0] == g[a]:
                    a += 1
                self.stats["spec_proposed"] += k_eff - 1
                self.stats["spec_accepted"] += a
                r.out.extend(int(g[t]) for t in range(a + 1))
                if self.config.trace_logits:
                    log_np = np.asarray(logits)
                    for t in range(a + 1):
                        r.logits.append(log_np[t, 0].copy())
                remaining[i] -= a + 1
                # rollback IS this: rows past `a` stay masked behind pos and
                # are overwritten by the next step's writes
                cache["pos"] = cache["pos"].at[i].set(p + a + 1)
                cache, retired = self._retire_window_pages(
                    cache, free, slot_pages, active)
                if remaining[i] == 0:
                    r.done = True
                    done.append(r)
                    active[i] = None
                    cache = self._release_slot(cache, free, slot_pages, i)
                    retired = True
                if retired:
                    cache, nxt = self._admit_idle_slots(
                        pending, done, cache, nxt, active, remaining, free,
                        slot_pages)
        if pending:
            raise RuntimeError(
                f"{len(pending)} requests unadmittable with "
                f"{len(free)}/{self.num_pages - 1} pages free")
        if self.config.share_prefix:
            self._pool = (cache, free)  # keep pinned prefix pages for waves
        return done

    def _release_slot(self, cache, free: list, slot_pages: list, slot: int):
        """Drop a finished slot's references and park the slot (all-trash
        table row, pos 0) so its junk decode writes land in the reserved
        trash page. A page returns to the free list only at refcount zero —
        prefix-index pins and other slots' aliases keep shared pages
        resident past this slot's lifetime."""
        for pg in slot_pages[slot]:
            self.page_refs[pg] -= 1
            if self.page_refs[pg] == 0:
                free.append(pg)
        slot_pages[slot] = []
        self._slot_rows[slot] = None
        cache["pages"] = cache["pages"].at[slot].set(0)
        cache["pos"] = cache["pos"].at[slot].set(0)
        return self._sync_refcount(cache)

    def _try_admit(self, pending: list, done: list, cache, nxt, active,
                   remaining, free: list, slot_pages: list, slot: int):
        """Admit the first pending request whose FRESH page need (total
        pages minus prefix-index aliases) fits the free list into `slot`,
        evicting LIFO index entries when nothing fits outright.

        Solo admission prefills the prompt at its power-of-two bucket width
        (right-padded — batch-independent by construction) and scatters the
        dense K/V into the allocated pages. A prefix-index hit instead
        ALIASES the matched pages (+1 refcount each) and prefills ONLY the
        unshared tail at the solo run's kv bucket (`Model.prefill_tail` —
        logits bitwise the solo prefill's), committing the tail K/V past
        the shared boundary. Either way the prompt's full pages are then
        registered in the prefix index for future sharers, and the first
        generated token (the prefill's greedy pick at the last real
        position) is recorded. Returns updated (cache, nxt)."""
        P = self.config.page_size
        while True:
            if not pending:  # nothing to admit — don't evict the index for it
                return cache, nxt
            cand = None
            while cand is None:
                for r in pending:
                    need = -(-(len(r.prompt) + r.max_new) // P)
                    n_share, aliased = self._prefix_match(
                        r.prompt, self._bucket(len(r.prompt)))
                    if need - n_share <= len(free):
                        cand = (r, need, n_share, aliased)
                        break
                else:
                    # eviction shortens donor chains, so re-scan after each
                    # dropped entry instead of precomputing an evictable total
                    if not self._evict_one(free):
                        return cache, nxt
            j, need, n_share, aliased = cand
            pending.remove(j)
            L = len(j.prompt)
            Ls = n_share * P  # prompt positions aliased from the index
            width, prefill_w = self._bucket(L), self._bucket(L - Ls)
            with span("repro.serve.admit", req=j.uid, width=prefill_w):
                pages = aliased + [free.pop() for _ in range(need - n_share)]
                for pg in pages:
                    self.page_refs[pg] += 1
                slot_pages[slot] = pages
                row = np.zeros(self.table_pages, np.int32)
                row[:need] = pages
                self._slot_rows[slot] = row
                # int8 pools: zero the FRESH pages' scale rows before any
                # write so the recycled pages' stale running-max scales never
                # alter this request's quantization (aliased prefix pages keep
                # theirs)
                cache = self._reset_page_scales(cache, pages[n_share:])
                j.entry_width = width
                self.stats["prompt_tokens"] += L
                # admitted after decoding began while another slot is still
                # decoding: a mid-stream join
                self.stats["joins"] += int(
                    self.stats["decode_rounds"] > 0
                    and any(a is not None for a in active))
                self.prefill_widths.add(prefill_w)
                self.stats["prefill_tokens"] += prefill_w
                toks = np.zeros((1, prefill_w), np.int32)
                toks[0, :L - Ls] = j.prompt[Ls:]  # RIGHT-pad past the mask
                if n_share:
                    self.stats["prefix_hit_tokens"] += Ls
                    self.stats["prefix_hits"] += 1
                    with span("repro.serve.prefill"):
                        logits, dense = self._get_tail_prefill(
                            prefill_w, n_share, width)(
                            self.params, jnp.asarray(toks), cache,
                            jnp.asarray(row),
                            jnp.asarray([L - Ls - 1], jnp.int32))
                else:
                    with span("repro.serve.prefill"):
                        logits, dense = self._get_paged_prefill(width)(
                            self.params, jnp.asarray(toks),
                            jnp.asarray([L - 1], jnp.int32))
                with span("repro.serve.commit"):
                    if n_share:
                        cache = self._get_tail_commit(prefill_w)(
                            cache, dense, jnp.asarray(row),
                            jnp.asarray(Ls, jnp.int32),
                            jnp.asarray(L, jnp.int32))
                    else:
                        cache = self._get_paged_commit(width)(
                            cache, dense, jnp.asarray(row),
                            jnp.asarray(L, jnp.int32))
                    cache = self._commit_cache(cache)
                    self._register_prefix(j.prompt, width, row, free)
                    cache["pages"] = cache["pages"].at[slot].set(
                        jnp.asarray(row))
                    cache["pos"] = cache["pos"].at[slot].set(L)
                    cache = self._sync_refcount(cache)
                first = greedy(logits)
                j.out.append(int(np.asarray(first)[0, 0]))
                if self.config.trace_logits:
                    j.logits.append(np.asarray(logits)[0, 0].copy())
                if j.max_new == 1:  # drained on its prefill; slot frees again
                    j.done = True
                    done.append(j)
                    cache = self._release_slot(cache, free, slot_pages, slot)
                    continue
                nxt = nxt.at[slot].set(first[0])
                active[slot] = j
                remaining[slot] = j.max_new - 1
                return cache, nxt

    # -------------------------------------------------------------- ring path
    def _try_join(self, pending: list, done: list, cache, nxt, active,
                  remaining, slot):
        """Fill freed `slot` from `pending` mid-stream: prefill the joining
        prompt left-padded to the batch's current position, splice its cache
        into the slot, and record its first generated token (the join
        prefill's greedy pick — the analogue of the wave prefill's `nxt`).
        Returns updated (cache, nxt) — unchanged when nothing fits (prompt
        longer than the elapsed positions, or decode budget past cache
        capacity).

        Cost note: the join prefill runs at the full batch width and at
        token length == the current position, so each distinct join position
        traces a new prefill shape — inherent to the ring cache's shared
        counter; the paged path is what removes the recompile and the
        wasted B-1 rows."""
        while True:
            cur = int(np.asarray(cache["pos"]))
            j = next((r for r in pending
                      if len(r.prompt) <= cur and cur + r.max_new <= self.max_len),
                     None)
            if j is None:
                return cache, nxt
            pending.remove(j)
            toks = np.zeros((self.B, cur), np.int32)
            toks[slot, cur - len(j.prompt):] = j.prompt
            j.entry_width = cur
            self.prefill_widths.add(cur)
            j_logits, j_cache = self._prefill(self.params,
                                              {"tokens": jnp.asarray(toks)})
            cache = self._commit_cache(_splice_slot(cache, j_cache, slot))
            first = greedy(j_logits)
            j.out.append(int(np.asarray(first)[slot, 0]))
            if self.config.trace_logits:
                j.logits.append(np.asarray(j_logits)[slot, -1].copy())
            if j.max_new == 1:  # drained on its own prefill; slot frees again
                j.done = True
                done.append(j)
                continue
            nxt = nxt.at[slot].set(first[slot])
            active[slot] = j
            remaining[slot] = j.max_new - 1
            return cache, nxt

    def _run_ring(self, pending: list, done: list) -> list:
        while pending:
            wave = pending[: self.B]
            pending = pending[self.B:]
            S = max(len(r.prompt) for r in wave)
            toks = np.zeros((self.B, S), np.int32)
            for i, r in enumerate(wave):
                toks[i, S - len(r.prompt):] = r.prompt  # left-pad
                r.entry_width = S
            self.prefill_widths.add(S)
            logits, cache = self._prefill(self.params, {"tokens": jnp.asarray(toks)})
            cache = self._commit_cache(cache)
            nxt = greedy(logits)
            if self.config.trace_logits:
                log_np = np.asarray(logits)
                for i, r in enumerate(wave):
                    r.logits.append(log_np[i, -1].copy())
            active: list = list(wave) + [None] * (self.B - len(wave))
            remaining = [r.max_new if r else 0 for r in active]
            while True:
                nxt_np = np.asarray(nxt)
                for i, r in enumerate(active):
                    if r is None or remaining[i] == 0:
                        continue
                    r.out.append(int(nxt_np[i, 0]))
                    remaining[i] -= 1
                    if remaining[i] == 0:
                        r.done = True
                        done.append(r)
                        active[i] = None
                        cache, nxt = self._try_join(
                            pending, done, cache, nxt, active, remaining, i)
                if not any(remaining):
                    break
                logits, cache = self._decode(self.params, cache, {"tokens": nxt})
                nxt = greedy(logits)
                if self.config.trace_logits:
                    log_np = np.asarray(logits)
                    for i, r in enumerate(active):
                        if r is not None and remaining[i] > 0:
                            r.logits.append(log_np[i, 0].copy())
        return done
