"""Batched serving driver: loads (or inits) a model, runs a wave of batched
greedy-decode requests through the Backend-dispatched ServeEngine.

  PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --requests 8 \
      --backend pallas --cache paged

`--backend` selects the attention implementation for prefill AND decode
(`reference` | `pallas` | `pallas_sharded` — same flag and semantics as the
benchmark CLIs); outputs are bit-identical across the three, so the flag is
purely a performance/scale choice. `pallas_sharded` additionally shards the
KV cache (ring leaves and paged page pools alike) head-wise over the mesh
model axis.

`--reduce smoke` (default) serves the arch's smoke-size reduced config in
f32; `--reduce none` serves its full published config in bf16 (the paged
pools then need pages of 16 tokens on TPU; 32 with `--kv_dtype int8`).

`--cache` selects the cache discipline: `paged` (block-table paged cache
with per-slot decode positions — batching-invariant outputs), `ring` (the
seed engine's shared-counter ring, kept as the differential oracle), or
`auto` (paged where the arch supports it). `--page_size` sizes the paged
pool's pages.

Paged-mode extras (both leave outputs bitwise unchanged — see the engine
module docstring): `--share_prefix` / `--no-share_prefix` toggles prefix
sharing (on by default; `--prefix_len N` gives every request the same
N-token prompt prefix so the sharing actually has something to hit), and
`--spec_k K` turns on speculative decode with K rows per verify step.

Long-context knobs (serving/README.md): `--prefill_chunk C` routes prompt
buckets wider than C through the chunked prefill (O(S*C) peak score memory,
bitwise-identical outputs), `--prefix_cap N` bounds the warm prefix index to
N entries with LRU whole-prefix eviction, and `--attn window:<W>` overrides
the arch's attention pattern with a W-token sliding window (`--attn full`
removes one) — routing prefill through the banded local-attention kernel.

Memory knobs: `--kv_dtype int8` holds the paged page pools as int8 codes
plus one f32 scale per (page, kv head) (~1.9x KV bytes per slot over bf16;
prefix sharing and spec decode are forced off — see the engine docstring),
and `--retire_pages` / `--no-retire_pages` toggles sliding-window page
retirement (on by default; bitwise-neutral, frees out-of-window pages so a
shrunk pool admits more concurrent slots).
"""
from __future__ import annotations

import argparse
import time
from dataclasses import replace as dc_replace

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.core.backend import get_backend
from repro.models import Model
from repro.serving.engine import Request, ServeConfig, ServeEngine
from repro.utils import get_logger

log = get_logger("repro.serve")


def main(argv=None) -> dict:
    """CLI entry; returns a summary dict (also used by tests/examples)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduce", default="smoke", choices=["smoke", "none"],
                    help="smoke = reduced config in f32; none = the full "
                         "published config in bf16")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt_len", type=int, default=32)
    ap.add_argument("--max_new", type=int, default=16)
    ap.add_argument("--backend", default="reference",
                    help="reference | pallas | pallas_sharded")
    ap.add_argument("--cache", default="auto",
                    help="auto | paged | ring (see repro.serving.ServeConfig)")
    ap.add_argument("--page_size", type=int, default=16,
                    help="tokens per physical page (paged cache)")
    ap.add_argument("--share_prefix", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="alias block-aligned shared prompt prefixes (paged)")
    ap.add_argument("--prefix_len", type=int, default=0,
                    help="common prompt prefix length across requests "
                         "(0 = fully independent prompts)")
    ap.add_argument("--spec_k", type=int, default=0,
                    help="speculative decode rows per step (<=1 = off)")
    ap.add_argument("--prefill_chunk", type=int, default=0,
                    help="chunked-prefill KV span in tokens (0 = full-width "
                         "flash prefill); bitwise-identical outputs")
    ap.add_argument("--prefix_cap", type=int, default=0,
                    help="max warm prefix-index entries, LRU-evicted past "
                         "the cap (0 = unbounded)")
    ap.add_argument("--attn", default="",
                    help="attention-pattern override: 'window:<W>' forces a "
                         "W-token sliding window, 'full' removes the arch's "
                         "window; empty keeps the arch pattern")
    ap.add_argument("--kv_dtype", default="", choices=["", "int8", "bf16"],
                    help="KV cache dtype override: int8 = quantized page "
                         "pools with per-(page, head) scales; empty = the "
                         "model's param dtype")
    ap.add_argument("--retire_pages", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="free block-table pages that slid fully out of the "
                         "attention window (paged + windowed archs only)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduce == "smoke":
        cfg = reduced(cfg)
    if args.attn:
        if args.attn == "full":
            cfg = dc_replace(cfg, attn_kind="full", sliding_window=0)
        elif args.attn.startswith("window:"):
            cfg = dc_replace(cfg, attn_kind="sliding",
                             sliding_window=int(args.attn.split(":", 1)[1]))
        else:
            raise SystemExit(
                f"unknown --attn {args.attn!r} (want 'window:<W>' or 'full')")
    import jax.numpy as jnp
    model = Model(cfg, param_dtype=(jnp.float32 if args.reduce == "smoke"
                                    else jnp.bfloat16))
    model.kv_dtype = {"int8": jnp.int8, "bf16": jnp.bfloat16,
                      "": None}[args.kv_dtype]
    params = model.init(jax.random.key(args.seed))
    engine = ServeEngine(
        model, params, backend=get_backend(args.backend),
        config=ServeConfig(batch_size=args.batch,
                           max_len=args.prompt_len + args.max_new,
                           cache=args.cache, page_size=args.page_size,
                           share_prefix=args.share_prefix,
                           spec_k=args.spec_k,
                           prefill_chunk=args.prefill_chunk,
                           prefix_cap=args.prefix_cap,
                           retire_pages=args.retire_pages))

    rng = np.random.default_rng(args.seed)
    pl = min(args.prefix_len, args.prompt_len)
    shared = rng.integers(0, cfg.vocab_size, pl)
    reqs = [
        Request(uid=i, prompt=np.concatenate([
            shared, rng.integers(0, cfg.vocab_size, args.prompt_len - pl),
        ]).astype(np.int64), max_new=args.max_new)
        for i in range(args.requests)
    ]
    t0 = time.time()
    done = engine.run(reqs)
    dt = time.time() - t0
    n_tok = sum(len(r.out) for r in done)
    stats = getattr(engine, "stats", {}) or {}
    hit_rate = (stats.get("prefix_hit_tokens", 0)
                / max(stats.get("prompt_tokens", 0), 1))
    log.info("served %d requests, %d tokens in %.2fs "
             "(%.1f tok/s, backend=%s, cache=%s, prefix_hit_rate=%.2f, "
             "prefill_chunk=%d, window=%d)",
             len(done), n_tok, dt, n_tok / dt, args.backend,
             engine.cache_mode, hit_rate, args.prefill_chunk,
             cfg.sliding_window)
    return {"requests": len(done), "tokens": n_tok, "wall_s": dt,
            "backend": args.backend, "cache": engine.cache_mode,
            "prefix_hit_rate": hit_rate, "stats": dict(stats),
            "prefill_chunk": args.prefill_chunk,
            "window": cfg.sliding_window}


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
