"""End-to-end fault-tolerant training driver.

Trains any assigned arch (reduced or full config) on synthetic token data
with the CHEF Eq. (1) weighting, on the locally available device mesh, with:
  * deterministic sharded data loading (restart-identical streams)
  * gradient accumulation + optional int8 error-feedback compression
  * atomic async checkpointing + automatic restore on restart
  * heartbeat + straggler monitoring
  * optional simulated failure (--kill_at) to exercise the restart path

Example (the (b) deliverable's end-to-end driver — ~100M model, few hundred
steps on CPU):

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --reduce 100m \
      --steps 200 --batch 8 --seq 256
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager
from repro.configs import get_config, reduced
from repro.data.loader import ShardedLoader
from repro.dist.fault import Heartbeat, StragglerMonitor, retry_step
from repro.launch.mesh import host_mesh
from repro.models import Model
from repro.optim import adamw, warmup_cosine
from repro.training.state import TrainState, init_train_state
from repro.training.steps import make_train_step
from repro.utils import get_logger

log = get_logger("repro.train")


def reduce_to_100m(cfg):
    """A ~100M-param member of the same family (example-scale driver)."""
    kw: dict = dict(
        n_layers=max(4, 2 * len(cfg.block_pattern)),
        d_model=512,
        n_heads=8,
        n_kv_heads=min(cfg.n_kv_heads, 4) if cfg.n_kv_heads else 0,
        head_dim=64,
        d_ff=2048,
        vocab_size=32_000,
        sliding_window=min(cfg.sliding_window, 256) if cfg.sliding_window else 0,
        remat=False,
    )
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(cfg.moe, n_experts=8, top_k=2, d_ff=512)
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(cfg.ssm, state_dim=64, chunk_size=64)
    if cfg.rglru is not None:
        kw["rglru"] = dataclasses.replace(cfg.rglru, lru_width=512)
    if cfg.is_encoder_decoder:
        kw["n_encoder_layers"] = 2
        kw["encoder_seq"] = 64
    return dataclasses.replace(cfg, name=cfg.name + "-100m", **kw)


def synth_batch(cfg, indices: np.ndarray, seq: int, gamma: float = 0.8):
    """Deterministic synthetic LM batch keyed by sample indices (stands in
    for a tokenized corpus; weights follow CHEF Eq. (1): a fraction of
    sequences carries probabilistic provenance and weight gamma)."""
    rng = np.random.default_rng(indices.sum() % (2**31))
    B = len(indices)
    toks = rng.integers(0, cfg.vocab_size, (B, seq + 1), dtype=np.int64)
    weights = np.where(indices % 4 == 0, 1.0, gamma).astype(np.float32)
    batch = {
        "tokens": jnp.asarray(toks[:, :-1]),
        "targets": jnp.asarray(toks[:, 1:]),
        "weights": jnp.asarray(weights),
    }
    if cfg.is_encoder_decoder:
        batch["enc_frames"] = jnp.asarray(
            rng.standard_normal((B, cfg.encoder_seq, cfg.d_model), dtype=np.float32)
        )
    if cfg.rope_kind == "mrope":
        batch["pos3"] = jnp.broadcast_to(np.arange(seq)[None, None, :], (B, 3, seq))
    return batch


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--reduce", default="smoke", choices=["smoke", "100m", "none"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt_dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt_every", type=int, default=25)
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--kill_at", type=int, default=0, help="simulate failure at step N")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduce == "smoke":
        cfg = reduced(cfg)
    elif args.reduce == "100m":
        cfg = reduce_to_100m(cfg)
    mesh = host_mesh()
    model = Model(cfg, param_dtype=jnp.float32, mesh=mesh)
    log.info("arch=%s params=%.1fM devices=%d", cfg.name, cfg.param_count() / 1e6,
             mesh.devices.size)

    opt = adamw(warmup_cosine(args.lr, 10, args.steps), weight_decay=0.01, grad_clip=1.0)
    train_step = jax.jit(
        make_train_step(model, opt, accum=args.accum, mesh=mesh, compress=args.compress),
        donate_argnums=(0,),
    )
    step_fn = retry_step(train_step)

    ckpt = CheckpointManager(Path(args.ckpt_dir) / cfg.name, keep=2)
    hb = Heartbeat(Path(args.ckpt_dir) / cfg.name / "heartbeat.json")
    strag = StragglerMonitor()

    params = model.init(jax.random.key(args.seed))
    state = init_train_state(params, opt)
    start_step = 0
    try:
        state, start_step = ckpt.restore_latest(state)
        log.info("restored checkpoint at step %d", start_step)
    except FileNotFoundError:
        pass

    loader = ShardedLoader(
        n=1_000_000, global_batch=args.batch, seed=args.seed,
        make_batch=lambda idx: synth_batch(cfg, idx, args.seq),
    )
    losses = []
    t_start = time.time()
    for step, batch in loader.iterate(start_step):
        if step >= args.steps:
            break
        if args.kill_at and step == args.kill_at:
            raise SystemExit(f"simulated failure at step {step}")
        t0 = time.time()
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        strag.record(step, time.time() - t0)
        hb.beat(step)
        if (step + 1) % args.ckpt_every == 0 or step + 1 == args.steps:
            ckpt.save(step + 1, state, blocking=False)
        if step % 10 == 0:
            log.info("step %d loss %.4f (%.2fs)", step, loss, time.time() - t0)
    ckpt.wait()
    out = {
        "final_loss": losses[-1] if losses else float("nan"),
        "first_loss": losses[0] if losses else float("nan"),
        "steps": len(losses),
        "stragglers": len(strag.flagged),
        "wall_s": time.time() - t_start,
    }
    log.info("done: %s", out)
    return out


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
