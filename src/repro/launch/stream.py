"""Streaming cleaning driver: run online CHEF over a synthetic weak-label
stream, cleaning between window arrivals.

  PYTHONPATH=src python -m repro.launch.stream --windows 4 --window_size 100 \
      --backend pallas --rounds_per_window 1

`--backend` selects the compute implementation end to end (`reference` |
`pallas` | `pallas_sharded` — same flag and semantics as the other launch
CLIs); streaming results are bit-identical across the three. `--cold`
switches from warm-start absorption (DeltaGrad-L replay per window, the
streaming design) to the from-scratch retrain oracle — useful for
parity/validation runs. `--ckpt_dir` checkpoints after every ingest and
round so a killed run resumes bit-for-bit via `--resume`.

`--model_annotator` swaps the simulated human vote for a `ServeEngine`
annotation round (a reduced `--arch` model served with logit tracing; see
repro/stream/annotator.py) — the model-in-the-loop configuration.
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs.chef_lr import ChefConfig
from repro.stream import StreamingCleaningSession, SyntheticStream
from repro.utils import get_logger

log = get_logger("repro.stream")


def main(argv=None) -> dict:
    """CLI entry; returns a summary dict (also used by tests/examples)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--windows", type=int, default=4)
    ap.add_argument("--window_size", type=int, default=100)
    ap.add_argument("--feature_dim", type=int, default=24)
    ap.add_argument("--backend", default="reference",
                    help="reference | pallas | pallas_sharded")
    ap.add_argument("--budget", type=int, default=40)
    ap.add_argument("--round_size", type=int, default=10)
    ap.add_argument("--rounds_per_window", type=int, default=1)
    ap.add_argument("--selector", default="increm",
                    help="full | increm | increm_tight")
    ap.add_argument("--cold", action="store_true",
                    help="warm_start=False: the from-scratch retrain oracle")
    ap.add_argument("--pipelined", action="store_true")
    ap.add_argument("--ckpt_dir", default=None)
    ap.add_argument("--resume", action="store_true",
                    help="restore from --ckpt_dir's latest checkpoint")
    ap.add_argument("--model_annotator", action="store_true",
                    help="annotate through a ServeEngine instead of the "
                         "simulated human vote")
    ap.add_argument("--arch", default="olmo-1b",
                    help="model config for --model_annotator (reduced)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    source = SyntheticStream(jax.random.key(args.seed),
                             window_size=args.window_size,
                             n_windows=args.windows,
                             feature_dim=args.feature_dim)
    cfg = ChefConfig(budget=args.budget, round_size=args.round_size,
                     n_epochs=8, batch_size=min(400, source.total_rows),
                     lr=0.05, l2=0.05, backend=args.backend, seed=args.seed)

    annotator = None
    if args.model_annotator:
        from repro.configs import get_config, reduced
        from repro.models import Model
        from repro.serving.engine import ServeConfig, ServeEngine
        from repro.stream import ModelAnnotator

        mcfg = reduced(get_config(args.arch))
        model = Model(mcfg)
        params = model.init(jax.random.key(args.seed + 1))
        engine = ServeEngine(model, params, config=ServeConfig(
            batch_size=4, max_len=args.feature_dim + 16, trace_logits=True))
        annotator = ModelAnnotator(engine)

    kw = dict(backend=args.backend, warm_start=not args.cold,
              selector=args.selector,
              constructor="deltagrad",
              pipelined=args.pipelined, annotator=annotator,
              ckpt_dir=args.ckpt_dir)
    if args.resume:
        if args.ckpt_dir is None:
            ap.error("--resume requires --ckpt_dir")
        session = StreamingCleaningSession.restore(
            args.ckpt_dir, source, cfg,
            **{k: v for k, v in kw.items() if k != "ckpt_dir"})
    else:
        session = StreamingCleaningSession(source, cfg, **kw)

    t0 = time.time()
    result = session.run(rounds_per_window=args.rounds_per_window)
    dt = time.time() - t0
    log.info("streamed %d windows (%d rows), %d rounds in %.2fs "
             "(f1_val=%.4f f1_test=%.4f, warm_start=%s, backend=%s)",
             session.windows_ingested, session.store.n, len(result.history),
             dt, result.f1_val_final, result.f1_test_final,
             not args.cold, args.backend)
    return {"windows": session.windows_ingested, "rows": session.store.n,
            "rounds": len(result.history), "wall_s": dt,
            "f1_val": result.f1_val_final, "f1_test": result.f1_test_final,
            "warm_start": not args.cold, "backend": args.backend}


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
