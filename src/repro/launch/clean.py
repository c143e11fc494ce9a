"""Fleet cleaning driver: run N cleaning jobs under the elastic supervisor,
optionally with scripted fault injection.

  PYTHONPATH=src python -m repro.launch.clean --jobs 2 --budget 30 \
      --backend pallas --chaos "kill:0@1;straggle:1@2x0.3"

`--backend` selects the compute implementation end to end (`reference` |
`pallas` | `pallas_sharded` — same flag and semantics as the other launch
CLIs). `--chaos` takes either a `FaultSchedule.parse` spec (see
repro/dist/chaos.py) or `seed:<N>` to draw a seeded random schedule — the
same seed reproduces the same schedule, eviction trace, and (bitwise) the
same results. `--verify` reruns every job without the supervisor and asserts
the fleet's recovered results match the plain runs exactly.
"""
from __future__ import annotations

import argparse
import tempfile
import time

import jax
import numpy as np

from repro.cleaning.supervisor import FleetJob, FleetSupervisor
from repro.configs.chef_lr import ChefConfig
from repro.data.synth import make_dataset
from repro.dist.chaos import FaultSchedule
from repro.utils import get_logger

log = get_logger("repro.clean")


def parse_chaos(text: str, *, workers: int, rounds: int) -> FaultSchedule:
    """`--chaos` argument -> FaultSchedule: either `seed:<N>` (seeded random
    schedule over the fleet) or a `FaultSchedule.parse` spec string."""
    if text.startswith("seed:"):
        return FaultSchedule.random(int(text[5:]), workers=workers,
                                    rounds=rounds)
    return FaultSchedule.parse(text)


def main(argv=None) -> dict:
    """CLI entry; returns a summary dict (also used by tests/examples)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=2,
                    help="fleet size (one cleaning session per replica group)")
    ap.add_argument("--n_train", type=int, default=300)
    ap.add_argument("--feature_dim", type=int, default=24)
    ap.add_argument("--budget", type=int, default=30)
    ap.add_argument("--round_size", type=int, default=10)
    ap.add_argument("--backend", default="reference",
                    help="reference | pallas | pallas_sharded")
    ap.add_argument("--selector", default="increm_tight",
                    help="full | increm | increm_tight")
    ap.add_argument("--constructor", default="deltagrad",
                    help="deltagrad | retrain")
    ap.add_argument("--chaos", default=None,
                    help="fault spec ('kill:0@1;straggle:1@2x0.5') or "
                         "'seed:<N>' for a seeded random schedule")
    ap.add_argument("--workdir", default=None,
                    help="heartbeats + checkpoints root (default: temp dir)")
    ap.add_argument("--stale_after", type=float, default=30.0,
                    help="seconds without a beat before a worker is evicted")
    ap.add_argument("--retries", type=int, default=2,
                    help="per-round transient-failure retries")
    ap.add_argument("--verify", action="store_true",
                    help="rerun each job unsupervised and assert the fleet's "
                         "results match bitwise")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = ChefConfig(budget=args.budget, round_size=args.round_size,
                     n_epochs=6, batch_size=min(100, args.n_train),
                     lr=0.05, l2=0.05, backend=args.backend, seed=args.seed)
    rounds = max(args.budget // max(args.round_size, 1), 1)
    jobs = [
        FleetJob(f"job{i}",
                 make_dataset(jax.random.key(args.seed + 7 + i),
                              n_train=args.n_train, n_val=64, n_test=64,
                              feature_dim=args.feature_dim),
                 cfg, selector=args.selector, constructor=args.constructor)
        for i in range(args.jobs)
    ]
    chaos = (parse_chaos(args.chaos, workers=args.jobs, rounds=rounds)
             if args.chaos else None)
    workdir = args.workdir or tempfile.mkdtemp(prefix="chef-fleet-")

    sup = FleetSupervisor(workdir, backend=args.backend, chaos=chaos,
                          stale_after_s=args.stale_after, retries=args.retries)
    t0 = time.time()
    results = sup.run(jobs)
    dt = time.time() - t0

    verified = None
    if args.verify:
        from repro.cleaning.scheduler import make_scheduler
        from repro.cleaning.service import prepare_session
        from repro.core.backend import get_backend

        backend = get_backend(args.backend, chunk_rows=cfg.score_chunk)
        for job in jobs:
            session = prepare_session(job.ds, job.cfg, backend=backend,
                                      selector=job.selector,
                                      constructor=job.constructor)
            plain = make_scheduler(session, method=job.method,
                                   selector=job.selector,
                                   constructor=job.constructor).run()
            got = results[job.name]
            np.testing.assert_array_equal(np.asarray(got.dataset.cleaned),
                                          np.asarray(plain.dataset.cleaned))
            np.testing.assert_array_equal(np.asarray(got.w),
                                          np.asarray(plain.w))
        verified = True
        log.info("verify: %d job(s) bitwise identical to unsupervised runs",
                 len(jobs))

    for name, res in results.items():
        log.info("%s: rounds=%d f1_val=%.4f f1_test=%.4f", name,
                 len(res.history), res.f1_val_final, res.f1_test_final)
    injected = list(sup.injector.trace) if sup.injector is not None else []
    log.info("fleet of %d done in %.2fs (backend=%s, evictions=%d, "
             "injected=%d, restore_s=%.2f)", len(jobs), dt, args.backend,
             sum(e[0] == "evict" for e in sup.trace), len(injected),
             sup.restore_s)
    return {
        "jobs": {n: {"rounds": len(r.history), "f1_val": r.f1_val_final,
                     "f1_test": r.f1_test_final} for n, r in results.items()},
        "wall_s": dt, "backend": args.backend,
        "chaos": chaos.spec() if chaos else None,
        "injected": injected, "trace": list(sup.trace),
        "restore_s": sup.restore_s, "verified": verified,
    }


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
