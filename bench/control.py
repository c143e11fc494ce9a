#!/usr/bin/env python3
"""Readings that set the limits of the check: the program's on sound runs,
and the control's, the reference in a lower precision put in the
program's place.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--seconds 5]

For each seed, in one process: the cell's driver sets up, runs a window of
`--seconds`, and its check compares what the window produced with the
reference (the program's reading); then the control is compared with the
reference in the same way (the control's reading).

  cleaning  the control is the reference chain at `high` precision (three
            bf16 passes per product) choosing its own rows;
  serving   the control is the reference with e4m3 weights, read at every
            position of the prompts and tokens the program served.

One JSON line per seed on standard output. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def clean_control(driver) -> list:
    """The cleaning comparison with the `high` reference in the program's
    place, over the rounds the window reached."""
    import numpy as np

    from bench import ref_chef
    from bench.drivers import clean_rounds

    n_k = max(r["k"] for r in driver.rounds) + 1
    hp = driver.hyper()
    ctl = list(ref_chef.session(driver.data, hp._replace(precision="high"), n_k))
    cleaned = np.zeros(driver.data["X"].shape[0], bool)
    by_k = {}
    for k, c in enumerate(ctl):
        idx = np.asarray(c.idx)
        onehot = np.eye(driver.cfg["n_classes"])[np.asarray(c.labels)]
        by_k[k] = [{"eligible": ~cleaned.copy(), "idx": idx,
                    "priority": np.asarray(c.priority), "labels": onehot,
                    "w": np.asarray(c.w)}]
        cleaned[idx] = True
    # the reference cleans the control's picks, as it does the program's
    refs = list(ref_chef.session(driver.data, hp, n_k,
                                 chosen=lambda k: by_k[k][0]["idx"]))
    return clean_rounds.compare(by_k, refs, driver.traffic["check"])


def serve_control(driver) -> list:
    """Widest gap, over the checked requests' served positions, of the token
    the e4m3 reference puts first."""
    import numpy as np

    from bench import serving

    t = driver.traffic["check"]
    picked = serving.sample_for_check(driver.done, driver.seed,
                                      t["min_tokens"], t["max_requests"])
    g = serving.reference_gaps(driver.params, driver.cfg, picked, t["pad_to"],
                               dtype="float8_e4m3fn")
    return [("token_gap", float(np.max(g)), None)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.run import driver_class, load_cell, start_jax

    _, cell, cfg, traffic = load_cell(args.workload)
    start_jax()
    Driver = driver_class(traffic)
    for seed in args.seeds:
        d = Driver(cfg, traffic, seed)
        d.seconds = args.seconds
        d.setup()
        d.window(args.seconds)
        d.release()
        line = {"seed": seed, "program": {n: v for n, v, _ in d.check()}}
        if not args.no_control:
            ctl = clean_control(d) if traffic["generator"] == "clean_rounds" \
                else serve_control(d)
            line["control"] = {n: v for n, v, _ in ctl}
        print(json.dumps(line), flush=True)
        del d
    return 0


if __name__ == "__main__":
    sys.exit(main())
