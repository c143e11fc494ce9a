#!/usr/bin/env python3
"""Sweep an open-loop serving cell's arrival rate once, to find its knee:
the highest rate the engine still turns into served tokens.

    python3 bench/sweep.py --workload serve-olmo1b-chat --seeds 7 8 \
        --seconds 50 --rates 0.5 1 1.5 2 3

One process and one set-up; then, for each seed and rate in turn, one
window of the cell's mix at that rate. What was due and not started when a
window closed is not served after it. Per window, one JSON line: tokens
offered (the replies of the requests due inside it) and served (stamped
inside it) per second, the requests and tokens still waiting for a call at
the close, the mean wait for a call, and the cell's own ITL and TTFT p95.

The knee is the highest rate up to which, on every seed, each step up in
rate raised the served tokens per second by at least half of what it
raised the offered: past it, extra load becomes backlog and not tokens.
Where even the first step fails, the knee lies below the lowest rate, and
the saturated capacity stands in for it: the median served tokens per
second past the knee over the mean reply. The last line gives the knee,
the capacity and 4/5 of the knee (or capacity), the rate that goes into
the traffic file. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def knee(lines: list, rates: list):
    """The knee by the rule above, from the per-window lines; None when
    even the first step up gained too little: the knee lies below the
    lowest rate swept."""
    best = None
    for lo, hi in zip(rates, rates[1:]):
        for seed in {ln["seed"] for ln in lines}:
            a = next(ln for ln in lines if ln["seed"] == seed and ln["rate"] == lo)
            b = next(ln for ln in lines if ln["seed"] == seed and ln["rate"] == hi)
            if (b["served_tok_s"] - a["served_tok_s"]
                    < 0.5 * (b["offered_tok_s"] - a["offered_tok_s"])):
                return best
        best = hi
    return best


def capacity(lines: list, k) -> float:
    """Requests per second the engine serves once saturated: the median
    served tokens per second of the windows past the knee (all of them when
    there is none) over the mean reply the mix offers."""
    past = [ln for ln in lines if k is None or ln["rate"] > k] or lines
    per_req = statistics.mean(ln["offered_tok_s"] / ln["rate"] for ln in lines)
    return statistics.median(ln["served_tok_s"] for ln in past) / per_req


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from bench.run import driver_class, load_cell, start_jax

    _, cell, cfg, traffic = load_cell(args.workload)
    start_jax()
    rates = sorted(args.rates)
    d = driver_class(traffic)(cfg, dict(traffic, rate_per_s=rates[-1]),
                              args.seeds[0])
    d.seconds, d.drain = args.seconds, False
    d.setup()  # warms the widths of the largest plan, which has them all
    lines = []
    for seed in args.seeds:
        for rate in rates:
            d.seed, d.traffic = seed, dict(traffic, rate_per_s=rate)
            d.plan(args.seconds)
            d.stats, d.calls = {}, 0
            d.window(args.seconds)
            c = d.counts()
            wait, ttft = d.waits_ms()
            span = d.t_end - d.t0
            line = {"seed": seed, "rate": rate, "calls": d.calls,
                    "offered_tok_s": c["offered_tokens"] / span,
                    "served_tok_s": c["served_tokens"] / span,
                    "due": c["due"], "waiting_at_close": c["waiting_at_close"],
                    "waiting_tokens": c["waiting_tokens"],
                    "call_wait_mean_ms": wait, "ttft_p95_ms": ttft,
                    "itl_p95_ms": d.end_to_end()["itl_p95_ms"][0]}
            lines.append(line)
            print(json.dumps(line), flush=True)
    k = knee(lines, rates)
    cap = capacity(lines, k)
    print(json.dumps({"knee": k, "capacity_req_s": cap,
                      "cell_rate": 0.8 * (cap if k is None else k)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
