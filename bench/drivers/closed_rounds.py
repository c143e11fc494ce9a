"""Closed-loop serving in rounds: each round's requests go to
`ServeEngine.run` together when the last round has finished, as the
cleaning loop waits for its model annotator.

A round is `round_requests` prompts, each the annotator's fixed task prefix
followed by one bin token per feature of a seeded row, quantized as
`repro.stream.ModelAnnotator` does (token 1 + bin of n_bins over
[lo, hi]). A request's latency counts from its round's start.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import serving
from bench.serving import ServeDriver


class Driver(ServeDriver):
    trace_logits = True

    def plan(self, seconds: float):
        t = self.traffic
        rng = np.random.default_rng(self.seed % 2 ** 63)
        n = t["rounds_planned"] * t["round_requests"]
        X = rng.standard_normal((n, t["features"]), dtype=np.float32)
        span = t["hi"] - t["lo"]
        bins = np.clip(np.round((X - t["lo"]) / span * (t["n_bins"] - 1)),
                       0, t["n_bins"] - 1).astype(np.int32)
        V = self.cfg["vocab_size"]
        prefix = ((np.arange(t["prefix_len"]) * 37 + 11) % V).astype(np.int32)
        self.prompts = [np.concatenate([prefix, 1 + row]) for row in bins]

    def warm_lengths(self):
        return [len(self.prompts[0])]

    def window(self, seconds: float):
        t = self.traffic
        b = t["round_requests"]
        t0 = time.perf_counter()
        end = t0 + seconds
        self.done, self.due, self.round_s = [], {}, []
        k = 0
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
        while True:
            base = (k * b) % len(self.prompts)
            reqs = [serving.make_request(k * b + j, self.prompts[base + j],
                                         t["max_new"]) for j in range(b)]
            start = time.perf_counter()
            for r in reqs:
                self.due[r.uid] = start
            with jax.profiler.TraceAnnotation("bench.round"):
                self.serve(reqs)
            self.round_s.append(time.perf_counter() - start)
            self.done.extend(reqs)
            k += 1
            if time.perf_counter() >= end:
                break
        span.__exit__(None, None, None)
        self.t0, self.t_end = t0, max(r.out.t[-1] for r in self.done)
        self.t_loop_end = time.perf_counter()
        self.rounds = k

    def notes(self) -> str:
        q = np.percentile(self.round_s, [0, 50, 90, 100])
        return (f"rounds {self.rounds} round_s min/p50/p90/max "
                + "/".join(f"{v:.4f}" for v in q))

    def end_to_end(self) -> dict:
        ttft = [(r.out.t[0] - self.due[r.uid]) * 1e3 for r in self.done]
        toks = sum(len(r.out) for r in self.done)
        return {"tokens_per_s": (toks / (self.t_end - self.t0), "tokens/s"),
                "ttft_p95_ms": (serving.p95(ttft), "ms")}
