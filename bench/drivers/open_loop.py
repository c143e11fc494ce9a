"""Open-loop serving: requests arrive on a Poisson schedule at the mix's
fixed rate, whether or not the engine keeps up.

Set-up builds the engine with seeded weights and warms the decode step and
one prefill per bucket width that the planned prompts fall into.

The window repeatedly hands `ServeEngine.run` every request that is due; a
request that arrives while a call runs waits for the next call. Latency
counts from each request's due time. Requests due before the window closes
but not yet started when it does are served after it, so that every
request of the window has its latency.

The cell judges the gap between output tokens. Time to first token is
printed among the notes and not judged: a request that arrives during a
call waits for the whole call, so its wait follows the longest reply of
that call and swings with the order of the sizes (PERF.md).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import serving
from bench.serving import ServeDriver


class Driver(ServeDriver):
    trace_logits = False
    drain = True  # serve, after the close, what was due and not started

    def plan(self, seconds: float):
        """round(rate x seconds) requests due inside the window: stratified
        sizes and gaps in an order drawn from the seed, the gaps scaled so
        the arrivals fill the window at the mix's rate for every seed."""
        t = self.traffic
        n = max(1, round(t["rate_per_s"] * seconds))
        rng = np.random.default_rng(self.seed % 2 ** 63)
        prompts = serving.lognormal_set(t["prompt"], n)[rng.permutation(n)]
        outs = serving.lognormal_set(t["output"], n)[rng.permutation(n)]
        gaps = serving.exponential_gaps(t["rate_per_s"], n)[rng.permutation(n)]
        due = np.cumsum(gaps) * (seconds * (n - 0.5) / n) / gaps.sum()
        V = self.cfg["vocab_size"]
        self.plan_ = [(float(due[i]), rng.integers(0, V, int(prompts[i])),
                       int(outs[i])) for i in range(n)]

    def warm_lengths(self):
        return serving.planned_widths(len(p) for _, p, _ in self.plan_)

    def window(self, seconds: float):
        reqs = [serving.make_request(i, p, m)
                for i, (_, p, m) in enumerate(self.plan_)]
        offs = [d for d, _, _ in self.plan_]
        n = len(reqs)
        t0 = time.perf_counter()
        end = t0 + seconds
        self.due = {}
        self.call_start = {}
        i = 0
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
        while True:
            now = time.perf_counter()
            if now >= end:
                break
            batch = []
            while i < n and t0 + offs[i] <= now:
                batch.append(reqs[i])
                self.due[reqs[i].uid] = t0 + offs[i]
                i += 1
            if not batch:
                nxt = t0 + offs[i] if i < n else end
                with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                    time.sleep(max(0.0, min(nxt, end) - now))
                continue
            for r in batch:
                self.call_start[r.uid] = now
            self.serve(batch)
        span.__exit__(None, None, None)
        self.t0, self.t_end, self.t_loop_end = t0, end, time.perf_counter()
        rest = []
        while i < n and t0 + offs[i] < end:
            rest.append(reqs[i])
            self.due[reqs[i].uid] = t0 + offs[i]
            i += 1
        self.offered = reqs[:i]
        if rest and self.drain:
            now = time.perf_counter()
            for r in rest:
                self.call_start[r.uid] = now
            self.serve(rest, late=True)
        self.done = [r for r in self.offered if r.uid in self.call_start]

    def counts(self) -> dict:
        """What the window offered and what it served: requests and tokens
        due inside it, tokens stamped inside it, and the requests (and their
        tokens) that no call had started when it closed."""
        waiting = [r for r in self.offered
                   if self.call_start.get(r.uid, self.t_end + 1) > self.t_end]
        return {"due": len(self.offered),
                "offered_tokens": sum(r.max_new for r in self.offered),
                "served_tokens": sum(1 for r in self.done for t in r.out.t
                                     if self.t0 <= t <= self.t_end),
                "waiting_at_close": len(waiting),
                "waiting_tokens": sum(r.max_new for r in waiting)}

    def waits_ms(self) -> tuple:
        """(mean wait for the call that carried a request, TTFT p95), ms."""
        wait = [(self.call_start[r.uid] - self.due[r.uid]) * 1e3
                for r in self.done]
        ttft = [(r.out.t[0] - self.due[r.uid]) * 1e3 for r in self.done]
        return sum(wait) / len(wait), serving.p95(ttft)

    def notes(self) -> str:
        c = self.counts()
        wait, ttft = self.waits_ms()
        return (f"calls {self.calls} requests {len(self.done)} "
                f"waiting_at_close {c['waiting_at_close']} served_tokens "
                f"{c['served_tokens']} offered_tokens {c['offered_tokens']} "
                f"call_wait_mean_ms {wait:.1f} ttft_p95_ms {ttft:.1f}")

    def end_to_end(self) -> dict:
        itl = [g * 1e3 for r in self.done for g in np.diff(r.out.t)]
        return {"itl_p95_ms": (serving.p95(itl), "ms")}
