"""The program's spans, and wall-clock timing for the CPU micro-benchmarks.

`span` is the one way the program marks a phase: a
`jax.profiler.TraceAnnotation` (recorded on the device trace's clock only
while a profile is being taken, ~1 us to enter and exit otherwise) that
also times itself on the host clock. Every span name starts with
``repro.``; its keyword args carry the identifiers that tie spans together
(`k` round, `req` request uid, `width` prefill width, `active` occupied
slots).
"""
from __future__ import annotations

import time

import jax

PREFIX = "repro."


class span:
    """Context manager: a named profiler span around the block, whose
    host-clock duration is `.seconds` after exit.

        with span("repro.chef.select", k=3) as sp:
            ...
        t_select = sp.seconds
    """

    __slots__ = ("_annotation", "_t0", "seconds")

    def __init__(self, name: str, **args):
        if not name.startswith(PREFIX):
            raise ValueError(f"span name {name!r} must start with {PREFIX!r}")
        self._annotation = jax.profiler.TraceAnnotation(name, **args)
        self.seconds = None

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        return False


def time_fn(fn, *args, iters: int = 5, warmup: int = 2, **kwargs) -> float:
    """Median wall time of fn(*args) over `iters` runs, blocking on outputs."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args, **kwargs))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]
