"""What the per-layer metric readers share: finding a kernel's or a
program's events in the device trace, and the window's token records.

A reader (bench/metrics/<metric>.py) exposes `read(ctx) -> float | None`,
where ctx holds the driver, the reduced trace (bench/trace.py), the peaks,
the configuration and traffic, and the window's compile count. A reader
that finds nothing to read returns None and the metric is left out of the
result line.
"""
from __future__ import annotations

import re

import numpy as np

from bench import trace as tr


def kernel_events(ctx, name: str) -> list:
    """A Mosaic kernel's op events on every chip. The kernel runs as one
    `tpu_custom_call` instruction named after the jitted wrapper that
    launched it: `%lr_hvp.6 = ... custom-call(...)` for ops.lr_hvp."""
    t = ctx["trace"]
    pat = re.compile(rf"^%{re.escape(name)}(\.\d+)? custom-call$")
    return [e for evs in t.ops.values() for e in evs if pat.match(e.op)]


def module_events(ctx, program: str) -> list:
    """Program (XLA module) events on every chip whose name has `program`."""
    return [e for evs in ctx["trace"].modules.values() for e in evs
            if program in e.name]


def kernels_in(ctx, program: str) -> list:
    """Custom-call op events that ran inside the given program's events."""
    t = ctx["trace"]
    out = []
    for chip, evs in t.ops.items():
        mods = [m for m in t.modules.get(chip, []) if program in m.name]
        out.extend(e for e in tr.within(evs, mods) if e.is_kernel)
    return out


def share(least_s: float, took_s: float):
    """least / took as a percentage; None when nothing was timed."""
    if took_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / took_s


def decode_tokens(driver, t0: float, t1: float):
    """(context length) of every token a decode step produced whose host
    stamp lies in [t0, t1]: out[j] for j >= 1 attended prompt + j positions."""
    ctx = []
    for r in driver.done:
        L = len(r.prompt)
        for j, ts in enumerate(r.out.t):
            if j >= 1 and t0 <= ts <= t1:
                ctx.append(L + j)
    return np.asarray(ctx, np.int64)


def prefills(driver, t0: float, t1: float):
    """Prompt lengths of requests whose first token (their prefill) arrived
    in [t0, t1]."""
    return np.asarray([len(r.prompt) for r in driver.done
                       if r.out.t and t0 <= r.out.t[0] <= t1], np.int64)


def window_compiles(ctx) -> float:
    """Programs built (compiled, or loaded from the persistent cache) inside
    the window, from jax.monitoring's backend-compile events."""
    return float(ctx["compiles"])
