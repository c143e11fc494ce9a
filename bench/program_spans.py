"""The program's own spans in a traced run, and the device-idle time that
falls inside them.

The program marks its phases with `repro.utils.timing.span`: host spans
named `repro.*` (`repro.chef.select`, `repro.serve.decode_round`, ...) on
the profiler's clock, the same clock as the device ops. bench/trace.py keeps
only the benchmark's own `bench.*` spans; this module reads the run's
`.xplane.pb` once more for the `repro.*` ones and keeps them on the reader
context, so the readers of one run share a single read. A program that
opens no such span (one older than them) gives none, and every reader
built on them returns None.
"""
from __future__ import annotations

import bisect
import os
from pathlib import Path

from bench import trace as tr

PREFIX = "repro."
# where bench/run.py writes each cell's trace (.bench/trace/<cell>/)
TRACE_ROOT = Path(__file__).resolve().parents[1] / ".bench" / "trace"


def read(path: str) -> tuple:
    """(window, spans) of the trace at `path` (a file or a directory holding
    one): the `bench.window` span as (start, end), or None without one, and
    every host event whose name starts with `repro.`, in seconds."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = tr.find_xplane(path)
    window, spans = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(tr._event(e))
                elif e.name == tr.WINDOW_SPAN and window is None:
                    w = tr._event(e)
                    window = (w.start, w.end)
    return window, spans


def spans(ctx) -> list:
    """The run's `repro.*` spans that overlap its window. The run's trace is
    the newest under TRACE_ROOT whose `bench.window` is the window of
    ctx["trace"]; read once and kept as ctx["program_spans"]."""
    if "program_spans" not in ctx:
        found = []
        try:
            path = tr.find_xplane(str(TRACE_ROOT))
        except FileNotFoundError:
            path = None
        if path is not None:
            window, evs = read(path)
            w0, w1 = ctx["trace"].window
            if window == (w0, w1):
                found = [e for e in evs if e.end > w0 and e.start < w1]
        ctx["program_spans"] = found
    return ctx["program_spans"]


def named(ctx, name: str) -> list:
    """The window's spans called exactly `name`."""
    return [s for s in spans(ctx) if s.name == name]


def _gaps(ctx):
    """Idle intervals of the cell's first chip (the chip bench/trace.py's
    breakdown labels gaps on), or None without device ops."""
    if "program_gaps" not in ctx:
        t = ctx["trace"]
        ctx["program_gaps"] = tr.gaps(t, sorted(t.ops)[0]) if t.ops else None
    return ctx["program_gaps"]


def idle_within(gaps: list, intervals) -> list:
    """Seconds of `gaps` (sorted, disjoint) that fall inside each of
    `intervals`."""
    starts = [g[0] for g in gaps]
    out = []
    for s, e in intervals:
        i = max(bisect.bisect_right(starts, s) - 1, 0)
        took = 0.0
        while i < len(gaps) and gaps[i][0] < e:
            took += max(0.0, min(gaps[i][1], e) - max(gaps[i][0], s))
            i += 1
        out.append(took)
    return out


def idle_in(ctx, name: str):
    """(device-idle seconds on the first chip inside the union of the
    window's `name` spans, the number of those spans); None when the trace
    has no device ops or the program opened no such span."""
    ss, gaps = named(ctx, name), _gaps(ctx)
    if not ss or gaps is None:
        return None
    return sum(idle_within(gaps, tr.union((s.start, s.end) for s in ss))), len(ss)


def idle_each(ctx, name: str):
    """Device-idle seconds on the first chip inside each of the window's
    `name` spans, in order; None as for `idle_in`."""
    ss, gaps = named(ctx, name), _gaps(ctx)
    if not ss or gaps is None:
        return None
    return idle_within(gaps, [(s.start, s.end) for s in ss])
