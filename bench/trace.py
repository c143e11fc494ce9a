"""Reduce a profiler trace (`.xplane.pb`) to device busy time, op and module
times, and idle gaps labelled by the host span that was open.

The layout read here is the TPU profiler's: one plane per chip named
`/device:TPU:<n>`, whose line "XLA Ops" holds one event per executed HLO
operation, named by the instruction's text (`%lr_hvp.6 = f32[...]
custom-call(...)`; a control-flow op such as `%while.4` spans the ops it
runs), and whose line "XLA Modules" holds one event per executed program
(`jit_decode_step(<hash>)`); and the host plane `/host:CPU`, whose thread
lines hold the `jax.profiler.TraceAnnotation` spans the benchmark opens.
All times come back in seconds.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"


_HLO = re.compile(r"^(%[\w.-]+) = .*?\s([a-z][\w-]*)\(")


@dataclass
class Event:
    name: str
    start: float  # seconds
    dur: float  # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def op(self) -> str:
        """'<instruction> <opcode>' of an XLA op event ('%lr_hvp.6
        custom-call'); the plain name of any other event."""
        m = _HLO.match(self.name)
        return f"{m.group(1)} {m.group(2)}" if m else self.name[:80]

    @property
    def is_kernel(self) -> bool:
        return self.op.endswith(" custom-call")


@dataclass
class Trace:
    window: tuple  # (start, end) seconds
    ops: dict = field(default_factory=dict)  # chip -> [Event] (XLA Ops)
    modules: dict = field(default_factory=dict)  # chip -> [Event]
    spans: list = field(default_factory=list)  # host [Event], bench spans

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def _event(e) -> Event:
    return Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)


def load(path: str, span_prefix: str = "bench.") -> Trace:
    """Read the trace at `path` (a file or a directory holding one). The
    window is the `bench.window` host span; device events are clipped to
    it."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = plane.name.rsplit(":", 1)[1]
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = [_event(e) for e in line.events]
                elif line.name == "XLA Modules":
                    modules[chip] = [_event(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(span_prefix):
                        spans.append(_event(e))
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if win:
        window = (win[0].start, win[0].end)
    else:
        evs = [e for v in ops.values() for e in v]
        if not evs:
            raise ValueError(f"{path}: no device ops and no {WINDOW_SPAN} span")
        window = (min(e.start for e in evs), max(e.end for e in evs))
    clip = lambda evs: [e for e in evs if e.end > window[0] and e.start < window[1]]
    return Trace(window, {c: clip(v) for c, v in ops.items()},
                 {c: clip(v) for c, v in modules.items()}, spans)


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy(trace: Trace, chip: str) -> list:
    """Disjoint intervals in which some op ran on `chip`, within the window."""
    w0, w1 = trace.window
    return [(max(s, w0), min(e, w1)) for s, e in
            union((e.start, e.end) for e in trace.ops.get(chip, []))
            if min(e, w1) > max(s, w0)]


def busy_s(trace: Trace) -> float:
    """Device busy seconds, averaged over the chips in the trace."""
    chips = list(trace.ops)
    if not chips:
        return 0.0
    return sum(sum(e - s for s, e in busy(trace, c)) for c in chips) / len(chips)


def idle_share(trace: Trace) -> float | None:
    """1 - busy / window, as a fraction; None without device ops."""
    if not trace.ops or trace.window_s <= 0:
        return None
    return 1.0 - busy_s(trace) / trace.window_s


def gaps(trace: Trace, chip: str) -> list:
    """Idle (start, end) intervals on `chip` inside the window."""
    w0, w1 = trace.window
    out, t = [], w0
    for s, e in busy(trace, chip):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if w1 > t:
        out.append((t, w1))
    return out


def span_at(trace: Trace, t0: float, t1: float) -> str:
    """The innermost benchmark span (shortest, other than the window) that
    covers most of [t0, t1]; 'window' when none does."""
    best, best_key = "window", None
    for s in trace.spans:
        if s.name == WINDOW_SPAN:
            continue
        ov = min(s.end, t1) - max(s.start, t0)
        if ov <= 0.5 * (t1 - t0):
            continue
        if best_key is None or s.dur < best_key:
            best, best_key = s.name, s.dur
    return best


def within(events, outer) -> list:
    """Events that lie inside some event of `outer` (e.g. ops of a module)."""
    spans = sorted((o.start, o.end) for o in outer)
    out, i = [], 0
    for e in sorted(events, key=lambda e: e.start):
        while i < len(spans) and spans[i][1] < e.start:
            i += 1
        if i < len(spans) and spans[i][0] <= e.start and e.end <= spans[i][1] + 1e-9:
            out.append(e)
    return out


def self_times(events) -> list:
    """(event, self seconds): each op's duration less the ops nested in it
    (a `%while` runs its body's ops inside its own interval)."""
    evs = sorted(events, key=lambda e: (e.start, -e.dur))
    out, stack = [], []  # stack of [event, child seconds]
    for e in evs:
        while stack and stack[-1][0].end <= e.start:
            out.append((stack[-1][0], stack[-1][0].dur - stack[-1][1]))
            stack.pop()
        if stack and e.end <= stack[-1][0].end + 1e-12:
            stack[-1][1] += e.dur
        stack.append([e, 0.0])
    out.extend((p, p.dur - c) for p, c in stack)
    return out


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time (self time summed by op, averaged
    over chips) and the longest idle gaps, each labelled by the host span
    open across it."""
    by_name: dict = {}
    for evs in trace.ops.values():
        for e, own in self_times(evs):
            by_name[e.op] = by_name.get(e.op, 0.0) + own
    n = max(len(trace.ops), 1)
    ops = sorted(((k, v / n) for k, v in by_name.items()),
                 key=lambda kv: -kv[1])[:top]
    chip = sorted(trace.ops)[0] if trace.ops else None
    gs = sorted(gaps(trace, chip), key=lambda g: g[0] - g[1])[:top] if chip else []
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[span_at(trace, s, e), e - s] for s, e in gs]}
