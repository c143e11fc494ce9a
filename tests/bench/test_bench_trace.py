"""The reduction from a profiler trace to busy time, idle gaps and their
host-span labels, on hand-made events and on a small trace recorded on a
TPU v5e chip (tests/bench/data/)."""
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
from bench import trace as tr

DATA = Path(__file__).parent / "data"


def _trace():
    ev = tr.Event
    ops = {"0": [ev("%lr_hvp.6 = f32[128,2176]{1,0} custom-call(f32[8,8] %x)",
                    1.0, 1.0),
                 ev("%fusion.2 = f32[2,8]{1,0} fusion(f32[8] %y), kind=kLoop",
                    1.2, 0.5),
                 ev("%while.4 = (s32[]) while((s32[]) %t)", 4.0, 0.5),
                 ev("d", 9.5, 2.0)]}
    mods = {"0": [ev("jit_step", 0.9, 1.7), ev("jit_other", 3.9, 0.7)]}
    spans = [ev("bench.window", 0.0, 10.0), ev("bench.round", 0.0, 5.0),
             ev("bench.select", 2.4, 2.0)]
    return tr.Trace((0.0, 10.0), ops, mods, spans)


def test_union_merges_overlaps():
    assert tr.union([(3, 4), (1, 2), (1.5, 2.5), (4, 5)]) == [(1, 2.5), (3, 5)]


def test_busy_and_idle_are_clipped_to_the_window():
    t = _trace()
    # [1, 2] (the fusion runs inside it) + [4, 4.5] + [9.5, 10] (d clipped
    # at the window's end)
    assert tr.busy(t, "0") == [(1.0, 2.0), (4.0, 4.5), (9.5, 10.0)]
    assert tr.busy_s(t) == pytest.approx(2.0)
    assert tr.idle_share(t) == pytest.approx(0.8)
    assert tr.gaps(t, "0") == [(0.0, 1.0), (2.0, 4.0), (4.5, 9.5)]


def test_gaps_are_labelled_by_the_innermost_open_span():
    t = _trace()
    assert tr.span_at(t, 2.0, 4.0) == "bench.select"
    assert tr.span_at(t, 0.0, 1.0) == "bench.round"
    assert tr.span_at(t, 4.5, 9.5) == "window"
    b = tr.breakdown(t)
    assert b["idle_gaps"][0] == ["window", pytest.approx(5.0)]
    assert b["device_ops"][0] == ["d", pytest.approx(2.0)]
    # the fusion runs inside the kernel's interval: its time is not the
    # kernel's own
    assert ["%lr_hvp.6 custom-call", pytest.approx(0.5)] in b["device_ops"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_op_names_and_within():
    t = _trace()
    assert [e.op for e in t.ops["0"]] == [
        "%lr_hvp.6 custom-call", "%fusion.2 fusion", "%while.4 while", "d"]
    assert [e.is_kernel for e in t.ops["0"]] == [True, False, False, False]
    inside = tr.within(t.ops["0"], [m for m in t.modules["0"]
                                    if m.name == "jit_step"])
    assert [e.op for e in inside] == ["%lr_hvp.6 custom-call", "%fusion.2 fusion"]


def test_recorded_tpu_trace():
    path = next(DATA.glob("*.xplane.pb"))
    t = tr.load(str(path))
    assert t.ops, "the recorded trace holds a TPU device plane"
    assert 0.0 < tr.busy_s(t) <= t.window_s
    assert 0.0 <= tr.idle_share(t) < 1.0
    assert any(s.name == "bench.step" for s in t.spans)
    b = tr.breakdown(t)
    assert b["device_ops"] and all(v > 0 for _, v in b["device_ops"])
