"""The program-span readers (bench/program_spans.py and the metrics built on
it): device-idle time inside the program's `repro.*` spans, on hand-made
events, on a trace recorded here on the CPU, and on the v5e trace recorded
before the program opened any span (where every such reader finds nothing
and returns None)."""
import time
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the repo root on sys.path)
from bench import program_spans as ps
from bench import trace as tr
from bench.run import BENCH, load_module

DATA = Path(__file__).parent / "data"
NEW = ("clean.select_idle_ms", "clean.update_idle_ms", "clean.commit_idle_ms",
       "serve.decode_gap_ms", "serve.run_idle_ms.annotate",
       "serve.prefill_attention_ms")


def _read(metric, ctx):
    return load_module(BENCH / "metrics" / f"{metric}.py",
                       f"test_metric_{metric.replace('.', '_')}").read(ctx)


def _ctx():
    """Device busy [1, 2], [4, 4.5], [9.5, 10] of a 10-s window, so idle
    (0, 1), (2, 4), (4.5, 9.5); and two rounds of program spans."""
    ev = tr.Event
    ops = {"0": [ev("%flash_attention.5 = bf16[1,16,1024,128]{3,2,1,0} "
                    "custom-call(bf16[1] %q)", 1.0, 1.0),
                 ev("%fusion.2 = f32[2,8]{1,0} fusion(f32[8] %y)", 4.0, 0.5),
                 ev("d", 9.5, 2.0)]}
    mods = {"0": [ev("jit_prefill(1)", 0.9, 1.2), ev("jit_prefill(1)", 3.9, 0.7)]}
    spans = [ev(n, s, e - s) for n, s, e in (
        ("repro.chef.round", 0.0, 5.0), ("repro.chef.round", 5.0, 10.0),
        ("repro.chef.select", 0.0, 1.5), ("repro.chef.select", 5.0, 6.0),
        ("repro.chef.select.cg", 0.6, 0.9),
        ("repro.chef.update", 1.5, 4.2), ("repro.chef.update", 6.0, 7.0),
        ("repro.chef.commit", 4.2, 5.0), ("repro.chef.commit", 7.0, 10.0),
        ("repro.serve.decode_round", 0.0, 3.0),
        ("repro.serve.decode_round", 2.5, 6.0),
        ("repro.serve.run", 0.0, 2.0), ("repro.serve.run", 2.0, 5.0),
        ("repro.serve.run", 5.0, 10.0))]
    trace = tr.Trace((0.0, 10.0), ops, mods, [ev("bench.window", 0.0, 10.0)])
    return {"trace": trace, "program_spans": spans}


def test_idle_within_and_idle_in():
    gaps = [(0.0, 1.0), (2.0, 4.0), (4.5, 9.5)]
    assert ps.idle_within(gaps, [(0.5, 3.0), (4.2, 5.0), (9.5, 10.0)]) == [
        pytest.approx(1.5), pytest.approx(0.5), 0.0]
    ctx = _ctx()
    # union of (0, 3) and (2.5, 6): idle 1 + 2 + 1.5, over two spans
    assert ps.idle_in(ctx, "repro.serve.decode_round") == (pytest.approx(4.5), 2)
    # exact names: the select's child does not count as a select
    assert ps.idle_in(ctx, "repro.chef.select") == (pytest.approx(2.0), 2)
    assert ps.idle_each(ctx, "repro.serve.run") == [
        pytest.approx(1.0), pytest.approx(2.5), pytest.approx(4.5)]
    assert ps.idle_in(ctx, "repro.nothing") is None


def test_new_metrics_read_the_hand_made_trace():
    ctx = _ctx()
    got = {m: _read(m, ctx) for m in NEW}
    assert got == {"clean.select_idle_ms": pytest.approx(1000.0),
                   "clean.update_idle_ms": pytest.approx(1500.0),
                   "clean.commit_idle_ms": pytest.approx(1500.0),
                   "serve.decode_gap_ms": pytest.approx(2250.0),
                   "serve.run_idle_ms.annotate": pytest.approx(2500.0),
                   "serve.prefill_attention_ms": pytest.approx(500.0)}
    # the three phases hold all the idle time of these two rounds
    per_round_ms = 1e3 * tr.idle_share(ctx["trace"]) * ctx["trace"].window_s / 2
    assert sum(got[m] for m in NEW[:3]) == pytest.approx(per_round_ms)


def test_readers_find_nothing_in_a_program_without_spans():
    """The v5e trace predates the program's spans and names its attention
    kernel `%closed_call.N`: every new metric is left out, none raises."""
    path = next(DATA.glob("*.xplane.pb"))
    window, spans = ps.read(str(path))
    assert spans == []
    ctx = {"trace": tr.load(str(path)), "program_spans": spans}
    assert window is None or window == ctx["trace"].window
    assert all(_read(m, ctx) is None for m in NEW)


def test_spans_of_the_run_found_by_its_window(tmp_path, monkeypatch):
    import jax

    run_dir = tmp_path / "cell"
    jax.profiler.start_trace(str(run_dir))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("repro.chef.round", k=0):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench.round"):
                pass
    finally:
        jax.profiler.stop_trace()
    window, spans = ps.read(str(run_dir))
    assert [s.name for s in spans] == ["repro.chef.round"]
    assert window[0] <= spans[0].start and spans[0].end <= window[1]

    monkeypatch.setattr(ps, "TRACE_ROOT", tmp_path)
    trace = tr.Trace(window, {}, {}, [])
    assert [s.name for s in ps.spans({"trace": trace})] == ["repro.chef.round"]
    # another run's window: not this run's trace, so no spans
    other = tr.Trace((window[0], window[1] + 1.0), {}, {}, [])
    assert ps.spans({"trace": other}) == []
    # no device ops on the CPU: nothing to attribute
    assert ps.idle_in({"trace": trace}, "repro.chef.round") is None
