"""The program's spans (`repro.utils.timing.span`): they nest, carry their
identifiers as args and time themselves; a cleaning round and a paged
`ServeEngine.run` record their phase spans under `jax.profiler` on the
trace's clock, nested as the benchmark's readers expect."""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.cleaning.scheduler import make_scheduler
from repro.cleaning.session import CleaningSession
from repro.configs import get_config, reduced
from repro.configs.chef_lr import ChefConfig
from repro.core.backend import get_backend
from repro.data.synth import ChefDataset
from repro.models import Model
from repro.serving.engine import Request, ServeConfig, ServeEngine
from repro.utils.timing import span


def _record(tmp_path, fn):
    """Run fn() under the profiler; return its result and the recorded
    `repro.*` host spans as (name, start_ns, end_ns, args)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for p in ProfileData.from_file(path).planes
             if p.name.startswith("/host:")
             for line in p.lines for e in line.events
             if e.name.startswith("repro.")]
    return out, spans


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _of(spans, name):
    return [s for s in spans if s[0] == name]


def test_span_nests_carries_args_and_times_itself(tmp_path):
    def body():
        with span("repro.test.outer", k=3) as outer:
            with span("repro.test.inner", req=7, width=64) as inner:
                time.sleep(0.01)
        return outer, inner

    (outer, inner), spans = _record(tmp_path, body)
    (o,), (i,) = _of(spans, "repro.test.outer"), _of(spans, "repro.test.inner")
    assert _inside(i, o)
    assert o[3] == {"k": 3} and i[3] == {"req": 7, "width": 64}
    assert 0.01 <= inner.seconds <= outer.seconds
    assert outer.seconds <= (o[2] - o[1]) * 1e-9 + 1e-3
    with pytest.raises(ValueError):
        span("chef.select")


def _dataset(n=300, n_val=64, d=24, n_annotators=3):
    """A two-class dataset of random rows (no generator to compile)."""
    r = np.random.default_rng(0)
    X, X_val = r.normal(size=(n, d)), r.normal(size=(n_val, d))
    y_true = (X[:, 0] > 0).astype(np.int32)
    flip = r.random((n, n_annotators)) < 0.1
    return ChefDataset(
        name="tiny", X=jnp.asarray(X, jnp.float32),
        y_prob=jnp.asarray(r.dirichlet([1.0, 1.0], n), jnp.float32),
        y_weight=jnp.full(n, 0.8, jnp.float32), cleaned=jnp.zeros(n, bool),
        y_true=jnp.asarray(y_true),
        human_labels=jnp.asarray(np.where(flip, 1 - y_true[:, None],
                                          y_true[:, None]), jnp.int32),
        X_val=jnp.asarray(X_val, jnp.float32),
        y_val=jax.nn.one_hot(jnp.asarray(X_val[:, 0] > 0, jnp.int32), 2),
        X_test=jnp.asarray(X_val, jnp.float32),
        y_test=jnp.asarray(X_val[:, 0] > 0, jnp.int32), n_classes=2)


def test_cleaning_round_spans(tmp_path):
    ds = _dataset()
    cfg = ChefConfig(budget=30, round_size=10, n_epochs=2, batch_size=100,
                     lr=0.05, l2=0.05, backend="reference")
    session = CleaningSession.initialize(ds, cfg, need_trajectory=True,
                                         need_provenance=True)
    sched = make_scheduler(session, method="infl", selector="increm",
                           constructor="deltagrad")
    rec, spans = _record(tmp_path, sched.step)
    (rnd,) = _of(spans, "repro.chef.round")
    assert rnd[3] == {"k": 0}
    for name in ("repro.chef.select", "repro.chef.annotate",
                 "repro.chef.update", "repro.chef.commit"):
        (s,) = _of(spans, name)
        assert _inside(s, rnd), name
    (sel,), (upd,) = _of(spans, "repro.chef.select"), _of(spans, "repro.chef.update")
    assert sel[3] == upd[3] == {"k": 0}
    for parent, children in ((sel, ("cg", "prune")), (upd, ("schedule", "replay"))):
        for c in children:
            (s,) = _of(spans, f"{parent[0]}.{c}")
            assert _inside(s, parent), s[0]
    # the round record's select and update times are the spans' own
    # durations on the host clock
    assert rec.t_select == pytest.approx((sel[2] - sel[1]) * 1e-9, abs=1e-3)
    assert rec.t_update == pytest.approx((upd[2] - upd[1]) * 1e-9, abs=1e-3)


def test_paged_run_spans(tmp_path):
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    eng = ServeEngine(model, model.init(jax.random.key(0)), batch_size=2,
                      max_len=32, backend=get_backend("reference"),
                      config=ServeConfig(cache="paged", share_prefix=False))
    rng = np.random.default_rng(0)

    def reqs():
        return [Request(uid, rng.integers(0, cfg.vocab_size, n).astype(np.int32), m)
                for uid, n, m in ((10, 8, 2), (11, 5, 4), (12, 6, 2))]

    done, spans = _record(tmp_path, lambda: eng.run(reqs()))
    assert len(done) == 3
    (run,) = _of(spans, "repro.serve.run")
    assert run[3] == {"n": 3}
    (init,) = _of(spans, "repro.serve.pool_init")
    assert _inside(init, run)
    admits = _of(spans, "repro.serve.admit")
    assert sorted(a[3]["req"] for a in admits) == [10, 11, 12]
    assert {a[3]["width"] for a in admits} == {8}
    for a in admits:
        assert _inside(a, run)
        for c in ("repro.serve.prefill", "repro.serve.commit"):
            assert sum(_inside(s, a) for s in _of(spans, c)) == 1
    rounds = _of(spans, "repro.serve.decode_round")
    assert rounds and all(_inside(r, run) for r in rounds)
    assert rounds[0][3] == {"active": 2}
    emits = _of(spans, "repro.serve.emit")
    assert len(emits) == len(rounds)
    assert all(any(_inside(e, r) for r in rounds) for e in emits)
    # the third request joins in the loop: its admission nests in an emit
    assert any(_inside(a, e) for a in admits for e in emits)
