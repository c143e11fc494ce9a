"""Each cell's run, with the timed path broken underneath, comes out as not
correct: the harness's look for a chip is skipped and the rest of a run
(set-up, window, release, check) is driven at test size on the CPU.

Faults a cell can have: a step that returns its state unchanged; half of
the batch left out, the mean taken over the rest; a token or an answer
altered where it is produced. (Both cells run on one chip, so no exchange
between chips exists to leave out.)"""
import jax
import numpy as np
import pytest

import bench_tiny as bt


@pytest.fixture
def fresh_jit():
    jax.clear_caches()
    yield
    jax.clear_caches()


def _clean():
    from bench.drivers import clean_rounds

    return clean_rounds.Driver(bt.clean_cfg(), bt.load("traffic", "clean-b10"),
                               2 ** 31 + 91)


def _fault_state_unchanged(mp):
    from repro.cleaning import phases

    def construct(self, session, idx, labels):
        return phases.ConstructorResult(session.ds.clean(idx, labels), session.w,
                                        session.traj, session.sched)

    mp.setattr(phases.DeltaGradConstructor, "construct", construct)


def _fault_half_batch(mp):
    from repro.kernels import ops

    orig = ops.minibatch_grad
    mp.setattr(ops, "minibatch_grad", lambda w, Xa, Y, wt, idx, l2: orig(
        w, Xa, Y, wt, idx[: idx.shape[0] // 2], l2))


def _fault_selection_altered(mp):
    from repro.cleaning import phases

    orig = phases.InflSelector.select

    def select(self, session, eligible, key):
        sel = orig(self, session, eligible, key)
        worst = int(np.argmax(np.where(np.asarray(eligible),
                                       np.asarray(sel.priority), -np.inf)))
        return sel._replace(idx=sel.idx.at[0].set(worst))

    mp.setattr(phases.InflSelector, "select", select)


def _fault_label_altered(mp):
    from repro.core import annotation

    orig = annotation.cleaned_labels

    def voted(*a, **k):
        labels = orig(*a, **k)
        return labels.at[0].set(1 - labels[0])

    mp.setattr(annotation, "cleaned_labels", voted)


def test_clean_sound_run_is_correct(fresh_jit):
    ok, checks = bt.run_cell(_clean())
    assert ok, checks


@pytest.mark.parametrize("fault", [_fault_state_unchanged, _fault_half_batch,
                                   _fault_selection_altered,
                                   _fault_label_altered])
def test_clean_fault_is_not_correct(fault, monkeypatch, fresh_jit):
    fault(monkeypatch)
    ok, checks = bt.run_cell(_clean())
    assert not ok, checks


def _serve(kind):
    from bench.drivers import closed_rounds, open_loop

    cfg = bt.olmo_cfg()
    if kind == "chat":
        return open_loop.Driver(cfg, bt.chat_traffic(), 2 ** 31 + 13)
    return closed_rounds.Driver(cfg, bt.annotate_traffic(), 2 ** 31 + 13)


def _fault_token_altered(mp):
    from repro.serving import engine

    mp.setattr(engine, "greedy", lambda logits: (jax.numpy.argmax(
        logits[:, -1, :], axis=-1).astype(jax.numpy.int32)[:, None] + 1)
        % logits.shape[-1])


def _fault_kv_unchanged(mp):
    from repro.models import attention

    mp.setattr(attention, "paged_update_decode",
               lambda cache, k, v, pos, pages: cache)


@pytest.mark.parametrize("kind", ["chat", "annotate"])
def test_serve_sound_run_is_correct(kind, fresh_jit):
    ok, checks = bt.run_cell(_serve(kind))
    assert ok, checks


@pytest.mark.parametrize("kind,fault", [
    ("chat", _fault_token_altered), ("chat", _fault_kv_unchanged),
    ("annotate", _fault_token_altered)])
def test_serve_fault_is_not_correct(kind, fault, monkeypatch, fresh_jit):
    fault(monkeypatch)
    ok, checks = bt.run_cell(_serve(kind))
    assert not ok, checks
