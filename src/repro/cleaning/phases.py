"""The three CHEF phases as pluggable protocol classes.

`run_chef`'s monolithic loop body is decomposed into the paper's Figure-1
boxes, each behind a small protocol, so baselines and backends plug in
uniformly and the scheduler composes them:

  Selector    — sample selection: INFL (+ Increm-INFL pruning) or a baseline.
                Everything score-shaped dispatches through the session's
                `Backend` (reference | pallas | pallas_sharded).
  Annotator   — the annotation phase. `SimulatedAnnotator` computes the voted
                labels deterministically but hands back an `AnnotationTask`
                whose result only becomes AVAILABLE after the configured
                human latency — the window the pipelined scheduler overlaps
                with compute. `predict()` exposes what is knowable before
                the humans answer (INFL's suggested labels), which is what
                the scheduler speculates on.
  Constructor — the model-constructor phase: DeltaGrad-L replay or full
                retrain. Constructors are PURE with respect to the session
                (they return a `ConstructorResult`; only
                `session.apply_round` commits), which is what makes
                speculative execution safe.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import NamedTuple, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import annotation, baselines, increm, lr_head
from repro.core.deltagrad import build_correction_schedule, deltagrad_replay
from repro.core.influence import infl, influence_vector, top_b
from repro.core.pipeline import train_head
from repro.utils.timing import span


class RoundSelection(NamedTuple):
    idx: jax.Array  # [b] selected sample indices
    priority: jax.Array  # [N]
    suggested: Optional[jax.Array]  # [N] INFL's proposed labels (None: baseline)
    n_candidates: int  # Increm-INFL survivors (N when Full)


class ConstructorResult(NamedTuple):
    ds: "object"  # dataset with this round's labels applied
    w: jax.Array
    traj: Optional[tuple]
    sched: jax.Array


# ------------------------------------------------------------------ selector


@runtime_checkable
class Selector(Protocol):
    def select(self, session, eligible, key) -> RoundSelection: ...


@dataclass(frozen=True)
class InflSelector:
    """INFL (Eq. 6), optionally pruned by Increm-INFL (Theorem 1 +
    Algorithm 1). `mode`: full | increm | increm_tight."""

    mode: str = "full"

    def select(self, session, eligible, key) -> RoundSelection:
        cfg, ds, bk = session.cfg, session.ds, session.backend
        with span("repro.chef.select.cg"):
            v, _ = influence_vector(
                session.w, session.Xa_val, ds.y_val, session.Xa, ds.y_weight,
                cfg.l2, cg_iters=cfg.cg_iters, cg_tol=cfg.cg_tol, backend=bk,
            )
        if self.mode.startswith("increm"):
            with span("repro.chef.select.prune"):
                priority, suggested, pruned = increm.increm_infl(
                    session.prov, session.w, v, session.Xa, ds.y_prob,
                    cfg.gamma, eligible, cfg.round_size,
                    tight=(self.mode == "increm_tight"), backend=bk,
                )
                n_cand = int(pruned.n_candidates)
        else:
            r = infl(session.w, v, session.Xa, ds.y_prob, cfg.gamma, backend=bk)
            priority, suggested, n_cand = r.priority, r.suggested, ds.n
        idx = top_b(priority, eligible, cfg.round_size)
        return RoundSelection(idx, priority, suggested, n_cand)


@dataclass(frozen=True)
class BaselineSelector:
    """The paper's Exp1 baselines (repro.core.baselines) behind the same
    protocol: infl_d | infl_y | active_one | active_two | o2u | tars | duti |
    loss | random."""

    method: str

    def select(self, session, eligible, key) -> RoundSelection:
        cfg, ds = session.cfg, session.ds
        Xa, Xa_val, w = session.Xa, session.Xa_val, session.w
        m = self.method
        if m in ("infl_d", "infl_y"):
            v, _ = influence_vector(
                w, Xa_val, ds.y_val, Xa, ds.y_weight, cfg.l2,
                cg_iters=cfg.cg_iters, cg_tol=cfg.cg_tol,
            )
            sel = (baselines.select_infl_d(w, v, Xa, ds.y_prob) if m == "infl_d"
                   else baselines.select_infl_y(w, v, Xa, ds.y_prob))
        elif m == "active_one":
            sel = baselines.select_active_one(w, Xa)
        elif m == "active_two":
            sel = baselines.select_active_two(w, Xa)
        elif m == "loss":
            sel = baselines.select_loss(w, Xa, ds.y_prob)
        elif m == "random":
            sel = baselines.select_random(key, ds.n)
        elif m == "o2u":
            sched = lr_head.batch_schedule(cfg.seed + 7, ds.n,
                                           min(cfg.batch_size, ds.n), 4)
            w0 = lr_head.init_head(key, ds.n_classes, ds.X.shape[1])
            sel = baselines.select_o2u(w0, Xa, ds.y_prob, ds.y_weight, sched,
                                       l2=cfg.l2, lr_max=cfg.lr * 4)
        elif m == "tars":
            sel = baselines.select_tars_lite(w, Xa, ds.y_prob, ds.human_labels,
                                             ds.n_classes)
        elif m == "duti":
            sel = baselines.select_duti_lite(w, Xa, ds.y_prob, ds.y_weight,
                                             Xa_val, ds.y_val, l2=cfg.l2, lr=cfg.lr)
        else:
            raise ValueError(m)
        idx = top_b(sel.priority, eligible, cfg.round_size)
        return RoundSelection(idx, sel.priority, sel.suggested, ds.n)


def make_selector(method: str, selector: str) -> Selector:
    """(method, selector) in `run_chef`'s vocabulary -> a Selector object."""
    if method == "infl":
        return InflSelector(mode=selector)
    assert selector == "full", "Increm-INFL prunes INFL scores"
    return BaselineSelector(method)


# ----------------------------------------------------------------- annotator


class AnnotationTask:
    """A deterministic simulated-async annotation: the voted labels are fixed
    at creation (the simulation knows them), but become *available* only
    `latency_s` later — modelling the human turnaround the paper's pipelined
    design overlaps with selection/update compute."""

    def __init__(self, labels: jax.Array, latency_s: float = 0.0):
        self._labels = labels
        self._ready_at = time.monotonic() + max(latency_s, 0.0)

    def ready(self) -> bool:
        return time.monotonic() >= self._ready_at

    def result(self) -> jax.Array:
        """Block (sleep the remaining simulated latency) until the annotators
        have answered, then return the voted labels [b]."""
        dt = self._ready_at - time.monotonic()
        if dt > 0:
            time.sleep(dt)
        return self._labels


@runtime_checkable
class Annotator(Protocol):
    def annotate(self, session, selection: RoundSelection, key) -> AnnotationTask: ...

    def predict(self, session, selection: RoundSelection) -> Optional[jax.Array]: ...


@dataclass(frozen=True)
class SimulatedAnnotator:
    """Section 5.1 annotators: majority vote over the dataset's simulated
    human labels, with INFL joining per the strategy (one | two | three)."""

    strategy: str = "three"
    latency_s: float = 0.0

    def _vote_inputs(self, session, selection: RoundSelection):
        ds = session.ds
        humans = ds.human_labels[selection.idx]
        if selection.suggested is not None:
            return humans, selection.suggested[selection.idx], self.strategy
        # no label suggestions -> humans only
        return humans, jnp.zeros(selection.idx.shape, jnp.int32), "one"

    def annotate(self, session, selection: RoundSelection, key) -> AnnotationTask:
        humans, infl_lbl, strategy = self._vote_inputs(session, selection)
        labels = annotation.cleaned_labels(strategy, humans, infl_lbl,
                                           session.ds.n_classes, key=key)
        return AnnotationTask(labels, self.latency_s)

    def predict(self, session, selection: RoundSelection) -> Optional[jax.Array]:
        """Best guess at the voted labels using only pre-vote information:
        INFL's suggestions. Exact for strategy 'two' (the suggestions ARE the
        labels); a speculation target for 'one'/'three'."""
        if selection.suggested is None:
            return None
        return selection.suggested[selection.idx].astype(jnp.int32)


# --------------------------------------------------------------- constructor


@runtime_checkable
class Constructor(Protocol):
    def construct(self, session, idx, labels) -> ConstructorResult: ...


@dataclass(frozen=True)
class DeltaGradConstructor:
    """DeltaGrad-L incremental replay against the round-(k-1) cache
    (Section 4.2 item (2)): cached gradients were computed on the old labels;
    corrections cover only this round's b samples. The replay dispatches
    through the session's `Backend` (explicit batch gradients + fused
    corrections; bit-identical across backends) and keeps the refreshed
    [T, C, d+1] trajectory row-sharded on pallas_sharded."""

    def construct(self, session, idx, labels) -> ConstructorResult:
        ds_old = session.ds
        ds_new = ds_old.clean(idx, labels)
        with span("repro.chef.update.schedule"):
            ci, cm = build_correction_schedule(np.asarray(session.sched),
                                               np.asarray(idx))
        with span("repro.chef.update.replay"):
            w, traj = deltagrad_replay(
                session.traj[0], session.traj[1], session.sched, session.Xa,
                ds_old.y_prob, ds_new.y_prob, ds_old.y_weight, ds_new.y_weight,
                ci, cm, session.dgc, int(session.sched.shape[1]),
                backend=session.backend,
            )
        return ConstructorResult(ds_new, w, session.backend.shard_trajectory(traj),
                                 session.sched)


@dataclass(frozen=True)
class RetrainConstructor:
    """Full from-scratch retrain (the paper's Retrain baseline) — the SGD
    scan dispatches through the session's `Backend`. Caches a fresh
    trajectory only when a DeltaGrad round may still follow."""

    cache_trajectory: bool = False

    def construct(self, session, idx, labels) -> ConstructorResult:
        ds_new = session.ds.clean(idx, labels)
        w, traj, sched = train_head(ds_new, session.cfg,
                                    cache=self.cache_trajectory,
                                    backend=session.backend)
        return ConstructorResult(ds_new, w, traj if self.cache_trajectory else None,
                                 sched)


def make_constructor(name: str) -> Constructor:
    if name == "deltagrad":
        return DeltaGradConstructor()
    if name == "retrain":
        return RetrainConstructor()
    raise ValueError(name)
