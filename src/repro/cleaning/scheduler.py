"""Pipelined round scheduler — the paper's overlap argument made real.

CHEF's Section-1 pitch is that cleaning, annotation, and incremental model
updates can overlap instead of strictly alternating. The blocking loop pays

    t_round = t_select + latency + t_update

per round (latency = human annotation turnaround). This scheduler overlaps
the latency window with *speculative* execution of everything downstream of
the votes:

  while round k's annotators are still voting, it
    1. runs the model constructor on the PREDICTED labels (INFL's suggested
       labels — exactly the votes under strategy 'two', a high-probability
       guess under 'one'/'three'), and
    2. prefetches round k+1's influence scoring against that speculative
       model,
  then validates: if the votes match the prediction the speculative round is
  adopted wholesale (t_round ≈ max(latency, t_update + t_select)); if not,
  the speculation is discarded and the constructor reruns on the real votes —
  costing nothing over the blocking loop, because the wasted work happened
  inside the latency window.

Speculation is validated against the actual votes, so the pipelined schedule
produces BIT-IDENTICAL selections, labels, and weights to the blocking one —
timing moves, results do not (asserted in tests/test_cleaning.py).

Fault tolerance rides the round loop: a `repro.dist.fault.Heartbeat` beats
every round, `retry_step` absorbs transient per-round failures, and the
session checkpoints through `repro.ckpt.CheckpointManager` (async writes
overlap the next round) so a killed job resumes bit-for-bit.

Early termination is first-class: `TargetF1`, `Patience`, and
`MarginalF1PerLabel` policy objects (composable; any firing stops the run).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Optional, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp

from repro.cleaning.phases import (
    Annotator,
    Constructor,
    ConstructorResult,
    RoundSelection,
    Selector,
    SimulatedAnnotator,
    make_constructor,
    make_selector,
)
from repro.cleaning.session import CleaningSession
from repro.core.pipeline import ChefResult, RoundRecord, _evaluate
from repro.dist.fault import Heartbeat, retry_step
from repro.utils.timing import span


# ------------------------------------------------------- termination policies


@runtime_checkable
class TerminationPolicy(Protocol):
    def should_stop(self, history: Sequence[RoundRecord]) -> bool: ...


@dataclass(frozen=True)
class TargetF1:
    """Stop once validation F1 reaches the target (paper's early stop)."""

    target: float

    def should_stop(self, history) -> bool:
        return bool(history) and history[-1].f1_val >= self.target


@dataclass(frozen=True)
class Patience:
    """Stop after `rounds` consecutive rounds in which the best validation F1
    failed to improve by MORE than `min_delta` (0 = any plateau stops)."""

    rounds: int
    min_delta: float = 0.0

    def should_stop(self, history) -> bool:
        if len(history) <= self.rounds:
            return False
        best_before = max(r.f1_val for r in history[: -self.rounds])
        recent_best = max(r.f1_val for r in history[-self.rounds:])
        return recent_best <= best_before + self.min_delta


@dataclass(frozen=True)
class MarginalF1PerLabel:
    """Stop when the marginal validation-F1 gain per cleaned label drops
    below `min_gain` — the resource-constrained stopping rule: annotator
    budget is the scarce resource, so stop when a label stops buying F1."""

    min_gain: float

    def should_stop(self, history) -> bool:
        if len(history) < 2:
            return False
        prev, last = history[-2], history[-1]
        labels = last.n_cleaned_total - prev.n_cleaned_total
        return labels > 0 and (last.f1_val - prev.f1_val) / labels < self.min_gain


def make_termination(cfg) -> tuple:
    """ChefConfig knobs -> policy objects (all default-disabled)."""
    policies = []
    if cfg.target_f1:
        policies.append(TargetF1(cfg.target_f1))
    if cfg.patience:
        policies.append(Patience(cfg.patience, cfg.patience_delta))
    if cfg.min_f1_per_label:
        policies.append(MarginalF1PerLabel(cfg.min_f1_per_label))
    return tuple(policies)


# ------------------------------------------------------------------ scheduler


class _Prefetch(NamedTuple):
    round: int
    selection: RoundSelection
    t_select: float  # compute time actually spent (hidden inside the latency)


class _Speculation(NamedTuple):
    labels: jax.Array
    result: ConstructorResult
    t_update: float
    prefetch: Optional[_Prefetch]


class _RoundOutcome(NamedTuple):
    """Everything round k computed, before any of it is committed."""

    round: int
    selection: RoundSelection
    t_select: float
    result: ConstructorResult
    t_update: float
    spec: Optional[str]  # "hit" | "miss" | None (not pipelined / no prediction)
    prefetch: Optional[_Prefetch]


class RoundScheduler:
    """Drives one `CleaningSession` through select -> annotate -> construct
    rounds, blocking or pipelined (see module docstring)."""

    def __init__(
        self,
        session: CleaningSession,
        selector: Selector,
        annotator: Annotator,
        constructor: Constructor,
        *,
        termination: Sequence[TerminationPolicy] = (),
        pipelined: bool = False,
        ckpt_dir=None,
        ckpt_every: int = 1,
        ckpt_keep: int = 3,
        heartbeat: Optional[Heartbeat] = None,
        retries: int = 0,
        step_wrapper=None,
        verbose: bool = False,
    ):
        self.session = session
        self.selector = selector
        self.annotator = annotator
        self.constructor = constructor
        self.termination = tuple(termination)
        self.pipelined = pipelined
        self.verbose = verbose
        self.spec_hits = 0
        self.spec_misses = 0
        self._prefetch: Optional[_Prefetch] = None
        self.ckpt = None
        self.ckpt_every = ckpt_every
        if ckpt_dir is not None:
            from pathlib import Path

            from repro.ckpt import CheckpointManager

            self.ckpt = CheckpointManager(ckpt_dir, keep=ckpt_keep)
            if heartbeat is None:
                heartbeat = Heartbeat(Path(ckpt_dir) / "heartbeat.json")
        self.heartbeat = heartbeat
        # retries wrap ONLY the round's compute, which mutates no session
        # state — the commit (apply_round, heartbeat, checkpoint) runs exactly
        # once per round. Wrapping the whole round would let a transient
        # failure AFTER the commit silently re-run as an extra round.
        # `step_wrapper` (the dist.chaos injection hook) sits INSIDE the
        # retry wrapper so injected transient failures are retried exactly
        # like real ones, and an injected kill escapes like a real one.
        compute = self._compute_round if step_wrapper is None \
            else step_wrapper(self._compute_round)
        self._compute = retry_step(compute, retries=retries) \
            if retries else compute

    # ------------------------------------------------------------- run state
    @property
    def exhausted(self) -> bool:
        s = self.session
        return s.terminated or not s.ledger.can_afford(s.cfg.round_size)

    def run(self, max_rounds: Optional[int] = None) -> ChefResult:
        done = 0
        while not self.exhausted and (max_rounds is None or done < max_rounds):
            self.step()
            done += 1
        if self.ckpt is not None:
            self.ckpt.wait()
        return self.result()

    def result(self) -> ChefResult:
        s = self.session
        if s.history:
            f1v, f1t = s.history[-1].f1_val, s.history[-1].f1_test
        else:
            f1v, f1t = _evaluate(s.w, s.ds)
        return ChefResult(s.w, s.ds, list(s.history), f1t, f1v, s.terminated)

    # ------------------------------------------------------------- one round
    def step(self) -> RoundRecord:
        with span("repro.chef.round", k=self.session.round):
            return self._commit(self._compute())

    def _compute_round(self) -> _RoundOutcome:
        """Select / annotate / construct for the current round. Mutates NO
        scheduler or session state (`self._prefetch` is only read), so a
        retry after a transient failure replays deterministically."""
        s = self.session
        k = s.round
        k_sel, k_vote = s.round_keys(k)
        eligible = s.eligible()

        # ---- selection phase (possibly prefetched inside round k-1's wait)
        pf = self._prefetch
        if pf is not None and pf.round == k:
            selection, t_select = pf.selection, pf.t_select
        else:
            selection, t_select = self._select(s, k, eligible, k_sel)

        # ---- annotation phase (simulated-async: votes land after latency;
        # the speculation runs inside that wait)
        with span("repro.chef.annotate"):
            task = self.annotator.annotate(s, selection, k_vote)

            spec: Optional[_Speculation] = None
            if self.pipelined and not task.ready():
                pred = self.annotator.predict(s, selection)
                if pred is not None:
                    spec = self._speculate(k, selection, pred)

            labels = task.result()

        # ---- model constructor phase (adopt speculation iff votes match)
        if spec is not None and bool(jnp.all(labels == spec.labels)):
            return _RoundOutcome(k, selection, t_select, spec.result,
                                 spec.t_update, "hit", spec.prefetch)
        result, t_update = self._construct(s, k, selection, labels)
        return _RoundOutcome(k, selection, t_select, result, t_update,
                             "miss" if spec is not None else None, None)

    def _select(self, s, k: int, eligible, key):
        """(selection, seconds): round k's selection, ended by
        block_until_ready, in its `repro.chef.select` span."""
        with span("repro.chef.select", k=k) as sp:
            selection = self.selector.select(s, eligible, key)
            jax.block_until_ready(selection.idx)
        return selection, sp.seconds

    def _construct(self, s, k: int, selection: RoundSelection, labels):
        """(result, seconds): round k's model update on `labels`, ended by
        block_until_ready, in its `repro.chef.update` span."""
        with span("repro.chef.update", k=k) as sp:
            result = self.constructor.construct(s, selection.idx, labels)
            jax.block_until_ready(result.w)
        return result, sp.seconds

    def _commit(self, o: _RoundOutcome) -> RoundRecord:
        """Apply one computed round: the only state-mutation point. Runs
        exactly once per round (outside the retry wrapper); a failure here
        propagates instead of silently re-running the round."""
        with span("repro.chef.commit"):
            s = self.session
            self._prefetch = o.prefetch
            if o.spec == "hit":
                self.spec_hits += 1
            elif o.spec == "miss":
                self.spec_misses += 1
            selection, result = o.selection, o.result
            match = (
                float(jnp.mean((selection.suggested[selection.idx]
                                == s.ds.y_true[selection.idx]).astype(jnp.float32)))
                if selection.suggested is not None else float("nan")
            )
            f1v, f1t = _evaluate(result.w, result.ds)
            record = RoundRecord(o.round, int(jnp.sum(result.ds.cleaned)), f1v,
                                 f1t, selection.n_candidates, o.t_select,
                                 o.t_update, match)
            s.apply_round(result.ds, result.w, result.traj, result.sched, record)
            if any(p.should_stop(s.history) for p in self.termination):
                s.terminated = True
            if self.verbose:
                print(
                    f"round {o.round}: cleaned={record.n_cleaned_total} "
                    f"f1_val={f1v:.4f} f1_test={f1t:.4f} "
                    f"cand={record.n_candidates} "
                    f"sel={o.t_select:.3f}s upd={o.t_update:.3f}s"
                )
            if self.heartbeat is not None:
                self.heartbeat.beat(s.round)
            if self.ckpt is not None and self.ckpt_every \
                    and s.round % self.ckpt_every == 0:
                s.save(self.ckpt)
            return record

    def _speculate(self, k: int, selection: RoundSelection, pred) -> _Speculation:
        """Run constructor + next-round selection on the predicted labels
        while the annotators are still voting. Pure w.r.t. the session."""
        s = self.session
        with span("repro.chef.speculate", k=k):
            result, t_update = self._construct(s, k, selection, pred)

            prefetch = None
            # prefetch round k+1's scoring unless the budget already ends the run
            if s.ledger.remaining >= 2 * s.cfg.round_size:
                child = s.child(result.ds, result.w, result.traj, result.sched)
                k_sel_next, _ = s.round_keys(k + 1)
                prefetch = _Prefetch(k + 1, *self._select(
                    child, k + 1, child.eligible(), k_sel_next))
        return _Speculation(pred, result, t_update, prefetch)


def make_scheduler(
    session: CleaningSession,
    *,
    method: str = "infl",
    selector: str = "increm",
    constructor: str = "deltagrad",
    pipelined: bool = False,
    **kw,
) -> RoundScheduler:
    """`run_chef`-vocabulary convenience constructor."""
    cfg = session.cfg
    return RoundScheduler(
        session,
        make_selector(method, selector),
        SimulatedAnnotator(cfg.strategy, cfg.annotator_latency_s),
        make_constructor(constructor),
        termination=make_termination(cfg),
        pipelined=pipelined,
        **kw,
    )
