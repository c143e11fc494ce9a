"""Cleaning rounds, blocking, back to back: the CHEF loop as its users run it.

Set-up makes the dataset on the device (the configuration's rows, in an
order drawn from the seed, so every seed does the same work), prepares the
session (`prepare_session`: SGD with the trajectory cache, Increm-INFL
provenance) and runs one whole session of B / b rounds, so that every
shape the seed's rounds draw is compiled before the window.

The window runs `RoundScheduler.step` (from `make_scheduler`, blocking,
simulated annotators at the mix's latency) round after round; when a
session has spent its budget the next one starts from the prepared
session's snapshot. A round that starts before the window's end runs to
its end, and the window closes when it does.

The check runs the plain reference (bench/ref_chef.py) over as many rounds
as the window reached and compares every round the window ran.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen_chef, ref_chef

# The numbers compared; their limits are the traffic file's "check":
# score_err: |priority - reference| over the Increm-INFL candidates, as a
#   share of the reference's largest |priority|.
# select_gap: how far above the reference's b-th smallest priority the
#   worst id the program cleaned lies, as a share of the same scale.
# label_mismatch: voted labels that differ from the reference's votes.
# weights_err: |w - reference| after the round's replay, as a share of the
#   reference's largest |w|.
NUMBERS = ("score_err", "select_gap", "label_mismatch", "weights_err")


class _Recorder:
    """Wraps the scheduler's selector and keeps each round's selection."""

    def __init__(self, inner, sink: list):
        self.inner, self.sink = inner, sink

    def select(self, session, eligible, key):
        with jax.profiler.TraceAnnotation("bench.select"):
            sel = self.inner.select(session, eligible, key)
        self.sink.append((session.round, eligible, sel))
        return sel


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.prog_seed = seed % (2 ** 31 - 1)
        self.rounds = []  # per window round: dict

    # ----------------------------------------------------------- set-up
    def chef_config(self):
        from repro.configs.chef_lr import ChefConfig

        c = self.cfg
        return ChefConfig(
            n_classes=c["n_classes"], feature_dim=c["feature_dim"], lr=c["lr"],
            l2=c["l2"], batch_size=c["batch_size"], n_epochs=c["n_epochs"],
            budget=c["budget"], round_size=c["round_size"],
            n_annotators=c["n_annotators"],
            annotator_error=c["annotator_error"], strategy=c["strategy"],
            gamma=c["gamma"], backend=c["backend"], seed=self.prog_seed,
            annotator_latency_s=self.traffic.get("annotator_latency_s", 0.0))

    def make_dataset(self):
        from repro.data.synth import ChefDataset

        a = gen_chef.make(jax.random.key(self.cfg["generator"]["data_seed"]),
                          gen_chef.spec_of(self.cfg))
        a = gen_chef.reorder(a, jax.random.key(self.seed % 2 ** 32))
        self.data = a
        return ChefDataset(
            name=self.cfg["name"], X=a["X"], y_prob=a["y_prob"],
            y_weight=a["y_weight"], cleaned=jnp.zeros(a["X"].shape[0], bool),
            y_true=a["y_true"], human_labels=a["human"], X_val=a["X_val"],
            y_val=a["y_val"], X_test=a["X_test"], y_test=a["y_test"],
            n_classes=self.cfg["n_classes"])

    def setup(self):
        from repro.cleaning.service import prepare_session
        from repro.core.backend import get_backend

        ccfg = self.chef_config()
        ds = self.make_dataset()
        self.base = prepare_session(
            ds, ccfg, backend=get_backend(ccfg.backend),
            selector=self.cfg["selector"], constructor=self.cfg["constructor"])
        jax.block_until_ready((self.base.w, self.base.traj, self.base.prov))
        warm = self.new_scheduler([])
        while not warm.exhausted:
            warm.step()

    def new_scheduler(self, sink: list):
        from repro.cleaning.scheduler import make_scheduler
        from repro.cleaning.session import BudgetLedger

        s = dataclasses.replace(self.base, ledger=BudgetLedger(self.base.cfg.budget),
                                history=[], round=0, terminated=False)
        sched = make_scheduler(s, method=self.cfg["method"],
                               selector=self.cfg["selector"],
                               constructor=self.cfg["constructor"],
                               pipelined=self.traffic.get("pipelined", False))
        sched.selector = _Recorder(sched.selector, sink)
        return sched

    # ----------------------------------------------------------- window
    def window(self, seconds: float):
        sink: list = []
        sched = self.new_scheduler(sink)
        t0 = time.perf_counter()
        end = t0 + seconds
        span = jax.profiler.TraceAnnotation("bench.window")
        span.__enter__()
        while True:
            if sched.exhausted:
                sched = self.new_scheduler(sink)
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.round"):
                rec = sched.step()
            te = time.perf_counter()
            s = sched.session
            k, eligible, sel = sink[-1]
            self.rounds.append({
                "k": k, "t_start": ts, "t_end": te, "t_select": rec.t_select,
                "t_update": rec.t_update, "n_candidates": rec.n_candidates,
                "eligible": eligible, "idx": sel.idx, "priority": sel.priority,
                "y_prob": s.ds.y_prob, "w": s.w})
            if te >= end:
                break
        span.__exit__(None, None, None)
        self.t0, self.t1 = t0, self.rounds[-1]["t_end"]

    def attempted_failed(self):
        return len(self.rounds), 0

    def notes(self) -> str:
        """Per round of the window: k, select, update and wall seconds."""
        return " ".join(f"{r['k']}:{r['t_select']:.3f}/{r['t_update']:.3f}/"
                        f"{r['t_end'] - r['t_start']:.3f}" for r in self.rounds)

    def end_to_end(self) -> dict:
        n = len(self.rounds)
        return {"round_s": ((self.t1 - self.t0) / n, "s")}

    # ----------------------------------------------------------- check
    def release(self):
        """Drop the program's session state; keep what the check compares."""
        keep = []
        for r in self.rounds:
            r = dict(r, labels=r.pop("y_prob")[r["idx"]])
            keep.append({k: (np.asarray(v) if isinstance(v, jax.Array) else v)
                         for k, v in r.items()})
        self.rounds = keep
        del self.base

    def hyper(self, precision: str = "highest") -> ref_chef.Hyper:
        c = self.chef_config()
        return ref_chef.Hyper(
            lr=c.lr, l2=c.l2, gamma=c.gamma, batch_size=c.batch_size,
            n_epochs=c.n_epochs, round_size=c.round_size, cg_iters=c.cg_iters,
            cg_tol=c.cg_tol, burn_in=c.dg_burn_in, period=c.dg_period,
            history=c.dg_history, seed=self.prog_seed, precision=precision)

    def check(self) -> list:
        """(name, value, limit) for each compared number."""
        by_k: dict = {}
        for r in self.rounds:
            by_k.setdefault(r["k"], []).append(r)
        n_k = max(by_k) + 1
        first = {k: rs[0]["idx"] for k, rs in by_k.items()}
        refs = list(ref_chef.session(self.data, self.hyper(), n_k,
                                     chosen=lambda k: first[k]))
        return compare(by_k, refs, self.traffic["check"])


def compare(by_k: dict, refs: list, limits: dict) -> list:
    """(name, worst over every round run, limit) for each of NUMBERS."""
    worst = dict.fromkeys(NUMBERS, 0.0)
    for k, rs in by_k.items():
        ref = refs[k]
        prio = np.asarray(ref.priority, np.float64)
        for r in rs:
            elig = np.asarray(r["eligible"])
            scale = max(float(np.max(np.abs(prio[elig]))), 1e-30)
            got = np.asarray(r["priority"], np.float64)
            cand = elig & np.isfinite(got)
            err = float(np.max(np.abs(got[cand] - prio[cand]))) / scale
            kth = np.sort(prio[elig])[len(r["idx"]) - 1]
            idx = np.asarray(r["idx"])
            gap = float(np.max(prio[idx] - kth)) / scale
            if not np.all(elig[idx]) or len(set(idx.tolist())) != len(idx):
                gap = float("inf")
            labels = np.argmax(np.asarray(r["labels"]), axis=-1)
            mism = int(np.sum(labels != np.asarray(ref.labels)))
            w_ref = np.asarray(ref.w, np.float64)
            werr = float(np.max(np.abs(np.asarray(r["w"], np.float64) - w_ref))
                         / max(np.max(np.abs(w_ref)), 1e-30))
            for name, v in (("score_err", err), ("select_gap", max(gap, 0.0)),
                            ("label_mismatch", mism), ("weights_err", werr)):
                worst[name] = max(worst[name], v)
    return [(k, worst[k], limits[k]) for k in NUMBERS]
