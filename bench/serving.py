"""Shared parts of the serving cells: the driver base class, the model and
engine from a configuration file, request sizes and arrivals drawn from a traffic file,
host-clock token stamps, and the check against the plain reference.

Sizes and gaps are stratified draws: n values at the quantiles
(i + 0.5) / n of the mix's distribution, put in an order drawn from the
seed. Every seed so serves the same set of lengths and gaps in another
order, and the work of a run does not depend on its seed.
"""
from __future__ import annotations

import math
import time
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np

from bench import ref_olmo, weights


class StampList(list):
    """A request's output list that stamps each token with the host clock
    when the engine appends it."""

    def __init__(self, *a):
        super().__init__(*a)
        self.t = []

    def append(self, x):
        super().append(x)
        self.t.append(time.perf_counter())

    def extend(self, xs):
        xs = list(xs)
        super().extend(xs)
        now = time.perf_counter()
        self.t.extend([now] * len(xs))


def lognormal_set(spec: dict, n: int) -> np.ndarray:
    """n integer lengths at the stratified quantiles of a lognormal with the
    given median and sigma, clipped to [min, max]."""
    nd = NormalDist()
    q = [(i + 0.5) / n for i in range(n)]
    v = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(p)) for p in q]
    return np.clip(np.round(v), spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """n inter-arrival gaps (s) at the stratified quantiles of an exponential
    distribution of the given rate: a Poisson process's gaps."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def check_model_config(cfg: dict, mcfg) -> None:
    """The repo's model configuration has the sizes the file states."""
    want = {"d_model": cfg["hidden_size"], "d_ff": cfg["intermediate_size"],
            "n_layers": cfg["num_hidden_layers"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "resolved_head_dim": cfg["head_dim"],
            "vocab_size": cfg["vocab_size"], "rope_theta": cfg["rope_theta"],
            "tie_embeddings": cfg["tie_word_embeddings"]}
    got = {k: getattr(mcfg, k) for k in want}
    if got != want:
        raise ValueError(f"repo config {cfg['repo_config']} differs: {got} != {want}")
    if mcfg.norm_kind != "nonparam_ln" or mcfg.mlp_kind != cfg["hidden_act"]:
        raise ValueError("repo config differs in norm or MLP kind")


def build(cfg: dict, seed: int, slots: int, max_len: int, trace_logits: bool):
    """(model, params, engine) for a decoder configuration file; the engine
    sizes its page pool for every slot's worst case."""
    from repro.configs import get_config
    from repro.core.backend import get_backend
    from repro.models import Model
    from repro.serving.engine import ServeConfig, ServeEngine

    mcfg = get_config(cfg["repo_config"])
    check_model_config(cfg, mcfg)
    model = Model(mcfg, param_dtype=jnp.dtype(cfg["dtype"]).type)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    params = weights.make(shapes, seed)
    jax.block_until_ready(params)
    s = cfg["serve"]
    eng = ServeEngine(model, params, backend=get_backend(s["backend"]),
                      config=ServeConfig(
                          batch_size=slots, max_len=max_len, cache=s["cache"],
                          page_size=s["page_size"],
                          share_prefix=s["share_prefix"],
                          trace_logits=trace_logits))
    return model, params, eng


def make_request(uid, prompt, max_new):
    from repro.serving.engine import Request

    return Request(uid, np.asarray(prompt, np.int32), int(max_new),
                   out=StampList())


def p95(values) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 95))


# ------------------------------------------------------------------ check
# token_gap: the widest gap, in logits, by which a served token's logit lies
# below the reference's best at the position that served it.
def reference_gaps(params, cfg: dict, reqs: list, pad_to: int,
                   dtype: str = "float32") -> np.ndarray:
    """Per served token of `reqs`: the reference's best logit minus that of
    the token served (dtype 'float32'), or minus that of the token the
    control puts first (any other dtype). One padded length, one compile."""
    view = weights.reference_view(params)
    theta = float(cfg["rope_theta"])
    out = []
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            seq = np.concatenate([np.asarray(r.prompt), np.asarray(r.out[:-1])])
            L, n = len(r.prompt), len(r.out)
            toks = np.zeros(pad_to, np.int32)
            toks[:len(seq)] = seq
            ref = ref_olmo.forward(view, jnp.asarray(toks), theta=theta)
            if dtype == "float32":
                g = ref_olmo.served_gaps(ref, jnp.asarray(r.out, jnp.int32), L - 1)
            else:
                ctl = ref_olmo.forward(view, jnp.asarray(toks), theta=theta,
                                       dtype=dtype)
                g = ref_olmo.control_gaps(ref, ctl, L - 1, n)
            out.append(np.asarray(g))
    return np.concatenate(out)


def sample_for_check(done: list, seed: int, min_tokens: int, max_reqs: int):
    """The longest finished request and others drawn from the seed, until
    `min_tokens` served tokens or `max_reqs` requests."""
    done = sorted(done, key=lambda r: r.uid)
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.out), r.uid))
    rng = np.random.default_rng(seed % 2 ** 63)
    picked, tokens = [longest], len(longest.out)
    for i in rng.permutation(len(done)):
        if tokens >= min_tokens or len(picked) >= max_reqs:
            break
        r = done[int(i)]
        if r is not longest:
            picked.append(r)
            tokens += len(r.out)
    return picked


def planned_widths(lengths, bucket_min: int = 8) -> list:
    """One prompt length per prefill bucket that the planned lengths fall
    into: the widths the window makes the engine trace, and no others."""
    from repro.serving.engine import bucket_len

    by_bucket = {bucket_len(int(L), bucket_min): int(L) for L in lengths}
    return sorted(by_bucket.values())


class ServeDriver:
    """Set-up, per-call bookkeeping, release and check of a serving cell;
    each generator's Driver adds `plan`, `warm_lengths`, `window` and
    `end_to_end`."""

    trace_logits = True

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.stats = {}
        self.calls = 0

    def setup(self):
        t = self.traffic
        self.model, self.params, self.eng = build(
            self.cfg, self.seed, t["slots"], t["max_len"], self.trace_logits)
        self.plan(self.seconds)
        V = self.cfg["vocab_size"]
        warm = [make_request(-1 - i, np.arange(L) % V, 2)
                for i, L in enumerate(self.warm_lengths())]
        self.eng.run(warm)
        self.stats = {}
        self.calls = 0

    def serve(self, batch: list, late: bool = False):
        with jax.profiler.TraceAnnotation("bench.run_call"):
            self.eng.run(batch)
        if not late:
            self.calls += 1
            for k, v in self.eng.stats.items():
                self.stats[k] = self.stats.get(k, 0) + v

    def attempted_failed(self):
        return len(self.done), sum(len(r.out) != r.max_new for r in self.done)

    def release(self):
        del self.eng

    def check(self) -> list:
        t = self.traffic["check"]
        picked = sample_for_check(self.done, self.seed,
                                          t["min_tokens"], t["max_requests"])
        gaps = reference_gaps(self.params, self.cfg, picked,
                                      t["pad_to"])
        self.checked_tokens = int(gaps.size)
        return [("token_gap", float(np.max(gaps)), t["token_gap_limit"])]
