#!/usr/bin/env python3
"""Chip smoke test: the CHEF cleaning loop and the paged serving engine, with
compiled Pallas kernels, on the TPU that JAX finds.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the pallas_sharded paths on four chips

One chip runs two phases in this one process, through the entry points a
user calls:

  clean  A CHEF cleaning session at MIMIC's Table-3 size (N = 78,487,
         val 579, test 1,628, d = 2,048, C = 2; Table 4's lr and l2, SGD
         batch 2,000) with the Section 5.1 settings (budget 100 in rounds of
         10, three annotators at 5% error, strategy "three"), built by
         `prepare_session` + `make_scheduler` on the `pallas` backend with
         the Increm-INFL selector and the DeltaGrad-L constructor. The
         training epochs are cut from Table 4's 150 to `CLEAN_EPOCHS`.
  serve  `ServeEngine` serving olmo-1b at its published widths (16 layers,
         d_model 2,048, vocab 50,304; seeded bf16 weights) on the paged
         cache and the `pallas` backend: six requests of 300-480 prompt
         tokens and 16-32 new tokens through four slots, so two of them
         join mid-stream.

Each phase checks its own result: every kernel-backed op on the round-1
inputs against the `reference` backend (f32, highest matmul precision),
the loop's invariants, prefill and first-decode logits against the
reference engine, greedy tokens wherever the reference's top-1 margin
exceeds the logit tolerance, and `tpu_custom_call` in each compiled step
(the kernels went through the TPU compiler, not an interpreter). Any
failed check raises, and the script exits non-zero.

`--chips 4` runs only the sharded paths and what they are compared with:
the cleaning session on `pallas_sharded` over a 4-device data mesh (rows
sharded) against one-chip `pallas` ops on the same inputs, and the engine on
`pallas_sharded` with a 4-way `model` axis (head-sharded page pools)
against the one-chip `pallas` engine.

Earlier stdout lines are one JSON object per phase: first-call wall time
(compilation included) and steady wall time, both from the host clock —
smoke timings, not benchmark numbers — and each comparison's worst error
against its tolerance. The last line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
Without a TPU the script prints no result and exits with code 2.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from dataclasses import dataclass
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CLEAN_EPOCHS = 10  # Table 4 trains MIMIC for 150; the smoke cuts depth only

# Tolerances, each as a fraction of the reference output's largest |value|.
# kernel vs the f32 reference: both sides contract at full f32 precision
# (HIGHEST), so only the order of the f32 sums over 78,487 rows differs
# (measured on v5e: up to 1.1e-5, in lr_hvp); bf16-pass contractions,
# Mosaic's default, measured 3.2e-4 to 3.3e-3, and a wrong row, class lane
# or block is O(1). The limit sits between the two.
TOL_CLEAN = 5e-5
# sharded vs one-chip pallas: the same kernels at the same precision; only
# the order of the psum over per-shard partial sums differs (f32
# reassociation, ~1e-6 of the scale).
TOL_SHARDED = 1e-4
# serving logits: a bf16 model; kernel and mirror (or sharded and one-chip)
# attention differ in f32 accumulation order and dot precision, which flips
# bf16 roundings (2^-8 relative) that then compound over 16 layers.
TOL_LOGITS = 5e-2


@dataclass(frozen=True)
class Sizes:
    """Everything the phases size themselves by (full size by default)."""

    n_train: int = 78_487
    n_val: int = 579
    n_test: int = 1_628
    feature_dim: int = 2_048
    lr: float = 0.0005
    l2: float = 0.05
    batch_size: int = 2_000
    epochs: int = CLEAN_EPOCHS
    budget: int = 100
    round_size: int = 10
    arch: str = "olmo-1b"
    reduce_arch: bool = False
    serve_dtype: str = "bfloat16"
    slots: int = 4
    prompt_lens: tuple = (300, 412, 480, 356, 448, 320)
    new_tokens: tuple = (16, 32, 24, 16, 32, 24)
    seed: int = 0


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


def jax_ready(*trees) -> None:
    import jax

    jax.block_until_ready(trees)


def timed(fn, *args, **kw):
    """(result, wall seconds) with the result's device work finished."""
    import jax

    t0 = time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def rel_err(got, want) -> float:
    """max |got - want| / max |want| over all elements, in f64 on host."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    assert got.shape == want.shape, (got.shape, want.shape)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def check(name: str, err: float, tol: float, **extra) -> None:
    emit(check=name, err=err, tol=tol, **extra)
    if not err <= tol:
        raise AssertionError(f"{name}: error {err:.3e} exceeds tolerance {tol:.1e}")


def assert_compiled(name: str, jitted, *args, **kw) -> None:
    """The compiled step holds a Mosaic kernel call (`tpu_custom_call`)."""
    hlo = jitted.lower(*args, **kw).compile().as_text()
    n = hlo.count("tpu_custom_call")
    emit(compiled=name, tpu_custom_calls=n)
    if n == 0:
        raise AssertionError(f"{name}: no tpu_custom_call in the compiled step")


# ----------------------------------------------------------------- cleaning
def clean_config(sz: Sizes, backend: str):
    from repro.configs.chef_lr import ChefConfig

    return ChefConfig(
        n_classes=2, feature_dim=sz.feature_dim, lr=sz.lr, l2=sz.l2,
        batch_size=sz.batch_size, n_epochs=sz.epochs, budget=sz.budget,
        round_size=sz.round_size, n_annotators=3, annotator_error=0.05,
        strategy="three", backend=backend, seed=sz.seed)


def make_mimic(sz: Sizes, cfg):
    import jax

    from repro.data import make_dataset

    return make_dataset(
        jax.random.key(sz.seed), name="mimic", n_train=sz.n_train,
        n_val=sz.n_val, n_test=sz.n_test, feature_dim=sz.feature_dim,
        n_classes=cfg.n_classes, gamma=cfg.gamma,
        n_annotators=cfg.n_annotators, annotator_error=cfg.annotator_error)


def round1_inputs(session):
    """The operands every kernel-backed op sees in round 1, at full size."""
    import jax
    import jax.numpy as jnp

    from repro.core.backend import get_backend
    from repro.core.influence import influence_vector

    ds, cfg = session.ds, session.cfg
    with jax.default_matmul_precision("highest"):
        v, _ = influence_vector(session.w, session.Xa_val, ds.y_val,
                                session.Xa, ds.y_weight, cfg.l2,
                                cg_iters=cfg.cg_iters, cg_tol=cfg.cg_tol,
                                backend=get_backend("reference"))
    r = 16  # changed slots of one replay iteration: b = 10 real, 6 padding
    ci = session.sched[0][:r].astype(jnp.int32)
    cm = (jnp.arange(r) < cfg.round_size).astype(jnp.float32)
    y_new = jax.nn.one_hot(ds.y_true, cfg.n_classes, dtype=jnp.float32)
    return dict(w=session.w, v=v, Xa=session.Xa, Y=ds.y_prob, wt=ds.y_weight,
                idx=session.sched[0], ci=ci, cm=cm, y_new=y_new,
                ones=jnp.ones_like(ds.y_weight), cfg=cfg)


def clean_ops(bk, a):
    """Every kernel-backed op of the cleaning loop on one backend."""
    cfg = a["cfg"]
    Xr = bk.row_gather_layout(a["Xa"])
    return {
        "scores": bk.probs_scores(a["w"], a["v"], a["Xa"], a["Y"], cfg.gamma),
        "lr_grad": bk.lr_grad(a["w"], a["Xa"], a["Y"], a["wt"], cfg.l2),
        "lr_hvp": bk.lr_hvp(a["w"], a["v"], a["Xa"], a["wt"], cfg.l2),
        "minibatch_grad": bk.minibatch_grad(a["w"], Xr, a["Y"], a["wt"],
                                            a["idx"], cfg.l2),
        "replay_correction": bk.replay_correction(
            a["w"], Xr, a["Y"], a["y_new"], a["wt"], a["ones"], a["ci"],
            a["cm"], cfg.batch_size),
    }


def compare_clean_ops(session, want_bk, tol: float, label: str) -> None:
    """Each op of `session.backend` against `want_bk` on round-1 inputs."""
    import jax

    a = round1_inputs(session)
    got = clean_ops(session.backend, a)
    # the one-chip side gets its operands on one device: a Mosaic kernel
    # outside shard_map cannot take mesh-sharded arrays
    one = {k: x if k == "cfg" else jax.device_put(x, jax.devices()[0])
           for k, x in a.items()}
    if want_bk.name == "reference":
        with jax.default_matmul_precision("highest"):
            want = clean_ops(want_bk, one)
    else:
        want = clean_ops(want_bk, one)
    errs = {op: rel_err(got[op], want[op]) for op in got}
    for op, err in errs.items():  # every reading printed before any raise
        emit(check=f"{label}.{op}", err=err, tol=tol)
    over = [f"{op} {err:.3e}" for op, err in errs.items() if not err <= tol]
    if over:
        raise AssertionError(f"{label}: over tolerance {tol:.1e}: {over}")


def assert_clean_compiled(session) -> None:
    """The jitted SGD scan and each op wrapper hold Mosaic kernels."""
    from repro.core import lr_head
    from repro.kernels import ops

    s, cfg = session, session.cfg
    Y, wt = s.ds.y_prob, s.ds.y_weight
    assert not ops._interpret(), "kernels would run in interpret mode"
    assert_compiled("sgd_train", lr_head.sgd_train, s.w, s.Xa, Y, wt, s.sched,
                    l2=cfg.l2, lr=cfg.lr, backend=s.backend)
    assert_compiled("infl_scores", ops.infl_scores, s.w, s.Xa, Y, Y,
                    gamma=cfg.gamma)
    assert_compiled("lr_grad", ops.lr_grad, s.w, s.Xa, Y, wt, l2=cfg.l2)
    assert_compiled("lr_hvp", ops.lr_hvp, s.w, s.w, s.Xa, wt, l2=cfg.l2)
    Xr = ops.row_gather_layout(s.Xa)
    assert_compiled("minibatch_grad", ops.minibatch_grad, s.w, Xr, Y, wt,
                    s.sched[0], l2=cfg.l2)
    assert_compiled("replay_correction", ops.replay_correction, s.w, Xr, Y,
                    Y, wt, wt, s.sched[0][:16], wt[:16],
                    batch_size=cfg.batch_size)


def run_cleaning(sz: Sizes, backend, label: str):
    """Generate the dataset and prepare a session (twice: compile, then
    steady); return the session."""
    from repro.cleaning.service import prepare_session

    cfg = clean_config(sz, backend.name)
    ds, t_data = timed(make_mimic, sz, cfg)

    def prepare():
        return prepare_session(ds, cfg, backend=backend, selector="increm",
                               constructor="deltagrad")

    walls = []
    for _ in range(2):
        t0 = time.perf_counter()
        session = prepare()
        jax_ready(session.w, session.traj, session.prov)
        walls.append(time.perf_counter() - t0)
    emit(phase=f"{label}.init", first_s=walls[0], steady_s=walls[1],
         data_s=t_data, n=sz.n_train, d=sz.feature_dim, epochs=sz.epochs)
    return session


def run_rounds(session, label: str) -> None:
    import jax.numpy as jnp

    from repro.cleaning.scheduler import make_scheduler

    cfg = session.cfg
    sched = make_scheduler(session, method="infl", selector="increm",
                           constructor="deltagrad")
    walls = []
    while not sched.exhausted:
        t0 = time.perf_counter()
        rec = sched.step()
        walls.append(time.perf_counter() - t0)
    steady = walls[1:] or walls
    emit(phase=f"{label}.rounds", rounds=len(walls), first_s=walls[0],
         steady_s=sum(steady) / len(steady), f1_val=rec.f1_val,
         f1_test=rec.f1_test)
    spent, cleaned = session.ledger.spent, int(jnp.sum(session.ds.cleaned))
    if session.ledger.remaining != 0 or spent != cfg.budget:
        raise AssertionError(f"{label}: budget not spent ({spent}/{cfg.budget})")
    if not (cleaned == rec.n_cleaned_total == len(walls) * cfg.round_size
            == spent):
        raise AssertionError(
            f"{label}: cleaned {cleaned} != selected "
            f"{len(walls)} x {cfg.round_size} (ledger {spent})")
    if not math.isfinite(rec.f1_val):
        raise AssertionError(f"{label}: val F1 {rec.f1_val} is not finite")


# ------------------------------------------------------------------ serving
def serve_model(sz: Sizes):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced
    from repro.models import Model

    cfg = get_config(sz.arch)
    if sz.reduce_arch:
        cfg = reduced(cfg)
    model = Model(cfg, param_dtype=jnp.dtype(sz.serve_dtype).type)
    params = model.init(jax.random.key(sz.seed))
    return model, params


def requests(sz: Sizes, vocab: int, max_new=None):
    import numpy as np

    from repro.serving.engine import Request

    rng = np.random.default_rng(sz.seed)
    prompts = [rng.integers(0, vocab, n).astype(np.int32)
               for n in sz.prompt_lens]
    return [Request(i, p, max_new or m)
            for i, (p, m) in enumerate(zip(prompts, sz.new_tokens))]


def make_engine(sz: Sizes, model, params, backend):
    from repro.kernels.paged_attention import page_tile_rows
    from repro.serving.engine import ServeConfig, ServeEngine

    max_len = max(p + n for p, n in zip(sz.prompt_lens, sz.new_tokens))
    pool_dtype = model.kv_dtype or model.param_dtype
    return ServeEngine(model, params, backend=backend, config=ServeConfig(
        batch_size=sz.slots, max_len=max_len, cache="paged",
        page_size=page_tile_rows(pool_dtype), share_prefix=False,
        trace_logits=True))


def serve_waves(sz: Sizes, model, params, backend, label: str):
    """Two identical waves (compile, then steady); returns the engine and
    the second wave's requests by uid."""
    eng = make_engine(sz, model, params, backend)
    V = model.cfg.vocab_size
    t0 = time.perf_counter()
    eng.run(requests(sz, V))
    first = time.perf_counter() - t0
    wave = requests(sz, V)
    t0 = time.perf_counter()
    done = eng.run(wave)
    steady = time.perf_counter() - t0
    n_tok = sum(len(r.out) for r in done)
    emit(phase=f"{label}.serve", first_s=first, steady_s=steady,
         tokens=n_tok, requests=len(done), joins=eng.stats["joins"],
         decode_rounds=eng.stats["decode_rounds"])
    if len(done) != len(sz.prompt_lens) or any(
            len(r.out) != r.max_new for r in done):
        raise AssertionError(f"{label}: requests did not finish their budget")
    if eng.stats["joins"] < 1:
        raise AssertionError(f"{label}: no request joined mid-stream")
    return eng, {r.uid: r for r in done}


def compare_logits(got: dict, want: dict, tol: float, label: str) -> None:
    """Prefill (row 0) and first-decode (row 1) logits per request, and the
    greedy tokens wherever the reference's top-1 margin exceeds the
    tolerance (the first decode row only when both runs fed it the same
    first token)."""
    import numpy as np

    scale = max(float(np.max(np.abs(np.asarray(r.logits[:2], np.float64))))
                for r in want.values())
    tol_abs = tol * scale
    errs, decided, skipped = [], 0, 0
    for uid, w in want.items():
        g = got[uid]
        for step in (0, 1):
            if step == 1 and g.out[0] != w.out[0]:
                skipped += 1
                continue
            lw = np.asarray(w.logits[step], np.float64)
            lg = np.asarray(g.logits[step], np.float64)
            errs.append(float(np.max(np.abs(lg - lw))))
            top2 = np.sort(lw)[-2:]
            if top2[1] - top2[0] > tol_abs:
                decided += 1
                if int(np.argmax(lg)) != int(np.argmax(lw)):
                    raise AssertionError(
                        f"{label}: request {uid} step {step} greedy token "
                        f"{int(np.argmax(lg))} != reference "
                        f"{int(np.argmax(lw))} at margin {top2[1] - top2[0]}")
    check(f"{label}.logits", max(errs) / scale, tol, logit_scale=scale,
          greedy_decided=decided, decode_rows_skipped=skipped)


def assert_serve_compiled(eng, sz: Sizes) -> None:
    """The engine's own jitted prefill and decode steps hold Mosaic kernels."""
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import bucket_len

    assert_compiled("decode_step", eng._decode, eng.params,
                    jax.eval_shape(lambda: eng.model.init_paged_cache(
                        eng.B, eng.num_pages, eng.config.page_size,
                        eng.table_pages)),
                    {"tokens": jax.ShapeDtypeStruct((eng.B, 1), jnp.int32)})
    width = bucket_len(max(sz.prompt_lens), eng.config.bucket_min)
    assert_compiled("prefill", eng._get_paged_prefill(width), eng.params,
                    jax.ShapeDtypeStruct((1, width), jnp.int32),
                    jax.ShapeDtypeStruct((1,), jnp.int32))


# -------------------------------------------------------------------- runs
def run_one_chip(sz: Sizes) -> None:
    from repro.core.backend import get_backend

    pallas, ref = get_backend("pallas"), get_backend("reference")
    session = run_cleaning(sz, pallas, "clean")
    compare_clean_ops(session, ref, TOL_CLEAN, "clean.vs_reference")
    assert_clean_compiled(session)
    run_rounds(session, "clean")
    del session

    model, params = serve_model(sz)
    eng, got = serve_waves(sz, model, params, pallas, "pallas")
    assert_serve_compiled(eng, sz)
    del eng
    ref_eng = make_engine(sz, model, params, ref)
    want = {r.uid: r for r in ref_eng.run(
        requests(sz, model.cfg.vocab_size, max_new=2))}
    compare_logits(got, want, TOL_LOGITS, "serve.vs_reference")


def run_four_chips(sz: Sizes) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.backend import get_backend
    from repro.launch.mesh import make_mesh_for

    pallas = get_backend("pallas")
    data_mesh = make_mesh_for(4, model_parallel=1)
    session = run_cleaning(sz, get_backend("pallas_sharded", mesh=data_mesh),
                           "clean4")
    compare_clean_ops(session, pallas, TOL_SHARDED, "clean4.vs_pallas")
    run_rounds(session, "clean4")
    del session

    model, params = serve_model(sz)
    heads_mesh = make_mesh_for(4, model_parallel=4)
    replicated = jax.device_put(params, NamedSharding(heads_mesh, P()))
    _, got = serve_waves(sz, model, replicated,
                         get_backend("pallas_sharded", mesh=heads_mesh),
                         "pallas_sharded")
    del replicated
    one = make_engine(sz, model, params, pallas)
    want = {r.uid: r for r in one.run(
        requests(sz, model.cfg.vocab_size, max_new=2))}
    compare_logits(got, want, TOL_LOGITS, "serve4.vs_pallas")


def device_or_exit(chips: int):
    """The TPU devices JAX sees; exit 2 (printing no result) without."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chip_smoke: needs {chips} TPU device(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        sys.exit(2)
    return devs


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)
    devs = device_or_exit(args.chips)

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.perf_counter()
    (run_four_chips if args.chips == 4 else run_one_chip)(Sizes())
    emit(phase="total", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
