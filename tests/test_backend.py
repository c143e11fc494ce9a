"""Backend dispatch contract: the three backends are interchangeable.

Op-level parity (grad / HVP / scores, awkward N, chunked sharding) plus one
full `run_chef` round under each backend on a single-device mesh producing
identical selections, suggested labels, and final head weights.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.chef_lr import ChefConfig
from repro.core import run_chef
from repro.core.backend import BACKENDS, Backend, get_backend
from repro.core import lr_head
from repro.data import make_dataset

NONREF = [b for b in BACKENDS if b != "reference"]


@pytest.fixture(scope="module")
def ds():
    # deliberately odd N: exercises row padding in every non-reference path
    return make_dataset(jax.random.key(3), n_train=515, n_val=64, n_test=64,
                        feature_dim=32)


def _op_data(key, N=301, D=51, C=3):
    k = jax.random.split(key, 5)
    Xa = jax.random.normal(k[0], (N, D))
    Y = jax.nn.softmax(jax.random.normal(k[1], (N, C)))
    w = jax.random.normal(k[2], (C, D)) * 0.1
    v = jax.random.normal(k[3], (C, D)) * 0.1
    w8 = jax.random.uniform(k[4], (N,))
    return Xa, Y, w, v, w8


def test_get_backend_resolution():
    assert get_backend(None).name == "reference"
    assert get_backend("pallas").name == "pallas"
    bk = get_backend("pallas_sharded", chunk_rows=64)
    assert bk.mesh is not None and bk.chunk_rows == 64
    assert get_backend(bk) is bk  # pass-through, no re-resolution
    with pytest.raises(ValueError):
        Backend("metal")
    with pytest.raises(ValueError):
        Backend("pallas_sharded")  # mesh required


@pytest.mark.parametrize("spec", NONREF + ["pallas_sharded_chunked",
                                           "pallas_sharded_chunk_boundary"])
def test_op_parity(spec, rng):
    # chunk_boundary: N one past the chunk cap — the regime where naive
    # padding to a full extra chunk would double the scored rows
    chunk = {"pallas_sharded_chunked": 64, "pallas_sharded_chunk_boundary": 300}.get(spec, 0)
    name = "pallas_sharded" if chunk else spec
    bk = get_backend(name, chunk_rows=chunk)
    ref = get_backend("reference")
    Xa, Y, w, v, w8 = _op_data(rng)
    P = lr_head.probs(w, Xa)
    np.testing.assert_allclose(
        np.asarray(bk.lr_grad(w, Xa, Y, w8, 0.05)),
        np.asarray(ref.lr_grad(w, Xa, Y, w8, 0.05)), atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(bk.lr_hvp(w, v, Xa, w8, 0.05)),
        np.asarray(ref.lr_hvp(w, v, Xa, w8, 0.05)), atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(bk.infl_scores(v, Xa, P, Y, 0.8)),
        np.asarray(ref.infl_scores(v, Xa, P, Y, 0.8)), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("spec", NONREF + ["pallas_sharded_chunked"])
def test_probs_scores_fused_parity(spec, rng):
    """Backend.probs_scores (fused probs + Eq. 6, one pad + one shard_map on
    the sharded path) == reference probs() then infl_scores()."""
    chunk = 64 if spec == "pallas_sharded_chunked" else 0
    bk = get_backend("pallas_sharded" if chunk else spec, chunk_rows=chunk)
    ref = get_backend("reference")
    Xa, Y, w, v, _ = _op_data(rng)
    want = ref.infl_scores(v, Xa, lr_head.probs(w, Xa), Y, 0.8)
    np.testing.assert_allclose(np.asarray(bk.probs_scores(w, v, Xa, Y, 0.8)),
                               np.asarray(want), atol=1e-4, rtol=1e-4)


def test_increm_backend_parity(rng):
    """Increm-INFL's Theorem-1 bound evaluation and exact pass dispatch
    through Backend: identical bounds, candidate sets, and selections on
    every backend (ROADMAP open item closed)."""
    from repro.core.increm import build_provenance, increm_infl, theorem1_bounds

    Xa, Y, w, v, _ = _op_data(rng, N=257)
    ks = jax.random.split(rng, 2)
    w_k = w + 0.03 * jax.random.normal(ks[0], w.shape)
    eligible = jnp.ones(Xa.shape[0], bool)
    ref = {}
    for name in BACKENDS:
        bk = get_backend(name)
        prov = build_provenance(w, Xa, power_iters=20, backend=bk)
        bounds = theorem1_bounds(prov, w_k, v, Xa, Y, 0.8, backend=bk)
        pri, sug, info = increm_infl(prov, w_k, v, Xa, Y, 0.8, eligible, 10,
                                     backend=bk)
        top = np.asarray(jax.lax.top_k(-pri, 10)[1])
        if name == "reference":
            ref = dict(lower=np.asarray(bounds.lower), upper=np.asarray(bounds.upper),
                       n_cand=int(info.n_candidates), top=set(top.tolist()),
                       sug=np.asarray(sug)[top])
        else:
            np.testing.assert_allclose(np.asarray(bounds.lower), ref["lower"],
                                       atol=1e-4, rtol=1e-4)
            np.testing.assert_allclose(np.asarray(bounds.upper), ref["upper"],
                                       atol=1e-4, rtol=1e-4)
            assert int(info.n_candidates) == ref["n_cand"], name
            assert set(top.tolist()) == ref["top"], name
            np.testing.assert_array_equal(np.asarray(sug)[top], ref["sug"])


def _constructor_data(key, N=301, D=33, C=3, bs=64):
    Xa, Y, w, v, w8 = _op_data(key, N=N, D=D, C=C)
    ks = jax.random.split(jax.random.fold_in(key, 17), 2)
    idx = jax.random.randint(ks[0], (bs,), 0, N)
    Y_new = jnp.roll(Y, 1, axis=1)
    w_new = jnp.ones((N,))
    return Xa, Y, Y_new, w, w8, w_new, idx


@pytest.mark.parametrize("spec", NONREF)
def test_constructor_op_parity_bitwise(spec, rng):
    """minibatch_grad / replay_correction are BIT-IDENTICAL across backends
    (not just allclose): the fused kernels run the same floating-point
    program as the reference gather + grad, and the sharded psum-gather is
    exact. This is the invariant the scan-level parity below rests on."""
    bk = get_backend(spec)
    ref = get_backend("reference")
    Xa, Y, Y_new, w, w8, w_new, idx = _constructor_data(rng)
    Xr, Xref = bk.row_gather_layout(Xa), ref.row_gather_layout(Xa)
    np.testing.assert_array_equal(
        np.asarray(bk.minibatch_grad(w, Xr, Y, w8, idx, 0.05)),
        np.asarray(ref.minibatch_grad(w, Xref, Y, w8, idx, 0.05)))
    ci, cm = idx[:7], jnp.ones((7,)).at[5:].set(0.0)  # padded slots exercise cm
    np.testing.assert_array_equal(
        np.asarray(bk.replay_correction(w, Xr, Y, Y_new, w8, w_new, ci, cm, 64)),
        np.asarray(ref.replay_correction(w, Xref, Y, Y_new, w8, w_new, ci, cm, 64)))


def test_sgd_train_bit_identical_across_backends(rng):
    """Full SGD scan: final weights AND the cached [T, C, d+1] trajectory are
    bit-identical on all three backends (per-step allclose would not survive
    T steps of drift — the parity contract is exact equality)."""
    Xa, Y, _, w, w8, _, _ = _constructor_data(rng)
    sched = lr_head.batch_schedule(3, Xa.shape[0], 50, 4)
    w0 = jnp.zeros_like(w)
    ref_w, ref_traj = lr_head.sgd_train(w0, Xa, Y, w8, sched, l2=0.05, lr=0.05,
                                        backend=get_backend("reference"))
    for name in NONREF:
        bk = get_backend(name)
        w_fin, traj = lr_head.sgd_train(w0, Xa, Y, w8, sched, l2=0.05, lr=0.05,
                                        backend=bk)
        np.testing.assert_array_equal(np.asarray(w_fin), np.asarray(ref_w),
                                      err_msg=name)
        for a, b in zip(traj, ref_traj):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)


def test_deltagrad_replay_bit_identical_across_backends(rng):
    """deltagrad_replay (w^I_T, new_traj) bit-identical across backends,
    including the L-BFGS approx iterations driven by the replayed cache."""
    from repro.core.deltagrad import DGConfig, build_correction_schedule, \
        deltagrad_replay

    Xa, Y, Y_new, w, w8, w_new, _ = _constructor_data(rng)
    sched = lr_head.batch_schedule(5, Xa.shape[0], 50, 5)
    _, traj = lr_head.sgd_train(jnp.zeros_like(w), Xa, Y, w8, sched,
                                l2=0.05, lr=0.05)
    ci, cm = build_correction_schedule(np.asarray(sched), np.arange(9))
    dgc = DGConfig(burn_in=4, period=4, history=2, lr=0.05, l2=0.05)
    args = (traj[0], traj[1], sched, Xa, Y, Y_new, w8, w_new, ci, cm, dgc,
            int(sched.shape[1]))
    ref_w, ref_traj = deltagrad_replay(*args, backend=get_backend("reference"))
    for name in NONREF:
        w_I, new_traj = deltagrad_replay(*args, backend=get_backend(name))
        np.testing.assert_array_equal(np.asarray(w_I), np.asarray(ref_w),
                                      err_msg=name)
        for a, b in zip(new_traj, ref_traj):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)


def test_sharded_trajectory_layout(rng):
    """On pallas_sharded the [T, C, d+1] caches come back committed onto the
    row-sharded layout (leading axis over the mesh's data axes); trajectory
    sharding helpers are no-ops on the other backends."""
    from repro.dist.sharding import trajectory_spec

    bk = get_backend("pallas_sharded")
    Xa, Y, _, w, w8, _, _ = _constructor_data(rng)
    sched = lr_head.batch_schedule(3, Xa.shape[0], 50, 4)  # T = 24 % dp == 0
    _, traj = lr_head.sgd_train(jnp.zeros_like(w), Xa, Y, w8, sched,
                                l2=0.05, lr=0.05, backend=bk)
    traj = bk.shard_trajectory(traj)
    spec = trajectory_spec(bk.mesh, sched.shape[0])
    assert spec[0] is not None  # genuinely row-sharded leading axis
    for t in traj:
        assert t.sharding.spec == spec, t.sharding
    assert get_backend("reference").shard_trajectory(traj) is traj
    assert get_backend("reference").trajectory_sharding(24) is None


def test_chunked_divisor_walk():
    """_chunked must not degenerate to 1-row chunks on prime-ish row counts:
    the chunk count walks the divisors of n_rows and falls back to balanced
    zero padding when no sane divisor exists."""
    bk = get_backend("pallas_sharded", chunk_rows=64)
    # divisor exists: picked exactly
    assert bk._chunk_count(1008) == 16  # 16 chunks of 63
    assert bk._chunk_count(320) == 5  # 5 chunks of 64
    # prime: old `while n % k: k += 1` walked to k = 997 (1-row chunks);
    # now: balanced 16 chunks of 63 with one zero-padded tail
    assert bk._chunk_count(997) == 16
    x = jax.random.normal(jax.random.key(0), (997, 5))
    got = bk._chunked(lambda t: t * 2.0, (x,), 997)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x * 2.0))
    got = bk._chunked(lambda t: jnp.sum(t, axis=0), (x,), 997, reduce=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.sum(x, axis=0)),
                               rtol=1e-6, atol=1e-6)


def test_run_chef_backend_parity(ds):
    """One full round (select -> annotate -> retrain) per backend: identical
    cleaned sets, suggested labels, and final weights within tolerance."""
    results = {}
    for bk in BACKENDS:
        cfg = ChefConfig(budget=10, round_size=10, n_epochs=8, batch_size=128,
                         lr=0.05, l2=0.05, backend=bk)
        results[bk] = run_chef(ds, cfg, method="infl", selector="full",
                               constructor="retrain")
    ref = results["reference"]
    for bk in NONREF:
        r = results[bk]
        assert np.array_equal(np.asarray(r.dataset.cleaned),
                              np.asarray(ref.dataset.cleaned)), bk
        np.testing.assert_array_equal(np.asarray(jnp.argmax(r.dataset.y_prob, -1)),
                                      np.asarray(jnp.argmax(ref.dataset.y_prob, -1)))
        np.testing.assert_allclose(np.asarray(r.w), np.asarray(ref.w),
                                   atol=1e-4, rtol=1e-3)
        assert abs(r.f1_test_final - ref.f1_test_final) < 1e-3, bk


def test_run_chef_backend_override_beats_config(ds, monkeypatch):
    """The backend= argument overrides ChefConfig.backend (explicit wins)."""
    import repro.core.pipeline as pipeline_mod

    resolved = []
    real = pipeline_mod.get_backend

    def spy(spec, **kw):
        bk = real(spec, **kw)
        resolved.append(bk.name)
        return bk

    monkeypatch.setattr(pipeline_mod, "get_backend", spy)
    cfg = ChefConfig(budget=10, round_size=10, n_epochs=5, batch_size=128,
                     lr=0.05, l2=0.05, backend="reference")
    r = run_chef(ds, cfg, method="infl", selector="full", constructor="retrain",
                 backend="pallas")
    # run_chef resolves once; train_head re-resolves the already-resolved
    # Backend object it is handed (a pass-through). cfg's "reference" must
    # never appear anywhere in the chain.
    assert resolved and all(name == "pallas" for name in resolved)
    assert np.isfinite(r.f1_test_final)
