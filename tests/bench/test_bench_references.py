"""The plain references against the program at small sizes on the CPU,
and the controls against the references.

Tolerances, with their reasons:
- CHEF: the program and the reference both compute in f32; on the CPU the
  kernels run interpreted, so only the order of f32 sums differs (a few
  ulps, amplified by the CG solve to ~1e-6 of the score scale). 1e-4 of
  scale leaves two orders of room and fails any wrong row or class lane.
- OLMo: an f32 program against the f32 reference differs in summation
  order only: 1e-4 of the logit scale. The bf16 program rounds every
  activation to 8 bits of mantissa; its greedy tokens lie within 0.1
  logits of the reference's best at this size.
"""
import numpy as np
import pytest

import bench_tiny as bt


def test_chef_reference_training_matches_program():
    import jax
    import jax.numpy as jnp

    from bench import gen_chef, ref_chef
    from repro.core import lr_head

    cfg = bt.clean_cfg()
    data = gen_chef.make(jax.random.key(3), gen_chef.spec_of(cfg))
    hp = ref_chef.Hyper(lr=cfg["lr"] * 100, l2=cfg["l2"], gamma=cfg["gamma"],
                        batch_size=cfg["batch_size"], n_epochs=cfg["n_epochs"],
                        round_size=10, cg_iters=64, cg_tol=1e-6, burn_in=10,
                        period=10, history=2, seed=5)
    Xa = ref_chef.augment(data["X"])
    w, traj, sched = ref_chef.train(Xa, data["y_prob"], data["y_weight"], hp)
    sched_p = lr_head.batch_schedule(5, Xa.shape[0], cfg["batch_size"],
                                     cfg["n_epochs"])
    assert bool(jnp.all(sched == sched_p))
    w_p, traj_p = lr_head.sgd_train(
        jnp.zeros_like(w), Xa, data["y_prob"], data["y_weight"], sched_p,
        l2=hp.l2, lr=hp.lr, backend="pallas")
    scale = float(jnp.max(jnp.abs(w)))
    assert float(jnp.max(jnp.abs(w_p - w))) <= 1e-5 * scale
    assert float(jnp.max(jnp.abs(traj_p[1] - traj[1]))) <= 1e-5 * float(
        jnp.max(jnp.abs(traj[1])))


def test_chef_cell_is_correct_against_the_reference():
    from bench.drivers import clean_rounds

    d = clean_rounds.Driver(bt.clean_cfg(), bt.load("traffic", "clean-b10"),
                            2 ** 31 + 77)
    ok, checks = bt.run_cell(d)
    assert ok, checks
    vals = {n: v for n, v, _ in checks}
    assert vals["score_err"] <= 1e-4 and vals["weights_err"] <= 1e-5


def test_high_precision_product_is_three_bf16_passes():
    import jax
    import jax.numpy as jnp

    from bench import ref_chef

    a = jax.random.normal(jax.random.key(0), (64, 512))
    b = jax.random.normal(jax.random.key(1), (512, 8))
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    err = lambda x: float(np.max(np.abs(np.asarray(x, np.float64) - exact)))
    e_hi, e_3 = err(ref_chef.dot_highest(a, b)), err(ref_chef.dot_high(a, b))
    one = err(jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32))
    # three passes sit between one bf16 pass and full f32
    assert e_hi < e_3 < one / 10


def _tiny_model(dtype):
    import jax
    import jax.numpy as jnp

    from bench import weights
    from repro.configs import get_config
    from repro.models import Model

    bt.register_tiny_olmo()
    model = Model(get_config(bt.TINY_ARCH), param_dtype=jnp.dtype(dtype).type)
    params = weights.make(jax.eval_shape(model.init, jax.random.key(0)), 9)
    return model, params


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 5e-2)])
def test_olmo_reference_matches_program_prefill(dtype, tol):
    import jax.numpy as jnp

    from bench import ref_olmo, weights
    from repro.core.backend import get_backend

    model, params = _tiny_model(dtype)
    toks = (np.arange(24) * 7 + 3) % 256
    logits, _ = model.prefill(params, {"tokens": jnp.asarray(toks[None])},
                              backend=get_backend("reference"))
    ref = ref_olmo.forward(weights.reference_view(params), jnp.asarray(toks),
                           theta=10000.0)
    got = np.asarray(logits[0, -1], np.float64)
    want = np.asarray(ref[-1], np.float64)
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


def test_e4m3_control_moves_the_logits():
    import jax.numpy as jnp

    from bench import ref_olmo, weights

    _, params = _tiny_model("bfloat16")
    view = weights.reference_view(params)
    toks = jnp.asarray((np.arange(32) * 5 + 1) % 256)
    ref = ref_olmo.forward(view, toks, theta=10000.0)
    ctl = ref_olmo.forward(view, toks, theta=10000.0, dtype="float8_e4m3fn")
    rel = float(jnp.max(jnp.abs(ctl - ref)) / jnp.max(jnp.abs(ref)))
    assert 1e-3 < rel < 0.5
    gaps = ref_olmo.control_gaps(ref, ctl, 0, 32)
    assert float(jnp.max(gaps)) > 0.0


def test_clean_control_reads_above_the_program():
    """The `high` control, put in the program's place at test size, reads
    far above the program on the numbers the check compares. (The limits
    are set from chip readings at the cell's own size, where the control
    reads 4e-3 on score_err against the program's 2e-6.)"""
    from bench import control
    from bench.drivers import clean_rounds

    d = clean_rounds.Driver(bt.clean_cfg(), bt.load("traffic", "clean-b10"),
                            2 ** 31 + 5)
    ok, prog = bt.run_cell(d)
    assert ok, prog
    prog = {n: v for n, v, _ in prog}
    ctl = {n: v for n, v, _ in control.clean_control(d)}
    assert ctl["score_err"] >= 5 * prog["score_err"] > 0
    assert ctl["weights_err"] >= 5 * prog["weights_err"]
    assert ctl["weights_err"] > 0


def test_serve_control_reads_above_the_program():
    """The e4m3 control moves the reference's choice of token where the
    bf16 program does not, at test size."""
    from bench import control
    from bench.drivers import open_loop

    d = open_loop.Driver(bt.olmo_cfg(), bt.chat_traffic(), 2 ** 31 + 5)
    ok, prog = bt.run_cell(d)
    assert ok, prog
    ctl = control.serve_control(d)
    assert ctl[0][1] > max(5 * prog[0][1], 1e-3)
