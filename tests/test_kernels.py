"""Per-kernel allclose sweeps (shapes x dtypes) against the ref.py oracles,
in interpret mode (assignment requirement (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.infl_scores import infl_scores_pallas
from repro.kernels.lr_grad import lr_grad_pallas
from repro.kernels.lr_hvp import lr_hvp_pallas

SHAPES = [(128, 32, 2), (256, 64, 4), (512, 128, 8), (64, 256, 16)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _data(key, N, D, C, dtype):
    k = jax.random.split(key, 5)
    X = jax.random.normal(k[0], (N, D), jnp.float32).astype(dtype)
    Y = jax.nn.softmax(jax.random.normal(k[1], (N, C), jnp.float32))
    P = jax.nn.softmax(jax.random.normal(k[2], (N, C), jnp.float32))
    w = (jax.random.normal(k[3], (C, D), jnp.float32) * 0.1).astype(dtype)
    v = (jax.random.normal(k[4], (C, D), jnp.float32) * 0.1).astype(dtype)
    w8 = jax.random.uniform(k[0], (N,), jnp.float32)
    return X, Y, P, w, v, w8


@pytest.mark.parametrize("N,D,C", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_infl_scores(N, D, C, dtype, rng):
    X, Y, P, w, v, w8 = _data(rng, N, D, C, dtype)
    out = infl_scores_pallas(v, X, P, Y, 0.8, block_n=min(64, N), interpret=True)
    want = ref.infl_scores_ref(v, X, P, Y, 0.8)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=tol, rtol=tol)


@pytest.mark.parametrize("N,D,C", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_lr_grad(N, D, C, dtype, rng):
    X, Y, P, w, v, w8 = _data(rng, N, D, C, dtype)
    out = lr_grad_pallas(w, X, Y, w8, 0.05, block_n=min(64, N), interpret=True)
    want = ref.lr_grad_ref(w, X, Y, w8, 0.05)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=tol, rtol=1e-2)


@pytest.mark.parametrize("N,D,C", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_lr_hvp(N, D, C, dtype, rng):
    X, Y, P, w, v, w8 = _data(rng, N, D, C, dtype)
    out = lr_hvp_pallas(w, v, X, w8, 0.05, block_n=min(64, N), interpret=True)
    want = ref.lr_hvp_ref(w, v, X, w8, 0.05)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=tol, rtol=1e-2)


@pytest.mark.parametrize("N,D,C", SHAPES)
@pytest.mark.parametrize("op", ["minibatch_grad", "replay_correction"])
def test_row_gather_op_bitwise(op, N, D, C, rng, monkeypatch):
    """The row-gather wrappers fed `row_gather_layout(Xa)` are bit-identical
    to the jitted reference functions on the plain Xa (the constructor
    parity contract: both run compiled, as inside the SGD and replay
    scans), and the layout is Xa itself plus zero lanes: unpadded in
    interpret mode, padded to 128 lanes on the compiled path."""
    from repro.core import deltagrad, lr_head

    X, Y, P, w, v, w8 = _data(rng, N, D, C, jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(rng, N + D), (48,), 0, N)
    Xr = ops.row_gather_layout(X)
    np.testing.assert_array_equal(np.asarray(Xr), np.asarray(X)[:, None, :])
    if op == "minibatch_grad":
        got = ops.minibatch_grad(w, Xr, Y, w8, idx, 0.05)
        want = jax.jit(lr_head.minibatch_grad_reference,
                       static_argnames="l2")(w, X, Y, w8, idx, l2=0.05)
    else:
        ci, cm = idx[:7], jnp.ones((7,)).at[5:].set(0.0)  # 2 padded slots
        ones = jnp.ones((N,))
        got = ops.replay_correction(w, Xr, Y, P, w8, ones, ci, cm, 48)
        want = jax.jit(deltagrad.replay_correction_reference,
                       static_argnames="batch_size")(
            w, X, Y, P, w8, ones, ci, cm, batch_size=48)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the compiled path's layout, traced unjitted so no cache keeps it
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    Xp = np.asarray(ops.row_gather_layout.__wrapped__(X))
    assert Xp.shape == (N, 1, -(-D // 128) * 128)
    np.testing.assert_array_equal(Xp[:, 0, :D], np.asarray(X))
    assert not Xp[:, :, D:].any()


@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Skv,D,causal,window",
    [
        (2, 4, 2, 128, 128, 32, True, 0),
        (1, 4, 1, 64, 128, 32, False, 0),
        (2, 2, 2, 128, 128, 16, True, 40),
        (1, 8, 4, 256, 256, 64, True, 128),
    ],
)
@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_attention(B, Hq, Hkv, Sq, Skv, D, causal, window, dtype, rng):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, D), jnp.float32).astype(dtype)
    qpos = jnp.arange(Sq) + (Skv - Sq)
    kpos = jnp.arange(Skv)
    out = flash_attention_pallas(
        q, k, v, qpos, kpos, causal=causal, window=window,
        block_q=32, block_k=64, interpret=True,
    )
    want = ref.flash_attention_ref(q, k, v, qpos, kpos, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_compiled_seq_pad_masks_padded_keys(rng):
    """The compiled attention path pads a sequence over 128 to a multiple
    of 128 (its kpos row block sits on lanes): padded keys sit past every
    query and the causal mask drops them, so the real rows match the
    unpadded attention; a non-causal spec cannot be padded and raises."""
    from repro.models.attention import AttnSpec

    B, H, S, D = 1, 2, 200, 32
    ks = jax.random.split(rng, 3)
    q, k, v = (jax.random.normal(kk, (B, H, S, D), jnp.float32) for kk in ks)
    pos = jnp.arange(S, dtype=jnp.int32)
    qp, kp, vp, qpp, kpp = ops._compiled_seq_pad(q, k, v, pos, pos,
                                                 AttnSpec(), {})
    assert qp.shape[2] == kp.shape[2] == 256
    out = flash_attention_pallas(qp, kp, vp, qpp, kpp, block_q=128,
                                 block_k=128, interpret=True)[:, :, :S]
    want = ref.flash_attention_ref(q, k, v, pos, pos, causal=True, window=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(NotImplementedError, match="causal"):
        ops._compiled_seq_pad(q, k, v, pos, pos, AttnSpec(causal=False), {})


def test_ops_wrappers_unaligned(rng):
    """Public wrappers handle non-128-aligned shapes via padding."""
    from repro.core import lr_head
    from repro.core.influence import infl_scores_reference

    N, d, C = 300, 50, 3
    X, Y, P, w, v, w8 = _data(rng, N, d + 1, C, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.lr_grad(w, X, Y, w8, 0.05)),
        np.asarray(lr_head.grad_reference(w, X, Y, w8, 0.05)), atol=1e-5, rtol=1e-4,
    )
    np.testing.assert_allclose(
        np.asarray(ops.lr_hvp(w, v, X, w8, 0.05)),
        np.asarray(lr_head.hvp_reference(w, v, X, w8, 0.05)), atol=1e-5, rtol=1e-4,
    )
    Pw = lr_head.probs(w, X)
    np.testing.assert_allclose(
        np.asarray(ops.infl_scores(v, X, Pw, Y, 0.8)),
        np.asarray(infl_scores_reference(v, X, Pw, Y, 0.8)), atol=1e-4, rtol=1e-4,
    )


@pytest.mark.parametrize("N", [301, 77, 5])
def test_ops_infl_scores_odd_rows(N, rng):
    """Odd row counts must not degrade the grid: rows are padded up to the
    chosen block (block_n=1 — one grid step per row — was the old worst
    case) and the sliced result still matches the reference."""
    from repro.core.influence import infl_scores_reference
    from repro.kernels.ops import _block_n_padded

    assert _block_n_padded(N) >= min(N, 8)  # never the degenerate 1-row block
    X, Y, P, w, v, w8 = _data(rng, N, 50, 3, jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.infl_scores(v, X, P, Y, 0.8)),
        np.asarray(infl_scores_reference(v, X, P, Y, 0.8)), atol=1e-4, rtol=1e-4,
    )


def test_pipeline_with_kernels_matches_jnp(rng):
    """End-to-end: INFL selection on the pallas backend picks the same samples."""
    from repro.configs.chef_lr import ChefConfig
    from repro.core import lr_head, train_head
    from repro.core.influence import infl, influence_vector
    from repro.data import make_dataset

    ds = make_dataset(rng, n_train=512, n_val=64, n_test=64, feature_dim=32)
    cfg = ChefConfig(n_epochs=10, batch_size=128, lr=0.05, l2=0.05)
    w, _, _ = train_head(ds, cfg, cache=False)
    Xa, Xa_val = lr_head.augment(ds.X), lr_head.augment(ds.X_val)
    sel = {}
    for bk in ("reference", "pallas"):
        v, _ = influence_vector(w, Xa_val, ds.y_val, Xa, ds.y_weight, cfg.l2,
                                backend=bk)
        r = infl(w, v, Xa, ds.y_prob, cfg.gamma, backend=bk)
        sel[bk] = np.asarray(jax.lax.top_k(-r.priority, 10)[1])
    assert set(sel["reference"].tolist()) == set(sel["pallas"].tolist())
