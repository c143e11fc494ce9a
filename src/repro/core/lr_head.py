"""The CHEF head: L2-regularized multinomial logistic regression on frozen
backbone features — the paper's strongly-convex model (Section 3.2).

Everything is closed-form (no autodiff needed), which is what makes the
Pallas kernels possible:

  z_i = W x̃_i                     x̃ = [x, 1] (bias absorbed), W [C, d+1]
  p_i = softmax(z_i)
  F(w, z_i)        = -sum_c y_ic log p_ic
  grad_W F(w,z_i)  = (p_i - y_i) x̃_iᵀ
  H(w,z_i) v      -> u_i = V x̃_i ; s_i = p_i*u_i - p_i (p_i·u_i) ; (s_i x̃_iᵀ)
  ∇_y∇_W F δ_y    = -δ_y x̃_iᵀ                       (Eq. 9 contracted; Σδ=0)

The batch objective follows paper Eq. (1): (1/N) Σ γ_z F(w,z) + (λ/2)||W||².

The hot functions (`grad` / `hvp`) dispatch through a `Backend` object
(repro.core.backend): `reference` is the jnp closed form below, `pallas` the
fused kernels in repro.kernels.ops, `pallas_sharded` the shard_map-wrapped
data-parallel kernels (identical semantics, validated against each other in
tests/test_kernels.py and tests/test_backend.py).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.backend import Backend, get_backend


def augment(X: jax.Array) -> jax.Array:
    """[N, d] -> [N, d+1] with a trailing ones column (absorbed bias)."""
    return jnp.concatenate([X, jnp.ones((*X.shape[:-1], 1), X.dtype)], axis=-1)


def init_head(key, n_classes: int, feat_dim: int, scale: float = 0.0) -> jax.Array:
    if scale == 0.0:
        return jnp.zeros((n_classes, feat_dim + 1), jnp.float32)
    return jax.random.normal(key, (n_classes, feat_dim + 1), jnp.float32) * scale


def probs(w: jax.Array, Xa: jax.Array) -> jax.Array:
    """softmax(W x̃) for augmented features Xa [N, d+1] -> [N, C]. The logits
    contract at full f32 precision (a TPU's default is bf16 passes), as the
    head's Pallas kernels do, so every backend feeds them the same P."""
    z = jnp.dot(Xa, w.T, precision=jax.lax.Precision.HIGHEST)
    return jax.nn.softmax(z.astype(jnp.float32), axis=-1)


def loss(w, Xa, Y, weights, l2: float) -> jax.Array:
    """Paper Eq. (1): (1/N) Σ γ_z CE(z) + (λ/2)||w||²."""
    z = (Xa @ w.T).astype(jnp.float32)
    logp = jax.nn.log_softmax(z, axis=-1)
    ce = -jnp.sum(Y * logp, axis=-1)
    return jnp.sum(weights * ce) / Xa.shape[0] + 0.5 * l2 * jnp.sum(w * w)


def grad_reference(w, Xa, Y, weights, l2: float) -> jax.Array:
    """Reference (jnp) form of the batch gradient."""
    P = probs(w, Xa)
    R = (P - Y) * weights[:, None]
    return jnp.einsum("nc,nd->cd", R, Xa) / Xa.shape[0] + l2 * w


def grad(w, Xa, Y, weights, l2: float, backend: Optional[Backend] = None) -> jax.Array:
    """(1/N) Σ γ_i (p_i - y_i) x̃_iᵀ + λ w — fused kernel hot spot."""
    return get_backend(backend).lr_grad(w, Xa, Y, weights, l2)


def hvp_reference(w, v, Xa, weights, l2: float,
                  P: Optional[jax.Array] = None) -> jax.Array:
    """Reference (jnp) form of H(w) v. P may be precomputed probs."""
    if P is None:
        P = probs(w, Xa)
    U = (Xa @ v.T).astype(jnp.float32)  # [N, C]
    S = P * U - P * jnp.sum(P * U, axis=-1, keepdims=True)
    S = S * weights[:, None]
    return jnp.einsum("nc,nd->cd", S, Xa) / Xa.shape[0] + l2 * v


def hvp(w, v, Xa, weights, l2: float, P: Optional[jax.Array] = None,
        backend: Optional[Backend] = None) -> jax.Array:
    """H(w) v for the batch objective. P may be precomputed probs."""
    return get_backend(backend).lr_hvp(w, v, Xa, weights, l2, P=P)


def per_sample_hessian_norm(w, Xa, P: Optional[jax.Array] = None,
                            iters: int = 12, key=None) -> jax.Array:
    """||H(w, z_i)|| for every sample (Theorem 1 provenance).

    The per-sample CE Hessian is the Kronecker product
    A_p ⊗ x̃x̃ᵀ with A_p = diag(p) − ppᵀ, so
    ||H_z|| = ||A_p|| * ||x̃||². ||A_p|| via the power method (Appendix D)
    batched over samples on the small C x C factor — same algorithm, TPU-sane
    cost (the Kronecker factorization is our hardware adaptation; the paper
    runs autodiff HVPs on the full (C·m)² Hessian per sample).
    """
    if P is None:
        P = probs(w, Xa)
    N, C = P.shape
    if key is None:
        key = jax.random.key(0)
    g = jax.random.normal(key, (N, C), jnp.float32)

    def body(g, _):
        Ag = P * g - P * jnp.sum(P * g, axis=-1, keepdims=True)
        g_new = Ag / jnp.maximum(jnp.linalg.norm(Ag, axis=-1, keepdims=True), 1e-30)
        return g_new, None

    g, _ = jax.lax.scan(body, g, None, length=iters)
    Ag = P * g - P * jnp.sum(P * g, axis=-1, keepdims=True)
    a_norm = jnp.sum(g * Ag, axis=-1) / jnp.maximum(jnp.sum(g * g, axis=-1), 1e-30)
    xsq = jnp.sum(Xa.astype(jnp.float32) ** 2, axis=-1)
    return jnp.maximum(a_norm, 0.0) * xsq


def minibatch_grad_reference(w, Xa, Y, weights, idx, l2: float) -> jax.Array:
    """Reference (jnp) gathered mini-batch gradient over B_t = Xa[idx] —
    the SGD-scan step and DeltaGrad-L's explicit iterations (Eq. 4 left
    term). This exact floating-point program is what the fused Pallas
    gather+grad kernel reproduces bit-for-bit (constructor parity)."""
    xb, yb, wb = Xa[idx], Y[idx], weights[idx]
    P = probs(w, xb)
    return jnp.einsum("nc,nd->cd", (P - yb) * wb[:, None], xb) / idx.shape[0] + l2 * w


def per_sample_loss(w, Xa, Y) -> jax.Array:
    z = (Xa @ w.T).astype(jnp.float32)
    logp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.sum(Y * logp, axis=-1)


# ----------------------------------------------------------------------------
# SGD training with trajectory caching (the substrate DeltaGrad-L replays)
# ----------------------------------------------------------------------------


class TrainCache(NamedTuple):
    """Provenance cached during training (paper Section 3.4): per-iteration
    parameters and mini-batch gradients, plus the batch schedule seed."""

    ws: jax.Array  # [T, C, d+1]
    gs: jax.Array  # [T, C, d+1]
    seed: int
    batch_size: int
    n_iters: int


def batch_schedule(seed: int, n: int, batch_size: int, n_epochs: int) -> jax.Array:
    """Deterministic mini-batch index schedule [T, batch_size]. Replayable by
    DeltaGrad-L without caching indices."""
    steps = max(n // batch_size, 1)
    keys = jax.random.split(jax.random.key(seed), n_epochs)
    perms = jax.vmap(lambda k: jax.random.permutation(k, n))(keys)  # [E, n]
    idx = perms[:, : steps * batch_size].reshape(n_epochs * steps, batch_size)
    return idx


@partial(jax.jit,
         static_argnames=("l2", "lr", "momentum", "cache_trajectory", "backend"))
def sgd_train(
    w0,
    Xa,
    Y,
    weights,
    idx_schedule,
    *,
    l2: float,
    lr: float,
    momentum: float = 0.0,
    cache_trajectory: bool = True,
    backend: Optional[Backend] = None,
):
    """Plain SGD (paper Section 5.1) over a precomputed batch schedule,
    optionally caching (w_t, g_t) for DeltaGrad-L.

    Every step's gathered mini-batch gradient dispatches through the
    `Backend` (constructor-phase mirror of the scoring dispatch): reference
    jnp, fused Pallas gather+grad kernel, or the shard_map path where
    Xa/Y/weights stay row-sharded and only the gathered [bs, d+1] batch is
    all-gathered per step. All three produce bit-identical weights and
    trajectories. On pallas_sharded the cached [T, C, d+1] trajectory is
    constrained row-sharded over the mesh's data axes."""
    bk = get_backend(backend)

    def step(carry, idx):
        w, mom = carry
        g = bk.minibatch_grad(w, Xa, Y, weights, idx, l2)
        mom_new = momentum * mom + g if momentum else mom
        w_new = w - lr * (mom_new if momentum else g)
        out = (w, g) if cache_trajectory else None
        return (w_new, mom_new), out

    mom0 = jnp.zeros_like(w0)
    (w_fin, _), traj = jax.lax.scan(step, (w0, mom0), idx_schedule)
    return w_fin, bk.constrain_trajectory(traj)
