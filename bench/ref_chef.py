"""Plain reference of a CHEF cleaning session: f32 jax.numpy at HIGHEST
precision, written from the paper (arXiv:2107.08588) and independent of the
program under test.

  init     SGD over the seeded batch schedule, caching (w_t, g_t)      Sec. 5.1
  select   v = -H^-1 grad F_val by conjugate gradients, Eq. 6 scores,
           the b smallest eligible priorities                          Sec. 4.1
  annotate majority vote of three annotators and INFL's label          Sec. 5.1
  update   DeltaGrad-L replay of the cached trajectory                 Sec. 4.2

Everything contracts at full f32 (`Precision.HIGHEST`). `dot` is the one
place a matrix product happens, so the control can swap in three-pass
bf16 (`high`) products and nothing else changes.

The rows-by-features contractions over all N rows run in blocks of
`block` rows, so the reference needs no more memory than one block of
temporaries beside the data it is given.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dot_highest(a, b):
    """f32 product at full precision."""
    return jnp.dot(a, b, precision=HIGHEST)


def dot_high(a, b):
    """The `high` precision product, three bf16 passes, written out so that it
    reads the same on every backend: a_hi b_hi + a_hi b_lo + a_lo b_hi."""
    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
        return hi, lo

    a_hi, a_lo = split(a)
    b_hi, b_lo = split(b)
    return (dot_highest(a_hi, b_hi) + dot_highest(a_hi, b_lo)
            + dot_highest(a_lo, b_hi))


DOTS = {"highest": dot_highest, "high": dot_high}


class Hyper(NamedTuple):
    """The session's hyper-parameters (static under jit)."""

    lr: float
    l2: float
    gamma: float
    batch_size: int
    n_epochs: int
    round_size: int
    cg_iters: int
    cg_tol: float
    burn_in: int
    period: int
    history: int
    seed: int
    precision: str = "highest"


def augment(X):
    return jnp.concatenate([X, jnp.ones((X.shape[0], 1), X.dtype)], axis=1)


def softmax_rows(z):
    z = z - jnp.max(z, axis=-1, keepdims=True)
    e = jnp.exp(z)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def batch_schedule(seed: int, n: int, bs: int, epochs: int):
    """[T, bs] row ids: one seeded permutation per epoch, cut into batches."""
    steps = max(n // bs, 1)
    keys = jax.random.split(jax.random.key(seed), epochs)
    perms = jax.vmap(lambda k: jax.random.permutation(k, n))(keys)
    return perms[:, :steps * bs].reshape(epochs * steps, bs)


def _blocks(n: int, block: int):
    return [(s, min(s + block, n)) for s in range(0, n, block)]


def full_hvp(w, v, Xa, wt, l2, dot, block):
    """H(w) v of (1/N) sum_i wt_i CE_i + l2/2 |w|^2, in row blocks."""
    n = Xa.shape[0]
    acc = jnp.zeros_like(w)
    for s, e in _blocks(n, block):
        x = Xa[s:e]
        p = softmax_rows(dot(x, w.T))
        u = dot(x, v.T)
        sm = (p * u - p * jnp.sum(p * u, axis=-1, keepdims=True)) * wt[s:e, None]
        acc = acc + dot(sm.T, x)
    return acc / n + l2 * v


def grad_rows(w, x, y, wt, l2, n_div, dot):
    """(1/n_div) sum wt_i (p_i - y_i) x_i^T + l2 w over the given rows."""
    p = softmax_rows(dot(x, w.T))
    return dot(((p - y) * wt[:, None]).T, x) / n_div + l2 * w


def scores(w, v, Xa, Y, gamma, dot, block):
    """Eq. 6, I(i, c) = (y_i - e_c + (1 - gamma)(p_i - y_i)) . u_i, as [N, C]."""
    out = []
    for s, e in _blocks(Xa.shape[0], block):
        x = Xa[s:e]
        p = softmax_rows(dot(x, w.T))
        u = dot(x, v.T)
        y = Y[s:e]
        base = jnp.sum((y + (1.0 - gamma) * (p - y)) * u, axis=-1)
        out.append(base[:, None] - u)
    return jnp.concatenate(out, axis=0)


@partial(jax.jit, static_argnames=("hp", "block"))
def train(Xa, Y, wt, hp: Hyper, block: int = 8192):
    """Plain SGD from zero weights over the seeded schedule; returns the final
    weights, the cached trajectory (w_t, g_t) and the schedule."""
    dot = DOTS[hp.precision]
    n, d1 = Xa.shape
    c = Y.shape[1]
    sched = batch_schedule(hp.seed, n, min(hp.batch_size, n), hp.n_epochs)

    def step(w, idx):
        g = grad_rows(w, Xa[idx], Y[idx], wt[idx], hp.l2, idx.shape[0], dot)
        return w - hp.lr * g, (w, g)

    w, traj = jax.lax.scan(step, jnp.zeros((c, d1), jnp.float32), sched)
    return w, traj, sched


@partial(jax.jit, static_argnames=("hp", "block"))
def select(w, Xa, Y, wt, Xa_val, Y_val, eligible, hp: Hyper,
           block: int = 8192):
    """Round selection: (priority [N], suggested [N], top-b ids [b])."""
    dot = DOTS[hp.precision]
    g_val = grad_rows(w, Xa_val, Y_val, jnp.ones(Xa_val.shape[0]), 0.0,
                      Xa_val.shape[0], dot)
    hvp = lambda p: full_hvp(w, p, Xa, wt, hp.l2, dot, block)

    def cond(st):
        return (st[4] < hp.cg_iters) & (st[3] > hp.cg_tol * hp.cg_tol)

    def body(st):
        x, r, p, rs, it = st
        hp_ = hvp(p)
        alpha = rs / jnp.maximum(jnp.sum(p * hp_), 1e-30)
        x = x + alpha * p
        r = r - alpha * hp_
        rs_new = jnp.sum(r * r)
        p = r + rs_new / jnp.maximum(rs, 1e-30) * p
        return x, r, p, rs_new, it + 1

    st0 = (jnp.zeros_like(g_val), g_val, g_val, jnp.sum(g_val * g_val),
           jnp.zeros((), jnp.int32))
    v = -jax.lax.while_loop(cond, body, st0)[0]
    S = scores(w, v, Xa, Y, hp.gamma, dot, block)
    priority = jnp.min(S, axis=-1)
    suggested = jnp.argmin(S, axis=-1).astype(jnp.int32)
    masked = jnp.where(eligible, priority, jnp.inf)
    _, idx = jax.lax.top_k(-masked, hp.round_size)
    return priority, suggested, idx


def vote(human, infl_label, n_classes: int, round_key):
    """Strategy 'three': majority over the annotators and INFL's label, ties
    broken by a seeded jitter of 1e-3 (the round's vote key)."""
    ballots = jnp.concatenate([human, infl_label[:, None]], axis=1)
    counts = jnp.sum(jax.nn.one_hot(ballots, n_classes), axis=1)
    counts = counts + 1e-3 * jax.random.uniform(round_key, counts.shape)
    return jnp.argmax(counts, axis=-1).astype(jnp.int32)


def round_keys(seed: int, k: int):
    """(select key, vote key) of round k of a session seeded with `seed`."""
    return jax.random.split(jax.random.fold_in(jax.random.key(seed + 1), k), 2)


def _lbfgs_Bv(S, Yh, n_pairs, v, dot):
    """Compact L-BFGS Hessian estimate times v (Byrd, Nocedal, Schnabel),
    most recent pair last; B = I when no pair is stored."""
    m0 = S.shape[0]
    valid = (jnp.arange(m0) >= (m0 - n_pairs)).astype(jnp.float32)
    Sv, Yv = S * valid[:, None], Yh * valid[:, None]
    sy = jnp.sum(S[-1] * Yh[-1])
    ss = jnp.sum(S[-1] * S[-1])
    sigma = jnp.maximum(jnp.where(ss > 1e-30, sy / jnp.maximum(ss, 1e-30), 1.0),
                        1e-8)
    STY = dot(Sv, Yv.T)
    L = jnp.tril(STY, k=-1)
    M = jnp.block([[sigma * dot(Sv, Sv.T), L], [L.T, -jnp.diag(jnp.diag(STY))]])
    m2 = jnp.concatenate([valid, valid])
    M = M * m2[:, None] * m2[None, :] + jnp.diag(1.0 - m2)
    rhs = jnp.concatenate([sigma * dot(Sv, v), dot(Yv, v)]) * m2
    z = jnp.linalg.solve(M, rhs) * m2
    Bv = sigma * v - (sigma * dot(Sv.T, z[:m0]) + dot(Yv.T, z[m0:]))
    return jnp.where(n_pairs > 0, Bv, v)


@partial(jax.jit, static_argnames=("hp",))
def replay(ws, gs, sched, Xa, Y_old, Y_new, wt_old, wt_new, changed, hp: Hyper):
    """DeltaGrad-L: replay the cached trajectory with this round's labels.
    Explicit steps (the first `burn_in`, then every `period`) compute the
    old-label batch gradient at the replayed weights and refresh the L-BFGS
    pairs; the others take B (w - w_t) + g_t. Each step adds the correction
    over the batch's changed rows, (1/|B|) sum [wt_new (p - y_new) -
    wt_old (p - y_old)] x^T. Returns the final weights and new trajectory."""
    dot = DOTS[hp.precision]
    T, C, D = ws.shape
    m0 = hp.history
    t = jnp.arange(T)
    explicit = (t < hp.burn_in) | (((t - hp.burn_in) % hp.period) == 0)
    bs = sched.shape[1]

    def step(carry, xs):
        w, Sb, Yb, npairs = carry
        idx, w_t, g_t, is_exp = xs
        x = Xa[idx]

        def exp_fn(_):
            g = grad_rows(w, x, Y_old[idx], wt_old[idx], hp.l2, bs, dot)
            s = (w - w_t).reshape(-1)
            y = (g - g_t).reshape(-1)
            good = jnp.sum(s * y) > 1e-12
            Sn = jnp.where(good, jnp.roll(Sb, -1, axis=0).at[-1].set(s), Sb)
            Yn = jnp.where(good, jnp.roll(Yb, -1, axis=0).at[-1].set(y), Yb)
            return g, Sn, Yn, jnp.where(good, jnp.minimum(npairs + 1, m0),
                                        npairs)

        def apx_fn(_):
            Bv = _lbfgs_Bv(Sb, Yb, npairs, (w - w_t).reshape(-1), dot)
            return Bv.reshape(C, D) + g_t, Sb, Yb, npairs

        g_old, Sb, Yb, npairs = jax.lax.cond(is_exp, exp_fn, apx_fn, None)
        hit = changed[idx]
        p = softmax_rows(dot(x, w.T))
        r = ((p - Y_new[idx]) * (wt_new[idx] * hit)[:, None]
             - (p - Y_old[idx]) * (wt_old[idx] * hit)[:, None])
        g = g_old + dot(r.T, x) / bs
        return (w - hp.lr * g, Sb, Yb, npairs), (w, g)

    zeros = jnp.zeros((m0, C * D), jnp.float32)
    (w, *_), traj = jax.lax.scan(
        step, (ws[0], zeros, zeros, jnp.zeros((), jnp.int32)),
        (sched, ws, gs, explicit))
    return w, traj


class RoundRef(NamedTuple):
    priority: jax.Array  # [N] min-class Eq. 6 score
    suggested: jax.Array  # [N]
    idx: jax.Array  # [b] the reference's own top-b
    labels: jax.Array  # [b] votes on the ids the round cleaned
    w: jax.Array  # [C, d+1] weights after the round's replay


def session(data: dict, hp: Hyper, rounds: int, chosen=None):
    """Run `rounds` rounds; yields a RoundRef per round.

    `chosen(k)` may return the ids the program cleaned in round k: the
    reference then cleans those rows (after the caller has checked that
    they are a valid top-b under the reference's scores), so one wrong pick
    is reported once instead of sending every later round elsewhere. With
    `chosen` None the reference cleans its own top-b."""
    Xa, Xa_val = augment(data["X"]), augment(data["X_val"])
    Y, wt = data["y_prob"], data["y_weight"]
    cleaned = jnp.zeros(Xa.shape[0], bool)
    w, traj, sched = train(Xa, Y, wt, hp)
    c = Y.shape[1]
    for k in range(rounds):
        priority, suggested, idx_ref = select(w, Xa, Y, wt, Xa_val,
                                              data["y_val"], ~cleaned, hp)
        idx = idx_ref if chosen is None else jnp.asarray(chosen(k), jnp.int32)
        _, k_vote = round_keys(hp.seed, k)
        labels = vote(data["human"][idx], suggested[idx], c, k_vote)
        Y_new = Y.at[idx].set(jax.nn.one_hot(labels, c, dtype=Y.dtype))
        wt_new = wt.at[idx].set(1.0)
        changed = jnp.zeros(Xa.shape[0], jnp.float32).at[idx].set(1.0)
        w, traj = replay(traj[0], traj[1], sched, Xa, Y, Y_new, wt, wt_new,
                         changed, hp)
        Y, wt, cleaned = Y_new, wt_new, cleaned.at[idx].set(True)
        yield RoundRef(priority, suggested, idx_ref, labels, w)
