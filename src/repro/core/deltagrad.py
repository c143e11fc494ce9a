"""DeltaGrad-L (paper Section 4.2, Algorithm 2): incremental model update
after cleaning a small set of labels, by replaying the cached SGD trajectory.

Label cleaning = delete the b samples with (old probabilistic labels, weight
γ) + add the same samples with (cleaned one-hot labels, weight 1). Per
Eq. (4) the updated mini-batch gradient is the cached/approximated old-batch
gradient plus a correction over ONLY the changed samples in the batch —
O(b) work instead of O(|B_t|).

The old-batch gradient at the *new* iterate w^I_t is:
  * computed explicitly in the first j0 iterations and every T0 afterwards
    (these iterations also update the L-BFGS (ΔW, ΔG) history), and
  * approximated elsewhere via Eq. (5):  B_t (w^I_t − w_t) + cached g_t,
    with B_t the compact limited-memory BFGS Hessian estimate
    (Byrd–Nocedal–Schnabel representation; history size m0).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lr_head
from repro.core.backend import Backend, get_backend


@dataclass(frozen=True)
class DGConfig:
    burn_in: int = 10  # j0
    period: int = 10  # T0
    history: int = 2  # m0
    lr: float = 0.05
    l2: float = 0.05


# ----------------------------------------------------------------------------
# Compact L-BFGS Hessian product: B v
# ----------------------------------------------------------------------------


def lbfgs_Bv(S, Yh, n_pairs, v):
    """Compact-form BFGS Hessian estimate applied to v.

    S, Yh: [m0, P] ring buffers of parameter / gradient differences (most
    recent last); n_pairs: how many entries are valid. Falls back to B = I
    scaling when no pairs exist.
    """
    m0, Pdim = S.shape
    valid = (jnp.arange(m0) >= (m0 - n_pairs)).astype(jnp.float32)  # recent last
    Sv = S * valid[:, None]
    Yv = Yh * valid[:, None]
    sy_last = jnp.sum(S[-1] * Yh[-1])
    ss_last = jnp.sum(S[-1] * S[-1])
    sigma = jnp.where(ss_last > 1e-30, sy_last / jnp.maximum(ss_last, 1e-30), 1.0)
    sigma = jnp.maximum(sigma, 1e-8)

    STS = Sv @ Sv.T  # [m0, m0]
    STY = Sv @ Yv.T
    Ltri = jnp.tril(STY, k=-1)
    D = jnp.diag(jnp.diag(STY))
    # M = [[sigma S^T S, L], [L^T, -D]]
    top = jnp.concatenate([sigma * STS, Ltri], axis=1)
    bot = jnp.concatenate([Ltri.T, -D], axis=1)
    M = jnp.concatenate([top, bot], axis=0)
    # regularize invalid rows/cols to identity so solve stays well-posed
    mask2 = jnp.concatenate([valid, valid])
    M = M * mask2[:, None] * mask2[None, :] + jnp.diag(1.0 - mask2)
    rhs = jnp.concatenate([sigma * (Sv @ v), Yv @ v]) * mask2
    z = jnp.linalg.solve(M, rhs)
    z = z * mask2
    Bv = sigma * v - (sigma * (Sv.T @ z[:m0]) + Yv.T @ z[m0:])
    return jnp.where(n_pairs > 0, Bv, v)


# ----------------------------------------------------------------------------
# Correction schedule (host-side, numpy): where do cleaned samples appear?
# ----------------------------------------------------------------------------


def build_correction_schedule(idx_schedule: np.ndarray, changed_idx: np.ndarray):
    """For each iteration t, the changed-sample slots inside B_t.

    Returns (corr_idx [T, r_max] int32 — global sample ids, padded with 0;
             corr_mask [T, r_max] f32 — 1 for real entries).

    Vectorized: one `np.isin` membership test over the whole [T, bs]
    schedule plus a stable argsort that compacts each row's hits to the
    front IN BATCH-SLOT ORDER — the same hit ordering the old per-row
    Python scan produced (the correction einsum's summation order, and
    therefore replay bit-parity, depends on it). The old double loop is
    kept as `_build_correction_schedule_loop` (equivalence test + the
    micro-benchmark in benchmarks/bench_constructor.py; at T >= 1k the
    vectorized form wins by well over an order of magnitude)."""
    idx_np = np.asarray(idx_schedule)
    changed = np.asarray(changed_idx).reshape(-1)
    hit = np.isin(idx_np, changed)  # [T, bs]
    r_max = max(1, int(hit.sum(axis=1).max(initial=0)))
    order = np.argsort(~hit, axis=1, kind="stable")[:, :r_max]
    sel = np.take_along_axis(hit, order, axis=1)
    ids = np.take_along_axis(idx_np, order, axis=1)
    corr_idx = np.where(sel, ids, 0).astype(np.int32)
    corr_mask = sel.astype(np.float32)
    return jnp.asarray(corr_idx), jnp.asarray(corr_mask)


def _build_correction_schedule_loop(idx_schedule: np.ndarray,
                                    changed_idx: np.ndarray):
    """Pre-vectorization reference (Python double loop over T x bs): the
    oracle `build_correction_schedule` must match exactly."""
    idx_np = np.asarray(idx_schedule)
    changed = set(int(c) for c in np.asarray(changed_idx).tolist())
    T = idx_np.shape[0]
    hits = [[int(s) for s in row if int(s) in changed] for row in idx_np]
    r_max = max(1, max((len(h) for h in hits), default=1))
    corr_idx = np.zeros((T, r_max), np.int32)
    corr_mask = np.zeros((T, r_max), np.float32)
    for t, h in enumerate(hits):
        for j, s in enumerate(h):
            corr_idx[t, j] = s
            corr_mask[t, j] = 1.0
    return jnp.asarray(corr_idx), jnp.asarray(corr_mask)


def replay_correction_reference(w, Xa, Y_old, Y_new, w_old, w_new,
                                corr_idx, corr_mask, batch_size: int):
    """Reference (jnp) replay correction for ONE iteration's changed slots:
    (1/|B|) Σ_changed [ 1·∇F(w, z_new) − γ·∇F(w, z_old) ]  (Eq. 4 / §4.2).
    The fused Pallas kernel reproduces this program bit-for-bit."""
    xb = Xa[corr_idx]  # [r, d+1]
    P = lr_head.probs(w, xb)
    g_new = (P - Y_new[corr_idx]) * (w_new[corr_idx] * corr_mask)[:, None]
    g_old = (P - Y_old[corr_idx]) * (w_old[corr_idx] * corr_mask)[:, None]
    return jnp.einsum("nc,nd->cd", g_new - g_old, xb) / batch_size


# ----------------------------------------------------------------------------
# Replay
# ----------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=("cfg", "batch_size", "backend"),
)
def deltagrad_replay(
    cache_ws,  # [T, C, d+1] cached parameters
    cache_gs,  # [T, C, d+1] cached mini-batch gradients
    idx_schedule,  # [T, bs]
    Xa,
    Y_old,
    Y_new,
    w_old,  # [N] old per-sample weights (gamma for uncleaned)
    w_new,  # [N] new per-sample weights (1 for cleaned)
    corr_idx,  # [T, r_max]
    corr_mask,  # [T, r_max]
    cfg: DGConfig,
    batch_size: int,
    backend: "Backend | None" = None,
):
    """Algorithm 2 adapted for label cleaning (Section 4.2). Returns w^I_T.

    Constructor-phase dispatch: the explicit-iteration batch gradients and
    the per-iteration corrections go through `Backend.minibatch_grad` /
    `Backend.replay_correction` (bit-identical across the three backends),
    both gathering from Xa laid out once here, before the scan
    (`Backend.row_gather_layout`: a copy of Xa, loop-invariant, which XLA
    would not hoist out of the loop itself). On pallas_sharded, Xa/Y stay
    row-sharded, only the gathered batch rows
    are all-gathered per step, the replayed [T, C, d+1] trajectory is
    constrained row-sharded over the data axes, and the L-BFGS (ΔW, ΔG)
    ring buffers are pinned replicated."""
    bk = get_backend(backend)
    T, C, D = cache_ws.shape
    Pdim = C * D
    m0 = cfg.history

    t_arr = jnp.arange(T)
    explicit = (t_arr < cfg.burn_in) | (((t_arr - cfg.burn_in) % cfg.period) == 0)

    Xr = bk.row_gather_layout(Xa)

    def batch_grad(w, idx):
        return bk.minibatch_grad(w, Xr, Y_old, w_old, idx, cfg.l2)

    def correction(w, ci, cm):
        """(1/|B|) Σ_changed [ 1·∇F(w, z_new) − γ·∇F(w, z_old) ]."""
        return bk.replay_correction(w, Xr, Y_old, Y_new, w_old, w_new,
                                    ci, cm, batch_size)

    def step(carry, xs):
        wI, Sbuf, Ybuf, n_pairs = carry
        idx, w_t, g_t, is_exp, ci, cm = xs

        def explicit_fn(args):
            wI, Sbuf, Ybuf, n_pairs = args
            g_exp = batch_grad(wI, idx)
            s = (wI - w_t).reshape(-1)
            y = (g_exp - g_t).reshape(-1)
            good = jnp.sum(s * y) > 1e-12  # curvature guard
            Sb = jnp.where(good, jnp.roll(Sbuf, -1, axis=0).at[-1].set(s), Sbuf)
            Yb = jnp.where(good, jnp.roll(Ybuf, -1, axis=0).at[-1].set(y), Ybuf)
            np_ = jnp.where(good, jnp.minimum(n_pairs + 1, m0), n_pairs)
            return g_exp, Sb, Yb, np_

        def approx_fn(args):
            wI, Sbuf, Ybuf, n_pairs = args
            dv = (wI - w_t).reshape(-1)
            Bv = lbfgs_Bv(Sbuf, Ybuf, n_pairs, dv)
            g_apx = Bv.reshape(C, D) + g_t
            return g_apx, Sbuf, Ybuf, n_pairs

        g_old_batch, Sbuf, Ybuf, n_pairs = jax.lax.cond(
            is_exp, explicit_fn, approx_fn, (wI, Sbuf, Ybuf, n_pairs)
        )
        g = g_old_batch + correction(wI, ci, cm)
        w_next = wI - cfg.lr * g
        # emit the refreshed provenance (Section 4.2 item (2)): the replayed
        # trajectory + its corrected gradients become the cache that the NEXT
        # cleaning round replays against.
        return (w_next, Sbuf, Ybuf, n_pairs), (wI, g)

    w0 = cache_ws[0]
    Sbuf = bk.constrain_replicated(jnp.zeros((m0, Pdim), jnp.float32))
    Ybuf = bk.constrain_replicated(jnp.zeros((m0, Pdim), jnp.float32))
    (w_fin, *_), new_traj = jax.lax.scan(
        step,
        (w0, Sbuf, Ybuf, jnp.zeros((), jnp.int32)),
        (idx_schedule, cache_ws, cache_gs, explicit, corr_idx, corr_mask),
    )
    return w_fin, bk.constrain_trajectory(new_traj)


def absorb_rows(
    traj,  # (cache_ws, cache_gs) — the previous window's trajectory
    sched,  # [T, bs] batch schedule (drawn over the FIXED capacity)
    Xa,
    Y_old,
    Y_new,
    w_old,
    w_new,
    changed_idx,
    cfg: DGConfig,
    backend: "Backend | None" = None,
):
    """Warm-start on newly-arrived data by trajectory replay — DeltaGrad-L's
    label-cleaning machinery reused for STREAMING ingest.

    A window append is, from the replay's point of view, exactly a label
    change on the arriving rows: they transition from (padding labels,
    weight 0 — exact neutral elements that contributed bitwise nothing to
    any cached batch gradient) to (weak labels, weight gamma). Per Eq. (4)
    the updated batch gradients are the cached ones plus corrections over
    ONLY the arriving rows that land in each batch, so absorbing an m-row
    window costs O(T * m * bs / N_cap) correction work instead of a full
    O(T * bs) retrain — the speedup benchmarks/bench_streaming.py records.

    Requires the schedule to have been drawn over the fixed capacity (the
    repro.stream window store's invariant) so arriving rows already occupy
    batch slots. Returns (w, new_traj) like `deltagrad_replay`; the caller
    re-commits the trajectory sharding."""
    ci, cm = build_correction_schedule(np.asarray(sched),
                                       np.asarray(changed_idx))
    return deltagrad_replay(
        traj[0], traj[1], sched, Xa, Y_old, Y_new, w_old, w_new,
        ci, cm, cfg, int(sched.shape[1]), backend=backend,
    )
