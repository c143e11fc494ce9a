"""A weakly supervised dataset of a Table-3 shape, made on the device from a
seed in one jitted call.

The generative model is the one of the paper's simulation (Section 5.1):
class prototypes in R^d with Gaussian features around them; labelling
functions that vote by nearest perturbed prototype and abstain below a
margin quantile; an accuracy-weighted vote that makes the probabilistic
labels; annotators that flip the truth with a fixed error rate. The
configuration file gives every size and rate.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _lf_votes(key, X, protos, n_lfs, acc_range, cov_range):
    d = X.shape[1]
    ks = jax.random.split(key, n_lfs * 3).reshape(n_lfs, 3)
    proto_scale = jnp.sqrt(jnp.mean(protos ** 2) + 1e-9)
    votes = []
    for lf in range(n_lfs):
        ka, kc, kw = ks[lf, 0], ks[lf, 1], ks[lf, 2]
        acc = jax.random.uniform(ka, (), minval=acc_range[0], maxval=acc_range[1])
        err = 6.0 * (1.0 - acc) * (d / 48.0) ** 0.25
        p = protos + err * proto_scale * jax.random.normal(kc, protos.shape)
        s = X @ p.T - 0.5 * jnp.sum(p ** 2, axis=-1)
        top2 = jax.lax.top_k(s, 2)[0]
        margin = top2[:, 0] - top2[:, 1]
        cov = jax.random.uniform(kw, (), minval=cov_range[0], maxval=cov_range[1])
        thresh = jnp.quantile(margin, 1.0 - cov)
        votes.append(jnp.where(margin >= thresh, jnp.argmax(s, axis=-1), -1))
    return jnp.stack(votes, axis=1)


def _label_model(votes, n_classes):
    onehot = jnp.where(votes[..., None] >= 0,
                       jax.nn.one_hot(jnp.maximum(votes, 0), n_classes), 0.0)
    mv = jnp.argmax(onehot.sum(axis=1) + 1e-6, axis=-1)
    agree = jnp.where(votes >= 0, (votes == mv[:, None]).astype(jnp.float32),
                      jnp.nan)
    acc = jnp.clip(jnp.nanmean(agree, axis=0), 0.55, 0.95)
    logit_w = jnp.log(acc / (1 - acc)) / max(n_classes - 1, 1)
    return jax.nn.softmax(jnp.einsum("nlc,l->nc", onehot, logit_w), axis=-1)


@partial(jax.jit, static_argnames=("spec",))
def make(key, spec: tuple):
    """Arrays of one dataset; `spec` is `spec_of(config)`."""
    (n_train, n_val, n_test, d, c, class_sep, noise, n_lfs, acc_range,
     cov_range, gamma, n_ann, ann_err) = spec
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    protos = jax.random.normal(k1, (c, d)) * class_sep * (48.0 / d) ** 0.5
    n_all = n_train + n_val + n_test
    y_all = jax.random.randint(k2, (n_all,), 0, c)
    X_all = protos[y_all] + jax.random.normal(k3, (n_all, d)) * noise
    X, X_val, X_test = jnp.split(X_all, [n_train, n_train + n_val])
    y_tr, y_v, y_te = jnp.split(y_all, [n_train, n_train + n_val])
    y_prob = _label_model(_lf_votes(k4, X, protos, n_lfs, acc_range, cov_range), c)
    kf, kl = jax.random.split(k5)
    flips = jax.random.bernoulli(kf, ann_err, (n_train, n_ann))
    wrong = (y_tr[:, None] + jax.random.randint(kl, (n_train, n_ann), 1, c)) % c
    human = jnp.where(flips, wrong, y_tr[:, None]).astype(jnp.int32)
    return {"X": X, "y_prob": y_prob,
            "y_weight": jnp.full((n_train,), gamma, jnp.float32),
            "y_true": y_tr, "human": human, "X_val": X_val,
            "y_val": jax.nn.one_hot(y_v, c), "X_test": X_test, "y_test": y_te}


def spec_of(cfg: dict) -> tuple:
    g = cfg["generator"]
    return (cfg["n_train"], cfg["n_val"], cfg["n_test"], cfg["feature_dim"],
            cfg["n_classes"], g["class_sep"], g["noise"], g["n_lfs"],
            tuple(g["lf_acc"]), tuple(g["lf_cov"]), cfg["gamma"],
            cfg["n_annotators"], cfg["annotator_error"])


TRAIN_KEYS = ("X", "y_prob", "y_weight", "y_true", "human")


@jax.jit
def reorder(arrays: dict, key) -> dict:
    """The same training rows in an order drawn from `key`."""
    perm = jax.random.permutation(key, arrays["X"].shape[0])
    return {k: (v[perm] if k in TRAIN_KEYS else v) for k, v in arrays.items()}
