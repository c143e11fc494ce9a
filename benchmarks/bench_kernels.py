"""Kernel microbenchmarks across the Backend dispatch layer.

Measures the five hot ops — the three selector-phase ops (infl_scores /
lr_grad / lr_hvp) and the two constructor-phase ops (minibatch_grad /
replay_correction, the fused gather kernels behind sgd_train and
DeltaGrad-L replay) — under any subset of the backends (`reference` |
`pallas` | `pallas_sharded`), so roofline tables cover both phases. On CPU the
interesting number is the REFERENCE column (XLA) — interpret-mode Pallas
timing measures the Python interpreter, so non-reference wall times are only
emitted on TPU, where `pallas_sharded` additionally shows the scaling of the
shard_map data-parallel path over the local mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.core import lr_head
from repro.core.backend import BACKENDS, get_backend
from repro.utils.timing import time_fn


def run(N: int = 8192, d: int = 2048, C: int = 2, backend: str = "all") -> list:
    import sys

    if backend in ("", "all"):
        names = list(BACKENDS)
    else:
        names = [n.strip() for n in backend.split(",") if n.strip()]
    bad = [n for n in names if n not in BACKENDS]
    if bad or not names:
        raise ValueError(f"unknown backend(s) {bad or [backend]}; "
                         f"expected 'all' or a comma list of {BACKENDS}")
    # reference first so speedup_vs_ref is derivable for the others
    names.sort(key=lambda n: n != "reference")
    if jax.default_backend() != "tpu":
        suppressed = [n for n in names if n != "reference"]
        if suppressed:
            print(f"# {','.join(suppressed)} wall-times suppressed on "
                  f"{jax.default_backend()} (interpret-mode Pallas measures "
                  "the Python interpreter)", file=sys.stderr)
            names = [n for n in names if n not in suppressed]
    ks = jax.random.split(jax.random.key(0), 6)
    Xa = jax.random.normal(ks[0], (N, d + 1))
    Y = jax.nn.softmax(jax.random.normal(ks[1], (N, C)))
    w = jax.random.normal(ks[2], (C, d + 1)) * 0.1
    v = jax.random.normal(ks[3], (C, d + 1)) * 0.1
    w8 = jnp.ones((N,))
    P = lr_head.probs(w, Xa)
    # constructor-phase op inputs: a gathered mini-batch and a correction set
    bs = min(1024, N)
    r = min(32, bs)
    idx = jax.random.randint(ks[4], (bs,), 0, N)
    Y_new = jnp.roll(Y, 1, axis=1)
    w8_new = jnp.ones((N,))
    ci = jax.random.randint(ks[5], (r,), 0, N)
    cm = jnp.ones((r,))
    hw = jax.default_backend()
    rows = []

    t_ref = {}
    for name in names:
        bk = get_backend(name)
        Xr = bk.row_gather_layout(Xa)
        # (op, fn, matmul-equivalents, rows the matmuls run over)
        pairs = [
            ("infl_scores", lambda: bk.infl_scores(v, Xa, P, Y, 0.8), 1, N),
            ("lr_grad", lambda: bk.lr_grad(w, Xa, Y, w8, 0.05), 2, N),
            ("lr_hvp", lambda: bk.lr_hvp(w, v, Xa, w8, 0.05), 2, N),
            ("minibatch_grad",
             lambda: bk.minibatch_grad(w, Xr, Y, w8, idx, 0.05), 2, bs),
            ("replay_correction",
             lambda: bk.replay_correction(w, Xr, Y, Y_new, w8, w8_new,
                                          ci, cm, bs), 2, r),
        ]
        for op, fn, matmuls, n_rows in pairs:
            fn = fn if name != "reference" else jax.jit(fn)
            t = time_fn(fn, iters=5)
            flops = 2 * n_rows * (d + 1) * C * matmuls
            derived = f"gflops={flops / t / 1e9:.1f};hw={hw}"
            if name == "reference":
                t_ref[op] = t
            elif op in t_ref:
                derived += f";speedup_vs_ref={t_ref[op] / t:.2f}x"
            emit(f"kernel_{op}_{name}", t, derived)
            rows.append((op, name, t))
    return rows


if __name__ == "__main__":
    run()
