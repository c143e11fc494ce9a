"""Compile rehearsal: every main-path Pallas kernel compiled for a described
TPU v5e at full size, with no chip attached.

The TPU compiler (libtpu) is installed here and compiles for a topology that
is described, not attached: what it refuses here (tiling, scoped VMEM, block
layouts) it would refuse on the chip, and interpret mode never checks any of
it. Shapes are the ones the compiled path runs:

  * the five CHEF kernels at MIMIC's Table-3 size, N = 78,487 rows of
    d + 1 = 2,049 features, lane-padded to 2,176, classes padded to 128;
  * the DeltaGrad-L replay and the SGD scan at the same size (T = 390
    steps of a 2,000-row batch), whose loops must not copy Xa;
  * the serving kernels at olmo-1b's published widths: 16 heads of 128,
    bf16 activations, a 2,048-token context.

Each kernel entry point is called with `interpret=False` on the shapes the
`ops` wrappers pad to. The topology is described inside a module fixture
(never at import: only one process may load libtpu), and the persistent
compilation cache is off around the compiles, since an entry written for a
described device cannot be read back without one.
"""
from __future__ import annotations

import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.configs.chef_lr import paper_dataset_specs
from repro.kernels import ops
from repro.kernels.chunked_prefill import chunked_prefill_partials_pallas
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.infl_scores import infl_scores_pallas
from repro.kernels.local_attention import (block_sparse_attention_pallas,
                                           local_attention_pallas)
from repro.kernels.lr_grad import lr_grad_pallas
from repro.kernels.lr_hvp import lr_hvp_pallas
from repro.kernels.minibatch_grad import minibatch_grad_pallas
from repro.kernels.paged_attention import (
    page_tile_rows,
    paged_attention_partials_pallas,
    paged_attention_partials_quant_pallas,
)
from repro.kernels.replay_correction import replay_correction_pallas
from repro.models.attention import AttnSpec

LANE = 128
MIMIC = paper_dataset_specs()["mimic"]
N = MIMIC.n_train  # 78,487 rows
DP = -(-(MIMIC.feature_dim + 1) // LANE) * LANE  # d + 1 = 2,049 -> 2,176
BATCH = 2000  # Table 4 SGD batch
OLMO = get_config("olmo-1b")
S = 2048  # serving context
B = 4  # decode slots


@pytest.fixture(scope="module")
def one_chip():
    """A single described v5e device (the first of a 2x2 topology), with
    the persistent compilation cache off for the module's compiles."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    if saved_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


def _compile(fn, shapes, sharding):
    """Lower + compile `fn` for the described chip; return the HLO text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


f32, bf16, i32, i8 = jnp.float32, jnp.bfloat16, jnp.int32, jnp.int8
BN = ops._block_n_padded(N)  # row block the ops wrappers pick at this N
NR = -(-N // BN) * BN  # rows padded up to it
BB = ops.GATHER_BLOCK
NB = -(-BATCH // BB) * BB  # batch padded to whole gather chunks
HKV, HD = OLMO.n_kv_heads, OLMO.resolved_head_dim
HQ = OLMO.n_heads
G = 8  # one query head per kv head (MHA), padded to a sublane tile

# (kernel under test, [(shape, dtype)] arguments) per compiled entry point
CASES = {
    "infl_scores": (
        lambda v, X, P, Y: infl_scores_pallas(v, X, P, Y, 0.8, block_n=BN,
                                              c_actual=2),
        [((LANE, DP), f32), ((NR, DP), f32), ((NR, LANE), f32),
         ((NR, LANE), f32)]),
    "lr_grad": (
        lambda w, X, Y, g: lr_grad_pallas(w, X, Y, g, 0.0, block_n=BN,
                                          c_actual=2),
        [((LANE, DP), f32), ((NR, DP), f32), ((NR, LANE), f32),
         ((NR,), f32)]),
    "lr_hvp": (
        lambda w, v, X, g: lr_hvp_pallas(w, v, X, g, 0.0, block_n=BN,
                                         c_actual=2),
        [((LANE, DP), f32), ((LANE, DP), f32), ((NR, DP), f32),
         ((NR,), f32)]),
    "minibatch_grad": (
        lambda w, X, y, g, i: minibatch_grad_pallas(
            w, X, y, g, i, 0.05, n_batch=BATCH, c_actual=2, block_b=BB),
        [((LANE, DP), f32), ((N, 1, DP), f32), ((NB, LANE), f32),
         ((NB,), f32), ((NB,), i32)]),
    "replay_correction": (
        lambda w, X, yo, yn, wo, wn, ci, cm: replay_correction_pallas(
            w, X, yo, yn, wo, wn, ci, cm, BATCH, c_actual=2),
        [((LANE, DP), f32), ((N, 1, DP), f32), ((16, LANE), f32),
         ((16, LANE), f32), ((16,), f32), ((16,), f32), ((16,), i32),
         ((16,), f32)]),
    "flash_attention": (
        lambda q, k, v, qp, kp: flash_attention_pallas(q, k, v, qp, kp),
        [((1, HQ, S, HD), bf16)] * 3 + [((S,), i32)] * 2),
    "flash_attention_unaligned": (  # ops pads 300 -> 384 on this path
        lambda q, k, v, qp, kp: ops._flash_adapt(
            flash_attention_pallas, q, k, v, qp, kp, AttnSpec(),
            interpret=False),
        [((1, 300, HQ, HD), bf16)] * 3 + [((300,), i32)] * 2),
    "local_attention": (
        lambda q, k, v, qp, kp: local_attention_pallas(q, k, v, qp, kp,
                                                       window=512),
        [((1, HQ, S, HD), bf16)] * 3 + [((S,), i32)] * 2),
    "block_sparse_attention": (
        lambda q, k, v, qp, kp, m: block_sparse_attention_pallas(
            q, k, v, qp, kp, block_mask=m),
        [((1, HQ, S, HD), bf16)] * 3 + [((S,), i32)] * 2
        + [((S // 128, S // 128), i32)]),
    "chunked_prefill": (
        lambda q, k, v, qp, kp: chunked_prefill_partials_pallas(
            q, k, v, qp, kp, chunk=512),
        [((1, HQ, S, HD), bf16)] * 3 + [((S,), i32)] * 2),
    "decode_attention": (
        lambda q, k, v, valid: decode_attention_pallas(q, k, v, valid),
        [((B, HKV, G, HD), bf16), ((B, HKV, S, HD), bf16),
         ((B, HKV, S, HD), bf16), ((S,), jnp.bool_)]),
}

for _dt, _name in ((bf16, "paged_attention"), (f32, "paged_attention_f32")):
    _P = page_tile_rows(_dt)
    _NP = 1 + B * (S // _P)
    CASES[_name] = (
        lambda q, k, v, pt, pos: paged_attention_partials_pallas(
            q, k, v, pt, pos),
        [((B, HKV, G, HD), _dt), ((_NP, _P, HKV, HD), _dt),
         ((_NP, _P, HKV, HD), _dt), ((B, S // _P), i32), ((B,), i32)])

_P8 = page_tile_rows(i8)
_NP8 = 1 + B * (S // _P8)
CASES["paged_attention_int8"] = (
    lambda q, k, v, ks, vs, pt, pos: paged_attention_partials_quant_pallas(
        q, k, v, ks, vs, pt, pos),
    [((B, HKV, G, HD), bf16), ((_NP8, _P8, HKV, HD), i8),
     ((_NP8, _P8, HKV, HD), i8), ((_NP8, HKV), f32), ((_NP8, HKV), f32),
     ((B, S // _P8), i32), ((B,), i32)])


# kernels whose pallas_call carries a name: the device trace shows the
# custom call under it (not as the enclosing `%closed_call.N`)
NAMED = {"flash_attention": "flash_attention",
         "flash_attention_unaligned": "flash_attention",
         "paged_attention": "paged_decode_attention",
         "paged_attention_f32": "paged_decode_attention",
         "paged_attention_int8": "quant_paged_decode_attention"}


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(kernel, one_chip):
    fn, shapes = CASES[kernel]
    hlo = _compile(fn, shapes, one_chip)
    # the kernel really went through Mosaic (not an XLA fallback)
    assert "tpu_custom_call" in hlo
    if kernel in NAMED:
        assert re.search(rf"%{NAMED[kernel]}(\.\d+)? = .*custom-call\(", hlo)


def test_page_tile_rows_follow_pool_dtype():
    assert [page_tile_rows(d) for d in (f32, bf16, i8)] == [8, 16, 32]


# HLO text: a computation's header, and an instruction's name, result type
# and opcode (a tuple type has no space before its opcode's parenthesis)
_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
_CALLS = re.compile(r"(?:calls|to_apply|body|condition|true_computation|"
                    r"false_computation)=%([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
# ops that pass an array along without writing it
_PASS = {"parameter", "get-tuple-element", "tuple", "bitcast"}


def _computations(hlo: str) -> dict:
    """{computation: [(name, result type, opcode, line)]} of an HLO text."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m and line.rstrip().endswith("{"):
            cur = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            cur.append((m.group(1), m.group(2), m.group(3), line))
    return comps


def _callees(comps: dict, roots) -> set:
    """`roots` and every computation they call, transitively."""
    seen, todo = set(), list(roots)
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for *_, line in comps.get(c, []):
            todo += _CALLS.findall(line)
            for group in _BRANCHES.findall(line):
                todo += [b.strip().lstrip("%") for b in group.split(",")]
    return seen


def _scan(name: str):
    """(step function, argument shapes, kernels in its loop) of one of the
    two constructor scans on pallas at MIMIC size: T = 390 steps (10
    epochs) of a 2,000-row batch, r_max 8 corrections a replay step."""
    from repro.core import lr_head
    from repro.core.backend import get_backend
    from repro.core.deltagrad import DGConfig, deltagrad_replay

    T, R, C = 390, 8, 2
    D = MIMIC.feature_dim + 1
    bk = get_backend("pallas")
    if name == "sgd_train":
        return (lambda *a: lr_head.sgd_train(*a, l2=0.05, lr=0.0005,
                                             backend=bk),
                [((C, D), f32), ((N, D), f32), ((N, C), f32), ((N,), f32),
                 ((T, BATCH), i32)], ("minibatch_grad",))
    return (lambda *a: deltagrad_replay(*a, DGConfig(), BATCH, backend=bk),
            [((T, C, D), f32), ((T, C, D), f32), ((T, BATCH), i32),
             ((N, D), f32), ((N, C), f32), ((N, C), f32), ((N,), f32),
             ((N,), f32), ((T, R), i32), ((T, R), f32)],
            ("minibatch_grad", "replay_correction"))


@pytest.mark.parametrize("scan", ["deltagrad_replay", "sgd_train"])
def test_scan_lays_out_xa_once_for_v5e(scan, one_chip, monkeypatch):
    """The DeltaGrad-L replay and the SGD scan on pallas at MIMIC size,
    compiled for v5e: the row-gather layout of Xa ([N, 1, 2,176]) is built
    once, before the scan, and no op in the scan's loop writes an array of
    Xa's rows (a pad, reshape or copy there would copy Xa on each of the T
    steps). `ops._interpret` is forced off: this process's backend is the
    CPU, and the compiled path is the one under test."""
    fn, shapes, kernels = _scan(scan)
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    try:
        hlo = _compile(fn, shapes, one_chip)
    finally:
        jax.clear_caches()  # drop the compiled-path traces
    comps = _computations(hlo)
    loops = [(m.group(1), m.group(2)) for m in re.finditer(
        r"condition=%([\w.\-]+), body=%([\w.\-]+)", hlo)]
    assert loops, "the scan compiled to no while loop"
    in_loop = _callees(comps, [c for pair in loops for c in pair])
    written = [line.strip()[:160] for c in in_loop for _, ty, op, line in
               comps[c] if ty.startswith(f"f32[{N},") and op not in _PASS]
    assert not written, written
    layout = [op for c, ins in comps.items() if c not in in_loop
              for _, ty, op, _ in ins
              if ty.startswith(f"f32[{N},1,{DP}]") and op not in _PASS]
    assert len(layout) == 1, layout
    # the kernels run in the loop, on the laid-out array
    for kernel in kernels:
        assert any(name.startswith(kernel) and op == "custom-call"
                   for c in in_loop for name, _, op, _ in comps[c]), kernel
