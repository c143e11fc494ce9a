"""Serving parity contract: prefill + decode (ring AND paged) dispatch
through Backend with BIT-IDENTICAL logits across reference | pallas |
pallas_sharded (exact equality, not allclose), the KV cache — ring leaves
and paged page pools — lands head-sharded over the mesh model axis on
pallas_sharded, the continuous-batching ServeEngine survives mid-stream
batch joins, and on the paged cache a joined request's tokens AND logits
are bitwise identical to a solo un-padded run (batching invariance; the
ring cache keeps the seed's left-pad join semantics as the differential
oracle). The prefix-sharing and speculative-decode optimizations ride the
same contract: shared-prefix admission and spec_k verification must leave
tokens AND logits bitwise identical to the plain paged run (with CoW and
the block-class / tail-floor admission rules unit-tested alongside).

`REPRO_TEST_BACKENDS` (comma-separated) restricts which non-reference
backends the parity tests sweep — the CI backend-matrix job sets it to run
one backend per matrix leg; unset means all."""
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.backend import BACKENDS, get_backend
from repro.models import Model
from repro.models.attention import (AttnSpec, KVCache, PagedKVCache,
                                    QuantKVCache, QuantPagedKVCache,
                                    ring_valid)
from repro.serving.engine import (Request, ServeConfig, ServeEngine,
                                  check_page_size)

_SEL = [b.strip() for b in os.environ.get(
    "REPRO_TEST_BACKENDS", ",".join(BACKENDS)).split(",") if b.strip()]
NONREF = [b for b in _SEL if b != "reference"]
# tests that exercise pallas_sharded BY NAME (sharding-layout asserts etc.)
# only belong on matrix legs that include it
needs_sharded = pytest.mark.skipif(
    "pallas_sharded" not in _SEL,
    reason="pallas_sharded excluded by REPRO_TEST_BACKENDS")


def _require_selected(backend: str):
    """Honest matrix rows: a leg that excluded `backend` SKIPS its tests
    (visible in the report) instead of silently substituting another
    backend."""
    if backend not in _SEL:
        pytest.skip(f"{backend} excluded by REPRO_TEST_BACKENDS")


def _qkv(key, B, S, Hq, Hkv, D):
    ks = jax.random.split(key, 3)
    return (
        jax.random.normal(ks[0], (B, S, Hq, D)),
        jax.random.normal(ks[1], (B, S, Hkv, D)),
        jax.random.normal(ks[2], (B, S, Hkv, D)),
    )


@pytest.mark.parametrize("spec", [
    AttnSpec(True, 0), AttnSpec(True, 8), AttnSpec(False, 0, 30.0),
])
@pytest.mark.parametrize("shape", [
    (2, 32, 4, 2, 16),   # GQA, 128-divisor-free seq
    (2, 15, 4, 4, 16),   # MHA + odd length (block_q degrades to 1)
])
def test_flash_attention_op_bitwise(spec, shape, rng):
    """Backend.flash_attention: reference == pallas == pallas_sharded to the
    bit (the reference is the jnp mirror of the kernel's blocked program)."""
    B, S, Hq, Hkv, D = shape
    q, k, v = _qkv(rng, B, S, Hq, Hkv, D)
    pos = jnp.arange(S)
    want = np.asarray(get_backend("reference").flash_attention(q, k, v, pos, pos, spec))
    for name in NONREF:
        got = np.asarray(get_backend(name).flash_attention(q, k, v, pos, pos, spec))
        np.testing.assert_array_equal(got, want, err_msg=f"{name} {spec}")


@pytest.mark.parametrize("spec", [
    AttnSpec(True, 0), AttnSpec(True, 8), AttnSpec(True, 0, 30.0),
])
@pytest.mark.parametrize("hkv", [2, 4])  # GQA and MHA (G == 1 matvec path)
def test_decode_attention_op_bitwise(spec, hkv, rng):
    """Backend.decode_attention over a ring cache: bit-identical across
    backends, including the ring/window validity masking."""
    B, Hq, D, W = 2, 4, 16, 24
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    k = jax.random.normal(ks[1], (B, W, hkv, D))
    v = jax.random.normal(ks[2], (B, W, hkv, D))
    valid = ring_valid(jnp.asarray(11), W, spec)
    want = np.asarray(get_backend("reference").decode_attention(q, k, v, valid, spec))
    for name in NONREF:
        got = np.asarray(get_backend(name).decode_attention(q, k, v, valid, spec))
        np.testing.assert_array_equal(got, want, err_msg=f"{name} {spec}")


def _logit_sequence(model, params, toks, backend, steps=4, cache_len=24):
    """Jitted prefill + `steps` decode logits through one Backend."""
    prefill = jax.jit(lambda p, t: model.prefill(
        p, {"tokens": t}, cache_len=cache_len, backend=backend))
    decode = jax.jit(lambda p, c, t: model.decode_step(
        p, c, {"tokens": t}, backend=backend))
    logits, cache = prefill(params, toks)
    seq = [np.asarray(logits)]
    nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    for _ in range(steps):
        logits, cache = decode(params, cache, nxt)
        seq.append(np.asarray(logits))
        nxt = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    return seq, cache


@pytest.mark.parametrize("arch", ["olmo-1b", "recurrentgemma-9b"])
def test_model_logits_bitwise_across_backends(arch, rng):
    """Full-model serving parity: prefill and every decode-step logits are
    bit-identical on all three backends — full attention (olmo, MHA) and
    ring-bounded sliding-window + RG-LRU (recurrentgemma)."""
    cfg = reduced(get_config(arch))
    model = Model(cfg)
    params = model.init(rng)
    toks = jax.random.randint(jax.random.fold_in(rng, 1), (2, 16), 0,
                              cfg.vocab_size).astype(jnp.int32)
    ref, _ = _logit_sequence(model, params, toks, get_backend("reference"))
    for name in NONREF:
        got, _ = _logit_sequence(model, params, toks, get_backend(name))
        for i, (a, b) in enumerate(zip(got, ref)):
            np.testing.assert_array_equal(a, b, err_msg=f"{name} step {i}")


@needs_sharded
def test_kv_cache_sharded_layout(rng):
    """On pallas_sharded, `Backend.shard_kv_cache` commits every KVCache leaf
    head-sharded over the mesh model axis (kv_cache_spec rule); the helpers
    are no-ops on the other backends."""
    from repro.dist.sharding import kv_cache_spec

    bk = get_backend("pallas_sharded")
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    toks = jax.random.randint(rng, (2, 8), 0, cfg.vocab_size).astype(jnp.int32)
    _, cache = jax.jit(lambda p, t: model.prefill(
        p, {"tokens": t}, cache_len=16, backend=bk))(params, toks)
    cache = bk.shard_kv_cache(cache)

    found = []

    def walk(node):
        if isinstance(node, (KVCache, QuantKVCache)):
            found.append(node)
            return
        if isinstance(node, dict):
            for x in node.values():
                walk(x)
        elif isinstance(node, tuple):
            for x in node:
                walk(x)

    walk(cache)
    assert found, "no KV leaves in the cache"
    for kv in found:
        want = kv_cache_spec(bk.mesh, kv.k.shape, kv.k.ndim - 2)
        assert want[kv.k.ndim - 2] == "model"  # genuinely head-sharded rule
        assert kv.k.sharding.spec == want, kv.k.sharding
        assert kv.v.sharding.spec == want, kv.v.sharding
    # no-ops elsewhere: reference passes the pytree through untouched
    assert get_backend("reference").shard_kv_cache(cache) is cache
    assert get_backend("reference").kv_cache_sharding((2, 16, 4, 16), 2) is None


def test_kv_cache_spec_divisibility_fallback():
    """Head counts that do not divide the model axis resolve to replicated
    (the rulebook's fallback), never to an error."""
    from repro.dist.sharding import kv_cache_spec
    from repro.dist.compat import abstract_mesh

    mesh = abstract_mesh((1, 2), ("data", "model"))
    assert kv_cache_spec(mesh, (2, 16, 4, 8), 2)[2] == "model"
    assert kv_cache_spec(mesh, (2, 16, 3, 8), 2) == jax.sharding.PartitionSpec()
    nomodel = abstract_mesh((2,), ("data",))
    assert kv_cache_spec(nomodel, (2, 16, 4, 8), 2) == jax.sharding.PartitionSpec()


@pytest.mark.parametrize("backend", ["reference", "pallas_sharded"])
def test_serve_engine_midstream_join_ring(backend, rng):
    """RING cache (the seed-semantics differential oracle): continuous
    batching survives a mid-stream batch join, every request gets its full
    decode budget, and the joined request's tokens exactly match a solo run
    with the same LEFT-padding (the seed's join-position-dependent
    semantics, preserved verbatim behind ServeConfig.cache='ring')."""
    _require_selected(backend)
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    bk = get_backend(backend)
    ring = ServeConfig(cache="ring")
    eng = ServeEngine(model, params, batch_size=2, max_len=48, backend=bk,
                      config=ring)
    assert eng.cache_mode == "ring"
    rng_np = np.random.default_rng(0)
    reqs = [
        Request(0, rng_np.integers(0, cfg.vocab_size, 8).astype(np.int32), 3),
        Request(1, rng_np.integers(0, cfg.vocab_size, 8).astype(np.int32), 10),
        Request(2, rng_np.integers(0, cfg.vocab_size, 6).astype(np.int32), 5),
    ]
    done = eng.run(reqs)
    assert len(done) == 3 and all(r.done for r in done)
    assert [len(r.out) for r in sorted(done, key=lambda r: r.uid)] == [3, 10, 5]
    # request 2 joined when slot 0 drained after its prefill token + 2
    # decode steps, i.e. at position 8 + 2 = 10 -> the join is exactly a
    # solo request left-padded to 10 (greedy decode is deterministic)
    solo_eng = ServeEngine(model, params, batch_size=1, max_len=48, backend=bk,
                           config=ring)
    solo_prompt = np.concatenate(
        [np.zeros(4, np.int32), reqs[2].prompt]).astype(np.int32)
    solo = solo_eng.run([Request(9, solo_prompt, 5)])[0]
    joined = next(r for r in done if r.uid == 2)
    assert joined.entry_width == 10
    assert joined.out == solo.out


@needs_sharded
@pytest.mark.parametrize("arch", ["recurrentgemma-9b", "mamba2-370m"])
def test_serve_engine_sharded_recurrent_state_survives(arch, rng):
    """shard_kv_cache must leave recurrent-state NamedTuples (RGLRUState /
    SSDState) intact — the generic tuple recursion once rebuilt them as bare
    tuples, crashing the first decode after the commit — so the sharded
    engine serves sub-quadratic archs end to end."""
    cfg = reduced(get_config(arch))
    model = Model(cfg)
    params = model.init(rng)
    eng = ServeEngine(model, params, batch_size=2, max_len=16,
                      backend=get_backend("pallas_sharded"))
    rng_np = np.random.default_rng(2)
    reqs = [Request(i, rng_np.integers(0, cfg.vocab_size, 8).astype(np.int32), 3)
            for i in range(2)]
    done = eng.run(reqs)
    assert len(done) == 2 and all(len(r.out) == 3 for r in done)


def test_serve_engine_zero_budget_request(rng):
    """max_new=0 requests complete immediately with empty output instead of
    being dropped from a wave or hanging the decode loop on a join."""
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    eng = ServeEngine(model, params, batch_size=1, max_len=24,
                      backend=get_backend("reference"))
    rng_np = np.random.default_rng(1)
    reqs = [Request(0, rng_np.integers(0, cfg.vocab_size, 8).astype(np.int32), 3),
            Request(1, rng_np.integers(0, cfg.vocab_size, 4).astype(np.int32), 0)]
    done = eng.run(reqs)
    assert len(done) == 2 and all(r.done for r in done)
    assert sorted((r.uid, len(r.out)) for r in done) == [(0, 3), (1, 0)]


def test_serve_engine_backend_logits_identical(rng):
    """The engine produces identical token streams under every backend —
    the serving parity contract observed end to end (on the default paged
    cache for olmo: 'auto' resolves to 'paged' for attention-only archs)."""
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    rng_np = np.random.default_rng(3)
    prompts = [rng_np.integers(0, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(3)]
    outs = {}
    for name in ["reference"] + NONREF:
        eng = ServeEngine(model, params, batch_size=2, max_len=24,
                          backend=get_backend(name))
        assert eng.cache_mode == "paged"  # auto resolves paged for olmo
        reqs = [Request(i, p.copy(), 4) for i, p in enumerate(prompts)]
        done = eng.run(reqs)
        outs[name] = {r.uid: r.out for r in done}
    for name in NONREF:
        assert outs[name] == outs["reference"], name


# ----------------------------------------------------------------------------
# Paged cache: op parity, pool sharding, batching invariance, bucketing
# ----------------------------------------------------------------------------


def _paged_inputs(rng, B, Hq, Hkv, D, P, n_table, n_pool, pos_list):
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    kp = jax.random.normal(ks[1], (n_pool, P, Hkv, D))
    vp = jax.random.normal(ks[2], (n_pool, P, Hkv, D))
    pt = jax.random.randint(ks[3], (B, n_table), 0, n_pool).astype(jnp.int32)
    pos = jnp.asarray(pos_list, jnp.int32)
    return q, kp, vp, pt, pos


@pytest.mark.parametrize("spec", [
    AttnSpec(True, 0), AttnSpec(True, 5), AttnSpec(True, 0, 30.0),
])
@pytest.mark.parametrize("hkv", [2, 4])  # GQA and MHA (G == 1 matvec path)
def test_paged_decode_attention_op_bitwise(spec, hkv, rng):
    """Backend.paged_decode_attention over a page pool + block table:
    bit-identical across backends, including windowed validity derived from
    the page-table position arithmetic and multi-page softmax merges."""
    q, kp, vp, pt, pos = _paged_inputs(rng, 2, 4, hkv, 16, 4, 3, 9, [10, 3])
    want = np.asarray(get_backend("reference").paged_decode_attention(
        q, kp, vp, pt, pos, spec))
    assert np.all(np.isfinite(want))
    for name in NONREF:
        got = np.asarray(get_backend(name).paged_decode_attention(
            q, kp, vp, pt, pos, spec))
        np.testing.assert_array_equal(got, want, err_msg=f"{name} {spec}")


@pytest.mark.parametrize("spec", [
    AttnSpec(True, 0), AttnSpec(True, 7), AttnSpec(True, 0, 30.0),
])
def test_paged_matches_ring_decode(spec, rng):
    """Differential oracle: the paged op on a paged layout of some cache
    contents agrees with the ring op on the dense layout of the SAME
    contents (allclose — the two run different softmax programs: split-page
    merge vs single-block)."""
    B, Hq, Hkv, D, P, NT = 2, 4, 2, 8, 4, 3
    W = NT * P
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, 1, Hq, D))
    kd = jax.random.normal(ks[1], (B, W, Hkv, D))
    vd = jax.random.normal(ks[2], (B, W, Hkv, D))
    # paged layout: slot b's page j is physical page 1 + b*NT + j
    kp = jnp.zeros((1 + B * NT, P, Hkv, D))
    vp = jnp.zeros((1 + B * NT, P, Hkv, D))
    pt = np.zeros((B, NT), np.int32)
    for b in range(B):
        for j in range(NT):
            pid = 1 + b * NT + j
            kp = kp.at[pid].set(kd[b, j * P:(j + 1) * P])
            vp = vp.at[pid].set(vd[b, j * P:(j + 1) * P])
            pt[b, j] = pid
    pos_v = W - 2  # same position for every slot so ring_valid applies
    bk = get_backend("reference")
    paged = np.asarray(bk.paged_decode_attention(
        q, kp, vp, jnp.asarray(pt), jnp.full((B,), pos_v, jnp.int32), spec))
    ring = np.asarray(bk.decode_attention(
        q, kd, vd, ring_valid(jnp.asarray(pos_v), W, spec), spec))
    np.testing.assert_allclose(paged, ring, rtol=2e-5, atol=2e-6)


@needs_sharded
def test_paged_pool_sharded_layout(rng):
    """On pallas_sharded, `Backend.shard_kv_cache` commits every PagedKVCache
    pool head-sharded over the mesh model axis (page_pool_spec rule); the
    block table and per-slot positions stay untouched."""
    from repro.dist.sharding import page_pool_spec

    bk = get_backend("pallas_sharded")
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    cache = model.init_paged_cache(batch=2, num_pages=9, page_size=8,
                                   table_pages=4)
    cache = bk.shard_kv_cache(cache)

    found = []

    def walk(node):
        if isinstance(node, PagedKVCache):
            found.append(node)
            return
        if isinstance(node, dict):
            for x in node.values():
                walk(x)
        elif isinstance(node, tuple):
            for x in node:
                walk(x)

    walk(cache)
    assert found, "no page pools in the cache"
    for pool in found:
        want = page_pool_spec(bk.mesh, pool.k.shape, pool.k.ndim - 2)
        assert want[pool.k.ndim - 2] == "model"  # genuinely head-sharded rule
        assert pool.k.sharding.spec == want, pool.k.sharding
        assert pool.v.sharding.spec == want, pool.v.sharding
    # reference backend: everything passes through untouched
    assert get_backend("reference").shard_kv_cache(cache) is cache
    assert get_backend("reference").page_pool_sharding((9, 8, 4, 16), 2) is None


@pytest.mark.parametrize("backend", list(BACKENDS))
def test_serve_engine_paged_join_matches_solo_unpadded(backend, rng):
    """THE paged upgrade over the seed semantics: a request joining
    mid-stream produces tokens AND logits bitwise identical to the same
    request run solo and un-padded — outputs are invariant to batching
    (per-slot positions + right-pad-causal pad masking), not merely
    deterministic given the request stream like the ring path."""
    _require_selected(backend)
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    bk = get_backend(backend)
    paged = ServeConfig(batch_size=2, max_len=48, cache="paged", page_size=8,
                        trace_logits=True)
    eng = ServeEngine(model, params, backend=bk, config=paged)
    rng_np = np.random.default_rng(0)
    prompts = [rng_np.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (8, 8, 6)]
    budgets = [3, 10, 5]
    done = eng.run([Request(i, p.copy(), b)
                    for i, (p, b) in enumerate(zip(prompts, budgets))])
    assert len(done) == 3 and all(r.done for r in done)
    assert [len(r.out) for r in sorted(done, key=lambda r: r.uid)] == budgets
    solo_cfg = ServeConfig(batch_size=1, max_len=48, cache="paged",
                           page_size=8, trace_logits=True)
    for r in sorted(done, key=lambda r: r.uid):
        solo_eng = ServeEngine(model, params, backend=bk, config=solo_cfg)
        solo = solo_eng.run(
            [Request(9, prompts[r.uid].copy(), budgets[r.uid])])[0]
        assert solo.out == r.out, (backend, r.uid)
        assert len(solo.logits) == len(r.logits) == len(r.out)
        for k, (a, b) in enumerate(zip(solo.logits, r.logits)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"{backend} uid={r.uid} token {k}")


def test_serve_engine_paged_sliding_window_join_matches_solo(rng):
    """Sliding-window archs on the paged cache: the bucketed prefill keeps
    EVERY position's K/V (Model.prefill(full_cache=True) — no ring
    eviction by right-pad writes), the window is enforced as decode-time
    page validity, and the joined==solo bitwise contract holds. Regression
    guard for the eviction bug: starcoder2's reduced window (32) is smaller
    than the 40-token prompts' 64-wide bucket, so any ring bound on the
    prefill cache would zero out in-window positions and break parity."""
    cfg = reduced(get_config("starcoder2-3b"))
    assert cfg.attn_kind == "sliding" and cfg.sliding_window == 32
    model = Model(cfg)
    params = model.init(rng)
    bk = get_backend("reference")
    paged = ServeConfig(batch_size=2, max_len=48, cache="paged", page_size=8,
                        trace_logits=True)
    eng = ServeEngine(model, params, backend=bk, config=paged)
    assert eng.cache_mode == "paged"
    rng_np = np.random.default_rng(1)
    prompts = [rng_np.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (40, 8, 40)]  # 40 > window=32, buckets to 64
    budgets = [4, 9, 5]
    done = eng.run([Request(i, p.copy(), b)
                    for i, (p, b) in enumerate(zip(prompts, budgets))])
    assert [len(r.out) for r in sorted(done, key=lambda r: r.uid)] == budgets
    solo_cfg = ServeConfig(batch_size=1, max_len=48, cache="paged",
                           page_size=8, trace_logits=True)
    for r in sorted(done, key=lambda r: r.uid):
        solo_eng = ServeEngine(model, params, backend=bk, config=solo_cfg)
        solo = solo_eng.run(
            [Request(9, prompts[r.uid].copy(), budgets[r.uid])])[0]
        assert solo.out == r.out, r.uid
        for k, (a, b) in enumerate(zip(solo.logits, r.logits)):
            np.testing.assert_array_equal(a, b, err_msg=f"uid={r.uid} tok {k}")
    # the window actually bites: with full attention instead, the first
    # decode LOGITS on the >window prompt must differ (otherwise this test
    # would prove nothing about windowed page validity)
    import dataclasses

    nowin = Model(dataclasses.replace(cfg, attn_kind="full"))
    nw = ServeEngine(nowin, params, backend=bk, config=solo_cfg)
    other = nw.run([Request(9, prompts[0].copy(), budgets[0])])[0]
    win_logits = next(r for r in done if r.uid == 0).logits
    assert not all(np.array_equal(a, b)
                   for a, b in zip(other.logits, win_logits))


def _int8_model(cfg, rng):
    """A Model over `cfg` with int8 KV pools, plus params (param init is
    dtype-independent, so the same params serve bf16 oracles)."""
    model = Model(cfg)
    model.kv_dtype = jnp.int8
    params = model.init(rng)
    return model, params


@pytest.mark.parametrize("backend", list(BACKENDS))
def test_serve_engine_int8_paged_join_matches_solo(backend, rng):
    """The paged batching-invariance contract survives int8 KV pools: a
    request joining mid-stream produces tokens AND logits bitwise identical
    to the same request run solo un-padded, per backend. This is the
    per-page-scale design's load-bearing property — quantize-on-commit plus
    running-max decode writes with reset-on-alloc make the pool contents a
    pure function of each request's own write sequence, independent of pool
    history and slot neighbours."""
    _require_selected(backend)
    cfg = reduced(get_config("olmo-1b"))
    model, params = _int8_model(cfg, rng)
    bk = get_backend(backend)
    eng = ServeEngine(model, params, backend=bk,
                      config=ServeConfig(batch_size=2, max_len=48,
                                         cache="paged", page_size=8,
                                         trace_logits=True))
    rng_np = np.random.default_rng(0)
    prompts = [rng_np.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (8, 8, 6)]
    budgets = [3, 10, 5]
    done = eng.run([Request(i, p.copy(), b)
                    for i, (p, b) in enumerate(zip(prompts, budgets))])
    assert len(done) == 3 and all(r.done for r in done)
    solo_cfg = ServeConfig(batch_size=1, max_len=48, cache="paged",
                           page_size=8, trace_logits=True)
    for r in sorted(done, key=lambda r: r.uid):
        solo_eng = ServeEngine(model, params, backend=bk, config=solo_cfg)
        solo = solo_eng.run(
            [Request(9, prompts[r.uid].copy(), budgets[r.uid])])[0]
        assert solo.out == r.out, (backend, r.uid)
        assert len(solo.logits) == len(r.logits) == len(r.out)
        for k, (a, b) in enumerate(zip(solo.logits, r.logits)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"{backend} uid={r.uid} token {k}")


def test_serve_engine_int8_paged_matches_ring_oracle(rng):
    """Differential oracle for the int8 paged path: the same requests
    through the ring-int8 engine (per-TOKEN scales, the seed quantization)
    emit IDENTICAL greedy token streams, and the per-step logits agree
    closely but deliberately NOT bitwise — the paged pool quantizes whole
    pages under one max|x|/127 scale where the ring quantizes each token
    under its own, so the dequantized K/V differ at the last bit (the
    documented deviation; serving/README.md)."""
    cfg = reduced(get_config("olmo-1b"))
    model, params = _int8_model(cfg, rng)
    bk = get_backend("reference")
    eng = ServeEngine(model, params, backend=bk,
                      config=ServeConfig(batch_size=2, max_len=48,
                                         cache="paged", page_size=8,
                                         trace_logits=True))
    rng_np = np.random.default_rng(0)
    prompts = [rng_np.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (8, 8, 6)]
    budgets = [3, 10, 5]
    done = eng.run([Request(i, p.copy(), b)
                    for i, (p, b) in enumerate(zip(prompts, budgets))])
    ring_model, _ = _int8_model(cfg, rng)
    oracle = ServeEngine(ring_model, params, backend=bk,
                         config=ServeConfig(batch_size=1, max_len=48,
                                            cache="ring", trace_logits=True))
    assert oracle.cache_mode == "ring"
    for r in sorted(done, key=lambda r: r.uid):
        solo = oracle.run(
            [Request(9, prompts[r.uid].copy(), budgets[r.uid])])[0]
        assert solo.out == r.out, r.uid
        for a, b in zip(solo.logits, r.logits):
            np.testing.assert_allclose(a, b, rtol=1e-1, atol=1e-1)


def test_serve_engine_int8_sliding_window_join_matches_solo(rng):
    """int8 pools + sliding window + page retirement, all at once: on the
    windowed arch (starcoder2, reduced window 32) the joined==solo bitwise
    contract holds with retirement active, and pages actually retire."""
    cfg = reduced(get_config("starcoder2-3b"))
    assert cfg.attn_kind == "sliding" and cfg.sliding_window == 32
    model, params = _int8_model(cfg, rng)
    bk = get_backend("reference")
    eng = ServeEngine(model, params, backend=bk,
                      config=ServeConfig(batch_size=2, max_len=64,
                                         cache="paged", page_size=8,
                                         trace_logits=True))
    assert eng._retire_window == 32
    rng_np = np.random.default_rng(1)
    prompts = [rng_np.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (40, 8, 40)]
    budgets = [8, 9, 8]
    done = eng.run([Request(i, p.copy(), b)
                    for i, (p, b) in enumerate(zip(prompts, budgets))])
    assert eng.stats["pages_retired"] > 0
    solo_cfg = ServeConfig(batch_size=1, max_len=64, cache="paged",
                           page_size=8, trace_logits=True)
    for r in sorted(done, key=lambda r: r.uid):
        solo_eng = ServeEngine(model, params, backend=bk, config=solo_cfg)
        solo = solo_eng.run(
            [Request(9, prompts[r.uid].copy(), budgets[r.uid])])[0]
        assert solo.out == r.out, r.uid
        for k, (a, b) in enumerate(zip(solo.logits, r.logits)):
            np.testing.assert_array_equal(a, b, err_msg=f"uid={r.uid} tok {k}")


def test_window_retirement_bitwise_neutral_and_lifts_concurrency(rng):
    """Page retirement is OFF the parity hook: identical tokens AND logits
    with retire_pages on vs off (an out-of-window page contributes exactly
    the neutral partial, which is also the trash-page skip), while on a
    SHRUNK pool the freed pages raise the average number of concurrently
    decoding slots — the capacity win that motivates retiring at all."""
    cfg = reduced(get_config("starcoder2-3b"))
    model = Model(cfg)
    params = model.init(rng)
    bk = get_backend("reference")
    rng_np = np.random.default_rng(1)
    prompts = [rng_np.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (40, 8, 40)]
    budgets = [8, 9, 8]

    def run(retire, **kw):
        c = ServeConfig(batch_size=2, max_len=64, cache="paged", page_size=8,
                        trace_logits=True, retire_pages=retire, **kw)
        e = ServeEngine(model, params, backend=bk, config=c)
        d = e.run([Request(i, p.copy(), b)
                   for i, (p, b) in enumerate(zip(prompts, budgets))])
        return e, sorted(d, key=lambda r: r.uid)

    e_on, d_on = run(True)
    e_off, d_off = run(False)
    assert e_on._retire_window == 32 and e_off._retire_window == 0
    assert e_on.stats["pages_retired"] > 0
    assert e_off.stats["pages_retired"] == 0
    for a, b in zip(d_on, d_off):
        assert a.out == b.out, a.uid
        for x, y in zip(a.logits, b.logits):
            np.testing.assert_array_equal(x, y, err_msg=f"uid={a.uid}")
    # shrunk pool (each 48-token request needs 6 pages; 8 usable pages):
    # without retirement at most one 40-token prompt decodes at a time;
    # retirement frees out-of-window pages mid-stream and a second slot
    # admits earlier — same outputs, more overlap
    e2_on, d2_on = run(True, num_pages=9, share_prefix=False)
    e2_off, d2_off = run(False, num_pages=9, share_prefix=False)
    for a, b in zip(d2_on, d2_off):
        assert a.out == b.out, a.uid
    conc_on = e2_on.stats["slot_rounds"] / e2_on.stats["decode_rounds"]
    conc_off = e2_off.stats["slot_rounds"] / e2_off.stats["decode_rounds"]
    assert conc_on > conc_off, (conc_on, conc_off)


@pytest.mark.parametrize("dtype,rows", [(jnp.float32, 8),
                                        (jnp.bfloat16, 16), (jnp.int8, 32)])
def test_page_size_check_follows_pool_dtype(dtype, rows):
    """Compiled (TPU) pages must be whole sublane tiles of the pool dtype:
    8 rows for f32, 16 for bf16, 32 for int8. The check runs at engine
    config time; interpret mode takes any positive page size."""
    check_page_size(rows, dtype, compiled=True)
    check_page_size(2 * rows, dtype, compiled=True)
    with pytest.raises(ValueError, match=f"page_size % {rows} == 0"):
        check_page_size(rows // 2, dtype, compiled=True)
    check_page_size(rows // 2, dtype, compiled=False)
    with pytest.raises(ValueError, match="page_size must be >= 1"):
        check_page_size(0, dtype, compiled=False)


@pytest.mark.parametrize("max_new,joins", [((3, 3, 3), 0), ((2, 5, 3), 1)])
def test_joins_count_only_admissions_beside_a_live_slot(rng, max_new, joins):
    """`stats["joins"]` counts a paged admission only when another slot is
    still decoding. Two slots: with (3, 3, 3) both first requests finish in
    the same round, so the third enters an idle engine (no join); with
    (2, 5, 3) the third takes the first slot while the second decodes."""
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    rng_np = np.random.default_rng(3)
    reqs = [Request(i, rng_np.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    m) for i, m in enumerate(max_new)]
    eng = ServeEngine(model, params, backend=get_backend("reference"),
                      config=ServeConfig(batch_size=2, max_len=16,
                                         cache="paged"))
    done = eng.run(reqs)
    assert sorted(len(r.out) for r in done) == sorted(max_new)
    assert eng.stats["joins"] == joins, eng.stats


def test_int8_auto_routes_paged(rng):
    """`cache="auto"` routes int8-KV attention-only archs to the PAGED
    engine (the ring fallback for quantized caches is gone), forcing the
    non-exact optimizations off: prefix sharing is disabled on the resolved
    config and spec_k > 1 fails loud."""
    cfg = reduced(get_config("olmo-1b"))
    model, params = _int8_model(cfg, rng)
    eng = ServeEngine(model, params, batch_size=2, max_len=16,
                      backend=get_backend("reference"))
    assert eng.cache_mode == "paged"
    assert eng._quant and not eng.config.share_prefix
    with pytest.raises(ValueError, match="spec_k"):
        ServeEngine(model, params, backend=get_backend("reference"),
                    config=ServeConfig(batch_size=2, max_len=16,
                                       cache="paged", spec_k=2))
    # explicit ring still honoured — the differential oracle stays reachable
    ring = ServeEngine(model, params, batch_size=2, max_len=16,
                       backend=get_backend("reference"),
                       config=ServeConfig(cache="ring"))
    assert ring.cache_mode == "ring"


@needs_sharded
def test_quant_paged_pool_sharded_layout(rng):
    """On pallas_sharded, `Backend.shard_kv_cache` commits int8 page pools
    head-sharded (page_pool_spec on the codes) WITH their scale arrays
    sharded in lockstep on the last axis (page_scale_spec) — a pool/scale
    pair can never land on inconsistent layouts."""
    from repro.dist.sharding import page_pool_spec, page_scale_spec

    bk = get_backend("pallas_sharded")
    cfg = reduced(get_config("olmo-1b"))
    model, _ = _int8_model(cfg, rng)
    cache = model.init_paged_cache(batch=2, num_pages=9, page_size=8,
                                   table_pages=4)
    cache = bk.shard_kv_cache(cache)

    found = []

    def walk(node):
        if isinstance(node, QuantPagedKVCache):
            found.append(node)
            return
        if isinstance(node, dict):
            for x in node.values():
                walk(x)
        elif isinstance(node, tuple):
            for x in node:
                walk(x)

    walk(cache)
    assert found, "no quantized page pools in the cache"
    for pool in found:
        assert pool.k.dtype == jnp.int8 and pool.k_scale.dtype == jnp.float32
        want = page_pool_spec(bk.mesh, pool.k.shape, pool.k.ndim - 2)
        assert want[pool.k.ndim - 2] == "model"
        assert pool.k.sharding.spec == want, pool.k.sharding
        assert pool.v.sharding.spec == want, pool.v.sharding
        swant = page_scale_spec(bk.mesh, pool.k_scale.shape,
                                pool.k_scale.ndim - 1)
        assert swant[pool.k_scale.ndim - 1] == "model"
        assert pool.k_scale.sharding.spec == swant, pool.k_scale.sharding
        assert pool.v_scale.sharding.spec == swant, pool.v_scale.sharding


def test_int8_pool_memory_halves(rng):
    """The tentpole's memory claim, measured on real pools: int8 codes +
    per-(page, head) f32 scales take under 52% of the bf16 pool bytes
    (>= 1.9x reduction at head_dim 16; asymptotically 2x)."""
    cfg = reduced(get_config("olmo-1b"))
    model_bf = Model(cfg)
    model_q, _ = _int8_model(cfg, rng)

    def pool_bytes(model, dtype=None):
        # explicit bf16 baseline: the reduced models' param dtype is f32,
        # which would overstate the reduction (~3.9x)
        cache = model.init_paged_cache(batch=2, num_pages=9, page_size=8,
                                       table_pages=4, dtype=dtype)
        total = 0

        def walk(node):
            nonlocal total
            if isinstance(node, (PagedKVCache, QuantPagedKVCache)):
                total += sum(int(x.nbytes) for x in node)
                return
            if isinstance(node, dict):
                for x in node.values():
                    walk(x)
            elif isinstance(node, tuple):
                for x in node:
                    walk(x)

        walk(cache)
        return total

    bf, q = pool_bytes(model_bf, jnp.bfloat16), pool_bytes(model_q)
    assert bf / q >= 1.9, (bf, q)


def test_paged_prefill_shapes_bucketed(rng):
    """Under many staggered joins with scattered prompt lengths, the paged
    engine traces only O(log max_len) distinct prefill widths (power-of-two
    buckets) — the ring engine's per-join-position recompile is gone."""
    import math

    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    max_len = 64
    eng = ServeEngine(model, params, backend=get_backend("reference"),
                      config=ServeConfig(batch_size=2, max_len=max_len,
                                         cache="paged", page_size=8))
    rng_np = np.random.default_rng(4)
    lens = [int(rng_np.integers(1, 40)) for _ in range(12)]
    reqs = [Request(i, rng_np.integers(0, cfg.vocab_size, n).astype(np.int32),
                    int(rng_np.integers(1, 5))) for i, n in enumerate(lens)]
    done = eng.run(reqs)
    assert len(done) == len(reqs)
    bound = int(math.log2(max_len)) + 1
    assert len(eng.prefill_widths) <= bound, (eng.prefill_widths, bound)
    assert all(w & (w - 1) == 0 for w in eng.prefill_widths), eng.prefill_widths


@pytest.mark.parametrize("cache_mode", ["paged", "ring"])
def test_serve_engine_randomized_schedule_oracle(cache_mode, rng):
    """Engine oracle under randomized arrival/finish schedules: every
    request's stream must equal its solo-run oracle. On `paged` the oracle
    is the request run SOLO, UN-padded (batching invariance — the pad
    -attention wart is gone); on `ring` it is the seed semantics oracle —
    the request left-padded with zeros to the width it entered the batch at
    (wave width or join position, recorded as Request.entry_width)."""
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    bk = get_backend("reference")
    conf = ServeConfig(batch_size=2, max_len=48, cache=cache_mode, page_size=8)
    eng = ServeEngine(model, params, backend=bk, config=conf)
    assert eng.cache_mode == cache_mode
    rng_np = np.random.default_rng(7)
    reqs = [Request(i,
                    rng_np.integers(0, cfg.vocab_size,
                                    int(rng_np.integers(2, 12))).astype(np.int32),
                    int(rng_np.integers(1, 7)))
            for i in range(7)]
    prompts = {r.uid: r.prompt.copy() for r in reqs}
    budgets = {r.uid: r.max_new for r in reqs}
    done = eng.run(reqs)
    assert len(done) == len(reqs) and all(r.done for r in done)
    solo_conf = ServeConfig(batch_size=1, max_len=48, cache=cache_mode,
                            page_size=8)
    for r in done:
        if cache_mode == "paged":
            solo_prompt = prompts[r.uid]
        else:  # seed semantics: left-pad to the recorded entry width
            pad = r.entry_width - len(prompts[r.uid])
            assert pad >= 0
            solo_prompt = np.concatenate(
                [np.zeros(pad, np.int32), prompts[r.uid]]).astype(np.int32)
        solo_eng = ServeEngine(model, params, backend=bk, config=solo_conf)
        solo = solo_eng.run([Request(99, solo_prompt, budgets[r.uid])])[0]
        assert solo.out == r.out, (cache_mode, r.uid)


# ----------------------------------------------------------------------------
# Prefix sharing (copy-on-write refcounts) + speculative multi-token decode
# ----------------------------------------------------------------------------


def _shared_prefix_requests(cfg, seed=11, prefix_len=16, tails=(4, 12, 24),
                            budgets=(3, 6, 5)):
    """Requests whose prompts extend one common `prefix_len`-token prefix by
    tails of scattered lengths (different power-of-two prompt buckets
    included — cross-width sharing must still be bitwise)."""
    rng_np = np.random.default_rng(seed)
    pref = rng_np.integers(1, cfg.vocab_size, prefix_len)
    reqs = []
    for u, (t, b) in enumerate(zip(tails, budgets)):
        tail = rng_np.integers(1, cfg.vocab_size, t)
        reqs.append(Request(u, np.concatenate([pref, tail]).astype(np.int32),
                            b))
    return reqs


@pytest.mark.parametrize("backend", list(BACKENDS))
def test_serve_engine_prefix_sharing_matches_unshared(backend, rng):
    """THE prefix-sharing contract: with `share_prefix` on, requests whose
    prompts extend an already-admitted block-aligned prefix ALIAS its
    physical pages and prefill only the unshared tail — and every token AND
    logit stays bitwise identical to the share_prefix=False run (which
    itself equals the solo-unpadded oracle). The tails span different
    power-of-two prompt buckets, so cross-width sharing is covered; the
    stats counters prove pages were actually aliased rather than the test
    passing vacuously on zero hits."""
    _require_selected(backend)
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    bk = get_backend(backend)
    base = dict(batch_size=2, max_len=48, cache="paged", page_size=8,
                trace_logits=True)
    plain = ServeEngine(model, params, backend=bk,
                        config=ServeConfig(**base, share_prefix=False))
    done_p = {r.uid: r for r in plain.run(_shared_prefix_requests(cfg))}
    assert plain.stats["prefix_hits"] == 0  # the control really is unshared
    shared = ServeEngine(model, params, backend=bk,
                         config=ServeConfig(**base, share_prefix=True))
    done_s = {r.uid: r for r in shared.run(_shared_prefix_requests(cfg))}
    # sharing genuinely happened: uid 0 registers the prefix, later
    # admissions alias its two full 8-token pages each
    assert shared.stats["prefix_hits"] >= 2
    assert shared.stats["prefix_hit_tokens"] >= 32
    assert shared.stats["prefill_tokens"] < plain.stats["prefill_tokens"]
    assert shared.stats["cow_copies"] == 0  # normal flow never trips CoW
    for u in done_p:
        assert done_s[u].out == done_p[u].out, (backend, u)
        assert len(done_s[u].logits) == len(done_p[u].logits) == len(done_p[u].out)
        for k, (a, b) in enumerate(zip(done_s[u].logits, done_p[u].logits)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"{backend} uid={u} token {k}")


def test_serve_engine_prefix_pool_persists_across_runs(rng):
    """The prefix index and its pinned pages survive `run()` waves: a second
    wave re-serving an identical prompt on the SAME engine aliases the pages
    the first wave prefilled (prefix hits with no earlier sharer in the
    wave), prefills only the un-matchable tail, and still emits tokens and
    logits bitwise identical to a cold engine's run of the same request."""
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    base = dict(batch_size=2, max_len=48, cache="paged", page_size=8,
                trace_logits=True, share_prefix=True)

    def req():
        return _shared_prefix_requests(cfg, tails=(24,), budgets=(5,))

    eng = ServeEngine(model, params, config=ServeConfig(**base))
    first = eng.run(req())[0]
    assert eng.stats["prefix_hits"] == 0  # nothing indexed before wave 1
    assert eng._pool is not None  # warm pool retained at run end
    second = eng.run(req())[0]
    # the identical 40-token prompt aliases its four matchable full pages
    # ((L-1)//P caps the walk so a 1-page tail still prefills), so wave 2
    # prefills strictly less than wave 1's full bucket
    assert eng.stats["prefix_hits"] == 1
    assert eng.stats["prefix_hit_tokens"] == 32
    assert eng.stats["prefill_tokens"] == 8
    cold = ServeEngine(model, params, config=ServeConfig(**base)).run(req())[0]
    assert second.out == first.out == cold.out
    for a, b in zip(second.logits, cold.logits):
        np.testing.assert_array_equal(a, b)


def test_serve_engine_pool_not_persisted_without_sharing(rng):
    """share_prefix=False keeps the seed semantics: every run rebuilds the
    pool from scratch and no state leaks between waves."""
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    eng = ServeEngine(model, params, config=ServeConfig(
        batch_size=2, max_len=48, cache="paged", page_size=8,
        share_prefix=False))
    reqs = _shared_prefix_requests(cfg, tails=(24,), budgets=(5,))
    eng.run(reqs)
    assert eng._pool is None
    w1 = eng.stats["prefill_tokens"]
    eng.run(_shared_prefix_requests(cfg, tails=(24,), budgets=(5,)))
    assert eng.stats["prefill_tokens"] == w1  # wave 2 redid the full prefill


@pytest.mark.parametrize("backend", list(BACKENDS))
def test_serve_engine_spec_decode_matches_plain(backend, rng):
    """Speculative multi-token decode (spec_k rows verified in one paged
    decode call, greedy longest-matching-prefix acceptance, rollback by
    position truncation) emits tokens AND logits bitwise identical to the
    plain paged loop — speculation is a pure speedup, never a semantics
    change. The stats counters prove drafts were actually proposed (and on
    these prompts, some accepted) rather than the loop degenerating."""
    _require_selected(backend)
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    bk = get_backend(backend)
    base = dict(batch_size=2, max_len=48, cache="paged", page_size=8,
                trace_logits=True)
    plain = ServeEngine(model, params, backend=bk,
                        config=ServeConfig(**base, share_prefix=False))
    done_p = {r.uid: r for r in plain.run(_shared_prefix_requests(cfg))}
    spec = ServeEngine(model, params, backend=bk,
                       config=ServeConfig(**base, spec_k=4))
    done_k = {r.uid: r for r in spec.run(_shared_prefix_requests(cfg))}
    assert spec.stats["spec_proposed"] > 0
    for u in done_p:
        assert done_k[u].out == done_p[u].out, (backend, u)
        assert len(done_k[u].logits) == len(done_p[u].logits)
        for k, (a, b) in enumerate(zip(done_k[u].logits, done_p[u].logits)):
            np.testing.assert_array_equal(
                a, b, err_msg=f"{backend} uid={u} token {k}")


def _page_bytes(cache, pg):
    """Snapshot every layer pool's K/V rows for physical page `pg`."""
    out = []

    def walk(node):
        if isinstance(node, PagedKVCache):
            out.append((np.asarray(node.k)[..., pg, :, :, :].copy(),
                        np.asarray(node.v)[..., pg, :, :, :].copy()))
        elif isinstance(node, dict):
            for x in node.values():
                walk(x)
        elif isinstance(node, tuple):
            for x in node:
                walk(x)

    walk(cache["blocks"])
    walk(cache["tail"])
    return out


def test_paged_cow_preserves_sharer_bytes(rng):
    """Copy-on-write mechanism: a write aimed at a page with refcount > 1
    (manufactured here by hand-pinning the write target — the normal flow
    never aliases a writable page) copies the page onto a fresh one,
    redirects ONLY this slot's table row, and leaves the original page's
    bytes untouched for its sharers; refcounts land at exactly 1 on each
    side of the split."""
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    eng = ServeEngine(model, params, backend=get_backend("reference"),
                      config=ServeConfig(batch_size=1, max_len=48,
                                         cache="paged", page_size=8))
    rng_np = np.random.default_rng(5)
    pending = [Request(0, rng_np.integers(0, cfg.vocab_size, 12)
                       .astype(np.int32), 8)]
    cache, nxt, free, slot_pages, active, remaining = eng._paged_init(
        pending, [])
    r = active[0]
    wpos = len(r.prompt) + len(r.out) - 1  # next decode's write position
    pidx = wpos // eng.config.page_size
    old = int(eng._slot_rows[0][pidx])
    eng.page_refs[old] += 1  # hand-pin: pretend another slot aliases it
    cache = eng._sync_refcount(cache)
    before = _page_bytes(cache, old)
    cache = eng._cow_guard(cache, free, slot_pages, 0, wpos)
    new = int(eng._slot_rows[0][pidx])
    assert new != old and eng.stats["cow_copies"] == 1
    assert eng.page_refs[old] == 1 and eng.page_refs[new] == 1
    assert int(np.asarray(cache["pages"])[0, pidx]) == new
    assert old not in slot_pages[0] and new in slot_pages[0]
    for (bk_, bv), (ok_, ov), (nk_, nv) in zip(
            before, _page_bytes(cache, old), _page_bytes(cache, new)):
        np.testing.assert_array_equal(ok_, bk_)  # sharer bytes intact
        np.testing.assert_array_equal(ov, bv)
        np.testing.assert_array_equal(nk_, bk_)  # copy is byte-faithful
        np.testing.assert_array_equal(nv, bv)
    # idempotent: the write target is now exclusively owned — no re-copy
    cache = eng._cow_guard(cache, free, slot_pages, 0, wpos)
    assert eng.stats["cow_copies"] == 1


def test_prefix_match_block_class_and_tail_floor(rng):
    """Admission-side sharing rules, unit-level: (a) a prefix indexed under
    one flash kv block class is invisible to a prompt bucketed into the
    other class (the bitwise-stability envelope stops at 128); (b) the
    alias count is capped so at least one prompt token always remains for
    the tail prefill, even when every full page of the prompt is indexed."""
    cfg = reduced(get_config("olmo-1b"))
    model = Model(cfg)
    params = model.init(rng)
    eng = ServeEngine(model, params, backend=get_backend("reference"),
                      config=ServeConfig(batch_size=1, max_len=48,
                                         cache="paged", page_size=8))
    rng_np = np.random.default_rng(6)
    prompt = rng_np.integers(1, cfg.vocab_size, 16).astype(np.int32)
    pb = np.asarray(prompt, np.int32)
    eng._prefix_index[(False, pb[:8].tobytes())] = 3
    eng._prefix_index[(False, pb[:16].tobytes())] = 4
    # same class (<=128 bucket): both pages alias... but capped at
    # (L-1)//P = 1 for the 16-token prompt — one token must stay unshared
    assert eng._prefix_match(prompt, 16) == (1, [3])
    longer = np.concatenate([pb, rng_np.integers(1, cfg.vocab_size, 4)
                             .astype(np.int32)])
    assert eng._prefix_match(longer, 32) == (2, [3, 4])
    # other block class (> 128 bucket): no match despite identical bytes
    assert eng._prefix_match(longer, 256) == (0, [])
    # sharing disabled: no match regardless
    eng.config = replace(eng.config, share_prefix=False)
    assert eng._prefix_match(longer, 32) == (0, [])


def test_paged_cache_rejects_unsupported_arch(rng):
    """cache='paged' on a recurrent arch fails loud; 'auto' falls back to
    ring so sub-quadratic archs keep serving on seed semantics."""
    cfg = reduced(get_config("recurrentgemma-9b"))
    model = Model(cfg)
    params = model.init(rng)
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(model, params, batch_size=2, max_len=16,
                    backend=get_backend("reference"),
                    config=ServeConfig(cache="paged"))
    eng = ServeEngine(model, params, batch_size=2, max_len=16,
                      backend=get_backend("reference"))
    assert eng.cache_mode == "ring"
