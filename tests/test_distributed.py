"""Distributed precision-planned Cholesky on a forced 4-host-device CPU
mesh: ``dist_cholesky == blocked_potrf`` per PAPER_CONFIGS entry, both
collective schedules, plan-driven compressed collectives, the
distributed solve, the serve engine's mesh mode, and the scheduler's
async drain. The shard-plan and async-drain tests are host-side and run
in the main 1-device session too; the mesh tests are driven via
tests/test_multidevice.py, or directly:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m pytest tests/test_distributed.py -q
"""
import dataclasses
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.core as core
from repro.core import distributed as dist
from repro.core import plan as plan_mod
from repro.core.plan import build_plan, shard
from repro.launch.mesh import make_mesh

needs4 = pytest.mark.skipif(jax.device_count() < 4,
                            reason="needs 4 host devices")

#: ladder-roundoff equivalence tolerance per coarsest level, as in
#: tests/test_blocked.py
_TOL = {"f16": 5e-3, "bf16": 4e-2, "int8": 4e-2, "f32": 5e-6, "f64": 1e-12}

CONFIGS = [k for k in core.PAPER_CONFIGS if "f64" not in k]
CONFIGS_F64 = [k for k in core.PAPER_CONFIGS if "f64" in k]


def _spd64(n, seed=2):
    rng = np.random.default_rng(seed)
    m = rng.uniform(-1, 1, (n, n))
    return m @ m.T + n * np.eye(n)


def _shard_a(a64, mesh, dtype=jnp.float32):
    return jax.device_put(jnp.asarray(a64, dtype),
                          NamedSharding(mesh, P("model", None)))


def _rel(l, ref):
    l = np.asarray(l, np.float64)
    ref = np.asarray(ref, np.float64)
    return np.abs(l - ref).max() / np.abs(ref).max()


# ---------------------------------------------------------------------------
# dist_cholesky == blocked_potrf (the single-device planned engine)
# ---------------------------------------------------------------------------
@needs4
@pytest.mark.parametrize("name", CONFIGS)
def test_dist_matches_blocked(name):
    """Default schedule (diag broadcast + plan-compressed collectives)
    matches the single-device blocked engine to ladder roundoff."""
    cfg = core.PAPER_CONFIGS[name]
    n = 1024
    mesh = make_mesh((4,), ("model",))
    a64 = _spd64(n)
    ref = core.blocked_potrf(jnp.asarray(a64, jnp.float32), cfg)
    l = dist.dist_cholesky(_shard_a(a64, mesh), mesh, cfg)
    rel = _rel(l, ref)
    assert rel < _TOL[cfg.levels[0]], (name, rel)
    assert np.abs(np.triu(np.asarray(l), 1)).max() == 0.0


@needs4
@pytest.mark.parametrize("name", CONFIGS_F64)
def test_dist_matches_blocked_f64(name):
    """f64-ladder entries (need x64; run by tests/test_multidevice.py
    in a JAX_ENABLE_X64 subprocess)."""
    if not jax.config.jax_enable_x64:
        pytest.skip("f64 ladders need JAX_ENABLE_X64=1")
    cfg = core.PAPER_CONFIGS[name]
    n = 1024
    mesh = make_mesh((4,), ("model",))
    a64 = _spd64(n)
    ref = core.blocked_potrf(jnp.asarray(a64, jnp.float64), cfg)
    l = dist.dist_cholesky(_shard_a(a64, mesh, jnp.float64), mesh, cfg)
    assert _rel(l, ref) < _TOL[cfg.levels[0]], name


@needs4
@pytest.mark.parametrize("bd", [True, False])
@pytest.mark.parametrize("cc", [True, False])
def test_dist_schedules_multitile(bd, cc):
    """Both collective schedules x compressed/full gathers on a w > leaf
    layout (leaf=128 -> 2 tile rows per shard: the local diagonal
    factorization dispatches the fused panel kernel and each shard
    storage-rounds its block-row slice of the solved panel)."""
    cfg = dataclasses.replace(core.PAPER_CONFIGS["f16_f32"], leaf=128)
    n = 1024
    mesh = make_mesh((4,), ("model",))
    a64 = _spd64(n)
    ref = core.blocked_potrf(jnp.asarray(a64, jnp.float32), cfg)
    l = dist.dist_cholesky(_shard_a(a64, mesh), mesh, cfg,
                           broadcast_diag_only=bd, compress_comm=cc)
    rel = _rel(l, ref)
    assert rel < _TOL["f16"], (bd, cc, rel)
    # and against the true factor (sanity beyond engine equivalence)
    want = np.linalg.cholesky(a64)
    assert _rel(l, want) < 5e-3, (bd, cc)


@needs4
def test_dist_solve():
    n = 1024
    mesh = make_mesh((4,), ("model",))
    cfg = core.PrecisionConfig(levels=("f32",), leaf=128)
    a64 = _spd64(n)
    rng = np.random.default_rng(0)
    xt = rng.standard_normal((n, 3))
    b = jax.device_put(jnp.asarray(a64 @ xt, jnp.float32),
                       NamedSharding(mesh, P("model", None)))
    x = dist.dist_cholesky_solve(_shard_a(a64, mesh), b, mesh, cfg)
    rel = np.abs(np.asarray(x, np.float64) - xt).max() / np.abs(xt).max()
    assert rel < 1e-4, rel


# ---------------------------------------------------------------------------
# sharded plan (host-side: runs without devices)
# ---------------------------------------------------------------------------
def test_sharded_plan_comm_schedule(monkeypatch):
    """Collective precision follows the plan: early panels move at the
    ladder's coarse level, panels whose every trailing consumer computes
    fine are gathered losslessly."""
    cfg = dataclasses.replace(core.PAPER_CONFIGS["bf16x3_f32"], leaf=128)
    sp = core.shard(build_plan(1024, cfg), 4)
    assert sp.panel_width == sp.shard_rows == 256 and sp.npanels == 4
    names = [sp.comm_name(j) for j in range(4)]
    assert names[0] == "bf16" and names[-1] == "f32", names
    # b-wide panels (b = 128 < w = 256): one collective per panel,
    # coarse first, lossless last, never coarser toward the diagonal
    monkeypatch.setattr(plan_mod, "PANEL_MAX", 128)
    spb = core.shard(build_plan(1024, cfg), 4)
    assert spb.npanels == 8 and len(spb.comm_table()) == 8
    levels = [spb.comm_level(j) for j in range(8)]
    assert levels == sorted(levels), levels
    assert spb.comm_name(0) == "bf16" and spb.comm_name(7) == "f32"
    # every panel's all-reduce and every gather but the last one's
    assert spb.collective_bytes(compress=False) == {
        "f32": 8 * 128 * 128 * 4 + 7 * 4 * 256 * 128 * 4}
    # pure ladders compress every panel; f32 ladders none
    sp16 = shard(build_plan(1024, dataclasses.replace(
        core.PAPER_CONFIGS["pure_f16"], leaf=128)), 4)
    assert all(sp16.comm_name(j) == "f16" for j in range(4))
    sp32 = shard(build_plan(1024, core.PAPER_CONFIGS["pure_f32"]), 4)
    assert all(sp32.comm_name(j) == "f32" for j in range(4))
    assert "panel 0: comm=bf16" in sp.describe()


def test_sharded_plan_views_match_parent(monkeypatch):
    """diag_plan / store_codes are views of the global tables, not a
    fresh local recursion."""
    cfg = dataclasses.replace(core.PAPER_CONFIGS["f16x3_f32"], leaf=128)
    plan = build_plan(2048, cfg)
    sp = shard(plan, 4)
    assert sp.tps == 4 and sp.panel_width == 512
    for j in (0, 3):
        dp = sp.diag_plan(j)
        assert dp.ntiles == 4
        for r in range(4):
            for c in range(r + 1):
                gi, gj = j * 4 + r, j * 4 + c
                assert dp.level(r, c) == plan.level(gi, gj)
                assert dp.name(r, c) == plan.name(gi, gj)
        codes = sp.store_codes(j)
        assert codes.shape == (16, 4)
        for i in range(16):
            for c in range(4):
                assert sp.names[codes[i, c]] == plan.store_name(i, j * 4 + c)
    # the deepest diagonal sub-block is NOT what a fresh size-512 plan
    # would assign (global levels are deeper): spot-check the far corner
    fresh = build_plan(512, cfg)
    glob = sp.diag_plan(3)
    assert glob.level(3, 0) >= fresh.level(3, 0)
    # panels of 256 columns: two a shard, two tiles each
    monkeypatch.setattr(plan_mod, "PANEL_MAX", 256)
    spb = shard(plan, 4)
    assert (spb.ptiles, spb.npanels, spb.shard_rows) == (2, 8, 512)
    for j in range(8):
        assert spb.owner(j) == j // 2
        dp = spb.diag_plan(j)
        assert dp.ntiles == 2
        assert dp.level(1, 0) == plan.level(2 * j + 1, 2 * j)
        codes = spb.store_codes(j)
        assert codes.shape == (16, 2)
        for i in range(16):
            for c in range(2):
                assert spb.names[codes[i, c]] == plan.store_name(i, 2 * j + c)


# ---------------------------------------------------------------------------
# serve engine mesh mode
# ---------------------------------------------------------------------------
@needs4
def test_engine_mesh_mode_routes_and_caches():
    from repro.serve import SolverEngine
    mesh = make_mesh((4,), ("model",))
    eng = SolverEngine("bf16_f32", max_sweeps=8, mesh=mesh,
                       dist_threshold=512)
    n = 1024
    a = np.asarray(_spd64(n, seed=7), np.float32)
    rng = np.random.default_rng(1)
    b = rng.standard_normal(n).astype(np.float32)
    x, info = eng.solve(a, b, target_digits=6, cache_key="big")
    assert info.distributed and info.converged and not info.factor_cached
    rel = np.abs(a.astype(np.float64) @ np.asarray(x, np.float64)
                 - b).max() / np.abs(b).max()
    assert rel < 1e-6, rel
    # second request reuses the SHARDED factor per fingerprint
    x2, info2 = eng.solve(a, 2.0 * b, target_digits=6, cache_key="big")
    assert info2.distributed and info2.factor_cached and info2.converged
    # below-threshold (and non-divisible) sizes stay on the local path
    asmall = np.asarray(_spd64(192, seed=9), np.float32)
    bs = rng.standard_normal(192).astype(np.float32)
    x3, info3 = eng.solve(asmall, bs, target_digits=6)
    assert not info3.distributed and info3.converged


# ---------------------------------------------------------------------------
# async drain (host-side: runs without devices)
# ---------------------------------------------------------------------------
def _spd32(n, seed):
    return np.asarray(_spd64(n, seed), np.float32)


def test_async_drain_batches_and_orders():
    """Futures resolve with each request's own solution; requests that
    land in one batching window share one refine call, in submission
    order."""
    from repro.serve import BatchScheduler, SolverEngine
    a = _spd32(64, 1)
    rng = np.random.default_rng(0)
    bs = [rng.standard_normal(64).astype(np.float32) for _ in range(4)]
    sch = BatchScheduler(SolverEngine("bf16_f32", max_sweeps=8),
                         max_wait_ms=300)
    sch.start()
    try:
        futs = [sch.submit_async(a, b, target_digits=6, cache_key="k")
                for b in bs]
        outs = [f.result(timeout=600) for f in futs]
    finally:
        sch.stop()
    assert len(sch) == 0
    for i, ((x, info), b) in enumerate(zip(outs, bs)):
        rel = np.abs(a @ np.asarray(x, np.float32) - b).max() / \
            np.abs(b).max()
        assert rel < 1e-5, (i, rel)
        assert info.batch_size == 4 and info.batch_index == i, info


def test_async_deadline_drains_lone_request():
    """A lone request is served once its max_wait_ms deadline passes —
    no follow-up submission or manual drain needed."""
    from repro.serve import BatchScheduler, SolverEngine
    a = _spd32(64, 2)
    b = np.random.default_rng(1).standard_normal(64).astype(np.float32)
    sch = BatchScheduler(SolverEngine("bf16_f32", max_sweeps=8),
                         max_wait_ms=50)
    sch.start()
    try:
        t0 = time.monotonic()
        x, info = sch.submit_async(a, b, target_digits=5).result(timeout=600)
        waited = time.monotonic() - t0
    finally:
        sch.stop()
    assert info.converged and info.batch_size == 1
    assert waited >= 0.05 * 0.5    # the window was actually observed


def test_async_admission_control():
    """A submission that would put more distinct factors in flight than
    the cache holds is rejected, not queued."""
    from repro.serve import BatchScheduler, SchedulerOverload, SolverEngine
    sch = BatchScheduler(SolverEngine("bf16_f32"), max_wait_ms=5000,
                         max_pending_factors=2)
    sch.start()
    b = np.random.default_rng(2).standard_normal(64).astype(np.float32)
    try:
        f1 = sch.submit_async(_spd32(64, 3), b, cache_key="k1")
        f2 = sch.submit_async(_spd32(64, 4), b, cache_key="k2")
        # same matrix again: not a NEW factor, admitted
        f3 = sch.submit_async(_spd32(64, 3), b, cache_key="k1")
        with pytest.raises(SchedulerOverload):
            sch.submit_async(_spd32(64, 5), b, cache_key="k3")
    finally:
        sch.stop()               # drains the admitted requests
    for f in (f1, f2, f3):
        _, info = f.result(timeout=60)
        assert info.converged


def test_async_requires_started_worker():
    from repro.serve import BatchScheduler, SolverEngine
    sch = BatchScheduler(SolverEngine("bf16_f32"), max_wait_ms=10)
    with pytest.raises(AssertionError):
        sch.submit_async(_spd32(64, 6), np.ones(64, np.float32))
