"""The distributed sweep with panels narrower than a shard's rows
(b < n/P), on a forced 4-host-device CPU mesh: the factor of every
PAPER_CONFIGS ladder against a plain f64 Cholesky, the refined solve to
1e-6, and the collective audits reconciling the b-wide schedule exactly.
Driven by tests/test_multidevice.py (each test its own target), or
directly:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m pytest tests/test_dist_panels.py -q

``plan.PANEL_MAX`` is lowered here so that small matrices sweep more
than one panel per shard, as n = 65536 does at the real cap. The panel
width is read when the sweep is traced, so JAX's caches are cleared
whenever the cap moves.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.core as core
from repro.core import distributed as dist
from repro.core import plan as plan_mod
from repro.launch.mesh import make_mesh

needs4 = pytest.mark.skipif(jax.device_count() < 4,
                            reason="needs 4 host devices")

#: per coarsest ladder level, as tests/test_distributed.py
_TOL = {"f16": 5e-3, "bf16": 4e-2, "int8": 4e-2, "f32": 5e-6}

LADDERS = [k for k in core.PAPER_CONFIGS if "f64" not in k]
N, LEAF, B = 1024, 128, 128


def cap_panels(monkeypatch, width):
    """Cap the sweep's panels at ``width`` columns for one test."""
    jax.clear_caches()
    monkeypatch.setattr(plan_mod, "PANEL_MAX", width)


@pytest.fixture
def narrow(monkeypatch):
    """Panels of ``B`` columns at ``N`` (w = 256 rows a shard)."""
    cap_panels(monkeypatch, B)
    assert plan_mod.panel_width(N, 4, LEAF) == B
    yield
    jax.clear_caches()


def _spd64(n, seed=2):
    rng = np.random.default_rng(seed)
    m = rng.uniform(-1, 1, (n, n))
    return m @ m.T + n * np.eye(n)


def _sharded(x, mesh):
    return jax.device_put(jnp.asarray(x, jnp.float32),
                          NamedSharding(mesh, P("model", None)))


@needs4
@pytest.mark.parametrize("name", LADDERS)
def test_dist_cholesky_narrow_panels(narrow, name):
    cfg = dataclasses.replace(core.PAPER_CONFIGS[name], leaf=LEAF)
    mesh = make_mesh((4,), ("model",))
    a64 = _spd64(N)
    l = np.asarray(dist.dist_cholesky(_sharded(a64, mesh), mesh, cfg),
                   np.float64)
    want = np.linalg.cholesky(a64)
    rel = np.abs(l - want).max() / np.abs(want).max()
    assert rel < _TOL[cfg.levels[0]], (name, rel)
    assert np.abs(np.triu(l, 1)).max() == 0.0


@needs4
def test_dist_refine_solve_reaches_1e6(narrow):
    cfg = dataclasses.replace(core.PAPER_CONFIGS["bf16_f32"], leaf=LEAF)
    mesh = make_mesh((4,), ("model",))
    a64 = _spd64(N, seed=5)
    b64 = np.random.default_rng(6).standard_normal((N, 8))
    a = _sharded(a64, mesh)
    rcfg = core.RefineConfig(tol=1e-6, residual_dtype="f32")

    @jax.jit
    def solve(a, b):
        l = dist.dist_cholesky(a, mesh, cfg)
        return dist.dist_refine_solve(a, b, mesh, cfg, refine=rcfg, l=l)

    res = solve(a, _sharded(b64, mesh))
    x = np.asarray(res.x, np.float64)
    rel = (np.linalg.norm(b64 - a64 @ x, axis=0)
           / np.linalg.norm(b64, axis=0))
    assert rel.max() <= 1e-6, rel
    assert bool(np.asarray(res.converged).all())
    x64 = np.linalg.solve(a64, b64)
    assert np.abs(x - x64).max() / np.abs(x64).max() < 1e-5


@needs4
def test_dist_audits_reconcile_narrow_panels(monkeypatch):
    """The traced and compiled collective audits price the b-wide
    schedule exactly (n = 2048, leaf 256, b = 256: 8 panels, 2 a
    shard), and the lossy-wire mutation is still named by panel."""
    from repro.audit import conformance, hloaudit
    cap_panels(monkeypatch, 256)
    cfg = core.PAPER_CONFIGS["f16x3_f32"]
    sp = core.shard(core.build_plan(2048, cfg), 4)
    assert (sp.panel_width, sp.npanels, sp.shard_rows) == (256, 8, 512)
    for res in (conformance.audit_dist(2048, cfg, 4),
                hloaudit.audit_hlo_dist(2048, cfg, 4)):
        errors = [str(v) for v in res.violations if v.severity == "error"]
        assert not errors, errors

    class Lossy:
        def __init__(self, sp):
            self._sp = sp

        def comm_name(self, j):
            return "f16" if j == 3 else self._sp.comm_name(j)

        def comm_quant(self, j):
            return False if j == 3 else self._sp.comm_quant(j)

        def __getattr__(self, k):
            return getattr(self._sp, k)

    f32 = core.PAPER_CONFIGS["pure_f32"]
    pristine = core.shard(core.build_plan(2048, f32), 4)
    monkeypatch.setattr(dist, "shard", lambda plan, ns: Lossy(
        core.ShardedPlan(plan, ns)))
    res = conformance.audit_dist(2048, f32, 4, sharded=pristine)
    jax.clear_caches()
    hits = [v for v in res.violations if v.rule == "collective-wire-dtype"]
    assert hits and all(v.panel == 3 for v in hits), res.violations
