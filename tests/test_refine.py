"""Iterative-refinement tests: convergence of classic IR and GMRES-IR
across precision ladders (f64 reference under jax_enable_x64), the
zero-sweep no-op contract, the operator-level API used by K-FAC, and the
accuracy-targeted serve engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core as core

RNG = np.random.default_rng(11)


def spd(n, dtype=np.float64, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.uniform(-1, 1, (n, n))
    return ((m @ m.T + n * np.eye(n))).astype(dtype)


LADDERS = ["pure_f16", "f16_f32", "bf16_f32"]


@pytest.mark.parametrize("ladder", LADDERS)
@pytest.mark.parametrize("method", ["ir", "gmres"])
def test_refine_converges_x64(ladder, method):
    """Every cheap ladder must reach ~f64 working accuracy: residuals in
    f64, corrections through the low-precision factor."""
    with jax.enable_x64(True):
        n = 512
        a = spd(n)
        b = a @ np.random.default_rng(1).standard_normal(n)
        rcfg = core.RefineConfig(max_sweeps=8, tol=1e-10, method=method,
                                 gmres_restart=8)
        res = core.refine_solve(a, b, core.PAPER_CONFIGS[ladder],
                                refine=rcfg)
        assert bool(res.converged), float(res.residual)
        assert float(res.residual) <= 1e-10
        relres = (np.linalg.norm(a @ np.asarray(res.x, np.float64) - b)
                  / np.linalg.norm(b))
        assert relres <= 5e-10, relres  # history matches true residual


def test_acceptance_f16_f32_5_sweeps():
    """ISSUE acceptance: 1024x1024 well-conditioned SPD, f16_f32 ladder,
    classic IR hits relative residual <= 1e-10 within 5 sweeps."""
    with jax.enable_x64(True):
        n = 1024
        a = spd(n, seed=3)
        b = a @ np.random.default_rng(3).standard_normal(n)
        res = core.refine_solve(a, b, core.PAPER_CONFIGS["f16_f32"],
                                refine=core.RefineConfig(max_sweeps=5,
                                                         tol=1e-10))
        assert bool(res.converged)
        assert int(res.iterations) <= 5
        assert float(res.residual) <= 1e-10


def test_zero_sweeps_matches_plain_solve():
    n = 384
    a = spd(n, dtype=np.float32, seed=5)
    b = np.random.default_rng(5).standard_normal((n, 3)).astype(np.float32)
    cfg = core.PAPER_CONFIGS["f16_f32"]
    plain = np.asarray(core.cholesky_solve(a, b, cfg))
    res = core.refine_solve(a, b, cfg, refine=0)
    np.testing.assert_array_equal(np.asarray(res.x, np.float32), plain)
    # multi-RHS results are per-column: iterations has shape (k,)
    assert res.iterations.shape == (3,)
    assert (np.asarray(res.iterations) == 0).all()


def test_refine_result_contract():
    n = 256
    a = spd(n, dtype=np.float32, seed=7)
    b = (a @ np.random.default_rng(7).standard_normal(n)).astype(np.float32)
    rcfg = core.RefineConfig(max_sweeps=4, tol=1e-6)
    res = core.refine_solve(a, b, core.PAPER_CONFIGS["pure_f16"],
                            refine=rcfg)
    hist = np.asarray(res.history)
    k = int(res.iterations)
    assert hist.shape == (5,)
    assert np.isfinite(hist[:k + 1]).all()
    assert np.isnan(hist[k + 1:]).all()      # untaken sweeps stay nan
    assert float(res.residual) == np.nanmin(hist)   # best iterate wins
    assert hist[0] > float(res.residual)     # refinement helped
    assert res.x.shape == (n,)


def test_refine_never_degrades_past_floor():
    """At the f32 residual floor (x64 off) refinement stalls; the loop
    must return the BEST iterate and stop early (after two consecutive
    non-improving sweeps), not burn the whole sweep budget."""
    n = 512
    a = spd(n, dtype=np.float32, seed=23)
    b = (a @ np.random.default_rng(23).standard_normal(n)).astype(np.float32)
    # engine pinned: the stall mechanics under test live in _refine_loop
    # (engine-independent); on this seed the blocked engine's solves keep
    # eking out genuine sub-floor improvements and legitimately never
    # trigger the two-sweep stall within the budget.
    cfg = core.PrecisionConfig(levels=("f32",), leaf=128, engine="tree")
    res = core.refine_solve(a, b, cfg,
                            refine=core.RefineConfig(max_sweeps=8,
                                                     tol=1e-12))
    hist = np.asarray(res.history)
    base = hist[0]
    assert float(res.residual) <= base          # never worse than x0
    assert int(res.iterations) < 8              # stall detected early


def _ill_conditioned_spd(n, cond, seed):
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.standard_normal((n, n)))
    a = (q * np.logspace(0, -np.log10(cond), n)) @ q.T
    return (a + a.T) / 2


def test_stall_tolerates_one_flat_sweep():
    """Regression: the loop used to abort after a SINGLE non-improving
    sweep, killing runs whose first sweep/restart is a flat transient.
    Ill-conditioned systems with a non-normal error in the approximate
    inverse (skewed stale preconditioners, GMRES-IR first restarts) do
    exactly this: the residual GROWS on sweep one, then collapses. Two
    consecutive non-improving sweeps are now required to exit."""
    with jax.enable_x64(True):
        n = 64
        a = _ill_conditioned_spd(n, 1e6, seed=3)
        ainv = np.linalg.inv(a)
        # approximate inverse A^{-1}(I - N) with nilpotent skew
        # N = 2 e0 e1^T: the residual iteration is r -> N r, so for
        # r0 = e1 sweep 1 doubles the residual and sweep 2 lands exactly
        nmat = np.zeros((n, n))
        nmat[0, 1] = 2.0
        m = jnp.asarray(ainv @ (np.eye(n) - nmat))
        b = jnp.zeros(n).at[1].set(1.0)
        rcfg = core.RefineConfig(max_sweeps=4, tol=1e-8)
        res = core.refine_operator(lambda x: jnp.asarray(a) @ x,
                                   lambda r: m @ r, b, jnp.zeros(n), rcfg)
        hist = np.asarray(res.history)
        assert hist[1] >= hist[0]       # first sweep is non-improving...
        assert bool(res.converged)      # ...but the run must not abort
        assert int(res.iterations) == 2
        assert float(res.residual) <= 1e-8


def test_stall_exits_diverging_run_with_best_iterate():
    """A genuinely diverging iteration (residual doubling every sweep)
    must exit after exactly two non-improving sweeps with the best
    iterate — not burn max_sweeps."""
    with jax.enable_x64(True):
        n = 64
        a = _ill_conditioned_spd(n, 1e4, seed=5)
        # A @ correct = -I, so r -> 2 r: divergence from sweep one
        m = jnp.asarray(np.linalg.inv(a) @ (-np.eye(n)))
        b = jnp.asarray(np.random.default_rng(5).standard_normal(n))
        x0 = jnp.zeros(n)
        rcfg = core.RefineConfig(max_sweeps=8, tol=1e-12)
        res = core.refine_operator(lambda x: jnp.asarray(a) @ x,
                                   lambda r: m @ r, b, x0, rcfg)
        assert int(res.iterations) == 2          # 2 flat sweeps, then out
        assert not bool(res.converged)
        assert float(res.residual) == np.asarray(res.history)[0]
        np.testing.assert_array_equal(np.asarray(res.x), np.asarray(x0))


def test_multi_rhs_per_column_convergence():
    """Columns with different per-column tolerances converge at
    different sweep counts; converged columns freeze (nan history)
    while slower neighbors keep sweeping."""
    with jax.enable_x64(True):
        n = 256
        a = spd(n)
        b = a @ np.random.default_rng(2).standard_normal((n, 3))
        rcfg = core.RefineConfig(max_sweeps=8, tol=1e-11)
        col_tol = np.array([1e-2, 1e-6, 1e-11])
        res = core.refine_solve(a, b, core.PAPER_CONFIGS["bf16_f32"],
                                refine=rcfg, col_tol=jnp.asarray(col_tol))
        it = np.asarray(res.iterations)
        assert res.residual.shape == (3,) and it.shape == (3,)
        assert bool(np.asarray(res.converged).all())
        assert (np.asarray(res.residual) <= col_tol).all()
        assert it[0] <= it[1] <= it[2] and it[0] < it[2]
        hist = np.asarray(res.history)
        assert np.isnan(hist[it[0] + 1:, 0]).all()   # col 0 froze early
        assert np.isfinite(hist[:it[2] + 1, 2]).all()  # col 2 kept going
        x = np.asarray(res.x)
        for j in range(3):
            rr = (np.linalg.norm(a @ x[:, j] - b[:, j])
                  / np.linalg.norm(b[:, j]))
            assert rr <= col_tol[j] * 1.01, (j, rr)


def test_slow_steady_convergence_is_not_stalled():
    """A run that improves EVERY sweep — however slowly — must never be
    stalled out: stall needs two consecutive sweeps with no new best."""
    with jax.enable_x64(True):
        n = 32
        # A = I, correct = 0.375 I  =>  r' = 0.625 r (a new best each sweep)
        b = jnp.asarray(np.random.default_rng(3).standard_normal(n))
        rcfg = core.RefineConfig(max_sweeps=20, tol=1e-4)
        res = core.refine_operator(lambda x: x, lambda r: 0.375 * r, b,
                                   jnp.zeros(n), rcfg)
        assert bool(res.converged), float(res.residual)
        assert int(res.iterations) == 20


def test_multi_rhs_scaled_solve_is_per_column():
    """Batched columns whose residual magnitudes differ by ~1e6 must
    each converge: a joint absmax scale would underflow the small column
    through the f16 correction path."""
    n = 256
    a = spd(n, dtype=np.float32, seed=33)
    rng = np.random.default_rng(33)
    b = np.stack([a @ rng.standard_normal(n),
                  1e6 * (a @ rng.standard_normal(n))],
                 axis=1).astype(np.float32)
    res = core.refine_solve(a, b, core.PAPER_CONFIGS["f16_f32"],
                            refine=core.RefineConfig(max_sweeps=8,
                                                     tol=1e-6))
    assert bool(np.asarray(res.converged).all()), np.asarray(res.residual)
    x = np.asarray(res.x, np.float64)
    for j in range(2):
        rr = (np.linalg.norm(a @ x[:, j] - b[:, j])
              / np.linalg.norm(b[:, j]))
        assert rr <= 2e-6, (j, rr)


def test_refine_keeps_residual_precision_for_narrow_rhs():
    """cholesky_solve(refine=) returns the residual-precision result: a
    bf16 RHS must NOT round-trip the refined solution back to bf16
    (which would throw away every digit refinement paid for)."""
    n = 256
    a = spd(n, dtype=np.float32, seed=29)
    xt = np.random.default_rng(29).standard_normal(n).astype(np.float32)
    b16 = jnp.asarray(a @ xt, jnp.bfloat16)
    cfg = core.PAPER_CONFIGS["bf16_f32"]
    x = core.cholesky_solve(a, b16, cfg, refine=4)
    assert x.dtype == jnp.float32            # residual precision, not bf16
    rr = (np.linalg.norm(a @ np.asarray(x, np.float64)
                         - np.asarray(b16, np.float64))
          / np.linalg.norm(np.asarray(b16, np.float64)))
    # bf16 eps is ~8e-3; the refined result must be far beyond that
    assert rr < 1e-5, rr


def test_cholesky_solve_refine_param():
    n = 256
    a = spd(n, dtype=np.float32, seed=9)
    b = (a @ np.random.default_rng(9).standard_normal(n)).astype(np.float32)
    cfg = core.PAPER_CONFIGS["bf16_f32"]
    x0 = np.asarray(core.cholesky_solve(a, b, cfg), np.float64)
    xr = np.asarray(core.cholesky_solve(a, b, cfg, refine=3), np.float64)
    r0 = np.linalg.norm(a @ x0 - b) / np.linalg.norm(b)
    rr = np.linalg.norm(a @ xr - b) / np.linalg.norm(b)
    assert rr < r0 / 10, (r0, rr)
    assert xr.shape == (n,) and core.cholesky_solve(
        a, b, cfg, refine=3).dtype == b.dtype


def test_refine_steps_operator():
    """The unrolled hot-path variant K-FAC uses: fixed sweeps against a
    deliberately stale preconditioner still contract the residual."""
    n = 128
    a = spd(n, dtype=np.float32, seed=13)
    stale = a + 0.05 * np.diag(np.abs(np.random.default_rng(13)
                                      .standard_normal(n))).astype(np.float32)
    l = np.linalg.cholesky(stale.astype(np.float64)).astype(np.float32)
    b = (a @ np.random.default_rng(14).standard_normal(n)).astype(np.float32)

    import scipy.linalg as sla

    def correct(r):
        y = sla.solve_triangular(l, np.asarray(r), lower=True)
        return jnp.asarray(sla.solve_triangular(l.T, y))

    matvec = lambda x: jnp.asarray(a) @ x  # noqa: E731
    x0 = correct(b)
    x = core.refine_steps(matvec, core.scaled_solve(correct),
                          jnp.asarray(b), x0, sweeps=4)
    r0 = np.linalg.norm(a @ np.asarray(x0) - b)
    r4 = np.linalg.norm(a @ np.asarray(x) - b)
    assert r4 < r0 / 50, (r0, r4)


def test_kfac_refine_sweeps_improves_whitening():
    """TreeNewtonConfig.refine_sweeps: IR against the CURRENT damped
    stats with a stale cached factor must steer the whitened direction
    toward the true Newton direction (A x ∝ g). Uses the identity
    factor K-FAC starts from — maximally stale — and also smokes the
    full jitted apply() path with refinement on."""
    import jax

    from repro.optim import kfac

    cfg = kfac.TreeNewtonConfig(block=128, refine_sweeps=3)
    cfg0 = kfac.TreeNewtonConfig(block=128, refine_sweeps=0)
    # stats drifted by several EMA steps since the factor was cached
    rng = np.random.default_rng(31)
    a_old = spd(128, dtype=np.float64, seed=31) / 128
    gg = rng.standard_normal((128, 256)) / 16
    a_new = 0.8 * a_old + 0.2 * (gg @ gg.T) / 256
    a_s = jnp.asarray(a_new, jnp.float32)[None]
    l_stale = jnp.asarray(np.linalg.cholesky(
        np.asarray(kfac._damped(jnp.asarray(a_old)[None], cfg))[0]),
        jnp.float32)[None]
    g = jnp.asarray(rng.standard_normal((128, 8)), jnp.float32)

    damped = np.asarray(kfac._damped(a_s, cfg))[0]

    def cos(x):
        ax = (damped @ np.asarray(x)).ravel()
        gf = np.asarray(g).ravel()
        return ax @ gf / (np.linalg.norm(ax) * np.linalg.norm(gf))

    x0 = kfac._whiten(g, l_stale, a_s, cfg0)
    x3 = kfac._whiten(g, l_stale, a_s, cfg)
    # angle error to the exact Newton direction shrinks >=10x
    assert 1 - cos(x3) < (1 - cos(x0)) / 10, (cos(x0), cos(x3))
    assert cos(x3) > 1 - 1e-6, cos(x3)

    params = {"mlp": {"w_in": jnp.zeros((128, 8))}}
    grads = {"mlp": {"w_in": g}}
    state = kfac.init(params, cfg)
    step = jax.jit(lambda gr, s, p: kfac.apply(gr, s, p, cfg))
    p1, s1, _ = step(grads, state, params)
    assert all(np.isfinite(x).all() for x in jax.tree.leaves(p1))


def test_gmres_beats_ir_when_factor_is_poor():
    """GMRES-IR tolerates a preconditioner too weak for classic IR."""
    with jax.enable_x64(True):
        n = 256
        a = spd(n, seed=17)
        # degrade the preconditioner far beyond ladder quality
        noise = np.random.default_rng(17).standard_normal((n, n))
        m_bad = a + 0.35 * (noise @ noise.T) / n
        l = np.linalg.cholesky(m_bad)
        b = a @ np.random.default_rng(18).standard_normal(n)
        cfg = core.PrecisionConfig(levels=("f32",), leaf=128)
        kw = dict(max_sweeps=6, gmres_restart=10)
        ir = core.refine_solve(a, b, cfg, l=l,
                               refine=core.RefineConfig(tol=1e-10, **kw))
        gm = core.refine_solve(
            a, b, cfg, l=l, refine=core.RefineConfig(
                tol=1e-10, method="gmres", **kw))
        assert float(gm.residual) < float(ir.residual) / 10
        assert bool(gm.converged)


def test_solver_engine_targets():
    from repro.serve import SolverEngine
    n = 384
    a = spd(n, dtype=np.float32, seed=21)
    b = (a @ np.random.default_rng(21).standard_normal(n)).astype(np.float32)
    eng = SolverEngine("f16_f32", max_sweeps=8)
    x, info = eng.solve(a, b, target_digits=6.0, cache_key="k")
    assert info.converged and info.residual <= 1e-6
    assert not info.factor_cached
    _, info2 = eng.solve(a, b, target_digits=3.0, cache_key="k")
    assert info2.factor_cached and info2.sweeps <= info.sweeps
    # targets beyond the residual precision clamp instead of spinning
    _, info3 = eng.solve(a, b, target_digits=99.0, cache_key="k")
    assert info3.target_digits <= 14.0
    assert info3.sweeps <= 8
