"""Continuous-batching serving: slot loop, shedding, options, metrics.

The load-bearing contract is DETERMINISM: classic IR is column-local
(per-column scaling, residuals and corrections), so a column's refinement
trajectory must be identical whether it runs in a window
(``SolverEngine.solve_batched``) or through the re-entrant slot loop
(``BatchScheduler(continuous=True)``), regardless of co-tenants or when
it joined. Everything else — mid-flight join, retire-once, deadlines,
tiered shedding, the SolveOptions redesign and the metrics layer — is
pinned around that.
"""
from __future__ import annotations

import threading
import warnings

import jax
import numpy as np
import pytest

from repro.serve import (BatchScheduler, InMemoryMetrics, MetricsTracker,
                         NullMetrics, SchedulerOverload, ServeFrontend,
                         SolveOptions, SolverEngine)

N = 64


def _spd(n=N, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.standard_normal((n, n))
    return (m @ m.T + n * np.eye(n)).astype(np.float32)


def _rhs(a, seed=0, k=None):
    rng = np.random.default_rng(100 + seed)
    shape = (a.shape[0],) if k is None else (a.shape[0], k)
    return (a @ rng.standard_normal(shape)).astype(np.float32)


@pytest.fixture(scope="module")
def eng():
    return SolverEngine("f16_f32", max_sweeps=8,
                        metrics=InMemoryMetrics())


# ---------------------------------------------------------------------------
# determinism: continuous == window, column for column
# ---------------------------------------------------------------------------
def test_continuous_matches_window_column_for_column(eng):
    """4 mixed-target requests through a 2-slot continuous loop (so two
    of them MUST join mid-flight) vs one windowed stacked call: same x,
    same sweep counts, same per-column residual histories."""
    a = _spd(seed=1)
    bs = [_rhs(a, seed=i) for i in range(4)]
    targets = [3.0, 6.0, 3.0, 6.0]

    xs_w, infos_w = eng.solve_batched(
        a, bs, SolveOptions(target_digits=targets, cache_key="det"))

    sch = BatchScheduler(eng, max_batch=2, continuous=True)
    sch.start()
    futs = [sch.submit_async(a, b, SolveOptions(target_digits=t,
                                                cache_key="det"))
            for b, t in zip(bs, targets)]
    outs = [f.result(timeout=120) for f in futs]
    sch.stop()

    for i, ((x_c, info_c), x_w, info_w) in enumerate(zip(outs, xs_w,
                                                         infos_w)):
        assert np.array_equal(np.asarray(x_c), np.asarray(x_w)), i
        assert info_c.sweeps == info_w.sweeps, i
        assert info_c.converged and info_w.converged, i
        assert info_c.history == info_w.history, i
        assert info_c.residual == pytest.approx(info_w.residual), i


def test_continuous_blockwidth_invariance(eng):
    """A request's result must not depend on the slot-block width it ran
    in (widths >= 2 share the GEMM kernel, so per-column results are
    bitwise equal; width 1 lowers to a GEMV and is out of scope)."""
    a = _spd(seed=2)
    b = _rhs(a, seed=9)
    outs = []
    for slots in (2, 4):
        sch = BatchScheduler(eng, max_batch=slots, continuous=True)
        sch.start()
        fut = sch.submit_async(a, b, SolveOptions(target_digits=6.0,
                                                  cache_key="width"))
        outs.append(fut.result(timeout=120))
        sch.stop()
    (x2, i2), (x4, i4) = outs
    assert np.array_equal(np.asarray(x2), np.asarray(x4))
    assert i2.history == i4.history


# ---------------------------------------------------------------------------
# the continuous base solve: one program per join width, factor an argument
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 2, 3])
def test_base_solve_matches_window_x0(eng, k):
    """The stepper's compiled base solve gives the windowed path's
    initial iterate bit for bit. A 1-digit target is met before any
    sweep, so the windowed call returns its x0 unchanged."""
    a = _spd(seed=11)
    blk = _rhs(a, seed=k, k=k)
    (x_w,), (info,) = eng.solve_batched(
        a, [blk], SolveOptions(target_digits=1.0, cache_key="x0"))
    assert info.sweeps == 0
    stepper, base_solve, _ = eng.continuous_stepper(a, slots=3,
                                                    cache_key="x0")
    x0 = base_solve(blk.astype(stepper.rdtype))
    assert np.array_equal(np.asarray(x0), np.asarray(x_w))


def test_base_solve_compiles_counts_join_widths():
    """``engine.base_solve_compiles`` counts each join width a stepper
    meets once: two lone admits share width 1, a 2-column block adds
    width 2."""
    a = _spd(seed=12)
    mt = InMemoryMetrics()
    eng2 = SolverEngine("f16_f32", max_sweeps=8, metrics=mt)
    sch = BatchScheduler(eng2, max_batch=4, continuous=True)
    sch.start()
    opts = SolveOptions(target_digits=4.0, cache_key="compiles")
    try:
        for i in range(2):
            sch.submit_async(a, _rhs(a, seed=i), opts).result(timeout=120)
        assert mt.snapshot()["counters"]["engine.base_solve_compiles"] == 1
        sch.submit_async(a, _rhs(a, seed=2, k=2), opts).result(timeout=120)
        assert mt.snapshot()["counters"]["engine.base_solve_compiles"] == 2
    finally:
        sch.stop()


def test_base_solve_takes_the_factor_as_an_argument(eng):
    """The compiled base solve's module takes ``l`` and ``linvs`` as
    entry arguments and holds no constant of their shapes. Closing over
    them instead writes them in as dense constants, which the same check
    catches. Two leaves, so every panel reads the factor."""
    from repro.serve.engine import _base_solve

    n = 2 * eng.cfg.leaf
    a = _spd(n=n, seed=13)
    l, linvs, _ = eng.factor(a, cache_key="hlo")
    cfg = eng._cfg_for(n)
    r = np.ones((n, 1), np.float32)
    types = [f"tensor<{'x'.join(map(str, x.shape))}x"
             for x in jax.tree.leaves((l, linvs))]

    def constants(text):
        return [t for t in types for ln in text.splitlines()
                if "stablehlo.constant" in ln and t in ln]

    text = _base_solve.lower(l, linvs, r, cfg=cfg).as_text()
    main = next(ln for ln in text.splitlines() if "func.func public" in ln)
    assert all(t in main for t in types), main   # entry arguments
    assert not constants(text)
    assert len(text) < l.size                    # no element written out

    closed = jax.jit(lambda rr: _base_solve(l, linvs, rr, cfg=cfg))
    assert sorted(set(constants(closed.lower(r).as_text()))) == sorted(types)


# ---------------------------------------------------------------------------
# stepper-level: mid-flight join, retire-once
# ---------------------------------------------------------------------------
def test_midflight_join_preserves_histories(eng):
    """A column joining two sweeps into a stranger's run must follow the
    exact trajectory it has when running alone in the same slot block —
    co-tenancy (who else occupies the block, and when they joined) must
    not perturb a column."""
    a = _spd(seed=3)
    b0, b1 = _rhs(a, seed=0), _rhs(a, seed=1)
    stepper, base_solve, _ = eng.continuous_stepper(a, slots=3,
                                                    cache_key="join")
    tol = 1e-12                       # unreachable: run both to stall

    def prep(b):
        bb = np.asarray(b, np.float32)[:, None]
        return bb, base_solve(bb.astype(stepper.rdtype))

    def solo(b, slot):
        """Reference: the column alone in an otherwise-empty block."""
        bb, x0 = prep(b)
        state = stepper.init()
        state = stepper.join(state, [slot], bb, x0, [tol])
        hist = [float(np.asarray(state.rel)[slot])]
        while stepper.active_mask(state).any():
            state, _ = stepper.step(state)
            hist.append(float(np.asarray(state.rel)[slot]))
        return tuple(hist)

    ref0, ref1 = solo(b0, 0), solo(b1, 1)

    state = stepper.init()
    bb0, x00 = prep(b0)
    state = stepper.join(state, [0], bb0, x00, [tol])
    hist = {0: [float(np.asarray(state.rel)[0])], 1: []}
    for _ in range(2):                # col 0 runs alone for two sweeps
        state, act = stepper.step(state)
        assert act[0] and not act[1]
        hist[0].append(float(np.asarray(state.rel)[0]))
    bb1, x01 = prep(b1)
    state = stepper.join(state, [1], bb1, x01, [tol])   # mid-flight join
    hist[1].append(float(np.asarray(state.rel)[1]))
    while stepper.active_mask(state).any():
        state, act = stepper.step(state)
        rel = np.asarray(state.rel)
        for s in (0, 1):
            if act[s]:
                hist[s].append(float(rel[s]))
    assert tuple(hist[0]) == ref0
    assert tuple(hist[1]) == ref1


def test_retired_slots_never_recompute(eng):
    """A retired slot is inert: cleared, excluded from the active mask,
    and untouched by later sweeps until a new column joins it."""
    a = _spd(seed=4)
    stepper, base_solve, _ = eng.continuous_stepper(a, slots=2,
                                                    cache_key="retire")
    bb = np.asarray(_rhs(a, seed=0), np.float32)[:, None]
    state = stepper.init()
    state = stepper.join(state, [0], bb,
                         base_solve(bb.astype(stepper.rdtype)), [1e-6])
    while not stepper.done_mask(state).any():
        state, _ = stepper.step(state)
    state, [(x, relres, sweeps, conv)] = stepper.retire(state, [0])
    assert conv and relres <= 1e-6 and sweeps >= 1
    assert not np.asarray(state.occ)[0]
    assert np.asarray(state.its)[0] == 0
    assert not np.asarray(state.x[:, 0]).any()    # cleared
    # join a second column into slot 1 and sweep: slot 0 must stay inert
    b2 = np.asarray(_rhs(a, seed=1), np.float32)[:, None]
    state = stepper.join(state, [1], b2,
                         base_solve(b2.astype(stepper.rdtype)), [1e-6])
    state, act = stepper.step(state)
    assert not act[0] and act[1]
    assert np.asarray(state.its)[0] == 0
    assert not np.asarray(state.x[:, 0]).any()


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------
def test_deadline_expiry_returns_best_so_far(eng):
    """deadline_ms=0 expires before the first sweep: the request comes
    back immediately with its initial iterate, marked, not converged."""
    a = _spd(seed=5)
    b = _rhs(a, seed=0)
    sch = BatchScheduler(eng, max_batch=2, continuous=True)
    sch.start()
    fut = sch.submit_async(a, b, SolveOptions(
        target_digits=6.0, deadline_ms=0.0, cache_key="dead"))
    x, info = fut.result(timeout=120)
    sch.stop()
    assert info.deadline_expired and not info.converged
    assert info.sweeps == 0
    assert len(info.history[0]) == 1          # rel0 only, no sweeps ran
    assert info.residual == pytest.approx(info.history[0][0])
    # best-so-far == the base (factored) solve's initial iterate
    stepper, base_solve, _ = eng.continuous_stepper(a, slots=2,
                                                    cache_key="dead")
    x0 = base_solve(np.asarray(b, np.float32)[:, None].astype(
        stepper.rdtype))
    assert np.array_equal(np.asarray(x), np.asarray(x0)[:, 0])


# ---------------------------------------------------------------------------
# tiered shedding (frontend)
# ---------------------------------------------------------------------------
class _StubScheduler:
    def __init__(self):
        self.metrics = InMemoryMetrics()
        self.depth = 0
        self.seen: list[SolveOptions] = []

    def pending_cols(self):
        return self.depth

    def submit_async(self, a, b, options):
        self.seen.append(options)
        return "future"


def test_shedding_tier_boundaries():
    sch = _StubScheduler()
    fe = ServeFrontend(sch, soft_pending=2, hard_pending=4,
                       degraded_digits=4.0)
    # tier 0: below soft — request passes through untouched
    sch.depth = 1
    fe.submit(None, None, SolveOptions(target_digits=7.0))
    assert sch.seen[-1].target_digits == 7.0
    assert sch.seen[-1].shed_tier == 0
    # tier 1: [soft, hard) — degrade the target, stamp the tier
    for depth in (2, 3):
        sch.depth = depth
        fe.submit(None, None, SolveOptions(target_digits=7.0))
        assert sch.seen[-1].target_digits == 4.0
        assert sch.seen[-1].shed_tier == 1
    # a request already below the degraded floor keeps its own target
    fe.submit(None, None, SolveOptions(target_digits=3.0))
    assert sch.seen[-1].target_digits == 3.0
    # tier 2: at/above hard — reject
    sch.depth = 4
    with pytest.raises(SchedulerOverload):
        fe.submit(None, None, SolveOptions(target_digits=7.0))
    m = sch.metrics
    assert m.counter("frontend.shed", tier=1) == 3
    assert m.counter("frontend.shed", tier=2) == 1
    assert m.counter("frontend.requests") == 5


def test_frontend_end_to_end_degrades(eng):
    """Against a real continuous scheduler: a backlogged queue degrades
    the admitted request and its SolveInfo says so."""
    a = _spd(seed=6)
    sch = BatchScheduler(eng, max_batch=2, continuous=True)
    fe = ServeFrontend(sch, soft_pending=1, hard_pending=64)
    sch.start()
    opts = SolveOptions(target_digits=7.0, cache_key="fe")
    futs = [fe.submit(a, _rhs(a, seed=i), opts) for i in range(6)]
    outs = [f.result(timeout=120) for f in futs]
    sch.stop()
    tiers = [info.shed_tier for _, info in outs]
    assert tiers[0] == 0
    assert 1 in tiers                 # backlog built up -> some degraded
    for _, info in outs:
        if info.shed_tier == 1:
            assert info.target_digits == pytest.approx(4.0)
            assert info.converged


# ---------------------------------------------------------------------------
# stop() vs submit race
# ---------------------------------------------------------------------------
def test_stop_after_submit_completes_or_raises(eng):
    """A submission racing stop() must either resolve its future or
    raise at submission — never hang or vanish (the silent-drop bug)."""
    a = _spd(seed=7)
    opts = SolveOptions(target_digits=3.0, cache_key="race")
    for round_ in range(5):
        sch = BatchScheduler(eng, max_batch=4, continuous=True)
        sch.start()
        futs, rejected = [], []

        def submitter():
            for i in range(4):
                try:
                    futs.append(sch.submit_async(a, _rhs(a, seed=i), opts))
                except (RuntimeError, AssertionError):
                    # stop won the race: refused loudly, never dropped
                    rejected.append(i)
                    break

        t = threading.Thread(target=submitter)
        t.start()
        sch.stop()
        t.join()
        for f in futs:                    # accepted => must resolve
            x, info = f.result(timeout=120)
            assert info.converged
        assert len(futs) + len(rejected) >= 1


def test_submit_async_raises_while_stopping(eng):
    """Deterministic half of the race: once the stop flag is up, new
    submissions are refused loudly instead of queued into the void."""
    a = _spd(seed=8)
    sch = BatchScheduler(eng, max_batch=2, continuous=True)
    sch.start()
    with sch._cv:
        sch._stop_flag = True             # worker not yet exited
        with pytest.raises(RuntimeError, match="stopping"):
            sch.submit_async(a, _rhs(a), SolveOptions(cache_key="x"))
        sch._stop_flag = False
    sch.stop()


# ---------------------------------------------------------------------------
# SolveOptions redesign: deprecated aliases
# ---------------------------------------------------------------------------
def test_deprecated_kwargs_warn_and_work(eng):
    a = _spd(seed=9)
    b = _rhs(a)
    with pytest.warns(DeprecationWarning, match="SolveOptions"):
        x_old, info_old = eng.solve(a, b, target_digits=5.0,
                                    cache_key="dep")
    with warnings.catch_warnings():
        warnings.simplefilter("error")    # options path must be silent
        x_new, info_new = eng.solve(a, b, SolveOptions(
            target_digits=5.0, cache_key="dep"))
    assert np.array_equal(np.asarray(x_old), np.asarray(x_new))
    assert info_old.sweeps == info_new.sweeps

    sch = BatchScheduler(eng, max_batch=4)
    with pytest.warns(DeprecationWarning):
        rid = sch.submit(a, b, target_digits=5.0, cache_key="dep")
    out = sch.drain()
    assert out[rid][1].converged


def test_unknown_kwarg_raises_typeerror(eng):
    a = _spd(seed=9)
    with pytest.raises(TypeError, match="SolveOptions"):
        eng.solve(a, _rhs(a), targets_digit=5.0)     # typo'd name


def test_options_validation():
    with pytest.raises(AssertionError):
        SolveOptions(method="qr")
    with pytest.raises(AssertionError):
        SolveOptions(shed_tier=3)
    with pytest.raises(AssertionError):
        SolveOptions(deadline_ms=-1.0)


# ---------------------------------------------------------------------------
# metrics layer
# ---------------------------------------------------------------------------
def test_metrics_protocol_and_emission():
    assert isinstance(InMemoryMetrics(), MetricsTracker)
    assert isinstance(NullMetrics(), MetricsTracker)

    a = _spd(seed=10)
    mt = InMemoryMetrics()
    eng2 = SolverEngine("f16_f32", max_sweeps=8, metrics=mt)
    sch = BatchScheduler(eng2, max_batch=2, continuous=True)
    assert sch.metrics is mt              # tracker chains down the stack
    sch.start()
    futs = [sch.submit_async(a, _rhs(a, seed=i),
                             SolveOptions(target_digits=4.0,
                                          cache_key="m"))
            for i in range(3)]
    for f in futs:
        f.result(timeout=120)
    sch.stop()
    snap = mt.snapshot()
    c = snap["counters"]
    assert c["scheduler.requests"] == 3
    assert c["engine.factor_cache_miss"] >= 1
    assert c["scheduler.sweeps"] >= 1
    assert snap["observations"]["scheduler.queue_ms"]["count"] == 3
    assert 0 < snap["gauges"]["scheduler.slot_occupancy"] <= 1.0
    assert any(k.startswith("scheduler.requests") for k in snap["rates"])
