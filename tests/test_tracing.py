"""Program spans on the serving host path, and named scopes in the
compiled programs.

The continuous scheduler marks its host work with
``jax.profiler.TraceAnnotation`` spans named ``repro.*`` (docs/SERVING.md,
"Tracing"), each per-request span carrying the request ids as ``rid``;
the factorization, solves and refinement carry ``jax.named_scope``
scopes into the compiled HLO's ``op_name`` metadata.
"""
from __future__ import annotations

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import core
from repro.core.refine import RefineConfig
from repro.serve import (BatchScheduler, InMemoryMetrics, SolveOptions,
                         SolverEngine)

N = 512


def _spd(n=N, seed=0):
    rng = np.random.default_rng(seed)
    m = rng.uniform(-1.0, 1.0, (n, n))
    return ((m + m.T) / 2 + n * np.eye(n)).astype(np.float32)


def _program_spans(trace_dir):
    """``[(name, thread line, start_ns, end_ns, rid)]`` of the ``repro.``
    host spans in the trace written under ``trace_dir``."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("repro."):
                    rid = dict(ev.stats).get("rid")
                    out.append((ev.name, i, ev.start_ns, ev.end_ns,
                                None if rid is None else str(rid)))
    return out


@pytest.fixture(scope="module")
def traced_serving(tmp_path_factory):
    """Four requests, one after another, through a continuous
    scheduler at n = 512, the whole exchange traced."""
    a = _spd()
    rng = np.random.default_rng(1)
    bs = [jnp.asarray(rng.standard_normal(N), jnp.float32)
          for _ in range(5)]
    sch = BatchScheduler(SolverEngine("bf16_f32"), max_batch=4,
                         continuous=True)
    opts = SolveOptions(target_digits=6.0, cache_key="m")
    sch.start()
    try:
        sch.submit_async(a, bs[4], opts).result(timeout=300)  # warm
        out = str(tmp_path_factory.mktemp("trace"))
        jax.profiler.start_trace(out)
        try:
            for b in bs[:4]:
                x, info = sch.submit_async(a, b, opts).result(timeout=300)
                jax.block_until_ready(x)
        finally:
            jax.profiler.stop_trace()
    finally:
        sch.stop()
    return _program_spans(out)


def test_every_request_is_admitted_and_retired_once(traced_serving):
    spans = traced_serving
    admits = [s for s in spans if s[0] == "repro.sched.admit"]
    retires = [s for s in spans if s[0] == "repro.sched.retire"]
    # the warm-up request was 0; the traced ones are 1-4
    assert sorted(s[4] for s in admits) == ["1", "2", "3", "4"]
    assert sorted(s[4] for s in retires) == ["1", "2", "3", "4"]
    # all on the scheduler's worker thread, each retire after its admit
    assert len({s[1] for s in admits + retires}) == 1
    by_rid = {s[4]: s for s in admits}
    for r in retires:
        assert r[2] >= by_rid[r[4]][3]
    # (whether the worker waits between two requests is a race with
    # the caller, so repro.sched.wait is not asked for)
    names = {s[0] for s in spans}
    assert {"repro.sched.step", "repro.sched.masks"} <= names


def test_base_solve_and_join_nest_inside_admit(traced_serving):
    spans = traced_serving
    admits = [s for s in spans if s[0] == "repro.sched.admit"]
    for name in ("repro.solve.base", "repro.refine.join"):
        inner = [s for s in spans if s[0] == name]
        assert len(inner) == len(admits), name
        for s in inner:
            assert any(a[1] == s[1] and a[2] <= s[2] and s[3] <= a[3]
                       for a in admits), name


def test_occupancy_gauge_reads_the_slot_table():
    """Untraced, the loop serves as before, and the occupancy gauge
    (from the host's slot table, no device sync) reads one live column
    of four slots."""
    a = _spd(seed=2)
    mt = InMemoryMetrics()
    sch = BatchScheduler(SolverEngine("bf16_f32", metrics=mt), max_batch=4,
                         continuous=True)
    sch.start()
    try:
        x, info = sch.submit_async(
            a, np.ones(N, np.float32),
            SolveOptions(target_digits=5.0, cache_key="m")).result(
                timeout=300)
    finally:
        sch.stop()
    assert info.converged
    assert mt.snapshot()["gauges"]["scheduler.slot_occupancy"] == 0.25


def _op_names(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = []
    for line in text.splitlines():
        if 'op_name="' in line:
            names.append(line.split('op_name="', 1)[1].split('"', 1)[0])
    return names


def test_named_scopes_reach_the_compiled_program():
    cfg = core.PAPER_CONFIGS["bf16_f32"]
    rcfg = RefineConfig(max_sweeps=2, tol=1e-6, residual_dtype="f32")

    def solve(a, b):
        l = core.cholesky_padded(a, cfg)
        return l, core.refine_solve(a, b, cfg, refine=rcfg, l=l)

    a = jax.ShapeDtypeStruct((N, N), jnp.float32)
    b = jax.ShapeDtypeStruct((N, 2), jnp.float32)
    names = _op_names(solve, a, b)
    for scope in ("factor/diag", "factor/panel", "factor/write",
                  "refine/solve", "/sweep/"):
        assert any(scope in n for n in names), scope
