"""Closed loop, one caller: time to a refined solve of a matrix not
seen before.

Each solve is one jitted program of the program's public entry points:
the factor in the cell's ladder (``repro.core.cholesky_padded``) and
refinement to the mix's tolerance on it (``repro.core.refine_solve``),
returning both. Consecutive solves take consecutive entries of a pool
of (A, B) pairs made on the device at set-up, so no two in a row share
a matrix.

The caller keeps ``in_flight`` solves dispatched and waits on the
oldest, so the chip has work queued while the host stands still. When
the window's time is up it sends nothing more, waits for every solve it
sent, and reads the clock after that wait: every solve sent counts,
over all of that time.

Traffic keys: ``ladder``, ``pool``, ``nrhs``, ``tol``,
``residual_dtype``, ``max_sweeps``, ``in_flight``, ``factor_sample``
(factors kept for the backward-error check, drawn from the seed).
"""
from __future__ import annotations

import collections

import numpy as np

from harness import gen, reference


def build(cfg: dict, mix: dict):
    """The timed program: ``(a, b) -> (L, RefineResult)``."""
    import jax

    from repro import core
    from repro.core.refine import RefineConfig
    ladder = core.PAPER_CONFIGS[mix["ladder"]]
    rcfg = RefineConfig(max_sweeps=mix["max_sweeps"], tol=mix["tol"],
                        residual_dtype=mix["residual_dtype"])

    def solve(a, b):
        l = core.cholesky_padded(a, ladder)
        return l, core.refine_solve(a, b, ladder, refine=rcfg, l=l)

    return jax.jit(solve)


def setup(run):
    import jax
    cfg, mix = run.config, run.traffic
    pool = gen.matrix_pool(run.seed, cfg["n"], mix["pool"], mix["nrhs"])
    prog = run.build(cfg, mix) if run.build else build(cfg, mix)
    for a, b in pool:                      # compiles, then warms each entry
        jax.block_until_ready(prog(a, b))
    return {"pool": pool, "prog": prog}


def window(run, st, seconds: float) -> dict:
    import jax
    pool, prog = st["pool"], st["prog"]
    depth = run.traffic["in_flight"]
    rng = np.random.default_rng([run.seed, 7])
    keep = run.traffic["factor_sample"]
    sample: list = []                       # reservoir of (index, L)
    solved = []                             # (pool index, RefineResult)
    pending = collections.deque()           # (solve number, index, (L, res))

    def finish():
        k, idx, out = pending.popleft()
        with run.span("bench.wait"):
            l, res = jax.block_until_ready(out)
        solved.append((idx, res))
        if len(sample) < keep:
            sample.append((idx, l))
        else:
            j = int(rng.integers(0, k + 1))
            if j < keep:
                sample[j] = (idx, l)

    t0 = run.clock()
    t_end = t0 + seconds
    i = 0
    while run.clock() < t_end:
        with run.span("bench.solve"):
            pending.append((i, i % len(pool), prog(*pool[i % len(pool)])))
        i += 1
        if len(pending) >= depth:
            finish()
    while pending:
        finish()
    t = run.clock()
    its = np.concatenate([np.atleast_1d(np.asarray(r.iterations))
                          for _, r in solved])
    return {"solves": i, "solved": solved, "sample": sample,
            "e2e": {"solve_ms": (t - t0) / i * 1e3},
            "counters": {"refine.sweeps": float(its.mean())}}


def check(run, st, stats) -> list:
    """Every answer of the window against its own (A, B) by the
    reference's residual, and the sampled factors by their backward
    error."""
    st.pop("prog")
    pool = st["pool"]
    limits = run.traffic["limits"]
    worst, failed = 0.0, 0
    for idx, res in stats["solved"]:
        a, b = pool[idx]
        rel = float(reference.relative_residuals(a, res.x, b).max())
        worst = max(worst, rel)
        failed += rel > limits["residual_max"]
    bwd = max(reference.backward_error(pool[idx][0], l[:run.config["n"],
                                                      :run.config["n"]])
              for idx, l in stats["sample"])
    return {"attempted": stats["solves"], "failed": failed,
            "checks": [("residual_max", worst, limits["residual_max"]),
                       ("backward_error_max", bwd,
                        limits["backward_error_max"])]}
