"""Closed loop, one caller, one solve at a time: time to a refined solve
of a matrix factored across the chips of one host.

The matrix is block-row-sharded over a ``("model",)`` mesh of the run's
devices, made on them from the seed (``harness.gen_sharded``: never
whole on one chip). Each solve is one jitted program of the program's
public entry points: the distributed factor in the mix's ladder
(``repro.core.distributed.dist_cholesky``) and refinement to the mix's
tolerance on it (``dist_refine_solve``), returning both.

Every solve refactors the same A (the pool is one matrix: a second would
not fit beside A and the factor). The caller sends a solve when the
last is answered. It keeps every solve's x, and the factor of the last
solve only: the previous factor is dropped before the next solve is
sent, so at most two factors are alive, the one kept and the one being
made. When the window's time is up it sends nothing more and reads the
clock once the solve it sent is answered.

Traffic keys: ``ladder``, ``pool`` (1), ``nrhs``, ``tol``,
``residual_dtype``, ``max_sweeps``, ``in_flight`` (1).

:func:`control_build` is the control planted in the program's place
(``build=``): the reference's factor and answer held in bfloat16.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from harness import gen_sharded, reference_dist

AXIS = "model"


def build(cfg: dict, mix: dict, mesh):
    """The timed program: ``(a, b) -> (L, RefineResult)``."""
    import dataclasses

    import jax

    from repro import core
    from repro.core.distributed import dist_cholesky, dist_refine_solve
    from repro.core.refine import RefineConfig
    ladder = dataclasses.replace(core.PAPER_CONFIGS[mix["ladder"]],
                                 leaf=cfg["leaf"])
    rcfg = RefineConfig(max_sweeps=mix["max_sweeps"], tol=mix["tol"],
                        residual_dtype=mix["residual_dtype"])

    def solve(a, b):
        l = dist_cholesky(a, mesh, ladder, AXIS)
        return l, dist_refine_solve(a, b, mesh, ladder, AXIS, refine=rcfg,
                                    l=l)

    return jax.jit(solve)


class Answer(NamedTuple):
    """What :func:`window` reads of a solve: the answer and its sweeps."""

    x: object
    iterations: object


def control_build(cfg: dict, mix: dict, mesh):
    """The timed program replaced by the control: ``(a, b) -> (L,
    Answer)``, factor and answer held in bfloat16
    (``harness.reference_dist``)."""
    import jax
    import jax.numpy as jnp

    def prog(a, b):
        l = reference_dist.control_factor(a, mesh, AXIS)
        return l, Answer(reference_dist.control_solve(l, b, mesh, AXIS),
                         jnp.zeros(b.shape[1], jnp.int32))
    return jax.jit(prog)


def mesh_of(devices):
    from jax.sharding import Mesh
    return Mesh(np.array(devices), (AXIS,))


def setup(run):
    import jax
    cfg, mix = run.config, run.traffic
    assert mix["pool"] == 1 and mix["in_flight"] == 1, mix
    mesh = mesh_of(run.devices)
    # the program first: a checkout without the distributed entry points
    # fails here, before any data is made
    prog = (run.build or build)(cfg, mix, mesh)
    a = gen_sharded.spd(run.seed, cfg["n"], mesh, AXIS)
    b = gen_sharded.rhs(run.seed, cfg["n"], mix["nrhs"], mesh, AXIS)
    jax.block_until_ready(prog(a, b))       # compiles, then warms
    return {"a": a, "b": b, "prog": prog, "mesh": mesh}


def window(run, st, seconds: float) -> dict:
    import jax
    a, b, prog = st["a"], st["b"], st["prog"]
    solved, l, out = [], None, None
    t0 = run.clock()
    t_end = t0 + seconds
    i = 0
    while run.clock() < t_end:
        l = out = None                        # drop the last factor
        with run.span("bench.solve"):
            out = prog(a, b)
        i += 1
        with run.span("bench.wait"):
            l, res = jax.block_until_ready(out)
        solved.append(res)
    t = run.clock()
    its = np.concatenate([np.atleast_1d(np.asarray(r.iterations))
                          for r in solved])
    return {"solves": i, "solved": solved, "l": l,
            "e2e": {"solve_ms": (t - t0) / i * 1e3},
            "counters": {"refine.sweeps": float(its.mean())}}


def check(run, st, stats) -> dict:
    """Every answer of the window by the reference's residual against
    A and B, and the kept factor by its backward error."""
    st.pop("prog")
    a, b = st["a"], st["b"]
    limits = run.traffic["limits"]
    worst, failed = 0.0, 0
    for res in stats["solved"]:
        rel = float(reference_dist.relative_residuals(a, res.x, b).max())
        rel = rel if np.isfinite(rel) else float("inf")
        worst = max(worst, rel)
        failed += rel > limits["residual_max"]
    bwd = reference_dist.backward_error(a, stats["l"], st["mesh"], AXIS)
    return {"attempted": stats["solves"], "failed": failed,
            "checks": [("residual_max", worst, limits["residual_max"]),
                       ("backward_error_max", bwd,
                        limits["backward_error_max"])]}
