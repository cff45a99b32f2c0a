"""Run the solver's main path once on a TPU and check what comes out.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the four-chip path only

One chip, in one process, with the Pallas kernels compiled for the chip:

* factor: the paper's SPD test matrix (uniform entries in [-1, 1],
  symmetrized, plus n on the diagonal; paper section IV-A) is made on
  the device from ``--seed`` at n = 16384 in f32 and factored by
  ``repro.core.cholesky`` on the bf16_f32, f16_f32 and int8_f32 ladders.
  Each factor's backward error ||A - L L^T||_F / ||A||_F, computed with
  XLA matmuls at HIGHEST precision, must stay within the unit roundoff
  of the ladder's low level, and the compiled program must contain the
  Pallas kernels (``tpu_custom_call``);
* refine: ``repro.core.refine_solve`` on f16_f32 with 8 right-hand sides
  must bring every column to a relative residual of 1e-5;
* serve: a ``SolverEngine("bf16_f32")`` behind a continuous
  ``BatchScheduler`` answers 8 requests sharing one cache key at
  n = 8192 with ``target_digits=5``; every answer is checked.

Four chips (``--four-chips``): ``dist_cholesky`` of the n = 16384 matrix
on a 4-device mesh, through ``SolverEngine(mesh=)``, compared with the
single-chip ``repro.core.cholesky`` of the same matrix, then a refined
distributed solve of 8 right-hand sides.

A failed check raises, and the script exits non-zero. With no TPU (JAX
sees only CPU devices), outside a checkout of this repository, or with
``REPRO_KERNEL_IMPL`` set to anything but ``pallas``, it exits non-zero
and prints no result. The last line of standard output, printed only
when every phase passed, is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

N_FACTOR = 16384
N_SERVE = 8192
NRHS = 8
LADDERS = ("bf16_f32", "f16_f32", "int8_f32")
#: unit roundoff of each ladder's low level, the bound on the factor's
#: backward error: bf16 keeps 8 significand bits (2^-8), f16 keeps 11
#: (2^-11), int8 rounds to half a step of absmax/127 (1/254 of absmax)
UNIT_ROUNDOFF = {"bf16_f32": 2.0 ** -8, "f16_f32": 2.0 ** -11,
                 "int8_f32": 1.0 / 254.0}
#: relative residual the refined solves must reach
TARGET = 1e-5
#: ladder of the four-chip path (the serving default)
DIST_LADDER = "bf16_f32"
#: text in a compiled program that shows a Pallas kernel compiled for
#: the chip (and not the jnp oracle) ran
KERNEL_MARK = "tpu_custom_call"


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)
    print(f"  ok: {what}", flush=True)


def spd_matrix(n: int, seed: int, sharding=None):
    """The paper's test matrix, made on the device: uniform entries in
    [-1, 1], symmetrized, plus ``n`` on the diagonal."""
    import jax
    import jax.numpy as jnp

    def make():
        m = jax.random.uniform(jax.random.key(seed), (n, n), jnp.float32,
                               -1.0, 1.0)
        return (m + m.T) * 0.5 + n * jnp.eye(n, dtype=jnp.float32)

    return jax.jit(make, out_shardings=sharding)()


def backward_error(a, l):
    """||A - L L^T||_F / ||A||_F with the product at HIGHEST precision."""
    import jax
    import jax.numpy as jnp

    def err(a, l):
        llt = jnp.matmul(l, l.T, precision=jax.lax.Precision.HIGHEST)
        return jnp.linalg.norm(a - llt) / jnp.linalg.norm(a)

    return float(jax.jit(err)(a, l))


def relative_difference(x, y) -> float:
    """||x - y||_F / ||y||_F."""
    import jax
    import jax.numpy as jnp
    return float(jax.jit(
        lambda x, y: jnp.linalg.norm(x - y) / jnp.linalg.norm(y))(x, y))


def relative_residuals(a, x, b):
    """Per-column ||b - A x||_2 / ||b||_2 with A x at HIGHEST precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def rel(a, x, b):
        r = b - jnp.matmul(a, x, precision=jax.lax.Precision.HIGHEST)
        return jnp.linalg.norm(r, axis=0) / jnp.linalg.norm(b, axis=0)

    return np.asarray(jax.jit(rel)(a, x, b))


def compile_cholesky(a, ladder: str):
    """Compile ``repro.core.cholesky`` for ``a``; returns the executable
    after checking that the Pallas kernels are in it."""
    import jax

    from repro import core
    cfg = core.PAPER_CONFIGS[ladder]
    t0 = time.perf_counter()
    compiled = jax.jit(lambda x: core.cholesky(x, cfg)).lower(a).compile()
    print(f"  compile {ladder} n={a.shape[0]}: "
          f"{time.perf_counter() - t0:.1f} s (host clock, set-up)")
    check(KERNEL_MARK in compiled.as_text(),
          f"{ladder}: compiled factor contains {KERNEL_MARK}")
    return compiled


def factor_phase(a) -> None:
    import jax
    n = a.shape[0]
    for ladder in LADDERS:
        print(f"factor {ladder} n={n}", flush=True)
        compiled = compile_cholesky(a, ladder)
        print(f"  memory_analysis: {compiled.memory_analysis()}")
        t0 = time.perf_counter()
        l = jax.block_until_ready(compiled(a))
        print(f"  first call: {time.perf_counter() - t0:.3f} s (host clock)")
        err = backward_error(a, l)
        bound = UNIT_ROUNDOFF[ladder]
        print(f"  backward error {err:.6e} (bound {bound:.6e})")
        check(err <= bound, f"{ladder}: backward error within the "
              "unit roundoff of the ladder's low level")
        del l


def refine_phase(a, seed: int) -> None:
    import jax

    from repro import core
    from repro.core.refine import RefineConfig
    n = a.shape[0]
    print(f"refine f16_f32 n={n} nrhs={NRHS} target={TARGET}", flush=True)
    b = jax.random.normal(jax.random.key(seed + 1), (n, NRHS))
    cfg = core.PAPER_CONFIGS["f16_f32"]
    rcfg = RefineConfig(max_sweeps=10, tol=TARGET, residual_dtype="f32")
    solve = jax.jit(lambda a, b: core.refine_solve(a, b, cfg, refine=rcfg))
    t0 = time.perf_counter()
    res = jax.block_until_ready(solve(a, b))
    print(f"  compile + solve: {time.perf_counter() - t0:.1f} s (host clock)")
    print(f"  sweeps per column: {[int(i) for i in res.iterations]}")
    print(f"  solver residuals: {[float(r) for r in res.residual]}")
    rel = relative_residuals(a, res.x, b)
    print(f"  HIGHEST residuals: {[float(r) for r in rel]}")
    check(bool(res.converged.all()), "refine: every column converged")
    check(bool((rel <= TARGET).all()),
          f"refine: every column within {TARGET} at HIGHEST")


def serve_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import BatchScheduler, SolveOptions, SolverEngine
    n = N_SERVE
    print(f"serve bf16_f32 n={n} requests={NRHS} target_digits=5",
          flush=True)
    a = spd_matrix(n, seed + 2)
    bs = jax.random.normal(jax.random.key(seed + 3), (NRHS, n))
    sch = BatchScheduler(SolverEngine("bf16_f32"), max_batch=NRHS,
                         continuous=True)
    opts = SolveOptions(target_digits=5, cache_key="smoke")
    t0 = time.perf_counter()
    sch.start()
    try:
        futs = [sch.submit_async(a, bs[i], opts) for i in range(NRHS)]
        results = [f.result(timeout=900) for f in futs]
    finally:
        sch.stop()
    print(f"  all answered: {time.perf_counter() - t0:.1f} s "
          "(host clock, compile included)")
    xs = jnp.stack([x for x, _ in results], axis=1)
    rel = relative_residuals(a, xs, bs.T)
    for i, (_, info) in enumerate(results):
        print(f"  request {i}: sweeps={info.sweeps} "
              f"residual={info.residual:.3e} HIGHEST={rel[i]:.3e} "
              f"converged={info.converged} cached={info.factor_cached}")
    check(all(info.converged for _, info in results),
          "serve: every request converged")
    check(bool((rel <= 10.0 ** -5).all()),
          "serve: every answer meets 5 digits at HIGHEST")
    check(bool(np.isfinite(np.asarray(xs)).all()), "serve: answers finite")


def four_chip_phase(seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.serve import SolveOptions, SolverEngine
    devs = jax.devices()
    check(len(devs) >= 4, f"four chips present ({len(devs)} found)")
    mesh = Mesh(np.array(devs[:4]), ("model",))
    n = N_FACTOR
    bound = UNIT_ROUNDOFF[DIST_LADDER]
    print(f"dist_cholesky {DIST_LADDER} n={n} on {mesh.shape}", flush=True)
    a = spd_matrix(n, seed, NamedSharding(mesh, P("model", None)))
    eng = SolverEngine(DIST_LADDER, mesh=mesh, dist_threshold=n)
    t0 = time.perf_counter()
    l_dist, _, _ = eng.factor(a, cache_key="four")
    jax.block_until_ready(l_dist)
    print(f"  compile + factor: {time.perf_counter() - t0:.1f} s "
          "(host clock)")
    print(f"  factor sharding: {l_dist.sharding}")
    shards = sorted((s.device.id, s.data.shape)
                    for s in l_dist.addressable_shards)
    print(f"  shards: {shards}")
    check(len(shards) == 4 and all(shape == (n // 4, n)
                                   for _, shape in shards),
          "factor is split by block row over the 4 devices")
    for d in devs[:4]:
        stats = d.memory_stats() or {}
        print(f"  device {d.id} bytes_in_use="
              f"{stats.get('bytes_in_use', 'not reported')}")
    err = backward_error(a, l_dist)
    print(f"  backward error {err:.6e} (bound {bound:.6e})")
    check(err <= bound, "dist factor: backward error within the ladder's "
          "unit roundoff")

    print(f"single-chip cholesky {DIST_LADDER} n={n} on device 0",
          flush=True)
    a0 = jax.device_put(a, devs[0])
    l_one = jax.block_until_ready(compile_cholesky(a0, DIST_LADDER)(a0))
    diff = relative_difference(jax.device_put(l_dist, devs[0]), l_one)
    print(f"  ||L_dist - L_one||_F / ||L_one||_F = {diff:.6e} "
          f"(bound {bound:.6e})")
    check(diff <= bound, "dist factor within the ladder's unit roundoff "
          "of the single-chip factor")
    del a0, l_one

    print(f"refined dist solve nrhs={NRHS} target_digits=5", flush=True)
    b = jax.device_put(jax.random.normal(jax.random.key(seed + 1),
                                         (n, NRHS)),
                       NamedSharding(mesh, P("model", None)))
    t0 = time.perf_counter()
    x, info = eng.solve(a, b, SolveOptions(target_digits=5,
                                           cache_key="four"))
    x = jax.block_until_ready(x)
    print(f"  compile + solve: {time.perf_counter() - t0:.1f} s "
          "(host clock)")
    rel = relative_residuals(a, x, b)
    print(f"  sweeps={info.sweeps} distributed={info.distributed} "
          f"cached={info.factor_cached} HIGHEST residuals="
          f"{[float(r) for r in rel]}")
    check(info.distributed and info.factor_cached,
          "solve ran on the mesh against the cached sharded factor")
    check(bool(info.converged), "dist refine converged")
    check(bool((rel <= 10.0 ** -5).all()),
          "dist solve: every column meets 5 digits at HIGHEST")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the matrices and right-hand sides")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip distributed path")
    args = ap.parse_args(argv)

    impl = os.environ.get("REPRO_KERNEL_IMPL")
    if impl and impl != "pallas":
        print(f"REPRO_KERNEL_IMPL={impl} would keep the Pallas kernels off "
              "the chip; unset it", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.compile_cache import enable_compile_cache
        from repro.kernels import ops
    except ImportError as e:
        print(f"run from a checkout of the repository: {e}",
              file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found {dev.platform} devices only",
              file=sys.stderr)
        return 1
    impl = ops.resolve_impl()
    if impl != "pallas":
        print(f"kernel impl resolved to {impl}, not pallas", file=sys.stderr)
        return 1
    cache = enable_compile_cache(ROOT)
    print(f"kernel impl: {impl}")
    print(f"device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}")
    print(f"jax {jax.__version__}; compile cache {cache}", flush=True)

    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        a = spd_matrix(N_FACTOR, args.seed)
        factor_phase(a)
        refine_phase(a, args.seed)
        del a
        serve_phase(args.seed)
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s "
          "(host clock)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
