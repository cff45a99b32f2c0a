"""Benchmark aggregator: one bench per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (assignment contract).

  bench_syrk         Fig. 4   recursive SYRK speedup
  bench_trsm         Fig. 5   recursive TRSM speedup
  bench_cholesky     Fig. 6/7 Cholesky throughput + speedup
  bench_accuracy     Fig. 8   precision-ladder digits (x64 subprocess)
  bench_refine       beyond-paper IR digits/sweep (x64 subprocess)
  bench_serve        beyond-paper batched solve serving + fused residual
  bench_depth        Fig. 10  size/depth scaling
  bench_portability  Fig. 9/11 backend dispatch agreement
  bench_dist         beyond-paper multi-chip solver (4-dev subprocess;
                     writes BENCH_dist.json for CI's dist gate)

Each bench runs in a process of its own, one after the other: the
accuracy and refinement benches need x64 and the distributed bench a
forced 4-device CPU mesh from their first jax import, and on a TPU only
one process at a time may hold the chip. This parent process never
imports jax. A bench that fails stops the run with its exit code.

``--smoke`` shrinks every bench to CI-sized problems (propagated to
the bench processes via REPRO_BENCH_SMOKE=1); ``--out results.json``
writes all rows as a JSON artifact so CI tracks the trajectory per PR.

``--tune`` runs the measured-search autotuner (repro.tune) instead of
the benches: a 4-device subprocess regenerates the committed tuning
database at ``src/repro/tune/data/<backend>.json`` (``--tune-out``
overrides the path). ``--tune --smoke`` shrinks the search grid and
writes ``tuned-smoke.json`` instead — smoke data never silently
replaces the committed database.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import subprocess
import sys

# allow `python benchmarks/run.py` (script dir shadows the repo root)
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


#: bench -> environment its process needs from the first jax import
_ENV = {"accuracy": {"JAX_ENABLE_X64": "1"},
        "refine": {"JAX_ENABLE_X64": "1"},
        "dist": {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}}


def _benches(smoke: bool) -> list[str]:
    # bench_serve is left out of smoke mode: CI's bench-smoke job runs
    # it as its own step (bench_serve.py --smoke --out bench-serve.json)
    names = ["syrk", "trsm", "cholesky", "depth", "portability"]
    if not smoke:
        names.append("serve")
    return names + ["accuracy", "refine", "dist"]


def _run_bench(name: str, smoke: bool) -> None:
    """Run one bench in this process (the ``--bench`` child)."""
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache(_ROOT)
    if name in ("syrk", "trsm", "portability"):
        import importlib
        mod = importlib.import_module(f"benchmarks.bench_{name}")
        if smoke:
            mod.run(sizes=(256,))
        else:
            mod.run()
    elif name == "cholesky":
        from benchmarks import bench_cholesky
        # tree-vs-blocked engine race; writes BENCH_cholesky.json at the
        # repo root (CI's perf gate asserts blocked >= tree at n >= 2048)
        if smoke:
            bench_cholesky.run(sizes=(256,))
            bench_cholesky.run_engines(sizes=(512, 2048))
        else:
            bench_cholesky.run()
            bench_cholesky.run_engines(sizes=(512, 2048, 4096))
    elif name == "depth":
        from benchmarks import bench_depth
        if smoke:
            bench_depth.run(sizes=(256, 1024, 4096))
        else:
            bench_depth.run()
    elif name == "serve":
        from benchmarks import bench_serve
        bench_serve.run()
    elif name == "accuracy":
        from benchmarks import bench_accuracy
        bench_accuracy.run(sizes=(256,) if smoke else (1024, 2048))
    elif name == "refine":
        from benchmarks import bench_refine
        if smoke:
            bench_refine.run(sizes=(256,), methods=("ir",))
        else:
            bench_refine.run()
    elif name == "dist":
        from benchmarks import bench_dist
        bench_dist.run(sizes=(1024, 2048) if smoke else (1024, 2048, 4096))
    else:
        raise SystemExit(f"unknown bench {name!r}")


def _parse_rows(text: str):
    rows = []
    for line in text.splitlines():
        parts = line.strip().split(",", 2)
        if len(parts) == 3 and parts[0] != "name":
            try:
                us = float(parts[1])
            except ValueError:
                continue
            rows.append({"name": parts[0], "us_per_call": us,
                         "derived": parts[2]})
    return rows


def _sub(name: str, smoke: bool):
    """Run bench ``name`` in its own process; exit with its code if it
    fails."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(_ROOT, "src"), _ROOT,
                    env.get("PYTHONPATH")) if p)
    env.update(_ENV.get(name, {}))
    cmd = [sys.executable, os.path.abspath(__file__), "--bench", name]
    if smoke:
        cmd.append("--smoke")
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=3000)
    sys.stdout.write(r.stdout)
    sys.stdout.flush()
    if r.returncode != 0:
        sys.stderr.write(r.stderr[-4000:])
        sys.stderr.write(f"bench {name} failed (exit {r.returncode})\n")
        raise SystemExit(r.returncode)
    return _parse_rows(r.stdout)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny problem sizes (CI benchmark-smoke job)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write all rows as a JSON artifact")
    ap.add_argument("--bench", default=None, metavar="NAME",
                    help="run one bench in this process (what the "
                         "aggregator starts for each bench)")
    ap.add_argument("--tune", action="store_true",
                    help="run the autotuner (repro.tune) instead of the "
                         "benches; writes the tuning database")
    ap.add_argument("--tune-out", default=None, metavar="PATH",
                    help="tuning-database path (default: the committed "
                         "src/repro/tune/data/<backend>.json; with "
                         "--smoke: ./tuned-smoke.json)")
    args = ap.parse_args(argv)
    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    if args.bench:
        _run_bench(args.bench, args.smoke)
        return

    if args.tune:
        # own process: the tuner needs the forced 4-device mesh from the
        # very first jax import, same as the distributed bench
        env = dict(os.environ)
        env.setdefault("PYTHONPATH", "src")
        env.setdefault("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")
        cmd = [sys.executable, "-m", "repro.tune"]
        if args.smoke:
            cmd.append("--smoke")
        out = args.tune_out
        if out is None and args.smoke:
            # smoke grids are for validating the tuner wiring, not for
            # producing winners — never clobber the committed database
            out = "tuned-smoke.json"
        if out:
            cmd += ["--out", out]
        raise SystemExit(subprocess.run(cmd, env=env).returncode)

    print("name,us_per_call,derived")
    rows = []
    for name in _benches(args.smoke):
        rows += _sub(name, args.smoke)
    # roofline table (reads experiments/dryrun if present; no jax)
    from benchmarks import roofline
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        roofline.run_csv()
    sys.stdout.write(buf.getvalue())
    rows += _parse_rows(buf.getvalue())

    if args.out:
        payload = {"smoke": args.smoke, "rows": rows}
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {len(payload['rows'])} rows to {args.out}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
