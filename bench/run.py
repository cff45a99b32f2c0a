"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<mix>.json``), whose ``loop`` names the loop kind in
``bench/loops/``. A run makes its data on the device from ``--seed``,
compiles and warms every shape it will use (the set-up), measures for
``--seconds``, and then checks every answer of the window against the
plain reference in ``bench/harness/reference.py``.

With ``--trace 0`` the result carries the cell's end-to-end metrics;
with ``--trace 1`` the window is traced by the JAX profiler and the
result carries the per-layer metrics, each read by
``bench/metrics/<metric>.py``, with the device's busy time and a
breakdown. The last line of standard output is one JSON object; the
numbers compared for ``correct`` end standard error and the line.

It exits non-zero and prints no result where JAX finds no TPU or fewer
chips than the cell asks for, or outside a checkout of the program.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import device, spec  # noqa: E402

#: where a traced run's profile is written, and removed once reduced
TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")


@dataclasses.dataclass
class Run:
    """What a loop and a metric reader see of the run."""

    cell: spec.Cell
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    #: seconds to wait past the window's close for an answer
    answer_wait_s: float = 60.0
    #: replaces a loop's timed program (the fault tests plant faults here)
    build: object = None
    stats: dict = dataclasses.field(default_factory=dict)
    summary: object = None            # harness.traces.Summary when traced

    clock = staticmethod(time.perf_counter)
    sleep = staticmethod(time.sleep)

    def span(self, name: str):
        """A host span in the profiler's trace (free when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    @property
    def peaks(self) -> dict:
        return device.peaks(self.devices[0].device_kind)


def _args(argv):
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Compiles:
    """Counts what JAX compiles and what it loads from the persistent
    cache, through :mod:`jax.monitoring`, so a run can say what set-up
    cost and whether anything compiled inside the window."""

    EVENTS = {"/jax/core/compile/backend_compile_duration": "compiled",
              "/jax/compilation_cache/cache_hits": "cache_hits"}

    def __init__(self):
        import jax
        self.counts = {"compiled": 0, "cache_hits": 0}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _event(self, name, **kw):
        if name in self.EVENTS:
            self.counts[self.EVENTS[name]] += 1

    def _dur(self, name, secs, **kw):
        self._event(name)

    def snapshot(self) -> dict:
        return dict(self.counts)

    def close(self):
        import jax
        jax.monitoring.unregister_event_listener(self._event)
        jax.monitoring.unregister_event_duration_listener(self._dur)


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def execute(cell: spec.Cell, seed: int, seconds: float, trace: bool,
            devices, *, overrides: dict | None = None,
            answer_wait_s: float = 60.0, build=None) -> dict:
    """Set up, measure and check one run of ``cell``; returns the result
    object. ``overrides`` replaces keys of the configuration and the mix
    (the tests run cells at small sizes on the CPU)."""
    overrides = overrides or {}
    config = {**cell.config, **overrides.get("config", {})}
    traffic = {**cell.traffic, **overrides.get("traffic", {})}
    run = Run(cell=cell, config=config, traffic=traffic, seed=seed,
              seconds=seconds, trace=trace, devices=devices,
              answer_wait_s=answer_wait_s, build=build)
    loop = spec.loop(traffic["loop"])
    compiles = Compiles()
    st = loop.setup(run)
    at_setup = compiles.snapshot()
    if trace:
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR,
                                 profiler_options=_profile_options())
    setup_s = run.clock() - T_START
    try:
        with run.span("bench.window"):
            stats = loop.window(run, st, seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    run.stats = stats
    in_window = {k: v - at_setup[k] for k, v in compiles.snapshot().items()}
    compiles.close()
    dev = device.describe(devices)
    if trace:
        from harness import traces
        run.summary = traces.reduce_dir(TRACE_DIR, len(devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        dev["busy_s"] = run.summary.busy_s
        dev["window_s"] = run.summary.window_s
    verdict = loop.check(run, st, stats)
    checks = verdict["checks"]
    correct = all(v <= lim for _, v, lim in checks)
    metrics = {}
    if trace:
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {**stats["e2e"], "setup_s": setup_s}
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    out = {"correct": correct, "attempted": verdict["attempted"],
           "failed": verdict["failed"], "metrics": metrics, "device": dev}
    if trace:
        out["breakdown"] = run.summary.breakdown()
    out["detail"] = {k: stats[k] for k in ("latency_ms",) if k in stats}
    out["detail"]["compiles"] = {"setup": at_setup, "window": in_window}
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in checks}
    return out


def main(argv=None) -> int:
    args = _args(argv)
    try:
        cell = spec.cell(args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(spec.ROOT, "src", "repro")):
        print("bench: run from a checkout of the program (no src/repro)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(spec.ROOT, "src"))
    try:
        devs = device.require_chips(cell.chips)
    except device.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    device.enable_cache()
    out = execute(cell, args.seed, args.seconds, bool(args.trace), devs)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
