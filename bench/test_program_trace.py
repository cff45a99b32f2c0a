"""The program's spans and scopes as the benchmark reads them, on the
CPU: idle gaps named by the program's host spans
(``harness/program_spans.py``), the map from device operations to the
program's named scopes (``harness/scopes.py``) and the two readers that
use it, and a serving trace recorded on the chip.

    PYTHONPATH=src python -m pytest -q bench/test_program_trace.py
"""
from __future__ import annotations

import collections
import os
import sys
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

from harness import program_spans, scopes, spec, traces  # noqa: E402

SERVE_TRACE = os.path.join(BENCH, "testdata", "serve_n1024.xplane.pb")


# -- idle gaps named by program spans ----------------------------------------
def _gap_labels(prog, spans=()):
    """Labels of the two idle gaps of a window (0, 10) whose device is
    busy over [4, 6]: gaps [0, 4] and [6, 10]."""
    s = traces.Summary(window=(0.0, 10.0),
                       ops={0: [traces.Op("fusion.1", 4.0, 6.0)]},
                       spans=[("bench.window", 0.0, 10.0), *spans])
    return [name for name, _ in program_spans.gaps(s, prog)]


def test_a_benchmark_span_wins_over_a_program_span():
    prog = [("repro.sched.admit", 1, 0.0, 10.0, "3")]
    assert _gap_labels(prog, [("bench.wait", 3.0, 4.0)]) == [
        "bench.wait", "repro.sched.admit"]


def test_a_program_span_replaces_only_unannotated():
    prog = [("repro.sched.retire", 1, 7.0, 9.0, "3")]
    assert _gap_labels(prog, [("bench.submit", 0.0, 1.0)]) == [
        "bench.submit", "repro.sched.retire"]


def test_self_time_decides_between_parent_and_child():
    # gap [0, 4]: admit's own time 1 s against base's 3 s -> base;
    # gap [6, 10]: step's own time 3 s against masks' 1 s -> step
    prog = [("repro.sched.admit", 1, 0.0, 5.0, "3"),
            ("repro.solve.base", 1, 1.0, 4.5, "3"),
            ("repro.sched.step", 1, 6.0, 10.0, None),
            ("repro.sched.masks", 1, 8.0, 9.0, None),
            # another thread waits over both gaps, shorter than each
            ("repro.sched.wait", 2, 2.0, 2.5, None)]
    assert _gap_labels(prog) == ["repro.solve.base", "repro.sched.step"]


def test_no_span_is_still_unannotated():
    assert _gap_labels([]) == ["unannotated", "unannotated"]
    prog = [("repro.sched.step", 1, 4.5, 5.5, None)]   # only while busy
    assert _gap_labels(prog) == ["unannotated", "unannotated"]


def test_gaps_keep_the_benchmark_reduction_seconds():
    s = traces.Summary(window=(0.0, 10.0),
                       ops={0: [traces.Op("fusion.1", 4.0, 6.0)]},
                       spans=[("bench.window", 0.0, 10.0)])
    prog = [("repro.sched.step", 1, 0.0, 10.0, None)]
    assert [sec for _, sec in program_spans.gaps(s, prog)] == [
        sec for _, sec in s.gaps()]
    assert program_spans.idle_gaps(s, prog) == [["repro.sched.step", 8.0]]


def test_self_pieces_of_nested_spans():
    pieces = program_spans.self_pieces([(0.0, 10.0, "a"), (2.0, 4.0, "b"),
                                        (3.0, 3.5, "c"), (6.0, 7.0, "d")])
    assert pieces == [(0.0, 2.0, "a"), (2.0, 3.0, "b"), (3.0, 3.5, "c"),
                      (3.5, 4.0, "b"), (4.0, 6.0, "a"), (6.0, 7.0, "d"),
                      (7.0, 10.0, "a")]


def test_program_seconds_cut_to_the_window():
    prog = [("repro.sched.admit", 0, 0.0, 2.0, "1"),
            ("repro.sched.admit", 0, 4.5, 6.0, "2"),
            ("repro.sched.step", 0, 2.0, 3.0, None)]
    assert program_spans.seconds(prog, (1.0, 5.0), "repro.sched.admit") == (
        pytest.approx(1.5), 2)
    assert program_spans.seconds(
        prog, (1.0, 5.0), ("repro.sched.admit", "repro.sched.step"))[1] == 3


# -- device time by named scope -----------------------------------------------
def test_scope_path_drops_wrappers_and_repeats():
    assert scopes.scope_path("jit(solve)/factor/panel/jit(panel_update)/"
                             "panel_update/pallas_call") == (
        "factor", "panel", "jit(panel_update)", "panel_update",
        "pallas_call")
    assert scopes.scope_path("jit(solve)/solve/solve/dot_general") == (
        "solve", "dot_general")
    assert scopes.scope_path("x") == ("x",)


def _entry_instructions(text):
    """Instruction names of a compiled HLO module's entry computation
    that are not its parameters."""
    import re
    body = text.split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    out = []
    for line in body.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line)
        if m and " parameter(" not in line:
            out.append(m.group(1))
    return out


def test_scope_map_of_the_solve_program_and_its_readers():
    """The solve loop's program compiled on the CPU at n = 512: every
    instruction of its entry computation maps to ``factor`` or
    ``refine``, and both device-scope readers read a trace whose
    operations are those instructions."""
    import jax
    cell = spec.cell("factor.spd16k.bf16")
    cfg = {**cell.config, "n": 512}
    mix = cell.traffic
    prog = spec.loop("solve").build(cfg, mix)
    a = jax.ShapeDtypeStruct((512, 512), np.float32)
    b = jax.ShapeDtypeStruct((512, mix["nrhs"]), np.float32)
    text = prog.lower(a, b).compile().as_text()
    names = scopes.op_names(text)
    entry = [n for n in _entry_instructions(text) if n in names]
    assert len(entry) > 5
    tops = {scopes.scope_path(names[n])[0] for n in entry}
    assert tops == {"factor", "refine"}, tops
    # a trace of two solves, one after another: each entry instruction
    # runs for 1 ms in its turn
    ops, t = [], 0.0
    for _ in range(2):
        for n in entry:
            ops.append(traces.Op(n, t, t + 1e-3))
            t += 1e-3
    run_ = types.SimpleNamespace(
        summary=traces.Summary(window=(0.0, t), ops={0: ops}, spans=[]),
        build=None, config=cfg, traffic=mix, devices=jax.devices()[:1],
        stats={"solves": 2})
    got = {m: spec.metric_reader(m)(run_)
           for m in ("factor.device_ms", "refine.device_ms")}
    per_scope = collections.Counter(scopes.scope_path(names[n])[0]
                                    for n in entry)
    assert got["factor.device_ms"] == pytest.approx(per_scope["factor"])
    assert got["refine.device_ms"] == pytest.approx(per_scope["refine"])
    # a trace of some other program is not read
    run_.summary.ops[0] = [traces.Op("no_such_op.1", 0.0, t)]
    assert spec.metric_reader("factor.device_ms")(run_) is None


def test_scope_readers_read_nothing_untraced():
    run_ = types.SimpleNamespace(summary=None, stats={})
    for m in ("factor.device_ms", "refine.device_ms"):
        assert spec.metric_reader(m)(run_) is None


# -- a serving trace recorded on the chip --------------------------------------
def test_reduction_of_a_serving_trace():
    """Three requests at n = 1024 through the continuous scheduler,
    traced on a TPU v5e by ``testdata/record_serve_trace.py``: the
    calling thread launches the right-hand sides, the scheduler's worker
    everything else."""
    from jax.profiler import ProfileData
    s = traces.reduce_dir(SERVE_TRACE)
    w0, w1 = s.window
    spans = [p for p in program_spans.read(SERVE_TRACE)
             if p[3] > w0 and p[2] < w1]
    admits = [p for p in spans if p[0] == "repro.sched.admit"]
    retires = [p for p in spans if p[0] == "repro.sched.retire"]
    assert sorted(p[4] for p in admits) == ["3", "4", "5"]
    assert sorted(p[4] for p in retires) == ["3", "4", "5"]
    assert len({p[1] for p in spans}) == 1            # the worker's line
    for name in ("repro.solve.base", "repro.refine.join"):
        inner = [p for p in spans if p[0] == name]
        assert len(inner) == 3
        assert all(any(a[2] <= p[2] and p[3] <= a[3] for a in admits)
                   for p in inner)
    # programs were launched from two host threads ...
    planes = ProfileData.from_file(SERVE_TRACE).planes
    host = next(p for p in planes if p.name == "/host:CPU")
    launching = [ln for ln in host.lines
                 if any(ev.name == traces.LAUNCH for ev in ln.events)]
    assert len(launching) == 2
    # ... and, shifted by the largest launch-to-start gap of the pairs
    # taken in order, each residual kernel ran inside the admit or the
    # sweep that waits for it on the host
    synced = [p for p in spans
              if p[0] in ("repro.sched.admit", "repro.sched.step")]
    res = [o for o in s.ops[0] if traces.op_kind(o) == "residual_fused"]
    assert len(res) == 5                   # 3 joins, 2 sweeps
    for o in res:
        assert any(a <= o.start and o.end <= b for _, _, a, b, _ in synced)
    # the benchmark's reduction leaves the worker's time unannotated;
    # the program's spans name it
    before = dict(s.breakdown()["idle_gaps"])
    assert before["unannotated"] > 0.5 * s.window_s
    idle = dict(program_spans.idle_gaps(s, spans))
    assert idle.get("unannotated", 0.0) < 0.05 * s.window_s
    assert sum(v for k, v in idle.items() if k.startswith("repro.")) > (
        0.8 * s.window_s)
    assert max(idle, key=idle.get) == "repro.solve.base"
    for k in ("bench.submit", "bench.wait"):
        assert idle.get(k) == before.get(k)


def test_the_command_line_prints_the_split(capsys):
    assert program_spans.main([SERVE_TRACE]) == 0
    import json
    out = json.loads(capsys.readouterr().out)
    assert out["spans"]["repro.sched.admit"]["count"] == 3
    assert out["idle_gaps"][0][0] == "repro.solve.base"
    assert program_spans.main([]) == 2
