"""The benchmark's own tests, on the CPU: finding a cell's parts by
name, the counts, the trace reduction on a trace recorded on the chip,
the traffic generators, the refusal of a CPU-only device, and the
faults that ``correct`` has to catch.

    PYTHONPATH=src python -m pytest -q bench/test_bench.py
"""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run  # noqa: E402
from harness import counts, gen, spec, traces  # noqa: E402

TRACE = os.path.join(BENCH, "testdata", "factor_n1024.xplane.pb")


# -- finding parts by name ---------------------------------------------------
def test_every_cell_finds_its_parts():
    bench = spec.benchmark()
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert "setup_s" in names
    for w in bench["workloads"]:
        cell = spec.cell(w["name"], bench)
        assert cell.config["n"] > 0 and cell.chips == w["chips"]
        loop = spec.loop(cell.traffic["loop"])
        assert all(hasattr(loop, f) for f in ("setup", "window", "check"))
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
            assert m["moves"] in e2e


def test_config_files_match_benchmark():
    bench = spec.benchmark()
    for c in bench["configs"]:
        path = os.path.join(spec.ROOT, c["file"])
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert cfg["published"][k] != cfg[k]


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.cell("no.such.cell")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no.such.metric")
    with pytest.raises(spec.SpecError):
        spec.loop("no_such_loop")


def test_peaks_refuse_unknown_devices():
    from harness import device
    assert device.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        device.peaks("cpu")


# -- counts --------------------------------------------------------------------
@pytest.mark.parametrize("n,leaf,flops,nbytes", [
    # n=512: one panel, m = 256: 256*256^2 + 256*257*256 flops;
    # 4 * (256*257 + 2*256*256) bytes
    (512, 256, 33_619_968, 787_456),
    # n=768: panels with m = 512 and m = 256
    (768, 256, 134_414_336, 4 * (512 * 513 + 2 * 512 * 256) + 787_456),
    (256, 256, 0, 0),
])
def test_panel_counts_by_hand(n, leaf, flops, nbytes):
    assert counts.panel_flops(n, leaf) == flops
    assert counts.panel_bytes(n, leaf) == nbytes


def test_panel_flops_approach_cholesky():
    n = 16384
    assert abs(counts.panel_flops(n, 256) / (n ** 3 / 3) - 1) < 0.03


def test_residual_counts_and_roofline():
    assert counts.residual_flops(8, 8, 2) == 256
    assert counts.residual_bytes(8, 8, 2) == 4 * (64 + 16 + 32)
    share, bound = counts.roofline_share(2e12, 1e9, 0.02, 200e12, 1e12)
    assert bound == "compute" and share == pytest.approx(50.0)
    share, bound = counts.roofline_share(1e9, 1e9, 0.002, 200e12, 1e12)
    assert bound == "memory" and share == pytest.approx(50.0)


# -- traffic -------------------------------------------------------------------
SEED = 2 ** 31 + 4321


def test_closed_loop_repeats_from_its_seed():
    a = gen.closed_loop(SEED, 64, 129, [4, 5, 6], 256)
    b = gen.closed_loop(SEED, 64, 129, [4, 5, 6], 256)
    c = gen.closed_loop(SEED + 1, 64, 129, [4, 5, 6], 256)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert a[0].shape == (64, 129) and not np.array_equal(a[0], c[0])
    np.testing.assert_array_equal(np.sort(a[0].ravel()),
                                  np.sort(c[0].ravel()))
    assert a[1].min() >= 0 and a[1].max() < 256


@pytest.mark.parametrize("per_caller", [1, 7, 16384])
def test_closed_loop_targets_balanced_in_every_window(per_caller):
    """Each run of three consecutive requests of a caller holds 4, 5
    and 6 once, so a window of any length holds them in equal shares to
    within one request; the orders differ between callers and seeds."""
    digits, _ = gen.closed_loop(SEED, 2, per_caller, [4, 5, 6], 256)
    for row in digits:
        for k in range(0, per_caller - 2, 3):
            assert sorted(row[k:k + 3]) == [4, 5, 6]
        counts = np.bincount(row, minlength=7)[4:]
        assert counts.max() - counts.min() <= 1
    if per_caller > 3:
        assert not np.array_equal(digits[0], digits[1])


def test_matrices_repeat_from_a_large_seed():
    import jax.numpy as jnp
    a1 = gen.matrix_pool(SEED, 256, 2, 2)
    a2 = gen.matrix_pool(SEED, 256, 2, 2)
    a3 = gen.matrix_pool(SEED + 2 ** 32, 256, 2, 2)
    assert bool(jnp.all(a1[0][0] == a2[0][0]))
    assert not bool(jnp.all(a1[0][0] == a1[1][0]))
    assert not bool(jnp.all(a1[0][0] == a3[0][0]))
    a = a1[0][0]
    assert bool(jnp.all(a == a.T))
    assert float(jnp.min(jnp.linalg.eigvalsh(a))) > 0


def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert gen.percentile(v, 95) == 95
    assert gen.percentile([3.0], 95) == 3.0


# -- the harness refuses what it cannot measure --------------------------------
def test_cpu_only_device_is_refused(capsys):
    rc = run.main(["--workload", "factor.spd16k.bf16", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "no TPU" in out.err


def test_bare_benchmark_directory_is_refused(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "factor.spd16k.bf16",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


# -- faults that correct has to catch -----------------------------------------
# Each drives a whole run at a small size on the CPU, past the harness's
# look for a chip, with the timed path broken underneath.
SMALL = {
    # at n = 512 the program's factors read about 3e-5 and the control's
    # 1.1e-3 (CPU), so the limit the chip's n = 16384 sets is moved
    "factor": ("factor.spd16k.bf16", {"config": {"n": 512}, "traffic": {
        "limits": {"residual_max": 1e-6, "backward_error_max": 2e-4}}}),
    "serve": ("serve.spd8k.closed1", {"config": {"n": 512}, "traffic": {
        "pool": 16, "max_batch": 4}}),
}


def _execute(case, build=None):
    import jax
    name, overrides = SMALL[case]
    return run.execute(spec.cell(name), SEED, 0.5, False,
                       jax.devices()[:1], overrides=overrides,
                       answer_wait_s=5.0, build=build)


def _unchanged(sweep, resid, relnorm, x, r, rel, bx, brel, its, stall, act):
    """A sweep that hands its state back as it got it, counting itself."""
    import jax.numpy as jnp
    return (x, r, rel, bx, brel, its + act.astype(jnp.int32),
            jnp.where(act, stall + 1, stall))


def _half(real):
    """A sweep that leaves out the first half of the columns (slots)."""
    def sweep(sweep, resid, relnorm, x, r, rel, bx, brel, its, stall, act):
        import jax.numpy as jnp
        out = real(sweep, resid, relnorm, x, r, rel, bx, brel, its, stall,
                   act)
        keep = (jnp.arange(act.shape[-1]) < act.shape[-1] // 2
                if act.ndim else False)
        olds = (x, r, rel, bx, brel)
        return tuple(jnp.where(keep, o, n) for o, n in zip(olds, out[:5])
                     ) + out[5:]
    return sweep


def _altered_build(cfg, mix):
    good = spec.loop("solve").build(cfg, mix)

    def prog(a, b):
        l, res = good(a, b)
        return l, res._replace(x=res.x * (1.0 + 1e-3))
    return prog


def test_solve_window_keeps_solves_in_flight_and_counts_every_one():
    """The solve loop keeps ``in_flight`` solves sent, waits on the
    oldest, and at the window's close waits for all it sent before it
    reads the clock; every solve sent counts."""
    import types
    events, ticks = [], iter(range(1000))

    class Out:
        def __init__(self, k):
            self.k = k

        def block_until_ready(self):
            events.append(("wait", self.k))
            return self

    def prog(a, b):
        k = sum(e == "send" for e, _ in events)
        events.append(("send", k))
        return Out(k), types.SimpleNamespace(iterations=np.ones(2, np.int32))

    depth = 3
    fake = types.SimpleNamespace(
        traffic={"in_flight": depth, "factor_sample": 2}, seed=SEED,
        clock=lambda: float(next(ticks)),
        span=lambda name: contextlib.nullcontext())
    stats = spec.loop("solve").window(fake, {"pool": [(0, 0)] * 2,
                                             "prog": prog}, 10.0)
    sent = [k for e, k in events if e == "send"]
    waited = [k for e, k in events if e == "wait"]
    assert stats["solves"] == len(sent) == len(waited) == len(stats["solved"])
    assert waited == sent                       # each waited on, in order
    out = 0
    for e, _ in events:
        out += 1 if e == "send" else -1
        assert 0 <= out <= depth
    assert events[-depth:] == [("wait", k) for k in sent[-depth:]]
    assert len(stats["sample"]) == 2
    assert stats["e2e"]["solve_ms"] > 0


@pytest.mark.parametrize("case", sorted(SMALL))
def test_sound_small_run_is_correct(case):
    out = _execute(case)
    assert out["correct"] and out["failed"] == 0, out["checks"]
    assert out["attempted"] > 0


@pytest.mark.parametrize("case", ["factor", "serve"])
@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_fault_makes_run_incorrect(case, fault, monkeypatch):
    from repro.core import refine
    build = None
    if fault == "unchanged":
        monkeypatch.setattr(refine, "_masked_sweep", _unchanged)
    elif fault == "half":
        monkeypatch.setattr(refine, "_masked_sweep",
                            _half(refine._masked_sweep))
    elif case == "factor":
        build = _altered_build
    else:
        real = refine.RefineStepper.retire

        def retire(self, state, idx):
            state, res = real(self, state, idx)
            return state, [(x * (1.0 + 1e-3),) + tuple(rest)
                           for x, *rest in res]
        monkeypatch.setattr(refine.RefineStepper, "retire", retire)
    out = _execute(case, build=build)
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0


# -- trace reduction -------------------------------------------------------------
def test_summary_on_hand_made_intervals():
    ops = {0: [traces.Op("panel_update.3", 1.0, 1.4),
               traces.Op("potrf_leaf.4", 1.3, 1.5),
               traces.Op("residual_fused", 2.0, 2.5)]}
    spans = [("bench.window", 0.5, 3.0), ("bench.solve", 0.5, 1.6),
             ("bench.wait", 1.6, 3.0)]
    s = traces.Summary(window=(0.5, 3.0), ops=ops, spans=spans)
    assert s.window_s == pytest.approx(2.5)
    assert s.busy_s == pytest.approx(0.5 + 0.5)      # [1.0, 1.5] and [2.0, 2.5]
    assert s.idle_pct() == pytest.approx(60.0)
    assert s.op_seconds("panel_update") == (pytest.approx(0.4), 1)
    assert s.op_seconds(("panel_update", "potrf_leaf"))[1] == 2
    gaps = dict(s.gaps())
    assert gaps["bench.solve"] == pytest.approx(0.5)    # 0.5 .. 1.0
    assert gaps["bench.wait"] == pytest.approx(0.5)     # 1.5 .. 2.0 and 2.5 .. 3.0
    b = s.breakdown()
    assert b["device_ops"][0] == ["residual_fused", pytest.approx(0.5)]
    assert {k for k, _ in b["idle_gaps"]} == {"bench.solve", "bench.wait"}


def test_clock_shift_pairs_launches_with_programs():
    assert traces._clock_shift([], [1.0]) == 0.0
    assert traces._clock_shift([2.0, 5.0, 9.0], [1.0, 4.0, 8.0]) == 1.0
    # programs sent ahead wait in the device's queue: only the first,
    # launched onto an idle device, gives the clocks' offset
    assert traces._clock_shift([2.0, 2.1, 2.2, 2.3],
                               [1.0, 2.0, 3.0, 4.0]) == 1.0


def test_reduction_of_a_chip_trace():
    """Two factorizations at n = 1024 (3 panels, 4 leaves each) traced
    on a TPU v5e by ``testdata/record_trace.py``."""
    s = traces.reduce_dir(TRACE)
    assert s.window_s == pytest.approx(0.008167699)
    assert s.op_seconds("panel_update")[1] == 6
    assert s.op_seconds("potrf_leaf")[1] == 8
    assert s.op_seconds("tri_inv_leaf")[1] == 6
    assert 0 < s.busy_s < s.window_s
    assert s.idle_pct() == pytest.approx(
        100 * (1 - s.busy_s / s.window_s))
    # shifted onto the host's clock, every device operation runs inside
    # one of the two bench.solve spans that launched it
    solves = [(a, b) for n, a, b in s.spans if n == "bench.solve"]
    assert len(solves) == 2
    for o in s.ops[0]:
        assert any(a <= o.start and o.end <= b for a, b in solves), o.name
    idle = dict(s.breakdown()["idle_gaps"])
    assert max(idle, key=idle.get) == "bench.pick"
    kinds = [k for k, _ in s.breakdown()["device_ops"]]
    assert kinds[:3] == ["potrf_leaf", "tri_inv_leaf", "panel_update"]
