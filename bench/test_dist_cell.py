"""The sharded cell ``factor.dist64k.4chip`` on a forced 4-host-device
CPU mesh at a small size: the sharded generator against its whole
assembly, and whole runs of the harness (``run.execute``) that read
``correct`` true for the program and false with the control planted in
its place (``build=``: the reference's factor and answer held in
bfloat16, ``loops/dist_solve.control_build``). Driven by
tests/test_multidevice.py, or directly:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m pytest -q bench/test_dist_cell.py

On the chip at the cell's own size, one run of the harness per seed
with the control planted, all in one process:

    python3 bench/test_dist_cell.py --seconds <s> --seed <n> [--seed ...]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run  # noqa: E402
from harness import (counts, counts_dist, gen_sharded,  # noqa: E402
                     scopes_dist, spec, traces)

CELL = "factor.dist64k.4chip"
SMALL = {"config": {"n": 1024}}
SEED = 2 ** 33 + 15


#: the loop's own control, planted through ``build=``
control_build = spec.loop("dist_solve").control_build


def _devices():
    import jax
    if jax.device_count() < 4:
        pytest.skip("needs 4 host devices")
    return jax.devices()[:4]


def test_sharded_matrix_is_symmetric_and_whole():
    from jax.sharding import Mesh
    devs = _devices()
    mesh = Mesh(np.array(devs), ("model",))
    n = 1024
    a = gen_sharded.spd(SEED, n, mesh)
    assert a.sharding.spec[0] == "model"
    whole = np.asarray(gen_sharded.spd_whole(SEED, n))
    got = np.asarray(a)
    assert np.array_equal(got, whole)
    assert np.array_equal(got, got.T)
    off = got - n * np.eye(n)
    assert np.abs(off).max() <= 1.0 and abs(off.mean()) < 0.01
    # another seed, another matrix; another device count, the same one
    assert not np.array_equal(np.asarray(gen_sharded.spd(SEED + 1, n, mesh)),
                              got)
    mesh2 = Mesh(np.array(devs[:2]), ("model",))
    assert np.array_equal(np.asarray(gen_sharded.spd(SEED, n, mesh2)), got)


def test_cell_correct_and_control_not():
    devs = _devices()
    cell = spec.cell(CELL)
    out = run.execute(cell, SEED, 0.5, False, devs, overrides=SMALL)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"solve_ms", "setup_s"}
    ctl = run.execute(cell, SEED, 0.5, False, devs, overrides=SMALL,
                      build=control_build)
    assert ctl["correct"] is False, ctl["checks"]
    assert ctl["failed"] == ctl["attempted"] >= 1, ctl
    for name in ("residual_max", "backward_error_max"):
        assert ctl["checks"][name]["value"] > out["checks"][name]["value"]


# -- the cell's trace readers on a hand-made trace -----------------------------
_SM = "jit(solve)/factor/dist/jit(_run_sharded)/shard_map"
#: instruction -> (op_name, opcode), as the compiled program names them
_PROGRAM = {
    "fusion.1": (_SM + "/diag/cholesky", "fusion"),
    "psum_invariant.2": (_SM + "/diag/psum", "all-reduce"),
    "all-gather-start.3": (_SM + "/gather/all_gather", "all-gather-start"),
    "qgemm.4": (_SM + "/update/pallas_call", "custom-call"),
    "dot.5": ("jit(solve)/refine/while/body/dot_general", "dot"),
}
#: seconds of each instruction in one solve on chips 0 and 1; the
#: refinement's dot runs twice a solve
_PER_SOLVE = {0: [("fusion.1", .1), ("psum_invariant.2", .5),
                  ("all-gather-start.3", .1), ("qgemm.4", .1),
                  ("dot.5", .05), ("dot.5", .05)],
              1: [("fusion.1", .1), ("psum_invariant.2", .05),
                  ("all-gather-start.3", .1), ("qgemm.4", .55),
                  ("dot.5", .05), ("dot.5", .05)]}


def _traced_run(monkeypatch, solves: int, cut_after: int | None):
    """A run of ``solves`` one-second solves on two chips whose trace
    holds each chip's first ``cut_after`` operations only (None: all)."""
    ops = {}
    for dev, steps in _PER_SOLVE.items():
        ops[dev], t = [], 0.0
        for i in range(solves):
            t = float(i)
            for name, sec in steps:
                ops[dev].append(traces.Op(name, t, t + sec))
                t += sec
        ops[dev] = ops[dev][:cut_after]
    summary = traces.Summary(window=(0.0, float(solves)), ops=ops,
                             spans=[])
    maps = ({k: v[0] for k, v in _PROGRAM.items()},
            {k: v[1] for k, v in _PROGRAM.items()})
    monkeypatch.setattr(scopes_dist, "program_maps", lambda run: maps)
    return types.SimpleNamespace(
        summary=summary, devices=[object(), object()],
        config={"n": 4096}, stats={"solves": solves},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def _read(name, run):
    return spec.metric_reader(name)(run)


@pytest.mark.parametrize("cut_after", [None, 2 * 6 + 2])
def test_readers_take_whole_traced_solves(monkeypatch, cut_after):
    """Collective time (found by opcode, the all-reduce named
    ``psum_invariant`` included), compute spread and the update's
    roofline, per solve the trace holds whole: the same whether the
    profiler recorded the whole window or stopped inside its third
    solve."""
    r = _traced_run(monkeypatch, 3, cut_after)
    assert scopes_dist.whole_solves(r, 0)[0] == (3 if cut_after is None
                                                 else 2)
    assert _read("collective_ms", r) == pytest.approx(600.0)
    # compute a solve: chip 0 0.3 s, chip 1 0.75 s
    assert _read("dist.busy_spread_pct", r) == pytest.approx(60.0)
    want, _ = counts.roofline_share(
        counts_dist.update_flops(4096) / 2,
        counts_dist.update_bytes(4096) / 2, 0.55, 197e12, 819e9)
    assert _read("dist_update_roofline", r) == pytest.approx(want)


def test_readers_find_nothing_untraced_or_unmapped(monkeypatch):
    r = _traced_run(monkeypatch, 2, None)
    r.summary.ops[1].append(traces.Op("copy.99", 2.0, 9.0))  # not mapped
    for name in ("collective_ms", "dist.busy_spread_pct",
                 "dist_update_roofline"):
        assert _read(name, r) is None
        assert _read(name, types.SimpleNamespace(
            summary=None, devices=r.devices)) is None


def test_opcodes_of_compiled_hlo_text():
    text = """
  %psum_invariant.2 = f32[256,256]{1,0:T(8,128)} all-reduce(f32[256,256]{1,0:T(8,128)} %s), channel_id=1, to_apply=%add, metadata={op_name="a/diag/psum"}
  %all-gather-start.3 = (f32[64,256]{1,0:T(8,128)}, f32[4,64,256]{2,1,0:T(8,128)}) all-gather-start(f32[64,256]{1,0:T(8,128)} %x), dimensions={0}
  ROOT %fusion.1 = f32[64,256]{1,0:T(8,128)S(1)} fusion(f32[64,256]{1,0} %p), kind=kLoop, calls=%fused_computation
  %qgemm.4 = f32[64,256]{1,0} custom-call(f32[64,256]{1,0} %a), custom_call_target="tpu_custom_call"
"""
    got = scopes_dist.opcodes(text)
    assert got == {"psum_invariant.2": "all-reduce",
                   "all-gather-start.3": "all-gather-start",
                   "fusion.1": "fusion", "qgemm.4": "custom-call"}
    assert [scopes_dist.is_collective(c) for c in got.values()] == [
        True, True, False, False]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from harness import device
    cell = spec.cell(CELL)
    devs = device.require_chips(cell.chips)
    device.enable_cache()
    for seed in args.seed:
        out = run.execute(cell, seed, args.seconds, False, devs,
                          build=control_build)
        print(json.dumps({"workload": CELL, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "checks": out["checks"],
                          "device": out["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
