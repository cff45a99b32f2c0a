"""The control: the plain reference put in the program's place one step
lower in precision (bfloat16 for the configurations' float32). Planted
under a whole run of the harness (``run.execute``), it has to come out
of every cell's own comparison as not correct.

* A ``solve`` cell's timed program is replaced (``build=``) by the
  control's factor and answer.
* A ``serve`` cell's answers are replaced where the refinement stepper
  retires them, by the control's answer to each slot's own right-hand
  side; the scheduler, the collector and the check run as in any run.

On the CPU (the test run) at a small size:

    PYTHONPATH=src python -m pytest -q bench/test_control.py

On the chip at a cell's own size, one run of the harness per seed, all
in one process, each printing its numbers beside their limits:

    python3 bench/test_control.py --workload <name> --seconds <s> --seed <n> [--seed ...]
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from typing import NamedTuple

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run  # noqa: E402
from harness import gen, reference, spec  # noqa: E402


class Answer(NamedTuple):
    """What the solve loop reads of a solve: the answer and its sweeps."""

    x: object
    iterations: object


def control_build(cfg: dict, mix: dict):
    """The solve loop's timed program, replaced by the control:
    ``(a, b) -> (L, Answer)``, factor and answer held in bfloat16."""
    import jax
    import jax.numpy as jnp

    def prog(a, b):
        l = reference.control_factor(a)
        return l, Answer(reference.control_solve(l, b),
                         jnp.zeros(b.shape[1], jnp.int32))
    return jax.jit(prog)


@contextlib.contextmanager
def control_retire(seed: int, cfg: dict, mix: dict):
    """While open, every column the serving stepper retires carries the
    control's answer to that slot's right-hand side, against the
    control's factor of the seed's matrix."""
    import jax.numpy as jnp

    from repro.core import refine
    a, _ = gen.serve_pool(seed, cfg["n"], mix["pool"])
    l = reference.control_factor(a)
    del a
    real = refine.RefineStepper.retire

    def retire(self, state, idx):
        b = state.b[:, jnp.asarray(idx, jnp.int32)].astype(jnp.float32)
        x = reference.control_solve(l, b)
        state, res = real(self, state, idx)
        return state, [(x[:, i].astype(xr.dtype),) + tuple(rest)
                       for i, (xr, *rest) in enumerate(res)]

    refine.RefineStepper.retire = retire
    try:
        yield
    finally:
        refine.RefineStepper.retire = real


def control_run(name: str, seed: int, seconds: float, devices, *,
                overrides: dict | None = None,
                answer_wait_s: float = 60.0) -> dict:
    """One run of cell ``name`` through :func:`run.execute`, with the
    control planted in the program's place; returns its result."""
    cell = spec.cell(name)
    overrides = overrides or {}
    cfg = {**cell.config, **overrides.get("config", {})}
    mix = {**cell.traffic, **overrides.get("traffic", {})}
    build, plant = None, contextlib.nullcontext()
    if mix["loop"] == "solve":
        build = control_build
    else:
        plant = control_retire(seed, cfg, mix)
    with plant:
        return run.execute(cell, seed, seconds, False, devices,
                           overrides=overrides,
                           answer_wait_s=answer_wait_s, build=build)


SMALL = {"config": {"n": 512}}


@pytest.mark.parametrize("name", [w["name"] for w in
                                  spec.benchmark()["workloads"]])
def test_control_is_not_correct(name):
    """The harness's own comparison calls every answer of the control's
    run wrong."""
    import jax
    out = control_run(name, 2 ** 31 + 99, 0.5, jax.devices()[:1],
                      overrides=SMALL, answer_wait_s=5.0)
    assert out["correct"] is False, out["checks"]
    assert out["failed"] == out["attempted"] > 0, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from harness import device
    devs = device.require_chips(spec.cell(args.workload).chips)
    device.enable_cache()
    for seed in args.seed:
        out = control_run(args.workload, seed, args.seconds, devs)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
