"""Runs the multi-device test files in subprocesses with forced host
devices (the main pytest session keeps the default 1 device, per the
assignment's instruction not to set device-count flags globally).

The distributed-solver suite (tests/test_distributed.py) runs on a
4-device mesh — the serving topology docs/SERVING.md documents — and
its f64-ladder equivalence entries get an extra JAX_ENABLE_X64 pass.
The b-wide panel sweep (tests/test_dist_panels.py) and the four-chip
benchmark cell at a small size (bench/test_dist_cell.py) run one
target per test, so a failure shows by name.
"""
import os
import subprocess
import sys

import pytest


@pytest.mark.parametrize("target,ndev,extra_env", [
    ("tests/test_moe_sharded.py", 8, {}),
    ("tests/test_train.py::test_ef_compression_dp_trainer", 8, {}),
    ("tests/test_elastic.py", 8, {}),
    ("tests/test_dist_solver.py", 8, {}),
    ("tests/test_distributed.py", 4, {}),
    ("tests/test_distributed.py::test_dist_matches_blocked_f64", 4,
     {"JAX_ENABLE_X64": "1"}),
    ("tests/test_dist_panels.py::test_dist_cholesky_narrow_panels", 4, {}),
    ("tests/test_dist_panels.py::test_dist_refine_solve_reaches_1e6", 4, {}),
    ("tests/test_dist_panels.py::test_dist_audits_reconcile_narrow_panels",
     4, {}),
    ("bench/test_dist_cell.py::test_sharded_matrix_is_symmetric_and_whole",
     4, {}),
    ("bench/test_dist_cell.py::test_cell_correct_and_control_not", 4, {}),
])
def test_multidevice_subprocess(target, ndev, extra_env):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["PYTHONPATH"] = "src"
    env.update(extra_env)
    r = subprocess.run(
        [sys.executable, "-m", "pytest", target, "-q", "--no-header"],
        env=env, capture_output=True, text=True, timeout=2400,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, f"\n{r.stdout[-3000:]}\n{r.stderr[-2000:]}"
