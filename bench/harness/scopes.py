"""Device time by the program's named scopes.

The program names its layers with ``jax.named_scope`` (``factor``,
``solve``, ``refine``, with ``diag`` / ``panel`` / ``write`` inside the
factor's panel loop and ``sweep`` inside refinement). A scope lives
only in the compiled program's HLO metadata: every instruction carries
an ``op_name`` such as ``jit(solve)/factor/panel/jit(panel_update)/...``,
while the trace names a device operation by its instruction alone
(``fusion.15``). So the map from instruction to scope is read from the
compiled HLO text of the program the window ran, compiled again here
after the window (from the persistent cache where it is there).

A scope path is the ``op_name`` without its leading transformation
wrappers (``jit(solve)``) and with a scope that repeats itself
(``solve/solve``, one scoped entry point calling another) taken once.
"""
from __future__ import annotations

import collections
import re

_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\bop_name="([^"]*)"')
_WRAPPER = re.compile(r"^[\w\-]+\(.*\)$")
#: share of the device's busy time the mapped operations must cover
#: before a scope's time is reported
MIN_COVER = 0.95


def op_names(hlo_text: str) -> dict:
    """Instruction name -> ``op_name``, over every computation of a
    compiled HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def scope_path(op_name: str) -> tuple:
    """``jit(solve)/refine/while/body/sweep/mul`` -> ``("refine",
    "while", "body", "sweep", "mul")``."""
    parts = op_name.split("/")
    while parts and _WRAPPER.match(parts[0]):
        parts.pop(0)
    out: list = []
    for p in parts:
        if not out or out[-1] != p:
            out.append(p)
    return tuple(out)


def _union(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def seconds_by_scope(summary, names: dict, depth: int = 1,
                     dev: int = 0) -> dict | None:
    """Union of device ``dev``'s operation intervals per scope path cut
    to ``depth`` (``{"factor": s, "refine": s}``, or with ``depth=2``
    ``{"factor/panel": s, ...}``), or None where the operations found in
    ``names`` cover less than :data:`MIN_COVER` of the device's busy
    time (the map is not the program that ran)."""
    ops = summary.ops.get(dev, [])
    mapped = [o for o in ops if o.name in names]
    busy = summary.busy_s_of(dev)
    if not busy or _union((o.start, o.end) for o in mapped) < (
            MIN_COVER * busy):
        return None
    by = collections.defaultdict(list)
    for o in mapped:
        key = "/".join(scope_path(names[o.name])[:depth])
        by[key].append((o.start, o.end))
    return {k: _union(v) for k, v in by.items()}


_MEMO: dict = {}


def solve_program_names(run) -> dict:
    """The instruction map of the solve loop's timed program as the
    window ran it: ``(run.build or solve.build)(config, traffic)``,
    compiled for the run's first device at the cell's shapes."""
    hit = _MEMO.get(id(run))
    if hit is not None and hit[0] is run:
        return hit[1]
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from harness import spec
    cfg, mix = run.config, run.traffic
    prog = (run.build or spec.loop("solve").build)(cfg, mix)
    lower = getattr(prog, "lower", None) or jax.jit(prog).lower
    on = SingleDeviceSharding(run.devices[0])
    n = cfg["n"]
    a = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=on)
    b = jax.ShapeDtypeStruct((n, mix["nrhs"]), jnp.float32, sharding=on)
    names = op_names(lower(a, b).compile().as_text())
    _MEMO.clear()
    _MEMO[id(run)] = (run, names)
    return names


def device_ms_per_solve(run, scope: str) -> float | None:
    """Device milliseconds per solve in operations whose top scope is
    ``scope``; None where the run was not traced, the map does not
    cover the trace, or no operation carries the scope."""
    if run.summary is None:
        return None
    by = seconds_by_scope(run.summary, solve_program_names(run))
    if not by or scope not in by:
        return None
    return by[scope] / run.stats["solves"] * 1e3
