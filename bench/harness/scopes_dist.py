"""Device time by the program's named scopes and by HLO opcode in a
sharded cell, per solve of the window that the trace holds whole.

As ``harness.scopes`` does for one chip: the map from a trace's
operation names to their ``op_name`` (and here also to their opcode) is
read from the compiled HLO of the program the window ran
(``dist_solve``'s, compiled again after the window, from the persistent
cache where it is there). The program runs as one SPMD program, so the
map holds on every chip.

Inside ``shard_map`` an ``op_name`` carries the wrappers JAX adds
(``jit(solve)/factor/dist/jit(_run_sharded)/shard_map/update/...``): a
scope path here drops every part that is a wrapper (``name(...)``) or
``shard_map``, wherever it stands. A trace names an operation by its
instruction, not its opcode (an all-reduce that JAX made from a
``psum`` is ``psum_invariant.3``), so collectives are found by opcode.

The profiler may stop recording a chip's operations before the window
ends (a traced run of this cell held each chip's operations for about
a quarter of its 51 s). So every per-solve figure here is taken over
the solves the trace holds whole on that chip (:func:`whole_solves`),
never over the window's count of solves.
"""
from __future__ import annotations

import collections
import re

from harness import scopes

_SKIP = {"shard_map"}
#: ``%name = <type> opcode(`` in a compiled HLO module's text
_OPCODE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
#: collective opcodes; each may also run as an async ``-start`` /
#: ``-done`` pair
COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "reduce-scatter", "all-to-all")
#: the scope of the factor: unrolled, so each of its instructions that
#: runs on a chip runs there once a solve
FACTOR = ("factor", "dist")


def scope_path(op_name: str) -> tuple:
    """``jit(solve)/factor/dist/jit(_run_sharded)/shard_map/update/dot``
    -> ``("factor", "dist", "update", "dot")``."""
    out: list = []
    for p in op_name.split("/"):
        if p in _SKIP or scopes._WRAPPER.match(p):
            continue
        if not out or out[-1] != p:
            out.append(p)
    return tuple(out)


def opcodes(hlo_text: str) -> dict:
    """Instruction name -> opcode, over every computation of a compiled
    HLO module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _OPCODE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def is_collective(opcode: str) -> bool:
    for suffix in ("-start", "-done"):
        if opcode.endswith(suffix):
            opcode = opcode[:-len(suffix)]
    return opcode in COLLECTIVES


_MEMO: dict = {}


def program_maps(run) -> tuple:
    """``(op_names, opcodes)`` of the ``dist_solve`` loop's timed
    program as the window ran it, compiled for the run's mesh at the
    cell's shapes."""
    hit = _MEMO.get(id(run))
    if hit is not None and hit[0] is run:
        return hit[1]
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from harness import spec
    loop = spec.loop("dist_solve")
    cfg, mix = run.config, run.traffic
    mesh = loop.mesh_of(run.devices)
    prog = (run.build or loop.build)(cfg, mix, mesh)
    rows = NamedSharding(mesh, P(loop.AXIS, None))
    n = cfg["n"]
    a = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=rows)
    b = jax.ShapeDtypeStruct((n, mix["nrhs"]), jnp.float32, sharding=rows)
    text = prog.lower(a, b).compile().as_text()
    maps = (scopes.op_names(text), opcodes(text))
    _MEMO.clear()
    _MEMO[id(run)] = (run, maps)
    return maps


def whole_solves(run, dev) -> tuple | None:
    """``(k, ops)``: the number of solves the trace holds whole on chip
    ``dev``, and that chip's operations that belong to them; None where
    the run was not traced, the map covers less than
    ``scopes.MIN_COVER`` of the chip's busy time, or no operation of
    the factor was traced.

    Every instruction under :data:`FACTOR` that runs on the chip runs
    once a solve, so the fewest times any of them was traced is the
    number of whole solves, k. The solves run one after another, so
    the (k + 1)-th run of the factor's first instruction starts the
    first solve the trace cut short; operations from there on are
    dropped."""
    summary = run.summary
    if summary is None:
        return None
    names, _ = program_maps(run)
    ops = [o for o in summary.ops.get(dev, []) if o.name in names]
    busy = summary.busy_s_of(dev)
    if not busy or scopes._union((o.start, o.end) for o in ops) < (
            scopes.MIN_COVER * busy):
        return None
    starts = collections.defaultdict(list)
    for o in ops:
        if scope_path(names[o.name])[:len(FACTOR)] == FACTOR:
            starts[o.name].append(o.start)
    if not starts:
        return None
    k = min(len(v) for v in starts.values())
    first = min(starts, key=lambda name: min(starts[name]))
    runs = sorted(starts[first])
    cut = runs[k] if len(runs) > k else float("inf")
    return k, [o for o in ops if o.start < cut]


def seconds_per_solve(run, select) -> dict | None:
    """Per chip, the union of the seconds of the operations ``select(
    op_name, opcode)`` picks, per whole solve; None where
    :func:`whole_solves` gives nothing for a chip."""
    if run.summary is None:
        return None
    names, codes = program_maps(run)
    out = {}
    for dev in sorted(run.summary.ops):
        whole = whole_solves(run, dev)
        if whole is None:
            return None
        k, ops = whole
        sel = [(o.start, o.end) for o in ops
               if select(names[o.name], codes.get(o.name, ""))]
        out[dev] = scopes._union(sel) / k
    return out or None


def under(prefix: tuple):
    """A ``select`` for :func:`seconds_per_solve`: operations whose scope
    path starts with ``prefix``."""
    return lambda op_name, code: scope_path(op_name)[:len(prefix)] == prefix


def collective(op_name, code) -> bool:
    return is_collective(code)


def computing(op_name, code) -> bool:
    return not is_collective(code)
