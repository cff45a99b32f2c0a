"""Plan-conformance checks over traced jaxprs (nothing is executed).

Each ``audit_*`` function traces one solver entry point with
``jax.make_jaxpr`` on ShapeDtypeStructs, runs the
:mod:`repro.audit.dtypeflow` walker, and reconciles the result against
the static expectations :class:`repro.core.plan.PrecisionPlan` exposes:

* ``audit_blocked`` — every dot's effective precision and every
  storage-round/quantize event in ``blocked_potrf`` matches the plan's
  per-tile compute/storage levels (FLOPs and rounded elements are
  compared *exactly*, per dtype); the executed plan's tables and
  ``panel_meta`` agree with the pristine ``build_plan`` geometry (this
  is what names the exact tile when a mutated plan sneaks in); no
  f16<->bf16 double-round and no promotion wider than the container.
* ``audit_solve`` / ``audit_refine`` — the triangular solves and the
  refinement loop are lossless: all dots wide, zero rounding events.
* ``audit_dist`` — the distributed panel sweep's collectives: one
  gather of the (w, b) panel slices for every b-wide panel but the
  last, each on the wire dtype ``ShardedPlan.comm_name(j)`` gives it,
  one (b, b) diagonal psum per panel, and scale gathers exactly where
  the plan quantizes the wire.
"""
from __future__ import annotations

import numpy as np

from repro.audit import dtypeflow
from repro.audit.report import CheckResult, Violation
from repro.core.dtypes import NP_TO_HLO, WIRE_DTYPE
from repro.core.plan import PrecisionPlan, ShardedPlan, build_plan
from repro.core.precision import PrecisionConfig


def _structs(*shapes, dtype=None):
    import jax
    import jax.numpy as jnp
    dt = dtype or jnp.float32
    return tuple(jax.ShapeDtypeStruct(s, dt) for s in shapes)


def _diff_tables(plan_exec, pristine, target: str) -> list:
    """Exact-tile diff of an executed plan against the pristine geometry."""
    out = []
    for attr, what in (("levels", "compute"), ("store_levels", "storage")):
        a = np.asarray(getattr(plan_exec, attr))
        b = np.asarray(getattr(pristine, attr))
        if a.shape != b.shape:
            out.append(Violation(
                "plan-table-mismatch", target,
                f"{what}-level table shape {a.shape} != plan {b.shape}"))
            continue
        for i, j in zip(*np.nonzero(a != b)):
            if j > i:
                continue            # mirrored upper triangle
            out.append(Violation(
                "plan-table-mismatch", target,
                f"{what} level of tile ({i}, {j}) is {int(a[i, j])} "
                f"({plan_exec.cfg.name_at(int(a[i, j]))}), plan says "
                f"{int(b[i, j])} ({pristine.cfg.name_at(int(b[i, j]))})",
                tile=(int(i), int(j))))
    return out


def _diff_meta(plan_exec, target: str) -> list:
    """Cross-check the executed plan's ``panel_meta`` (what the blocked
    schedule actually consumes) against its own level tables — catches a
    schedule that drops or rewrites a storage round without touching the
    tables."""
    out = []
    for p in range(plan_exec.ntiles - 1):
        got = plan_exec.panel_meta(p)
        want = PrecisionPlan.panel_meta(plan_exec, p)
        if got == want:
            continue
        for k, (g, w) in enumerate(zip(got.store_names, want.store_names)):
            if g != w:
                out.append(Violation(
                    "plan-meta-mismatch", target,
                    f"panel {p}: storage round of tile ({p + 1 + k}, {p}) "
                    f"is {g!r} in the executed schedule, plan tables say "
                    f"{w!r}", panel=p, tile=(p + 1 + k, p)))
        for i, (gr, wr) in enumerate(zip(got.pair_names, want.pair_names)):
            for j, (g, w) in enumerate(zip(gr, wr)):
                if g != w:
                    out.append(Violation(
                        "plan-meta-mismatch", target,
                        f"panel {p}: trailing pair ({p + 1 + i}, "
                        f"{p + 1 + j}) computes at {g!r}, plan tables say "
                        f"{w!r}", panel=p, tile=(p + 1 + i, p + 1 + j)))
        if not out:
            out.append(Violation(
                "plan-meta-mismatch", target,
                f"panel {p}: quant flags differ from plan tables",
                panel=p))
    return out


def _attribute_panels(plan_exec, pristine, container, kind) -> str:
    """Name the panels whose expectations differ (trace-level findings
    can only localize to the panel granularity)."""
    bad = []
    for p in range(pristine.ntiles - 1):
        if kind == "dots":
            a = plan_exec.panel_dot_flops(p, container)
            b = pristine.panel_dot_flops(p, container)
        else:
            a = plan_exec.panel_round_elems(p, container)
            b = pristine.panel_round_elems(p, container)
        if a != b:
            bad.append(p)
    return f" (panels {bad})" if bad else ""


def _flow_violations(res, pristine, plan_exec, container, target) -> list:
    out = []
    got_dots = res.dot_flops_by_name()
    want_dots = pristine.expected_dot_flops(container)
    if got_dots != want_dots:
        where = _attribute_panels(plan_exec, pristine, container, "dots")
        for nm in sorted(set(got_dots) | set(want_dots)):
            g, w = got_dots.get(nm, 0.0), want_dots.get(nm, 0.0)
            if g != w:
                out.append(Violation(
                    "plan-dot-precision", target,
                    f"{nm} GEMM flops traced={g:.0f} planned={w:.0f}"
                    + where))
    got_r = res.round_elems_by_name()
    want_r = pristine.expected_round_elems(container)
    if got_r != want_r:
        where = _attribute_panels(plan_exec, pristine, container, "rounds")
        for nm in sorted(set(got_r) | set(want_r)):
            g, w = got_r.get(nm, 0), want_r.get(nm, 0)
            if g < w:
                out.append(Violation(
                    "plan-missing-round", target,
                    f"{nm} storage-round events cover {g} elements, plan "
                    f"requires {w}" + where))
            elif g > w:
                out.append(Violation(
                    "plan-extra-round", target,
                    f"{nm} storage-round events cover {g} elements, plan "
                    f"allows only {w}" + where))
    for r in res.double_rounds():
        out.append(Violation(
            "double-rounding", target,
            f"value on the {r.prev} grid re-rounded to {r.name} "
            f"({r.elems} elements): incommensurate 16-bit grids"))
    from repro.core.dtypes import BYTES
    cw = BYTES[container]
    for src, dst, elems in res.promotions:
        if BYTES.get(dst, 0) > cw:
            out.append(Violation(
                "promotion", target,
                f"unplanned {src}->{dst} promotion of {elems} elements "
                f"(container is {container})"))
    return out


def audit_blocked(n: int, cfg: PrecisionConfig, *, plan=None,
                  label: str | None = None) -> CheckResult:
    """Dtype-flow conformance of ``blocked_potrf`` at size ``n``.

    ``plan`` overrides the executed plan (the mutation self-test's
    injection point); expectations always come from the pristine
    ``build_plan(n, cfg)``.
    """
    from repro.core.blocked import blocked_potrf
    target = label or f"blocked[n={n},{cfg.describe()}]"
    pristine = build_plan(n, cfg)
    plan_exec = plan if plan is not None else pristine
    container = cfg.high_name
    viols = _diff_tables(plan_exec, pristine, target)
    viols += _diff_meta(plan_exec, target)
    (a,) = _structs((n, n))
    res = dtypeflow.trace(blocked_potrf, a, cfg=cfg, plan=plan_exec)
    viols += _flow_violations(res, pristine, plan_exec, container, target)
    return CheckResult("blocked-conformance", target, viols)


def audit_solve(n: int, cfg: PrecisionConfig, nrhs: int = 8) -> CheckResult:
    """The triangular solves must be lossless: O(n^2) work, so any
    narrow dot or rounding event there costs digits for nothing."""
    from repro.core.blocked import blocked_trsm_left
    target = f"trsm[n={n},{cfg.describe()}]"
    b, l = _structs((n, nrhs), (n, n))
    viols = []
    for trans in (False, True):
        res = dtypeflow.trace(
            lambda bb, ll: blocked_trsm_left(bb, ll, cfg, trans=trans),
            b, l)
        for nm, f in res.dot_flops_by_name().items():
            if nm != cfg.high_name:
                viols.append(Violation(
                    "solve-narrow", target,
                    f"trans={trans} solve runs {f:.0f} GEMM flops at "
                    f"{nm}; solves must stay at {cfg.high_name}"))
        rr = res.round_elems_by_name()
        if rr:
            viols.append(Violation(
                "solve-narrow", target,
                f"trans={trans} solve emits rounding events {rr}; the "
                "solve path must not round"))
    return CheckResult("solve-conformance", target, viols)


def audit_refine(n: int, cfg: PrecisionConfig, nrhs: int = 4,
                 sweeps: int = 2) -> CheckResult:
    """The refinement loop (given a factor) must be lossless outside the
    factor itself: residuals and corrections never round narrow."""
    import jax.numpy as jnp
    from repro.core.refine import RefineConfig, iterative_refine
    target = f"refine[n={n},{cfg.describe()}]"
    b = cfg.leaf
    a, rhs, l = _structs((n, n), (n, nrhs), (n, n))
    linvs = _structs((n // b, b, b))[0]
    rcfg = RefineConfig(max_sweeps=sweeps, tol=0.0)
    res = dtypeflow.trace(
        lambda aa, bb, ll, li: iterative_refine(
            aa, bb, cfg, rcfg, l=ll, linvs=li),
        a, rhs, l, linvs)
    del jnp
    viols = []
    for nm, f in res.dot_flops_by_name().items():
        if nm != cfg.high_name and nm != "f64":
            viols.append(Violation(
                "refine-narrow", target,
                f"refinement sweep runs {f:.0f} GEMM flops at {nm}; "
                f"sweeps must stay at >= {cfg.high_name}"))
    rr = res.round_elems_by_name()
    if rr:
        viols.append(Violation(
            "refine-narrow", target,
            f"refinement sweep emits rounding events {rr}"))
    return CheckResult("refine-conformance", target, viols)


def audit_dist(n: int, cfg: PrecisionConfig, nshards: int, *,
               compress: bool = True, sharded=None) -> CheckResult:
    """Traced-collective conformance of ``dist_cholesky``.

    ``sharded`` overrides the *expected* schedule source only when the
    self-test wants expectations from a pristine view while the traced
    executor runs a patched one; normally expectations come from
    ``ShardedPlan(build_plan(n, cfg), nshards)``.
    """
    import jax
    from jax.sharding import Mesh
    from repro.core.distributed import dist_cholesky
    target = (f"dist[n={n},P={nshards},{cfg.describe()}"
              f"{'' if compress else ',raw-wire'}]")
    devs = jax.devices()
    if len(devs) < nshards:
        return CheckResult("dist-conformance", target, [Violation(
            "dist-untestable", target,
            f"only {len(devs)} devices visible, need {nshards} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count)",
            severity="warn")])
    mesh = Mesh(np.array(devs[:nshards]), ("model",))
    sp = sharded or ShardedPlan(build_plan(n, cfg), nshards)
    w, b, npan = n // nshards, sp.panel_width, sp.npanels
    (a,) = _structs((n, n))
    res = dtypeflow.trace(
        lambda x: dist_cholesky(x, mesh, cfg, compress_comm=compress), a)

    viols = []
    gathers = [c for c in res.collectives
               if c.prim == "all_gather" and c.shape == (w, b)]
    scale_gathers = [c for c in res.collectives
                     if c.prim == "all_gather" and c.shape == ()]
    psums = [c for c in res.collectives
             if c.prim == "psum" and c.shape == (b, b)]
    if len(gathers) != npan - 1:
        viols.append(Violation(
            "collective-count", target,
            f"traced {len(gathers)} (w, b) panel gathers, schedule has "
            f"{npan - 1}"))
    if len(psums) != npan:
        viols.append(Violation(
            "collective-count", target,
            f"traced {len(psums)} diagonal psums, schedule has {npan}"))
    expect_scales = 0
    for j, g in enumerate(gathers[:npan - 1]):
        nm, q = sp.comm_name(j), sp.comm_quant(j)
        want_wire = WIRE_DTYPE[nm] if compress else "f32"
        got_wire = NP_TO_HLO.get(g.wire, g.wire)
        if got_wire != want_wire:
            viols.append(Violation(
                "collective-wire-dtype", target,
                f"panel {j} gathered on a {got_wire} wire; plan comm "
                f"level is {nm} => {want_wire} wire", panel=j))
        expect_scales += int(compress and q)
    if compress and len(scale_gathers) != expect_scales:
        viols.append(Violation(
            "collective-count", target,
            f"traced {len(scale_gathers)} scale gathers, quantized "
            f"schedule has {expect_scales}"))
    for c in res.collectives:
        if c.prim in ("psum", "all_gather") and "64" in c.wire:
            viols.append(Violation(
                "promotion", target,
                f"{c.prim} moves {c.wire} (shape {c.shape}); nothing in "
                "the distributed sweep is planned wider than f32"))
    return CheckResult("dist-conformance", target, viols)
