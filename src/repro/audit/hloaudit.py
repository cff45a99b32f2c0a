"""Compiled-HLO cross-checks: what XLA actually emits vs the plan.

The jaxpr-level checks (:mod:`repro.audit.conformance`) verify the
*traced* program; XLA can still break conformance after the fact —
constant-folding a GEMM away, fusing a convert out of existence, or
commuting a 16-bit collective convert ahead of the gather (doubling the
wire bytes; the exact bug ``_gather_panel``'s u16 bitcast exists to
prevent). These checks parse ``compiled.as_text()`` through the extended
:func:`repro.launch.hloparse.census` and reconcile:

* total dot FLOPs against ``PrecisionPlan.expected_dot_flops`` (exact:
  the blocked schedule's GEMMs all survive as HLO dots on every backend
  we compile for),
* per-wire-dtype collective bytes against
  ``ShardedPlan.collective_bytes()`` (exact: one gather of the (w, b)
  panel slices per b-wide panel but the last, one (b, b) diagonal
  all-reduce per panel, one (P,) f32 scale gather per quantized panel),
* per-operand-dtype dot FLOPs, reported as a *warning* on CPU — XLA CPU
  legally promotes f16/bf16 dots into f32 containers (the value-level
  rounding still applies), so narrow dot dtypes only appear on MXU
  backends where the check tightens to an error.
"""
from __future__ import annotations

import numpy as np

from repro.audit.report import CheckResult, Violation
from repro.core.plan import ShardedPlan, build_plan
from repro.core.precision import PrecisionConfig

#: relative slack on exact-FLOP reconciliation (float accumulation only)
_REL_TOL = 1e-9


def _compile_hlo(fn, *structs) -> str:
    import jax
    return jax.jit(fn).lower(*structs).compile().as_text()


def audit_hlo_single(n: int, cfg: PrecisionConfig) -> CheckResult:
    """Compiled blocked_potrf: dot-FLOP reconciliation vs the plan."""
    import jax
    import jax.numpy as jnp
    from repro.core.blocked import blocked_potrf
    from repro.launch.hloparse import census
    target = f"hlo-blocked[n={n},{cfg.describe()}]"
    hlo = _compile_hlo(lambda x: blocked_potrf(x, cfg),
                       jax.ShapeDtypeStruct((n, n), jnp.float32))
    cen = census(hlo)
    plan = build_plan(n, cfg)
    want_by = plan.expected_dot_flops(cfg.high_name)
    want = sum(want_by.values())
    viols = []
    got = cen["flops"]
    if want and abs(got - want) > _REL_TOL * want:
        viols.append(Violation(
            "hlo-dot-flops", target,
            f"compiled module runs {got:.0f} dot flops, plan prices "
            f"{want:.0f} — XLA folded or duplicated a planned GEMM"))
    by = cen["dot_flops_by_dtype"]
    narrow_planned = {k: v for k, v in want_by.items()
                      if k not in ("f32", "f64")}
    narrow_keys = [k for k in by if not k.startswith(("f32", "f64"))]
    if narrow_planned and not narrow_keys:
        viols.append(Violation(
            "hlo-dot-dtype", target,
            f"plan prices {sum(narrow_planned.values()):.0f} flops at "
            f"{sorted(narrow_planned)} but every compiled dot is wide "
            f"({sorted(by)}); expected on CPU (XLA promotes narrow dots "
            "into f32 containers; value rounding still applies) — on an "
            "MXU backend this is a lost speedup", severity="warn"))
    return CheckResult("hlo-blocked", target, viols)


def audit_hlo_dist(n: int, cfg: PrecisionConfig, nshards: int, *,
                   compress: bool = True, sharded=None) -> CheckResult:
    """Compiled dist_cholesky: exact per-wire-dtype collective bytes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from repro.core.distributed import dist_cholesky
    from repro.launch.hloparse import census
    target = (f"hlo-dist[n={n},P={nshards},{cfg.describe()}"
              f"{'' if compress else ',raw-wire'}]")
    devs = jax.devices()
    if len(devs) < nshards:
        return CheckResult("hlo-dist", target, [Violation(
            "dist-untestable", target,
            f"only {len(devs)} devices visible, need {nshards}",
            severity="warn")])
    mesh = Mesh(np.array(devs[:nshards]), ("model",))
    hlo = _compile_hlo(
        lambda x: dist_cholesky(x, mesh, cfg, compress_comm=compress),
        jax.ShapeDtypeStruct((n, n), jnp.float32))
    del jnp
    cen = census(hlo)
    sp = sharded or ShardedPlan(build_plan(n, cfg), nshards)
    npan = sp.npanels
    exp = sp.collective_bytes(compress)
    n_scale = sum(1 for row in sp.comm_table()[:npan - 1]
                  if compress and row["quant"])

    got = cen["collective_bytes_by_dtype"]
    viols = []
    for dt in sorted(set(exp) | set(got)):
        g, e = got.get(dt, 0.0), exp.get(dt, 0.0)
        if g == e:
            continue
        panels = [row["panel"] for row in sp.comm_table()[:npan - 1]
                  if (row["wire"] if compress else "f32") == dt]
        viols.append(Violation(
            "hlo-collective-bytes", target,
            f"{dt} collective bytes: compiled {g:.0f}, plan prices "
            f"{e:.0f} (panels gathered on a {dt} wire: {panels}) — a "
            "convert commuted across the collective or a gather changed "
            "wire dtype"))
    counts = cen["collectives"]
    want_ag = (npan - 1) + n_scale
    if counts["all-gather"]["count"] != want_ag:
        viols.append(Violation(
            "hlo-collective-bytes", target,
            f"compiled all-gather count {counts['all-gather']['count']:.0f}"
            f" != scheduled {want_ag}"))
    if counts["all-reduce"]["count"] != npan:
        viols.append(Violation(
            "hlo-collective-bytes", target,
            f"compiled all-reduce count {counts['all-reduce']['count']:.0f}"
            f" != scheduled {npan} diagonal broadcasts"))
    return CheckResult("hlo-dist", target, viols)
