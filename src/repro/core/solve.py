"""SPD solve / factorization public API with engine dispatch.

``PrecisionConfig.engine`` selects the execution engine behind every
entry point here:

* ``"blocked"`` (default) — the flat in-place tile schedule driven by
  the static precision plan (:mod:`repro.core.plan`,
  :mod:`repro.core.blocked`): copy-free, one fused panel-update kernel
  per leaf panel, no recursion.
* ``"tree"`` — the paper's nested recursion (:mod:`repro.core.tree`),
  kept as the reference oracle the equivalence suite checks the blocked
  engine against.
* ``"auto"`` — resolved here, at factor time, against the tuning
  database (:mod:`repro.tune`, docs/TUNING.md): the measured winner for
  the problem size on this backend, falling back to ``"blocked"`` when
  no database entry applies.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.blocked import blocked_potrf, blocked_trsm_left, diag_tri_inv
from repro.core.precision import PrecisionConfig
from repro.core.tree import (pad_factor, pad_spd, tree_potrf, tree_trsm_left)


def _autoresolve(cfg: PrecisionConfig, n: int) -> PrecisionConfig:
    """Resolve ``engine="auto"`` via the tuning DB (no-op otherwise)."""
    if cfg.engine != "auto":
        return cfg
    from repro import tune  # local: tune is a consumer of this module
    return tune.resolve_cfg(cfg, n)


def _potrf(a_padded, cfg: PrecisionConfig):
    if cfg.engine == "blocked":
        return blocked_potrf(a_padded, cfg)
    return tree_potrf(a_padded, cfg)


def _trsm_left(b, l, cfg: PrecisionConfig, *, trans, linvs=None):
    if cfg.engine == "blocked":
        return blocked_trsm_left(b, l, cfg, trans=trans, linvs=linvs)
    return tree_trsm_left(b, l, cfg, trans=trans)


@jax.named_scope("factor")
def cholesky(a, cfg: PrecisionConfig | None = None):
    """Lower Cholesky factor via the mixed-precision engine selected by
    ``cfg.engine``. Handles arbitrary n by identity-padding to the leaf
    size."""
    cfg = cfg or PrecisionConfig()
    n = a.shape[-1]
    return cholesky_padded(a, cfg)[:n, :n]


@jax.named_scope("factor")
def cholesky_padded(a, cfg: PrecisionConfig | None = None):
    """Leaf-padded lower factor (identity tail, shape a multiple of
    ``cfg.leaf``) — the form the solve paths and factor caches consume
    directly, skipping the trim-then-re-pad round trip.
    ``cholesky_padded(a)[:n, :n] == cholesky(a)`` exactly."""
    cfg = cfg or PrecisionConfig()
    a_p, _ = pad_spd(jnp.asarray(a), cfg.leaf)
    return _potrf(a_p, _autoresolve(cfg, a_p.shape[-1]))


@jax.named_scope("solve")
def cholesky_solve(a, b, cfg: PrecisionConfig | None = None, *, l=None,
                   refine=None, linvs=None):
    """Solve A x = b for SPD A via L (L^T x) = b.

    ``b`` may be (n,) or (n, k). Pass a precomputed ``l`` to reuse a
    factorization (the K-FAC optimizer does this across steps); ``l``
    may be either the tight (n, n) factor or the leaf-padded factor
    (``pad_factor``) — the serve engine caches the padded form so
    non-multiple-of-leaf solves skip the re-padding writes. ``linvs``
    additionally reuses the blocked engine's per-diagonal-tile inverses
    (:func:`repro.core.blocked.diag_tri_inv`), which both triangular
    sweeps share.

    ``refine`` (int sweep count or :class:`repro.core.refine.RefineConfig`)
    runs mixed-precision iterative refinement after the base solve: the
    factorization stays in the cheap ladder while residuals are formed in
    the refinement precision, recovering working-precision accuracy.
    Requires ``a``. Returns just ``x`` (use :func:`refine_solve` for the
    full :class:`~repro.core.refine.RefineResult`).

    NOTE: with ``refine`` the result comes back in the RESIDUAL precision
    (f32, or f64 under x64), NOT ``b.dtype`` — casting a refined solution
    back to an f16/bf16 RHS dtype would throw away every digit the sweeps
    just paid for. Callers that need the narrow dtype (none in-tree: the
    K-FAC whitening path and the serve engine both consume the wide
    result) must downcast explicitly.
    """
    cfg = cfg or PrecisionConfig()
    if refine is not None:
        return refine_solve(a, b, cfg, refine=refine, l=l, linvs=linvs).x

    vec = b.ndim == 1
    if vec:
        b = b[:, None]
    n = b.shape[0]
    npad = -(-n // cfg.leaf) * cfg.leaf
    cfg = _autoresolve(cfg, npad)
    if l is None:
        lp = cholesky_padded(a, cfg)
    elif l.shape[-1] == npad:
        lp = l                      # already padded (serve factor cache)
    else:
        lp = pad_factor(l, cfg.leaf)
    if npad == n:
        bp = b
    else:
        bp = jnp.zeros((npad, b.shape[1]), b.dtype).at[:n].set(b)
    if cfg.engine == "blocked" and linvs is None:
        linvs = diag_tri_inv(lp, cfg)
    y = _trsm_left(bp, lp, cfg, trans=False, linvs=linvs)
    x = _trsm_left(y, lp, cfg, trans=True, linvs=linvs)
    x = x[:n]
    return x[:, 0] if vec else x


def solve_factored(l, b, cfg: PrecisionConfig | None = None, *, linvs=None):
    """Two triangular solves with an existing factor (hot K-FAC path).
    ``linvs`` reuses cached diagonal-tile inverses (blocked engine)."""
    return cholesky_solve(None, b, cfg, l=l, linvs=linvs)


def refine_solve(a, b, cfg: PrecisionConfig | None = None, *,
                 refine=None, l=None, col_tol=None, linvs=None):
    """Accuracy-targeted solve: cheap-ladder factorization + iterative
    refinement. Returns the full :class:`~repro.core.refine.RefineResult`
    (solution, residual history, sweeps, converged — per column for an
    (n, k) ``b``). ``refine`` is an int sweep bound or a
    :class:`~repro.core.refine.RefineConfig` (choosing classic IR or
    GMRES-IR); ``None`` means the default 5-sweep IR. ``col_tol`` sets
    per-column tolerances for multi-RHS blocks (the serve scheduler's
    per-request accuracy targets). ``l``/``linvs`` reuse a cached factor
    and its diagonal-tile inverses across sweeps and requests.
    """
    from repro.core import refine as _refine  # circular-import guard
    if cfg is not None and cfg.engine == "auto":
        npad = -(-b.shape[0] // cfg.leaf) * cfg.leaf
        cfg = _autoresolve(cfg, npad)
    return _refine.iterative_refine(a, b, cfg, refine, l=l,
                                    col_tol=col_tol, linvs=linvs)


def logdet(l):
    """log det(A) = 2 sum(log diag(L)) — used by the GP example."""
    return 2.0 * jnp.sum(jnp.log(jnp.diagonal(l)))


@functools.partial(jax.jit, static_argnames=("cfg",))
def cholesky_jit(a, cfg: PrecisionConfig):
    return cholesky(a, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",))
def cholesky_solve_jit(a, b, cfg: PrecisionConfig):
    return cholesky_solve(a, b, cfg)
