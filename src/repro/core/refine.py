"""Mixed-precision iterative refinement over the tree-Cholesky ladders.

The paper's recursive precision ladder trades digits for MXU throughput;
this module claws the digits back the HPL-MxP way: factor ONCE in the
cheap ladder, then iterate

    r_k = b - A x_k          (high "residual" precision)
    d_k = (L L^T)^{-1} r_k   (cheap mixed-precision tree solves)
    x_{k+1} = x_k + d_k      (high precision accumulate)

Classic IR converges linearly at rate ~ cond(A) * eps(ladder); each sweep
costs two O(n^2) tree-TRSMs + one O(n^2) residual GEMM, so a handful of
sweeps turns a ~3-digit f16 factorization into a working-precision solve
at low-precision factorization speed (Abdelfattah et al. 2020, Dongarra &
Luszczek 2025). For ill-conditioned systems where classic IR stalls
(cond(A) * eps(ladder) >~ 1), :func:`gmres_refine` runs restarted GMRES
right-preconditioned by the same cheap factor (GMRES-IR, Carson &
Higham 2017).

Everything here is jit-compatible: iteration bounds are static, early
exit is a ``lax.while_loop``, and results come back as a
:class:`RefineResult` pytree (solution, residual history, sweep count,
converged flag). The operator-level entry points (:func:`refine_operator`,
:func:`refine_steps`) take ``matvec``/``correct`` callables so callers
that already hold a factor — the K-FAC optimizer, the serve engine — can
reuse it across sweeps without re-factorizing.

Multi-RHS refinement is PER-COLUMN: a (n, k) right-hand side gets a
per-column convergence mask, per-column residual history, per-column
sweep counts and (optionally, via ``tol``) per-column tolerances, so one
slow column doesn't burn sweeps for converged neighbors — the serve
scheduler stacks cross-request RHS into one such call. Columns that
converge (or stall) are frozen at their best iterate while the rest keep
sweeping; each sweep forms ONE residual (carried between iterations, and
fused into a single Pallas kernel on TPU — see
:mod:`repro.kernels.residual`) instead of the naive two.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import annotate_function

from repro.core.precision import DTYPES, PrecisionConfig
from repro.core.solve import cholesky_padded, solve_factored
from repro.kernels import ops

_TINY = 1e-30


@dataclasses.dataclass(frozen=True)
class RefineConfig:
    """Static refinement policy (hashable: usable as a jit static arg)."""

    max_sweeps: int = 5          # classic-IR sweeps / GMRES restarts
    tol: float = 1e-10           # relative-residual early-exit target
    method: str = "ir"           # "ir" | "gmres"
    gmres_restart: int = 16      # Krylov dimension per GMRES cycle
    residual_dtype: str | None = None  # None -> f64 if x64 is on, else f32

    def __post_init__(self):
        assert self.max_sweeps >= 0, self.max_sweeps
        assert self.method in ("ir", "gmres"), self.method
        assert self.gmres_restart >= 1, self.gmres_restart
        if self.residual_dtype is not None:
            assert self.residual_dtype in DTYPES, self.residual_dtype

    def rdtype(self):
        if self.residual_dtype is not None:
            return DTYPES[self.residual_dtype]
        return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


class RefineResult(NamedTuple):
    """Pytree result of a refinement run.

    ``history[0]`` is the pre-refinement relative residual; ``history[k]``
    the residual after sweep k (``nan`` for sweeps never run — including,
    for multi-RHS, sweeps where that column was already frozen).

    For a vector ``b`` the per-column fields are scalars (the PR-1
    contract); for an (n, k) ``b`` they are (k,)-shaped: residual,
    iterations and converged are PER COLUMN and history is
    [max_sweeps + 1, k].
    """

    x: jax.Array            # refined solution, residual dtype
    residual: jax.Array     # final relative residual, scalar | (k,)
    history: jax.Array      # [max_sweeps + 1(, k)] relative residuals
    iterations: jax.Array   # int32 sweeps actually taken, scalar | (k,)
    converged: jax.Array    # bool residual <= tol, scalar | (k,)


# ---------------------------------------------------------------------------
# operator-level core (factor-agnostic; K-FAC and serve reuse these)
# ---------------------------------------------------------------------------
def scaled_solve(correct: Callable) -> Callable:
    """Wrap a linear corrector with PER-COLUMN absmax pre-scaling.

    As IR converges the residual shrinks below f16's smallest normal
    (6.1e-5) and the per-block quantizer — which only scales *down*
    (alpha >= 1) — lets it underflow into subnormals, stalling
    convergence. Scaling r to O(1) before the solve and back after is
    exact for a linear operator and is what HPL-MxP does.

    The scale is per COLUMN for multi-RHS blocks: the serve scheduler
    stacks unrelated requests whose residual magnitudes can differ by
    orders of magnitude (different RHS norms, different convergence
    stages), and a single joint absmax would underflow every small
    column next to a large neighbor. Column-wise scaling is still exact
    — the corrector solves columns independently.
    """
    def wrapped(r):
        absmax = (jnp.max(jnp.abs(r), axis=0, keepdims=True)
                  if r.ndim == 2 else jnp.max(jnp.abs(r)))
        s = jnp.maximum(absmax, _TINY)
        return correct(r / s) * s

    return wrapped



def _colnorm(v):
    """Per-column 2-norm: scalar for a vector, (k,) for an (n, k) block."""
    return jnp.linalg.norm(v, axis=0) if v.ndim == 2 else jnp.linalg.norm(v)


@jax.named_scope("sweep")
def _masked_sweep(sweep: Callable, resid: Callable, relnorm: Callable,
                  x, r, rel, bx, brel, its, stall, act):
    """One per-column-masked refinement sweep — the shared inner step.

    Both refinement drivers run exactly this math per sweep: the jitted
    window loop (:func:`_refine_loop`) inside a ``lax.while_loop``, and
    the re-entrant slot stepper (:class:`RefineStepper`) once per host
    visit, so a column's trajectory is identical whichever loop drives
    it (the continuous==window determinism contract, pinned by
    tests/test_serve_continuous.py).  ``act`` masks the sweep: frozen
    columns keep their iterate, their residual columns are zeroed out of
    the sweep input, and their bookkeeping (best iterate, stall counter,
    sweep count) does not advance.
    """
    rm = r * act.astype(r.dtype)             # mask frozen residuals
    xn = jnp.where(act, sweep(x, rm), x)     # frozen columns keep x
    rn = resid(xn)
    reln = jnp.where(act, relnorm(rn), rel)
    improved = reln < brel                   # new best this sweep?
    bx = jnp.where(act & improved, xn, bx)
    brel = jnp.where(act, jnp.minimum(reln, brel), brel)
    stall = jnp.where(act, jnp.where(improved, 0, stall + 1), stall)
    return xn, rn, reln, bx, brel, its + act.astype(jnp.int32), stall


def _refine_loop(sweep: Callable, resid: Callable, relnorm: Callable, x0,
                 rcfg: RefineConfig, tol=None) -> RefineResult:
    """Shared outer loop: run ``sweep`` until tol / max_sweeps / stall,
    with PER-COLUMN bookkeeping for multi-RHS blocks.

    ``resid(x)`` forms the residual (one GEMM — it is carried between
    iterations so each sweep costs a single residual evaluation, and is
    the seam the fused Pallas kernel plugs into); ``relnorm(r)`` maps it
    to per-column relative norms; ``sweep(x, r)`` applies one correction.

    Tracks the BEST iterate seen per column, not the last one: when a
    column stalls or diverges (residual precision floor, preconditioner
    too weak) the caller gets back an x no worse than its starting
    point. A column exits on convergence or after TWO consecutive
    non-improving sweeps (no new per-column best) — a single flat sweep
    is a normal transient for GMRES-IR restarts and non-normal IR
    iterations, so it must not abort the run. Converged/stalled columns
    are frozen while the rest keep sweeping, so one slow RHS doesn't
    burn sweeps for its neighbors; their residual columns are zeroed
    out of the sweep input so a frozen (possibly diverged) column can't
    hijack a joint GMRES-IR restart. ``tol`` may be a per-column array
    (the serve scheduler passes per-request accuracy targets); it
    defaults to the scalar ``rcfg.tol``.
    """
    r0 = resid(x0)
    rel0 = relnorm(r0)
    tol = jnp.asarray(rcfg.tol if tol is None else tol, rel0.dtype)
    hist0 = jnp.full((rcfg.max_sweeps + 1,) + rel0.shape, jnp.nan,
                     rel0.dtype).at[0].set(rel0)
    zero = jnp.zeros(rel0.shape, jnp.int32)
    state = (x0, r0, rel0, x0, rel0, hist0, zero, zero, jnp.int32(0))

    def active(brel, stall):
        return (brel > tol) & (stall < 2)

    def cond(s):
        _, _, _, _, brel, _, _, stall, i = s
        return (i < rcfg.max_sweeps) & jnp.any(active(brel, stall))

    def body(s):
        x, r, rel, bx, brel, hist, its, stall, i = s
        act = active(brel, stall)
        xn, rn, reln, bx, brel, its, stall = _masked_sweep(
            sweep, resid, relnorm, x, r, rel, bx, brel, its, stall, act)
        hist = hist.at[i + 1].set(jnp.where(act, reln, jnp.nan))
        return (xn, rn, reln, bx, brel, hist, its, stall, i + 1)

    _, _, _, bx, brel, hist, its, _, _ = lax.while_loop(cond, body, state)
    return RefineResult(bx, brel, hist, its, brel <= tol)


# ---------------------------------------------------------------------------
# re-entrant slot-block refinement (continuous batching)
# ---------------------------------------------------------------------------
class SlotState(NamedTuple):
    """Pytree state of a :class:`RefineStepper` slot block.

    One RHS column per slot; ``(n, S)`` arrays hold the block, ``(S,)``
    arrays the per-slot bookkeeping.  Empty slots are all-zero with
    ``occ=False``, ``bnorm=1`` — algebraically inert (their residual is
    0, their correction is 0) so they cost nothing but their share of
    the block GEMM.
    """

    x: jax.Array       # (n, S) current iterate (residual dtype)
    r: jax.Array       # (n, S) carried residual b - A x
    b: jax.Array       # (n, S) right-hand sides
    bx: jax.Array      # (n, S) best iterate seen per slot
    rel: jax.Array     # (S,) latest relative residual
    brel: jax.Array    # (S,) best relative residual
    bnorm: jax.Array   # (S,) ||b|| denominators (1 for empty slots)
    tol: jax.Array     # (S,) per-slot tolerance
    occ: jax.Array     # (S,) bool: slot holds a live column
    its: jax.Array     # (S,) int32 sweeps taken
    stall: jax.Array   # (S,) int32 consecutive non-improving sweeps


class RefineStepper:
    """Re-entrant, slot-addressed refinement loop — the continuous-
    batching core (vLLM's idiom applied to IR sweeps).

    :func:`_refine_loop` runs a whole refinement *window* inside one
    ``lax.while_loop``: every column joins at sweep 0 and the batch
    returns when the last column exits.  The stepper runs the SAME
    per-column-masked sweep (:func:`_masked_sweep`, jitted once per
    ``(n, slots)`` shape) but yields to the host between sweeps, so a
    serving loop can **retire** converged/stalled columns mid-flight
    (freeing their slots) and **join** newly arrived RHS columns into
    the running block without waiting for a window boundary.

    Classic IR is column-local — the correction, residual and scaling
    all act per column — so a column's trajectory is bitwise identical
    whether it runs here or in a window, and independent of which
    co-tenants share its block.  GMRES-IR's joint Krylov space is NOT
    column-local; continuous serving therefore only accepts
    ``method="ir"`` (the scheduler windows GMRES requests).

    ``correct(r)`` applies the cheap factor (already per-column scaled,
    e.g. :func:`scaled_solve`); ``resid(x, b)`` forms ``b - A x`` in the
    residual precision for the whole block (the fused-kernel seam).
    Host-side helpers (:meth:`active_mask`, :meth:`done_mask`,
    :meth:`retire`, :meth:`join`) move only ``(S,)``-sized vectors over
    the device boundary; the block itself stays resident.
    """

    def __init__(self, correct: Callable, resid: Callable, *, n: int,
                 slots: int, rcfg: RefineConfig):
        assert slots >= 1, slots
        self.n, self.slots, self.rcfg = n, slots, rcfg
        self.rdtype = rcfg.rdtype()
        self._correct, self._resid = correct, resid
        self._step = jax.jit(self._step_impl)

    # -- state constructors -------------------------------------------------
    def init(self) -> SlotState:
        n, s, dt = self.n, self.slots, self.rdtype
        z, zs = jnp.zeros((n, s), dt), jnp.zeros((s,), dt)
        return SlotState(x=z, r=z, b=z, bx=z, rel=zs, brel=zs,
                         bnorm=jnp.ones((s,), dt), tol=zs,
                         occ=jnp.zeros((s,), bool),
                         its=jnp.zeros((s,), jnp.int32),
                         stall=jnp.zeros((s,), jnp.int32))

    @functools.partial(annotate_function, name="repro.refine.join")
    def join(self, state: SlotState, idx, b_cols, x0_cols,
             tols) -> SlotState:
        """Insert columns into free slots mid-flight.

        ``idx`` are free slot indices (``len(idx)`` columns), ``b_cols``
        / ``x0_cols`` the ``(n, k)`` right-hand sides and initial
        iterates (the caller's base solve — unscaled, exactly like the
        window path's ``x0``), ``tols`` the per-column tolerances.  The
        block residual is recomputed once; live columns' residuals are
        reproduced bitwise (``r`` always equals ``resid(x, b)``), so a
        join never perturbs an in-flight column.
        """
        idx = jnp.asarray(idx, jnp.int32)
        b_cols = jnp.asarray(b_cols, self.rdtype)
        x0_cols = jnp.asarray(x0_cols, self.rdtype)
        new = jnp.zeros((self.slots,), bool).at[idx].set(True)
        x = state.x.at[:, idx].set(x0_cols)
        b = state.b.at[:, idx].set(b_cols)
        bnorm = state.bnorm.at[idx].set(
            jnp.maximum(_colnorm(b_cols), _TINY).astype(self.rdtype))
        r = self._resid(x, b)
        rel = jnp.where(new, (_colnorm(r) / bnorm).astype(self.rdtype),
                        state.rel)
        return SlotState(
            x=x, r=r, b=b, bx=state.bx.at[:, idx].set(x0_cols),
            rel=rel, brel=jnp.where(new, rel, state.brel), bnorm=bnorm,
            tol=state.tol.at[idx].set(jnp.asarray(tols, self.rdtype)),
            occ=state.occ | new,
            its=state.its.at[idx].set(0), stall=state.stall.at[idx].set(0))

    # -- the sweep ----------------------------------------------------------
    def _active(self, state: SlotState):
        return (state.occ & (state.brel > state.tol) & (state.stall < 2)
                & (state.its < self.rcfg.max_sweeps))

    def _step_impl(self, state: SlotState):
        act = self._active(state)

        def resid(x):
            return self._resid(x, state.b)

        def relnorm(r):
            return (_colnorm(r) / state.bnorm).astype(self.rdtype)

        def sweep(x, rm):
            return x + self._correct(rm).astype(self.rdtype)

        xn, rn, reln, bx, brel, its, stall = _masked_sweep(
            sweep, resid, relnorm, state.x, state.r, state.rel, state.bx,
            state.brel, state.its, state.stall, act)
        return SlotState(x=xn, r=rn, b=state.b, bx=bx, rel=reln,
                         brel=brel, bnorm=state.bnorm, tol=state.tol,
                         occ=state.occ, its=its, stall=stall), act

    def step(self, state: SlotState):
        """One masked sweep over the block; returns ``(state, act)``
        where ``act`` is the numpy mask of slots the sweep advanced."""
        state, act = self._step(state)
        return state, np.asarray(act)

    # -- host-side bookkeeping ----------------------------------------------
    def active_mask(self, state: SlotState):
        """Numpy mask of slots that would advance on the next sweep."""
        return np.asarray(self._active(state))

    def done_mask(self, state: SlotState):
        """Numpy mask of occupied slots that are finished (converged,
        stalled twice, or out of sweeps) and ready to retire."""
        return np.asarray(state.occ) & ~self.active_mask(state)

    def retire(self, state: SlotState, idx):
        """Free slots ``idx``; returns ``(state, results)``.

        ``results[i]`` is ``(x, relres, sweeps, converged)`` for slot
        ``idx[i]`` — the BEST iterate seen (the window loop's contract),
        its relative residual, sweep count and convergence flag.  The
        freed slots are zeroed so they stay algebraically inert; a
        retired column is never touched again (its result is copied out
        here, before the slot is recycled).
        """
        ja = jnp.asarray(idx, jnp.int32)
        xs = state.bx[:, ja]                     # one device gather
        brel = np.asarray(state.brel[ja])
        its = np.asarray(state.its[ja])
        conv = brel <= np.asarray(state.tol[ja])
        results = [(xs[:, i], float(brel[i]), int(its[i]), bool(conv[i]))
                   for i in range(len(idx))]
        zc = jnp.zeros((self.n, len(idx)), self.rdtype)
        zv = jnp.zeros((len(idx),), self.rdtype)
        zi = jnp.zeros((len(idx),), jnp.int32)
        state = SlotState(
            x=state.x.at[:, ja].set(zc), r=state.r.at[:, ja].set(zc),
            b=state.b.at[:, ja].set(zc), bx=state.bx.at[:, ja].set(zc),
            rel=state.rel.at[ja].set(zv), brel=state.brel.at[ja].set(zv),
            bnorm=state.bnorm.at[ja].set(jnp.ones_like(zv)),
            tol=state.tol.at[ja].set(zv),
            occ=state.occ.at[ja].set(False),
            its=state.its.at[ja].set(zi), stall=state.stall.at[ja].set(zi))
        return state, results


def refine_operator(matvec: Callable, correct: Callable, b, x0,
                    rcfg: RefineConfig, *, resid: Callable | None = None,
                    tol=None) -> RefineResult:
    """Classic IR on an abstract operator.

    ``matvec(x)`` applies A in the residual precision; ``correct(r)``
    applies the cheap approximate inverse (e.g. two tree-TRSMs with a
    cached factor). ``resid`` overrides the residual evaluation
    ``b - matvec(x)`` — :func:`iterative_refine` passes the fused Pallas
    kernel here. ``tol`` may be per-column (see :func:`_refine_loop`).
    Early-exits once the relative residual hits tolerance, refinement
    stops improving for two consecutive sweeps, or ``rcfg.max_sweeps``
    sweeps have run; returns the best iterate seen (per column).
    """
    rdtype = rcfg.rdtype()
    b = b.astype(rdtype)
    x0 = x0.astype(rdtype)
    if resid is None:
        def resid(x):
            return b - matvec(x)
    bnorm = jnp.maximum(_colnorm(b), _TINY)

    def relnorm(r):
        return (_colnorm(r) / bnorm).astype(rdtype)

    def sweep(x, r):
        return x + correct(r).astype(rdtype)

    return _refine_loop(sweep, resid, relnorm, x0, rcfg, tol)


def refine_steps(matvec: Callable, correct: Callable, b, x, sweeps: int):
    """Fixed-sweep classic IR, fully unrolled — the hot-path variant for
    per-step optimizer use (no norms, no control flow, vmap-friendly)."""
    for _ in range(sweeps):
        x = x + correct(b - matvec(x)).astype(x.dtype)
    return x


def gmres_operator(matvec: Callable, correct: Callable, b, x0,
                   rcfg: RefineConfig, *, resid: Callable | None = None,
                   tol=None) -> RefineResult:
    """Restarted GMRES right-preconditioned by ``correct`` (GMRES-IR).

    Each restart runs an ``rcfg.gmres_restart``-dimensional Arnoldi
    process on ``A M^{-1}`` (modified Gram-Schmidt), solves the small
    least-squares problem, and applies ``x += M^{-1} V y``. The outer
    loop recomputes the TRUE residual in the residual precision and
    shares :func:`_refine_loop` with classic IR, so ``max_sweeps``
    counts restarts and the two methods share a result contract
    (best-iterate per column, two-sweep stall detection, per-column
    history). The Krylov cycle itself stays joint across RHS columns
    (the flattened A (x) I_k operator); only the outer convergence
    bookkeeping is per column.
    """
    rdtype = rcfg.rdtype()
    m = rcfg.gmres_restart
    b = b.astype(rdtype)
    x0 = x0.astype(rdtype)
    if resid is None:
        def resid(x):
            return b - matvec(x)
    shape = b.shape
    n = b.size  # multi-RHS solves flatten: A (x) I_k is block-diagonal
    bnorm = jnp.maximum(_colnorm(b), _TINY)

    def opvec(v):  # v flat, in the preconditioned (u) space
        return matvec(correct(v.reshape(shape)).astype(rdtype)).ravel()

    def cycle(r_flat):
        beta = jnp.linalg.norm(r_flat)
        v0 = r_flat / jnp.maximum(beta, _TINY)
        vs = jnp.zeros((m + 1, n), rdtype).at[0].set(v0)
        hess = jnp.zeros((m + 1, m), rdtype)

        def arnoldi(j, carry):
            vs, hess = carry
            w = opvec(vs[j])

            def mgs(k, wh):
                # rows past j are still zero, so their projections vanish
                w, hcol = wh
                hk = jnp.vdot(vs[k], w)
                return w - hk * vs[k], hcol.at[k].set(hk)

            w, hcol = lax.fori_loop(0, m + 1, mgs,
                                    (w, jnp.zeros(m + 1, rdtype)))
            hj1 = jnp.linalg.norm(w)
            vnext = jnp.where(hj1 > _TINY, w / jnp.maximum(hj1, _TINY), 0.0)
            hess = hess.at[:, j].set(hcol).at[j + 1, j].set(hj1)
            return vs.at[j + 1].set(vnext), hess

        vs, hess = lax.fori_loop(0, m, arnoldi, (vs, hess))
        e1 = jnp.zeros(m + 1, rdtype).at[0].set(beta)
        y, *_ = jnp.linalg.lstsq(hess, e1)
        return (vs[:m].T @ y).reshape(shape)  # u-space correction

    def relnorm(r):
        return (_colnorm(r) / bnorm).astype(rdtype)

    def sweep(x, r):
        du = cycle(r.ravel())
        return x + correct(du).astype(rdtype)

    return _refine_loop(sweep, resid, relnorm, x0, rcfg, tol)


# ---------------------------------------------------------------------------
# matrix-level drivers
# ---------------------------------------------------------------------------
def _as_refine_config(refine) -> RefineConfig:
    if isinstance(refine, RefineConfig):
        return refine
    if isinstance(refine, int):
        return RefineConfig(max_sweeps=refine)
    if refine is None:
        return RefineConfig()
    raise TypeError(f"refine must be int | RefineConfig | None: {refine!r}")


@jax.named_scope("refine")
def iterative_refine(a, b, cfg: PrecisionConfig | None = None,
                     refine: int | RefineConfig | None = None, *,
                     l=None, col_tol=None, linvs=None) -> RefineResult:
    """Factor once in ``cfg``'s ladder, refine to ``refine.tol``.

    ``a`` is required here (the residual needs it) in the residual
    precision; pass a precomputed ``l`` to skip the factorization.
    Dispatches on ``refine.method``: classic IR or GMRES-IR. The sweep
    residual ``b - A x`` goes through :func:`repro.kernels.ops.residual`
    — the fused Pallas kernel on TPU (or when ``cfg.kernel_impl``
    forces it), the XLA oracle elsewhere. ``col_tol`` gives an (n, k)
    ``b`` per-column tolerances overriding the scalar ``refine.tol``
    (the serve scheduler's per-request accuracy targets). ``linvs``
    reuses cached diagonal-tile inverses across every sweep's pair of
    triangular solves (blocked engine; see ``core.blocked.diag_tri_inv``).
    """
    cfg = cfg or PrecisionConfig()
    rcfg = _as_refine_config(refine)
    rdtype = rcfg.rdtype()
    assert a is not None, "refinement forms residuals b - A x: pass A"
    if l is None:
        l = cholesky_padded(a, cfg)   # solves consume the padded form
    if linvs is None and cfg.engine == "blocked":
        # every sweep runs two triangular passes against the same factor:
        # invert the diagonal leaves once here instead of per sweep
        from repro.core.blocked import diag_tri_inv
        from repro.core.tree import pad_factor
        l = pad_factor(l, cfg.leaf)
        linvs = diag_tri_inv(l, cfg)
    a_r = jnp.asarray(a, rdtype)
    b_r = jnp.asarray(b, rdtype)

    def matvec(x):
        return a_r @ x

    def resid(x):
        return ops.residual(a_r, x, b_r, impl=cfg.kernel_impl)

    def base_solve(r):
        return solve_factored(l, r.astype(l.dtype), cfg,
                              linvs=linvs).astype(rdtype)

    correct = scaled_solve(base_solve)
    # the initial solve is unscaled so refine=0 reproduces cholesky_solve
    x0 = base_solve(b_r)
    run = gmres_operator if rcfg.method == "gmres" else refine_operator
    return run(matvec, correct, b_r, x0, rcfg, resid=resid, tol=col_tol)


def gmres_refine(a, b, cfg: PrecisionConfig | None = None,
                 refine: int | RefineConfig | None = None, *,
                 l=None, col_tol=None, linvs=None) -> RefineResult:
    """GMRES-IR convenience wrapper (``method`` forced to ``"gmres"``)."""
    rcfg = dataclasses.replace(_as_refine_config(refine), method="gmres")
    return iterative_refine(a, b, cfg, rcfg, l=l, col_tol=col_tol,
                            linvs=linvs)
