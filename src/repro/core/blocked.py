"""Flat blocked mixed-precision Cholesky executor (copy-free tree).

The tree recursion (:mod:`repro.core.tree`) pays for its precision
assignment with ``jnp.concatenate`` reassembly of the full matrix at
every node — O(depth) whole-matrix copies and a dispatch DAG XLA cannot
fuse across. This module executes the *same* precision assignment as a
flat right-looking schedule over leaf panels of a single buffer:

    for each leaf panel p:
        L[p,p]   <- potrf leaf at the plan's diagonal level
        L[:, p]  <- fused panel update (kernels/panel.py): the TRSM
                    ``L21 = A21 @ L11^-T`` and the trailing SYRK
                    ``A22 -= L21 @ L21^T`` in one gridded kernel, with
                    every tile rounded/quantized once per use at the
                    precision :mod:`repro.core.plan` assigns it

No recursion and no per-node reassembly: the trailing matrix is carried
as a shrinking working set, every finished block column is emitted
exactly once, and the output is assembled in a single O(n^2) pass —
versus the tree's O(depth) whole-matrix concatenate chains.

Numerics vs the tree (the reference oracle): identical precision
assignment per tile — compute level = the potrf-split separation level,
storage level = the TRSM-leaf level, quantization per
``cfg.needs_quant``, and the trailing matrix stored at its tiles'
precision between updates (paper Fig. 3) — but the flat schedule rounds
trailing partial sums once per panel where the tree rounds once per
recursion node, so the blocked factor equals the tree factor up to the
ladder's own unit roundoff (and bit-identically for single-tile
problems, where both engines reduce to the same leaf call). The
equivalence suite (tests/test_blocked.py) pins this per PAPER_CONFIGS
entry. Triangular solves are O(n^2) against the O(n^3) factorization
and run in the ladder's high precision over the stored (rounded) factor.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.plan import build_plan
from repro.core.precision import PrecisionConfig
from repro.core.quantize import storage_round
from repro.core.tree import _sym_from_lower
from repro.kernels import ops


def _round(x, name: str, cfg: PrecisionConfig):
    """Storage rounding at ``name`` (no-op when the config disables it)."""
    if not cfg.storage_rounding:
        return x
    return storage_round(x, name, cfg.quantize)


def blocked_potrf(a, cfg: PrecisionConfig, *, plan=None):
    """Lower Cholesky factor of SPD ``a`` via the flat tile schedule.

    Reads the lower triangle only; returns L with zeroed upper triangle.
    ``a.shape[-1]`` must be a multiple of ``cfg.leaf`` (use
    :func:`repro.core.tree.pad_spd` otherwise — :func:`repro.core.solve.
    cholesky` does). Numerically equivalent to :func:`tree_potrf`; see
    the module docstring for the exact contract.

    ``plan`` overrides the per-tile precision table (default: the plan
    of ``a``'s own geometry). The distributed solver passes a
    :meth:`~repro.core.plan.PrecisionPlan.subplan` view here so its
    redundant diagonal-block factorizations compute every tile at the
    precision the GLOBAL plan assigns it.
    """
    a = jnp.asarray(a)
    n = a.shape[-1]
    assert a.shape == (n, n), a.shape
    assert n % cfg.leaf == 0, (n, cfg.leaf)
    if plan is None:
        plan = build_plan(n, cfg)
    assert plan.ntiles == n // cfg.leaf, (plan.ntiles, n, cfg.leaf)
    b, T, high = cfg.leaf, plan.ntiles, cfg.high_dtype
    # The trailing matrix is carried as a shrinking working set and each
    # finished block column is written exactly once, in place, into one
    # output buffer — O(n^2) assembly total, where the tree
    # re-concatenates the full matrix at every recursion node. (Built by
    # concatenation instead, the factor made XLA keep dozens of partial
    # copies live once solves consumed it in the same program: 33 GiB
    # of HBM at n = 16384 on a TPU v5e.) On the Pallas path the fused
    # kernel also keeps the trailing update tile-resident in VMEM.
    trail = a
    out = jnp.zeros((n, n), a.dtype)
    # Named scopes split each step into the leaf factor and inverse
    # (``diag``), the fused update with its slices (``panel``) and the
    # writes into the output (``write``), as a profile reads them.
    for p in range(T):
        r0 = p * b
        name_p = plan.name(p, p)
        with jax.named_scope("diag"):
            diag = _round(_sym_from_lower(trail[:b, :b]), name_p, cfg)
            lpp = ops.potrf(diag.astype(high), impl=cfg.kernel_impl)
            lpp = _round(lpp.astype(a.dtype), name_p, cfg)
        with jax.named_scope("write"):
            out = out.at[r0:r0 + b, r0:r0 + b].set(lpp)
        if p < T - 1:
            with jax.named_scope("diag"):
                linv = ops.tri_inv(lpp.astype(high), impl=cfg.kernel_impl)
            meta = plan.panel_meta(p)
            with jax.named_scope("panel"):
                l21, trail = ops.panel_update(
                    linv.astype(a.dtype), trail[b:, :b], trail[b:, b:],
                    store_names=meta.store_names,
                    store_quants=meta.store_quants,
                    pair_names=meta.pair_names,
                    pair_quants=meta.pair_quants,
                    rounding=cfg.storage_rounding, impl=cfg.kernel_impl)
            with jax.named_scope("write"):
                out = out.at[r0 + b:, r0:r0 + b].set(l21)
    return out


def diag_tri_inv(l, cfg: PrecisionConfig):
    """Stacked inverses of the factor's diagonal leaf tiles, shape
    ``(T, leaf, leaf)``. Computed once per factor and reused by both
    triangular solves of every subsequent :func:`blocked_trsm_left`
    call — the serve engine caches this next to the factor, K-FAC-style
    repeated solves never re-invert a diagonal tile."""
    n = l.shape[-1]
    b = cfg.leaf
    assert n % b == 0, (n, b)
    high = cfg.high_dtype
    return jnp.stack([
        ops.tri_inv(l[i * b:(i + 1) * b, i * b:(i + 1) * b].astype(high),
                    impl=cfg.kernel_impl)
        for i in range(n // b)])


@jax.named_scope("solve")
def blocked_trsm_left(bmat, l, cfg: PrecisionConfig, *, trans: bool,
                      linvs=None):
    """Flat left triangular solve against a blocked factor.

    trans=False : X = L^{-1} B   (forward substitution, one GEMM/panel)
    trans=True  : X = L^{-T} B   (back substitution, reversed order)

    ``bmat``: (n, k); ``l``: (n, n) lower-triangular with n a multiple of
    ``cfg.leaf``. ``linvs`` takes the precomputed :func:`diag_tri_inv`
    stack (the factor-cache hot path). The solve runs in the ladder's
    high precision — it is O(n^2) next to the O(n^3) factorization, so
    narrowing it would buy nothing and cost digits.
    """
    bmat = jnp.asarray(bmat)
    n, _ = bmat.shape
    assert l.shape == (n, n), (bmat.shape, l.shape)
    b = cfg.leaf
    assert n % b == 0, (n, b)
    T = n // b
    if linvs is None:
        linvs = diag_tri_inv(l, cfg)
    high = cfg.high_dtype
    x = bmat.astype(high)
    impl = cfg.kernel_impl
    if not trans:
        for p in range(T):
            r0, r1 = p * b, (p + 1) * b
            xp = ops.qgemm(linvs[p], x[r0:r1], out_dtype=high, impl=impl)
            x = x.at[r0:r1].set(xp)
            if r1 < n:
                x = x.at[r1:].set(ops.qgemm(
                    l[r1:, r0:r1].astype(high), xp, scale=-1.0,
                    c=x[r1:], beta=1.0, out_dtype=high, impl=impl))
    else:
        for p in reversed(range(T)):
            r0, r1 = p * b, (p + 1) * b
            xp = ops.qgemm(linvs[p].T, x[r0:r1], out_dtype=high, impl=impl)
            x = x.at[r0:r1].set(xp)
            if r0 > 0:
                x = x.at[:r0].set(ops.qgemm(
                    l[r0:r1, :r0].T.astype(high), xp, scale=-1.0,
                    c=x[:r0], beta=1.0, out_dtype=high, impl=impl))
    return x.astype(bmat.dtype)
