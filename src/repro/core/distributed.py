"""Multi-chip distributed Cholesky via shard_map.

(Architecture notes: docs/ARCHITECTURE.md, "Distributed solver".)

1-D block-row layout: device i of the ``axis`` mesh axis owns rows
[i*w, (i+1)*w) of the global (n, n) SPD matrix, w = n/P. The factorization
is a right-looking panel sweep whose *step loop unrolls at trace time*
(P is static), so every trailing update has exact static shapes — no
masked FLOP waste.

Per panel j:
  1. broadcast (or all-gather) the (w, w) diagonal block   (comm: w^2|n*w)
  2. every device factorizes the diagonal block redundantly (tiny vs the
     panel) and TRSMs its own row block                     (compute: w^3)
  3. all-gather the solved panel                            (comm: n*w)
  4. local trailing GEMM update of its rows (qgemm, mixed precision)

The local POTRF/TRSM are the same precision-planned engines as the
single-device path (``cfg.engine`` selects them):

* ``"blocked"`` (default) — :func:`repro.core.blocked.blocked_potrf` /
  :func:`~repro.core.blocked.blocked_trsm_left`, driven by the global
  :class:`~repro.core.plan.PrecisionPlan` partitioned by block row
  (:func:`repro.core.plan.shard`). The diagonal factorization runs on a
  :meth:`~repro.core.plan.PrecisionPlan.subplan` view so every tile
  keeps its GLOBAL precision, each shard storage-rounds its block-row
  slice of the solved panel per the plan, and — for w > leaf — the
  per-panel fused panel kernel (:mod:`repro.kernels.panel`) dispatches
  locally inside the diagonal factorization.
* ``"tree"`` — the paper's recursive routines (the pre-plan schedule,
  kept as the distributed reference oracle and raced by
  ``benchmarks/bench_dist.py``).

Collectives are quantized by default (``compress_comm=True``): the
solved panel travels at the precision the sharded plan assigns the
collective — the coarsest level any trailing consumer computes at
(:meth:`~repro.core.plan.ShardedPlan.comm_name`). Early panels move in
the ladder's low precision (halving the dominant n*w term, per-shard
scales riding along as (P,) f32); panels near the diagonal, whose
consumers all compute at fine levels, are gathered losslessly. The tree
engine predates the plan and always compresses at level 0. Collective
cost 2*n*w per step is the open perf item (docs/ARCHITECTURE.md,
"Performance notes" C3: replace gather-1 with a (w, w) ppermute
broadcast).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.blocked import blocked_potrf, blocked_trsm_left, diag_tri_inv
from repro.core.plan import build_plan, shard
from repro.core.precision import PrecisionConfig
from repro.core.quantize import quant_block, storage_round
from repro.core.tree import tree_potrf, tree_trsm, tree_trsm_left
from repro.kernels import ops


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _run_sharded(local, kw, mesh, in_specs, out_specs, *args):
    """``jax.shard_map`` of ``local(*args, **dict(kw))`` under ``jit``,
    cached on the static arguments. Called eagerly, a shard_map runs its
    body one operation at a time, compiling each; the panel loops here
    unroll into thousands of them."""
    fn = functools.partial(local, **dict(kw))
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)(*args)


def _gather_panel(li, name: str, quant: bool, axis: str, compress: bool):
    """All-gather the solved (w, w) panel block at precision ``name``.

    Returns ``(liq, s1, gathered)``: the local block quantized to the
    collective's dtype, its scale, and the (P, w, w) gather in that
    dtype. ``compress=False`` or a wide ``name`` moves raw f32 — the
    quantization then happens after the gather, exactly as before.
    """
    if not compress or name in ("f32", "f64"):
        gath = jax.lax.all_gather(li, axis)
        return None, None, gath
    liq, s1 = quant_block(li, name, quant)
    if liq.dtype == jnp.int8:
        gath = jax.lax.all_gather(liq, axis)         # int8 wire format
    else:
        # bitcast to u16 so XLA cannot commute the 16-bit -> f32 convert
        # ahead of the collective (it otherwise gathers at f32, doubling
        # the bytes — measured in benchmarks/bench_dist.py)
        bits = jax.lax.bitcast_convert_type(liq, jnp.uint16)
        gath = jax.lax.bitcast_convert_type(jax.lax.all_gather(bits, axis),
                                            liq.dtype)
    return liq, s1, gath


def _round_panel_rows(li, my, codes, names, quants, leaf: int):
    """Round each (leaf, leaf) tile of a shard's (w, w) panel block onto
    the storage grid its plan slice assigns it.

    ``codes`` is the ShardedPlan's (T, tps) int32 store-code table
    (shared by all shards — SPMD traces once); ``my`` is the traced
    shard id, so shard s reads rows ``s*tps + r``. Mirrors the panel
    kernel's static-variants + traced-select idiom at the jnp level.
    """
    tps = codes.shape[1]
    out = li
    for r in range(tps):
        for c in range(tps):
            tile = li[r * leaf:(r + 1) * leaf, c * leaf:(c + 1) * leaf]
            code = codes[my * tps + r, c]
            t = storage_round(tile, names[0], quants[0])
            for k in range(1, len(names)):
                t = jnp.where(code == k,
                              storage_round(tile, names[k], quants[k]), t)
            out = out.at[r * leaf:(r + 1) * leaf,
                         c * leaf:(c + 1) * leaf].set(t)
    return out


def _local_potrf_blocked(a_local, *, axis: str, nshards: int,
                         cfg: PrecisionConfig, broadcast_diag_only: bool,
                         compress_comm: bool):
    """Plan-driven local engine: blocked POTRF/TRSM + planned collectives."""
    w, n = a_local.shape
    my = jax.lax.axis_index(axis)
    sp = shard(build_plan(n, cfg), nshards)
    for j in range(nshards):
        colpanel = a_local[:, j * w:(j + 1) * w]                 # (w, w)
        if broadcast_diag_only:
            # Optimized collective schedule (perf note C1): only the
            # owner's (w, w) diagonal block moves (psum of a masked
            # block), saving the first n*w all-gather.
            mine = jnp.where(my == j, colpanel, jnp.zeros_like(colpanel))
            diag = jax.lax.psum(mine, axis)
        else:
            diag = jax.lax.all_gather(colpanel, axis)[j]
        # redundant diagonal factorization at the GLOBAL plan's tile
        # precisions; w > leaf dispatches the fused panel kernel inside
        ld = blocked_potrf(diag, cfg, plan=sp.diag_plan(j))
        linvs = diag_tri_inv(ld, cfg)
        # own row block: li = colpanel @ ld^{-T}  via  (ld^{-1} colpanel^T)^T
        li = blocked_trsm_left(colpanel.T, ld, cfg, trans=False,
                               linvs=linvs).T
        if cfg.storage_rounding:
            # each shard rounds ITS block-row slice of the solved panel
            # onto the plan's storage grids (the single-device engine's
            # TRSM-leaf rounding, partitioned by block row)
            codes = jnp.asarray(sp.store_codes(j))
            li = _round_panel_rows(li, my, codes, sp.names, sp.quants,
                                   cfg.leaf)
        li = jnp.where(my == j, ld, li)     # owner keeps the exact factor
        if j < nshards - 1:
            # collective + trailing update at the sharded plan's comm
            # precision: the coarsest level any trailing consumer runs at
            name, q = sp.comm_name(j), sp.comm_quant(j)
            trail0 = (j + 1) * w
            liq, s1, gath = _gather_panel(li, name, q, axis, compress_comm)
            if liq is None:                  # wide (or uncompressed) wire
                lt = gath[j + 1:].reshape(-1, w)
                liq, s1 = quant_block(li, name, q)
                ltq, s2 = quant_block(lt, name, q)
                a_local = a_local.at[:, trail0:].set(
                    ops.qgemm(liq, ltq, scale=-(s1 * s2),
                              c=a_local[:, trail0:], beta=1.0,
                              trans_b=True, out_dtype=a_local.dtype,
                              impl=cfg.kernel_impl))
            else:                            # quantized collective
                lt = gath[j + 1:].reshape(-1, w)
                upd = ops.qgemm(liq, lt, scale=s1, trans_b=True,
                                out_dtype=jnp.float32,
                                impl=cfg.kernel_impl)            # (w, m)
                if q:
                    # per-shard scales travel as (P,) f32 and rescale the
                    # GEMM output column blocks
                    scales = jax.lax.all_gather(s1, axis)        # (P,)
                    upd = upd * jnp.repeat(scales[j + 1:], w)[None, :]
                a_local = a_local.at[:, trail0:].add(
                    -upd.astype(a_local.dtype))
        a_local = a_local.at[:, j * w:(j + 1) * w].set(li)
    # zero the (junk-filled) upper triangle of my rows
    gr = jnp.arange(w)[:, None] + my * w
    keep = jnp.arange(n)[None, :] <= gr
    return jnp.where(keep, a_local, 0.0)


def _local_potrf_tree(a_local, *, axis: str, nshards: int,
                      cfg: PrecisionConfig, broadcast_diag_only: bool,
                      compress_comm: bool):
    """Legacy local engine: the paper's recursive routines, level-0 comm.

    Kept as the distributed reference oracle (``cfg.engine == "tree"``)
    and the baseline ``benchmarks/bench_dist.py`` races the planned
    blocked engine against.
    """
    w, n = a_local.shape
    my = jax.lax.axis_index(axis)
    for j in range(nshards):
        colpanel = a_local[:, j * w:(j + 1) * w]                 # (w, w)
        if broadcast_diag_only:
            mine = jnp.where(my == j, colpanel, jnp.zeros_like(colpanel))
            diag = jax.lax.psum(mine, axis)
        else:
            allpan = jax.lax.all_gather(colpanel, axis)          # (P, w, w)
            diag = allpan[j]
        ld = tree_potrf(diag, cfg)                               # redundant
        li = tree_trsm(colpanel, ld, cfg)
        li = jnp.where(my == j, ld, li)
        name = cfg.name_at(0)
        q = cfg.needs_quant(0)
        if compress_comm and j < nshards - 1:
            # the tree predates the plan: the trailing update always
            # consumes the gathered panel at level-0 precision, so the
            # collective always quantizes to level 0
            liq, s1, gath = _gather_panel(li, name, q, axis, True)
            lt = gath[j + 1:].reshape(-1, w)
            upd = ops.qgemm(liq, lt, scale=s1, trans_b=True,
                            out_dtype=jnp.float32,
                            impl=cfg.kernel_impl)                # (w, m)
            if q:
                scales = jax.lax.all_gather(s1, axis)            # (P,)
                upd = upd * jnp.repeat(scales[j + 1:], w)[None, :]
            a_local = a_local.at[:, (j + 1) * w:].add(
                -upd.astype(a_local.dtype))
        elif j < nshards - 1:
            solved = jax.lax.all_gather(li, axis)                # (P, w, w)
            lt = solved[j + 1:].reshape(-1, w)                   # f32 rows
            liq, s1 = quant_block(li, name, q)
            ltq, s2 = quant_block(lt, name, q)
            a_local = a_local.at[:, (j + 1) * w:].set(
                ops.qgemm(liq, ltq, scale=-(s1 * s2),
                          c=a_local[:, (j + 1) * w:], beta=1.0,
                          trans_b=True, out_dtype=a_local.dtype,
                          impl=cfg.kernel_impl))
        a_local = a_local.at[:, j * w:(j + 1) * w].set(li)
    # zero the (junk-filled) upper triangle of my rows
    gr = jnp.arange(w)[:, None] + my * w
    keep = jnp.arange(n)[None, :] <= gr
    return jnp.where(keep, a_local, 0.0)


def _autoresolve(cfg: PrecisionConfig, n: int, nshards: int):
    """Resolve ``engine="auto"`` via the tuning DB (no-op otherwise)."""
    if cfg.engine != "auto":
        return cfg
    from repro import tune  # local: tune is a consumer of this module
    return tune.resolve_cfg(cfg, n, nshards)


def dist_cholesky(a, mesh, cfg: PrecisionConfig | None = None,
                  axis: str = "model", *, broadcast_diag_only: bool = True,
                  compress_comm: bool | None = None):
    """Distributed lower Cholesky of a block-row-sharded SPD matrix.

    ``a``: global (n, n), n divisible by ``mesh.shape[axis] * cfg.leaf``.
    Returns L with the same sharding. ``cfg.engine`` selects the local
    engine (``"blocked"`` — plan-driven, the default — ``"tree"``, the
    recursive oracle, or ``"auto"`` to consult the tuning database for
    the measured winner at this ``(n, nshards)``; docs/TUNING.md).
    ``compress_comm`` gathers the solved panel in the precision the
    sharded plan assigns the collective; ``False`` forces full-precision
    gathers (the baseline ``benchmarks/bench_dist.py`` races) and the
    default ``None`` takes the tuning database's measured choice
    (falling back to compressed).
    """
    cfg = cfg or PrecisionConfig()
    nshards = mesh.shape[axis]
    n = a.shape[-1]
    assert n % nshards == 0 and (n // nshards) % cfg.leaf == 0, (
        f"n={n} must be divisible by shards*leaf={nshards}*{cfg.leaf}")
    cfg = _autoresolve(cfg, n, nshards)
    if compress_comm is None:
        from repro import tune
        compress_comm = tune.decide(
            n, tune.ladder_key(cfg), nshards).compress_comm
    local = (_local_potrf_tree if cfg.engine == "tree"
             else _local_potrf_blocked)
    kw = (("axis", axis), ("nshards", nshards), ("cfg", cfg),
          ("broadcast_diag_only", broadcast_diag_only),
          ("compress_comm", compress_comm))
    spec = P(axis, None)
    return _run_sharded(local, kw, mesh, (spec,), spec, a)


def _local_solve(l_local, b_local, *, axis: str, nshards: int,
                 cfg: PrecisionConfig):
    """Forward then back substitution on block-row-sharded L and B.

    The per-shard diagonal solves run through the engine ``cfg.engine``
    selects — :func:`~repro.core.blocked.blocked_trsm_left` (flat GEMM
    substitution against cached leaf inverses) by default, the recursive
    :func:`~repro.core.tree.tree_trsm_left` for the tree oracle. Both
    run in the ladder's high precision: the solve is O(n^2) next to the
    O(n^3) factorization, so narrowing it would buy nothing.
    """
    w = l_local.shape[0]
    my = jax.lax.axis_index(axis)
    nrhs = b_local.shape[1]

    def trsm_left(bm, lm, trans):
        if cfg.engine == "tree":
            return tree_trsm_left(bm, lm, cfg, trans=trans)
        return blocked_trsm_left(bm, lm, cfg, trans=trans)

    # forward: y_j = L_jj^{-1} (b_j - sum_{k<j} L_jk y_k)
    y = jnp.zeros_like(b_local)
    for j in range(nshards):
        acc = b_local
        if j > 0:
            yg = jax.lax.all_gather(y, axis)                     # (P, w, r)
            past = yg[:j].reshape(-1, nrhs)                      # (j*w, r)
            lpast = l_local[:, :j * w]
            acc = b_local - ops.qgemm(
                lpast.astype(cfg.high_dtype), past.astype(cfg.high_dtype),
                out_dtype=b_local.dtype, impl=cfg.kernel_impl)
        diag_mine = jnp.where(
            my == j, l_local[:, j * w:(j + 1) * w],
            jnp.zeros((w, w), l_local.dtype))
        diag = jax.lax.psum(diag_mine, axis)
        yj = trsm_left(acc, diag, False)
        y = jnp.where(my == j, yj, y)
    # backward: x_j = L_jj^{-T} (y_j - sum_{k>j} L_kj^T x_k)
    x = jnp.zeros_like(y)
    for j in reversed(range(nshards)):
        acc = y
        if j < nshards - 1:
            xg = jax.lax.all_gather(x, axis)                     # (P, w, r)
            future = xg[j + 1:].reshape(-1, nrhs)                # ((P-j-1)w, r)
            # need L[rows>j*w.., cols j]^T  = (column panel j below diag)^T;
            # column panel j rows are spread across devices k > j: gather
            # each device's (w, w) block of column panel j.
            myblk = l_local[:, j * w:(j + 1) * w]                # (w, w)
            blks = jax.lax.all_gather(myblk, axis)               # (P, w, w)
            below = blks[j + 1:].reshape(-1, w)                  # ((P-j-1)w, w)
            acc = y - ops.qgemm(
                below.T.astype(cfg.high_dtype), future.astype(cfg.high_dtype),
                out_dtype=y.dtype, impl=cfg.kernel_impl)
        diag_mine = jnp.where(
            my == j, l_local[:, j * w:(j + 1) * w],
            jnp.zeros((w, w), l_local.dtype))
        diag = jax.lax.psum(diag_mine, axis)
        xj = trsm_left(acc, diag, True)
        x = jnp.where(my == j, xj, x)
    return x


def dist_residual(a, x, b, mesh, cfg: PrecisionConfig | None = None,
                  axis: str = "model"):
    """Refinement residual ``b - A x`` with A and b block-row-sharded
    over ``axis``: each device forms the residual of its own rows
    against the whole ``x`` (gathered). The fused residual kernel runs
    per shard because the chip's compiler cannot partition a Pallas
    kernel on its own."""
    cfg = cfg or PrecisionConfig()
    spec = P(axis, None)
    return _run_sharded(ops.residual, (("impl", cfg.kernel_impl),), mesh,
                        (spec, P(), spec), spec, a, x, b)


def dist_cholesky_solve(a, b, mesh, cfg: PrecisionConfig | None = None,
                        axis: str = "model", *, l=None):
    """Solve A x = b with A (and b) block-row-sharded over ``axis``."""
    cfg = cfg or PrecisionConfig()
    nshards = mesh.shape[axis]
    cfg = _autoresolve(cfg, a.shape[-1] if a is not None else b.shape[0],
                       nshards)
    if l is None:
        l = dist_cholesky(a, mesh, cfg, axis)
    vec = b.ndim == 1
    if vec:
        b = b[:, None]
    kw = (("axis", axis), ("nshards", nshards), ("cfg", cfg))
    spec = P(axis, None)
    x = _run_sharded(_local_solve, kw, mesh, (spec, spec), spec, l, b)
    return x[:, 0] if vec else x
