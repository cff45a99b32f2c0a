"""Multi-chip distributed Cholesky and refined solve via shard_map.

(Architecture notes: docs/ARCHITECTURE.md, "Distributed solver".)

1-D block-row layout: device i of the ``axis`` mesh axis owns rows
[i*w, (i+1)*w) of the global (n, n) SPD matrix, w = n/P. The factorization
is a right-looking sweep over panels of b columns, b a multiple of the
leaf that divides w (:func:`repro.core.plan.panel_width`: the largest
such b up to 2048, where the trailing GEMM passes the v5e's ridge; the
reason is in its docstring). The panel loop unrolls at trace time, so
every step has static shapes.

Per panel j (columns [j*b, (j+1)*b), owned by shard o = j*b // w):
  1. the owner's (b, b) diagonal block travels (psum of a masked
     block)                                                 (comm: b^2)
  2. every device factorizes it redundantly at the global plan's tile
     precisions                                             (b^3/3)
  3. every device TRSMs its own rows of the panel column     (w b^2)
  4. the solved panel is all-gathered at the precision
     ``ShardedPlan.comm_name(j)`` gives it                   (comm: n*b)
  5. every device updates its trailing rows with ``qgemm``, one b-wide
     column block at a time (``(w, b)`` temporaries, never ``(w, n)``);
     a block that lies wholly above a device's rows is skipped
     (``lax.cond``).

What is still wasted: each device TRSMs and updates all w of its rows,
including rows already finished (their products are zeroed or land in
the upper triangle, zeroed at the end), and the upper triangle of its
own diagonal column block; the diagonal factorization is redundant on
every device. At n = 65536, P = 4 the busiest device (the last) does
about w n^2 = 7.0e13 update flops against 5.4e13 useful, and the
first does one block's worth: the 1-D layout's load imbalance
(ROADMAP section 1 item 6).

Memory per device: A's row block (n^2 / P f32) and L's (the factor is
built in one copy of it), the gathered panel (n x b), and a few (w, b)
blocks (the panel column, its solve, one column block's GEMM operand
and result): at n = 65536, P = 4, 4 + 4 GiB and about 1 GiB.

The local POTRF/TRSM are the same precision-planned engines as the
single-device path (``cfg.engine`` selects them):

* ``"blocked"`` (default) — :func:`repro.core.blocked.blocked_potrf` /
  :func:`~repro.core.blocked.blocked_trsm_left`, driven by the global
  :class:`~repro.core.plan.PrecisionPlan` partitioned by block row
  (:func:`repro.core.plan.shard`). The diagonal factorization runs on a
  :meth:`~repro.core.plan.PrecisionPlan.subplan` view so every tile
  keeps its GLOBAL precision, each shard storage-rounds its block-row
  slice of the solved panel per the plan, and — for b > leaf — the
  per-panel fused panel kernel (:mod:`repro.kernels.panel`) dispatches
  locally inside the diagonal factorization.
* ``"tree"`` — the paper's recursive routines (the pre-plan schedule,
  kept as the distributed reference oracle and raced by
  ``benchmarks/bench_dist.py``). It keeps panels of width w = n/P.

Collectives are quantized by default (``compress_comm=True``): the
solved panel travels at the precision the sharded plan assigns the
collective — the coarsest level any trailing consumer computes at
(:meth:`~repro.core.plan.ShardedPlan.comm_name`). Early panels move in
the ladder's low precision (halving the dominant n*b term, per-shard
scales riding along as (P,) f32); panels near the diagonal, whose
consumers all compute at fine levels, are gathered losslessly. The tree
engine predates the plan and always compresses at level 0.

The triangular solves (:func:`_local_solve`) sweep the same b-wide
panels: forward substitution broadcasts each (b, k) block of y from the
owner (psum) and updates every device's rows; back substitution psums
each device's (b, k) partial product. Nothing of the factor moves.
:func:`dist_refine_solve` refines on top of them, every sweep's residual
formed per shard.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.profiler import annotate_function
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.blocked import blocked_potrf, blocked_trsm_left, diag_tri_inv
from repro.core.plan import build_plan, panel_width, shard
from repro.core.precision import PrecisionConfig
from repro.core.quantize import quant_block
from repro.core.tree import tree_potrf, tree_trsm, tree_trsm_left
from repro.kernels import ops
from repro.kernels.ref import _round_tiles

_HIGHEST = jax.lax.Precision.HIGHEST


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
    """All-gather the solved (w, b) panel slices at precision ``name``.

    Returns ``(liq, s1, gathered)``: the local slice quantized to the
    collective's dtype, its scale, and the (P, w, b) gather in that
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
    """Round each (leaf, leaf) tile of a shard's (w, b) panel slice onto
    the storage grid its plan slice assigns it.

    ``codes`` is the ShardedPlan's (T, b/leaf) int32 store-code table
    (shared by all shards — SPMD traces once); ``my`` is the traced
    shard id, so shard s reads rows ``s*tps .. (s+1)*tps``. Every name's
    rounding runs over the whole slice, tile by tile in one pass
    (:func:`repro.kernels.ref._round_tiles`), and each tile keeps the
    one its code selects.
    """
    w, b = li.shape
    tps = w // leaf
    mine = jax.lax.dynamic_slice_in_dim(jnp.asarray(codes), my * tps, tps)
    pick = jnp.repeat(jnp.repeat(mine, leaf, axis=0), leaf, axis=1)
    out = _round_tiles(li, names[0], quants[0], leaf)
    for k in range(1, len(names)):
        out = jnp.where(pick == k, _round_tiles(li, names[k], quants[k],
                                                leaf), out)
    return out


def _local_potrf_blocked(a_local, *, axis: str, nshards: int,
                         cfg: PrecisionConfig, broadcast_diag_only: bool,
                         compress_comm: bool):
    """Plan-driven local engine: b-wide panels, blocked POTRF/TRSM and
    planned collectives (the module docstring's steps 1-5)."""
    w, n = a_local.shape
    my = jax.lax.axis_index(axis)
    sp = shard(build_plan(n, cfg), nshards)
    b = sp.panel_width
    rows = my * w + jnp.arange(w)[:, None]     # global index of each row
    for j in range(sp.npanels):
        c0, c1 = j * b, (j + 1) * b
        o = sp.owner(j)
        off = c0 - o * w                      # panel's rows on the owner
        colpanel = a_local[:, c0:c1]                            # (w, b)
        with jax.named_scope("diag"):
            if broadcast_diag_only:
                # only the owner's (b, b) diagonal block moves (psum of a
                # masked block)
                mine = jnp.where(my == o, colpanel[off:off + b],
                                 jnp.zeros((b, b), colpanel.dtype))
                diag = jax.lax.psum(mine, axis)
            else:
                diag = jax.lax.all_gather(colpanel, axis,
                                          tiled=True)[c0:c1]
            # redundant diagonal factorization at the GLOBAL plan's tile
            # precisions; b > leaf dispatches the fused panel kernel
            ld = blocked_potrf(diag, cfg, plan=sp.diag_plan(j))
            linvs = diag_tri_inv(ld, cfg)
        with jax.named_scope("trsm"):
            # own rows: li = colpanel @ ld^{-T} via (ld^{-1} colpanel^T)^T;
            # rows above the panel are finished and hold zeros
            li = blocked_trsm_left(colpanel.T, ld, cfg, trans=False,
                                   linvs=linvs).T
            li = jnp.where(rows >= c1, li, 0.0)
            if cfg.storage_rounding:
                # each shard rounds ITS block-row slice of the solved
                # panel onto the plan's storage grids (the single-device
                # engine's TRSM-leaf rounding, partitioned by block row)
                li = _round_panel_rows(li, my, sp.store_codes(j), sp.names,
                                       sp.quants, cfg.leaf)
            # the owner keeps the exact diagonal factor
            li = li.at[off:off + b].set(
                jnp.where(my == o, ld, li[off:off + b]))
        if j < sp.npanels - 1:
            # collective + trailing update at the sharded plan's comm
            # precision: the coarsest level any trailing consumer runs at
            name, q = sp.comm_name(j), sp.comm_quant(j)
            with jax.named_scope("gather"):
                liq, s1, gath = _gather_panel(li, name, q, axis,
                                              compress_comm)
                raw = liq is None            # wide (or uncompressed) wire
                if raw:
                    liq, s1 = quant_block(li, name, q)
                # per-shard scales travel as (P,) f32; each column block
                # below is one shard's rows of the gathered panel
                scales = (jax.lax.all_gather(s1, axis) if q and not raw
                          else None)
            with jax.named_scope("update"):
                # one b-wide column block at a time: (w, b) temporaries
                for lo in range(c1, n, b):
                    k = lo // w              # the shard whose rows these are
                    lt = gath[k, lo - k * w:lo - k * w + b]      # (b, b)
                    if raw:
                        ltq, s2 = quant_block(lt, name, q)
                    else:
                        ltq, s2 = lt, (scales[k] if q else 1.0)

                    def update(cur, ltq=ltq, s2=s2):
                        return ops.qgemm(liq, ltq, scale=-(s1 * s2), c=cur,
                                         beta=1.0, trans_b=True,
                                         out_dtype=a_local.dtype,
                                         impl=cfg.kernel_impl)

                    # the block lies wholly above the rows of shards < k
                    cur = jax.lax.cond(my >= k, update, lambda cur: cur,
                                       a_local[:, lo:lo + b])
                    a_local = a_local.at[:, lo:lo + b].set(cur)
        a_local = a_local.at[:, c0:c1].set(li)
    # zero the (junk-filled) upper triangle of my rows
    keep = jnp.arange(n)[None, :] <= rows
    return jnp.where(keep, a_local, 0.0)


def _local_potrf_tree(a_local, *, axis: str, nshards: int,
                      cfg: PrecisionConfig, broadcast_diag_only: bool,
                      compress_comm: bool):
    """Legacy local engine: the paper's recursive routines, level-0 comm.

    Kept as the distributed reference oracle (``cfg.engine == "tree"``)
    and the baseline ``benchmarks/bench_dist.py`` races the planned
    blocked engine against. Its panels are w = n/P wide, as before the
    blocked engine took b-wide ones.
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


@functools.partial(annotate_function, name="repro.dist.factor")
def dist_cholesky(a, mesh, cfg: PrecisionConfig | None = None,
                  axis: str = "model", *, broadcast_diag_only: bool = True,
                  compress_comm: bool | None = None):
    """Distributed lower Cholesky of a block-row-sharded SPD matrix.

    ``a``: global (n, n), n divisible by ``mesh.shape[axis] * cfg.leaf``.
    Returns L with the same sharding. ``cfg.engine`` selects the local
    engine (``"blocked"`` — plan-driven, the default — ``"tree"``, the
    recursive oracle, or ``"auto"`` to consult the tuning database for
    the measured winner at this ``(n, nshards)``; docs/TUNING.md).
    The blocked engine sweeps panels of
    :func:`~repro.core.plan.panel_width` columns.
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
    kw = (("axis", axis), ("nshards", nshards), ("cfg", cfg),
          ("broadcast_diag_only", broadcast_diag_only),
          ("compress_comm", compress_comm))
    local = _local_potrf_tree if cfg.engine == "tree" else _local_potrf_blocked
    spec = P(axis, None)
    with jax.named_scope("factor"), jax.named_scope("dist"):
        return _run_sharded(local, kw, mesh, (spec,), spec, a)


def _local_solve(l_local, b_local, *, axis: str, nshards: int,
                 cfg: PrecisionConfig):
    """Forward then back substitution on block-row-sharded L and B, in
    b-wide panels.

    Forward: the owner of panel j solves its (b, k) block of y against
    the diagonal block it holds, a psum hands that block to every
    device, and each device subtracts ``L[my rows, panel j] y_j`` from
    its rows. Back: each device forms its partial ``L[my rows, panel
    j]^T x[my rows]`` over the rows solved so far (zero elsewhere), a
    psum sums the (b, k) partials, and the owner solves its block. Only
    (b, k) blocks move; no block of L does.

    The per-panel diagonal solves run through the engine ``cfg.engine``
    selects — :func:`~repro.core.blocked.blocked_trsm_left` (flat GEMM
    substitution against leaf inverses) by default, the recursive
    :func:`~repro.core.tree.tree_trsm_left` for the tree oracle. Both
    run in the ladder's high precision: the solve is O(n^2) next to the
    O(n^3) factorization, so narrowing it would buy nothing. The
    per-panel products with L are XLA dots at HIGHEST, which read their
    (w, b) column panel of L in place: a Pallas ``qgemm`` takes each
    slice as a copy, and XLA kept the copies of every panel alive at
    once (4.4e9 bytes a device at n = 65536).
    """
    w = l_local.shape[0]
    n = nshards * w
    b = panel_width(n, nshards, cfg.leaf)
    my = jax.lax.axis_index(axis)
    high = cfg.high_dtype

    def trsm_left(bm, lm, trans):
        if cfg.engine == "tree":
            return tree_trsm_left(bm, lm, cfg, trans=trans)
        return blocked_trsm_left(bm, lm, cfg, trans=trans)

    def own(j):
        o = j * b // w
        return o, j * b - o * w

    with jax.named_scope("solve"), jax.named_scope("dist"):
        # forward: y_j = L_jj^{-1} (b_j - sum_{k<j} L_jk y_k), the sum
        # applied right-looking as each y_j lands
        y = b_local
        for j in range(n // b):
            o, off = own(j)
            lpan = l_local[:, j * b:(j + 1) * b]                 # (w, b)
            yj = jax.lax.cond(
                my == o,
                lambda r, d: trsm_left(r, d, False),
                lambda r, d: jnp.zeros_like(r),
                y[off:off + b], lpan[off:off + b])
            yj = jax.lax.psum(yj, axis)                          # (b, k)
            if j < n // b - 1:
                y = y - jnp.matmul(lpan.astype(high), yj.astype(high),
                                   precision=_HIGHEST).astype(y.dtype)
            y = y.at[off:off + b].set(jnp.where(my == o, yj,
                                                y[off:off + b]))
        # backward: x_j = L_jj^{-T} (y_j - sum_{k>j} L_kj^T x_k)
        x = jnp.zeros_like(y)
        for j in reversed(range(n // b)):
            o, off = own(j)
            lpan = l_local[:, j * b:(j + 1) * b]
            acc = y[off:off + b]
            if j < n // b - 1:
                part = jnp.matmul(lpan.T.astype(high), x.astype(high),
                                  precision=_HIGHEST)            # (b, k)
                acc = acc - jax.lax.psum(part, axis).astype(y.dtype)
            xj = jax.lax.cond(
                my == o,
                lambda r, d: trsm_left(r, d, True),
                lambda r, d: jnp.zeros_like(r),
                acc, lpan[off:off + b])
            x = x.at[off:off + b].set(jnp.where(my == o, xj,
                                                x[off:off + b]))
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
    n = a.shape[-1] if a is not None else b.shape[0]
    cfg = _autoresolve(cfg, n, nshards)
    if l is None:
        l = dist_cholesky(a, mesh, cfg, axis)
    vec = b.ndim == 1
    if vec:
        b = b[:, None]
    kw = (("axis", axis), ("nshards", nshards), ("cfg", cfg))
    spec = P(axis, None)
    x = _run_sharded(_local_solve, kw, mesh, (spec, spec), spec, l, b)
    return x[:, 0] if vec else x


@functools.partial(annotate_function, name="repro.dist.refine")
@jax.named_scope("refine")
def dist_refine_solve(a, b, mesh, cfg: PrecisionConfig | None = None,
                      axis: str = "model", *, refine=None, l=None,
                      col_tol=None):
    """Accuracy-targeted solve with A block-row-sharded over ``axis``:
    the four-chip counterpart of :func:`repro.core.solve.refine_solve`,
    with the same :class:`~repro.core.refine.RefineResult` contract.

    Factors ``a`` with :func:`dist_cholesky` unless ``l`` (the sharded
    factor) is given; the base solve and every sweep's correction go
    through :func:`dist_cholesky_solve`, and residuals form in the
    residual precision through :func:`dist_residual`, each device's rows
    by the fused-residual dispatch like the local path. ``refine`` is
    an int sweep bound or a :class:`~repro.core.refine.RefineConfig`;
    ``col_tol`` sets per-column tolerances. Jit-compatible: under one
    ``jax.jit`` with :func:`dist_cholesky` it is one program.
    """
    from repro.core.refine import (_as_refine_config, gmres_operator,
                                   refine_operator, scaled_solve)
    cfg = cfg or PrecisionConfig()
    rcfg = _as_refine_config(refine)
    rdtype = rcfg.rdtype()
    cfg = _autoresolve(cfg, a.shape[-1], mesh.shape[axis])
    if l is None:
        l = dist_cholesky(a, mesh, cfg, axis)
    # keep A block-row-sharded for the sweep GEMMs too: the per-sweep
    # matvec/residual is the dominant O(n^2 k) term, and a replicated
    # A would run it on one device
    a_r = jax.device_put(jnp.asarray(a, rdtype), NamedSharding(
        mesh, P(axis, None)))
    b_r = jnp.asarray(b, rdtype)

    def base_solve(r):
        x = dist_cholesky_solve(a, r.astype(l.dtype), mesh, cfg,
                                axis=axis, l=l)
        return x.astype(rdtype)

    def matvec(x):
        return a_r @ x

    def resid(x):
        return dist_residual(a_r, x, b_r, mesh, cfg, axis=axis)

    correct = scaled_solve(base_solve)
    x0 = base_solve(b_r)    # unscaled, like iterative_refine
    run = gmres_operator if rcfg.method == "gmres" else refine_operator
    return run(matvec, correct, b_r, x0, rcfg, resid=resid, tol=col_tol)
