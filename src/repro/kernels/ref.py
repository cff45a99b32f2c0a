"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the Pallas kernels are validated against
(tests/test_kernels.py sweeps shapes & dtypes with assert_allclose).
They are also the CPU execution path selected by ops.py when no TPU is
present, so the whole framework runs end-to-end on a laptop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.precision import DTYPES


def _acc_dtype(*xs):
    """f32 accumulation (MXU semantics) unless an operand is f64 — the
    f64 ladder levels exist only on CPU and must not truncate."""
    if any(jnp.dtype(x.dtype) == jnp.float64 for x in xs):
        return jnp.float64
    return jnp.float32


def qgemm_ref(a, b, *, trans_b=False, scale=1.0, c=None, beta=0.0,
              out_dtype=jnp.float32):
    """Mixed-precision GEMM oracle: out = scale * (a @ b[T]) + beta * c.

    ``a``/``b`` arrive already quantized/cast to the low compute dtype;
    the contraction accumulates in f32 (MXU semantics; f64 on the CPU
    f64 ladder), the epilogue applies the dequantization scale and the
    optional accumulator.
    """
    bt = b.T if trans_b else b
    if jnp.issubdtype(a.dtype, jnp.integer):
        # int8 ladder level: exact integer contraction, f32 epilogue
        acc = jnp.dot(a, bt, preferred_element_type=jnp.int32)
        ad = jnp.float32
    else:
        ad = _acc_dtype(a, b, *((c,) if c is not None else ()))
        acc = jnp.dot(a, bt, preferred_element_type=ad)
    out = acc.astype(ad) * jnp.asarray(scale, ad)
    if c is not None:
        out = out + jnp.asarray(beta, ad) * c.astype(ad)
    return out.astype(out_dtype)


def _compute_dtype(dt):
    """LAPACK/XLA factorizations need >= f32; narrow dtypes compute in f32
    and round back (exactly what real hardware leaf kernels do)."""
    return jnp.float32 if jnp.dtype(dt).itemsize < 4 else dt


def potrf_ref(a):
    """Lower Cholesky factor (upper triangle zeroed)."""
    cd = _compute_dtype(a.dtype)
    return jnp.linalg.cholesky(a.astype(cd)).astype(a.dtype)


def tri_inv_ref(l):
    """Inverse of a lower-triangular matrix."""
    cd = _compute_dtype(l.dtype)
    eye = jnp.eye(l.shape[-1], dtype=cd)
    out = jax.scipy.linalg.solve_triangular(l.astype(cd), eye, lower=True)
    return out.astype(l.dtype)


def trsm_ref(b, l, *, side="right", trans=True):
    """Triangular solve oracle.

    side=right, trans=True  : X = B L^{-T}   (the paper's Alg. 2 form)
    side=left,  trans=False : X = L^{-1} B
    side=left,  trans=True  : X = L^{-T} B
    """
    cd = _compute_dtype(b.dtype)
    bc, lc = b.astype(cd), l.astype(cd)
    if side == "right" and trans:
        y = jax.scipy.linalg.solve_triangular(lc, bc.T, lower=True, trans=0)
        return y.T.astype(b.dtype)
    if side == "left" and not trans:
        return jax.scipy.linalg.solve_triangular(
            lc, bc, lower=True, trans=0).astype(b.dtype)
    if side == "left" and trans:
        return jax.scipy.linalg.solve_triangular(
            lc, bc, lower=True, trans=1).astype(b.dtype)
    raise NotImplementedError(f"trsm side={side} trans={trans}")


def residual_ref(a, x, b):
    """IR residual oracle: r = b - a @ x with f32 accumulation (f64 if
    any operand is f64). ``x``/``b`` may be (n,) or (n, k) multi-RHS."""
    ad = _acc_dtype(a, x, b)
    acc = jnp.dot(a, x, preferred_element_type=ad)
    return (b.astype(ad) - acc).astype(b.dtype)


def _to_grid(t, dt, out):
    """``t`` rounded to ``dt``'s grid, returned as dtype ``out``.

    From float32 to bfloat16 the rounding is done on the bits (to
    nearest, ties to even, NaN kept), which gives what the cast does:
    XLA may drop an f32 -> bf16 -> f32 cast pair as excess precision,
    but not integer operations.
    """
    if dt == jnp.dtype(DTYPES["bf16"]) and t.dtype == jnp.float32:
        bits = jax.lax.bitcast_convert_type(t, jnp.uint32)
        bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & 1)) & jnp.uint32(
            0xFFFF0000)
        r = jax.lax.bitcast_convert_type(bits, jnp.float32)
        return jnp.where(jnp.isnan(t), t, r).astype(out)
    return t.astype(dt).astype(out)


def _round_tiles(x, name, quant, b):
    """Per-(b, b)-tile ``storage_round``, vectorized over an (R, C) block.

    Bitwise-identical per tile to ``repro.core.quantize.storage_round``
    (same reductions, same rounding) but one fused pass instead of a
    python loop over tiles — the oracle's hot rounding path, and the
    distributed engine's storage rounding (:func:`_to_grid`).
    """
    from repro.core.precision import DTYPES, NARROW, RMAX  # lazy: no cycle
    dt = jnp.dtype(DTYPES[name])
    if dt == x.dtype:
        return x
    R, C = x.shape
    t = x.reshape(R // b, b, C // b, b)
    if name == "int8":
        amax = jnp.max(jnp.abs(t), axis=(1, 3), keepdims=True)
        amax = amax.astype(jnp.float32)
        alpha = jnp.maximum(amax, jnp.float32(1e-30)) / jnp.float32(127.0)
        q = jnp.clip(jnp.round(t.astype(jnp.float32) / alpha), -127, 127)
        return (q * alpha).astype(x.dtype).reshape(R, C)
    if name in NARROW and quant:
        amax = jnp.max(jnp.abs(t), axis=(1, 3), keepdims=True)
        amax = amax.astype(jnp.float32)
        alpha = jnp.maximum(jnp.float32(1.0),
                            amax / jnp.float32(RMAX[name]))
        q = _to_grid(t / alpha.astype(t.dtype), dt, x.dtype)
        return (q * alpha.astype(x.dtype)).reshape(R, C)
    return _to_grid(t, dt, x.dtype).reshape(R, C)


def _name_runs(names, quants):
    """Contiguous (start, end, name, quant) runs of equal dtype name."""
    runs, i = [], 0
    while i < len(names):
        i2 = i
        while i2 < len(names) and names[i2] == names[i]:
            i2 += 1
        runs.append((i, i2, names[i], quants[i]))
        i = i2
    return runs


def _pair_rects(pair_names, nt):
    """Decompose the strict-lower pair-dtype map into constant-dtype
    rectangles ``(r0, r1, c0, c1, name)`` by merging equal row-runs
    across adjacent columns — the plan's bisection structure makes the
    coarse levels merge into a handful of large blocks, so the trailing
    update runs as a few big GEMMs instead of one per tile pair."""
    rects, open_ = [], {}
    for j in range(nt + 1):
        runs = set()
        if j < nt:
            i = j + 1
            while i < nt:
                nm = pair_names[i][j]
                i2 = i
                while i2 < nt and pair_names[i2][j] == nm:
                    i2 += 1
                runs.add((i, i2, nm))
                i = i2
        for key in list(open_):
            if key not in runs:
                rects.append((key[0], key[1], open_.pop(key), j, key[2]))
        for key in runs:
            open_.setdefault(key, j)
    return rects


@functools.partial(
    jax.jit,
    static_argnames=("store_names", "store_quants", "pair_names",
                     "pair_quants", "rounding"))
def panel_update_ref(linv, a21, c, *, store_names, store_quants,
                     pair_names, pair_quants, rounding=True):
    """Oracle for the fused panel update (kernels/panel.py).

    Same math as the kernel, tile for tile: per-tile storage rounding of
    the incoming panel, ``L21 = A21 @ L11^-T`` with wide accumulation,
    per-tile storage rounding of L21, then the lower-triangular trailing
    update with both operands rounded to each (i, j) pair's compute
    dtype and the updated partial sums rounded back to tile precision.
    Work is grouped for XLA: panel rows by storage-dtype run, trailing
    pairs by constant-dtype rectangle (:func:`_pair_rects`), rounding by
    fused per-tile passes (:func:`_round_tiles`).
    """
    m, b = a21.shape
    nt = m // b
    assert m % b == 0 and c.shape == (m, m), (a21.shape, c.shape)
    ad = _acc_dtype(linv, a21, c)
    linv_t = linv.T.astype(ad)

    segs = []
    for (i, i2, nm, q) in _name_runs(store_names, store_quants):
        blk = a21[i * b:i2 * b].astype(ad)
        if rounding:
            blk = _round_tiles(blk, nm, q, b)
        li = jnp.dot(blk, linv_t, preferred_element_type=ad)
        if rounding:
            li = _round_tiles(li, nm, q, b)
        segs.append(li)
    l21 = segs[0] if len(segs) == 1 else jnp.concatenate(segs, axis=0)

    quant_by = {nm: q for row_n, row_q in zip(pair_names, pair_quants)
                for nm, q in zip(row_n, row_q)}
    lq = {nm: _round_tiles(l21, nm, q, b) for nm, q in quant_by.items()}

    for (r0, r1, c0, c1, nm) in _pair_rects(pair_names, nt):
        u = jnp.dot(lq[nm][r0 * b:r1 * b], lq[nm][c0 * b:c1 * b].T,
                    preferred_element_type=ad)
        blk = c[r0 * b:r1 * b, c0 * b:c1 * b].astype(ad) - u
        if rounding:
            blk = _round_tiles(blk, nm, quant_by[nm], b)
        c = c.at[r0 * b:r1 * b, c0 * b:c1 * b].set(blk.astype(c.dtype))

    rows = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    for j in range(nt):
        nm = pair_names[j][j]
        lj = lq[nm][j * b:(j + 1) * b]
        j0 = j * b
        cur = c[j0:j0 + b, j0:j0 + b].astype(ad)
        upd = cur - jnp.dot(lj, lj.T, preferred_element_type=ad)
        if rounding:
            upd = _round_tiles(upd, nm, quant_by[nm], b)
        upd = jnp.where(rows >= cols, upd, cur)
        c = c.at[j0:j0 + b, j0:j0 + b].set(upd.astype(c.dtype))
    return l21.astype(a21.dtype), c


def syrk_ref(c, a, *, alpha=1.0, beta=1.0, scale=1.0):
    """SYRK oracle: lower(C) <- beta*C + alpha*scale*(A A^T); upper kept.

    ``scale`` carries the dequantization factor when A is quantized.
    """
    if jnp.issubdtype(a.dtype, jnp.integer):
        a = a.astype(DTYPES["bf16"])      # exact for int8 (|v| <= 127)
    ad = _acc_dtype(c, a)
    acc = jnp.dot(a, a.T, preferred_element_type=ad)
    upd = (jnp.asarray(beta, ad) * c.astype(ad)
           + jnp.asarray(alpha, ad) * jnp.asarray(scale, ad) * acc)
    n = c.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.where(row >= col, upd, c.astype(ad)).astype(c.dtype)
