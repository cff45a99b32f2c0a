"""Fused panel-update Pallas kernel for the flat blocked Cholesky.

For each factored leaf panel the blocked executor (core/blocked.py) must
apply

    L21 = A21 @ L11^{-T}          (panel TRSM, leaf inverse precomputed)
    A22 -= L21 @ L21^T            (trailing SYRK, lower tiles only)

The tree dispatches these as a trsm call plus a syrk call per recursion
node; this kernel fuses both into ONE gridded ``pallas_call`` per panel:
the grid enumerates only the ``nt(nt+1)/2`` lower trailing tiles (reusing
:func:`repro.kernels.syrk._tri_decode`'s triangular index decode), each
program recomputes its row/column L21 tiles from VMEM-resident ``L11^-1``
(an extra rank-``b`` GEMM per tile — cheap on the MXU next to the tile
update, and it removes the inter-kernel HBM round-trip for L21), applies
the update with f32 accumulation, and the per-tile storage rounding /
quantization (the plan's dtype assignment) runs in the epilogue. The
``(i, 0..i)`` programs for one row are consecutive, so the L21 output
block stays VMEM-resident and is written once per row tile.

Per-tile precision metadata arrives as *static* tuples (the plan is pure
geometry); the rounding variants are compiled in, and two tiny int32
code tables (per-row storage dtype, per-pair compute dtype) ride along
as VMEM inputs read with masked-iota lookups. f64 containers route to
the jnp oracle in ops.py (the MXU has no f64 path), exactly like the
residual kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import vma_of
from repro.core.precision import DTYPES, RMAX
from repro.kernels.syrk import _tri_decode

#: the tiles are f32 containers holding values on the plan's grids
#: (f16 values need 11 significand bits, f32 ones 24): every dot runs at
#: full f32 on the MXU, not as Mosaic's default single bf16 pass
_HIGHEST = jax.lax.Precision.HIGHEST


def _round_name(x, name: str, quant: bool):
    """Round f32 VALUES onto ``name``'s storage grid (keeps f32).

    Mirrors ``repro.core.quantize.storage_round`` op-for-op so the
    kernel and the jnp oracle agree bitwise; inlined here (rather than
    imported) because the quantized paths must stay Pallas-traceable.
    """
    if name in ("f32", "f64"):
        # f64 CONTAINERS route to the jnp oracle in ops.py; an f64 level
        # NAME on the f32 container this kernel runs on is the identity
        return x
    if name == "bf16":
        return x.astype(DTYPES["bf16"]).astype(jnp.float32)
    if name == "int8":
        amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
        alpha = jnp.maximum(amax, jnp.float32(1e-30)) / jnp.float32(127.0)
        q = jnp.clip(jnp.round(x / alpha), -127.0, 127.0)
        return q * alpha
    assert name == "f16", name
    if not quant:
        return _f16_grid(x)
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    alpha = jnp.maximum(jnp.float32(1.0), amax / jnp.float32(RMAX["f16"]))
    return _f16_grid(x / alpha) * alpha


def _f16_grid(x):
    """``x.astype(float16).astype(float32)`` in f32 arithmetic.

    Mosaic cannot pack f32 vectors into f16 on v5e, so the rounding is
    emulated bit for bit: round-to-nearest-even onto the 11-bit
    significand (integer ops on the f32 bit pattern; a carry into the
    exponent is the correct round-up), the fixed 2^-24 spacing of f16
    subnormals below 2^-14, overflow past 65504 to inf, sign (zeros
    included) and NaN carried over.
    """
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    sign = bits & jnp.int32(-0x80000000)
    mag = bits & jnp.int32(0x7FFFFFFF)
    odd = (mag >> 13) & 1
    normal = jax.lax.bitcast_convert_type(
        (mag + jnp.int32(0xFFF) + odd) & jnp.int32(~0x1FFF), jnp.float32)
    ax = jnp.abs(x)
    sub = jnp.round(ax * jnp.float32(2.0 ** 24)) * jnp.float32(2.0 ** -24)
    y = jnp.where(ax < jnp.float32(2.0 ** -14), sub, normal)
    y = jnp.where(y > jnp.float32(RMAX["f16"]), jnp.float32(jnp.inf), y)
    y = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(y, jnp.int32) | sign, jnp.float32)
    return jnp.where(jnp.isnan(x), x, y)


def _round_select(x, code, names, quants):
    """Apply the rounding variant selected by the traced scalar ``code``
    (an index into the static ``names`` tuple)."""
    out = _round_name(x, names[0], quants[0])
    for k in range(1, len(names)):
        out = jnp.where(code == k, _round_name(x, names[k], quants[k]), out)
    return out


def _code_lookup(arr, *idx):
    """Masked-iota gather of a (VMEM-resident) int32 code table by traced
    indices — dynamic scalar indexing without SMEM plumbing."""
    mask = jnp.ones(arr.shape, bool)
    for d, ix in enumerate(idx):
        iota = jax.lax.broadcasted_iota(jnp.int32, arr.shape, d)
        mask = mask & (iota == ix)
    return jnp.sum(jnp.where(mask, arr, 0))


def _panel_kernel(sc_ref, pc_ref, linv_ref, ai_ref, aj_ref, c_ref,
                  l21_ref, co_ref, *, names, quants, rounding, b):
    t = pl.program_id(0)
    i, j = _tri_decode(t)
    store_codes = sc_ref[...]
    pair_codes = pc_ref[...]
    linv_t = linv_ref[...].astype(jnp.float32).T

    def solve_tile(a_tile, row):
        code = _code_lookup(store_codes, row)
        a = a_tile.astype(jnp.float32)
        if rounding:
            a = _round_select(a, code, names, quants)
        lt = jnp.dot(a, linv_t, precision=_HIGHEST,
                     preferred_element_type=jnp.float32)
        if rounding:
            lt = _round_select(lt, code, names, quants)
        return lt

    li = solve_tile(ai_ref[...], i)
    l21_ref[...] = li.astype(l21_ref.dtype)

    # trailing update at the (i, j) pair's compute precision
    pc = _code_lookup(pair_codes, i, j)
    qi = _round_select(li, pc, names, quants)
    lj = solve_tile(aj_ref[...], j)
    qj = _round_select(lj, pc, names, quants)
    upd = (c_ref[...].astype(jnp.float32)
           - jnp.dot(qi, qj.T, precision=_HIGHEST,
                     preferred_element_type=jnp.float32))
    if rounding:
        # the trailing matrix LIVES at its tiles' precision between
        # panels (paper Fig. 3) — round the updated partial sum back
        upd = _round_select(upd, pc, names, quants)
    rows = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    keep = jnp.logical_or(i != j, rows >= cols)
    co_ref[...] = jnp.where(keep, upd,
                            c_ref[...].astype(jnp.float32)).astype(co_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("store_names", "store_quants", "pair_names",
                     "pair_quants", "rounding", "interpret"))
def panel_update(linv, a21, c, *, store_names, store_quants, pair_names,
                 pair_quants, rounding=True, interpret=False):
    """Fused panel TRSM + trailing SYRK update.

    ``linv``: (b, b) inverse of the factored diagonal leaf; ``a21``:
    (m, b) sub-diagonal panel; ``c``: (m, m) trailing matrix (lower
    triangle meaningful, upper returned untouched). ``store_names`` /
    ``store_quants`` give each trailing row tile's storage dtype;
    ``pair_names``/``pair_quants`` give the compute dtype of every
    trailing (i, j) tile pair — all static, straight out of
    ``PrecisionPlan.panel_meta``. Returns ``(l21, c_updated)``.
    """
    m, b = a21.shape
    assert linv.shape == (b, b), (linv.shape, a21.shape)
    assert c.shape == (m, m), (c.shape, m)
    assert m % b == 0, (m, b)
    nt = m // b
    assert len(store_names) == nt and len(pair_names) == nt
    names = tuple(sorted({*store_names,
                          *(nm for row in pair_names for nm in row)}))
    quant_by = {}
    for nm, q in zip(store_names, store_quants):
        quant_by[nm] = q
    for row_n, row_q in zip(pair_names, pair_quants):
        for nm, q in zip(row_n, row_q):
            assert quant_by.setdefault(nm, q) == q, nm
    quants = tuple(quant_by[nm] for nm in names)
    store_codes = jnp.asarray([names.index(nm) for nm in store_names],
                              jnp.int32).reshape(nt, 1)
    pair_codes = jnp.asarray([[names.index(nm) for nm in row]
                              for row in pair_names], jnp.int32)
    ntri = nt * (nt + 1) // 2
    vma = vma_of(linv, a21, c)

    def ai_map(t):
        i, _ = _tri_decode(t)
        return (i, 0)

    def aj_map(t):
        _, j = _tri_decode(t)
        return (j, 0)

    def c_map(t):
        return _tri_decode(t)

    l21, c_out = pl.pallas_call(
        functools.partial(_panel_kernel, names=names, quants=quants,
                          rounding=rounding, b=b),
        grid=(ntri,),
        in_specs=[
            pl.BlockSpec((nt, 1), lambda t: (0, 0)),
            pl.BlockSpec((nt, nt), lambda t: (0, 0)),
            pl.BlockSpec((b, b), lambda t: (0, 0)),
            pl.BlockSpec((b, b), ai_map),
            pl.BlockSpec((b, b), aj_map),
            pl.BlockSpec((b, b), c_map),
        ],
        out_specs=[
            pl.BlockSpec((b, b), ai_map),
            pl.BlockSpec((b, b), c_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, b), a21.dtype, vma=vma),
            jax.ShapeDtypeStruct((m, m), c.dtype, vma=vma),
        ],
        interpret=interpret,
    )(store_codes, pair_codes, linv, a21, a21, c)
    # Upper trailing tiles were never visited; restore them from the
    # input so callers see an intact upper triangle (syrk_packed idiom).
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    touched = (rows // b) >= (cols // b)
    return l21, jnp.where(touched, c_out, c.astype(c_out.dtype))
