"""SYRK Pallas kernels: C <- beta*C + alpha*scale*(A A^T), lower triangle.

Two kernels:

* ``syrk_leaf`` — the tree recursion's diagonal leaf: a single (b, b)
  output tile with the k-dimension gridded (A panels can be very wide),
  f32 VMEM accumulator, diagonal masking fused in the epilogue.

* ``syrk_packed`` — beyond-paper fused SYRK for *large* n: instead of
  recursing (paper) or running a rectangular grid and discarding the upper
  half (2x waste), the grid enumerates only the n_t(n_t+1)/2 lower tiles;
  the (i, j) tile coordinates are decoded from the linear triangular index
  inside the index_map. This is the flat-kernel rival we hillclimb against
  tree-SYRK in benchmarks/bench_syrk.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import vma_of
from repro.core.precision import DTYPES

DEFAULT_BN = 256
DEFAULT_BK = 512


def _mask_lower(tile, i_blk, j_blk, bn):
    """Zero the strictly-upper part of a diagonal tile (i_blk == j_blk)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
    on_diag = i_blk == j_blk
    keep = jnp.logical_or(jnp.logical_not(on_diag), rows >= cols)
    return jnp.where(keep, tile, 0.0)


def _syrk_leaf_kernel(s_ref, a_ref, c_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    acc_ref[...] += jnp.dot(a, a.T, preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        scale, beta = s_ref[0, 0], s_ref[1, 0]
        c = c_ref[...].astype(jnp.float32)
        upd = beta * c + scale * acc_ref[...]
        n = upd.shape[0]
        rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        o_ref[...] = jnp.where(rows >= cols, upd, c).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def syrk_leaf(c, a, scale, beta, *, bk=DEFAULT_BK, interpret=False):
    """Diagonal-leaf SYRK: c (n,n) f32-ish, a (n,K) low precision."""
    n, K = a.shape
    assert c.shape == (n, n)
    if jnp.issubdtype(a.dtype, jnp.integer):
        a = a.astype(DTYPES["bf16"])      # exact for int8 (|v| <= 127)
    bk = min(bk, K)
    Kp = (-(-K // bk)) * bk
    if Kp != K:
        a = jnp.pad(a, ((0, 0), (0, Kp - K)))
    nk = Kp // bk
    s = jnp.stack([jnp.asarray(scale, jnp.float32),
                   jnp.asarray(beta, jnp.float32)]).reshape(2, 1)
    return pl.pallas_call(
        functools.partial(_syrk_leaf_kernel, nk=nk),
        grid=(nk,),
        in_specs=[
            pl.BlockSpec((2, 1), lambda k: (0, 0)),
            pl.BlockSpec((n, bk), lambda k: (0, k)),
            pl.BlockSpec((n, n), lambda k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((n, n), lambda k: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), c.dtype,
                                       vma=vma_of(s, a, c)),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
        name="syrk_leaf",
    )(s, a, c)


def _tri_decode(t):
    """Decode linear lower-triangular index t -> (i, j), i >= j.

    i = floor((sqrt(8t+1)-1)/2) computed in f32 with a +-1 integer
    correction (exact for the grid sizes we use, t < 2^20).
    """
    tf = t.astype(jnp.float32)
    i0 = jnp.floor((jnp.sqrt(8.0 * tf + 1.0) - 1.0) / 2.0).astype(jnp.int32)
    # correct rounding both ways
    i0 = jnp.where((i0 + 1) * (i0 + 2) // 2 <= t, i0 + 1, i0)
    i0 = jnp.where(i0 * (i0 + 1) // 2 > t, i0 - 1, i0)
    j = t - i0 * (i0 + 1) // 2
    return i0, j


def _syrk_packed_kernel(s_ref, a_ref, at_ref, c_ref, o_ref, acc_ref, *, nk,
                        bn):
    k = pl.program_id(1)
    t = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], at_ref[...].T,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        i_blk, j_blk = _tri_decode(t)
        scale, beta = s_ref[0, 0], s_ref[1, 0]
        c = c_ref[...].astype(jnp.float32)
        upd = beta * c + scale * acc_ref[...]
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1)
        keep = jnp.logical_or(i_blk != j_blk, rows >= cols)
        o_ref[...] = jnp.where(keep, upd, c).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def syrk_packed(c, a, scale, beta, *, bn=DEFAULT_BN, bk=DEFAULT_BK,
                interpret=False):
    """Fused triangular-packed SYRK over the full (n, n) lower triangle.

    Grid = (n_t(n_t+1)/2, K/bk): only lower tiles are enumerated; tile
    coordinates are decoded from the linear index inside the index_maps.
    """
    n, K = a.shape
    assert c.shape == (n, n)
    if jnp.issubdtype(a.dtype, jnp.integer):
        a = a.astype(DTYPES["bf16"])      # exact for int8 (|v| <= 127)
    bn = min(bn, n)
    bk = min(bk, K)
    npad = (-(-n // bn)) * bn
    Kp = (-(-K // bk)) * bk
    if (npad, Kp) != (n, K):
        a = jnp.pad(a, ((0, npad - n), (0, Kp - K)))
    if npad != n:
        c = jnp.pad(c, ((0, npad - n), (0, npad - n)))
    nt = npad // bn
    nk = Kp // bk
    ntri = nt * (nt + 1) // 2
    s = jnp.stack([jnp.asarray(scale, jnp.float32),
                   jnp.asarray(beta, jnp.float32)]).reshape(2, 1)

    def a_map(t, k):
        i, _ = _tri_decode(t)
        return (i, k)

    def at_map(t, k):
        _, j = _tri_decode(t)
        return (j, k)

    def c_map(t, k):
        i, j = _tri_decode(t)
        return (i, j)

    out = pl.pallas_call(
        functools.partial(_syrk_packed_kernel, nk=nk, bn=bn),
        grid=(ntri, nk),
        in_specs=[
            pl.BlockSpec((2, 1), lambda t, k: (0, 0)),
            pl.BlockSpec((bn, bk), a_map),
            pl.BlockSpec((bn, bk), at_map),
            pl.BlockSpec((bn, bn), c_map),
        ],
        out_specs=pl.BlockSpec((bn, bn), c_map),
        out_shape=jax.ShapeDtypeStruct((npad, npad), c.dtype,
                                       vma=vma_of(s, a, c)),
        scratch_shapes=[pltpu.VMEM((bn, bn), jnp.float32)],
        interpret=interpret,
        name="syrk_packed",
    )(s, a, a, c)
    # Off-triangle tiles of the padded output were never visited; restore
    # them from the input so callers see an intact upper triangle.
    rows = jax.lax.broadcasted_iota(jnp.int32, (npad, npad), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (npad, npad), 1)
    tile_touched = (rows // bn) >= (cols // bn)
    out = jnp.where(tile_touched, out, c.astype(out.dtype))
    return out[:n, :n] if npad != n else out
