"""Leaf TRSM Pallas kernel: X = B @ L^{-T} for a leaf-sized L.

TPU adaptation (documented in docs/ARCHITECTURE.md, "Leaf kernels"): instead of per-column
substitution (latency-bound on a systolic array), we invert the leaf
triangle once in VMEM (kernels/potrf.py:tri_inv_leaf) and turn the solve
into a GEMM, which is exactly what the MXU wants. The row dimension of B
is gridded so arbitrarily tall panels stream through VMEM while L^{-1}
stays resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat import vma_of
from repro.kernels.potrf import tri_inv_leaf

DEFAULT_BM = 512


def _trsm_kernel(b_ref, linv_ref, o_ref, *, trans):
    b = b_ref[...]
    linv = linv_ref[...]
    if trans:
        linv = linv.T
    # f32 panels solve at full f32 on the MXU (Mosaic takes no precision
    # for narrow operands)
    prec = jax.lax.Precision.HIGHEST if b.dtype == jnp.float32 else None
    o_ref[...] = jnp.dot(b, linv, precision=prec,
                         preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def trsm_leaf(b, l=None, *, linv=None, bm=DEFAULT_BM, interpret=False):
    """Solve X L^T = B (right, lower, transposed — the paper's Alg. 2 leaf).

    b: (M, n) panel; l: (n, n) lower-triangular leaf (n multiple of 128).
    ``linv`` takes a precomputed ``tri_inv_leaf(l)`` so repeated solves
    against one factor (cholesky_solve's two sweeps, K-FAC steps, the
    serve factor cache) skip the O(n^3) leaf inversion; otherwise it is
    computed here from ``l``.
    """
    M, n = b.shape
    if linv is None:
        assert l is not None and l.shape == (n, n), (b.shape,)
        linv = tri_inv_leaf(l, interpret=interpret)
    assert linv.shape == (n, n), (linv.shape, b.shape)

    bm = min(bm, M)
    Mp = (-(-M // bm)) * bm
    if Mp != M:
        b = jnp.pad(b, ((0, Mp - M), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_trsm_kernel, trans=True),
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((n, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, n), b.dtype,
                                       vma=vma_of(b, linv)),
        interpret=interpret,
        name="trsm_leaf",
    )(b, linv.astype(b.dtype))
    return out[:M] if Mp != M else out
