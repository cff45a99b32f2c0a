"""Leaf Cholesky (POTRF) Pallas kernel.

The tree recursion bottoms out on a b x b SPD tile (b <= 512) that fits in
VMEM. Inside the kernel we run a blocked right-looking Cholesky over
128-wide panels (MXU-aligned):

    for each 128-panel j (python-unrolled, shapes static):
        L_jj, L_jj^-1  <- vectorised Cholesky + forward substitution
                           (fori_loop over 128 columns, VPU rank-1 updates)
        panel          <- A[below, j] @ L_jj^-T           (MXU)
        trailing       <- trailing - panel @ panel^T      (MXU)

This replaces the paper's cuSOLVER leaf: on TPUs the in-VMEM panel
factorisation keeps the MXU busy on the trailing updates while the 128x128
diagonal factorisation runs on the VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import vma_of

MICRO = 128  # diagonal micro-panel, matches MXU/VREG lane width
#: every dot here is an f32 leaf step of the ladder's high level: pin it
#: to full f32 on the MXU, not Mosaic's default single bf16 pass
_HIGHEST = jax.lax.Precision.HIGHEST


def _chol_micro(a):
    """Vectorised unblocked Cholesky of a (m, m) tile; returns lower L.

    Column ``j`` is read and written through masked-iota selects and
    reductions over the whole tile (only the selected entries are
    nonzero, so every sum is exact): Mosaic has no lowering for
    ``dynamic_slice`` on a traced loop index.
    """
    m = a.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    rvec = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)

    def body(j, a):
        col = jnp.sum(jnp.where(cols == j, a, 0.0), axis=1,
                      keepdims=True)                                # (m, 1)
        d = jnp.sqrt(jnp.sum(jnp.where(rvec == j, col, 0.0), axis=0,
                             keepdims=True))                        # (1, 1)
        col = jnp.where(rvec >= j, col / d, 0.0)
        a = jnp.where(cols == j, col, a)
        # col as a row vector, via the diagonal (no transpose/reshape)
        row = jnp.sum(jnp.where(rows == cols, col, 0.0), axis=0,
                      keepdims=True)                                # (1, m)
        upd = jnp.where(cols > j, col * row, 0.0)
        return a - upd

    a = jax.lax.fori_loop(0, m, body, a)
    return jnp.where(rows >= cols, a, 0.0)


def _tri_inv_micro(l):
    """X = L^-1 for lower-triangular (m, m) via row-wise forward subst.

    Row ``i`` of ``L`` is read and row ``i`` of ``X`` written through
    masked-iota selects, as in :func:`_chol_micro`.
    """
    m = l.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (1, m), 1)
    x0 = jnp.zeros_like(l)

    def body(i, x):
        li = jnp.sum(jnp.where(rows == i, l, 0.0), axis=0,
                     keepdims=True)                                 # (1, m)
        li_strict = jnp.where(cols < i, li, 0.0)
        s = jnp.dot(li_strict, x, precision=_HIGHEST,
                    preferred_element_type=jnp.float32)
        e = (cols == i).astype(l.dtype)
        lii = jnp.sum(jnp.where(cols == i, li, 0.0), axis=1,
                      keepdims=True)                                # (1, 1)
        row = (e - s.astype(l.dtype)) / lii
        return jnp.where(rows == i, row, x)

    return jax.lax.fori_loop(0, m, body, x0)


def _potrf_kernel(a_ref, o_ref, w_ref, *, b):
    # ``w_ref`` is the f32 working tile in VMEM; block writes go through
    # static ref slices (Mosaic lowers no dynamic_update_slice)
    w_ref[...] = a_ref[...].astype(jnp.float32)
    nb = b // MICRO
    for j in range(nb):  # python-unrolled: static shapes per panel
        j0, j1 = j * MICRO, (j + 1) * MICRO
        l = _chol_micro(w_ref[j0:j1, j0:j1])
        w_ref[j0:j1, j0:j1] = l
        if j < nb - 1:
            linv = _tri_inv_micro(l)
            panel = jnp.dot(w_ref[j1:, j0:j1], linv.T, precision=_HIGHEST,
                            preferred_element_type=jnp.float32)
            w_ref[j1:, j0:j1] = panel
            w_ref[j1:, j1:] = w_ref[j1:, j1:] - jnp.dot(
                panel, panel.T, precision=_HIGHEST,
                preferred_element_type=jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, b), 1)
    o_ref[...] = jnp.where(rows >= cols, w_ref[...], 0.0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def potrf_leaf(a, *, interpret=False):
    """Cholesky of a single SPD tile (n multiple of 128, n <= 512)."""
    n = a.shape[-1]
    assert n % MICRO == 0 and a.shape == (n, n), a.shape
    return pl.pallas_call(
        functools.partial(_potrf_kernel, b=n),
        in_specs=[pl.BlockSpec((n, n), lambda: (0, 0))],
        out_specs=pl.BlockSpec((n, n), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), a.dtype,
                                       vma=vma_of(a)),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
        name="potrf_leaf",
    )(a)


def _tri_inv_kernel(l_ref, o_ref, x_ref, *, b):
    nb = b // MICRO
    # Diagonal micro-inverses, then blocked forward substitution:
    #   X[i,j] = -inv_i @ ( sum_{j<=k<i} L[i,k] X[k,j] )
    # with X accumulated in the f32 VMEM tile ``x_ref``.
    invs = []
    for i in range(nb):
        i0, i1 = i * MICRO, (i + 1) * MICRO
        invs.append(_tri_inv_micro(l_ref[i0:i1, i0:i1].astype(jnp.float32)))
    x_ref[...] = jnp.zeros((b, b), jnp.float32)
    for j in range(nb):
        j0, j1 = j * MICRO, (j + 1) * MICRO
        x_ref[j0:j1, j0:j1] = invs[j]
        for i in range(j + 1, nb):
            i0, i1 = i * MICRO, (i + 1) * MICRO
            s = jnp.zeros((MICRO, MICRO), jnp.float32)
            for k in range(j, i):
                k0, k1 = k * MICRO, (k + 1) * MICRO
                s = s + jnp.dot(l_ref[i0:i1, k0:k1].astype(jnp.float32),
                                x_ref[k0:k1, j0:j1], precision=_HIGHEST,
                                preferred_element_type=jnp.float32)
            x_ref[i0:i1, j0:j1] = -jnp.dot(invs[i], s, precision=_HIGHEST,
                                           preferred_element_type=jnp.float32)
    o_ref[...] = x_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def tri_inv_leaf(l, *, interpret=False):
    """Inverse of a lower-triangular leaf tile (n multiple of 128)."""
    n = l.shape[-1]
    assert n % MICRO == 0 and l.shape == (n, n), l.shape
    return pl.pallas_call(
        functools.partial(_tri_inv_kernel, b=n),
        in_specs=[pl.BlockSpec((n, n), lambda: (0, 0))],
        out_specs=pl.BlockSpec((n, n), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), l.dtype,
                                       vma=vma_of(l)),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
        name="tri_inv_leaf",
    )(l)
