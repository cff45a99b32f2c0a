"""The plain reference for the sharded cells, and its control: what
``harness.reference`` is for one chip, for a matrix held block-row-
sharded over a mesh (at n = 65536 neither A nor L fits one chip).

Imports nothing of the program and takes nothing it made but the
answers it checks. Every product is formed by XLA at ``HIGHEST``
precision, with the operands' row sharding, so nothing is gathered
whole:

* :func:`relative_residuals` — per column ||b - A x||_2 / ||b||_2;
* :func:`backward_error` — ||A - L L^T||_F / ||A||_F, L L^T formed one
  block of :data:`COLS` columns at a time: the block's rows of L are
  summed to every device from their owner (a psum of a masked block,
  COLS x n), and each device forms its own rows of the block. A
  sliced L at a traced offset would be gathered whole by XLA (16 GiB a
  chip at n = 65536). Every block is used, none sampled: at n = 65536
  a block's product is 2 n^2 COLS / P = 4.4e12 flops a chip, 32 blocks
  of about 0.2 s at HIGHEST on a v5e (a few seconds a run, and only
  the window's last factor is checked);
* :func:`control_factor` / :func:`control_solve` — the control: a plain
  right-looking block Cholesky of bfloat16(A) at HIGHEST and its two
  triangular solves, each result held in bfloat16 (the step below the
  configurations' float32), as ``reference.control_factor`` /
  ``control_solve`` do on one chip.
"""
from __future__ import annotations

import functools

#: columns of L L^T formed at once by :func:`backward_error`, and the
#: panel width of the control's factor and solves
COLS = 2048


def _hi():
    import jax
    return jax.lax.Precision.HIGHEST


@functools.lru_cache(maxsize=None)
def _residual_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def rel_residual(a, x, b):
        r = b - jnp.matmul(a, x, precision=_hi())
        return jnp.linalg.norm(r, axis=0) / jnp.linalg.norm(b, axis=0)
    return rel_residual


def _block_local(a, l, c0, *, axis: str, cols: int):
    """Columns [c0, c0 + cols) of A - L L^T on a device's rows, squared
    and summed over all devices, and the same of A."""
    import jax
    import jax.numpy as jnp
    w = a.shape[0]
    my = jax.lax.axis_index(axis)
    o = c0 // w
    lc = jax.lax.dynamic_slice_in_dim(l, c0 - o * w, cols, axis=0)
    lc = jax.lax.psum(jnp.where(my == o, lc, 0.0), axis)     # (cols, n)
    ac = jax.lax.dynamic_slice_in_dim(a, c0, cols, axis=1)    # (w, cols)
    d = ac - jnp.matmul(l, lc.T, precision=_hi())
    return (jax.lax.psum(jnp.sum(d * d), axis),
            jax.lax.psum(jnp.sum(ac * ac), axis))


@functools.lru_cache(maxsize=None)
def _block_fn(mesh, axis: str, cols: int):
    import jax
    from jax.sharding import PartitionSpec as P
    spec = P(axis, None)
    return jax.jit(jax.shard_map(
        functools.partial(_block_local, axis=axis, cols=cols), mesh=mesh,
        in_specs=(spec, spec, P()), out_specs=(P(), P())))


def relative_residuals(a, x, b):
    """Per column ||b - A x||_2 / ||b||_2, A x at HIGHEST. ``a``: (n, n),
    ``x``, ``b``: (n, k), any sharding."""
    import numpy as np
    return np.asarray(_residual_fn()(a, x, b))


def backward_error(a, l, mesh, axis: str = "model") -> float:
    """||A - L L^T||_F / ||A||_F over every column block. ``a``, ``l``:
    (n, n), rows sharded over ``axis`` of ``mesh``."""
    import jax.numpy as jnp
    n = a.shape[-1]
    w = n // mesh.shape[axis]
    cols = min(COLS, w)
    assert w % cols == 0, (w, cols)
    block = _block_fn(mesh, axis, cols)
    num = den = 0.0
    for c0 in range(0, n, cols):
        dd, aa = block(a, l, jnp.int32(c0))
        num += float(dd)
        den += float(aa)
    return (num / den) ** 0.5


def _to_bf16(v):
    """``v`` rounded to the nearest bfloat16 (ties to even), kept in
    float32. Done on the bits, which no compiler pass may take out: a
    control that rounded by a float32 -> bfloat16 -> float32 cast pair
    read a residual of 9.3e-6 at n = 65536 on a v5e, where an answer
    held in bfloat16 gives about 1e-3."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & 1)) & jnp.uint32(
        0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


def _chol_local(a, *, axis: str, nshards: int, cb: int):
    """Right-looking block Cholesky of a block-row-sharded A: per panel
    of ``cb`` columns the owner's diagonal block is summed to every
    device, factored, the panel solved on every device's rows and
    gathered, and each device's trailing rows updated."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular
    w, n = a.shape
    my = jax.lax.axis_index(axis)
    rows = my * w + jnp.arange(w)[:, None]
    for j in range(n // cb):
        c0, c1 = j * cb, (j + 1) * cb
        o, off = c0 // w, c0 % w
        col = a[:, c0:c1]
        d = jax.lax.psum(jnp.where(my == o, col[off:off + cb], 0.0), axis)
        ld = jnp.linalg.cholesky(d)
        li = solve_triangular(ld, col.T, lower=True).T
        li = jnp.where(rows >= c1, li, 0.0)
        li = li.at[off:off + cb].set(jnp.where(my == o, ld,
                                               li[off:off + cb]))
        if c1 < n:
            g = jax.lax.all_gather(li, axis)
            for k in range(c1 // w, nshards):
                lo, hi = max(c1, k * w), (k + 1) * w
                a = a.at[:, lo:hi].add(-jnp.matmul(
                    li, g[k, lo - k * w:hi - k * w].T, precision=_hi()))
        a = a.at[:, c0:c1].set(li)
    return jnp.where(jnp.arange(n)[None, :] <= rows, a, 0.0)


def _solve_local(l, b, *, axis: str, nshards: int, cb: int):
    """Forward then back substitution on block-row-sharded L and B, in
    panels of ``cb``: only (cb, k) blocks move."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.linalg import solve_triangular
    w = l.shape[0]
    n = w * nshards
    my = jax.lax.axis_index(axis)
    y = b
    for j in range(n // cb):
        c0, c1 = j * cb, (j + 1) * cb
        o, off = c0 // w, c0 % w
        pan = l[:, c0:c1]
        yj = solve_triangular(pan[off:off + cb], y[off:off + cb],
                              lower=True)
        yj = jax.lax.psum(jnp.where(my == o, yj, 0.0), axis)
        y = y - jnp.matmul(pan, yj, precision=_hi())
        y = y.at[off:off + cb].set(jnp.where(my == o, yj, y[off:off + cb]))
    x = jnp.zeros_like(y)
    for j in reversed(range(n // cb)):
        c0, c1 = j * cb, (j + 1) * cb
        o, off = c0 // w, c0 % w
        pan = l[:, c0:c1]
        part = jax.lax.psum(jnp.matmul(pan.T, x, precision=_hi()), axis)
        xj = solve_triangular(pan[off:off + cb], y[off:off + cb] - part,
                              lower=True, trans=1)
        x = x.at[off:off + cb].set(jnp.where(my == o, xj, x[off:off + cb]))
    return x


def _sharded(local, mesh, axis, nargs):
    import jax
    from jax.sharding import PartitionSpec as P
    spec = P(axis, None)
    return jax.shard_map(local, mesh=mesh, in_specs=(spec,) * nargs,
                         out_specs=spec)


@functools.lru_cache(maxsize=None)
def _control_fns(mesh, axis: str, n: int):
    import jax
    nshards = mesh.shape[axis]
    cb = min(COLS, n // nshards)
    chol = _sharded(functools.partial(_chol_local, axis=axis,
                                      nshards=nshards, cb=cb), mesh, axis, 1)
    solve = _sharded(functools.partial(_solve_local, axis=axis,
                                       nshards=nshards, cb=cb), mesh, axis, 2)

    @jax.jit
    def factor(a):
        with jax.default_matmul_precision("highest"):
            return _to_bf16(chol(_to_bf16(a)))

    @jax.jit
    def answer(l, b):
        with jax.default_matmul_precision("highest"):
            return _to_bf16(solve(l, b))
    return factor, answer


def control_factor(a, mesh, axis: str = "model"):
    """The control's factor of block-row-sharded ``a``: the reference
    Cholesky of bfloat16(A), held in bfloat16."""
    return _control_fns(mesh, axis, a.shape[-1])[0](a)


def control_solve(l, b, mesh, axis: str = "model"):
    """The control's answer for ``b`` against :func:`control_factor`'s
    ``l``, held in bfloat16."""
    return _control_fns(mesh, axis, l.shape[-1])[1](l, b)
