"""The plain reference the program's answers are checked against, and
the control: the same reference a step lower in precision.

Imports nothing of the program and takes nothing it made: the matrices
and right-hand sides come from :mod:`harness.gen`, and every product is
formed by XLA at ``HIGHEST`` precision (float32 on the MXU, as the
configurations state).
"""
from __future__ import annotations

import functools

import numpy as np

#: columns of a residual formed at once, so the reference fits beside
#: what the run keeps
COLS = 256


@functools.lru_cache(maxsize=None)
def _fns():
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def rel_residual(a, x, b):
        r = b - jnp.matmul(a, x, precision=hi)
        return jnp.linalg.norm(r, axis=0) / jnp.linalg.norm(b, axis=0)

    @jax.jit
    def backward_error(a, l):
        llt = jnp.matmul(l, l.T, precision=hi)
        return jnp.linalg.norm(a - llt) / jnp.linalg.norm(a)

    def to_bf16(v):
        return v.astype(jnp.bfloat16).astype(jnp.float32)

    @jax.jit
    def control_factor(a):
        # Cholesky of A at HIGHEST, held in bfloat16
        with jax.default_matmul_precision("highest"):
            return to_bf16(jnp.linalg.cholesky(to_bf16(a)))

    @jax.jit
    def control_solve(l, b):
        # two triangular solves against the bfloat16 factor, the answer
        # held in bfloat16
        with jax.default_matmul_precision("highest"):
            y = jax.scipy.linalg.solve_triangular(l, b, lower=True)
            x = jax.scipy.linalg.solve_triangular(l.T, y, lower=False)
        return to_bf16(x)

    return rel_residual, backward_error, control_factor, control_solve


def relative_residuals(a, x, b) -> np.ndarray:
    """Per column ||b - A x||_2 / ||b||_2, A x at HIGHEST, in blocks of
    :data:`COLS` columns. ``x``, ``b``: (n, k)."""
    rel_residual = _fns()[0]
    k = x.shape[1]
    out = []
    for c in range(0, k, COLS):
        xs, bs = x[:, c:c + COLS], b[:, c:c + COLS]
        if xs.shape[1] < COLS and k > COLS:
            # pad the last block so every block has one shape
            import jax.numpy as jnp
            pad = COLS - xs.shape[1]
            xs = jnp.pad(xs, ((0, 0), (0, pad)))
            bs = jnp.pad(bs, ((0, 0), (0, pad)), constant_values=1.0)
            out.append(np.asarray(rel_residual(a, xs, bs))[:COLS - pad])
        else:
            out.append(np.asarray(rel_residual(a, xs, bs)))
    return np.concatenate(out)


def backward_error(a, l) -> float:
    """||A - L L^T||_F / ||A||_F with L L^T at HIGHEST."""
    return float(_fns()[1](a, l))


def control_factor(a):
    """The control's factor: the reference Cholesky, stored in bfloat16
    (the step below the configurations' float32)."""
    return _fns()[2](a)


def control_solve(l, b):
    """The control's answer for ``b`` against :func:`control_factor`'s
    ``l``, held in bfloat16."""
    return _fns()[3](l, b)
