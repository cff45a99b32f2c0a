"""Fused residual kernel: r = b - A @ x, the IR hot path.

Every iterative-refinement sweep forms the residual in the residual
precision — an O(n^2) GEMM that the mixed-precision literature says
should be nearly free next to the O(n^3) factorization, but which
dominates serve-side sweep latency when left to generic XLA (separate
matmul + subtract, two HBM round-trips for the intermediate).
``residual_fused`` tiles the GEMM over (row-block, k-block) grid cells,
accumulates A @ x in an f32 VMEM scratch, and fuses the ``b - acc``
epilogue into the final k-step so the intermediate product never touches
HBM.

``ref.residual_ref`` is the pure-jnp oracle (and the CPU execution
path); ``ops.residual`` dispatches between them. f64 residuals (the x64
accuracy ladder) always take the reference path — the TPU MXU has no
f64, and the fused kernel's f32 accumulator would silently truncate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import vma_of

DEFAULT_BM = 256
DEFAULT_BK = 512
#: TPU lane width — RHS column counts are padded up to a multiple of this
LANE = 128


def _residual_kernel(a_ref, x_ref, b_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the residual is the refinement's f32 yardstick: f32 operands run at
    # full f32 on the MXU (Mosaic takes no precision for narrow ones)
    a = a_ref[...]
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    acc_ref[...] += jnp.dot(a, x_ref[...], precision=prec,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        o_ref[...] = (b_ref[...].astype(jnp.float32)
                      - acc_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "interpret"))
def residual_fused(a, x, b, *, bm=DEFAULT_BM, bk=DEFAULT_BK,
                   interpret=False):
    """Fused r = b - a @ x. a: (m, n); x: (n,) or (n, k); b: (m,) or
    (m, k). A row block of a square system (m < n) is what each device
    forms on the distributed path.

    Grid = (m/bm, n/bk); each row-block accumulates its k-panels in an
    f32 VMEM scratch and subtracts from b in the epilogue. Inputs are
    zero-padded to tile/lane multiples and the result sliced back, so
    arbitrary m, n and k are accepted.
    """
    vec = x.ndim == 1
    if vec:
        x, b = x[:, None], b[:, None]
    n, kc = x.shape
    m = a.shape[0]
    assert a.shape == (m, n) and b.shape == (m, kc), (a.shape, b.shape)
    bm, bk = min(bm, m), min(bk, n)
    mpad = -(-m // bm) * bm          # row blocking of A / b / r
    kpad = -(-n // bk) * bk          # contraction blocking of A / x
    cpad = -(-kc // LANE) * LANE
    if (mpad, kpad) != (m, n):
        a = jnp.pad(a, ((0, mpad - m), (0, kpad - n)))
    if kpad != n:
        x = jnp.pad(x, ((0, kpad - n), (0, 0)))
    if mpad != m:
        b = jnp.pad(b, ((0, mpad - m), (0, 0)))
    if cpad != kc:
        x = jnp.pad(x, ((0, 0), (0, cpad - kc)))
        b = jnp.pad(b, ((0, 0), (0, cpad - kc)))
    nm, nk = mpad // bm, kpad // bk
    out = pl.pallas_call(
        functools.partial(_residual_kernel, nk=nk),
        grid=(nm, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
            pl.BlockSpec((bk, cpad), lambda i, k: (k, 0)),
            pl.BlockSpec((bm, cpad), lambda i, k: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, cpad), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mpad, cpad), b.dtype,
                                       vma=vma_of(a, x, b)),
        scratch_shapes=[pltpu.VMEM((bm, cpad), jnp.float32)],
        interpret=interpret,
        name="residual_fused",
    )(a, x, b)
    out = out[:m, :kc]
    return out[:, 0] if vec else out
