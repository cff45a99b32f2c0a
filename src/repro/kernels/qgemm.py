"""Quantized mixed-precision GEMM Pallas kernel (the framework's hot loop).

TPU mapping of the paper's FP16 tensor-core GEMMs:
  * inputs arrive in the low compute dtype (bf16 native on MXU, f16 for
    paper-faithful quantized mode),
  * contraction runs on the MXU with f32 accumulation in a VMEM scratch
    accumulator,
  * the dequantization scale (alpha * scale_a * scale_b) and the optional
    ``beta * C`` accumuland are fused into the epilogue on the last k-step.

Grid is (M/bm, N/bn, K/bk) with k innermost ("arbitrary") so the VMEM
accumulator carries across k-steps; m/n are parallel dimensions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import vma_of
from repro.core.precision import DTYPES

# Default tile sizes: MXU-aligned (multiples of 128), working set
# 2*(bm*bk + bk*bn)*2B + bm*bn*4B ~ 1.3 MB << 16 MB VMEM.
DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _kernel(s_ref, a_ref, b_ref, o_ref, acc_ref, *, trans_b, nk, has_c,
            c_ref=None):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    b = b_ref[...]
    if trans_b:
        b = b.T
    # f32 operands (the ladder's high-precision solves) run at full f32
    # on the MXU; Mosaic takes no precision for narrow operands
    prec = jax.lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    acc_ref[...] += jnp.dot(a, b, precision=prec,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _epilogue():
        scale = s_ref[0, 0]
        out = acc_ref[...] * scale
        if has_c:
            beta = s_ref[1, 0]
            out = out + beta * c_ref[...].astype(jnp.float32)
        o_ref[...] = out.astype(o_ref.dtype)


def _kernel_with_c(s_ref, a_ref, b_ref, c_ref, o_ref, acc_ref, *, trans_b, nk):
    _kernel(s_ref, a_ref, b_ref, o_ref, acc_ref, trans_b=trans_b, nk=nk,
            has_c=True, c_ref=c_ref)


@functools.partial(
    jax.jit,
    static_argnames=("trans_b", "out_dtype", "bm", "bn", "bk", "interpret"))
def qgemm(a, b, scale, *, c=None, beta=0.0, trans_b=False,
          out_dtype=jnp.float32, bm=DEFAULT_BM, bn=DEFAULT_BN,
          bk=DEFAULT_BK, interpret=False):
    """out = scale * (a @ b[.T]) [+ beta * c], f32 accumulation.

    a: (M, K) low precision.  b: (K, N) or (N, K) when trans_b.
    scale: scalar f32 dequantization factor (already includes alpha).
    c: optional (M, N) accumuland in any float dtype.
    """
    M, K = a.shape
    N = b.shape[0] if trans_b else b.shape[1]
    kb = b.shape[1] if trans_b else b.shape[0]
    assert kb == K, (a.shape, b.shape, trans_b)

    # int8 ladder level: values in [-127, 127] are exact in bf16 and the
    # f32 accumulator is exact up to k*127^2 < 2^24, so the bf16 MXU path
    # is bit-identical to int32 accumulation at our tile sizes. A native
    # s8 MXU kernel (2x rate on v5e) is the on-hardware upgrade path.
    if jnp.issubdtype(a.dtype, jnp.integer):
        a = a.astype(DTYPES["bf16"])
    if jnp.issubdtype(b.dtype, jnp.integer):
        b = b.astype(DTYPES["bf16"])

    bm = min(bm, M)
    bn = min(bn, N)
    bk = min(bk, K)
    # Pad to tile multiples; zero padding is exact for matmul.
    Mp, Np, Kp = (-(-M // bm)) * bm, (-(-N // bn)) * bn, (-(-K // bk)) * bk
    if (Mp, Kp) != (M, K):
        a = jnp.pad(a, ((0, Mp - M), (0, Kp - K)))
    if trans_b:
        if (Np, Kp) != b.shape:
            b = jnp.pad(b, ((0, Np - N), (0, Kp - K)))
    else:
        if (Kp, Np) != b.shape:
            b = jnp.pad(b, ((0, Kp - K), (0, Np - N)))
    has_c = c is not None
    if has_c and (Mp, Np) != c.shape:
        c = jnp.pad(c, ((0, Mp - M), (0, Np - N)))

    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)

    s = jnp.stack([jnp.asarray(scale, jnp.float32),
                   jnp.asarray(beta, jnp.float32)]).reshape(2, 1)

    b_spec = (pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)) if trans_b
              else pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)))
    in_specs = [
        pl.BlockSpec((2, 1), lambda i, j, k: (0, 0)),
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        b_spec,
    ]
    operands = [s, a, b]
    if has_c:
        in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)))
        operands.append(c)
        kernel = functools.partial(_kernel_with_c, trans_b=trans_b, nk=nk)
    else:
        kernel = functools.partial(_kernel, trans_b=trans_b, nk=nk,
                                   has_c=False)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype,
                                       vma=vma_of(*operands)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="qgemm",
    )(*operands)
    if (Mp, Np) != (M, N):
        out = out[:M, :N]
    return out
