"""Flash-attention Pallas kernel (causal, GQA-aware).

The model zoo's hottest layer: the pure-JAX scan in models/attention.py
is the oracle; this kernel is the TPU-native version — online softmax
with the (m, l, acc) state in VMEM scratch, grid (batch*heads, q-block,
kv-block) with the kv dimension innermost so the running state carries
across kv steps. Fully-masked kv blocks are skipped with pl.when (the
causal lower triangle costs ~half the blocks). GQA never materializes
repeated K/V: the kv index_map divides the head index by the group size.

VMEM per program: q (bq, hd) + k/v (bk, hd) + acc (bq, hd) f32 + m/l
(bq, 128): bq=bk=256, hd<=256 => ~1.2 MB << 16 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 256
DEFAULT_BK = 256
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, nk, bq, bk, causal):
    kb = pl.program_id(2)
    qb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: kv block strictly after the q block contributes nothing
    run = (kb * bk <= qb * bq + (bq - 1)) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            qi = qb * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            ki = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qi >= ki, s, NEG_INF)
        m_prev = m_ref[:, :1]                             # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_ref[:, :1] = l_ref[:, :1] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[:, :1] = m_new

    @pl.when(kb == nk - 1)
    def _fin():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, bq=DEFAULT_BQ, bk=DEFAULT_BK,
                    interpret=False):
    """q: [H, S, hd]; k/v: [KV, T, hd] with H = KV * G (GQA).

    Returns [H, S, hd]. S/T padded to block multiples internally (the
    padded kv rows are masked by the causal test / a length mask).
    """
    H, S, hd = q.shape
    KV, T, _ = k.shape
    assert H % KV == 0, (H, KV)
    G = H // KV
    scale = hd ** -0.5
    bq = min(bq, S)
    bk = min(bk, T)
    Sp, Tp = (-(-S // bq)) * bq, (-(-T // bk)) * bk
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0)))
    if Tp != T:
        # pad keys so padded positions never win the max: since callers
        # use causal attention with T == S, padded kv rows are masked by
        # the causal test; for the non-causal path we mask via -inf keys.
        k = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0)),
                    constant_values=0.0)
        v = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0)))
    assert causal or Tp == T, "non-causal path requires T % bk == 0"

    nq, nk = Sp // bq, Tp // bk
    grid = (H, nq, nk)
    scratch = [pltpu.VMEM((bq, 128), jnp.float32),
               pltpu.VMEM((bq, 128), jnp.float32),
               pltpu.VMEM((bq, hd), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, nk=nk, bq=bq, bk=bk,
                          causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j, G=G: (h // G, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j, G=G: (h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((H, Sp, hd), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return out[:, :S] if Sp != S else out


def flash_attention_bshd(q, k, v, *, causal=True, interpret=False,
                         **blocks):
    """Batched convenience wrapper: q [B, S, H, hd], k/v [B, T, KV, hd]
    -> [B, S, H, hd] (vmap over batch)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    fn = functools.partial(flash_attention, causal=causal,
                           interpret=interpret, **blocks)
    out = jax.vmap(fn)(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)
