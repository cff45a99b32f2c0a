"""Inputs made from ``--seed``: the paper's SPD test matrices and
right-hand sides on the device, and the targets of the traffic on the
host.

Every seed gets the same amounts of work: each run of ``len(targets)``
consecutive requests of a caller holds every target once, in an order
drawn from the seed, so any window holds the targets in equal shares
to within one request each.
"""
from __future__ import annotations

import math

import numpy as np

#: streams drawn from one seed, kept apart
_MATRIX, _RHS, _ORDER = 0, 1, 2


def key(seed: int, stream: int):
    """A JAX key from a seed of any size: ``jax.random.key`` keeps only
    the low 32 bits, so the high bits are folded in."""
    import jax
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(k, seed >> 32), stream)


def spd(key, n: int, dtype=None):
    """The paper's test matrix (arXiv 2601.08082, section IV-A): uniform
    entries in [-1, 1], symmetrized, plus ``n`` on the diagonal.
    Traceable: called inside the one jitted call that makes a pool."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.float32
    m = jax.random.uniform(key, (n, n), dtype, -1.0, 1.0)
    return (m + m.T) * 0.5 + n * jnp.eye(n, dtype=dtype)


def matrix_pool(seed: int, n: int, count: int, nrhs: int):
    """``count`` pairs ``(A, B)``: A the SPD test matrix, B an (n, nrhs)
    standard normal block, all made on the device in one jitted call.
    The key is an argument, so every seed runs one compiled program."""
    import jax

    def make(k):
        out = []
        for i in range(count):
            ka, kb = jax.random.split(jax.random.fold_in(k, i))
            out.append((spd(ka, n), jax.random.normal(kb, (n, nrhs))))
        return tuple(out)

    return jax.block_until_ready(jax.jit(make)(key(seed, _MATRIX)))


def serve_pool(seed: int, n: int, pool: int):
    """One shared SPD matrix and ``pool`` right-hand sides, each its own
    (n,) array, made on the device in one jitted call from the seed's
    keys."""
    import jax

    def make(ka, kb):
        bs = jax.random.normal(kb, (pool, n))
        return spd(ka, n), tuple(bs[i] for i in range(pool))

    return jax.block_until_ready(
        jax.jit(make)(key(seed, _MATRIX), key(seed, _RHS)))


def _balanced(values, blocks: int, rng) -> np.ndarray:
    """``blocks`` runs of ``values``, each a permutation drawn from
    ``rng``, one after another."""
    values = np.asarray(values)
    return np.concatenate([rng.permutation(values) for _ in range(blocks)])


def closed_loop(seed: int, callers: int, per_caller: int, targets,
                pool: int):
    """Each caller's sequence of requests: ``(digits, rhs)`` arrays of
    shape (callers, per_caller). A caller's targets come in runs of
    ``len(targets)`` that each hold every target once."""
    rng = np.random.default_rng([seed, _ORDER])
    blocks = -(-per_caller // len(targets))
    digits = np.stack([_balanced(targets, blocks, rng)[:per_caller]
                       for _ in range(callers)])
    rhs = rng.integers(0, pool, size=(callers, per_caller))
    return digits, rhs


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by the nearest-rank rule: the smallest
    value with at least ``q`` percent of the values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    return float(v[max(0, math.ceil(q / 100.0 * v.size) - 1)])
