"""The paper's SPD test matrix made block-row-sharded on the devices of
a mesh, from ``--seed``, never whole on one device.

``harness.gen.spd`` draws the whole (n, n) uniform matrix on one device;
at n = 65536 that is 16 GiB, all of one v5e's memory. Here each device
makes its own row block, tile by tile: tile (I, J) of A, a (TILE, TILE)
block, is

    (U(key(I, J)) + U(key(J, I))^T) / 2 + n * delta_IJ * I

with U(k) a uniform [-1, 1] draw from key k. That is gen.spd's
distribution (every off-diagonal entry the mean of two independent
uniforms, every diagonal entry a uniform plus n), and A is exactly
symmetric: tile (J, I) is tile (I, J) transposed, bit for bit. The
matrix depends on the seed and n only, not on the number of devices.
"""
from __future__ import annotations

from harness import gen

#: side of a generator tile: the leaf of every configuration, so it
#: divides any row block the solver takes
TILE = 256


def _tile(k, i, j):
    import jax
    import jax.numpy as jnp
    return jax.random.uniform(jax.random.fold_in(jax.random.fold_in(k, i), j),
                              (TILE, TILE), jnp.float32, -1.0, 1.0)


def _row_block(k, n: int, first, rows: int, axis: str | None = None):
    """Rows [first * TILE, first * TILE + rows) of A: a (rows, n) block
    built one column of tiles at a time, in place (``axis``: the mesh
    axis the block varies over, inside ``shard_map``)."""
    import jax
    import jax.numpy as jnp
    rt = rows // TILE
    tiles_i = first + jnp.arange(rt)
    eye = n * jnp.eye(TILE, dtype=jnp.float32)

    def column(jt, out):
        u = jax.vmap(lambda i: _tile(k, i, jt))(tiles_i)
        v = jax.vmap(lambda i: _tile(k, jt, i))(tiles_i)
        blk = (u + jnp.swapaxes(v, 1, 2)) * 0.5
        blk = blk + jnp.where((tiles_i == jt)[:, None, None], eye, 0.0)
        return jax.lax.dynamic_update_slice(
            out, blk.reshape(rows, TILE), (0, jt * TILE))

    out = jnp.zeros((rows, n), jnp.float32)
    if axis is not None:
        out = jax.lax.pcast(out, (axis,), to="varying")
    return jax.lax.fori_loop(0, n // TILE, column, out)


def spd(seed: int, n: int, mesh, axis: str = "model"):
    """A (n, n) float32, rows sharded over ``axis`` of ``mesh``; each
    device makes only its own rows."""
    import jax
    from jax.sharding import PartitionSpec as P
    nshards = mesh.shape[axis]
    rows = n // nshards
    assert rows % TILE == 0, (n, nshards, TILE)

    def local(k):
        my = jax.lax.axis_index(axis)
        return _row_block(k, n, my * (rows // TILE), rows, axis)

    make = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(),
                                 out_specs=P(axis, None)))
    return jax.block_until_ready(make(gen.key(seed, gen._MATRIX)))


def spd_whole(seed: int, n: int):
    """The same matrix assembled on one device (the tests' check of
    :func:`spd`)."""
    import jax
    return jax.jit(lambda k: _row_block(k, n, 0, n))(
        gen.key(seed, gen._MATRIX))


def rhs(seed: int, n: int, nrhs: int, mesh, axis: str = "model"):
    """B, (n, nrhs) standard normal from the seed, rows sharded like A."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    make = jax.jit(lambda k: jax.random.normal(k, (n, nrhs)),
                   out_shardings=NamedSharding(mesh, P(axis, None)))
    return jax.block_until_ready(make(gen.key(seed, gen._RHS)))
