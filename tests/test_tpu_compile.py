"""Compile the main path's Pallas kernels for a TPU v5e, without the chip.

The TPU compiler is installed with jax, and compiles for a described
``v5e:2x2`` topology while the tests keep ``JAX_PLATFORMS=cpu``. This
catches what interpret mode cannot: primitives Mosaic does not lower,
casts it cannot pack, and ``pallas_call`` outputs without a ``vma``
under ``shard_map``. Nothing runs; each case checks that the compiled
program holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture (never while the
module is imported): only one process at a time may load the TPU
library, and pytest-xdist workers all import every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.plan import build_plan
from repro.core.precision import PAPER_CONFIGS
from repro.kernels import panel, potrf, qgemm, residual, trsm

LEAF = 256
M = 4096


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back here: keep the cache out of it
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_has_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _panel_fn(ladder):
    cfg = PAPER_CONFIGS[ladder]
    meta = build_plan(M + cfg.leaf, cfg).panel_meta(0)

    def fn(linv, a21, c):
        return panel.panel_update(
            linv, a21, c, store_names=meta.store_names,
            store_quants=meta.store_quants, pair_names=meta.pair_names,
            pair_quants=meta.pair_quants)
    return fn


@pytest.mark.parametrize("kernel", ["potrf_leaf", "tri_inv_leaf"])
def test_leaf_kernels_compile(one_chip, kernel):
    a = jax.ShapeDtypeStruct((LEAF, LEAF), jnp.float32, sharding=one_chip)
    _compile_has_kernel(getattr(potrf, kernel), a)


def test_trsm_leaf_compiles(one_chip):
    b = jax.ShapeDtypeStruct((M, LEAF), jnp.float32, sharding=one_chip)
    linv = jax.ShapeDtypeStruct((LEAF, LEAF), jnp.float32, sharding=one_chip)
    _compile_has_kernel(lambda b, linv: trsm.trsm_leaf(b, linv=linv),
                        b, linv)


@pytest.mark.parametrize("ladder", ["bf16_f32", "int8_f32", "f16_f32"])
def test_panel_update_compiles(one_chip, ladder):
    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    _compile_has_kernel(_panel_fn(ladder), s((LEAF, LEAF)), s((M, LEAF)),
                        s((M, M)))


def test_residual_fused_compiles(one_chip):
    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    _compile_has_kernel(residual.residual_fused, s((M, M)), s((M, 8)),
                        s((M, 8)))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8, jnp.float32])
def test_qgemm_compiles(one_chip, dtype):
    x = jax.ShapeDtypeStruct((M, M), dtype, sharding=one_chip)
    _compile_has_kernel(lambda a, b: qgemm.qgemm(a, b, 1.0), x, x)


def test_sharded_panel_update_compiles(topo):
    """The distributed path runs the panel kernel inside ``shard_map``
    (``check_vma=True``): its outputs must carry their ``vma``."""
    mesh = Mesh(topo.devices[:4], ("model",))
    fn = jax.shard_map(_panel_fn("bf16_f32"), mesh=mesh,
                       in_specs=(P(), P("model"), P("model")),
                       out_specs=(P("model"), P("model")))

    def s(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=NamedSharding(mesh, spec))
    _compile_has_kernel(fn, s((LEAF, LEAF), P()), s((4 * M, LEAF), P("model")),
                        s((4 * M, M), P("model")))


def _kernel_calls(fn, *args):
    """Names of the compiled program's Pallas custom-calls, without the
    number XLA appends."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return {re.sub(r"\.\d+$", "", m.group(1)) for m in re.finditer(
        r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)}


@pytest.mark.parametrize("kernel", ["panel_update", "potrf_leaf",
                                    "tri_inv_leaf", "residual_fused",
                                    "qgemm"])
def test_kernel_keeps_the_name_the_benchmark_reads(one_chip, kernel):
    """The benchmark finds each kernel in a device trace by its custom-
    call's name (``bench/metrics``: ``factor.kernel_ms``,
    ``panel_update_roofline``, ``residual_fused_roofline``): a renamed
    kernel would read ``null`` there, so the names are pinned."""
    def s(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    calls = {
        "panel_update": lambda: (_panel_fn("bf16_f32"), s((LEAF, LEAF)),
                                 s((M, LEAF)), s((M, M))),
        "potrf_leaf": lambda: (potrf.potrf_leaf, s((LEAF, LEAF))),
        "tri_inv_leaf": lambda: (potrf.tri_inv_leaf, s((LEAF, LEAF))),
        "residual_fused": lambda: (residual.residual_fused, s((M, M)),
                                   s((M, 8)), s((M, 8))),
        "qgemm": lambda: (lambda a, b: qgemm.qgemm(a, b, 1.0),
                          s((LEAF, LEAF)), s((LEAF, LEAF))),
    }
    fn, *args = calls[kernel]()

    def program(*xs):          # a caller of another name around it
        return fn(*xs)
    assert _kernel_calls(program, *args) == {kernel}
