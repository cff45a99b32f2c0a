"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
sweeping shapes and dtypes (assignment deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _rand(shape, dtype=np.float32):
    return RNG.standard_normal(shape).astype(dtype)


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 128),
                                   (300, 200, 180), (64, 1000, 72)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_qgemm_shapes_dtypes(m, k, n, dtype):
    a = jnp.asarray(_rand((m, k)), dtype)
    b = jnp.asarray(_rand((k, n)), dtype)
    got = ops.qgemm(a, b, 1.7, impl="interpret")
    want = ref.qgemm_ref(a, b, scale=1.7)
    # k-chunked accumulation order differs from the single-dot oracle
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("trans_b", [False, True])
@pytest.mark.parametrize("beta", [0.0, 1.0, -0.5])
def test_qgemm_epilogue(trans_b, beta):
    a = jnp.asarray(_rand((192, 160)), jnp.bfloat16)
    b_shape = (96, 160) if trans_b else (160, 96)
    b = jnp.asarray(_rand(b_shape), jnp.bfloat16)
    c = _rand((192, 96))
    got = ops.qgemm(a, b, 0.3, c=c, beta=beta, trans_b=trans_b,
                    impl="interpret")
    want = ref.qgemm_ref(a, b, trans_b=trans_b, scale=0.3, c=c, beta=beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,k", [(128, 1), (256, 4), (300, 3), (129, 130),
                                 (512, 8)])
def test_residual_fused(n, k):
    a = _rand((n, n))
    x = _rand((n, k))
    b = _rand((n, k))
    got = ops.residual(a, x, b, impl="interpret")
    want = ref.residual_ref(a, x, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-3)


@pytest.mark.parametrize("m,n,k", [(256, 1024, 8), (100, 300, 3)])
def test_residual_fused_row_block(m, n, k):
    """A row block of the system (each device's rows on the distributed
    path) against the whole x."""
    a = _rand((m, n))
    x = _rand((n, k))
    b = _rand((m, k))
    got = ops.residual(a, x, b, impl="interpret")
    want = ref.residual_ref(a, x, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-3)


def test_residual_fused_vector():
    n = 200
    a = _rand((n, n))
    x = _rand((n,))
    b = _rand((n,))
    got = ops.residual(a, x, b, impl="interpret")
    assert got.shape == (n,)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.residual_ref(a, x, b)),
                               rtol=2e-4, atol=2e-3)


def _panel_meta(levels, nt, panel=0):
    from repro.core.plan import build_plan
    from repro.core.precision import PrecisionConfig
    cfg = PrecisionConfig(levels=levels, leaf=128)
    return build_plan((nt + 1 + panel) * 128, cfg).panel_meta(panel)


def _panel_operands(nt, b=128, seed=3):
    rng = np.random.default_rng(seed)
    linv = np.tril(rng.standard_normal((b, b)).astype(np.float32))
    linv[np.diag_indices(b)] += 3.0
    a21 = rng.standard_normal((nt * b, b)).astype(np.float32)
    c = rng.standard_normal((nt * b, nt * b)).astype(np.float32)
    return jnp.asarray(linv), jnp.asarray(a21), jnp.asarray(c)


@pytest.mark.parametrize("levels,nt", [
    (("f32",), 2), (("f16", "f32"), 3), (("f16", "f16", "f32"), 4),
    (("bf16", "f32"), 2), (("int8", "f32"), 3)])
def test_panel_update_fused(levels, nt):
    """Fused panel kernel (interpret) is bit-identical to the oracle
    across ladder mixes: same per-tile rounding, same update tiling."""
    meta = _panel_meta(levels, nt)
    linv, a21, c = _panel_operands(nt)
    kw = dict(store_names=meta.store_names, store_quants=meta.store_quants,
              pair_names=meta.pair_names, pair_quants=meta.pair_quants)
    l21r, cr = ref.panel_update_ref(linv, a21, c, **kw)
    l21k, ck = ops.panel_update(linv, a21, c, impl="interpret", **kw)
    np.testing.assert_array_equal(np.asarray(l21r), np.asarray(l21k))
    np.testing.assert_array_equal(np.asarray(cr), np.asarray(ck))


def test_panel_update_no_rounding():
    """storage_rounding=False: raw f32 trsm + syrk, upper c preserved."""
    meta = _panel_meta(("f16", "f32"), 3)
    linv, a21, c = _panel_operands(3)
    kw = dict(store_names=meta.store_names, store_quants=meta.store_quants,
              pair_names=meta.pair_names, pair_quants=meta.pair_quants,
              rounding=False)
    l21, cu = ops.panel_update(linv, a21, c, impl="interpret", **kw)
    l21r, cur = ref.panel_update_ref(linv, a21, c, **kw)
    np.testing.assert_array_equal(np.asarray(l21), np.asarray(l21r))
    np.testing.assert_array_equal(np.asarray(cu), np.asarray(cur))
    # strictly-upper tiles of c pass through untouched
    got = np.asarray(cu)
    want = np.asarray(c)
    b = 128
    for i in range(3):
        for j in range(i + 1, 3):
            np.testing.assert_array_equal(
                got[i * b:(i + 1) * b, j * b:(j + 1) * b],
                want[i * b:(i + 1) * b, j * b:(j + 1) * b])


def test_trsm_leaf_accepts_precomputed_linv():
    """Satellite: trsm_leaf(linv=) skips the leaf inversion and matches
    the from-scratch call bitwise (jnp dispatch path included)."""
    from repro.kernels import potrf as _potrf
    from repro.kernels import trsm as _trsm
    rng = np.random.default_rng(9)
    l = np.tril(rng.standard_normal((128, 128)).astype(np.float32))
    l[np.diag_indices(128)] += 8.0
    b = rng.standard_normal((300, 128)).astype(np.float32)
    x1 = _trsm.trsm_leaf(jnp.asarray(b), jnp.asarray(l), interpret=True)
    linv = _potrf.tri_inv_leaf(jnp.asarray(l), interpret=True)
    x2 = _trsm.trsm_leaf(jnp.asarray(b), linv=linv, interpret=True)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x2))
    # ops-level jnp dispatch with a provided linv turns into one GEMM
    x3 = ops.trsm(jnp.asarray(b), jnp.asarray(l), linv=linv, impl="jnp")
    np.testing.assert_allclose(np.asarray(x3), np.asarray(x1),
                               rtol=1e-4, atol=1e-4)


def test_residual_f64_routes_to_oracle():
    """f64 residuals (the x64 accuracy path) must bypass the fused
    kernel's f32 accumulator bit-for-bit."""
    with jax.enable_x64(True):
        n = 96
        a = jnp.asarray(RNG.standard_normal((n, n)))
        x = jnp.asarray(RNG.standard_normal(n))
        b = jnp.asarray(RNG.standard_normal(n))
        got = ops.residual(a, x, b, impl="interpret")
        assert got.dtype == jnp.float64
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(ref.residual_ref(a, x, b)))


@pytest.mark.parametrize("n", [128, 256, 384, 512])
def test_potrf_leaf(n):
    m = _rand((n, n))
    a = m @ m.T + n * np.eye(n, dtype=np.float32)
    got = ops.potrf(a, impl="interpret")
    want = ref.potrf_ref(a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=3e-4, atol=3e-4 * n)


@pytest.mark.parametrize("n", [128, 256, 512])
def test_tri_inv_leaf(n):
    l = np.tril(_rand((n, n))) + np.sqrt(n) * 4 * np.eye(n,
                                                         dtype=np.float32)
    got = ops.tri_inv(l, impl="interpret")
    want = ref.tri_inv_ref(l)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("m,n", [(128, 128), (700, 256), (1024, 128),
                                 (65, 384)])
def test_trsm_leaf(m, n):
    l = np.tril(_rand((n, n))) + 4 * np.sqrt(n) * np.eye(n,
                                                         dtype=np.float32)
    b = _rand((m, n))
    got = ops.trsm(b, l, impl="interpret")
    want = ref.trsm_ref(b, l)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("side,trans", [("left", False), ("left", True)])
def test_trsm_left_forms(side, trans):
    n, m = 256, 192
    l = np.tril(_rand((n, n))) + 4 * np.sqrt(n) * np.eye(n,
                                                         dtype=np.float32)
    b = _rand((n, m))
    got = ops.trsm(b, l, side=side, trans=trans, impl="interpret")
    want = ref.trsm_ref(b, l, side=side, trans=trans)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("n,k", [(128, 128), (256, 1000), (256, 64)])
@pytest.mark.parametrize("scale,beta", [(1.0, 1.0), (0.5, -0.25)])
def test_syrk_leaf(n, k, scale, beta):
    c = _rand((n, n))
    a = _rand((n, k))
    got = ops.syrk(c, a, scale, beta, impl="interpret")
    want = ref.syrk_ref(c, a, scale=scale, beta=beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("n,k", [(512, 256), (640, 300), (500, 513)])
def test_syrk_packed(n, k):
    c = _rand((n, n))
    a = _rand((n, k))
    got = ops.syrk(c, a, 0.7, 0.9, packed=True, impl="interpret")
    want = ref.syrk_ref(c, a, scale=0.7, beta=0.9)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_syrk_packed_preserves_upper():
    n, k = 256, 128
    c = _rand((n, n))
    a = _rand((n, k))
    got = np.asarray(ops.syrk(c, a, 1.0, 1.0, packed=True,
                              impl="interpret"))
    iu = np.triu_indices(n, 1)
    np.testing.assert_allclose(got[iu], c[iu], rtol=1e-6)


def test_tri_decode_exact():
    """Triangular index decode must be exact over a large range."""
    from repro.kernels.syrk import _tri_decode
    t = jnp.arange(0, 200000, dtype=jnp.int32)
    i, j = jax.jit(_tri_decode)(t)
    i, j = np.asarray(i), np.asarray(j)
    # reconstruct and compare
    np.testing.assert_array_equal(i * (i + 1) // 2 + j, np.arange(200000))
    assert (j <= i).all()
