"""Training-substrate integration tests: loss goes down with both
optimizers, grad-accum invariance, checkpoint round-trip + elastic
restore, and the int8 error-feedback data-parallel trainer."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.launch.mesh import make_mesh
from repro.data import SyntheticLM
from repro.models.common import ModelConfig
from repro.optim import AdamWConfig, TreeNewtonConfig
from repro.train import (TrainConfig, compress, init_state, make_train_step,
                         reshape_for_accum)

CFG = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                  d_ff=128, vocab=128, n_heads=4, n_kv=2, mlp="swiglu",
                  max_seq=64, remat=False)


def _run(tcfg, steps=30, seed=0):
    data = SyntheticLM(CFG.vocab, batch=8, seq=32, seed=seed)
    state = init_state(jax.random.PRNGKey(seed), CFG, tcfg)
    step = jax.jit(make_train_step(CFG, tcfg))
    losses = []
    for i in range(steps):
        batch = jax.tree.map(jnp.asarray, data.get(i))
        batch = reshape_for_accum(batch, tcfg.accum)
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    return losses, state


def test_adamw_loss_decreases():
    adam = AdamWConfig(lr=1e-2, warmup=5, total_steps=100)
    losses, _ = _run(TrainConfig(optimizer="adamw", adam=adam))
    assert losses[-1] < losses[0] - 0.5, losses[::10]
    assert np.isfinite(losses).all()


def test_tree_newton_loss_decreases():
    adam = AdamWConfig(lr=1e-2, warmup=5, total_steps=100)
    tn = TreeNewtonConfig(adam=adam, block=64, factor_every=5,
                          stats_every=1)
    losses, _ = _run(TrainConfig(optimizer="tree_newton", tree_newton=tn))
    assert losses[-1] < losses[0] - 0.5, losses[::10]
    assert np.isfinite(losses).all()


def test_tree_newton_not_worse_than_adam():
    """The paper-solver optimizer should at least match AdamW here."""
    adam = AdamWConfig(lr=1e-2, warmup=5, total_steps=100)
    la, _ = _run(TrainConfig(optimizer="adamw", adam=adam), steps=40)
    tn = TreeNewtonConfig(adam=adam, block=64, factor_every=5)
    lt, _ = _run(TrainConfig(optimizer="tree_newton", tree_newton=tn),
                 steps=40)
    assert np.mean(lt[-5:]) <= np.mean(la[-5:]) + 0.25


def test_grad_accum_equivalence():
    """accum=2 must match accum=1 on the same global batch (modulo f32
    reduction order)."""
    adam = AdamWConfig(lr=1e-3, warmup=0, total_steps=100)
    t1 = TrainConfig(optimizer="adamw", adam=adam, accum=1)
    t2 = TrainConfig(optimizer="adamw", adam=adam, accum=2)
    data = SyntheticLM(CFG.vocab, batch=8, seq=32, seed=3)
    batch = jax.tree.map(jnp.asarray, data.get(0))
    s1 = init_state(jax.random.PRNGKey(0), CFG, t1)
    s2 = init_state(jax.random.PRNGKey(0), CFG, t2)
    s1, m1 = jax.jit(make_train_step(CFG, t1))(s1, batch)
    s2, m2 = jax.jit(make_train_step(CFG, t2))(
        s2, reshape_for_accum(batch, 2))
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
    d = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree.leaves(s1["params"]), jax.tree.leaves(s2["params"])))
    assert d < 1e-5, d


def test_checkpoint_roundtrip_and_resume(tmp_path):
    adam = AdamWConfig(lr=1e-2, warmup=0, total_steps=100)
    tcfg = TrainConfig(optimizer="adamw", adam=adam)
    data = SyntheticLM(CFG.vocab, batch=8, seq=32, seed=1)
    step = jax.jit(make_train_step(CFG, tcfg))

    state = init_state(jax.random.PRNGKey(1), CFG, tcfg)
    for i in range(5):
        state, _ = step(state, jax.tree.map(jnp.asarray, data.get(i)))
    h = ckpt.save(str(tmp_path), 5, state, blocking=True)
    h.wait()

    # continue 5 more steps from live state
    live = state
    for i in range(5, 10):
        live, ml = step(live, jax.tree.map(jnp.asarray, data.get(i)))

    # restore and replay the same steps — deterministic pipeline =>
    # identical result
    restored, s0 = ckpt.restore(str(tmp_path), state)
    assert s0 == 5
    for i in range(5, 10):
        restored, mr = step(restored,
                            jax.tree.map(jnp.asarray, data.get(i)))
    assert abs(float(ml["loss"]) - float(mr["loss"])) < 1e-5
    d = max(float(jnp.abs(a - b).max()) for a, b in zip(
        jax.tree.leaves(live["params"]), jax.tree.leaves(restored["params"])))
    assert d < 1e-5


def test_checkpoint_keep_last(tmp_path):
    state = {"x": jnp.arange(4.0)}
    for s in range(6):
        ckpt.save(str(tmp_path), s, state, keep_last=2, blocking=True)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(steps) == 2 and steps[-1] == "step_00000005"
    assert ckpt.latest_step(str(tmp_path)) == 5


def test_ef_compression_dp_trainer():
    """Mini data-parallel trainer with int8+EF gradient all-reduce on 8
    host devices: converges like the uncompressed baseline."""
    if jax.device_count() < 8:
        pytest.skip("needs --xla_force_host_platform_device_count=8 "
                    "(run via tests/conftest multi-device session)")
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh((8,), ("dp",))
    rng = np.random.default_rng(0)
    w_true = rng.standard_normal((16, 4)).astype(np.float32)
    X = rng.standard_normal((64, 16)).astype(np.float32)
    Y = X @ w_true

    def local_step(w, res, x, y, lr):
        res = res[0]                    # [1,16,4] local shard -> [16,4]
        def loss(w):
            return jnp.mean((x @ w - y) ** 2)
        g = jax.grad(loss)(w)
        g, res = compress.ef_allreduce_mean({"w": g}, {"w": res}, "dp")
        return w - lr * g["w"], res["w"][None]

    fn = jax.jit(jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(P(), P("dp"), P("dp"), P("dp"), P()),
        out_specs=(P(), P("dp"))))
    w = jnp.zeros((16, 4))
    res = jnp.zeros((8, 16, 4))         # per-replica EF residual
    lr = jnp.float32(0.05)
    for _ in range(600):    # int8 EF noise slows early progress; err at
        w, res = fn(w, res, X, Y, lr)   # 600 steps is ~9e-3, margin 5x
    err = float(jnp.abs(w - w_true).max())
    assert err < 5e-2, err


def test_kfac_refactor_engines_agree():
    """Satellite of the tuner PR: the K-FAC factor stack now vmaps the
    blocked engine by default — its factors must match the tree path on
    the same damped stats (shared bf16 ladder, so agreement is tight),
    and engine="auto" must produce one of the two."""
    import dataclasses

    from repro.optim import kfac

    cfg = kfac.TreeNewtonConfig()           # block=512, bf16_f32, leaf 128
    rng = np.random.default_rng(0)
    n = cfg.block
    m = rng.uniform(-1, 1, (3, n, n))
    a = (m + m.transpose(0, 2, 1)) / 2
    idx = np.diag_indices(n)
    a[:, idx[0], idx[1]] += n
    a = jnp.asarray(a, jnp.float32)

    def with_engine(eng):
        p = dataclasses.replace(cfg.precision, engine=eng)
        return np.asarray(kfac._refactor(a, dataclasses.replace(
            cfg, precision=p)), np.float64)

    l_blocked = with_engine("blocked")
    l_tree = with_engine("tree")
    scale = np.abs(l_tree).max()
    assert np.abs(l_blocked - l_tree).max() / scale < 1e-4
    l_auto = with_engine("auto")
    assert (np.array_equal(l_auto, l_blocked)
            or np.array_equal(l_auto, l_tree))
    # both reconstruct the damped stats to bf16-ladder accuracy
    damped = np.asarray(kfac._damped(a, cfg), np.float64)
    rec = np.einsum("bij,bkj->bik", l_blocked, l_blocked)
    assert np.abs(rec - damped).max() / np.abs(damped).max() < 4e-2

    # blocks smaller than the leaf stay on the tree base case
    small_cfg = dataclasses.replace(cfg, block=64)
    l_small = np.asarray(kfac._refactor(jnp.asarray(a[:, :64, :64]),
                                        small_cfg), np.float64)
    assert np.isfinite(l_small).all()
