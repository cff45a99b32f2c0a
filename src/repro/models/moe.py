"""Mixture-of-Experts FFN (DeepSeek style: shared + routed, top-k).

Expert parallelism: experts are sharded over the ``model`` mesh axis.
Each (data, model) device routes its *local* tokens, keeps only the
assignments that hit its local experts (sorted by local expert id into a
static-capacity buffer), runs the expert FFNs as a grouped GEMM with
``jax.lax.ragged_dot`` (TPU MegaBlocks analogue), scatters back weighted
by the gates, and psums the partial outputs over the model axis. No
all-to-all, no [tokens, experts, capacity] dispatch tensor.

Capacity: C = ceil(T * topk / EP * capacity_factor); overflow tokens are
dropped (standard GShard semantics) — ragged_dot zero-fills rows past the
group sums so drops are exact zeros, and the shared experts (always
dense) keep every token covered.

On a laptop (no mesh) the same code runs with EP=1, which makes it an
exact dropless reference when capacity_factor covers all assignments —
tests exploit this against a dense per-expert loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.models.common import ModelConfig, Sharder, _init


def moe_params(rng, cfg: ModelConfig):
    d, E, F = cfg.d_model, cfg.moe_experts, cfg.moe_dff or cfg.d_ff
    ks = jax.random.split(rng, 5)
    p = {
        "router": _init(ks[0], (d, E), jnp.float32),      # router in f32
        "w_in": _init(ks[1], (E, d, F), cfg.pdt),
        "w_gate": _init(ks[2], (E, d, F), cfg.pdt),
        "w_out": _init(ks[3], (E, F, d), cfg.pdt),
    }
    if cfg.moe_shared:
        Fs = F * cfg.moe_shared
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {"w_in": _init(k1, (d, Fs), cfg.pdt),
                       "w_gate": _init(k2, (d, Fs), cfg.pdt),
                       "w_out": _init(k3, (Fs, d), cfg.pdt)}
    return p


def _expert_ffn_local(x_rows, w_in, w_gate, w_out, group_sizes):
    """Grouped SwiGLU over sorted rows: ragged_dot per expert group."""
    h = jax.lax.ragged_dot(x_rows, w_in, group_sizes)
    g = jax.lax.ragged_dot(x_rows, w_gate, group_sizes)
    h = jax.nn.silu(g) * h
    return jax.lax.ragged_dot(h.astype(x_rows.dtype), w_out, group_sizes)


def _moe_local(x, router_w, w_in, w_gate, w_out, *, cfg: ModelConfig,
               ep: int, axis: str | None, all_axes: tuple = ()):
    """Per-device MoE. x: [B_loc, S, D]; expert weights are the local
    shard [E/ep, D, F]. Returns the *partial* output (psum over axis).
    ``all_axes``: every mesh axis name — the scalar aux loss is pmean'd
    over all of them so its out_spec can be fully replicated."""
    B, S, D = x.shape
    T = B * S
    K = cfg.moe_topk
    E = cfg.moe_experts
    e_loc = E // ep
    my = jax.lax.axis_index(axis) if axis else 0

    xf = x.reshape(T, D)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                   # [T, K]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    flat_e = idx.reshape(T * K)
    flat_g = gate.reshape(T * K)
    flat_t = jnp.repeat(jnp.arange(T), K)

    local = (flat_e // e_loc) == my
    key = jnp.where(local, flat_e % e_loc, e_loc)         # non-local last
    order = jnp.argsort(key)

    cap = int(-(-T * K // ep) * cfg.moe_capacity_factor)
    cap = max(min(cap, T * K), 1)
    sel = order[:cap]
    sel_key = key[sel]                                    # sorted ascending
    rows = xf[flat_t[sel]]                                # [cap, D]
    counts = jnp.bincount(jnp.where(sel_key < e_loc, sel_key, e_loc),
                          length=e_loc + 1)[:e_loc]
    out_rows = _expert_ffn_local(rows, w_in, w_gate, w_out,
                                 counts.astype(jnp.int32))
    # rows beyond sum(counts) are zero (ragged_dot) => exact drop
    weighted = out_rows * flat_g[sel][:, None].astype(out_rows.dtype)
    out = jnp.zeros((T, D), out_rows.dtype).at[flat_t[sel]].add(weighted)
    if axis:
        out = jax.lax.psum(out, axis)
    # router aux (load-balance) loss terms, averaged later
    me = probs.mean(axis=0)                               # [E]
    ce = jnp.zeros((E,), jnp.float32).at[flat_e].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)
    if all_axes:
        # aux varies over the batch axes but is invarying over 'model'
        # (x is replicated there); promote the missing axes, then mean
        # over everything so the out_spec can be fully replicated.
        have = compat.vma_of(aux)
        missing = tuple(a for a in all_axes if a not in have)
        if missing:
            aux = jax.lax.pcast(aux, missing, to="varying")
        aux = jax.lax.pmean(aux, all_axes)
    return out.reshape(B, S, D).astype(x.dtype), aux


def moe_ffn(x, p, cfg: ModelConfig, sharder: Sharder):
    """Full MoE block: routed experts (+ shared experts dense path)."""
    if sharder.enabled:
        mesh = sharder.mesh
        assert mesh is not None, "Sharder.mesh required for sharded MoE"
        ep = mesh.shape[sharder.model_axis]
        pspec_x = P(sharder.batch_axes, None, None)
        fn = functools.partial(_moe_local, cfg=cfg, ep=ep,
                               axis=sharder.model_axis,
                               all_axes=tuple(mesh.axis_names))
        routed, aux = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(pspec_x, P(None, None),
                      P(sharder.model_axis, None, None),
                      P(sharder.model_axis, None, None),
                      P(sharder.model_axis, None, None)),
            out_specs=(pspec_x, P()),
        )(x, p["router"], p["w_in"], p["w_gate"], p["w_out"])
    else:
        routed, aux = _moe_local(x, p["router"], p["w_in"], p["w_gate"],
                                 p["w_out"], cfg=cfg, ep=1, axis=None)
    if cfg.moe_shared:
        sp = p["shared"]
        h = jnp.einsum("bsd,df->bsf", x, sp["w_in"])
        g = jnp.einsum("bsd,df->bsf", x, sp["w_gate"])
        h = jax.nn.silu(g).astype(h.dtype) * h
        h = sharder.act_ffn(h)
        routed = routed + jnp.einsum("bsf,fd->bsd", h, sp["w_out"])
    return routed, aux


def moe_ffn_dense_reference(x, p, cfg: ModelConfig):
    """O(E)-cost dropless reference (tests only): every expert computes
    every token densely; outputs combined with the top-k gates."""
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, cfg.moe_topk)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    h = jnp.einsum("td,edf->tef", xf, p["w_in"])
    g = jnp.einsum("td,edf->tef", xf, p["w_gate"])
    h = jax.nn.silu(g) * h
    y = jnp.einsum("tef,efd->ted", h.astype(xf.dtype), p["w_out"])
    mask = jax.nn.one_hot(idx, cfg.moe_experts, dtype=jnp.float32)  # [T,K,E]
    w = jnp.einsum("tk,tke->te", gate, mask)
    out = jnp.einsum("te,ted->td", w.astype(y.dtype), y)
    return out.reshape(B, S, D)
