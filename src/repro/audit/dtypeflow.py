"""Jaxpr dtype-flow analysis: recover *value* precision from f32 graphs.

The CPU execution path (``ops.resolve_impl`` -> the jnp oracles) keeps
every array in its f32/f64 container and applies the ladder's precision
as VALUE-level rounding: ``storage_round`` / ``_round_tiles`` cast
through the narrow dtype (``x.astype(f16).astype(f32)``) or, for int8,
round against a per-block scale. A naive dtype census of such a jaxpr
therefore sees only f32xf32 dots. This walker recovers the effective
precision by propagating a *precision tag* along def-use chains:

* ``convert_element_type`` to a strictly narrower dtype tags the value
  with that dtype (the rounding event); converting back up keeps the tag.
* ``round_nearest_even`` (the jnp.round in int8 quantization) tags int8.
* pure data movement (slice/reshape/transpose/broadcast/concatenate/
  gather/squeeze/rev/copy/pad) joins operand tags (coarsest wins).
* ``mul``/``div``/``max``/``min`` where one operand has strictly fewer
  elements than the other (a broadcast quantization scale or clip bound)
  preserves the big operand's tag — this is what keeps the per-block
  scale multiply in ``_round_tiles`` from washing out the tag.
* every other computation produces a wide (container-precision) value.

The effective precision of a ``dot_general`` is then the coarsest
effective precision among its operands — exactly the number the
:class:`~repro.core.plan.PrecisionPlan` assigns per tile, which
:mod:`repro.audit.conformance` reconciles.

The walker also recurses through ``pjit``/``scan``/``while``/``cond``/
``shard_map`` call primitives (both ClosedJaxpr and raw Jaxpr params —
shard_map carries a raw Jaxpr) and records collective sites with their
wire dtype for the distributed conformance check.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.dtypes import BYTES

#: precision tags the walker tracks (ladder names). f8 variants ride
#: along so a future f8 ladder audits without touching the walker.
_TAGGABLE = ("int8", "f8e4m3", "f8e5m2", "f16", "bf16", "f32", "f64")

#: np dtype name -> ladder name
_NP_TO_LADDER = {"int8": "int8", "float16": "f16", "bfloat16": "bf16",
                 "float32": "f32", "float64": "f64",
                 "float8_e4m3fn": "f8e4m3", "float8_e5m2": "f8e5m2"}

#: primitives that move data without changing values: tag passes through
_PASSTHROUGH = {
    "slice", "dynamic_slice", "dynamic_update_slice", "squeeze", "reshape",
    "transpose", "broadcast_in_dim", "concatenate", "rev", "copy", "gather",
    "scatter", "pad", "select_n", "stop_gradient", "expand_dims",
}

#: elementwise ops where a broadcast small operand (quant scale / clip
#: bound) must not wash out the big operand's tag
_SCALE_OPS = {"mul", "div", "max", "min", "clamp"}

#: call primitives whose params carry sub-jaxprs to recurse into
_CALL_PRIMS = {"pjit", "closed_call", "core_call", "custom_jvp_call",
               "custom_vjp_call", "custom_vjp_call_jaxpr", "remat",
               "checkpoint", "scan", "while", "cond", "shard_map"}

_COLLECTIVE_PRIMS = {"all_gather", "psum", "psum2", "psum_invariant",
                     "ppermute", "all_to_all", "reduce_scatter",
                     "psum_scatter"}


def ladder_name(dtype) -> str:
    """Ladder name of a concrete np/jnp dtype (container alphabet)."""
    return _NP_TO_LADDER.get(np.dtype(dtype).name, np.dtype(dtype).name)


def _width(name: str) -> int:
    return BYTES.get(name, 8)


def coarsest(a: str, b: str) -> str:
    """The lower-precision of two ladder names (byte width, int8 lowest)."""
    if a == b:
        return a
    wa, wb = _width(a), _width(b)
    if wa != wb:
        return a if wa < wb else b
    # same width (f16 vs bf16): neither is finer; pick deterministically
    return min(a, b)


@dataclasses.dataclass
class DotSite:
    """One dot_general with effective operand precisions."""

    lhs_name: str           # effective precision of the lhs value
    rhs_name: str
    eff_name: str           # coarsest of the two = the GEMM's precision
    flops: float
    out_shape: tuple


@dataclasses.dataclass
class RoundEvent:
    """One value-rounding event (convert-to-narrower or int8 round)."""

    name: str               # target precision
    elems: int              # elements rounded
    prev: str | None        # tag the value carried before (double-round)


@dataclasses.dataclass
class CollectiveSite:
    """One collective with its wire dtype (container of the operand)."""

    prim: str               # all_gather / psum / ...
    wire: str               # np dtype name on the wire: uint16, int8, ...
    shape: tuple            # operand shape


@dataclasses.dataclass
class FlowResult:
    dots: list
    rounds: list
    collectives: list
    promotions: list        # (src_name, dst_name, elems) widening converts

    def dot_flops_by_name(self) -> dict:
        out: dict[str, float] = {}
        for d in self.dots:
            out[d.eff_name] = out.get(d.eff_name, 0.0) + d.flops
        return out

    def round_elems_by_name(self) -> dict:
        out: dict[str, int] = {}
        for r in self.rounds:
            out[r.name] = out.get(r.name, 0) + r.elems
        return out

    def double_rounds(self) -> list:
        """Incommensurate narrow->narrow re-rounds (f16<->bf16): a value
        already on one 16-bit grid re-rounded onto the other loses bits
        both ways; no ladder in PAPER_CONFIGS produces this chain."""
        bad = []
        for r in self.rounds:
            if r.prev and {r.prev, r.name} == {"f16", "bf16"}:
                bad.append(r)
        return bad


def _aval_elems(var) -> int:
    try:
        return int(np.prod(var.aval.shape, dtype=np.int64))
    except Exception:
        return 0


def _dot_flops(eqn) -> float:
    (contract, _), _ = eqn.params["dimension_numbers"]
    out = eqn.outvars[0].aval
    lhs = eqn.invars[0].aval
    k = 1
    for d in contract:
        k *= lhs.shape[d]
    return 2.0 * float(np.prod(out.shape, dtype=np.int64)) * k


class _Walker:
    def __init__(self):
        self.res = FlowResult([], [], [], [])

    # tags: dict var -> ladder name (only set when narrower than container)
    def walk(self, jaxpr, tags=None):
        tags = dict(tags or {})

        def tag_of(v):
            if hasattr(v, "val"):       # Literal
                return ladder_name(np.asarray(v.val).dtype)
            t = tags.get(v)
            if t is not None:
                return t
            return ladder_name(v.aval.dtype)

        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "convert_element_type":
                src_v = eqn.invars[0]
                src = tag_of(src_v)
                dst = ladder_name(eqn.params["new_dtype"])
                out = eqn.outvars[0]
                container = ladder_name(src_v.aval.dtype)
                floats = (np.issubdtype(np.dtype(src_v.aval.dtype),
                                        np.floating)
                          and dst in _TAGGABLE and dst != "int8")
                if floats and _width(dst) < _width(container):
                    # precision-losing float convert: a rounding event
                    prev = src if _width(src) <= 2 and src != dst else None
                    self.res.rounds.append(
                        RoundEvent(dst, _aval_elems(out), prev))
                    tags[out] = dst
                elif dst == "int8" and np.issubdtype(
                        np.dtype(src_v.aval.dtype), np.floating):
                    # float -> int8 container cast. The rounding already
                    # happened at the round prim (quant_int8); an astype
                    # of an int8-tagged value is the dequant chain, not a
                    # second round.
                    if src != "int8":
                        self.res.rounds.append(
                            RoundEvent("int8", _aval_elems(out), None))
                    tags[out] = "int8"
                elif floats and _width(dst) > _width(container):
                    # widening float convert: value keeps its tag
                    self.res.promotions.append(
                        (container, dst, _aval_elems(out)))
                    if src in _TAGGABLE and _width(src) < _width(dst):
                        tags[out] = src
                elif src in _TAGGABLE and _width(src) < _width(dst):
                    # int8 container widening back to float, and
                    # same-width converts: tag rides along
                    tags[out] = src
            elif prim == "round_nearest_even" or prim == "round":
                # jnp.round: only reached by int8 per-block quantization
                out = eqn.outvars[0]
                src = tag_of(eqn.invars[0])
                prev = src if _width(src) <= 2 else None
                self.res.rounds.append(
                    RoundEvent("int8", _aval_elems(out), prev))
                tags[out] = "int8"
            elif prim == "dot_general":
                ln = tag_of(eqn.invars[0])
                rn = tag_of(eqn.invars[1])
                self.res.dots.append(DotSite(
                    ln, rn, coarsest(ln, rn), _dot_flops(eqn),
                    tuple(eqn.outvars[0].aval.shape)))
            elif prim in _COLLECTIVE_PRIMS:
                op = eqn.invars[0]
                # jax names the multi-operand psum primitive "psum2",
                # and a psum under shard_map's vma checks "psum_invariant"
                base = "psum" if prim.startswith("psum") else prim
                self.res.collectives.append(CollectiveSite(
                    base, np.dtype(op.aval.dtype).name,
                    tuple(op.aval.shape)))
                for ov, iv in zip(eqn.outvars, eqn.invars):
                    t = tags.get(iv)
                    if t is not None:
                        tags[ov] = t
            elif prim in _PASSTHROUGH:
                tin = [tags[v] for v in eqn.invars
                       if not hasattr(v, "val") and v in tags]
                if tin and len(tin) == sum(
                        1 for v in eqn.invars
                        if not hasattr(v, "val")
                        and np.issubdtype(np.dtype(v.aval.dtype),
                                          np.floating)):
                    t = tin[0]
                    for u in tin[1:]:
                        t = coarsest(t, u)
                    for ov in eqn.outvars:
                        tags[ov] = t
                elif len(tin) == 1 and prim in ("dynamic_slice", "slice",
                                                "reshape", "transpose",
                                                "broadcast_in_dim",
                                                "squeeze", "rev", "copy",
                                                "expand_dims", "gather"):
                    # single-array movement: index operands don't count
                    for ov in eqn.outvars:
                        tags[ov] = tin[0]
            elif prim in _SCALE_OPS:
                sized = [(0 if hasattr(v, "val") else _aval_elems(v), k)
                         for k, v in enumerate(eqn.invars)]
                mx = max(e for e, _ in sized)
                big = [k for e, k in sized if e == mx]
                if len(big) == 1 and eqn.invars[big[0]] in tags:
                    tags[eqn.outvars[0]] = tags[eqn.invars[big[0]]]
            elif prim in _CALL_PRIMS or any(
                    self._is_jaxpr(v) for v in eqn.params.values()):
                self._recurse(eqn, tags, tag_of)
            # everything else: fresh wide value, no tag

        return tags

    @staticmethod
    def _is_jaxpr(v):
        return hasattr(v, "jaxpr") or hasattr(v, "eqns")

    def _sub_jaxprs(self, params):
        for v in params.values():
            if hasattr(v, "jaxpr"):         # ClosedJaxpr
                yield v.jaxpr
            elif hasattr(v, "eqns"):        # raw Jaxpr (shard_map)
                yield v
            elif isinstance(v, (tuple, list)):
                for u in v:
                    if hasattr(u, "jaxpr"):
                        yield u.jaxpr
                    elif hasattr(u, "eqns"):
                        yield u

    def _recurse(self, eqn, tags, tag_of):
        subs = list(self._sub_jaxprs(eqn.params))
        for sub in subs:
            sub_tags = {}
            # map caller tags onto callee invars positionally where the
            # arity lines up (pjit/shard_map); otherwise walk untagged —
            # rounding events inside are still collected either way.
            consts = getattr(sub, "constvars", [])
            nin = len(sub.invars)
            args = eqn.invars[-nin:] if len(eqn.invars) >= nin else []
            for iv, av in zip(sub.invars, args):
                if not hasattr(av, "val") and av in tags:
                    sub_tags[iv] = tags[av]
            del consts
            out_tags = self.walk(sub, sub_tags)
            if len(sub.outvars) == len(eqn.outvars):
                for ov, sov in zip(eqn.outvars, sub.outvars):
                    if not hasattr(sov, "val") and sov in out_tags:
                        tags[ov] = out_tags[sov]


def analyze(closed_jaxpr) -> FlowResult:
    """Walk a ClosedJaxpr (or raw Jaxpr) and return the flow census."""
    w = _Walker()
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    w.walk(jaxpr)
    return w.res


def trace(fn, *args, **kwargs) -> FlowResult:
    """jax.make_jaxpr + analyze in one step (args are ShapeDtypeStructs
    or concrete arrays; nothing is executed)."""
    import jax
    return analyze(jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args))
