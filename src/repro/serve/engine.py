"""Serving engine: batched prefill + decode with sharded caches.

``prefill_step`` / ``serve_step`` are the two functions the decode_* and
long_* dry-run cells lower (assignment: decode shapes lower serve_step —
one new token against a seq_len KV cache — not train_step).

``generate`` is the host-side loop used by examples/serve.py: prefill a
prompt batch, then greedy/temperature decode with a step-jitted
serve_step. Continuous batching at cluster scale would slot new requests
into free cache rows between steps; the cache layout (batch-major,
position-indexed) is chosen so that insertion is a dynamic_update_slice
per row (documented seam, not exercised here).

``SolverEngine`` is the linear-algebra side of serving: SPD solve
requests carry a per-request ACCURACY TARGET (decimal digits of relative
residual) instead of naming a precision ladder. The engine always
factorizes in the cheapest ladder and spends iterative-refinement sweeps
— O(n^2) each — to reach the requested digits, caching factors across
requests that share a matrix.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation, annotate_function
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.blocked import diag_tri_inv
from repro.core.distributed import dist_cholesky, dist_refine_solve
from repro.core.plan import build_plan, shard
from repro.core.precision import PAPER_CONFIGS, PrecisionConfig
from repro.core.refine import (RefineConfig, RefineResult, RefineStepper,
                               scaled_solve)
from repro.core.solve import cholesky_padded, refine_solve, solve_factored
from repro.kernels import ops
from repro.models import transformer as T
from repro.models.common import ModelConfig, NO_SHARD, Sharder
from repro.serve.metrics import MetricsTracker, NullMetrics
from repro.serve.options import SolveOptions, resolve_options


def prefill_step(params, batch, cfg: ModelConfig,
                 sharder: Sharder = NO_SHARD):
    """Full-sequence forward; returns (last_logits, caches)."""
    logits, _, caches = T.forward(params, batch, cfg, sharder,
                                  mode="prefill", last_only=True)
    return logits[:, -1], caches


def serve_step(params, caches, tokens, pos, cfg: ModelConfig,
               sharder: Sharder = NO_SHARD, extra=None):
    """One decode step. tokens: [B, 1] (audio: [B, 1, n_codebooks]);
    pos: scalar int32 absolute position. Returns (logits, new_caches)."""
    batch = {"tokens": tokens}
    if extra:
        batch.update(extra)
    logits, _, caches = T.forward(params, batch, cfg, sharder,
                                  mode="decode", caches=caches, pos=pos)
    return logits[:, 0], caches


def generate(params, prompt_batch, cfg: ModelConfig, *, n_tokens: int,
             sharder: Sharder = NO_SHARD, temperature: float = 0.0,
             rng=None, max_len: int | None = None):
    """Greedy / sampled generation (host loop, jitted step)."""
    S = prompt_batch["tokens"].shape[1]
    max_len = max_len or (S + n_tokens)
    last, caches = prefill_step(params, prompt_batch, cfg, sharder)
    caches = T.pad_caches(caches, max_len)

    step = jax.jit(functools.partial(serve_step, cfg=cfg, sharder=sharder))

    outs = []
    tok = _pick(last, cfg, temperature, rng, 0)
    outs.append(tok)
    for i in range(1, n_tokens):
        logits, caches = step(params, caches, tok, jnp.int32(S + i - 1))
        tok = _pick(logits, cfg, temperature, rng, i)
        outs.append(tok)
    return jnp.concatenate(outs, axis=1)


# ---------------------------------------------------------------------------
# accuracy-targeted SPD solve serving
# ---------------------------------------------------------------------------
def matrix_fingerprint(a, samples: int = 8):
    """Cheap identity check for a cached factor: shape, dtype, trace and
    a strided sample of the diagonal and first row.

    O(n) device work and a ~2*samples-float transfer — negligible next
    to the O(n^3) factorization it guards. Collisions require two
    matrices agreeing on every sampled entry AND the trace, which no
    real request stream produces by accident; the failure it prevents
    (a reused ``cache_key`` silently solving against a stale factor) was
    an actual correctness bug.
    """
    a = jnp.asarray(a)
    n = a.shape[0]
    stride = max(1, n // samples)
    probe = jnp.concatenate([
        jnp.diagonal(a)[::stride].ravel(),
        a[0, ::stride].ravel(),
        jnp.trace(a)[None],
    ]).astype(jnp.float32)
    return (a.shape, str(a.dtype), np.asarray(probe).tobytes())


@functools.partial(jax.jit, static_argnames=("cfg",))
def _base_solve(l, linvs, r, cfg: PrecisionConfig):
    """The unscaled factored solve of a block ``r``, as one program.

    ``l`` and ``linvs`` are arguments, never constants of the program
    (JAX writes a closed-over array into the module as a dense
    constant), so one executable serves every factor of its shape and
    JAX's own cache compiles once per block width.
    """
    return solve_factored(l, r.astype(l.dtype), cfg,
                          linvs=linvs).astype(r.dtype)


@functools.partial(jax.jit, static_argnames=("cfg", "rcfg"))
def _refine(a, bmat, l, linvs, col_tol, cfg: PrecisionConfig,
            rcfg: RefineConfig) -> RefineResult:
    """Single-device :func:`refine_solve` as one program: the matrix,
    the block, the factor and the per-column tolerances are arguments,
    so a new target mix or a new factor of the same shape reuses it."""
    return refine_solve(a, bmat, cfg, refine=rcfg, l=l, col_tol=col_tol,
                        linvs=linvs)


def _strip_history(h):
    """Nan-padded ``[sweeps+1, k]`` history -> per-column float tuples.

    Drops the window loop's nan padding (sweeps a column never ran /
    ran frozen) so the windowed and continuous paths hand back the
    same trajectory for the same column.
    """
    return tuple(tuple(float(v) for v in col[~np.isnan(col)])
                 for col in h.T)


@dataclasses.dataclass
class SolveInfo:
    """Per-request serving metadata returned next to the solution.

    ``queue_ms``/``shed_tier``/``deadline_expired`` are stamped by the
    serving layer (scheduler/frontend); direct engine calls leave their
    defaults.  ``history`` is the per-column relative-residual
    trajectory — ``history[j][0]`` the pre-refinement residual of this
    request's column ``j``, then one entry per sweep that column
    actually ran (the window loop's nan padding is stripped, so the
    continuous and windowed paths report identical histories).
    """

    ladder: str                 # PAPER_CONFIGS key actually used
    method: str                 # "ir" | "gmres"
    sweeps: int                 # refinement sweeps spent
    residual: float             # achieved relative residual
    converged: bool
    target_digits: float        # digits actually targeted (post-clamp)
    factor_cached: bool         # True if the factor was reused
    batch_size: int = 1         # requests sharing this refine call
    batch_index: int = 0        # this request's slot in the batch
    distributed: bool = False   # factor/solves ran on the engine's mesh
    queue_ms: float = 0.0       # submit -> solve-start latency
    shed_tier: int = 0          # 0 = as requested, 1 = degraded target
    deadline_expired: bool = False  # retired at its deadline, best-so-far
    history: tuple = ()         # per-column residual trajectories


class SolverEngine:
    """Serve SPD solves against a per-request accuracy target.

    Clients ask for *digits* (``-log10`` of the relative residual), not a
    precision ladder: the engine always factorizes in its cheap default
    ladder and buys accuracy with iterative-refinement sweeps (O(n^2)
    each) instead of higher-precision factorizations (O(n^3)). Targets
    beyond the residual precision's floor are clamped (f32 residuals cap
    at ~7 digits; enable x64 for more — the engine picks the widest
    enabled dtype automatically).

    Factors are cached under a caller-provided ``cache_key`` so request
    streams that share a matrix (GP hyperparameter sweeps, K-FAC-style
    repeated solves) pay the O(n^3) factorization once. Each cached
    factor carries a :func:`matrix_fingerprint` of the matrix it was
    computed from — a reused key with a DIFFERENT matrix forces
    refactorization instead of silently solving against a stale factor
    — and the cache is LRU-bounded by ``max_cached_factors`` so it
    cannot grow without limit under production traffic.

    :meth:`solve_batched` is the cross-request entry point the
    :class:`~repro.serve.scheduler.BatchScheduler` uses: it stacks many
    RHS sharing a factor into ONE multi-RHS refine call with per-column
    accuracy targets, so easy requests stop sweeping while hard
    neighbors continue.

    **Multi-device mode** (docs/SERVING.md, "Multi-device mode"): pass
    ``mesh=`` to route factorizations of matrices at or above
    ``dist_threshold`` (whose size divides the mesh axis times the leaf)
    through the distributed block-panel solver
    (:func:`repro.core.distributed.dist_cholesky`), with every
    refinement sweep's correction solve running distributed too
    (:func:`~repro.core.distributed.dist_refine_solve`). The factor
    cache then stores the SHARDED factor per fingerprint — cache hits
    reuse device-resident shards, no re-gather. Smaller or non-divisible
    matrices fall back to the single-device path; ``SolveInfo
    .distributed`` records which path served each request.
    """

    #: digits attainable by the residual precision (with ~1 digit margin)
    _FLOOR_DIGITS = {"f32": 7.0, "f64": 14.0}

    def __init__(self, ladder: str | PrecisionConfig = "bf16_f32", *,
                 max_sweeps: int = 10, gmres_restart: int = 16,
                 max_cached_factors: int = 16, mesh=None,
                 dist_threshold: int | None = None,
                 dist_axis: str = "model",
                 dist_compress: bool | None = None, tuning_db=None,
                 metrics: MetricsTracker | None = None):
        if isinstance(ladder, str):
            self.ladder_name = ladder
            self.cfg = PAPER_CONFIGS[ladder]
        else:
            self.ladder_name = ladder.describe()
            self.cfg = ladder
        self.max_sweeps = max_sweeps
        self.gmres_restart = gmres_restart
        assert max_cached_factors >= 1, max_cached_factors
        self.max_cached_factors = max_cached_factors
        self.mesh = mesh
        #: None = consult the tuning DB per problem size (docs/TUNING.md),
        #: falling back to the pre-tuner 2048; an int pins the threshold
        self.dist_threshold = dist_threshold
        self.dist_axis = dist_axis
        #: None = the tuning DB's measured per-size choice; a bool pins it
        self.dist_compress = dist_compress
        #: injected TuningDB (tests); None = the committed per-backend DB
        self._tuning_db = tuning_db
        #: pluggable metrics sink (repro.serve.metrics); shared by the
        #: scheduler/frontend stacked on this engine unless overridden
        self.metrics: MetricsTracker = (metrics if metrics is not None
                                        else NullMetrics())
        if mesh is not None:
            assert dist_axis in mesh.shape, (dist_axis, mesh)
        #: cache_key -> (fingerprint, padded factor, diag-tile inverses),
        #: most-recently-used last; in mesh mode the factor entry is the
        #: block-row-sharded L. Guarded by ``_cache_lock``: the async
        #: scheduler's drain worker shares this cache with direct-call
        #: engine users on other threads.
        self._factors: collections.OrderedDict = collections.OrderedDict()
        #: (cache_key, fingerprint, slots) -> (RefineStepper, base_solve):
        #: a stepper's jitted sweep is cached per factor so re-activating
        #: a continuous group doesn't recompile (same LRU bound)
        self._steppers: collections.OrderedDict = collections.OrderedDict()
        self._cache_lock = threading.RLock()

    def _tuned(self, n: int, nshards: int):
        """Tuning-DB decision for ``(n, ladder, nshards)`` (repro.tune)."""
        from repro import tune
        return tune.decide(n, tune.ladder_key(self.cfg), nshards,
                           db=self._tuning_db)

    def _use_dist(self, n: int) -> bool:
        """True when a size-``n`` solve takes the distributed path.

        Deterministic in ``n`` so :meth:`_factorize` and
        :meth:`solve_batched` always agree on what a cached factor is.
        With ``dist_threshold=None`` the threshold is the tuning
        database's measured value for this size (default 2048).
        """
        if self.mesh is None:
            return False
        nshards = self.mesh.shape[self.dist_axis]
        if n % (nshards * self.cfg.leaf) != 0:
            return False
        thr = self.dist_threshold
        if thr is None:
            thr = self._tuned(n, nshards).dist_threshold
        return n >= thr

    def _cfg_for(self, n: int) -> PrecisionConfig:
        """Per-size engine resolution for ``engine="auto"`` configs.

        Factorization and every later solve against the cached factor
        route through this, so both always agree on the engine (and thus
        on whether ``linvs`` exist for the factor).
        """
        if self.cfg.engine != "auto":
            return self.cfg
        nshards = (self.mesh.shape[self.dist_axis]
                   if self._use_dist(n) else 1)
        return dataclasses.replace(self.cfg,
                                   engine=self._tuned(n, nshards).engine)

    def _clamp(self, target_digits: float) -> float:
        rname = "f64" if jax.config.jax_enable_x64 else "f32"
        return min(float(target_digits), self._FLOOR_DIGITS[rname])

    def _factorize(self, a):
        """Padded factor + blocked-engine diagonal-tile inverses.

        The factor is kept in its leaf-padded form (``pad_factor``
        semantics) so non-multiple-of-leaf solves skip re-padding on
        every request, and ``linvs`` lets every refinement sweep's pair
        of triangular solves reuse the one-off leaf inversions.

        In mesh mode, matrices :meth:`_use_dist` accepts are factorized
        by the distributed block-panel engine instead; the cached factor
        is then the block-row-sharded L (no ``linvs`` — the distributed
        solve inverts its diagonal blocks per shard).
        """
        a = jnp.asarray(a)
        n = a.shape[-1]
        cfg = self._cfg_for(n)
        if self._use_dist(n):
            compress = self.dist_compress
            if compress is None:
                compress = self._tuned(
                    n, self.mesh.shape[self.dist_axis]).compress_comm
            a_sh = jax.device_put(a, NamedSharding(
                self.mesh, PartitionSpec(self.dist_axis, None)))
            l = dist_cholesky(a_sh, self.mesh, cfg,
                              axis=self.dist_axis,
                              compress_comm=compress)
            if cfg.engine == "blocked":
                sp = shard(build_plan(n, cfg), self.mesh.shape[self.dist_axis])
                self.metrics.inc("dist.collective_bytes",
                                 sum(sp.collective_bytes(compress).values()))
            return l, None
        l = cholesky_padded(a, cfg)
        linvs = (diag_tri_inv(l, cfg)
                 if cfg.engine == "blocked" else None)
        return l, linvs

    @functools.partial(annotate_function, name="repro.engine.factor")
    def factor(self, a, cache_key=None, *, fingerprint=None):
        """Factorize (or fetch the cached factor for) ``a``.

        Returns ``(l, linvs, cached)`` — the leaf-padded factor, the
        cached diagonal-tile inverses (None for the tree engine) and a
        cache-hit flag. A cache hit is only served when the stored
        fingerprint matches ``a`` — a reused key with new matrix data
        refactorizes (and replaces the stale entry) rather than
        returning a factor of some other matrix. Insertions evict
        least-recently-used entries beyond ``max_cached_factors``.
        ``fingerprint`` lets callers that already fingerprinted ``a``
        (the scheduler does, at submit time) skip the redundant O(n)
        device round-trip.
        """
        if cache_key is None:
            l, linvs = self._factorize(a)
            self.metrics.inc("engine.factor_cache_miss")
            return l, linvs, False
        fp = fingerprint if fingerprint is not None else matrix_fingerprint(a)
        with self._cache_lock:
            hit = self._factors.get(cache_key)
            if hit is not None and hit[0] == fp:
                self._factors.move_to_end(cache_key)
                self.metrics.inc("engine.factor_cache_hit")
                return hit[1], hit[2], True
        self.metrics.inc("engine.factor_cache_miss")
        l, linvs = self._factorize(a)
        with self._cache_lock:
            self._factors[cache_key] = (fp, l, linvs)
            self._factors.move_to_end(cache_key)
            while len(self._factors) > self.max_cached_factors:
                self._factors.popitem(last=False)
        return l, linvs, False

    def evict(self, cache_key):
        with self._cache_lock:
            self._factors.pop(cache_key, None)
            for k in [k for k in self._steppers if k[0] == cache_key]:
                self._steppers.pop(k)

    def cached_keys(self):
        """Cache keys currently held, least-recently-used first."""
        with self._cache_lock:
            return list(self._factors)

    def solve(self, a, b, options: SolveOptions | None = None, **kw):
        """Solve A x = b per ``options``; returns ``(x, SolveInfo)``.

        ``options.method="gmres"`` requests GMRES-IR for ill-conditioned
        systems where classic IR stalls. ``b`` may be (n,) or (n, k);
        for a multi-RHS ``b`` the SolveInfo aggregates across columns
        (max sweeps/residual, all-converged). Pre-``SolveOptions``
        kwargs (``target_digits=``, ``method=``, ``cache_key=``) keep
        working as deprecated aliases.
        """
        opts = resolve_options(options, kw, caller="SolverEngine.solve")
        xs, infos = self.solve_batched(a, [b], opts)
        return xs[0], infos[0]

    def solve_batched(self, a, bs, options: SolveOptions | None = None,
                      **kw):
        """Solve A x_i = b_i for a batch of RHS sharing one factor.

        ``bs`` is a sequence of (n,) vectors and/or (n, k_i) blocks (one
        per request); ``options.target_digits`` is a scalar or a
        per-request sequence. All RHS are stacked into a single
        multi-RHS refine call whose per-column tolerances encode each
        request's target, so converged columns freeze while slow ones
        keep sweeping. Returns ``(xs, infos)`` aligned with ``bs``; each
        request's x keeps its input arity (vector in, vector out) in the
        residual precision. Deprecated kwarg aliases as in
        :meth:`solve` (plus ``fingerprint=``).
        """
        opts = resolve_options(options, kw,
                               caller="SolverEngine.solve_batched")
        method = opts.method
        bs = [jnp.asarray(b) for b in bs]
        assert bs, "solve_batched needs at least one RHS"
        n = bs[0].shape[0]
        for b in bs:
            assert b.ndim in (1, 2) and b.shape[0] == n, b.shape
        cols = [1 if b.ndim == 1 else b.shape[1] for b in bs]
        target_digits = opts.target_digits
        if np.isscalar(target_digits):
            target_digits = [target_digits] * len(bs)
        assert len(target_digits) == len(bs), (len(target_digits), len(bs))
        digits = [self._clamp(d) for d in target_digits]
        if opts.col_tol is not None:
            col_tol = np.asarray(opts.col_tol, np.float64)
            assert col_tol.shape == (sum(cols),), (col_tol.shape, cols)
        else:
            col_tol = np.repeat([10.0 ** -d for d in digits], cols)
        # ``col_tol`` governs convergence; ``rcfg`` holds no per-request
        # value, so it can key the compiled refine program
        rcfg = RefineConfig(max_sweeps=self.max_sweeps, method=method,
                            gmres_restart=self.gmres_restart)
        l, linvs, cached = self.factor(a, opts.cache_key,
                                       fingerprint=opts.fingerprint)
        bmat = jnp.concatenate(
            [b[:, None] if b.ndim == 1 else b for b in bs], axis=1)
        dist = self._use_dist(n)
        if dist:
            res: RefineResult = dist_refine_solve(
                a, bmat, self.mesh, self._cfg_for(n), axis=self.dist_axis,
                refine=rcfg, l=l, col_tol=jnp.asarray(col_tol))
        else:
            res = _refine(a, bmat, l, linvs, jnp.asarray(col_tol),
                          cfg=self._cfg_for(n), rcfg=rcfg)
        sweeps = np.atleast_1d(np.asarray(res.iterations))
        resid = np.atleast_1d(np.asarray(res.residual))
        conv = np.atleast_1d(np.asarray(res.converged))
        hist = np.asarray(res.history)          # [S+1] or [S+1, k]
        if hist.ndim == 1:
            hist = hist[:, None]
        self.metrics.inc("engine.requests", len(bs))
        for s in sweeps:
            self.metrics.observe("engine.sweeps_per_column", int(s))
        xs, infos = [], []
        off = 0
        for i, (b, k) in enumerate(zip(bs, cols)):
            x = res.x[:, off:off + k]
            xs.append(x[:, 0] if b.ndim == 1 else x)
            sl = slice(off, off + k)
            infos.append(SolveInfo(
                ladder=self.ladder_name, method=method,
                sweeps=int(sweeps[sl].max()),
                residual=float(resid[sl].max()),
                converged=bool(conv[sl].all()),
                target_digits=digits[i], factor_cached=cached,
                batch_size=len(bs), batch_index=i, distributed=dist,
                shed_tier=opts.shed_tier,
                history=_strip_history(hist[:, sl])))
            off += k
        return xs, infos

    @functools.partial(annotate_function, name="repro.engine.stepper")
    def continuous_stepper(self, a, *, slots: int, cache_key=None,
                           fingerprint=None):
        """Factor ``a`` (through the cache) and return the continuous-
        batching machinery bound to it: ``(stepper, base_solve, cached)``.

        ``stepper`` is a :class:`repro.core.refine.RefineStepper` over a
        ``slots``-wide RHS block — the re-entrant loop the scheduler's
        continuous worker drives (join/step/retire between sweeps);
        ``base_solve`` computes the initial iterate for joining columns
        (the same unscaled factored solve the windowed path starts
        from, so a column's trajectory is identical in either mode).
        It runs as one compiled program per join width, the factor an
        argument; each width a stepper meets first adds one to
        ``engine.base_solve_compiles``.
        Classic IR only — GMRES-IR's joint Krylov space cannot retire
        columns mid-restart — and single-device only (the scheduler
        windows distributed-path requests).

        The stepper (and its jitted sweep) is cached per
        ``(cache_key, fingerprint, slots)`` next to the factor cache, so
        re-activating a continuous group — the scheduler does this every
        time its block drains and traffic returns — reuses the compiled
        sweep instead of paying an XLA compile per activation.
        """
        a = jnp.asarray(a)
        n = a.shape[-1]
        assert not self._use_dist(n), \
            "continuous batching is single-device; dist requests window"
        fp = fingerprint if fingerprint is not None else matrix_fingerprint(a)
        memo_key = (cache_key, fp, slots)
        with self._cache_lock:
            hit = self._steppers.get(memo_key)
            if hit is not None:
                self._steppers.move_to_end(memo_key)
                return hit[0], hit[1], True
        cfg = self._cfg_for(n)
        l, linvs, cached = self.factor(a, cache_key, fingerprint=fp)
        rcfg = RefineConfig(max_sweeps=self.max_sweeps, method="ir",
                            gmres_restart=self.gmres_restart)
        rdtype = rcfg.rdtype()
        a_r = jnp.asarray(a, rdtype)

        def solve(r):
            return solve_factored(l, r.astype(l.dtype), cfg,
                                  linvs=linvs).astype(rdtype)

        widths: set = set()

        def base_solve(r):
            # one compiled program per join width, the factor an argument
            r = jnp.asarray(r, rdtype)
            with TraceAnnotation("repro.solve.base"):
                with self._cache_lock:
                    new = r.shape not in widths
                    widths.add(r.shape)
                if new:
                    self.metrics.inc("engine.base_solve_compiles")
                return _base_solve(l, linvs, r, cfg=cfg)

        def resid(x, b):
            return ops.residual(a_r, x, b, impl=cfg.kernel_impl)

        stepper = RefineStepper(scaled_solve(solve), resid,
                                n=n, slots=slots, rcfg=rcfg)
        with self._cache_lock:
            self._steppers[memo_key] = (stepper, base_solve)
            while len(self._steppers) > self.max_cached_factors:
                self._steppers.popitem(last=False)
        return stepper, base_solve, cached


def _pick(logits, cfg: ModelConfig, temperature, rng, i):
    """logits: [B, V] (audio: [B, n_cb, V]) -> next token [B, 1, ...]."""
    if temperature > 0:
        assert rng is not None
        k = jax.random.fold_in(rng, i)
        tok = jax.random.categorical(k, logits / temperature, axis=-1)
    else:
        tok = jnp.argmax(logits, axis=-1)
    if cfg.family == "audio":
        return tok[:, None, :]          # [B, 1, n_cb]
    return tok[:, None]
