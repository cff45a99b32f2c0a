"""Accuracy-targeted solve serving against one cached factor.

The program's documented continuous-batching stack: ``SolverEngine``
behind ``BatchScheduler(continuous=True)``, with an ``InMemoryMetrics``
injected through ``metrics=``. The factor is made and cached during
set-up, so the window runs the refinement stepper, the residual kernel,
the triangular solves and the scheduler's host loop.

A closed loop: each of ``callers`` sends its next request when the
answer to its last is ready. Each request is one right-hand side from a
pool made on the device, with a target of digits, and is timed from its
send to its answer being ready (``block_until_ready`` on ``x``). The
run reports the window's time over the requests answered in it
(``request_ms``), the window running until the last request sent in it
is answered.

Traffic keys: ``ladder``, ``max_batch``, ``pool``, ``targets``,
``callers``, ``per_caller`` (the length of each caller's sequence).
"""
from __future__ import annotations

import queue

import numpy as np

from harness import gen, reference

CACHE_KEY = "bench"


def setup(run):
    import jax
    import jax.numpy as jnp

    from repro.serve import (BatchScheduler, InMemoryMetrics,
                             SolveOptions, SolverEngine,
                             matrix_fingerprint)
    cfg, mix = run.config, run.traffic
    a, bs = gen.serve_pool(run.seed, cfg["n"], mix["pool"])
    metrics = InMemoryMetrics()
    eng = SolverEngine(mix["ladder"], metrics=metrics)
    slots = mix["max_batch"]
    sch = BatchScheduler(eng, max_batch=slots, continuous=True)
    fp = matrix_fingerprint(a)
    opts = {d: SolveOptions(target_digits=d, cache_key=CACHE_KEY,
                            fingerprint=fp) for d in mix["targets"]}
    # factor and cache; then every join and retire width the loop can
    # meet, through the same stepper the scheduler will be handed
    stepper, base_solve, _ = eng.continuous_stepper(
        a, slots=slots, cache_key=CACHE_KEY, fingerprint=fp)
    state = stepper.init()
    # a closed loop never has more requests in flight than callers
    for k in range(1, min(slots, mix["callers"]) + 1):
        blk = jnp.concatenate([bs[i % len(bs)][:, None] for i in range(k)],
                              axis=1).astype(stepper.rdtype)
        state = stepper.join(state, list(range(k)), blk, base_solve(blk),
                             np.full(k, 10.0 ** -max(mix["targets"])))
        stepper.active_mask(state)
        state, _ = stepper.step(state)
        stepper.done_mask(state)
        state, res = stepper.retire(state, list(range(k)))
        jax.block_until_ready([x for x, *_ in res])
    sch.start()
    for i, d in enumerate(mix["targets"]):
        fut = sch.submit_async(a, bs[i % len(bs)], opts[d])
        jax.block_until_ready(fut.result()[0])
    return {"a": a, "bs": bs, "sch": sch, "opts": opts,
            "metrics": metrics}


def _counters(metrics) -> dict:
    snap = metrics.snapshot()
    q = snap["observations"].get("scheduler.queue_ms",
                                 {"count": 0, "mean": 0.0})
    return {"sweeps": snap["counters"].get("scheduler.sweeps", 0.0),
            "requests": snap["counters"].get("scheduler.requests", 0.0),
            "queue_n": q["count"], "queue_total": q["count"] * q["mean"]}


class _Collector:
    """Stamps each answer when it is ready, in the order the scheduler
    resolves them."""

    def __init__(self, run):
        self.run = run
        self.done: queue.Queue = queue.Queue()
        self.ready: dict = {}
        self.answers: dict = {}

    def track(self, rid, fut):
        fut.add_done_callback(lambda f: self.done.put((rid, f)))

    def take(self, timeout=None):
        """Wait for the next resolved request; stamp it when its answer
        is on the device. Returns its id."""
        import jax
        rid, fut = self.done.get(timeout=timeout)
        x, info = fut.result()
        with self.run.span("bench.wait"):
            jax.block_until_ready(x)
        self.ready[rid] = self.run.clock()
        self.answers[rid] = (x, info)
        return rid


def _window(run, st, seconds):
    mix = run.traffic
    callers = mix["callers"]
    digits, rhs = gen.closed_loop(run.seed, callers, mix["per_caller"],
                                  mix["targets"], mix["pool"])
    sch, a, bs, opts = st["sch"], st["a"], st["bs"], st["opts"]
    col = _Collector(run)
    nxt = [0] * callers
    requests, sent = {}, {}

    def send(c):
        j = nxt[c] % mix["per_caller"]
        rid = (c, nxt[c])
        nxt[c] += 1
        requests[rid] = (rid, int(rhs[c, j]), int(digits[c, j]))
        sent[rid] = run.clock()
        with run.span("bench.submit"):
            col.track(rid, sch.submit_async(a, bs[requests[rid][1]],
                                            opts[requests[rid][2]]))

    t0 = run.clock()
    t_end = t0 + seconds
    for c in range(callers):
        send(c)
    outstanding = callers
    while outstanding:
        try:
            rid = col.take(timeout=run.answer_wait_s)
        except queue.Empty:
            break                        # the rest never came
        outstanding -= 1
        if col.ready[rid] < t_end:
            send(rid[0])
            outstanding += 1
    lat = [(col.ready[r] - sent[r]) * 1e3 for r in col.ready]
    e2e = {}
    if col.ready:
        # the window runs until the last request sent in it is answered
        t_last = max(col.ready.values())
        e2e["request_ms"] = (t_last - t0) / len(col.ready) * 1e3
    return {"attempted": len(requests),
            "requests": list(requests.values()), "answers": col.answers,
            "e2e": e2e,
            "latency_ms": {"p50": gen.percentile(lat, 50) if lat else None,
                           "p95": gen.percentile(lat, 95) if lat else None,
                           "n": len(lat)}}


def window(run, st, seconds: float) -> dict:
    before = _counters(st["metrics"])
    stats = _window(run, st, seconds)
    after = _counters(st["metrics"])
    d = {k: after[k] - before[k] for k in after}
    counters = {"sched.requests": d["requests"],
                "sched.sweeps": d["sweeps"]}
    if d["queue_n"]:
        counters["sched.queue_mean_ms"] = d["queue_total"] / d["queue_n"]
    stats["counters"] = counters
    return stats


def check(run, st, stats) -> dict:
    """Every request's answer against its own matrix and right-hand side
    by the reference's residual, over its own target; a request with no
    answer counts as failed."""
    import jax.numpy as jnp
    st.pop("sch").stop()
    st.pop("metrics")
    a, bs = st["a"], st["bs"]
    answers = stats["answers"]
    got = [r for r in stats["requests"] if r[0] in answers]
    unanswered = len(stats["requests"]) - len(got)
    ratio = np.zeros(len(got))
    cols = reference.COLS
    for c in range(0, len(got), cols):
        blk = got[c:c + cols]
        pad = cols - len(blk)
        x = jnp.stack([answers[r[0]][0] for r in blk]
                      + [jnp.zeros_like(bs[0])] * pad, axis=1)
        b = jnp.stack([bs[r[1]] for r in blk]
                      + [jnp.ones_like(bs[0])] * pad, axis=1)
        rel = reference.relative_residuals(a, x, b)[:len(blk)]
        ratio[c:c + len(blk)] = rel / np.array(
            [10.0 ** -r[2] for r in blk])
    worst = float(ratio.max()) if len(got) else float("inf")
    limits = run.traffic["limits"]
    failed = unanswered + int((ratio > limits["residual_over_target"]).sum())
    return {"attempted": stats["attempted"], "failed": failed,
            "checks": [("residual_over_target", worst,
                        limits["residual_over_target"]),
                       ("unanswered", float(unanswered),
                        limits["unanswered"])]}
