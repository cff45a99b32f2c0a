"""Record the small serving trace the trace-reduction test reads.

    python3 bench/testdata/record_serve_trace.py [dest]   # on a TPU

Serves three requests, one after another, at n = 1024 through the
program's continuous scheduler (``SolverEngine("bf16_f32")`` behind
``BatchScheduler(max_batch=4, continuous=True)``, the factor cached and
every shape warmed first), targets 4, 5 and 6 digits, all inside a
``bench.window`` span. The calling thread makes each right-hand side on
the device inside ``bench.submit``, so programs are launched from two
threads, and waits for each answer inside ``bench.wait``. The
scheduler's worker thread records its ``repro.*`` spans. Writes the
trace to ``dest`` (by default ``serve_n1024.xplane.pb`` beside this
file).
"""
import glob
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.dirname(HERE))

N = 1024
TARGETS = (4, 5, 6)


def main():
    import jax

    from harness import device, gen
    from repro.serve import (BatchScheduler, SolveOptions, SolverEngine,
                             matrix_fingerprint)
    device.require_chips(1)
    a, _ = gen.serve_pool(1, N, 1)
    fp = matrix_fingerprint(a)
    opts = {d: SolveOptions(target_digits=d, cache_key="m", fingerprint=fp)
            for d in TARGETS}
    make = jax.jit(lambda k: jax.random.normal(jax.random.key(k), (N,)))
    sch = BatchScheduler(SolverEngine("bf16_f32"), max_batch=4,
                         continuous=True)
    sch.start()

    def request(i, d):
        with jax.profiler.TraceAnnotation("bench.submit"):
            fut = sch.submit_async(a, make(i), opts[d])
        x, _ = fut.result()
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready(x)

    for i, d in enumerate(TARGETS):             # factor, compile, warm
        request(i, d)
    out = os.path.join(ROOT, ".bench_trace_record")
    shutil.rmtree(out, ignore_errors=True)
    opts_p = jax.profiler.ProfileOptions()
    opts_p.python_tracer_level = 0
    opts_p.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts_p)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i, d in enumerate(TARGETS):
            request(len(TARGETS) + i, d)
    jax.profiler.stop_trace()
    sch.stop()
    src = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    dest = (sys.argv[1] if len(sys.argv) > 1
            else os.path.join(HERE, "serve_n1024.xplane.pb"))
    os.makedirs(os.path.dirname(os.path.abspath(dest)), exist_ok=True)
    shutil.copy(src[0], dest)
    shutil.rmtree(out)


if __name__ == "__main__":
    main()
