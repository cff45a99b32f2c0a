"""Record the small chip trace the trace-reduction test reads.

    python3 bench/testdata/record_trace.py [dest]   # on a TPU

Factors the paper's test matrix at n = 1024 (leaf 256: three panel
updates, four leaf factors) twice inside a ``bench.window`` span, each
call inside ``bench.solve`` and a pause between them inside
``bench.pick``, and writes the trace to ``dest`` (by default
``factor_n1024.xplane.pb`` beside this file).
"""
import glob
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.dirname(HERE))


def main():
    import jax

    from harness import device, gen
    from repro import core
    device.require_chips(1)
    cfg = core.PAPER_CONFIGS["bf16_f32"]
    (a, _), = gen.matrix_pool(1, 1024, 1, 1)
    f = jax.jit(lambda a: core.cholesky_padded(a, cfg))
    jax.block_until_ready(f(a))
    out = os.path.join(ROOT, ".bench_trace_record")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.solve"):
                jax.block_until_ready(f(a))
            with jax.profiler.TraceAnnotation("bench.pick"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(out, "**", "*.xplane.pb"), recursive=True)
    dest = (sys.argv[1] if len(sys.argv) > 1
            else os.path.join(HERE, "factor_n1024.xplane.pb"))
    os.makedirs(os.path.dirname(os.path.abspath(dest)), exist_ok=True)
    shutil.copy(src[0], dest)
    shutil.rmtree(out)


if __name__ == "__main__":
    main()
