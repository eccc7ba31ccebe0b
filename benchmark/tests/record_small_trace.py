"""By hand, on the chip: record a trace small enough to check in, for
test_trace.py. Three calls of one small named program with host sleeps
between them, inside the benchmark's window annotation.

    python benchmark/tests/record_small_trace.py <out.xplane.pb>
"""
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.harness import trace  # noqa: E402


def small_step(x):
    return jnp.tanh(x @ x) @ x


def main(out):
    f = jax.jit(small_step)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    f(x).block_until_ready()
    tdir = os.path.join(ROOT, ".bench_work", "small_trace")
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for _ in range(3):
            f(x).block_until_ready()
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = trace.find_xplane(tdir)
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    shutil.copy(path, out)
    print(trace.describe(path, top=8))
    print(trace.reduce(trace.load(path)))
    print("bytes", os.path.getsize(out))
    shutil.rmtree(tdir, ignore_errors=True)


if __name__ == "__main__":
    main(sys.argv[1])
