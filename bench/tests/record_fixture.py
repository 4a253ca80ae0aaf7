"""Record the small trace that ``test_trace_reduce.py`` reads.

    python bench/tests/record_fixture.py bench/tests/fixtures/small.xplane.pb

Run on a TPU: two jitted programs, four ``bench.ask`` spans and one
``bench.sleep`` span (20 ms with the device idle) inside ``bench.window``.
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print("record_fixture: no TPU", file=sys.stderr)
        return 3
    x = jnp.ones((1024, 1024), jnp.float32)
    f = jax.jit(lambda a: jnp.tanh(a @ a) + 1.0)
    g = jax.jit(lambda a: jnp.sum(a * 2.0))
    jax.block_until_ready((f(x), g(x)))
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.ask"):
                f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.sleep"):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation("bench.ask"):
            g(x).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0]
    shutil.copy(src, out)
    shutil.rmtree(d, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
