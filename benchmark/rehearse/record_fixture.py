"""Record the small trace the tests of the reduction read
(tests/benchmark/data/small.xplane.pb): three jitted steps of a matrix
product under the harness's own spans, on whatever device JAX has.

    python -m benchmark.rehearse.record_fixture chiprun_out/small.xplane.pb
"""
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp

from ..trace import find_xplane


def main():
    out = sys.argv[1]
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((512, 512), jnp.bfloat16)
    f(x).block_until_ready()
    logdir = tempfile.mkdtemp(prefix="bench_fixture_")
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("step_dispatched"):
                y = f(x)
            with jax.profiler.TraceAnnotation("step_waited"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    shutil.copy(find_xplane(logdir), out)
    shutil.rmtree(logdir, ignore_errors=True)
    print(out, jax.devices()[0].device_kind)


if __name__ == "__main__":
    main()
