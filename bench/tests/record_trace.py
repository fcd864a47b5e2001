"""Record the small TPU trace that ``test_xtrace.py`` reduces (run on the
chip; the file is kept under ``bench/tests/data``).

    python3 bench/tests/record_trace.py <out dir>

Three host spans ``bench.step``, each around one bf16 matmul of
2048 x 2048 and one call of the program's Pallas row-norm kernel, with a
20 ms sleep (device idle) inside ``bench.wait`` between them.
"""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import spec  # noqa: E402
import xtrace  # noqa: E402


def main():
    out = sys.argv[1]
    spec.use_program()
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    if jax.devices()[0].platform != "tpu":
        sys.exit("needs a TPU")
    x = jax.random.normal(jax.random.PRNGKey(0), (2048, 2048), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    rn = jax.jit(lambda a: ops.row_norms(a))
    jax.block_until_ready((mm(x), rn(x)))
    jax.profiler.start_trace(out, profiler_options=xtrace.options())
    for _ in range(3):
        with jax.profiler.TraceAnnotation("bench.step"):
            jax.block_until_ready((mm(x), rn(x)))
        with jax.profiler.TraceAnnotation("bench.wait"):
            time.sleep(0.02)
    jax.profiler.stop_trace()
    print(xtrace.xplane_file(out), os.path.getsize(xtrace.xplane_file(out)))


if __name__ == "__main__":
    main()
