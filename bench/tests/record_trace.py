#!/usr/bin/env python3
"""Record the small device trace ``test_trace_reduce.py`` reads.

  python3 bench/tests/record_trace.py bench/tests/data/small_tpu.xplane.pb

On a TPU: two ``bench.call`` spans, each a jitted loop of a few
milliseconds of matrix products followed by 10 ms of host sleep,
traced by the JAX profiler; the ``.xplane.pb`` is copied to the given
path.
"""
import glob
import shutil
import sys
import tempfile
import time


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def loop(x):
        return jax.lax.fori_loop(0, 2000, lambda i, v: jnp.tanh(v @ v), x)

    x = jnp.full((512, 512), 0.01)
    loop(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.call"):
            loop(x).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0], out)
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
