#!/usr/bin/env python3
"""Record the small scoped trace ``test_stage_time.py`` reads.

  python3 bench/tests/record_scoped_trace.py bench/tests/data/small_tpu_scoped

On a TPU: two ``bench.call`` spans, each a ``repro.fetch`` span around a
jitted loop whose body runs two name scopes, ``stage.first`` (a matrix
product) and ``stage.second`` (a column reduction), after an unscoped
scaling, then 10 ms of host sleep.  Writes ``<out>.xplane.pb`` and the
compiled program's text, ``<out>.hlo.txt``.
"""
import glob
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np


def body(i, v):
    with jax.named_scope("stage.first"):
        v = jnp.tanh(v @ v)
    with jax.named_scope("stage.second"):
        v = v - 0.5 * jnp.max(v, axis=0, keepdims=True)
    return v


def loop(x):
    return jax.lax.fori_loop(0, 300, body, 2.0 * x)


def main(out: str) -> int:
    fn = jax.jit(loop)
    x = jnp.full((512, 512), 0.01)
    fn(x).block_until_ready()
    with open(f"{out}.hlo.txt", "w") as f:
        f.write(fn.lower(x).compile().as_text())
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.call"):
            with jax.profiler.TraceAnnotation("repro.fetch"):
                np.asarray(fn(x))
            time.sleep(0.01)
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0],
                f"{out}.xplane.pb")
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
