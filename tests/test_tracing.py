"""The simulator names its own work: every cycle-pipeline stage as a name
scope in the compiled program's metadata, and each call into the model as
host spans on the profiler's clock."""
import re

import jax
import numpy as np
import pytest

from repro.core import simulator as sim
from repro.core.simulator import (DEFAULT_PIPELINE, SCHEDULE_PIPELINE,
                                  SimParams, Trace, simulate, simulate_batch)


def _trace(X=4, N=6):
    rng = np.random.default_rng(0)
    return Trace(is_write=rng.integers(0, 2, (X, N)),
                 burst=rng.integers(1, 9, (X, N)),
                 addr=rng.integers(0, 3000, (X, N)),
                 prio=rng.integers(0, 4, X))


@pytest.mark.parametrize("stages", [DEFAULT_PIPELINE, SCHEDULE_PIPELINE],
                         ids=["dense", "schedule"])
def test_every_stage_names_its_operations(stages):
    prm = SimParams(stages=stages, max_cycles=400)
    text = sim.compile_simulate(_trace(), prm).compiled.as_text()
    scopes = {c for op in re.findall(r'op_name="([^"]*)"', text)
              for c in op.split("/") if c.startswith("stage.")}
    assert scopes == {f"stage.{name}" for name in stages}


def test_each_call_spans_prepare_then_fetch(tmp_path):
    t, prm = _trace(), SimParams(max_cycles=400)
    simulate(t, prm)                       # compile outside the trace
    simulate_batch([t, t], [prm, prm])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        simulate(t, prm)
        simulate_batch([t, t], [prm, prm])
    path = next(tmp_path.rglob("*.xplane.pb"))
    prof = jax.profiler.ProfileData.from_file(str(path))
    threads = [sorted(((e.start_ns, -e.end_ns, e.name) for e in line.events
                       if e.name.startswith("repro.")))
               for plane in prof.planes if plane.name.startswith("/host:")
               for line in plane.lines]
    threads = [t for t in threads if t]
    assert len(threads) == 1
    spans = threads[0]
    assert [n for _, _, n in spans] == ["repro.simulate", "repro.prepare",
                                        "repro.fetch"] * 2
    for i in (0, 3):
        (s0, e0, _), (s1, e1, _), (s2, e2, _) = spans[i:i + 3]
        assert s0 <= s1 < -e1 <= s2 < -e2 <= -e0
