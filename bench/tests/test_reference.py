"""The plain reference agrees with the model on seeded small scenarios:
the dense pipeline, the schedule pipeline with the exact collector, and the
schedule pipeline with the streaming collector, across knob settings that
turn the regulator, aging and bank occupancy."""
from __future__ import annotations

from dataclasses import replace

import numpy as np
import pytest

from bench.reference import compare, fabric, masters, summary
from bench.tests.tiny import CONFIGS, GEOMETRY, PARAMS

KNOBS = [{}, {"reg_rate": 32, "outstanding": 3, "bank_occupancy": 4,
              "qos_aging": 0, "ret_latency": 1},
         {"qos_aging": 16, "split_buffer": 16, "outstanding": 2}]


def _model(traffic, knobs, pipeline, collect, mix):
    from repro.core.address import MemoryGeometry
    from repro.core.simulator import SCHEDULE_PIPELINE, SimParams
    from repro.scenarios.spec import MasterSpec, Scenario
    prm = SimParams(geom=MemoryGeometry(**GEOMETRY),
                    **{**PARAMS, **knobs})
    if pipeline == "schedule":
        prm = replace(prm, stages=SCHEDULE_PIPELINE, collect=collect)
    res = Scenario("t", [MasterSpec(**m) for m in mix],
                   MemoryGeometry(**GEOMETRY)).compile().simulate(prm)
    return res.metrics, res.per_class


@pytest.mark.parametrize("seed", [0, 7, 2**33 + 5])
@pytest.mark.parametrize("knobs", KNOBS)
@pytest.mark.parametrize("pipeline,collect", [("dense", "exact"),
                                              ("schedule", "exact"),
                                              ("schedule", "stream")])
def test_reference_matches_model(seed, knobs, pipeline, collect):
    mix = [dict(m, seed=m["seed"] + seed)
           for m in CONFIGS["tiny_soc"]["masters"]]
    traffic = masters.build(mix, GEOMETRY["total_bytes"] // 32)
    got, got_class = _model(traffic, knobs, pipeline, collect, mix)
    want = fabric.simulate(traffic, GEOMETRY,
                           fabric.Knobs(**{**PARAMS, **knobs}),
                           collect=collect)
    want_class = summary.per_class(traffic, [m["qos"] for m in mix], want)
    numbers = compare.compare(got, want, got_class, want_class)
    assert numbers["int_mismatches"] == 0
    assert compare.within(numbers), numbers


def test_rebuilt_traffic_matches_scenario_layer():
    from repro.core.address import MemoryGeometry
    from repro.scenarios.spec import MasterSpec, Scenario
    mix = CONFIGS["tiny_soc"]["masters"]
    sched = Scenario("t", [MasterSpec(**m) for m in mix],
                     MemoryGeometry(**GEOMETRY)).compile().schedule()
    rebuilt = masters.build(mix, GEOMETRY["total_bytes"] // 32)
    for k in ("is_write", "burst", "addr", "start", "prio", "cls",
              "deadline"):
        assert np.array_equal(np.asarray(getattr(sched, k)), rebuilt[k]), k
