"""Regenerate tests/data/golden_single_slice.json.

The golden file pins the simulator's exact outputs for ``num_slices=1``
workloads; the regression test (tests/test_slices.py) replays the same
inputs and requires bit-for-bit equality, so any refactor of the scan core
must leave the single-slice fabric untouched.  Run from the repo root:

  PYTHONPATH=src python tests/data/capture_golden.py

Only regenerate when an intentional, reviewed behaviour change to the
single-slice model lands.
"""
from __future__ import annotations

import json
from dataclasses import replace
from pathlib import Path

import numpy as np

from repro.core.simulator import SimParams, simulate, simulate_batch
from repro.core.traffic import random_uniform, stack_traces
from repro.scenarios import highway_pilot, urban_perception


def golden_cases():
    """(name, trace, params) points spanning the simulator's feature surface:
    random full-duplex traffic, QoS-classed scenario traces with injection
    timing, and non-default dyn knobs (regulator + aging)."""
    urban = urban_perception(txns=24).compile().trace
    highway = highway_pilot(txns=24).compile().trace
    return [
        ("random_uniform", random_uniform(8, 40, burst=8, seed=3),
         SimParams(max_cycles=3000)),
        ("urban_perception", urban, SimParams(max_cycles=4000)),
        ("highway_qos", highway,
         SimParams(max_cycles=4000, outstanding=4, bank_occupancy=6,
                   qos_aging=64, reg_rate=32, reg_burst=8)),
    ]


def golden_batch(cases):
    """(stacked traces, params) of the pinned batched pair: the two
    scenario points of ``cases`` as one vmapped scan."""
    traces = stack_traces([cases[1][1], cases[2][1]])
    prms = [replace(cases[1][2], max_cycles=4000),
            replace(cases[2][2], max_cycles=4000)]
    return traces, prms


#: metric keys pinned by the golden file — the pre-refactor output surface
#: (new slice metrics added later are deliberately NOT pinned)
GOLDEN_KEYS = (
    "throughput", "read_throughput", "write_throughput", "throughput_busy",
    "read_throughput_busy", "write_throughput_busy", "busy_cycles",
    "read_lat_avg", "read_lat_max", "write_lat_avg", "write_lat_max",
    "all_done", "beats_done", "cycles", "complete_cycle", "accept_cycle",
)


def _jsonable(metrics):
    return {k: np.asarray(metrics[k]).tolist() for k in GOLDEN_KEYS}


def main() -> None:
    out = {"cases": {}, "batch": None}
    for name, trace, prm in golden_cases():
        out["cases"][name] = _jsonable(simulate(trace, prm))
    # the batched path: two scenario points, one vmapped scan
    out["batch"] = _jsonable(simulate_batch(*golden_batch(golden_cases())))
    path = Path(__file__).parent / "golden_single_slice.json"
    path.write_text(json.dumps(out))
    print(f"wrote {path} ({path.stat().st_size} bytes)")


if __name__ == "__main__":
    main()
