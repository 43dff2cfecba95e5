"""The master-mix cell's readers: the QoS counters' shares and the scenario
host span, on hand-built runs, and silent on outputs and traces of a
program that has neither."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench.harness import Run, reader
from bench.trace_reduce import Reduced
from bench.workload import Call

REPO = Path(__file__).resolve().parents[2]
NEW = ["scenario_host_ms", "regulator_hold_share", "aged_grant_share"]


def _point(effective, skipped, held=None, aged=None, grants=(0,)):
    p = {"effective_cycles": np.int32(effective),
         "skipped_cycles": np.int32(skipped),
         "slice_beats": np.asarray(grants, np.int32)}
    if held is not None:
        p.update(reg_held=np.int32(held), aged_grants=np.int32(aged))
    return p


def _call(i, point, besteffort=2):
    c = Call(i, [{}], 0)
    c.points = [point]
    c.per_class = [{"safety": {"masters": 8},
                    "besteffort": {"masters": besteffort}}]
    return c


def test_counter_shares_worked():
    run = Run([_call(0, _point(1000, 200, held=400, aged=30, grants=[600])),
               _call(1, _point(500, 0, held=100, aged=0, grants=[400]))],
              window_s=1.0, setup_s=1.0)
    # held over 2 regulated ports x stepped cycles: 500 / (2 x 1300)
    assert reader(REPO, "regulator_hold_share")(run) == pytest.approx(
        500 / 2600)
    assert reader(REPO, "aged_grant_share")(run) == pytest.approx(30 / 1000)


def test_readers_silent_without_counters_spans_or_regulated_ports():
    run = Run([_call(0, _point(1000, 0, grants=[600]))], 1.0, 1.0)
    for name in NEW:
        assert reader(REPO, name)(run) is None, name
    run = Run([_call(0, _point(1000, 0, held=0, aged=0), besteffort=0)],
              1.0, 1.0)
    assert reader(REPO, "regulator_hold_share")(run) is None
    assert reader(REPO, "aged_grant_share")(run) is None


def test_scenario_host_ms_reads_the_span():
    ms = 1_000_000
    trace = Reduced((0, 100 * ms), [np.array([[50 * ms, 90 * ms]])], {}, {},
                    [(0, 10 * ms, "repro.scenario"),
                     (2 * ms, 8 * ms, "repro.generate"),
                     (12 * ms, 20 * ms, "bench.call")])
    run = Run([_call(0, _point(1000, 0))], 1.0, 1.0, trace, 1)
    assert reader(REPO, "scenario_host_ms")(run) == pytest.approx(10.0)
    trace.host_events.pop(0)
    assert reader(REPO, "scenario_host_ms")(run) is None


def test_new_metrics_in_benchmark_json():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    got = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:
        assert got[name]["workloads"] == ["adas_cams_sched_b1"]
        assert got[name]["moves"] == "sim_cycles_per_s"
