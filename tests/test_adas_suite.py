"""The sourced ADAS camera suite and the QoS-mechanism counters.

* The camera line-DMA derivation from sensor figures, and the benchmark
  configuration's masters being exactly the preset's.
* The preset, cut to a short line time and a few hundred NPU/CPU
  transactions, against the plain reference in ``bench/reference`` on both
  pipelines (the comparison that decides a benchmark run's ``correct``).
* ``reg_held`` and ``aged_grants`` on hand-built traces whose counts are
  worked out here, equal on the dense and schedule pipelines and with the
  time skip on or off.
"""
from __future__ import annotations

import json
import sys
from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from bench.reference import compare, fabric, masters, summary  # noqa: E402

from repro.core.address import MemoryGeometry, flat_bank_id
from repro.core.simulator import SCHEDULE_PIPELINE, SimParams, Trace, simulate
from repro.scenarios import MasterSpec, adas_camera_suite_qos
from repro.scenarios.generators import camera_line_cadence
from repro.scenarios.library import HW3_CAMERA

CONFIG = json.loads(
    (REPO / "bench" / "configs" / "adas_camera_suite_qos.json").read_text())

# (stages, collect) — every pipeline/collection combination the cores run
VARIANTS = [
    pytest.param(None, "exact", id="dense-exact"),
    pytest.param(SCHEDULE_PIPELINE, "exact", id="sched-exact"),
    pytest.param(SCHEDULE_PIPELINE, "stream", id="sched-stream"),
]


def test_camera_line_cadence_from_sensor_figures():
    """1280 px x 2 B = 2,560 B = 80 beats; 1 GHz / (36 fps x 1,000 lines)
    = 27,777.8 -> a 27,778-cycle line time, the generator's period and the
    deadline."""
    cad = camera_line_cadence(1280, 2, 36.0, 1000, 1e9)
    assert cad["params"] == {"line_beats": 80, "frame_lines": 1}
    assert cad["deadline"] == 27_778
    assert cad["rate"] == pytest.approx(80 / 27_778, rel=1e-15)
    assert int(np.ceil(80 / cad["rate"])) == 27_778
    with pytest.raises(ValueError, match="whole number of 16-beat bursts"):
        camera_line_cadence(1000, 2, 36.0, 1000, 1e9)
    with pytest.raises(ValueError, match="cannot be written"):
        camera_line_cadence(1280, 2, 36.0, 1000, 1e6)


def test_bench_config_is_the_preset():
    sc = adas_camera_suite_qos()
    assert [MasterSpec(**m) for m in CONFIG["masters"]] == list(sc.masters)
    assert MemoryGeometry(**CONFIG["geometry"]) == sc.geom
    cams = [m for m in sc.masters if m.model == "camera"]
    assert len(cams) == 8 and {m.txns for m in cams} == {5}
    assert {m.deadline for m in cams} == {27_778}
    assert [m.qos for m in sc.masters] == (["safety"] * 8 + ["realtime"] * 6
                                           + ["besteffort"] * 2)
    assert CONFIG["params"]["reg_rate"] == 64
    assert CONFIG["params"]["reg_burst"] == 16


@pytest.mark.parametrize("stages,collect", [
    pytest.param(None, "exact", id="dense-exact"),
    pytest.param(SCHEDULE_PIPELINE, "stream", id="sched-stream")])
def test_preset_matches_reference(stages, collect):
    """A line time cut to 2,778 cycles (10,000 lines a frame) and a few
    hundred NPU and CPU transactions: the model's outputs and per-class
    summary against the reference's, within the benchmark's limits."""
    sc = adas_camera_suite_qos(
        npu_txns=200, cpu_txns=300,
        camera=dict(HW3_CAMERA, lines_per_frame=10_000))
    sc = replace(sc, masters=[replace(m, seed=m.seed + 1234567)
                              for m in sc.masters])
    prm = SimParams(reg_rate=64, reg_burst=16, max_cycles=4200,
                    stages=stages, collect=collect)
    got = sc.compile().simulate(prm)
    mix = [{"model": m.model, "qos": m.qos, "rate": m.rate, "txns": m.txns,
            "seed": m.seed, "params": m.params, "deadline": m.deadline}
           for m in sc.masters]
    geom = CONFIG["geometry"]
    traffic = masters.build(mix, sc.geom.beats_total)
    want = fabric.simulate(
        traffic, geom,
        fabric.Knobs(**{**CONFIG["params"], "max_cycles": 4200}),
        slots=prm.slots_per_master, collect=collect)
    numbers = compare.compare(
        got.metrics, want, got.per_class,
        summary.per_class(traffic, [m.qos for m in sc.masters], want))
    assert compare.within(numbers), numbers
    assert bool(got.metrics["all_done"])
    assert got.per_class["safety"]["deadline_txns"] == 40
    assert int(got.metrics["reg_held"]) > 0


# ---------------------------------------------------------------------------
# the QoS-mechanism counters on worked traces
# ---------------------------------------------------------------------------

#: aging promotes a waiting beat one level per AGING cycles
AGING = 3
#: burst-1 reads of one bank by one level-1 port
HOT_READS = 8


def _worked_trace() -> Trace:
    """Port 0 (level 1) issues HOT_READS burst-1 reads of one bank at cycle
    0: read k is accepted at cycle k and reaches the bank at 8 + k, and the
    bank, busy 2 cycles a grant, grants it at 8 + 2k, so it has waited k
    cycles; aging has promoted it iff k >= AGING.

    Port 1 (level 2, regulated at 64/256 beats a cycle with a 4-beat
    bucket) issues 4-beat reads due at 0, 0, 0, 2000, 2000 to other banks.
    Its bucket holds one burst: a full bucket takes the first read of each
    group, then the bucket refills in 1024 / 64 = 16 cycles, so each later
    read of a group waits 15 cycles for tokens alone: 2 x 15 + 15 = 45.
    Between the groups nothing is due, which the time skip jumps."""
    g = MemoryGeometry()
    beats = np.arange(20_000)
    bank = flat_bank_id(beats, g)
    hot = beats[bank == bank[100]][:HOT_READS]
    cold = 40_000
    assert bank[100] not in set(flat_bank_id(np.arange(cold, cold + 4), g))
    N = HOT_READS
    start1 = [0, 0, 0, 2000, 2000] + [0] * (N - 5)
    return Trace(is_write=np.zeros((2, N), np.int32),
                 burst=np.array([[1] * N, [4] * 5 + [0] * (N - 5)]),
                 addr=np.array([hot, [cold] * N]),
                 start=np.array([[0] * N, start1]),
                 prio=np.array([1, 2]))


@pytest.mark.parametrize("time_skip", [True, False], ids=["skip", "noskip"])
@pytest.mark.parametrize("stages,collect", VARIANTS)
def test_qos_counters_worked_trace(stages, collect, time_skip):
    prm = SimParams(max_cycles=2600, qos_aging=AGING, reg_rate=64,
                    reg_burst=4, stages=stages, collect=collect,
                    time_skip=time_skip)
    out = simulate(_worked_trace(), prm)
    assert bool(out["all_done"])
    if collect == "exact":      # the worked timeline of port 0's reads
        k = np.arange(HOT_READS)
        np.testing.assert_array_equal(out["accept_cycle"][0], k)
        # granted at 8 + 2k, returned 4 cycles later, complete 9 after that
        np.testing.assert_array_equal(out["complete_cycle"][0], 21 + 2 * k)
        np.testing.assert_array_equal(out["accept_cycle"][1, :5],
                                      [0, 16, 32, 2000, 2016])
    assert int(out["aged_grants"]) == HOT_READS - AGING
    assert int(out["reg_held"]) == 45
    skipped = int(out["skipped_cycles"])
    assert skipped > 0 if (time_skip and stages) else skipped == 0


def test_qos_counters_idle_without_mechanisms(rng):
    """With the regulator off and every port at one level, neither counter
    moves: aging cannot lift a level-0 beat, and no port is regulated."""
    t = Trace(is_write=rng.integers(0, 2, (4, 6)),
              burst=rng.integers(1, 9, (4, 6)),
              addr=rng.integers(0, 3000, (4, 6)))
    out = simulate(t, SimParams(max_cycles=1500, qos_aging=1))
    assert int(out["aged_grants"]) == 0 and int(out["reg_held"]) == 0
