"""Scenario fuzzer: sampling, round-trip, determinism, oracles, shrinking.

Everything here runs on the cheapest geometry (``small16``) under one shared
padding envelope so the whole module compiles a single batched program.
"""
import json
import os
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest

from repro.core.traffic import pad_trace
from repro.scenarios.fuzz import (FuzzConfig, case_from_json, case_to_json,
                                  evaluate_cases, run_fuzz, sample_case)
from repro.scenarios.properties import (PropertyContext, oracle_conservation,
                                        oracle_deadline_misses,
                                        oracle_isolation,
                                        oracle_metric_sanity,
                                        oracle_no_starvation)
from repro.scenarios.spec import QOS_CLASSES

#: one envelope for the whole module — every evaluation below shares it (and
#: therefore one compiled program)
ENV = (6, 16)
CFG = FuzzConfig(seed=5, budget=4, chunk=8, geometries=("small16",),
                 max_masters=ENV[0], txns_hi=ENV[1], max_cycles=6000)


@pytest.fixture(scope="module")
def evaluated():
    cases = [sample_case(CFG, i) for i in range(CFG.budget)]
    return cases, evaluate_cases(cases, CFG, envelope=ENV)


def _ctx(case, result, **over):
    """Rebuild the PropertyContext evaluate_cases used (envelope-padded)."""
    comp = case.scenario.compile()
    wrap = replace(comp, trace=pad_trace(comp.trace, *ENV))
    kw = dict(compiled=wrap, params=case.params, result=result)
    kw.update(over)
    return PropertyContext(**kw)


# ---------------------------------------------------------------------------
# sampling + serialization
# ---------------------------------------------------------------------------

def test_sampled_specs_valid_and_deterministic():
    cfg = FuzzConfig(seed=3, budget=0)
    for i in range(12):
        a, b = sample_case(cfg, i), sample_case(cfg, i)
        assert case_to_json(a) == case_to_json(b)   # index-keyed determinism
        a.scenario.validate()
        assert cfg.min_masters <= len(a.scenario.masters) <= cfg.max_masters
        for m in a.scenario.masters:
            assert m.qos in QOS_CLASSES
            assert 1 <= m.txns <= cfg.txns_hi
            assert 0 < m.rate <= 1.0
        assert a.params.slots_override is not None


def test_sampling_covers_the_spec_space():
    cfg = FuzzConfig(seed=3, budget=0, plant_rate=0.3)
    cases = [sample_case(cfg, i) for i in range(64)]
    assert {c.geometry for c in cases} == set(cfg.geometries)
    assert any(c.planted for c in cases) and not all(c.planted for c in cases)
    assert any(m.region is not None
               for c in cases for m in c.scenario.masters)
    assert any(m.slice_affinity is not None
               for c in cases for m in c.scenario.masters)
    assert any(m.deadline is not None and m.deadline >= cfg.deadline_floor
               for c in cases for m in c.scenario.masters)
    models = {m.model for c in cases for m in c.scenario.masters}
    assert models >= {"camera", "radar", "lidar", "npu", "cpu", "uniform"}


def test_case_json_round_trip(tmp_path):
    case = sample_case(FuzzConfig(seed=11, budget=0), 4)
    path = tmp_path / "case.json"
    path.write_text(json.dumps(case_to_json(case)))
    loaded = case_from_json(json.loads(path.read_text()))
    assert case_to_json(loaded) == case_to_json(case)
    assert loaded.geometry == case.geometry
    assert loaded.params.static_key() == case.params.static_key()


def test_case_from_json_rejects_unknown_format():
    with pytest.raises(ValueError, match="format"):
        case_from_json({"format": 99})


# ---------------------------------------------------------------------------
# evaluation + determinism
# ---------------------------------------------------------------------------

def test_clean_specs_pass_and_verdicts_are_deterministic(evaluated):
    cases, res1 = evaluated
    assert len(res1) == len(cases)
    res2 = evaluate_cases(cases, CFG, envelope=ENV)
    for r1, r2 in zip(res1, res2):
        assert [v.oracle for v in r1.violations] \
            == [v.oracle for v in r2.violations]
        assert int(r1.result.metrics["drained_cycle"]) \
            == int(r2.result.metrics["drained_cycle"])
        np.testing.assert_array_equal(r1.result.metrics["txns_done_port"],
                                      r2.result.metrics["txns_done_port"])


def test_run_fuzz_is_deterministic_across_runs():
    out1 = run_fuzz(CFG, shrink=False)
    out2 = run_fuzz(CFG, shrink=False)
    assert out1.evaluated == out2.evaluated == CFG.budget
    def key(o):
        return [(r.case.index, sorted(v.oracle for v in r.violations))
                for r in o.violating]
    assert key(out1) == key(out2)
    assert not out1.truncated


# ---------------------------------------------------------------------------
# oracle unit tests (tampered metrics must trip the right oracle)
# ---------------------------------------------------------------------------

def test_oracle_conservation_catches_over_retire(evaluated):
    cases, results = evaluated
    case, res = cases[0], results[0]
    assert not res.violations
    tdp = np.array(res.result.metrics["txns_done_port"], copy=True)
    tdp[0, 0] += 1                      # one phantom retired transaction
    bad = replace(res.result, metrics={**res.result.metrics,
                                       "txns_done_port": tdp})
    v = oracle_conservation(_ctx(case, bad))
    assert v and v[0].oracle == "conservation"
    assert "more transactions" in v[0].message


def test_oracle_conservation_catches_lost_txns_at_drain(evaluated):
    cases, results = evaluated
    case, res = cases[0], results[0]
    assert int(res.result.metrics["drained_cycle"]) >= 0
    tdp = np.array(res.result.metrics["txns_done_port"], copy=True)
    tdp[0] = 0                          # a master's work vanished
    bad = replace(res.result, metrics={**res.result.metrics,
                                       "txns_done_port": tdp})
    assert any("fewer transactions" in v.message
               for v in oracle_conservation(_ctx(case, bad)))


def test_oracle_metric_sanity_catches_inconsistent_counters(evaluated):
    cases, results = evaluated
    case, res = cases[0], results[0]
    cycles = int(res.result.metrics["cycles"])
    bad = replace(res.result, metrics={
        **res.result.metrics,
        "drained_cycle": np.int32(cycles + 5),    # after the run ended
        "read_throughput": np.full_like(
            np.asarray(res.result.metrics["read_throughput"]), 1.5)})
    msgs = [v.message for v in oracle_metric_sanity(_ctx(case, bad))]
    assert any("drained_cycle" in m for m in msgs)
    assert any("read_throughput exceeds 1 beat/cycle" in m for m in msgs)


def test_oracle_no_starvation_catches_a_silent_master(evaluated):
    cases, results = evaluated
    case, res = cases[0], results[0]
    ctx = _ctx(case, res.result)
    horizon = case.params.max_cycles
    early = np.flatnonzero(
        (ctx.offered() > 0)
        & (ctx.first_start() <= 0.25 * horizon))
    assert early.size, "fixture case has no early-start master"
    tdp = np.array(res.result.metrics["txns_done_port"], copy=True)
    tdp[early[0]] = 0                   # starve one early master
    bad = replace(res.result, metrics={**res.result.metrics,
                                       "txns_done_port": tdp,
                                       "drained_cycle": np.int32(-1)})
    v = oracle_no_starvation(_ctx(case, bad))
    assert v and int(early[0]) in v[0].details["starved_masters"]


def test_oracle_deadline_misses_catches_excess_misses(evaluated):
    cases, results = evaluated
    case, res = cases[0], results[0]
    stats = {"deadline_txns": 10, "deadline_misses": 5,
             "deadline_miss_rate": 0.5}
    bad = replace(res.result, per_class={"safety": stats})
    ctx = _ctx(case, bad, params=replace(case.params, qos_aging=64))
    v = oracle_deadline_misses(ctx)
    assert v and v[0].details["class"] == "safety"


def test_oracle_isolation_catches_latency_blowup(evaluated):
    cases, results = evaluated
    case, res = cases[0], results[0]
    full = replace(res.result, per_class={"safety": {"read_lat_p99": 9000.0,
                                                     "write_lat_p99": 10.0}})
    alone = replace(res.result, per_class={"safety": {"read_lat_p99": 12.0,
                                                      "write_lat_p99": 9.0}})
    ctx = _ctx(case, full, alone=alone,
               params=replace(case.params, qos_aging=64, reg_rate=8))
    v = oracle_isolation(ctx)
    assert v and v[0].details["metric"] == "read_lat_p99"
    # within the bound -> silent
    ctx.result = replace(res.result,
                         per_class={"safety": {"read_lat_p99": 20.0,
                                               "write_lat_p99": 9.0}})
    assert not oracle_isolation(ctx)


# ---------------------------------------------------------------------------
# planted violations: found within budget, shrunk to a minimal reproducer
# ---------------------------------------------------------------------------

def test_planted_violation_found_and_shrunk():
    cfg = replace(CFG, seed=7, budget=2, plant_rate=1.0, shrink_limit=1)
    outcome = run_fuzz(cfg)
    assert outcome.violating, "planted violation not found within budget"
    worst = outcome.violating[0].violations[0]
    assert worst.oracle == "deadline_misses"
    rep = outcome.reproducers[0]
    assert rep["shrunk"]["masters"] <= 3
    assert "deadline_misses" in rep["verdict"]["violated_oracles"]
    # the reproducer is a valid, replayable spec
    loaded = case_from_json(json.loads(json.dumps(rep["case"])))
    final = evaluate_cases([loaded], cfg, envelope=ENV)[0]
    assert any(v.oracle == "deadline_misses" for v in final.violations)


# ---------------------------------------------------------------------------
# driver: exit codes + reproducer artifacts (the CI failure path, in a test)
# ---------------------------------------------------------------------------

def test_fuzz_driver_writes_reproducers_and_fails(tmp_path):
    out_dir = tmp_path / "fuzz"
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.fuzz", "--seed", "7",
         "--budget", "2", "--plant-rate", "1.0", "--shrink-limit", "1",
         "--max-cycles", "6000", "--geometries", "small16",
         "--out-dir", str(out_dir), "--quiet"],
        capture_output=True, text=True, cwd=Path(__file__).parent.parent,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")})
    assert proc.returncode == 1, proc.stderr[-2000:]
    summary = json.loads((out_dir / "fuzz_summary.json").read_text())
    assert summary["violations"] >= 1
    reps = sorted(out_dir.glob("reproducer_*.json"))
    assert reps, "no reproducer artifacts written"
    rep = json.loads(reps[0].read_text())
    assert case_from_json(rep["case"]).scenario.masters


def test_run_py_registers_fuzz_job():
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--list"],
        capture_output=True, text=True, cwd=Path(__file__).parent.parent,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "fuzz" in proc.stdout.split()
