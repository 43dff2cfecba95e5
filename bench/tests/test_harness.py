"""The harness on the CPU at tiny sizes: cells found by name, a run that
finds no TPU, the control, and runs whose timed path is broken underneath
coming out not correct."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench.tests.tiny import CELLS, CONFIGS, MIXES, make_root

REPO = Path(__file__).resolve().parents[2]


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _measure(root, cell, seed=20260101, trace=False):
    from bench.harness import measure
    return measure(root, cell, seed, 0.5, trace, time.perf_counter())


def _fresh_programs():
    """Drop the model's compiled entry points so a patched stage is
    traced anew."""
    from repro.core import simulator
    for name in dir(simulator):
        fn = getattr(simulator, name)
        if name.endswith("_cached") and hasattr(fn, "cache_clear"):
            fn.cache_clear()


def test_new_config_traffic_and_metric_found_by_name(tmp_path):
    configs = dict(CONFIGS, brand_new_soc=CONFIGS["tiny_soc"])
    mixes = dict(MIXES, brand_new_mix=MIXES["grid"])
    root = make_root(tmp_path, cells=[("brand_new_cell", "brand_new_soc",
                                       "brand_new_mix")],
                     configs=configs, mixes=mixes,
                     per_layer=[{"name": "lanes_per_call", "unit": "lanes",
                                 "better": "higher",
                                 "source": "program_counter",
                                 "layer": "batch", "moves": "setup_s",
                                 "workloads": ["brand_new_cell"]}])
    (root / "bench" / "metrics" / "lanes_per_call.py").write_text(
        "def read(run):\n"
        "    return sum(c.lanes for c in run.calls) / len(run.calls)\n")
    r = _measure(root, "brand_new_cell", trace=True)
    assert r["correct"], r["checks"]
    assert r["metrics"]["lanes_per_call"]["value"] == 4
    assert "stepped_share" in r["metrics"]
    assert list(r)[-1] == "checks"
    r = _measure(root, "brand_new_cell")
    assert set(r["metrics"]) == {"sim_cycles_per_s", "setup_s"}


@pytest.mark.parametrize("cell", [c[0] for c in CELLS])
def test_sound_run_is_correct(tmp_path, cell):
    r = _measure(make_root(tmp_path), cell)
    assert r["correct"] and r["failed"] == 0, r["checks"]
    assert r["attempted"] >= 1
    assert r["metrics"]["sim_cycles_per_s"]["value"] > 0


def _state_unchanged(monkeypatch):
    """Every cycle returns the state as it came, the clock aside."""
    from repro.core import simulator

    def pipeline(prm, ctx):
        return lambda st, _: (st.replace(now=st.now + 1), None)
    monkeypatch.setattr(simulator, "_pipeline_cycle", pipeline)


def _half_batch(monkeypatch):
    """A batch runs its first half only and repeats it for the rest."""
    from repro.scenarios import sweep
    full = sweep.simulate_batch

    def half(traces, prms, **kw):
        h = max(len(prms) // 2, 1)
        out = full(traces, prms[:h], **kw)
        return {k: np.concatenate([v] * -(-len(prms) // h))[:len(prms)]
                for k, v in out.items()}
    monkeypatch.setattr(sweep, "simulate_batch", half)


def _beat_altered(monkeypatch):
    """Returned read beats are counted twice where the return bus
    produces them."""
    from repro.core import simulator
    bus = simulator.STAGE_REGISTRY["return_bus"]

    def altered(st, wires, c):
        st, wires = bus(st, wires, c)
        extra = wires["ret"]["ret_any"].astype(st.beats_done.dtype)
        return st.replace(beats_done=st.beats_done + extra), wires
    monkeypatch.setitem(simulator.STAGE_REGISTRY, "return_bus", altered)


FAULTS = {"state_unchanged": (_state_unchanged, [c[0] for c in CELLS]),
          "half_batch": (_half_batch, ["soc_grid"]),
          "beat_altered": (_beat_altered, [c[0] for c in CELLS])}


@pytest.mark.parametrize("fault,cell", [(f, c) for f, (_, cells)
                                        in FAULTS.items() for c in cells])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault,
                                          cell):
    FAULTS[fault][0](monkeypatch)
    _fresh_programs()
    try:
        r = _measure(make_root(tmp_path), cell)
    finally:
        monkeypatch.undo()
        _fresh_programs()
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", [c[0] for c in CELLS])
def test_control_is_not_correct(tmp_path, cell):
    from bench.control import readings
    for seed in (1, 2, 3):
        for r in readings(make_root(tmp_path / str(seed)), cell, seed):
            assert not r["within"], r


def _run_py(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run_py(REPO, "--workload", "fig4_full_b1", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for path in spec["paths"]:
        shutil.copytree(REPO / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path, "--workload", "fig4_full_b1", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_grid_sharded_over_four_devices(tmp_path):
    """The four-chip path (the batch sharded over four devices) on four
    virtual CPU devices, in a process of its own."""
    root = make_root(tmp_path, cells=[("grid4", "tiny_soc", "grid4")],
                     mixes=dict(MIXES, grid4=dict(MIXES["grid"],
                                                  points_per_call=8)))
    code = ("import json, sys, time; from pathlib import Path; "
            "from bench.harness import measure; "
            f"r = measure(Path({str(root)!r}), 'grid4', 7, 0.5, False, "
            "time.perf_counter()); print(json.dumps(r))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{REPO / 'src'}{os.pathsep}{REPO}",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache4"))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["device"]["count"] == 4
    assert r["correct"], r["checks"]
