"""Device time by stage and host time by phase: the attribution rules on
hand-written HLO, the readers on a small scoped trace recorded on a TPU v5e
(``record_scoped_trace.py``), and every new reader silent on the scope-free
trace of ``record_trace.py``."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import stage_time
from bench.harness import Run, reader
from bench.stage_time import (UNSTAGED, host_phase_ms, idle_spans,
                              scope_seconds, stage_of_ops, stage_us_per_step)
from bench.trace_reduce import Reduced, merge, reduce
from bench.workload import Call

REPO = Path(__file__).resolve().parents[2]
DATA = Path(__file__).with_name("data")
SCOPED = DATA / "small_tpu_scoped"
NEW = ["accept_dispatch_us_per_step", "bank_arbitrate_us_per_step",
       "router_release_us_per_step", "return_bus_us_per_step",
       "retire_us_per_step", "unstaged_us_per_step", "prepare_host_ms",
       "fetch_host_ms"]

HLO = """HloModule jit_f, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/while/body/stage.bank_arbitrate/add"}
}

%fused_computation.2 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(f)/while/body/stage.accept_dispatch/mul"}
  ROOT %sub.1 = f32[8]{0} subtract(%mul.1, %param_0.1), metadata={op_name="jit(f)/while/body/stage.retire/sub"}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/stage.return_bus/stage.router_release/neg"}
  %copy.4 = f32[8]{0} copy(%fusion.3)
  ROOT %custom-call.5 = f32[8]{0} custom-call(%copy.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/while/body/stage.bank_arbitrate/pallas_call"}
}
"""


def test_attribution_rules():
    got = stage_of_ops(HLO)
    assert got["fusion.3"] == "router_release"      # own op_name, innermost
    assert got["custom-call.5"] == "bank_arbitrate"
    assert got["fusion.1"] == "bank_arbitrate"      # the one stage it calls
    assert got["fusion.2"] == UNSTAGED              # two stages inside
    assert got["copy.4"] == UNSTAGED                # no scope
    assert got["x"] == UNSTAGED


def test_buckets_sum_to_the_leaf_total():
    ops = {"%fusion.1": 1.0, "%fusion.3": 0.5, "%copy.4": 0.25,
           "%fusion.99": 0.125}                     # not in this program
    got = scope_seconds(ops, stage_of_ops(HLO))
    assert got == {"bank_arbitrate": 1.0, "router_release": 0.5,
                   UNSTAGED: 0.375, "retire": 0.0, "accept_dispatch": 0.0}
    assert sum(got.values()) == sum(ops.values())


def test_idle_spans_name_each_gap():
    busy = [merge(np.array([10, 50]), np.array([40, 90]))]
    events = [(0, 100, "bench.call"), (5, 95, "repro.simulate"),
              (5, 12, "repro.prepare"), (45, 95, "repro.fetch"),
              (100, 120, "$time sleep")]
    r = Reduced((0, 120), busy, {"bench.call": [(0, 100)]}, {}, events)
    assert idle_spans(r) == {"repro.prepare": 10e-9, "repro.fetch": 10e-9,
                             "between calls": 30e-9}
    assert sum(idle_spans(r).values()) == pytest.approx(
        r.window_s - r.busy_s)


def _calls(n, steps):
    return [Call(i, [{}], 0, points=[{"effective_cycles": np.int32(steps),
                                      "skipped_cycles": np.int32(0)}])
            for i in range(n)]


def _run(path, monkeypatch, text):
    import jax
    r = reduce(jax.profiler.ProfileData.from_file(str(path)))
    monkeypatch.setattr(stage_time, "program_text", lambda run: text)
    return Run(_calls(2, 300), r.window_s, 0.0, r, 2)


def test_recorded_scoped_trace(monkeypatch):
    text = (DATA / "small_tpu_scoped.hlo.txt").read_text()
    run = _run(f"{SCOPED}.xplane.pb", monkeypatch, text)
    got = stage_time.device_scopes(run)
    assert set(got) == {"first", "second", UNSTAGED}
    assert got["first"] > got["second"] > 0
    leaf = sum(run.trace.op_seconds.values())
    assert sum(got.values()) == pytest.approx(leaf)
    assert leaf <= run.trace.busy_s * (1 + 1e-9)
    per_step = {b: stage_us_per_step(run, b) for b in got}
    assert sum(per_step.values()) == pytest.approx(1e6 * leaf / 600)
    assert stage_us_per_step(run, "bank_arbitrate") is None
    # the fetch span holds the loop: its host share is what the device
    # left idle inside it, within the call's own
    fetch = host_phase_ms(run, "repro.fetch")
    assert 0 < fetch <= reader(REPO, "call_host_ms")(run)
    assert host_phase_ms(run, "repro.prepare") is None


def test_new_readers_silent_without_scopes_or_spans(monkeypatch):
    import jax
    import jax.numpy as jnp
    # a program that names no stage scope
    text = jax.jit(lambda x: jnp.tanh(x @ x)).lower(
        jnp.ones((8, 8))).compile().as_text()
    run = _run(DATA / "small_tpu.xplane.pb", monkeypatch, text)
    assert run.trace.busy
    for name in NEW:
        assert reader(REPO, name)(run) is None, name


def test_new_readers_in_benchmark_json():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    got = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW:
        assert got[name]["workloads"] == ["fig4_full_b1"]
        assert got[name]["moves"] == "sim_cycles_per_s"
        assert (REPO / "bench" / "metrics" / f"{name}.py").is_file()


def test_program_text_is_the_cells_program(monkeypatch):
    """The cell named on the command line, its first traced call compiled
    again: every stage of its pipeline names operations."""
    from bench.harness import cell_parts, load_spec
    from bench.workload import Workload
    from repro.core.simulator import DEFAULT_PIPELINE
    monkeypatch.setattr(sys, "argv", ["bench/run.py", "--workload",
                                      "fig4_full_b1", "--trace", "1"])
    _, config, mix, _, _ = cell_parts(REPO, load_spec(REPO), "fig4_full_b1")
    wl = Workload(config, mix, 5, 1)
    c = wl.call(0)
    c.fig4 = wl.fig4_traffic(c)
    run = Run([c], 1.0, 0.0, None, 1)
    assert set(stage_of_ops(stage_time.program_text(run)).values()) == {
        *DEFAULT_PIPELINE, UNSTAGED}
    monkeypatch.setattr(sys, "argv", ["bench/run.py"])
    assert stage_time.program_text(run) is None


def test_program_text_ignores_a_scope_free_twin_in_the_cache(
        tmp_path, monkeypatch):
    """The persistent compile cache keys programs without their metadata:
    a twin compiled without the scopes, cached first and then loaded for
    the program's own calls, must not answer for the program the readers
    map."""
    import contextlib

    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from bench.harness import cell_parts, load_spec
    from bench.workload import Workload
    from repro.core import simulator
    _, config, mix, _, _ = cell_parts(REPO, load_spec(REPO), "fig4_full_b1")
    wl = Workload(config, mix, 5, 1)
    c = wl.call(0)
    c.fig4 = wl.fig4_traffic(c)
    run = Run([c], 1.0, 0.0, None, 1)
    monkeypatch.setattr(sys, "argv", ["bench/run.py", "--workload",
                                      "fig4_full_b1"])
    opts = {"jax_enable_compilation_cache": True,
            "jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0}
    was = {k: getattr(jax.config, k) for k in opts}
    for k, v in opts.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    simulator._core_jitted_cached.cache_clear()
    try:
        with monkeypatch.context() as m:
            m.setattr(jax, "named_scope",
                      lambda name: contextlib.nullcontext())
            t = c.fig4
            twin = simulator.compile_simulate(
                simulator.Trace(t["is_write"], t["burst"], t["addr"],
                                t["start"], t["prio"]), wl.params(c)[0])
            assert "stage." not in twin.compiled.as_text()
        simulator._core_jitted_cached.cache_clear()
        assert list(tmp_path.iterdir())            # the twin is cached
        loaded = simulator.compile_simulate(       # as the warm call does
            simulator.Trace(t["is_write"], t["burst"], t["addr"],
                            t["start"], t["prio"]), wl.params(c)[0])
        assert "stage." not in loaded.compiled.as_text()
        stages = set(stage_of_ops(stage_time.program_text(run)).values())
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        simulator._core_jitted_cached.cache_clear()
    assert stages > {UNSTAGED}
