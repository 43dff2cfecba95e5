"""Declarative ADAS scenario engine (paper §II-C, Figs. 6–7).

A :class:`~repro.scenarios.spec.Scenario` composes per-master traffic sources
(camera frame DMA, Radar chirps, Lidar scatter, AI-accelerator tiles, CPU
scatter — or recorded LLM-serving streams) with QoS classes, memory-region
placement, and injection rates.  Every workload goes through one interface:
``TrafficSource.emit → Scenario.compile() → CompiledScenario.simulate`` (or
``.simulate_batch`` for a parameter grid as one compiled ``vmap``-ed scan);
``scenarios.sweep.run_sweep`` does the same for scenario × parameter grids.
"""
from repro.scenarios.spec import (CompiledScenario, MasterSpec, Scenario,
                                  SyntheticSource, TrafficSource,
                                  QOS_CLASSES, QOS_PRIORITY, compile_scenario)
from repro.scenarios.generators import GENERATORS
from repro.scenarios.library import (adas_camera_suite_qos,
                                     camera_line_master, highway_pilot,
                                     parking_surround,
                                     preset_scenarios, qos_isolation,
                                     sensor_stress, slice_scaling,
                                     urban_perception)
from repro.scenarios.serving import ServingSource, serving_scenario
from repro.scenarios.sweep import (DEPRECATED_METRIC_KEYS, MetricAliasDict,
                                   SweepPoint, SweepResult, run_sweep,
                                   summarize_compiled, summarize_point)
from repro.scenarios.fuzz import (FuzzCase, FuzzConfig, FuzzOutcome,
                                  case_from_json, case_to_json,
                                  evaluate_cases, load_reproducer,
                                  replay_case, run_fuzz, sample_case,
                                  shrink_case)
from repro.scenarios.properties import (ORACLES, OracleBounds,
                                        PropertyContext, Violation,
                                        check_properties)
from repro.serving.record import record_serving_run

__all__ = [
    "CompiledScenario", "MasterSpec", "Scenario", "SyntheticSource",
    "TrafficSource", "QOS_CLASSES", "QOS_PRIORITY", "compile_scenario",
    "GENERATORS", "DEPRECATED_METRIC_KEYS", "MetricAliasDict", "SweepPoint",
    "SweepResult", "run_sweep", "summarize_compiled", "summarize_point",
    "ServingSource", "serving_scenario", "record_serving_run",
    "adas_camera_suite_qos", "camera_line_master",
    "highway_pilot", "parking_surround", "preset_scenarios", "qos_isolation",
    "sensor_stress", "slice_scaling", "urban_perception",
    "FuzzCase", "FuzzConfig", "FuzzOutcome", "case_from_json", "case_to_json",
    "evaluate_cases", "load_reproducer", "replay_case", "run_fuzz",
    "sample_case", "shrink_case", "ORACLES", "OracleBounds",
    "PropertyContext", "Violation", "check_properties",
]
