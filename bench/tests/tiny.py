"""A tiny benchmark tree for CPU tests: a 16-bank fabric, two master mixes
and a knob grid, with the real metric readers copied in."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

GEOMETRY = {"num_masters": 4, "num_clusters": 2, "arrays_per_cluster": 2,
            "banks_per_array": 4, "sub_banks": 4, "beat_bytes": 32,
            "total_bytes": 1 << 20, "num_slices": 1}
PARAMS = {"outstanding": 4, "split_buffer": 32, "cmd_latency": 8,
          "ret_latency": 9, "bank_occupancy": 2, "bank_latency": 2,
          "qos_aging": 128, "reg_rate": 0, "reg_burst": 16,
          "expand_rate": 4, "max_burst": 16, "max_cycles": 1500}
CONFIGS = {
    "tiny_fig4": {"geometry": GEOMETRY, "params": dict(PARAMS, max_burst=8),
                  "fig4_traffic": {"masters": 2, "txns": 20, "burst": 8,
                                   "read_fraction": 0.5}},
    "tiny_soc": {"geometry": GEOMETRY, "params": PARAMS, "masters": [
        {"model": "camera", "qos": "safety", "rate": 0.8, "txns": 24,
         "seed": 0, "deadline": 300},
        {"model": "npu", "qos": "realtime", "rate": 1.0, "txns": 24,
         "seed": 20},
        {"model": "cpu", "qos": "besteffort", "rate": 0.3, "txns": 24,
         "seed": 30}]},
}
MIXES = {
    "b1": {"points_per_call": 1, "check_points": 1, "trace_seconds": 2},
    "grid": {"points_per_call": 4, "pipeline": "schedule",
             "collect": "stream", "check_points": 2, "trace_seconds": 2,
             "grid_axes": {"outstanding": [2, 3, 4],
                           "bank_occupancy": [1, 2], "qos_aging": [0, 64],
                           "reg_rate": [0, 32]}},
}
CELLS = [("fig4_b1", "tiny_fig4", "b1"), ("soc_grid", "tiny_soc", "grid"),
         ("soc_b1", "tiny_soc", "b1")]


def make_root(tmp: Path, cells=CELLS, configs=CONFIGS, mixes=MIXES,
              per_layer=None) -> Path:
    """Write BENCHMARK.json and the data files under ``tmp``."""
    shutil.copytree(BENCH / "metrics", tmp / "bench" / "metrics")
    for sub, items in (("configs", configs), ("traffic", mixes)):
        (tmp / "bench" / sub).mkdir(parents=True)
        for name, data in items.items():
            (tmp / "bench" / sub / f"{name}.json").write_text(
                json.dumps(dict(data, name=name)))
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": n, "source": "test", "reduced": [],
                        "file": f"bench/configs/{n}.json", "why": "test"}
                       for n in configs]
    spec["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": 1,
                          "why": "test"} for n, c, t in cells]
    names = {n for n, _, _ in cells}
    for m in spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted(names)
    spec["per_layer"] += per_layer or []
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
