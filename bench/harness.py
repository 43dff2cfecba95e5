"""Run one cell of ``BENCHMARK.json``: set up, measure, check, report.

Everything a cell needs is found by the names ``BENCHMARK.json`` gives:
the configuration file it names, ``bench/traffic/<traffic>.json`` and one
reader ``bench/metrics/<metric>.py`` per metric the cell reports.  No name
is written into this code.

A run:

1. Set-up (``setup_s``, from process start): JAX and the device, the
   persistent compile cache inside the checkout, and one warm call of the
   cell's own shapes.
2. The window: calls back to back until ``--seconds`` have passed; the call
   in flight is finished.  Nothing may compile here; the count of compiles
   JAX reports inside the window is printed.
3. With ``--trace 1`` the profiler records the calls that start within the
   traffic mix's ``trace_seconds`` of the window's start (at least one
   call: the profiler takes some 30 s a million device operations to stop,
   and a cycle loop makes some 230k a second), and the per-layer metrics
   are read from the counters, the host spans and the reduced device
   trace; with ``--trace 0`` the end-to-end metrics are reported.
4. The device's peak memory is read, then sampled points are replayed on
   the plain reference (``bench/check.py``) to decide ``correct``.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, List, Optional

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclass
class Run:
    """What a metric reader sees: the window's calls, its length on the
    host clock, the set-up time and, when traced, the reduced trace and
    the calls it covers (the first ``traced`` of ``calls``)."""
    calls: list
    window_s: float
    setup_s: float
    trace: Optional[object] = None
    traced: int = 0

    @property
    def traced_calls(self) -> list:
        return self.calls[:self.traced]


def load_spec(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell_parts(root: Path, spec: dict, name: str):
    """(cell, configuration data, traffic mix data, metrics to report)."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; have "
                         f"{sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in moved
                              else [])]
    return cell, config, mix, e2e, layer


def reader(root: Path, metric: str) -> Callable[[Run], Optional[float]]:
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCount:
    """Programs JAX compiled or loaded from its cache, and the seconds."""

    def __init__(self):
        import jax
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.n += 1
            self.seconds += duration


def pin_one_chip(chips: int) -> None:
    """A one-chip cell uses one chip even on a larger host (before JAX)."""
    if chips == 1:
        for var, val in (("TPU_VISIBLE_CHIPS", "0"),
                         ("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1"),
                         ("TPU_PROCESS_BOUNDS", "1,1,1")):
            os.environ.setdefault(var, val)


def measure(root: Path, name: str, seed: int, seconds: float, trace: bool,
            t0: float, log=sys.stderr) -> dict:
    """One run of cell ``name``; returns the result line's object."""
    import jax
    from bench import check
    from bench.reference import compare
    from bench.workload import Workload
    from repro.compile_cache import use_compile_cache

    spec = load_spec(root)
    cell, config, mix, e2e, layer = cell_parts(root, spec, name)
    devices = jax.devices()
    cache = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileCount()
    wl = Workload(config, mix, seed, len(devices))

    wl.run(wl.call(0))                               # warm the cell's shapes
    clock = time.perf_counter
    start = clock()
    setup_s = start - t0
    warm = compiles.n
    print(f"set-up {setup_s!r} s: {warm} program(s) compiled or loaded in "
          f"{compiles.seconds!r} s, cache {cache}", flush=True)

    tracer, reduced, traced = None, None, 0
    trace_s = float(mix["trace_seconds"])
    if trace:
        from bench.trace_reduce import Tracer
        tracer = Tracer(Path(tempfile.mkdtemp(prefix="bench_trace_")))
        tracer.start()
        start = clock()
    calls: List = []
    while not calls or clock() - start < seconds:
        if tracer is not None and calls and clock() - start >= trace_s:
            reduced, traced, tracer = tracer.stop(), len(calls), None
        annotate = jax.profiler.TraceAnnotation if tracer else None
        calls.append(wl.run(wl.call(len(calls)), clock, annotate))
    end = clock()
    if tracer is not None:
        reduced, traced = tracer.stop(), len(calls)
    in_window = compiles.n - warm
    print(f"compiles inside the window: {in_window}", flush=True)
    print("call seconds: " + " ".join(
        f"{c.spans['call'][1] - c.spans['call'][0]:.4f}" for c in calls),
        flush=True)

    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    run = Run(calls, end - start, setup_s, reduced, traced)
    metrics = {}
    for m in (layer if trace else e2e):
        value = reader(root, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    numbers, failed = check.check(wl, calls, seed)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": bool(numbers) and failed == 0,
              "attempted": int(sum(len(c.points) for c in calls)),
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None and reduced.busy:
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["breakdown"] = reduced.breakdown()
    checks = {k: {"value": v, "limit": compare.LIMITS[k]}
              for k, v in numbers.items()}
    for k, v in checks.items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=log, flush=True)
    result["checks"] = checks
    return result


def main(argv=None, t0: Optional[float] = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[1]
    if not (root / "src" / "repro").is_dir():
        print(f"bench: the model's sources are not in {root / 'src'}",
              file=sys.stderr)
        return 2
    spec = load_spec(root)
    chips = {w["name"]: w["chips"] for w in spec["workloads"]}.get(
        args.workload)
    if chips is None:
        print(f"bench: no workload {args.workload!r}", file=sys.stderr)
        return 2
    pin_one_chip(chips)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != chips:
        print(f"bench: {args.workload} needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    result = measure(root, args.workload, args.seed, args.seconds,
                     bool(args.trace), t0)
    print(json.dumps(result), flush=True)
    return 0
