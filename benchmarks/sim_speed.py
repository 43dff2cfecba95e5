"""Raw simulator speed: simulated-cycles/sec × batch width.

The ROADMAP's "make cycles/sec a first-class benchmark" item: every open
direction (100k-point DSE grids, scenario fuzzing, NoC topologies, serving
co-sim at thousands of requests) is gated on how fast one ``lax.scan`` cycle
body runs.  This benchmark measures it directly:

  * a fixed random full-duplex workload (`core.traffic.random_uniform`) is
    replicated to each batch width and run through ``simulate_batch`` — the
    same vmapped-scan path every sweep uses;
  * the first call is timed as ``compile_s`` (JIT) + one steady run, the
    second call (warm jit cache, fresh host->device input buffers) is
    ``run_s``;
  * ``cycles_per_sec = batch * max_cycles / run_s`` — *simulated* fabric
    cycles per wall-clock second, the number that decides how big a grid is
    affordable.

Standalone usage (CI gate + artifact)::

  PYTHONPATH=src python -m benchmarks.sim_speed           # write BENCH_sim_speed.json
  PYTHONPATH=src python -m benchmarks.sim_speed --check   # fail on >20% regression

``--check`` compares against the committed ``BENCH_sim_speed.json`` at the
repo root and exits non-zero when any batch width's cycles/sec drops below
``(1 - tolerance)`` × baseline (default tolerance 0.20).
"""
from __future__ import annotations

import argparse
import json
import subprocess
import time
from dataclasses import replace
from pathlib import Path
from typing import Dict, Optional, Sequence

import numpy as np

REPO_ROOT = Path(__file__).resolve().parents[1]
BENCH_PATH = REPO_ROOT / "BENCH_sim_speed.json"

#: batch widths reported by default — 64 is the acceptance-gate width
BATCH_WIDTHS = (1, 8, 64)

#: default simulated cycles per measurement — the committed baseline and the
#: CI gate must use the same value (cycles/sec is steady-state and thus
#: nearly cycle-count independent, but keep them identical anyway)
DEFAULT_CYCLES = 400


def _workload(batch: int, masters: int, txns: int, burst: int, seed: int):
    from repro.core.simulator import SimParams
    from repro.core.traffic import random_uniform

    traces = [random_uniform(masters, txns, burst=burst, full_duplex=True,
                             seed=seed + i) for i in range(batch)]
    return traces, SimParams


def measure_point(batch: int, *, masters: int = 8, txns: int = 24,
                  burst: int = 8, max_cycles: int = DEFAULT_CYCLES,
                  seed: int = 0) -> Dict[str, float]:
    """One (batch width) measurement: compile time, steady-state rate, and
    the batch's live memory footprint.

    Returns ``{compile_s, run_s, cycles_per_sec, batch, max_cycles,
    effective_cycles, drained_fraction, input_bytes, carry_bytes}``.
    ``cycles_per_sec`` keeps the NOMINAL ``batch * max_cycles`` numerator so
    baselines stay comparable; ``effective_cycles`` (summed over the batch)
    and ``drained_fraction`` report how much of that horizon the early-exit
    driver actually simulated.  ``input_bytes``/``carry_bytes`` are the peak live
    prepared-input and scan-carry bytes of the whole batch (shape-only
    accounting via ``core.simulator.input_nbytes``/``carry_nbytes`` — the
    quantities a 100k-point grid multiplies).
    """
    import jax

    from repro.core.simulator import (carry_nbytes, input_nbytes,
                                      simulate_batch)

    traces, SimParams = _workload(batch, masters, txns, burst, seed)
    prms = [SimParams(max_cycles=max_cycles)] * batch

    t0 = time.perf_counter()
    jax.block_until_ready(
        jax.tree_util.tree_map(lambda x: x,
                               simulate_batch(traces, prms, shard=False)))
    t1 = time.perf_counter()
    # steady state: warm jit cache, fresh host->device buffers each call
    out = simulate_batch(traces, prms, shard=False)
    jax.block_until_ready(out)
    t2 = time.perf_counter()
    run_s = t2 - t1
    return {
        "batch": batch,
        "max_cycles": max_cycles,
        "compile_s": round(max(t1 - t0 - run_s, 0.0), 3),
        "run_s": round(run_s, 4),
        "cycles_per_sec": round(batch * max_cycles / run_s, 1),
        "effective_cycles": int(np.sum(out["effective_cycles"])),
        "drained_fraction": round(
            float(np.mean(np.asarray(out["drained_cycle"]) >= 0)), 4),
        "input_bytes": sum(input_nbytes(t, p) for t, p in zip(traces, prms)),
        "carry_bytes": sum(carry_nbytes(p, masters, txns) for p in prms),
    }


#: drain-heavy row defaults: frame-cadence workload over a long horizon —
#: most cycles are idle, so this is where early exit + time skip pay off
#: (batch kept small: the fixed-horizon OFF leg scans every cycle)
DRAIN_BATCH = 16
DRAIN_CYCLES = 4000


def measure_drain_heavy(batch: int = DRAIN_BATCH, *, masters: int = 8,
                        txns: int = 24, burst: int = 8,
                        max_cycles: int = DRAIN_CYCLES,
                        seed: int = 0) -> Dict[str, float]:
    """Early-exit win on a drain-heavy workload, pinned as a bench row.

    A frame-cadence batch (``core.traffic.random_bursty``) is run twice —
    early exit + time skip ON vs the fixed horizon OFF — and the row
    records both points/sec rates and their ratio (``speedup``).  The two
    modes are separate compiles (the driver is a static property), timed
    warm, same process.
    """
    import jax

    from repro.core.simulator import SCHEDULE_PIPELINE, SimParams, simulate_batch
    from repro.core.traffic import random_bursty

    traces = [random_bursty(masters, txns, burst=burst, gap=150,
                            seed=seed + i) for i in range(batch)]
    base = SimParams(max_cycles=max_cycles, stages=SCHEDULE_PIPELINE,
                     collect="stream")
    modes = {"on": [base] * batch,
             "off": [replace(base, early_exit=False)] * batch}
    row: Dict[str, float] = {"batch": batch, "max_cycles": max_cycles}
    for name, prms in modes.items():
        jax.block_until_ready(simulate_batch(traces, prms, shard=False))
        t0 = time.perf_counter()
        out = simulate_batch(traces, prms, shard=False)
        jax.block_until_ready(out)
        run_s = time.perf_counter() - t0
        row[f"run_s_{name}"] = round(run_s, 4)
        row[f"points_per_sec_{name}"] = round(batch / run_s, 2)
        if name == "on":
            row["effective_cycles"] = int(np.sum(out["effective_cycles"]))
            row["skipped_cycles"] = int(np.sum(out["skipped_cycles"]))
            row["drained_fraction"] = round(
                float(np.mean(np.asarray(out["drained_cycle"]) >= 0)), 4)
    row["speedup"] = round(row["points_per_sec_on"]
                           / row["points_per_sec_off"], 2)
    return row


def _git_commit() -> str:
    try:
        return subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                              cwd=REPO_ROOT, capture_output=True, text=True,
                              timeout=10).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def sim_speed_bench(batch_widths: Sequence[int] = BATCH_WIDTHS,
                    max_cycles: int = DEFAULT_CYCLES) -> Dict[str, object]:
    """Run every batch width; returns the BENCH_sim_speed.json payload."""
    detail = {}
    for b in batch_widths:
        detail[str(b)] = measure_point(b, max_cycles=max_cycles)
        print(f"# sim_speed batch={b}: "
              f"{detail[str(b)]['cycles_per_sec']:.0f} cycles/s "
              f"(compile {detail[str(b)]['compile_s']:.1f}s, "
              f"run {detail[str(b)]['run_s']:.2f}s, "
              f"drained {detail[str(b)]['drained_fraction']:.0%})")
    drain = measure_drain_heavy()
    print(f"# sim_speed drain-heavy batch={drain['batch']}: "
          f"{drain['points_per_sec_on']:.1f} pts/s with early exit vs "
          f"{drain['points_per_sec_off']:.1f} without "
          f"({drain['speedup']:.1f}x, drained {drain['drained_fraction']:.0%})")
    return {
        "date": time.strftime("%Y-%m-%d"),
        "commit": _git_commit(),
        "cycles_per_sec": {b: detail[b]["cycles_per_sec"] for b in detail},
        "footprint_bytes": {b: detail[b]["input_bytes"]
                            + detail[b]["carry_bytes"] for b in detail},
        "drain_heavy": drain,
        "detail": detail,
    }


def check_regression(new: Dict[str, object],
                     baseline_path: Path = BENCH_PATH,
                     tolerance: float = 0.20) -> Optional[str]:
    """None when every batch width is within ``tolerance`` of the committed
    baseline (or no baseline exists yet); else a human-readable failure.

    Two gates per width: cycles/sec may not DROP more than ``tolerance``
    below baseline, and the live input+carry footprint may not GROW more
    than ``tolerance`` above it (the footprint is deterministic, so any
    growth is a real carry/input regression, not noise)."""
    if not baseline_path.exists():
        return None
    base = json.loads(baseline_path.read_text())
    for width, rate in new["cycles_per_sec"].items():
        old = base.get("cycles_per_sec", {}).get(width)
        if old and rate < (1.0 - tolerance) * float(old):
            return (f"cycles/sec regression at batch {width}: "
                    f"{rate:.0f} < {(1 - tolerance) * float(old):.0f} "
                    f"(baseline {float(old):.0f} from "
                    f"{base.get('commit', '?')} {base.get('date', '?')}, "
                    f"tolerance {tolerance:.0%})")
    for width, nbytes in new.get("footprint_bytes", {}).items():
        old = base.get("footprint_bytes", {}).get(width)
        if old and float(nbytes) > (1.0 + tolerance) * float(old):
            return (f"memory-footprint regression at batch {width}: "
                    f"{nbytes} bytes > "
                    f"{(1 + tolerance) * float(old):.0f} "
                    f"(baseline {float(old):.0f} from "
                    f"{base.get('commit', '?')} {base.get('date', '?')}, "
                    f"tolerance {tolerance:.0%})")
    drain = new.get("drain_heavy", {})
    base_drain = base.get("drain_heavy", {})
    if drain and base_drain:
        rate, old = drain["points_per_sec_on"], base_drain["points_per_sec_on"]
        if rate < (1.0 - tolerance) * float(old):
            return (f"drain-heavy points/sec regression: {rate:.1f} < "
                    f"{(1 - tolerance) * float(old):.1f} "
                    f"(baseline {float(old):.1f} from "
                    f"{base.get('commit', '?')} {base.get('date', '?')})")
    if drain and float(drain.get("speedup", 99.0)) < 1.5:
        return (f"early-exit speedup collapsed on the drain-heavy row: "
                f"{drain['speedup']:.2f}x < 1.5x (the driver should skip "
                f"most of a frame-cadence horizon)")
    return None


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--check", action="store_true",
                    help="fail on >tolerance regression vs the committed "
                         "BENCH_sim_speed.json (which is NOT overwritten)")
    ap.add_argument("--tolerance", type=float, default=0.20)
    ap.add_argument("--out", type=Path, default=BENCH_PATH)
    ap.add_argument("--cycles", type=int, default=DEFAULT_CYCLES)
    ap.add_argument("--widths", type=str, default=None,
                    help="comma-separated batch widths (default 1,8,64)")
    args = ap.parse_args()

    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    widths = (tuple(int(w) for w in args.widths.split(","))
              if args.widths else BATCH_WIDTHS)
    payload = sim_speed_bench(widths, max_cycles=args.cycles)
    if args.check and args.out == BENCH_PATH:
        # never clobber the baseline we are checking against
        args.out = Path("experiments/sim_speed_ci.json")
        args.out.parent.mkdir(exist_ok=True)
    args.out.write_text(json.dumps(payload, indent=1) + "\n")
    print(f"# wrote {args.out}")
    if args.check:
        msg = check_regression(payload, tolerance=args.tolerance)
        if msg:
            raise SystemExit(msg)
        print("# sim_speed: within tolerance of committed baseline")


if __name__ == "__main__":
    main()
