"""Chunked parameter-grid scaling: 100k points on one CPU, flat memory.

The memory story behind the ROADMAP's "100k-point DSE grid" item.  A dense
batched sweep materializes, per point, the prepared input tables AND two
``[X, N]`` per-transaction timestamp columns — a 100k-point grid OOMs on
those long before the compute saturates.  This benchmark runs the same grid
the scale-out way and measures that the footprint stays flat:

  * ONE shared workload (the scenario's packed event schedule — a few KB)
    enters the compiled program unbatched; only the 11-int dyn vector is
    per-point;
  * ``collect="stream"`` carries fixed-size P²/class/deadline accumulators
    in the scan instead of per-transaction latencies, so each point's output
    is O(classes × percentiles), independent of the transaction count;
  * ``chunk=C`` streams the grid through a ``lax.map`` over C-point chunks:
    peak live state is one chunk's carries, not the grid's.

Per-class latency percentiles for the WHOLE grid come from
``repro.core.percentile.p2_merge_quantile`` — the per-lane marker states are
merged host-side, never the raw samples (which were never materialized).

Standalone usage (CI scale-smoke job)::

  PYTHONPATH=src python -m benchmarks.scale_sweep --points 10000 \
      --chunk 512 --rss-cap-mb 4096 --out experiments/scale_sweep_summary.json

``--rss-cap-mb`` applies a hard ``RLIMIT_AS`` address-space cap before any
simulation work, so a footprint regression fails the job with MemoryError
instead of silently paging.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Dict, Optional, Sequence

import numpy as np

#: dyn-knob axes the grid cycles through (all traced — one compiled program)
GRID_AXES = {
    "outstanding": (2, 3, 4, 6, 8),
    "bank_occupancy": (1, 2, 4, 8),
    "ret_latency": (1, 2, 4),
    "qos_aging": (0, 64),
    "reg_rate": (0, 32),
}


def _tiny_scenario(*, masters: int, txns: int, seed: int):
    """Smallest meaningful QoS scenario: uniform-scatter masters alternating
    realtime/besteffort over a 16-bank single-slice fabric."""
    from repro.core.address import MemoryGeometry
    from repro.scenarios import MasterSpec, Scenario

    geom = MemoryGeometry(num_masters=max(masters, 2), num_clusters=2,
                          arrays_per_cluster=2, banks_per_array=4,
                          total_bytes=1 * 2**20)
    specs = [
        MasterSpec(model="uniform", qos=("realtime" if m % 2 == 0
                                         else "besteffort"),
                   txns=txns, seed=seed + m,
                   deadline=256 if m % 2 == 0 else None,
                   params={"burst": 2, "read_fraction": 0.5})
        for m in range(masters)]
    return Scenario(name="scale_sweep", masters=specs, geom=geom).compile()


def _grid(base, n: int):
    """n SimParams cycling the cartesian dyn-knob grid (deterministic)."""
    from dataclasses import replace
    axes = list(GRID_AXES.items())
    sizes = [len(v) for _, v in axes]
    out = []
    for i in range(n):
        knobs, r = {}, i
        for (name, vals), s in zip(axes, sizes):
            knobs[name] = vals[r % s]
            r //= s
        out.append(replace(base, **knobs))
    return out


def apply_rss_cap(mb: int) -> None:
    """Hard address-space cap (RLIMIT_AS) — the CI guard that a footprint
    regression dies loudly instead of paging."""
    import resource
    resource.setrlimit(resource.RLIMIT_AS, (mb * 2**20, mb * 2**20))


def scale_grid(*, points: int, masters: int = 2, txns: int = 8,
               max_cycles: int = 48, seed: int = 0):
    """(shared schedule, ``points`` SimParams): the grid :func:`scale_sweep`
    runs, on the schedule pipeline with streaming collection."""
    from repro.core.simulator import SCHEDULE_PIPELINE, SimParams

    compiled = _tiny_scenario(masters=masters, txns=txns, seed=seed)
    base = SimParams(geom=compiled.scenario.geom, max_cycles=max_cycles,
                     stages=SCHEDULE_PIPELINE, collect="stream")
    return compiled.schedule(), _grid(base, points)


def scale_sweep(*, points: int = 10_000, chunk: int = 512,
                masters: int = 2, txns: int = 8, max_cycles: int = 48,
                seed: int = 0) -> Dict:
    """Run a ``points``-sized dyn-parameter grid chunked over ONE schedule."""
    from repro.core.percentile import STREAM_PCTS, p2_merge_quantile
    from repro.core.simulator import (STREAM_CLASSES, batch_envelope,
                                      carry_nbytes, input_nbytes,
                                      simulate_batch)
    from repro.scenarios import QOS_CLASSES

    sched, prms = scale_grid(points=points, masters=masters, txns=txns,
                             max_cycles=max_cycles, seed=seed)
    base = batch_envelope(prms)

    t0 = time.perf_counter()
    out = simulate_batch([sched], prms, chunk=chunk)
    wall = time.perf_counter() - t0

    done = np.asarray(out["all_done"])
    # merged whole-grid percentiles per (class, dir): lane marker states in,
    # quantiles out — the raw latencies never existed anywhere
    merged = {}
    for cls in ("realtime", "besteffort"):
        cid = QOS_CLASSES.index(cls)
        for d, dname in ((0, "read"), (1, "write")):
            g = cid * 2 + d
            merged[f"{cls}_{dname}"] = {
                f"p{int(q)}": round(p2_merge_quantile(
                    out["p2_height"][:, g, i, :], out["p2_npos"][:, g, i, :],
                    out["p2_count"][:, g], q / 100.0), 2)
                for i, q in enumerate(STREAM_PCTS)}

    per_point_carry = carry_nbytes(base, sched.num_masters, sched.num_txns)
    return {
        "points": points,
        "chunk": chunk,
        "max_cycles": max_cycles,
        "wall_s": round(wall, 2),
        "points_per_sec": round(points / wall, 2),
        "all_done_fraction": round(float(done.mean()), 4),
        "merged_latency": merged,
        "shared_input_bytes": input_nbytes(sched, base),
        "carry_bytes_per_point": per_point_carry,
        "peak_live_carry_bytes": per_point_carry * min(chunk, points),
        "dyn_bytes_total": int(np.int32(0).nbytes * 11 * points),
        "stream_classes": STREAM_CLASSES,
    }


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--points", type=int, default=10_000)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--max-cycles", type=int, default=48)
    ap.add_argument("--rss-cap-mb", type=int, default=None,
                    help="hard RLIMIT_AS cap applied before simulating")
    ap.add_argument("--out", default=None,
                    help="also write the summary JSON here")
    args = ap.parse_args(argv)
    if args.rss_cap_mb:
        apply_rss_cap(args.rss_cap_mb)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    summary = scale_sweep(points=args.points, chunk=args.chunk,
                          max_cycles=args.max_cycles)
    text = json.dumps(summary, indent=1)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)


if __name__ == "__main__":
    main()
