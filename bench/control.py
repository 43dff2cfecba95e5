#!/usr/bin/env python3
"""Readings of the control: the plain reference put in the model's place
with its statistics in bfloat16, one precision below the float32 the
configurations state, compared with the float32 reference by the same
comparison that decides ``correct``.  The control has to fail it.

  python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed, the points of the cell's first call that a run's sample
would hold first (its first and last point) at the cell's own sizes; one
JSON line per point with the compared numbers and whether they are within
the limits.  No chip is used: the reference runs on the host.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def readings(root: Path, name: str, seed: int, dtype=None) -> list:
    import ml_dtypes

    from bench.check import reference_point
    from bench.harness import cell_parts, load_spec
    from bench.reference import compare, summary
    from bench.workload import Workload

    dtype = dtype or ml_dtypes.bfloat16
    cell, config, mix, _, _ = cell_parts(root, load_spec(root), name)
    wl = Workload(config, mix, seed, cell["chips"])
    c = wl.call(0)
    if "fig4_traffic" in config:
        c.fig4 = wl.fig4_traffic(c)
    out = []
    for point in sorted({0, len(c.knobs) - 1}):
        want, traffic, qos = reference_point(wl, c, point)
        got, _, _ = reference_point(wl, c, point, stat_dtype=dtype)
        classes = ((summary.per_class(traffic, qos, got),
                    summary.per_class(traffic, qos, want))
                   if qos is not None else (None, None))
        numbers = compare.compare(got, want, *classes)
        out.append({"workload": name, "seed": seed, "point": point,
                    "numbers": numbers, "within": compare.within(numbers)})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    for seed in args.seeds:
        for r in readings(ROOT, args.workload, seed):
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
