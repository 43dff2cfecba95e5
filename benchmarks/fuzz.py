"""Budgeted scenario-fuzz driver (the CI ``fuzz-smoke`` entry point).

  PYTHONPATH=src python -m benchmarks.fuzz --seed 0 --budget 200 \
      --time-limit 1500 --out-dir experiments/fuzz

Samples ``--budget`` random scenario specs from the seeded space (see
``repro.scenarios.fuzz``), evaluates them in batched chunks on the
schedule/streaming pipeline, and checks every property oracle.  On any
violation the driver shrinks the spec to a minimal reproducer, writes one
``reproducer_<index>.json`` per find plus a ``fuzz_summary.json`` into
``--out-dir``, and exits non-zero — CI uploads the directory as an artifact.

``--time-limit`` bounds wall clock (the run truncates rather than overshoots
a CI budget; truncation alone is not a failure), ``--rss-cap-mb`` applies the
same hard RLIMIT_AS guard as the scale-smoke job, and ``--plant-rate`` seeds
guaranteed-violation specs (used by tests to exercise the failure path —
leave at 0 for real fuzzing).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Dict, Optional


def run_fuzz_job(*, seed: int = 0, budget: int = 200,
                 time_limit: Optional[float] = None, chunk: int = 64,
                 plant_rate: float = 0.0, shrink_limit: int = 6,
                 max_cycles: int = 20_000, geometries=None,
                 out_dir: Optional[Path] = None,
                 verbose: bool = False) -> Dict[str, object]:
    """One budgeted fuzz run; returns (and optionally writes) the summary."""
    from repro.scenarios.fuzz import FuzzConfig, run_fuzz

    extra = {} if not geometries else {"geometries": tuple(geometries)}
    cfg = FuzzConfig(seed=seed, budget=budget, chunk=chunk,
                     plant_rate=plant_rate, shrink_limit=shrink_limit,
                     max_cycles=max_cycles, **extra)
    outcome = run_fuzz(cfg, time_limit_s=time_limit,
                       log=print if verbose else None)
    summary = outcome.summary()
    if out_dir is not None:
        out_dir.mkdir(parents=True, exist_ok=True)
        for rep in outcome.reproducers:
            idx = rep["original"]["index"]
            path = out_dir / f"reproducer_{idx}.json"
            path.write_text(json.dumps(rep, indent=1))
            print(f"# wrote {path}")
        (out_dir / "fuzz_summary.json").write_text(
            json.dumps(summary, indent=1, default=str))
        print(f"# wrote {out_dir / 'fuzz_summary.json'}")
    return summary


def fuzz_job(*, budget: int = 48, seed: int = 0) -> Dict[str, object]:
    """The ``benchmarks.run`` registry entry: a small clean-tree fuzz pass.

    Violations surface in the summary (and fail CI through the runner's
    non-zero exit on raised jobs) — reproducer shrinking/artifacts belong to
    the dedicated ``fuzz-smoke`` job, so this keeps ``--cold`` cheap.
    """
    summary = run_fuzz_job(seed=seed, budget=budget, shrink_limit=0)
    if summary["violations"]:
        raise RuntimeError(
            f"fuzz: {summary['violations']} oracle violation(s) at seed "
            f"{seed}: {summary['violated_oracles']} — rerun "
            f"benchmarks.fuzz --seed {seed} for reproducers")
    return {"fuzz": summary}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--budget", type=int, default=200,
                    help="specs to generate and evaluate")
    ap.add_argument("--time-limit", type=float, default=None,
                    help="wall-clock bound in seconds (truncates, not fails)")
    ap.add_argument("--chunk", type=int, default=64,
                    help="simulate_batch chunk size (peak-memory cap)")
    ap.add_argument("--max-cycles", type=int, default=20_000)
    ap.add_argument("--plant-rate", type=float, default=0.0,
                    help="P(planted guaranteed violation) — test hook")
    ap.add_argument("--shrink-limit", type=int, default=6,
                    help="violating cases to shrink per run")
    ap.add_argument("--geometries", default=None,
                    help="comma-separated GEOMETRIES palette subset "
                         "(default: all)")
    ap.add_argument("--out-dir", type=Path,
                    default=Path("experiments/fuzz"),
                    help="summary + reproducer JSON output directory")
    ap.add_argument("--rss-cap-mb", type=int, default=None,
                    help="hard RLIMIT_AS cap (CI footprint guard)")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)

    if args.rss_cap_mb:
        from benchmarks.scale_sweep import apply_rss_cap
        apply_rss_cap(args.rss_cap_mb)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    t0 = time.time()
    summary = run_fuzz_job(
        seed=args.seed, budget=args.budget, time_limit=args.time_limit,
        chunk=args.chunk, plant_rate=args.plant_rate,
        shrink_limit=args.shrink_limit, max_cycles=args.max_cycles,
        geometries=(args.geometries.split(",") if args.geometries else None),
        out_dir=args.out_dir, verbose=not args.quiet)
    print(f"fuzz: {summary['evaluated']}/{summary['budget']} specs in "
          f"{time.time() - t0:.1f}s, {summary['violations']} violation(s)"
          + (" [truncated]" if summary["truncated"] else ""))
    if summary["violations"]:
        print(f"fuzz: FAILED oracles {summary['violated_oracles']}; "
              f"reproducers in {args.out_dir}")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
