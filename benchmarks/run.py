"""Benchmark harness: one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full]

Prints ``name,compile_s,run_s,derived`` CSV lines and writes
experiments/bench_results.json for EXPERIMENTS.md.

Each job runs TWICE: the first (cold) call pays JIT compilation, the second
hits the warm jit cache — so the JSON separates ``compile_s`` (cold minus
warm) from ``run_s`` (steady state), and a jitted job whose wall time is all
compile no longer reads as a slow simulator.  Both calls are fenced with
``jax.block_until_ready`` so async dispatch cannot leak work past the timer.
``--cold`` skips the warm pass (halves wall time; ``run_s`` then includes
compile and ``compile_s`` is null).  ``--profile`` wraps each job's warm
pass in ``jax.profiler.trace`` and writes the trace directory next to the
JSON artifact (``experiments/profile/<job>/``) so the remaining hot stages
can be inspected in TensorBoard/Perfetto instead of guessed."""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path


def _timed(fn, trace_dir: Path | None = None):
    """(result, compile_s, run_s) — cold call then warm call, both fenced.

    With ``trace_dir`` the warm call runs inside ``jax.profiler.trace`` so
    the trace captures steady-state device/host activity, not compilation.
    """
    import contextlib

    import jax

    t0 = time.time()
    out = jax.block_until_ready(fn())
    t1 = time.time()
    prof = (jax.profiler.trace(str(trace_dir)) if trace_dir is not None
            else contextlib.nullcontext())
    with prof:
        jax.block_until_ready(fn())
    t2 = time.time()
    run_s = t2 - t1
    return out, max((t1 - t0) - run_s, 0.0), run_s


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale transaction counts (slow on 1 CPU)")
    ap.add_argument("--only", default=None,
                    help="comma-separated job names to run")
    ap.add_argument("--list", action="store_true",
                    help="print the available job names and exit")
    ap.add_argument("--cold", action="store_true",
                    help="single cold run per job (no compile/run split)")
    ap.add_argument("--profile", action="store_true",
                    help="wrap each job's warm pass in jax.profiler.trace; "
                         "traces land in experiments/profile/<job>/ "
                         "(implies the warm pass, i.e. not --cold)")
    args = ap.parse_args()
    if args.profile and args.cold:
        raise SystemExit("--profile needs the warm pass; drop --cold")

    from benchmarks import paper_figures as F
    from benchmarks.fuzz import fuzz_job
    from benchmarks.qos_isolation import qos_isolation_sweep
    from benchmarks.scale_sweep import scale_sweep
    from benchmarks.scenario_sweep import scenario_sweep
    from benchmarks.serving_cosim import serving_cosim
    from benchmarks.slice_scaling import slice_scaling_bench

    scale = dict(num_txns=1000) if args.full else {}
    jobs = [
        ("fig4_throughput", lambda: F.fig4_throughput(**scale)),
        ("fig5_bulk", lambda: F.fig5_bulk(
            payloads_kb=(4, 16, 64, 256, 1024, 2048) if args.full
            else (4, 16, 64, 256, 1024))),
        ("table1_outstanding", lambda: F.table1_outstanding()),
        ("fig67_traces", lambda: F.fig67_traces(
            max_txns=3000 if args.full else 1200)),
        ("comparators", lambda: F.comparators()),
        ("qos_isolation", lambda: F.qos_isolation()),
        ("pool_balance", lambda: F.pool_balance()),
        ("moe_whitening", lambda: F.moe_whitening()),
        ("scenario_sweep", lambda: scenario_sweep(
            txns=128 if args.full else 64,
            max_cycles=16_000 if args.full else 8000)),
        ("qos_isolation_sweep", lambda: qos_isolation_sweep(
            txns=96 if args.full else 64,
            max_cycles=14_000 if args.full else 10_000)),
        ("slice_scaling", lambda: slice_scaling_bench(
            txns=96 if args.full else 64,
            max_cycles=12_000 if args.full else 10_000)),
        # full mode scales requests, not batch: batch 8 on one slice
        # self-congests even alone (decode alone overruns 256 banks at
        # occupancy 32), which is a capacity result, not an isolation one
        ("serving_cosim", lambda: serving_cosim(
            num_requests=32 if args.full else 24)),
        # streaming/chunked grid scaling (the CI scale-smoke job runs the
        # same module standalone at >= 10k points under an RSS cap)
        ("scale_sweep", lambda: scale_sweep(
            points=2048 if args.full else 512, chunk=256)),
        # randomized-spec property fuzz (the CI fuzz-smoke job runs the same
        # module standalone with a bigger budget + reproducer shrinking)
        ("fuzz", lambda: fuzz_job(budget=96 if args.full else 48)),
    ]
    valid = [j[0] for j in jobs]
    if args.list:
        print("\n".join(valid))
        return
    if args.only:
        wanted = args.only.split(",")
        unknown = set(wanted) - set(valid)
        if unknown:
            raise SystemExit(
                f"unknown --only jobs: {sorted(unknown)}; "
                f"valid jobs: {valid} (see also --list)")
        jobs = [j for j in jobs if j[0] in wanted]

    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    results = {}
    failed = []
    print("name,compile_s,run_s,derived")
    for name, fn in jobs:
        try:
            if args.cold:
                t0 = time.time()
                out = fn()
                compile_s, run_s = None, time.time() - t0
                trace_dir = None
            else:
                trace_dir = (Path("experiments/profile") / name
                             if args.profile else None)
                if trace_dir is not None:
                    trace_dir.mkdir(parents=True, exist_ok=True)
                out, compile_s, run_s = _timed(fn, trace_dir)
        except Exception as e:
            # keep running the remaining jobs, but make sure a crashed job
            # cannot read as a silently-passing CI smoke step
            import traceback
            traceback.print_exc()
            failed.append(name)
            results[name] = {"error": f"{type(e).__name__}: {e}"}
            print(f"{name},,,FAILED ({type(e).__name__})")
            continue
        results[name] = {
            "seconds": round((compile_s or 0.0) + run_s, 2),  # total, legacy
            "compile_s": None if compile_s is None else round(compile_s, 2),
            "run_s": round(run_s, 2),
            "results": out,
        }
        if trace_dir is not None:
            results[name]["profile_dir"] = str(trace_dir)
            print(f"# profile trace: {trace_dir}")
        key = next(iter(out))
        cs = "" if compile_s is None else f"{compile_s:.2f}"
        print(f"{name},{cs},{run_s:.2f},{json.dumps(out[key])[:110]}")

    out_path = Path("experiments/bench_results.json")
    out_path.parent.mkdir(exist_ok=True)
    out_path.write_text(json.dumps(results, indent=1, default=str))
    print(f"# wrote {out_path}")

    # per-class QoS summary as its own artifact file (CI uploads it)
    if "qos_isolation_sweep" in results and "results" in results["qos_isolation_sweep"]:
        q_path = Path("experiments/qos_isolation_summary.json")
        q_path.write_text(json.dumps(
            results["qos_isolation_sweep"]["results"], indent=1, default=str))
        print(f"# wrote {q_path}")

    # multi-slice scaling summary, likewise uploaded by CI
    if "slice_scaling" in results and "results" in results["slice_scaling"]:
        s_path = Path("experiments/slice_scaling_summary.json")
        s_path.write_text(json.dumps(
            results["slice_scaling"]["results"], indent=1, default=str))
        print(f"# wrote {s_path}")

    # serving co-sim decode-isolation summary, likewise uploaded by CI
    if "serving_cosim" in results and "results" in results["serving_cosim"]:
        v_path = Path("experiments/serving_cosim_summary.json")
        v_path.write_text(json.dumps(
            results["serving_cosim"]["results"], indent=1, default=str))
        print(f"# wrote {v_path}")

    # chunked-scaling summary, likewise uploaded by CI
    if "scale_sweep" in results and "results" in results["scale_sweep"]:
        g_path = Path("experiments/scale_sweep_summary.json")
        g_path.write_text(json.dumps(
            results["scale_sweep"]["results"], indent=1, default=str))
        print(f"# wrote {g_path}")

    if failed:
        raise SystemExit(f"failed jobs: {failed}")


if __name__ == "__main__":
    main()
