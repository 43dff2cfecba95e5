#!/usr/bin/env python3
"""Drive the fabric simulator's main path once on a TPU and check the result.

  python3 chip_smoke.py             # one chip: every phase below, in order
  python3 chip_smoke.py --chips 4   # four chips: the sharded grid only

One-chip phases, each through the public entry points and each checked
against something that does not depend on the chip:

  golden    the pinned golden cases and their batched pair
            (``tests/data/golden_single_slice.json``), plus a
            ``compile_simulate`` runner called twice
  paper     Fig. 4 at its full size (16 masters at full injection on the
            prototype geometry) with its paper-threshold asserts, and the
            16-master point on the schedule pipeline bit-equal to the dense
  grid      the chunked shared-schedule grid at the ``benchmarks.run`` size,
            a few points bit-equal to sequential ``simulate``
  serving   the 1024-request serving co-sim: drains, no decode deadline miss
  fuzz      the committed fuzz corpus against its recorded verdicts
  pallas    ``arbiter="pallas"`` bit-equal to ``"jax"``, with the kernel
            compiled (``tpu_custom_call`` in the program)

``--chips 4`` runs only the chunked grid sharded over four devices (batch and
chunk divisible by 4, and not; the ``benchmarks.run`` grid and a grid at the
prototype geometry) against the same grid on one device.

Each phase prints one line with its outcome, wall time and compile time.
The last line of standard output is one JSON object naming the device, and
it is printed only when every phase passed.  With no TPU the script exits
non-zero and names the platform it found.  Everything runs in this one
process; the compile cache is placed by ``repro.compile_cache``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback
from dataclasses import replace
from pathlib import Path

ROOT = Path(__file__).resolve().parent
DATA = ROOT / "tests" / "data"

#: full sizes (the rehearsal on CPU may shrink them)
FIG4_TXNS = 1000          # ``benchmarks.run --full``
GRID_POINTS, GRID_CHUNK = 512, 256       # ``benchmarks.run`` scale_sweep
SHARDED_GRIDS = ((512, 256), (509, 253))  # divisible by 4, and not
#: the same knob grid on ``urban_perception`` at the prototype geometry
#: (16 ports, 256 banks); it drains in ~8k cycles
PROTO_GRID, PROTO_GRID_TXNS, PROTO_GRID_CYCLES = (66, 30), 256, 12_000
SERVING_REQUESTS = 1024
PROTO_MASTERS = 16

#: JAX's compile-time events: tracing, lowering, backend compile
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def _require(ok, message: str) -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(message)


def _bit_equal(got, want, where: str) -> None:
    import numpy as np
    _require(set(got) == set(want),
             f"{where}: keys differ {sorted(set(got) ^ set(want))}")
    for k in want:
        _require(np.array_equal(np.asarray(got[k]), np.asarray(want[k])),
                 f"{where}: {k} differs")


def _match_golden(got, pin, where: str, ulp_keys: list) -> None:
    """Integers equal; float32 keys equal, or at most 1 ulp apart (listed
    in ``ulp_keys``)."""
    import numpy as np
    from capture_golden import GOLDEN_KEYS
    for k in GOLDEN_KEYS:
        a = np.asarray(got[k])
        b = np.asarray(pin[k], a.dtype)
        if np.array_equal(a, b):
            continue
        _require(a.dtype == np.float32, f"{where}: {k} differs from the pin")
        ulps = np.abs(a.view(np.int32).astype(np.int64)
                      - b.view(np.int32).astype(np.int64))
        _require(int(ulps.max()) <= 1,
                 f"{where}: {k} is {int(ulps.max())} ulp from the pin")
        ulp_keys.append(f"{where}/{k}")


def phase_golden() -> str:
    from capture_golden import golden_batch, golden_cases
    from repro.core.simulator import (compile_simulate, simulate,
                                      simulate_batch)
    pins = json.loads((DATA / "golden_single_slice.json").read_text())
    ulp_keys: list = []
    cases = golden_cases()
    for name, trace, prm in cases:
        _match_golden(simulate(trace, prm), pins["cases"][name], name,
                      ulp_keys)
    _match_golden(simulate_batch(*golden_batch(cases)), pins["batch"],
                  "batch", ulp_keys)
    name, trace, prm = cases[1]
    run = compile_simulate(trace, prm)
    first, second = run(), run()
    _bit_equal(second, first, "compile_simulate, second call")
    _match_golden(first, pins["cases"][name], f"{name}/compile_simulate",
                  ulp_keys)
    if ulp_keys:
        print("golden: within 1 ulp of the pin, not equal: "
              + ", ".join(ulp_keys), flush=True)
    return (f"{len(cases)} cases + batched pair + compile_simulate x2; "
            f"{len(ulp_keys)} key(s) at 1 ulp")


@functools.lru_cache(maxsize=1)
def _prototype():
    """(trace, params, dense jax-arbiter metrics) of the 16-master Fig. 4
    point at full injection."""
    from benchmarks.paper_figures import fig4_point
    from repro.core.simulator import simulate
    trace, prm = fig4_point(PROTO_MASTERS, FIG4_TXNS)
    return trace, prm, simulate(trace, prm)


def phase_paper() -> str:
    from benchmarks.paper_figures import fig4_throughput
    from repro.core.simulator import SCHEDULE_PIPELINE, simulate
    rows = fig4_throughput(num_txns=FIG4_TXNS)     # paper-threshold asserts
    trace, prm, dense = _prototype()
    _bit_equal(simulate(trace, replace(prm, stages=SCHEDULE_PIPELINE)),
               dense, "schedule vs dense pipeline")
    r = rows[max(rows)]
    return (f"{max(rows)} masters x {FIG4_TXNS} txns: read "
            f"{r['read_throughput']!r} write {r['write_throughput']!r}; "
            "schedule == dense")


def phase_grid() -> str:
    import numpy as np
    from benchmarks.scale_sweep import scale_grid
    from repro.core.simulator import batch_envelope, simulate, simulate_batch
    sched, prms = scale_grid(points=GRID_POINTS)
    out = simulate_batch([sched], prms, chunk=GRID_CHUNK)
    env = batch_envelope(prms)
    picks = sorted({0, 1, GRID_CHUNK - 1, GRID_CHUNK % GRID_POINTS,
                    GRID_POINTS - 1})
    for i in picks:
        seq = simulate(sched, replace(
            prms[i], slots_override=env.slots_override,
            inflight_override=env.inflight_override))
        _bit_equal({k: np.asarray(v)[i] for k, v in out.items()}, seq,
                   f"grid point {i}")
    done = float(np.asarray(out["all_done"]).mean())
    return (f"{GRID_POINTS} points in chunks of {GRID_CHUNK}; points "
            f"{picks} == sequential; all_done fraction {done!r}")


def phase_serving() -> str:
    from benchmarks.serving_cosim import serving_scale
    out = serving_scale(num_requests=SERVING_REQUESTS, speedup_floor=0)
    misses = out["decode"]["deadline_misses"]
    _require(misses == 0, f"{misses} decode deadline miss(es)")
    ee = out["early_exit"]
    print(f"serving: early-exit off/on wall ratio {ee['speedup']!r} "
          f"(off {ee['wall_s_off']!r}s, on {ee['wall_s_on']!r}s; a first "
          "reading, not a gate)", flush=True)
    return (f"{out['requests']} requests drained in {out['sim_cycles']} "
            f"cycles ({ee['effective_cycles']} effective); 0 deadline "
            "misses")


def phase_fuzz() -> str:
    from repro.scenarios.fuzz import load_reproducer, replay_case
    specs = sorted((DATA / "fuzz_corpus").glob("*.json"))
    _require(specs, "empty fuzz corpus")
    for path in specs:
        case, verdict = load_reproducer(path)
        want = sorted(verdict.get("violated_oracles", []))
        got = sorted({v.oracle for v in replay_case(case).violations})
        _require(got == want,
                 f"{path.name}: violated {got}, recorded {want}")
    return f"{len(specs)} reproducers replay to their recorded verdicts"


def _pallas_matches(trace, prm, want, where: str) -> None:
    from repro.core.simulator import compile_simulate
    run = compile_simulate(trace, replace(prm, arbiter="pallas"))
    _require("tpu_custom_call" in run.compiled.as_text(),
             f"{where}: the Pallas arbiter did not compile to a TPU kernel")
    _bit_equal(run(), want, f"{where}: pallas vs jax arbiter")


def phase_pallas() -> str:
    from capture_golden import golden_cases
    from repro.core.simulator import simulate
    cases = golden_cases()
    for name, trace, prm in cases:
        _pallas_matches(trace, prm, simulate(trace, prm), name)
    trace, prm, dense = _prototype()
    _pallas_matches(trace, prm, dense, f"{PROTO_MASTERS} masters")
    return (f"{len(cases)} golden cases + the {PROTO_MASTERS}-master point "
            "bit-equal, tpu_custom_call in each program")


def _prototype_grid(points: int):
    """(shared schedule, ``points`` SimParams): the ``scale_grid`` knobs
    over ``urban_perception`` at the prototype geometry."""
    from benchmarks.scale_sweep import scale_grid
    from repro.scenarios import urban_perception
    scen = urban_perception(txns=PROTO_GRID_TXNS).compile()
    _, knobs = scale_grid(points=points)
    return scen.schedule(), [replace(p, geom=scen.scenario.geom,
                                     max_cycles=PROTO_GRID_CYCLES)
                             for p in knobs]


def phase_sharded_grid() -> str:
    import jax
    import numpy as np
    from benchmarks.scale_sweep import scale_grid
    from repro.core.simulator import prepare_batch, simulate_batch
    devices = set(jax.devices())
    grids = [(f"scale_sweep {p}/{c}", scale_grid(points=p), c, False)
             for p, c in SHARDED_GRIDS]
    grids.append((f"prototype {PROTO_GRID[0]}/{PROTO_GRID[1]}",
                  _prototype_grid(PROTO_GRID[0]), PROTO_GRID[1], True))
    for where, (sched, prms), chunk, drains in grids:
        prepared = prepare_batch([sched], prms, chunk=chunk)
        held = {s.device for a in prepared.args[prepared.batched:]
                for s in a.addressable_shards}
        _require(held == devices,
                 f"{where}: shards on {len(held)} of {len(devices)}")
        many = prepared.run()
        one = simulate_batch([sched], prms, chunk=chunk, shard=False)
        _bit_equal(many, one, where)
        done = np.asarray(many["all_done"])
        _require(done.shape == (len(prms),), f"{where}: wrong output shape")
        _require(done.all() or not drains, f"{where}: a point did not drain")
    return (f"grids {[g[0] for g in grids]} (points/chunk) on "
            f"{len(devices)} devices == one device, a shard on every device")


PHASES = {1: [("golden", phase_golden), ("paper", phase_paper),
              ("grid", phase_grid), ("serving", phase_serving),
              ("fuzz", phase_fuzz), ("pallas", phase_pallas)],
          4: [("sharded_grid", phase_sharded_grid)]}


def run_phases(phases) -> list:
    """Run every phase, one outcome line each; returns the failed names."""
    import jax
    compile_s = [0.0]

    def on_event(event, duration, **_):
        if event in COMPILE_EVENTS:
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    failed = []
    for name, fn in phases:
        c0, t0 = compile_s[0], time.perf_counter()
        try:
            detail = fn()
            status = "PASS"
        except Exception:
            traceback.print_exc()
            detail, status = "see the traceback on stderr", "FAIL"
            failed.append(name)
        wall = time.perf_counter() - t0
        print(f"phase {name}: {status} wall={wall!r}s "
              f"compile={compile_s[0] - c0!r}s  {detail}", flush=True)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=sorted(PHASES), default=1,
                    help="1 (default): every one-chip phase; 4: only the "
                         "grid sharded over four chips")
    args = ap.parse_args(argv)
    if args.chips == 1:
        # one process on one chip, even where the host has more
        for var, val in (("TPU_VISIBLE_CHIPS", "0"),
                         ("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1"),
                         ("TPU_PROCESS_BOUNDS", "1,1,1")):
            os.environ.setdefault(var, val)
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{platform!r}", file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"device(s); JAX found {len(devices)}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT), str(DATA)]
    from repro.compile_cache import use_compile_cache
    print(f"chip_smoke: {len(devices)} x {devices[0].device_kind}, "
          f"compile cache {use_compile_cache()}", flush=True)
    failed = run_phases(PHASES[args.chips])
    stray = [m for m in ("repro.launch.dryrun", "repro.analysis.costs")
             if m in sys.modules]
    if stray:
        print(f"chip_smoke: the chip path imported {stray}", file=sys.stderr)
        return 1
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
