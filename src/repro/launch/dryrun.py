import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count on first initialization).  Do not reorder.

# Multi-pod dry-run: lower + compile every (architecture × input shape) on the
# production meshes and record memory/cost/collective analyses.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --all
#   PYTHONPATH=src python -m repro.launch.dryrun --arch stablelm-1.6b \
#       --shape train_4k --multi-pod
#
# Artifacts: experiments/dryrun/<mesh>/<arch>__<shape>.json — consumed by
# EXPERIMENTS.md.
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs import SHAPES, SHAPES_BY_NAME, get_config, list_archs, \
    shape_applicable
from repro.analysis import costs as costs_mod
from repro.analysis.hlo import collective_wire_bytes
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell

_DTYPE_BYTES = {"f64": 8, "s64": 8, "u64": 8, "c64": 8, "f32": 4, "s32": 4,
                "u32": 4, "f16": 2, "bf16": 2, "s16": 2, "u16": 2, "s8": 1,
                "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"= ((?:\([^)]*\))|(?:\S+\[[^\]]*\]\S*)) "
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-device wire-byte estimate per collective kind, from result shapes.

    Ring cost model (documented in EXPERIMENTS.md §Roofline): all-reduce moves
    2× its payload; all-gather / reduce-scatter / all-to-all / permute 1×.
    """
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for shape_str, kind in _COLL_RE.findall(hlo_text):
        b = _shape_bytes(shape_str)
        out[kind] += b
        out["count"] += 1
    out["wire_bytes"] = (2 * out["all-reduce"] + out["all-gather"]
                         + out["reduce-scatter"] + out["all-to-all"]
                         + out["collective-permute"])
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: Path, run=None, tag: str = "") -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "status": "ok"}
    t0 = time.time()
    try:
        cell = build_cell(arch, shape_name, mesh, run=run)
        with jax.set_mesh(mesh):
            jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                             out_shardings=cell.out_shardings,
                             donate_argnums=cell.donate_argnums)
            lowered = jitted.lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        ca = compiled.cost_analysis()
        if not isinstance(ca, dict):
            ca = ca[0] if ca else {}
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
        rec.update(
            meta=cell.meta,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            flops_per_device=ca.get("flops", 0.0),
            bytes_per_device=ca.get("bytes accessed", 0.0),
            transcendentals=ca.get("transcendentals", 0.0),
            memory={
                "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
                "output_bytes": getattr(ma, "output_size_in_bytes", None),
                "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
                "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
            },
            collectives=collective_stats(hlo),
            collectives_loop_corrected=collective_wire_bytes(hlo),
        )
        # three-term roofline from the compiled artifact + analytic flops
        cfg = get_config(arch)
        shp = SHAPES_BY_NAME[shape_name]
        chips = 512 if multi_pod else 256
        run_eff = run or __import__(
            "repro.launch.specs", fromlist=["default_run_config"]
        ).default_run_config(arch, shape_name)
        rec["roofline"] = costs_mod.roofline_terms(
            cfg, shp, chips=chips, tp=16,
            cache_len=cell.meta.get("cache_len", 0),
            wire_bytes=rec["collectives_loop_corrected"]["wire_bytes"],
            remat=run_eff.remat_policy,
            triangular=run_eff.triangular_attn)
    except Exception as e:  # a failing cell is a bug — record and surface it
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{arch}__{shape_name}{tag}.json"
    path.write_text(json.dumps(rec, indent=1, default=str))
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = list_archs() if args.arch is None else [args.arch]
    shapes = [s.name for s in SHAPES] if args.shape is None else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all and args.multi_pod
                               ) else [args.multi_pod]
    if args.all and not args.multi_pod:
        meshes = [False, True]

    n_ok = n_fail = n_skip = 0
    for multi_pod in meshes:
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
        out_dir = Path(args.out) / mesh_name
        for arch in archs:
            cfg = get_config(arch)
            for shape_name in shapes:
                from repro.configs import SHAPES_BY_NAME
                ok, why = shape_applicable(cfg, SHAPES_BY_NAME[shape_name])
                if not ok:
                    n_skip += 1
                    (out_dir).mkdir(parents=True, exist_ok=True)
                    (out_dir / f"{arch}__{shape_name}.json").write_text(
                        json.dumps({"arch": arch, "shape": shape_name,
                                    "mesh": mesh_name, "status": "skip",
                                    "reason": why}, indent=1))
                    print(f"[skip] {mesh_name} {arch} {shape_name}: {why}",
                          flush=True)
                    continue
                rec = run_cell(arch, shape_name, multi_pod=multi_pod,
                               out_dir=out_dir)
                if rec["status"] == "ok":
                    n_ok += 1
                    print(f"[ok]   {mesh_name} {arch} {shape_name} "
                          f"compile={rec['compile_s']}s "
                          f"flops/dev={rec['flops_per_device']:.3g} "
                          f"coll={rec['collectives']['count']}", flush=True)
                else:
                    n_fail += 1
                    print(f"[FAIL] {mesh_name} {arch} {shape_name}: "
                          f"{rec['error']}", flush=True)
    print(f"done: ok={n_ok} fail={n_fail} skip={n_skip}")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
