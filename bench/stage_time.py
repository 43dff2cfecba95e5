"""Device time by cycle-pipeline stage and host time by program phase.

The simulator names its own work.  Each stage of the cycle body runs under
the name scope ``stage.<registry name>``, which the compiled program keeps
in each instruction's ``op_name`` metadata.  Its drivers mark three host
spans on the profiler's clock: ``repro.simulate`` around a call, and
inside it ``repro.prepare`` (inputs normalised, beat tables built, placed
on the device) and ``repro.fetch`` (waiting for the device and copying the
results back); dispatch is the parent's self time.

A device operation in the trace is named by its HLO instruction.  Its
bucket is the innermost ``stage.*`` scope of the instruction's own
``op_name``; failing that, the one stage named inside the computation it
calls (``calls=``); failing that, ``unstaged`` (loop plumbing, carry
copies, set-up and result reductions, and any operation whose name the
program's text does not hold).  The reduced trace sums leaf operations by
name over every module the window ran, so an operation of another program
that happens to share an instruction name with the simulator's is counted
in that instruction's bucket.

The readers' helpers return ``None`` where the program names no stage or
the trace holds no ``repro.*`` span, as a program from before the scopes
does.
"""
from __future__ import annotations

import argparse
import re
import sys
from pathlib import Path
from typing import Dict, Optional

STAGE = "stage."
UNSTAGED = "unstaged"

_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")


def _stage(op_name: str) -> Optional[str]:
    """The innermost ``stage.*`` component of an ``op_name``."""
    inner = [c for c in op_name.split("/") if c.startswith(STAGE)]
    return inner[-1][len(STAGE):] if inner else None


def stage_of_ops(hlo_text: str) -> Dict[str, str]:
    """Bucket of each instruction of a compiled HLO module's text, keyed by
    the instruction's name without its ``%``."""
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    inside: Dict[str, set] = {}
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY ") else 0]
            comp = comp.lstrip("%")
            inside[comp] = set()
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = _stage(op.group(1)) if op else None
        if own[name] and comp is not None:
            inside[comp].add(own[name])
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
    out = {}
    for name, stage in own.items():
        if stage is None:
            named = inside.get(calls.get(name), set())
            stage = next(iter(named)) if len(named) == 1 else None
        out[name] = stage or UNSTAGED
    return out


def scope_seconds(op_seconds: Dict[str, float],
                  stages: Dict[str, str]) -> Dict[str, float]:
    """Sum per-operation seconds (keyed as the trace names them, ``%``
    included) into the buckets of :func:`stage_of_ops`; every bucket the
    program names is present, at zero where none of its operations ran."""
    out = dict.fromkeys(stages.values(), 0.0)
    for name, sec in op_seconds.items():
        b = stages.get(name.lstrip("%"), UNSTAGED)
        out[b] = out.get(b, 0.0) + sec
    return out


def program_text(run) -> Optional[str]:
    """Compiled HLO text of the program the traced calls ran, or ``None``
    for calls other than Fig. 4 traces.

    The harness hands a reader the run alone, so the cell is the one this
    process measures, as its command line names it (``bench/run.py
    --workload <cell>``).  The first traced call's trace and parameters are
    compiled again through ``simulator.compile_simulate``.  JAX's persistent
    compile cache keys a program without its metadata, so the calls may
    have run a twin compiled without the scopes (an older checkout sharing
    the cache), which JAX's in-memory caches then hold: they are cleared,
    and this one compile keys on the metadata too.  The twins'
    instructions, and so the names the trace gives them, are the same."""
    import jax
    from bench.harness import cell_parts, load_spec
    from bench.workload import Workload
    from repro.core import simulator
    c = run.traced_calls[0]
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    cell = ap.parse_known_args(sys.argv[1:])[0].workload
    if c.fig4 is None or cell is None:
        return None
    root = Path(__file__).resolve().parents[1]
    _, config, mix, _, _ = cell_parts(root, load_spec(root), cell)
    prm = Workload(config, mix, 0, 1).params(c)[0]
    t = c.fig4
    trace = simulator.Trace(t["is_write"], t["burst"], t["addr"], t["start"],
                            t["prio"])
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    jax.clear_caches()
    try:
        return simulator.compile_simulate(trace, prm).compiled.as_text()
    finally:
        jax.config.update(key, was)


_memo: list = [None, None]          # [reduced trace, its buckets]


def device_scopes(run) -> Optional[Dict[str, float]]:
    """Device seconds per bucket in the traced window, averaged over the
    chips; ``None`` where the program names no stage."""
    if run.trace is None or not run.trace.busy or not run.traced_calls:
        return None
    if _memo[0] is not run.trace:
        text = program_text(run)
        stages = stage_of_ops(text) if text else {}
        buckets = None
        if any(b != UNSTAGED for b in stages.values()):
            chips = len(run.trace.busy)
            buckets = {b: s / chips for b, s in
                       scope_seconds(run.trace.op_seconds, stages).items()}
        _memo[:] = [run.trace, buckets]
    return _memo[1]


def stage_us_per_step(run, prefix: str) -> Optional[float]:
    """Device microseconds per executed loop step in the buckets whose name
    starts with ``prefix``, over the same steps as ``device_us_per_step``:
    per traced call its largest stepped count.  ``None`` where the program
    has no such bucket."""
    buckets = device_scopes(run) or {}
    mine = [s for b, s in buckets.items() if b.startswith(prefix)]
    steps = sum(int(c.stepped().max()) for c in run.traced_calls)
    return 1e6 * sum(mine) / steps if mine and steps else None


def host_phase_ms(run, span: str) -> Optional[float]:
    """Host milliseconds per traced call inside the program's ``span``
    events during which no operation ran on the device."""
    if run.trace is None or not run.trace.busy or not run.traced_calls:
        return None
    spans = [(s, e) for s, e, name in run.trace.host_events if name == span]
    if not spans:
        return None
    idle = sum((e - s) - run.trace.busy_within(s, e) for s, e in spans)
    return idle / len(run.traced_calls) / 1e6


def idle_spans(trace) -> Dict[str, float]:
    """Chip-0 idle seconds of the window grouped by the innermost
    ``repro.*`` or ``bench.*`` span in flight at each gap's middle, or
    ``between calls``."""
    spans = [(s, e, n) for s, e, n in trace.host_events
             if n.startswith(("repro.", "bench."))]
    out: Dict[str, float] = {}
    for g0, g1 in trace.gaps():
        mid = (g0 + g1) // 2
        name = min(((e - s, n) for s, e, n in spans if s <= mid < e),
                   default=(0, "between calls"))[1]
        out[name] = out.get(name, 0.0) + (g1 - g0) / 1e9
    return out
