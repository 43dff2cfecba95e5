"""Reduce a JAX profiler trace to what the per-layer metrics read.

The profiler writes an ``.xplane.pb`` that ``jax.profiler.ProfileData``
reads.  On a TPU host it holds one plane per chip (``/device:TPU:<n>``)
whose ``XLA Ops`` line has one event per operation run, nested (a while
loop's event encloses its body's), and a host plane (``/host:CPU``) whose
lines are host threads: the runtime's events, the Python tracer's function
events (``$file.py:line name``) and the benchmark's own spans
(``bench.call``, ``bench.scenario``).  All timestamps share one clock, in
nanoseconds.

* window: from the start of the first ``bench.*`` span (or device
  operation) to the end of the last, i.e. the traced calls.  The device
  planes' clock is mapped onto the host's to about a millisecond, so a
  call's first operations can appear just before its span opens.
* busy: per chip, the union of its operation intervals; ``busy_s`` is the
  mean over the chips of the busy time inside the window.
* device ops: leaf operations (those enclosing no other) summed by name,
  so an enclosing loop does not count its body twice.
* idle gaps: stretches of the window in which chip 0 ran nothing, each
  named by the innermost host event in flight at its middle.
"""
from __future__ import annotations

import glob
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

SPAN_PREFIX = "bench."
OP_LINE = "XLA Ops"


def merge(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Union of [start, end) intervals, as a sorted ``[n, 2]`` array."""
    if not len(starts):
        return np.zeros((0, 2), np.int64)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.nonzero(new)[0]
    last = np.append(first[1:], len(s)) - 1
    return np.stack([s[first], reach[last]], axis=1)


def covered(union: np.ndarray, t0: int, t1: int) -> int:
    """Nanoseconds of [t0, t1) that the merged intervals cover."""
    if not len(union):
        return 0
    s = np.clip(union[:, 0], t0, t1)
    e = np.clip(union[:, 1], t0, t1)
    return int(np.sum(e - s))


def leaf_seconds(starts, ends, names, labels) -> Dict[str, float]:
    """Seconds per operation label over the events that enclose no other
    event of their line (events of one line nest properly); ``names`` are
    indices into ``labels``."""
    order = np.lexsort((-(ends - starts), starts))
    s, e = starts[order], ends[order]
    leaf = np.ones(len(s), bool)
    leaf[:-1] = s[1:] >= e[:-1]
    total = np.bincount(names[order][leaf], weights=(e - s)[leaf] / 1e9,
                        minlength=len(labels))
    return {labels[i]: float(t) for i, t in enumerate(total) if t > 0}


@dataclass
class Reduced:
    window: Tuple[int, int]
    busy: List[np.ndarray]                   # merged intervals per chip
    host_spans: Dict[str, List[Tuple[int, int]]]
    op_seconds: Dict[str, float]             # leaf ops, summed over chips
    host_events: List[Tuple[int, int, str]]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_within(*self.window) / 1e9

    def busy_within(self, t0: int, t1: int) -> float:
        """Mean over chips of the busy nanoseconds inside [t0, t1)."""
        return float(np.mean([covered(b, t0, t1) for b in self.busy]))

    def gaps(self) -> List[Tuple[int, int]]:
        """Idle stretches of chip 0 inside the window."""
        t0, t1 = self.window
        b = self.busy[0]
        edges = np.concatenate([[t0], b.ravel(), [t1]])
        s, e = np.maximum(edges[0::2], t0), np.minimum(edges[1::2], t1)
        return [(int(a), int(z)) for a, z in zip(s, e) if z > a]

    def what_host_did(self, t: int) -> str:
        """The innermost host event in flight at ``t``."""
        best = None
        for s, e, name in self.host_events:
            if s <= t < e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "no host event"

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:10]
        return {"device_ops": [[n, s / len(self.busy)] for n, s in ops],
                "idle_gaps": [[self.what_host_did((s + e) // 2),
                               (e - s) / 1e9] for s, e in gaps]}


def reduce(profile) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``; ``busy`` is empty where the
    trace holds no device operations."""
    busy, ops = [], {}
    spans: Dict[str, List[Tuple[int, int]]] = {}
    host = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                ids: Dict[str, int] = {}
                rows = [(ev.start_ns, ev.end_ns,
                         ids.setdefault(ev.name.split(" ", 1)[0], len(ids)))
                        for ev in line.events]
                if not rows:
                    continue
                starts, ends, names = (np.array(c, np.int64)
                                       for c in zip(*rows))
                busy.append(merge(starts, ends))
                for n, sec in leaf_seconds(starts, ends, names,
                                           list(ids)).items():
                    ops[n] = ops.get(n, 0.0) + sec
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.setdefault(ev.name, []).append(
                            (int(ev.start_ns), int(ev.end_ns)))
                    host.append((int(ev.start_ns), int(ev.end_ns), ev.name))
    marks = [t for v in spans.values() for s in v for t in s]
    marks += [t for b in busy if len(b) for t in (b[0, 0], b[-1, 1])]
    window = (int(min(marks)), int(max(marks))) if marks else (0, 0)
    return Reduced(window, busy, spans, ops, host)


class Tracer:
    """Profile part of the window into ``log_dir``; ``stop`` reduces the
    trace and deletes its files."""

    def __init__(self, log_dir: Path):
        self.dir = Path(log_dir)

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(str(self.dir))
        self.t0 = time.perf_counter()

    def stop(self) -> Reduced:
        """Stop, read and reduce; prints the seconds each step took and the
        trace's size on standard error."""
        import jax
        t1 = time.perf_counter()
        jax.profiler.stop_trace()
        try:
            t2 = time.perf_counter()
            path = max(glob.glob(str(self.dir / "**" / "*.xplane.pb"),
                                 recursive=True),
                       key=lambda f: Path(f).stat().st_mtime)
            out = reduce(jax.profiler.ProfileData.from_file(path))
            print(f"trace: {Path(path).stat().st_size} bytes over "
                  f"{t1 - self.t0:.3f} s; stop {t2 - t1:.3f} s, read and "
                  f"reduce {time.perf_counter() - t2:.3f} s",
                  file=sys.stderr, flush=True)
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
