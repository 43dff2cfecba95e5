"""Replay sampled design points of a run on the plain reference.

The sample is drawn from the run's seed among the points the window
finished, and always holds the point that took the most cycles.  Each
point's traffic is rebuilt from the configuration's data (the master mix,
or the Fig. 4 stream the benchmark made), its knobs from the configuration
and the call, and the reference's outputs are compared with what the timed
call returned.  ``stat_dtype`` lets the control run the same reference with
its statistics in a lower precision than the configuration states.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from bench.reference import compare, fabric, masters, summary
from bench.workload import Call, Workload


def sample(calls: List[Call], seed: int, count: int) -> List[Tuple[int, int]]:
    """``count`` (call, point) pairs: the point that took the most cycles,
    the last point of a call drawn from the seed (the last lane of a
    batch, on the last chip where it is sharded), then others drawn from
    the seed."""
    pairs = [(ci, pi) for ci, c in enumerate(calls)
             for pi in range(len(c.points))]
    longest = max(pairs, key=lambda p: int(
        calls[p[0]].points[p[1]]["effective_cycles"]))
    rng = np.random.default_rng([seed & (2**64 - 1), 1])
    ci = int(rng.integers(len(calls)))
    picks = [longest]
    for p in [(ci, len(calls[ci].points) - 1)] + [
            pairs[i] for i in rng.permutation(len(pairs))]:
        if len(picks) >= count:
            break
        if p not in picks:
            picks.append(p)
    return picks


def reference_point(wl: Workload, c: Call, point: int,
                    stat_dtype=np.float32) -> Tuple[dict, dict, list]:
    """(reference outputs, traffic, QoS class per master) of one point."""
    cfg = wl.config
    geom = cfg["geometry"]
    beats_total = (geom["total_bytes"] // geom["beat_bytes"]
                   * geom["num_slices"])
    if "fig4_traffic" in cfg:
        traffic, qos = c.fig4, None
    else:
        mix = wl.masters(c)
        traffic = masters.build(mix, beats_total)
        qos = [m["qos"] for m in mix]
    knobs = [fabric.Knobs(**{**cfg["params"], **k}) for k in c.knobs]
    # the points of one batched call share the batch's largest ring, and
    # the ring slot breaks return-bus ties
    slots = max(k.ring_slots() for k in knobs)
    out = fabric.simulate(traffic, geom, knobs[point], slots=slots,
                          collect=wl.mix.get("collect", "exact"),
                          stat_dtype=stat_dtype)
    return out, traffic, qos


def check(wl: Workload, calls: List[Call], seed: int
          ) -> Tuple[Dict[str, float], int]:
    """The largest of each compared number over the sample, and how many
    sampled points failed a limit."""
    worst: Dict[str, float] = {}
    failed = 0
    for ci, pi in sample(calls, seed, int(wl.mix["check_points"])):
        c = calls[ci]
        want, traffic, qos = reference_point(wl, c, pi)
        want_class = (summary.per_class(traffic, qos, want)
                      if qos is not None else None)
        got = compare.compare(c.points[pi], want, c.per_class[pi],
                              want_class)
        failed += not compare.within(got)
        for k, v in got.items():
            worst[k] = max(worst.get(k, 0), v)
    return worst, failed
