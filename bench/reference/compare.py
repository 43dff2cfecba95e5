"""The comparison that decides ``correct``: the model's outputs for one
design point against the plain reference's, reduced to three numbers.

* ``int_mismatches`` -- integer and boolean outputs that differ: drain and
  horizon cycles, ``all_done``, per-port beats, busy cycles and completed
  transactions, per-transaction accept and completion cycles (exact
  collector), per-class completions, deadline counts and sample counts
  (streaming collector), and the integer fields of the per-class summary.
  Cycle-level semantics are integer, so the limit is 0.
* ``stat_rel_gap`` -- the largest relative gap over the float statistics:
  per-port throughput, busy throughput, mean and largest latency, the
  largest latency per class and direction, and the float fields of the
  per-class summary other than the streaming percentiles.
* ``p2_rel_gap`` -- the largest relative gap over the streaming percentile
  estimates (P-square markers), per group and in the per-class summary.

A relative gap is ``|got - want| / |want|``; where ``want`` is 0 it is 0 if
``got`` is 0 too and infinite otherwise; two NaNs agree.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

#: each number compared, with its limit (readings in PERF.md)
LIMITS = {"int_mismatches": 0, "stat_rel_gap": 1e-4, "p2_rel_gap": 1e-3}

INT_KEYS = ("all_done", "beats_done", "busy_cycles", "txns_done_port",
            "cycles", "drained_cycle", "effective_cycles", "slice_beats",
            "remote_beats", "accept_cycle", "complete_cycle", "cls_done",
            "dl_done", "dl_miss", "p2_count")
FLOAT_KEYS = ("throughput", "read_throughput", "write_throughput",
              "throughput_busy", "read_throughput_busy",
              "write_throughput_busy", "read_lat_avg", "read_lat_max",
              "write_lat_avg", "write_lat_max", "remote_beat_fraction",
              "p2_max")
CLASS_INTS = ("masters", "txns_done", "txns_total", "deadline_txns",
              "deadline_misses")


def rel_gap(got, want) -> float:
    got = np.asarray(got, np.float64).ravel()
    want = np.asarray(want, np.float64).ravel()
    if got.shape != want.shape:
        return float("inf")
    both_nan = np.isnan(got) & np.isnan(want)
    diff = np.abs(got - want)
    scale = np.abs(want)
    gap = np.where(scale > 0, diff / np.where(scale > 0, scale, 1),
                   np.where(diff == 0, 0.0, np.inf))
    gap = np.where(both_nan, 0.0, np.where(np.isnan(gap), np.inf, gap))
    return float(gap.max(initial=0.0))


def mismatches(got, want) -> int:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return max(got.size, want.size, 1)
    return int(np.count_nonzero(got.astype(np.int64)
                                != want.astype(np.int64)))


def p2_estimates(height, npos, count) -> np.ndarray:
    """Read the model's raw P-square state out as estimates per group and
    percentile: below five samples the exact percentile of the sample
    buffer, else the central marker."""
    h = np.asarray(height, np.float64)
    c = np.asarray(count)
    out = np.full(h.shape[:2], np.nan)
    for g in range(h.shape[0]):
        if 0 < c[g] < 5:
            buf = np.sort(h[g, 0])[:c[g]]
            out[g] = [np.percentile(buf, q) for q in (50, 95, 99)]
        elif c[g] >= 5:
            out[g] = h[g, :, 2]
    return out


def compare(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
            got_class: Optional[dict] = None,
            want_class: Optional[dict] = None) -> Dict[str, float]:
    """The three numbers for one design point (``p2_rel_gap`` only where
    the point was collected in streaming form)."""
    ints = sum(mismatches(got[k], want[k]) for k in INT_KEYS if k in want)
    floats = max(rel_gap(got[k], want[k]) for k in FLOAT_KEYS if k in want)
    out = {"int_mismatches": ints, "stat_rel_gap": floats}
    stream = "p2_quantiles" in want
    if stream:
        est = (got["p2_quantiles"] if "p2_quantiles" in got else
               p2_estimates(got["p2_height"], got["p2_npos"],
                            got["p2_count"]))
        out["p2_rel_gap"] = rel_gap(est, want["p2_quantiles"])
    if want_class is not None:
        if got_class is None or set(got_class) != set(want_class):
            out["int_mismatches"] += 1
            return out
        for cls, w in want_class.items():
            g = got_class[cls]
            for k, v in w.items():
                if k in CLASS_INTS:
                    out["int_mismatches"] += mismatches(g.get(k, -1), v)
                elif stream and "_lat_p" in k:
                    out["p2_rel_gap"] = max(out["p2_rel_gap"],
                                            rel_gap(g.get(k, np.inf), v))
                else:
                    out["stat_rel_gap"] = max(out["stat_rel_gap"],
                                              rel_gap(g.get(k, np.inf), v))
    return out


def within(numbers: Dict[str, float]) -> bool:
    return all(v <= LIMITS[k] for k, v in numbers.items())
