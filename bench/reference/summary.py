"""Per-class summary of one reference run, in the model's key schema.

For each QoS class of the mix: how many masters and transactions, the mean
read and write throughput over the class's masters that issued that
direction, latency percentiles and maximum per direction (acceptance to
completion, and earliest issue to completion as ``*_e2e_*``), and the
deadline count, misses and miss rate.  Exact runs take percentiles of the
per-transaction latencies; streaming runs take the P-square estimates.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

from bench.reference.fabric import CLASSES as GROUP_CLASSES
from bench.reference.masters import CLASSES

PCTS = (50, 95, 99)


def per_class(traffic: Dict[str, np.ndarray], qos: Sequence[str],
              out: Dict[str, np.ndarray]) -> Dict[str, dict]:
    iw = np.asarray(traffic["is_write"])
    real = np.asarray(traffic["burst"]) > 0
    start = np.asarray(traffic["start"])
    dl = np.asarray(traffic["deadline"])
    stream = "p2_quantiles" in out
    result = {}
    for cls in sorted(set(qos)):
        rows = np.array([i for i, c in enumerate(qos) if c == cls])
        cid = CLASSES.index(cls)
        s = {"masters": len(rows), "txns_total": int(real[rows].sum())}
        for d, w in (("read", 0), ("write", 1)):
            has = (real[rows] & (iw[rows] == w)).any(axis=1)
            for k in (f"{d}_throughput", f"{d}_throughput_busy"):
                s[k] = (float(out[k][rows][has].mean()) if has.any()
                        else float("nan"))
        with_dl = rows[dl[rows] >= 0]
        considered = int(real[with_dl].sum())
        if stream:
            s["txns_done"] = int(out["cls_done"][cid].sum())
            for d, w in (("read", 0), ("write", 1)):
                for view, name in ((0, d), (1, f"{d}_e2e")):
                    g = view * 2 * GROUP_CLASSES + cid * 2 + w
                    n = out["p2_count"][g]
                    for i, p in enumerate(PCTS):
                        s[f"{name}_lat_p{p}"] = (
                            float(out["p2_quantiles"][g, i]) if n > 0
                            else float("nan"))
                    s[f"{name}_lat_max"] = (float(out["p2_max"][g]) if n > 0
                                            else float("nan"))
            missed = (int(out["dl_miss"][cid]) + considered
                      - int(out["dl_done"][cid]))
        else:
            acc, com = out["accept_cycle"], out["complete_cycle"]
            done = (com >= 0) & (acc >= 0) & real
            sel = np.zeros_like(done)
            sel[rows] = done[rows]
            s["txns_done"] = int(sel.sum())
            for d, w in (("read", 0), ("write", 1)):
                pick = sel & (iw == w)
                for name, v in ((d, com - acc), (f"{d}_e2e", com - start)):
                    vals = v[pick].astype(np.float64)
                    for p in PCTS:
                        s[f"{name}_lat_p{p}"] = (float(np.percentile(vals, p))
                                                 if vals.size else float("nan"))
                    s[f"{name}_lat_max"] = (float(vals.max()) if vals.size
                                            else float("nan"))
            late = ~done[with_dl] | (com[with_dl] - start[with_dl]
                                     > dl[with_dl][:, None])
            missed = int((real[with_dl] & late).sum())
        s["deadline_txns"] = considered
        s["deadline_misses"] = missed
        s["deadline_miss_rate"] = (missed / considered if considered
                                   else float("nan"))
        result[cls] = s
    return result
