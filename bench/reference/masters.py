"""The ADAS master mix as transactions, rebuilt from a configuration's data.

The model turns a master list into per-port transaction rows in its scenario
layer, which the benchmark times.  The check rebuilds the same rows here from
the same data: each master gets an equal share of the address space in list
order, its generator is seeded with ``seed + 7919 * index``, rows are padded
with zero bursts to the longest, the QoS class sets the arbitration level
and the class index, and a master without a deadline has -1.  The three
generators are the access patterns the mix names: a camera's frame DMA
with vblank cadence, an NPU's tiled reads, weight stream and write-back,
and a CPU's rate-limited random scatter.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

CLASSES = ("safety", "realtime", "besteffort")
LEVEL = {"safety": 0, "realtime": 1, "besteffort": 2}
SEED_STRIDE = 7919


def _rows(iw, b, a, s, lo, hi, txns):
    b = np.asarray(b, np.int64)[:txns]
    a = np.clip(np.asarray(a, np.int64)[:txns], lo, np.maximum(hi - b, lo))
    s = np.clip(np.asarray(s, np.int64)[:txns], 0, 2**30)
    return np.asarray(iw, np.int64)[:txns], b, a, s


def _paced(bursts, rate: float) -> np.ndarray:
    """Issue times that hold a stream to ``rate`` beats a cycle."""
    before = np.concatenate([[0], np.cumsum(bursts)[:-1]])
    return (before / min(max(rate, 1e-6), 1.0)).astype(np.int64)


def camera(lo, hi, txns, rate, seed, params):
    line = int(params.get("line_beats", 120))
    lines = int(params.get("frame_lines", 16))
    readback = bool(params.get("readback", False))
    chunks = max(line // 16, 1)
    frame = lines * chunks * 16
    extra = ((lines + 1) // 2) * 16 if readback else 0
    period = int(np.ceil((frame + extra) / min(max(rate, 1e-6), 1.0)))
    phase = int(np.random.default_rng(seed).integers(0, max(period // 2, 1)))
    buf = min((hi - lo) // 2, frame + 64)
    iw, b, a, s = [], [], [], []
    f = 0
    while len(iw) < txns:
        base, t0, beat = lo + (f % 2) * buf, phase + f * period, 0
        for ln in range(lines):
            for c in range(chunks):
                iw.append(1)
                b.append(16)
                a.append(base + (ln * line + c * 16) % max(buf - 16, 1))
                s.append(t0 + beat)
                beat += 16
            if readback and ln % 2 == 0:
                iw.append(0)
                b.append(16)
                a.append(lo + ((f + 1) % 2) * buf
                         + (ln * line) % max(buf - 16, 1))
                s.append(t0 + beat)
                beat += 16
        f += 1
    return _rows(iw, b, a, s, lo, hi, txns)


def npu(lo, hi, txns, rate, seed, params):
    width = int(params.get("map_width_beats", 512))
    tile_h = int(params.get("tile", 8))
    tile_w = int(params.get("tile_width_beats", 32))
    size = hi - lo
    weights, outputs = lo + size // 2, lo + 3 * size // 4
    in_span, wo_span = max(size // 2 - 16, 1), max(size // 4 - 16, 1)
    per_row = max(width // tile_w, 1)
    t = int(np.random.default_rng(seed).integers(0, 4 * per_row))
    iw, b, a = [], [], []
    while len(iw) < txns:
        tr, tc = t // per_row, t % per_row
        for r in range(tile_h):
            off = ((tr * tile_h + r) * width + tc * tile_w) % in_span
            for c in range(0, tile_w, 8):
                iw.append(0); b.append(8); a.append(lo + off + c)
        for c in range(0, tile_w, 8):
            iw.append(0); b.append(8)
            a.append(weights + (t * tile_w + c) % wo_span)
        for c in range(0, tile_w, 8):
            iw.append(1); b.append(8)
            a.append(outputs + (t * tile_w + c) % wo_span)
        t += 1
    return _rows(iw, b, a, _paced(b, rate), lo, hi, txns)


def cpu(lo, hi, txns, rate, seed, params):
    reads = float(params.get("read_fraction", 0.7))
    rng = np.random.default_rng(seed)
    iw = (rng.random(txns) >= reads).astype(np.int64)
    b = rng.choice([1, 2], size=txns)
    a = lo + rng.integers(0, max(hi - lo - 2, 1), txns)
    return _rows(iw, b, a, _paced(b, rate), lo, hi, txns)


GENERATORS = {"camera": camera, "npu": npu, "cpu": cpu}


def regions(count: int, beats_total: int) -> List[tuple]:
    """Equal consecutive shares of the address space, in list order."""
    share = beats_total // count
    return [(i * share, (i + 1) * share) for i in range(count)]


def build(masters: Sequence[Dict], beats_total: int) -> Dict[str, np.ndarray]:
    """Transaction rows (``is_write``, ``burst``, ``addr``, ``start``
    ``[X, N]``) and per-port ``prio``, ``cls`` and ``deadline`` ``[X]``."""
    rows = []
    for i, (m, (lo, hi)) in enumerate(zip(masters,
                                          regions(len(masters), beats_total))):
        gen = GENERATORS[m["model"]]
        rows.append(gen(lo, hi, int(m["txns"]), float(m["rate"]),
                        int(m["seed"]) + SEED_STRIDE * i,
                        m.get("params", {})))
    n = max(len(r[0]) for r in rows)
    out = {k: np.zeros((len(rows), n), np.int64)
           for k in ("is_write", "burst", "addr", "start")}
    for x, r in enumerate(rows):
        for k, v in zip(("is_write", "burst", "addr", "start"), r):
            out[k][x, :len(v)] = v
    out["prio"] = np.array([LEVEL[m["qos"]] for m in masters])
    out["cls"] = np.array([CLASSES.index(m["qos"]) for m in masters])
    out["deadline"] = np.array([m.get("deadline") or -1 for m in masters])
    return out
