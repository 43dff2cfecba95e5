"""A plain cycle-by-cycle model of the many-ported banked memory fabric.

Written from the fabric's stated semantics, one simulated cycle per loop
iteration and no early exit, time skip, vmap or packed state:

* A port offers its transactions in order; the next one is accepted when
  its earliest-issue cycle has come, its AXI channel has fewer than
  ``outstanding`` commands and at least ``burst`` split-buffer credits, a
  write finds the port's write-data bus free, and a regulated port (QoS
  level 2 or more, with ``reg_rate`` > 0) holds ``min(burst, reg_burst)``
  beats of tokens.  Every port's bucket refills by ``reg_rate`` / 256 beats
  a cycle up to ``reg_burst`` beats.
* An accepted burst's beats take the next ring slots of the port.  Beat
  ``k`` reaches its bank ``cmd_latency + k`` cycles later for a write (one
  data beat a cycle) or ``cmd_latency + k // expand_rate`` for a read.
* Each free bank grants one waiting beat a cycle: lowest QoS level first
  (a beat rises one level per ``qos_aging`` cycles of waiting), then the
  oldest, then round robin from the master after the bank's last winner,
  then the lowest ring slot.  The bank is then busy ``bank_occupancy``
  cycles; the beat's split-buffer credit returns at the grant.
* A granted read beat may return ``bank_occupancy + bank_latency`` cycles
  after its grant; each port returns one beat a cycle, the earliest ready
  first, then the lowest slot.  A write beat is delivered at its grant.
* A transaction completes ``ret_latency`` cycles after its last beat is
  delivered; a port is busy on a channel while it has commands there.
* The run drains on the cycle after which no command is left to offer or
  in flight.

One slice only (no router); see ``address.py``.  Statistics are accumulated
in ``stat_dtype`` (the configuration states float32).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

from bench.reference.address import bank_of_beat
from bench.reference.p2 import P2Group

LEVELS = 8
REGULATED = 2
TOKEN = 256
IDLE, WAITING, GRANTED = 0, 1, 2
NEVER = 2**30
CLASSES = 4           # safety, realtime, besteffort, unclassified


@dataclass
class Knobs:
    """The fabric's timing and policy values for one design point."""
    outstanding: int = 8
    split_buffer: int = 64
    cmd_latency: int = 8
    ret_latency: int = 9
    bank_occupancy: int = 2
    bank_latency: int = 2
    qos_aging: int = 128
    reg_rate: int = 0
    reg_burst: int = 16
    expand_rate: int = 4
    max_burst: int = 16
    max_cycles: int = 200_000

    def ring_slots(self) -> int:
        """Ring slots per port: twice the larger of a channel's command
        beats and the split buffer, rounded up to a power of two."""
        need = 2 * max(self.outstanding * self.max_burst, self.split_buffer)
        return 1 << (need - 1).bit_length()


def age_cap(max_cycles: int, ports: int) -> int:
    """Where the waiting age saturates: the next power of two above the
    horizon, less one, kept small enough that the packed arbitration key
    stays under 2**30."""
    cap = 1 << max(max_cycles, 255).bit_length()
    return min(cap - 1, (2**30 - 1) // (LEVELS * max(ports, 1)) - 1)


def simulate(traffic: Dict[str, np.ndarray], geom: dict, kn: Knobs, *,
             slots: Optional[int] = None, collect: str = "exact",
             stat_dtype=np.float32) -> Dict[str, np.ndarray]:
    """Run the fabric on ``traffic`` (``is_write``, ``burst``, ``addr``,
    ``start`` ``[X, N]``; ``prio``, ``cls``, ``deadline`` ``[X]``) and
    return the statistics the model reports, keyed as it keys them."""
    iw = np.asarray(traffic["is_write"], np.int64)
    burst = np.asarray(traffic["burst"], np.int64)
    addr = np.asarray(traffic["addr"], np.int64)
    start = np.asarray(traffic["start"], np.int64)
    prio = np.clip(np.asarray(traffic["prio"], np.int64), 0, LEVELS - 1)
    X, N = iw.shape
    P = slots or kn.ring_slots()
    cap = age_cap(kn.max_cycles, X)
    nbanks = (geom["num_clusters"] * geom["arrays_per_cluster"]
              * geom["banks_per_array"])
    regulated = (prio >= REGULATED) & (kn.reg_rate > 0)
    zero = burst == 0
    offered = np.where(zero.any(1), zero.argmax(1), N)   # first padding ends
    beat = np.arange(max(int(burst.max(initial=0)), 1))
    banks = bank_of_beat(addr[:, :, None] + beat, geom)   # [X, N, beat]

    next_txn = np.zeros(X, np.int64)
    cmds = np.zeros((X, 2), np.int64)                    # by channel
    credits = np.full((X, 2), kn.split_buffer, np.int64)
    issued = np.zeros(X, np.int64)
    wbus_free = np.zeros(X, np.int64)
    tokens = np.full(X, kn.reg_burst * TOKEN, np.int64)
    busy = np.zeros((X, 3), np.int64)                    # read, write, any
    phase = np.zeros((X, P), np.int64)
    s_write = np.zeros((X, P), np.int64)
    s_bank = np.zeros((X, P), np.int64)
    s_arrive = np.full((X, P), NEVER, np.int64)
    s_ready = np.full((X, P), NEVER, np.int64)
    s_txn = np.zeros((X, P), np.int64)
    bank_free = np.zeros(nbanks, np.int64)
    rr_next = np.zeros(nbanks, np.int64)
    left = np.where(burst > 0, burst, 0)
    accepted = np.full((X, N), -1, np.int64)
    completed = np.full((X, N), -1, np.int64)
    returned = np.zeros(X, np.int64)
    granted_beats = 0
    drained = -1
    stream = (_Stream(traffic, iw, start, stat_dtype)
              if collect == "stream" else None)

    now = 0
    while now < kn.max_cycles:
        # -- acceptance and dispatch, port by port
        tokens = np.minimum(tokens + kn.reg_rate, kn.reg_burst * TOKEN)
        for x in range(X):
            t = next_txn[x]
            if t >= N:
                continue
            b, w = burst[x, t], iw[x, t]
            if (b == 0 or start[x, t] > now or cmds[x, w] >= kn.outstanding
                    or credits[x, w] < b or (w and wbus_free[x] > now)
                    or (regulated[x]
                        and tokens[x] < min(b, kn.reg_burst) * TOKEN)):
                continue
            if regulated[x]:
                tokens[x] -= b * TOKEN
            accepted[x, t] = now
            next_txn[x] += 1
            cmds[x, w] += 1
            credits[x, w] -= b
            if w:
                wbus_free[x] = now + b
            k = np.arange(b)
            p = (issued[x] + k) % P
            phase[x, p] = WAITING
            s_write[x, p] = w
            s_bank[x, p] = banks[x, t, :b]
            s_arrive[x, p] = now + kn.cmd_latency + (
                k if w else k // kn.expand_rate)
            s_ready[x, p] = NEVER
            s_txn[x, p] = t
            issued[x] += b

        # -- per-bank arbitration
        delivered = []                                  # (port, txn) beats
        elig = ((phase == WAITING) & (s_arrive <= now)
                & (bank_free[s_bank] <= now))
        if elig.any():
            xs, ps = np.nonzero(elig)
            bk = s_bank[xs, ps]
            age = np.minimum(now - s_arrive[xs, ps], cap)
            boost = age // kn.qos_aging if kn.qos_aging > 0 else 0
            level = np.clip(prio[xs] - boost, 0, LEVELS - 1)
            rr = (xs - rr_next[bk]) % X
            order = np.lexsort((xs * P + ps, rr, cap - age, level, bk))
            first = np.ones(len(order), bool)
            first[1:] = bk[order][1:] != bk[order][:-1]
            win = order[first]
            wx, wp, wb = xs[win], ps[win], bk[win]
            bank_free[wb] = np.maximum(bank_free[wb], now) + kn.bank_occupancy
            rr_next[wb] = (wx + 1) % X
            phase[wx, wp] = GRANTED
            s_ready[wx, wp] = now + kn.bank_occupancy + kn.bank_latency
            np.add.at(credits, (wx, s_write[wx, wp]), 1)
            granted_beats += len(win)
            wr = s_write[wx, wp] == 1
            delivered += zip(wx[wr].tolist(), s_txn[wx[wr], wp[wr]].tolist())

        # -- read return bus, one beat per port
        back = (phase == GRANTED) & (s_write == 0) & (s_ready <= now)
        if back.any():
            t_min = np.where(back, s_ready, NEVER).min(1)
            pick = back & (s_ready == t_min[:, None])
            has = pick.any(1)
            px = np.nonzero(has)[0]
            pp = pick[px].argmax(1)
            phase[px, pp] = IDLE
            returned[px] += 1
            delivered += zip(px.tolist(), s_txn[px, pp].tolist())
        phase[(phase == GRANTED) & (s_write == 1)] = IDLE

        # -- completion
        done = []
        for x, t in delivered:
            left[x, t] -= 1
            if left[x, t] == 0:
                completed[x, t] = now + kn.ret_latency
                cmds[x, iw[x, t]] -= 1
                done.append((x, t))
        active = cmds > 0
        busy[:, 0] += active[:, 0]
        busy[:, 1] += active[:, 1]
        busy[:, 2] += active.any(1)
        now += 1
        if stream is not None and done:
            stream.cycle(done, accepted, completed)
        if ((next_txn >= offered).all() and (cmds == 0).all()
                and (phase == IDLE).all()):
            drained = now
            break

    out = _port_stats(iw, burst, accepted, completed, busy, stat_dtype)
    out.update(
        all_done=bool(np.all(np.where(burst > 0, completed >= 0, True))),
        beats_done=returned, cycles=np.int64(kn.max_cycles),
        drained_cycle=np.int64(drained),
        effective_cycles=np.int64(drained if drained >= 0 else kn.max_cycles),
        slice_beats=np.array([granted_beats]), remote_beats=np.int64(0),
        remote_beat_fraction=np.float32(0.0))
    if stream is not None:
        out.update(stream.outputs())
    else:
        out.update(accept_cycle=accepted, complete_cycle=completed)
    return out


def _port_stats(iw, burst, accepted, completed, busy, f) -> dict:
    """Per-port throughput, busy throughput and latency, read and write."""
    real = burst > 0
    done = real & (completed >= 0)
    lat = completed - accepted
    sel = {"": done, "read_": done & (iw == 0), "write_": done & (iw == 1)}
    col = {"": 2, "read_": 0, "write_": 1}
    out = {}
    for d, s in sel.items():
        any_ = s.any(1)
        beats = np.where(s, burst, 0).sum(1)
        first = np.where(s, accepted, NEVER).min(1)
        last = np.where(s, completed, -1).max(1)
        span = np.maximum(last - first, 1)
        out[f"{d}throughput"] = np.where(
            any_, beats.astype(f) / span.astype(f), f(0))
        out[f"{d}throughput_busy"] = np.where(
            any_, beats.astype(f) / np.maximum(busy[:, col[d]], 1).astype(f),
            f(0))
    for d in ("read", "write"):
        s = sel[f"{d}_"]
        n = s.sum(1)
        total = np.array([np.sum(lat[x][s[x]].astype(f), dtype=f)
                          for x in range(len(s))], dtype=f)
        out[f"{d}_lat_avg"] = np.where(
            n > 0, total / np.maximum(n, 1).astype(f), f(0))
        out[f"{d}_lat_max"] = np.where(s, lat, 0).max(1).astype(f)
    out["busy_cycles"] = busy[:, 2].copy()
    out["txns_done_port"] = np.stack(
        [sel["read_"].sum(1), sel["write_"].sum(1)], axis=1)
    return out


class _Stream:
    """The streaming collector's per-class bookkeeping: completions by class
    and direction, deadline checks, the largest latency and the P-square
    markers of each (view, class, direction) group.  View 0 is
    acceptance to completion, view 1 earliest issue to completion."""

    def __init__(self, traffic, iw, start, f):
        self.iw, self.start, self.f = iw, start, f
        self.cls = np.asarray(traffic["cls"], np.int64)
        self.deadline = np.asarray(traffic["deadline"], np.int64)
        groups = 4 * CLASSES
        self.p2 = [P2Group(f) for _ in range(groups)]
        self.p2_max = np.zeros(groups, f)
        self.cls_done = np.zeros((CLASSES, 2), np.int64)
        self.dl_done = np.zeros(CLASSES, np.int64)
        self.dl_miss = np.zeros(CLASSES, np.int64)

    def cycle(self, done, accepted, completed) -> None:
        """Fold one cycle's completed transactions in."""
        batch: Dict[int, list] = {}
        for x, t in done:
            c, w = self.cls[x], self.iw[x, t]
            end = completed[x, t]
            lat, e2e = end - accepted[x, t], end - self.start[x, t]
            self.cls_done[c, w] += 1
            if self.deadline[x] >= 0:
                self.dl_done[c] += 1
                self.dl_miss[c] += e2e > self.deadline[x]
            g = 2 * c + w
            batch.setdefault(g, []).append(lat)
            batch.setdefault(g + 2 * CLASSES, []).append(e2e)
        for g, vals in batch.items():
            self.p2[g].add(vals)
            self.p2_max[g] = max(self.p2_max[g], self.f(max(vals)))

    def outputs(self) -> dict:
        return dict(cls_done=self.cls_done, dl_done=self.dl_done,
                    dl_miss=self.dl_miss,
                    p2_count=np.array([g.count for g in self.p2]),
                    p2_max=self.p2_max,
                    p2_quantiles=np.stack([g.quantiles() for g in self.p2]))
