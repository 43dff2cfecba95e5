"""The one general generator: (configuration, traffic mix, seed) -> calls.

A configuration (``bench/configs/<name>.json``) is data: the fabric's
``geometry`` (``MemoryGeometry`` fields), its ``params`` (``SimParams``
values), and either a ``masters`` list (an SoC's master mix, run through the
model's scenario layer) or a ``fig4_traffic`` block (the paper's Fig. 4
stream, made here and handed to ``simulate``).  A traffic mix
(``bench/traffic/<name>.json``) is data too: how many design points one call
evaluates (one ``simulate_batch`` over a master mix's shared schedule when
more than one), the knob grid they are drawn from, the pipeline and
collector, how many of a run's points the correctness check replays, and
how long the traced window lasts.

Call ``i`` of a run with seed ``s`` is a pure function of (config, mix, s,
i): its grid points are the ``i``-th consecutive slice of the grid in an
order drawn from ``s`` (every axis varies within a call, and the seeds
between them cover the grid), and its traffic is drawn from ``derive(s,
i)``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.fig4_traffic import random_uniform_full_duplex


def derive(seed: int, index: int) -> int:
    """A 32-bit seed for call ``index`` of a run seeded ``seed`` (any
    integer, however large)."""
    words = [seed & (2**64 - 1), index]
    return int(np.random.SeedSequence(words).generate_state(1)[0])


def grid_points(axes: Dict[str, list]) -> List[Dict[str, int]]:
    """Every combination of the knob axes, first axis varying fastest."""
    names = list(axes)
    total = int(np.prod([len(axes[n]) for n in names]))
    out = []
    for i in range(total):
        knobs, r = {}, i
        for n in names:
            knobs[n] = axes[n][r % len(axes[n])]
            r //= len(axes[n])
        out.append(knobs)
    return out


@dataclass
class Call:
    """One timed call into the model and what the run keeps of it."""
    index: int
    knobs: List[Dict[str, int]]        # the design points, in call order
    traffic_seed: int
    lanes: int = 1                     # vmapped lanes, padding included
    spans: Dict[str, tuple] = field(default_factory=dict)  # name -> (t0, t1)
    points: List[Dict[str, np.ndarray]] = field(default_factory=list)
    per_class: List[Optional[dict]] = field(default_factory=list)
    fig4: Optional[Dict[str, np.ndarray]] = None

    def effective(self) -> np.ndarray:
        return np.array([int(p["effective_cycles"]) for p in self.points])

    def stepped(self) -> np.ndarray:
        """Cycles each point's loop stepped: its effective cycles less the
        idle stretches the time skip jumped."""
        return self.effective() - np.array(
            [int(p["skipped_cycles"]) for p in self.points])


class Workload:
    """Builds and runs the calls of one cell through the model's public
    entry points (``simulate``, ``Scenario.compile`` and
    ``CompiledScenario.simulate`` / ``simulate_batch``)."""

    def __init__(self, config: dict, mix: dict, seed: int, devices: int):
        from repro.core.address import MemoryGeometry
        from repro.core.simulator import SCHEDULE_PIPELINE, SimParams
        self.config, self.mix, self.seed = config, mix, seed
        self.devices = devices
        self.geom = MemoryGeometry(**config["geometry"])
        base = dict(config["params"])
        if mix.get("pipeline") == "schedule":
            base["stages"] = SCHEDULE_PIPELINE
        if mix.get("collect"):
            base["collect"] = mix["collect"]
        self.base = SimParams(geom=self.geom, **base)
        grid = grid_points(mix["grid_axes"]) if "grid_axes" in mix else [{}]
        order = np.random.default_rng([seed & (2**64 - 1), 2]).permutation(
            len(grid))
        self.grid = [grid[i] for i in order]
        self.per_call = int(mix["points_per_call"])
        if "fig4_traffic" in config and self.per_call != 1:
            raise ValueError("Fig. 4 traffic runs one point a call")

    def call(self, index: int) -> Call:
        n = len(self.grid)
        knobs = [self.grid[(index * self.per_call + j) % n]
                 for j in range(self.per_call)]
        return Call(index, knobs, derive(self.seed, index))

    def params(self, c: Call):
        return [replace(self.base, **k) for k in c.knobs]

    def masters(self, c: Call) -> List[dict]:
        """The configuration's master list, every seed offset by the
        call's traffic seed."""
        return [dict(m, seed=int(m["seed"]) + c.traffic_seed)
                for m in self.config["masters"]]

    def fig4_traffic(self, c: Call) -> Dict[str, np.ndarray]:
        f = self.config["fig4_traffic"]
        return random_uniform_full_duplex(
            f["masters"], f["txns"], burst=f["burst"],
            read_fraction=f["read_fraction"],
            beats_total=self.geom.beats_total, seed=c.traffic_seed)

    def run(self, c: Call, clock: Callable[[], float] = time.perf_counter,
            annotate=None) -> Call:
        """Make the call's traffic, call the model, keep its outputs.
        ``annotate(name)`` gives a context manager that marks a host span
        in the profiler's trace (or does nothing)."""
        from repro.core import simulator
        from repro.scenarios.spec import MasterSpec, Scenario
        prms = self.params(c)
        if "fig4_traffic" in self.config:
            t = self.fig4_traffic(c)
            c.fig4 = t
            trace = simulator.Trace(t["is_write"], t["burst"], t["addr"],
                                    t["start"], t["prio"])
            with _span(c, "call", clock, annotate):
                c.points = [simulator.simulate(trace, prms[0])]
            c.per_class = [None]
        else:
            with _span(c, "scenario", clock, annotate):
                compiled = Scenario(
                    self.config["name"],
                    [MasterSpec(**m) for m in self.masters(c)],
                    self.geom).compile()
            with _span(c, "call", clock, annotate):
                if len(prms) == 1:
                    results = [compiled.simulate(prms[0])]
                else:
                    results = compiled.simulate_batch(prms)
            c.points = [r.metrics for r in results]
            c.per_class = [r.per_class for r in results]
        c.lanes = self.lanes(len(prms))
        return c

    def lanes(self, points: int) -> int:
        """Vmapped lanes, padding included, as ``simulate_batch`` lays a
        call of ``points`` out over the devices."""
        return -(-points // self.devices) * self.devices


class _span:
    """Host span of one call phase: wall times on ``clock`` and, when
    tracing, a profiler annotation."""

    def __init__(self, c: Call, name: str, clock, annotate):
        self.c, self.name, self.clock = c, name, clock
        self.ann = annotate(f"bench.{name}") if annotate else None

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = self.clock()

    def __exit__(self, *exc):
        self.c.spans[self.name] = (self.t0, self.clock())
        if self.ann is not None:
            self.ann.__exit__(*exc)
