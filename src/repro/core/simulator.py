"""Cycle-level simulator of the many-ported banked shared memory (§II-C/§III).

Faithful model of the prototype:
  * X master ports, 256-bit (1 beat/cycle) read-return and write-data buses
  * two-level split-by-4 dispatch: a burst fans out at 4 beats/cycle (one per
    cluster); beat → (slice, cluster, array, bank) via ``core.address``
    (slice select above the cluster split, then structural round-robin +
    fractal hash)
  * per-bank QoS-aware arbitration: priority-first (per-master levels carried
    by ``Trace.prio``, 0 = most critical), FCFS within a level, round-robin
    tie-break among masters, and an anti-starvation aging bonus that promotes
    a waiting beat one level every ``qos_aging`` cycles; with all priorities
    equal (the default) this degrades exactly to the original FCFS+RR
  * an optional per-port token-bucket regulator that throttles best-effort
    masters (``Trace.prio >= REGULATED_PRIO``) to ``reg_rate/256`` beats per
    cycle with a ``reg_burst``-beat burst allowance (``reg_rate=0`` disables)
  * SRAMs at half the fabric clock ⇒ a bank is busy 2 fabric cycles per beat
  * per-port outstanding-command credits (8 default; Table I sweeps 16/1) and
    a 64-beat split/dispatch buffer providing backpressure
  * read latency is measured from command *acceptance* (credit granted) to the
    cycle the last beat leaves the return bus — the AXI-observable latency the
    paper reports; AXI5 read-data chunking ⇒ beats may return out of order.

Multi-slice fabric (§IV scalability/modularity): ``geom.num_slices`` tiles S
identical memory instances behind an inter-slice router.  Each master port
attaches to a home slice (``core.address.master_home_slices``); a beat whose
target bank lives in a remote slice pays ``hop_latency`` fabric cycles per
ring hop on the command path and again on the read-return path, and its whole
burst must win per-destination-slice ingress credits (``slice_ingress``
outstanding remote beats per slice, 0 = uncapped) before the port may accept
the command — the router's backpressure.  With ``num_slices=1`` every beat is
local, no credit is ever consumed, and results are bit-for-bit identical to
the single-slice simulator (pinned by the golden regression test).

Cycle core
----------

The scan carry is a typed :class:`repro.core.state.SimState` pytree with
narrow storage dtypes (``core/state.py`` has the field table); stages widen
fields to int32 on read and narrow on write.  Beat slots are laid out
``[X, P]`` (port-major), so the per-port return bus and dispatch ring are
dense vector ops along ``P``; only per-bank arbitration reduces across
ports: on the chip through dense [X, NB, P] bank masks, on a CPU through
one flat comparator tree (``SimParams.arbiter``: the jax reduction, or the
Pallas kernel of ``kernels/bank_arbiter/``; bit-exact either way).

The cycle body runs registered stages (:func:`register_stage`) in the order
``SimParams.stages`` names.  Two pipelines give bit-exact results:
``DEFAULT_PIPELINE`` reads per-beat bank tables the host precomputes, and
``SCHEDULE_PIPELINE`` advances packed per-master event schedules inside the
scan, routing beats to banks on the fly and keeping per-command state in a
fixed-width in-flight table (it alone skips idle stretches and can stream
its statistics).  Both run through one input path (:func:`_host_input`,
:func:`_device_input`), one compiled-program cache
(:func:`_core_jitted_cached`) and one setup (:func:`_setup`); their own
stages share the acceptance gates, the ring write, the completion step and
the port-level metrics.

Everything is a fixed-size jnp array and one ``lax.scan`` over cycles, so a
whole sweep runs as a single vmapped scan: :func:`simulate_batch` evaluates a
stack of (trace, dynamic-parameter) points in one compiled ``vmap``-of-``scan``
call, and shards the batch axis across devices when more than one is visible
(see :func:`batch_sharding`).  Parameters that only appear as *values* in the
dataflow (outstanding credits, buffer depth, pipeline latencies, bank
occupancy, hop latency, ingress credits) are passed as a traced ``dyn`` vector
so they can differ per point; parameters that shape the program (geometry,
banking, burst ceiling, cycle count, pipeline, arbiter backend) stay static.

Traces may carry per-transaction earliest-issue times (``Trace.start``), which
gates command acceptance — this is how the scenario engine expresses injection
rates and sensor periodicity (camera vblank, Radar chirp cadence).

Comparator topologies (§II-A, used by benchmarks/comparators.py):
  * ``banking='paper'``     — the proposed structure
  * ``banking='linear'``    — monolithic region-per-bank banking (no burst
                              splitting): masters camp on single banks
  * ``banking='no_fractal'``— round-robin clusters but no second-level hash:
                              power-of-two strides re-collide
"""
from __future__ import annotations

from dataclasses import dataclass, replace as dataclasses_replace
from functools import lru_cache, partial
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.address import (MemoryGeometry, flat_bank_id,
                                flat_bank_id_dev, master_home_slices,
                                slice_of_bank, slice_of_beat,
                                slice_of_beat_dev)
from repro.core.percentile import STREAM_PCTS, p2_update
from repro.core.qos import aging_boost, arbitration_priority_key
from repro.core.state import (INF32, SLOT_GRANTED, SLOT_IDLE, SLOT_WAITING,
                              SimState, bank_dtype, init_state,
                              pack_slot_flags, unpack_slot_flags, widen)
from repro.kernels.bank_arbiter.ops import bank_arbiter_winners, bid_winners
from repro.kernels.bank_arbiter.ref import KEY_FILLER

#: SimParams fields that enter the scan as traced *values* (per-point in a
#: batched sweep).  Order defines the layout of the ``dyn`` vector.
DYN_FIELDS = ("outstanding", "split_buffer", "cmd_latency", "ret_latency",
              "bank_occupancy", "bank_latency", "qos_aging", "reg_rate",
              "reg_burst", "hop_latency", "slice_ingress")

#: distinct QoS priority levels the arbiter keys on (0 = most critical)
PRIO_LEVELS = 8
#: masters at this priority level or numerically higher (less critical)
#: are subject to the regulator
REGULATED_PRIO = 2
#: fixed-point scale of the regulator token bucket (tokens per beat)
REG_SCALE = 256

#: ``max_burst`` ceiling — per-transaction remaining-beat counters are int8
MAX_BURST_LIMIT = 127
#: ``outstanding``/``split_buffer`` ceiling — credit counters are int16
CREDIT_LIMIT = 2**14

#: streaming-collection QoS class slots: the three QOS_CLASSES in their
#: canonical order plus one trailing "unclassified" slot (padding rows,
#: schedules compiled without class info)
STREAM_CLASSES = 4
#: class index of the trailing unclassified slot
UNCLASSIFIED = STREAM_CLASSES - 1


@dataclass(frozen=True)
class SimParams:
    geom: MemoryGeometry = MemoryGeometry()
    outstanding: int = 8         # commands per port (Table I: 16 / 1)
    split_buffer: int = 64       # beats in flight past the splitter, per port
    cmd_latency: int = 8         # port -> bank-queue pipeline (fabric cycles)
    ret_latency: int = 9         # bank -> port pipeline
    bank_occupancy: int = 2      # SRAM at 500 MHz vs 1 GHz fabric
    bank_latency: int = 2       # access latency before data heads back
    qos_aging: int = 128         # cycles of waiting per priority-level boost
                                 # (anti-starvation; 0 = pure priority)
    reg_rate: int = 0            # regulator refill, 1/256 beats per cycle
                                 # (0 = regulator off; 256 = 1 beat/cycle)
    reg_burst: int = 16          # regulator bucket depth, beats
    hop_latency: int = 6         # inter-slice router, cycles per ring hop
                                 # (charged on command AND read-return paths)
    slice_ingress: int = 0       # remote beats in flight per destination
                                 # slice (router backpressure; 0 = uncapped)
    expand_rate: int = 4         # split-by-4: beats entering fabric per cycle
    max_burst: int = 16
    banking: str = "paper"       # paper | linear | no_fractal
    max_cycles: int = 200_000
    slots_override: Optional[int] = None  # force a common ring size (batching)
    stages: Optional[Tuple[str, ...]] = None  # None = DEFAULT_PIPELINE
    arbiter: str = "jax"         # per-bank comparator backend: jax | pallas
    collect: str = "exact"       # exact | stream — per-txn timestamps vs
                                 # fixed-size streaming (P²) accumulators;
                                 # stream requires the schedule pipeline
    inflight_override: Optional[int] = None  # force a common in-flight-table
                                 # size (batching; schedule pipeline only)
    early_exit: bool = True      # stop scanning K-cycle blocks once the
                                 # fabric drains (bit-exact vs fixed horizon)
    block_cycles: int = 32       # K: cycles per early-exit scan block
    time_skip: bool = True       # schedule pipeline + early_exit: jump idle
                                 # stretches to the next event's issue time

    @property
    def slots_per_master(self) -> int:
        # enough ring slots for every accepted command's beats
        if self.slots_override is not None:
            return int(self.slots_override)
        return int(2 ** np.ceil(np.log2(
            max(self.outstanding * self.max_burst, self.split_buffer) * 2)))

    @property
    def inflight_slots(self) -> int:
        """Schedule-pipeline in-flight table width: a port's two AXI channels
        can each hold ``outstanding`` live commands, so 2× covers them."""
        if self.inflight_override is not None:
            return int(self.inflight_override)
        return int(2 ** np.ceil(np.log2(max(2 * self.outstanding, 2))))

    def static_key(self) -> tuple:
        """Fields that must agree across every point of one compiled batch."""
        return (self.geom, self.expand_rate, self.max_burst, self.banking,
                self.max_cycles, self.stages, self.arbiter, self.collect,
                self.early_exit, self.block_cycles, self.time_skip)

    def dyn_vector(self) -> np.ndarray:
        """The traced per-point parameter vector (see ``DYN_FIELDS``)."""
        if not (0 <= self.outstanding < CREDIT_LIMIT
                and 0 <= self.split_buffer < CREDIT_LIMIT):
            raise ValueError(
                f"outstanding/split_buffer must be in [0, {CREDIT_LIMIT}) "
                f"(int16 credit counters); got {self.outstanding}/"
                f"{self.split_buffer}")
        if self.reg_burst * REG_SCALE >= 2**30:
            raise ValueError(f"reg_burst too large: {self.reg_burst}")
        return np.array([getattr(self, f) for f in DYN_FIELDS], np.int32)

    def pipeline(self) -> Tuple[str, ...]:
        """The stage names ``cycle()`` will run, validated loudly."""
        names = tuple(self.stages) if self.stages else DEFAULT_PIPELINE
        unknown = [n for n in names if n not in STAGE_REGISTRY]
        if unknown:
            raise ValueError(
                f"unknown stage(s) {unknown}; registered stages: "
                f"{sorted(STAGE_REGISTRY)}")
        if self.collect not in ("exact", "stream"):
            raise ValueError(f"collect must be 'exact' or 'stream'; "
                             f"got {self.collect!r}")
        if self.collect == "stream" and "retire_sched" not in names:
            raise ValueError(
                "collect='stream' needs the schedule pipeline (streaming "
                "accumulators live in the in-flight table the dense stages "
                "do not maintain); set stages=SCHEDULE_PIPELINE")
        if self.block_cycles < 1:
            raise ValueError(
                f"block_cycles must be >= 1; got {self.block_cycles}")
        return names

    def uses_schedule(self) -> bool:
        """True when this point runs the event-schedule pipeline (packed
        per-master schedules advanced in-scan, no dense beat tables)."""
        names = self.pipeline()
        return "accept_sched" in names or "accept_dispatch_sched" in names


def bank_of(addr, prm: SimParams):
    g = prm.geom
    if prm.banking == "paper":
        return flat_bank_id(addr, g)
    if prm.banking == "linear":
        a = np.asarray(addr).astype(np.int64)
        region = g.beats_total // g.num_banks
        return np.clip(a // region, 0, g.num_banks - 1).astype(np.int32)
    if prm.banking == "no_fractal":  # structural split only, no hash
        sl, local = slice_of_beat(addr, g)
        a = np.asarray(local).astype(np.int64)
        c = a % g.num_clusters
        arr = (a // g.num_clusters) % g.arrays_per_cluster
        bank = (a // (g.num_clusters * g.arrays_per_cluster)) % g.banks_per_array
        flat = ((c * g.arrays_per_cluster + arr) * g.banks_per_array + bank)
        return (np.asarray(sl).astype(np.int64) * g.banks_per_slice
                + flat).astype(np.int32)
    raise ValueError(prm.banking)


def bank_of_dev(addr, prm: SimParams):
    """Traced (jnp, int32) twin of :func:`bank_of` — the schedule pipeline
    maps the candidate burst's beats to banks *inside* the scan instead of
    reading the dense precomputed [X, N, max_burst] tables.  Bit-exact
    against the numpy path for every banking comparator (parity-tested);
    addresses must already be validated in [0, beats_total)."""
    g = prm.geom
    if prm.banking == "paper":
        return flat_bank_id_dev(addr, g)
    if prm.banking == "linear":
        region = g.beats_total // g.num_banks
        return jnp.clip(addr // region, 0, g.num_banks - 1)
    if prm.banking == "no_fractal":
        sl, local = slice_of_beat_dev(addr, g)
        c = local % g.num_clusters
        arr = (local // g.num_clusters) % g.arrays_per_cluster
        bank = (local // (g.num_clusters * g.arrays_per_cluster)) \
            % g.banks_per_array
        flat = (c * g.arrays_per_cluster + arr) * g.banks_per_array + bank
        return sl * g.banks_per_slice + flat
    raise ValueError(prm.banking)


# ---------------------------------------------------------------------------
# Trace container: per master, padded to a common transaction count
# ---------------------------------------------------------------------------

@dataclass
class Trace:
    """is_write/burst/addr: [X, N] int32 (addr in beat units; burst==0 ⇒ pad).

    ``start`` (optional, [X, N] int32) is the earliest fabric cycle at which a
    transaction may be *offered* at its port — the injection-timing hook used
    by the scenario engine.  ``None`` means every transaction is ready at
    cycle 0 (the original back-to-back behaviour, bit-for-bit).

    ``prio`` (optional, [X] int32) is the per-master QoS priority level
    (0 = most critical, up to ``PRIO_LEVELS - 1``); the scenario engine
    derives it from the QoS class.  ``None`` means every master is level 0,
    which makes the arbiter behave exactly like the original QoS-blind
    FCFS+RR and exempts every port from the regulator.
    """
    is_write: np.ndarray
    burst: np.ndarray
    addr: np.ndarray
    start: Optional[np.ndarray] = None
    prio: Optional[np.ndarray] = None

    @property
    def num_masters(self) -> int:
        return self.is_write.shape[0]

    @property
    def num_txns(self) -> int:
        return self.is_write.shape[1]

    def start_or_zeros(self) -> np.ndarray:
        if self.start is None:
            return np.zeros_like(np.asarray(self.is_write, np.int32))
        return np.asarray(self.start, np.int32)

    def prio_or_zeros(self) -> np.ndarray:
        if self.prio is None:
            return np.zeros((self.num_masters,), np.int32)
        return np.asarray(self.prio, np.int32)


def _precompute_beats(trace: Trace, prm: SimParams):
    """Static per-beat routing info (numpy): global bank ids, inter-slice
    hop counts, and per-transaction ingress-credit needs
    ([X, N, num_slices] remote beats per destination slice).

    Hops and ingress needs derive from the *bank's* slice (``bank_id //
    banks_per_slice``) — the slice whose ingress the beat actually enters —
    so the router's credit consumption, release, and per-slice counters stay
    consistent under every banking comparator (with ``banking="paper"`` this
    equals ``slice_of_beat``'s slice by construction)."""
    g = prm.geom
    if prm.max_burst > MAX_BURST_LIMIT:
        raise ValueError(f"max_burst must be <= {MAX_BURST_LIMIT} "
                         f"(int8 beat counters); got {prm.max_burst}")
    X, N = trace.addr.shape
    off = np.arange(prm.max_burst)[None, None, :]
    beat_addr = trace.addr[..., None] + off
    valid = off < trace.burst[..., None]
    # loud domain check: an out-of-range beat would map to a phantom slice/
    # bank the scan's segment ops silently drop (the transaction would never
    # complete and the run would spin to max_cycles)
    oob = valid & ((beat_addr < 0) | (beat_addr >= g.beats_total))
    if oob.any():
        bad = np.argwhere(oob)[0]
        raise ValueError(
            f"trace addresses out of range: master {bad[0]} txn {bad[1]} "
            f"touches beat {int(beat_addr[tuple(bad)])} but the fabric has "
            f"{g.beats_total} beats ({g.num_slices} slice(s))")
    flat = beat_addr.reshape(-1)
    banks = bank_of(flat, prm).reshape(X, N, prm.max_burst)
    home = master_home_slices(X, g)                           # [X]
    tgt = slice_of_bank(banks, g)                             # [X, N, mb]
    d = np.abs(tgt - home[:, None, None])
    hops = np.minimum(d, g.num_slices - d)                    # ring distance
    hops = np.where(valid, hops, 0).astype(np.int32)
    remote = valid & (hops > 0)
    ingress = np.stack([(remote & (tgt == s)).sum(axis=-1)
                        for s in range(g.num_slices)], axis=-1)
    return banks.astype(np.int32), hops, ingress.astype(np.int32)


def _validate_schedule(sched, prm: SimParams) -> None:
    """Loud domain checks mirroring :func:`_precompute_beats` (which the
    schedule path skips): an out-of-range beat would route to a phantom
    bank and spin to max_cycles; a burst past ``max_burst`` would never
    drain its tail beats."""
    g = prm.geom
    b = np.asarray(sched.burst)
    a = np.asarray(sched.addr)
    real = b > 0
    if b.max(initial=0) > prm.max_burst:
        bad = np.argwhere(b > prm.max_burst)[0]
        raise ValueError(
            f"schedule burst {int(b[tuple(bad)])} at master {bad[0]} event "
            f"{bad[1]} exceeds max_burst={prm.max_burst} — beats past the "
            "dispatch window would never issue")
    oob = real & ((a < 0) | (a + b > g.beats_total))
    if oob.any():
        bad = np.argwhere(oob)[0]
        raise ValueError(
            f"schedule addresses out of range: master {bad[0]} event "
            f"{bad[1]} touches beat {int(a[tuple(bad)] + b[tuple(bad)]) - 1} "
            f"but the fabric has {g.beats_total} beats "
            f"({g.num_slices} slice(s))")


# ---------------------------------------------------------------------------
# The input path: host preparation, then device conversion
# ---------------------------------------------------------------------------

def _host_input(trace, prm: SimParams) -> tuple:
    """One point's core inputs on the host (``dyn`` apart), validated, from
    a :class:`Trace` or :class:`~repro.core.traffic.EventSchedule`: packed
    event columns for the schedule pipeline (a trace compiles to a schedule
    with no class or deadline), precomputed per-beat bank, hop and ingress
    tables for the dense one (a schedule runs as its trace view)."""
    from repro.core.traffic import EventSchedule, compile_schedule
    if prm.uses_schedule():
        s = (trace if isinstance(trace, EventSchedule)
             else compile_schedule(trace))
        _validate_schedule(s, prm)
        return (np.asarray(s.is_write, np.int8), np.asarray(s.burst, np.int8),
                np.asarray(s.addr, np.int32), np.asarray(s.start, np.int32),
                np.asarray(s.prio, np.int8), np.asarray(s.cls, np.int8),
                np.asarray(s.deadline, np.int32))
    t = trace.to_trace() if isinstance(trace, EventSchedule) else trace
    banks, hops, ing = _precompute_beats(t, prm)
    return (np.asarray(t.is_write, np.int32), np.asarray(t.burst, np.int32),
            banks, hops, ing, t.start_or_zeros(), t.prio_or_zeros())


def _device_input(prm: SimParams, host: tuple, dyn) -> tuple:
    """Host inputs (one point's, or stacked on leading batch axes) and
    ``dyn`` → the core's device arguments, in narrow dtypes: int8 flags,
    bursts, hops, priorities and classes, int16 ingress, banks the
    narrowest dtype that indexes the fabric's banks, int32 the rest."""
    if prm.uses_schedule():
        kinds = (jnp.int8, jnp.int8, jnp.int32, jnp.int32, jnp.int8,
                 jnp.int8, jnp.int32)
    else:
        kinds = (jnp.int8, jnp.int8, bank_dtype(prm.geom.num_banks),
                 jnp.int8, jnp.int16, jnp.int32, jnp.int8)
    return tuple(jnp.asarray(a, k) for a, k in
                 zip(host + (dyn,), kinds + (jnp.int32,)))


def simulate(trace, prm: SimParams = SimParams()) -> Dict[str, np.ndarray]:
    """Run the sim; returns per-port and per-txn statistics (numpy).

    Accepts a dense :class:`Trace` or a packed
    :class:`~repro.core.traffic.EventSchedule`; ``prm.stages`` selects the
    pipeline (``SCHEDULE_PIPELINE`` advances schedules in-scan, the default
    dense pipeline precomputes beat tables) and inputs are converted to
    match."""
    with jax.profiler.TraceAnnotation("repro.simulate"):
        with jax.profiler.TraceAnnotation("repro.prepare"):
            args = _device_input(prm, _host_input(trace, prm),
                                 prm.dyn_vector())
        out = _core_jitted_cached(_static_prm(prm), "single", False)(*args)
        with jax.profiler.TraceAnnotation("repro.fetch"):
            return jax.tree_util.tree_map(np.asarray, out)


def compile_simulate(trace, prm: SimParams):
    """AOT-compile :func:`simulate` for this (trace, prm); returns a
    zero-argument runner producing the same metrics dict.

    Benchmarks use this to time a *warm* run without first paying a
    compile+execute call — e.g. the early-exit ON/OFF wall-clock gate,
    where one fixed-horizon execution is expensive enough that running it
    twice just to warm the jit cache would dominate the job.  The runner
    keeps its prepared device inputs (the cores donate nothing), so it can
    be called any number of times.  ``run.compiled`` is the compiled
    program, for callers that inspect it (``as_text()``).
    """
    args = _device_input(prm, _host_input(trace, prm), prm.dyn_vector())
    compiled = _core_jitted_cached(_static_prm(prm), "single",
                                   False).lower(*args).compile()

    def run():
        out = jax.block_until_ready(compiled(*args))
        return jax.tree_util.tree_map(np.asarray, out)

    run.compiled = compiled
    return run


def batch_envelope(prms: Sequence[SimParams]) -> SimParams:
    """The static envelope shared by a batch: every point must agree on the
    program-shaping fields; the beat-slot ring (and, on the schedule
    pipeline, the in-flight table) is sized for the largest point so one
    compiled scan serves all of them."""
    if not prms:
        raise ValueError("empty parameter batch")
    key = prms[0].static_key()
    for p in prms[1:]:
        if p.static_key() != key:
            raise ValueError(
                "batched points must share geom/expand_rate/max_burst/"
                "banking/max_cycles/stages/arbiter/collect/early_exit/"
                f"block_cycles/time_skip; got {p.static_key()} vs {key}")
    slots = max(p.slots_per_master for p in prms)
    inflight = max(p.inflight_slots for p in prms)
    return dataclasses_replace(prms[0], slots_override=slots,
                               inflight_override=inflight)


def batch_sharding(batch_size: int, axis: int = 0):
    """``NamedSharding`` that splits batch ``axis`` across every visible
    device, or ``None`` on a single device.  With several devices the batch
    must be a device multiple (:func:`simulate_batch` pads up to one);
    anything else raises rather than leaving the grid on one device."""
    devices = jax.devices()
    if len(devices) == 1:
        return None
    if batch_size % len(devices):
        raise ValueError(f"batch of {batch_size} does not split across "
                         f"{len(devices)} devices; pad it to a multiple")
    mesh = jax.sharding.Mesh(np.array(devices), ("batch",))
    return jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*([None] * axis), "batch"))


def _pad_batch(arrs: list, pad: int) -> list:
    """Repeat each stacked array's last row ``pad`` times — inert padding
    lanes whose outputs are sliced off before the caller sees them."""
    if pad == 0:
        return arrs
    return [np.concatenate([a, np.repeat(a[-1:], pad, axis=0)]) for a in arrs]


@dataclass(frozen=True)
class PreparedBatch:
    """What :func:`simulate_batch` runs: the compiled program and its placed
    device inputs.  ``args[batched:]`` carry the (padded) batch on their
    leading ``lead`` axes."""
    fn: Callable
    args: tuple
    size: int          # the caller's point count B
    lead: int          # leading batch axes: 1, or 2 (chunks, points) chunked
    batched: int       # index of the first batched input

    def run(self) -> Dict[str, np.ndarray]:
        out = self.fn(*self.args)
        with jax.profiler.TraceAnnotation("repro.fetch"):
            return jax.tree_util.tree_map(
                lambda a: np.asarray(a).reshape((-1,) + a.shape[self.lead:])
                [:self.size], out)


def simulate_batch(traces, prms: Sequence[SimParams], *,
                   shard: bool = True,
                   chunk: Optional[int] = None) -> Dict[str, np.ndarray]:
    """Run B (trace, params) points as ONE compiled ``vmap``-of-``scan``.

    All traces must already share a common [X, N] shape (see
    ``core.traffic.stack_traces``) and all params must share their static
    envelope (see :func:`batch_envelope`).  Returns the same metrics dict as
    :func:`simulate` with a leading batch axis; each row is bit-for-bit equal
    to ``simulate(traces[i], replace(prms[i], slots_override=envelope))``.

    Scaling knobs:

    * **Shared trace** — pass ``traces`` of length 1 with B > 1 parameter
      points and the trace enters the compiled program *unbatched*
      (``vmap`` ``in_axes=None``): a 100k-point parameter grid carries one
      copy of the workload instead of 100k.
    * **Chunking** (``chunk=C``) — the batch streams through a
      ``lax.map`` over ``ceil(B / C)`` chunks of C vmapped points each, so
      peak live memory is one chunk's worth, not the whole grid's;
      non-divisible batches are padded with inert repeat-lanes and sliced
      back to B.  Combine with ``collect="stream"`` points to keep the
      *outputs* fixed-size too.
    * **Sharding** (``shard=True``, default) — with more than one JAX
      device, the batch axis (each chunk's point axis when chunked) is
      split across every device via :func:`batch_sharding`.  A batch or
      chunk the device count does not divide is padded up to the device
      multiple and sliced back, on every path.
    """
    with jax.profiler.TraceAnnotation("repro.simulate"):
        with jax.profiler.TraceAnnotation("repro.prepare"):
            prepared = prepare_batch(traces, prms, shard=shard, chunk=chunk)
        return prepared.run()


def prepare_batch(traces, prms: Sequence[SimParams], *, shard: bool = True,
                  chunk: Optional[int] = None) -> PreparedBatch:
    """Validate, pad, place and pick the program for :func:`simulate_batch`
    (same arguments) without running it."""
    if not prms:
        raise ValueError("empty parameter batch")
    B = len(prms)
    shared = len(traces) == 1 and B > 1
    if not shared and len(traces) != B:
        raise ValueError(f"{len(traces)} traces vs {len(prms)} param points "
                         "(pass one trace to share it across all points)")
    env = batch_envelope(prms)
    dyn = np.stack([p.dyn_vector() for p in prms])
    per = [_host_input(t, p) for t, p in zip(traces, prms)]
    shape = per[0][0].shape
    for h in per[1:]:
        if h[0].shape != shape:
            raise ValueError("all traces in a batch must share [X, N]; "
                             f"got {h[0].shape} vs {shape}")
    rows = [dyn] if shared else [np.stack(c) for c in zip(*per)] + [dyn]

    ndev = len(jax.devices()) if shard else 1
    chunked = chunk is not None and 0 < chunk < B
    if chunked:
        C = -(-chunk // ndev) * ndev          # every chunk splits evenly
        lead = (-(-B // C), C)
    else:
        lead = (-(-B // ndev) * ndev,)
    batched = _pad_batch(rows, int(np.prod(lead)) - B)
    batched = [a.reshape(lead + a.shape[1:]) for a in batched]
    host = per[0] if shared else tuple(batched[:-1])
    args = list(_device_input(env, host, batched[-1]))
    first = len(args) - 1 if shared else 0
    if ndev > 1:
        sharding = batch_sharding(lead[-1], axis=len(lead) - 1)
        args[first:] = [jax.device_put(a, sharding) for a in args[first:]]
    fn = _core_jitted_cached(_static_prm(env), "shared" if shared else "each",
                             chunked)
    return PreparedBatch(fn, tuple(args), B, len(lead), first)


def _static_prm(prm: SimParams) -> SimParams:
    """Canonical jit-cache key: dyn fields travel as traced values, so two
    SimParams differing only in them share one compiled program.  The ring
    and in-flight-table sizes are pinned first (they derive from
    ``outstanding``/``split_buffer`` when not overridden)."""
    return dataclasses_replace(prm, slots_override=prm.slots_per_master,
                               inflight_override=prm.inflight_slots,
                               **{f: 0 for f in DYN_FIELDS})


@lru_cache(maxsize=32)
def _core_jitted_cached(prm: SimParams, lanes: str, chunked: bool):
    """The compiled program every entry point runs: a ``jax.jit`` of
    ``_core`` or ``_core_sched`` (as ``prm``'s pipeline needs), keyed on
    ``_static_prm(prm)`` and the lane layout.  ``lanes``: ``"single"`` one
    point, ``"each"`` a vmapped batch, ``"shared"`` one trace broadcast
    across the points (only ``dyn`` batched); ``chunked`` maps the vmapped
    core over chunks (``lax.map``): peak memory is one chunk of points."""
    core = partial(_core_sched if prm.uses_schedule() else _core, prm=prm)
    if lanes == "single":
        return jax.jit(core)
    shared = lanes == "shared"
    body = jax.vmap(core, in_axes=(None,) * 7 + (0,)) if shared \
        else jax.vmap(core)
    if not chunked:
        return jax.jit(body)
    if shared:
        def fn(*args):
            targs, dyn = args[:7], args[7]        # dyn: [n_chunks, C, ...]
            return jax.lax.map(lambda dd: body(*targs, dd), dyn)
    else:
        def fn(*args):                            # each: [n_chunks, C, ...]
            return jax.lax.map(lambda aa: body(*aa), args)
    return jax.jit(fn)


def _age_cap(prm: SimParams, num_masters: int) -> int:
    """Static saturation point of the FCFS age term: the next power of two
    above ``max_cycles`` (so the FCFS key cannot saturate within a run),
    clamped so the packed (level, age, round-robin) arbitration key stays
    strictly below the int32 ineligible-filler (2**30)."""
    cap = 1 << int(np.ceil(np.log2(max(prm.max_cycles + 1, 256))))
    budget = (2**30 - 1) // (PRIO_LEVELS * max(num_masters, 1)) - 1
    return int(min(cap - 1, budget))


# ---------------------------------------------------------------------------
# Footprint accounting (the scale benchmarks and the serving co-sim)
# ---------------------------------------------------------------------------

def carry_nbytes(prm: SimParams, num_masters: int, num_txns: int) -> int:
    """Bytes of ONE point's scan carry (:class:`SimState`) — what a batch or
    chunk multiplies.  Shape-only (``jax.eval_shape``), nothing allocated."""
    p = _static_prm(prm)

    def build():
        d = {f: jnp.int32(0) for f in DYN_FIELDS}
        return init_state(
            X=num_masters, N=num_txns, P=p.slots_per_master,
            NB=p.geom.num_banks, NSL=p.geom.num_slices,
            tx_burst=jnp.zeros((num_masters, num_txns), jnp.int8),
            d=d, **_carry_layout(p))

    shapes = jax.eval_shape(build)
    return int(sum(int(np.prod(leaf.shape)) * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(shapes)))


def input_nbytes(trace, prm: SimParams) -> int:
    """Bytes of ONE point's prepared simulator inputs.  The dense path's
    precomputed [X, N, max_burst] beat tables dominate it; the schedule
    path carries only the packed event arrays."""
    return int(sum(a.nbytes for a in _host_input(trace, prm))
               + prm.dyn_vector().nbytes)


# ---------------------------------------------------------------------------
# Cycle stages — the registry.
#
# Uniform signature: ``stage(state, wires, ctx) -> (state, wires)``.
#   * ``state`` — the :class:`SimState` carry (narrow storage dtypes; widen
#     on read, narrow on write — see ``core/state.py``)
#   * ``wires`` — intra-cycle values stages hand downstream (``"accept"``,
#     ``"arb"``, ``"ret"``); reset to {} at the top of every cycle
#   * ``ctx``   — static per-run tensors + traced dyn scalars; every stage
#     reads the *current* cycle from ``state.now`` and only ``retire``
#     advances it.
#
# Register replacements (alternate routers/arbiters/instrumentation) under a
# new name and select them via ``SimParams.stages``.
# ---------------------------------------------------------------------------

Stage = Callable[[SimState, dict, dict], Tuple[SimState, dict]]

STAGE_REGISTRY: Dict[str, Stage] = {}

#: acceptance and dispatch run fused as one registered stage (they share the
#: accepted-burst wires and no other stage may observe the state between
#: them); the unfused ``accept``/``dispatch`` names stay registered for
#: custom pipelines and are composition-identical to the fused stage.
DEFAULT_PIPELINE = ("accept_dispatch", "bank_arbitrate", "router_release",
                    "return_bus", "retire")

#: the event-schedule pipeline: packed per-master schedules advanced inside
#: the scan (beat→bank routing computed on the fly, per-command state in the
#: fixed-width in-flight table) — select via ``SimParams(stages=...)``.  The
#: dense DEFAULT_PIPELINE stays the golden-pinned compatibility path.
SCHEDULE_PIPELINE = ("accept_dispatch_sched", "bank_arbitrate",
                     "router_release", "return_bus", "retire_sched")


def register_stage(name: str):
    """Decorator: add a cycle stage to the registry under ``name``."""
    def deco(fn: Stage) -> Stage:
        STAGE_REGISTRY[name] = fn
        return fn
    return deco


def _admit(st: SimState, c, due, burst, is_w, need):
    """The acceptance gates both pipelines share, for each port's next
    command (``due``: it exists and its issue time has come; ``need``
    [X, NSL]: its remote beats per destination slice).  Returns (accepted
    [X], regulator tokens and ingress use after acceptance, and the
    per-port ``reg_held`` with this cycle's regulator holds added).

    Token-bucket regulator: a best-effort port must hold tokens for the
    whole burst — or a full bucket when the burst exceeds the bucket depth,
    in which case the balance goes negative (debt) and the port stalls
    until refill repays it, so a burst > reg_burst is delayed, never
    deadlocked, and the sustained rate cap still holds.

    Router admission: every destination slice of the burst's remote beats
    must have room for them (slice_ingress == 0 disables the cap; local
    beats need no credit, so a 1-slice fabric never blocks here).  Like the
    regulator, the per-slice check clamps the requirement to the cap — a
    burst with more remote beats than slice_ingress is admitted alone and
    drives the counter into debt (delayed, never deadlocked).  Ports are
    admitted credit-aware within the cycle: each port also counts the needs
    of every lower-indexed candidate (an in-order ingress queue, so one
    admission round cannot oversubscribe a slice beyond the debt allowance;
    lower port index = admission priority).

    A regulator hold is a port whose due command passes every gate but the
    token check.  A cycle the time skip jumps has no due command anywhere,
    so it holds nothing, as the stepped cycle would not have."""
    d, ar, now = c["d"], c["ar"], st.now
    reg_gate = c["regulated"] & (d["reg_rate"] > 0)
    reg_tokens = jnp.minimum(st.reg_tokens + d["reg_rate"],
                             d["reg_burst"] * REG_SCALE)
    reg_need = jnp.minimum(burst, d["reg_burst"]) * REG_SCALE
    tokens_ok = ~reg_gate | (reg_tokens >= reg_need)
    room = (due & (st.outstanding[ar, is_w] < d["outstanding"])
            & (st.credits[ar, is_w] >= burst)
            & ((is_w == 0) | (st.fwd_free <= now)))
    pre_can = room & tokens_ok
    need_cand = jnp.where(pre_can[:, None], need, 0)
    prior = jnp.cumsum(need_cand, axis=0) - need_cand       # exclusive [X,NSL]
    need_clamped = jnp.minimum(need, d["slice_ingress"])
    # the per-slice term only applies where the burst actually needs that
    # slice — a port with no remote beats toward a congested slice (local
    # traffic especially) must never stall on its debt
    ing_ok = jnp.all(
        (d["slice_ingress"] == 0) | (need_clamped == 0)
        | (st.ing_used[None, :] + prior + need_clamped
           <= d["slice_ingress"]),
        axis=1)
    can = pre_can & ing_ok
    reg_tokens = reg_tokens - jnp.where(can & reg_gate,
                                        burst * REG_SCALE, 0)
    ing_used = st.ing_used + jnp.sum(
        jnp.where(can[:, None], need, 0), axis=0)
    reg_held = st.reg_held + (room & ing_ok & ~tokens_ok)
    return can, reg_tokens, ing_used, reg_held


def _charge(st: SimState, can, is_w, burst):
    """An accepted command's outstanding count and split-buffer credits on
    its AXI channel (column 0 read, 1 write), as a dense [X, 2] select: one
    command a port, so no scatter."""
    on = can[:, None] & (is_w[:, None] == jnp.arange(2))
    return (st.outstanding + on.astype(st.outstanding.dtype),
            st.credits - jnp.where(on, burst[:, None], 0).astype(
                st.credits.dtype))


@register_stage("accept")
def _stage_accept(st: SimState, wires, c):
    """Command acceptance, one per port per cycle: outstanding credits,
    split-buffer credits, W-data-bus pacing, the best-effort token-bucket
    regulator, and the inter-slice router's admission gate (a burst with
    remote beats needs free ingress credits on every destination slice)."""
    N = c["N"]
    now = st.now
    ar = c["ar"]
    nt = st.next_txn
    has_txn = nt < N
    nt_c = jnp.minimum(nt, N - 1)
    burst = widen(c["tx_burst"][ar, nt_c])
    is_w = widen(c["tx_write"][ar, nt_c])
    ready = c["tx_start"][ar, nt_c] <= now
    need = widen(c["tx_ing"][ar, nt_c])                     # [X, NSL]
    can, reg_tokens, ing_used, reg_held = _admit(
        st, c, has_txn & (burst > 0) & ready, burst, is_w, need)
    accept = jnp.where(can[:, None] & (c["txn_ids"] == nt_c[:, None]),
                       now, st.accept_cycle)
    next_txn = nt + can.astype(jnp.int32)
    outstanding, credits = _charge(st, can, is_w, burst)
    fwd_free = jnp.where(can & (is_w > 0), now + burst, st.fwd_free)
    st = st.replace(next_txn=next_txn, outstanding=outstanding,
                    credits=credits, fwd_free=fwd_free,
                    reg_tokens=reg_tokens, ing_used=ing_used,
                    accept_cycle=accept, reg_held=reg_held)
    return st, dict(wires, accept=dict(can=can, burst=burst, is_w=is_w,
                                       nt_c=nt_c))


def _select_beat(row, offc):
    """Each ring slot's beat value ``row[x, offc[x, p]]`` [X, P], picked
    from its port's accepted-burst ``row`` [X, mb] by a dense one-hot:
    every slot's offset is compared with each beat index, the hit selected
    and summed over the beat axis.  Laid out [X, mb, P] with the ring
    slots on the lane axis, so the mask fuses into its reduction; no
    gather or scatter over the slots.  Exactly one beat index hits each
    slot (``offc`` lies in [0, mb)), so the sum is that beat's value.  On
    a CPU it costs what the per-slot lookups did, so one lowering serves
    every platform."""
    beats = jnp.arange(row.shape[-1], dtype=offc.dtype)[None, :, None]
    hit = offc[:, None, :] == beats                           # [X, mb, P]
    return jnp.sum(jnp.where(hit, row[:, :, None], 0), axis=1,
                   dtype=jnp.int32).astype(row.dtype)


def _write_ring(st: SimState, c, acc, banks, hops, tag) -> SimState:
    """Both dispatch stages: fan the accepted burst's beats (``acc``, the
    accept wires) into the per-master slot ring.  Reads expand
    ``expand_rate`` beats/cycle at the splitter; write data is paced by the
    1-beat/cycle port bus; a remote beat reaches its bank queue
    ``hop_latency`` later per ring hop (the router's command path).

    Dense over ``[X, P]``: slot ``p`` of port ``x`` would hold beat
    ``(p - beats_issued[x]) mod P``; a slot whose beat is inside the burst
    is (re)written, with no scatter.  ``banks``/``hops`` are the burst's
    [X, max_burst] rows, or [X, N, max_burst] tables read at the accepted
    transaction; :func:`_select_beat` picks each slot's beat from its row,
    with no per-slot gather.  Written slots record ``tag`` [X]."""
    prm, d = c["prm"], c["d"]
    can, burst, is_w = acc["can"], acc["burst"], acc["is_w"]

    def row(v):
        return v[c["ar"], acc["nt_c"]] if v.ndim == 3 else v

    off = (c["pos"][None, :] - st.beats_issued[:, None]) % c["P"]  # [X, P]
    wr = can[:, None] & (off < burst[:, None])
    offc = jnp.minimum(off, prm.max_burst - 1)
    bank_new = _select_beat(row(banks), offc)                # [X, P]
    hops_new = _select_beat(row(hops), offc)
    pace = jnp.where(is_w[:, None] > 0, off, off // prm.expand_rate)
    arrive = (st.now + d["cmd_latency"] + pace
              + d["hop_latency"] * widen(hops_new))
    phase, write = unpack_slot_flags(st.sl_flags)
    return st.replace(
        sl_flags=pack_slot_flags(jnp.where(wr, SLOT_WAITING, phase),
                                 jnp.where(wr, is_w[:, None], write)),
        sl_bank=jnp.where(wr, bank_new.astype(st.sl_bank.dtype), st.sl_bank),
        sl_arrive=jnp.where(wr, arrive, st.sl_arrive),
        sl_ready=jnp.where(wr, INF32, st.sl_ready),
        sl_txn=jnp.where(wr, tag[:, None].astype(st.sl_txn.dtype),
                         st.sl_txn),
        sl_hops=jnp.where(wr, hops_new.astype(st.sl_hops.dtype), st.sl_hops),
        beats_issued=st.beats_issued + jnp.where(can, burst, 0))


@register_stage("dispatch")
def _stage_dispatch(st: SimState, wires, c):
    """Dense dispatch (:func:`_write_ring`) from the precomputed beat tables;
    slots record the transaction's index."""
    acc = wires["accept"]
    return _write_ring(st, c, acc, c["tx_banks"], c["tx_hops"],
                       acc["nt_c"]), wires


@register_stage("accept_dispatch")
def _stage_accept_dispatch(st: SimState, wires, c):
    """Fused acceptance + dispatch (the ROADMAP follow-up): one registered
    stage, one registry hop per cycle, and the accepted-burst values flow
    straight from the acceptance gates into the ring write without an
    intermediate pipeline boundary.  Composition of the two stages verbatim,
    so it is bit-exact against ``("accept", "dispatch")`` by construction."""
    st, wires = _stage_accept(st, wires, c)
    return _stage_dispatch(st, wires, c)


def _arbiter_by_mask(bank, key, waiting, bank_free, bank_rr, now, *, c):
    """Winners and grants with every per-slot bank lookup as a dense mask:
    ``hit`` [X, NB, P] compares every bank id with each slot's bank (ring
    slots on the lane axis), and the bank's free flag, its round-robin term
    (an [X, NB] table — it depends on the port and the bank alone) and its
    winner reach the slots through it as compare, select and reduce, with
    no gather or scatter over the slots.  Returns (win [NB], granted
    [X, P])."""
    X, NB = c["X"], c["NB"]
    banks = jnp.arange(NB, dtype=jnp.int32)[:, None]
    hit = (bank[:, None, :] == banks) & (bank_free <= now)[:, None]
    rr = (c["master_col"] - bank_rr[None, :]) % X             # [X, NB]
    # a slot that is not waiting bids the filler plus its rr: no bid
    key = jnp.where(waiting, key, KEY_FILLER)
    bid = jnp.where(hit, key[:, None, :] + rr[:, :, None], KEY_FILLER)
    win = bid_winners(bid, bank, backend=c["prm"].arbiter)    # [NB]
    # a slot is granted iff it IS some bank's winner (a winner bids for its
    # own bank alone; a bank with no eligible slot reports the sentinel S)
    granted = jnp.any(c["flat_ids"][:, None, :] == win[:, None], axis=1)
    return win, granted


def _arbiter_by_lookup(bank, key, waiting, bank_free, bank_rr, now, *, c):
    """:func:`_arbiter_by_mask` by per-slot lookups into the [NB] tables and
    the flat comparator tree (``segment_min`` on the jax backend): the CPU
    lowering, where a lookup costs a few ns a slot and the mask NB times
    as many element operations.  Bit-identical results."""
    X, S, NB = c["X"], c["S"], c["NB"]
    elig = waiting & (bank_free[bank] <= now)
    key = key + (c["master_col"] - bank_rr[bank]) % X
    win = bank_arbiter_winners(key.reshape(S), bank.reshape(S),
                               elig.reshape(S), num_banks=NB,
                               backend=c["prm"].arbiter)      # [NB]
    return win, c["flat_ids"] == win[bank]


@register_stage("bank_arbitrate")
def _stage_bank_arbitrate(st: SimState, wires, c):
    """Per-bank arbitration, one grant per bank per cycle: priority level
    first (aging promotes a waiting beat one level per ``qos_aging`` cycles
    so best-effort can never starve), FCFS within a level (AGE_CAP >=
    max_cycles: the age term cannot saturate within a run), round-robin among
    masters as the tie-break.  A granted read's data heads home after the
    bank's access latency plus the router's return-path hops.

    The winners and grants come from :func:`_arbiter_by_mask` on the chip
    and :func:`_arbiter_by_lookup` on a CPU, chosen when the program is
    lowered for its platform (``SimParams.arbiter`` picks the jax reduction
    or the Pallas kernel in either); every piece of bookkeeping then
    derives from the [NB] winner view.  ``aged_grants`` counts, on each
    ring slot, the grants to a beat that aging had lifted above its
    master's level; it is added in the slot update the stage makes anyway,
    so no reduction or gather joins the cycle for it."""
    X, P, S = c["X"], c["P"], c["S"]
    d = c["d"]
    now = st.now
    phase, write = unpack_slot_flags(st.sl_flags)
    waiting = (phase == SLOT_WAITING) & (st.sl_arrive <= now)
    age = jnp.clip(now - st.sl_arrive, 0, c["AGE_CAP"])
    boost = aging_boost(age, d["qos_aging"])
    level = jnp.clip(c["slot_prio"] - boost, 0, PRIO_LEVELS - 1)
    # the round-robin term is added per bank by the arbiter
    key = arbitration_priority_key(level, age, 0, age_cap=c["AGE_CAP"],
                                   num_masters=X)
    win, granted = jax.lax.platform_dependent(
        widen(st.sl_bank), key, waiting, st.bank_free, st.bank_rr, now,
        cpu=partial(_arbiter_by_lookup, c=c),
        default=partial(_arbiter_by_mask, c=c))
    has_win = win < S
    winc = jnp.minimum(win, S - 1)
    wmaster = winc // P
    wwrite = write.reshape(S)[winc]
    occ = d["bank_occupancy"]
    bank_free = jnp.where(has_win, jnp.maximum(st.bank_free, now) + occ,
                          st.bank_free)
    bank_rr = jnp.where(has_win,
                        st.bank_rr + (wmaster - st.bank_rr) % X + 1,
                        st.bank_rr)
    sl_ready = jnp.where(granted, now + occ + d["bank_latency"]
                         + d["hop_latency"] * widen(st.sl_hops), st.sl_ready)
    # freed split-buffer credits per port, from the [NB] winner view: a
    # dense one-hot owner matrix summed along banks replaces the former
    # segment_sum scatter (one comparison per (port, bank) cell — regular,
    # fusable, and vmap-friendly)
    owner = has_win[None, :] & (wmaster[None, :] == c["ar"][:, None])  # [X,NB]
    freed_r = jnp.sum(owner & (wwrite[None, :] == 0), axis=1,
                      dtype=jnp.int32)
    freed_w = jnp.sum(owner & (wwrite[None, :] == 1), axis=1,
                      dtype=jnp.int32)
    credits = st.credits + jnp.stack(
        [freed_r, freed_w], axis=1).astype(st.credits.dtype)
    # grants to a beat that aging had promoted past its master's level
    st = st.replace(bank_free=bank_free, bank_rr=bank_rr,
                    sl_flags=pack_slot_flags(
                        jnp.where(granted, SLOT_GRANTED, phase), write),
                    sl_ready=sl_ready, credits=credits,
                    # ``level < slot_prio``, read off the wait: a second
                    # reader of ``level`` keeps XLA from fusing the key
                    aged_grants=st.aged_grants + (
                        granted & (now - st.sl_arrive >= c["aged_after"])))
    arb = dict(has_win=has_win, wmaster=wmaster, wwrite=wwrite,
               whops=widen(st.sl_hops).reshape(S)[winc],
               wtxn=widen(st.sl_txn).reshape(S)[winc])
    return st, dict(wires, arb=arb)


@register_stage("router_release")
def _stage_router_release(st: SimState, wires, c):
    """Inter-slice router bookkeeping at bank grant: a remote beat leaving
    the ingress queue for its bank returns its slice's ingress credit, and
    per-slice service counters feed the occupancy metrics.  Works on the
    [NB] winner view.  Banks are laid out slice-major (slice = bank //
    banks_per_slice), so the per-slice reductions are plain
    ``reshape(NSL, -1)`` row sums — the former ``segment_sum`` scatters are
    gone from the cycle body."""
    NSL = c["NSL"]
    arb = wires["arb"]
    has_win, whops = arb["has_win"], arb["whops"]
    remote = has_win & (whops > 0)
    released = jnp.sum(remote.reshape(NSL, -1), axis=1, dtype=jnp.int32)
    slice_beats = st.slice_beats + jnp.sum(
        has_win.reshape(NSL, -1), axis=1, dtype=jnp.int32)
    return st.replace(ing_used=st.ing_used - released,
                      slice_beats=slice_beats,
                      remote_beats=st.remote_beats + jnp.sum(released)), wires


@register_stage("return_bus")
def _stage_return_bus(st: SimState, wires, c):
    """Read-return bus: one beat per port per cycle, oldest-ready first
    (AXI5 read-data chunking ⇒ beats may return out of order across banks).
    Write slots free immediately after grant (no return path).  Dense over
    the [X, P] layout: the per-port pick is a min-reduction along P."""
    P = c["P"]
    now = st.now
    phase, write = unpack_slot_flags(st.sl_flags)
    retq = (phase == SLOT_GRANTED) & (st.sl_ready <= now) & (write == 0)
    rkey = jnp.clip(st.sl_ready, 0, 2**20)
    rbest = jnp.min(jnp.where(retq, rkey, 2**30), axis=1, keepdims=True)
    ris = retq & (rkey == rbest)
    rwin = jnp.min(jnp.where(ris, c["pos"][None, :], P), axis=1,
                   keepdims=True)                             # [X, 1]
    returned = ris & (c["pos"][None, :] == rwin)
    phase = jnp.where(returned, SLOT_IDLE, phase)
    ret_any = jnp.any(returned, axis=1)
    # write slots free immediately after grant (no return path)
    phase = jnp.where((phase == SLOT_GRANTED) & (write == 1), SLOT_IDLE,
                      phase)
    ret_txn = widen(st.sl_txn)[c["ar"], jnp.minimum(rwin[:, 0], P - 1)]
    st = st.replace(sl_flags=pack_slot_flags(phase, write),
                    beats_done=st.beats_done + ret_any.astype(jnp.int32))
    return st, dict(wires, ret=dict(ret_any=ret_any, ret_txn=ret_txn))


def _latch_drained(st: SimState, c) -> SimState:
    """Latch ``drained_at`` the first cycle the fabric goes quiescent.

    Called on the *post-retire* state (``now`` already advanced), so the
    latched value is the count of simulated cycles after which nothing can
    ever change again: every reachable event consumed (a zero-burst event
    permanently blocks its port's stream — ``ctx["n_events"]`` is the first
    zero-burst index), no outstanding commands, every beat slot idle, no
    in-flight-table beats, and all router ingress credits returned.  On a
    drained state every stage is a no-op except the clock tick and the
    (capped, metric-free) regulator refill — the property the early-exit
    driver's bit-exactness rests on, pinned by tests.  Maintained on fixed-
    horizon runs too, so ``drained_cycle`` is reported either way and
    early-exit vs fixed-horizon metrics agree key-for-key."""
    phase, _ = unpack_slot_flags(st.sl_flags)
    drained = (jnp.all(st.next_txn >= c["n_events"])
               & jnp.all(widen(st.outstanding) == 0)
               & jnp.all(phase == SLOT_IDLE)
               & jnp.all(widen(st.ing_used) == 0)
               & jnp.all(widen(st.remaining) <= 0)
               & jnp.all(widen(st.ift_remaining) == 0))
    return st.replace(drained_at=jnp.where((st.drained_at < 0) & drained,
                                           st.now, st.drained_at))


def _port_event_counts(tx_burst, N: int):
    """Per-port count of *reachable* events: acceptance requires burst > 0,
    so the first zero-burst event (trailing padding by convention) ends the
    port's stream permanently."""
    zb = widen(tx_burst) == 0
    return jnp.where(jnp.any(zb, axis=1),
                     jnp.argmax(zb.astype(jnp.int32), axis=1), N)


def _count_down(rem, wires, c):
    """Both retire stages' beat delivery: decrement the per-command beat
    counters ``rem`` ([X, N] dense, the [X, F] in-flight table) from the
    cycle's winners — a granted write at its grant, a returned read at its
    return-bus pick — by [NB]- and [X]-sized scatter-adds.  Returns the
    int32 counters and ``just_done`` (this was the command's last beat)."""
    arb, ret = wires["arb"], wires["ret"]
    rem_before = widen(rem)
    wdec = (arb["has_win"] & (arb["wwrite"] == 1)).astype(jnp.int32)
    remaining = rem_before.at[arb["wmaster"], arb["wtxn"]].add(-wdec)
    remaining = remaining.at[c["ar"], ret["ret_txn"]].add(
        -ret["ret_any"].astype(jnp.int32))
    return remaining, (remaining == 0) & (rem_before > 0)


def _settle(st: SimState, c, field: str, remaining, done_r, done_w, *,
            stamp: bool = False):
    """Both retire stages' bookkeeping from each port's completions per
    direction: outstanding counts, busy counters (busy while a command is
    accepted but incomplete on that channel), ``remaining`` back in
    ``field`` and the clock's advance.  Returns the completion stamp (the
    cycle plus the return latency; with ``stamp`` only) and the updates."""
    now = st.now
    outstanding = st.outstanding - jnp.stack(
        [done_r, done_w], axis=1).astype(st.outstanding.dtype)
    in_r = (outstanding[:, 0] > 0).astype(jnp.int32)
    in_w = (outstanding[:, 1] > 0).astype(jnp.int32)
    complete_t = now + c["d"]["ret_latency"] if stamp else None
    return complete_t, {
        "now": now + 1, "outstanding": outstanding,
        field: remaining.astype(getattr(st, field).dtype),
        "busy_r": st.busy_r + in_r, "busy_w": st.busy_w + in_w,
        "busy_any": st.busy_any + jnp.maximum(in_r, in_w)}


@register_stage("retire")
def _stage_retire(st: SimState, wires, c):
    """Transaction completion (:func:`_count_down`, :func:`_settle`): writes
    complete at their last beat's grant, reads at its return; each is
    stamped in the [X, N] ``complete_cycle``."""
    remaining, just_done = _count_down(st.remaining, wires, c)
    complete = jnp.where(just_done, st.now + c["d"]["ret_latency"],
                         st.complete_cycle)
    done_r = jnp.sum(just_done & (c["tx_write"] == 0), axis=1)
    done_w = jnp.sum(just_done & (c["tx_write"] == 1), axis=1)
    _, upd = _settle(st, c, "remaining", remaining, done_r, done_w)
    return _latch_drained(st.replace(complete_cycle=complete, **upd),
                          c), wires


@register_stage("accept_sched")
def _stage_accept_sched(st: SimState, wires, c):
    """Schedule-pipeline acceptance: the same credit/regulator/router gate as
    ``accept``, but the candidate burst's beat→(bank, hops, ingress-need)
    routing is computed on the fly from its address (``bank_of_dev``) instead
    of gathered from dense precomputed tables, and the accepted command is
    allocated a slot in the in-flight table.  The gates are ``accept``'s own
    (:func:`_admit`), so decisions are identical (golden-pinned via
    ``collect="exact"``)."""
    N, NSL = c["N"], c["NSL"]
    now = st.now
    ar = c["ar"]
    nt = st.next_txn
    has_txn = nt < N
    nt_c = jnp.minimum(nt, N - 1)
    burst = widen(c["tx_burst"][ar, nt_c])
    is_w = widen(c["tx_write"][ar, nt_c])
    ready = c["tx_start"][ar, nt_c] <= now
    # in-scan beat routing for the candidate burst only ([X, max_burst] —
    # nothing sized by the schedule length)
    off = c["beat_off"][None, :]                           # [1, mb]
    bvalid = off < burst[:, None]                          # [X, mb]
    beat = jnp.where(bvalid, c["tx_addr"][ar, nt_c][:, None] + off, 0)
    banks_txn = bank_of_dev(beat, c["prm"])                # [X, mb] int32
    tgt = banks_txn // c["banks_per_slice"]
    dist = jnp.abs(tgt - c["home"][:, None])
    hops_txn = jnp.where(bvalid, jnp.minimum(dist, NSL - dist), 0)
    remote = bvalid & (hops_txn > 0)
    need = jnp.sum(
        remote[:, :, None] & (tgt[:, :, None]
                              == jnp.arange(NSL)[None, None, :]),
        axis=1).astype(jnp.int32)                          # [X, NSL]
    can, reg_tokens, ing_used, reg_held = _admit(
        st, c, has_txn & (burst > 0) & ready, burst, is_w, need)
    # in-flight table allocation: the credit gate caps live commands at
    # 2×outstanding - 1 < F, so a free slot (remaining == 0) always exists
    free = widen(st.ift_remaining) == 0                    # [X, F]
    idx = jnp.argmax(free, axis=1).astype(jnp.int32)
    # the accepted command's table entry, as a dense [X, F] select
    slot = can[:, None] & (jnp.arange(free.shape[1]) == idx[:, None])

    def put(tbl, val):
        return jnp.where(slot, jnp.expand_dims(val, -1).astype(tbl.dtype),
                         tbl)

    outstanding, credits = _charge(st, can, is_w, burst)
    upd = dict(
        next_txn=nt + can.astype(jnp.int32),
        outstanding=outstanding, credits=credits,
        fwd_free=jnp.where(can & (is_w > 0), now + burst, st.fwd_free),
        reg_tokens=reg_tokens, ing_used=ing_used, reg_held=reg_held,
        ift_write=put(st.ift_write, is_w),
        ift_burst=put(st.ift_burst, burst),
        ift_remaining=put(st.ift_remaining, burst),
        ift_accept=put(st.ift_accept, now),
        ift_start=put(st.ift_start, c["tx_start"][ar, nt_c]),
        ift_txn=put(st.ift_txn, nt_c),
    )
    if c["exact"]:
        upd["accept_cycle"] = jnp.where(
            can[:, None] & (jnp.arange(N) == nt_c[:, None]), now,
            st.accept_cycle)
    st = st.replace(**upd)
    return st, dict(wires, accept=dict(can=can, burst=burst, is_w=is_w,
                                       nt_c=nt_c, banks_txn=banks_txn,
                                       hops_txn=hops_txn, ift_idx=idx))


@register_stage("dispatch_sched")
def _stage_dispatch_sched(st: SimState, wires, c):
    """Schedule dispatch (:func:`_write_ring`) from the rows acceptance routed
    in-scan; slots record the in-flight-table index."""
    acc = wires["accept"]
    return _write_ring(st, c, acc, acc["banks_txn"], acc["hops_txn"],
                       acc["ift_idx"]), wires


@register_stage("accept_dispatch_sched")
def _stage_accept_dispatch_sched(st: SimState, wires, c):
    """Fused schedule-pipeline acceptance + dispatch — see
    ``accept_dispatch``; here the fusion also keeps the in-scan beat→bank
    routing (``banks_txn``/``hops_txn``) local to one stage body."""
    st, wires = _stage_accept_sched(st, wires, c)
    return _stage_dispatch_sched(st, wires, c)


@register_stage("retire_sched")
def _stage_retire_sched(st: SimState, wires, c):
    """Schedule-pipeline retire: the same completion logic as ``retire`` on
    the [X, F] in-flight table instead of the dense [X, N] beat counters.
    ``collect="exact"`` scatters timestamps back to the [X, N] arrays
    (golden parity); ``collect="stream"`` folds each completion into the
    fixed-size accumulators — per-port windows for throughput, P² marker
    groups per (view, class, direction) for latency percentiles, and
    per-class deadline counters — so nothing in the carry scales with the
    schedule length."""
    remaining, just_done = _count_down(st.ift_remaining, wires, c)
    iw = widen(st.ift_write)
    jr = just_done & (iw == 0)
    jw = just_done & (iw == 1)
    complete_t, upd = _settle(st, c, "ift_remaining", remaining,
                              jnp.sum(jr, axis=1), jnp.sum(jw, axis=1),
                              stamp=True)
    if c["exact"]:
        rows = jnp.broadcast_to(c["ar"][:, None], just_done.shape)
        upd["complete_cycle"] = st.complete_cycle.at[
            rows, widen(st.ift_txn)].max(
            jnp.where(just_done, complete_t, -1))
        return _latch_drained(st.replace(**upd), c), wires

    # --- streaming accumulators (collect="stream") ---------------------
    acc = st.ift_accept
    bts = widen(st.ift_burst)
    lat = (complete_t - acc).astype(jnp.float32)
    e2e = (complete_t - st.ift_start).astype(jnp.float32)

    def per_dir(fn, sel_r, sel_w):
        return jnp.stack([fn(sel_r), fn(sel_w)], axis=1)   # [X, 2]

    upd.update(
        pt_first=jnp.minimum(st.pt_first, per_dir(
            lambda s: jnp.min(jnp.where(s, acc, INF32), axis=1), jr, jw)),
        pt_last=jnp.where(
            per_dir(lambda s: jnp.any(s, axis=1), jr, jw),
            complete_t, st.pt_last),
        pt_beats=st.pt_beats + per_dir(
            lambda s: jnp.sum(jnp.where(s, bts, 0), axis=1), jr, jw),
        pt_count=st.pt_count + per_dir(
            lambda s: jnp.sum(s, axis=1), jr, jw),
        pt_lat_sum=st.pt_lat_sum + per_dir(
            lambda s: jnp.sum(jnp.where(s, lat, 0.0), axis=1), jr, jw),
        pt_lat_max=jnp.maximum(st.pt_lat_max, per_dir(
            lambda s: jnp.max(jnp.where(s, lat, 0.0), axis=1), jr, jw)),
    )
    NC = c["NC"]
    cls = jnp.broadcast_to(widen(c["tx_class"])[:, None], iw.shape)
    gcd = (cls * 2 + iw).reshape(-1)                       # class × dir
    jd_f = just_done.reshape(-1)
    upd["cls_done"] = (st.cls_done.reshape(-1).at[gcd]
                       .add(jd_f.astype(jnp.int32)).reshape(NC, 2))
    has_dl = c["tx_deadline"][:, None] >= 0
    late = (complete_t - st.ift_start) > c["tx_deadline"][:, None]
    dd = (just_done & has_dl).reshape(-1)
    cls_f = cls.reshape(-1)
    upd["dl_done"] = st.dl_done.at[cls_f].add(dd.astype(jnp.int32))
    upd["dl_miss"] = st.dl_miss.at[cls_f].add(
        (dd & late.reshape(-1)).astype(jnp.int32))
    # P² groups: view-major (0 = accept→complete, 1 = earliest-issue→complete)
    vals = jnp.concatenate([lat.reshape(-1), e2e.reshape(-1)])
    gid = jnp.concatenate([gcd, gcd + 2 * NC])
    mask = jnp.concatenate([jd_f, jd_f])
    h, n, pc = p2_update(st.p2_height, st.p2_npos, st.p2_count,
                         vals, gid, mask)
    upd.update(p2_height=h, p2_npos=n, p2_count=pc,
               p2_max=st.p2_max.at[gid].max(jnp.where(mask, vals, 0.0)))
    return _latch_drained(st.replace(**upd), c), wires


def _time_skip(st: SimState, c, K: int) -> SimState:
    """Block-boundary idle-cycle skip (schedule pipeline): when nothing is
    in flight and every reachable pending event's issue time lies strictly
    in the future, jump ``now`` to the earliest of them in one step.

    Exactness: on such a state each skipped cycle body changes only ``now``
    (+1, retire) and the regulator buckets (one capped refill per cycle,
    accept) — iterated capped refills compose as
    ``min(tokens + delta * rate, cap)``, so both are advanced analytically;
    every other field is provably untouched (no acceptance can fire: every
    pending start is ``> now``, and no slot/bank/return work exists).  The
    target is clamped to ``max_cycles - K`` so the following K-cycle block
    can never overrun the horizon, keeping skipped runs bit-exact against
    fixed horizon (cycles beyond the clamp are simulated normally)."""
    d = c["d"]
    MC = c["prm"].max_cycles
    phase, _ = unpack_slot_flags(st.sl_flags)
    idle = (jnp.all(widen(st.outstanding) == 0)
            & jnp.all(phase == SLOT_IDLE)
            & jnp.all(widen(st.ing_used) == 0)
            & jnp.all(widen(st.ift_remaining) == 0))
    pending = st.next_txn < c["n_events"]                    # [X]
    nt_c = jnp.minimum(st.next_txn, c["N"] - 1)
    ns = jnp.min(jnp.where(pending, c["tx_start"][c["ar"], nt_c], INF32))
    target = jnp.minimum(ns, MC - K)
    delta = jnp.where(idle & jnp.any(pending) & (target > st.now),
                      target - st.now, 0)
    # analytic refill, overflow-safe: past ``need`` cycles the bucket is
    # full anyway, so clamp the multiplier before it can wrap int32
    cap = d["reg_burst"] * REG_SCALE
    need = jnp.where(d["reg_rate"] > 0,
                     (cap - st.reg_tokens + d["reg_rate"] - 1)
                     // jnp.maximum(d["reg_rate"], 1), 0)
    d_eff = jnp.minimum(delta, jnp.maximum(need, 0))
    refill = jnp.minimum(st.reg_tokens + d_eff * d["reg_rate"], cap)
    return st.replace(now=st.now + delta, skipped=st.skipped + delta,
                      reg_tokens=jnp.where(delta > 0, refill,
                                           st.reg_tokens))


def _run_cycles(state: SimState, cycle, ctx, prm: SimParams, *,
                skip: bool) -> SimState:
    """Drive the cycle body for ``max_cycles`` simulated cycles.

    ``early_exit=False`` is the original unconditional
    ``lax.scan(..., length=max_cycles)``.  With ``early_exit=True`` (the
    default) the driver scans K-cycle blocks under a ``lax.while_loop`` and
    stops as soon as the drain predicate latched (``drained_at >= 0`` — see
    :func:`_latch_drained`) or another full block would cross the horizon;
    a trailing K-cycle *gated* scan (per-cycle ``tree_map`` select on
    ``active``) then covers the sub-block remainder exactly, so only K
    cycles ever pay the select overhead.  Finally a drained run's clock is
    fast-forwarded to ``max_cycles`` — on a drained state the remaining
    fixed-horizon cycles advance nothing but ``now`` and the (metric-free,
    capped) regulator refill, so reported metrics are bit-exact against the
    fixed horizon.  The block counter bounds the while loop even if a
    custom stage freezes the clock.  Under ``vmap`` the while loop runs
    until every lane drains; extra blocks on already-drained lanes are
    no-ops modulo the fast-forwarded clock, so batching keeps bit-exactness
    (at the wall-clock cost of the slowest lane)."""
    MC = prm.max_cycles
    if not prm.early_exit:
        state, _ = jax.lax.scan(cycle, state, None, length=MC)
        return state

    K = max(1, min(prm.block_cycles, MC))
    nblocks = MC // K

    def block(carry):
        st, i = carry
        if skip:
            st = _time_skip(st, ctx, K)
        st, _ = jax.lax.scan(cycle, st, None, length=K)
        return st, i + 1

    def cond(carry):
        st, i = carry
        return ((st.drained_at < 0) & (i < nblocks)
                & (st.now + K <= MC))

    state, _ = jax.lax.while_loop(cond, block, (state, jnp.int32(0)))

    def gated(st, _):
        active = (st.drained_at < 0) & (st.now < MC)
        st2, _ = cycle(st, None)
        return jax.tree_util.tree_map(
            lambda a, b: jnp.where(active, b, a), st, st2), None

    state, _ = jax.lax.scan(gated, state, None, length=K)
    return state.replace(now=jnp.where(state.drained_at >= 0,
                                       jnp.int32(MC), state.now))


def _aged_after(prio, d, age_cap: int):
    """Per port, the wait (cycles at the bank) from which aging has lifted a
    beat above its master's level: ``level < prio`` exactly when ``prio >
    0`` and ``age // qos_aging >= 1``, with the age capped at ``age_cap``.
    ``INF32`` where aging never lifts it.  [X, 1]"""
    qa = d["qos_aging"]
    lifts = (prio > 0) & (qa > 0) & (qa <= age_cap)
    return jnp.where(lifts, qa, INF32)[:, None]


def _carry_layout(prm: SimParams) -> dict:
    """``init_state``'s pipeline-dependent sizes: the schedule pipeline's
    in-flight table and, streaming, the accumulators."""
    if not prm.uses_schedule():
        return {}
    exact = prm.collect == "exact"
    return dict(F=prm.inflight_slots, NC=0 if exact else STREAM_CLASSES,
                NQ=len(STREAM_PCTS), exact=exact)


#: the cores' inputs before ``dyn``, by name: dense beat tables, or the
#: schedule's packed event columns
_DENSE_INPUTS = ("tx_write", "tx_burst", "tx_banks", "tx_hops", "tx_ing",
                 "tx_start", "tx_prio")
_SCHED_INPUTS = ("tx_write", "tx_burst", "tx_addr", "tx_start", "tx_prio",
                 "tx_class", "tx_deadline")


def _setup(args, prm: SimParams):
    """Cycle-0 state + stage context from a core's arguments (its inputs,
    then ``dyn``), for either pipeline: the common context, then each
    pipeline's own entries."""
    sched = prm.uses_schedule()
    tx = dict(zip(_SCHED_INPUTS if sched else _DENSE_INPUTS, args[:-1]))
    tx_burst = tx["tx_burst"]
    X, N = tx_burst.shape
    P = prm.slots_per_master
    exact = prm.collect == "exact"

    dyn = jnp.asarray(args[-1], jnp.int32)
    d = {name: dyn[i] for i, name in enumerate(DYN_FIELDS)}

    tx_prio = jnp.clip(widen(tx.pop("tx_prio")), 0, PRIO_LEVELS - 1)
    ar = jnp.arange(X, dtype=jnp.int32)
    pos = jnp.arange(P, dtype=jnp.int32)

    ctx = dict(X=X, N=N, P=P, S=X * P, NB=prm.geom.num_banks,
               NSL=prm.geom.num_slices, AGE_CAP=_age_cap(prm, X),
               prm=prm, d=d, ar=ar, pos=pos, exact=exact, NC=STREAM_CLASSES)
    if not sched:                     # the dense acceptance's one-hot
        ctx["txn_ids"] = jnp.arange(N, dtype=jnp.int32)[None, :]
    ctx.update(
        master_col=ar[:, None],
        flat_ids=ar[:, None] * P + pos[None, :],             # [X, P]
        slot_prio=tx_prio[:, None],                          # [X, 1]
        aged_after=_aged_after(tx_prio, d, _age_cap(prm, X)),  # [X, 1]
        regulated=tx_prio >= REGULATED_PRIO,                 # [X]
        n_events=_port_event_counts(tx_burst, N),            # [X]
        **tx)
    if sched:                         # in-scan beat routing
        ctx.update(
            beat_off=jnp.arange(prm.max_burst, dtype=jnp.int32),
            home=jnp.asarray(master_home_slices(X, prm.geom), jnp.int32),
            banks_per_slice=prm.geom.banks_per_slice)

    state = init_state(X=X, N=N, P=P, NB=ctx["NB"], NSL=ctx["NSL"],
                       tx_burst=tx_burst, d=d, **_carry_layout(prm))
    return state, ctx


def _setup_trace(trace, prm: SimParams):
    """Cycle-0 state + stage context for one trace or schedule, through the
    entry points' input path (eager; for tests that step the cycle
    body)."""
    return _setup(_device_input(prm, _host_input(trace, prm),
                                prm.dyn_vector()), prm)


def _pipeline_cycle(prm: SimParams, ctx):
    """One full pipeline pass as a scan body ``cycle(state, _)``.  Each
    stage's operations carry the name scope ``stage.<registry name>`` in
    their metadata, which a profiler trace of the compiled program keeps."""
    stage_fns = [(name, STAGE_REGISTRY[name]) for name in prm.pipeline()]

    def cycle(st, _):
        wires: dict = {}
        for name, fn in stage_fns:
            with jax.named_scope(f"stage.{name}"):
                st, wires = fn(st, wires, ctx)
        return st, None

    return cycle


def _run_core(args, prm: SimParams):
    """Set up, run the cycle loop (time skip on the schedule pipeline) and
    collect the metrics."""
    state, ctx = _setup(args, prm)
    cycle = _pipeline_cycle(prm, ctx)
    skip = prm.time_skip and prm.uses_schedule()
    state = _run_cycles(state, cycle, ctx, prm, skip=skip)
    if prm.collect == "exact":
        return _metrics(state, ctx["tx_burst"], ctx["tx_write"], prm)
    return _stream_metrics(state, ctx["tx_burst"], ctx["tx_write"], prm)


def _core(tx_write, tx_burst, tx_banks, tx_hops, tx_ing, tx_start, tx_prio,
          dyn, *, prm: SimParams):
    """Dense-pipeline core: per-transaction beat tables precomputed on the
    host (:func:`_precompute_beats`)."""
    return _run_core((tx_write, tx_burst, tx_banks, tx_hops, tx_ing,
                      tx_start, tx_prio, dyn), prm)


def _core_sched(tx_write, tx_burst, tx_addr, tx_start, tx_prio, tx_class,
                tx_deadline, dyn, *, prm: SimParams):
    """Schedule-pipeline core: packed per-master event schedules (int8
    direction/burst + int32 addr/start per event, per-master class/deadline)
    advanced inside the scan — no dense [X, N, max_burst] beat tables, and
    with ``collect="stream"`` no [X, N] timestamp arrays either."""
    return _run_core((tx_write, tx_burst, tx_addr, tx_start, tx_prio,
                      tx_class, tx_deadline, dyn), prm)


def _port_metrics(st: SimState, own) -> Dict[str, jnp.ndarray]:
    """What both collectors report besides their own entries (``own()``,
    built after the granted-beat count, before the rest): busy cycles,
    beats, clock and drain, QoS counters and the multi-slice view."""
    # granted-beat population for the remote fraction: remote_beats and
    # slice_beats are both counted at bank grant, so the ratio stays in
    # [0, 1] even when a run hits max_cycles without draining
    granted_beats = jnp.sum(st.slice_beats)
    out = own()
    out.update({
        "busy_cycles": st.busy_any,
        "beats_done": st.beats_done,
        "cycles": st.now,
        # cycle the run went quiescent (-1: never — it hit max_cycles);
        # effective_cycles is what the run actually had to simulate, minus
        # any idle stretches the time skip jumped (skipped_cycles)
        "drained_cycle": st.drained_at,
        "effective_cycles": jnp.where(st.drained_at >= 0, st.drained_at,
                                      st.now),
        "skipped_cycles": st.skipped,
        # QoS mechanisms at work: port-cycles a regulated port's due
        # command waited for tokens alone, and bank grants aging decided
        "reg_held": jnp.sum(st.reg_held),
        "aged_grants": jnp.sum(st.aged_grants),
        # multi-slice fabric view: beats each slice's banks served, and how
        # much traffic crossed the inter-slice router (0 at num_slices=1)
        "slice_beats": st.slice_beats,
        "remote_beats": st.remote_beats,
        "remote_beat_fraction": jnp.where(
            granted_beats > 0,
            st.remote_beats / jnp.maximum(granted_beats, 1)
            .astype(jnp.float32), 0.0),
    })
    return out


def _stream_metrics(st: SimState, burst, is_w,
                    prm: SimParams) -> Dict[str, jnp.ndarray]:
    """Metrics from the streaming accumulators: the same port-level surface
    as :func:`_metrics` minus the per-transaction timestamp arrays, plus the
    raw P²/class/deadline accumulator state (summarized host-side by
    ``scenarios.sweep``; merged across batch lanes by
    ``repro.core.percentile.p2_merge_quantile``)."""
    n_real = jnp.sum(widen(burst) > 0)
    first = jnp.concatenate([st.pt_first,
                             jnp.min(st.pt_first, 1, keepdims=True)], 1)
    last = jnp.concatenate([st.pt_last,
                            jnp.max(st.pt_last, 1, keepdims=True)], 1)
    beats = jnp.concatenate([st.pt_beats,
                             jnp.sum(st.pt_beats, 1, keepdims=True)], 1)
    count = jnp.concatenate([st.pt_count,
                             jnp.sum(st.pt_count, 1, keepdims=True)], 1)
    span = jnp.maximum(last - first, 1).astype(jnp.float32)
    tput = jnp.where(count > 0, beats / span, 0.0)         # [X, (r, w, any)]
    busy = jnp.stack([st.busy_r, st.busy_w, st.busy_any], axis=1)
    tput_busy = jnp.where(
        count > 0, beats / jnp.maximum(busy, 1).astype(jnp.float32), 0.0)
    cnt = st.pt_count.astype(jnp.float32)
    return _port_metrics(st, lambda: {
        "throughput": tput[:, 2],
        "read_throughput": tput[:, 0],
        "write_throughput": tput[:, 1],
        "throughput_busy": tput_busy[:, 2],
        "read_throughput_busy": tput_busy[:, 0],
        "write_throughput_busy": tput_busy[:, 1],
        "read_lat_avg": jnp.where(cnt[:, 0] > 0,
                                  st.pt_lat_sum[:, 0]
                                  / jnp.maximum(cnt[:, 0], 1.0), 0.0),
        "read_lat_max": st.pt_lat_max[:, 0],
        "write_lat_avg": jnp.where(cnt[:, 1] > 0,
                                   st.pt_lat_sum[:, 1]
                                   / jnp.maximum(cnt[:, 1], 1.0), 0.0),
        "write_lat_max": st.pt_lat_max[:, 1],
        "all_done": jnp.sum(st.pt_count) == n_real,
        # streaming accumulator state (fixed-size; see percentile.py)
        "p2_height": st.p2_height,
        "p2_npos": st.p2_npos,
        "p2_count": st.p2_count,
        "p2_max": st.p2_max,
        "cls_done": st.cls_done,
        "dl_done": st.dl_done,
        "dl_miss": st.dl_miss,
        "txns_done_port": st.pt_count,
    })


def _metrics(st: SimState, burst, is_w, prm: SimParams) -> Dict[str, jnp.ndarray]:
    burst = widen(burst)
    real = burst > 0
    done = st.complete_cycle >= 0
    lat = (st.complete_cycle - st.accept_cycle).astype(jnp.float32)
    r = real & done & (is_w == 0)
    w = real & done & (is_w == 1)
    read_lat = jnp.where(r, lat, 0.0)
    write_lat = jnp.where(w, lat, 0.0)
    n_r = jnp.maximum(jnp.sum(r, axis=1), 1)
    n_w = jnp.maximum(jnp.sum(w, axis=1), 1)
    # per-direction port throughput: beats delivered per active cycle on that
    # AXI channel (R return bus / W data bus are independent, 1 beat/cycle).
    # The wall-span view divides by last_complete - first_accept, which an
    # injection-gated trace (camera vblank, Radar PRI idle gaps) deflates;
    # the ``*_busy`` view divides by busy cycles only — cycles with any
    # accepted-but-incomplete transaction on that channel — and reads as
    # achieved service rate regardless of the offered duty cycle.
    def tput(sel):
        first = jnp.min(jnp.where(sel, st.accept_cycle, INF32), axis=1)
        last = jnp.max(jnp.where(sel, st.complete_cycle, -1), axis=1)
        beats = jnp.sum(jnp.where(sel, burst, 0), axis=1)
        span = jnp.maximum(last - first, 1).astype(jnp.float32)
        return jnp.where(jnp.sum(sel, 1) > 0, beats / span, 0.0)

    def tput_busy(sel, busy):
        beats = jnp.sum(jnp.where(sel, burst, 0), axis=1)
        cyc = jnp.maximum(busy, 1).astype(jnp.float32)
        return jnp.where(jnp.sum(sel, 1) > 0, beats / cyc, 0.0)

    return _port_metrics(st, lambda: {
        "throughput": tput(real & done),
        "read_throughput": tput(r),
        "write_throughput": tput(w),
        "throughput_busy": tput_busy(real & done, st.busy_any),
        "read_throughput_busy": tput_busy(r, st.busy_r),
        "write_throughput_busy": tput_busy(w, st.busy_w),
        "read_lat_avg": jnp.where(jnp.sum(r, 1) > 0,
                                  jnp.sum(read_lat, 1) / n_r, 0.0),
        "read_lat_max": jnp.max(jnp.where(r, lat, 0.0), axis=1),
        "write_lat_avg": jnp.where(jnp.sum(w, 1) > 0,
                                   jnp.sum(write_lat, 1) / n_w, 0.0),
        "write_lat_max": jnp.max(jnp.where(w, lat, 0.0), axis=1),
        "all_done": jnp.all(jnp.where(real, done, True)),
        # completed transactions per port, split by direction [X, 2] — same
        # schema as the streaming collector's pt_count, so per-master
        # conservation checks work on either collection path
        "txns_done_port": jnp.stack([jnp.sum(r, axis=1), jnp.sum(w, axis=1)],
                                    axis=1).astype(jnp.int32),
        "complete_cycle": st.complete_cycle,
        "accept_cycle": st.accept_cycle,
    })
