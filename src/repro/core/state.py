"""Typed, width-packed simulator state — the ``lax.scan`` carry.

The cycle core used to carry an untyped ``dict`` of all-``int32`` arrays:
booleans, 2-bit slot phases, 3-bit QoS levels, and 4-bit hop counts each
burned 4 bytes of memory traffic per element per simulated cycle.  This
module replaces it with :class:`SimState`, a registered-dataclass pytree
whose fields carry explicit *narrow* dtypes:

=================  ==========  =============================================
field              dtype       contents (shape)
=================  ==========  =============================================
now                int32       current fabric cycle ()
next_txn           int32       next transaction index per port [X]
outstanding        int16       in-flight commands per port+channel [X, 2]
credits            int16       split-buffer credits per port+channel [X, 2]
beats_issued       int32       beats ever dispatched per port [X]
fwd_free           int32       W-channel data-bus free time [X]
reg_tokens         int32       regulator bucket, 1/256-beat fixed pt [X]
busy_r/w/any       int32       busy-cycle counters [X]
sl_flags           uint8       PACKED: slot phase (2 bits) | write bit [X,P]
sl_bank            int16/32    target bank per slot [X, P] (int16 iff banks
                               fit; see :func:`bank_dtype`)
sl_arrive          int32       cycle the beat reaches its bank queue [X, P]
sl_ready           int32       cycle the read beat may return [X, P]
sl_txn             int16/32    owning transaction per slot [X, P]
sl_hops            int8        inter-slice ring hops per slot [X, P]
bank_free          int32       bank busy-until cycle [NB]
bank_rr            int32       round-robin pointer basis [NB]
ing_used           int32       remote beats in flight per slice [NSL]
slice_beats        int32       beats served per slice [NSL]
remote_beats       int32       total router-crossing beats ()
remaining          int8        undelivered beats per transaction [X, N]
accept_cycle       int32       acceptance timestamp per transaction [X, N]
complete_cycle     int32       completion timestamp per transaction [X, N]
beats_done         int32       read beats returned per port [X]
drained_at         int32       cycle the run went quiescent, -1 if never ()
skipped            int32       idle cycles jumped by the time skip ()
reg_held           int32       cycles the port's due command waited for
                               regulator tokens alone [X]
aged_grants        int32       grants to a beat aging had lifted above its
                               master's level, per ring slot [X, P]
=================  ==========  =============================================

Schedule-pipeline extension (``init_state(F=..., ...)``; every array below is
zero-size on the dense path, so the dense carry is byte-identical):

=================  ==========  =============================================
ift_write/burst    int8        in-flight transaction table [X, F]: direction
ift_remaining      int8        and undelivered beats per live command
ift_accept/start   int32       acceptance / earliest-issue cycle [X, F]
ift_txn            int16/32    schedule index of the live command [X, F]
pt_first/last      int32       per-port per-direction completion window [X,2]
pt_beats/count     int32       completed beats / transactions [X, 2]
pt_lat_sum/max     float32     accept→complete latency accumulators [X, 2]
p2_height/npos     float32     P² markers [G, NQ, 5] (G = 4 × NC groups:
p2_count           int32       (view, class, direction); NQ percentiles)
p2_max             float32     exact per-group latency maximum [G]
cls_done           int32       completed transactions per class × dir [NC,2]
dl_done/dl_miss    int32       deadline bookkeeping per class [NC]
=================  ==========  =============================================

The in-flight table replaces the dense per-transaction ``remaining``/
``accept_cycle``/``complete_cycle`` arrays as the scan's per-command store:
``F`` is sized to ``2 × outstanding`` (a port can never hold more live
commands than its two channels' credit caps), so the carry stops scaling
with the schedule length ``N`` — the change that lets 100k-point grids and
thousand-request serving streams fit in memory.  With ``collect="exact"``
the schedule pipeline still carries the ``[X, N]`` timestamp arrays (for
golden-pinned parity); ``collect="stream"`` drops them and carries the
streaming accumulators instead.

Slot arrays are laid out ``[X, P]`` (port-major) rather than flat ``[S]``:
per-port operations (the return bus, dispatch ring math) become dense
reductions along the ``P`` axis instead of segment/scatter ops, and the flat
view needed by per-bank arbitration is a free ``reshape``.

Stage functions never do arithmetic in the narrow dtypes.  The pack/unpack
helpers below widen a field to a plain ``int32`` view on read
(:func:`unpack_slot_flags`, :func:`widen`) and narrow on write
(:func:`pack_slot_flags`, :func:`narrow`), so overflow semantics stay
int32 and the narrow types are purely a storage format — the golden
single-slice regression pins that this changes no simulated behaviour.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp

#: "infinite" cycle sentinel (also the arbitration-key filler ceiling)
INF32 = jnp.int32(2**30)

#: slot phase values carried in the low 2 bits of ``sl_flags``
SLOT_IDLE, SLOT_WAITING, SLOT_GRANTED = 0, 1, 2
_PHASE_MASK = 0b11
_WRITE_SHIFT = 2


# ---------------------------------------------------------------------------
# dtype pickers + pack/unpack helpers
# ---------------------------------------------------------------------------

def bank_dtype(num_banks: int):
    """Narrowest signed dtype that can index ``num_banks`` banks *plus* the
    out-of-range filler segment used by the arbiter (value ``num_banks``)."""
    return jnp.int16 if num_banks < 2**15 - 1 else jnp.int32


def txn_dtype(num_txns: int):
    """Narrowest signed dtype for transaction indices in [0, num_txns]."""
    return jnp.int16 if num_txns < 2**15 - 1 else jnp.int32


def pack_slot_flags(phase, write):
    """Pack (slot phase, write bit) int32 views into the uint8 store."""
    return (phase | (write << _WRITE_SHIFT)).astype(jnp.uint8)


def unpack_slot_flags(flags):
    """uint8 store -> readable (phase, write) int32 views."""
    f = flags.astype(jnp.int32)
    return f & _PHASE_MASK, f >> _WRITE_SHIFT


def widen(x):
    """Narrow storage -> int32 compute view (no-op on int32 fields)."""
    return x.astype(jnp.int32)


def narrow(x, like):
    """int32 compute result -> the storage dtype of field ``like``."""
    return x.astype(like.dtype)


# ---------------------------------------------------------------------------
# the state pytree
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SimState:
    """One cycle's complete simulator state (see module table for dtypes)."""
    now: jnp.ndarray
    next_txn: jnp.ndarray
    outstanding: jnp.ndarray
    credits: jnp.ndarray
    beats_issued: jnp.ndarray
    fwd_free: jnp.ndarray
    reg_tokens: jnp.ndarray
    busy_r: jnp.ndarray
    busy_w: jnp.ndarray
    busy_any: jnp.ndarray
    sl_flags: jnp.ndarray
    sl_bank: jnp.ndarray
    sl_arrive: jnp.ndarray
    sl_ready: jnp.ndarray
    sl_txn: jnp.ndarray
    sl_hops: jnp.ndarray
    bank_free: jnp.ndarray
    bank_rr: jnp.ndarray
    ing_used: jnp.ndarray
    slice_beats: jnp.ndarray
    remote_beats: jnp.ndarray
    remaining: jnp.ndarray
    accept_cycle: jnp.ndarray
    complete_cycle: jnp.ndarray
    beats_done: jnp.ndarray
    # schedule-pipeline extension (zero-size on the dense path)
    ift_write: jnp.ndarray
    ift_burst: jnp.ndarray
    ift_remaining: jnp.ndarray
    ift_accept: jnp.ndarray
    ift_start: jnp.ndarray
    ift_txn: jnp.ndarray
    pt_first: jnp.ndarray
    pt_last: jnp.ndarray
    pt_beats: jnp.ndarray
    pt_count: jnp.ndarray
    pt_lat_sum: jnp.ndarray
    pt_lat_max: jnp.ndarray
    p2_height: jnp.ndarray
    p2_npos: jnp.ndarray
    p2_count: jnp.ndarray
    p2_max: jnp.ndarray
    cls_done: jnp.ndarray
    dl_done: jnp.ndarray
    dl_miss: jnp.ndarray
    # drain bookkeeping (early-exit driver + time skip; always maintained)
    drained_at: jnp.ndarray
    skipped: jnp.ndarray
    # QoS mechanism counters (regulator holds, aging-promoted grants)
    reg_held: jnp.ndarray
    aged_grants: jnp.ndarray

    def replace(self, **updates) -> "SimState":
        """Functional field update (the stage functions' write path)."""
        return dataclasses.replace(self, **updates)


jax.tree_util.register_dataclass(
    SimState, data_fields=[f.name for f in dataclasses.fields(SimState)],
    meta_fields=[])


def init_state(*, X: int, N: int, P: int, NB: int, NSL: int,
               tx_burst, d, F: int = 0, NC: int = 0, NQ: int = 0,
               exact: bool = True) -> SimState:
    """Cycle-0 state for ``X`` ports × ``P`` ring slots, ``N`` transactions,
    ``NB`` banks, ``NSL`` slices.  ``d`` maps dyn-field names to traced int32
    scalars (credits and regulator buckets initialize from them);
    ``tx_burst`` seeds the per-transaction remaining-beat counters.

    ``F > 0`` allocates the schedule pipeline's in-flight table; ``exact``
    keeps the ``[X, N]`` timestamp arrays (dense path, or schedule path in
    golden-parity mode).  ``exact=False`` swaps them for the streaming
    accumulators — ``NC`` QoS classes × ``NQ`` tracked percentiles."""
    from repro.core.percentile import p2_init
    from repro.core.simulator import REG_SCALE  # value-only, no cycle dep

    nex = N if exact else 0          # dense timestamp width
    stream = F > 0 and not exact
    XS = X if stream else 0          # streaming per-port accumulator width
    G = 4 * NC                       # (lat|e2e) × class × direction groups
    p2_h, p2_n, p2_c = p2_init(G, NQ)
    i16_zeros2 = jnp.zeros((X, 2), jnp.int16)
    return SimState(
        now=jnp.int32(0),
        next_txn=jnp.zeros((X,), jnp.int32),
        outstanding=i16_zeros2,
        credits=i16_zeros2 + d["split_buffer"].astype(jnp.int16),
        beats_issued=jnp.zeros((X,), jnp.int32),
        fwd_free=jnp.zeros((X,), jnp.int32),
        reg_tokens=jnp.zeros((X,), jnp.int32) + d["reg_burst"] * REG_SCALE,
        busy_r=jnp.zeros((X,), jnp.int32),
        busy_w=jnp.zeros((X,), jnp.int32),
        busy_any=jnp.zeros((X,), jnp.int32),
        sl_flags=jnp.zeros((X, P), jnp.uint8),
        sl_bank=jnp.zeros((X, P), bank_dtype(NB)),
        sl_arrive=jnp.full((X, P), INF32),
        sl_ready=jnp.full((X, P), INF32),
        sl_txn=jnp.zeros((X, P), txn_dtype(N)),
        sl_hops=jnp.zeros((X, P), jnp.int8),
        bank_free=jnp.zeros((NB,), jnp.int32),
        bank_rr=jnp.zeros((NB,), jnp.int32),
        ing_used=jnp.zeros((NSL,), jnp.int32),
        slice_beats=jnp.zeros((NSL,), jnp.int32),
        remote_beats=jnp.int32(0),
        # the schedule pipeline (F > 0) tracks undelivered beats in the
        # in-flight table instead of one dense row per transaction
        remaining=(jnp.zeros((X, 0), jnp.int8) if F > 0 else
                   jnp.where(tx_burst > 0, tx_burst, 0).astype(jnp.int8)),
        accept_cycle=jnp.full((X, nex), -1, jnp.int32),
        complete_cycle=jnp.full((X, nex), -1, jnp.int32),
        beats_done=jnp.zeros((X,), jnp.int32),
        ift_write=jnp.zeros((X, F), jnp.int8),
        ift_burst=jnp.zeros((X, F), jnp.int8),
        ift_remaining=jnp.zeros((X, F), jnp.int8),
        ift_accept=jnp.zeros((X, F), jnp.int32),
        ift_start=jnp.zeros((X, F), jnp.int32),
        ift_txn=jnp.zeros((X, F), txn_dtype(max(N, 1))),
        pt_first=jnp.full((XS, 2), INF32),
        pt_last=jnp.full((XS, 2), -1, jnp.int32),
        pt_beats=jnp.zeros((XS, 2), jnp.int32),
        pt_count=jnp.zeros((XS, 2), jnp.int32),
        pt_lat_sum=jnp.zeros((XS, 2), jnp.float32),
        pt_lat_max=jnp.zeros((XS, 2), jnp.float32),
        p2_height=p2_h,
        p2_npos=p2_n,
        p2_count=p2_c,
        p2_max=jnp.zeros((G,), jnp.float32),
        cls_done=jnp.zeros((NC, 2), jnp.int32),
        dl_done=jnp.zeros((NC,), jnp.int32),
        dl_miss=jnp.zeros((NC,), jnp.int32),
        drained_at=jnp.int32(-1),
        skipped=jnp.int32(0),
        reg_held=jnp.zeros((X,), jnp.int32),
        aged_grants=jnp.zeros((X, P), jnp.int32),
    )
