"""Packed-state cycle core: stage registry, SimState dtypes, and the
bank-arbiter kernel's grant-for-grant parity with the arbitration stage.

The hypothesis property test is skipped where hypothesis is absent; the
randomized parity sweeps below it cover the same contract everywhere.
"""
import dataclasses
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.qos import arbitration_priority_key
from repro.core.simulator import (DEFAULT_PIPELINE, STAGE_REGISTRY, SimParams,
                                  Trace, _age_cap, register_stage, simulate)
from repro.core.state import (SimState, bank_dtype, init_state,
                              pack_slot_flags, txn_dtype, unpack_slot_flags)
from repro.kernels.bank_arbiter.ops import bank_arbiter_winners
from repro.kernels.bank_arbiter.ref import bank_arbiter_ref


def _random_arb_inputs(rng, S, NB, age_cap, X):
    level = rng.integers(0, 8, S)
    age = rng.integers(0, min(age_cap + 1, 4096), S)
    rr = rng.integers(0, X, S)
    key = arbitration_priority_key(level, age, rr, age_cap=age_cap,
                                  num_masters=X)
    bank = rng.integers(0, NB, S)
    elig = rng.random(S) < 0.4
    return (jnp.asarray(key, jnp.int32), jnp.asarray(bank, jnp.int32),
            jnp.asarray(elig))


# ---------------------------------------------------------------------------
# bank-arbiter kernel parity (interpret mode — how the CPU runs the kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,NB,X", [(64, 16, 4), (256, 256, 8),
                                    (2048, 256, 16), (300, 130, 8)])
def test_bank_arbiter_kernel_matches_ref(S, NB, X, rng):
    age_cap = _age_cap(SimParams(), X)
    for trial in range(3):
        key, bank, elig = _random_arb_inputs(rng, S, NB, age_cap, X)
        ref = bank_arbiter_winners(key, bank, elig, num_banks=NB,
                                   backend="jax")
        ker = bank_arbiter_winners(key, bank, elig, num_banks=NB,
                                   backend="pallas")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))


def test_bank_arbiter_no_eligible_slots_sentinel():
    S, NB = 32, 8
    key = jnp.zeros((S,), jnp.int32)
    bank = jnp.zeros((S,), jnp.int32)
    none = jnp.zeros((S,), bool)
    for backend in ("jax", "pallas"):
        win = bank_arbiter_winners(key, bank, none, num_banks=NB,
                                   backend=backend)
        np.testing.assert_array_equal(np.asarray(win), np.full(NB, S))


def test_bank_arbiter_vmap_parity(rng):
    S, NB, X = 128, 32, 4
    age_cap = _age_cap(SimParams(), X)
    batches = [_random_arb_inputs(rng, S, NB, age_cap, X) for _ in range(4)]
    key = jnp.stack([b[0] for b in batches])
    bank = jnp.stack([b[1] for b in batches])
    elig = jnp.stack([b[2] for b in batches])
    run = lambda be: jax.vmap(  # noqa: E731
        lambda k, b, e: bank_arbiter_winners(k, b, e, num_banks=NB,
                                             backend=be))(key, bank, elig)
    np.testing.assert_array_equal(np.asarray(run("jax")),
                                  np.asarray(run("pallas")))


def test_bank_arbiter_unknown_backend_raises():
    z = jnp.zeros((8,), jnp.int32)
    with pytest.raises(ValueError, match="unknown bank-arbiter backend"):
        bank_arbiter_winners(z, z, z > 0, num_banks=4, backend="verilog")


def test_pallas_interpreter_is_cpu_only(monkeypatch):
    """The Pallas arbiter is interpreted on CPU, compiled on TPU, and
    refused anywhere else — never a silent interpreter on an accelerator."""
    from repro.kernels.bank_arbiter import ops
    assert ops.pallas_interpret() is (jax.default_backend() == "cpu")
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops.pallas_interpret() is False
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(NotImplementedError, match="gpu"):
        ops.pallas_interpret()


def test_bank_arbiter_hypothesis_parity():
    pytest.importorskip("hypothesis", reason="property tests need hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=30, deadline=None)
    @given(data=st.data(),
           S=st.integers(min_value=1, max_value=200),
           NB=st.integers(min_value=1, max_value=64))
    def prop(data, S, NB):
        key = np.array(data.draw(st.lists(
            st.integers(min_value=0, max_value=2**29),
            min_size=S, max_size=S)), np.int32)
        bank = np.array(data.draw(st.lists(
            st.integers(min_value=0, max_value=NB - 1),
            min_size=S, max_size=S)), np.int32)
        elig = np.array(data.draw(st.lists(st.booleans(),
                                           min_size=S, max_size=S)))
        ref = bank_arbiter_ref(jnp.asarray(key), jnp.asarray(bank),
                               jnp.asarray(elig), num_banks=NB)
        ker = bank_arbiter_winners(jnp.asarray(key), jnp.asarray(bank),
                                   jnp.asarray(elig), num_banks=NB,
                                   backend="pallas")
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(ker))
        # the contract itself: each winner is the eligible min-key slot of
        # its bank, lowest slot id on ties; S where the bank is empty
        win = np.asarray(ref)
        for b in range(NB):
            slots = np.nonzero(elig & (bank == b))[0]
            if len(slots) == 0:
                assert win[b] == S
            else:
                best = slots[np.argmin(key[slots])]  # argmin: first minimum
                assert win[b] == best

    prop()


def test_full_sim_pallas_arbiter_bit_exact(rng):
    """Grant-for-grant equivalence end to end: every metric (completion
    cycles included) matches between the jax and Pallas arbiter backends."""
    X, N = 8, 8
    t = Trace(is_write=rng.integers(0, 2, (X, N)),
              burst=rng.integers(1, 13, (X, N)),
              addr=rng.integers(0, 4000, (X, N)),
              prio=rng.integers(0, 4, X))
    prm = SimParams(max_cycles=2500, qos_aging=32, reg_rate=64)
    a = simulate(t, prm)
    b = simulate(t, replace(prm, arbiter="pallas"))
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


# ---------------------------------------------------------------------------
# the arbitration stage's dense bank masks against per-slot lookups
# ---------------------------------------------------------------------------

def _lookup_arbitrate(st, wires, c):
    """The arbitration stage as per-slot lookups: ``bank_free[bank]``,
    ``bank_rr[bank]``, the flat comparator tree (``segment_min`` on the jax
    backend) and ``win[bank]`` — the oracle for the dense masks."""
    from repro.core.qos import aging_boost
    from repro.core.simulator import PRIO_LEVELS
    from repro.core.state import SLOT_GRANTED, SLOT_WAITING, widen
    X, P, S, NB = c["X"], c["P"], c["S"], c["NB"]
    d, now = c["d"], st.now
    phase, write = unpack_slot_flags(st.sl_flags)
    bank = widen(st.sl_bank)
    waiting = (phase == SLOT_WAITING) & (st.sl_arrive <= now)
    elig = waiting & (st.bank_free[bank] <= now)
    age = jnp.clip(now - st.sl_arrive, 0, c["AGE_CAP"])
    level = jnp.clip(c["slot_prio"] - aging_boost(age, d["qos_aging"]), 0,
                     PRIO_LEVELS - 1)
    rr = (c["master_col"] - st.bank_rr[bank]) % X
    key = arbitration_priority_key(level, age, rr, age_cap=c["AGE_CAP"],
                                   num_masters=X)
    if c["prm"].arbiter == "jax":
        win = bank_arbiter_ref(key.reshape(S), bank.reshape(S),
                               elig.reshape(S), num_banks=NB)
    else:
        win = bank_arbiter_winners(key.reshape(S), bank.reshape(S),
                                   elig.reshape(S), num_banks=NB,
                                   backend=c["prm"].arbiter)
    has_win = win < S
    winc = jnp.minimum(win, S - 1)
    wmaster = winc // P
    granted = c["flat_ids"] == win[bank]
    wwrite = write.reshape(S)[winc]
    occ = d["bank_occupancy"]
    owner = has_win[None, :] & (wmaster[None, :] == c["ar"][:, None])
    freed = jnp.stack([jnp.sum(owner & (wwrite[None, :] == w), axis=1,
                               dtype=jnp.int32) for w in (0, 1)], axis=1)
    st2 = st.replace(
        bank_free=jnp.where(has_win, jnp.maximum(st.bank_free, now) + occ,
                            st.bank_free),
        bank_rr=jnp.where(has_win, st.bank_rr + (wmaster - st.bank_rr) % X
                          + 1, st.bank_rr),
        sl_flags=pack_slot_flags(jnp.where(granted, SLOT_GRANTED, phase),
                                 write),
        sl_ready=jnp.where(granted, now + occ + d["bank_latency"]
                           + d["hop_latency"] * widen(st.sl_hops),
                           st.sl_ready),
        credits=st.credits + freed.astype(st.credits.dtype),
        aged_grants=st.aged_grants + (granted & (level < c["slot_prio"])))
    arb = dict(has_win=has_win, wmaster=wmaster, wwrite=wwrite,
               whops=widen(st.sl_hops).reshape(S)[winc],
               wtxn=widen(st.sl_txn).reshape(S)[winc])
    return st2, dict(wires, arb=arb)


def _random_slot_state(rng, NB, arbiter, X=4, P=64, N=6):
    """A dense-pipeline state with random slot contents: every phase,
    arrivals in the past and the future (few distinct ages, so keys tie
    within and across ports), busy and free banks, and half the banks with
    no slot."""
    from repro.core.address import MemoryGeometry
    from repro.core.simulator import _setup_trace, _static_prm
    prm = _static_prm(SimParams(
        geom=MemoryGeometry(num_slices=NB // 256), slots_override=P,
        max_cycles=4000, arbiter=arbiter, qos_aging=4))
    t = Trace(is_write=rng.integers(0, 2, (X, N)),
              burst=rng.integers(1, 9, (X, N)),
              addr=rng.integers(0, 3000, (X, N)),
              prio=rng.integers(0, 2, X))
    st, ctx = _setup_trace(t, replace(prm, qos_aging=4, hop_latency=6))
    now = 1000
    # slots target half the banks, a quarter of them eight hot banks, so
    # many slots of one port and of several ports meet at one bank
    used = rng.choice(NB, NB // 2, replace=False)
    hot = rng.random((X, P)) < 0.25
    st = st.replace(
        now=jnp.int32(now),
        sl_flags=pack_slot_flags(jnp.asarray(rng.integers(0, 3, (X, P))),
                                 jnp.asarray(rng.integers(0, 2, (X, P)))),
        sl_bank=jnp.asarray(used[np.where(hot, rng.integers(0, 8, (X, P)),
                                          rng.integers(0, NB // 2, (X, P)))],
                            st.sl_bank.dtype),
        sl_arrive=jnp.asarray(now + rng.choice([-40, -9, -1, 0, 2], (X, P)),
                              jnp.int32),
        sl_ready=jnp.asarray(rng.integers(0, 2000, (X, P)), jnp.int32),
        sl_hops=jnp.asarray(rng.integers(0, 3, (X, P)), st.sl_hops.dtype),
        sl_txn=jnp.asarray(rng.integers(0, N, (X, P)), st.sl_txn.dtype),
        bank_free=jnp.asarray(now + rng.integers(-3, 3, NB), jnp.int32),
        bank_rr=jnp.asarray(rng.integers(0, 3 * X, NB), jnp.int32))
    return st, ctx


def _assert_same_stage_out(a, b):
    for f in dataclasses.fields(SimState):
        np.testing.assert_array_equal(np.asarray(getattr(a[0], f.name)),
                                      np.asarray(getattr(b[0], f.name)),
                                      err_msg=f.name)
    for k, v in b[1]["arb"].items():
        np.testing.assert_array_equal(np.asarray(a[1]["arb"][k]),
                                      np.asarray(v), err_msg=k)


@pytest.fixture
def masked_arbiter(monkeypatch):
    """Lower the arbitration stage's dense masks on this CPU too (the CPU
    lowering is per-slot lookups)."""
    from repro.core import simulator
    monkeypatch.setattr(simulator, "_arbiter_by_lookup",
                        simulator._arbiter_by_mask)


@pytest.mark.parametrize("arbiter", ["jax", "pallas"])
@pytest.mark.parametrize("NB", [256, 512, 1024])
def test_masked_arbitration_matches_lookups(NB, arbiter, rng,
                                            masked_arbiter):
    """Dense bank masks against the per-slot lookup oracle: the same
    SimState and the same arb wires, grant for grant."""
    from repro.core.simulator import _stage_bank_arbitrate
    for _ in range(3):
        st, ctx = _random_slot_state(rng, NB, arbiter)
        got = jax.jit(lambda s: _stage_bank_arbitrate(s, {}, ctx))(st)
        want = jax.jit(lambda s: _lookup_arbitrate(s, {}, ctx))(st)
        assert int(jnp.sum(got[1]["arb"]["has_win"])) > 0
        assert not bool(jnp.all(got[1]["arb"]["has_win"]))
        _assert_same_stage_out(got, want)


@pytest.mark.parametrize("arbiter", ["jax", "pallas"])
def test_masked_arbitration_vmap_matches_lookups(arbiter, rng,
                                                 masked_arbiter):
    """The dense masks under vmap, as batched and sharded sweeps run them."""
    from repro.core.simulator import _stage_bank_arbitrate
    states = [_random_slot_state(rng, 512, arbiter) for _ in range(3)]
    ctx = states[0][1]
    batch = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                   *[s for s, _ in states])
    run = lambda fn: jax.jit(jax.vmap(  # noqa: E731
        lambda s: fn(s, {}, ctx)))(batch)
    _assert_same_stage_out(run(_stage_bank_arbitrate), run(_lookup_arbitrate))


def test_masked_arbitration_full_sim_bit_exact(rng, monkeypatch):
    """A whole run lowered with the dense masks on this CPU: every metric
    matches the per-slot lookups' run."""
    from functools import partial
    from repro.core import simulator
    X, N = 8, 8
    t = Trace(is_write=rng.integers(0, 2, (X, N)),
              burst=rng.integers(1, 13, (X, N)),
              addr=rng.integers(0, 4000, (X, N)),
              prio=rng.integers(0, 4, X))
    prm = SimParams(max_cycles=2500, qos_aging=32, reg_rate=64)
    sprm = simulator._static_prm(prm)
    args = simulator._device_input(sprm, simulator._host_input(t, sprm),
                                   prm.dyn_vector())
    run = lambda: jax.jit(partial(simulator._core, prm=sprm))(*args)  # noqa: E731
    want = run()
    monkeypatch.setattr(simulator, "_arbiter_by_lookup",
                        simulator._arbiter_by_mask)
    got = run()
    assert int(got["cycles"]) > 0
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)


# ---------------------------------------------------------------------------
# acceptance + dispatch: dense selects against scatters and per-slot gathers
# ---------------------------------------------------------------------------

def _slot_gather(row, offc):
    """One lookup a ring slot into its port's burst row: the oracle for
    ``_select_beat``."""
    return row[jnp.arange(row.shape[0])[:, None], offc]


def _ring_offsets(beats_issued, P, mb):
    """Each slot's clamped beat offset, as the dispatch stages compute it."""
    off = (jnp.arange(P)[None, :] - beats_issued[:, None]) % P
    return jnp.minimum(off, mb - 1)


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.int16, jnp.int32])
@pytest.mark.parametrize("P,mb", [(256, 16), (64, 16), (48, 4)])
def test_select_beat_matches_slot_gather(P, mb, dtype, rng):
    """Random rows at every ring phase: one port per ``beats_issued``
    residue, so every offset, wrapped or not, is read; on one lane and
    under vmap."""
    from repro.core.simulator import _select_beat
    info = jnp.iinfo(dtype)
    rows = jnp.asarray(rng.integers(info.min, info.max, (3, P, mb),
                                    endpoint=True), dtype)
    offc = jnp.stack([_ring_offsets(jnp.asarray(rng.permutation(P) + k * P),
                                    P, mb) for k in range(3)])
    got = jax.jit(_select_beat)(rows[0], offc[0])
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_slot_gather(rows[0], offc[0])))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(jax.vmap(_select_beat))(rows, offc)),
        np.asarray(jax.vmap(_slot_gather)(rows, offc)))


def _scatter_accept_dispatch(st, c, sched):
    """Acceptance + dispatch as the cycle core did them with [X]-index
    scatters (outstanding count, credits, in-flight table, acceptance time)
    and one lookup a ring slot for the beat's bank and hops; the admission
    gates are the stage's own."""
    from repro.core import simulator
    from repro.core.state import widen
    ar, now = c["ar"], st.now
    accept = (simulator._stage_accept_sched if sched
              else simulator._stage_accept)
    new, wires = accept(st, {}, c)
    a = wires["accept"]
    can, burst, is_w, nt_c = a["can"], a["burst"], a["is_w"], a["nt_c"]
    upd = dict(
        outstanding=st.outstanding.at[ar, is_w].add(
            can.astype(st.outstanding.dtype)),
        credits=st.credits.at[ar, is_w].add(
            (-jnp.where(can, burst, 0)).astype(st.credits.dtype)))
    if sched:
        idx = a["ift_idx"]

        def put(tbl, val):
            keep = widen(tbl[ar, idx])
            return tbl.at[ar, idx].set(
                jnp.where(can, val, keep).astype(tbl.dtype))

        upd.update(ift_write=put(st.ift_write, is_w),
                   ift_burst=put(st.ift_burst, burst),
                   ift_remaining=put(st.ift_remaining, burst),
                   ift_accept=put(st.ift_accept, now),
                   ift_start=put(st.ift_start, c["tx_start"][ar, nt_c]),
                   ift_txn=put(st.ift_txn, nt_c))
        if c["exact"]:
            upd["accept_cycle"] = st.accept_cycle.at[ar, nt_c].max(
                jnp.where(can, now, -1))
    dispatch = (simulator._stage_dispatch_sched if sched
                else simulator._stage_dispatch)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(simulator, "_select_beat", _slot_gather)
        return dispatch(new.replace(**upd), wires, c)


def _mid_run_states(rng, sched, slices, lanes=4, X=16, P=64):
    """States of either pipeline a few cycles into a random run, one a
    lane, with ``beats_issued`` moved so that the lanes together put every
    port's ring at every phase (lane k, port x: residue k * X + x)."""
    from repro.core import simulator as sim
    from repro.core.address import MemoryGeometry
    from repro.core.simulator import SCHEDULE_PIPELINE
    full = SimParams(geom=MemoryGeometry(num_slices=slices),
                     slots_override=P, max_cycles=200,
                     stages=SCHEDULE_PIPELINE if sched else DEFAULT_PIPELINE)
    prm = sim._static_prm(full)
    states = []
    for k in range(lanes):
        t = Trace(is_write=rng.integers(0, 2, (X, 12)),
                  burst=rng.integers(1, prm.max_burst + 1, (X, 12)),
                  addr=rng.integers(0, 1 << 16, (X, 12)))
        st, ctx = sim._setup_trace(t, full)
        st = jax.lax.scan(sim._pipeline_cycle(prm, ctx), st, None,
                          length=int(rng.integers(0, 6)))[0]
        bi = k * X + np.arange(X) + P * rng.integers(0, 4, X)
        states.append((st.replace(beats_issued=jnp.asarray(
            bi, st.beats_issued.dtype)), ctx))
    return states, prm


def _assert_same_state(a, b):
    for f in dataclasses.fields(SimState):
        np.testing.assert_array_equal(np.asarray(getattr(a, f.name)),
                                      np.asarray(getattr(b, f.name)),
                                      err_msg=f.name)


@pytest.mark.parametrize("slices", [1, 2])
@pytest.mark.parametrize("pipeline", ["dense", "schedule"])
def test_accept_dispatch_matches_scatters_and_slot_gathers(pipeline, slices,
                                                          rng):
    """Both fused acceptance + dispatch stages against their scatter and
    per-slot-gather oracle, field for field, at every ring phase with bursts
    of every length up to ``max_burst``: each state on its own and the
    lanes stacked under vmap.  Two slices put non-zero hops in the ring."""
    from repro.core.simulator import (_stage_accept_dispatch,
                                      _stage_accept_dispatch_sched)
    sched = pipeline == "schedule"
    stage = _stage_accept_dispatch_sched if sched else _stage_accept_dispatch
    states, prm = _mid_run_states(rng, sched, slices)
    wrapped = hops = 0
    for st, ctx in states:
        got = jax.jit(lambda s: stage(s, {}, ctx)[0])(st)
        want = jax.jit(lambda s: _scatter_accept_dispatch(s, ctx, sched)[0])(st)
        _assert_same_state(got, want)
        # the ring was (re)written across its end, with hops where remote
        wrote = np.asarray(got.beats_issued - st.beats_issued)
        wrapped += int(np.sum((wrote > 0) & (np.asarray(st.beats_issued)
                                             % ctx["P"] + wrote > ctx["P"])))
        hops += int(np.sum(np.asarray(got.sl_hops) != np.asarray(st.sl_hops)))
    assert wrapped > 0
    assert (hops > 0) == (slices > 1)
    # the states' shapes agree across lanes, so they stack for vmap (the
    # traces differ, the context arrays do not: lane 0's serves every lane
    # for the stage and its oracle alike)
    ctx = states[0][1]
    batch = jax.tree_util.tree_map(lambda *a: jnp.stack(a),
                                   *[s for s, _ in states])
    run = lambda fn: jax.jit(jax.vmap(  # noqa: E731
        lambda s: fn(s)[0]))(batch)
    _assert_same_state(run(lambda s: stage(s, {}, ctx)),
                       run(lambda s: _scatter_accept_dispatch(s, ctx, sched)))


# ---------------------------------------------------------------------------
# stage registry
# ---------------------------------------------------------------------------

def _small_trace(rng, X=4, N=5):
    return Trace(is_write=rng.integers(0, 2, (X, N)),
                 burst=rng.integers(1, 9, (X, N)),
                 addr=rng.integers(0, 3000, (X, N)))


def test_default_pipeline_registered():
    assert set(DEFAULT_PIPELINE) <= set(STAGE_REGISTRY)
    assert SimParams().pipeline() == DEFAULT_PIPELINE


def test_unknown_stage_rejected():
    with pytest.raises(ValueError, match="unknown stage"):
        SimParams(stages=("accept", "teleport")).pipeline()


def test_explicit_default_pipeline_matches_implicit(rng):
    t = _small_trace(rng)
    a = simulate(t, SimParams(max_cycles=1500))
    b = simulate(t, SimParams(max_cycles=1500, stages=DEFAULT_PIPELINE))
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


def test_registered_stage_is_swappable(rng):
    """A stage added by configuration runs inside the scan: an observer
    stage that rewrites a state field is visible in the outputs."""
    @register_stage("test_freeze_clock")
    def freeze(st, wires, ctx):
        return st.replace(now=st.now - 1), wires  # cancel retire's +1

    try:
        t = _small_trace(rng)
        out = simulate(t, SimParams(
            max_cycles=50, stages=DEFAULT_PIPELINE + ("test_freeze_clock",)))
        assert int(out["cycles"]) == 0      # clock never advanced
        assert not bool(out["all_done"])    # and nothing ever completed
    finally:
        del STAGE_REGISTRY["test_freeze_clock"]


def test_pipeline_is_static_key():
    base = SimParams()
    assert base.static_key() != replace(
        base, stages=("accept", "retire")).static_key()
    assert base.static_key() != replace(base, arbiter="pallas").static_key()


# ---------------------------------------------------------------------------
# SimState packing + validation
# ---------------------------------------------------------------------------

def test_slot_flags_roundtrip():
    phase = jnp.array([[0, 1, 2, 0]], jnp.int32)
    write = jnp.array([[1, 0, 1, 0]], jnp.int32)
    flags = pack_slot_flags(phase, write)
    assert flags.dtype == jnp.uint8
    p2, w2 = unpack_slot_flags(flags)
    assert p2.dtype == jnp.int32 and w2.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(p2), np.asarray(phase))
    np.testing.assert_array_equal(np.asarray(w2), np.asarray(write))


def test_dtype_pickers():
    assert bank_dtype(256) == jnp.int16
    assert bank_dtype(2**15 - 1) == jnp.int32
    assert txn_dtype(100) == jnp.int16
    assert txn_dtype(2**16) == jnp.int32


def test_init_state_narrow_dtypes():
    d = {"split_buffer": jnp.int32(64), "reg_burst": jnp.int32(16)}
    st = init_state(X=4, N=6, P=32, NB=256, NSL=1,
                    tx_burst=jnp.ones((4, 6), jnp.int8), d=d)
    assert isinstance(st, SimState)
    assert st.sl_flags.dtype == jnp.uint8
    assert st.sl_hops.dtype == jnp.int8
    assert st.remaining.dtype == jnp.int8
    assert st.outstanding.dtype == jnp.int16
    assert st.credits.dtype == jnp.int16
    assert st.sl_bank.dtype == jnp.int16
    assert st.sl_arrive.dtype == jnp.int32
    # and it is a pytree the scan can carry: every field is a leaf, and the
    # schedule/streaming extensions are zero-size on the dense path
    leaves = jax.tree_util.tree_leaves(st)
    assert len(leaves) == len(dataclasses.fields(SimState)) == 48
    assert st.ift_write.shape == (4, 0) and st.pt_count.shape == (0, 2)


def test_param_width_validation():
    with pytest.raises(ValueError, match="int16 credit counters"):
        SimParams(split_buffer=2**14).dyn_vector()
    with pytest.raises(ValueError, match="max_burst"):
        simulate(Trace(is_write=np.zeros((1, 1), int),
                       burst=np.full((1, 1), 200),
                       addr=np.zeros((1, 1), int)),
                 SimParams(max_burst=200))
