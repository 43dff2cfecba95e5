"""Early-exit driver + idle-cycle time skip.

Bit-exactness against the fixed horizon across pipeline x collect x
sequential/batched/chunked/shared combinations, ``drained_cycle``
semantics, and the drained-state fixpoint property the early exit rests
on (every registered stage is a no-op on a drained ``SimState`` modulo
the cycle counter and the regulator refill).

The hypothesis property test is skipped where hypothesis is absent; the
randomized fixpoint sweep below it covers the same contract everywhere.
"""
import dataclasses
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import simulator as sim
from repro.core.simulator import (SCHEDULE_PIPELINE, SimParams, Trace,
                                  simulate, simulate_batch)

# (stages, collect) — every pipeline/collection combination the cores run
VARIANTS = [
    pytest.param(None, "exact", id="dense-exact"),
    pytest.param(SCHEDULE_PIPELINE, "exact", id="sched-exact"),
    pytest.param(SCHEDULE_PIPELINE, "stream", id="sched-stream"),
]

# the fixed horizon never skips, so this key differs by construction
SKIP_KEYS = ("skipped_cycles",)


def _gapped_trace(rng, X=4, N=6, gap=200):
    """Bursty workload: long idle stretches between issue times, so both
    the drain predicate and the time skip get exercised."""
    start = (np.arange(N)[None, :] * gap
             + rng.integers(0, 8, (X, N))).astype(np.int32)
    return Trace(is_write=rng.integers(0, 2, (X, N)),
                 burst=rng.integers(1, 9, (X, N)),
                 addr=rng.integers(0, 3000, (X, N)),
                 start=start,
                 prio=rng.integers(0, 4, X))


def _packed_trace(rng, X=4, N=6):
    """Full-injection workload: everything ready at cycle 0."""
    return Trace(is_write=rng.integers(0, 2, (X, N)),
                 burst=rng.integers(1, 9, (X, N)),
                 addr=rng.integers(0, 3000, (X, N)),
                 prio=rng.integers(0, 4, X))


def _prm(stages, collect, **kw):
    kw.setdefault("max_cycles", 2600)
    kw.setdefault("reg_rate", 64)
    kw.setdefault("qos_aging", 32)
    return SimParams(stages=stages, collect=collect, **kw)


def _assert_same(a, b, skip=SKIP_KEYS):
    assert set(a) == set(b)
    for k in a:
        if k in skip:
            continue
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=k)


# ---------------------------------------------------------------------------
# bit-exactness vs the fixed horizon
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stages,collect", VARIANTS)
@pytest.mark.parametrize("make", [_gapped_trace, _packed_trace],
                         ids=["gapped", "packed"])
def test_early_exit_bit_exact_sequential(rng, stages, collect, make):
    t = make(rng)
    prm = _prm(stages, collect)
    fast = simulate(t, prm)
    slow = simulate(t, replace(prm, early_exit=False))
    _assert_same(fast, slow)
    assert bool(fast["all_done"])
    assert 0 <= int(fast["drained_cycle"]) < prm.max_cycles
    assert int(slow["skipped_cycles"]) == 0


@pytest.mark.parametrize("stages,collect", VARIANTS)
def test_early_exit_bit_exact_batched(rng, stages, collect):
    traces = [_gapped_trace(rng), _packed_trace(rng), _gapped_trace(rng)]
    prms = [_prm(stages, collect, outstanding=o) for o in (4, 8, 6)]
    slow_prms = [replace(p, early_exit=False) for p in prms]

    for kw in ({}, {"chunk": 2}):
        fast = simulate_batch(traces, prms, **kw)
        slow = simulate_batch(traces, slow_prms, **kw)
        _assert_same(fast, slow)
        assert np.all(np.asarray(fast["drained_cycle"]) >= 0)

    # shared-trace grid: one workload, B parameter points, trace unbatched
    fast = simulate_batch(traces[:1], prms)
    slow = simulate_batch(traces[:1], slow_prms)
    _assert_same(fast, slow)


@pytest.mark.parametrize("collect", ["exact", "stream"])
def test_time_skip_bit_exact_and_fires(rng, collect):
    t = _gapped_trace(rng, gap=350)
    prm = _prm(SCHEDULE_PIPELINE, collect)
    on = simulate(t, prm)
    off = simulate(t, replace(prm, time_skip=False))
    _assert_same(on, off)
    assert int(on["skipped_cycles"]) > 0      # gaps actually got jumped
    assert int(off["skipped_cycles"]) == 0


@pytest.mark.parametrize("stages,collect", VARIANTS)
def test_block_size_invariance(rng, stages, collect):
    """K is a speed knob, not a semantics knob: every block size gives
    identical metrics (skipped_cycles excepted: skips fire at block
    boundaries, so the skip accounting legitimately depends on K)."""
    t = _gapped_trace(rng)
    ref = simulate(t, _prm(stages, collect, block_cycles=32))
    for K in (1, 7, 5000):
        out = simulate(t, _prm(stages, collect, block_cycles=K))
        _assert_same(out, ref)


def test_drained_cycle_semantics(rng):
    t = _packed_trace(rng)
    done = simulate(t, _prm(None, "exact"))
    assert bool(done["all_done"])
    assert int(done["drained_cycle"]) == int(done["effective_cycles"])
    # the nominal horizon is still what "cycles" reports (golden-pin compat)
    assert int(done["cycles"]) == 2600

    cut = simulate(t, _prm(None, "exact", max_cycles=3))
    assert not bool(cut["all_done"])
    assert int(cut["drained_cycle"]) == -1
    assert int(cut["effective_cycles"]) == int(cut["cycles"]) == 3


# ---------------------------------------------------------------------------
# the drained-state fixpoint property
# ---------------------------------------------------------------------------

# post-drain, one pipeline pass may only advance the clock and refill the
# regulator buckets (both overwritten / capped before any metric reads them)
FIXPOINT_EXEMPT = {"now", "reg_tokens"}


def _assert_drained_fixpoint(trace, prm):
    state, ctx = sim._setup_trace(trace, prm)
    cycle = sim._pipeline_cycle(prm, ctx)
    st = jax.jit(lambda s: jax.lax.scan(
        cycle, s, None, length=prm.max_cycles)[0])(state)
    assert int(st.drained_at) >= 0, "fixpoint probe needs a draining workload"
    st2 = jax.jit(lambda s: cycle(s, None)[0])(st)
    changed = [f.name for f in dataclasses.fields(type(st))
               if not np.array_equal(np.asarray(getattr(st, f.name)),
                                     np.asarray(getattr(st2, f.name)))]
    assert set(changed) <= FIXPOINT_EXEMPT, changed
    assert int(st2.now) == int(st.now) + 1


@pytest.mark.parametrize("stages,collect", VARIANTS)
def test_stages_fix_drained_state(rng, stages, collect):
    for _ in range(2):
        _assert_drained_fixpoint(_gapped_trace(rng, gap=120),
                                 _prm(stages, collect, max_cycles=2000))


def test_stages_fix_drained_state_hypothesis():
    pytest.importorskip("hypothesis", reason="property tests need hypothesis")
    from hypothesis import given, settings, strategies as st

    X, N = 3, 4

    @settings(max_examples=6, deadline=None)
    @given(data=st.data(),
           variant=st.sampled_from([(None, "exact"),
                                    (SCHEDULE_PIPELINE, "exact"),
                                    (SCHEDULE_PIPELINE, "stream")]),
           reg_rate=st.sampled_from([0, 64, 256]))
    def prop(data, variant, reg_rate):
        def grid(lo, hi):
            return np.array(data.draw(st.lists(
                st.integers(min_value=lo, max_value=hi),
                min_size=X * N, max_size=X * N))).reshape(X, N)
        t = Trace(is_write=grid(0, 1), burst=grid(1, 8),
                  addr=grid(0, 2000),
                  start=np.sort(grid(0, 600), axis=1),
                  prio=np.array(data.draw(st.lists(
                      st.integers(min_value=0, max_value=3),
                      min_size=X, max_size=X))))
        _assert_drained_fixpoint(
            t, _prm(variant[0], variant[1], max_cycles=2000,
                    reg_rate=reg_rate))

    prop()


# ---------------------------------------------------------------------------
# the time-skip invariants themselves
# ---------------------------------------------------------------------------

def test_p2_update_all_false_mask_is_noop(rng):
    """The streaming P2 accumulators never observe anything on an idle
    cycle (the retire mask is all-False), so jumping idle stretches in one
    step cannot perturb them — the invariant the time skip relies on."""
    from repro.core.percentile import p2_init, p2_update
    G, M = 3, 8
    h, n, c = p2_init(G, 3)
    vals = jnp.asarray(rng.random(M), jnp.float32) * 100
    gid = jnp.asarray(rng.integers(0, G, M), jnp.int32)
    # feed some real observations first so the state is mid-stream
    for _ in range(4):
        h, n, c = p2_update(h, n, c, vals, gid, jnp.ones((M,), bool))
    h2, n2, c2 = p2_update(h, n, c, vals, gid, jnp.zeros((M,), bool))
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h2))
    np.testing.assert_array_equal(np.asarray(n), np.asarray(n2))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(c2))


def test_regulator_refill_advanced_analytically(rng):
    """A skipped idle stretch must land the token buckets exactly where
    per-cycle refills would have: a tightly regulated gapped run (small
    bucket, slow refill) is bit-exact with the skip on vs off."""
    t = _gapped_trace(rng, gap=350)
    prm = _prm(SCHEDULE_PIPELINE, "exact", reg_rate=16, reg_burst=4)
    on = simulate(t, prm)
    off = simulate(t, replace(prm, time_skip=False))
    _assert_same(on, off)
    assert int(on["skipped_cycles"]) > 0
