"""Streaming-collection contract: event schedules, P² percentiles, chunking.

Three layers of guarantees:

  * **Bit-exactness** — the schedule pipeline in exact mode reproduces the
    dense-Trace pipeline bit-for-bit, including against the committed golden
    single-slice pin, so the packed representation is a pure footprint
    optimization.
  * **Documented P² bound** — streaming p50/p95/p99 stay inside the rank
    band declared in ``repro.core.percentile`` (±P2_RANK_TOL_PCT percentile
    points, P2_REL_TOL relative slack) of ``numpy.percentile``, for direct
    accumulator use, for merged batch lanes, and end-to-end through the
    simulator against exact collection.
  * **Batch-path equivalence** — shared-trace, chunked (divisible and not),
    and listed batches all equal sequential ``simulate`` runs.
"""
from __future__ import annotations

import json
import sys
from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "tests" / "data"))

from capture_golden import GOLDEN_KEYS, golden_cases  # noqa: E402

from repro.core.percentile import (P2_MIN_SAMPLES, P2_RANK_TOL_PCT,
                                   P2_REL_TOL, STREAM_PCTS, p2_init,
                                   p2_merge_quantile, p2_quantiles,
                                   p2_update)
from repro.core.simulator import (SCHEDULE_PIPELINE, SimParams,
                                  batch_envelope, carry_nbytes,
                                  input_nbytes, prepare_batch, simulate,
                                  simulate_batch)
from repro.core.traffic import (EventSchedule, compile_schedule,
                                random_uniform, stack_traces)
from repro.scenarios import highway_pilot

GOLDEN = json.loads(
    (REPO / "tests" / "data" / "golden_single_slice.json").read_text())


def in_rank_band(sample: np.ndarray, estimate: float, pct: float) -> bool:
    """The documented contract: the estimate lies within the
    ±P2_RANK_TOL_PCT rank band of the exact percentile (widened by
    P2_REL_TOL relative slack)."""
    lo = np.percentile(sample, max(pct - P2_RANK_TOL_PCT, 0.0))
    hi = np.percentile(sample, min(pct + P2_RANK_TOL_PCT, 100.0))
    slack = P2_REL_TOL * max(abs(lo), abs(hi), 1.0)
    return lo - slack <= estimate <= hi + slack


def _stream_sample(values, batch: int = 7, num_groups: int = 1, gid=None):
    """Feed ``values`` through p2_update in ``batch``-sized masked calls."""
    h, n, c = p2_init(num_groups, len(STREAM_PCTS))
    values = np.asarray(values, np.float32)
    gid = np.zeros(len(values), np.int32) if gid is None else gid
    for i in range(0, len(values), batch):
        v = values[i:i + batch]
        g = gid[i:i + batch]
        pad = batch - len(v)
        vj = np.concatenate([v, np.zeros(pad, np.float32)])
        gj = np.concatenate([g, np.zeros(pad, np.int32)])
        mask = np.arange(batch) < len(v)
        import jax.numpy as jnp
        h, n, c = p2_update(h, n, c, jnp.asarray(vj), jnp.asarray(gj),
                            jnp.asarray(mask))
    return h, n, c


# ---------------------------------------------------------------------------
# P² accumulator vs numpy.percentile
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dist,seed", [
    ("uniform", 0), ("uniform", 3), ("lognormal", 1), ("lognormal", 4),
    ("bimodal", 2), ("integers", 5),
])
def test_p2_within_documented_bound(dist, seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(P2_MIN_SAMPLES, 400))
    if dist == "uniform":
        vals = rng.uniform(10, 500, n)
    elif dist == "lognormal":
        vals = rng.lognormal(3.0, 1.0, n)
    elif dist == "bimodal":
        vals = np.where(rng.random(n) < 0.8, rng.uniform(20, 40, n),
                        rng.uniform(400, 800, n))
    else:
        vals = rng.integers(8, 64, n).astype(np.float64)
    h, np_, c = _stream_sample(vals)
    est = p2_quantiles(h, np_, c)
    assert int(np.asarray(c)[0]) == n
    for i, pct in enumerate(STREAM_PCTS):
        assert in_rank_band(vals, est[0, i], pct), (dist, seed, pct, est)


def test_p2_small_groups_are_exact_order_stats():
    # below 5 observations the heights are a sorted sample buffer and the
    # read-out interpolates it exactly like numpy
    vals = np.array([42.0, 7.0, 19.0])
    h, n, c = _stream_sample(vals, batch=2)
    est = p2_quantiles(h, n, c)
    for i, pct in enumerate(STREAM_PCTS):
        assert est[0, i] == pytest.approx(np.percentile(vals, pct))


def test_p2_multi_group_isolation():
    # interleaved groups accumulate independently
    rng = np.random.default_rng(7)
    v0 = rng.uniform(0, 100, 200)
    v1 = rng.uniform(1000, 2000, 200)
    vals = np.empty(400, np.float64)
    vals[0::2], vals[1::2] = v0, v1
    gid = np.tile([0, 1], 200).astype(np.int32)
    h, n, c = _stream_sample(vals, batch=16, num_groups=2, gid=gid)
    est = p2_quantiles(h, n, c)
    assert list(np.asarray(c)) == [200, 200]
    for i, pct in enumerate(STREAM_PCTS):
        assert in_rank_band(v0, est[0, i], pct)
        assert in_rank_band(v1, est[1, i], pct)


def test_p2_merge_across_lanes_within_band():
    # split one sample across 4 lanes, merge the marker states: the merged
    # estimate stays in the pooled sample's rank band
    rng = np.random.default_rng(11)
    pooled = rng.lognormal(3.5, 0.8, 600)
    lanes = np.array_split(pooled, 4)
    hs, ns, cs = [], [], []
    for lane in lanes:
        h, n, c = _stream_sample(lane)
        hs.append(np.asarray(h)[0])     # [NQ, 5]
        ns.append(np.asarray(n)[0])
        cs.append(int(np.asarray(c)[0]))
    for i, pct in enumerate(STREAM_PCTS):
        merged = p2_merge_quantile(
            np.stack([h[i] for h in hs]), np.stack([n[i] for n in ns]),
            np.asarray(cs), pct / 100.0)
        assert in_rank_band(pooled, merged, pct), (pct, merged)


def test_p2_property_hypothesis():
    hypothesis = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hypothesis.settings(max_examples=30, deadline=None)
    @hypothesis.given(st.lists(st.floats(min_value=1.0, max_value=1e6,
                                         allow_nan=False),
                               min_size=P2_MIN_SAMPLES, max_size=300),
                      st.integers(min_value=1, max_value=32))
    def prop(vals, batch):
        vals = np.asarray(vals, np.float32)
        h, n, c = _stream_sample(vals, batch=batch)
        est = p2_quantiles(h, n, c)
        for i, pct in enumerate(STREAM_PCTS):
            assert in_rank_band(vals, est[0, i], pct)

    prop()


# ---------------------------------------------------------------------------
# schedule pipeline vs the golden dense pin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", [c[0] for c in golden_cases()])
def test_schedule_exact_matches_golden(name):
    trace, prm = next((t, p) for n, t, p in golden_cases() if n == name)
    m = simulate(trace, replace(prm, stages=SCHEDULE_PIPELINE))
    for k in GOLDEN_KEYS:
        assert np.array_equal(np.asarray(GOLDEN["cases"][name][k]),
                              np.asarray(m[k])), (name, k)


def test_schedule_exact_matches_golden_batched():
    cases = golden_cases()
    traces = stack_traces([cases[1][1], cases[2][1]])
    prms = [replace(cases[1][2], max_cycles=4000, stages=SCHEDULE_PIPELINE),
            replace(cases[2][2], max_cycles=4000, stages=SCHEDULE_PIPELINE)]
    mb = simulate_batch(traces, prms)
    for k in GOLDEN_KEYS:
        assert np.array_equal(np.asarray(GOLDEN["batch"][k]),
                              np.asarray(mb[k])), k


def test_schedule_input_is_smaller_than_dense():
    tr = random_uniform(8, 40, burst=8, seed=3)
    dense = SimParams(max_cycles=100)
    sched = replace(dense, stages=SCHEDULE_PIPELINE)
    assert input_nbytes(tr, sched) < input_nbytes(tr, dense) / 4
    # streaming carry is fixed-size: independent of the transaction count
    stream = replace(sched, collect="stream")
    assert carry_nbytes(stream, 8, 40) == carry_nbytes(stream, 8, 4000)
    # exact carry is not (it holds per-transaction timestamp columns)
    assert carry_nbytes(sched, 8, 4000) > carry_nbytes(sched, 8, 40)


# ---------------------------------------------------------------------------
# compile_schedule contract
# ---------------------------------------------------------------------------

def test_compile_schedule_roundtrip_and_validation():
    tr = random_uniform(4, 12, burst=8, seed=0, full_duplex=False)
    sched = compile_schedule(tr, classes=[0, 1, 2, 3],
                             deadlines=[100, None, 50, None])
    assert isinstance(sched, EventSchedule)
    back = sched.to_trace()
    for a, b in ((back.is_write, tr.is_write), (back.burst, tr.burst),
                 (back.addr, tr.addr)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert list(np.asarray(sched.deadline)) == [100, -1, 50, -1]
    with pytest.raises(ValueError, match="classes"):
        compile_schedule(tr, classes=[0, 1])
    with pytest.raises(ValueError, match="class"):
        compile_schedule(tr, classes=[0, 1, 2, 9])
    with pytest.raises(ValueError, match="deadline"):
        compile_schedule(tr, deadlines=[0, 1, 2])


# ---------------------------------------------------------------------------
# streaming scenario summaries vs exact
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def qos_pair():
    comp = highway_pilot(txns=48).compile()
    prm = SimParams(max_cycles=6000, outstanding=4, qos_aging=64)
    exact = comp.simulate(prm)
    stream = comp.simulate(replace(prm, stages=SCHEDULE_PIPELINE,
                                   collect="stream"))
    return comp, exact, stream


def test_stream_summary_nonpercentile_keys_exact(qos_pair):
    _, exact, stream = qos_pair
    assert bool(stream.metrics["all_done"])
    for cls, e in exact.per_class.items():
        s = stream.per_class[cls]
        assert set(e) == set(s)
        for k, ev in e.items():
            if "_lat_p" in k:
                continue                    # P² keys: bounded, not exact
            sv = s[k]
            if isinstance(ev, float) and np.isnan(ev):
                assert np.isnan(sv), (cls, k)
            else:
                assert sv == pytest.approx(ev, abs=1e-5), (cls, k)


def test_stream_summary_percentiles_within_band(qos_pair):
    comp, exact, stream = qos_pair
    acc = np.asarray(exact.metrics["accept_cycle"])
    com = np.asarray(exact.metrics["complete_cycle"])
    iw = np.asarray(comp.trace.is_write)
    start = comp.trace.start_or_zeros()
    real = np.asarray(comp.trace.burst) > 0
    done = (com >= 0) & (acc >= 0) & real
    for cls in exact.per_class:
        rows = comp.masters_of_class(cls)
        sel = np.zeros_like(done)
        sel[rows] = done[rows]
        for d, dname in ((0, "read"), (1, "write")):
            for values, prefix in (((com - acc), dname),
                                   ((com - start), f"{dname}_e2e")):
                sample = values[sel & (iw == d)].astype(np.float64)
                if sample.size < P2_MIN_SAMPLES:
                    continue                # documented bound needs n >= 40
                for pct in STREAM_PCTS:
                    est = stream.per_class[cls][f"{prefix}_lat_p{int(pct)}"]
                    assert in_rank_band(sample, est, pct), \
                        (cls, prefix, pct, est)


# ---------------------------------------------------------------------------
# batch-path equivalence (shared / chunked / listed vs sequential)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stages", [None, SCHEDULE_PIPELINE])
def test_batch_paths_equal_sequential(stages):
    tr = random_uniform(4, 20, burst=8, seed=1)
    kw = {} if stages is None else {"stages": stages}
    prms = [SimParams(max_cycles=1200, outstanding=o, **kw)
            for o in (2, 4, 8, 6, 3)]
    env = batch_envelope(prms)
    pinned = [replace(p, slots_override=env.slots_per_master,
                      inflight_override=env.inflight_slots) for p in prms]
    seq = [simulate(tr, p) for p in pinned]
    for tag, out in [
        ("listed", simulate_batch([tr] * len(prms), prms)),
        ("shared", simulate_batch([tr], prms)),
        ("chunk2", simulate_batch([tr] * len(prms), prms, chunk=2)),
        ("shared-chunk2", simulate_batch([tr], prms, chunk=2)),
        ("shared-chunk3", simulate_batch([tr], prms, chunk=3)),
    ]:
        for i in range(len(prms)):
            for k in seq[0]:
                assert np.array_equal(np.asarray(seq[i][k]),
                                      np.asarray(out[k])[i]), (tag, i, k)


@pytest.mark.parametrize("lanes", ["single", "each", "shared", "chunked"])
@pytest.mark.parametrize("stages", [None, SCHEDULE_PIPELINE])
def test_dyn_points_share_one_program(stages, lanes):
    """Points that differ only in ``DYN_FIELDS`` reuse one compiled program:
    the compiled-program cache grows by one entry for the lane layout, not
    one a point, and that program compiles once."""
    from repro.core import simulator
    tr = random_uniform(4, 12, burst=8, seed=2)
    kw = {} if stages is None else {"stages": stages}
    knobs = [{}, dict(outstanding=6, cmd_latency=3, reg_rate=64, reg_burst=4),
             dict(split_buffer=32, ret_latency=2, bank_occupancy=1,
                  bank_latency=5, qos_aging=16, hop_latency=2,
                  slice_ingress=8)]
    simulator._core_jitted_cached.cache_clear()
    fns = set()
    for k in knobs:
        prm = SimParams(max_cycles=300, **kw, **k)
        if lanes == "single":
            assert simulate(tr, prm)["all_done"]
            fns.add(simulator._core_jitted_cached(
                simulator._static_prm(prm), "single", False))
            continue
        pts = [prm, replace(prm, cmd_latency=prm.cmd_latency + 4),
               replace(prm, qos_aging=2)]
        pb = prepare_batch([tr] if lanes == "shared" else [tr] * len(pts),
                           pts, chunk=2 if lanes == "chunked" else None)
        assert np.asarray(pb.run()["all_done"]).all()
        fns.add(pb.fn)
    info = simulator._core_jitted_cached.cache_info()
    assert (info.currsize, info.misses) == (1, 1)
    (fn,) = fns
    assert fn._cache_size() == 1


def test_stream_chunked_batch_drains():
    tr = random_uniform(4, 20, burst=8, seed=1)
    prms = [SimParams(max_cycles=1200, outstanding=o,
                      stages=SCHEDULE_PIPELINE, collect="stream")
            for o in (2, 4, 8)]
    out = simulate_batch([tr], prms, chunk=2)
    assert np.asarray(out["all_done"]).all()
    assert "accept_cycle" not in out        # nothing per-transaction
    assert np.asarray(out["p2_count"]).shape[0] == len(prms)
