"""Multi-slice fabric: single-slice bit-exactness (golden regression), the
inter-slice router's observable behaviour, slice-affine placement, sweep
slice reporting, device-sharded batching, and the benchmark CLI.

Hypothesis-free (the address-map property tests live in
``test_address_slices.py``) so this suite runs without optional dev deps.
"""
import json
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest

from repro.core.address import MemoryGeometry, master_home_slices
from repro.core.qos import regions_isolated
from repro.core.simulator import (SimParams, Trace, batch_sharding, simulate,
                                  simulate_batch)
from repro.core.traffic import pad_trace
from repro.scenarios import (MasterSpec, Scenario, SweepPoint,
                             run_sweep, slice_scaling)
from repro.scenarios.spec import resolve_regions

REPO = Path(__file__).resolve().parents[1]
DATA = Path(__file__).parent / "data"

GEOM2 = MemoryGeometry(num_slices=2, slice_policy="region")


def _directed_trace(geom, *, remote: bool, masters=8, txns=32, burst=8,
                    seed=0):
    """Read-only trace whose every address targets the issuing master's home
    slice (or the next slice over, when ``remote``)."""
    rng = np.random.default_rng(seed)
    home = master_home_slices(masters, geom)
    tgt = (home + 1) % geom.num_slices if remote else home
    bps = geom.beats_per_slice
    addr = np.stack([t * bps + rng.integers(0, bps - burst, txns)
                     for t in tgt])
    return Trace(np.zeros((masters, txns), np.int32),
                 np.full((masters, txns), burst, np.int32),
                 addr.astype(np.int32))


# ---------------------------------------------------------------------------
# single-slice refactor regression: bit-for-bit vs the pre-refactor goldens
# ---------------------------------------------------------------------------

def test_single_slice_outputs_match_pre_refactor_goldens():
    """Acceptance criterion: with num_slices=1 the stage-decomposed core
    reproduces the monolithic pre-refactor simulator exactly, sequential and
    batched, on existing presets (goldens captured before the refactor; see
    tests/data/capture_golden.py)."""
    sys.path.insert(0, str(DATA))
    try:
        from capture_golden import (GOLDEN_KEYS, _jsonable, golden_batch,
                                    golden_cases)
    finally:
        sys.path.pop(0)
    golden = json.loads((DATA / "golden_single_slice.json").read_text())
    for name, trace, prm in golden_cases():
        got = _jsonable(simulate(trace, prm))
        for k in GOLDEN_KEYS:
            assert got[k] == golden["cases"][name][k], (name, k)
    got = _jsonable(simulate_batch(*golden_batch(golden_cases())))
    for k in GOLDEN_KEYS:
        assert got[k] == golden["batch"][k], ("batch", k)


def test_single_slice_metrics_report_no_crossings():
    tr = _directed_trace(MemoryGeometry(), remote=False, masters=4, txns=16)
    m = simulate(tr, SimParams(max_cycles=3000))
    assert m["slice_beats"].shape == (1,)
    assert int(m["remote_beats"]) == 0
    assert float(m["remote_beat_fraction"]) == 0.0
    assert int(m["slice_beats"].sum()) == int(tr.burst.sum())


# ---------------------------------------------------------------------------
# the inter-slice router
# ---------------------------------------------------------------------------

def test_local_vs_remote_placement_crossing_counts():
    prm = SimParams(geom=GEOM2, max_cycles=5000)
    ml = simulate(_directed_trace(GEOM2, remote=False), prm)
    mr = simulate(_directed_trace(GEOM2, remote=True), prm)
    assert bool(ml["all_done"]) and bool(mr["all_done"])
    assert float(ml["remote_beat_fraction"]) == 0.0
    assert float(mr["remote_beat_fraction"]) == 1.0
    total = int(_directed_trace(GEOM2, remote=True).burst.sum())
    assert int(mr["remote_beats"]) == total
    # every beat is granted exactly once, whatever the placement
    assert int(ml["slice_beats"].sum()) == total
    assert int(mr["slice_beats"].sum()) == total


def test_hop_latency_penalizes_remote_reads_monotonically():
    tr = _directed_trace(GEOM2, remote=True)
    lats = [float(simulate(tr, SimParams(geom=GEOM2, max_cycles=6000,
                                         hop_latency=h))
                  ["read_lat_avg"].mean()) for h in (0, 6, 20)]
    assert lats[0] < lats[1] < lats[2], lats
    # local traffic does not care about the hop knob
    tl = _directed_trace(GEOM2, remote=False)
    m0 = simulate(tl, SimParams(geom=GEOM2, max_cycles=6000, hop_latency=0))
    m1 = simulate(tl, SimParams(geom=GEOM2, max_cycles=6000, hop_latency=20))
    assert np.array_equal(m0["complete_cycle"], m1["complete_cycle"])


def test_slice_ingress_credits_throttle_remote_traffic():
    tr = _directed_trace(GEOM2, remote=True)
    base = SimParams(geom=GEOM2, max_cycles=12_000, bank_occupancy=8)
    uncapped = simulate(tr, base)                       # slice_ingress=0
    capped = simulate(tr, replace(base, slice_ingress=8))
    assert bool(capped["all_done"]), "credits must throttle, never deadlock"
    assert float(capped["read_throughput"].mean()) < \
        float(uncapped["read_throughput"].mean())
    assert int(capped["beats_done"].sum()) == int(tr.burst.sum())
    # the cap is inert for local traffic
    tl = _directed_trace(GEOM2, remote=False)
    m_cap = simulate(tl, replace(base, slice_ingress=8))
    m_unc = simulate(tl, base)
    assert np.array_equal(m_cap["complete_cycle"], m_unc["complete_cycle"])


def test_oversized_remote_burst_is_delayed_never_deadlocked():
    """A burst needing more ingress credits than the cap goes into debt
    (like the regulator) instead of never being accepted."""
    tr = _directed_trace(GEOM2, remote=True, masters=4, txns=8, burst=16)
    m = simulate(tr, SimParams(geom=GEOM2, max_cycles=8000, slice_ingress=4))
    assert bool(m["all_done"])
    assert int(m["beats_done"].sum()) == int(tr.burst.sum())


def test_same_cycle_admission_respects_the_ingress_cap():
    """16 ports offering remote bursts in the same cycle must not blow the
    per-slice cap: with in-order admission the first cycle admits only as
    many bursts as the credits allow, visible as serialized accept times."""
    geom = MemoryGeometry(num_slices=2, slice_policy="region")
    tr = _directed_trace(geom, remote=True, masters=16, txns=4, burst=8)
    capped = simulate(tr, SimParams(geom=geom, max_cycles=8000,
                                    slice_ingress=8))
    free = simulate(tr, SimParams(geom=geom, max_cycles=8000))
    assert bool(capped["all_done"])
    # uncapped: every port's first txn is accepted at cycle 0; capped: only
    # one 8-beat burst fits the 8-credit slice, the rest queue
    first = np.asarray(capped["accept_cycle"])[:, 0]
    assert int((first == 0).sum()) < int(
        (np.asarray(free["accept_cycle"])[:, 0] == 0).sum())
    assert len(np.unique(first)) > 1


def test_local_ports_never_stall_on_remote_slice_debt():
    """Mixed placement: a port with zero ingress needs (purely local traffic)
    is unaffected by another port driving a remote slice into credit debt."""
    bps = GEOM2.beats_per_slice
    rng = np.random.default_rng(2)
    N = 12
    # port 0 (home slice 0): burst-16 remote reads into slice 1, need > cap
    # port 1 (home slice 0): purely local burst-16 reads in slice 0
    addr = np.stack([bps + rng.integers(0, bps - 16, N),
                     rng.integers(0, bps - 16, N)]).astype(np.int32)
    tr = Trace(np.zeros((2, N), np.int32), np.full((2, N), 16, np.int32),
               addr)
    prm = SimParams(geom=GEOM2, max_cycles=8000, slice_ingress=8,
                    hop_latency=8)
    mixed = simulate(tr, prm)
    alone = simulate(Trace(tr.is_write, np.where([[False], [True]], tr.burst,
                                                 0).astype(np.int32),
                           tr.addr), prm)
    assert bool(mixed["all_done"])
    # the local port's acceptance schedule is identical with or without the
    # debt-ridden remote neighbour (they share no banks and no credits)
    assert np.array_equal(np.asarray(mixed["accept_cycle"])[1],
                          np.asarray(alone["accept_cycle"])[1])


def test_remote_fraction_bounded_even_when_undrained():
    tr = _directed_trace(GEOM2, remote=True, masters=8, txns=64, burst=16)
    m = simulate(tr, SimParams(geom=GEOM2, max_cycles=300,   # too few cycles
                               bank_occupancy=32))
    assert not bool(m["all_done"])
    frac = float(m["remote_beat_fraction"])
    assert 0.0 <= frac <= 1.0


def test_linear_banking_router_accounting_is_consistent():
    """Under banking comparators the router's hops/credits key off the
    bank's slice, so credits released always match credits consumed."""
    geom = MemoryGeometry(num_slices=2)        # hash slice policy
    tr = _directed_trace(MemoryGeometry(num_slices=2, slice_policy="region"),
                         remote=True, masters=4, txns=16)
    for banking in ("linear", "no_fractal"):
        m = simulate(tr, SimParams(geom=geom, max_cycles=10_000,
                                   banking=banking, slice_ingress=8))
        assert bool(m["all_done"]), banking
        assert int(m["slice_beats"].sum()) == int(tr.burst.sum()), banking
        assert 0.0 <= float(m["remote_beat_fraction"]) <= 1.0, banking


def test_padding_never_reassigns_home_slices():
    """Home slices key off the geometry's port fan-out, not the trace's row
    count — padding a trace to a sweep's wider master envelope must not turn
    slice-local placement into remote traffic."""
    h8 = master_home_slices(8, GEOM2)
    h16 = master_home_slices(16, GEOM2)
    assert np.array_equal(h8, h16[:8])
    tr = _directed_trace(GEOM2, remote=True, masters=4, txns=8)
    prm = SimParams(geom=GEOM2, max_cycles=6000, hop_latency=8)
    assert float(simulate(tr, prm)["remote_beat_fraction"]) == 1.0
    padded = simulate(pad_trace(tr, 8, 12), prm)
    assert float(padded["remote_beat_fraction"]) == 1.0
    tl = _directed_trace(GEOM2, remote=False, masters=4, txns=8)
    assert float(simulate(pad_trace(tl, 8, 12), prm)
                 ["remote_beat_fraction"]) == 0.0


def test_out_of_range_addresses_fail_loudly():
    """A beat past beats_total must raise, not silently spin to max_cycles
    (its phantom bank id would be dropped by the scan's segment ops)."""
    for geom in (GEOM2, MemoryGeometry(num_slices=2), MemoryGeometry()):
        tr = Trace(np.zeros((1, 1), np.int32), np.full((1, 1), 4, np.int32),
                   np.array([[geom.beats_total - 1]], np.int32))
        with pytest.raises(ValueError, match="out of range"):
            simulate(tr, SimParams(geom=geom, max_cycles=100))
    # in-range traffic is untouched, and inert padding (burst 0) is exempt
    ok = Trace(np.zeros((1, 2), np.int32), np.array([[4, 0]], np.int32),
               np.array([[0, 2**30]], np.int32))
    m = simulate(ok, SimParams(max_cycles=2000))
    assert bool(m["all_done"])


def test_batched_multi_slice_matches_sequential():
    traces = [_directed_trace(GEOM2, remote=False),
              _directed_trace(GEOM2, remote=True)]
    prm = SimParams(geom=GEOM2, max_cycles=5000, slice_ingress=16)
    out = simulate_batch(traces, [prm, prm])
    for i, t in enumerate(traces):
        seq = simulate(t, replace(prm, slots_override=prm.slots_per_master))
        for k in seq:
            assert np.array_equal(np.asarray(out[k])[i], seq[k]), (i, k)


# ---------------------------------------------------------------------------
# device sharding
# ---------------------------------------------------------------------------

def test_batch_sharding_single_device_falls_back():
    import jax
    n = len(jax.devices())
    if n == 1:
        assert batch_sharding(4) is None      # nothing to split on one device
    else:
        with pytest.raises(ValueError, match="does not split"):
            batch_sharding(n + 1)             # callers pad; never one device


_SHARD_PROG = """
import sys
import numpy as np, jax
ND = int(sys.argv[1])
assert len(jax.devices()) == ND, jax.devices()
from repro.core.simulator import (SCHEDULE_PIPELINE, SimParams, Trace,
                                  batch_sharding, prepare_batch,
                                  simulate_batch)
rng = np.random.default_rng(0)
X, N = 4, 16
traces = [Trace(np.zeros((X, N), np.int32), np.full((X, N), 8, np.int32),
                rng.integers(0, 2**18, (X, N)).astype(np.int32))
          for _ in range(5)]
prms = [SimParams(max_cycles=800, outstanding=o) for o in (8, 4, 2, 6, 3)]
assert batch_sharding(ND) is not None
try:
    batch_sharding(ND + 1)
    raise AssertionError("a non-divisible batch must not shard silently")
except ValueError:
    pass

def check(tr, pr, **kw):
    pb = prepare_batch(tr, pr, shard=True, **kw)
    held = {s.device for a in pb.args[pb.batched:]
            for s in a.addressable_shards}
    assert held == set(jax.devices()), (len(tr), len(pr), kw, held)
    s = pb.run()
    u = simulate_batch(tr, pr, shard=False, **kw)
    for k in u:
        assert np.asarray(s[k]).shape[0] == len(pr), k
        assert np.array_equal(s[k], u[k]), (len(tr), len(pr), kw, k)

check(traces[:4], prms[:4])                  # divisible batch
check(traces[:3], prms[:3])                  # padded to the device multiple
check(traces[:1], prms[:3])                  # shared trace, B % ND != 0
check(traces[:1], prms, chunk=3)             # shared, chunk % ND != 0
check(traces, prms, chunk=3)                 # per-point traces, chunk % ND
check(traces, prms, chunk=ND)                # chunk divisible by ND
stream = [SimParams(max_cycles=800, outstanding=o, stages=SCHEDULE_PIPELINE,
                    collect="stream") for o in (8, 4, 2, 6, 3)]
check(traces[:1], stream, chunk=3)           # the scale-sweep path
print("OK")
"""


@pytest.mark.parametrize("ndev", [2, 4])
def test_sharded_batch_matches_unsharded_across_devices(ndev):
    """Force ``ndev`` host devices in a subprocess (the flag must precede
    jax import) and check every sharded batch path is bit-identical to the
    unsharded run and really holds a shard on every device: divisible and
    NON-divisible batches (padded up to the device multiple and sliced
    back), a shared trace with B not divisible by the device count, and
    chunked runs whose chunk the device count does and does not divide."""
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={ndev}",
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(REPO / "src"),
           "PATH": "/usr/local/bin:/usr/bin:/bin"}
    res = subprocess.run([sys.executable, "-c", _SHARD_PROG, str(ndev)],
                         env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr
    assert "OK" in res.stdout


# ---------------------------------------------------------------------------
# scenario layer: slice-affine placement + sweep reporting
# ---------------------------------------------------------------------------

def test_slice_affinity_places_regions_in_slice_spans():
    for s_count in (1, 2, 4):
        for remote in ([False] if s_count == 1 else [False, True]):
            sc = slice_scaling(s_count, txns=8, remote=remote)
            c = sc.compile()
            assert regions_isolated(c.trace, sc.geom), sc.name
            bps = sc.geom.beats_per_slice
            home = master_home_slices(len(sc.masters), sc.geom)
            for m, (lo, hi) in enumerate(c.regions):
                want = (home[m] + 1) % s_count if remote else home[m]
                assert lo // bps == want and (hi - 1) // bps == want


def test_unconstrained_masters_default_to_home_slice_on_region_fabric():
    """Affine and unconstrained auto-placed masters coexist: without an
    explicit affinity a master lands in its *home* slice's span instead of
    fighting the affine groups for the whole address space."""
    g = MemoryGeometry(num_slices=2, slice_policy="region")
    sc = Scenario("mixed", [
        MasterSpec("radar", qos="safety", txns=8, slice_affinity=0),
        MasterSpec("npu", qos="realtime", txns=8, slice_affinity=1),
        MasterSpec("cpu", txns=8),                 # unconstrained
    ], g)
    c = sc.compile()
    assert regions_isolated(c.trace, g)
    bps = g.beats_per_slice
    home = master_home_slices(3, g)
    assert c.regions[0][1] <= bps                  # affinity 0
    assert c.regions[1][0] >= bps                  # affinity 1
    lo, hi = c.regions[2]                          # home slice of master 2
    assert lo // bps == home[2] and (hi - 1) // bps == home[2]


def test_slice_affinity_validation():
    g = MemoryGeometry(num_slices=2, slice_policy="region")
    with pytest.raises(ValueError, match="out of range"):
        Scenario(
            "t", [MasterSpec("cpu", slice_affinity=7)], g).compile()
    with pytest.raises(ValueError, match="slice_policy"):
        Scenario(
            "t", [MasterSpec("cpu", slice_affinity=1)],
            MemoryGeometry(num_slices=2)).compile()      # hash policy: no affine spans
    # affinity is a no-op constraint on a single-slice fabric
    c = Scenario(
        "t", [MasterSpec("cpu", txns=8, slice_affinity=0)]).compile()
    assert c.regions[0][1] <= MemoryGeometry().beats_total


def test_region_exceeding_memory_raises_clear_error():
    """Satellite: declared regions past total_bytes fail loudly (both via
    Scenario.validate and resolve_regions directly), never wrap."""
    g = MemoryGeometry()
    bad = Scenario("t", [MasterSpec("cpu", region=(0, g.beats_total + 512))])
    with pytest.raises(ValueError, match="exceeds memory"):
        bad.validate()
    with pytest.raises(ValueError, match="exceeds memory"):
        resolve_regions(bad)                    # bypassing validate()
    with pytest.raises(ValueError, match="exceeds memory"):
        resolve_regions(Scenario(
            "t", [MasterSpec("cpu", region=(-256, 512))]))
    with pytest.raises(ValueError, match="inverted"):
        resolve_regions(Scenario(
            "t", [MasterSpec("cpu", region=(4096, 1024))]))


def test_sweep_reports_slice_stats():
    sc_l = slice_scaling(2, txns=12)
    sc_r = slice_scaling(2, txns=12, remote=True)
    prm = SimParams(geom=sc_l.geom, max_cycles=6000)
    res = run_sweep([SweepPoint(sc_l, prm), SweepPoint(sc_r, prm)])
    local, rem = res
    assert local.slices["num_slices"] == 2
    assert local.slices["crossing_fraction"] == 0.0
    assert rem.slices["crossing_fraction"] == 1.0
    assert float(rem.metrics["remote_beat_fraction"]) == 1.0
    occ = np.asarray(local.slices["slice_occupancy"])
    assert occ.shape == (2,) and abs(float(occ.sum()) - 1.0) < 1e-6
    assert "slices" in local.summary()
    # e2e percentiles exist and dominate the accept-based view (acceptance
    # can only happen at or after a transaction's earliest-issue time)
    for cls, s in local.per_class.items():
        for d in ("read", "write"):
            if not np.isnan(s[f"{d}_lat_p99"]):
                assert s[f"{d}_e2e_lat_p99"] >= s[f"{d}_lat_p99"], (cls, d)


# ---------------------------------------------------------------------------
# benchmark CLI (satellite: --list + loud unknown-job failure)
# ---------------------------------------------------------------------------

def _run_bench_cli(*argv):
    env = {"PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": "/usr/local/bin:/usr/bin:/bin"}
    return subprocess.run([sys.executable, "-m", "benchmarks.run", *argv],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=300)


def test_bench_cli_lists_jobs_and_rejects_unknown():
    res = _run_bench_cli("--list")
    assert res.returncode == 0, res.stderr
    jobs = res.stdout.split()
    assert "slice_scaling" in jobs and "fig4_throughput" in jobs
    bad = _run_bench_cli("--only", "definitely_not_a_job")
    assert bad.returncode != 0
    assert "definitely_not_a_job" in bad.stderr
    assert "slice_scaling" in bad.stderr      # the valid list is shown


def test_compile_cache_placement(monkeypatch):
    """Entry points keep JAX's compile cache where JAX_COMPILATION_CACHE_DIR
    says, else at one fixed path in the checkout; nothing else is set."""
    import jax

    from repro.compile_cache import CACHE_DIR, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert use_compile_cache() == str(CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert CACHE_DIR == REPO / ".jax_cache"
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
