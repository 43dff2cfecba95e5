"""The trace reduction: interval arithmetic on hand-made intervals, and the
whole reduction on a small trace recorded on a TPU v5e
(``record_trace.py``: two ``bench.call`` spans around a jitted loop)."""
from pathlib import Path

import numpy as np
import pytest

from bench.trace_reduce import Reduced, covered, leaf_seconds, merge, reduce

DATA = Path(__file__).with_name("data") / "small_tpu.xplane.pb"


def test_merge_and_cover():
    u = merge(np.array([5, 0, 2, 20]), np.array([9, 3, 4, 30]))
    assert u.tolist() == [[0, 4], [5, 9], [20, 30]]
    assert covered(u, 3, 25) == 1 + 4 + 5
    assert covered(merge(np.array([]), np.array([])), 0, 10) == 0


def test_leaf_seconds_counts_enclosed_work_once():
    # a loop [0, 100) enclosing two body ops and one op after it
    starts = np.array([0, 10, 40, 100])
    ends = np.array([100, 30, 70, 110])
    labels = ["%while", "%fusion.1", "%fusion.2", "%copy"]
    got = leaf_seconds(starts, ends, np.arange(4), labels)
    assert got == {"%fusion.1": 20e-9, "%fusion.2": 30e-9, "%copy": 10e-9}


def test_gaps_breakdown_and_busy():
    busy = [merge(np.array([10, 50]), np.array([40, 90]))]
    r = Reduced((0, 100), busy, {"bench.call": [(0, 100)]},
                {"%f": 60e-9}, [(0, 100, "bench.call"), (40, 50, "$x.py:1 f")])
    assert r.busy_s == 70e-9 and r.window_s == 100e-9
    assert r.gaps() == [(0, 10), (40, 50), (90, 100)]
    b = r.breakdown()
    assert b["device_ops"] == [["%f", 60e-9]]
    assert [g[0] for g in b["idle_gaps"]] == ["bench.call", "$x.py:1 f",
                                              "bench.call"]
    assert sum(g[1] for g in b["idle_gaps"]) + r.busy_s == pytest.approx(
        r.window_s)


def test_recorded_tpu_trace():
    import jax
    r = reduce(jax.profiler.ProfileData.from_file(str(DATA)))
    assert len(r.busy) == 1
    assert len(r.host_spans["bench.call"]) == 2
    assert 0 < r.busy_s < r.window_s
    for t0, t1 in r.host_spans["bench.call"]:
        assert 0 < r.busy_within(t0, t1) <= t1 - t0
    gaps = sum(e - s for s, e in r.gaps()) / 1e9
    assert gaps + r.busy_s == pytest.approx(r.window_s, rel=1e-9)
    # leaf ops never exceed the busy time they are part of
    assert 0 < sum(r.op_seconds.values()) <= r.busy_s * (1 + 1e-9)
    assert r.window[0] <= r.busy[0][0, 0] and r.busy[0][-1, 1] <= r.window[1]
    assert r.breakdown()["idle_gaps"]
