"""The benchmark's copy of the Fig. 4 generator makes the model's stream."""
import numpy as np

from bench.fig4_traffic import random_uniform_full_duplex


def test_copy_equals_model_generator():
    from repro.core.address import MemoryGeometry
    from repro.core.traffic import random_uniform
    geom = MemoryGeometry()
    want = random_uniform(16, 1000, burst=16, seed=1234, geom=geom)
    got = random_uniform_full_duplex(16, 1000, burst=16, read_fraction=0.5,
                                     beats_total=geom.beats_total, seed=1234)
    for k in ("is_write", "burst", "addr"):
        assert np.array_equal(getattr(want, k), got[k]), k
    assert np.array_equal(want.start_or_zeros(), got["start"])
    assert np.array_equal(want.prio_or_zeros(), got["prio"])
