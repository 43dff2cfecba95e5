"""The paper's Fig. 4 traffic, kept with the benchmark so the yardstick holds.

Each master is a full-duplex pair of ports, one read and one write, each
issuing bursts at random beat-aligned addresses with every transaction
ready at cycle 0 (full injection).  Same stream, bit for bit, as the
model's own Fig. 4 generator at the same seed (a test pins that).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

#: the streaming collector's class for ports outside any QoS class
UNCLASSIFIED = 3


def random_uniform_full_duplex(masters: int, txns: int, *, burst: int,
                               read_fraction: float, beats_total: int,
                               seed: int) -> Dict[str, np.ndarray]:
    """``[2 * masters, n]`` rows: reads on the first ``masters``, writes on
    the rest; a direction's rows end in zero bursts past its share.  Every
    port is at QoS level 0, unclassified, with no deadline."""
    rng = np.random.default_rng(seed)
    hi = beats_total - burst
    n_r = int(txns * read_fraction)
    n_w = txns - n_r
    n = max(n_r, n_w)
    rows = []
    for is_w, used in ((0, n_r), (1, n_w)):
        b = np.full((masters, n), burst, np.int32)
        a = rng.integers(0, hi, (masters, n)).astype(np.int32)
        b[:, used:] = 0
        rows.append((np.full((masters, n), is_w, np.int32), b, a))
    iw, b, a = (np.concatenate(parts) for parts in zip(*rows))
    return {"is_write": iw, "burst": b, "addr": a,
            "start": np.zeros_like(iw),
            "prio": np.zeros(2 * masters, np.int32),
            "cls": np.full(2 * masters, UNCLASSIFIED),
            "deadline": np.full(2 * masters, -1)}
