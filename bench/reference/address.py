"""Beat address -> bank, written from the paper's two dispatch rules.

Rule 1 (structural): beat ``a`` of a slice goes to cluster ``a mod M`` and,
inside it, to array ``(a div M) mod N``.  Rule 2 (fractal): the array index
is offset by a hash of the higher address bits, and the bank inside the
array is the next address bits offset by a hash of the bits above those.
The hash is the xorshift-multiply avalanche the model uses, computed here in
plain Python integers masked to 32 bits.

Only a one-slice fabric is modelled; a multi-slice geometry raises.
"""
from __future__ import annotations

import numpy as np

_MASK = 0xFFFFFFFF


def avalanche(x: np.ndarray) -> np.ndarray:
    """32-bit xorshift-multiply hash of each element (uint64 arithmetic,
    masked to 32 bits after every multiply)."""
    x = np.asarray(x, np.uint64) & _MASK
    x ^= x >> np.uint64(16)
    x = (x * np.uint64(0x9E3779B1)) & _MASK
    x ^= x >> np.uint64(13)
    x = (x * np.uint64(0x85EBCA77)) & _MASK
    x ^= x >> np.uint64(16)
    return x


def bank_of_beat(addr, geom: dict) -> np.ndarray:
    """Flat bank id ``(cluster * N + array) * K + bank`` of each beat."""
    if geom["num_slices"] != 1:
        raise NotImplementedError("the reference models one slice only")
    a = np.asarray(addr, np.int64)
    m, n, k = (geom["num_clusters"], geom["arrays_per_cluster"],
               geom["banks_per_array"])
    cluster = a % m
    above = a // (m * n)
    array = ((a // m) % n + (avalanche(above) % n).astype(np.int64)) % n
    bank = (above % k
            + (avalanche(above // k + 0x5bd1) % k).astype(np.int64)) % k
    return ((cluster * n + array) * k + bank).astype(np.int64)
