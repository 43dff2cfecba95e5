"""Backend dispatcher for the per-bank QoS arbitration comparator tree.

``bid_winners`` is the entry the simulator's arbitration stage calls each
cycle, on a dense *bid matrix*: ``bid[x, b, p]`` is the arbitration key of
port ``x``'s ring slot ``p`` for bank ``b``, ``KEY_FILLER`` or above where
the slot does not bid for that bank (each slot bids for at most its own
bank).  ``backend="jax"`` (the default) resolves it with one (bid, slot)
min-reduction over the slot axes — no gather or scatter;
``backend="pallas"`` folds each slot's bids back to its one key and runs
the Pallas comparator tree, compiled on TPU.  ``bank_arbiter_winners``
takes flat per-slot inputs instead: ``"jax"`` runs the two-pass
``segment_min`` of ``ref.py`` there (the stage's CPU lowering), and
``"pallas"`` the same kernel.  The Pallas interpreter is the CPU's way to
run the kernel (tests, CPU rehearsals) and is used there only; any other
backend raises rather than silently interpreting.  Bit-exact in every
combination.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bank_arbiter.kernel import bank_arbiter
from repro.kernels.bank_arbiter.ref import KEY_FILLER, bank_arbiter_ref

BACKENDS = ("jax", "pallas")


def pallas_interpret() -> bool:
    """Whether the Pallas kernel runs in the interpreter: on CPU yes, on TPU
    no (compiled, or the lowering raises).  Read at trace time."""
    backend = jax.default_backend()
    if backend in ("cpu", "tpu"):
        return backend == "cpu"
    raise NotImplementedError(
        f"the Pallas bank arbiter compiles for TPU and is interpreted on CPU; "
        f"backend {backend!r} is neither (use arbiter='jax')")


def _lower_bid(a, b):
    """Lexicographic min of (bid, slot) pairs: the reduction's comparator."""
    (ak, ai), (bk, bi) = a, b
    take_b = (bk < ak) | ((bk == ak) & (bi < ai))
    return jnp.where(take_b, bk, ak), jnp.where(take_b, bi, ai)


def dense_bank_winners(bid):
    """Winning slot per bank of a bid matrix: bid [X, NB, P] int32 ->
    win [NB] int32, the lowest flat slot id ``x * P + p`` holding the
    bank's minimum bid (``X * P`` where no slot bids).  One (bid, slot)
    min-reduction over the port and ring-slot axes, which XLA fuses with
    the compare and select that build the bids.  The ring slots stay the
    minor axis: each bank's row then folds across ports lane-wise before
    one cross-lane step, and no reshape merges the slot axes (under vmap
    that would materialise the whole matrix)."""
    X, _, P = bid.shape
    slot = (jax.lax.broadcasted_iota(jnp.int32, bid.shape, 0) * P
            + jax.lax.broadcasted_iota(jnp.int32, bid.shape, 2))
    best, win = jax.lax.reduce((bid, slot), (jnp.int32(KEY_FILLER),
                                             jnp.int32(X * P)),
                               _lower_bid, (0, 2))
    return jnp.where(best < KEY_FILLER, win, X * P)


def bid_winners(bid, bank, *, backend: str = "jax"):
    """Winning slot per bank: bid [X, NB, P] (see the module docstring),
    bank [X, P] each slot's own bank -> win_slot [NB] int32 (``X * P``
    where a bank has no bidder).  Trace-safe: callable from inside
    jit/vmap/scan."""
    if backend == "jax":
        return dense_bank_winners(bid)
    key = jnp.min(bid, axis=1).reshape(-1)  # the slot's one bid, or filler
    return bank_arbiter_winners(key, bank.reshape(-1), key < KEY_FILLER,
                                num_banks=bid.shape[1], backend=backend)


def bank_arbiter_winners(key, bank, elig, *, num_banks: int,
                         backend: str = "jax"):
    """Winning slot per bank: key/bank/elig [S] -> win_slot [num_banks] int32
    (``S`` where a bank has no eligible slot).  Trace-safe: callable from
    inside jit/vmap/scan."""
    if backend == "jax":
        return bank_arbiter_ref(key, bank, elig, num_banks=num_banks)
    if backend != "pallas":
        raise ValueError(
            f"unknown bank-arbiter backend {backend!r}; pick from {BACKENDS}")
    S = key.shape[-1]
    # encode ineligibility as an out-of-range bank so the kernel is maskless
    masked_bank = jnp.where(elig, bank.astype(jnp.int32), num_banks)
    masked_key = jnp.where(elig, key.astype(jnp.int32), KEY_FILLER)
    return bank_arbiter(masked_key, masked_bank, num_banks=num_banks,
                        num_slots=S, interpret=pallas_interpret())
