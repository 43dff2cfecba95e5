"""Backend dispatcher for the per-bank QoS arbitration comparator tree.

``bank_arbiter_winners`` is the single entry the simulator's arbitration
stage calls each cycle.  ``backend="jax"`` (the default) runs the two-pass
``segment_min`` reference; ``backend="pallas"`` runs the Pallas comparator
tree, compiled on TPU.  The Pallas interpreter is the CPU's way to run the
same kernel (tests, CPU rehearsals) and is used there only; any other
backend raises rather than silently interpreting.  Bit-exact either way.
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
