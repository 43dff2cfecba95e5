"""Pure-jnp oracle for the per-bank QoS arbitration comparator tree.

The contract shared with the Pallas kernel (``kernel.py``):

  given per-slot arbitration keys (``core.qos.arbitration_priority_key``
  packing: smaller wins), per-slot target banks, and an eligibility mask,
  return ``win_slot[NB]`` — the flat index of the winning slot per bank:
  the *eligible* slot with the minimum key, ties broken by the lowest slot
  index; ``num_slots`` when the bank has no eligible slot.

This is exactly the two-pass ``segment_min`` the pre-refactor arbitration
stage inlined.  It is the tests' oracle and the ``"jax"`` backend of the
flat ``ops.bank_arbiter_winners``, which only the arbitration stage's CPU
lowering calls: on the chip the stage resolves a dense bid matrix
(``ops.dense_bank_winners``) with no scatter.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: key value for ineligible slots — above every real arbitration key
#: (``core.simulator._age_cap`` budgets keys strictly below 2**30)
KEY_FILLER = 2**30


def bank_arbiter_ref(key, bank, elig, *, num_banks: int):
    """key/bank/elig: [S] (int32/int-like/bool). Returns win_slot [NB] int32."""
    S = key.shape[-1]
    slot_ids = jnp.arange(S, dtype=jnp.int32)
    seg = jnp.where(elig, bank, num_banks)
    best = jax.ops.segment_min(jnp.where(elig, key, KEY_FILLER), seg,
                               num_segments=num_banks + 1)[:-1]
    is_best = elig & (key == best[bank])
    win = jax.ops.segment_min(jnp.where(is_best, slot_ids, S),
                              jnp.where(is_best, bank, num_banks),
                              num_segments=num_banks + 1)[:-1]
    # an empty segment (no eligible slot) yields int32-max; normalize to S so
    # both backends share one "no winner" sentinel
    return jnp.minimum(win, S).astype(jnp.int32)
