"""Pallas TPU per-bank QoS arbitration: the §II-C comparator tree on-chip.

One simulated cycle's arbitration is, per bank, a reduction over every beat
slot: *the eligible slot with the smallest (QoS level, FCFS age, round-robin)
key wins, lowest slot index breaking ties* — pure integer comparator work
with no data movement, exactly the "keep the hot dataflow on-chip" shape the
dataflow-accelerator literature argues for.  The kernel evaluates it as a
dense comparator tree on the VPU:

  * the grid tiles banks ``BANK_BLOCK`` at a time (one lane-tile of the
    ``[1, NB]`` output row each);
  * slots arrive as a ``[S/LANES, LANES]`` layout held entirely in VMEM —
    per grid step a ``fori_loop`` walks the slot rows, comparing each
    ``[1, LANES]`` row against the step's ``[BANK_BLOCK, 1]`` bank ids and
    folding a running (best key, best slot) pair per bank;
  * ineligible slots are encoded by the *caller* as ``bank = num_banks_pad``
    (matching no bank row) so the kernel needs no separate mask operand.

Ties fold correctly because slot ids increase monotonically across rows:
within a row the masked ``min`` picks the lowest lane, across rows an equal
key never replaces the earlier (lower-id) winner.

The kernel is bit-exact against ``ref.bank_arbiter_ref`` (hypothesis-tested
grant-for-grant).  It compiles for TPU; on CPU it runs under
``interpret=True`` (``ops.pallas_interpret``) with identical results.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bank_arbiter.ref import KEY_FILLER

LANES = 128        # TPU lane width: slots per VMEM row
BANK_BLOCK = 128   # banks resolved per grid step

#: slot filler — far above any real flat slot index (ring sizes are 2**k)
SLOT_FILLER = 2**30


def _arbiter_kernel(key_ref, bank_ref, win_ref):
    nrows = key_ref.shape[0]
    bank0 = pl.program_id(0) * BANK_BLOCK
    bank_ids = bank0 + jax.lax.broadcasted_iota(
        jnp.int32, (BANK_BLOCK, 1), 0)                       # [BB, 1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def fold_row(i, carry):
        best_key, best_slot = carry                          # [BB, 1] each
        krow = key_ref[i, :][None, :]                        # [1, LANES]
        brow = bank_ref[i, :][None, :]
        srow = i * LANES + lane                              # flat slot ids
        hit = brow == bank_ids                               # [BB, LANES]
        mk = jnp.where(hit, krow, KEY_FILLER)
        row_key = jnp.min(mk, axis=1, keepdims=True)         # [BB, 1]
        ms = jnp.where(hit & (krow == row_key), srow, SLOT_FILLER)
        row_slot = jnp.min(ms, axis=1, keepdims=True)
        tie = row_key == best_key
        best_slot = jnp.where(row_key < best_key, row_slot,
                              jnp.where(tie, jnp.minimum(best_slot, row_slot),
                                        best_slot))
        best_key = jnp.minimum(best_key, row_key)
        return best_key, best_slot

    init = (jnp.full((BANK_BLOCK, 1), KEY_FILLER, jnp.int32),
            jnp.full((BANK_BLOCK, 1), SLOT_FILLER, jnp.int32))
    _, best_slot = jax.lax.fori_loop(0, nrows, fold_row, init)
    win_ref[...] = best_slot.reshape(1, BANK_BLOCK)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


@functools.partial(jax.jit,
                   static_argnames=("num_banks", "num_slots", "interpret"))
def bank_arbiter(key, bank, *, num_banks: int, num_slots: int,
                 interpret: bool = False):
    """key/bank: [S] int32 — ineligible slots MUST carry ``bank >= num_banks``
    (use ``ops.bank_arbiter_winners`` for the masked convenience wrapper).

    Returns win_slot [num_banks] int32; ``num_slots`` ⇒ no eligible slot.
    """
    S = key.shape[-1]
    Sp = _round_up(max(S, 1), LANES)
    NBp = _round_up(max(num_banks, 1), BANK_BLOCK)
    pad = [(0, Sp - S)]
    key2d = jnp.pad(key.astype(jnp.int32), pad,
                    constant_values=KEY_FILLER).reshape(-1, LANES)
    bank2d = jnp.pad(bank.astype(jnp.int32), pad,
                     constant_values=NBp).reshape(-1, LANES)
    nrows = Sp // LANES

    win = pl.pallas_call(
        _arbiter_kernel,
        grid=(NBp // BANK_BLOCK,),
        in_specs=[pl.BlockSpec((nrows, LANES), lambda i: (0, 0)),
                  pl.BlockSpec((nrows, LANES), lambda i: (0, 0))],
        # one [1, NBp] row, tiled BANK_BLOCK lanes per grid step: a block
        # row of 1 is legal on TPU only because it spans the whole dimension
        out_specs=pl.BlockSpec((1, BANK_BLOCK), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, NBp), jnp.int32),
        interpret=interpret,
    )(key2d, bank2d)
    # banks with no eligible slot report num_slots, matching the reference
    return jnp.minimum(win.reshape(-1)[:num_banks], num_slots)
