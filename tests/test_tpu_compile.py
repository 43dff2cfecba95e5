"""Compile the main path's kernel and cores for a described TPU v5e.

Nothing runs here: JAX compiles for a ``v5e:2x2`` topology that is described,
not attached, so whatever the chip's compiler refuses (a block shape off the
(8, 128) tiling, a kernel that cannot lower) fails on the CPU.  A compile
that passes is not a chip run.  The topology is described inside a fixture,
never at import, and every test of this kind stays in this one file.
"""
import os
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import simulator
from repro.core.simulator import (DEFAULT_PIPELINE, SCHEDULE_PIPELINE,
                                  SimParams)
from repro.kernels.bank_arbiter import ops
from repro.kernels.bank_arbiter.kernel import bank_arbiter
from repro.scenarios import urban_perception

#: the prototype's beat slots: 16 ports x 256 ring slots
PROTO_SLOTS = 16 * 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            described = topologies.get_topology_desc(platform="tpu",
                                                     topology_name="v5e:2x2")
        except Exception as e:
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield described
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("batch", [None, 64])
@pytest.mark.parametrize("num_banks", [256, 1024])
def test_bank_arbiter_compiles_for_v5e(one_chip, num_banks, batch):
    shape = (PROTO_SLOTS,) if batch is None else (batch, PROTO_SLOTS)
    arg = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    fn = partial(bank_arbiter, num_banks=num_banks, num_slots=PROTO_SLOTS,
                 interpret=False)
    if batch is not None:
        fn = jax.vmap(fn)
    compiled = jax.jit(fn).lower(arg, arg).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_schedule_core_compiles_with_pallas_arbiter(one_chip, monkeypatch):
    """The whole schedule core at the prototype geometry, with the Pallas
    arbiter compiled rather than interpreted (this CPU process would
    otherwise pick the interpreter while tracing)."""
    monkeypatch.setattr(ops, "pallas_interpret", lambda: False)
    sched = urban_perception(txns=256).compile().schedule()
    prm = simulator._static_prm(SimParams(
        max_cycles=20_000, stages=SCHEDULE_PIPELINE, arbiter="pallas"))
    host = simulator._host_input(sched, prm) + (prm.dyn_vector(),)
    args = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                 sharding=one_chip) for a in host]
    core = jax.jit(partial(simulator._core_sched, prm=prm))
    compiled = core.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


@pytest.fixture(scope="module")
def fig4_dense_text(one_chip):
    """The dense core's compiled text at the Fig. 4 shapes (32 ports x 500
    transactions, ``max_burst`` 16, 32 x 256 = 8,192 ring slots)."""
    rng = np.random.default_rng(0)
    X, N = 32, 500
    trace = simulator.Trace(
        is_write=np.repeat([[0], [1]], X // 2, axis=0) * np.ones((1, N), int),
        burst=np.full((X, N), 16), addr=rng.integers(0, 1 << 19, (X, N)),
        prio=np.zeros(X, int))
    prm = simulator._static_prm(SimParams(max_cycles=22_800))
    dev = simulator._device_input(prm, simulator._host_input(trace, prm),
                                  prm.dyn_vector())
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in dev]
    core = jax.jit(partial(simulator._core, prm=prm))
    return core.lower(*args).compile().as_text()


def test_dense_core_fusions_name_every_stage_for_v5e(fig4_dense_text):
    """The dense core at the Fig. 4 shapes: each stage of the default
    pipeline owns at least one fusion by its own ``op_name``, so a chip
    trace can put the device time of each stage down to it."""
    owners = set()
    for line in fig4_dense_text.splitlines():
        own = re.search(r'op_name="([^"]*)"', line)
        if " fusion(" in line and own:
            owners.update(c for c in own.group(1).split("/")
                          if c.startswith("stage."))
    assert {f"stage.{name}" for name in DEFAULT_PIPELINE} <= owners


@pytest.fixture(scope="module")
def sched_core(one_chip):
    """The schedule core's compiled text at the prototype geometry, its
    port count and its ring slots (``urban_perception`` at 256
    transactions a master)."""
    sched = urban_perception(txns=256).compile().schedule()
    prm = simulator._static_prm(SimParams(max_cycles=20_000,
                                          stages=SCHEDULE_PIPELINE))
    host = simulator._host_input(sched, prm) + (prm.dyn_vector(),)
    args = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                 sharding=one_chip) for a in host]
    core_fn = jax.jit(partial(simulator._core_sched, prm=prm))
    X = len(sched.burst)
    return (core_fn.lower(*args).compile().as_text(), X,
            X * prm.slots_per_master)


def _stage_lookups(text, stage):
    """(op, result elements) of each gather and scatter that the compiled
    text puts under ``stage.<stage>``.  A gather of one element per index
    has as many result elements as indices."""
    found = []
    for line in text.splitlines():
        m = re.search(r"= \w+\[([\d,]*)\]\S* (gather|scatter)\(", line)
        if m and re.search(rf"stage\.{stage}[/\"]", line):
            dims = [int(n) for n in m.group(1).split(",") if n]
            found.append((m.group(2), int(np.prod(dims))))
    return found


@pytest.mark.parametrize("core", ["dense", "schedule"])
def test_bank_arbitrate_has_no_slot_lookups_for_v5e(core, fig4_dense_text,
                                                    sched_core):
    """On the chip the arbitration stage reaches the slots through dense
    bank masks: no scatter, and no gather with an index per ring slot —
    only the [NB]-index lookups of the winners' write, hops and txn."""
    NB = SimParams().geom.num_banks
    if core == "dense":
        text, slots = fig4_dense_text, 32 * 256
    else:
        text, _, slots = sched_core
    found = _stage_lookups(text, "bank_arbitrate")
    assert found, "the stage's [NB]-index gathers are missing"
    assert all(op == "gather" and n <= NB < slots for op, n in found), found


@pytest.mark.parametrize("core", ["dense", "schedule"])
def test_accept_dispatch_has_no_slot_lookups_for_v5e(core, fig4_dense_text,
                                                     sched_core):
    """On the chip acceptance + dispatch reach the ring slots through a
    dense one-hot over each port's burst row: no scatter, and no gather
    with an index per ring slot — only [X]-index lookups of the ports'
    candidate commands and, in the dense core, their [max_burst] rows."""
    mb = SimParams().max_burst
    if core == "dense":
        (text, X, slots), stage = (fig4_dense_text, 32, 32 * 256), \
            "accept_dispatch"
    else:
        (text, X, slots), stage = sched_core, "accept_dispatch_sched"
    found = _stage_lookups(text, stage)
    assert found, "the stage's [X]-index lookups are missing"
    assert all(op == "gather" and n <= X * mb < slots
               for op, n in found), found


def test_vmapped_bank_arbitrate_fuses_its_masks_for_v5e(one_chip):
    """The stage vmapped to 64 points at NB = 1024 (four slices) and 8,192
    ring slots: each [X, NB, P] mask is fused into the reductions that read
    it.  One such intermediate for the batch would take 512 MiB as pred
    and 2 GiB as int32; the bound is a sixteenth of the smaller."""
    from repro.core.address import MemoryGeometry
    B, X, N = 64, 32, 8
    rng = np.random.default_rng(0)
    prm = simulator._static_prm(SimParams(
        max_cycles=22_800, geom=MemoryGeometry(num_slices=4)))
    NB, S = prm.geom.num_banks, X * prm.slots_per_master
    assert (NB, S) == (1024, 8192)
    trace = simulator.Trace(is_write=rng.integers(0, 2, (X, N)),
                            burst=np.full((X, N), 16),
                            addr=rng.integers(0, 1 << 19, (X, N)))
    dev = simulator._device_input(prm, simulator._host_input(trace, prm),
                                  prm.dyn_vector())

    def one_point(st, *point):
        _, ctx = simulator._setup(point, prm)
        return simulator._stage_bank_arbitrate(st, {}, ctx)

    state = jax.eval_shape(
        lambda *p: simulator._setup(p, prm)[0], *dev)
    batched = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        (B,) + a.shape, a.dtype, sharding=one_chip)
    args = [jax.tree_util.tree_map(batched, state)] + [batched(a) for a in dev]
    compiled = jax.jit(jax.vmap(one_point)).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= B * S * NB // 16
