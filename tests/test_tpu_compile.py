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
    host = simulator._host_args(sched, prm, True) + (prm.dyn_vector(),)
    args = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                 sharding=one_chip) for a in host]
    core = jax.jit(partial(simulator._core_sched, prm=prm))
    compiled = core.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


def test_dense_core_fusions_name_every_stage_for_v5e(one_chip):
    """The dense core at the Fig. 4 shapes (32 ports x 500 transactions,
    ``max_burst`` 16): each stage of the default pipeline owns at least one
    fusion by its own ``op_name``, so a chip trace can put the device time
    of each stage down to it."""
    rng = np.random.default_rng(0)
    X, N = 32, 500
    trace = simulator.Trace(
        is_write=np.repeat([[0], [1]], X // 2, axis=0) * np.ones((1, N), int),
        burst=np.full((X, N), 16), addr=rng.integers(0, 1 << 19, (X, N)),
        prio=np.zeros(X, int))
    prm = simulator._static_prm(SimParams(max_cycles=22_800))
    dev = simulator._device_args(prm, *simulator._host_args(trace, prm, False),
                                 prm.dyn_vector())
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in dev]
    core = jax.jit(partial(simulator._core, prm=prm))
    text = core.lower(*args).compile().as_text()
    owners = set()
    for line in text.splitlines():
        own = re.search(r'op_name="([^"]*)"', line)
        if " fusion(" in line and own:
            owners.update(c for c in own.group(1).split("/")
                          if c.startswith("stage."))
    assert {f"stage.{name}" for name in DEFAULT_PIPELINE} <= owners
