"""Ahead-of-time compiles of the surrogate kernel for a described TPU v5e.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not attached, so these tests catch what the chip's compiler
would refuse (and programs that outgrow its memory) without a chip.  The
topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library, and every test worker imports
this file."""
import pytest

from repro.simcluster import surrogate as sg

#: device temp memory one compiled sub-batch may claim (a v5e holds 16 GB)
_TEMP_LIMIT = 1 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _packed_shapes(n_jobs, batch, sharding):
    """``pack_cell``'s output as shapes: per-job rows and cell scalars,
    with a leading cell axis when batched."""
    import jax
    import jax.numpy as jnp
    lead = () if batch is None else (batch,)
    shapes = {k: jax.ShapeDtypeStruct(lead + (n_jobs,), jnp.float32,
                                      sharding=sharding)
              for k in sg._JOB_FIELDS}
    shapes.update({k: jax.ShapeDtypeStruct(lead, jnp.float32,
                                           sharding=sharding)
                   for k in sg._SCALAR_FIELDS})
    return shapes


@pytest.mark.parametrize("n_jobs,n_steps,batch", [
    (128, 2048, 64),      # atlas-scale bucket at the default sub-batch
    (1024, 4096, None),   # the fleet bucket, unbatched (run_cell)
    (1024, 4096, 4),      # the fleet bucket, vmapped (run_batch)
])
def test_surrogate_kernel_compiles_for_v5e(one_chip, no_persistent_cache,
                                           n_jobs, n_steps, batch):
    fn = sg._compiled(n_jobs, n_steps, batched=batch is not None)
    compiled = fn.lower(_packed_shapes(n_jobs, batch, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < _TEMP_LIMIT, mem


def test_v5e_executable_names_the_ring_stages(one_chip, no_persistent_cache):
    """The chip's compiler keeps the stage scopes: the operations of the
    v5e executable map to the kernel's ring stages."""
    fn = sg._compiled(128, sg.CHUNK, batched=True)
    compiled = fn.lower(_packed_shapes(128, 4, one_chip)).compile()
    found = set(sg.hlo_stages(compiled.as_text()).values())
    assert {"ring_drain", "ring_scatter", "map_alloc"} <= found


def test_v5e_executable_has_no_priority_gathers(one_chip, no_persistent_cache):
    """Jobs come packed in priority order, so strict-priority allocation
    is a cumsum on the rows as they lie: no instruction of the v5e
    executable gathers through ``jnp.take``."""
    fn = sg._compiled(128, sg.CHUNK, batched=True)
    text = fn.lower(_packed_shapes(128, 4, one_chip)).compile().as_text()
    op_names = sg._OP_NAME.findall(text)
    assert any("/map_alloc/" in name for name in op_names)
    assert not [name for name in op_names if "jit(_take)/gather" in name]
