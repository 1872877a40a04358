"""The engine's Pallas dataplane kernel compiles for a TPU v5e at the
paper's fabric widths.  No chip is needed: the TPU compiler compiles for a
described (not attached) v5e, which catches what interpret mode cannot —
Mosaic's layout, dot-shape and fast-memory checks.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest worker imports this
file."""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import linkload as ll
from repro.netsim import topology


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A described-chip executable is written to the persistent cache but
    cannot be read back without the chip: keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (fabric, sub-flows per flow): Fig. 12's sim_2tier and Fig. 14's 320-host
# three_tier, each with SeqBalance's N = 4 and the single-path schemes'
# N = 1 (the co-sim's steered ECMP), with the topology's link-id bands as
# the engines pass them.  ``batch`` None is the B = 1 program; 2 is the
# vmapped program the sweep runs on an accelerator for B > 1 (and, per
# chip, under its pmap), which adds a grid axis through the Pallas
# batching rule
@pytest.mark.parametrize("batch", [None, 2])
@pytest.mark.parametrize("fabric,n_sub", [("sim_2tier", 4), ("sim_2tier", 1),
                                          ("three_tier", 4), ("three_tier", 1)])
def test_tiered_kernel_compiles_for_v5e(fabric, n_sub, batch, one_chip,
                                        no_persistent_cache):
    topo_ = getattr(topology, fabric)()
    L, hf, n = topo_.n_links, topo_.n_fabric_hops, 2048
    lead = () if batch is None else (batch,)

    def spec(shape, dtype, batched=True):
        return jax.ShapeDtypeStruct((lead if batched else ()) + shape, dtype,
                                    sharding=one_chip)

    args = (spec((n, n_sub, hf), jnp.int32), spec((n,), jnp.int32),
            spec((n,), jnp.int32), spec((n, n_sub), jnp.float32),
            spec((L,), jnp.float32), spec((L,), jnp.float32, False),
            spec((L,), jnp.float32, False))
    kernel = functools.partial(ll.linkload_cascade_tiered, n_links=L,
                               bands=topo_.bands)
    if batch is not None:  # capacity and queue mask are shared, as in sweep
        kernel = jax.vmap(kernel, in_axes=(0, 0, 0, 0, 0, None, None))
    compiled = jax.jit(kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
