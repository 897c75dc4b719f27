"""Ahead-of-time compiles of the main path's device programs for a TPU v5e.

The TPU compiler is installed with JAX and compiles for a described chip
that is not attached, so these tests refuse, with no chip, what the chip's
compiler would refuse: a block that breaks the (8, 128) tiling rule, a
primitive Mosaic cannot lower, more scoped VMEM than a kernel may use.
Shapes are the real ones: the proximity kernel at K=16,384 FEMNIST-shaped
signatures (n=784, p=5) and the serving dispatch at a B=256 query bucket
against C=1,024 representatives.  A compile that passes is not a run.

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.proximity.proximity import BLOCK, _proximity_pallas_jit
from repro.serving.dispatch import _assign_scores

K, N, P = 16384, 784, 5
B_QUERIES, C_REPS = 256, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler, or its lock is held
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("measure", ["eq3", "eq2"])
def test_proximity_kernel_compiles_at_real_width(one_chip, measure):
    U = jax.ShapeDtypeStruct((K, N, P), jnp.float32, sharding=one_chip)
    compiled = _proximity_pallas_jit.lower(
        U, measure=measure, bk=BLOCK, interpret=False
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == K * K * 4


@pytest.mark.parametrize("measure", ["eq3", "eq2"])
def test_assign_scores_compiles_at_serving_bucket(one_chip, measure):
    Uq = jax.ShapeDtypeStruct((B_QUERIES, N, P), jnp.float32, sharding=one_chip)
    R = jax.ShapeDtypeStruct((C_REPS, N, P), jnp.float32, sharding=one_chip)
    c_real = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = _assign_scores.lower(Uq, R, c_real, measure, "jacobi").compile()
    idx, dmin = compiled.out_info
    assert idx.shape == dmin.shape == (B_QUERIES,)
