"""The Pallas kernels of the training path compiled for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology and refuses what the chip would refuse (block shapes, layouts,
gathers Mosaic cannot lower), which interpret mode on the CPU never
checks. Each test compiles one kernel at the size the scanned round runs
it and asserts that the kernel is in the program (``tpu_custom_call``).

The topology is described in a module fixture, so only the worker that
runs these tests loads the TPU library; the persistent compilation cache
is off around the compiles (an entry compiled for a described chip
cannot be read back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cosine_sim import cosine_sim, merge_candidates
from repro.kernels.prox_update import prox_update_flat

D_MLP = 19210      # |θ| of SYNTH_MLP 64→256→10, the Ψ width of the MLP task
D_LM = 8192        # project_dim of the LM path


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("k,d", [(128, D_MLP), (256, D_MLP), (1024, D_MLP),
                                 (4096, D_MLP), (4096, D_LM)])
def test_merge_candidates_compiles_for_v5e(one_chip, k, d):
    x = jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=one_chip)
    live = jax.ShapeDtypeStruct((k,), jnp.bool_, sharding=one_chip)
    text = _compiled_text(lambda a, b: merge_candidates(a, b, tau=0.5),
                          x, live)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("k,d", [(4096, D_MLP), (4096, D_LM)])
def test_cosine_sim_compiles_for_v5e(one_chip, k, d):
    x = jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(cosine_sim, x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prox_update_compiles_under_cohort_vmap_for_v5e(one_chip, dtype):
    v = jax.ShapeDtypeStruct((200, D_MLP), dtype, sharding=one_chip)
    step = jax.vmap(lambda a, b, c, e: prox_update_flat(
        a, b, c, e, 0.1, 0.05, donate=False))
    assert "tpu_custom_call" in _compiled_text(step, v, v, v, v)
