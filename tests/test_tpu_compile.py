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
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import device_clustering as dc
from repro.kernels import ops
from repro.kernels.cosine_sim import cosine_sim, merge_candidates
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.prox_update import prox_update_flat
from repro.kernels.segment_rows import segment_row_sum

D_MLP = 19210      # |θ| of SYNTH_MLP 64→256→10, the Ψ width of the MLP task
D_LM = 8192        # project_dim of the LM path


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


# DeepSeek-V2-Lite's expert FFN on one chip: 8 held experts of width 1408
# at d 2048, the row capacity of one client batch (16 x 512 tokens, top 6)
@pytest.mark.parametrize("k,n,transpose", [(2048, 1408, False),
                                           (1408, 2048, False),
                                           (1408, 2048, True),
                                           (2048, 1408, True)])
def test_grouped_matmul_compiles_for_v5e(one_chip, k, n, transpose):
    rows = jax.ShapeDtypeStruct((16 * 512 * 6, k), jnp.float32,
                                sharding=one_chip)
    w = jax.ShapeDtypeStruct((8, n, k) if transpose else (8, k, n),
                             jnp.float32, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)
    text = _compiled_text(lambda a, b, c: grouped_matmul(a, b, c, transpose),
                          rows, w, sizes)
    assert "tpu_custom_call" in text


# the cluster means' row segment sum over the Ψ bank: the MNIST MLP's at
# 256 clients, DeepSeek-V2-Lite's LoRA Ψ at 32, and a 4096-row bank
@pytest.mark.parametrize("k,d", [(256, 1_628_170), (32, 2_631_680),
                                 (4096, D_MLP), (8192, D_MLP),
                                 (45056, D_MLP)])
def test_segment_rows_compiles_for_v5e(one_chip, k, d):
    x = jax.ShapeDtypeStruct((k, d), jnp.float32, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((k,), jnp.int32, sharding=one_chip)
    assert "tpu_custom_call" in _compiled_text(segment_row_sum, x, seg)


def test_segment_rows_compiles_under_shard_map_for_v5e(topo, monkeypatch):
    """``ops.segment_rows`` on the client mesh: the kernel runs on every
    chip over the replicated bank. ``ops`` asks the default backend,
    which is the CPU here, so the test steers it to the TPU branch."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(4), ("clients",))
    rep = NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((256, D_MLP), jnp.float32, sharding=rep)
    seg = jax.ShapeDtypeStruct((256,), jnp.int32, sharding=rep)
    live = jax.ShapeDtypeStruct((256,), jnp.bool_, sharding=rep)
    text = _compiled_text(
        lambda a, b, c: ops.segment_rows(a, b, c, mesh=mesh), x, seg, live)
    assert "tpu_custom_call" in text


# the eager device backend's jitted programs over a state that a span on
# the client mesh handed back, its Ψ bank replicated on every chip
_ON_A_MESH = {
    "nearest": lambda m: dc._jit_nearest(m),
    "objective_closed": lambda m: dc._jit_objective_closed(m),
    "objective": lambda m: dc._jit_objective(256, m),
    "merge_round": lambda m: dc._jit_merge_round(0.5, 256, m),
    "cluster_means": lambda m: dc._jit_cluster_means(m),
}


@pytest.mark.parametrize("program", sorted(_ON_A_MESH))
def test_cluster_programs_compile_on_a_mesh_for_v5e(topo, monkeypatch,
                                                    program):
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = jax.sharding.Mesh(np.array(topo.devices).reshape(4), ("clients",))
    rep = NamedSharding(mesh, P())
    state = dc.DeviceClusterState(
        parent=jax.ShapeDtypeStruct((256,), jnp.int32, sharding=rep),
        live=jax.ShapeDtypeStruct((256,), jnp.bool_, sharding=rep),
        rep=jax.ShapeDtypeStruct((256, D_MLP), jnp.float32, sharding=rep))
    assert dc.state_mesh(state) is mesh
    fn = _ON_A_MESH[program](dc.state_mesh(state))
    args = (state,)
    if program == "nearest":
        args += (jax.ShapeDtypeStruct((D_MLP,), jnp.float32, sharding=rep),)
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
