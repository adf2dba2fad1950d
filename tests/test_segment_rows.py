"""The row segment-sum kernel (``kernels.segment_rows``) and its wrapper.

The kernel adds rows in ascending order with plain f32 adds, as a serial
scatter does, so in interpret mode its sums are BITWISE those of
``jax.ops.segment_sum``; the cluster means built on it are bitwise the
oracle's on a churned partition; and the wrapper sends only the K its
VMEM blocks hold to the kernel.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import device_clustering as dc
from repro.kernels import ops, ref
from repro.kernels import segment_rows as sr


def _segments(pattern: str, k: int, rng):
    """(seg, live) of one pattern; dead rows carry seg == k."""
    ids = np.arange(k)
    if pattern == "singletons":
        seg, live = ids, np.ones(k, bool)
    elif pattern == "one_cluster":
        seg, live = np.zeros(k, np.int64), np.ones(k, bool)
    elif pattern == "dead_rows":
        seg = np.minimum(ids, rng.integers(0, max(k // 4, 1), k))
        live = rng.random(k) < 0.6
        live[-1] = k == 1          # at least one dead row; K = 1: all dead
    else:                          # empty_segment: no row adds into 1
        seg = rng.integers(0, k, k)
        seg[seg == 1] = 0
        live = np.ones(k, bool)
    seg = np.where(live, seg, k).astype(np.int32)
    return jnp.asarray(seg), jnp.asarray(live)


def _rows(k: int, d: int, live, rng):
    """Random rows; dead rows hold NaN, which no kept sum may see."""
    x = rng.standard_normal((k, d)).astype(np.float32)
    x[~np.asarray(live)] = np.nan
    return jnp.asarray(x)


@pytest.mark.parametrize("d", [128, 1000, 19210])
@pytest.mark.parametrize("k", [1, 2, 32, 256])
@pytest.mark.parametrize("pattern", ["singletons", "one_cluster",
                                     "dead_rows", "empty_segment"])
def test_kernel_is_bitwise_segment_sum(pattern, k, d):
    rng = np.random.default_rng(k * 7919 + d)
    seg, live = _segments(pattern, k, rng)
    x = _rows(k, d, live, rng)
    got = np.asarray(sr.segment_row_sum(x, seg, interpret=True))
    want = np.asarray(jax.ops.segment_sum(
        jnp.where(live[:, None], x, 0.0), seg, num_segments=k + 1)[:k])
    assert np.array_equal(got, want)
    assert np.array_equal(got, np.asarray(ref.segment_rows_ref(x, seg, live)))
    if pattern == "empty_segment" and k > 1:
        assert not got[1].any()


def _churned_state(cap: int, dim: int, seed: int):
    """A device partition after observe, merges and departures."""
    rng = np.random.default_rng(seed)
    anchors = rng.standard_normal((4, dim)).astype(np.float32)
    n = cap - cap // 8
    reps = anchors[rng.integers(0, 4, n)] + 0.05 * rng.standard_normal(
        (n, dim)).astype(np.float32)
    state = dc.init_state(cap, dim)
    state = dc.observe(state, jnp.arange(n, dtype=jnp.int32), jnp.asarray(reps))
    state = dc.merge_round(state, 0.9)[0]
    for cid in rng.choice(n, size=n // 5, replace=False):
        state = dc._jit_remove()(state, int(cid))[0]
    return state


@pytest.fixture
def kernel_on_cpu(monkeypatch):
    """Steer ``ops.segment_rows`` to the kernel, run in interpret mode."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    monkeypatch.setattr(sr, "segment_row_sum", functools.partial(
        sr.segment_row_sum, interpret=True))


@pytest.mark.parametrize("cap,dim,seed,mesh", [(16, 128, 0, False),
                                               (64, 1000, 1, False),
                                               (256, 300, 2, False),
                                               (64, 1000, 3, True)])
def test_cluster_means_kernel_matches_oracle(request, cap, dim, seed, mesh):
    state = _churned_state(cap, dim, seed)
    assert 0 < int(state.live.sum()) < cap
    want = jax.jit(dc._cluster_means)(state)
    request.getfixturevalue("kernel_on_cpu")
    m = (jax.sharding.Mesh(np.array(jax.devices()[:1]), ("clients",))
         if mesh else None)
    got = jax.jit(functools.partial(dc._cluster_means, mesh=m))(state)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("k,kernel", [(256, True), (4096, True),
                                      (8192, True), (45056, True),
                                      (45057, False), (65536, False)])
def test_envelope_picks_kernel_or_oracle(monkeypatch, k, kernel):
    assert sr.fits(k) == kernel
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    called = []
    monkeypatch.setattr(sr, "segment_row_sum",
                        lambda x, seg: called.append(x.shape) or x)
    k_fits = sr.vmem_limit(k, sr.LANES) <= sr.VMEM_BYTES * 3 // 4
    assert k_fits == kernel
    x = jnp.ones((k, 2), jnp.float32)
    seg = jnp.arange(k, dtype=jnp.int32)
    out = ops.segment_rows(x, seg, jnp.ones((k,), bool))
    assert bool(called) == kernel
    assert np.array_equal(np.asarray(out), np.ones((k, 2), np.float32))


def test_blocks_fit_the_scoped_vmem_at_every_k():
    """The chosen tile's double-buffered blocks stay within the scoped
    VMEM the kernel asks for, with half the default left to the
    compiler, at every K the kernel takes; up to K = 4,096 that is the
    default limit; and the tile is a whole number of lanes."""
    for k in (1, 2, 32, 256, 1024, 4096, 8192, 45056):
        for d in (128, 1000, 1_628_170):
            bd = sr.tile_width(k, d)
            assert bd % sr.LANES == 0
            limit = sr.vmem_limit(k, bd)
            assert 4 * k * bd * 4 + sr.SCOPED_VMEM_BYTES // 2 <= limit
            assert limit <= sr.VMEM_BYTES * 3 // 4
            assert (limit == sr.SCOPED_VMEM_BYTES) == (k <= 4096)


@pytest.mark.parametrize("k,d", [(32, 1000), (256, 300)])
@pytest.mark.parametrize("pattern", ["dead_rows", "empty_segment"])
def test_wrapper_skips_dead_rows_whatever_their_segment(kernel_on_cpu,
                                                        pattern, k, d):
    """A dead row adds into nothing on either branch of
    ``ops.segment_rows``, even where its segment id names a kept row."""
    rng = np.random.default_rng(k + d)
    seg, _ = _segments(pattern, k, rng)
    live = jnp.asarray(rng.random(k) < 0.7)
    x = _rows(k, d, live, rng)
    got = np.asarray(jax.jit(ops.segment_rows)(x, seg, live))
    want = np.asarray(ref.segment_rows_ref(x, seg, live))
    assert np.array_equal(got, want)
    assert not np.isnan(got).any()


def test_state_on_one_device_has_no_mesh():
    """Only a bank laid over several devices runs the kernels under a
    mesh (``tests/test_tpu_compile.py`` compiles that case)."""
    assert dc.state_mesh(_churned_state(16, 128, 0)) is None
