"""Per-kernel allclose vs the ref.py oracles, with hypothesis shape/dtype
sweeps, executed in Pallas interpret mode on CPU (TPU is the target;
tests/test_tpu_compile.py compiles the same kernels for a v5e)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # property tests need the test extra
from hypothesis import given, settings, strategies as st

from repro.core.clustering import UnionFind
from repro.kernels import ref
from repro.kernels.cosine_sim import cosine_sim
from repro.kernels.prox_update import prox_update_flat
from repro.kernels.ssm_scan import ssm_scan
from repro.kernels import ops

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------ cosine_sim
@settings(max_examples=12, deadline=None)
@given(n=st.integers(3, 70), d=st.integers(2, 160),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_cosine_sim_sweep(n, d, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(n * 1000 + d), (n, d)) * 2).astype(dtype)
    got = cosine_sim(x, bn=16, bk=64, interpret=True)
    want = ref.cosine_sim_ref(x)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)


def test_cosine_sim_diagonal_ones():
    x = jax.random.normal(KEY, (33, 50))
    got = cosine_sim(x, bn=16, bk=64, interpret=True)
    np.testing.assert_allclose(np.diag(np.asarray(got)), 1.0, atol=1e-5)


def test_cosine_sim_zero_row_safe():
    x = jnp.zeros((8, 16)).at[1].set(1.0)
    got = cosine_sim(x, bn=8, bk=16, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    assert np.asarray(got)[0, 0] == 0.0       # zero vector -> zero sim


# ------------------------------------------------------------ prox_update
@settings(max_examples=12, deadline=None)
@given(n=st.integers(1, 5000), eta=st.floats(0.0, 1.0), lam=st.floats(0.0, 10.0))
def test_prox_update_sweep(n, eta, lam):
    ks = jax.random.split(jax.random.PRNGKey(n), 4)
    t, o, gt, go = (jax.random.normal(k, (n,)) for k in ks)
    got_t, got_o = prox_update_flat(t, o, gt, go, eta, lam, block_rows=16, interpret=True)
    want_t, want_o = ref.prox_update_ref(t, o, gt, go, eta, lam)
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(want_t), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=1e-5)


def test_prox_update_lambda_zero_is_sgd():
    """λ=0 degenerates to two independent SGD steps (paper §3.4)."""
    ks = jax.random.split(KEY, 4)
    t, o, gt, go = (jax.random.normal(k, (300,)) for k in ks)
    got_t, got_o = prox_update_flat(t, o, gt, go, 0.1, 0.0, block_rows=16, interpret=True)
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(t - 0.1 * gt), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(o - 0.1 * go), atol=1e-6)


def test_prox_update_pull_toward_omega():
    """Large λ pulls θ toward ω."""
    t = jnp.ones((100,)) * 5.0
    o = jnp.zeros((100,))
    z = jnp.zeros((100,))
    got_t, _ = prox_update_flat(t, o, z, z, 0.1, 1.0, block_rows=16, interpret=True)
    assert float(jnp.max(jnp.abs(got_t))) < 5.0


# ------------------------------------------------------------ ssm_scan
@settings(max_examples=10, deadline=None)
@given(b=st.integers(1, 3), s=st.integers(1, 70), d=st.integers(1, 40),
       n=st.integers(1, 16))
def test_ssm_scan_sweep(b, s, d, n):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(b * s + d), 3)
    dA = jax.nn.sigmoid(jax.random.normal(k1, (b, s, d, n)))
    dBx = jax.random.normal(k2, (b, s, d, n)) * 0.1
    C = jax.random.normal(k3, (b, s, n))
    got = ssm_scan(dA, dBx, C, bd=16, chunk=16, interpret=True)
    want = ref.ssm_scan_ref(dA, dBx, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


def test_ssm_scan_decay_zero_is_pointwise():
    """dA=0 ⇒ h_t = dBx_t: scan degenerates to a pointwise contraction."""
    b, s, d, n = 2, 10, 8, 4
    dBx = jax.random.normal(KEY, (b, s, d, n))
    C = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, n))
    got = ssm_scan(jnp.zeros((b, s, d, n)), dBx, C, bd=8, chunk=8, interpret=True)
    want = jnp.einsum("bsdn,bsn->bsd", dBx, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# ---------------------------------------------- arena pad-and-mask shapes
# The ClientArena pads ragged populations with zero rows; the kernels see
# rep matrices whose tail rows are pad and flat params whose lengths don't
# hit block multiples. Pad rows must be inert: exact zeros in the output,
# zero influence on the real block.

def test_cosine_sim_pad_rows_are_inert():
    """Arena-style (N_real + pad) rep matrix: pallas == ref everywhere,
    pad rows/cols come out exactly 0, and the real block is unchanged
    vs computing on the unpadded matrix alone."""
    n_real, n_pad, d = 11, 21, 40            # pad to a ragged non-multiple
    x = jax.random.normal(KEY, (n_real, d))
    xp = jnp.zeros((n_pad, d)).at[:n_real].set(x)
    got = cosine_sim(xp, bn=16, bk=64, interpret=True)
    want = ref.cosine_sim_ref(xp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    g = np.asarray(got)
    np.testing.assert_array_equal(g[n_real:, :], 0.0)      # mask rows
    np.testing.assert_array_equal(g[:, n_real:], 0.0)      # mask cols
    alone = cosine_sim(x, bn=16, bk=64, interpret=True)
    np.testing.assert_allclose(g[:n_real, :n_real], np.asarray(alone),
                               atol=1e-6)


@settings(max_examples=8, deadline=None)
@given(n_real=st.integers(1, 30), n_pad_extra=st.integers(0, 20))
def test_cosine_sim_padded_sweep(n_real, n_pad_extra):
    x = jax.random.normal(jax.random.PRNGKey(n_real * 31 + n_pad_extra),
                          (n_real, 24))
    xp = jnp.zeros((n_real + n_pad_extra, 24)).at[:n_real].set(x)
    got = np.asarray(cosine_sim(xp, bn=8, bk=32, interpret=True))
    np.testing.assert_allclose(got, np.asarray(ref.cosine_sim_ref(xp)),
                               atol=1e-5)
    assert (got[n_real:] == 0.0).all()


def test_prox_update_ragged_tail_matches_ref():
    """Flat param lengths from ragged-arena models never align to the
    block (16 rows × 128 lanes = 2048 floats); the kernel's internal
    zero-pad must not leak into the tail."""
    for n in [1, 127, 128, 129, 2047, 2049, 5000]:
        ks = jax.random.split(jax.random.PRNGKey(n), 4)
        t, o, gt, go = (jax.random.normal(k, (n,)) for k in ks)
        got_t, got_o = prox_update_flat(t, o, gt, go, 0.05, 0.3,
                                        block_rows=16, interpret=True)
        want_t, want_o = ref.prox_update_ref(t, o, gt, go, 0.05, 0.3)
        assert got_t.shape == (n,) and got_o.shape == (n,)
        np.testing.assert_allclose(np.asarray(got_t), np.asarray(want_t),
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                                   atol=1e-5)


def test_prox_update_masked_region_identity():
    """Zero gradients on masked entries (what a masked loss produces for
    pad rows) leave θ moving only by the prox pull and ω exactly fixed —
    pad examples cannot train."""
    n = 130
    t = jax.random.normal(KEY, (n,))
    o = jax.random.normal(jax.random.fold_in(KEY, 1), (n,))
    mask = (jnp.arange(n) < 77).astype(jnp.float32)
    gt = jax.random.normal(jax.random.fold_in(KEY, 2), (n,)) * mask
    go = jax.random.normal(jax.random.fold_in(KEY, 3), (n,)) * mask
    got_t, got_o = prox_update_flat(t, o, gt, go, 0.1, 0.5,
                                    block_rows=16, interpret=True)
    pad = np.asarray(mask) == 0.0
    np.testing.assert_allclose(np.asarray(got_o)[pad],
                               np.asarray(o)[pad], atol=1e-6)
    want_pad_t = np.asarray(t)[pad] - 0.1 * 0.5 * (np.asarray(t)[pad]
                                                   - np.asarray(o)[pad])
    np.testing.assert_allclose(np.asarray(got_t)[pad], want_pad_t, atol=1e-6)


# ------------------------------------------------------------ ops wrappers
def test_ops_backend_agreement():
    x = jax.random.normal(KEY, (20, 30))
    np.testing.assert_allclose(
        np.asarray(ops.pairwise_cosine(x, backend="jnp")),
        np.asarray(cosine_sim(x, bn=16, bk=16, interpret=True)), atol=1e-5)


def test_prox_update_tree_matches_flat():
    tree = {"a": jax.random.normal(KEY, (10, 3)), "b": jax.random.normal(KEY, (7,))}
    om = jax.tree.map(lambda x: x * 0.5, tree)
    gt = jax.tree.map(lambda x: x * 0.1, tree)
    go = jax.tree.map(lambda x: x * 0.2, tree)
    th2, om2 = ops.prox_update_tree(tree, om, gt, go, 0.1, 0.5, backend="jnp")
    for kk in tree:
        wt, wo = ref.prox_update_ref(tree[kk].ravel(), om[kk].ravel(),
                                     gt[kk].ravel(), go[kk].ravel(), 0.1, 0.5)
        np.testing.assert_allclose(np.asarray(th2[kk]).ravel(), np.asarray(wt), atol=1e-6)
        np.testing.assert_allclose(np.asarray(om2[kk]).ravel(), np.asarray(wo), atol=1e-6)


# ------------------------------------------------- merge_candidates (fused)
@settings(max_examples=12, deadline=None)
@given(n=st.integers(3, 70), d=st.integers(2, 160),
       tau=st.floats(-1.0, 1.0), seed=st.integers(0, 100))
def test_merge_candidates_sweep(n, d, tau, seed):
    """Fused masked-cosine+τ kernel ≡ jnp oracle over shapes, τ, and
    random live masks (interpret mode; Mosaic on real TPU)."""
    from repro.kernels.cosine_sim import merge_candidates
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (n, d))
    live = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.7, (n,))
    got = merge_candidates(x, live, tau=float(tau), bn=16, bk=64,
                           interpret=True)
    want = ref.merge_candidates_ref(x, live, float(tau))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_merge_candidates_diagonal_and_dead_rows():
    """τ=-1 admits every pair EXCEPT the diagonal and dead rows."""
    from repro.kernels.cosine_sim import merge_candidates
    x = jax.random.normal(KEY, (9, 12))
    live = jnp.array([1, 1, 0, 1, 1, 1, 0, 1, 1], bool)
    adj = np.asarray(merge_candidates(x, live, tau=-1.0, bn=8, bk=16,
                                      interpret=True))
    assert (np.diag(adj) == 0).all()
    assert (adj[2] == 0).all() and (adj[:, 6] == 0).all()
    lv = np.asarray(live)
    expect = np.outer(lv, lv) * (1 - np.eye(9))
    np.testing.assert_array_equal(adj, expect)


# --------------------------------------------- resolve_roots (pointer halving)
def _numpy_find_roots(parent):
    """The host union-find ``find`` of every node — the reference for
    ``ops.resolve_roots``."""
    uf = UnionFind()
    uf.parent = {i: int(p) for i, p in enumerate(parent)}
    return np.array([uf.find(i) for i in range(len(parent))], np.int32)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 200), seed=st.integers(0, 1000))
def test_resolve_roots_pallas_sweep(n, seed):
    """Pointer halving resolves ANY random forest to the roots a plain
    union-find ``find`` reaches."""
    rng = np.random.default_rng(seed)
    parent = np.arange(n, dtype=np.int32)
    for i in rng.permutation(n)[: n // 2]:      # random valid forest:
        parent[i] = rng.integers(0, i + 1)      # parent id <= own id
    got = np.asarray(ops.resolve_roots(jnp.asarray(parent)))
    np.testing.assert_array_equal(got, _numpy_find_roots(parent))
    # and the result is a fixed point: every root self-parents
    np.testing.assert_array_equal(got, got[got])


def test_resolve_roots_worst_case_chain():
    """A maximal-depth chain still resolves in the static ⌈log2 N⌉+1
    halving steps."""
    n = 4096
    parent = np.maximum(np.arange(n, dtype=np.int32) - 1, 0)
    got = np.asarray(ops.resolve_roots(jnp.asarray(parent)))
    np.testing.assert_array_equal(got, _numpy_find_roots(parent))
    np.testing.assert_array_equal(got, np.zeros(n, np.int32))
