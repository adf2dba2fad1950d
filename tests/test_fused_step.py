"""Fused flat-step battery: the prox kernel's padding boundaries, the
flatten-once adapter, and the ``fused_step`` engine knob.

Three layers, matching the dispatch chain:

  kernel    ``kernels.prox_update.prox_update_flat`` (Pallas, interpret
            mode off-TPU) against the pure-jnp oracle at every padding
            boundary n ∈ {0, 1, block−1, block, block+1} — the aligned
            sizes take the no-copy fast path, the misaligned ones the
            append-pad path, and both must match the oracle exactly.
  adapter   ``bilevel.make_client_update(fused=True)`` /
            ``bilevel.local_sgd(fused=True)`` are BITWISE equal to the
            per-leaf tree path in fp32 (same f32-accumulate expression
            tree, flatten/unflatten is a pure permutation).
  engine    a federation run with ``EngineConfig(fused_step=True)``
            reproduces the unfused trajectory bitwise (fp32) for every
            strategy, eager and scanned.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.core import bilevel
from repro.kernels import ops
from repro.kernels.prox_update import prox_update_flat as prox_pallas
from repro.models import simple

TASK = simple.SYNTH_MLP
LOSS = lambda p, b: simple.loss_fn(p, b, TASK)

ALL = ["stocfl", "fedavg", "fedprox", "ditto", "ifca", "cfl"]
BLOCK_ROWS = 16
BLOCK = BLOCK_ROWS * 128        # floats per kernel block


def _vecs(n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return tuple(jax.random.normal(k, (n,), jnp.float32) for k in ks)


@pytest.mark.parametrize("n", [0, 1, BLOCK - 1, BLOCK, BLOCK + 1,
                               3 * BLOCK, 3 * BLOCK + 2])
def test_prox_kernel_matches_oracle_at_padding_boundaries(n):
    th, om, gt, go = _vecs(n)
    eta, lam = 0.1, 0.05
    want = ops.prox_update_flat(th, om, gt, go, eta, lam, backend="jnp")
    got = prox_pallas(th, om, gt, go, eta, lam, block_rows=BLOCK_ROWS,
                      interpret=True, donate=False)
    for w, g in zip(want, got):
        assert g.shape == (n,)
        # kernel and oracle are separate XLA programs — FMA contraction
        # may differ by an ulp; bitwise identity is only claimed for the
        # jnp-oracle hot path (adapter tests below)
        np.testing.assert_allclose(np.asarray(w), np.asarray(g),
                                   rtol=1e-6, atol=1e-7)


def test_prox_kernel_empty_is_identity():
    th, om, gt, go = _vecs(0)
    t2, o2 = prox_pallas(th, om, gt, go, 0.1, 0.05, block_rows=BLOCK_ROWS,
                         interpret=True, donate=False)
    assert t2.shape == (0,) and o2.shape == (0,)


def test_prox_oracle_matches_tree_leafwise():
    # the flat oracle is the tree formula on the concatenated vector
    params = simple.init(jax.random.PRNGKey(1), TASK)
    ref = simple.init(jax.random.PRNGKey(2), TASK)
    gt = jax.tree.map(lambda x: x + 0.3, params)
    go = jax.tree.map(lambda x: x - 0.1, ref)
    spec = bilevel.flat_spec(params)
    th_t, om_t = ops.prox_update_tree(params, ref, gt, go, 0.1, 0.05,
                                      backend="jnp")
    th_f, om_f = ops.prox_update_flat(
        bilevel.flatten_tree(params), bilevel.flatten_tree(ref),
        bilevel.flatten_tree(gt), bilevel.flatten_tree(go), 0.1, 0.05,
        backend="jnp")
    for a, b in zip(jax.tree.leaves(th_t),
                    jax.tree.leaves(bilevel.unflatten_tree(th_f, spec))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(om_t),
                    jax.tree.leaves(bilevel.unflatten_tree(om_f, spec))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_flatten_roundtrip_mixed_dtypes():
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": jnp.ones((4,), jnp.bfloat16),
            "c": jnp.float32(2.5).reshape(())}
    spec = bilevel.flat_spec(tree)
    back = bilevel.unflatten_tree(bilevel.flatten_tree(tree), spec)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def _batch(seed=0, n=16):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"x": jax.random.normal(k1, (n, 64)),
            "y": jax.random.randint(k2, (n,), 0, 10)}


def _tree_eq(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_fused_client_update_bitwise_fp32():
    theta = simple.init(jax.random.PRNGKey(3), TASK)
    omega = simple.init(jax.random.PRNGKey(4), TASK)
    batch = _batch()
    plain = bilevel.make_client_update(LOSS, 0.1, 0.05, local_steps=3,
                                       backend="jnp")
    fused = bilevel.make_client_update(LOSS, 0.1, 0.05, local_steps=3,
                                       backend="jnp", fused=True)
    th_p, om_p = jax.jit(plain)(theta, omega, batch)
    th_f, om_f = jax.jit(fused)(theta, omega, batch)
    _tree_eq(th_p, th_f)
    _tree_eq(om_p, om_f)


def test_fused_client_update_bitwise_under_vmap():
    # the adapter captures per-client (unbatched) shapes at trace time
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    thetas = jax.vmap(lambda k: simple.init(k, TASK))(keys)
    omega = simple.init(jax.random.PRNGKey(6), TASK)
    batches = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[_batch(seed=i) for i in range(4)])
    plain = bilevel.make_cohort_update(LOSS, 0.1, 0.05, local_steps=2,
                                       backend="jnp")(thetas, omega, batches)
    fused = bilevel.make_cohort_update(LOSS, 0.1, 0.05, local_steps=2,
                                       backend="jnp",
                                       fused=True)(thetas, omega, batches)
    _tree_eq(plain[0], fused[0])
    _tree_eq(plain[1], fused[1])


@pytest.mark.parametrize("prox", [False, True])
def test_fused_local_sgd_bitwise_fp32(prox):
    params = simple.init(jax.random.PRNGKey(7), TASK)
    anchor = simple.init(jax.random.PRNGKey(8), TASK) if prox else None
    batch = _batch(seed=1)
    kw = dict(lr=0.1, steps=3, prox_to=anchor, lam=0.05 if prox else 0.0)
    plain = jax.jit(lambda p: bilevel.local_sgd(LOSS, p, batch, **kw))
    fused = jax.jit(lambda p: bilevel.local_sgd(LOSS, p, batch,
                                                backend="jnp", fused=True,
                                                **kw))
    _tree_eq(plain(params), fused(params))


# --------------------------------------------------------------- engine level
def _fed(n_clients=12, n_per=32, seed=3):
    from repro.data import rotated
    clients, tc, tests = rotated(n_clusters=2, n_clients=n_clients,
                                 n_per=n_per, seed=seed)
    return [jax.tree.map(jnp.asarray, c) for c in clients], tc, tests


def _cfg(name, **kw):
    kw.setdefault("local_steps", 2)
    kw.setdefault("sample_rate", 0.5)
    kw.setdefault("seed", 0)
    kw.setdefault("rng_backend", "device")
    if name == "stocfl":
        kw.setdefault("cluster_backend", "device")
    if name == "cfl":
        kw["sample_rate"] = 1.0
        kw.setdefault("eps_rel", 0.9)
        kw.setdefault("eps2", 1e-4)
    return engine.EngineConfig(**kw)


def _run(name, fused, rounds=4, scan=False):
    clients, _, _ = _fed()
    st = engine.init(name, LOSS, simple.init(jax.random.PRNGKey(0), TASK),
                     clients, _cfg(name, fused_step=fused), arena=True)
    if scan:
        return engine.run_rounds(st, rounds)
    for _ in range(rounds):
        st, _ = engine.run_round(st)
    return st


@pytest.mark.parametrize("name", ALL)
def test_engine_fused_step_bitwise_fp32(name):
    a = _run(name, fused=False)
    b = _run(name, fused=True)
    _tree_eq(a.omega, b.omega)
    assert set(a.models.keys()) == set(b.models.keys())
    for k in a.models:
        _tree_eq(a.models[k], b.models[k])
    for k in a.personal:
        _tree_eq(a.personal[k], b.personal[k])
    assert a.history == b.history


def test_scan_fused_matches_eager_fused():
    a = _run("stocfl", fused=True, scan=False)
    b = _run("stocfl", fused=True, scan=True)
    _tree_eq(a.omega, b.omega)
    assert a.history == b.history
