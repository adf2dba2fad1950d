"""Persistent-compilation-cache + donation-safety checks.

``utils.cache.enable_compilation_cache`` must make recompiles after
``jax.clear_caches()`` get SERVED from disk — observed through the
``cache_hits`` counter that ``analysis.sanitize.compile_budget`` now
tallies (the backend-compile event fires per request, served or not, so
a warm serve shows up as ``cache_hits >= 1`` alongside the count).

The donation tests pin the safety contract of the donating entry
points: donation resolves at call/build time and is OFF on CPU, so
donated-in-name inputs stay readable and no hidden host↔device copies
appear (``no_transfer`` guard).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import sanitize
from repro.kernels.prox_update import prox_update_flat
from repro.utils.cache import (CHECKOUT_CACHE_DIR, cache_dir,
                               enable_compilation_cache)


def test_compilation_cache_serves_after_clear(tmp_path, monkeypatch):
    """With ``$JAX_COMPILATION_CACHE_DIR`` set, the cache lands there and
    nowhere else, and a recompile after ``clear_caches`` is served."""
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_time = jax.config.jax_persistent_cache_min_compile_time_secs
    prev_size = jax.config.jax_persistent_cache_min_entry_size_bytes
    checkout = os.listdir(CHECKOUT_CACHE_DIR) if os.path.isdir(
        CHECKOUT_CACHE_DIR) else []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        used = enable_compilation_cache()
        assert used == str(tmp_path)

        @jax.jit
        def f(x):
            return jnp.tanh(x) * 3.0 + jnp.cos(x) - 0.25

        x = jnp.arange(128, dtype=jnp.float32)
        want = np.asarray(f(x))                   # cold: compiles + writes
        assert os.listdir(tmp_path), "no cache entry in the env directory"
        jax.clear_caches()
        with sanitize.compile_budget() as log:
            got = np.asarray(f(x))                # warm: served from disk
        np.testing.assert_array_equal(want, got)
        assert log.cache_hits >= 1, "recompile was not served from the cache"
        assert log.count >= log.cache_hits
        now = os.listdir(CHECKOUT_CACHE_DIR) if os.path.isdir(
            CHECKOUT_CACHE_DIR) else []
        assert now == checkout, "an entry went to the checkout cache too"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_time)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          prev_size)
        # drop the cache handle + used-latch so later tests re-resolve
        # against the restored config instead of this test's tmpdir
        from jax.experimental.compilation_cache import compilation_cache as cc
        cc.reset_cache()


def test_cache_dir_defaults_to_checkout(monkeypatch):
    """Unset, the cache goes to ``<checkout>/.jax_cache``, which git
    ignores; set, the variable wins."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cache_dir() == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert cache_dir() == "/elsewhere"


def test_prox_donation_contract_on_cpu():
    # the donate=None default resolves to NON-donating on CPU: inputs
    # stay readable and no implicit host transfer sneaks past the guard
    th, om = jnp.ones((64,)), jnp.zeros((64,))
    gt, go = jnp.full((64,), 0.5), jnp.full((64,), 0.25)
    eta, lam = jnp.float32(0.1), jnp.float32(0.05)   # device scalars
    with sanitize.no_transfer():
        t2, o2 = prox_update_flat(th, om, gt, go, eta, lam,
                                  block_rows=16, interpret=True)
        t2.block_until_ready()
    f32 = np.float32
    exp_t = f32(1.0) - f32(0.1) * (f32(0.5) + f32(0.05) * (f32(1.0) - f32(0.0)))
    exp_o = f32(0.0) - f32(0.1) * f32(0.25)
    np.testing.assert_array_equal(np.asarray(th), np.ones(64))
    np.testing.assert_array_equal(np.asarray(t2), np.full(64, exp_t, f32))
    np.testing.assert_array_equal(np.asarray(o2), np.full(64, exp_o, f32))

    # explicit donate=True consumes the operands EVEN on CPU (jax
    # invalidates donated arrays whether or not the backend can alias
    # them) — this is why the call-time default matters, and why every
    # fused call site rebinds θ/ω immediately instead of reusing them
    t3, _ = prox_update_flat(th, om, gt, go, eta, lam,
                             block_rows=16, interpret=True, donate=True)
    np.testing.assert_array_equal(np.asarray(t3), np.asarray(t2))
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(th)


def test_run_rounds_state_readable_after_scan():
    # the scanned round loop donates its carry off-CPU; on CPU the input
    # state must remain fully readable after the call (build-time resolve)
    from repro import engine
    from repro.data import rotated
    from repro.models import simple

    task = simple.SYNTH_MLP
    loss = lambda p, b: simple.loss_fn(p, b, task)
    clients, _, _ = rotated(n_clusters=2, n_clients=8, n_per=16, seed=0)
    clients = [jax.tree.map(jnp.asarray, c) for c in clients]
    cfg = engine.EngineConfig(local_steps=1, sample_rate=0.5, seed=0,
                              rng_backend="device",
                              cluster_backend="device")
    st = engine.init("stocfl", loss, simple.init(jax.random.PRNGKey(0), task),
                     clients, cfg, arena=True)
    out = engine.run_rounds(st, 2)
    # reading the PRE-scan state after the scan would be use-after-donate
    # if donation were (incorrectly) enabled on CPU
    for leaf in jax.tree.leaves(st.omega):
        assert np.isfinite(np.asarray(leaf)).all()
    assert out.round == st.round + 2


def test_donated_carry_sharding_is_scan_fixed_point():
    """Donation audit under sharding: on accelerators the scan donates
    its carry, and XLA can only alias a donated buffer when the carry's
    OUTPUT sharding equals its input sharding. This pins that contract
    for the one client-sharded carry leaf (Ditto's stacked personal
    bank) and for a replicated carry (fedavg's ω): every carry leaf
    must come out of the compiled span with the sharding it went in
    with — a silent reshard would break donation (and double the
    scan's carry memory) the day this runs on TPU. Mesh size adapts to
    the available devices (1 on plain tier-1, 4+ in the CI mesh lane),
    so the invariant itself is checked everywhere."""
    from repro import engine
    from repro.data import rotated
    from repro.launch.mesh import make_client_mesh
    from repro.models import simple

    task = simple.SYNTH_MLP
    loss = lambda p, b: simple.loss_fn(p, b, task)
    clients, _, _ = rotated(n_clusters=2, n_clients=8, n_per=16, seed=0)
    clients = [jax.tree.map(jnp.asarray, c) for c in clients]
    mesh = make_client_mesh(min(4, len(jax.devices())))
    for name in ("ditto", "fedavg"):
        cfg = engine.EngineConfig(local_steps=1, sample_rate=0.5, seed=0,
                                  rng_backend="device")
        st = engine.init(name, loss,
                         simple.init(jax.random.PRNGKey(0), task),
                         clients, cfg, arena=True, mesh=mesh)
        fn, carry0, consts, _fin = engine.scan_program(st, 2)
        carry1, _ys = fn(carry0, consts)
        # jax's own equivalence: handles trailing-None specs and size-1
        # mesh axes (P("clients") ≡ P() on one device) — exactly the
        # notion XLA's donation aliasing uses
        for a, b in zip(jax.tree.leaves(carry0), jax.tree.leaves(carry1)):
            assert a.sharding.is_equivalent_to(b.sharding, a.ndim), \
                f"{name}: carry sharding not a scan fixed point " \
                f"({a.sharding} -> {b.sharding})"


@pytest.mark.parametrize("name", ["stocfl", "fedavg", "fedprox", "ditto",
                                  "ifca", "cfl"])
def test_donated_scan_matches_undonated(name, monkeypatch):
    """The accelerator path, rehearsed on the CPU (which implements
    donation): with every donation gate on, two back-to-back
    ``run_rounds`` spans — the second warm-resumed from the first's
    donated carry — then ``evaluate`` run without a deleted-array or
    donate-and-read error, and land bitwise on the undonated result. A
    fresh state's ω is ``ctx.init_params``, which the scan also reads
    as a const; the scan must not donate that buffer."""
    from repro import engine
    from repro.data import rotated
    from repro.models import simple

    task = simple.SYNTH_MLP
    loss = lambda p, b: simple.loss_fn(p, b, task)
    evalf = jax.jit(lambda p, b: simple.accuracy(p, b, task))
    clients, tc, tests = rotated(n_clusters=2, n_clients=8, n_per=16, seed=0)
    clients = [jax.tree.map(jnp.asarray, c) for c in clients]
    tests = {k: jax.tree.map(jnp.asarray, v) for k, v in tests.items()}
    kw = dict(local_steps=1, sample_rate=1.0 if name == "cfl" else 0.5,
              seed=0, rng_backend="device")
    if name == "stocfl":
        kw["cluster_backend"] = "device"

    def run():
        st = engine.init(name, loss, simple.init(jax.random.PRNGKey(0), task),
                         clients, engine.EngineConfig(**kw), eval_fn=evalf,
                         arena=True)
        st = engine.run_rounds(engine.run_rounds(st, 2), 2)
        return st, engine.evaluate(st, tests, tc)

    plain, plain_eval = run()
    # every `default_backend() != "cpu"` gate opens; "gpu" keeps the
    # kernels on their jnp oracles
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    donated, donated_eval = run()
    assert donated_eval == plain_eval
    assert donated.history == plain.history
    for a, b in zip(jax.tree.leaves(donated.omega),
                    jax.tree.leaves(plain.omega)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
