"""Shared harness for the paper-table benchmarks — on the engine API."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine
from repro.core import adjusted_rand_index
from repro.models import simple

TASK = simple.SYNTH_MLP
LOSS = lambda p, b: simple.loss_fn(p, b, TASK)
EVAL = jax.jit(lambda p, b: simple.accuracy(p, b, TASK))


def setup_cache() -> str:
    """Enable the persistent XLA compilation cache for this bench
    process (``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``).
    CI shares one directory across bench steps so every step after the
    first starts warm; returns the directory used."""
    from repro.utils.cache import enable_compilation_cache
    return enable_compilation_cache()


def to_dev(clients, tests):
    clients = [jax.tree.map(jnp.asarray, c) for c in clients]
    tests = {k: jax.tree.map(jnp.asarray, v) for k, v in tests.items()}
    return clients, tests


def init_params(seed=0):
    return simple.init(jax.random.PRNGKey(seed), TASK)


def run_stocfl(clients, tc, tests, rounds=25, tau=0.5, lam=0.05, lr=0.1,
               local_steps=5, sample_rate=0.2, seed=0):
    st = engine.init("stocfl", LOSS, init_params(seed), clients,
                     engine.EngineConfig(tau=tau, lam=lam, lr=lr,
                                         local_steps=local_steps,
                                         sample_rate=sample_rate, seed=seed),
                     eval_fn=EVAL)
    t0 = time.time()
    st = engine.run(st, rounds)
    wall = time.time() - t0
    assign = st.clusters.assignment()
    ids = sorted(assign)
    ari = adjusted_rand_index([assign[c] for c in ids], [tc[c] for c in ids]) if ids else 0.0
    res = engine.evaluate(st, tests, tc)
    return {"acc": res["cluster_avg"], "global_acc": res["global_avg"],
            "ari": ari, "k": st.clusters.n_clusters(),
            "us_per_round": wall / rounds * 1e6, "state": st}


def run_baseline(name, clients, tc, tests, rounds=25, lr=0.1, local_steps=5,
                 sample_rate=0.2, seed=0, mu=0.05, n_models=4):
    cfg = engine.EngineConfig(lr=lr, local_steps=local_steps,
                              sample_rate=1.0 if name == "cfl" else sample_rate,
                              seed=seed, mu=mu, n_models=n_models)
    st = engine.init(name, LOSS, init_params(seed), clients, cfg, eval_fn=EVAL)
    t0 = time.time()
    st = engine.run(st, rounds)
    wall = time.time() - t0
    res = engine.evaluate(st, tests, tc)
    return {"acc": res["cluster_avg"], "us_per_round": wall / rounds * 1e6,
            "state": st}


def emit(rows):
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")
