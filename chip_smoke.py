#!/usr/bin/env python3
"""Smoke run of both main paths on one TPU chip, through the entry points
a user calls.

    python chip_smoke.py               # one chip: four phases, below
    python chip_smoke.py --four-chips  # train-scan on a 4-chip client mesh
                                       # vs one chip, nothing else

Phases of the one-chip run, each printing one JSON line of what it saw:

  train-scan        ``engine.init("stocfl", …, arena=True)`` on the rotated
                    federation (4 latent clusters, 4000 clients × 32
                    examples, the SYNTH_MLP 64→256→10 task), device
                    clustering and sampling at sample rate 0.05 (cohort
                    200, capacity 4096); ``engine.run_rounds`` for 10
                    rounds, then 5 more on the returned state (warm
                    resume with a donated carry), then ``engine.evaluate``.
  train-fused-bf16  the same federation with ``fused_step=True`` and
                    ``dtype="bfloat16"``, 5 rounds.
  kernels           the Pallas kernels at the shapes the training phases
                    run, against their jnp oracles at full precision.
  serve             ``serve.ServeEngine`` on the smoke-size qwen2-1.5b with
                    2 clusters × 4 slots answering 8 requests, against
                    ``serve.SequentialLoop`` at full matmul precision.

Any failed check raises, so the script exits non-zero. The last line of a
passing run is ``{"ok": true, "device": {...}}``. Without a TPU the script
exits non-zero before any phase. Everything runs in this one process; the
compile cache goes where ``repro.utils.cache`` puts it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TAU_BAND = 1e-5          # kernel/oracle may disagree only this close to τ
MESH_RTOL, MESH_ATOL = 1e-3, 1e-5   # 4-chip vs 1-chip trained floats


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(phase: str, **obs) -> None:
    print(json.dumps({"phase": phase, **obs}), flush=True)


def peak_bytes(device=None):
    """``peak_bytes_in_use`` of a device (None where not reported)."""
    import jax
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ training
def federation(n_clients: int, n_per: int, seed: int):
    """The rotated setting as device arrays: (clients, true cluster per
    client, {latent cluster: held-out batch})."""
    import jax
    import jax.numpy as jnp
    from repro.data import rotated

    clients, true_cluster, tests = rotated(n_clusters=4, n_clients=n_clients,
                                           n_per=n_per, seed=seed)
    clients = [jax.tree.map(jnp.asarray, c) for c in clients]
    tests = {k: jax.tree.map(jnp.asarray, v) for k, v in tests.items()}
    return clients, true_cluster, tests


def init_state(fed, seed: int, sample_rate: float, mesh=None, **cfg_kw):
    """A fresh StoCFL ``ServerState`` over ``fed`` through ``engine.init``."""
    import jax
    from repro import engine
    from repro.models import simple

    task = simple.SYNTH_MLP
    clients, _, _ = fed
    cfg = engine.EngineConfig(sample_rate=sample_rate, seed=seed,
                              cluster_backend="device", rng_backend="device",
                              **cfg_kw)
    return engine.init(
        "stocfl", lambda p, b: simple.loss_fn(p, b, task),
        simple.init(jax.random.PRNGKey(seed), task), clients, cfg,
        eval_fn=jax.jit(lambda p, b: simple.accuracy(p, b, task)),
        mesh=mesh, arena=True)


def run_spans(state, spans):
    """``engine.run_rounds`` once per span, each on the state the last one
    returned. Returns (state, [(seconds, compiles, compile seconds)])."""
    import jax
    from repro import engine
    from repro.analysis import sanitize

    timings = []
    for rounds in spans:
        t0 = time.perf_counter()
        with sanitize.compile_budget() as log:
            state = engine.run_rounds(state, rounds)
            jax.block_until_ready(state.omega)
        timings.append((time.perf_counter() - t0, log.count, log.seconds))
    return state, timings


def unmerged_singletons(clusters, tau: float):
    """Roots of one-member clusters, and the highest cosine any of them
    has to a multi-member cluster's mean (-1 when there are none). A
    client whose Ψ clears τ against no cluster mean stays a singleton by
    Algorithm 1's rule, so a settled partition may hold a few of them
    next to the true clusters."""
    import numpy as np

    members = clusters.clusters()
    single = [r for r, m in members.items() if len(m) == 1]
    if not single:
        return single, -1.0
    roots, means = clusters.cluster_means()
    means = means.astype(np.float64)
    big = np.stack([means[i] for i, r in enumerate(roots)
                    if len(members[r]) > 1])
    big /= np.linalg.norm(big, axis=1, keepdims=True)
    reps = np.stack([np.asarray(clusters.reps[r], np.float64)
                     for r in single])
    reps /= np.linalg.norm(reps, axis=1, keepdims=True)
    return single, float(np.max(reps @ big.T))


def train_checks(phase: str, state, fed, timings) -> dict:
    """The training phases' checks: finite losses and objectives, four
    clusters (besides singletons that clear τ against no cluster), ARI ≥
    0.9 over the clients seen. Returns the observations."""
    import numpy as np
    from repro import engine
    from repro.core import adjusted_rand_index
    from repro.models import simple

    _, true_cluster, tests = fed
    res = engine.evaluate(state, tests, true_cluster)
    assign = state.clusters.assignment()
    ids = sorted(assign)
    ari = adjusted_rand_index([assign[c] for c in ids],
                              [true_cluster[c] for c in ids])
    roots = sorted(set(assign.values()))
    tau = state.ctx.cfg.tau
    single, single_cos = unmerged_singletons(state.clusters, tau)
    losses = [float(simple.loss_fn(state.cluster_model(r), tests[k],
                                   simple.SYNTH_MLP))
              for r in roots for k in tests]
    losses += [float(simple.loss_fn(state.omega, tests[k], simple.SYNTH_MLP))
               for k in tests]
    objectives = [h["objective"] for h in state.history]
    obs = dict(rounds=state.round, wall_s=[t for t, _, _ in timings],
               compiles=[c for _, c, _ in timings],
               compile_s=[s for _, _, s in timings],
               n_clusters=state.clusters.n_clusters(),
               singletons=len(single), singleton_max_cos=single_cos,
               seen=len(ids), ari=ari,
               cluster_avg_acc=res["cluster_avg"],
               global_avg_acc=res["global_avg"],
               max_loss=max(losses), peak_bytes_in_use=peak_bytes())
    emit(phase, **obs)
    check(bool(np.all(np.isfinite(losses + objectives))),
          f"{phase}: non-finite loss or objective")
    check(obs["n_clusters"] - len(single) == 4,
          f"{phase}: {obs['n_clusters'] - len(single)} multi-member "
          "clusters, expected 4")
    check(single_cos < tau, f"{phase}: a singleton has cosine "
                            f"{single_cos:.4f} >= tau to a cluster mean")
    check(ari >= 0.9, f"{phase}: ARI {ari:.4f} < 0.9")
    return obs


def phase_train_scan(n_clients: int = 4000, n_per: int = 32,
                     spans=(10, 5), sample_rate: float = 0.05,
                     seed: int = 0, fed=None) -> dict:
    """train-scan: two back-to-back ``run_rounds`` spans, then evaluate."""
    fed = fed or federation(n_clients, n_per, seed)
    state, timings = run_spans(init_state(fed, seed, sample_rate), spans)
    check(state.round == sum(spans), "train-scan: round counter")
    return train_checks("train-scan", state, fed, timings)


def phase_train_fused_bf16(n_clients: int = 4000, n_per: int = 32,
                           rounds: int = 5, sample_rate: float = 0.05,
                           seed: int = 0, fed=None) -> dict:
    """train-fused-bf16: the fused flat prox step in bf16 compute."""
    fed = fed or federation(n_clients, n_per, seed)
    state, timings = run_spans(
        init_state(fed, seed, sample_rate, fused_step=True,
                   dtype="bfloat16"), (rounds,))
    return train_checks("train-fused-bf16", state, fed, timings)


# ------------------------------------------------------------------- kernels
def phase_kernels(k: int = 4096, d: int = 19210, cohort: int = 200,
                  tau: float = 0.5, seed: int = 0) -> dict:
    """The device-path kernels against their oracles: merge candidates at
    (k, d), the fused prox step under the cohort vmap at (cohort, d) in
    f32 and bf16, and root resolution on a worst-case chain of k."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.clustering import UnionFind
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    # four cluster directions plus per-row noise: within-cluster cosines
    # spread across τ, so the threshold decides real pairs
    protos = jax.random.normal(k1, (4, d))
    protos = protos / jnp.linalg.norm(protos, axis=1, keepdims=True)
    member = jax.random.randint(k2, (k,), 0, 4)
    scale = jax.random.uniform(k3, (k, 1), minval=0.5, maxval=1.5)
    x = protos[member] + scale * jax.random.normal(k4, (k, d)) / np.sqrt(d)
    live = jax.random.bernoulli(k5, 0.9, (k,))
    got = np.asarray(ops.merge_pairs(x, live, tau))
    with jax.default_matmul_precision("highest"):
        cos = np.asarray(ref.cosine_sim_ref(x))
        want = np.asarray(ref.merge_candidates_ref(x, live, tau))
    differ = got != want
    outside = differ & (np.abs(cos - tau) >= TAU_BAND)
    n_pairs = int(want.sum())

    prox_err = {}
    for dt in (jnp.float32, jnp.bfloat16):
        kk = jax.random.split(jax.random.fold_in(key, 7), 4)
        th, om, gt, go = (jax.random.normal(q, (cohort, d)).astype(dt)
                          for q in kk)
        fused = jax.jit(jax.vmap(
            lambda a, b, c, e: ops.prox_update_flat(a, b, c, e, 0.1, 0.05)))
        oracle = jax.jit(jax.vmap(
            lambda a, b, c, e: ops.prox_update_flat(a, b, c, e, 0.1, 0.05,
                                                    backend="jnp")))
        w_t, w_o = oracle(th, om, gt, go)   # before the fused call donates
        g_t, g_o = fused(th, om, gt, go)
        tol = 1e-5 if dt == jnp.float32 else 1e-2
        for g, w in ((g_t, w_t), (g_o, w_o)):
            check(g.shape == (cohort, d) and g.dtype == dt,
                  "kernels: prox output shape/dtype")
            g32 = np.asarray(g.astype(jnp.float32))
            w32 = np.asarray(w.astype(jnp.float32))
            prox_err[jnp.dtype(dt).name] = max(
                prox_err.get(jnp.dtype(dt).name, 0.0),
                float(np.max(np.abs(g32 - w32))))
            check(np.allclose(g32, w32, rtol=tol, atol=tol),
                  f"kernels: fused prox ({jnp.dtype(dt).name}) vs oracle")

    chain = np.maximum(np.arange(k, dtype=np.int32) - 1, 0)
    roots = np.asarray(ops.resolve_roots(jnp.asarray(chain)))
    uf = UnionFind()
    uf.parent = {i: int(p) for i, p in enumerate(chain)}
    want_roots = np.array([uf.find(i) for i in range(k)], np.int32)

    emit("kernels", k=k, d=d, merge_pairs=n_pairs,
         merge_differ=int(differ.sum()), merge_differ_outside_band=int(
             outside.sum()), prox_max_abs_err=prox_err,
         resolve_chain=k, peak_bytes_in_use=peak_bytes())
    check(n_pairs > 0, "kernels: no candidate pairs at all — test data "
                       "does not exercise the threshold")
    check(not outside.any(), f"kernels: merge_candidates differs from the "
                             f"oracle on {int(outside.sum())} pairs with "
                             f"|cos - tau| >= {TAU_BAND}")
    check(np.array_equal(roots, want_roots),
          "kernels: resolve_roots on the worst-case chain")
    return {"merge_differ": int(differ.sum())}


# --------------------------------------------------------------------- serve
def phase_serve(n_requests: int = 8, prompt_len: int = 32, gen: int = 16,
                clusters: int = 2, slots: int = 4, seed: int = 0) -> dict:
    """serve: the continuous-batching engine against the sequential loop,
    both in fp32 at full matmul precision. Every request gets ``gen``
    tokens from its domain's cluster, the same tokens on both paths."""
    import jax
    import numpy as np
    from repro import serve
    from repro.configs import get_config
    from repro.launch.serve import build_server_state, make_requests
    from repro.models import build

    # fp32 compute: bf16 logits tie at one ulp (2^-8 near 1) often enough
    # that the batched and the one-request decode, which reduce in
    # different orders, pick different greedy tokens
    cfg = get_config("qwen2-1.5b", smoke=True).with_(dtype="float32")
    model = build(cfg)
    max_len = prompt_len + gen
    with jax.default_matmul_precision("highest"):
        state = build_server_state(cfg, model, clusters, tau=0.3, seed=seed)
        domain_root = [state.client_root(k) for k in range(clusters)]
        reqs = make_requests(cfg, n_requests, prompt_len, gen, clusters)
        eng = serve.ServeEngine(model, state, serve.ServeConfig(
            slots=slots, max_len=max_len, max_gen=gen))
        t0 = time.perf_counter()
        eng.submit_many(reqs)
        got = eng.run()
        wall = time.perf_counter() - t0
        loop = serve.SequentialLoop(model, state, max_len=max_len,
                                    max_gen=gen)
        want = {r.rid: loop.serve(r) for r in reqs}
    n_tokens = sum(len(r.tokens) for r in got.values())
    emit("serve", requests=len(got), tokens=n_tokens, wall_s=wall,
         clusters=sorted({r.cluster for r in got.values()}),
         peak_bytes_in_use=peak_bytes())
    check(sorted(got) == sorted(want), "serve: not every request answered")
    for r, req in enumerate(reqs):
        res = got[req.rid]
        check(len(res.tokens) == gen, f"serve: request {req.rid} got "
                                      f"{len(res.tokens)} tokens")
        check(res.cluster == domain_root[r % clusters],
              f"serve: request {req.rid} routed to {res.cluster}, its "
              f"domain's cluster is {domain_root[r % clusters]}")
        check(want[req.rid].cluster == res.cluster,
              f"serve: request {req.rid} routed differently by the loop")
        check(np.array_equal(res.tokens, want[req.rid].tokens),
              f"serve: request {req.rid} tokens differ from the "
              "sequential loop")
    return {"tokens": n_tokens}


# ---------------------------------------------------------------- four chips
def phase_mesh(n_devices: int = 4, n_clients: int = 4000, n_per: int = 32,
               spans=(10, 5), sample_rate: float = 0.05,
               seed: int = 0) -> dict:
    """The train-scan federation on a ``make_client_mesh(n_devices)``
    client mesh and on one device, each from a freshly built state.
    Integer bookkeeping must be identical (partition, cluster count,
    every round's cohort ids); trained floats agree to
    (MESH_RTOL, MESH_ATOL); the arena rows split across the devices and
    the scan runs collectives."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import engine
    from repro.engine import sampler
    from repro.launch.mesh import make_client_mesh
    from repro.sharding import specs

    check(len(jax.devices()) >= n_devices,
          f"mesh: needs {n_devices} devices, found {len(jax.devices())}")
    fed = federation(n_clients, n_per, seed)
    mesh = make_client_mesh(n_devices)
    runs = {}
    for name, m in (("mesh", mesh), ("single", None)):
        state = init_state(fed, seed, sample_rate, mesh=m)
        arena = state.ctx.arena
        rows_per_device = sorted(
            {s.data.shape[0] for leaf in jax.tree.leaves(arena.packed)
             for s in leaf.addressable_shards})
        devices = sorted({d.id for leaf in jax.tree.leaves(arena.packed)
                          for d in leaf.sharding.device_set})
        all_reduces = 0
        if m is not None:
            fn, carry0, consts, _ = engine.scan_program(state, spans[0])
            all_reduces = fn.lower(carry0, consts).compile().as_text() \
                .count("all-reduce")
            del carry0, consts
        # each round's cohort, replayed from the state's own key through
        # the scan's draw, placed as the scan places key and pool
        pool = sampler.cohort_pool(
            state.n_clients, state.left,
            capacity=sampler.pool_capacity(state.n_clients))
        m_size = sampler.cohort_size(sample_rate, state.n_clients,
                                     int(pool.sum()))
        key, pool = state.rng_key, jnp.asarray(pool)
        if m is not None:
            key, pool = specs.place_replicated((key, pool), m)
        draw = jax.jit(lambda k, p: sampler.draw(k, p, m_size))
        cohorts = []
        for _ in range(sum(spans)):
            key, ids = draw(key, pool)
            cohorts.append(np.asarray(ids))
        state, timings = run_spans(state, spans)
        runs[name] = dict(
            state=state, cohorts=np.stack(cohorts),
            rows_per_device=rows_per_device, devices=devices,
            all_reduces=all_reduces, wall_s=[t for t, _, _ in
                                                         timings],
            peak=[peak_bytes(d) for d in jax.devices()[:n_devices]])

    a, b = runs["mesh"]["state"], runs["single"]["state"]
    diffs = {}
    for name, x, y in (("omega", a.omega, b.omega),
                       *((f"cluster{r}", a.cluster_model(r),
                          b.cluster_model(r))
                         for r in sorted(set(a.clusters.assignment()
                                             .values())))):
        for lx, ly in zip(jax.tree.leaves(x), jax.tree.leaves(y)):
            lx, ly = np.asarray(lx), np.asarray(ly)
            diffs[name] = max(diffs.get(name, 0.0),
                              float(np.max(np.abs(lx - ly))))
            check(np.allclose(lx, ly, rtol=MESH_RTOL, atol=MESH_ATOL),
                  f"mesh: {name} differs beyond rtol={MESH_RTOL} "
                  f"atol={MESH_ATOL}")
    emit("mesh", devices=n_devices,
         arena_rows_per_device=runs["mesh"]["rows_per_device"],
         arena_devices=runs["mesh"]["devices"],
         scan_all_reduces=runs["mesh"]["all_reduces"],
         peak_bytes_in_use_per_device=runs["mesh"]["peak"],
         wall_s={k: v["wall_s"] for k, v in runs.items()},
         n_clusters=[a.clusters.n_clusters(), b.clusters.n_clusters()],
         max_abs_diff=diffs)
    check(a.clusters.assignment() == b.clusters.assignment(),
          "mesh: partitions differ")
    check(a.clusters.n_clusters() == b.clusters.n_clusters(),
          "mesh: cluster counts differ")
    check(np.array_equal(runs["mesh"]["cohorts"], runs["single"]["cohorts"]),
          "mesh: cohort ids differ")
    check(a.clusters.seen == b.clusters.seen, "mesh: observed clients differ")
    check(np.array_equal(np.asarray(jax.random.key_data(a.rng_key)),
                         np.asarray(jax.random.key_data(b.rng_key))),
          "mesh: final sampling keys differ")
    capacity = jax.tree.leaves(a.ctx.arena.packed)[0].shape[0]
    check(runs["mesh"]["rows_per_device"] == [capacity // n_devices]
          and len(runs["mesh"]["devices"]) == n_devices,
          "mesh: arena rows are not split across the devices")
    check(runs["mesh"]["all_reduces"] > 0,
          "mesh: the scanned round runs no cross-device reduction")
    return {"max_abs_diff": diffs}


# ---------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the train-scan federation on a 4-chip "
                         "client mesh against one chip")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.utils.cache import enable_compilation_cache
    emit("setup", compile_cache=enable_compilation_cache(),
         jax=jax.__version__, devices=len(jax.devices()))

    if args.four_chips:
        phase_mesh(4)
    else:
        fed = federation(4000, 32, 0)
        phase_train_scan(fed=fed)
        phase_train_fused_bf16(fed=fed)
        phase_kernels()
        phase_serve()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
