"""End-to-end training driver on the functional engine API.

Any registered strategy (stocfl, fedavg, fedprox, ditto, ifca, cfl) runs
through the same ``engine.init -> engine.run_round`` loop; StoCFL adds
clustering metrics, checkpointing of the full ``ServerState``, and §4.4
inference. ``--mesh`` places the vmapped cohort step on a client-axis
mesh over the local devices (the sharded scanned engine — docs/SHARDING.md). ``--churn`` swaps the static loop for the
§5 dynamic-federation simulator (``repro.sim``): Poisson joins/leaves/
stragglers or a replayed JSON trace, e.g.

      PYTHONPATH=src python -m repro.launch.train --setting rotated \\
          --rounds 50 --arena --churn join=1.0,leave=0.5,straggle=0.1

Two modes:
  classification (paper-faithful, default): cross-device federation on a
    synthetic Non-IID setting with the paper's MLP task model.

      PYTHONPATH=src python -m repro.launch.train --setting rotated \\
          --rounds 100 --algo stocfl

  LLM (substrate path): federated pretraining of an assigned architecture
    (reduced via --smoke) on domain-clustered synthetic token streams;
    clients ride the vmapped cohort axis exactly as on the production mesh.

      PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \\
          --rounds 10 --clients 8 --domains 2
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine
from repro.checkpoint import save_server_state, wait_pending
from repro.core import adjusted_rand_index
from repro.data import make_federation, synthetic_lm_batch
from repro.models import build, simple
from repro.configs import get_config
from repro.launch.mesh import make_client_mesh
from repro.utils.cache import enable_compilation_cache


def _engine_cfg(args) -> engine.EngineConfig:
    cluster_backend = args.cluster_backend
    rng_backend = "numpy"
    if getattr(args, "scan_rounds", False):
        # the fused loop needs device sampling; StoCFL additionally
        # needs the device partition (run_rounds preconditions)
        rng_backend = "device"
        if args.algo == "stocfl" and cluster_backend != "device":
            print("--scan-rounds: forcing --cluster-backend device")
            cluster_backend = "device"
    async_cfg = None
    if getattr(args, "async_mode", False):
        async_cfg = engine.AsyncConfig(staleness_decay=args.staleness_decay,
                                       staleness_cap=args.staleness_cap)
    return engine.EngineConfig(
        tau=args.tau, lam=args.lam, lr=args.lr, local_steps=args.local_steps,
        sample_rate=1.0 if args.algo == "cfl" else args.sample_rate,
        seed=args.seed, mu=args.lam, cohort_chunk=args.cohort_chunk,
        cluster_backend=cluster_backend, rng_backend=rng_backend,
        fused_step=args.fused_step, dtype=args.dtype, async_cfg=async_cfg)


def _churn_timeline(args, n_clusters: int):
    """Build the --churn Timeline (trace path or Poisson spec) plus the
    setting's client factory for Join events."""
    from repro.data.synthetic import SETTING_FACTORIES
    from repro.sim import Timeline
    tl = Timeline.from_spec(args.churn, rounds=args.rounds, seed=args.seed,
                            n_clusters=n_clusters)
    factory = None
    if args.setting in SETTING_FACTORIES:
        factory = SETTING_FACTORIES[args.setting](n_clusters=n_clusters,
                                                  seed=args.seed)
    elif any(k == "join" for k in tl.counts()):
        raise SystemExit(f"--churn with joins needs a client factory; "
                         f"setting {args.setting!r} has none "
                         f"(see repro.data.synthetic.SETTING_FACTORIES)")
    return tl, factory


def run_classification(args) -> dict:
    clients_np, true_cluster, test_sets = make_federation(
        args.setting, n_clients=args.clients, seed=args.seed)
    clients = [{"x": jnp.asarray(c["x"]), "y": jnp.asarray(c["y"])} for c in clients_np]
    test_sets = {k: {"x": jnp.asarray(v["x"]), "y": jnp.asarray(v["y"])}
                 for k, v in test_sets.items()}

    task = simple.SYNTH_MLP if args.task == "synth_mlp" else simple.MNIST_MLP
    key = jax.random.PRNGKey(args.seed)
    params = simple.init(key, task)
    loss = lambda p, b: simple.loss_fn(p, b, task)
    evalf = jax.jit(lambda p, b: simple.accuracy(p, b, task))

    mesh = make_client_mesh() if args.mesh else None
    t0 = time.time()
    arena = args.arena or args.scan_rounds   # scans gather from the arena
    st = engine.init(args.algo, loss, params, clients, _engine_cfg(args),
                     eval_fn=evalf, mesh=mesh, arena=arena)
    out = {"algo": args.algo, "rounds": args.rounds}
    if args.churn:
        from repro.sim import simulate
        tl, factory = _churn_timeline(args, n_clusters=len(test_sets))
        st, log = simulate(st, tl, rounds=args.rounds,
                           client_factory=factory, seed=args.seed,
                           cohort_quantum=args.cohort_quantum,
                           eval_every=max(args.rounds // 10, 1),
                           test_sets=test_sets, true_cluster=true_cluster,
                           scan_spans=args.scan_rounds,
                           async_mode=args.async_mode)
        out["churn"] = {"timeline": tl.counts(),
                        "joined": len(log.joined),
                        "departed": len(log.departed),
                        "final_gap": log.records[-1].get("gap")}
        # joined clients need latent-cluster labels for evaluate()
        true_cluster = list(true_cluster) + [
            log.joined[cid] if log.joined[cid] is not None else -1
            for cid in sorted(log.joined)]
        if args.save_log:
            with open(args.save_log, "w") as f:
                json.dump(log.to_json(), f, indent=1)
    elif args.async_mode:
        for t in range(args.rounds):
            st, rec = engine.run_round_async(st)
            if t % max(args.rounds // 10, 1) == 0:
                print(f"round {t}: {rec}")
    elif args.scan_rounds:
        st = engine.run_rounds(st, args.rounds)   # ONE jitted lax.scan
        for t, rec in enumerate(st.history):
            if t % max(args.rounds // 10, 1) == 0:
                print(f"round {t}: {rec}")
    else:
        st = engine.run(st, args.rounds, log_every=max(args.rounds // 10, 1))
    res = engine.evaluate(st, test_sets, true_cluster)
    out.update({"cluster_avg_acc": res["cluster_avg"],
                "wall_s": round(time.time() - t0, 1)})
    if st.clusters is not None:
        assign = st.clusters.assignment()
        ids = sorted(assign)
        out["ari"] = adjusted_rand_index([assign[c] for c in ids],
                                         [true_cluster[c] for c in ids])
        out["n_clusters"] = st.clusters.n_clusters()
        out["global_avg_acc"] = res["global_avg"]
    if args.save:
        # async: the JSON summary below overlaps the checkpoint write;
        # wait_pending() barriers before the process exits
        save_server_state(args.save, st, block=False)
    print(json.dumps(out, indent=1))
    wait_pending()
    return out


def run_llm(args) -> dict:
    cfg = get_config(args.arch, smoke=args.smoke)
    model = build(cfg)
    seq, per_client = args.seq_len, args.batch
    clients = []
    true_cluster = []
    for i in range(args.clients):
        dom = i % args.domains
        clients.append(synthetic_lm_batch(cfg, seq, per_client, seed=i, domain=dom))
        true_cluster.append(dom)
    clients = [jax.tree.map(jnp.asarray, c) for c in clients]

    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    from repro.core.extractor import llm_leaf_filter
    ecfg = engine.EngineConfig(tau=args.tau, lam=args.lam, lr=args.lr,
                               local_steps=args.local_steps,
                               sample_rate=args.sample_rate, seed=args.seed,
                               project_dim=8192, cohort_chunk=args.cohort_chunk,
                               cluster_backend=args.cluster_backend,
                               fused_step=args.fused_step, dtype=args.dtype)
    mesh = make_client_mesh() if args.mesh else None
    st = engine.init("stocfl", model.loss_fn, params, clients, ecfg,
                     leaf_filter=llm_leaf_filter, mesh=mesh, arena=args.arena)
    t0 = time.time()
    for t in range(args.rounds):
        st, rec = engine.run_round(st)
        loss0 = float(model.loss_fn(st.omega, clients[0]))
        print(f"round {t}: clusters={rec['n_clusters']} omega_loss={loss0:.4f}")
    assign = st.clusters.assignment()
    ids = sorted(assign)
    ari = adjusted_rand_index([assign[c] for c in ids], [true_cluster[c] for c in ids])
    out = {"arch": cfg.name, "ari": ari, "n_clusters": st.clusters.n_clusters(),
           "rounds": args.rounds, "wall_s": round(time.time() - t0, 1)}
    if args.save:
        save_server_state(args.save, st, block=False)
    print(json.dumps(out, indent=1))
    wait_pending()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--setting", default="rotated",
                    choices=["pathological", "rotated", "shifted", "hybrid", "femnist"])
    ap.add_argument("--task", default="synth_mlp")
    ap.add_argument("--algo", default="stocfl",
                    choices=sorted(engine.list_strategies()))
    ap.add_argument("--arch", default=None, help="LLM mode: assigned arch id")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", action="store_true",
                    help="shard the engine over a (\"clients\",) mesh of the local devices (docs/SHARDING.md)")
    ap.add_argument("--arena", action="store_true",
                    help="pack client shards into a device-resident arena "
                         "(cohort = one gather instead of a per-round restack)")
    ap.add_argument("--cluster-backend", default="numpy",
                    choices=["numpy", "device"],
                    help="StoCFL partition backend: host ClusterState "
                         "(fallback) or the jitted device union-find "
                         "(core.device_clustering)")
    ap.add_argument("--scan-rounds", action="store_true",
                    help="run the whole round loop as ONE jitted lax.scan "
                         "(engine.run_rounds): on-device cohort sampling, "
                         "no per-round host dispatch; implies --arena and "
                         "rng_backend=device (and cluster-backend device "
                         "for stocfl). Under --churn, event-free spans "
                         "are scanned (sim scan_spans)")
    ap.add_argument("--cohort-chunk", type=int, default=0,
                    help="max clients per vmapped step; larger cohorts run "
                         "in lax.map chunks with flat memory (0 = unchunked)")
    ap.add_argument("--fused-step", action="store_true",
                    help="route the bilevel inner step through the fused "
                         "prox kernel (kernels.prox_update: one flat "
                         "in-place update instead of a per-leaf chain); "
                         "jnp oracle off-TPU, bitwise-identical in fp32")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="compute dtype for client params/grads/batches; "
                         "Ψ-embeddings, cluster means and the Eq. 2 "
                         "objective always stay float32")
    ap.add_argument("--async", dest="async_mode", action="store_true",
                    help="async buffered aggregation (engine."
                         "run_round_async): delayed client deltas land in "
                         "a device-resident buffer and flush as staleness-"
                         "weighted merges; bitwise equal to the sync loop "
                         "at zero delay (docs/ASYNC.md). Supported by "
                         "stocfl/fedavg/fedprox; under --churn, Straggle "
                         "victims report back late instead of dropping")
    ap.add_argument("--staleness-decay", type=float, default=1.0,
                    help="async merge-weight decay γ (weight = "
                         "count · γ^staleness; 1.0 = pure count weighting)")
    ap.add_argument("--staleness-cap", type=int, default=4,
                    help="max rounds a buffered delta may age before it is "
                         "dropped instead of merged")
    ap.add_argument("--churn", default=None,
                    help="dynamic-federation mode (§5): a JSON trace path, "
                         "or Poisson churn 'join=2.0,leave=1.5,straggle=0.1' "
                         "(see repro.sim.Timeline.from_spec)")
    ap.add_argument("--cohort-quantum", type=int, default=0,
                    help="under --churn, truncate each cohort to a multiple "
                         "of this so the set of compiled cohort shapes stays "
                         "bounded as the population drifts (0 = off)")
    ap.add_argument("--save-log", default=None,
                    help="under --churn, write the per-round simulator log "
                         "(SimLog.to_json) to this path")
    ap.add_argument("--clients", type=int, default=80)
    ap.add_argument("--domains", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--tau", type=float, default=0.5)
    ap.add_argument("--lam", type=float, default=0.05)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--local-steps", type=int, default=5)
    ap.add_argument("--sample-rate", type=float, default=0.2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--save", default=None)
    args = ap.parse_args()
    if args.async_mode and args.scan_rounds:
        raise SystemExit("--async is host-orchestrated (the delta buffer "
                         "bookkeeping lives on the host) and cannot be "
                         "fused with --scan-rounds")
    print(f"compilation cache: {enable_compilation_cache()}")
    if args.arch:
        run_llm(args)
    else:
        run_classification(args)


if __name__ == "__main__":
    main()
