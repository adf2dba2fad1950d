"""The six federated strategies as thin definitions over shared machinery.

The paper frames StoCFL as a family that degenerates into the baselines
(§3.4: τ=1 → Ditto, τ=−1 → FedProx-family, λ=0 → CFL, λ=0 ∧ τ=−1 →
FedAvg); this module makes that literal: every method is a ``Strategy``
over the same vmapped cohort primitives (``bilevel.local_sgd`` /
``bilevel.make_cohort_update``), the same weighted aggregation, and the
same pure ``ServerState`` transitions — so benchmarks compare methods,
not orchestration code.

Scale substrate: when the context carries a ``ClientArena``, cohort data
is ONE device gather (``arena.gather``) and cluster models are batched
through the stacked ``ClusterBank`` (gather in, segment-sum aggregate
out) — per-round host work is O(1) in cohort size. Without an arena the
legacy per-round Python restack path runs instead (the pre-arena
behavior, kept as the fallback and as the benchmark baseline). Cohorts
larger than ``cfg.cohort_chunk`` execute in lax.map chunks with flat
memory (``bilevel.chunk_map``), which is what sustains 100%
participation at thousands of clients.

All transitions are pure: they copy the containers they change and return
a new ``ServerState``. Host-side control flow (partition bookkeeping,
model selection) stays in numpy; the per-round math is one jitted SPMD
computation with clients on the leading axis, optionally placed on the
mesh's client axis (``EngineContext.mesh``).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bilevel
from repro.core import device_clustering as devclust
from repro.core.aggregators import AGGREGATORS
from repro.core.device_clustering import make_cluster_state
from repro.engine import sampler as cohort_sampler
from repro.engine.bank import ClusterBank, _pow2 as bank_pow2
from repro.engine.registry import register
from repro.engine.state import (EngineContext, ServerState, fresh_rng_key,
                                fresh_rng_state)
from repro.sharding import specs
from repro.utils import trees


# --------------------------------------------------------------------- shared
def client_sizes(clients) -> tuple:
    return tuple(int(np.shape(jax.tree.leaves(c)[0])[0]) for c in clients)


def _stack(ctx: EngineContext, ids) -> dict:
    """Legacy cohort data path: per-round Python restack of the host
    client list (the arena-less fallback)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[ctx.clients[int(c)] for c in ids])


def _batches(ctx: EngineContext, ids):
    """Cohort data: one arena gather, or the legacy per-round restack."""
    if ctx.arena is not None:
        return ctx.arena.gather(ids)
    return _stack(ctx, ids)


def _chunk(ctx: EngineContext) -> int:
    """Effective cohort chunk: the config knob, mesh-aligned so chunks
    shard evenly over the client axis."""
    return specs.align_cohort_chunk(int(ctx.cfg.cohort_chunk or 0), ctx.mesh)


def _append_to_arena(ctx: EngineContext, batch) -> None:
    if ctx.arena is not None:
        ctx.arena = ctx.arena.append(batch)


def _retire_from_arena(ctx: EngineContext, cid: int) -> None:
    """Tombstone a departed client's arena row (compacted in bulk once
    enough rows die — see ``ClientArena.tombstone``)."""
    if ctx.arena is not None:
        ctx.arena = ctx.arena.tombstone(int(cid))


@functools.lru_cache(maxsize=512)
def _sizes_np(sizes: tuple) -> np.ndarray:
    """Per-client sample counts as a host f32 vector, one conversion per
    distinct size tuple (the eager path calls per round; sizes only
    change on membership events)."""
    # jaxlint: disable=R2 — sizes is a host int tuple, converted once (cached)
    return np.asarray(sizes, np.float32)


def _weights(state: ServerState, ids) -> np.ndarray:  # jaxlint: hot-path
    # jaxlint: disable=R2 — eager-path weights are host-side by design
    return _sizes_np(state.sizes)[np.asarray(ids)]


# ------------------------------------------------------- scan scaffolding
def _arena_consts(ctx: EngineContext) -> dict:  # jaxlint: hot-path
    """The arena's device operands for a scanned round body. Passed as
    scan ARGUMENTS (not closed over), so the compiled scan cached on the
    context never embeds stale arrays — after churn rebuilds the arena,
    the next ``run_rounds`` call feeds the fresh buffers through the
    same compiled program. The cid→row map rides the arena's cached
    device copy (``ClientArena.device_rows``) instead of a fresh upload
    per span."""
    ar = ctx.arena
    return {"packed": ar.packed, "amask": ar.mask,
            "rowmap": ar.device_rows}


def _gather_scan(consts: dict, ids, ragged: bool, mesh=None):
    """Traceable cohort gather from ``_arena_consts`` operands — the
    same takes (and the same ragged ``"mask"`` leaf) as
    ``ClientArena.gather``, so scanned batches are bitwise-identical to
    the eager path's. With a mesh, the arena rows are resident shards
    (``ClientArena.place``), the take is a cross-shard gather, and the
    gathered batch is re-constrained onto the client axes so the
    per-client training that follows partitions over the devices."""
    with jax.named_scope("cohort_gather"):
        idx = jnp.take(consts["rowmap"], ids)
        batch = jax.tree.map(lambda x: jnp.take(x, idx, axis=0),
                             consts["packed"])
        if ragged:
            batch = dict(batch)
            batch["mask"] = jnp.take(consts["amask"], idx, axis=0)
        return specs.constrain_cohort(batch, mesh)


@functools.lru_cache(maxsize=512)
def _sizes_f32_upload(sizes: tuple):
    arr = np.zeros(cohort_sampler.pool_capacity(len(sizes)), np.float32)
    # jaxlint: disable=R2 — one upload per distinct size tuple, cached
    arr[: len(sizes)] = np.asarray(sizes, np.float32)
    return jnp.asarray(arr)


def _sizes_f32(state: ServerState):  # jaxlint: hot-path
    """Per-client sample counts as a device f32 vector (the scanned
    counterpart of ``_weights``), uploaded once per distinct size tuple
    — repeat rounds/spans over a stable federation reuse the cached
    device array instead of re-uploading every consts build. Padded to
    the pow2 population bracket (``sampler.pool_capacity``): scan
    consts shapes, like the pool itself, must not recompile per join.
    Padding rows are 0-weight and belong to unregistered ids — never
    drawn, never taken."""
    return _sizes_f32_upload(tuple(state.sizes))


def _cohort_stats(stats) -> dict:
    """The round's forward-pass counters from the cohort step's optional
    third output (per-client ``(m,)`` counters, in ``stats`` — an empty
    list where the loss returns none), reduced over the cohort as
    ``bilevel.merge_stats`` combines them over gradients."""
    return {k: (jnp.max if "max" in k else jnp.sum)(v)
            for per in stats for k, v in per.items()}


def _row_mask(mask, leaf):
    """Broadcast a (rows,) bool mask against a (rows, ...) leaf."""
    return mask.reshape((-1,) + (1,) * (leaf.ndim - 1))


def _scan_history(ys, rounds: int) -> tuple:
    """Stacked scan metrics -> eager-style history records (delegates to
    ``engine.api.scan_history``; local alias avoids an import cycle at
    module load)."""
    from repro.engine.api import scan_history
    return scan_history(ys, rounds)


def _place(ctx: EngineContext, tree, replicated: bool = False):
    """Place a cohort input on the client-axis mesh, when one is active."""
    if ctx.mesh is None:
        return tree
    if replicated:
        return specs.place_replicated(tree, ctx.mesh)
    return specs.place_cohort(tree, ctx.mesh)


def _constrain(ctx: EngineContext, tree):
    """Trace-time cohort constraint (``sharding.constrain_cohort``) —
    the in-step counterpart of ``_place`` for values produced INSIDE
    the scanned round body (gathered batches, per-cohort model stacks,
    scatter-updated carries). No-op without a mesh."""
    return specs.constrain_cohort(tree, ctx.mesh)


def _scan_consts(ctx: EngineContext, consts: dict) -> dict:
    """Pin the scan's const operands to the mesh: arena buffers keep
    their row sharding (leading capacity axis over the client devices —
    a no-op device_put when ``ClientArena.place`` already placed them),
    everything else (pool mask, sizes, row map, ω₀, the frozen tree)
    replicates. Without a mesh this is the identity, so the single-device
    scan's operands are untouched."""
    if ctx.mesh is None:
        return consts
    out = {}
    for k, v in consts.items():
        if k in ("packed", "amask"):
            out[k] = specs.place_cohort(v, ctx.mesh)
        else:
            out[k] = specs.place_replicated(v, ctx.mesh)
    return out


def merge_cluster_models(models, merges, counts, init_params):
    """Merge θ along partition merges, each side weighted by its member
    count — a 10-client cluster absorbing a singleton moves by 1/11, not
    1/2. ``counts`` is the pre-merge {root: n_members} snapshot; cascaded
    merges within one round accumulate correctly.

    ``ClusterBank`` inputs take the batched gather/segment-sum path
    (``bank.merge``); plain dicts keep the original sequential pairwise
    means (same math — the cascade IS the flat count-weighted mean)."""
    if isinstance(models, ClusterBank):
        return models.merge(merges, counts, init_params)
    models = dict(models)
    counts = dict(counts)
    for keep, absorb in merges:
        m_keep = models.pop(keep, init_params)
        m_abs = models.pop(absorb, init_params)
        n_k = float(counts.get(keep, 1))
        n_a = float(counts.get(absorb, 1))
        models[keep] = trees.tree_weighted_mean([m_keep, m_abs], [n_k, n_a])
        counts[keep] = n_k + n_a
    return models


class Strategy:
    """Protocol every federated method implements.

    ``init_state(ctx)`` builds the initial ``ServerState``;
    ``round(ctx, state, client_ids)`` is one pure server round;
    ``evaluate`` / ``join`` / ``leave`` / ``infer`` are the serving-side
    transitions. Register implementations with ``@register("name")``.
    """

    name = "base"
    needs_extractor = False
    full_participation = False
    supports_async = False
    takes_frozen = False        # engine.init(..., frozen=tree) allowed

    # ------------------------------------------------------------ lifecycle
    def init_state(self, ctx: EngineContext) -> ServerState:
        """Round-0 ``ServerState``: ω = ω₀, empty bank, fresh sampling
        rng (the numpy bit-generator, plus a device threefry key under
        ``rng_backend="device"``)."""
        key = (fresh_rng_key(ctx.cfg.seed)
               if ctx.cfg.rng_backend == "device" else None)
        return ServerState(ctx=ctx, strategy=self.name, round=0,
                           rng_state=fresh_rng_state(ctx.cfg.seed),
                           sizes=client_sizes(ctx.clients), left=frozenset(),
                           omega=ctx.init_params, models=ClusterBank.empty(),
                           personal={}, rng_key=key)

    def round(self, ctx: EngineContext, state: ServerState, client_ids):
        """One pure server round over the sampled cohort:
        ``(ctx, state, client_ids) -> (state', metrics dict)``."""
        raise NotImplementedError

    def scan_round(self, ctx: EngineContext, state: ServerState,
                   pool: np.ndarray, m: int):
        """The strategy's round as a scannable step for
        ``engine.run_rounds``.

        Returns ``(carry0, consts, step, finalize, statics)``:
        ``carry0`` is the fixed-shape scan carry built from ``state``
        (PRNG key, model pytrees, stacked banks, device partition),
        ``consts`` the round-invariant device operands (arena buffers,
        draw pool, sample counts) that are threaded as scan ARGUMENTS
        so cached compilations never go stale, ``step(carry, consts) ->
        (carry', metrics)`` one traceable round (bit-faithful to
        ``round``), ``finalize(state, carry, ys, rounds)`` the host
        conversion back to a ``ServerState``, and ``statics`` a
        hashable tuple of every value the step bakes into its TRACE
        beyond the carry/const shapes (arena raggedness, merge bounds) —
        ``run_rounds`` keys its compiled-scan cache on it. ``pool`` is
        the boolean draw-pool mask, ``m`` the static cohort size."""
        raise NotImplementedError(
            f"strategy {self.name!r} has no scannable round step")

    # ------------------------------------------------------------ serving
    def evaluate(self, ctx, state, test_sets, true_cluster=None) -> dict:
        """Held-out evaluation; the base serves every test set with ω."""
        accs = {k: float(ctx.eval_fn(state.omega, b)) for k, b in test_sets.items()}
        return {"cluster_avg": float(np.mean(list(accs.values()))), "per": accs}

    def join(self, ctx, state, batch):
        """Register a new client (§5): append its data to the world
        (client list + arena) and its size to the state; returns
        ``(state', cid)``. Subclasses add placement (Ψ-inference, model
        seeding)."""
        cid = len(ctx.clients)
        ctx.clients.append(batch)
        _append_to_arena(ctx, batch)
        sizes = state.sizes + (int(np.shape(jax.tree.leaves(batch)[0])[0]),)
        return state.replace(sizes=sizes), cid

    def leave(self, ctx, state, cid):
        """Departure (§5): stop sampling ``cid`` and tombstone its arena
        row. Subclasses additionally repair their partition."""
        _retire_from_arena(ctx, cid)
        return state.replace(left=state.left | {int(cid)})

    def infer(self, ctx, state, batch) -> dict:
        """Cluster inference for unseen data (§4.4) — clustered
        strategies only."""
        raise NotImplementedError(f"strategy {self.name!r} has no cluster inference")

    def infer_many(self, ctx, state, batches) -> list:
        """Batched ``infer`` — one result dict per batch, in order. The
        base implementation loops ``infer``; strategies with a
        vectorizable Ψ rule (StoCFL) override it with a single stacked
        extraction + one nearest-cluster pass (``engine.infer_batch``)."""
        return [self.infer(ctx, state, b) for b in batches]

    # ------------------------------------------------------------ async
    def async_dispatch(self, ctx, state, client_ids, buf, slots):
        """Async round's pre-aggregation half: run this strategy's
        clustering + local-training work for the dispatched cohort and
        scatter the trained rows into the buffer's reserved ``slots``;
        ``(ctx, state, client_ids, buf, slots) -> (state', buf')``.
        Only strategies with ``supports_async = True`` implement it."""
        raise NotImplementedError(
            f"strategy {self.name!r} has no async dispatch hook")

    def async_merge(self, ctx, state, batch, weights):
        """Async round's aggregation half: merge one ``FlushBatch`` of
        arrived contributions under the staleness-effective ``weights``
        (host f32, dispatch order) through the SAME aggregation
        functions the synchronous round calls;
        ``(ctx, state, batch, weights) -> (state', metrics dict)``."""
        raise NotImplementedError(
            f"strategy {self.name!r} has no async merge hook")


# --------------------------------------------------------------------- stocfl
@register("stocfl")
class StoCFLStrategy(Strategy):
    """Algorithm 1: stochastic Ψ-clustering + bi-level cohort update."""

    needs_extractor = True
    supports_async = True
    takes_frozen = True

    def init_state(self, ctx):
        """Adds the Ψ-clustering bookkeeping: the host ``ClusterState``
        or, with ``cfg.cluster_backend="device"``, the jitted
        ``DeviceClusters`` union-find (same partition semantics, no
        per-round host round-trip — see ``core.device_clustering``)."""
        clusters = make_cluster_state(ctx.cfg.tau, ctx.cfg.cluster_backend,
                                      capacity=len(ctx.clients))
        return super().init_state(ctx).replace(clusters=clusters)

    def _cohort(self, ctx):
        cfg = ctx.cfg
        fused = bool(cfg.fused_step)
        # fused routes through the flat kernel dispatch ("auto": Pallas
        # on TPU, jnp oracle elsewhere); the tree path pins "jnp" so big
        # jitted graphs never embed interpret-mode per-leaf kernels
        return ctx.jit(f"stocfl_cohort:{fused}", lambda: bilevel.chunk_map(
            bilevel.make_cohort_update(ctx.loss_fn, cfg.lr, cfg.lam,
                                       cfg.local_steps,
                                       backend="auto" if fused else "jnp",
                                       fused=fused),
            (0, None, 0, None), _chunk(ctx)))

    def round(self, ctx, state, client_ids):
        cfg = ctx.cfg
        client_ids = np.asarray(client_ids)
        clusters = state.clusters.copy()

        # --- stochastic client clustering (Algorithm 1 lines 5-13)
        new_ids = [int(c) for c in client_ids if c not in clusters.seen]
        if new_ids:
            # extractor outputs stay device arrays: the numpy backend
            # converts internally (the old host sync); the device backend
            # scatters them straight into its Ψ bank with no round-trip.
            # With an arena, Ψ reads the SAME padded+masked arena row
            # the scanned loop extracts from (bitwise-identical to the
            # raw shard for equal-size shards) — one consistent Ψ
            # source, so ragged federations stay scan-vs-eager exact
            if ctx.arena is not None:
                reps = [ctx.extractor(jax.tree.map(
                    lambda x: x[0], ctx.arena.gather([c])), ctx.frozen)
                    for c in new_ids]
            else:
                reps = [ctx.extractor(ctx.clients[c], ctx.frozen)
                        for c in new_ids]
            clusters.observe(new_ids, reps)
        counts = {r: len(m) for r, m in clusters.clusters().items()}
        merges = clusters.merge_round()
        models = merge_cluster_models(state.models, merges, counts, ctx.init_params)

        # --- bi-level CFL (lines 14-19): one SPMD cohort step
        roots = np.fromiter((clusters.uf.find(int(c)) for c in client_ids),
                            np.int64, len(client_ids))
        if ctx.arena is not None:
            thetas = models.take(roots, ctx.init_params)     # one gather
        else:                       # legacy per-client Python model stack
            thetas = jax.tree.map(lambda *xs: jnp.stack(xs),
                                  *[models.get(int(r), ctx.init_params)
                                    for r in roots])
        batches = _batches(ctx, client_ids)
        thetas = _place(ctx, thetas)
        batches = _place(ctx, batches)
        omega = _place(ctx, state.omega, replicated=True)
        thetas_i, omegas_i, *stats = self._cohort(ctx)(
            thetas, omega, batches, ctx.frozen)

        w = _weights(state, client_ids)
        omega = AGGREGATORS[cfg.aggregator](omegas_i, w)
        uroots, seg = np.unique(roots, return_inverse=True)
        # pow2-padded segment count: the per-round unique-cluster count
        # drifts under churn, and an exact count would recompile the
        # segment-sum + scatter every round (pad rows are zero, discarded
        # by put's scratch row)
        agg = bilevel.aggregate_segments(thetas_i, w, seg,
                                         bank_pow2(len(uroots)))
        models = models.put([int(r) for r in uroots], agg)

        if isinstance(clusters, devclust.DeviceClusters):
            # shape-stable closed form: the exact float the scanned loop
            # records (see objective_closed_impl)
            objective = devclust.objective_closed(clusters.state)
        else:
            objective = clusters.objective()
        rec = {"n_clusters": clusters.n_clusters(),
               "objective": objective,
               "sampled": len(client_ids),
               **{k: int(v) for k, v in
                  _cohort_stats(stats).items()}}
        return state.replace(omega=omega, models=models, clusters=clusters), rec

    # ------------------------------------------------------------ async
    def async_dispatch(self, ctx, state, client_ids, buf, slots):
        """The sync round's pre-aggregation half with the Ψ handshake
        routed through the buffer: new clients' embeddings are scattered
        into the buffer's Ψ rows and ``observe``/``merge_round`` read
        them back (clustering never waits on a delta), then the bi-level
        cohort step trains from the post-merge cluster models and the
        (θᵢ, ωᵢ) stacks land in the reserved buffer slots. Line-for-line
        the same clustering + training calls as ``round`` — that is what
        makes the zero-delay flush bitwise."""
        client_ids = np.asarray(client_ids)
        clusters = state.clusters.copy()

        # --- stochastic client clustering (Algorithm 1 lines 5-13)
        new_pos = [i for i, c in enumerate(client_ids)
                   if int(c) not in clusters.seen]
        if new_pos:
            new_ids = [int(client_ids[i]) for i in new_pos]
            if ctx.arena is not None:
                reps = [ctx.extractor(jax.tree.map(
                    lambda x: x[0], ctx.arena.gather([c])), ctx.frozen)
                    for c in new_ids]
            else:
                reps = [ctx.extractor(ctx.clients[c], ctx.frozen)
                        for c in new_ids]
            # the buffer IS the observe data path: Ψ rows in, Ψ rows out
            # (pure scatter/gather — the read-back is bit-identical)
            new_slots = np.asarray(slots)[new_pos]
            buf = buf.write_psi(new_slots, jnp.stack(reps))
            back = buf.read_psi(new_slots)
            clusters.observe(new_ids, [back[i] for i in range(len(new_ids))])
        counts = {r: len(m) for r, m in clusters.clusters().items()}
        merges = clusters.merge_round()
        models = merge_cluster_models(state.models, merges, counts,
                                      ctx.init_params)

        # --- bi-level CFL (lines 14-19): one SPMD cohort step
        roots = np.fromiter((clusters.uf.find(int(c)) for c in client_ids),
                            np.int64, len(client_ids))
        if ctx.arena is not None:
            thetas = models.take(roots, ctx.init_params)
        else:
            thetas = jax.tree.map(lambda *xs: jnp.stack(xs),
                                  *[models.get(int(r), ctx.init_params)
                                    for r in roots])
        batches = _batches(ctx, client_ids)
        thetas = _place(ctx, thetas)
        batches = _place(ctx, batches)
        omega = _place(ctx, state.omega, replicated=True)
        thetas_i, omegas_i, *_ = self._cohort(ctx)(thetas, omega, batches,
                                                   ctx.frozen)
        buf = buf.write(slots, thetas_i, omegas_i)
        return state.replace(models=models, clusters=clusters), buf

    def async_merge(self, ctx, state, batch, weights):
        """The sync round's aggregation half over one flush: global ω
        via ``AGGREGATORS[cfg.aggregator]``, per-cluster θ via the
        pow2-padded ``aggregate_segments`` — with each flushed delta
        re-rooted through the CURRENT partition (``find(cid)``), so
        merges that happened while it was in flight are honored."""
        cfg = ctx.cfg
        clusters = state.clusters
        omega = AGGREGATORS[cfg.aggregator](batch.aux, weights)
        roots = np.fromiter((clusters.uf.find(int(c)) for c in batch.cids),
                            np.int64, len(batch.cids))
        uroots, seg = np.unique(roots, return_inverse=True)
        agg = bilevel.aggregate_segments(batch.payload, weights, seg,
                                         bank_pow2(len(uroots)))
        models = state.models.put([int(r) for r in uroots], agg)
        if isinstance(clusters, devclust.DeviceClusters):
            objective = devclust.objective_closed(clusters.state)
        else:
            objective = clusters.objective()
        rec = {"n_clusters": clusters.n_clusters(), "objective": objective}
        return state.replace(omega=omega, models=models), rec

    def _cold_carry(self, ctx, state, clusters):
        """Build the scanned round's initial carry pieces from scratch:
        the grown partition state, the row-keyed model bank, the
        objective seed and an un-settled merge flag. The warm-resume
        path in ``scan_round`` skips all of this for back-to-back
        ``run_rounds`` calls on an untouched state."""
        if clusters.state is None:
            dim = int(jax.eval_shape(ctx.extractor, ctx.clients[0],
                                     ctx.frozen).shape[0])
            dcs0 = devclust.init_state(
                max(clusters._capacity_hint, state.n_clients), dim)
        else:
            dcs0 = devclust.grow(clusters.state, state.n_clients)
        cap = int(dcs0.parent.shape[0])
        has0 = np.zeros(cap, bool)
        roots0 = state.models.roots
        # the row-keyed bank is capacity-sized (cap × |θ| — hundreds of
        # MB at thousands of clients), so building it with eager ops
        # costs two full-bank passes of dispatch per run_rounds CALL
        # (zeros, then a whole-bank copy for the root scatter) — at
        # 4000 clients that was ~0.3 s, a third of a 20-round span.
        # One jitted program fuses zeros + scatter into a single
        # write, cached on the context (bank capacity is pow2-
        # quantized, so the program set stays O(log K))
        if roots0:
            bcap = state.models.capacity
            idx_np = np.full(bcap, cap, np.int32)  # spare bank rows drop
            idx_np[:len(roots0)] = np.asarray(roots0, np.int32)

            def _build():
                def f(S, idx, init):
                    return jax.tree.map(
                        lambda i, s: jnp.zeros((cap,) + i.shape, i.dtype)
                        .at[idx].set(s.astype(i.dtype), mode="drop"),
                        init, S)
                return jax.jit(f)

            rows0 = ctx.jit(f"stocfl_rows0:{cap}:{bcap}", _build)(
                state.models.stacked, jnp.asarray(idx_np), ctx.init_params)
            has0[list(roots0)] = True
        else:
            rows0 = ctx.jit(
                f"stocfl_rows0:{cap}:0",
                lambda: jax.jit(lambda init: jax.tree.map(
                    lambda x: jnp.zeros((cap,) + x.shape, x.dtype),
                    init)))(ctx.init_params)
        # cached objective seed: the SAME standalone jit the eager
        # metric path calls (objective_closed), so a cache-carried value
        # is the exact float eager would have recorded for an unchanged
        # partition
        obj0 = devclust._jit_objective_closed()(dcs0).astype(jnp.float32)
        return (dcs0, cap, rows0, jnp.asarray(has0), obj0,
                jnp.asarray(False))

    def scan_round(self, ctx, state, pool, m):
        """StoCFL's whole round — Ψ-extraction, observe, fused merge,
        count-weighted bank merge, bi-level cohort step, per-cluster
        aggregation — as one traceable step (``cluster_backend="device"``
        required; checked by ``run_rounds``).

        The carry keeps the partition as a raw ``DeviceClusterState``
        and the cluster models as a row-keyed bank: ``rows[r]`` is the
        model of the cluster rooted at client id r, ``has[r]`` whether
        one exists (lazy θ_k = ω₀ otherwise) — the fixed-shape twin of
        ``ClusterBank``'s host-keyed rows, rebuilt into one by
        ``finalize``. Merge-group and per-cluster aggregations are
        segment-sums over ascending row order, matching
        ``ClusterBank.merge``'s and the eager round's summation order
        bitwise."""
        cfg = ctx.cfg
        tau = float(cfg.tau)
        ragged = ctx.arena.ragged
        clusters = state.clusters
        # warm resume: consecutive run_rounds calls on an untouched state
        # rebuild the cap-sized row bank, re-derive the objective seed
        # and re-arm the first merge pass from scratch — several full-
        # bank passes per CALL. finalize stashes the final carry pieces
        # keyed by the exact models/clusters OBJECTS it returned; every
        # state transition between spans (eager round, join, leave,
        # checkpoint load) replaces those objects, so identity is a
        # sound staleness key (bank/partition updates are copy-on-write
        # by construction — the one legacy in-place surface,
        # ClusterBank.__setitem__, has no engine callers). Bank rows
        # with has=False are never read (every consumer masks on has),
        # so resuming stale absorbed rows is bitwise-identical to the
        # zero rows a cold build would produce.
        resume = ctx.cache.get("stocfl_scan_resume")
        if (resume is not None
                and resume["models"] is state.models
                and resume["clusters"] is state.clusters
                and state.n_clients <= int(resume["dcs"].parent.shape[0])):
            dcs0 = resume["dcs"]
            cap = int(dcs0.parent.shape[0])
            rows0 = resume["rows"]
            has_arr0 = resume["has"]
            obj0 = resume["obj"]
            settled0 = resume["settled"]
        else:
            dcs0, cap, rows0, has_arr0, obj0, settled0 = \
                self._cold_carry(ctx, state, clusters)
        consts = dict(_arena_consts(ctx), pool=jnp.asarray(pool),
                      sizes=_sizes_f32(state), init=ctx.init_params)
        if ctx.frozen is not None:
            consts["frozen"] = ctx.frozen
        consts = _scan_consts(ctx, consts)
        # carry: everything replicated — the partition/bank rows are
        # cluster-keyed (not client-sharded); the cohort-sharded work is
        # the per-round batches/thetas, whose segment-sums GSPMD lowers
        # to per-shard partials + a cross-shard reduce
        carry0 = _place(ctx, (state.rng_key, state.omega, dcs0, rows0,
                              has_arr0, obj0, settled0), replicated=True)
        cohort = self._cohort(ctx)
        psi = ctx.extractor
        aggname = cfg.aggregator
        mesh = ctx.mesh
        # static live-cluster bound for the merge pass: current clusters
        # plus every still-unseen live client (each could open a
        # singleton); can only shrink during the scan, so it stays
        # sufficient — and it keeps the pairwise candidate work K̃²-ish
        # instead of capacity² (the merge partition is k_max-invariant)
        n_live = state.n_clients - len(state.left)
        k_now = (state.clusters.n_clusters()
                 if state.clusters.state is not None else 0)
        unseen = max(n_live - len(state.clusters.seen), 0)
        k_bound = min(bank_pow2(max(k_now + unseen, 1)), cap)

        def step(carry, cs):
            key, omega, dcs, rows, has, obj, settled = carry
            ids_arr = jnp.arange(cap, dtype=jnp.int32)
            # each layer of the round runs under one named scope: the
            # ops' metadata, and so a profiler trace, carries the name
            with jax.named_scope("cohort_gather"):
                key, ids = cohort_sampler.draw(key, cs["pool"], m)
            batches = _gather_scan(cs, ids, ragged, mesh)

            def observe(d):
                # Ψ per cohort member, one client at a time (lax.map
                # keeps the per-client extractor program identical to
                # the eager per-client calls — bitwise, not just
                # allclose); skipped entirely once everyone is observed
                reps = jax.lax.map(lambda b: psi(b, cs.get("frozen")),
                                   batches)
                idx = jnp.where(new, ids, cap).astype(jnp.int32)
                return devclust.DeviceClusterState(
                    parent=d.parent.at[idx].set(
                        idx.astype(d.parent.dtype), mode="drop"),
                    live=d.live.at[idx].set(True, mode="drop"),
                    rep=d.rep.at[idx].set(reps.astype(d.rep.dtype),
                                          mode="drop"))

            with jax.named_scope("psi_extraction"):
                new = ~jnp.take(dcs.live, ids)
                new_any = jnp.any(new)
                dcs = jax.lax.cond(new_any, observe, lambda d: d, dcs)
            # settled-skip: once a merge pass runs with no merges, the
            # partition is at its fixed point — re-running the pass on
            # an unchanged state is a provable bitwise no-op (the parent
            # array is kept fully compressed and dead rows self-rooted
            # through every transition), so steady-state rounds skip the
            # whole means→candidates→components pipeline. Any new
            # observation re-arms the pass; a pass that merges leaves
            # ``settled`` False so cascades continue next round, exactly
            # like the eager per-round merge_round() calls.
            run_merge = new_any | ~settled

            def do_merge(d):
                return devclust.merge_round_impl(d, tau, k_bound, mesh)

            def skip_merge(d):
                pad = jnp.full((k_bound,), cap, jnp.int32)
                return d, pad, pad, jnp.zeros((k_bound,), jnp.float32)

            with jax.named_scope("merge_pass"):
                dcs, rows_live, new_roots, counts_c = jax.lax.cond(
                    run_merge, do_merge, skip_merge, dcs)
            # --- count-weighted bank merge (ClusterBank.merge, row-keyed;
            # the heavy θ segment-sums are cond-skipped on merge-free
            # rounds, mirroring ClusterBank.merge's early return)
            with jax.named_scope("bank_merge"):
                mapped = ids_arr.at[rows_live].set(new_roots, mode="drop")
                w_full = jnp.zeros((cap,), jnp.float32).at[rows_live].set(
                    counts_c.astype(jnp.float32), mode="drop")
                gsize = jax.ops.segment_sum(
                    (w_full > 0).astype(jnp.int32), mapped,
                    num_segments=cap)
                merged = gsize > 1
                any_merged = jnp.any(merged)
                settled = jnp.where(run_merge, ~any_merged, settled)
                absorbed = (w_full > 0) & (mapped != ids_arr)

                def bank_merge(operand):
                    rows, has = operand
                    theta_full = jax.tree.map(
                        lambda R, I: jnp.where(
                            _row_mask(has, R), R,
                            jnp.asarray(I)[None].astype(R.dtype)),
                        rows, cs["init"])
                    denom = jax.ops.segment_sum(w_full, mapped,
                                                num_segments=cap)
                    wn = jnp.where(denom[mapped] > 0,
                                   w_full / denom[mapped], 0.0)
                    agg = jax.tree.map(
                        lambda x: jax.ops.segment_sum(
                            x * _row_mask(wn, x), mapped,
                            num_segments=cap).astype(x.dtype), theta_full)
                    rows = jax.tree.map(
                        lambda R, A: jnp.where(_row_mask(merged, R),
                                               A.astype(R.dtype), R),
                        rows, agg)
                    return rows, (has & ~absorbed) | merged

                rows, has = jax.lax.cond(any_merged, bank_merge,
                                         lambda o: o, (rows, has))
            # --- bi-level cohort step over post-merge cluster models
            with jax.named_scope("cohort_gather"):
                r_ids = jnp.take(dcs.parent, ids)  # fully compressed roots
                has_r = jnp.take(has, r_ids)
                thetas = jax.tree.map(
                    lambda R, I: jnp.where(
                        _row_mask(has_r, R[:1]), jnp.take(R, r_ids, axis=0),
                        jnp.asarray(I)[None].astype(R.dtype)),
                    rows, cs["init"])
                thetas = specs.constrain_cohort(thetas, mesh)
            with jax.named_scope("local_update"):
                thetas_i, omegas_i, *stats = cohort(thetas, omega, batches,
                                                    cs.get("frozen"))
            with jax.named_scope("aggregation"):
                w = jnp.take(cs["sizes"], ids)
                omega = AGGREGATORS[aggname](omegas_i, w)
                # per-cluster FedAvg over COMPACT cohort slots (≤ m), then
                # a scatter of just the touched root rows: same segment
                # sums in the same cohort order as the eager unique-root
                # path, but the per-round bank traffic is O(m·|θ|), not
                # O(capacity·|θ|) — the scan's write-back stays cluster-
                # sized no matter how big the federation's row space is
                pos = jnp.arange(m, dtype=jnp.int32)
                firsts = jnp.argmax(r_ids[:, None] == r_ids[None, :],
                                    axis=1).astype(jnp.int32)
                is_first = firsts == pos
                slot_of_pos = jnp.cumsum(is_first.astype(jnp.int32)) - 1
                slot = jnp.take(slot_of_pos, firsts)
                agg2 = bilevel.aggregate_segments(thetas_i, w, slot, m)
                target = jnp.where(is_first, r_ids, cap).astype(jnp.int32)
                rows = jax.tree.map(
                    lambda R, A: R.at[target].set(
                        jnp.take(A, slot, axis=0).astype(R.dtype),
                        mode="drop"),
                    rows, agg2)
                has = has.at[target].set(True, mode="drop")
            with jax.named_scope("objective"):
                n_clusters = jnp.sum(
                    dcs.live & (dcs.parent == ids_arr)).astype(jnp.int32)
                # Eq. 2 only moves when the partition does (observe or
                # merge); otherwise the carried value IS this round's
                # exact objective (same partition, deterministic
                # reduction), so the O(capacity·D) recompute is
                # cond-skipped
                obj = jax.lax.cond(new_any | any_merged,
                                   devclust.objective_closed_impl,
                                   lambda _d: obj, dcs)
            rec = {"n_clusters": n_clusters,
                   "objective": obj,
                   "sampled": jnp.int32(m),
                   **_cohort_stats(stats)}
            return (key, omega, dcs, rows, has, obj, settled), rec

        def finalize(state, carry, ys, rounds):
            """The host hand-off after the scan. Only what the host
            reads crosses to it: the partition's ``parent`` and ``live``
            (the host mirrors of ``DeviceClusters``), the bank flags
            ``has`` and the per-round metrics — a few KB. The Ψ bank
            ``dcs.rep`` stays the carry's device buffer, held by the
            returned state's ``clusters``; the model rows stay in the
            warm-resume stash, and the returned ``ClusterBank`` is one
            device gather of them. A donating backend consumes the
            carry with the state on its next ``run_rounds`` call, as it
            does ω."""
            key, omega, dcs, rows, has, obj, settled = carry
            # the host hand-off in three spans: waiting for the scan,
            # the device→host copies (their total size as ``bytes``),
            # the host-side rebuild
            ids = dict(round=state.round, rounds=rounds)
            fetched = (dcs.parent, dcs.live, has, ys)
            with jax.profiler.TraceAnnotation("repro.finalize.wait", **ids):
                jax.block_until_ready(fetched)
            nbytes = sum(x.nbytes for x in jax.tree.leaves(fetched))
            with jax.profiler.TraceAnnotation("repro.finalize.fetch",
                                              bytes=nbytes, **ids):
                parent, live, has_np, ys = jax.device_get(fetched)
            with jax.profiler.TraceAnnotation("repro.finalize.rebuild",
                                              **ids):
                clusters = devclust.DeviceClusters.from_state(
                    tau, dcs, parent, live)
                roots = [int(r) for r in np.nonzero(has_np)[0]]
                models = ClusterBank.empty()
                if roots:
                    # the bank's rows in ascending root order, spare rows
                    # zero (out-of-range index → fill): one jitted
                    # gather, not one eager slice per root and leaf
                    bcap = bank_pow2(len(roots))
                    idx = np.full(bcap, cap, np.int32)
                    idx[:len(roots)] = roots
                    take = ctx.jit(
                        f"stocfl_bank_take:{cap}:{bcap}",
                        lambda: jax.jit(lambda R, i: jax.tree.map(
                            lambda x: jnp.take(x, i, axis=0, mode="fill",
                                               fill_value=0), R)))
                    models = ClusterBank(take(rows, jnp.asarray(idx)),
                                         roots)
                # stash the carry for the warm-resume path (see
                # scan_round): keyed by the exact objects returned below,
                # so any state transition between spans invalidates it.
                # Its dcs IS clusters.state (the same device arrays).
                # The carried obj always equals objective_closed(dcs) (it
                # is recomputed on every partition change), and a True
                # settled flag only skips a merge pass that is a provable
                # no-op on this partition — both are bitwise-safe to
                # resume.
                ctx.cache["stocfl_scan_resume"] = dict(
                    models=models, clusters=clusters, dcs=dcs, rows=rows,
                    has=has, obj=obj, settled=settled)
                return state.replace(
                    omega=omega, rng_key=key, clusters=clusters,
                    models=models, round=state.round + rounds,
                    history=state.history + _scan_history(ys, rounds))

        return carry0, consts, step, finalize, (ragged, cap, k_bound)

    def evaluate(self, ctx, state, test_sets, true_cluster=None):
        """Each true cluster is evaluated with the model of the learned
        cluster holding most of its clients; ω is evaluated on everything."""
        assert ctx.eval_fn is not None
        assign = state.clusters.assignment()
        out, glob = {}, {}
        for tc, batch in test_sets.items():
            roots = [assign[c] for c in assign if true_cluster[c] == tc]
            if roots:
                root = max(set(roots), key=roots.count)
                model = state.cluster_model(root)
            else:
                model = state.omega
            out[tc] = float(ctx.eval_fn(model, batch))
            glob[tc] = float(ctx.eval_fn(state.omega, batch))
        return {"cluster": out, "cluster_avg": float(np.mean(list(out.values()))),
                "global": glob, "global_avg": float(np.mean(list(glob.values())))}

    def join(self, ctx, state, batch):
        """Dynamic join (§5): register the client, infer its cluster via Ψ
        against the PRE-EXISTING clusters, or open a fresh cluster seeded
        from the nearest one's model."""
        state, cid = super().join(ctx, state, batch)
        clusters = state.clusters.copy()
        models = state.models
        rep = ctx.extractor(batch, ctx.frozen)  # device array; backends convert
        root, near, _sim = clusters.nearest(rep)
        clusters.observe([cid], [rep])
        if root is not None:
            clusters.uf.union(min(root, cid), max(root, cid))
            # cid inherits the cluster model (no merge needed: cid had none)
        elif near is not None:
            models = models.set(clusters.uf.find(cid),
                                models.get(near, ctx.init_params))
        return state.replace(clusters=clusters, models=models), cid

    def leave(self, ctx, state, cid):
        """Dynamic leave: drop the client from reps AND the union-find so
        assignments stay consistent; the cluster keeps its model (knowledge
        persists, §5), re-keyed if the departure changed the root."""
        state = super().leave(ctx, state, cid)
        clusters = state.clusters.copy()
        remap = clusters.remove(cid)
        return state.replace(clusters=clusters,
                             models=state.models.rename(remap))

    def infer(self, ctx, state, batch):
        """Cluster inference for an unseen client (§4.4), without joining."""
        rep = ctx.extractor(batch, ctx.frozen)
        root, near, sim = state.clusters.nearest(rep)
        src = root if root is not None else near
        model = state.cluster_model(src) if src is not None else state.omega
        return {"cluster": root, "seed_from": src, "similarity": sim, "model": model}

    def infer_many(self, ctx, state, batches):
        """§4.4 for MANY unseen batches in one pass: stack the batches on
        a new leading axis, run the Ψ extractor once under ``vmap``, pull
        ONE cluster-means snapshot, and score every (rep, cluster) pair
        as a single (J, K̃) cosine matrix. Routing decisions (nearest
        root, τ clearance) match per-batch ``infer`` — this is the
        serving router's amortized path (``repro.serve.Router``)."""
        if not batches:
            return []
        stacked = jax.tree.map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *batches)
        reps = np.asarray(jax.vmap(lambda b: ctx.extractor(b, ctx.frozen))(
            stacked), np.float32)
        if state.clusters is None or state.clusters.n_clusters() == 0:
            return [{"cluster": None, "seed_from": None, "similarity": 0.0,
                     "model": state.omega} for _ in batches]
        roots, means = state.clusters.cluster_means()
        mn = means / (np.linalg.norm(means, axis=1, keepdims=True) + 1e-12)
        rn = reps / (np.linalg.norm(reps, axis=1, keepdims=True) + 1e-12)
        sims = rn @ mn.T                                   # (J, K̃)
        tau = state.clusters.tau
        out = []
        for j in range(len(batches)):
            best = int(np.argmax(sims[j]))
            sim = float(sims[j][best])
            root = int(roots[best])
            out.append({"cluster": root if sim >= tau else None,
                        "seed_from": root, "similarity": sim,
                        "model": state.cluster_model(root)})
        return out


# ------------------------------------------------------------------ baselines
@register("fedavg")
class FedAvgStrategy(Strategy):
    """Single global model; λ=0 ∧ τ=−1 degeneration of StoCFL."""

    prox = False
    supports_async = True

    def _upd(self, ctx):
        cfg = ctx.cfg

        def build():
            fused = bool(cfg.fused_step)
            if self.prox:
                fn = lambda p, b: bilevel.local_sgd(ctx.loss_fn, p, b, cfg.lr,
                                                    cfg.local_steps, prox_to=p,
                                                    lam=cfg.mu, fused=fused)
            else:
                fn = lambda p, b: bilevel.local_sgd(ctx.loss_fn, p, b, cfg.lr,
                                                    cfg.local_steps, fused=fused)
            return bilevel.chunk_map(jax.jit(jax.vmap(fn, in_axes=(None, 0))),
                                     (None, 0), _chunk(ctx))

        return ctx.jit(f"{self.name}_upd:{bool(cfg.fused_step)}", build)

    def round(self, ctx, state, client_ids):
        ids = np.asarray(client_ids)
        batches = _place(ctx, _batches(ctx, ids))
        outs = self._upd(ctx)(_place(ctx, state.omega, replicated=True), batches)
        omega = bilevel.aggregate_stacked(outs, _weights(state, ids))
        return state.replace(omega=omega), {"sampled": len(ids)}

    # ------------------------------------------------------------ async
    def async_dispatch(self, ctx, state, client_ids, buf, slots):
        """Broadcast ω and run the cohort's local SGD (the sync round's
        training half, same compiled update), scattering the local
        params into the reserved buffer slots."""
        ids = np.asarray(client_ids)
        batches = _place(ctx, _batches(ctx, ids))
        outs = self._upd(ctx)(_place(ctx, state.omega, replicated=True),
                              batches)
        return state, buf.write(slots, outs)

    def async_merge(self, ctx, state, batch, weights):
        """Weighted mean of the flushed local params — the sync round's
        ``aggregate_stacked`` on the staleness-effective weights."""
        omega = bilevel.aggregate_stacked(batch.payload, weights)
        return state.replace(omega=omega), {}

    def scan_round(self, ctx, state, pool, m):
        """Scannable FedAvg/FedProx round: draw → gather → local SGD →
        weighted mean, carry ``(key, ω)`` — the same compiled cohort
        update as the eager round, on the same shapes."""
        ragged = ctx.arena.ragged
        upd = self._upd(ctx)
        mesh = ctx.mesh
        consts = _scan_consts(ctx, dict(_arena_consts(ctx),
                                        pool=jnp.asarray(pool),
                                        sizes=_sizes_f32(state)))
        carry0 = _place(ctx, (state.rng_key, state.omega), replicated=True)

        def step(carry, cs):
            key, omega = carry
            key, ids = cohort_sampler.draw(key, cs["pool"], m)
            batches = _gather_scan(cs, ids, ragged, mesh)
            outs = upd(omega, batches)
            omega = bilevel.aggregate_stacked(outs, jnp.take(cs["sizes"], ids))
            return (key, omega), {"sampled": jnp.int32(m)}

        def finalize(state, carry, ys, rounds):
            key, omega = carry
            return state.replace(omega=omega, rng_key=key,
                                 round=state.round + rounds,
                                 history=state.history + _scan_history(ys, rounds))

        return carry0, consts, step, finalize, (ragged,)


@register("fedprox")
class FedProxStrategy(FedAvgStrategy):
    """FedAvg + prox to the broadcast global (prox_to closes over the
    round's initial params, constant through the local scan)."""
    prox = True


@register("ditto")
class DittoStrategy(Strategy):
    """Global FedAvg + per-client personal models with prox to global
    (τ=1 degeneration: every client is its own cluster)."""

    def init_state(self, ctx):
        personal = {i: ctx.init_params for i in range(len(ctx.clients))}
        return super().init_state(ctx).replace(personal=personal)

    def _upds(self, ctx):
        cfg = ctx.cfg
        # gupd must NOT donate batches: the same cohort batch feeds pupd
        # right after (donation would free it on accelerators)
        fused = bool(cfg.fused_step)
        gupd = ctx.jit(f"ditto_g:{fused}", lambda: bilevel.chunk_map(
            jax.jit(jax.vmap(
                lambda p, b: bilevel.local_sgd(ctx.loss_fn, p, b, cfg.lr,
                                               cfg.local_steps, fused=fused),
                in_axes=(None, 0))), (None, 0), _chunk(ctx), donate=()))
        pupd = ctx.jit(f"ditto_p:{fused}", lambda: bilevel.chunk_map(
            jax.jit(jax.vmap(
                lambda v, g, b: bilevel.local_sgd(ctx.loss_fn, v, b, cfg.lr,
                                                  cfg.local_steps, prox_to=g,
                                                  lam=cfg.mu, fused=fused),
                in_axes=(0, None, 0))), (0, None, 0), _chunk(ctx)))
        return gupd, pupd

    def round(self, ctx, state, client_ids):
        ids = np.asarray(client_ids)
        gupd, pupd = self._upds(ctx)
        batches = _place(ctx, _batches(ctx, ids))
        omega = _place(ctx, state.omega, replicated=True)
        g_outs = gupd(omega, batches)
        v_stack = jax.tree.map(lambda *xs: jnp.stack(xs),
                               *[state.personal[int(c)] for c in ids])
        v_outs = pupd(_place(ctx, v_stack), omega, batches)
        omega = bilevel.aggregate_stacked(g_outs, _weights(state, ids))
        personal = dict(state.personal)
        for j, c in enumerate(ids):
            personal[int(c)] = jax.tree.map(lambda x: x[j], v_outs)
        return state.replace(omega=omega, personal=personal), {"sampled": len(ids)}

    def scan_round(self, ctx, state, pool, m):
        """Scannable Ditto round. The per-client personal models ride
        the carry as ONE stacked ``(n_clients, ...)`` pytree (cid ↔
        row); a round gathers the cohort's rows, proxes them to the
        broadcast ω, and scatters them back — ``finalize`` unstacks to
        the eager path's per-cid dict."""
        ragged = ctx.arena.ragged
        gupd, pupd = self._upds(ctx)
        n = state.n_clients
        # pow2 row capacity, like the pool/sizes consts: the stacked
        # personal carry must not re-shape (= recompile the scan) on
        # every join. Pad rows belong to unregistered cids — never
        # drawn, never gathered, never scattered — so their content is
        # irrelevant; duplicating row 0 keeps the stack a single eager
        # op whose compile is keyed by capn (pow2), not by n.
        capn = cohort_sampler.pool_capacity(n)
        personal0 = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[state.personal[i if i < n else 0] for i in range(capn)])
        mesh = ctx.mesh
        consts = _scan_consts(ctx, dict(_arena_consts(ctx),
                                        pool=jnp.asarray(pool),
                                        sizes=_sizes_f32(state)))
        # the stacked personal bank is the one client-indexed carry leaf:
        # shard its rows over the client axis (pow2 capn divides the pow2
        # mesh whenever capn ≥ devices) and re-pin the scatter output so
        # the carry's sharding is a scan fixed point — donation on
        # accelerators requires the in/out shardings to match
        carry0 = (_place(ctx, (state.rng_key, state.omega),
                         replicated=True)
                  + (_place(ctx, personal0),))

        def step(carry, cs):
            key, omega, personal = carry
            key, ids = cohort_sampler.draw(key, cs["pool"], m)
            batches = _gather_scan(cs, ids, ragged, mesh)
            g_outs = gupd(omega, batches)
            v = specs.constrain_cohort(
                jax.tree.map(lambda P: jnp.take(P, ids, axis=0), personal),
                mesh)
            v_outs = pupd(v, omega, batches)
            omega = bilevel.aggregate_stacked(g_outs,
                                              jnp.take(cs["sizes"], ids))
            personal = specs.constrain_cohort(
                jax.tree.map(lambda P, V: P.at[ids].set(V),
                             personal, v_outs),
                mesh)
            return (key, omega, personal), {"sampled": jnp.int32(m)}

        def finalize(state, carry, ys, rounds):
            key, omega, personal = carry
            # unstack every capn row (not just n): the per-index gather
            # compiles are then keyed by the pow2 bracket and fully warm
            # after the first churn cycle — later joins inside the same
            # bracket add zero compiles
            rows = [jax.tree.map(lambda P, ii=i: P[ii], personal)
                    for i in range(capn)]
            pd = {i: rows[i] for i in range(n)}
            return state.replace(omega=omega, rng_key=key, personal=pd,
                                 round=state.round + rounds,
                                 history=state.history + _scan_history(ys, rounds))

        return carry0, consts, step, finalize, (ragged,)

    def evaluate(self, ctx, state, test_sets, true_cluster=None):
        """Per true cluster: average of its clients' personal models' acc."""
        out = {}
        n = state.n_clients
        for tc, batch in test_sets.items():
            members = [i for i in range(n) if true_cluster[i] == tc]
            accs = [float(ctx.eval_fn(state.personal[i], batch)) for i in members[:8]]
            out[tc] = (float(np.mean(accs)) if accs
                       else float(ctx.eval_fn(state.omega, batch)))
        return {"cluster_avg": float(np.mean(list(out.values()))), "per": out}

    def join(self, ctx, state, batch):
        state, cid = super().join(ctx, state, batch)
        personal = dict(state.personal)
        personal[cid] = ctx.init_params
        return state.replace(personal=personal), cid


@register("ifca")
class IFCAStrategy(Strategy):
    """Ghosh et al. 2020: M̃ hypothesis models, clients pick argmin loss."""

    def init_state(self, ctx):
        cfg = ctx.cfg
        keys = jax.random.split(jax.random.PRNGKey(cfg.init_key), cfg.n_models)
        # perturb around init: IFCA needs distinct initializations
        models = {m: jax.tree.map(
            lambda x, k=k: x + 0.1 * jax.random.normal(
                jax.random.fold_in(k, 0), x.shape, x.dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, ctx.init_params)
            for m, k in enumerate(keys)}
        return super().init_state(ctx).replace(models=ClusterBank.from_dict(models))

    def _upd(self, ctx):
        cfg = ctx.cfg
        fused = bool(cfg.fused_step)
        return ctx.jit(f"ifca_upd:{fused}", lambda: bilevel.chunk_map(
            jax.jit(jax.vmap(
                lambda p, b: bilevel.local_sgd(ctx.loss_fn, p, b, cfg.lr,
                                               cfg.local_steps, fused=fused),
                in_axes=(0, 0))), (0, 0), _chunk(ctx)))

    def _choice(self, ctx):
        """(M, ...) models × (C, ...) batches -> (C, M) losses, one
        batched computation (the per-client Python loss loop was O(M·C)
        host dispatches). The cohort axis leads so the same chunking
        bounds the choice step's memory too — it would otherwise
        materialize M·C activations at once."""
        return ctx.jit("ifca_choice", lambda: bilevel.chunk_map(
            jax.jit(lambda ms, bs: jax.vmap(
                lambda b: jax.vmap(lambda m: ctx.loss_fn(m, b))(ms))(bs)),
            (None, 0), _chunk(ctx), donate=()))

    def round(self, ctx, state, client_ids):
        ids = np.asarray(client_ids)
        m_all = np.arange(ctx.cfg.n_models)
        batches = _batches(ctx, ids)
        hyps = state.models.take(m_all, ctx.init_params)
        losses = np.asarray(self._choice(ctx)(hyps, batches))
        choices = np.argmin(losses, axis=1)
        thetas = state.models.take(choices, ctx.init_params)
        outs = self._upd(ctx)(_place(ctx, thetas), _place(ctx, batches))
        w = _weights(state, ids)
        um, seg = np.unique(choices, return_inverse=True)
        agg = bilevel.aggregate_segments(outs, w, seg, bank_pow2(len(um)))
        models = state.models.put([int(m) for m in um], agg)
        return state.replace(models=models), {"sampled": len(ids)}

    def scan_round(self, ctx, state, pool, m):
        """Scannable IFCA round: the M̃ hypothesis models ride the carry
        stacked; choice = batched argmin loss, update = local SGD from
        the chosen hypothesis, write-back = a full-M̃ segment mean with
        untouched hypotheses kept (the fixed-shape equivalent of the
        eager path's unique-root scatter)."""
        ragged = ctx.arena.ragged
        M = int(ctx.cfg.n_models)
        choice, upd = self._choice(ctx), self._upd(ctx)
        rows0 = state.models.take(np.arange(M), ctx.init_params)
        mesh = ctx.mesh
        consts = _scan_consts(ctx, dict(_arena_consts(ctx),
                                        pool=jnp.asarray(pool),
                                        sizes=_sizes_f32(state)))
        carry0 = _place(ctx, (state.rng_key, rows0), replicated=True)

        def step(carry, cs):
            key, rows = carry
            key, ids = cohort_sampler.draw(key, cs["pool"], m)
            batches = _gather_scan(cs, ids, ragged, mesh)
            losses = choice(rows, batches)
            choices = jnp.argmin(losses, axis=1)
            thetas = specs.constrain_cohort(
                jax.tree.map(lambda R: jnp.take(R, choices, axis=0),
                             rows), mesh)
            outs = upd(thetas, batches)
            w = jnp.take(cs["sizes"], ids)
            agg = bilevel.aggregate_segments(outs, w, choices, M)
            present = jax.ops.segment_sum(jnp.ones_like(w), choices,
                                          num_segments=M) > 0
            rows = jax.tree.map(
                lambda R, A: jnp.where(_row_mask(present, R),
                                       A.astype(R.dtype), R), rows, agg)
            return (key, rows), {"sampled": jnp.int32(m)}

        def finalize(state, carry, ys, rounds):
            key, rows = carry
            models = ClusterBank.from_dict(
                {i: jax.tree.map(lambda R, ii=i: R[ii], rows)
                 for i in range(M)})
            return state.replace(models=models, rng_key=key,
                                 round=state.round + rounds,
                                 history=state.history + _scan_history(ys, rounds))

        return carry0, consts, step, finalize, (ragged, M)

    def evaluate(self, ctx, state, test_sets, true_cluster=None):
        out = {}
        for tc, batch in test_sets.items():
            accs = [float(ctx.eval_fn(state.models[m], batch))
                    for m in range(ctx.cfg.n_models)]
            out[tc] = float(np.max(accs))     # best-model (oracle assignment)
        return {"cluster_avg": float(np.mean(list(out.values()))), "per": out}


@register("cfl")
class CFLStrategy(Strategy):
    """Sattler et al. 2020a: full participation; recursively bi-partition a
    cluster near stationarity (relative-norm criterion); split seeds are
    the least-similar update pair, greedy assignment to the closer seed."""

    full_participation = True

    def init_state(self, ctx):
        state = super().init_state(ctx)
        return state.replace(members=(tuple(range(len(ctx.clients))),),
                             models=ClusterBank.from_dict({0: ctx.init_params}))

    def _core(self, ctx, L: int):
        """The WHOLE CFL round as one jitted program over a fixed
        ``L``-client layout: ``(assign (L,), k scalar, model rows
        (L, ...), batches, sizes) -> (assign', k', rows')``.

        Every client trains from its cluster's model (one gathered
        vmap), per-cluster FedAvg and the Sattler split statistics are
        masked reductions over the full client axis, and split emission
        renumbers clusters by cumulative-split offset (split cluster j →
        slots j+off and j+off+1, exactly the sequential emission order
        of the original per-cluster loop). Both the eager ``round`` and
        the ``run_rounds`` scan call THIS function — scan-vs-eager
        parity is by construction, and the split decisions (host floats
        before) are now device-deterministic."""
        cfg = ctx.cfg

        def build():
            upd = bilevel.chunk_map(
                jax.jit(jax.vmap(
                    lambda p, b: bilevel.local_sgd(ctx.loss_fn, p, b,
                                                   cfg.lr, cfg.local_steps,
                                                   fused=bool(cfg.fused_step)),
                    in_axes=(0, 0))), (0, 0), _chunk(ctx), donate=())

            def core(assign, k, rows, batches, sizes):
                # cohort-constrain the per-client operands HERE — eager
                # and scan both call this program, so the sharded
                # lowering (and its reduction order) is shared by
                # construction
                batches = specs.constrain_cohort(batches, ctx.mesh)
                thetas = specs.constrain_cohort(
                    jax.tree.map(lambda R: jnp.take(R, assign, axis=0),
                                 rows), ctx.mesh)
                outs = upd(thetas, batches)
                deltas = jax.tree.map(lambda o, t: o - t, outs, thetas)
                flat = jax.vmap(trees.tree_flatten_vector)(deltas)  # (L, d)
                norms = jnp.linalg.norm(flat, axis=1)
                ks = jnp.arange(L, dtype=jnp.int32)
                # per-cluster stats as O(L·d) segment reductions (every
                # client sits in exactly one cluster; within-segment
                # order is ascending cid, the member-tuple order)
                cnt = jax.ops.segment_sum(jnp.ones_like(assign), assign,
                                          num_segments=L)
                denom = jax.ops.segment_sum(sizes, assign, num_segments=L)
                wn = sizes / jnp.take(denom, assign)
                new_models = jax.tree.map(
                    lambda O: jax.ops.segment_sum(
                        O * wn.reshape((-1,) + (1,) * (O.ndim - 1)),
                        assign, num_segments=L).astype(O.dtype), outs)
                mean_g = jax.ops.segment_sum(flat, assign, num_segments=L
                                             ) / jnp.maximum(cnt, 1)[:, None]
                mean_norm = jnp.linalg.norm(mean_g, axis=1)
                max_norm = jax.ops.segment_max(norms, assign,
                                               num_segments=L)
                candidate = ((ks < k) & (cnt > 2)
                             & (max_norm > cfg.eps2)
                             & (mean_norm < cfg.eps_rel * max_norm))

                # split seeds: least-similar member pair, first-min in
                # row-major member order (the np.unravel_index rule).
                # The O(L²·d) similarity matrix and the per-cluster
                # masked argmins are cond-gated: rounds (and clusters)
                # with no split candidate skip them entirely — the
                # steady-state CFL round stays O(L·d)
                def seeds(_):
                    sims = flat / (norms[:, None] + 1e-12)
                    M = sims @ sims.T

                    def one(j):
                        def seed(j):
                            mask = assign == j
                            Mj = jnp.where(mask[:, None] & mask[None, :],
                                           M, jnp.inf)
                            amin = jnp.argmin(Mj)
                            gi, gj = amin // L, amin % L
                            c1 = mask & (M[:, gi] >= M[:, gj])
                            c2 = mask & ~c1
                            return c2, jnp.any(c1) & jnp.any(c2)

                        return jax.lax.cond(
                            candidate[j], seed,
                            lambda _: (jnp.zeros((L,), bool),
                                       jnp.bool_(False)), j)

                    return jax.lax.map(one, ks)

                c2, seed_ok = jax.lax.cond(
                    jnp.any(candidate), seeds,
                    lambda _: (jnp.zeros((L, L), bool),
                               jnp.zeros((L,), bool)), 0)
                split = candidate & seed_ok
                s = split.astype(jnp.int32)
                off = jnp.cumsum(s) - s
                new_pos = ks + off
                c2_p = c2[assign, jnp.arange(L)]
                base = jnp.take(new_pos, assign)
                assign2 = jnp.where(c2_p & jnp.take(split, assign),
                                    base + 1, base).astype(jnp.int32)
                idx1 = jnp.where(ks < k, new_pos, L)
                idx2 = jnp.where(split, new_pos + 1, L)
                rows2 = jax.tree.map(
                    lambda R, NM: R.at[idx1].set(NM.astype(R.dtype),
                                                 mode="drop")
                                   .at[idx2].set(NM.astype(R.dtype),
                                                 mode="drop"),
                    rows, new_models)
                k2 = (k + jnp.sum(jnp.where(ks < k, s, 0))).astype(jnp.int32)
                return assign2, k2, rows2

            return jax.jit(core)

        return ctx.jit(f"cfl_core:{L}", build)

    def _matrix(self, ctx, state):
        """Host matrix form of the CFL state: ``(live cids asc, assign
        per live position, k, (L, ...) model rows)`` — the fixed-shape
        layout ``_core`` runs on; member tuples keep clients ascending,
        so matrix ↔ tuples round-trips exactly."""
        live = np.array([i for i in range(state.n_clients)
                         if i not in state.left], np.int64)
        pos = {int(c): p for p, c in enumerate(live)}
        assign = np.zeros(len(live), np.int32)
        for j, grp in enumerate(state.members):
            for c in grp:
                assign[pos[int(c)]] = j
        k = len(state.members)
        rows = jax.tree.map(
            lambda x: jnp.zeros((len(live),) + tuple(jnp.shape(x)),
                                jnp.asarray(x).dtype), ctx.init_params)
        stacked = state.models.take(np.arange(k), ctx.init_params)
        rows = jax.tree.map(lambda Z, S: Z.at[:k].set(S.astype(Z.dtype)),
                            rows, stacked)
        return live, assign, k, rows

    @staticmethod
    def _untangle(live, assign, k, rows):
        """Matrix form back to the tuple partition + ``ClusterBank``."""
        members = tuple(tuple(int(c) for c in live[assign == j])
                        for j in range(k))
        models = ClusterBank.from_dict(
            {j: jax.tree.map(lambda R, jj=j: R[jj], rows)
             for j in range(k)})
        return members, models

    def round(self, ctx, state, client_ids):
        live, assign, k, rows = self._matrix(ctx, state)
        batches = _place(ctx, _batches(ctx, live))
        sizes = jnp.asarray(np.asarray(state.sizes, np.float32)[live])
        assign2, k2, rows2 = self._core(ctx, len(live))(
            jnp.asarray(assign), jnp.int32(k), rows, batches, sizes)
        members, models = self._untangle(live, np.asarray(assign2),
                                         int(k2), rows2)
        state = state.replace(members=members, models=models)
        return state, {"n_clusters": len(members),
                       "sampled": sum(len(m) for m in members)}

    def scan_round(self, ctx, state, pool, m):
        """Scannable CFL rounds: the carry is the matrix partition
        (``assign``, ``k``, model rows) and each step is one ``_core``
        call over the full live population (availability masks do not
        apply to full participation, mirroring the eager path)."""
        ragged = ctx.arena.ragged
        live, assign, k, rows = self._matrix(ctx, state)
        L = len(live)
        core = self._core(ctx, L)
        mesh = ctx.mesh
        consts = _scan_consts(ctx, dict(
            _arena_consts(ctx),
            live=jnp.asarray(live.astype(np.int32)),
            sizes=jnp.asarray(
                np.asarray(state.sizes, np.float32)[live])))
        carry0 = _place(ctx, (jnp.asarray(assign), jnp.int32(k), rows),
                        replicated=True)

        def step(carry, cs):
            assign, k, rows = carry
            batches = _gather_scan(cs, cs["live"], ragged, mesh)
            assign, k, rows = core(assign, k, rows, batches, cs["sizes"])
            return (assign, k, rows), {"n_clusters": k,
                                       "sampled": jnp.int32(L)}

        def finalize(state, carry, ys, rounds):
            assign, k, rows = carry
            members, models = self._untangle(live, np.asarray(assign),
                                             int(k), rows)
            return state.replace(members=members, models=models,
                                 round=state.round + rounds,
                                 history=state.history + _scan_history(ys, rounds))

        return carry0, consts, step, finalize, (ragged, L)

    def cluster_of(self, state, cid: int) -> int:
        for k, c in enumerate(state.members):
            if cid in c:
                return k
        return 0

    def join(self, ctx, state, batch):
        """CFL has no Ψ inference; assign the newcomer to the cluster whose
        model fits its data best (argmin loss, IFCA-style) so it trains
        and splits with that cluster from the next round on."""
        state, cid = super().join(ctx, state, batch)
        k = int(np.argmin([float(ctx.loss_fn(state.models[m], batch))
                           for m in range(len(state.members))]))
        members = list(state.members)
        members[k] = members[k] + (cid,)
        return state.replace(members=tuple(members)), cid

    def leave(self, ctx, state, cid):
        """Full participation trains on ``members``, so departure must
        rewrite the partition: drop the client everywhere, discard any
        cluster it leaves empty, and re-index the model table to match."""
        state = super().leave(ctx, state, cid)
        cid = int(cid)
        members, models = [], {}
        for k, group in enumerate(state.members):
            group = tuple(m for m in group if m != cid)
            if group:
                models[len(members)] = state.models[k]
                members.append(group)
        if not members:                       # last client left: keep the
            members = [()]                    # root cluster's model around
            models = {0: state.models.get(0, ctx.init_params)}
        return state.replace(members=tuple(members),
                             models=ClusterBank.from_dict(models))

    def evaluate(self, ctx, state, test_sets, true_cluster=None):
        out = {}
        for tc, batch in test_sets.items():
            ks = [self.cluster_of(state, i) for i in range(state.n_clients)
                  if true_cluster[i] == tc]
            k = max(set(ks), key=ks.count)
            out[tc] = float(ctx.eval_fn(state.models[k], batch))
        return {"cluster_avg": float(np.mean(list(out.values()))), "per": out,
                "n_clusters": len(state.members)}
