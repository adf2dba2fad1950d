"""Functional engine API: pure transitions over ``ServerState``.

    state = engine.init("stocfl", loss_fn, params, clients, cfg, eval_fn=acc)
    state, rec = engine.run_round(state)            # samples internally
    state, rec = engine.run_round(state, [0, 3, 7]) # or explicit cohort
    state, cid = engine.join(state, new_batch)      # §5 dynamic membership
    state = engine.leave(state, cid)
    engine.evaluate(state, test_sets, true_cluster)
    engine.infer(state, unseen_batch)               # §4.4 cluster inference

Every transition returns a NEW state; the input is never mutated (the one
deliberate exception: ``join``/``leave`` update the context's client
list/arena — the context is the world, not the state). Client sampling
draws from the rng stored IN the state — the numpy bit-generator under
``rng_backend="numpy"`` (compatibility mode), a device threefry key
under ``rng_backend="device"`` — so a checkpointed run resumes
bit-exactly either way. ``run_rounds`` collapses a whole multi-round
span into ONE jitted ``lax.scan`` (on-device sampling included) and is
bit-faithful to the eager ``run_round`` loop; ``repro.sim.simulate``
drives these same transitions over a churn timeline — there is no
second code path.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from repro.core.extractor import make_extractor
from repro.engine import sampler
from repro.engine.registry import get_strategy
from repro.engine.state import EngineConfig, EngineContext, ServerState


def init(strategy: str, loss_fn, init_params, clients,
         cfg: Optional[EngineConfig] = None, eval_fn=None,
         leaf_filter=None, mesh=None, arena: bool = False) -> ServerState:
    """Build the static context and the strategy's initial ``ServerState``.

    Args:
      strategy: registered strategy name (``engine.list_strategies()``) —
        ``"stocfl"`` (Algorithm 1) or one of the paper's §4 baselines.
      loss_fn: ``(params, batch) -> scalar`` local objective f_i.
      init_params: ω₀ — also the frozen Ψ anchor (§3.1) and the lazy
        cluster-model default θ_k.
      clients: list of client datasets (pytrees with a shared leading
        example axis).
      cfg: ``EngineConfig`` hyperparameters (strategy-specific subset).
        ``cfg.cluster_backend="device"`` keeps StoCFL's partition as a
        jitted device union-find (``core.device_clustering``) — the
        clustering step then runs with no per-round host round-trip.
      eval_fn: optional ``(params, batch) -> accuracy`` used by
        ``evaluate`` and the simulator's §5 recovery tracking.
      leaf_filter: optional Ψ restriction to a parameter subset (LLM
        anchors: ``extractor.llm_leaf_filter``).
      mesh: optional jax Mesh; cohort steps are placed on its client axis.
      arena: pack all client shards into a device-resident
        ``ClientArena`` so each round's cohort is one gather instead of a
        per-round Python restack (ragged shard sizes are pad-and-masked;
        the loss must then honor the batch's ``"mask"`` leaf).
        ``cfg.cohort_chunk`` bounds how many clients one vmapped step
        executes — see ``bilevel.chunk_map``.

    Returns:
      The strategy's initial ``ServerState`` (round 0, nothing trained).
    """
    cfg = cfg or EngineConfig()
    # Ψ stays anchored at the ORIGINAL fp32 params even in bf16 mode:
    # the anchor is frozen (§3.1), so embeddings/means/Eq. 2 keep full
    # precision while params/grads/batches run in cfg.dtype
    psi_anchor = init_params
    if cfg.dtype != "float32":
        dt = _np_like_dtype(cfg.dtype)
        init_params = _cast_floating(init_params, dt)
        clients = [_cast_floating(c, dt) for c in clients]
    ctx = EngineContext(loss_fn=loss_fn, init_params=init_params,
                        clients=list(clients), cfg=cfg, eval_fn=eval_fn,
                        leaf_filter=leaf_filter, mesh=mesh)
    if arena:
        from repro.data.arena import ClientArena
        from repro.sharding import specs as shard_specs
        # mesh-aligned row capacity: the packed leading axis must divide
        # the client-axis device count for the arena rows to shard (the
        # pad rows are zeroed spare capacity, never gathered); the
        # pow2-doubling grow preserves the alignment thereafter
        cap = (shard_specs.align_cohort_chunk(len(ctx.clients), mesh)
               if mesh is not None else None)
        ctx.arena = ClientArena.from_clients(ctx.clients, capacity=cap)
        if mesh is not None:
            ctx.arena = ctx.arena.place(mesh)
    strat = get_strategy(strategy)
    if strat.needs_extractor:
        ctx.extractor = make_extractor(loss_fn, psi_anchor, cfg.project_dim,
                                       leaf_filter=leaf_filter)
    return strat.init_state(ctx)


def _np_like_dtype(name: str):
    import jax.numpy as jnp
    dt = jnp.dtype(name)
    if not jnp.issubdtype(dt, jnp.floating):
        raise ValueError(f"EngineConfig.dtype must be a float dtype, got {name!r}")
    return dt


def _cast_floating(tree, dt):
    """Cast every floating leaf of a pytree to ``dt`` (ints/bools — labels,
    masks, counters — keep their dtype)."""
    import jax
    import jax.numpy as jnp

    def leaf(x):
        x = jnp.asarray(x)
        return x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x

    return jax.tree.map(leaf, tree)


def sample_clients(state: ServerState, unavailable=frozenset()):
    """Draw one round's cohort without replacement (§3.3 "arbitrary
    proportion of client participation").

    The cohort size is ``cfg.sample_rate`` × the LIVE population
    (registered minus departed), drawn from the rng stored in ``state``
    — pure and checkpoint-exact. ``unavailable`` removes additional
    clients from the pool for this draw only (the simulator's
    availability windows, §5). Under ``rng_backend="device"`` the draw
    is the on-device threefry sampler (``engine.sampler.draw_cohort``,
    size ⌈rate·live⌉) — the SAME traceable draw the ``run_rounds`` scan
    inlines, so eager and scanned loops sample identical cohorts.

    Returns:
      (advanced rng: bit-generator state dict or device key, sampled
      client id array) — thread the first element back with
      ``advance_rng``.
    """
    cfg = state.ctx.cfg
    if cfg.rng_backend == "device":
        pool = sampler.cohort_pool(state.n_clients, state.left, unavailable,
                                   capacity=sampler.pool_capacity(
                                       state.n_clients))
        live = state.n_clients - len(state.left)
        m = sampler.cohort_size(cfg.sample_rate, live, int(pool.sum()))
        if m == 0:
            return state.rng_key, np.zeros(0, np.int64)
        key, ids = sampler.draw_cohort(state.rng_key, pool, m)
        return key, np.asarray(ids).astype(np.int64)
    rng = state.rng()
    pool = np.array([i for i in range(state.n_clients)
                     if i not in state.left and i not in unavailable])
    live = state.n_clients - len(state.left)
    m = max(int(round(cfg.sample_rate * live)), 1)
    ids = rng.choice(pool, size=min(m, len(pool)), replace=False)
    return rng.bit_generator.state, ids


def advance_rng(state: ServerState, rng) -> ServerState:
    """Store an advanced sampling rng back into the state — the dict
    bit-generator state (numpy backend) or the split device key (device
    backend), i.e. whatever ``sample_clients`` returned first."""
    if state.ctx.cfg.rng_backend == "device":
        return state.replace(rng_key=rng)
    return state.replace(rng_state=rng)


def run_round(state: ServerState, client_ids: Optional[Sequence[int]] = None):
    """One server round: ``(state, client_ids?) -> (state', metrics)``.

    With ``client_ids=None`` the cohort is sampled internally (advancing
    the state's rng; full-participation strategies take every live
    client). An explicit cohort skips sampling and leaves the rng
    untouched — the hook the simulator uses to apply availability
    windows and straggler dropout before training. ``metrics`` is the
    strategy's per-round record (appended to ``state.history``).
    """
    strat = get_strategy(state.strategy)
    rng_state, rng_key = state.rng_state, state.rng_key
    if client_ids is None:
        if strat.full_participation:
            client_ids = np.array([i for i in range(state.n_clients)
                                   if i not in state.left])
        elif state.ctx.cfg.rng_backend == "device":
            rng_key, client_ids = sample_clients(state)
        else:
            rng_state, client_ids = sample_clients(state)
    client_ids = np.asarray(client_ids)
    if client_ids.size == 0:
        raise ValueError("run_round needs a non-empty cohort "
                         "(no clients sampled — all departed or "
                         "unavailable?); the scanned loop handles this "
                         "as a skipped no-op round instead "
                         "(see run_rounds)")
    state, rec = strat.round(state.ctx, state, client_ids)
    state = state.replace(round=state.round + 1, rng_state=rng_state,
                          rng_key=rng_key,
                          history=state.history + (dict(rec),))
    return state, rec


def run(state: ServerState, rounds: int, log_every: int = 0) -> ServerState:
    """Convenience loop: ``rounds`` × ``run_round`` with optional progress
    printing every ``log_every`` rounds. Returns the final state (per-round
    metrics accumulate in ``state.history``)."""
    for t in range(rounds):
        state, rec = run_round(state)
        if log_every and t % log_every == 0:
            extras = "".join(f" {k}={v:.3f}" if isinstance(v, float) else f" {k}={v}"
                             for k, v in rec.items())
            print(f"round {t}:{extras}")
    return state


def scan_blockers(state: ServerState) -> Optional[str]:
    """Why this state cannot run through ``run_rounds`` — a readable
    reason string, or None when it can. The single predicate behind
    both ``run_rounds``' host-side precondition errors and the
    simulator's silent eager fallback (``simulate(scan_spans=True)``):
    the scan needs a device arena (cohort gathers must be traceable),
    device rng for sampled strategies, the device clustering backend
    for StoCFL, and every live client resident in the arena."""
    from repro.engine.strategies import Strategy

    strat = get_strategy(state.strategy)
    ctx = state.ctx
    if type(strat).scan_round is Strategy.scan_round:
        return (f"strategy {state.strategy!r} has no scannable round "
                "step (Strategy.scan_round not implemented) — use the "
                "eager run_round loop")
    if ctx.arena is None:
        return ("run_rounds needs engine.init(..., arena=True): "
                "the scanned round body gathers cohorts on device")
    if not strat.full_participation and state.rng_key is None:
        return ("run_rounds needs EngineConfig(rng_backend='device'): "
                "the scan samples cohorts from the threefry key in "
                "ServerState.rng_key (the numpy bit-generator cannot "
                "be traced)")
    if state.strategy == "stocfl" and ctx.cfg.cluster_backend != "device":
        return ("run_rounds('stocfl') needs "
                "EngineConfig(cluster_backend='device'): the host "
                "ClusterState cannot ride a lax.scan carry")
    bad = [c for c in range(state.n_clients) if c not in state.left
           and ctx.arena.rows[c] < 0]
    if bad:
        return (f"live clients {bad} were compacted out of the arena — "
                "rebuild it before scanning")
    return None


def run_rounds(state: ServerState, rounds: int,
               unavailable=frozenset()) -> ServerState:
    """The whole multi-round loop as ONE jitted ``lax.scan`` — the
    fused counterpart of ``rounds`` × ``run_round``.

    Each scanned round samples its cohort on device
    (``engine.sampler.draw``), gathers client shards from the arena,
    runs the strategy's round math, and aggregates — with NO host
    round-trip between rounds. The carry is fixed-shape (model pytrees,
    stacked banks, ``DeviceClusterState``, the PRNG key), per-round
    metrics stack as scan outputs and land in ``state.history`` exactly
    as the eager loop would have recorded them. The result is
    bit-faithful to ``run_round``: the scan-vs-eager parity battery
    (``tests/test_round_scan.py``) pins bitwise-equal final states for
    every registered strategy, through churn boundaries and checkpoint
    resume.

    Requirements (checked eagerly, see the raised messages):
    ``arena=True``, ``rng_backend="device"`` for sampled strategies, and
    ``cluster_backend="device"`` for StoCFL. Population changes cannot
    happen inside a scan — call ``join``/``leave`` between ``run_rounds``
    calls (the simulator scans exactly the event-free spans).

    ``unavailable`` holds a constant set of clients out of every scanned
    draw. If it empties the pool entirely, the rounds become no-op
    rounds recorded as ``{"skipped": True}`` metrics (the eager path
    raises instead — a scan cannot). Availability does not apply to
    full-participation strategies (CFL trains its whole partition —
    same rule as the eager loop and the simulator).

    With ``engine.init(..., mesh=...)`` the scanned span runs SPMD over
    the mesh's client axes: arena rows are resident shards, gathered
    cohorts and per-cohort-slot training partition over the devices,
    and cross-client aggregations lower to per-shard partial reductions
    plus an all-reduce (docs/SHARDING.md; parity pinned by
    ``tests/test_mesh_engine.py`` at mesh sizes {1, 2, 4, 8}).

    Returns the state after ``rounds`` rounds.
    """
    from jax.profiler import TraceAnnotation

    rounds = int(rounds)
    if rounds <= 0:
        return state
    # host spans of the call, tied together by their ``round`` argument
    # (docs/ARCHITECTURE.md, "Tracing a run")
    ids = dict(round=state.round, rounds=rounds)
    with TraceAnnotation("repro.scan.prepare", **ids):
        program = scan_program(state, rounds, unavailable)
    if program is None:
        # all departed/unavailable: the eager path raises per round; the
        # scanned path records the span as skipped no-op rounds
        recs = tuple({"skipped": True, "sampled": 0} for _ in range(rounds))
        return state.replace(round=state.round + rounds,
                             history=state.history + recs)
    fn, carry0, consts, finalize = program
    with TraceAnnotation("repro.scan.dispatch", **ids):
        carry, ys = fn(carry0, consts)
    with TraceAnnotation("repro.scan.finalize", **ids):
        return finalize(state, carry, ys, rounds)


def scan_program(state: ServerState, rounds: int, unavailable=frozenset()):
    """Prepare (but do not run) the jitted multi-round scan behind
    ``run_rounds``: returns ``(fn, carry0, consts, finalize)``, or None
    when the pool is empty (``run_rounds`` records those as skipped
    rounds).

    ``fn(carry0, consts) -> (carry, ys)`` is the cached jitted program
    — all device-resident operands in, all device-resident results out;
    ``finalize(state, carry, ys, rounds)`` is the only host hand-off
    (history records, rebuilt banks). The split exists so the runtime
    sanitizers can make claims about the scan itself: the zero-transfer
    battery warms ``fn`` up, then re-invokes it under
    ``analysis.sanitize.no_transfer()`` to prove the scanned span never
    touches the host, and the compile-budget battery counts ``fn``'s
    XLA compiles across a churn timeline. Raises ``ValueError`` (see
    ``scan_blockers``) when the state cannot scan.
    """
    import jax

    strat = get_strategy(state.strategy)
    ctx = state.ctx
    rounds = int(rounds)
    blocker = scan_blockers(state)
    if blocker is not None:
        raise ValueError(blocker)
    live = state.n_clients - len(state.left)
    # the pool is pow2-padded EXACTLY like the eager sample_clients
    # draw: both paths feed the same uniform shape, so scan-vs-eager
    # cohorts stay bitwise identical while the compiled-program set
    # stays O(log population) under churn
    capw = sampler.pool_capacity(state.n_clients)
    if strat.full_participation:
        pool = sampler.cohort_pool(state.n_clients, state.left, (),
                                   capacity=capw)
        m = int(pool.sum())
    else:
        pool = sampler.cohort_pool(state.n_clients, state.left, unavailable,
                                   capacity=capw)
        m = sampler.cohort_size(ctx.cfg.sample_rate, live, int(pool.sum()))
    if m == 0:
        return None
    carry0, consts, step, finalize, statics = strat.scan_round(
        ctx, state, pool, m)
    structure = jax.tree.structure((carry0, consts))
    shapes = tuple((tuple(l.shape), str(l.dtype))
                   for l in jax.tree.leaves((carry0, consts)))
    # statics are the values the step BAKES INTO ITS TRACE beyond the
    # carry/const shapes (arena raggedness, merge bounds, …) — they must
    # key the cache, or a flipped static would silently reuse a stale
    # compiled scan. The mesh fingerprint is a static too: the step
    # bakes with_sharding_constraint(mesh) into its trace, so a context
    # whose mesh changed must not reuse the old program
    from repro.sharding import specs as shard_specs
    statics = statics + (shard_specs.mesh_fingerprint(ctx.mesh),)
    cache_key = (f"scan:{state.strategy}:{rounds}:{m}:"
                 f"{hash((str(structure), shapes, statics))}")

    # donate the carry off-CPU: the prior state's model/bank/partition
    # buffers roll straight into the scan's carry allocation, so a
    # steady-state span allocates nothing net. Callers already treat
    # the input state as consumed (run_rounds returns the successor
    # state and the parity battery rebinds it); on CPU the input state
    # stays readable, so donation is skipped there.
    donate = jax.default_backend() != "cpu"
    if donate:
        carry0 = _unaliased(carry0, (consts, ctx.init_params))

    def build():
        def scan_fn(c0, cs):
            return jax.lax.scan(lambda c, _: step(c, cs), c0, None,
                                length=rounds)
        return jax.jit(scan_fn, donate_argnums=(0,) if donate else ())

    return ctx.jit(cache_key, build), carry0, consts, finalize


def _buffers(x) -> set:
    """Device buffer addresses behind a jax array (one per shard)."""
    import jax

    if not isinstance(x, jax.Array):
        return set()
    return {s.data.unsafe_buffer_pointer() for s in x.addressable_shards}


def _unaliased(carry, held):
    """``carry`` with a private copy of every leaf whose device buffer is
    also behind ``held`` or an earlier carry leaf. A fresh state's ω IS
    ``ctx.init_params`` — the lazy cluster-model default, the Ψ anchor in
    fp32 and a scan const — and a call that donates a buffer while also
    reading it is refused (``f(donate(a), a)``), besides deleting the
    context's copy. Only aliased leaves are copied, so a warm carry keeps
    donating in place."""
    import jax
    import jax.numpy as jnp

    taken = set()
    for x in jax.tree.leaves(held):
        taken |= _buffers(x)

    def own(x):
        bufs = _buffers(x)
        if bufs & taken:
            x = jnp.copy(x)
            bufs = _buffers(x)
        taken.update(bufs)
        return x

    return jax.tree.map(own, carry)


def scan_history(ys, rounds: int):
    """Convert stacked per-round scan metrics (``{key: (rounds,) array}``)
    into the eager loop's history records (one ``{key: int|float}`` dict
    per round, same key set and value types as ``run_round``'s)."""
    host = {k: np.asarray(v) for k, v in ys.items()}
    recs = []
    for t in range(rounds):
        rec = {}
        for k, v in host.items():
            x = v[t]
            rec[k] = int(x) if np.issubdtype(x.dtype, np.integer) else float(x)
        recs.append(rec)
    return tuple(recs)


def evaluate(state: ServerState, test_sets, true_cluster=None) -> dict:
    """Strategy-appropriate held-out evaluation (paper §4.2 protocol).

    Args:
      test_sets: ``{latent cluster id: batch}`` held-out sets.
      true_cluster: latent cluster per client id — used by clustered
        strategies to route each test set through the learned cluster
        holding most of that latent cluster's clients.

    Returns:
      Dict with at least ``cluster_avg`` (mean per-cluster accuracy);
      StoCFL adds per-cluster and global-model numbers.
    """
    return get_strategy(state.strategy).evaluate(state.ctx, state,
                                                 test_sets, true_cluster)


def join(state: ServerState, batch):
    """Register a newly-arrived client (§5 dynamic membership).

    Appends ``batch`` to the context's client list (and arena, amortized
    O(1) via capacity doubling), assigns the next client id, and lets the
    strategy place the newcomer — StoCFL runs Ψ-inference against the
    existing partition (§4.4), joining the nearest cluster above τ or
    opening a fresh one seeded from the nearest cluster's model.

    Returns:
      (state', new client id).
    """
    return get_strategy(state.strategy).join(state.ctx, state, batch)


def leave(state: ServerState, cid: int) -> ServerState:
    """Remove a client from the federation (§5 departures).

    The client stops being sampled, the clustering partition drops it
    consistently (clusters keep their models — knowledge persists), and
    its arena row is tombstoned (reclaimed in bulk once enough rows die).
    Returns the new state.
    """
    return get_strategy(state.strategy).leave(state.ctx, state, cid)


def infer(state: ServerState, batch) -> dict:
    """Cluster inference for an UNSEEN client (§4.4), without joining:
    which cluster would serve this data, at what Ψ-cosine similarity,
    with which model. Returns ``{"cluster", "seed_from", "similarity",
    "model"}``; raises for strategies with no inference rule."""
    return get_strategy(state.strategy).infer(state.ctx, state, batch)


def infer_batch(state: ServerState, batches) -> list:
    """Batched §4.4 cluster inference: ONE Ψ-extraction + nearest pass
    for many unseen-client batches. All batches must share one pytree
    structure and leaf shapes — they are stacked on a new leading axis,
    the Ψ extractor runs once under ``vmap``, and a single cluster-means
    snapshot scores every (rep, cluster) pair. Returns one
    ``infer``-shaped dict per batch, in submission order; strategies
    without a vectorized rule fall back to a per-batch ``infer`` loop.
    This is the serving router's fast path
    (``repro.serve.Router.route_many``): routing cost amortizes to one
    extractor call per admission wave instead of one per request."""
    return get_strategy(state.strategy).infer_many(state.ctx, state,
                                                   list(batches))
