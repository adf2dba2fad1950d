"""Device-resident stochastic clustering core (Algorithm 1 on device).

The numpy ``ClusterState`` keeps the partition in a Python ``UnionFind``
dict and pays a device→host sync plus an O(K̃²) Python pair scan every
``merge_round`` — fine at tens of clusters, a wall at the ROADMAP's
million-client scale. This module is the same math as one jitted device
program:

  ``DeviceClusterState``  a pytree of three pow2-capacity-padded arrays:
      ``parent``  (capacity,) int32  union-find pointers, row i ↔ client
                  id i; kept FULLY path-compressed (every entry is a
                  root), so root lookup is one vectorized gather
      ``live``    (capacity,) bool   observed and not departed; a
                  departure flips the bit (an arena-style tombstone) —
                  the row's rep stays allocated and is reused on re-join
      ``rep``     (capacity, D) f32  the Ψ(D_i) bank

  transitions (pure, jitted once per pow2 capacity):
      ``observe``      scatter new Ψ rows + self-rooted parents (update
                       count pow2-quantized through a dropped pad index)
      ``merge_round``  cluster means by segment-sum over roots → fused
                       masked-cosine-τ candidate kernel
                       (``kernels.merge_pairs``) → connected components
                       of the candidate graph by min-label propagation
                       with pointer jumping (O(log K̃) steps) → new fully
                       compressed ``parent``
      ``union`` / ``remove``   the §5 join/leave repairs
      ``nearest`` / ``objective``   §4.4 inference and the Eq. 2 metric

The partition semantics are EXACTLY the numpy path's: a merge pass
unions every pair of live clusters with cos(Ψ̃_i, Ψ̃_j) ≥ τ transitively,
i.e. the new partition is the connected components of the τ-threshold
graph over pre-merge cluster means, and every root is its cluster's
smallest member id (the numpy ``keep = min(ra, rb)`` rule). That
equivalence is what the parity battery in
``tests/test_device_clustering.py`` pins down.

``DeviceClusters`` wraps the pytree in the host-facing ``ClusterState``
API (``observe`` / ``merge_round`` / ``nearest`` / ``infer`` /
``remove`` / ``clusters`` / ``assignment`` / ``uf.find``), so the
engine's strategies run unchanged on either backend
(``EngineConfig.cluster_backend``). The wrapper maintains host *mirrors*
of ``parent``/``live`` — pure bookkeeping, refreshed from the small int
arrays a mutating transition already returns — so per-round host
traffic is O(K̃) index ints for the bank keys, never the Ψ matrix, and
the clustering math itself runs transfer-free (see the transfer-guard
test). See ``docs/CLUSTERING.md`` for the full memory model.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops


def _pow2(n: int) -> int:
    """Smallest power of two >= n (capacity quantum, as in ClusterBank)."""
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


class DeviceClusterState(NamedTuple):
    """The clustering server as a device pytree (row i ↔ client id i)."""

    parent: jax.Array   # (capacity,) int32, fully compressed union-find
    live: jax.Array     # (capacity,) bool, observed ∧ not departed
    rep: jax.Array      # (capacity, D) float32 Ψ bank (dead rows zeroed)


def init_state(capacity: int, dim: int) -> DeviceClusterState:
    """Fresh all-singleton state: every row self-rooted, nothing live."""
    cap = _pow2(capacity)
    return DeviceClusterState(
        parent=jnp.arange(cap, dtype=jnp.int32),
        live=jnp.zeros((cap,), bool),
        rep=jnp.zeros((cap, dim), jnp.float32))


def grow(state: DeviceClusterState, capacity: int) -> DeviceClusterState:
    """Double (pow2) the row capacity — the churn-cheap analogue of
    ``ClientArena.grow``: new rows are self-rooted, dead, zero-Ψ."""
    old = state.parent.shape[0]
    cap = _pow2(max(capacity, old))
    if cap == old:
        return state
    return DeviceClusterState(
        parent=jnp.concatenate(
            [state.parent, jnp.arange(old, cap, dtype=jnp.int32)]),
        live=jnp.concatenate([state.live, jnp.zeros((cap - old,), bool)]),
        rep=jnp.concatenate(
            [state.rep,
             jnp.zeros((cap - old, state.rep.shape[1]), jnp.float32)]))


# ----------------------------------------------------------- jitted math
def _cluster_means(state: DeviceClusterState, mesh=None):
    """(root, means, counts): per-row resolved root (dead rows → the
    scratch segment ``cap``), per-root-row member-mean Ψ̃ and member
    count (zero for non-root rows). The member sums are one row segment
    sum over the Ψ bank (``kernels.ops.segment_rows``); ``mesh`` is the
    client mesh a calling program is partitioned over."""
    cap = state.parent.shape[0]
    root = ops.resolve_roots(state.parent)
    seg = jnp.where(state.live, root, cap)
    sums = ops.segment_rows(state.rep, seg, state.live, mesh=mesh)
    counts = jax.ops.segment_sum(
        state.live.astype(jnp.float32), seg, num_segments=cap + 1)[:cap]
    means = sums / jnp.maximum(counts, 1.0)[:, None]
    return root, means, counts


def component_labels(adj, steps: Optional[int] = None):
    """Connected-component labels of a 0/1 adjacency matrix: each node's
    label converges to the smallest node id in its component.

    Min-label propagation with pointer jumping, run to a FIXED POINT
    (``lax.while_loop`` until a full pass changes no label): per pass
    every node takes the min over its neighbours' labels, then follows
    its own label's label (``label <- label[label]``). At a fixed point
    adjacent nodes hold equal labels (each is ≤ the other's), labels
    never leave their component, and the common value must be the
    component minimum — so the exit condition IS the correctness proof.
    The jumping makes well-ordered graphs close in O(log N) passes; a
    fixed step count alone is NOT safe (an adversarially permuted chain
    needs more — the regression tests pin this), which is why the
    data-dependent loop is the default. ``steps`` forces an explicit
    pass count instead (tests/benchmarks only). All shapes static: this
    is the jittable union of Algorithm 1's whole merge pass."""
    n = adj.shape[0]
    ids = jnp.arange(n, dtype=jnp.int32)

    def one_pass(label):
        neigh = jnp.min(jnp.where(adj > 0, label[None, :], n), axis=1)
        label = jnp.minimum(label, neigh.astype(label.dtype))
        return jnp.take(label, label)

    if steps is not None:
        return jax.lax.fori_loop(0, steps, lambda _, l: one_pass(l), ids)
    return jax.lax.while_loop(
        lambda c: jnp.any(c[0] != c[1]),
        lambda c: (c[1], one_pass(c[1])),
        (jnp.full((n,), -1, jnp.int32), ids))[1]


def state_mesh(state: DeviceClusterState):
    """The mesh a state's Ψ bank lies on, or None on one device.

    A ``run_rounds`` span on a client mesh hands back its carry's bank
    replicated over every chip of the mesh. A jitted program that takes
    such a state runs the Pallas kernels of ``_cluster_means`` and
    ``merge_pairs`` per chip under ``shard_map`` over this mesh: GSPMD
    cannot partition a Mosaic kernel."""
    sharding = state.rep.sharding
    if len(sharding.device_set) < 2:
        return None
    if isinstance(sharding, jax.sharding.NamedSharding):
        return sharding.mesh
    devices = sorted(sharding.device_set, key=lambda d: d.id)
    return jax.sharding.Mesh(np.array(devices), ("clients",))


@functools.lru_cache(maxsize=None)
def _jit_cluster_means(mesh=None):
    """Jitted ``_cluster_means`` (memoized wrapper, one compile per
    capacity and mesh)."""
    return jax.jit(functools.partial(_cluster_means, mesh=mesh))


@functools.lru_cache(maxsize=None)
def _jit_observe():
    """(state, idx (P,), reps (P, D)) -> state'. Pad idx entries point at
    ``capacity`` and are dropped by the scatter, so the compiled shape
    set is quantized in P (pow2) like ``ClusterBank.put``."""

    def run(state, idx, reps):
        return DeviceClusterState(
            parent=state.parent.at[idx].set(idx.astype(state.parent.dtype),
                                            mode="drop"),
            live=state.live.at[idx].set(True, mode="drop"),
            rep=state.rep.at[idx].set(reps.astype(state.rep.dtype),
                                      mode="drop"))

    return jax.jit(run)


def merge_round_impl(state: DeviceClusterState, tau: float, k_max: int,
                     mesh=None):
    """Traceable body of one fused merge pass:
    ``(state, tau, static k_max) -> (state', roots (k_max,), new_roots
    (k_max,), counts (k_max,))``. ``mesh`` is the client mesh a calling
    program is partitioned over (see ``kernels.ops.merge_pairs``).

    One device program for Algorithm 1 lines 10-13: means → live-root
    compaction → fused masked-cosine-τ candidates → components →
    compressed parents. ``k_max`` (static, the caller's pow2-quantized
    live-cluster bound ≤ capacity) sizes the candidate matrix: the
    pairwise work is O(k_max²), not O(capacity²), so a settled
    4096-capacity federation with 4 clusters pays a 4-row scan — the
    compaction happens on device (``jnp.nonzero`` with a static size),
    so nothing crosses the host boundary. The three returned k_max-row
    arrays (pre-merge live roots ascending, their post-merge roots,
    their member counts; pads = capacity / 0) are ALL the host needs to
    re-key the host-indexed ``ClusterBank`` and refresh its mirror —
    O(K̃) ints, never a capacity-length array, never the Ψ matrix.

    The resulting partition is identical for ANY sufficient ``k_max``
    (pads are masked out of the candidate kernel and isolated in the
    component graph) — which is why the ``run_rounds`` scan can inline
    this with the static ``k_max = capacity`` while the eager wrapper
    compacts to the live-cluster count, and still land bitwise-equal
    parents."""
    cap = state.parent.shape[0]
    ids = jnp.arange(cap, dtype=jnp.int32)
    root, means, counts = _cluster_means(state, mesh)
    # live-root rows, ascending (so compact row order = root-id
    # order and a min row index IS the min root id); pads → cap
    (rows,) = jnp.nonzero(counts > 0, size=k_max, fill_value=cap)
    rows = rows.astype(jnp.int32)
    means_ext = jnp.concatenate(
        [means, jnp.zeros((1, means.shape[1]), means.dtype)])
    counts_c = jnp.take(jnp.concatenate([counts, jnp.zeros(1)]), rows)
    adj = ops.merge_pairs(jnp.take(means_ext, rows, axis=0),
                          counts_c > 0, tau, mesh=mesh)
    # steady-state rounds have no candidate pair at all — skip the
    # O(log K̃) propagation entirely instead of running it on an
    # empty graph (the common case once the partition settles)
    label = jax.lax.cond(jnp.any(adj > 0), component_labels,
                         lambda a: jnp.arange(a.shape[0],
                                              dtype=jnp.int32), adj)
    # back to root-id space: compact row i's cluster re-roots at the
    # root id of its component's min row; scatter builds the
    # {old root: new root} map over all capacity rows
    new_root_c = jnp.where(rows < cap, jnp.take(rows, label),
                           jnp.int32(cap))
    mapped = ids.at[rows].set(new_root_c, mode="drop")
    new_root = jnp.take(mapped, root, mode="clip")
    parent = jnp.where(state.live, new_root, ids)
    return (DeviceClusterState(parent=parent, live=state.live,
                               rep=state.rep),
            rows, new_root_c, counts_c)


@functools.lru_cache(maxsize=None)
def _jit_merge_round(tau: float, k_max: int, mesh=None):
    """Jitted ``merge_round_impl`` (one compile per (τ, k_max, mesh))."""
    return jax.jit(functools.partial(merge_round_impl, tau=tau, k_max=k_max,
                                     mesh=mesh))


@functools.lru_cache(maxsize=None)
def _jit_union():
    """(state, a, b) -> state': merge a's and b's clusters, smaller root
    wins (the §4.4 join placement)."""

    def run(state, a, b):
        root = ops.resolve_roots(state.parent)
        ra, rb = root[a], root[b]
        keep, absorb = jnp.minimum(ra, rb), jnp.maximum(ra, rb)
        return state._replace(parent=jnp.where(root == absorb, keep, root))

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _jit_remove():
    """(state, cid) -> (state', old_root, new_root, n_left): tombstone a
    departed client's row and re-root its cluster at the smallest
    remaining member (``new_root == capacity`` when none remain)."""

    def run(state, cid):
        cap = state.parent.shape[0]
        ids = jnp.arange(cap, dtype=jnp.int32)
        root = ops.resolve_roots(state.parent)
        r = root[cid]
        stay = state.live & (root == r) & (ids != cid)
        n_left = jnp.sum(stay)
        new_root = jnp.min(jnp.where(stay, ids, cap))
        parent = jnp.where(stay, new_root.astype(root.dtype), root)
        parent = parent.at[cid].set(cid)
        return (DeviceClusterState(parent=parent,
                                   live=state.live.at[cid].set(False),
                                   rep=state.rep.at[cid].set(0.0)),
                r, new_root, n_left)

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _jit_nearest(mesh=None):
    """(state, query) -> (best root, best cosine, live-cluster count):
    §4.4 nearest-cluster-by-Ψ, dead rows masked to −inf. ``mesh``: the
    state's (``state_mesh``)."""

    def run(state, query):
        _, means, counts = _cluster_means(state, mesh)
        qn = query / (jnp.linalg.norm(query) + 1e-12)
        mn = means / (jnp.linalg.norm(means, axis=1, keepdims=True) + 1e-12)
        sims = jnp.where(counts > 0, mn @ qn, -jnp.inf)
        best = jnp.argmax(sims)
        return best, sims[best], jnp.sum(counts > 0)

    return jax.jit(run)


def objective_impl(state: DeviceClusterState, k_max: int, mesh=None):
    """Traceable Eq. 2 objective Σ_{i<j} cos(Ψ̃_i, Ψ̃_j) over live
    clusters (0 with fewer than two). ``k_max`` (static live-cluster
    bound) compacts the pairwise work to O(k_max²), same as the merge
    pass — a settled big-capacity federation pays a K̃′² matrix, not a
    capacity² one. The ``run_rounds`` scan inlines this with
    ``k_max = capacity``. ``mesh``: as in ``merge_round_impl``."""
    cap = state.parent.shape[0]
    _, means, counts = _cluster_means(state, mesh)
    (rows,) = jnp.nonzero(counts > 0, size=k_max, fill_value=cap)
    means_ext = jnp.concatenate(
        [means, jnp.zeros((1, means.shape[1]), means.dtype)])
    mc = jnp.take(means_ext, rows, axis=0).astype(jnp.float32)
    live_c = jnp.take(jnp.concatenate([counts, jnp.zeros(1)]), rows) > 0
    norms = jnp.linalg.norm(mc, axis=1, keepdims=True)
    mn = jnp.where(norms > 0, mc / norms, 0.0)
    M = mn @ mn.T
    k_ids = jnp.arange(k_max)
    pairs = (live_c[:, None] & live_c[None, :]
             & (k_ids[:, None] < k_ids[None, :]))
    return jnp.sum(jnp.where(pairs, M, 0.0))


@functools.lru_cache(maxsize=None)
def _jit_objective(k_max: int, mesh=None):
    """Jitted ``objective_impl`` (one compile per (k_max, mesh))."""
    return jax.jit(functools.partial(objective_impl, k_max=k_max, mesh=mesh))


def objective_closed_impl(state: DeviceClusterState, mesh=None):
    """Eq. 2 as the closed form ``(‖Σ m̂‖² − Σ ‖m̂‖²)/2`` over the live
    clusters' normalized means — O(capacity·D), no pairwise matrix and
    no live-cluster compaction, so the reduction SHAPE depends only on
    the (pow2) capacity. That shape-stability is why the engine's
    per-round objective metric uses this form on the device backend:
    the eager loop and the ``run_rounds`` scan then record bitwise-equal
    trajectories, while the cost stays linear in capacity instead of
    the pairwise k_max². (Same quantity as ``objective_impl`` up to
    float association; exact 0.0 with fewer than two clusters.)
    ``mesh``: as in ``merge_round_impl``."""
    _, means, counts = _cluster_means(state, mesh)
    norms = jnp.linalg.norm(means, axis=1, keepdims=True)
    mn = jnp.where((counts[:, None] > 0) & (norms > 0), means / norms, 0.0)
    s = jnp.sum(mn, axis=0)
    return (jnp.sum(s * s) - jnp.sum(mn * mn)) / 2.0


@functools.lru_cache(maxsize=None)
def _jit_objective_closed(mesh=None):
    """Jitted ``objective_closed_impl`` (one compile per capacity and
    mesh)."""
    return jax.jit(functools.partial(objective_closed_impl, mesh=mesh))


def objective_closed(state: DeviceClusterState) -> float:
    """Host wrapper for ``objective_closed_impl`` (the engine's eager
    device-backend metric call)."""
    return float(_jit_objective_closed(state_mesh(state))(state))


# public jitted-transition aliases (the DeviceClusterState-level API)
def observe(state: DeviceClusterState, idx, reps) -> DeviceClusterState:
    """Record Ψ rows for client ids ``idx`` (pad entries = capacity are
    dropped); rows become live, self-rooted singletons."""
    return _jit_observe()(state, idx, reps)


def merge_round(state: DeviceClusterState, tau: float,
                k_max: Optional[int] = None):
    """One fused merge pass; returns (state', pre-merge live roots,
    their post-merge roots, their member counts) — three k_max-row
    device arrays (pads = capacity / 0).

    ``k_max`` (static) bounds the live-cluster count and sizes the
    O(k_max²) candidate matrix; default: the full capacity (always
    safe). Callers that track K̃ pass its pow2 quantization."""
    cap = int(state.parent.shape[0])
    k_max = cap if k_max is None else min(_pow2(k_max), cap)
    return _jit_merge_round(float(tau), k_max, state_mesh(state))(state)


def nearest(state: DeviceClusterState, query):
    """(best root row, best cosine, live-cluster count) for a Ψ query."""
    return _jit_nearest(state_mesh(state))(state, query)


def infer(state: DeviceClusterState, query, tau: float):
    """§4.4 as device values: (best root, cosine, cleared-τ flag)."""
    best, sim, n = nearest(state, query)
    return best, sim, (n > 0) & (sim >= tau)


# ================================================================ wrapper
class _RepsView:
    """Read-only mapping view of the Ψ bank keyed by live client id —
    the ``ClusterState.reps`` surface (membership tests, checkpoint
    iteration) without materializing a host dict."""

    def __init__(self, owner: "DeviceClusters"):
        self._o = owner

    def __contains__(self, cid) -> bool:
        """True when ``cid`` has been observed and has not departed."""
        return int(cid) in self._o.seen

    def __iter__(self):
        """Live client ids, ascending."""
        return iter(sorted(self._o.seen))

    def __len__(self) -> int:
        """Number of live observed clients."""
        return len(self._o.seen)

    def __getitem__(self, cid) -> np.ndarray:
        """One client's Ψ row (pulled to host)."""
        if int(cid) not in self._o.seen:
            raise KeyError(cid)
        return np.asarray(self._o._state.rep[int(cid)])

    def items(self):
        """(cid, Ψ row) pairs — the checkpoint-save iteration."""
        return ((c, self[c]) for c in self)


class _UFView:
    """``ClusterState.uf``-shaped view: ``find`` reads the host parent
    mirror (the device array is always fully compressed, so the mirror
    IS the root table); ``union`` runs the jitted device transition."""

    def __init__(self, owner: "DeviceClusters"):
        self._o = owner

    def find(self, i: int) -> int:
        """Root (= cluster id) of client ``i``."""
        return int(self._o._parent[int(i)])

    def union(self, a: int, b: int) -> bool:
        """Merge a's and b's clusters (smaller root wins); True if they
        were distinct."""
        return self._o._union(int(a), int(b))

    @property
    def parent(self) -> Dict[int, int]:
        """{observed client id: root} — the numpy ``UnionFind.parent``
        dict surface (host mirror; for checkpoint/tests)."""
        return {int(c): int(self._o._parent[c]) for c in sorted(self._o.seen)}


class DeviceClusters:
    """Host-facing wrapper: the ``ClusterState`` API over a
    ``DeviceClusterState`` pytree.

    Drop-in for the numpy backend everywhere the engine touches the
    partition. Mutating methods replace ``self._state`` with the jitted
    transition's output (arrays are immutable, so ``copy()`` is O(1)
    structural sharing, exactly like ``ClusterState.copy``); the host
    mirrors (``_parent`` ndarray, ``seen`` set) are refreshed from the
    transition's small integer outputs so reads (``uf.find``,
    ``clusters()``, ``assignment()``) never touch the device."""

    def __init__(self, tau: float, capacity: int = 0, dim: int = 0):
        self.tau = float(tau)
        self._capacity_hint = max(int(capacity), 1)
        self._state: Optional[DeviceClusterState] = None
        if dim:
            self._state = init_state(self._capacity_hint, int(dim))
        self.seen: set = set()
        self._parent = np.arange(self.capacity, dtype=np.int64)

    # ----------------------------------------------------------- plumbing
    @property
    def capacity(self) -> int:
        """Allocated union-find rows (power of two; grows on demand)."""
        if self._state is None:
            return _pow2(self._capacity_hint)
        return int(self._state.parent.shape[0])

    @property
    def state(self) -> Optional[DeviceClusterState]:
        """The underlying device pytree (None until first ``observe``)."""
        return self._state

    @property
    def uf(self) -> _UFView:
        """Union-find view (``find`` / ``union`` / ``parent``)."""
        return _UFView(self)

    @property
    def reps(self) -> _RepsView:
        """Mapping view of live clients' Ψ rows."""
        return _RepsView(self)

    def copy(self) -> "DeviceClusters":
        """Structural copy: device arrays shared (immutable), host
        mirrors duplicated — the engine's pure-transition fork."""
        new = object.__new__(DeviceClusters)
        new.tau = self.tau
        new._capacity_hint = self._capacity_hint
        new._state = self._state
        new.seen = set(self.seen)
        new._parent = self._parent.copy()
        return new

    def _ensure(self, n_ids: int, dim: int) -> None:
        """Allocate/grow so row ``n_ids - 1`` exists (pow2 capacity)."""
        if self._state is None:
            self._state = init_state(max(self._capacity_hint, n_ids),
                                     int(dim))
        elif n_ids > self.capacity:
            self._state = grow(self._state, n_ids)
        if len(self._parent) < self.capacity:
            self._parent = np.concatenate(
                [self._parent,
                 np.arange(len(self._parent), self.capacity)])

    def _union(self, a: int, b: int) -> bool:
        ra, rb = int(self._parent[a]), int(self._parent[b])
        if ra == rb:
            return False
        self._state = _jit_union()(self._state, jnp.int32(a), jnp.int32(b))
        keep, absorb = min(ra, rb), max(ra, rb)
        self._parent[self._parent == absorb] = keep
        return True

    # ------------------------------------------------------------ observe
    def observe(self, client_ids: Sequence[int], reps) -> List[int]:
        """Record Ψ for newly-seen clients (one quantized device
        scatter; already-seen ids are skipped). Returns the new ids."""
        new, take, batch_seen = [], [], set()
        for i, cid in enumerate(client_ids):
            cid = int(cid)
            if cid not in self.seen and cid not in batch_seen:
                new.append(cid)
                take.append(i)
                batch_seen.add(cid)
        if not new:
            return []
        if hasattr(reps, "ndim") and getattr(reps, "ndim", 0) == 2:
            rows = [reps[i] for i in take]
        else:
            reps = list(reps)
            rows = [reps[i] for i in take]
        stacked = jnp.stack([jnp.asarray(r, jnp.float32) for r in rows])
        self._ensure(max(new) + 1, stacked.shape[1])
        cap = self.capacity
        p = _pow2(len(new))
        idx = np.full(p, cap, np.int32)          # pad writes are dropped
        idx[: len(new)] = new
        if p > len(new):
            stacked = jnp.concatenate(
                [stacked, jnp.zeros((p - len(new), stacked.shape[1]),
                                    stacked.dtype)])
        self._state = observe(self._state, jnp.asarray(idx), stacked)
        self.seen.update(new)
        self._parent[new] = new
        return new

    # -------------------------------------------------------------- views
    def clusters(self) -> Dict[int, List[int]]:
        """root -> sorted member client ids (live clients only)."""
        out: Dict[int, List[int]] = {}
        for cid in sorted(self.seen):
            out.setdefault(int(self._parent[cid]), []).append(cid)
        return out

    def assignment(self) -> Dict[int, int]:
        """{client id: root} over live observed clients."""
        return {cid: int(self._parent[cid]) for cid in self.seen}

    def n_clusters(self) -> int:
        """Live cluster count."""
        return len({int(self._parent[c]) for c in self.seen})

    def cluster_means(self) -> Tuple[List[int], np.ndarray]:
        """(sorted roots, (K̃, D) member-mean matrix) — host pull of the
        device segment means, numpy-API-shaped for tests/tools."""
        roots = sorted({int(self._parent[c]) for c in self.seen})
        _, means, _ = _jit_cluster_means(state_mesh(self._state))(
            self._state)
        return roots, np.asarray(means)[np.asarray(roots, np.int64)]

    def similarity_matrix(self) -> Tuple[List[int], np.ndarray]:
        """(sorted roots, K̃×K̃ cosine matrix over cluster means)."""
        roots, means = self.cluster_means()
        m32 = means.astype(np.float32)
        norms = np.linalg.norm(m32, axis=1, keepdims=True)
        mn = np.where(norms > 0, m32 / np.maximum(norms, 1e-30), 0.0)
        return roots, mn @ mn.T

    # ------------------------------------------------------------- merging
    def merge_round(self) -> List[Tuple[int, int]]:
        """One fused device merge pass (Algorithm 1 lines 10-13).

        Returns (root_kept, root_absorbed) merges in the NORMALIZED form
        (component_min, member): the same final partition as the numpy
        scan (both are the τ-graph's transitive closure), and the same
        downstream ``ClusterBank.merge`` result bitwise — the bank
        reconstructs merge GROUPS from the list's own transitive
        closure, so any list with the same closure aggregates
        identically (pinned by the chain-topology test). The list
        itself can differ from the numpy scan's visit order on
        chain-topology graphs where a scan's intermediate keep is not
        the component min. Host traffic: the two k_max-row root arrays
        the jitted pass returns — O(K̃) ints, independent of capacity."""
        if len(self.seen) < 2:
            return []
        st, rows, new_roots, _counts = merge_round(self._state, self.tau,
                                                   k_max=self.n_clusters())
        self._state = st
        cap = self.capacity
        rows = np.asarray(rows).astype(np.int64)
        new_roots = np.asarray(new_roots).astype(np.int64)
        valid = rows < cap
        rows, new_roots = rows[valid], new_roots[valid]
        merges = [(int(f), int(r)) for r, f in zip(rows, new_roots)
                  if f != r]
        # mirror refresh: every live client's pre-merge root is one of
        # ``rows`` (ascending), so one searchsorted maps it to its
        # post-merge root — no capacity-length device pull
        live = np.fromiter(self.seen, np.int64, len(self.seen))
        pre = self._parent[live]
        self._parent[live] = new_roots[np.searchsorted(rows, pre)]
        return sorted(merges)

    # ------------------------------------------------------------- metrics
    def objective(self) -> float:
        """Eq. 2: Σ_{i<j} cos(Ψ̃^{(i)}, Ψ̃^{(j)}) over live clusters
        (pairwise form, compacted to the pow2 live-cluster count; the
        engine's per-round metric instead uses the shape-stable
        ``objective_closed`` so eager and scanned loops agree
        bitwise)."""
        k = self.n_clusters()
        if k < 2:
            return 0.0
        k_max = min(_pow2(k), self.capacity)
        return float(_jit_objective(k_max, state_mesh(self._state))(
            self._state))

    # ----------------------------------------------------------- departure
    def remove(self, cid: int) -> Dict[int, int]:
        """Tombstone a departed client's row (§5) and re-root its
        cluster at the smallest remaining member. Returns
        {old_root: new_root} when the root changed (the bank re-key)."""
        cid = int(cid)
        if cid not in self.seen:
            return {}
        st, r, new_root, n_left = _jit_remove()(self._state, jnp.int32(cid))
        self._state = st
        self.seen.discard(cid)
        r, new_root, n_left = int(r), int(new_root), int(n_left)
        remap = {}
        if n_left and new_root != r:
            self._parent[self._parent == r] = new_root
            remap = {r: new_root}
        # the departed row itself re-roots to cid AFTER the remap mask,
        # so the mirror never reports it as a member of the re-rooted
        # cluster (it must match the device array exactly)
        self._parent[cid] = cid
        return remap

    # ----------------------------------------------------------- inference
    def nearest(self, rep) -> Tuple[Optional[int], Optional[int], float]:
        """§4.4 nearest-cluster-by-Ψ: (root above τ or None, nearest
        root regardless, best cosine)."""
        if not self.seen:
            return None, None, 0.0
        best, sim, _n = nearest(self._state, jnp.asarray(rep, jnp.float32))
        best, sim = int(best), float(sim)
        return (best if sim >= self.tau else None), best, sim

    def infer(self, rep) -> Tuple[Optional[int], float]:
        """§4.4: (nearest root above τ or None, best cosine)."""
        root, _, sim = self.nearest(rep)
        return root, sim

    # -------------------------------------------------------- serialization
    def arrays(self) -> Dict[str, np.ndarray]:
        """Host copies of the pytree (checkpoint payload); empty state
        serializes as zero-capacity arrays."""
        if self._state is None:
            return {"parent": np.zeros(0, np.int32),
                    "live": np.zeros(0, bool),
                    "rep": np.zeros((0, 0), np.float32)}
        return {"parent": np.asarray(self._state.parent),
                "live": np.asarray(self._state.live),
                "rep": np.asarray(self._state.rep)}

    @classmethod
    def from_arrays(cls, tau: float, parent, live, rep) -> "DeviceClusters":
        """Rebuild from checkpointed host arrays (exact mirror restore):
        all three are uploaded."""
        return cls.from_state(tau, DeviceClusterState(
            parent=jnp.asarray(parent, jnp.int32),
            live=jnp.asarray(live, bool),
            rep=jnp.asarray(rep, jnp.float32)), parent, live)

    @classmethod
    def from_state(cls, tau: float, state: DeviceClusterState, parent,
                   live) -> "DeviceClusters":
        """Wrap a device state as it is, with no copy. ``parent`` and
        ``live`` are host copies of ``state.parent`` and ``state.live``:
        the only arrays the host mirrors (``_parent``, ``seen``) read,
        so the (capacity, D) Ψ bank never crosses to the host."""
        out = cls(tau, capacity=max(len(parent), 1))
        if len(parent):
            out._state = state
            out.seen = {int(i) for i in np.nonzero(np.asarray(live))[0]}
            out._parent = np.asarray(parent).astype(np.int64)
        return out

    def __repr__(self) -> str:
        return (f"DeviceClusters(tau={self.tau}, capacity={self.capacity}, "
                f"live={len(self.seen)}, k={self.n_clusters()})")


def make_cluster_state(tau: float, backend: str = "numpy",
                       capacity: int = 0):
    """Factory for the engine: ``"numpy"`` → host ``ClusterState``
    (shimmed fallback), ``"device"`` → ``DeviceClusters``."""
    if backend == "device":
        return DeviceClusters(tau, capacity=capacity)
    if backend == "numpy":
        from repro.core.clustering import ClusterState
        return ClusterState(tau)
    raise ValueError(f"unknown cluster_backend {backend!r} "
                     "(expected 'numpy' or 'device')")
