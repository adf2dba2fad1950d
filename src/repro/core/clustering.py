"""Stochastic federated client clustering (paper §3.2, Algorithm 1 l.4-13).

Server-side state over client distribution representations Ψ(D_i):
  - partition C (union-find over client ids), initially singletons;
  - per round: observe Ψ of newly-participating clients, recompute cluster
    mean representations, build the pairwise cosine matrix M (Pallas
    ``cosine_sim`` kernel on TPU), greedily merge every pair with
    M_ij ≥ τ (transitively, via union-find);
  - objective (Eq. 2): Σ_{i<j} cos(Ψ̃_i, Ψ̃_j) — decreases as merging
    removes similar pairs;
  - new-client inference (§4.4): nearest cluster if best cosine ≥ τ, else
    a fresh cluster seeded from the nearest cluster's model.

This is plain host-side logic (numpy); only the similarity matrix is a
device computation. It is the reference implementation and the shimmed
FALLBACK: ``core.device_clustering`` runs the same partition semantics
as jitted device transitions (``EngineConfig.cluster_backend="device"``),
and the parity battery in ``tests/test_device_clustering.py`` holds the
two to the same answers.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.kernels import ops


class UnionFind:
    """Host union-find over client ids (path-halving find, smaller-root-
    wins union — the semantics the device pointer-halving resolve
    mirrors, see ``kernels.ops.resolve_roots``)."""

    def __init__(self):
        self.parent: Dict[int, int] = {}

    def add(self, i: int):
        """Register ``i`` as a singleton (no-op when already present)."""
        self.parent.setdefault(i, i)

    def find(self, i: int) -> int:
        """Root of ``i``'s cluster, compressing the path as it walks."""
        p = self.parent
        while p[i] != i:
            p[i] = p[p[i]]
            i = p[i]
        return i

    def union(self, a: int, b: int) -> bool:
        """Merge a's and b's clusters; returns True when they were
        distinct. The smaller root id always wins, so every root is its
        cluster's minimum member id (an invariant ``remove`` and the
        device backend both rely on)."""
        ra, rb = self.find(a), self.find(b)
        if ra == rb:
            return False
        if rb < ra:
            ra, rb = rb, ra
        self.parent[rb] = ra          # deterministic: smaller id wins
        return True


class ClusterState:
    """The StoCFL server's clustering bookkeeping."""

    def __init__(self, tau: float):
        self.tau = float(tau)
        self.uf = UnionFind()
        self.reps: Dict[int, np.ndarray] = {}       # client id -> Ψ(D_i)
        self.seen: set = set()                      # P in Algorithm 1

    def copy(self) -> "ClusterState":
        """Shallow-structural copy (reps arrays shared — they are never
        mutated in place). Lets the engine's pure transitions fork the
        clustering bookkeeping without touching the input state."""
        new = ClusterState(self.tau)
        new.uf.parent = dict(self.uf.parent)
        new.reps = dict(self.reps)
        new.seen = set(self.seen)
        return new

    # ------------------------------------------------------------- observe
    def observe(self, client_ids: Sequence[int], reps) -> List[int]:
        """Record Ψ for newly-seen clients. Returns the new ids."""
        new = []
        for cid, rep in zip(client_ids, reps):
            self.uf.add(int(cid))
            if cid not in self.seen:
                self.reps[int(cid)] = np.asarray(rep, dtype=np.float32)
                self.seen.add(int(cid))
                new.append(int(cid))
        return new

    # ------------------------------------------------------------- views
    def clusters(self) -> Dict[int, List[int]]:
        """root -> sorted member client ids (only observed clients)."""
        out: Dict[int, List[int]] = {}
        for cid in sorted(self.reps):
            out.setdefault(self.uf.find(cid), []).append(cid)
        return out

    def cluster_means(self) -> Tuple[List[int], np.ndarray]:
        """Ψ̃ per cluster: (roots, (K̃, D) matrix of member means).

        Vectorized (segment-sum over the stacked rep matrix) — the
        per-cluster Python mean loop was O(N) host work per round, a wall
        when thousands of singletons arrive in round 1."""
        cids = sorted(self.reps)
        per = np.fromiter((self.uf.find(c) for c in cids), np.int64, len(cids))
        roots, inv = np.unique(per, return_inverse=True)
        R = np.stack([self.reps[c] for c in cids])
        mat = np.zeros((len(roots), R.shape[1]), np.float32)
        np.add.at(mat, inv, R)
        mat /= np.bincount(inv).astype(np.float32)[:, None]
        return [int(r) for r in roots], mat

    def assignment(self) -> Dict[int, int]:
        """{client id: cluster root} over observed clients."""
        return {cid: self.uf.find(cid) for cid in self.reps}

    def n_clusters(self) -> int:
        """Current cluster count K̃."""
        return len(self.clusters())

    # ------------------------------------------------------------- merging
    def similarity_matrix(self, pad_to: int = 64) -> Tuple[List[int], np.ndarray]:
        """(roots, K̃×K̃ cosine matrix over cluster means).

        The device computation is padded to a multiple of ``pad_to`` rows
        (zero rows: norm-guarded to similarity 0, sliced off before
        return). Under churn (§5) the cluster count drifts every round,
        and an exact-shape kernel would recompile per K̃ — quantizing the
        shape bounds the compile set the same way the TPU Pallas kernel's
        internal 128-padding already does."""
        roots, means = self.cluster_means()
        k = len(roots)
        if pad_to and k % pad_to:
            kp = -(-k // pad_to) * pad_to
            means = np.concatenate(
                [means, np.zeros((kp - k, means.shape[1]), means.dtype)])
        M = np.asarray(ops.pairwise_cosine(means))
        if M.shape[0] > k and (M[k:, :].any() or M[:k, k:].any()):
            # pad rows are zero-Ψ ghosts whose similarities must be
            # exact 0 — the kernels' norm guard makes them so, the
            # cos(0,0) diagonal included. Should a kernel/guard change
            # ever leak nonzero similarity into the pad block, scrub it
            # here so no scan (this class's or a caller keeping the
            # padded matrix) can turn a ghost into an off-by-pad merge.
            M = M.copy()                     # device output is read-only
            M[k:, :] = 0.0
            M[:, k:] = 0.0
        M = M[:k, :k]
        return roots, M

    def merge_round(self) -> List[Tuple[int, int]]:
        """One greedy merge pass (Algorithm 1, lines 10-13).

        Returns the list of (root_kept, root_absorbed) merges actually
        performed — the trainer uses it to merge cluster models."""
        if len(self.reps) < 2:
            return []
        roots, M = self.similarity_matrix()
        # vectorized pair scan: threshold the whole matrix at once, then
        # union only the qualifying pairs in the same row-major order the
        # original O(K̃²) Python loop visited them (merge list unchanged).
        iu, ju = np.nonzero(np.triu(M >= self.tau, k=1))
        merges = []
        for i, j in zip(iu.tolist(), ju.tolist()):
            ra, rb = self.uf.find(roots[i]), self.uf.find(roots[j])
            if ra != rb:
                keep, absorb = min(ra, rb), max(ra, rb)
                self.uf.union(keep, absorb)
                merges.append((keep, absorb))
        return merges

    # ------------------------------------------------------------- metrics
    def objective(self) -> float:
        """Eq. 2: Σ_{i<j} cos(Ψ̃^{(i)}, Ψ̃^{(j)}) over current clusters."""
        if self.n_clusters() < 2:
            return 0.0
        _, M = self.similarity_matrix()
        iu = np.triu_indices(M.shape[0], k=1)
        return float(np.sum(M[iu]))

    # ------------------------------------------------------------- departure
    def remove(self, cid: int) -> Dict[int, int]:
        """Drop a departed client from reps/seen AND the union-find so
        ``cluster_means()``/``assignment()`` and root lookups stay
        consistent. Each affected cluster is re-rooted at its smallest
        remaining member id; returns {old_root: new_root} for clusters
        whose root changed, so callers can remap cluster-model keys.
        (A cluster emptied by the departure simply disappears from the
        partition; its model is the caller's to keep or drop.)"""
        cid = int(cid)
        groups: Dict[int, List[int]] = {}
        for i in self.uf.parent:
            groups.setdefault(self.uf.find(i), []).append(i)
        self.reps.pop(cid, None)
        self.seen.discard(cid)
        if cid not in self.uf.parent:
            return {}
        parent: Dict[int, int] = {}
        remap: Dict[int, int] = {}
        for root, members in groups.items():
            members = [m for m in members if m != cid]
            if not members:
                continue
            new_root = min(members)
            if new_root != root:
                remap[root] = new_root
            for m in members:
                parent[m] = new_root
        self.uf.parent = parent
        return remap

    # ------------------------------------------------------------- inference
    def nearest(self, rep) -> Tuple[Optional[int], Optional[int], float]:
        """Shared nearest-cluster-by-Ψ lookup (§4.4).

        Returns (root or None, nearest_root, best cosine): root is the
        nearest cluster iff its cosine clears τ; nearest_root is the
        nearest cluster regardless (the seed donor when opening a fresh
        cluster). Both None when no client has been observed yet."""
        if not self.reps:
            return None, None, 0.0
        roots, means = self.cluster_means()
        rep = np.asarray(rep, np.float32)
        rn = rep / (np.linalg.norm(rep) + 1e-12)
        mn = means / (np.linalg.norm(means, axis=1, keepdims=True) + 1e-12)
        sims = mn @ rn
        best = int(np.argmax(sims))
        root = roots[best] if sims[best] >= self.tau else None
        return root, roots[best], float(sims[best])

    def infer(self, rep) -> Tuple[Optional[int], float]:
        """§4.4: nearest cluster for a new client's Ψ.

        Returns (root or None, best cosine). None ⇒ caller should open a
        new cluster (seeding its model from the nearest cluster)."""
        root, _, sim = self.nearest(rep)
        return root, sim


def adjusted_rand_index(labels_a: Sequence[int], labels_b: Sequence[int]) -> float:
    """ARI between two clusterings (for validating cluster recovery)."""
    a = np.asarray(labels_a)
    b = np.asarray(labels_b)
    n = len(a)
    ua, ia = np.unique(a, return_inverse=True)
    ub, ib = np.unique(b, return_inverse=True)
    cont = np.zeros((len(ua), len(ub)), dtype=np.int64)
    np.add.at(cont, (ia, ib), 1)
    comb = lambda x: x * (x - 1) // 2
    sum_ij = comb(cont).sum()
    sum_a = comb(cont.sum(axis=1)).sum()
    sum_b = comb(cont.sum(axis=0)).sum()
    total = comb(n)
    expected = sum_a * sum_b / total if total else 0.0
    max_idx = (sum_a + sum_b) / 2
    if max_idx == expected:
        return 1.0
    return float((sum_ij - expected) / (max_idx - expected))
