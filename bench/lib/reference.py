"""Plain StoCFL reference: Algorithm 1 of arXiv 2303.00897, written from
the paper's description with nothing of the program imported.

Per round, from the sampling key ``PRNGKey(engine seed)``:

  1. cohort: split the key, draw a uniform per client id over the
     power-of-two pool (ids past the population get +inf), and take the
     ``m = ceil(rate * N)`` smallest (a uniform draw without replacement);
  2. Psi of each never-seen cohort member: the L2-normalised gradient of
     the loss at the frozen initial weights w0 over its whole shard; the
     client becomes a singleton cluster rooted at its own id;
  3. merge pass (whenever a client was new this round or the last pass
     merged something): clusters whose Psi means have cosine >= tau are
     joined transitively, each component rooted at its smallest id, and
     its model is the member-count-weighted mean of its clusters' models
     (w0 for a cluster that has none yet);
  4. local update of every cohort member from its cluster's model theta
     (w0 if none) and the global omega, E full-batch steps of
         theta <- theta - lr (grad f(theta) + lam (theta - omega))
         omega <- omega - lr grad f(omega);
  5. omega <- the size-weighted mean of the members' omegas; each touched
     cluster's model <- the size-weighted mean of its members' thetas.

The loss is the mean cross-entropy over every target: one per example
for (B, C) logits, one per position for (B, T, V) logits. ``apply(params,
x, frozen)`` gives the logits; ``frozen`` is a configuration's frozen
tree (None where it has none), passed to every compiled program as an
argument, never closed over, so no program holds it as a constant.
``client_block`` runs the Psi and the local updates of a round's clients
in blocks of that many (``lax.map`` over blocks of a vmap), so that the
reference fits at a model's full widths; None runs the whole cohort as
one vmap.

Float work runs in float32 at the highest matmul precision. The cosines
of the merge pass come from a float64 Gram matrix of the Psi rows on the
host, so no (K, D) product of cluster means is ever formed.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def _pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (int(n) - 1).bit_length()


class Reference:
    STRATEGY = "stocfl"

    def __init__(self, apply, training: dict, x, y, omega0, engine_seed: int,
                 sample_rate: float, frozen=None, client_block=None):
        self.x, self.y, self.frozen = x, y, frozen
        self.n = int(x.shape[0])
        self.cap = _pow2(self.n)
        self.m = min(int(math.ceil(float(sample_rate) * self.n)), self.n)
        self.tau = float(training["tau"])
        lr, lam = float(training["lr"]), float(training["lam"])
        steps = int(training["local_steps"])
        self.omega0 = jax.tree.map(jnp.asarray, omega0)
        self.omega = self.omega0
        self.models: Dict[int, object] = {}
        self.parent = np.arange(self.n, dtype=np.int64)
        self.live = np.zeros(self.n, bool)
        self.settled = False
        self.gram = np.zeros((self.n, self.n), np.float64)
        self.key = jax.random.PRNGKey(int(engine_seed))
        self.sizes = np.full(self.n, float(y.shape[1]), np.float32)
        pool = np.zeros(self.cap, bool)
        pool[: self.n] = True
        self.pool = jnp.asarray(pool)
        dim = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(omega0))
        self.bank = jnp.zeros((self.cap, dim), jnp.float32)

        def loss(p, xb, yb, fz):
            logits = apply(p, xb, fz).astype(jnp.float32)
            logz = jax.scipy.special.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, yb[..., None], axis=-1)[..., 0]
            return jnp.mean(logz - gold)

        grad = jax.grad(loss)
        m = self.m
        block = (None if client_block is None or int(client_block) >= m
                 else int(client_block))

        def draw(key, pool):
            key, sub = jax.random.split(key)
            u = jnp.where(pool, jax.random.uniform(sub, pool.shape), jnp.inf)
            return key, jnp.argsort(u)[:m].astype(jnp.int32)

        def psi(xb, yb, fz):
            g = grad(self.omega0, xb, yb, fz)
            v = jnp.concatenate([jnp.ravel(l).astype(jnp.float32)
                                 for l in jax.tree.leaves(g)])
            nrm = jnp.linalg.norm(v)
            return jnp.where(nrm > 0, v / nrm, v)

        def observe(bank, ids, new, xs, ys, fz):
            if block is None:
                p = jax.vmap(psi, in_axes=(0, 0, None))(xs, ys, fz)
            else:
                p = jax.lax.map(lambda a: psi(*a, fz), (xs, ys),
                                batch_size=block)
            idx = jnp.where(new, ids, self.cap)
            bank = bank.at[idx].set(p, mode="drop")
            return bank, p @ bank.T

        def client(theta, omega, xb, yb, fz):
            for _ in range(steps):
                gt, go = grad(theta, xb, yb, fz), grad(omega, xb, yb, fz)
                theta = jax.tree.map(
                    lambda t, g, o: t - lr * (g + lam * (t - o)),
                    theta, gt, omega)
                omega = jax.tree.map(lambda o, g: o - lr * g, omega, go)
            return theta, omega

        def cohort(thetas, omega, xs, ys, w, seg, fz):
            if block is None:
                th, om = jax.vmap(client, in_axes=(0, None, 0, 0, None))(
                    thetas, omega, xs, ys, fz)
            else:
                th, om = jax.lax.map(
                    lambda a: client(a[0], omega, a[1], a[2], fz),
                    (thetas, xs, ys), batch_size=block)
            wn = w / jnp.sum(w)
            omega = jax.tree.map(
                lambda a: jnp.tensordot(wn, a, axes=1), om)
            den = jax.ops.segment_sum(w, seg, num_segments=m)
            ws = w / den[seg]
            per = jax.tree.map(lambda a: jax.ops.segment_sum(
                a * ws.reshape((-1,) + (1,) * (a.ndim - 1)), seg,
                num_segments=m), th)
            return omega, per

        self._draw = jax.jit(draw)
        self._take = jax.jit(lambda a, i: jnp.take(a, i, axis=0))
        self._observe = jax.jit(observe)
        self._stack = jax.jit(lambda *ts: jax.tree.map(
            lambda *ls: jnp.stack(ls), *ts))
        self._cohort = jax.jit(cohort)
        self._pick = jax.jit(lambda t, k: jax.tree.map(lambda a: a[k], t))

    # ---------------------------------------------------------------- round
    def run(self, rounds: int) -> None:
        with jax.default_matmul_precision("highest"):
            for _ in range(int(rounds)):
                self.round()

    def round(self) -> None:
        self.key, ids = self._draw(self.key, self.pool)
        ids_h = np.asarray(ids).astype(np.int64)
        xs, ys = self._take(self.x, ids), self._take(self.y, ids)
        new = ~self.live[ids_h]
        if new.any():
            self.bank, rows = self._observe(self.bank, ids, jnp.asarray(new),
                                            xs, ys, self.frozen)
            rows = np.asarray(rows, np.float64)[:, : self.n]
            for j in np.nonzero(new)[0]:
                c = ids_h[j]
                self.gram[c, :] = rows[j]
                self.gram[:, c] = rows[j]
            self.live[ids_h[new]] = True
            self.parent[ids_h[new]] = ids_h[new]
        if new.any() or not self.settled:
            self.settled = not self._merge()
        roots = self.parent[ids_h]
        uniq = sorted(set(int(r) for r in roots))
        slot = np.searchsorted(np.asarray(uniq), roots)
        thetas = self._stack(*[self.models.get(int(r), self.omega0)
                               for r in roots])
        self.omega, per = self._cohort(
            thetas, self.omega, xs, ys, jnp.asarray(self.sizes[ids_h]),
            jnp.asarray(slot, jnp.int32), self.frozen)
        for k, r in enumerate(uniq):
            self.models[r] = self._pick(per, jnp.int32(k))

    def _merge(self) -> bool:
        """One merge pass over the live clusters; True if any merged."""
        live = np.nonzero(self.live)[0]
        roots = self.parent[live]
        uroots, idx = np.unique(roots, return_inverse=True)
        k = len(uroots)
        if k < 2:
            return False
        member = np.zeros((k, len(live)))
        member[idx, np.arange(len(live))] = 1.0
        dots = member @ self.gram[np.ix_(live, live)] @ member.T
        counts = member.sum(axis=1)
        means = dots / np.outer(counts, counts)
        norms = np.sqrt(np.maximum(np.diag(means), 0.0))
        cos = means / np.maximum(np.outer(norms, norms), 1e-300)
        adj = (cos >= self.tau) & ~np.eye(k, dtype=bool)
        label = np.arange(k)
        changed = True
        while changed:
            nb = np.where(adj, label[None, :], k).min(axis=1)
            new = np.minimum(label, nb)
            changed = bool((new != label).any())
            label = new
        new_root = uroots[label]
        groups: Dict[int, list] = {}
        for a, r in enumerate(uroots):
            groups.setdefault(int(new_root[a]), []).append(a)
        merged = False
        for keep, members in groups.items():
            if len(members) < 2:
                continue
            merged = True
            w = counts[members] / counts[members].sum()
            models = [self.models.pop(int(uroots[a]), self.omega0)
                      for a in members]
            self.models[keep] = jax.tree.map(
                lambda *ls: sum(float(wi) * l for wi, l in zip(w, ls)),
                *models)
        self.parent[live] = new_root[idx]
        return merged

    # -------------------------------------------------------------- results
    def result(self):
        """(omega, {root: model}, {client: root}) as host arrays."""
        omega = jax.device_get(self.omega)
        models = {r: jax.device_get(t) for r, t in self.models.items()}
        live = np.nonzero(self.live)[0]
        return omega, models, {int(c): int(self.parent[c]) for c in live}
