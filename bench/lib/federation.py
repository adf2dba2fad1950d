"""Federations and initial weights made from a seed, on the device.

The generators are the benchmark's own copy of the repository's
``data.synthetic`` settings, widened from its 64 dimensions to the
model's input: class prototypes of norm ``sep * sqrt(dim / base_dim)``
(the 64-dimensional generator's per-dimension signal-to-noise), Gaussian
noise of ``noise`` per dimension, and four latent clusters of clients.

  rotated   each cluster applies its own random orthogonal transform to
            the features (cluster 0 the identity): feature skew.
  shifted   each cluster relabels y as (y + s) mod n_classes, s from
            ``shifts``: label-concept skew.
  tokens    token sequences for a language model: the benchmark's own
            on-device copy of the Markov-chain streams of the
            repository's ``data.tokens``. Each latent cluster has its
            own chain of ``n_states`` states; a state emits a token
            uniformly from its band of ``n_classes / n_states`` ids, and
            moves to one of 4 successor states, uniformly. A cluster's
            bands lie in its own window of ``2 * n_classes /
            n_clusters`` ids starting at ``c * n_classes / n_clusters``
            (mod the vocabulary), so adjacent clusters share half their
            window: they differ in their token marginals and in their
            transitions. ``input_shape`` is ``[T]`` and ``n_classes`` the
            vocabulary; x holds positions 0..T-1 of a stream of T + 1
            tokens and y positions 1..T, each (N, n, T) int32. Of the
            ``federation`` group it reads ``n_clusters``, ``n_clients``,
            ``n_per`` and ``n_states``.

Clients are ordered by latent cluster, ``n_clients / n_clusters`` each.
Everything is drawn in one jitted call from keys derived from the seed
(for ``tokens`` the chains' structure too), so the same seed gives the
same federation and the same weights.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PURPOSES = {"data": 0, "weights": 1, "engine": 2, "frozen": 3}


def sub_seed(seed: int, purpose: str) -> int:
    """A 31-bit seed for one purpose, derived from any whole number."""
    ss = np.random.SeedSequence(int(seed) & (2 ** 64 - 1),
                                spawn_key=(PURPOSES[purpose],))
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


def key(seed: int, purpose: str):
    return jax.random.PRNGKey(sub_seed(seed, purpose))


@functools.partial(jax.jit, static_argnames=(
    "setting", "n_clusters", "n_clients", "n_per", "dim", "n_classes",
    "base_dim", "shifts"))
def _generate(k, *, setting, n_clusters, n_clients, n_per, dim, n_classes,
              base_dim, sep, noise, shifts):
    kp, kq, ky, kx = jax.random.split(k, 4)
    p = jax.random.normal(kp, (n_classes, dim))
    protos = (sep * math.sqrt(dim / base_dim)
              * p / jnp.linalg.norm(p, axis=1, keepdims=True))
    per = n_clients // n_clusters
    cluster = jnp.arange(n_clients, dtype=jnp.int32) // per
    y = jax.random.randint(ky, (n_clients, n_per), 0, n_classes, jnp.int32)
    x = protos[y] + noise * jax.random.normal(kx, (n_clients, n_per, dim))
    if setting == "rotated":
        qs = [jnp.eye(dim, dtype=jnp.float32)]
        for kk in jax.random.split(kq, n_clusters - 1):
            q, _ = jnp.linalg.qr(jax.random.normal(kk, (dim, dim)))
            qs.append(q)
        x = jnp.einsum("kpnd,kde->kpne",
                       x.reshape(n_clusters, per, n_per, dim), jnp.stack(qs),
                       precision=jax.lax.Precision.HIGHEST)
        x = x.reshape(n_clients, n_per, dim)
    elif setting == "shifted":
        s = jnp.asarray(shifts, jnp.int32)[cluster % len(shifts)]
        y = (y + s[:, None]) % n_classes
    else:
        raise ValueError(f"unknown setting {setting!r}")
    return x.astype(jnp.float32), y, cluster


FANOUT = 4


@functools.partial(jax.jit, static_argnames=(
    "n_clusters", "n_clients", "n_per", "seq", "vocab", "n_states"))
def _tokens(k, *, n_clusters, n_clients, n_per, seq, vocab, n_states):
    kb, kt, k0, kd = jax.random.split(k, 4)
    width = max(vocab // n_states, 1)
    window = max(2 * vocab // n_clusters, width)
    lo = jnp.arange(n_clusters, dtype=jnp.int32) * (vocab // n_clusters)
    bands = lo[:, None] + jax.random.randint(
        kb, (n_clusters, n_states), 0, window - width + 1, jnp.int32)
    trans = jax.random.randint(kt, (n_clusters, n_states, FANOUT), 0,
                               n_states, jnp.int32)
    per = n_clients // n_clusters
    cluster = jnp.arange(n_clients, dtype=jnp.int32) // per
    cl = jnp.broadcast_to(cluster[:, None], (n_clients, n_per))
    state0 = jax.random.randint(k0, (n_clients, n_per), 0, n_states,
                                jnp.int32)

    def step(state, kk):
        ke, kn = jax.random.split(kk)
        off = jax.random.randint(ke, state.shape, 0, width, jnp.int32)
        tok = (bands[cl, state] + off) % vocab
        move = jax.random.randint(kn, state.shape, 0, FANOUT, jnp.int32)
        return trans[cl, state, move], tok

    _, toks = jax.lax.scan(step, state0, jax.random.split(kd, seq + 1))
    toks = jnp.moveaxis(toks, 0, -1)
    return toks[..., :-1], toks[..., 1:], cluster


def generate(fed: dict, input_shape, n_classes: int, seed: int):
    """Device arrays ``(x, y, latent cluster (N,) i32)`` of a federation
    described by ``fed`` (a config's ``federation`` group): x (N, n,
    *input_shape) f32 and y (N, n) i32 for a feature setting, x and y
    (N, n, T) i32 for ``tokens``."""
    n_clients, n_clusters = int(fed["n_clients"]), int(fed["n_clusters"])
    if n_clients % n_clusters:
        raise ValueError("n_clients must be a multiple of n_clusters")
    if fed["setting"] == "tokens":
        (seq,) = (int(s) for s in input_shape)
        return _tokens(key(seed, "data"), n_clusters=n_clusters,
                       n_clients=n_clients, n_per=int(fed["n_per"]),
                       seq=seq, vocab=int(n_classes),
                       n_states=int(fed["n_states"]))
    dim = int(np.prod(input_shape))
    x, y, cluster = _generate(
        key(seed, "data"), setting=fed["setting"], n_clusters=n_clusters,
        n_clients=n_clients, n_per=int(fed["n_per"]), dim=dim,
        n_classes=int(n_classes), base_dim=int(fed["base_dim"]),
        sep=float(fed["sep"]), noise=float(fed["noise"]),
        shifts=tuple(fed.get("shifts", (0,))))
    return x.reshape((n_clients, int(fed["n_per"])) + tuple(input_shape)), \
        y, cluster


def host_clients(x, y):
    """The federation as the engine takes it: one ``{"x", "y"}`` dict of
    host arrays per client (views into one host copy)."""
    xh, yh = np.asarray(x), np.asarray(y)
    return [{"x": xh[i], "y": yh[i]} for i in range(xh.shape[0])]
