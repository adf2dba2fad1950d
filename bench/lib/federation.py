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

Clients are ordered by latent cluster, ``n_clients / n_clusters`` each.
Everything is drawn in one jitted call from keys derived from the seed,
so the same seed gives the same federation and the same weights.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PURPOSES = {"data": 0, "weights": 1, "engine": 2}


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


def generate(fed: dict, input_shape, n_classes: int, seed: int):
    """Device arrays ``(x (N, n, *input_shape) f32, y (N, n) i32, latent
    cluster (N,) i32)`` of a federation described by ``fed`` (a config's
    ``federation`` group)."""
    n_clients, n_clusters = int(fed["n_clients"]), int(fed["n_clusters"])
    if n_clients % n_clusters:
        raise ValueError("n_clients must be a multiple of n_clusters")
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
