"""Jit'd public wrappers for the Pallas kernels, with backend selection.

``backend="auto"`` picks by platform: the Pallas kernel compiled by
Mosaic on TPU, the pure-jnp oracle everywhere else. ``backend="jnp"``
forces the oracle. Nothing here runs a kernel in interpret mode or
falls back to the oracle after a TPU compile error: the kernel tests
call the kernels in interpret mode themselves, and
``tests/test_tpu_compile.py`` compiles them for a described v5e.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ref
from repro.kernels.cosine_sim import cosine_sim as _cosine_pallas
from repro.kernels.cosine_sim import merge_candidates as _candidates_pallas
from repro.kernels.prox_update import prox_update_flat as _prox_pallas
from repro.kernels.ssm_scan import ssm_scan as _ssm_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pairwise_cosine(x, backend: str = "auto"):
    """(N, D) representation matrix -> (N, N) cosine similarity."""
    if backend == "jnp" or (backend == "auto" and not _on_tpu()):
        return ref.cosine_sim_ref(x)
    return _cosine_pallas(x)


def merge_pairs(means, live, tau: float, backend: str = "auto", mesh=None):
    """(K, D) cluster means + (K,) live mask -> (K, K) fp32 0/1 adjacency
    of mergeable pairs (cos ≥ τ, both live, diagonal off) — Algorithm 1
    line 10 as one fused device op (``cosine_sim.merge_candidates``).

    ``mesh``: the client mesh the calling program is partitioned over.
    GSPMD cannot partition a Mosaic kernel, so there the kernel runs
    under ``shard_map`` on every device over the replicated inputs."""
    if backend == "jnp" or (backend == "auto" and not _on_tpu()):
        return ref.merge_candidates_ref(means, live, tau)
    kernel = functools.partial(_candidates_pallas, tau=float(tau))
    if mesh is not None:
        kernel = jax.shard_map(kernel, mesh=mesh, in_specs=(P(), P()),
                               out_specs=P(), check_vma=False)
    return kernel(means, live)


def resolve_roots(parent):
    """(N,) union-find parent array (``parent[i] == i`` at roots) ->
    (N,) fully-resolved roots.

    Iterated pointer halving ``p <- p[p]``: every find-path halves per
    step, so ⌈log2 N⌉+1 gathers resolve ANY forest — the device
    replacement for the numpy ``UnionFind.find`` Python loop. The whole
    array resolves as one vectorized op per step, and the step count
    depends only on the (static, pow2-padded) capacity, so the op jits
    into the clustering round with no data-dependent control flow.

    The same XLA gather runs on every platform: the pointer chase needs
    a 1-D dynamic gather, which Mosaic (Pallas on TPU) does not lower,
    so there is no kernel for it."""
    return ref.resolve_roots_ref(parent)


def prox_update_tree(theta, omega, g_theta, g_omega, eta, lam, backend: str = "auto"):
    """Fused bi-level update applied leaf-wise over parameter pytrees."""
    if backend == "jnp" or (backend == "auto" and not _on_tpu()):
        th = jax.tree.map(
            lambda t, o, g: (t.astype(jnp.float32)
                             - eta * (g.astype(jnp.float32) + lam * (t.astype(jnp.float32) - o.astype(jnp.float32)))
                             ).astype(t.dtype),
            theta, omega, g_theta)
        om = jax.tree.map(
            lambda o, g: (o.astype(jnp.float32) - eta * g.astype(jnp.float32)).astype(o.dtype),
            omega, g_omega)
        return th, om

    th_leaves, treedef = jax.tree.flatten(theta)
    om_leaves = treedef.flatten_up_to(omega)
    gt_leaves = treedef.flatten_up_to(g_theta)
    go_leaves = treedef.flatten_up_to(g_omega)
    new_th, new_om = [], []
    for t, o, gt, go in zip(th_leaves, om_leaves, gt_leaves, go_leaves):
        tn, on = _prox_pallas(t.ravel(), o.ravel(), gt.ravel(), go.ravel(),
                              eta, lam)
        new_th.append(tn.reshape(t.shape).astype(t.dtype))
        new_om.append(on.reshape(o.shape).astype(o.dtype))
    return jax.tree.unflatten(treedef, new_th), jax.tree.unflatten(treedef, new_om)


def prox_update_flat(theta, omega, g_theta, g_omega, eta, lam,
                     backend: str = "auto", **kw):
    """Fused bi-level update on flat 1-D vectors (Algorithm 1 l.21-22).

    The hot-path entry used by ``core.bilevel``'s flatten-once adapter:
    one fused elementwise pass over the concatenated parameter vector
    instead of per-leaf tree math. The jnp oracle mirrors the
    ``prox_update_tree`` leaf formula exactly (f32 accumulate, cast back
    to the operand dtype) so fused and tree paths agree bitwise off-TPU."""
    if backend == "jnp" or (backend == "auto" and not _on_tpu()):
        th32 = theta.astype(jnp.float32)
        om32 = omega.astype(jnp.float32)
        th = (th32 - eta * (g_theta.astype(jnp.float32) + lam * (th32 - om32))
              ).astype(theta.dtype)
        om = (om32 - eta * g_omega.astype(jnp.float32)).astype(omega.dtype)
        return th, om
    return _prox_pallas(theta, omega, g_theta, g_omega, eta, lam, **kw)


def ssm_scan(dA, dBx, C, backend: str = "auto", **kw):
    """Fused selective scan. See kernels/ssm_scan.py."""
    if backend == "jnp" or (backend == "auto" and not _on_tpu()):
        return ref.ssm_scan_ref(dA, dBx, C)
    return _ssm_pallas(dA, dBx, C, **kw)
