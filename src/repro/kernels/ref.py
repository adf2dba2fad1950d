"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def cosine_sim_ref(x):
    """x: (N, D) -> (N, N) fp32 cosine similarity."""
    x32 = x.astype(jnp.float32)
    norms = jnp.linalg.norm(x32, axis=1, keepdims=True)
    xn = jnp.where(norms > 0, x32 / norms, 0.0)
    return xn @ xn.T


def merge_candidates_ref(x, live, tau):
    """(K, D) means + (K,) live -> (K, K) fp32 0/1 merge-pair adjacency
    (cos ≥ τ, both rows live, diagonal off)."""
    M = cosine_sim_ref(x)
    lv = live.astype(bool)
    ids = jnp.arange(x.shape[0])
    ok = (M >= tau) & lv[:, None] & lv[None, :] & (ids[:, None] != ids[None, :])
    return ok.astype(jnp.float32)


def segment_rows_ref(x, seg, live):
    """(K, D) rows, (K,) segment ids (dead rows: K), (K,) live -> (K, D)
    row sums by XLA's scatter-add over the live-masked rows."""
    k = x.shape[0]
    return jax.ops.segment_sum(jnp.where(live[:, None], x, 0.0), seg,
                               num_segments=k + 1)[:k]


def resolve_roots_ref(parent):
    """(N,) union-find parent pointers -> (N,) roots by iterated pointer
    halving ``p <- p[p]`` (⌈log2 N⌉+1 steps: each halves every path)."""
    steps = max(int(parent.shape[0]).bit_length(), 1)
    return jax.lax.fori_loop(0, steps, lambda _, p: jnp.take(p, p), parent)


def prox_update_ref(theta, omega, g_theta, g_omega, eta, lam):
    th = theta.astype(jnp.float32)
    om = omega.astype(jnp.float32)
    theta_new = th - eta * (g_theta.astype(jnp.float32) + lam * (th - om))
    omega_new = om - eta * g_omega.astype(jnp.float32)
    return theta_new.astype(theta.dtype), omega_new.astype(omega.dtype)


def ssm_scan_ref(dA, dBx, C):
    """Sequential-scan oracle. dA,dBx: (B,S,D,N); C: (B,S,N) -> (B,S,D)."""
    B, S, D, N = dA.shape

    def step(h, inp):
        a, b, c = inp
        h = a * h + b
        return h, jnp.einsum("bdn,bn->bd", h, c)

    h0 = jnp.zeros((B, D, N), jnp.float32)
    xs = (
        dA.astype(jnp.float32).transpose(1, 0, 2, 3),
        dBx.astype(jnp.float32).transpose(1, 0, 2, 3),
        C.astype(jnp.float32).transpose(1, 0, 2),
    )
    _, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2)


def grouped_matmul_ref(lhs, rhs, group_sizes, transpose_rhs=False):
    """(m, k) rows sorted by group @ their group's (k, n) weight -> (m, n);
    rows past ``sum(group_sizes)`` read 0. One masked matmul per group."""
    if transpose_rhs:
        rhs = jnp.swapaxes(rhs, 1, 2)
    ends = jnp.cumsum(group_sizes)
    row = jnp.arange(lhs.shape[0])[:, None]
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), lhs.dtype)
    for g in range(rhs.shape[0]):
        mine = (row >= ends[g] - group_sizes[g]) & (row < ends[g])
        out = out + jnp.where(mine, lhs @ rhs[g], 0.0)
    return out
