"""Pallas TPU kernels: pairwise cosine-similarity and merge-candidate
matrices.

StoCFL's clustering hot-spot: the server recomputes the K̃×K̃ (up to N×N,
N=4800 cross-device) cosine matrix over distribution representations every
round (Algorithm 1, line 10). That is an X·Xᵀ on the MXU with fused
per-row inverse-norm scaling.

Tiling: grid (N/bn, N/bn, D/bk); operand tiles (bn, bk) live in VMEM, fp32
accumulation in the output tile across the contraction grid axis (TPU grid
iterates the trailing axis innermost, so out_ref accumulates correctly).
MXU-aligned defaults bn=128, bk=512.

Per-row vectors (inverse norms, the live mask) enter as 2-D operands: a
``(n_pad, 1)`` column with ``(bn, 1)`` blocks for the tile's rows and a
``(1, n_pad)`` row with ``(1, bn)`` blocks for its columns. Mosaic tiles
a 1-D ``(bn,)`` block differently from the XLA layout of a long 1-D
array and refuses the kernel above one block (N > bn); the 2-D forms
are legal at every N because each block dim is either 1 (the full
extent) or bn (a multiple of 128).

``merge_candidates`` is the fused device-clustering variant: the same
X·Xᵀ tiling, but the final contraction step also applies the live-row
mask and the τ threshold in-register, emitting the 0/1 adjacency of
mergeable cluster pairs directly — the K̃² cosine matrix never leaves
VMEM, so the union-find merge pass (``core.device_clustering``) consumes
candidate pairs without materializing similarities in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _accumulate(x_ref, y_ref, out_ref):
    """One contraction step of the (i, j) tile: zero at k=0, then add
    the x_i · y_jᵀ partial product at full fp32 precision (the τ test
    is a hard threshold, so the cosine may not carry bf16 error)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32),
        y_ref[...].astype(jnp.float32).T,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _cosine_kernel(x_ref, y_ref, inv_i_ref, inv_j_ref, out_ref):
    _accumulate(x_ref, y_ref, out_ref)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _scale():
        out_ref[...] *= inv_i_ref[...] * inv_j_ref[...]


def _candidates_kernel(tau, bn, x_ref, y_ref, inv_i_ref, inv_j_ref,
                       live_i_ref, live_j_ref, out_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    _accumulate(x_ref, y_ref, out_ref)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _threshold():
        cos = out_ref[...] * inv_i_ref[...] * inv_j_ref[...]
        rows = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0) + i * bn
        cols = jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1) + j * bn
        ok = ((cos >= tau)
              & (live_i_ref[...] > 0)
              & (live_j_ref[...] > 0)
              & (rows != cols))
        out_ref[...] = ok.astype(jnp.float32)


def _padded(x, bn: int, bk: int):
    """Zero-pad (N, D) to (bn, bk) multiples; return it with its
    per-row inverse norms (0 for zero rows, so pad entries come out 0)."""
    n, d = x.shape
    n_pad = -(-n // bn) * bn
    d_pad = -(-d // bk) * bk
    xp = jnp.pad(x, ((0, n_pad - n), (0, d_pad - d)))
    norms = jnp.sqrt(jnp.sum(xp.astype(jnp.float32) ** 2, axis=1))
    inv = jnp.where(norms > 0, jnp.float32(1.0) / norms, jnp.float32(0.0))
    return xp, inv


def _tiled_call(kernel, name: str, xp, row_vecs, bn: int, bk: int,
                interpret: bool):
    """Run ``kernel`` over the (N/bn, N/bn, D/bk) grid as the op ``name``.
    Each (n_pad,) vector in ``row_vecs`` is passed twice: as a column for
    the tile's rows (``*_i_ref``), then as a row for its columns
    (``*_j_ref``)."""
    n_pad, d_pad = xp.shape
    vec_specs = [pl.BlockSpec((bn, 1), lambda i, j, k: (i, 0)),
                 pl.BlockSpec((1, bn), lambda i, j, k: (0, j))] * len(row_vecs)
    vec_args = [a for v in row_vecs
                for a in (v.reshape(n_pad, 1), v.reshape(1, n_pad))]
    return pl.pallas_call(
        kernel,
        grid=(n_pad // bn, n_pad // bn, d_pad // bk),
        in_specs=[
            pl.BlockSpec((bn, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            *vec_specs,
        ],
        out_specs=pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32),
        interpret=interpret,
        name=name,
    )(xp, xp, *vec_args)


@functools.partial(jax.jit, static_argnames=("bn", "bk", "interpret"))
def cosine_sim(x, *, bn: int = 128, bk: int = 512, interpret: bool = False):
    """x: (N, D) -> (N, N) cosine similarity, fp32.

    N is padded to bn and D to bk internally; zero rows get inverse norm
    0 so padded entries are 0 and harmless.
    """
    n = x.shape[0]
    xp, inv = _padded(x, bn, bk)
    return _tiled_call(_cosine_kernel, "cosine_sim", xp, [inv], bn, bk,
                       interpret)[:n, :n]


@functools.partial(jax.jit,
                   static_argnames=("tau", "bn", "bk", "interpret"))
def merge_candidates(x, live, *, tau: float, bn: int = 128, bk: int = 512,
                     interpret: bool = False):
    """(K, D) cluster means + (K,) live mask -> (K, K) fp32 0/1 adjacency.

    ``adj[i, j] = 1`` iff rows i ≠ j are both live and cos(x_i, x_j) ≥ τ
    — the candidate merge pairs of Algorithm 1 line 10, fused so the
    cosine tile is thresholded in VMEM instead of round-tripping a K̃²
    similarity matrix through HBM. Zero rows are norm-guarded to cosine
    0 (and are masked out by ``live`` anyway); the diagonal is always 0,
    so a τ ≤ cos(x, x) can never self-merge a cluster.
    """
    n = x.shape[0]
    xp, inv = _padded(x, bn, bk)
    lv = jnp.pad(live.astype(jnp.float32), (0, xp.shape[0] - n))
    # jaxlint: disable=R2 — tau is static (static_argnames), baked into the kernel
    kernel = functools.partial(_candidates_kernel, float(tau), bn)
    return _tiled_call(kernel, "merge_candidates", xp, [inv, lv], bn, bk,
                       interpret)[:n, :n]
