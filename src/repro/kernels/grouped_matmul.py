"""Grouped matmul over a chip's share of the experts, on the TPU.

``grouped_matmul(lhs, rhs, group_sizes)`` computes, for rows sorted by
group, ``lhs[rows of group g] @ rhs[g]``: the expert FFN's three
matmuls once the (token, expert) rows routed to the experts held here
are sorted by expert. It runs JAX's megablox ``gmm`` Pallas kernel
(``jax.experimental.pallas.ops.tpu.megablox``), whose grid visits only
the row tiles of non-empty groups, so the work follows the rows routed
here and not the static row capacity. Rows past ``sum(group_sizes)`` are
left unwritten: callers mask them.

The kernel's ops are named ``gmm.*`` in the compiled program and the
device trace (``expert_gmm_roofline`` reads them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.ops import backend as _gmm

TM = 512        # row tile: one tile of an expert's rows per grid step
_MULTI_PASS = ("high", "highest", "bfloat16_3x", "tensorfloat32", "float32")


def tiling(m: int, k: int, n: int) -> tuple:
    """Tiles (tm, tk, tn) within the v5e's scoped VMEM: a whole output
    width up to 1536 columns (so the rows are read once), 1024 past it."""
    tk = 256 if k % 256 == 0 else (128 if k % 128 == 0 else k)
    tn = n if n <= 1536 else (1024 if n % 1024 == 0 else 512)
    return (min(TM, m), tk, tn)


def grouped_matmul(lhs, rhs, group_sizes, transpose_rhs: bool = False,
                   interpret: bool = False):
    """(m, k) rows sorted by group, (G, k, n) weights (``(G, n, k)`` with
    ``transpose_rhs``), (G,) int32 row counts -> (m, n). ``m`` is padded
    up to a whole row tile and the pad cut off again. ``interpret`` runs
    the kernel in Pallas's interpreter (the CPU tests)."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiling(m, k, n)
    pad = -m % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    # Mosaic knows no three-pass f32 dot: where the caller asks for more
    # than one pass, the kernel takes the exact one
    exact = jax.config.jax_default_matmul_precision in _MULTI_PASS
    with jax.default_matmul_precision("highest" if exact else None):
        out = _gmm.gmm(lhs, rhs, group_sizes.astype(jnp.int32),
                       preferred_element_type=lhs.dtype, tiling=(tm, tk, tn),
                       transpose_rhs=transpose_rhs, interpret=interpret)
    return out[:m] if pad else out
