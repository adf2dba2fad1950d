"""Pallas TPU kernel: row segment sum, the cluster means' numerator.

``segment_row_sum(x (K, D), seg (K,)) -> (K, D)`` adds row ``i`` of
``x`` into output row ``seg[i]``; rows with ``seg[i]`` outside
``[0, K)`` (dead rows, parked in the scratch segment ``K``) are
skipped. It replaces XLA's scatter-add ``jax.ops.segment_sum`` over the
(capacity, |θ|) Ψ bank, which the TPU lowers as a loop of one
row-sized dynamic-update-slice per row.

Tiling: the grid walks D in lane tiles of ``bd`` columns (a multiple of
128). Each step holds the (K, bd) input block and the (K, bd) output
block in VMEM, zeroes the output block and adds the rows into it in
ascending ``i``. The last block may hang over D: Pallas pads its reads
and masks its writes, so ``x`` is never padded or copied. ``seg``
arrives by scalar prefetch (SMEM), so each row's target is a scalar
read.

Accumulation order: row ``i``'s add follows row ``i - 1``'s, as in a
serial f32 scatter, and every add is a plain f32 add (no matmul, no
bf16), so the sums are bitwise those of ``jax.ops.segment_sum``.

VMEM envelope: the input and output blocks, double-buffered, take
``4 · K · bd · 4`` bytes. Blocks wider than half the default scoped VMEM
raise the kernel's scoped limit to what they need, with half the
default left over for the compiler's own scratch. At the narrowest tile
(``bd`` = 128) that has to stay within three quarters of the
TensorCore's VMEM, which bounds K (``fits``, K ≤ 45,056); the wrapper
``kernels.ops.segment_rows`` sends larger K to the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MiB = 1024 * 1024
# default scoped VMEM of a TPU v5e TensorCore (the compiler's limit for
# a kernel that names none), and the VMEM the TensorCore has
SCOPED_VMEM_BYTES = 16 * MiB
VMEM_BYTES = 128 * MiB
# the blocks' share of the default scoped VMEM; the rest is the
# compiler's scratch, kept when a kernel raises its limit
_BLOCK_BUDGET = SCOPED_VMEM_BYTES // 2
# the most VMEM a kernel of this module asks for
_VMEM_CAP = VMEM_BYTES * 3 // 4
# the widest lane tile: on a v5e at K = 32, D = 2,631,680 a call takes
# 1.43 ms at 2048 lanes and 1.09 ms at 8192 (per-step overhead); at
# K = 256 the budget allows 2048, and wider tiles gain under 2%
_MAX_LANES = 8192


def _block_bytes(k: int, bd: int) -> int:
    """VMEM of the (K, bd) f32 input and output blocks, double-buffered."""
    return 2 * 2 * k * bd * 4


def vmem_limit(k: int, bd: int) -> int:
    """The scoped VMEM a (K, bd) tile asks for: the default, or the
    blocks plus the compiler's scratch where the blocks need more."""
    return max(SCOPED_VMEM_BYTES, _block_bytes(k, bd) + _BLOCK_BUDGET)


def fits(k: int) -> bool:
    """True when K rows fit the kernel's VMEM at the narrowest tile."""
    return vmem_limit(k, LANES) <= _VMEM_CAP


def tile_width(k: int, d: int) -> int:
    """Lane tile: the widest multiple of 128 whose blocks stay within
    half the default scoped VMEM, at most ``_MAX_LANES`` and no wider
    than D needs; 128 where even those blocks need more
    (``vmem_limit``)."""
    bd = _BLOCK_BUDGET // _block_bytes(k, 1) // LANES * LANES
    return max(LANES, min(bd, _MAX_LANES, -(-d // LANES) * LANES))


def _kernel(seg_ref, x_ref, out_ref):
    k = x_ref.shape[0]
    out_ref[...] = jnp.zeros_like(out_ref)

    def add_row(i, carry):
        s = seg_ref[i]

        @pl.when((s >= 0) & (s < k))
        def _add():
            out_ref[pl.ds(s, 1), :] += x_ref[pl.ds(i, 1), :]

        return carry

    jax.lax.fori_loop(0, k, add_row, 0)


@functools.partial(jax.jit, static_argnames=("bd", "interpret"))
def segment_row_sum(x, seg, *, bd: int | None = None,
                    interpret: bool = False):
    """(K, D) f32 rows + (K,) int32 segment ids -> (K, D) f32 sums."""
    k, d = x.shape
    bd = tile_width(k, d) if bd is None else bd
    block = pl.BlockSpec((k, bd), lambda j, seg_ref: (0, j))
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(pl.cdiv(d, bd),),
            in_specs=[block], out_specs=block),
        out_shape=jax.ShapeDtypeStruct((k, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_limit(k, bd)),
        interpret=interpret,
        name="segment_rows",
    )(seg.astype(jnp.int32), x)
