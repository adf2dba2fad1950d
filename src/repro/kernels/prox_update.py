"""Pallas TPU kernel: fused bi-level StoCFL client update.

Algorithm 1 lines 21-22, fused into one HBM pass:
    θ' = θ − η (g_θ + λ (θ − ω))
    ω' = ω − η g_ω
Unfused this reads/writes 4+2 arrays in ~7 passes; fused it streams each
operand exactly once (memory-bound, VPU elementwise).

Tiling: the flat parameter vector is viewed as a 2-D ``(rows, 128)``
slab (one vreg lane width per row) and the grid walks blocks of
``block_rows`` rows; the default 512 rows is 64k floats (256 KiB fp32)
per operand, so the 6-operand working set stays ≈1.5 MiB — comfortably
inside VMEM. The 2-D view is what lets the kernel run under the cohort
``vmap``: batching prepends an axis to the block, and Mosaic accepts a
block only when its last two dims are (multiple of 8, multiple of 128)
or span the array — a 1-D ``(block,)`` block becomes an illegal
``(1, block)``, a 2-D ``(block_rows, 128)`` one stays legal.

Vectors whose length is a whole number of blocks pass straight through:
the 2-D view is a free reshape, no padding copy, and θ/ω alias their
outputs so the update happens in the operands' own buffers. Other sizes
pay one ``jnp.pad`` per operand. Inputs are donated off-CPU — callers
must treat the four arrays as consumed, which every call site of the
fused path already does (grads are per-step temporaries, θ/ω are
immediately rebound).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
# row granule: 16 sublanes fit one packed bf16 vreg (8 for f32), so a
# block of a multiple of 16 rows is legal for both compute dtypes
_ROW_GRANULE = 16


def _prox_kernel(theta_ref, omega_ref, gt_ref, go_ref, eta_ref, lam_ref,
                 theta_out_ref, omega_out_ref):
    eta = eta_ref[0, 0]
    lam = lam_ref[0, 0]
    th = theta_ref[...].astype(jnp.float32)
    om = omega_ref[...].astype(jnp.float32)
    theta_out_ref[...] = (th - eta * (gt_ref[...].astype(jnp.float32) + lam * (th - om))
                          ).astype(theta_out_ref.dtype)
    omega_out_ref[...] = (om - eta * go_ref[...].astype(jnp.float32)).astype(omega_out_ref.dtype)


def _prox_call(theta, omega, g_theta, g_omega, eta, lam, *,
               block_rows: int, interpret: bool):
    """Traced body shared by the donating and non-donating entry jits."""
    n = theta.shape[0]
    rows = -(-n // LANES)
    rows = -(-rows // _ROW_GRANULE) * _ROW_GRANULE
    br = min(-(-block_rows // _ROW_GRANULE) * _ROW_GRANULE, rows)
    rows = -(-rows // br) * br
    n_pad = rows * LANES
    if n_pad != n:
        # misaligned tail: one append-pad per operand (pad values are
        # computed but sliced off below — they never feed anything)
        theta, omega, g_theta, g_omega = (
            jnp.pad(a, (0, n_pad - n))
            for a in (theta, omega, g_theta, g_omega))
    slabs = [a.reshape(rows, LANES) for a in (theta, omega, g_theta, g_omega)]
    eta_v = jnp.full((1, 1), eta, jnp.float32)
    lam_v = jnp.full((1, 1), lam, jnp.float32)

    block = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0))
    outs = pl.pallas_call(
        _prox_kernel,
        grid=(rows // br,),
        in_specs=[block, block, block, block, scalar, scalar],
        out_specs=[block, block],
        out_shape=[
            jax.ShapeDtypeStruct((rows, LANES), theta.dtype),
            jax.ShapeDtypeStruct((rows, LANES), omega.dtype),
        ],
        # θ/ω update in place: with the jit-level donation below, the
        # aligned path writes back into the operands' own HBM buffers
        # (interpret mode runs the aliasing through the interpreter's
        # copy semantics — still correct, just not in-place)
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
        name="prox_update",
    )(*slabs, eta_v, lam_v)
    th_out, om_out = (o.reshape(n_pad) for o in outs)
    if n_pad != n:
        return th_out[:n], om_out[:n]
    return th_out, om_out


_prox_jit = functools.partial(jax.jit,
                              static_argnames=("block_rows", "interpret"))
_prox_plain = _prox_jit(_prox_call)
_prox_donating = _prox_jit(_prox_call, donate_argnums=(0, 1, 2, 3))


def prox_update_flat(theta, omega, g_theta, g_omega, eta, lam, *,
                     block_rows: int = 512, interpret: bool = False,
                     donate=None):
    """All four arrays 1-D of equal length; returns (theta', omega').

    ``block_rows`` is the grid block height in 128-lane rows (rounded up
    to a multiple of 16, capped at the vector's own height).
    ``donate=None`` resolves at CALL time: off-CPU the four operands are
    donated (their buffers are recycled into the outputs — the caller
    must not reuse them); on CPU the plain jit is used so inputs stay
    readable. Pass an explicit bool to override."""
    if theta.shape[0] == 0:
        return theta, omega
    if donate is None:
        donate = jax.default_backend() != "cpu"
    fn = _prox_donating if donate else _prox_plain
    return fn(theta, omega, g_theta, g_omega, eta, lam,
              block_rows=block_rows, interpret=interpret)
