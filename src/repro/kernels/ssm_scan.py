"""Pallas TPU kernel: fused chunked selective-scan (Mamba recurrence).

y[t] = Σ_n h[t, d, n] · C[t, n]  with  h[t] = dA[t] ⊙ h[t-1] + dBx[t].

The recurrent state h (bd, N) lives in a VMEM scratch that persists across
the sequential chunk axis of the grid (TPU executes the trailing grid axis
innermost/sequentially), so the full h trajectory is NEVER materialized in
HBM — only the contracted output y streams out. This is the TPU-native
replacement for the GPU mamba kernel's shared-memory chunking.

Grid: (B, D/bd, S/chunk); scratch resets at chunk==0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(dA_ref, dBx_ref, c_ref, y_ref, h_scratch):
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        h_scratch[...] = jnp.zeros_like(h_scratch)

    dA = dA_ref[0]          # (chunk, bd, N)
    dBx = dBx_ref[0]
    C = c_ref[0]            # (chunk, N)

    def step(h, inp):
        a, b, c = inp
        h = a * h + b                               # (bd, N)
        return h, jnp.sum(h * c[None, :], axis=1)   # y_t: (bd,)

    h, ys = jax.lax.scan(step, h_scratch[...], (dA, dBx, C))
    y_ref[0] = ys
    h_scratch[...] = h


@functools.partial(jax.jit, static_argnames=("bd", "chunk", "interpret"))
def ssm_scan(dA, dBx, C, *, bd: int = 128, chunk: int = 128, interpret: bool = False):
    """dA, dBx: (B, S, D, N); C: (B, S, N) -> y: (B, S, D), fp32.

    D padded to bd, S to chunk (dA pads with 1s so padded steps keep h)."""
    B, S, D, N = dA.shape
    d_pad = -(-D // bd) * bd
    s_pad = -(-S // chunk) * chunk

    dA_p = jnp.ones((B, s_pad, d_pad, N), jnp.float32).at[:, :S, :D].set(dA)
    dBx_p = jnp.zeros((B, s_pad, d_pad, N), jnp.float32).at[:, :S, :D].set(dBx)
    C_p = jnp.zeros((B, s_pad, N), jnp.float32).at[:, :S].set(C)

    y = pl.pallas_call(
        _scan_kernel,
        grid=(B, d_pad // bd, s_pad // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, bd, N), lambda b, d, s: (b, s, d, 0)),
            pl.BlockSpec((1, chunk, bd, N), lambda b, d, s: (b, s, d, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, s: (b, s, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, bd), lambda b, d, s: (b, s, d)),
        out_shape=jax.ShapeDtypeStruct((B, s_pad, d_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        interpret=interpret,
        name="ssm_scan",
    )(dA_p, dBx_p, C_p)
    return y[:, :S, :D]
