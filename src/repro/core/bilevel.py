"""Bi-level clustered FL optimization (paper §3.3, Algorithm 1 l.14-23).

Client procedure (lines 20-23), E local steps, fused prox kernel:
    θ ← θ − η (∇f_i(θ) + λ (θ − ω))
    ω ← ω − η ∇f_i(ω)
Server (lines 17-19): ω ← Aggregate([ωᵢ]) over all sampled clients;
θ_k ← FedAvg([θᵢ], i ∈ c_k) per cluster.

``make_client_update`` returns a jitted, vmappable function — the whole
sampled cohort executes as ONE SPMD computation with clients stacked on
the leading axis (the mesh's client/data axis in production).

The loss is ``loss(params, batch)``, or ``loss(params, batch, frozen)``
where the model reads a tree that is not trained (a frozen backbone):
the update then takes that tree as an argument, so a compiled cohort
step never holds it as a constant. A loss may return ``(loss, stats)``
with ``stats`` a dict of scalar counters of its forward pass; the update
then also returns them, combined over its gradients by ``merge_stats``.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.utils import trees


def flat_spec(tree):
    """Static unflatten recipe for ``flatten_tree``: (treedef, shapes,
    dtypes, split points). Computed once per trace — under vmap the
    per-client (unbatched) shapes are captured, so the adapter composes
    with ``jax.vmap`` / ``chunk_map`` transparently."""
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(l.shape for l in leaves)
    dtypes = tuple(l.dtype for l in leaves)
    sizes = [functools.reduce(lambda a, b: a * b, s, 1) for s in shapes]
    splits = tuple(sum(sizes[:i + 1]) for i in range(len(sizes) - 1))
    return treedef, shapes, dtypes, splits


def flatten_tree(tree):
    """Concatenate all leaves into one 1-D vector (the fused-kernel view)."""
    return jnp.concatenate([jnp.ravel(l) for l in jax.tree.leaves(tree)])


def unflatten_tree(vec, spec):
    treedef, shapes, dtypes, splits = spec
    parts = jnp.split(vec, splits)
    return jax.tree.unflatten(
        treedef,
        [p.reshape(s).astype(d) for p, s, d in zip(parts, shapes, dtypes)])


def loss_args(frozen) -> tuple:
    """The loss's arguments after ``(params, batch)``: the frozen tree,
    or none at all where there is no such tree."""
    return () if frozen is None else (frozen,)


def merge_stats(a: dict, b: dict) -> dict:
    """Combine two dicts of forward-pass counters: a counter whose name
    holds ``max`` keeps the larger value, every other one adds up."""
    return {k: (jnp.maximum if "max" in k else jnp.add)(a[k], b[k])
            for k in a}


def _grad_with_stats(loss_fn):
    """``grad(params, batch, *frozen) -> (grads, stats)``; ``stats`` is
    ``{}`` for a loss that returns a scalar."""
    def loss_stats(*args):
        out = loss_fn(*args)
        return out if isinstance(out, tuple) else (out, {})

    return jax.grad(loss_stats, has_aux=True)


def make_client_update(loss_fn: Callable, lr: float, lam: float,
                       local_steps: int = 1, backend: str = "auto",
                       fused: bool = False):
    """loss_fn(params, batch[, frozen]) -> scalar or (scalar, stats).

    Returns client_update(theta, omega, batch, frozen=None) ->
    (theta_i, omega_i), or (theta_i, omega_i, stats) where the loss
    returns counters (combined over the update's 2·E gradients): E =
    local_steps full-batch SGD steps of the bi-level objective.

    ``fused=True`` flattens θ/ω ONCE, runs the E-step scan on the flat
    vectors with the fused ``prox_update_flat`` kernel (jnp oracle
    off-TPU — same f32-accumulate formula, so fused/tree agree bitwise
    in fp32), and unflattens once at the end. Grads still see the
    original pytree via a per-step unflatten view."""
    grad_fn = _grad_with_stats(loss_fn)

    def finish(th, om, stats):
        return (th, om, stats) if stats else (th, om)

    def stats0(theta, batch, extra):
        _, st = jax.eval_shape(grad_fn, theta, batch, *extra)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), st)

    if fused:
        def client_update(theta, omega, batch, frozen=None):
            extra = loss_args(frozen)
            spec = flat_spec(theta)
            th_f = flatten_tree(theta)
            om_f = flatten_tree(omega)

            def step(carry, _):
                thf, omf, st = carry
                g_t, s_t = grad_fn(unflatten_tree(thf, spec), batch, *extra)
                g_o, s_o = grad_fn(unflatten_tree(omf, spec), batch, *extra)
                thf, omf = ops.prox_update_flat(
                    thf, omf, flatten_tree(g_t), flatten_tree(g_o), lr, lam,
                    backend=backend)
                return (thf, omf, merge_stats(merge_stats(st, s_t), s_o)), None

            (th_f, om_f, st), _ = jax.lax.scan(
                step, (th_f, om_f, stats0(theta, batch, extra)), None,
                length=local_steps)
            return finish(unflatten_tree(th_f, spec),
                          unflatten_tree(om_f, spec), st)

        return client_update

    def client_update(theta, omega, batch, frozen=None):
        extra = loss_args(frozen)

        def step(carry, _):
            th, om, st = carry
            g_t, s_t = grad_fn(th, batch, *extra)
            g_o, s_o = grad_fn(om, batch, *extra)
            th, om = ops.prox_update_tree(th, om, g_t, g_o, lr, lam, backend=backend)
            return (th, om, merge_stats(merge_stats(st, s_t), s_o)), None

        (th, om, st), _ = jax.lax.scan(
            step, (theta, omega, stats0(theta, batch, extra)), None,
            length=local_steps)
        return finish(th, om, st)

    return client_update


def make_cohort_update(loss_fn, lr, lam, local_steps=1, backend: str = "auto",
                       fused: bool = False, donate: bool = True):
    """vmapped cohort step: thetas stacked per client, omega and the
    frozen tree (if any) shared.

    thetas: pytree with leading client axis; batches: stacked client
    batches. Returns (thetas_i, omegas_i) both with client axis, and
    the per-client counters where the loss returns them.

    Off-CPU the stacked cohort buffers (thetas, batches) are donated:
    both are per-round temporaries at every call site (thetas are
    gathered from the bank/rows, batches from the arena), so their HBM
    recycles into the outputs and the cohort step allocates nothing
    net. Pass ``donate=False`` if a caller reuses either after the
    call. CPU ignores donation; the knob resolves when the cohort fn is
    built, which is per-EngineContext (not per-import)."""
    cu = make_client_update(loss_fn, lr, lam, local_steps, backend, fused=fused)
    dn = (0, 2) if (donate and jax.default_backend() != "cpu") else ()

    @functools.wraps(cu)
    def cohort(thetas, omega, batches, frozen=None):
        return jax.vmap(cu, in_axes=(0, None, 0, None))(thetas, omega,
                                                        batches, frozen)

    return jax.jit(cohort, donate_argnums=dn)


def chunk_map(fn, in_axes, chunk: int, donate=None):
    """Memory-flat cohort execution: run a vmapped per-client ``fn`` over
    the cohort in fixed-size chunks via ``lax.map``.

    ``in_axes`` mirrors the vmap spec (0 = stacked per-client arg, None =
    shared/broadcast arg). Cohorts of ≤ ``chunk`` clients run unchunked;
    larger ones are padded to a chunk multiple (repeating leading rows —
    the pad outputs are sliced off) and reshaped to ``(n_chunks, chunk,
    ...)`` so ``lax.map`` executes one chunk at a time with reused
    buffers: peak activation memory is O(chunk), not O(cohort), which is
    what lets 100% participation at thousands of clients fit. The wrapper
    is jitted so the whole chunk loop is one XLA program; ``donate``
    argument positions (default: every stacked arg) are donated off-CPU
    so their buffers are recycled in place — pass a narrower tuple when
    the caller reuses a stacked input after the call.

    ``chunk <= 0`` disables chunking (returns ``fn`` unchanged).
    """
    if not chunk or chunk <= 0:
        return fn
    mapped_pos = tuple(i for i, ax in enumerate(in_axes) if ax == 0)
    donate = mapped_pos if donate is None else tuple(donate)

    def wrapper(*args):
        C = jax.tree.leaves(args[mapped_pos[0]])[0].shape[0]
        if C <= chunk:
            return fn(*args)
        n_chunks = -(-C // chunk)
        pad = n_chunks * chunk - C

        def prep(tree):
            def one(x):
                if pad:
                    x = jnp.concatenate([x, x[:pad]], axis=0)
                return x.reshape((n_chunks, chunk) + x.shape[1:])

            return jax.tree.map(one, tree)

        stacked = tuple(prep(args[i]) for i in mapped_pos)

        def body(chunks):
            full = list(args)
            for p, c in zip(mapped_pos, chunks):
                full[p] = c
            return fn(*full)

        outs = jax.lax.map(body, stacked)
        return jax.tree.map(
            lambda x: x.reshape((n_chunks * chunk,) + x.shape[2:])[:C], outs)

    if jax.default_backend() == "cpu":      # donation unimplemented on CPU
        donate = ()
    return jax.jit(wrapper, donate_argnums=donate)


def aggregate(trees_list, weights):
    """Server Aggregate/FedAvg: sample-count weighted mean."""
    return trees.tree_weighted_mean(trees_list, weights)


def aggregate_segments(stacked, weights, segment_ids, num_segments: int):
    """Per-cluster FedAvg as ONE batched op: weighted mean over rows of a
    stacked pytree grouped by ``segment_ids`` (cohort row -> cluster
    index). Replaces the per-root Python gather/aggregate loop — the
    server side of the round stays a fixed number of device ops no matter
    how many clusters the cohort spans."""
    w = jnp.asarray(weights, jnp.float32)
    seg = jnp.asarray(segment_ids)
    denom = jax.ops.segment_sum(w, seg, num_segments=num_segments)
    wn = w / denom[seg]

    def leaf(x):
        wb = wn.reshape((-1,) + (1,) * (x.ndim - 1))
        return jax.ops.segment_sum(x * wb, seg,
                                   num_segments=num_segments).astype(x.dtype)

    return jax.tree.map(leaf, stacked)


def aggregate_stacked(stacked, weights):
    """Weighted mean over the leading client axis of a stacked pytree."""
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)

    def mean_leaf(x):
        wb = w.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.sum(x * wb, axis=0).astype(x.dtype)

    return jax.tree.map(mean_leaf, stacked)


def local_sgd(loss_fn, params, batch, lr, steps, prox_to=None, lam=0.0,
              fused: bool = False, backend: str = "auto"):
    """Generic E-step local SGD (shared by FedAvg/FedProx/Ditto/IFCA/CFL).

    prox_to: optional reference params for a FedProx/Ditto prox term.
    ``fused=True`` runs the step loop on the flattened vector through
    ``prox_update_flat`` (θ-output only; the reference is the prox
    anchor, or θ itself with λ=0 for plain SGD — algebraically the same
    expression tree as the unfused path, so fp32 stays bitwise)."""
    grad_fn = jax.grad(loss_fn)

    if fused:
        spec = flat_spec(params)
        ref_f = None if prox_to is None else flatten_tree(prox_to)

        def fstep(pf, _):
            g_f = flatten_tree(grad_fn(unflatten_tree(pf, spec), batch))
            ref = pf if ref_f is None else ref_f
            lam_eff = 0.0 if ref_f is None else lam
            pf, _unused = ops.prox_update_flat(pf, ref, g_f, g_f, lr, lam_eff,
                                               backend=backend)
            return pf, None

        out_f, _ = jax.lax.scan(fstep, flatten_tree(params), None, length=steps)
        return unflatten_tree(out_f, spec)

    def step(p, _):
        g = grad_fn(p, batch)
        if prox_to is not None:
            g = jax.tree.map(lambda gi, pi, ri: gi + lam * (pi - ri), g, p, prox_to)
        p = jax.tree.map(lambda pi, gi: (pi - lr * gi).astype(pi.dtype), p, g)
        return p, None

    out, _ = jax.lax.scan(step, params, None, length=steps)
    return out
