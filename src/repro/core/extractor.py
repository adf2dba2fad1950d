"""Distribution extractor Ψ (paper §3.1).

Ψ(D) = Normalize(∂ℓ(ψ; D)/∂ψ): the L2-normalized gradient of a *frozen*
anchor model ψ over a client's local dataset — a representation of the
local data distribution. The anchor is never optimized; the paper sets
ψ = ω₀ (the FL initialization), which we follow by default.

For LLM-scale anchors the full-gradient representation is |θ|-dimensional;
``project_dim`` enables a sparse Johnson-Lindenstrauss sketch (signed
feature hashing) so the server-side clustering state is O(project_dim) per
client. This is a beyond-paper optimization — OFF by default.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import bilevel
from repro.utils import trees


def _jl_sketch(vec, dim: int, seed: int = 0):
    """Signed-bucket projection: preserves cosine in expectation."""
    n = vec.shape[0]
    key = jax.random.PRNGKey(seed)
    kb, ks = jax.random.split(key)
    buckets = jax.random.randint(kb, (n,), 0, dim)
    signs = jax.random.rademacher(ks, (n,), dtype=jnp.float32)
    return jax.ops.segment_sum(vec * signs, buckets, num_segments=dim)


def make_extractor(loss_fn: Callable, anchor_params,
                   project_dim: Optional[int] = None,
                   batched: bool = False,
                   leaf_filter: Optional[Callable[[str], bool]] = None) -> Callable:
    """Returns Ψ: batch -> normalized representation vector.

    loss_fn(params, batch) -> scalar, or loss_fn(params, batch, frozen)
    where the model reads a frozen tree; Ψ is then ``psi(batch, frozen)``
    and the tree an argument of its program, never a constant. A loss
    that returns ``(loss, stats)`` has its stats dropped here. Ψ is the
    gradient over the trained tree alone. If ``batched``, the returned fn
    maps a stacked client batch (leading client axis) to stacked
    representations via vmap — the SPMD path used when clients ride the
    mesh's data axis.

    leaf_filter("path/to/leaf") -> bool restricts Ψ to a parameter subset.
    For LLM anchors the data-distribution signal concentrates in the
    embedding/lm_head gradients (token marginals); the body gradient is
    per-token noise that drowns the cosine signal (see examples/
    federated_llm.py) — ``llm_leaf_filter`` keeps only those rows.
    """
    def scalar_loss(*args):
        out = loss_fn(*args)
        return out[0] if isinstance(out, tuple) else out

    grad_fn = jax.grad(scalar_loss)

    def psi(batch, frozen=None):
        g = grad_fn(anchor_params, batch, *bilevel.loss_args(frozen))
        if leaf_filter is not None:
            flat = jax.tree_util.tree_flatten_with_path(g)[0]
            kept = [jnp.ravel(v) for kp, v in flat
                    if leaf_filter("/".join(str(getattr(k, "key", k)) for k in kp))]
            vec = jnp.concatenate([x.astype(jnp.float32) for x in kept])
        else:
            vec = trees.tree_flatten_vector(g)
        if project_dim:
            vec = _jl_sketch(vec, project_dim)
        norm = jnp.linalg.norm(vec)
        return jnp.where(norm > 0, vec / norm, vec)

    psi = jax.jit(psi)
    if batched:
        return jax.jit(jax.vmap(lambda b: psi(b)))
    return psi


def representation(loss_fn, anchor_params, batch, project_dim=None):
    """One-shot Ψ(D) (convenience, non-jitted caller side)."""
    return make_extractor(loss_fn, anchor_params, project_dim)(batch)


def llm_leaf_filter(path: str) -> bool:
    """Ψ restricted to the distribution-bearing vocab matrices."""
    return ("embed" in path) or ("lm_head" in path)
