"""The comparison that decides ``correct``: the program's state at the
hand-off to the window against the plain reference after the same rounds.

Numbers compared (each with a limit of its own, ``bench/limits/<cell>.json``):

  partition_mismatch   clients whose cluster root differs between the two,
                       or that only one side has observed (exact: limit 0)
  omega_change_gap     over the leaves of omega, the worst gap between the
                       norms of the change from w0, program against
                       reference, over the reference's norm of that leaf
                       or of the median leaf, whichever is larger
  cluster_change_gap   the same over every cluster model's leaves; a
                       cluster model that only one side has reads 1

Leaves whose reference change is under a thousandth of the median leaf's
are left out: they move by round-off alone.
"""
from __future__ import annotations

import json
import os

import jax
import numpy as np

LEAF_FLOOR = 1e-3


def _host64(tree):
    return [np.asarray(np.asarray(l, np.float32), np.float64)
            for l in jax.tree.leaves(tree)]


def change_gap(prog, ref, base) -> float:
    """Worst leaf's gap between the norms of the change from ``base``."""
    p, r, b = _host64(prog), _host64(ref), _host64(base)
    dp = np.array([np.linalg.norm(x - z) for x, z in zip(p, b)])
    dr = np.array([np.linalg.norm(x - z) for x, z in zip(r, b)])
    med = float(np.median(dr))
    keep = dr >= LEAF_FLOOR * med
    if not keep.any():
        return 0.0
    gaps = np.abs(dp - dr) / np.maximum(dr, med)
    return float(np.max(gaps[keep]))


def compare(prog, ref, omega0) -> dict:
    """``prog`` and ``ref`` are ``(omega, {root: model}, {client: root})``."""
    p_omega, p_models, p_assign = prog
    r_omega, r_models, r_assign = ref
    clients = set(p_assign) | set(r_assign)
    mismatch = sum(1 for c in clients
                   if p_assign.get(c, -1) != r_assign.get(c, -2))
    cluster = 0.0
    for root in set(p_models) | set(r_models):
        if root in p_models and root in r_models:
            cluster = max(cluster, change_gap(p_models[root], r_models[root],
                                              omega0))
        else:
            cluster = max(cluster, 1.0)
    return {"partition_mismatch": float(mismatch),
            "omega_change_gap": change_gap(p_omega, r_omega, omega0),
            "cluster_change_gap": cluster}


def limits(root: str, cell: str) -> dict:
    path = os.path.join(root, "bench", "limits", f"{cell}.json")
    with open(path) as f:
        return {k: float(v) for k, v in json.load(f)["limits"].items()}


def judge(numbers: dict, lim: dict) -> bool:
    """Every number finite and at or under its limit."""
    return all(k in numbers and np.isfinite(numbers[k])
               and numbers[k] <= v for k, v in lim.items())
