"""Faults planted in the program under test, to show that the comparison
catches them. Each is a context manager that patches the program for the
block and restores it after.

  unchanged    a step that returns its state unchanged: ``run_rounds``
               advances the round counter and does nothing else
  half_batch   half of the cohort left out of the global aggregation, the
               mean taken over the rest
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def unchanged():
    from repro import engine

    def run_rounds(state, rounds, unavailable=frozenset()):
        return state.replace(round=state.round + int(rounds))

    saved = engine.run_rounds
    engine.run_rounds = run_rounds
    try:
        yield
    finally:
        engine.run_rounds = saved


@contextlib.contextmanager
def half_batch():
    import jax.numpy as jnp
    from repro.core import aggregators

    mean = aggregators.AGGREGATORS["mean"]

    def first_half(stacked, weights):
        w = jnp.asarray(weights, jnp.float32)
        return mean(stacked, w.at[w.shape[0] // 2:].set(0.0))

    aggregators.AGGREGATORS["mean"] = first_half
    try:
        yield
    finally:
        aggregators.AGGREGATORS["mean"] = mean


FAULTS = {"unchanged": unchanged, "half_batch": half_batch}
