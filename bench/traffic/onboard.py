"""Traffic kind ``onboard``: the first ``unit_rounds`` rounds of a fresh
federation, repeated.

Each unit is ``run_rounds`` on the engine's initial state with a fresh
copy of its sampling key (one compiled program, the same context), so
every unit observes new clients, extracts their Psi and runs the merge
pass each round. The state a unit returns is dropped. Parameters, from
the mix's JSON file: ``sample_rate``, ``unit_rounds``.
"""
import jax
import jax.numpy as jnp

from bench.lib import harness


class Driver:
    def __init__(self, prog, traffic: dict):
        from repro import engine
        self.engine, self.prog = engine, prog
        self.rounds = int(traffic["unit_rounds"])
        self.key0 = jnp.copy(prog.state0.rng_key)
        out = self._run()
        self.rounds_before = self.rounds
        self.handoff = harness.snapshot(out)
        self.n_seen = len(out.clusters.seen)
        self.last_finite = harness.finite(out.omega)
        del out
        harness.drop_device_stashes(prog.state0.ctx)

    def _fresh(self):
        return self.prog.state0.replace(rng_key=jnp.copy(self.key0))

    def _run(self):
        out = self.engine.run_rounds(self._fresh(), self.rounds)
        jax.block_until_ready(out.omega)
        return out

    def unit(self) -> int:
        out = self._run()
        self.last_finite = harness.finite(out.omega)
        del out
        harness.drop_device_stashes(self.prog.state0.ctx)
        return self.rounds

    def scan_args(self):
        """The state and round count of the window's compiled scan."""
        return self._fresh(), self.rounds

    def psi_per_unit(self) -> int:
        return self.n_seen

    def finite(self) -> bool:
        return self.last_finite

    def release(self) -> None:
        self.prog.state0 = None
