"""Traffic kind ``settled``: a federation past its onboarding.

Set-up runs one long warm-up span (repeated, at most three times, until
every client has been observed), then the window calls
``run_rounds(state, span_rounds)`` back to back on the state each call
returns (the evaluation cadence). Parameters, from the mix's JSON file:
``sample_rate``, ``warmup_rounds``, ``span_rounds``.
"""
import jax

from bench.lib import harness


class Driver:
    def __init__(self, prog, traffic: dict):
        from repro import engine
        self.engine, self.prog = engine, prog
        self.span = int(traffic["span_rounds"])
        warm = int(traffic["warmup_rounds"])
        state, calls = prog.state0, 0
        while True:
            state = engine.run_rounds(state, warm)
            calls += 1
            if len(state.clusters.seen) >= prog.n_clients or calls == 3:
                break
        self.all_seen = len(state.clusters.seen) >= prog.n_clients
        hist = state.history
        self.settled = (self.all_seen and len(hist) > 1 and
                        hist[-1]["n_clusters"] == hist[-2]["n_clusters"])
        state = engine.run_rounds(state, self.span)
        jax.block_until_ready(state.omega)
        self.rounds_before = calls * warm + self.span
        self.handoff = harness.snapshot(state)
        self.state = state
        prog.state0 = None

    def unit(self) -> int:
        self.state = self.engine.run_rounds(self.state, self.span)
        jax.block_until_ready(self.state.omega)
        return self.span

    def scan_args(self):
        """The state and round count of the window's compiled scan."""
        return self.state, self.span

    def psi_per_unit(self) -> int:
        return 0

    def finite(self) -> bool:
        st = self.state
        return harness.finite(st.omega) and all(
            harness.finite(st.cluster_model(r)) for r in st.models.roots)

    def release(self) -> None:
        harness.drop_device_stashes(self.state.ctx)
        self.state = None
