"""One run of one cell: set-up, the measured window, the trace, the
comparison with the reference. Everything a cell needs is found by name:

  BENCHMARK.json                       the cell, its configuration, its metrics
  bench/configs/<config>.json          the configuration as it is run
  bench/configs/<config>.py            its plain model reference, FLOP
                                       count and the program's loss, and
                                       the optional hooks below
  bench/traffic/<traffic>.json         the traffic mix's parameters
  bench/traffic/<kind>.py              the driver of the mix's ``kind``
  bench/metrics/<metric>.py            one reader per per-layer metric
  bench/limits/<cell>.json             the limits of the numbers compared

The system under test is the program's public engine: ``engine.init(
<strategy>, ..., arena=True)`` with the device partition and device
sampling, driven through ``engine.run_rounds``. The data comes from the
run's seed; the initial weights and the engine's sampling key come from
the configuration's ``fixed_seeds``, so every seed runs the same
compiled programs on the same arrival order.

A configuration module always defines ``init_params(key, model)``,
``apply(params, x, model)`` and ``forward_flops(model)``, and
``program_loss(model)`` unless it defines ``program_init``. It may
define, and where it does not the harness does what the default says:

  frozen_params(key, model)   a tree of weights that is not trained (a
                              frozen backbone), drawn on the device from
                              ``fixed_seeds.frozen``; the reference's
                              ``apply`` then takes it as a fourth
                              argument, ``apply(params, x, model,
                              frozen)``. Default: none.
  program_init(model, frozen, strategy, params0, clients, ecfg, mesh)
                              the program's initial ``ServerState``.
                              Default: ``engine.init(strategy,
                              program_loss(model), params0, clients,
                              ecfg, mesh=mesh, arena=True)``.
  gradient_flops(model)       model FLOPs of one example's gradient.
                              Default: ``3 * forward_flops(model)``.

and its JSON file may hold ``reference.client_block``, the number of
clients the reference runs at once (default: the whole cohort).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import checks, federation
from bench.lib import trace as tr
from bench.lib.reference import Reference


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str):
    with open(path) as f:
        return json.load(f)


class Cell:
    """A workload of ``BENCHMARK.json`` with its files loaded."""

    def __init__(self, root: str, name: str, overrides=None):
        bench = _read_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json")
        self.root, self.name = root, name
        w = cells[name]
        self.chips = int(w["chips"])
        conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
        self.config = _read_json(os.path.join(root, conf["file"]))
        strategy = self.config["training"]["strategy"]
        if strategy != Reference.STRATEGY:
            raise ValueError(f"no plain reference for strategy {strategy!r}")
        for group, values in (overrides or {}).items():
            self.config[group] = dict(self.config[group], **values)
        self.model_ref = load_module(
            os.path.join(root, conf["file"])[: -len(".json")] + ".py",
            "bench_config_" + w["config"].replace("-", "_"))
        self.traffic = _read_json(os.path.join(
            root, "bench", "traffic", w["traffic"] + ".json"))
        kind = self.traffic["kind"]
        self.driver = load_module(
            os.path.join(root, "bench", "traffic", kind + ".py"),
            "bench_traffic_" + kind.replace("-", "_")).Driver
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def limits(self) -> dict:
        return checks.limits(self.root, self.name)


# ------------------------------------------------------------------ program
class Program:
    """The federation, ω₀ and the engine's initial state for one seed."""

    def __init__(self, cell: Cell, seed: int, training=None):
        from repro import engine
        cfg = cell.config
        model, fed = cfg["model"], cfg["federation"]
        self.training = dict(cfg["training"], **(training or {}))
        x, y, _ = federation.generate(fed, model["input_shape"],
                                      model["n_classes"], seed)
        clients = federation.host_clients(x, y)
        del x, y
        init = jax.jit(lambda k: cell.model_ref.init_params(k, model))
        fixed = cfg["fixed_seeds"]
        params0 = init(federation.key(fixed["weights"], "weights"))
        frozen = frozen_params(cell)
        self.omega0 = jax.device_get(params0)
        self.n_params = sum(int(np.prod(l.shape))
                            for l in jax.tree.leaves(self.omega0))
        t = self.training
        self.ecfg = engine.EngineConfig(
            tau=float(t["tau"]), lam=float(t["lam"]), lr=float(t["lr"]),
            local_steps=int(t["local_steps"]),
            sample_rate=float(cell.traffic["sample_rate"]),
            seed=federation.sub_seed(fixed["sampling"], "engine"),
            aggregator=t["aggregator"], cluster_backend="device",
            rng_backend="device", fused_step=bool(t["fused_step"]),
            dtype=t["dtype"])
        mesh = None
        if cell.chips > 1:
            from repro.launch.mesh import make_client_mesh
            mesh = make_client_mesh(cell.chips)
        program_init = getattr(cell.model_ref, "program_init", None)
        if program_init is None:
            self.state0 = engine.init(
                t["strategy"], cell.model_ref.program_loss(model), params0,
                clients, self.ecfg, mesh=mesh, arena=True)
        else:
            self.state0 = program_init(model, frozen, t["strategy"],
                                       params0, clients, self.ecfg, mesh)
        self.n_clients = len(clients)
        self.n_per = int(fed["n_per"])
        self.m = int(math.ceil(self.ecfg.sample_rate * self.n_clients))


def frozen_params(cell: Cell):
    """The configuration's frozen tree, drawn in one jitted call on the
    device from ``fixed_seeds.frozen``; None where it has none."""
    make = getattr(cell.model_ref, "frozen_params", None)
    if make is None:
        return None
    model = cell.config["model"]
    k = federation.key(cell.config["fixed_seeds"]["frozen"], "frozen")
    return jax.jit(lambda kk: make(kk, model))(k)


def gradient_flops(cell: Cell) -> int:
    """Model FLOPs of one example's gradient: the configuration's own
    count, or forward + backward = 3 forwards where every weight is
    trained."""
    model, ref = cell.config["model"], cell.model_ref
    if hasattr(ref, "gradient_flops"):
        return int(ref.gradient_flops(model))
    return 3 * int(ref.forward_flops(model))


def snapshot(state):
    """(omega, {root: model}, {client: root}) of a state, as host arrays."""
    omega = jax.device_get(state.omega)
    assign = {int(c): int(r) for c, r in state.clusters.assignment().items()}
    models = {r: jax.device_get(state.cluster_model(r))
              for r in set(assign.values()) if r in state.models}
    return omega, models, assign


def finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(l.astype(jnp.float32))))
               for l in jax.tree.leaves(tree)
               if jnp.issubdtype(l.dtype, jnp.floating))


def drop_device_stashes(ctx) -> None:
    """Forget what the context keeps between calls besides compiled
    programs (the warm-resume carry): a fresh federation never resumes,
    and the stash holds (C, |θ|) buffers on the device."""
    for k in [k for k, v in ctx.cache.items() if not callable(v)]:
        del ctx.cache[k]


# --------------------------------------------------------------------- run
def memory_peak(chips: int) -> int:
    """``peak_bytes_in_use`` of the fullest of the cell's chips."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def run_window(drv, seconds: float):
    """Units back to back until ``seconds`` have passed; returns
    (units, rounds, window seconds, XLA compiles requested in it)."""
    from repro.analysis import sanitize
    units = rounds = 0
    with sanitize.compile_budget() as log, \
            jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.unit"):
                rounds += drv.unit()
            units += 1
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    return units, rounds, window_s, log.count


def breakdown(trace: tr.Trace, lo: float, hi: float, n: int = 10) -> dict:
    """The device operations that took most time (self time, seconds per
    chip, mean over the chips) and the longest idle gaps of the first
    chip, each named by what the host was doing in it."""
    planes = sorted(trace.devices)
    by_name: dict = {}
    for p in planes:
        for e, t in tr.self_times(tr.clip(trace.devices[p], lo, hi)):
            k = tr.short_name(e.name)
            by_name[k] = by_name.get(k, 0.0) + t
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:n]
    gaps = tr.idle_gaps(trace.devices[planes[0]], lo, hi)[:n] if planes else []
    return {
        "device_ops": [[k, v / 1e9 / max(len(planes), 1)] for k, v in ops],
        "idle_gaps": [[tr.host_activity(trace, (a + b) / 2), (b - a) / 1e9]
                      for a, b in gaps],
    }


def run(cell: Cell, seed: int, seconds: float, traced: bool,
        t_start: float, peak=None, training=None) -> dict:
    """One run of ``cell``; returns the result's fields and the numbers
    compared. ``training`` overrides keys of the configuration's training
    group in the program only (the control), never in the reference."""
    from bench.lib import peaks
    from repro.analysis import sanitize
    devs = jax.devices()
    kind = devs[0].device_kind
    peak = peak or peaks.peak(kind)
    t_program = time.perf_counter()
    with sanitize.compile_budget() as setup_log:
        prog = Program(cell, seed, training)
        t_traffic = time.perf_counter()
        drv = cell.driver(prog, cell.traffic)
    setup_s = time.perf_counter() - t_start
    phases = {"start_s": t_program - t_start,
              "program_s": t_traffic - t_program,
              "traffic_setup_s": t_start + setup_s - t_traffic,
              "setup_compiles": setup_log.count,
              "setup_cache_hits": setup_log.cache_hits}

    result_trace = None
    if traced:
        with tr.recording() as rec:
            units, rounds, window_s, compiles = run_window(drv, seconds)
        result_trace = rec.get("trace")
    else:
        units, rounds, window_s, compiles = run_window(drv, seconds)
    attempted = rounds * prog.m
    failed = 0 if drv.finite() else attempted
    mem = memory_peak(cell.chips)

    steps = int(prog.training["local_steps"])
    # model FLOPs: two gradients (θ and ω) per example and local step,
    # one more per example of each new client's Ψ
    flops_unit = prog.n_per * gradient_flops(cell) * (
        (rounds // max(units, 1)) * prog.m * steps * 2 + drv.psi_per_unit())
    view = {
        "trace": result_trace, "window_s": window_s, "chips": cell.chips,
        "peak": peak, "model_flops": flops_unit * units,
        "compiles": compiles,
        "merge_k": 1 << (prog.n_clients - 1).bit_length(),
        "psi_dim": prog.n_params,
    }
    handoff, rounds_before = drv.handoff, drv.rounds_before
    omega0, ecfg = prog.omega0, prog.ecfg
    drv.release()
    del drv, prog
    gc.collect()

    metrics, extra = {}, {}
    if traced:
        if result_trace is not None and result_trace.devices:
            lo, hi = result_trace.window()
            view["window_ns"] = (lo, hi)
            planes = sorted(result_trace.devices)[: cell.chips]
            busy = [tr.busy_ns(result_trace.devices[p], lo, hi)
                    for p in planes]
            extra["busy_s"] = float(np.mean(busy)) / 1e9
            extra["window_s"] = (hi - lo) / 1e9
            extra["breakdown"] = breakdown(result_trace, lo, hi)
        for m in cell.per_layer:
            reader = load_module(os.path.join(
                cell.root, "bench", "metrics", m["name"] + ".py"),
                "bench_metric_" + m["name"].replace(".", "_"))
            value = reader.read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"setup_s": setup_s,
                  "client_updates_per_s": attempted / window_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    t_ref = time.perf_counter()
    numbers = reference_numbers(cell, seed, handoff, rounds_before, omega0,
                                ecfg)
    phases["reference_s"] = time.perf_counter() - t_ref
    return {"metrics": metrics, "attempted": attempted, "failed": failed,
            "memory_peak_bytes": mem, "numbers": numbers, "phases": phases,
            "kind": kind, "platform": devs[0].platform, **extra}


def reference_numbers(cell: Cell, seed: int, handoff, rounds: int, omega0,
                      ecfg) -> dict:
    """Run the reference over the same rounds and compare."""
    model = cell.config["model"]
    x, y, _ = federation.generate(cell.config["federation"],
                                  model["input_shape"], model["n_classes"],
                                  seed)
    frozen = frozen_params(cell)
    if frozen is None:
        def apply(p, xb, fz):
            return cell.model_ref.apply(p, xb, model)
    else:
        def apply(p, xb, fz):
            return cell.model_ref.apply(p, xb, model, fz)
    ref = Reference(apply, cell.config["training"], x, y, omega0, ecfg.seed,
                    ecfg.sample_rate, frozen=frozen,
                    client_block=cell.config.get("reference", {}).get(
                        "client_block"))
    ref.run(rounds)
    return checks.compare(handoff, ref.result(), omega0)
