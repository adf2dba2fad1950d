"""CPU tests of the benchmark harness: the trace reduction, the FLOP
counts, that every file named in BENCHMARK.json loads, that run.py
refuses to run without a TPU, that each traffic mix runs end to end at a
tiny size and compares equal to the reference, and that the comparison
catches the bf16 control and the planted faults.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import json
import os
import subprocess
import sys
import time

import pytest

from bench.lib import checks, harness
from bench.lib import trace as tr
from bench.tools import faults

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]
TINY = {"federation": {"n_clients": 40, "n_per": 16},
        "model": {"hidden": 64, "fc_hidden": 16,
                  "conv_channels": [4, 8]}}
TINY_TRAFFIC = {"warmup_rounds": 30, "unit_rounds": 6}
PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


# --------------------------------------------------------------- the trace
def _ev(a, b, name="fusion", cat=""):
    return tr.Event(float(a), float(b), name, cat)


def _trace():
    dev = [_ev(10, 30), _ev(20, 40),
           _ev(60, 70, "%merge_candidates.1 = f32[8,8] custom-call(...)"),
           _ev(70, 71, "%compare_reduce_fusion.1 = pred[] fusion("
                       "f32[8,8] %merge_candidates.1)"),
           _ev(90, 120), _ev(125, 130, "%all-reduce.3 = f32[4] all-reduce()")]
    host = [("python", _ev(0, 100, tr.WINDOW_SPAN)),
            ("python", _ev(0, 50, "bench.unit")),
            ("python", _ev(50, 100, "bench.unit")),
            ("python", _ev(42, 58, "TransferFromDevice"))]
    return tr.Trace({"/device:TPU:0": dev}, host)


def test_trace_union_busy_and_gaps():
    t = _trace()
    lo, hi = t.window()
    assert (lo, hi) == (0.0, 100.0)
    evs = t.devices["/device:TPU:0"]
    assert tr.union(tr.clip(evs, lo, hi)) == [(10, 40), (60, 71), (90, 100)]
    assert tr.busy_ns(evs, lo, hi) == 51.0
    assert tr.idle_gaps(evs, lo, hi) == [(40, 60), (71, 90), (0, 10)]
    assert tr.host_activity(t, 45.0) == "bench.unit > TransferFromDevice"


def test_trace_kernel_time_by_name():
    evs = _trace().devices["/device:TPU:0"]
    own = lambda key: lambda s: tr.short_name(s).startswith(key)  # noqa
    assert tr.time_by_name(evs, own("merge_candidates")) == (10.0, 1)
    assert tr.time_by_name(evs, own("all-reduce")) == (5.0, 1)
    # an op that reads the kernel's output names it in its HLO text
    assert tr.time_by_name(evs, lambda s: "merge_candidates" in s) == \
        (11.0, 2)


def test_breakdown_and_metric_readers_on_a_trace():
    t = _trace()
    lo, hi = t.window()
    b = harness.breakdown(t, lo, hi)
    assert b["idle_gaps"][0][1] == pytest.approx(20e-9)
    assert "TransferFromDevice" in b["idle_gaps"][0][0]
    assert b["device_ops"][0][0] == "fusion"
    assert "compare_reduce_fusion.1" in [k for k, _ in b["device_ops"]]
    view = {"trace": t, "window_ns": (lo, hi), "chips": 1, "window_s": 1.0,
            "peak": PEAK, "model_flops": 1e10, "compiles": 0,
            "merge_k": 512, "psi_dim": 1000}
    mod = {m: harness.load_module(os.path.join(ROOT, "bench", "metrics",
                                               m + ".py"), "t_" + m)
           for m in ("device_idle_share", "round_step_mfu",
                     "merge_candidates_roofline", "window_compiles")}
    assert mod["device_idle_share"].read(view) == pytest.approx(49.0)
    assert mod["round_step_mfu"].read(view) == pytest.approx(1.0)
    assert mod["window_compiles"].read(view) == 0.0
    least = mod["merge_candidates_roofline"].min_seconds(512, 1000, PEAK)
    assert mod["merge_candidates_roofline"].read(view) == pytest.approx(
        100.0 * least / 10e-9)
    view["trace"] = tr.Trace({"/device:TPU:0": [_ev(0, 10)]}, t.host)
    assert mod["merge_candidates_roofline"].read(view) is None


# ------------------------------------------------------------------ FLOPs
@pytest.mark.parametrize("config,flops", [
    ("stocfl-mnist-mlp", 2 * (784 * 2048 + 2048 * 10)),
    ("stocfl-cifar-cnn", 1_769_472 + 9_437_184 + 1_048_576 + 2_560)])
def test_forward_flops_match_hand_counts(config, flops):
    conf = json.load(open(os.path.join(ROOT, "bench", "configs",
                                       config + ".json")))
    mod = harness.load_module(os.path.join(ROOT, "bench", "configs",
                                           config + ".py"), "t_" + config)
    assert mod.forward_flops(conf["model"]) == flops


@pytest.mark.parametrize("config", ["stocfl-mnist-mlp", "stocfl-cifar-cnn"])
def test_reference_model_matches_program_model(config):
    """The plain forward pass and the program's model agree on the same
    weights (so the reference trains the same function)."""
    import jax
    import numpy as np
    from repro.models import simple
    conf = json.load(open(os.path.join(ROOT, "bench", "configs",
                                       config + ".json")))
    model = conf["model"]
    mod = harness.load_module(os.path.join(ROOT, "bench", "configs",
                                           config + ".py"), "t2_" + config)
    p = mod.init_params(jax.random.PRNGKey(0), model)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (3,) + tuple(model["input_shape"]))
    with jax.default_matmul_precision("highest"):
        got = mod.apply(p, x, model)
        want = simple.apply(p, x, mod.program_task(model))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- the files
def test_every_named_file_loads():
    for w in BENCH["workloads"]:
        cell = harness.Cell(ROOT, w["name"])
        assert callable(cell.driver.unit)
        assert callable(cell.model_ref.program_loss)
        assert callable(cell.model_ref.forward_flops)
        assert set(cell.limits()) == {"partition_mismatch",
                                      "omega_change_gap",
                                      "cluster_change_gap"}
    for m in BENCH["per_layer"]:
        mod = harness.load_module(os.path.join(
            ROOT, "bench", "metrics", m["name"] + ".py"), "t3_" + m["name"])
        assert callable(mod.read)
    for c in BENCH["configs"]:
        assert json.load(open(os.path.join(ROOT, c["file"])))["name"] == \
            c["name"]


def test_seed_draws_the_data_and_nothing_the_program_compiles():
    """Two seeds share the initial weights (compiled into the program's
    scans) and the sampling key, and differ in their data."""
    import jax
    import numpy as np
    from bench.lib import federation
    cell = harness.Cell(ROOT, ONE_CHIP[0], overrides=TINY)
    a, b = (harness.Program(cell, s) for s in (11, 3000000019))
    for la, lb in zip(*(jax.tree.leaves(p.omega0) for p in (a, b))):
        np.testing.assert_array_equal(la, lb)
    assert a.ecfg.seed == b.ecfg.seed
    fed, model = cell.config["federation"], cell.config["model"]
    xa, xb = (federation.generate(fed, model["input_shape"],
                                  model["n_classes"], s)[0]
              for s in (11, 3000000019))
    assert not np.array_equal(np.asarray(xa), np.asarray(xb))


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", BENCH["workloads"][0]["name"],
                        "--seed", "3000000001", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# --------------------------------------------------- end to end, tiny, CPU
def _tiny_run(name, training=None, seed=3000000007):
    cell = harness.Cell(ROOT, name, overrides=TINY)
    cell.traffic = dict(cell.traffic, **{
        k: v for k, v in TINY_TRAFFIC.items() if k in cell.traffic})
    out = harness.run(cell, seed, 0.5, False, time.perf_counter(),
                      peak=PEAK, training=training)
    return cell, out


@pytest.mark.parametrize("name", ONE_CHIP)
def test_traffic_runs_end_to_end_and_matches_reference(name):
    cell, out = _tiny_run(name)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["client_updates_per_s"]["value"] > 0
    assert out["numbers"]["partition_mismatch"] == 0
    assert checks.judge(out["numbers"], cell.limits())


@pytest.mark.parametrize("name", ONE_CHIP)
def test_bf16_control_is_not_correct(name):
    cell, out = _tiny_run(name, training={"dtype": "bfloat16"})
    assert not checks.judge(out["numbers"], cell.limits())


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("name", ONE_CHIP)
def test_planted_fault_is_not_correct(name, fault):
    with faults.FAULTS[fault]():
        cell, out = _tiny_run(name)
    assert not checks.judge(out["numbers"], cell.limits())
