"""CPU tests of the benchmark harness: the trace reduction, the FLOP
counts, that every file named in BENCHMARK.json loads, that run.py
refuses to run without a TPU, that each traffic mix runs end to end at
its configuration's CPU size and compares equal to the reference, and
that the comparison catches the bf16 control and the planted faults. A
token language model with a frozen backbone (``fixtures/root``, a
checkout of its own) runs through every optional hook of a
configuration.

Each configuration's CPU size is its JSON file's ``cpu_test`` group,
applied over the sizes it runs at on the chip.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import glob
import json
import math
import os
import shutil
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
FIXTURE = os.path.join(ROOT, "bench", "tests", "fixtures", "root")
CONFIG_FILES = sorted(
    glob.glob(os.path.join(ROOT, "bench", "configs", "*.json"))
    + glob.glob(os.path.join(FIXTURE, "bench", "configs", "*.json")))
TINY_TRAFFIC = {"warmup_rounds": 30, "unit_rounds": 6}
PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _config_id(path):
    return os.path.basename(path)[: -len(".json")]


def _cpu_cell(root, name):
    """The cell at its configuration's CPU size, with short traffic."""
    conf = harness.Cell(root, name).config
    cell = harness.Cell(root, name, overrides=conf.get("cpu_test", {}))
    cell.traffic = dict(cell.traffic, **{
        k: v for k, v in TINY_TRAFFIC.items() if k in cell.traffic})
    return cell


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


def test_gradient_flops_default_and_hook(lm_root):
    """Where every weight is trained a gradient is 3 forwards (the MLP's
    ``round_step_mfu`` count); a configuration with a frozen backbone
    gives its own count, here less than 3 forwards."""
    cell = harness.Cell(ROOT, ONE_CHIP[0])
    model = cell.config["model"]
    assert harness.gradient_flops(cell) == \
        3 * cell.model_ref.forward_flops(model)
    lm = harness.Cell(lm_root, "lm.onboard")
    fwd = lm.model_ref.forward_flops(lm.config["model"])
    assert 2 * fwd < harness.gradient_flops(lm) < 3 * fwd


@pytest.mark.parametrize("path", CONFIG_FILES, ids=_config_id)
def test_reference_model_matches_program_model(path):
    """The plain forward pass and the program's model agree on the same
    weights, at the sizes the configuration runs at (so the reference
    trains the same function): the module's ``program_apply`` where it
    has one, else the program's ``simple.apply``; a frozen tree is passed
    to both."""
    import jax
    import numpy as np
    conf = json.load(open(path))
    model = conf["model"]
    fed = conf["federation"]
    mod = harness.load_module(path[: -len(".json")] + ".py",
                              "t2_" + _config_id(path).replace("-", "_"))
    p = mod.init_params(jax.random.PRNGKey(0), model)
    shape = (3,) + tuple(model["input_shape"])
    if fed["setting"] == "tokens":
        x = jax.random.randint(jax.random.PRNGKey(1), shape, 0,
                               int(model["n_classes"]))
    else:
        x = jax.random.normal(jax.random.PRNGKey(1), shape)
    fz = ((mod.frozen_params(jax.random.PRNGKey(2), model),)
          if hasattr(mod, "frozen_params") else ())
    with jax.default_matmul_precision("highest"):
        got = mod.apply(p, x, model, *fz)
        if hasattr(mod, "program_apply"):
            want = mod.program_apply(p, x, model, *fz)
        else:
            from repro.models import simple
            want = simple.apply(p, x, mod.program_task(model))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------- the files
def test_every_named_file_loads():
    for w in BENCH["workloads"]:
        cell = harness.Cell(ROOT, w["name"])
        assert callable(cell.driver.unit)
        ref = cell.model_ref
        assert callable(getattr(ref, "program_init", None)
                        or ref.program_loss)
        assert callable(ref.forward_flops)
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
    cell = _cpu_cell(ROOT, ONE_CHIP[0])
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
def _tiny_run(name, training=None, seed=3000000007, root=ROOT):
    cell = _cpu_cell(root, name)
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


# ------------------------------- a frozen-backbone token LM (fixture)
@pytest.fixture(scope="module")
def lm_root(tmp_path_factory):
    """The fixture's checkout: its own BENCHMARK.json, configuration and
    limits, with this checkout's traffic mixes and metric readers."""
    root = str(tmp_path_factory.mktemp("lm") / "root")
    shutil.copytree(FIXTURE, root)
    for d in ("traffic", "metrics"):
        os.symlink(os.path.join(ROOT, "bench", d),
                   os.path.join(root, "bench", d))
    return root


def test_token_federation_is_next_token_prediction_in_cluster_windows():
    import numpy as np
    from bench.lib import federation
    fed = {"setting": "tokens", "n_clusters": 4, "n_clients": 8,
           "n_per": 3, "n_states": 8}
    x, y, cluster = federation.generate(fed, [12], 64, 3000000019)
    assert x.shape == y.shape == (8, 3, 12)
    assert str(x.dtype) == str(y.dtype) == "int32"
    x, y = np.asarray(x), np.asarray(y)
    np.testing.assert_array_equal(x[..., 1:], y[..., :-1])
    np.testing.assert_array_equal(np.asarray(cluster),
                                  [0, 0, 1, 1, 2, 2, 3, 3])
    for c in range(4):
        # cluster c draws from ids [16 c, 16 c + 32) mod 64
        off = (x[2 * c: 2 * c + 2] - 16 * c) % 64
        assert off.max() < 32
    x2 = federation.generate(fed, [12], 64, 3000000019)[0]
    np.testing.assert_array_equal(x, np.asarray(x2))


def test_lm_fixture_runs_end_to_end_and_matches_reference(lm_root):
    cell, out = _tiny_run("lm.onboard", root=lm_root)
    cohort = math.ceil(cell.traffic["sample_rate"]
                       * cell.config["federation"]["n_clients"])
    assert cell.config["reference"]["client_block"] < cohort
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["numbers"]["partition_mismatch"] == 0
    assert checks.judge(out["numbers"], cell.limits())


def test_lm_fixture_bf16_control_is_not_correct(lm_root):
    cell, out = _tiny_run("lm.onboard", training={"dtype": "bfloat16"},
                          root=lm_root)
    assert not checks.judge(out["numbers"], cell.limits())


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_lm_fixture_planted_fault_is_not_correct(lm_root, fault):
    with faults.FAULTS[fault]():
        cell, out = _tiny_run("lm.onboard", root=lm_root)
    assert not checks.judge(out["numbers"], cell.limits())


def test_reference_client_blocks_match_whole_cohort(lm_root):
    """Blocks of one client and the whole cohort at once give the same
    reference, and so the same numbers."""
    cell = _cpu_cell(lm_root, "lm.onboard")
    prog = harness.Program(cell, 3000000011)
    drv = cell.driver(prog, cell.traffic)
    args = (drv.handoff, drv.rounds_before, prog.omega0, prog.ecfg)
    numbers = {}
    for block in (1, None):
        cell.config["reference"] = {"client_block": block}
        numbers[block] = harness.reference_numbers(cell, 3000000011, *args)
    assert numbers[1]["partition_mismatch"] == \
        numbers[None]["partition_mismatch"] == 0
    for k in ("omega_change_gap", "cluster_change_gap"):
        assert abs(numbers[1][k] - numbers[None][k]) < 1e-6


def test_reference_takes_the_frozen_tree_as_an_argument(lm_root):
    """With a 256 x 256 frozen matrix (256 KB of f32) the reference's
    lowered ``observe`` stays small: the tree is an argument of the
    program, not a constant inside it."""
    import jax
    import jax.numpy as jnp
    from bench.lib import federation
    from bench.lib.reference import Reference
    cell = _cpu_cell(lm_root, "lm.onboard")
    model = dict(cell.config["model"], d_model=256)
    cell.config["model"] = model
    frozen = harness.frozen_params(cell)
    assert frozen["w"].shape[1:] == (256, 256)
    x, y, _ = federation.generate(cell.config["federation"],
                                  model["input_shape"], model["n_classes"],
                                  5)
    omega0 = jax.device_get(cell.model_ref.init_params(
        jax.random.PRNGKey(0), model))
    ref = Reference(lambda p, xb, fz: cell.model_ref.apply(p, xb, model, fz),
                    cell.config["training"], x, y, omega0, 3, 0.2,
                    frozen=frozen, client_block=2)
    m = ref.m
    text = ref._observe.lower(
        ref.bank, jnp.arange(m, dtype=jnp.int32), jnp.ones(m, bool),
        x[:m], y[:m], frozen).as_text()
    assert len(text) < 64 * 1024
