"""DeepSeek-V2-Lite as a federated LoRA fine-tune, on the CPU at a small
size of the same structure (``bench/configs/stocfl-deepseek-v2-lite-lora``'s
``cpu_test`` group, seeded random weights):

- the program's block (MLA with the latent RMSNorm and YaRN, the held
  share of the experts, the adapters) equals the plain reference;
- YaRN's frequencies and MLA's softmax scale equal hand-computed values;
- the expert shares of all chips, the shared experts counted once, add
  up to the uncut layer, and the held-share layer drops no token where
  the capacity layer would;
- the grouped-matmul kernel (interpreted) and its frozen-weight VJP;
- ``run_rounds`` with a frozen tree equals the eager round loop, and its
  lowered scan holds no constant of the frozen tree's size;
- with no frozen tree the MLP's lowered scans are the text they were
  before the frozen argument existed.
"""
import hashlib
import json
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.configs.deepseek_v2_lite import FULL
from repro.data import rotated
from repro.kernels import ops, ref
from repro.kernels.grouped_matmul import grouped_matmul
from repro.models import attention, layers, moe, simple, transformer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from bench.lib import harness  # noqa: E402

CONF = json.load(open(os.path.join(
    ROOT, "bench", "configs", "stocfl-deepseek-v2-lite-lora.json")))
MODEL = dict(CONF["model"], **CONF["cpu_test"]["model"])
REF = harness.load_module(os.path.join(
    ROOT, "bench", "configs", "stocfl-deepseek-v2-lite-lora.py"), "dsv2_ref")


def _trained(key, model=MODEL, scale=0.05):
    """Adapters moved off ω₀ (B = 0 there), so they change the output."""
    p = REF.init_params(key, model)
    leaves, tdef = jax.tree.flatten(p)
    ks = jax.random.split(jax.random.fold_in(key, 7), len(leaves))
    return jax.tree.unflatten(tdef, [
        l + scale * jax.random.normal(k, l.shape) for l, k in zip(leaves, ks)])


# ----------------------------------------------------------- the block
def test_block_matches_reference():
    p = _trained(jax.random.PRNGKey(0))
    fz = REF.frozen_params(jax.random.PRNGKey(1), MODEL)
    x = jax.random.randint(jax.random.PRNGKey(2), (3, 16), 0,
                           MODEL["n_classes"])
    with jax.default_matmul_precision("highest"):
        want = REF.apply(p, x, MODEL, fz)
        got = REF.program_apply(p, x, MODEL, fz)
    assert float(jnp.max(jnp.abs(want))) > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_yarn_frequencies_and_softmax_scale_by_hand():
    """DeepSeek-V2-Lite's rope: the correction range of beta 32 and 1 over
    64 dims, base 10000 and 4096 original positions is [10, 23]; below it
    the frequencies are kept, above it divided by 40, linearly between."""
    dim, base = 64, 10000.0
    low = math.floor(dim * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(base)))
    high = math.ceil(dim * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(base)))
    assert (low, high) == (10, 23)
    got = np.asarray(layers.rope_inv_freq(FULL, dim))
    extra = base ** (-np.arange(0, dim, 2) / dim)
    assert got[0] == pytest.approx(1.0)
    np.testing.assert_allclose(got[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], extra[23:] / 40, rtol=1e-6)
    r = (16 - 10) / 13
    assert got[16] == pytest.approx(extra[16] / 40 * r + extra[16] * (1 - r),
                                    rel=1e-6)
    assert layers.rope_mscale(FULL) == 1.0
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert mscale ** 2 == pytest.approx(1.58963, abs=1e-5)
    assert attention.mla_softmax_scale(FULL) == pytest.approx(
        192 ** -0.5 * mscale ** 2, rel=1e-12)


def test_config_reads_the_published_numbers():
    assert (FULL.n_layers, FULL.d_model, FULL.n_heads, FULL.kv_lora_rank,
            FULL.n_experts, FULL.moe_top_k, FULL.n_shared_experts,
            FULL.moe_d_ff, FULL.d_ff, FULL.vocab_size,
            FULL.norm_topk_prob) == (27, 2048, 16, 512, 64, 6, 2, 1408,
                                     10944, 102400, False)
    top = {k: v for k, v in CONF.items() if not isinstance(v, dict)}
    for k, v in top.items():
        if k in CONF["model"] and k not in ("n_routed_experts",):
            assert CONF["model"][k] == v, k
    assert CONF["model"]["n_routed_experts"] == \
        CONF["published"]["n_routed_experts"] == 64
    assert CONF["n_routed_experts"] == CONF["model"]["experts_held"] == 8
    assert CONF["model"]["rope_scaling"] == CONF["rope_scaling"]


def test_fresh_adapters_leave_the_backbone_forward_unchanged():
    """B = 0 at ω₀: the LoRA forward equals the backbone's own forward
    (``transformer.forward_train``), which runs the held-share layer for
    a config that holds a share of the experts."""
    from repro.configs.deepseek_v2_lite import smoke
    cfg = smoke()
    frozen = transformer.init(jax.random.PRNGKey(13), cfg)
    adapters = transformer.lora_init(jax.random.PRNGKey(14), cfg)
    x = jax.random.randint(jax.random.PRNGKey(15), (2, 16), 0,
                           cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want, _ = transformer.forward_train(frozen, x, cfg)
        got, stats = transformer.lora_forward(adapters, x, frozen, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert 0 < int(stats["moe_max_expert_rows"]) <= int(stats["moe_rows"])


# ------------------------------------------------------- the expert share
def _moe_cfg(held):
    """The CPU-size config with 16 router outputs, the first ``held`` of
    them here."""
    return REF.program_config(MODEL).with_(experts_held=held)


def _naive_layer(params, x, cfg):
    """Every expert of the router, gate-weighted where among the top k."""
    xt = x.reshape(-1, x.shape[-1])
    w, idx = moe.route(params["router"]["w"], xt, cfg)
    out = layers.swiglu(params["shared"], xt)
    for e in range(cfg.n_experts):
        g = jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None]
        ex = jax.tree.map(lambda a: a[e], params["experts"])
        out = out + g * layers.swiglu(ex, xt)
    return out.reshape(x.shape)


def test_expert_shares_add_up_to_the_uncut_layer():
    full = _moe_cfg(16)
    params = moe.moe_init(jax.random.PRNGKey(3), full)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 16, full.d_model))
    shared = layers.swiglu(params["shared"], x)
    total = jnp.zeros_like(x)
    for off in range(0, 16, 4):
        # each chip's router lists its own experts first
        perm = jnp.asarray(list(range(off, off + 4)) +
                           [e for e in range(16) if not off <= e < off + 4])
        part = dict(params,
                    router={"w": params["router"]["w"][:, perm]},
                    experts=jax.tree.map(lambda a: a[perm[:4]],
                                         params["experts"]))
        out, stats = moe.held_moe_ffn(part, x, _moe_cfg(4))
        total = total + out - shared
    with jax.default_matmul_precision("highest"):
        want = _naive_layer(params, x, full)
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_router_sending_every_token_to_one_expert_drops_none():
    """Every token picks experts 0-5; experts 0-3 are held, so expert 0
    takes all n tokens and the layer computes 4 n rows. The capacity
    layer, at its factor 1.25 of the mean load, would drop most."""
    cfg = _moe_cfg(4)
    params = moe.moe_init(jax.random.PRNGKey(5), cfg)
    d, n_exp = cfg.d_model, cfg.n_experts
    w = jnp.zeros((d, n_exp)).at[:, :6].set(
        jnp.linspace(2.0, 1.0, 6)[None, :])
    params["router"]["w"] = w
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (2, 16, d))) + 0.1
    out, stats = moe.held_moe_ffn(params, x, cfg)
    n = 2 * 16
    assert int(stats["moe_max_expert_rows"]) == n
    assert int(stats["moe_rows"]) == 4 * n
    full = _moe_cfg(16)
    padded = dict(params, experts=jax.tree.map(
        lambda a: jnp.concatenate([a, jnp.zeros((12,) + a.shape[1:])]),
        params["experts"]))
    with jax.default_matmul_precision("highest"):
        want = _naive_layer(padded, x, full)
        cap, _ = moe.moe_ffn(padded, x, full.with_(norm_topk_prob=False,
                                                    capacity_factor=1.25))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(cap - want))) > 1e-2


# ---------------------------------------------------- the grouped matmul
@pytest.mark.parametrize("transpose", [False, True])
def test_grouped_matmul_kernel_matches_reference(transpose):
    k, n = 128, 256
    lhs = jax.random.normal(jax.random.PRNGKey(7), (600, k))
    rhs = jax.random.normal(jax.random.PRNGKey(8),
                            (4, n, k) if transpose else (4, k, n))
    sizes = jnp.array([100, 0, 300, 57], jnp.int32)
    got = grouped_matmul(lhs, rhs, sizes, transpose, interpret=True)
    want = ref.grouped_matmul_ref(lhs, rhs, sizes, transpose)
    live = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got[:live]),
                               np.asarray(want[:live]), rtol=1e-4, atol=1e-3)
    assert not np.any(np.asarray(want[live:]))


def test_frozen_expert_matmul_gradient_is_the_rows_alone():
    lhs = jax.random.normal(jax.random.PRNGKey(9), (40, 8))
    rhs = jax.random.normal(jax.random.PRNGKey(10), (3, 8, 5))
    sizes = jnp.array([10, 20, 5], jnp.int32)
    f = lambda a: jnp.sum(jnp.sin(ops.frozen_expert_matmul(a, rhs, sizes)))  # noqa
    g = lambda a: jnp.sum(jnp.sin(ref.grouped_matmul_ref(a, rhs, sizes)))  # noqa
    np.testing.assert_allclose(np.asarray(jax.grad(f)(lhs)),
                               np.asarray(jax.grad(g)(lhs)), rtol=1e-5,
                               atol=1e-6)
    # no weight gradient: the frozen weights' cotangent is zero
    gw = jax.grad(lambda w: jnp.sum(ops.frozen_expert_matmul(lhs, w, sizes)))
    assert not np.any(np.asarray(gw(rhs)))


# ------------------------------------------------- the engine, frozen tree
def _lm_state(frozen_model=MODEL, n_clients=8, rounds_seed=0):
    model = frozen_model
    cfg = REF.program_config(model)
    fz = REF.frozen_params(jax.random.PRNGKey(11), model)
    p0 = transformer.lora_init(jax.random.PRNGKey(12), cfg)
    # the program's adapters have the reference's layout, B zero
    assert jax.tree.map(jnp.shape, p0) == jax.tree.map(
        jnp.shape, REF.init_params(jax.random.PRNGKey(12), model))
    assert not any(np.any(np.asarray(ad["b"]))
                   for stack in p0.values() for ad in stack.values())
    rng = np.random.default_rng(rounds_seed)
    t = model["input_shape"][0]
    clients = []
    for c in range(n_clients):
        lo = (c % 2) * model["n_classes"] // 2
        x = rng.integers(lo, lo + model["n_classes"] // 2, (2, t + 1))
        clients.append({"x": x[:, :-1].astype(np.int32),
                        "y": x[:, 1:].astype(np.int32)})
    ecfg = engine.EngineConfig(tau=0.5, lam=0.05, lr=0.1, local_steps=2,
                               sample_rate=0.25, seed=3,
                               rng_backend="device", cluster_backend="device")
    loss = lambda p, b, f: transformer.lora_loss(p, b, f, cfg)  # noqa
    return engine.init("stocfl", loss, p0, clients, ecfg, arena=True,
                       frozen=fz), fz


def test_run_rounds_with_frozen_tree_equals_eager_rounds():
    scanned, _ = _lm_state()
    eager, _ = _lm_state()
    scanned = engine.run_rounds(scanned, 3)
    for _ in range(3):
        eager, rec = engine.run_round(eager)
    for a, b in zip(jax.tree.leaves(scanned.omega),
                    jax.tree.leaves(eager.omega)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)
    assert scanned.clusters.assignment() == eager.clusters.assignment()
    for r in eager.models.roots:
        for a, b in zip(jax.tree.leaves(scanned.cluster_model(r)),
                        jax.tree.leaves(eager.cluster_model(r))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
    for s, e in zip(scanned.history, eager.history):
        assert s["moe_rows"] == e["moe_rows"] > 0
        assert s["moe_max_expert_rows"] == e["moe_max_expert_rows"] > 0


@pytest.mark.parametrize("strategy", ["fedavg", "ditto"])
def test_strategies_without_a_frozen_argument_refuse_one(strategy):
    state, fz = _lm_state()
    ctx = state.ctx
    with pytest.raises(ValueError, match="frozen"):
        engine.init(strategy, ctx.loss_fn, ctx.init_params, ctx.clients,
                    ctx.cfg, frozen=fz)


def test_scan_holds_no_constant_the_size_of_the_frozen_tree():
    """A 4096-row vocabulary makes the embedding and the head 1 MB each;
    the scan's largest constant (the Ψ anchor's adapters) stays far
    below, so the frozen tree is an argument and not baked in."""
    model = dict(MODEL, n_classes=4096)
    state, fz = _lm_state(model)
    fn, carry0, consts, _ = engine.scan_program(state, 2)
    text = fn.lower(carry0, consts).as_text()
    hexes = re.findall(r'dense<"0x([0-9A-Fa-f]+)">', text)
    largest = max((len(h) // 2 for h in hexes), default=0)
    smallest_big = min(l.nbytes for l in jax.tree.leaves(fz)
                       if l.nbytes >= 1 << 20)
    assert largest < smallest_big // 4
    assert any(l.shape == fz["embed"].shape
               for l in jax.tree.leaves(consts["frozen"]))


MODEL_SCOPES = ("mla_attention", "moe_router", "moe_experts",
                "shared_experts", "dense_ffn", "lm_head")


@pytest.fixture(scope="module")
def lm_op_names():
    """The ``op_name`` metadata of the compiled LM scan's ops."""
    state, _ = _lm_state()
    fn, carry0, consts, _ = engine.scan_program(state, 2)
    text = fn.lower(carry0, consts).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("scope", MODEL_SCOPES)
def test_model_scopes_nest_under_the_round_scopes(lm_op_names, scope):
    """Each layer of the block carries its scope inside the round's
    ``local_update`` and ``psi_extraction`` scopes."""
    for outer in ("local_update", "psi_extraction"):
        assert any(re.search(r"\b" + outer + r"/.*\b" + scope + r"\b", n)
                   for n in lm_op_names), (outer, scope)


# ----------------------------------------- the MLP's programs, unchanged
# sha256 of the StoCFL scan's lowered text for SYNTH_MLP, 12 rotated
# clients x 16, 3 rounds, as lowered before the engine took a frozen tree
# (cohort_chunk 0 and 2)
MLP_SCAN_SHA256 = {
    0: "ddad10519ed51da49324c34e4deb34939710242c7a2e7309735ad7150674a43d",
    2: "87c5dabebf6ab5cec49236bbe83763e2fd85f3fa48ff0b6361a479154dbbe84d"}


@pytest.mark.parametrize("chunk", sorted(MLP_SCAN_SHA256))
def test_mlp_scan_lowers_to_the_text_it_had(chunk):
    task = simple.SYNTH_MLP
    clients, _, _ = rotated(n_clusters=2, n_clients=12, n_per=16, seed=3)
    clients = [jax.tree.map(jnp.asarray, c) for c in clients]
    cfg = engine.EngineConfig(local_steps=2, sample_rate=0.5, seed=0,
                              rng_backend="device", cluster_backend="device",
                              cohort_chunk=chunk)
    state = engine.init("stocfl", lambda p, b: simple.loss_fn(p, b, task),
                        simple.init(jax.random.PRNGKey(0), task), clients,
                        cfg, arena=True)
    fn, carry0, consts, _ = engine.scan_program(state, 3)
    text = fn.lower(carry0, consts).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == MLP_SCAN_SHA256[chunk]


# -------------------------------------------------- the roofline reader
def test_expert_gmm_roofline_counts_by_hand():
    mod = harness.load_module(os.path.join(
        ROOT, "bench", "metrics", "expert_gmm_roofline.py"), "t_gmm_roof")
    s = mod.sizes()
    # 16 x 512 tokens x 6 of 64 experts x 8 held
    assert s == {"rows": 6144.0, "d": 2048, "f": 1408, "held": 8}
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flops = 2 * 6144 * 2048 * 1408
    nbytes = 4 * 8 * 2048 * 1408 + 4 * 6144 * (2048 + 1408)
    assert (flops, nbytes) == (35_433_480_192, 177_209_344)
    least = mod.min_seconds(peak=peak, **s)
    assert least == pytest.approx(nbytes / 819e9)       # bound by bytes
    from bench.lib import trace as tr
    ev = [tr.Event(0.0, 1e6, "%gmm.3 = f32[49152,1408] custom-call()", ""),
          tr.Event(2e6, 3e6, "%gmm = f32[49152,2048] custom-call()", ""),
          tr.Event(3e6, 4e6, "%fusion.2 = f32[8] fusion(f32[9] %gmm.3)", ""),
          tr.Event(4e6, 5e6, "%gmmx.1 = f32[8] fusion()", "")]
    run = {"trace": tr.Trace({"/device:TPU:0": ev}, []), "chips": 1,
           "window_ns": (0.0, 1e7), "peak": peak}
    assert mod.read(run) == pytest.approx(100.0 * 2 * least / 2e-3)
    run["trace"] = tr.Trace({"/device:TPU:0": ev[2:]}, [])
    assert mod.read(run) is None


def test_expert_gmm_roofline_refuses_cells_of_other_sizes(tmp_path):
    """Listed cells whose configurations give one call other sizes leave
    the reader no size to use: it reads None, not the first cell's."""
    mod = harness.load_module(os.path.join(
        ROOT, "bench", "metrics", "expert_gmm_roofline.py"), "t_gmm_roof2")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    (conf,) = [c for c in bench["configs"]
               if c["name"] == "stocfl-deepseek-v2-lite-lora"]
    cfg = json.load(open(os.path.join(ROOT, conf["file"])))
    os.makedirs(tmp_path / "bench" / "configs")
    for name, n_per in (("a", 16), ("b", 8)):
        c = dict(cfg, federation=dict(cfg["federation"], n_per=n_per))
        json.dump(c, open(tmp_path / "bench" / "configs" / f"{name}.json",
                          "w"))
    metric = {"name": "expert_gmm_roofline", "workloads": ["a.x"]}
    fake = {"configs": [{"name": n, "file": f"bench/configs/{n}.json"}
                        for n in "ab"],
            "workloads": [{"name": f"{n}.x", "config": n} for n in "ab"],
            "per_layer": [metric]}
    json.dump(fake, open(tmp_path / "BENCHMARK.json", "w"))
    assert mod.sizes(str(tmp_path))["rows"] == 6144.0
    metric["workloads"] = ["a.x", "b.x"]
    json.dump(fake, open(tmp_path / "BENCHMARK.json", "w"))
    assert mod.sizes(str(tmp_path)) is None
