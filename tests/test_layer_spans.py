"""The layer names the program writes into a profiler trace, and the
benchmark's readers of them.

(a) The scanned StoCFL round step puts each layer under one
``jax.named_scope``, and every Pallas kernel is a named op. (b) A traced
``run_rounds`` call writes the six ``repro.*`` host spans, tied together
by their ``round`` argument, and StoCFL's finalize nests its wait, fetch
and rebuild inside ``repro.scan.finalize``. (c) ``bench/lib/layers.py``
(the ops' scopes read from a raw TPU trace, the per-layer shares) and the
per-layer metric readers, on synthetic traces.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import engine
from repro.data import rotated
from repro.models import simple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import harness  # noqa: E402
from bench.lib import layers  # noqa: E402
from bench.lib import trace as tr  # noqa: E402

TASK = simple.SYNTH_MLP
SCOPES = ("cohort_gather", "psi_extraction", "merge_pass", "bank_merge",
          "local_update", "aggregation", "objective")
SPANS = ("repro.scan.prepare", "repro.scan.dispatch", "repro.scan.finalize",
         "repro.finalize.wait", "repro.finalize.fetch",
         "repro.finalize.rebuild")
ROUNDS = 3


def _state():
    clients, _, _ = rotated(n_clusters=2, n_clients=12, n_per=16, seed=3)
    clients = [jax.tree.map(jnp.asarray, c) for c in clients]
    cfg = engine.EngineConfig(local_steps=2, sample_rate=0.5, seed=0,
                              rng_backend="device",
                              cluster_backend="device")
    return engine.init("stocfl", lambda p, b: simple.loss_fn(p, b, TASK),
                       simple.init(jax.random.PRNGKey(0), TASK), clients,
                       cfg, arena=True)


# ------------------------------------------------ (a) scopes and kernels
@pytest.fixture(scope="module")
def scan_text():
    fn, carry0, consts, _ = engine.scan_program(_state(), ROUNDS)
    return fn.lower(carry0, consts).as_text(debug_info=True)


@pytest.mark.parametrize("scope", SCOPES)
def test_scan_ops_carry_layer_scope(scan_text, scope):
    """Ops of the fresh federation's scan (which observes, merges and
    recomputes the objective) carry each layer's scope in their
    location, which becomes the op's ``op_name`` metadata."""
    assert re.search(r'loc\("[^"]*\b' + scope + '/', scan_text), scope


def _pallas_names(jaxpr):
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            yield e.params["name"]
        for p in e.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    yield from _pallas_names(getattr(inner, "jaxpr", inner))


def _kernels():
    from repro.kernels.cosine_sim import cosine_sim, merge_candidates
    from repro.kernels.prox_update import prox_update_flat
    from repro.kernels.ssm_scan import ssm_scan
    x = jax.ShapeDtypeStruct((256, 1024), jnp.float32)
    live = jax.ShapeDtypeStruct((256,), jnp.bool_)
    v = jax.ShapeDtypeStruct((4, 4096), jnp.float32)
    a = jax.ShapeDtypeStruct((1, 128, 128, 16), jnp.float32)
    c = jax.ShapeDtypeStruct((1, 128, 16), jnp.float32)
    prox = jax.vmap(lambda *t: prox_update_flat(*t, 0.1, 0.05, donate=False))
    # ssm_scan's kernel does not lower for the TPU (Mosaic has no scan
    # over blocked inputs), so only its traced call is checked
    return {"merge_candidates": (lambda a, b: merge_candidates(
                a, b, tau=0.5), (x, live), True),
            "cosine_sim": (cosine_sim, (x,), True),
            "prox_update": (prox, (v, v, v, v), True),
            "ssm_scan": (ssm_scan, (a, a, c), False)}


@pytest.mark.parametrize("name", ["merge_candidates", "cosine_sim",
                                  "prox_update", "ssm_scan"])
def test_pallas_calls_carry_their_names(name):
    """Each ``pallas_call`` is named, and the TPU custom call lowered
    from it (no chip needed) carries that name as its kernel name."""
    fn, args, tpu = _kernels()[name]
    assert list(_pallas_names(jax.make_jaxpr(fn)(*args).jaxpr)) == [name]
    if tpu:
        text = jax.export.export(jax.jit(fn), platforms=["tpu"])(
            *args).mlir_module()
        calls = [line for line in text.splitlines()
                 if "tpu_custom_call" in line]
        assert calls and all(f'kernel_name = "{name}"' in line
                             for line in calls)


# -------------------------------------------------------- (b) host spans
@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two ``run_rounds`` calls under the profiler, after a warm-up call
    that compiles the scan: the ``repro.*`` spans of the raw trace as
    (thread, event) and their arguments. Read from the raw trace, since
    ``trace.load`` keeps only host events of 50 µs or more, and on the
    CPU some of these spans are shorter."""
    state = engine.run_rounds(_state(), ROUNDS)
    rounds_at = [state.round, state.round + ROUNDS]
    keep = str(tmp_path_factory.mktemp("trace") / "t.xplane.pb")
    with tr.recording(keep):
        for _ in range(2):
            state = engine.run_rounds(state, ROUNDS)
    from jax.profiler import ProfileData
    spans, args = [], {}
    for plane in ProfileData.from_file(keep).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in SPANS:
                    spans.append((line.name, tr.Event(
                        float(e.start_ns), float(e.start_ns + e.duration_ns),
                        e.name)))
                    args.setdefault(e.name, []).append(dict(e.stats))
    return spans, args, rounds_at


@pytest.mark.parametrize("span", SPANS)
def test_run_rounds_writes_host_span_once_per_call(traced, span):
    spans, args, rounds_at = traced
    assert [e.name for _, e in spans].count(span) == 2
    assert sorted(a["round"] for a in args[span]) == rounds_at
    assert all(a["rounds"] == ROUNDS for a in args[span])


@pytest.mark.parametrize("span", SPANS[3:])
def test_finalize_spans_nest_inside_scan_finalize(traced, span):
    spans, _, _ = traced
    outer = [(th, e) for th, e in spans if e.name == "repro.scan.finalize"]
    for th, e in spans:
        if e.name == span:
            assert any(th == th2 and o.start <= e.start and e.end <= o.end
                       for th2, o in outer), span


# ------------------------------------------- the op scopes of a TPU trace
def _pb(*fields) -> bytes:
    """A protobuf message of (field number, int | str | bytes) fields."""
    def varint(n):
        out = b""
        while True:
            out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def _tpu_xspace(op_stat):
    """One TPU plane with one ``XLA Ops`` event whose event metadata
    carries ``op_stat`` under the stat metadata named ``tf_op``, and a
    host plane with the window span."""
    op = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    meta = _pb((1, 7), (2, op), (5, _pb((1, 3), *op_stat)))
    stat_meta = [_pb((1, k), (2, _pb((1, k), (2, n))))
                 for k, n in ((3, "tf_op"),
                              (9, "jit(f)/while/body/local_update/mul:"))]
    tpu = _pb((1, 1), (2, "/device:TPU:0"),
              (3, _pb((1, 1), (2, tr.OP_LINE), (3, 1000),
                      (4, _pb((1, 7), (2, 0), (3, 5_000_000))))),
              (4, _pb((1, 7), (2, meta))), *[(5, m) for m in stat_meta])
    host = _pb((1, 2), (2, "/host:CPU"),
               (3, _pb((1, 1), (2, "python"), (3, 0),
                       (4, _pb((1, 1), (2, 0), (3, 10_000_000_000))))),
               (4, _pb((1, 1), (2, _pb((1, 1), (2, tr.WINDOW_SPAN))))))
    return _pb((1, tpu), (1, host)), op


@pytest.mark.parametrize("op_stat", [
    ((5, "jit(f)/while/body/local_update/mul:"),),   # a string
    ((7, 9),),                                       # a reference
], ids=["str_value", "ref_value"])
def test_op_scopes_read_from_event_metadata(tmp_path, op_stat):
    """On the TPU an op's ``tf_op`` (``op_name:op_type``) sits in its
    event metadata, which ``ProfileData`` does not expose:
    ``layers.op_scopes`` reads it from the raw trace, as a string or a
    reference, and ``bench/tools/layer_shares.py`` reads the layer's
    share of the window from the file."""
    data, op = _tpu_xspace(op_stat)
    assert layers.op_scopes(data) == {
        "/device:TPU:0": {op: "jit(f)/while/body/local_update/mul"}}
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(data)
    (ev,) = tr.load(str(path)).devices["/device:TPU:0"]
    assert (ev.name, ev.end - ev.start) == (op, 5000.0)
    tool = harness.load_module(os.path.join(
        ROOT, "bench", "tools", "layer_shares.py"), "t_layer_shares_tool")
    out = tool.shares(str(path))
    assert out["local_update"] == pytest.approx(0.05)   # 5 µs of 10 ms
    assert out["idle"] + out["local_update"] == pytest.approx(100.0)
    assert out["unattributed"] == pytest.approx(0.0, abs=1e-9)
    assert out["merge_pass"] is None and out["prepare"] is None


# ----------------------------------------------- (c) the readers, offline
BODY = "jit(scan_fn)/while/body/"
PEAK = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def _h(a, b, name):
    return tr.Event(float(a), float(b), name)


def _ev(a, b, name="fusion", scope=""):
    """A device op and the scope path the raw trace would give it."""
    return _h(a, b, name), scope


def _reader(metric):
    return harness.load_module(os.path.join(
        ROOT, "bench", "metrics", metric + ".py"), "t_layers_" + metric)


def _view(dev, host=(), window=(0.0, 100.0)):
    """A metric reader's view of a one-chip trace, and the ops' scopes
    (``op_scopes`` of the raw trace) under ``"scopes"``."""
    host = [("python", _h(*window, tr.WINDOW_SPAN))] + list(host)
    return {"trace": tr.Trace({"/device:TPU:0": [e for e, _ in dev]}, host),
            "window_ns": window, "chips": 1, "window_s": 1.0, "peak": PEAK,
            "model_flops": 1e10, "compiles": 0, "merge_k": 512,
            "psi_dim": 1000,
            "scopes": {"/device:TPU:0": {e.name: s for e, s in dev if s}}}


def _share(view, scope):
    return layers.device_share(view, scope, view["scopes"])


def case_scoped_ops_inside_while_count_by_self_time():
    view = _view([_ev(0, 100, "while", "jit(scan_fn)/while"),
                  _ev(10, 40, "fusion.1", BODY + "local_update/dot_general"),
                  _ev(50, 70, "fusion.2", BODY + "aggregation/add")])
    assert _share(view, "local_update") == pytest.approx(30.0)
    assert _share(view, "aggregation") == pytest.approx(20.0)


def case_layer_shares_and_idle_add_to_100():
    dev = [_ev(10 * i, 10 * i + 8, f"fusion.{i}", BODY + s + "/op")
           for i, s in enumerate(SCOPES)]
    view = _view(dev)
    shares = [_share(view, s) for s in SCOPES]
    assert shares == [pytest.approx(8.0)] * len(SCOPES)
    idle = _reader("device_idle_share").read(view)
    assert sum(shares) + idle == pytest.approx(100.0)


def case_finalize_host_share_leaves_out_wait():
    host = [("python", _h(0, 10, "repro.scan.prepare")),
            ("python", _h(10, 60, "repro.scan.finalize")),
            ("python", _h(10, 30, "repro.finalize.wait")),
            ("python", _h(30, 50, "repro.finalize.fetch")),
            ("python", _h(50, 60, "repro.finalize.rebuild")),
            ("other", _h(40, 45, "repro.finalize.wait"))]
    view = _view([_ev(0, 100)], host)
    assert _reader("finalize_host_share").read(view) == pytest.approx(30.0)
    assert _reader("prepare_host_share").read(view) == pytest.approx(10.0)


def case_unscoped_op_counts_for_no_layer():
    view = _view([_ev(0, 50, "copy.1", ""),
                  _ev(50, 60, "fusion.1", BODY + "objective/reduce")])
    assert _share(view, "objective") == pytest.approx(10.0)
    for s in SCOPES[:-1]:
        assert _share(view, s) is None
    # a program without spans or scopes reads nothing, and does not raise
    assert _reader("finalize_host_share").read(view) is None
    assert _share(_view([_ev(0, 50)]), "local_update") is None


def case_existing_readers_read_as_pinned():
    """The trace of ``bench/tests/test_bench.py`` with scoped ops: the
    existing readers give the values that file pins."""
    dev = [_ev(10, 30, scope=BODY + "local_update/a"),
           _ev(20, 40, scope=BODY + "local_update/b"),
           _ev(60, 70, "%merge_candidates.1 = f32[8,8] custom-call(...)",
               BODY + "merge_pass/merge_candidates/pallas_call"),
           _ev(70, 71, "%compare_reduce_fusion.1 = pred[] fusion("
                       "f32[8,8] %merge_candidates.1)", BODY + "merge_pass/x"),
           _ev(90, 120), _ev(125, 130, "%all-reduce.3 = f32[4] all-reduce()")]
    view = _view(dev, [("python", _h(0, 50, "bench.unit")),
                       ("python", _h(50, 100, "bench.unit"))])
    assert _reader("device_idle_share").read(view) == pytest.approx(49.0)
    assert _reader("round_step_mfu").read(view) == pytest.approx(1.0)
    assert _reader("window_compiles").read(view) == 0.0
    roof = _reader("merge_candidates_roofline")
    assert roof.read(view) == pytest.approx(
        100.0 * roof.min_seconds(512, 1000, PEAK) / 10e-9)


CASES = {f.__name__[len("case_"):]: f for f in (
    case_scoped_ops_inside_while_count_by_self_time,
    case_layer_shares_and_idle_add_to_100,
    case_finalize_host_share_leaves_out_wait,
    case_unscoped_op_counts_for_no_layer,
    case_existing_readers_read_as_pinned)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_layer_readers_on_a_synthetic_trace(case):
    CASES[case]()
