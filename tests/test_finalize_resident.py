"""StoCFL's scan hand-off keeps the Ψ bank on the device.

``finalize`` copies to the host only what the host reads (the
partition's ``parent`` and ``live``, the bank flags, the per-round
metrics) and wraps the carry's own ``DeviceClusterState`` in the
returned ``DeviceClusters``. These tests pin (a) that the returned Ψ
bank is the warm-resume stash's device buffer, (b) that the state is
bitwise the one the former host round-trip (``from_arrays`` of
``np.asarray`` copies, the bank stacked from one slice per root) gave,
through back-to-back spans, an eager round between spans and a
join/leave between spans, (c) that a checkpoint of a scanned state
round-trips Ψ bitwise, and (d) that the
``repro.finalize.fetch`` span's ``bytes`` argument is small and does not
grow with the Ψ width.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.checkpoint import load_server_state, save_server_state
from repro.core.device_clustering import DeviceClusters
from repro.data import rotated
from repro.engine.bank import ClusterBank
from repro.models import simple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.lib import trace as tr  # noqa: E402

TASK = simple.SYNTH_MLP
STASH = "stocfl_scan_resume"


def _fed(n_clients=12, n_per=32, seed=3):
    clients, _, _ = rotated(n_clusters=2, n_clients=n_clients, n_per=n_per,
                            seed=seed)
    return [jax.tree.map(jnp.asarray, c) for c in clients]


def _init(clients, **kw):
    cfg = engine.EngineConfig(local_steps=2, sample_rate=0.5, seed=0,
                              rng_backend="device",
                              cluster_backend="device", **kw)
    return engine.init("stocfl", lambda p, b: simple.loss_fn(p, b, TASK),
                       simple.init(jax.random.PRNGKey(0), TASK), clients,
                       cfg, arena=True)


def _host_round_trip(state):
    """The former hand-off: Ψ, parent and live copied to the host and
    uploaded again by ``from_arrays``, and the bank stacked from one
    slice per root; the returned objects key the warm-resume stash, as
    they did."""
    c, stash = state.clusters, state.ctx.cache[STASH]
    old = DeviceClusters.from_arrays(c.tau, np.asarray(c.state.parent),
                                     np.asarray(c.state.live),
                                     np.asarray(c.state.rep))
    roots = [int(r) for r in np.nonzero(np.asarray(stash["has"]))[0]]
    models = ClusterBank.from_dict(
        {r: jax.tree.map(lambda R, rr=r: R[rr], stash["rows"])
         for r in roots})
    stash.update(clusters=old, models=models)
    return state.replace(clusters=old, models=models)


def _assert_same(a, b, query):
    """Field by field: the partition arrays, its host reads, §4.4
    inference, ω, the bank (roots, rows and spare rows), the history and
    the key — bitwise."""
    ca, cb = a.clusters, b.clusters
    for field in ("parent", "live", "rep"):
        assert np.array_equal(np.asarray(getattr(ca.state, field)),
                              np.asarray(getattr(cb.state, field))), field
    assert ca.seen == cb.seen
    assert np.array_equal(ca._parent, cb._parent)
    assert ca.assignment() == cb.assignment()
    assert ca.n_clusters() == cb.n_clusters()
    assert ca.objective() == cb.objective()
    assert ca.nearest(query) == cb.nearest(query)
    for x, y in zip(jax.tree.leaves(a.omega), jax.tree.leaves(b.omega)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), "omega"
    assert a.models.roots == b.models.roots
    for x, y in zip(jax.tree.leaves(a.models.stacked),
                    jax.tree.leaves(b.models.stacked)):
        assert x.shape == y.shape and x.dtype == y.dtype
        assert np.array_equal(np.asarray(x), np.asarray(y)), "bank rows"
    assert a.history == b.history
    assert a.round == b.round
    assert np.array_equal(np.asarray(a.rng_key), np.asarray(b.rng_key))


# ----------------------------------------------- (a) the stash's buffer
def test_returned_psi_bank_is_the_stash_buffer():
    state = engine.run_rounds(engine.run_rounds(_init(_fed()), 2), 3)
    stash = state.ctx.cache[STASH]
    rep = state.clusters.state.rep
    assert isinstance(rep, jax.Array)
    assert stash["clusters"] is state.clusters
    assert stash["models"] is state.models
    # the same arrays, not equal copies: on the CPU a host round-trip
    # can alias the buffer too, so the objects are compared first
    assert state.clusters.state is stash["dcs"]
    for field in ("parent", "live", "rep"):
        got = getattr(state.clusters.state, field)
        want = getattr(stash["dcs"], field)
        assert (got.unsafe_buffer_pointer()
                == want.unsafe_buffer_pointer()), field


# ---------------------------------- (b) parity with the host round-trip
def _back_to_back(state, extra):
    return state


def _eager_round(state, extra):
    return engine.run_round(state)[0]


def _join_leave(state, extra):
    state, _cid = engine.join(state, extra)
    return engine.leave(state, 3)


BETWEEN = {"back_to_back": _back_to_back, "eager_round": _eager_round,
           "join_leave": _join_leave}


@pytest.mark.parametrize("between", sorted(BETWEEN))
def test_resident_hand_off_matches_host_round_trip(between):
    """Two scanned spans with ``between`` in the middle, once as the
    program runs and once with the former host round-trip after every
    span: every field agrees after each step."""
    clients, extra = _fed(), _fed(n_clients=2, seed=11)[0]
    new, old = _init(clients), _init(clients)
    query = np.asarray(new.ctx.extractor(extra))
    step = BETWEEN[between]
    for fn, scanned in ((lambda s: engine.run_rounds(s, 2), True),
                        (lambda s: step(s, extra), False),
                        (lambda s: engine.run_rounds(s, 3), True)):
        new, old = fn(new), fn(old)
        if scanned:
            old = _host_round_trip(old)
        _assert_same(new, old, query)


# ------------------------------------------------------ (c) checkpoints
def test_checkpoint_of_a_scanned_state_round_trips_psi(tmp_path):
    clients = _fed()
    state = engine.run_rounds(engine.run_rounds(_init(clients), 2), 2)
    save_server_state(str(tmp_path / "ck"), state)
    back = load_server_state(str(tmp_path / "ck"), _init(clients))
    for field in ("parent", "live", "rep"):
        assert np.array_equal(
            np.asarray(getattr(back.clusters.state, field)),
            np.asarray(getattr(state.clusters.state, field))), field
    assert back.clusters.assignment() == state.clusters.assignment()
    assert back.clusters.seen == state.clusters.seen


# ------------------------------------------- (d) the fetch span's bytes
def _fetch_bytes(project_dim, tmp_path):
    """The ``bytes`` argument of the ``repro.finalize.fetch`` span of one
    traced ``run_rounds`` call, read from the raw trace, and the size of
    the Ψ bank the state holds."""
    state = engine.run_rounds(_init(_fed(), project_dim=project_dim), 2)
    keep = str(tmp_path / f"d{project_dim}.xplane.pb")
    with tr.recording(keep):
        state = engine.run_rounds(state, 2)
    from jax.profiler import ProfileData
    found = [dict(e.stats)["bytes"]
             for plane in ProfileData.from_file(keep).planes
             for line in plane.lines for e in line.events
             if e.name == "repro.finalize.fetch"]
    assert len(found) == 1
    return int(found[0]), state.clusters.state.rep.nbytes


def test_fetch_span_bytes_do_not_follow_psi_width(tmp_path):
    full, full_rep = _fetch_bytes(None, tmp_path)
    sketch, sketch_rep = _fetch_bytes(64, tmp_path)
    assert full_rep > sketch_rep and full_rep > 64 * 1024
    assert full == sketch
    assert 0 < full < 64 * 1024
