"""Parity battery for the device-resident clustering core.

``core.device_clustering`` must be indistinguishable from the numpy
``ClusterState`` everywhere the engine can observe:

  * union-find root resolution matches ``UnionFind`` under random union
    sequences (hypothesis property);
  * observe → merge_round produces the same partition, the same merge
    set, the same remaps under departures;
  * all six strategies produce bitwise-identical trajectories with
    ``cluster_backend`` flipped (clustered + unclustered, static + under
    churn), and device checkpoints round-trip bit-exactly;
  * ARI(device partition, host partition) == 1.0 on all four Non-IID
    settings;
  * the clustering step itself runs with ZERO per-round host transfers
    (enforced with ``jax.transfer_guard``) — the tentpole's reason to
    exist.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import engine
from repro.checkpoint import load_server_state, save_server_state
from repro.core.clustering import ClusterState, UnionFind, adjusted_rand_index
from repro.core import device_clustering as dc
from repro.core.device_clustering import DeviceClusters
from repro.data import make_federation
from repro.models import simple

TASK = simple.SYNTH_MLP
LOSS = lambda p, b: simple.loss_fn(p, b, TASK)
EVAL = jax.jit(lambda p, b: simple.accuracy(p, b, TASK))


def _unit_reps(labels, seed=0, d=16, noise=0.02):
    rng = np.random.default_rng(seed)
    anchors = rng.normal(size=(max(labels) + 1, d))
    anchors /= np.linalg.norm(anchors, axis=1, keepdims=True)
    out = []
    for g in labels:
        v = anchors[g] + rng.normal(size=d) * noise
        out.append((v / np.linalg.norm(v)).astype(np.float32))
    return out


def _pair(tau=0.8, n=0):
    return ClusterState(tau=tau), DeviceClusters(tau=tau, capacity=n)


def _leaves_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            return False
    return True


# ------------------------------------------------------------ union-find
def _check_union_sequence(edges, n=16):
    """Device parent array (pointer-halving resolution) must equal
    numpy ``UnionFind.find`` for every id after this union sequence."""
    uf = UnionFind()
    for i in range(n):
        uf.add(i)
    state = dc.init_state(n, 2)
    state = dc.observe(state, jnp.arange(n, dtype=jnp.int32),
                       jnp.zeros((n, 2), jnp.float32))
    for a, b in edges:
        uf.union(a, b)
        state = dc._jit_union()(state, jnp.int32(a), jnp.int32(b))
    from repro.kernels import ops
    roots = np.asarray(ops.resolve_roots(state.parent))
    for i in range(n):
        assert int(roots[i]) == uf.find(i)


def test_device_unionfind_matches_numpy_seeded_sweep():
    """Deterministic slice of the hypothesis property (see
    ``tests/test_device_properties.py``), runnable without the test
    extra: 30 seeded random union sequences."""
    for seed in range(30):
        rng = np.random.default_rng(seed)
        edges = [tuple(rng.integers(0, 16, 2)) for _ in range(rng.integers(0, 40))]
        _check_union_sequence(edges)


def test_component_labels_worst_case_path():
    """A path graph is the deepest component per node count: the
    fixed-point min-label propagation must still close it."""
    for n in (2, 3, 17, 64, 129):
        adj = np.zeros((n, n), np.float32)
        for i in range(n - 1):
            adj[i, i + 1] = adj[i + 1, i] = 1.0
        labels = np.asarray(dc.component_labels(jnp.asarray(adj)))
        assert (labels == 0).all()
    # two components + an isolated node
    adj = np.zeros((5, 5), np.float32)
    adj[0, 1] = adj[1, 0] = adj[2, 3] = adj[3, 2] = 1.0
    assert np.asarray(dc.component_labels(jnp.asarray(adj))).tolist() == \
        [0, 0, 2, 2, 4]


def test_component_labels_permuted_paths():
    """Regression: chains whose node ids are a RANDOM permutation of
    path order defeated the old fixed ⌈log2 N⌉+1 step count (the
    pointer-jumping 'radius doubles' argument fails off sorted order —
    200/200 wrong at n=64); the fixed-point loop must close them all."""
    for trial in range(25):
        rng = np.random.default_rng(trial)
        n = int(rng.integers(4, 80))
        order = rng.permutation(n)
        adj = np.zeros((n, n), np.float32)
        for x, y in zip(order[:-1], order[1:]):
            adj[x, y] = adj[y, x] = 1.0
        labels = np.asarray(dc.component_labels(jnp.asarray(adj)))
        assert (labels == 0).all(), (trial, n)


def test_arc_chain_partition_parity_permuted_ids():
    """Regression (end-to-end form of the above): 16 clusters on a 10°
    arc with τ=cos(15°) — only arc-adjacent pairs qualify, so the
    τ-graph is a chain through a random id permutation. Both backends
    must collapse it to ONE cluster."""
    tau = float(np.cos(np.deg2rad(15.0)))
    for seed in range(8):
        rng = np.random.default_rng(seed)
        perm = rng.permutation(16)
        ang = {int(cid): 10.0 * pos for pos, cid in enumerate(perm)}
        reps = np.stack(
            [[np.cos(np.deg2rad(ang[i])), np.sin(np.deg2rad(ang[i]))]
             for i in range(16)]).astype(np.float32)
        a, b = _pair(tau=tau, n=16)
        a.observe(range(16), list(reps))
        b.observe(range(16), list(reps))
        a.merge_round()
        b.merge_round()
        assert a.assignment() == b.assignment()
        assert b.n_clusters() == 1


# --------------------------------------------------------------- merging
def test_merge_round_parity_random_groups():
    """Same observations → same merge set and same partition as the
    numpy scan, over a seeded sweep of random group layouts."""
    for seed in range(12):
        rng = np.random.default_rng(seed + 100)
        labels = rng.integers(0, 4, size=int(rng.integers(2, 24))).tolist()
        reps = _unit_reps(labels, seed)
        a, b = _pair(n=len(labels))
        a.observe(range(len(labels)), reps)
        b.observe(range(len(labels)), reps)
        ma, mb = a.merge_round(), b.merge_round()
        assert sorted(ma) == mb
        assert a.assignment() == b.assignment()
        assert a.clusters() == b.clusters()


def test_streaming_and_departures_parity():
    """Clients arriving over rounds + departures (root and non-root):
    partitions, remaps, and uf.parent stay equal throughout."""
    labels = [0, 1, 2, 0, 1, 2, 0, 1, 2, 0, 1, 2]
    reps = _unit_reps(labels, seed=7)
    a, b = _pair(n=4)                        # force device grow() path
    for lo in range(0, 12, 3):
        ids = list(range(lo, lo + 3))
        a.observe(ids, reps[lo:lo + 3])
        b.observe(ids, reps[lo:lo + 3])
        assert sorted(a.merge_round()) == b.merge_round()
        assert a.assignment() == b.assignment()
    for cid in (0, 5, 1, 11):                # roots and members
        ra, rb = a.remove(cid), b.remove(cid)
        assert ra == rb
        assert a.assignment() == b.assignment()
        assert a.uf.parent == b.uf.parent
        # the host mirror must equal the device parent array EXACTLY,
        # tombstoned rows included (regression: removing a cluster's
        # root used to leave the dead row pointing at the new root)
        assert np.array_equal(b._parent,
                              np.asarray(b.state.parent).astype(np.int64))
    # rejoin after departure reuses the tombstoned row
    a.observe([0], [reps[0]])
    b.observe([0], [reps[0]])
    assert sorted(a.merge_round()) == b.merge_round()
    assert a.assignment() == b.assignment()


def test_chain_topology_same_partition_and_bank_merge():
    """Chain τ-graphs where a scan's intermediate keep is not the
    component min: the two backends emit DIFFERENT merge lists (the
    device normalizes to (component_min, member)), but the partition is
    identical and — because ``ClusterBank.merge`` reconstructs groups
    from the list's transitive closure — the merged bank is bitwise
    identical either way."""
    from repro.engine.bank import ClusterBank

    # unit vectors on an arc; τ = cos(45°) admits exactly the 40°-apart
    # pairs: edges {(0,3), (2,3), (1,2)} — a chain 0-3-2-1
    angles = np.deg2rad([0.0, 120.0, 80.0, 40.0])
    reps = np.stack([np.cos(angles), np.sin(angles)], 1).astype(np.float32)
    tau = float(np.cos(np.deg2rad(45.0)))
    a, b = _pair(tau=tau)
    a.observe(range(4), list(reps))
    b.observe(range(4), list(reps))
    counts = {r: len(m) for r, m in a.clusters().items()}
    ma, mb = a.merge_round(), b.merge_round()
    assert sorted(ma) != mb          # the lists DO diverge on a chain...
    assert a.assignment() == b.assignment() == {i: 0 for i in range(4)}
    models = ClusterBank.from_dict(
        {i: {"w": jnp.full((3,), float(i + 1))} for i in range(4)})
    init = {"w": jnp.zeros(3)}
    bank_a = models.merge(ma, counts, init)
    bank_b = models.merge(mb, counts, init)
    assert set(bank_a.keys()) == set(bank_b.keys())   # ...and the banks
    for k in bank_a:                                  # stay bitwise equal
        assert _leaves_equal(bank_a[k], bank_b[k])


def test_pallas_kernels_match_oracles_interpret_mode():
    """Smoke for the device-clustering kernels (the hypothesis sweeps in
    test_kernels.py need the test extra; this always runs): the fused
    masked-cosine+τ candidate kernel in interpret mode against its jnp
    oracle, and pointer-halving root resolution against a plain
    union-find ``find``."""
    from repro.kernels import ops, ref
    from repro.kernels.cosine_sim import merge_candidates

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(13, 24)).astype(np.float32))
    live = jnp.asarray(rng.random(13) > 0.3)
    for tau in (-1.0, 0.2, 0.95):
        got = merge_candidates(x, live, tau=tau, bn=8, bk=16,
                               interpret=True)
        want = ref.merge_candidates_ref(x, live, tau)
        assert np.array_equal(np.asarray(got), np.asarray(want))
    parent = np.arange(37, dtype=np.int32)
    for i in rng.permutation(37)[:20]:
        parent[i] = rng.integers(0, i + 1)
    got = ops.resolve_roots(jnp.asarray(parent))
    uf = UnionFind()
    uf.parent = {i: int(p) for i, p in enumerate(parent)}
    want = [uf.find(i) for i in range(len(parent))]
    assert np.array_equal(np.asarray(got), want)


def test_nearest_and_infer_parity():
    labels = [0, 0, 1, 1, 2, 2]
    reps = _unit_reps(labels, seed=5)
    a, b = _pair()
    a.observe(range(6), reps)
    b.observe(range(6), reps)
    a.merge_round(), b.merge_round()
    for q in _unit_reps([0, 1, 2], seed=11) + [np.ones(16, np.float32) / 4]:
        root_a, near_a, sim_a = a.nearest(q)
        root_b, near_b, sim_b = b.nearest(q)
        assert (root_a, near_a) == (root_b, near_b)
        assert sim_a == pytest.approx(sim_b, abs=1e-6)
        assert a.infer(q)[0] == b.infer(q)[0]
    assert a.objective() == pytest.approx(b.objective(), abs=1e-5)


def test_empty_and_singleton_edge_cases():
    a, b = _pair()
    assert b.merge_round() == [] == a.merge_round()
    assert a.nearest(np.ones(4)) == b.nearest(np.ones(4)) == (None, None, 0.0)
    assert a.remove(3) == b.remove(3) == {}
    assert a.objective() == b.objective() == 0.0
    a.observe([0], _unit_reps([0]))
    b.observe([0], _unit_reps([0]))
    assert a.merge_round() == b.merge_round() == []
    assert a.n_clusters() == b.n_clusters() == 1


# --------------------------------------------------- pad norm-guard (fix)
def test_similarity_matrix_pad_rows_stay_zero():
    """K̃ not a multiple of the 64-row pad quantum: the padded ghost
    rows/cols (their diagonal included) must reach merge_round as exact 0 —
    a τ ≤ 0 run must merge only REAL clusters."""
    labels = [0, 1, 2]                       # K̃ = 3, far from 64
    cs = ClusterState(tau=-1.0)
    cs.observe(range(3), _unit_reps(labels, noise=0.3))
    roots, M = cs.similarity_matrix()
    assert M.shape == (3, 3)
    merges = cs.merge_round()
    touched = {r for pair in merges for r in pair}
    assert touched <= set(range(3))          # no ghost roots ever
    assert cs.n_clusters() == 1


# ------------------------------------------------------- engine trajectories
def _fed(setting="rotated", n_clients=12, seed=3):
    clients, tc, tests = make_federation(setting, n_clients=n_clients,
                                         seed=seed)
    clients = [jax.tree.map(jnp.asarray, c) for c in clients]
    tests = {k: jax.tree.map(jnp.asarray, v) for k, v in tests.items()}
    return clients, tc, tests


def _cfg(**kw):
    kw.setdefault("local_steps", 2)
    kw.setdefault("sample_rate", 0.5)
    kw.setdefault("seed", 0)
    return engine.EngineConfig(**kw)


def _run(backend, name="stocfl", rounds=4, arena=False, setting="rotated"):
    clients, tc, tests = _fed(setting=setting)
    stt = engine.init(name, LOSS, _params(), clients,
                      _cfg(cluster_backend=backend), eval_fn=EVAL,
                      arena=arena)
    for _ in range(rounds):
        stt, _ = engine.run_round(stt)
    return stt, tc, tests


def _params(seed=0):
    return simple.init(jax.random.PRNGKey(seed), TASK)


@pytest.mark.parametrize("name", ["stocfl", "fedavg", "fedprox", "ditto",
                                  "ifca", "cfl"])
def test_backend_parity_all_strategies(name):
    """Bitwise parity with ``cluster_backend`` flipped, for every
    registered strategy (clustered ones exercise the device path; the
    rest prove the flag is inert for them)."""
    a, tc, tests = _run("numpy", name)
    b, _, _ = _run("device", name)
    assert _leaves_equal(a.omega, b.omega)
    assert set(a.models.keys()) == set(b.models.keys())
    for k in a.models:
        assert _leaves_equal(a.models[k], b.models[k])
    if a.clusters is not None:
        assert a.clusters.assignment() == b.clusters.assignment()
        ids = sorted(a.clusters.assignment())
        assert adjusted_rand_index(
            [a.clusters.assignment()[i] for i in ids],
            [b.clusters.assignment()[i] for i in ids]) == 1.0
    assert engine.evaluate(a, tests, tc) == engine.evaluate(b, tests, tc)


def test_backend_parity_with_arena():
    """Arena + device clustering vs arena + numpy: still bitwise."""
    a, _, _ = _run("numpy", arena=True)
    b, _, _ = _run("device", arena=True)
    assert _leaves_equal(a.omega, b.omega)
    assert a.clusters.assignment() == b.clusters.assignment()


@pytest.mark.parametrize("setting", ["pathological", "rotated", "shifted",
                                     "hybrid"])
def test_partition_ari_across_noniid_settings(setting):
    """ARI(device partition, host partition) == 1.0 on every Non-IID
    data skew the paper evaluates (§4.1)."""
    a, _, _ = _run("numpy", rounds=5, setting=setting)
    b, _, _ = _run("device", rounds=5, setting=setting)
    ids = sorted(a.clusters.assignment())
    assert ids == sorted(b.clusters.assignment())
    ari = adjusted_rand_index([a.clusters.assignment()[i] for i in ids],
                              [b.clusters.assignment()[i] for i in ids])
    assert ari == 1.0


def test_backend_parity_under_churn():
    """§5 joins/leaves through the simulator: both backends walk the
    identical trajectory (partition, ω, routed accuracy)."""
    from repro.sim import Join, Leave, Timeline
    from repro.sim.simulate import simulate

    from repro.data.synthetic import rotated_factory
    factory = rotated_factory(n_clusters=4, n_per=128, seed=0)
    events = [Join(t=2, cluster=1), Leave(t=3, cid=0),
              Join(t=4, cluster=2), Leave(t=5, cid=None)]
    outs = {}
    for backend in ("numpy", "device"):
        clients, tc, tests = _fed()
        stt = engine.init("stocfl", LOSS, _params(), clients,
                          _cfg(cluster_backend=backend), eval_fn=EVAL)
        tl = Timeline(events=list(events))
        stt, log = simulate(stt, tl, rounds=7, client_factory=factory,
                            seed=0, eval_every=3, test_sets=tests,
                            true_cluster=tc)
        outs[backend] = (stt, log)
    a, la = outs["numpy"]
    b, lb = outs["device"]
    assert _leaves_equal(a.omega, b.omega)
    assert a.clusters.assignment() == b.clusters.assignment()
    assert a.left == b.left
    assert la.records == lb.records or all(
        {k: v for k, v in ra.items() if not k.startswith("sec")} ==
        {k: v for k, v in rb.items() if not k.startswith("sec")}
        for ra, rb in zip(la.records, lb.records))


def test_checkpoint_roundtrip_device(tmp_path):
    """Device-backend checkpoint: save mid-run, restore into a fresh
    context, continue — bitwise identical to the uninterrupted run
    (partition arrays included)."""
    clients, tc, tests = _fed()
    cfg = _cfg(cluster_backend="device")
    stt = engine.init("stocfl", LOSS, _params(), clients, cfg, eval_fn=EVAL)
    for _ in range(2):
        stt, _ = engine.run_round(stt)
    save_server_state(str(tmp_path / "dev"), stt)

    a = stt
    for _ in range(3):
        a, _ = engine.run_round(a)

    b = engine.init("stocfl", LOSS, _params(), clients, cfg, eval_fn=EVAL)
    b = load_server_state(str(tmp_path / "dev"), b)
    assert isinstance(b.clusters, DeviceClusters)
    assert b.clusters.assignment() == stt.clusters.assignment()
    assert np.array_equal(np.asarray(b.clusters.state.parent),
                          np.asarray(stt.clusters.state.parent))
    assert np.array_equal(np.asarray(b.clusters.state.rep),
                          np.asarray(stt.clusters.state.rep))
    for _ in range(3):
        b, _ = engine.run_round(b)
    assert _leaves_equal(a.omega, b.omega)
    assert a.clusters.assignment() == b.clusters.assignment()
    assert engine.evaluate(a, tests, tc) == engine.evaluate(b, tests, tc)


# --------------------------------------------------------- transfer guard
def test_clustering_step_zero_host_transfers():
    """The acceptance bar: once warm, the jitted clustering transitions
    (observe + merge_round) execute with NO device↔host transfer —
    ``jax.transfer_guard("disallow")`` would raise on any."""
    labels = [0, 1, 2, 0, 1, 2, 0, 1]
    reps = jnp.asarray(np.stack(_unit_reps(labels, seed=1)))
    state = dc.init_state(len(labels), reps.shape[1])
    idx = jnp.arange(len(labels), dtype=jnp.int32)
    # warm-up: compile every shape
    state_w = dc.observe(state, idx, reps)
    dc.merge_round(state_w, 0.8)
    jax.block_until_ready(state_w.parent)

    with jax.transfer_guard("disallow"):
        s2 = dc.observe(state, idx, reps)
        s3, rows, new_roots, counts = dc.merge_round(s2, 0.8)
        jax.block_until_ready((s3.parent, rows, new_roots, counts))
    # sanity: the guarded computation produced the real partition
    assert np.unique(np.asarray(s3.parent)[:len(labels)]).size == 3


def test_observe_shapes_are_quantized():
    """Different per-round new-client counts reuse pow2-padded scatter
    shapes (the compile-set bound under churn)."""
    b = DeviceClusters(tau=0.8, capacity=16)
    reps = _unit_reps([0] * 9, seed=2)
    b.observe([0], reps[:1])
    b.observe([1, 2, 3], reps[1:4])          # pads 3 -> 4
    b.observe([4, 5, 6, 7, 8], reps[4:9])    # pads 5 -> 8
    assert sorted(b.seen) == list(range(9))
    assert b.capacity == 16
    b.observe([16], _unit_reps([0], seed=3))  # beyond capacity: grow
    assert b.capacity == 32
    assert 16 in b.seen and b.uf.find(16) == 16
