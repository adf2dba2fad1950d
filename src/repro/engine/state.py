"""Engine state: an explicit, pytree-serializable federated server.

``ServerState`` is the ONLY thing a strategy transition may read and the
only thing it may produce — transitions are pure: they never mutate their
input, they return a new state (``dataclasses.replace`` + copied
containers). The model-bearing fields (``omega``, ``models``,
``personal``) are the pytree leaves, so the whole server checkpoint is
``jax.device_get(state)`` away and the cohort step can be placed on a
client-axis mesh; host-side bookkeeping (partition, rng, round counter)
rides along as aux data.

``EngineContext`` is the static world the state refers to: loss/eval
functions, the client datasets, compiled cohort updates, the Ψ extractor,
the optional frozen tree the loss reads and the optional mesh. It is
built once by ``engine.init`` and is never checkpointed — restoring a
checkpoint reattaches the arrays to a freshly built context.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.clustering import ClusterState


@dataclasses.dataclass
class EngineConfig:
    """Union of the knobs of every registered strategy.

    StoCFL uses (tau, lam, lr, local_steps, sample_rate, aggregator,
    project_dim); FedProx/Ditto read ``mu``; IFCA reads ``n_models`` and
    ``init_key``; CFL reads (eps_rel, eps2) and always runs full
    participation. ``cohort_chunk`` bounds how many clients execute in
    one vmapped step — larger cohorts run in lax.map chunks with flat
    memory (see ``bilevel.chunk_map``); 0 = unchunked.
    ``cluster_backend`` picks where StoCFL's partition lives: ``"device"``
    runs the jitted union-find + fused merge kernels of
    ``core.device_clustering`` (no per-round Ψ host sync, no Python pair
    scan); ``"numpy"`` is the host ``ClusterState`` fallback the parity
    battery checks the device path against.
    ``rng_backend`` picks where cohort sampling lives: ``"device"`` draws
    from a threefry key carried in ``ServerState.rng_key``
    (``engine.sampler`` — required by the fully-jitted ``run_rounds``
    scan, identical draws eager or scanned); ``"numpy"`` is the host
    bit-generator compatibility mode (bit-exact with pre-scan
    checkpoints and the legacy-trainer parity tests).
    ``fused_step`` routes every strategy's local update through the
    flatten-once ``kernels.prox_update_flat`` path (one fused elementwise
    pass on TPU; jnp oracle off-TPU — fp32 results stay bitwise).
    ``dtype`` is the compute precision of params/grads/batches
    ("float32" | "bfloat16"); Ψ-embeddings, cluster means, and the Eq. 2
    objective always stay fp32 (see ``engine.init``).
    ``async_cfg`` opts into async buffered aggregation: an
    ``engine.AsyncConfig`` consumed by ``run_round_async`` (staleness
    decay γ, staleness cap, buffer capacity, flush cadence); ``None``
    keeps the engine purely synchronous.
    """
    tau: float = 0.5
    lam: float = 0.05
    lr: float = 0.1
    local_steps: int = 5
    sample_rate: float = 0.1
    seed: int = 0
    aggregator: str = "mean"          # G(·): mean | median | trimmed_mean | krum
    project_dim: Optional[int] = None
    mu: float = 0.05                  # FedProx / Ditto prox weight
    n_models: int = 4                 # IFCA hypothesis count
    init_key: int = 0                 # IFCA perturbation key
    eps_rel: float = 0.35             # CFL split thresholds
    eps2: float = 0.01
    cohort_chunk: int = 0             # max clients per vmapped step (0=off)
    cluster_backend: str = "numpy"    # StoCFL partition: numpy | device
    rng_backend: str = "numpy"        # cohort sampling: numpy | device
    fused_step: bool = False          # flat fused bilevel/SGD local update
    dtype: str = "float32"            # param/grad compute precision
    async_cfg: Optional[Any] = None   # AsyncConfig: async buffered aggregation


@dataclasses.dataclass
class EngineContext:
    """Static (non-checkpointed) world: functions, data, compiled updates."""
    loss_fn: Callable
    init_params: Any
    clients: List[dict]
    cfg: EngineConfig
    eval_fn: Optional[Callable] = None
    leaf_filter: Optional[Callable] = None
    mesh: Optional[Any] = None        # jax Mesh: place cohort on client axis
    arena: Optional[Any] = None       # ClientArena: device-resident shards
    frozen: Optional[Any] = None      # untrained tree the loss reads
    extractor: Optional[Callable] = None
    cache: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def jit(self, key: str, builder: Callable) -> Callable:
        """Memoize a compiled update under ``key`` (per-context cache)."""
        if key not in self.cache:
            self.cache[key] = builder()
        return self.cache[key]

    def mesh_devices(self) -> int:
        """Devices along the mesh's client axes (1 when no mesh is
        attached) — the shard count of every cohort-sharded leading
        axis; see ``sharding.mesh_client_count``."""
        if self.mesh is None:
            return 1
        from repro.sharding import specs
        return max(specs.mesh_client_count(self.mesh), 1)


@dataclasses.dataclass
class ServerState:
    """The federated server as a value.

    Pytree leaves: ``omega`` (global model), ``models`` (cluster /
    hypothesis models keyed by int), ``personal`` (per-client personal
    models, Ditto). Aux data: everything the host orchestration needs —
    strategy name, round counter, numpy bit-generator state (so client
    sampling is checkpoint-exact), per-client sample counts, the departed
    set, the Ψ clustering bookkeeping, CFL membership, and the metric
    history. Under ``cfg.rng_backend="device"`` the sampling state is
    instead the ``rng_key`` leaf — a device threefry key, so the whole
    multi-round loop (sampling included) can run as one ``lax.scan``
    (``engine.run_rounds``).
    """
    ctx: EngineContext
    strategy: str
    round: int
    rng_state: dict
    sizes: Tuple[int, ...]
    left: frozenset
    omega: Any
    models: Dict[int, Any]
    personal: Dict[int, Any]
    clusters: Optional[ClusterState] = None
    members: Optional[Tuple[Tuple[int, ...], ...]] = None   # CFL partition
    history: Tuple[dict, ...] = ()
    rng_key: Optional[Any] = None     # device sampling key (rng_backend="device")
    buffer: Optional[Any] = None      # AsyncBuffer: in-flight delayed deltas

    # ------------------------------------------------------------- helpers
    @property
    def n_clients(self) -> int:
        """Registered clients, departed included (ids are stable; the
        live count is ``n_clients - len(left)``)."""
        return len(self.ctx.clients)

    def cluster_model(self, root: int):
        """θ_k for a cluster root (lazy: ω₀ until first aggregate)."""
        return self.models.get(root, self.ctx.init_params)

    def client_root(self, cid: int) -> int:
        """Union-find root (= cluster id) of an observed client."""
        assert self.clusters is not None
        return self.clusters.uf.find(int(cid))

    def rng(self) -> np.random.Generator:
        """Materialize the generator at this state's position (pure: the
        state only stores the serializable bit-generator state)."""
        g = np.random.default_rng(0)
        g.bit_generator.state = self.rng_state
        return g

    def replace(self, **kw) -> "ServerState":
        """``dataclasses.replace`` shorthand — the one way transitions
        derive a new state from an old one."""
        return dataclasses.replace(self, **kw)


def fresh_rng_state(seed: int) -> dict:
    return np.random.default_rng(seed).bit_generator.state


def fresh_rng_key(seed: int):
    """Device sampling key for ``rng_backend="device"`` (threefry; lives
    in ``ServerState.rng_key``, advanced by splitting once per draw)."""
    import jax.random
    return jax.random.PRNGKey(int(seed))


def _flatten_state(s: ServerState):
    children = (s.omega, s.models, s.personal, s.rng_key, s.buffer)
    aux = (s.ctx, s.strategy, s.round, s.rng_state, s.sizes, s.left,
           s.clusters, s.members, s.history)
    return children, aux


def _unflatten_state(aux, children):
    omega, models, personal, rng_key, buffer = children
    ctx, strategy, rnd, rng_state, sizes, left, clusters, members, history = aux
    return ServerState(ctx=ctx, strategy=strategy, round=rnd,
                       rng_state=rng_state, sizes=sizes, left=left,
                       omega=omega, models=models, personal=personal,
                       clusters=clusters, members=members, history=history,
                       rng_key=rng_key, buffer=buffer)


jax.tree_util.register_pytree_node(ServerState, _flatten_state, _unflatten_state)
