"""Shared simulation environment for all FL methods (paper §6.1 setup).

100 clients on synthetic non-i.i.d. data; latency profile with the paper's
five delay bands; 10 "unstable" clients that drop out permanently at a
random time; fixed seeds so every method sees identical partitions,
latencies, and dropout schedule.

The environment also owns the execution substrate: the device-resident
train stacks and (optionally, ``SimConfig.mesh``) the device mesh the
fused round step client-shards over — see :class:`SimEnv` and
DESIGN.md §Scale-mapping.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import faults as faults_mod
from repro.core import population as population_mod
from repro.core import tiering
from repro.core import topology as topology_mod
from repro.core.clients import make_client_update, make_eval_fn
from repro.runtime import sharding
from repro.data.federated import FederatedDataset, make_federated, pad_stack
from repro.models import registry as model_registry

PAPER_DELAY_BANDS = ((0.0, 0.0), (0.0, 5.0), (6.0, 10.0), (11.0, 15.0),
                     (20.0, 30.0))


@dataclasses.dataclass
class SimConfig:
    #: registered model name (models/registry.py): cnn | logreg | tiny_lm
    #: | anything registered since — the model decides the data kind
    model: str = "cnn"
    n_clients: int = 100
    n_classes: int = 10
    classes_per_client: int = 2
    samples_per_client: int = 60
    image_hw: int = 12
    n_features: int = 128
    vocab_size: int = 64           # tokens-kind models: vocabulary size
    seq_len: int = 16              # tokens-kind models: sequence length
    #: attention path for transformer-family models: "auto" | "flash" |
    #: "reference" (configs/base.py ATTENTION_BACKENDS).  "flash" routes
    #: every client step through the kernel layer; "reference" keeps the
    #: chunked-softmax parity oracle; "auto" = flash wherever available.
    attention_backend: str = "auto"
    n_tiers: int = 5
    clients_per_round: int = 10
    local_epochs: int = 3
    batch_size: int = 10
    lr: float = 1e-3
    prox_lambda: float = 0.4
    n_unstable: int = 10
    base_compute: float = 1.0      # seconds per local round before delays
    seed: int = 0
    #: "#class" (the paper's skew) or "dirichlet:<alpha>" (data/federated.py)
    partitioner: str = "#class"
    #: per-tier latency bands added on top of base_compute (paper §6.1)
    delay_bands: Tuple[Tuple[float, float], ...] = PAPER_DELAY_BANDS
    #: unstable clients drop permanently at uniform(*dropout_window)
    dropout_window: Tuple[float, float] = (50.0, 400.0)
    #: transient availability churn (core/faults.py churn_schedule): each
    #: client is a churner with probability churn_rate and gets
    #: churn_events down-windows (onsets uniform in churn_window,
    #: durations exponential with mean churn_downtime).  0.0 keeps
    #: alive() the exact permanent-dropout compare (zero-fault parity).
    churn_rate: float = 0.0
    churn_events: int = 2
    churn_downtime: float = 30.0
    churn_window: Tuple[float, float] = (50.0, 400.0)
    #: dedicated fault-plane rng stream seed (spec faults.seed) — churn
    #: draws never touch the environment rng
    fault_seed: int = 0
    #: named device mesh for the fused round step (launch/mesh.py grammar:
    #: None/"single" | "host[:n_pods]" | "production[:n_pods]").  With a
    #: data axis > 1 the per-round client fan-out is sharded over it
    #: (core/executor.py); clients_per_round must then pad to a multiple
    #: of the data-axis size.
    mesh: Optional[str] = None
    #: additionally shard the tier-model stack over the mesh's pod axis
    #: (only meaningful when the mesh has one)
    shard_tiers: bool = False
    #: population plane (core/population.py; spec section ``population``):
    #: the indexed 100k-1M-client data planes (stacked/streaming) and the
    #: FLGo-style availability/responsiveness/completion processes.  None
    #: (the spec's all-defaults section) keeps the exact legacy
    #: full-population stack — bitwise parity with the pre-population
    #: environment.
    population: Optional[population_mod.PopulationConfig] = None
    #: topology plane (core/topology.py; spec section ``topology``): the
    #: hierarchical clients -> edges -> silos -> global tree with
    #: per-link delay bands/codecs and delayed-gradient compensation.
    #: None (the spec's all-defaults section) is the exact flat FedAT
    #: engine.
    topology: Optional[topology_mod.TopologyConfig] = None


class SimEnv:
    """One materialized scenario: partitions, latencies/tiers, dropout
    schedule, model init, the device-resident data plane, and (optionally)
    the device mesh the fused round step shards over.

    ``sc.mesh`` names the mesh (launch/mesh.py grammar); with a data axis
    of size D > 1 the executor runs the per-round client stack under
    ``shard_map`` with clients split over ``data``, which requires
    ``clients_per_round % D == 0`` (checked here so misconfiguration
    fails at build time, before any compile).
    """

    def __init__(self, sc: SimConfig):
        self.sc = sc
        rng = np.random.default_rng(sc.seed)

        # device mesh for the sharded round step (None = single device);
        # resolved here (lazily per env) so importing never touches
        # jax device state.
        from repro.launch import mesh as mesh_mod
        self.mesh = mesh_mod.resolve_mesh(sc.mesh)
        # sized from this env's own mesh only — never the thread-local
        # ambient mesh (a no-mesh env built inside a use_mesh() context
        # must stay single-device)
        self.data_axis = (self.mesh.shape.get("data", 1)
                          if self.mesh is not None else 1)
        # the per-round fan-out that must pad over the data axis is the
        # per-edge sample size under the topology plane, else the flat
        # clients_per_round — the error names the spec field that failed
        k, k_field = sc.clients_per_round, "tiers.clients_per_round"
        if sc.topology is not None and sc.topology.clients_per_edge:
            k, k_field = (sc.topology.clients_per_edge,
                          "topology.clients_per_edge")
        if k % self.data_axis:
            d = self.data_axis
            raise ValueError(
                f"{k_field}={k} does not pad to a multiple of the "
                f"mesh data axis (size {d}, mesh {sc.mesh!r}); use a "
                f"multiple of {d} (e.g. {((k + d - 1) // d) * d})")
        self.rng = rng
        # the bound model (registry) decides the data kind the federated
        # partitioner synthesizes and how params/loss/eval are built
        self.model = model_registry.build_model(
            sc.model, model_registry.DataDims(
                n_classes=sc.n_classes, image_hw=sc.image_hw,
                n_features=sc.n_features, vocab_size=sc.vocab_size,
                seq_len=sc.seq_len,
                attention_backend=sc.attention_backend))
        # population plane (None = legacy full-population environment);
        # all its draws come from dedicated spec-seeded streams, so the
        # environment rng below is untouched either way
        self.population = (None if sc.population is None
                           else population_mod.Population(
                               sc.population, sc, self.model))
        #: True when per-round batches are host-materialized and streamed
        #: to the fused step instead of gathered from a resident stack
        self.streaming = (self.population is not None
                          and self.population.plane == "streaming")

        if self.population is not None and self.population.cfg.indexed:
            # indexed data plane: flat (N,) state arrays + lazy per-client
            # content streams (core/population.py); the test stack only
            # materializes the eval subset
            pop = self.population
            self.ds = None
            self.n_train_all = pop.n_train
            self.train = None if self.streaming else pop.materialize_stack()
            self.test = pop.test_stack(pop.eval_ids)
        else:
            self.ds = make_federated(
                task=self.model.data_kind, n_clients=sc.n_clients,
                n_classes=sc.n_classes,
                classes_per_client=sc.classes_per_client,
                samples_per_client=sc.samples_per_client,
                image_hw=sc.image_hw,
                n_features=sc.n_features, seed=sc.seed,
                partitioner=sc.partitioner, vocab_size=sc.vocab_size,
                seq_len=sc.seq_len)
            self.train = pad_stack(self.ds)
            self.n_train_all = self.train["n_samples"]
            self.test = self._stack_test()
            if (self.population is not None
                    and len(self.population.eval_ids) < sc.n_clients):
                ids = self.population.eval_ids
                self.test = {k: v[ids] for k, v in self.test.items()}

        # latency profile -> tiers (paper: 5 delay bands on top of compute)
        base = np.full(sc.n_clients, sc.base_compute)
        lat = tiering.profile_latencies(base, sc.delay_bands, rng)
        if (self.population is not None
                and self.population.resp_factors is not None):
            # FLGo-style responsiveness: per-client multiplicative speed
            # factors (dedicated RESP_STREAM) reshape the tier assignment
            lat = lat * self.population.resp_factors
        self.tm = tiering.assign_tiers(lat, sc.n_tiers)

        # topology plane: silo/edge membership over the same profiled
        # (responsiveness-scaled) latencies; None = flat FedAT.  Per-run
        # link-delay draw state lives on the strategy (new_link_rng), so
        # this cached env stays shareable across runs.
        self.topology = (None if sc.topology is None else
                         topology_mod.Topology(
                             sc.topology, sc.n_clients, lat,
                             sc.clients_per_round))

        # unstable clients drop permanently at a random time; the single
        # source of truth is the per-client dropout instant (+inf = stable),
        # so alive(now) is one array compare (dropout_time derives the old
        # dict view for tests that still want it)
        self.dropout_ids = rng.choice(sc.n_clients, sc.n_unstable,
                                      replace=False)
        self.dropout_at = np.full(sc.n_clients, np.inf)
        self.dropout_at[self.dropout_ids] = rng.uniform(
            *sc.dropout_window, size=sc.n_unstable)

        # transient churn windows on top of permanent dropout, drawn from
        # the dedicated fault stream (core/faults.py) so the environment
        # rng stream above is untouched; None when churn is off
        self.churn_down = faults_mod.churn_schedule(
            sc.n_clients, sc.churn_rate, sc.churn_events,
            sc.churn_downtime, sc.churn_window, sc.fault_seed)

        # model init + jitted client update / eval — all built from the
        # registry's bound FLModel over arbitrary pytree params
        key = jax.random.PRNGKey(sc.seed)
        self.params0 = self.replicate(self.model.init_params(key))
        self.apply_fn = self.model.apply
        # raw (un-jitted) update bodies compose inside the fused round
        # step (core/executor.py); jitting the same bodies gives the
        # standalone per-call entry points, so both paths share one trace
        # source and identical numerics.
        self.update_fn_raw = make_client_update(
            self.model, local_epochs=sc.local_epochs,
            batch_size=sc.batch_size, lr=sc.lr,
            prox_lambda=sc.prox_lambda, jit=False)
        self.update_fn_noprox_raw = make_client_update(
            self.model, local_epochs=sc.local_epochs,
            batch_size=sc.batch_size, lr=sc.lr, prox_lambda=0.0, jit=False)
        self.update_fn = jax.jit(self.update_fn_raw)
        self.update_fn_noprox = jax.jit(self.update_fn_noprox_raw)
        self.eval_fn = make_eval_fn(self.model)
        self.model_bytes = sum(np.asarray(l).nbytes
                               for l in jax.tree.leaves(self.params0))

        # device-resident data plane: the padded train stacks live on
        # device once; per-event selection is an in-graph gather
        # (core/executor.py), never a host->device copy.  Under a mesh the
        # stacks shard along the client axis (logical "clients" ->
        # physical "data", runtime/sharding.py); the gather runs in the
        # auto-sharded region.
        # (the streaming plane has no resident stacks: the executor
        # uploads one fixed-shape K-client batch per round instead)
        self.train_dev = (None if self.train is None else
                          {k: self._place_stack(self.train[k])
                           for k in ("x", "y", "mask")})
        self._test_dev = None
        self._executor = None

    def replicate(self, tree):
        """Server state on this env's devices: replicated over the mesh, so
        the fused steps see the same input placement on the first call as
        on every later one (a single-device input would retrace the
        sharded step once its mesh-placed outputs come back)."""
        if self.mesh is None:
            return jax.tree.map(jnp.asarray, tree)
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def _place_stack(self, arr: np.ndarray):
        """Upload one (n_clients, ...) train stack, client-sharded over the
        mesh's data axis.  The client count must divide over that axis:
        a silently replicated stack would put every client's data on
        every device."""
        if self.mesh is None:
            return jnp.asarray(arr)
        if arr.shape[0] % self.data_axis:
            raise ValueError(
                f"the resident train stack has {arr.shape[0]} clients, "
                f"which does not split over the mesh data axis (size "
                f"{self.data_axis}); use a data.n_clients that is a "
                f"multiple of {self.data_axis}, or population.plane="
                f"streaming (no resident stack)")
        place = sharding.logical_sharding(
            ("clients",) + (None,) * (arr.ndim - 1), self.mesh)
        return jax.device_put(arr, place)

    def _stack_test(self):
        cap = max(len(c.y_test) for c in self.ds.clients)
        n = self.ds.n_clients
        xs = np.zeros((n, cap) + self.ds.input_shape, self.ds.input_dtype)
        ys = np.zeros((n, cap), np.int32)
        mask = np.zeros((n, cap), bool)
        for i, c in enumerate(self.ds.clients):
            k = len(c.y_test)
            xs[i, :k] = c.x_test
            ys[i, :k] = c.y_test
            mask[i, :k] = True
        return {"x": xs, "y": ys, "mask": mask}

    # ------------------------------------------------------------------
    def executor(self):
        """The cached fused-round executor for this environment (the jit
        cache lives on the executor, so repeated engine runs over one env
        never recompile)."""
        if self._executor is None:
            from repro.core.executor import RoundExecutor
            self._executor = RoundExecutor(self)
        return self._executor

    @property
    def dropout_time(self) -> Dict[int, float]:
        """Dict view of the dropout schedule (derived from ``dropout_at``)."""
        return {int(c): float(self.dropout_at[c]) for c in self.dropout_ids}

    def alive(self, now: float) -> np.ndarray:
        """Per-client availability at ``now``: not permanently dropped and
        not inside a transient churn down-window.  A client sampled while
        up can be down by the time its round completes — the strategies
        re-filter on completion, which is how mid-round failures shrink
        the participant set (Eq. 4 renormalizes over survivors).  With a
        population availability process the slotted Bernoulli mask is
        folded in too (core/population.py)."""
        with jax.profiler.TraceAnnotation("repro.alive"):
            up = self.dropout_at > now
            if self.churn_down is not None:
                starts, ends = self.churn_down
                down = ((starts <= now) & (now < ends)).any(axis=1)
                up = up & ~down
            if self.population is not None:
                avail = self.population.availability_mask(now)
                if avail is not None:
                    up = up & avail
            return up

    def completion(self, now: float) -> Optional[np.ndarray]:
        """Per-client round-completion mask at ``now`` under the
        population plane's completion process, or None when no process is
        spec'd — the strategies then keep the exact legacy
        completion-time paths (bitwise zero-population parity)."""
        if self.population is None:
            return None
        return self.population.completion_mask(now)

    def retier(self, rng: np.random.Generator, drift: float = 0.2) -> bool:
        """Re-profile client latencies (multiplicative drift) and rebuild the
        tier map (tiering.retier); returns True when any tier membership
        changed.  The engine drives this via ``EngineConfig.retier_every``
        and restores the original map at the end of the run so shared/cached
        environments stay reproducible."""
        new_lat = tiering.drift_latencies(self.tm.latencies, rng, drift)
        old = self.tm
        self.tm = tiering.retier(self.tm, new_lat)
        return any(not np.array_equal(a, b)
                   for a, b in zip(old.members, self.tm.members))

    def sample_clients(self, pool: np.ndarray, k: int,
                       rng: np.random.Generator) -> np.ndarray:
        if len(pool) == 0:
            return pool
        k = min(k, len(pool))
        return rng.choice(pool, k, replace=False)

    def client_batch(self, ids: np.ndarray) -> Dict[str, jnp.ndarray]:
        if self.train is None:  # streaming plane: materialize on demand
            return {k: jnp.asarray(v)
                    for k, v in self.population.materialize(ids).items()}
        return {k: jnp.asarray(self.train[k][ids])
                for k in ("x", "y", "mask")}

    def n_samples(self, ids: np.ndarray) -> jnp.ndarray:
        return jnp.asarray(self.n_train_all[ids])

    def data_plane_bytes(self) -> int:
        """Peak device-resident data-plane footprint in bytes: the train
        stacks (resident planes) or the streamed per-round batch buffer
        (streaming plane — the executor's high-water mark, or the static
        bound before any round ran), plus the eval test stack.  The
        streaming plane's flat-memory invariant (the bench's ``within 10%
        of the 1k-client run``) is asserted over this number."""
        test = sum(np.asarray(v).nbytes for v in self.test.values())
        if self.train_dev is not None:
            return test + sum(int(v.nbytes)
                              for v in self.train_dev.values())
        peak = (self._executor.stream_bytes
                if self._executor is not None
                and self._executor.stream_bytes else
                self.population.batch_nbytes(self.sc.clients_per_round))
        return test + peak

    def evaluate(self, params) -> Tuple[float, float]:
        """(weighted global accuracy, per-client accuracy variance).

        The eval program queues behind every round step still running on
        the device; ``repro.eval.wait`` makes that wait explicit, so a
        profiler trace shows it apart from the eval itself."""
        with jax.profiler.TraceAnnotation("repro.eval"):
            if self._test_dev is None:  # upload the test stack once
                self._test_dev = tuple(jnp.asarray(self.test[k])
                                       for k in ("x", "y", "mask"))
            with jax.profiler.TraceAnnotation("repro.eval.wait"):
                jax.block_until_ready(params)
            accs = np.asarray(self.eval_fn(params, *self._test_dev))
            weights = self.test["mask"].sum(1)
            glob = float((accs * weights).sum() / weights.sum())
            return glob, float(np.var(accs))
