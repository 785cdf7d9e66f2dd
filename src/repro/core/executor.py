"""Fused, fixed-shape, device-resident round execution (DESIGN.md §Perf).

The engine's hot path used to pay three host-side taxes per popped event:
re-uploading the selected clients' data from numpy, retracing the client
update whenever dropout shrank the sample to a new length, and running the
Eq. 4 / Eq. 3 aggregation as a swarm of tiny un-jitted dispatches.  The
:class:`RoundExecutor` removes all three:

* **Resident data plane** — ``SimEnv`` uploads the padded train stacks to
  the device once; per-event client selection is an in-graph ``jnp.take``
  over a fixed-length id vector.  Under the **streaming population
  plane** (DESIGN.md §Population-plane) there is no resident stack: the
  K sampled clients' padded batch is host-materialized per round and
  passed to the same step body as data — a jit argument is
  bitwise-identical input to the in-graph gather of the same rows, so
  the two planes share one step body at a distinct ``("stream",)``
  trace key.
* **Fixed-shape padding contract** — a dropout-shrunken sample of ``n``
  live clients is padded to ``clients_per_round`` slots by repeating a
  live id with a **zero aggregation weight**.  Adding exactly-zero terms
  to the Eq. 4 weighted sum is bitwise-neutral, so the trajectory is
  identical to the variable-shape path while the jitted step compiles
  exactly once per strategy configuration.
* **Fused round step** — downlink codec ``lossy`` → gather → vmapped
  local train → uplink ``lossy`` → Eq. 4 intra-tier average →
  ``tier_models.at[m].set`` → Eq. 3 cross-tier aggregation run as one
  jitted call, with buffer donation for the server-state arguments
  (every backend, the CPU included, updates them in place).

Bitwise parity with the eager seed loops constrains what may live inside
the fused program: XLA rewrites division into reciprocal-multiply and
contracts multiply-into-reduction (FMA) when it can fuse, and neither
rewrite happens in op-by-op dispatch.  So the tiny aggregation *weight*
vectors (Eq. 4 client weights, Eq. 3 cross-tier weights) are computed
eagerly per event and passed in as data, and
:func:`~repro.core.aggregation.weighted_average` pins its product behind
an optimization barrier; the model-sized math (train, codec, averages,
tier-slot scatter) all stays in-graph.

RNG parity: the seed loops draw ``rng.integers(2**31)`` per event and
``jax.random.split`` to the *live* client count.  Under the partitionable
threefry (``jax_threefry_partitionable``, JAX's default) splits are
prefix-stable: ``split(key, K)[:n] == split(key, n)`` bit for bit.  So
each step takes the event's ``seed`` and live count ``n_live`` as int32
data and derives the padded (K, 2) key array in the graph
(:func:`_round_keys`): the K-way split, with rows ``>= n_live`` zeroed.
Padded slots train on zero keys but carry zero weight.  The live count
is traced, so it never retraces the step.  The round's integers (FedAT's
tier slot ``m``, ``seed``, ``n_live``) travel as one int32 vector: each
host array passed to a jitted call is a host-to-device copy of its own
(0.1–0.35 ms on a TPU v5e host).

Trace accounting: every fused step bumps ``trace_counts[step_key]`` at
trace time (a Python side effect inside the jitted function body), which
is what ``tests/test_round_executor.py`` uses to assert zero shape-driven
retraces across a dropout-laden run.

**Client sharding on a device mesh** (DESIGN.md §Scale-mapping).  When the
environment carries a mesh whose ``data`` axis has size D > 1, the
per-round client stack is split over that axis: the downlink ``lossy``
(each device on its own replica of the global model — GSPMD cannot
partition a Pallas kernel), the vmapped local train + pinned uplink
``lossy`` + partial Eq. 4 weighted sum run under ``shard_map`` (each
device trains K/D clients), and one ``psum`` over ``data`` completes the
tier model.  Everything outside that leg — the in-graph gather over the
(client-sharded) resident train stacks, the tier-slot scatter, and the
Eq. 3 cross-tier average — stays in the auto-sharded (GSPMD) region of
the same jitted program.  ``clients_per_round`` must be a
multiple of D (checked at :class:`~repro.core.simulation.SimEnv` build).

Parity contract across the mesh dimension: with D == 1 (no mesh, or a
one-device host mesh) the executor builds the *exact* single-device steps
— same trace keys, bitwise-identical trajectories.  With D > 1 the steps
get distinct trace keys (``(..., "dataD")``) and match the single-device
trajectory within a pinned numerical tolerance only: the psum
re-associates the Eq. 4 sum and XLA schedules the shard-local vmap
differently, and blockwise codecs (``quantize8/16``) group their blocks
shard-locally.  ``tests/test_mesh_executor.py`` pins both sides.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import aggregation
from repro.runtime import sharding as shd


def _round_keys(seed, n_live, K: int) -> jax.Array:
    """The round's (K, 2) client keys, derived inside a fused step:
    ``split(PRNGKey(seed), K)`` with the rows of dead slots
    (``>= n_live``) zeroed — bitwise ``RoundExecutor._pad_keys(seed,
    n_live)``, since the split is prefix-stable."""
    assert jax.config.jax_threefry_partitionable, (
        "in-graph round keys need jax_threefry_partitionable: only then "
        "is split(key, K)[:n] == split(key, n), the seed loops' keys")
    keys = jax.random.split(jax.random.PRNGKey(seed), K)
    return jnp.where(jnp.arange(K)[:, None] < n_live, keys, 0)


def _pin(tree: Any) -> Any:
    """Materialization point inside a fused step.

    The parity oracle (the eager seed loops) rounds every pipeline stage
    to f32 at an op boundary.  Inside one fused program XLA would fuse
    across those boundaries and reassociate / FMA-contract the arithmetic,
    producing ulp-level differences that chaotic training then amplifies.
    Pinning each stage output with an optimization barrier reproduces the
    eager rounding exactly while keeping everything else fused.
    """
    return jax.tree.map(jax.lax.optimization_barrier, tree)


class RoundExecutor:
    """Owns the device-resident data plane and the per-strategy fused round
    steps.  Strategies parameterize a step (prox on/off, codec, aggregation
    weights); the executor caches one compiled step per configuration.

    One executor is cached per :class:`~repro.core.simulation.SimEnv`
    (``env.executor()``) so repeated engine runs over the same environment
    reuse the compile cache.

    The environment's mesh decides the execution shape: with a ``data``
    axis of size D > 1 the per-round client stack runs client-sharded
    under ``shard_map`` (one compiled step per configuration *and* mesh,
    keyed ``(..., "dataD")``); with D == 1 the byte-identical
    single-device steps are built, so a one-device host mesh reproduces
    the no-mesh trajectory bitwise.
    """

    def __init__(self, env):
        self.env = env
        self.K = int(env.sc.clients_per_round)
        #: device mesh (None = single device) and its data-axis size D;
        #: D > 1 selects the shard_map round steps (distinct trace keys),
        #: D == 1 keeps the single-device steps byte-for-byte.
        self.mesh = getattr(env, "mesh", None)
        self.D = int(getattr(env, "data_axis", 1))
        assert self.K % max(self.D, 1) == 0, "SimEnv validates divisibility"
        #: shard the (M, ...) tier-model stack over the mesh's pod axis
        #: (the TiFL/FedAT tier axis); a no-op without a multi-pod mesh.
        #: sized from this env's own mesh only, never the ambient one.
        self.shard_tiers = bool(getattr(env.sc, "shard_tiers", False)) \
            and self.mesh is not None \
            and self.mesh.shape.get("pod", 1) > 1
        #: streaming population plane (DESIGN.md §Population-plane): no
        #: resident train stacks — the K sampled clients' rows are
        #: host-materialized per round and passed to the fused step as
        #: data.  Streaming steps get a distinct ("stream",) trace-key
        #: tag; the step bodies themselves are shared (``_select``).
        self.streaming = bool(getattr(env, "streaming", False))
        self._tag: Tuple[str, ...] = ("stream",) if self.streaming else ()
        #: topology plane (core/topology.py): per-silo rounds fan out
        #: over E edges x K_edge clients in one fused step; None = flat.
        self.topo = getattr(env, "topology", None)
        if self.topo is not None:
            self.E = int(self.topo.edges_per_silo)
            self.K_edge = int(self.topo.k_edge)
        #: high-water mark of the streamed per-round batch bytes (0 until
        #: a streaming round runs; SimEnv.data_plane_bytes reads it)
        self.stream_bytes = 0
        self._steps: Dict[tuple, Any] = {}
        #: step key -> number of times the step body was traced; a fixed-
        #: shape step traces exactly once per configuration.
        self.trace_counts: Dict[tuple, int] = {}

    # ------------------------------------------------------------------
    # host-side marshalling (tiny per-event vectors; the model-sized
    # tensors never leave the device)
    # ------------------------------------------------------------------
    def _pad_ids(self, ids: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """(ids (n,)) -> (padded ids (K,), padded sample counts (K,)).

        Dead slots repeat a live id (valid gather target, finite params)
        and get sample count 0, which zeroes them out of Eq. 4 exactly.
        """
        n = len(ids)
        pid = np.empty(self.K, np.int32)
        pid[:n] = ids
        pid[n:] = ids[0] if n else 0
        ns = np.zeros(self.K, np.float32)
        ns[:n] = self.env.n_train_all[ids]
        return pid, ns

    def _pad_keys(self, seed: int, n: int) -> jax.Array:
        """Split to the live count (rng parity with the seed loops), then
        pad to K rows; padded rows are zero keys behind zero weights.
        The host-side twin of the steps' in-graph :func:`_round_keys`."""
        keys = jax.random.split(jax.random.PRNGKey(seed), n)
        if n == self.K:
            return keys
        pad = jnp.zeros((self.K - n,) + keys.shape[1:], keys.dtype)
        return jnp.concatenate([keys, pad], axis=0)

    def _select(self, data):
        """Client rows for the round: an in-graph gather over the resident
        train stacks when ``data`` is the padded id vector, or the
        streamed batch itself when ``data`` is the materialized dict
        (streaming population plane).  A batch passed as a jit argument
        is bitwise-identical input to the in-graph gather of the same
        rows, so the two planes share one step body
        (tests/test_population.py pins the parity)."""
        if isinstance(data, dict):
            return data
        stacks = self.env.train_dev
        return {k: jnp.take(stacks[k], data, axis=0)
                for k in ("x", "y", "mask")}

    def _round_data(self, pid: np.ndarray):
        """What the fused step selects from: the padded id vector
        (resident planes) or the host-materialized padded batch
        (streaming plane).  Padded dead slots repeat a live id, so the
        streamed batch repeats that client's rows — the same selection
        the resident gather produces, behind a zero Eq. 4 weight."""
        if not self.streaming:
            return pid
        batch = self.env.population.materialize(pid)
        self.stream_bytes = max(self.stream_bytes,
                                sum(a.nbytes for a in batch.values()))
        with jax.profiler.TraceAnnotation("repro.round.h2d"):
            return {k: jnp.asarray(v) for k, v in batch.items()}

    def _pad_topology(self, ids_edges):
        """Per-edge live id lists -> the flat (E*K_edge,) padded id
        vector plus the eagerly-normalized weight vectors: ``w_intra`` is
        per-edge Eq. 4 normalized (each edge's K_edge slots sum to 1 over
        its live clients; empty edges stay all-zero), ``w_edge`` is the
        Eq. 4-over-edges weights ∝ per-edge live sample mass (renormalized
        over non-empty edges).  Dead slots repeat a live id from any edge
        (valid gather target) behind exactly-zero weights — the same
        bitwise-neutral padding contract as :meth:`_pad_ids`."""
        E, Ke = self.E, self.K_edge
        fallback = next(int(ids[0]) for ids in ids_edges if len(ids))
        pid = np.full(E * Ke, fallback, np.int32)
        ns = np.zeros(E * Ke, np.float32)
        w_intra = np.zeros(E * Ke, np.float32)
        edge_samples = np.zeros(E, np.float32)
        counts = []
        for e, ids in enumerate(ids_edges):
            n = len(ids)
            counts.append(n)
            if n:
                pid[e * Ke:e * Ke + n] = ids
                ns[e * Ke:e * Ke + n] = self.env.n_train_all[ids]
                w_intra[e * Ke:(e + 1) * Ke] = \
                    aggregation.client_weights_host(ns[e * Ke:(e + 1) * Ke])
                edge_samples[e] = ns[e * Ke:(e + 1) * Ke].sum(
                    dtype=np.float32)
        return pid, w_intra, aggregation.client_weights_host(edge_samples), \
            counts

    def _pad_topology_keys(self, seed: int, counts) -> jax.Array:
        """Split to the total live count (one split call, rng parity with
        the flat round), then scatter each edge's keys into the head of
        its K_edge slot block; padded rows are zero keys behind zero
        weights."""
        E, Ke = self.E, self.K_edge
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed),
                                           sum(counts)))
        out = np.zeros((E * Ke,) + keys.shape[1:], keys.dtype)
        off = 0
        for e, n in enumerate(counts):
            out[e * Ke:e * Ke + n] = keys[off:off + n]
            off += n
        return jnp.asarray(out)

    # ------------------------------------------------------------------
    # fused steps (one compile per configuration, cached)
    # ------------------------------------------------------------------
    def _bump(self, key: tuple) -> None:
        self.trace_counts[key] = self.trace_counts.get(key, 0) + 1

    @staticmethod
    def _check_in_graph(codec) -> None:
        if codec is not None and not codec.in_graph:
            raise NotImplementedError(
                f"codec {codec.name!r} declares in_graph=False; the fused "
                "round step needs a jit-composable lossy() for both links "
                "(all registered codecs are in-graph — see DESIGN.md §Perf)")

    # -- client-sharded leg (mesh data axis, D > 1) ---------------------
    def _train_psum(self, update, lossy):
        """The shard_map'd leg of a sharded round: pinned downlink
        ``lossy`` of the replicated model, vmapped local train over the
        K/D shard-local clients, pinned uplink ``lossy``, partial Eq. 4
        weighted sum (same barrier-on-product rounding as
        :func:`~repro.core.aggregation.weighted_average`), then one
        ``psum`` over ``data`` completes the weighted tier average.
        Both links' codec runs in here because GSPMD cannot partition a
        Pallas (Mosaic) kernel: each device encodes its own replica.

        ``w_intra`` arrives already normalized (host-side, exactly as in
        the single-device step), so the psum of shard-partial sums *is*
        the full weighted average; padded zero-weight slots stay exactly
        neutral on whichever shard they land.
        """
        def body(w, batch, keys, w_intra):
            w_sent = _pin(lossy(w)) if lossy is not None else w
            client_params, _ = update(w_sent, batch, keys)
            client_params = (_pin(lossy(_pin(client_params)))
                             if lossy is not None else _pin(client_params))

            def part(leaf):
                w = w_intra.reshape(
                    (-1,) + (1,) * (leaf.ndim - 1)).astype(jnp.float32)
                prod = jax.lax.optimization_barrier(
                    leaf.astype(jnp.float32) * w)
                return jnp.sum(prod, axis=0)

            sums = jax.tree.map(part, client_params)
            return jax.tree.map(lambda x: jax.lax.psum(x, "data"), sums)

        # clients split over "data"; unmentioned mesh axes (model, pod)
        # see replicated inputs, and the psum makes the P() outputs
        # replicated too.  check_vma stays off: under it, autodiff would
        # psum each client's gradient of the replicated model across
        # shards, and the Pallas codec kernel does not trace with
        # varying-axis types.
        return jax.shard_map(body, mesh=self.mesh,
                             in_specs=(P(), P("data"), P("data"), P("data")),
                             out_specs=P(), check_vma=False)

    def _tier_place(self, tier_models):
        """Optionally pin the (M, ...) tier stack to the pod (tier) axis
        (logical axis "tiers" -> physical "pod", runtime/sharding.py)."""
        if not self.shard_tiers:
            return tier_models
        return jax.tree.map(
            lambda leaf: jax.lax.with_sharding_constraint(
                leaf, shd.logical_sharding(
                    ("tiers",) + (None,) * (leaf.ndim - 1), self.mesh)),
            tier_models)

    def _fedat_step_sharded(self, codec, use_prox: bool):
        self._check_in_graph(codec)
        key = ("fedat", codec.name, use_prox, f"data{self.D}") + self._tag
        if key in self._steps:
            return self._steps[key]
        env = self.env
        update = env.update_fn_raw if use_prox else env.update_fn_noprox_raw
        train = self._train_psum(update, codec.lossy)

        def step(w_global, tier_models, ints, data, w_intra, w_cross):
            self._bump(key)
            m, keys = ints[0], _round_keys(ints[1], ints[2], self.K)
            tier_model = _pin(
                train(w_global, self._select(data), keys, w_intra))
            tier_models = self._tier_place(jax.tree.map(
                lambda s, nw: s.at[m].set(nw), tier_models, tier_model))
            w_global = aggregation.weighted_average(tier_models, w_cross)
            return w_global, tier_models

        self._steps[key] = jax.jit(step, donate_argnums=(0, 1))
        return self._steps[key]

    def _fedavg_step_sharded(self, codec=None):
        self._check_in_graph(codec)
        key = (("fedavg",) if codec is None else ("fedavg", codec.name)) \
            + (f"data{self.D}",) + self._tag
        if key in self._steps:
            return self._steps[key]
        update = self.env.update_fn_noprox_raw
        train = self._train_psum(update, None if codec is None
                                 else codec.lossy)

        def step(w, data, w_intra, ints):
            self._bump(key)
            keys = _round_keys(ints[0], ints[1], self.K)
            return train(w, self._select(data), keys, w_intra)

        self._steps[key] = jax.jit(step, donate_argnums=(0,))
        return self._steps[key]

    # -- single-device steps (and the D == 1 path under any mesh) -------
    def _fedat_step(self, codec, use_prox: bool):
        if self.D > 1:
            return self._fedat_step_sharded(codec, use_prox)
        self._check_in_graph(codec)
        key = ("fedat", codec.name, use_prox) + self._tag
        if key in self._steps:
            return self._steps[key]
        env = self.env
        update = env.update_fn_raw if use_prox else env.update_fn_noprox_raw
        lossy = codec.lossy

        def step(w_global, tier_models, ints, data, w_intra, w_cross):
            self._bump(key)
            m, keys = ints[0], _round_keys(ints[1], ints[2], self.K)
            w_sent = _pin(lossy(w_global))
            client_params, _ = update(w_sent, self._select(data), keys)
            client_params = _pin(lossy(_pin(client_params)))
            tier_model = _pin(
                aggregation.weighted_average(client_params, w_intra))
            tier_models = jax.tree.map(lambda s, nw: s.at[m].set(nw),
                                       tier_models, tier_model)
            w_global = aggregation.weighted_average(tier_models, w_cross)
            return w_global, tier_models

        self._steps[key] = jax.jit(step, donate_argnums=(0, 1))
        return self._steps[key]

    def _fedat_topology_step(self, codecs, use_prox: bool):
        """One fused hierarchical silo round (DESIGN.md §Topology-plane):
        downlink codec chain (silo_global -> edge_silo -> client_edge) on
        the silo's *dispatch-time* global snapshot → vmapped local train
        over all E x K_edge sampled clients → client_edge uplink lossy →
        per-edge Eq. 4 (static unroll over edges, exactly the flat Eq. 4
        body per edge) → edge_silo lossy → Eq. 4 over edges (weights ∝
        live sample mass, renormalized over non-empty edges) → silo_global
        lossy → optional delayed-gradient compensation
        ``lam * (w_global_now - w_dispatch)`` → silo-slot scatter →
        Eq. 3 over the silo stack.

        With 1 silo / 1 edge, zero-width delay bands and default codecs
        every extra stage is an exact identity (x1.0 singleton averages,
        bitwise-neutral pins), so this step reproduces the flat
        :meth:`_fedat_step` trajectory bitwise — pinned by
        tests/test_topology.py.
        """
        ce, es, sg = codecs
        for c in codecs:
            self._check_in_graph(c)
        lam = float(self.topo.cfg.compensation)
        key = ("fedat_topo", ce.name, es.name, sg.name, use_prox, lam) \
            + self._tag
        if key in self._steps:
            return self._steps[key]
        env = self.env
        update = env.update_fn_raw if use_prox else env.update_fn_noprox_raw
        E, Ke = self.E, self.K_edge
        lam32 = jnp.float32(lam)

        def step(w_global, silo_models, dispatch, s, data, w_intra,
                 w_edge, w_cross, keys):
            self._bump(key)
            # the silo trains from the global model it fetched when this
            # round was dispatched (stale under WAN delay), compressed by
            # the downlink chain global -> silo -> edge -> client
            w_stale = _pin(jax.tree.map(lambda d: d[s], dispatch))
            w_sent = _pin(ce.lossy(_pin(es.lossy(_pin(sg.lossy(w_stale))))))
            client_params, _ = update(w_sent, self._select(data), keys)
            client_params = _pin(ce.lossy(_pin(client_params)))
            # per-edge Eq. 4 over each edge's K_edge slots — a static
            # unroll so each edge runs the exact flat Eq. 4 body
            edge_models = []
            for e in range(E):
                pe = jax.tree.map(lambda l, e=e: l[e * Ke:(e + 1) * Ke],
                                  client_params)
                em = _pin(aggregation.weighted_average(
                    pe, w_intra[e * Ke:(e + 1) * Ke]))
                edge_models.append(_pin(es.lossy(_pin(em))))
            edge_stack = jax.tree.map(lambda *ls: jnp.stack(ls),
                                      *edge_models)
            silo_model = _pin(aggregation.weighted_average(
                edge_stack, w_edge))
            silo_model = _pin(sg.lossy(_pin(silo_model)))
            if lam > 0:
                # delayed-gradient compensation ("Stragglers Are Not
                # Disaster"): restore lam of the global drift the silo
                # missed while its round was in flight; the product is
                # pinned so the add never FMA-contracts
                silo_model = _pin(jax.tree.map(
                    lambda m_, g, st: m_ + jax.lax.optimization_barrier(
                        lam32 * (g - st)),
                    silo_model, w_global, w_stale))
            silo_models = self._tier_place(jax.tree.map(
                lambda st, nw: st.at[s].set(nw), silo_models, silo_model))
            w_new = aggregation.weighted_average(silo_models, w_cross)
            # the silo re-fetches the fresh global for its next round
            dispatch = jax.tree.map(lambda d, g: d.at[s].set(g),
                                    dispatch, w_new)
            return w_new, silo_models, dispatch

        self._steps[key] = jax.jit(step, donate_argnums=(1, 2))
        return self._steps[key]

    def _fedat_step_gated(self, codec, use_prox: bool, gate):
        """FedAT round step with the fault plane's server-side validation
        gate (core/steps.py) spliced in after the uplink decode: poison
        injection (NaN uplinks) → non-finite zero-weighting + renormalize
        → optional delta-norm clip → Eq. 4 over survivors, with the
        previous tier/global model kept when *no* client survives.  A
        distinct trace key (gate config included) keeps the ungated step
        byte-for-byte the parity-oracle body."""
        if self.D > 1:
            raise NotImplementedError(
                "the update validation gate is single-device only for now "
                f"(mesh data axis D={self.D}); run gated fault scenarios "
                "without a mesh data axis")
        self._check_in_graph(codec)
        key = ("fedat", codec.name, use_prox, "gate", gate.clip_norm) \
            + self._tag
        if key in self._steps:
            return self._steps[key]
        from repro.core import steps as fl_steps
        env = self.env
        update = env.update_fn_raw if use_prox else env.update_fn_noprox_raw
        lossy = codec.lossy
        clip = float(gate.clip_norm)

        def step(w_global, tier_models, ints, data, w_intra, w_cross,
                 poison):
            self._bump(key)
            m, keys = ints[0], _round_keys(ints[1], ints[2], self.K)
            w_sent = _pin(lossy(w_global))
            client_params, _ = update(w_sent, self._select(data), keys)
            client_params = _pin(lossy(_pin(client_params)))
            client_params = fl_steps.poison_updates(client_params, poison)
            client_params, w_ok, any_ok = fl_steps.gate_updates(
                client_params, w_intra, w_sent, clip)
            tier_model = _pin(
                aggregation.weighted_average(client_params, w_ok))
            prev = jax.tree.map(lambda s: s[m], tier_models)
            tier_model = jax.tree.map(
                lambda nw, p: jnp.where(any_ok, nw, p), tier_model, prev)
            tier_models = jax.tree.map(lambda s, nw: s.at[m].set(nw),
                                       tier_models, tier_model)
            w_global = aggregation.weighted_average(tier_models, w_cross)
            return w_global, tier_models

        self._steps[key] = jax.jit(step, donate_argnums=(0, 1))
        return self._steps[key]

    def _fedavg_step(self, codec=None):
        """``codec=None`` is the paper's raw-f32 baseline link and keeps the
        seed step body (and its trace-count key) byte-for-byte; a codec adds
        the same pinned lossy downlink/uplink stages the FedAT step uses."""
        if self.D > 1:
            return self._fedavg_step_sharded(codec)
        self._check_in_graph(codec)
        key = (("fedavg",) if codec is None
               else ("fedavg", codec.name)) + self._tag
        if key in self._steps:
            return self._steps[key]
        update = self.env.update_fn_noprox_raw

        def step(w, data, w_intra, ints):
            self._bump(key)
            keys = _round_keys(ints[0], ints[1], self.K)
            w_in = w if codec is None else _pin(codec.lossy(w))
            client_params, _ = update(w_in, self._select(data), keys)
            if codec is not None:
                client_params = _pin(codec.lossy(_pin(client_params)))
            return aggregation.weighted_average(_pin(client_params), w_intra)

        self._steps[key] = jax.jit(step, donate_argnums=(0,))
        return self._steps[key]

    def _fedavg_step_gated(self, codec, gate):
        """FedAvg/TiFL round step with the validation gate; the no-survivor
        fallback keeps the server's previous model."""
        if self.D > 1:
            raise NotImplementedError(
                "the update validation gate is single-device only for now "
                f"(mesh data axis D={self.D}); run gated fault scenarios "
                "without a mesh data axis")
        self._check_in_graph(codec)
        key = (("fedavg",) if codec is None else ("fedavg", codec.name)) \
            + ("gate", gate.clip_norm) + self._tag
        if key in self._steps:
            return self._steps[key]
        from repro.core import steps as fl_steps
        update = self.env.update_fn_noprox_raw
        clip = float(gate.clip_norm)

        def step(w, data, w_intra, ints, poison):
            self._bump(key)
            keys = _round_keys(ints[0], ints[1], self.K)
            w_in = w if codec is None else _pin(codec.lossy(w))
            client_params, _ = update(w_in, self._select(data), keys)
            if codec is not None:
                client_params = _pin(codec.lossy(_pin(client_params)))
            client_params = _pin(client_params)
            client_params = fl_steps.poison_updates(client_params, poison)
            client_params, w_ok, any_ok = fl_steps.gate_updates(
                client_params, w_intra, w_in, clip)
            new_w = aggregation.weighted_average(client_params, w_ok)
            return jax.tree.map(lambda nw, p: jnp.where(any_ok, nw, p),
                                new_w, w)

        self._steps[key] = jax.jit(step)
        return self._steps[key]

    def _fedasync_step(self, codec=None):
        """FedAsync trains one client per event, so there is no client
        fan-out to shard: this step is identical under any mesh (the model
        math itself still lands in the auto-sharded GSPMD region)."""
        self._check_in_graph(codec)
        key = (("fedasync",) if codec is None
               else ("fedasync", codec.name)) + self._tag
        if key in self._steps:
            return self._steps[key]
        update = self.env.update_fn_noprox_raw

        def step(w, data, c_glob, c_loc, seed):
            self._bump(key)
            keys = _round_keys(seed, 1, 1)
            w_in = w if codec is None else _pin(codec.lossy(w))
            client_params, _ = update(w_in, self._select(data), keys)
            client_w = _pin(jax.tree.map(lambda a: a[0], client_params))
            if codec is not None:
                client_w = _pin(codec.lossy(client_w))
            # pin both products: the eager oracle materializes them before
            # the add, which XLA would otherwise contract into an FMA.
            # The staleness mix interpolates toward the server's own copy
            # of w (downlink loss only affects what the client trained on).
            return jax.tree.map(
                lambda g, l: (jax.lax.optimization_barrier(c_glob * g)
                              + jax.lax.optimization_barrier(c_loc * l)),
                w, client_w)

        self._steps[key] = jax.jit(step, donate_argnums=(0,))
        return self._steps[key]

    # ------------------------------------------------------------------
    # public per-event entry points
    # ------------------------------------------------------------------
    def fedat_round(self, w_global, tier_models, m: int, ids: np.ndarray,
                    seed: int, *, codec, use_prox: bool, cross_weights,
                    gate=None, poison=None):
        """One FedAT tier-completion round (Algorithm 1 steps 1-5), fused.

        ``cross_weights`` is the (M,) Eq. 3 weight vector, computed
        *eagerly* by the strategy from its update counts (see
        :func:`~repro.core.aggregation.client_weights` on why weight
        normalization must stay out of the fused program).  Returns
        ``(w_global, tier_models)``.

        Donation contract: the server-state arguments (``w_global``,
        ``tier_models``) are donated — callers must pass
        buffers they own (strategies copy ``env.params0`` at bind time)
        and replace their references with the returned values.  The same
        contract holds for the sharded step: shard_map does not change
        which arguments are donated, only how the client fan-out is laid
        out across the mesh.

        With the fault plane's ``gate`` (an :class:`~repro.core.steps.
        UpdateGate`) a distinct gated step is compiled; ``poison`` is the
        (K,) bool uplink-poison mask over the padded client axis (None =
        no poisoning this round).
        """
        with jax.profiler.TraceAnnotation("repro.round"):
            pid, ns = self._pad_ids(ids)
            data = self._round_data(pid)
            with jax.profiler.TraceAnnotation("repro.round.keys"):
                ints = np.array([m, seed, len(ids)], np.int32)
            if gate is None:
                step, extra = self._fedat_step(codec, use_prox), ()
            else:
                step = self._fedat_step_gated(codec, use_prox, gate)
                extra = (np.zeros(self.K, bool) if poison is None
                         else poison,)
            w_intra = aggregation.client_weights_host(ns)
            with jax.profiler.TraceAnnotation("repro.round.launch"):
                return step(w_global, tier_models, ints, data, w_intra,
                            cross_weights, *extra)

    def fedat_topology_round(self, w_global, silo_models, dispatch, s: int,
                             ids_edges, seed: int, *, codecs,
                             use_prox: bool, cross_weights):
        """One hierarchical silo round (DESIGN.md §Topology-plane), fused.

        ``ids_edges`` is a length-E sequence of per-edge live client id
        arrays (already availability/completion filtered; at least one
        must be non-empty).  ``codecs`` is the (client_edge, edge_silo,
        silo_global) codec triple; ``cross_weights`` the (S,) Eq. 3
        vector, computed eagerly by the strategy.  Returns ``(w_global,
        silo_models, dispatch)`` — the dispatch stack's silo-s slot is
        refreshed to the new global in-graph (the silo re-fetches on its
        next round; resample/blackout paths refresh it eagerly instead).

        Donation: ``silo_models``/``dispatch`` are donated;
        ``w_global`` is never donated — the compensation term reads it
        next to the dispatch snapshot that may alias it.
        """
        if self.D > 1:
            raise NotImplementedError(
                f"the topology plane is single-data-axis for now (mesh "
                f"data axis D={self.D}); use a D==1 mesh — multi-pod "
                f"host meshes with one device per pod still map silos "
                f"onto the pod axis (mesh.shard_tiers)")
        with jax.profiler.TraceAnnotation("repro.round"):
            pid, w_intra, w_edge, counts = self._pad_topology(ids_edges)
            data = self._round_data(pid)
            with jax.profiler.TraceAnnotation("repro.round.keys"):
                keys = self._pad_topology_keys(seed, counts)
            step = self._fedat_topology_step(codecs, use_prox)
            with jax.profiler.TraceAnnotation("repro.round.launch"):
                return step(w_global, silo_models, dispatch, np.int32(s),
                            data, w_intra, w_edge, cross_weights, keys)

    def fedavg_round(self, w, ids: np.ndarray, seed: int, *, codec=None,
                     gate=None, poison=None):
        """One synchronous FedAvg round over the sampled clients, fused.
        ``codec=None`` = the paper's raw f32 links; a codec compresses both
        links exactly as in the FedAT step.  Client-shards over the mesh
        data axis exactly like :meth:`fedat_round` (TiFL rounds run
        through here too).  ``gate``/``poison`` select the fault plane's
        gated step, as in :meth:`fedat_round`."""
        with jax.profiler.TraceAnnotation("repro.round"):
            pid, ns = self._pad_ids(ids)
            data = self._round_data(pid)
            with jax.profiler.TraceAnnotation("repro.round.keys"):
                ints = np.array([seed, len(ids)], np.int32)
            if gate is None:
                step, extra = self._fedavg_step(codec), ()
            else:
                step = self._fedavg_step_gated(codec, gate)
                extra = (np.zeros(self.K, bool) if poison is None
                         else poison,)
            w_intra = aggregation.client_weights_host(ns)
            with jax.profiler.TraceAnnotation("repro.round.launch"):
                return step(w, data, w_intra, ints, *extra)

    def fedasync_round(self, w, client: int, a_eff: float, seed: int, *,
                       codec=None):
        """One asynchronous client update with staleness mix-in, fused.

        The interpolation coefficients are rounded to f32 host-side so the
        in-graph math matches the seed loop's eager ``(1-a)*g + a*l``.
        """
        with jax.profiler.TraceAnnotation("repro.round"):
            step = self._fedasync_step(codec)
            with jax.profiler.TraceAnnotation("repro.round.keys"):
                key = np.int32(seed)
            data = self._round_data(np.asarray([client], np.int32))
            with jax.profiler.TraceAnnotation("repro.round.launch"):
                return step(w, data, np.float32(1.0 - a_eff),
                            np.float32(a_eff), key)
