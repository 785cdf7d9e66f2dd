"""Unified event-driven FL engine (the single loop behind every method).

The paper's protocol family — synchronous intra-tier rounds composed with
asynchronous cross-tier updates over (optionally) compressed links — and all
of its baselines are instances of one discrete-event loop:

    pop event -> (dropout filter / sampling) -> downlink -> local train
    -> uplink -> aggregate -> reschedule -> periodic eval,

with byte accounting along the two links.  What differs between FedAT,
FedAvg, TiFL and FedAsync is *server policy*: what an event means, how the
server state is aggregated, and what gets rescheduled.  Those differences
live behind the :class:`ServerStrategy` interface (FLGo's
``BasicServer.iterate()`` hook pattern, adapted to an event queue); the loop
itself lives in :func:`run_engine` and exists exactly once.

RNG discipline: a strategy declares ``seed_offset`` and draws exclusively
from ``ctx.rng`` in event order, so a (strategy, SimEnv, EngineConfig, seed)
tuple fully determines the :class:`~repro.core.scheduler.Metrics`
trajectory.  The offsets match the deleted per-method loops, keeping every
trajectory reproducible against the seed implementations
(tests/test_engine_parity.py).
"""
from __future__ import annotations

import abc
import dataclasses
import enum
import pickle
from typing import Any, Optional

import numpy as np

from repro.core import faults as faults_mod
from repro.core import tiering
from repro.core.scheduler import EventQueue, Metrics
from repro.core.simulation import SimEnv


@dataclasses.dataclass
class EngineConfig:
    """Knobs shared by every method; strategy-specific knobs live on the
    strategy object (see core/strategies/)."""
    total_updates: int = 200   # T: global update budget
    eval_every: int = 10
    seed: int = 0
    #: re-profile latencies + rebuild the tier map every N global updates
    #: (0 = never).  Draws from the engine rng, so a run with re-tiering
    #: is still fully determined by (strategy, SimEnv, EngineConfig).
    retier_every: int = 0
    #: multiplicative latency drift per re-profiling (tiering.drift_latencies)
    retier_drift: float = 0.2
    #: engine-plane fault knobs (core/faults.py FaultConfig): tier
    #: blackouts, uplink poisoning / the validation gate, and the
    #: crash-resume checkpoint cadence.  None (the default) keeps the
    #: loop byte-for-byte the zero-fault engine.
    faults: Optional[faults_mod.FaultConfig] = None


class Outcome(enum.Enum):
    """What a handled event did to the global round counter ``t``.

    STEP        committed one global update: t += 1, eval cadence applies.
    SKIP_ROUND  consumed a round of budget without an update (e.g. TiFL
                drawing a tier whose members all dropped out): t += 1 but
                no eval — mirrors the seed loops' ``continue`` after the
                round counter advanced.
    DISCARD     the event produced nothing (dead FedAsync client, FedAT
                tier resample): t unchanged.
    """
    STEP = "step"
    SKIP_ROUND = "skip_round"
    DISCARD = "discard"


@dataclasses.dataclass
class EngineContext:
    """Mutable per-run state handed to every strategy hook.

    ``executor`` is the engine-owned :class:`~repro.core.executor.
    RoundExecutor`: the fused, fixed-shape, device-resident round step
    that strategies parameterize (prox on/off, codec, aggregation
    weights).  It replaces the old per-event ``local_train`` leg — the
    whole downlink → train → uplink → aggregate pipeline now runs as one
    jitted call over resident data (DESIGN.md §Perf).  The environment's
    mesh (``SimConfig.mesh``, selected via the spec's ``mesh`` section)
    decides whether that call is single-device or client-sharded over the
    mesh's data axis (DESIGN.md §Scale-mapping); the loop itself is
    mesh-agnostic.

    ``draw_seed`` is the one host rng draw per training event; its
    position in event order is the parity contract with the seed loops.
    """
    q: EventQueue
    rng: np.random.Generator
    metrics: Metrics
    cfg: EngineConfig
    executor: Any = None
    bytes_up: float = 0.0
    bytes_down: float = 0.0
    t_global: int = 0
    #: the run's FaultPlane (core/faults.py), or None for zero-fault runs
    #: — strategies read the gate config and poison draws off it
    faults: Any = None

    def draw_seed(self) -> int:
        """The per-event PRNG seed draw (exactly one ``rng.integers``)."""
        return int(self.rng.integers(2 ** 31))


class ServerStrategy(abc.ABC):
    """Server policy plugged into :func:`run_engine`.

    Lifecycle: ``bind`` (allocate server state from the env) ->
    ``bootstrap`` (push initial events) -> ``on_event`` per popped event ->
    ``on_eval`` after each periodic evaluation.
    """

    name: str = "strategy"
    #: added to EngineConfig.seed for this strategy's rng stream; the values
    #: in core/strategies/ reproduce the seed implementations bit-for-bit.
    seed_offset: int = 0

    def bind(self, env: SimEnv, cfg: EngineConfig) -> None:
        """Allocate server-side state (models, counters) for a fresh run."""

    @abc.abstractmethod
    def bootstrap(self, env: SimEnv, ctx: EngineContext) -> None:
        """Push the initial event(s) onto ``ctx.q``."""

    @abc.abstractmethod
    def on_event(self, env: SimEnv, ctx: EngineContext, now: float,
                 actor: Any) -> Outcome:
        """Handle one completion event; return what it did to ``t``."""

    @abc.abstractmethod
    def global_params(self) -> Any:
        """The model the server would deploy right now (eval target)."""

    def on_eval(self, env: SimEnv, ctx: EngineContext) -> None:
        """Hook after each periodic eval (e.g. re-measure the wire ratio)."""

    def on_fault(self, env: SimEnv, ctx: EngineContext, now: float,
                 actor: Any) -> Outcome:
        """Handle a fault-plane marker event (core/faults.py pushes them;
        the loop routes them here instead of ``on_event``).  Default:
        ignore — strategies without a tier model treat a blackout as a
        no-op."""
        return Outcome.DISCARD

    # -- crash-resume (DESIGN.md §Fault-plane) --------------------------
    def snapshot(self):
        """(device_pytree, host_state) capturing all server state; the
        device tree round-trips through the CheckpointManager, the host
        dict through a pickle.  Bitwise resume requires *everything* the
        strategy mutates to be here."""
        raise NotImplementedError(
            f"strategy {self.name!r} does not implement engine crash-resume")

    def restore(self, dev, host) -> None:
        """Apply a :meth:`snapshot` onto a freshly bound strategy."""
        raise NotImplementedError(
            f"strategy {self.name!r} does not implement engine crash-resume")


def _engine_snapshot(ctx: EngineContext, strategy: ServerStrategy,
                     env: SimEnv) -> dict:
    """Everything a resumed run needs to replay bitwise: the strategy's
    device/host state, the event queue, the engine rng stream position,
    metrics so far, byte counters, the fault-plane stream, and the
    (possibly re-tiered) tier map.  Device arrays go through the
    CheckpointManager's array path; the host side rides along as one
    pickled uint8 leaf."""
    dev, host = strategy.snapshot()
    blob = pickle.dumps({
        "t_global": ctx.t_global,
        "bytes_up": ctx.bytes_up,
        "bytes_down": ctx.bytes_down,
        "metrics": dataclasses.asdict(ctx.metrics),
        "queue": ctx.q.state(),
        "rng": ctx.rng.bit_generator.state,
        "faults": None if ctx.faults is None else ctx.faults.state(),
        "strategy": host,
        "tm": (env.tm.tier_of, list(env.tm.members), env.tm.latencies),
    })
    return {"dev": dev, "host": np.frombuffer(blob, np.uint8)}


def _apply_engine_snapshot(snap: dict, ctx: EngineContext,
                           strategy: ServerStrategy, env: SimEnv) -> None:
    host = pickle.loads(np.asarray(snap["host"]).tobytes())
    ctx.t_global = int(host["t_global"])
    ctx.bytes_up = float(host["bytes_up"])
    ctx.bytes_down = float(host["bytes_down"])
    ctx.metrics = Metrics(**host["metrics"])
    ctx.q.set_state(host["queue"])
    ctx.rng.bit_generator.state = host["rng"]
    if ctx.faults is not None and host["faults"] is not None:
        ctx.faults.set_state(host["faults"])
    if ctx.cfg.retier_every:  # the map can only have drifted when retiering
        tier_of, members, lat = host["tm"]
        env.tm = tiering.TierMap(tier_of=tier_of, members=list(members),
                                 latencies=lat)
    # same shapes, dtypes and placement as the live state, so the restored
    # state hits the executor's existing compile-cache entries — zero
    # extra recompiles
    strategy.restore(env.replicate(snap["dev"]), host["strategy"])


def run_engine(env: SimEnv, strategy: ServerStrategy, cfg: EngineConfig,
               on_record=None, checkpoint_dir: Optional[str] = None,
               resume: bool = False) -> Metrics:
    """The one event loop.  Timestamp-ordered server reactions (Figure 1's
    timeline), a global update budget, and the shared eval cadence.

    ``on_record(point: dict)`` streams each recorded eval point to the
    caller (the api layer's ``Run.run(on_eval=...)``); the dict carries the
    same fields :meth:`~repro.core.scheduler.Metrics.record` appends.

    With ``cfg.retier_every > 0`` the environment's tier map is rebuilt
    from drifted latencies every N committed updates; the original map is
    restored on exit so shared/cached environments stay reproducible.

    Fault plane (``cfg.faults``, DESIGN.md §Fault-plane): blackout markers
    are scheduled at bootstrap and routed to ``strategy.on_fault``; with
    ``checkpoint_dir`` and ``faults.checkpoint_every > 0`` the full engine
    state is checkpointed every N committed updates through
    checkpoint/ckpt.py, and ``resume=True`` restores the newest snapshot
    (falling back to a fresh start when none exists) — the resumed run
    replays to a bitwise-identical metrics trajectory.
    """
    ctx = EngineContext(
        q=EventQueue(),
        rng=np.random.default_rng(cfg.seed + strategy.seed_offset),
        metrics=Metrics(), cfg=cfg, executor=env.executor())
    if cfg.faults is not None and cfg.faults.injects_faults:
        # blackouts strike the strategy's cross-aggregation units: flat
        # tiers, or silos under the topology plane (same marker protocol,
        # same elastic renormalization)
        topo = getattr(env, "topology", None)
        n_units = topo.n_silos if topo is not None else env.tm.n_tiers
        ctx.faults = faults_mod.FaultPlane(cfg.faults, n_units)
    strategy.bind(env, cfg)

    every = cfg.faults.checkpoint_every if cfg.faults is not None else 0
    mgr = None
    if checkpoint_dir is not None and every > 0:
        from repro.checkpoint import CheckpointManager
        mgr = CheckpointManager(checkpoint_dir, keep=2)

    tm0 = env.tm if cfg.retier_every else None
    resumed = False
    if mgr is not None and resume:
        try:
            snap, _ = mgr.restore(like=_engine_snapshot(ctx, strategy, env))
            _apply_engine_snapshot(snap, ctx, strategy, env)
            resumed = True
        except FileNotFoundError:
            pass  # no snapshot yet (killed before the first save)
    if not resumed:
        strategy.bootstrap(env, ctx)
        if ctx.faults is not None:
            ctx.faults.schedule(ctx.q)

    try:
        while ctx.t_global < cfg.total_updates and len(ctx.q):
            now, actor = ctx.q.pop()
            if ctx.faults is not None and faults_mod.is_fault_event(actor):
                out = strategy.on_fault(env, ctx, now, actor)
            else:
                out = strategy.on_event(env, ctx, now, actor)
            if out is Outcome.DISCARD:
                continue
            ctx.t_global += 1
            if (out is not Outcome.SKIP_ROUND
                    and (ctx.t_global % cfg.eval_every == 0
                         or ctx.t_global == cfg.total_updates)):
                acc, var = env.evaluate(strategy.global_params())
                strategy.on_eval(env, ctx)
                ctx.metrics.record(now, ctx.t_global, acc, var,
                                   ctx.bytes_up, ctx.bytes_down)
                if on_record is not None:
                    on_record({"time": now, "round": ctx.t_global,
                               "acc": acc, "acc_var": var,
                               "bytes_up": ctx.bytes_up,
                               "bytes_down": ctx.bytes_down})
            if cfg.retier_every and ctx.t_global % cfg.retier_every == 0:
                env.retier(ctx.rng, cfg.retier_drift)
            if mgr is not None and ctx.t_global % every == 0:
                mgr.save(ctx.t_global, _engine_snapshot(ctx, strategy, env))
    finally:
        if mgr is not None:
            mgr.wait()
        if tm0 is not None:
            env.tm = tm0
    return ctx.metrics


def run_strategy(env: SimEnv, name: str, cfg: EngineConfig = None,
                 **strategy_kwargs) -> Metrics:
    """Convenience: look up a registered strategy by name and run it."""
    from repro.core import strategies
    return run_engine(env, strategies.make_strategy(name, **strategy_kwargs),
                      cfg or EngineConfig())
