"""Benchmark harness: one function per paper table/figure + kernel benches.

Prints ``name,us_per_call,derived`` CSV lines (derived = the headline
quantity for that table: accuracy, MB, ratio, ...).  Budget-aware: table
benches use a reduced but structurally faithful setup (synthetic non-IID
data, 40 clients / 5 tiers, the paper's delay bands & dropout).

All FL-run benches are driven through the declarative spec API
(:mod:`repro.api`) — one cached environment per scenario, and every
structured result carries the spec hash that produced it.

  PYTHONPATH=src python -m benchmarks.run           # everything
  PYTHONPATH=src python -m benchmarks.run table1 fig5 kernels
  PYTHONPATH=src python -m benchmarks.run engine engine_scaled \\
      engine_lm engine_sharded --json BENCH_engine.json

``engine_lm`` measures the federated-LM path (``data.model=tiny_lm``
through the model registry) with and without the polyline codec —
events/sec, bytes-on-wire, and a result hash over the accuracy
trajectory — plus the flash-vs-reference attention rows on the
long-sequence ``tiny_lm_long`` (seq_len 128), where the backends
actually separate.

``engine_faults`` measures the fault-plane degradation curve (FedAT at
0/5%/20% fault pressure: churn, poisoned uplinks, a tier blackout) —
events/sec and accuracy per level, with the zero-fault row cross-checked
bitwise against a second run.

``engine_population`` measures the population plane's scale axis
(streaming/gather data path at 1k -> 100k -> 1M simulated clients;
100k under ``--smoke``): events/sec, peak data-plane bytes, and the
flat-memory ratio vs the 1k row, with a 256-client stacked-vs-streaming
parity row cross-checked bitwise.

``roofline`` runs the measured kernel roofline
(benchmarks/roofline.kernel_roofline): per-kernel achieved FLOP/s and
% of the machine roof, into ``JSON_DOC["roofline"]``.  ``--smoke``
shrinks sizes/reps for the CI push workflow.  ``--json`` *merges* into
an existing file (records keyed by strategy/scenario), so
``bench-engine`` and ``bench-roofline`` compose into one
BENCH_engine.json.

``--json PATH`` additionally writes the structured results of the
``engine*`` targets (events/sec, per-event us, fused-step trace counts,
per-strategy spec hashes) so the perf trajectory is machine-readable and
attributable across PRs.

Scale axis: ``engine_scaled`` measures the 512-client workload
(``BENCH_SCALED_CLIENTS`` overrides, e.g. 2048) on one device;
``engine_sharded`` re-runs it in the same process under a host mesh over
every visible device and records the sharded events/sec next to the
single-device number.  It raises with fewer than two devices.
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import api
from repro.core.fedat import measure_ratio

ROWS: List[str] = []


def emit(name: str, us: float, derived: str):
    row = f"{name},{us:.1f},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def _spec(strategy="fedat", *, classes=2, seed=0, n_clients=40, cpr=8,
          total=120, eval_every=15, codec=None, **kwargs):
    """The bench scenario: 40 clients / 5 tiers, paper delay bands &
    dropout, reduced budget."""
    return api.ExperimentSpec(
        data=api.DataSpec(n_clients=n_clients, classes_per_client=classes,
                          samples_per_client=40, image_hw=8, seed=seed),
        tiers=api.TierSpec(n_tiers=5, clients_per_round=cpr, n_unstable=4),
        strategy=api.StrategySpec(name=strategy, kwargs=dict(kwargs)),
        transport=api.TransportSpec(codec=codec),
        engine=api.EngineSpec(total_updates=total, eval_every=eval_every,
                              local_epochs=2))


def _timed(spec):
    """(metrics, us_per_update); env materialization stays outside the
    clock, but the first run over a fresh env pays the one-off fused-step
    compile inside it (as the seed-era benches did) — the ``engine``
    target is the steady-state number, it warms explicitly."""
    run = api.build(spec)
    t0 = time.perf_counter()
    m = run.run().metrics
    us = (time.perf_counter() - t0) * 1e6
    return m, us / spec.engine.total_updates


_BASE_TOTAL, _BASELINE_TOTAL = 120, 60


def table1_accuracy():
    """Table 1: best accuracy + per-client accuracy variance, per method,
    across non-IID levels."""
    for ncls in (2, 4, 10):  # 10 == iid
        m, us = _timed(_spec("fedat", classes=ncls, total=_BASE_TOTAL))
        emit(f"table1/fedat/cls{ncls}", us,
             f"acc={m.best_acc:.3f};var={m.acc_var[-1]:.5f}")
        for name in ("fedavg", "tifl", "fedasync"):
            m, us = _timed(_spec(name, classes=ncls, total=_BASELINE_TOTAL))
            emit(f"table1/{name}/cls{ncls}", us,
                 f"acc={m.best_acc:.3f};var={m.acc_var[-1]:.5f}")


def table2_comm_cost():
    """Table 2: MB transferred to reach a target accuracy (2-class)."""
    target = 0.45
    for name in ("fedat", "fedavg", "tifl", "fedasync"):
        total = _BASE_TOTAL if name == "fedat" else _BASELINE_TOTAL
        m = api.run_spec(_spec(name, total=total)).metrics
        b = m.bytes_to_accuracy(target)
        emit(f"table2/{name}", 0.0,
             f"mb_to_{target}={'%.1f' % (b/1e6) if b else 'n/a'};"
             f"total_mb={(m.bytes_up[-1]+m.bytes_down[-1])/1e6:.1f}")


def fig2_time_to_accuracy():
    """Fig. 2: simulated wall-clock to target accuracy."""
    target = 0.40
    runs = {}
    for name in ("fedat", "fedavg", "tifl", "fedasync"):
        total = 120 if name in ("fedat", "fedasync") else 60
        runs[name] = api.run_spec(
            _spec(name, seed=1, total=total, eval_every=10)).metrics
    tf = runs["fedat"].time_to_accuracy(target)
    for name, m in runs.items():
        t = m.time_to_accuracy(target)
        rel = (t / tf) if (t and tf) else float("nan")
        emit(f"fig2/{name}", 0.0,
             f"sim_s_to_{target}={'%.0f' % t if t else 'n/a'};"
             f"x_vs_fedat={rel:.2f}")


def fig5_precision_tradeoff():
    """Fig. 5: compression precision vs accuracy + bytes (a spec sweep
    over the strategy's precision kwarg)."""
    results = api.sweep(_spec("fedat", seed=2),
                        {"strategy.kwargs.precision": [3, 4, 6, None]})
    for res in results:
        m = res.metrics
        prec = res.spec.strategy.kwargs["precision"]
        total_mb = (m.bytes_up[-1] + m.bytes_down[-1]) / 1e6
        emit(f"fig5/precision_{prec}", 0.0,
             f"acc={m.best_acc:.3f};total_mb={total_mb:.1f}")


def fig6_weighted_aggregation():
    """Fig. 6: Eq. 3 weighted aggregation vs uniform."""
    mw = api.run_spec(_spec("fedat", seed=3, weighted=True)).metrics
    mu = api.run_spec(_spec("fedat", seed=3, weighted=False)).metrics
    emit("fig6/weighted", 0.0, f"acc={mw.best_acc:.3f}")
    emit("fig6/uniform", 0.0, f"acc={mu.best_acc:.3f}")
    emit("fig6/delta", 0.0, f"impr={(mw.best_acc-mu.best_acc):.3f}")


def fig7_participation():
    """Fig. 7 (appendix B.1): client participation level."""
    for cpr in (2, 8):
        mf = api.run_spec(_spec("fedat", seed=4, cpr=cpr)).metrics
        ma = api.run_spec(
            _spec("fedavg", seed=4, cpr=cpr, total=_BASELINE_TOTAL)).metrics
        emit(f"fig7/k{cpr}", 0.0,
             f"fedat={mf.best_acc:.3f};fedavg={ma.best_acc:.3f}")


def codec():
    """Compression ratio of the faithful polyline codec + the TPU codec."""
    rng = np.random.default_rng(0)
    w = {"w": rng.normal(0, 0.05, 100_000).astype(np.float32)}
    for prec in (3, 4, 6):
        t0 = time.perf_counter()
        r = measure_ratio(w, prec)
        us = (time.perf_counter() - t0) * 1e6
        emit(f"codec/polyline_p{prec}", us, f"ratio_vs_f32={1/r:.2f}x")
    from repro.compress import polyline, quantize
    # vectorized vs scalar-reference polyline encoder
    t0 = time.perf_counter()
    polyline.encode_values(w["w"], 4)
    us_vec = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    polyline.encode_values_ref(w["w"], 4)
    us_ref = (time.perf_counter() - t0) * 1e6
    emit("codec/polyline_encode_100k", us_vec,
         f"speedup_vs_ref={us_ref / us_vec:.1f}x")
    x = jnp.asarray(w["w"])
    for bits in (8, 16):
        c = quantize.compress(x, bits)
        ratio = x.size * 4 / quantize.wire_bytes(c)
        emit(f"codec/quantize_int{bits}", 0.0, f"ratio_vs_f32={ratio:.2f}x")


def codec_e2e():
    """FedAT end-to-end per transport codec (engine + strategy + codec)."""
    for codec in ("none", "polyline:4", "quantize8", "quantize16"):
        m, us = _timed(_spec("fedat", seed=5, total=_BASELINE_TOTAL,
                             codec=codec))
        total_mb = (m.bytes_up[-1] + m.bytes_down[-1]) / 1e6
        emit(f"codec_e2e/fedat_{codec.replace(':', '_')}", us,
             f"acc={m.best_acc:.3f};total_mb={total_mb:.1f}")


#: structured results for ``--json`` (filled by the engine target)
JSON_DOC: Dict[str, Any] = {"bench": "engine", "results": []}


def engine():
    """Engine hot-path throughput: events/sec + per-event us per strategy
    on the 40-client bench env.  One warm run amortizes the single fused
    compile, then a timed run measures the steady state; the executor's
    trace counters document that no shape-driven retraces occurred.  Each
    JSON record carries the spec hash of the timed configuration."""
    for name, n in (("fedat", 120), ("fedavg", 60), ("tifl", 60),
                    ("fedasync", 120)):
        spec = _spec(name, seed=6, total=n)
        warm = spec.with_overrides(
            {"engine.total_updates": max(n // 10, 5)})
        api.build(warm).run()  # warm: compile the fused step once
        run = api.build(spec)
        t0 = time.perf_counter()
        run.run()
        dt = time.perf_counter() - t0
        ev_s = n / dt
        emit(f"engine/{name}", dt / n * 1e6, f"events_per_sec={ev_s:.2f}")
        JSON_DOC["results"].append({
            "strategy": name, "total_updates": n,
            "events_per_sec": round(ev_s, 3),
            "us_per_event": round(dt / n * 1e6, 1),
            "spec_hash": spec.hash(),
        })
    env = api.get_env(_spec("fedat", seed=6))
    JSON_DOC["trace_counts"] = {
        "/".join(map(str, k)): v
        for k, v in env.executor().trace_counts.items()}
    JSON_DOC["spec_hashes"] = {r["strategy"]: r["spec_hash"]
                               for r in JSON_DOC["results"]}


def _scaled_spec(mesh=None):
    """The scale-axis scenario: >= 512 clients, a larger per-round client
    fan-out, reduced budget (the per-event cost is what's measured)."""
    n = int(os.environ.get("BENCH_SCALED_CLIENTS", "512"))
    mesh_spec = api.MeshSpec.from_name(mesh) if mesh else api.MeshSpec()
    return api.ExperimentSpec(
        data=api.DataSpec(n_clients=n, classes_per_client=2,
                          samples_per_client=40, image_hw=8, seed=8),
        tiers=api.TierSpec(n_tiers=5, clients_per_round=32,
                           n_unstable=n // 16),
        strategy=api.StrategySpec(name="fedat"),
        engine=api.EngineSpec(total_updates=12, eval_every=12,
                              local_epochs=1),
        mesh=mesh_spec)


def engine_scaled():
    """Scaled FedAT workload (512+ clients, clients_per_round=32) on one
    device — the measured max-workload point (``engine_sharded`` runs the
    same scenario client-sharded)."""
    _measure_scaled(None)


def _measure_scaled(mesh):
    spec = _scaled_spec(mesh)
    n_updates = spec.engine.total_updates
    warm = spec.with_overrides({"engine.total_updates": 3})
    api.build(warm).run()            # warm: compile the fused step once
    run = api.build(spec)
    t0 = time.perf_counter()
    run.run()
    dt = time.perf_counter() - t0
    env = run.env
    tag = f"scaled_{spec.data.n_clients}" + (f"_{mesh}" if mesh else "")
    emit(f"engine/{tag}", dt / n_updates * 1e6,
         f"events_per_sec={n_updates / dt:.2f};devices={len(jax.devices())}"
         f";data_axis={env.data_axis}")
    JSON_DOC["results"].append({
        "strategy": "fedat", "scenario": tag,
        "n_clients": spec.data.n_clients,
        "clients_per_round": spec.tiers.clients_per_round,
        "mesh": mesh or "single", "n_devices": len(jax.devices()),
        "data_axis": env.data_axis,
        "total_updates": n_updates,
        "events_per_sec": round(n_updates / dt, 3),
        "us_per_event": round(dt / n_updates * 1e6, 1),
        "trace_counts": {"/".join(map(str, k)): v
                         for k, v in env.executor().trace_counts.items()},
        "spec_hash": spec.hash(),
    })


def _lm_spec(codec=None, *, model="tiny_lm", seq_len=16, backend="auto",
             total=24):
    """The federated-LM scenario: tiny_lm (models/registry.py) over
    class-conditional token streams, 24 clients / 3 tiers.  The long-seq
    flash-vs-reference rows pass ``model="tiny_lm_long"``/``seq_len=128``
    and pin ``backend`` explicitly."""
    return api.ExperimentSpec(
        data=api.DataSpec(model=model, n_clients=24,
                          classes_per_client=2, samples_per_client=24,
                          vocab_size=64, seq_len=seq_len,
                          attention_backend=backend, seed=9),
        tiers=api.TierSpec(n_tiers=3, clients_per_round=4, n_unstable=2),
        strategy=api.StrategySpec(name="fedat"),
        transport=api.TransportSpec(codec=codec),
        engine=api.EngineSpec(total_updates=total, eval_every=total // 2,
                              local_epochs=1))


def _run_lm_row(spec, tag, extra=None):
    """Warm + time one federated-LM scenario and append its JSON record
    (spec hash + result hash over the accuracy trajectory); scenarios
    sharing a cached env record only their own trace delta, so every
    record reads "one trace per config" on its own.  Returns the record.
    """
    import hashlib
    n = spec.engine.total_updates
    before = dict(api.get_env(spec).executor().trace_counts)
    warm = spec.with_overrides({"engine.total_updates": 3})
    api.build(warm).run()            # warm: compile the fused step once
    run = api.build(spec)
    t0 = time.perf_counter()
    m = run.run().metrics
    dt = time.perf_counter() - t0
    total_mb = (m.bytes_up[-1] + m.bytes_down[-1]) / 1e6
    emit(f"engine/{tag}", dt / n * 1e6,
         f"events_per_sec={n / dt:.2f};acc={m.best_acc:.3f}"
         f";total_mb={total_mb:.2f}")
    result_hash = hashlib.sha256(
        np.asarray(m.acc, np.float64).tobytes()).hexdigest()[:12]
    rec = {
        "strategy": "fedat", "scenario": tag, "model": spec.data.model,
        "codec": spec.transport.codec or "none",
        "attention_backend": spec.data.attention_backend,
        "seq_len": spec.data.seq_len, "total_updates": n,
        "events_per_sec": round(n / dt, 3),
        "us_per_event": round(dt / n * 1e6, 1),
        "best_acc": round(m.best_acc, 4),
        "bytes_up": m.bytes_up[-1], "bytes_down": m.bytes_down[-1],
        "trace_counts": {
            "/".join(map(str, k)): v - before.get(k, 0)
            for k, v in run.env.executor().trace_counts.items()
            if v - before.get(k, 0)},
        "result_hash": result_hash,
        "spec_hash": spec.hash(),
    }
    rec.update(extra or {})
    JSON_DOC["results"].append(rec)
    return rec


def engine_lm():
    """Federated LM through the registry path: events/sec and
    bytes-on-wire with and without the polyline codec, plus the
    flash-vs-reference attention rows on the long-sequence tiny_lm
    (seq_len 128, where the O(S^2) attention term dominates the client
    step — the short-seq scenario can't separate the backends).  Each
    record carries the spec hash and a result hash so the LM path's
    output is attributable and comparable across PRs."""
    for codec in ("none", "polyline:4"):
        _run_lm_row(_lm_spec(codec), f"lm_{codec.replace(':', '_')}")

    # the attention-backend axis: same long-seq scenario, only the
    # attention path differs; the headline is the events/sec ratio
    total = 8 if SMOKE[0] else 16
    rows = {}
    for backend in ("reference", "flash"):
        spec = _lm_spec(model="tiny_lm_long", seq_len=128,
                        backend=backend, total=total)
        rows[backend] = _run_lm_row(spec, f"lm_long_{backend}")
    speedup = (rows["flash"]["events_per_sec"]
               / rows["reference"]["events_per_sec"])
    emit("engine/lm_long_flash_speedup", 0.0,
         f"x_vs_reference={speedup:.2f}")
    rows["flash"]["speedup_vs_reference"] = round(speedup, 3)


def engine_faults():
    """Fault-plane degradation curve: FedAT on the bench scenario at
    increasing fault pressure — 0 (the zero-fault baseline), 5% client
    churn, and 20% churn + poisoned uplinks + a tier blackout.  Records
    events/sec (the fault plane must not tax the hot loop) and the
    accuracy degradation; the zero-fault row is additionally run twice
    and cross-checked bitwise (trajectory *and* bytes-on-wire), pinning
    the spec-level side of the zero-fault parity contract."""
    total = 20 if SMOKE[0] else 60
    base = _spec("fedat", seed=7, total=total, eval_every=total // 4)
    # windows sit inside the scenario's actual sim-time span (~13-50s of
    # simulated time for 60 updates under the paper delay bands)
    levels = (
        ("faults_0", {}),
        ("faults_5", {"faults.churn_rate": 0.05,
                      "faults.churn_window": [5.0, 45.0],
                      "faults.churn_downtime": 15.0}),
        ("faults_20", {"faults.churn_rate": 0.20,
                       "faults.churn_window": [5.0, 45.0],
                       "faults.churn_downtime": 15.0,
                       "faults.nan_rate": 0.10,
                       "faults.blackouts": 1,
                       "faults.blackout_window": [10.0, 35.0],
                       "faults.blackout_duration": 8.0}),
    )
    for tag, overrides in levels:
        spec = base.with_overrides(overrides) if overrides else base
        warm = spec.with_overrides({"engine.total_updates": 5})
        api.build(warm).run()        # warm: compile the (gated) step once
        run = api.build(spec)
        t0 = time.perf_counter()
        m = run.run().metrics
        dt = time.perf_counter() - t0
        total_mb = (m.bytes_up[-1] + m.bytes_down[-1]) / 1e6
        emit(f"engine/{tag}", dt / total * 1e6,
             f"events_per_sec={total / dt:.2f};acc={m.best_acc:.3f}"
             f";final_acc={m.acc[-1]:.3f};total_mb={total_mb:.1f}")
        rec = {
            "strategy": "fedat", "scenario": tag,
            "total_updates": total,
            "events_per_sec": round(total / dt, 3),
            "us_per_event": round(dt / total * 1e6, 1),
            "best_acc": round(m.best_acc, 4),
            "final_acc": round(m.acc[-1], 4),
            "total_mb": round(total_mb, 3),
            "spec_hash": spec.hash(),
        }
        if tag == "faults_0":
            # the degradation curve's origin doubles as a parity pin
            m2 = api.build(spec).run().metrics
            rec["zero_fault_bitwise"] = (
                m.times == m2.times and m.acc == m2.acc
                and m.bytes_up == m2.bytes_up
                and m.bytes_down == m2.bytes_down)
        JSON_DOC["results"].append(rec)


def _population_spec(n, plane="streaming", total=10):
    """The population-plane scenario: the scaled workload shape
    (clients_per_round=32, 5 tiers) over the indexed population with
    FLGo-style availability/responsiveness processes, at any N."""
    return api.ExperimentSpec(
        data=api.DataSpec(n_clients=n, classes_per_client=2,
                          samples_per_client=24, image_hw=8, seed=8),
        tiers=api.TierSpec(n_tiers=5, clients_per_round=32,
                           n_unstable=max(n // 16, 1)),
        strategy=api.StrategySpec(name="fedat"),
        engine=api.EngineSpec(total_updates=total, eval_every=total,
                              local_epochs=1),
        population=api.PopulationSpec(
            plane=plane, availability="bernoulli:0.9:20",
            responsiveness="lognormal:0.25", eval_clients=64, seed=1))


def engine_population():
    """Population-plane scale axis (DESIGN.md §Population-plane):

    * a parity pin at N=256 — the streaming plane re-run against the
      stacked plane and cross-checked bitwise (trajectory + bytes),
      recorded like ``engine_faults``' zero-fault pin;
    * streaming rows at 1k -> 100k -> 1M clients (100k in ``--smoke``),
      each recording events/sec, the peak data-plane bytes, and the
      flat-memory ratio vs the 1k row (the acceptance bound: within 10%).

    Environments are evicted between rows so a 1M-client population's
    host state doesn't sit under the next row's measurement."""
    # -- parity pin ----------------------------------------------------
    api.clear_env_cache()
    m_stack = api.run_spec(_population_spec(256, plane="stacked")).metrics
    api.clear_env_cache()
    spec = _population_spec(256)
    m_stream = api.run_spec(spec).metrics
    bitwise = (m_stack.times == m_stream.times
               and m_stack.acc == m_stream.acc
               and m_stack.bytes_up == m_stream.bytes_up
               and m_stack.bytes_down == m_stream.bytes_down)
    api.clear_env_cache()
    emit("engine/population_parity_256", 0.0,
         f"stream_bitwise_eq_stacked={bitwise}")
    JSON_DOC["results"].append({
        "strategy": "fedat", "scenario": "population_parity_256",
        "n_clients": 256, "stream_bitwise_eq_stacked": bitwise,
        "spec_hash": spec.hash(),
    })

    # -- scale rows ----------------------------------------------------
    sizes = (1_000, 100_000) if SMOKE[0] else (1_000, 100_000, 1_000_000)
    bytes_1k = None
    for n in sizes:
        spec = _population_spec(n)
        total = spec.engine.total_updates
        warm = spec.with_overrides({"engine.total_updates": 3})
        api.build(warm).run()        # warm: compile the fused step once
        run = api.build(spec)
        t0 = time.perf_counter()
        m = run.run().metrics
        dt = time.perf_counter() - t0
        env = run.env
        peak = env.data_plane_bytes()
        if bytes_1k is None:
            bytes_1k = peak
        ratio = peak / bytes_1k
        tag = f"population_{n}"
        emit(f"engine/{tag}", dt / total * 1e6,
             f"events_per_sec={total / dt:.2f};"
             f"data_plane_mb={peak / 1e6:.2f};flat_vs_1k={ratio:.3f}")
        JSON_DOC["results"].append({
            "strategy": "fedat", "scenario": tag, "n_clients": n,
            "clients_per_round": spec.tiers.clients_per_round,
            "plane": "streaming", "total_updates": total,
            "events_per_sec": round(total / dt, 3),
            "us_per_event": round(dt / total * 1e6, 1),
            "best_acc": round(m.best_acc, 4),
            "data_plane_bytes": int(peak),
            "flat_vs_1k": round(ratio, 4),
            "trace_counts": {"/".join(map(str, k)): v
                             for k, v in env.executor().trace_counts.items()},
            "spec_hash": spec.hash(),
        })
        api.clear_env_cache()   # free the (N,)-sized host state arrays


def _topology_spec(total, lam=0.0, codec=None, seed=9):
    """The topology-plane scenario: 2 regional silos x 2 edges over 32
    clients with WAN delay bands on every link class and a strong
    region skew (silo 1's WAN draws are 4x silo 0's), so the slow silo
    commits genuinely stale Eq. 3 updates — the regime delayed-gradient
    compensation targets.  The update budget is deliberately small: at
    saturation every trajectory converges and the compensation axis
    flattens out."""
    return api.ExperimentSpec(
        data=api.DataSpec(n_clients=32, classes_per_client=2,
                          samples_per_client=24, image_hw=8, seed=seed),
        tiers=api.TierSpec(n_tiers=1, clients_per_round=4, n_unstable=0),
        strategy=api.StrategySpec("fedat"),
        engine=api.EngineSpec(total_updates=total,
                              eval_every=max(total // 4, 1),
                              local_epochs=1),
        topology=api.TopologySpec(
            n_silos=2, edges_per_silo=2,
            delay={"client_edge": (0.5, 1.5), "edge_silo": (1.0, 3.0),
                   "silo_global": (20.0, 60.0)},
            codec=codec or {}, silo_skew=3.0, compensation=lam))


def engine_topology():
    """Topology-plane axis (DESIGN.md §Topology-plane):

    * the degenerate bitwise pin, re-checked on every bench run — a
      1-silo/1-edge zero-delay topology replays the flat FedAT run
      byte-for-byte (trajectory *and* wire bytes);
    * flat vs hierarchical events/sec on the same 32-client workload;
    * the hierarchical row with distinct per-link codecs, recording the
      per-link-class wire bytes (client_edge / edge_silo / silo_global
      are separate ledgers — the WAN hop can be compressed harder);
    * the region-skew accuracy axis: compensation lambda=0 vs 0.8 under
      a 4x-skewed WAN, recording ``comp_beats_uncomp`` (the acceptance
      bound: the compensated run ends at higher final accuracy)."""
    total = 24 if SMOKE[0] else 40

    # -- degenerate bitwise pin ---------------------------------------
    # the same scenario with the topology section dialed back to its
    # defaults (to_config() is None -> the flat engine), and the
    # degenerate *active* topology on top (1 silo, 1 edge, a zero-width
    # delay band keeps the section active without adding any delay)
    flat = _topology_spec(total).with_overrides({
        "topology.n_silos": 1, "topology.edges_per_silo": 1,
        "topology.delay": {}, "topology.silo_skew": 0.0})
    degen = flat.with_overrides({
        "topology.delay.silo_global": [0.0, 0.0]})
    m_flat = api.run_spec(flat).metrics
    m_degen = api.run_spec(degen).metrics
    bitwise = (m_flat.times == m_degen.times and m_flat.acc == m_degen.acc
               and m_flat.bytes_up == m_degen.bytes_up
               and m_flat.bytes_down == m_degen.bytes_down)
    emit("engine/topology_degenerate_pin", 0.0,
         f"degenerate_bitwise_eq_flat={bitwise}")
    JSON_DOC["results"].append({
        "strategy": "fedat", "scenario": "topology_degenerate_pin",
        "degenerate_bitwise_eq_flat": bitwise,
        "spec_hash": degen.hash(),
    })

    # -- flat vs hierarchical events/sec + per-link wire bytes --------
    rows = {}
    for tag, spec in (
        ("topology_flat", flat),
        ("topology_hier", _topology_spec(
            total, codec={"client_edge": "quantize8",
                          "silo_global": "quantize8"})),
    ):
        warm = spec.with_overrides({"engine.total_updates": 5})
        api.build(warm).run()        # warm: compile the step once
        run = api.build(spec)
        t0 = time.perf_counter()
        m = run.run().metrics
        dt = time.perf_counter() - t0
        rec = {
            "strategy": "fedat", "scenario": tag,
            "total_updates": total,
            "events_per_sec": round(total / dt, 3),
            "us_per_event": round(dt / total * 1e6, 1),
            "best_acc": round(m.best_acc, 4),
            "final_acc": round(m.acc[-1], 4),
            "spec_hash": spec.hash(),
        }
        detail = f"events_per_sec={total / dt:.2f};acc={m.best_acc:.3f}"
        if tag == "topology_hier":
            lb = run.strategy.link_bytes
            rec["link_bytes"] = {k: int(v) for k, v in lb.items()}
            detail += ";" + ";".join(
                f"{k}_mb={v / 1e6:.2f}" for k, v in sorted(lb.items()))
        emit(f"engine/{tag}", dt / total * 1e6, detail)
        rows[tag] = rec
        JSON_DOC["results"].append(rec)

    # -- region skew: compensation on vs off --------------------------
    finals = {}
    for lam in (0.0, 0.8):
        spec = _topology_spec(total, lam=lam)
        m = api.run_spec(spec).metrics
        finals[lam] = m.acc[-1]
        tag = f"topology_skew_lam{lam:g}"
        emit(f"engine/{tag}", 0.0,
             f"final_acc={m.acc[-1]:.3f};best_acc={m.best_acc:.3f}")
        JSON_DOC["results"].append({
            "strategy": "fedat", "scenario": tag,
            "total_updates": total, "compensation": lam,
            "final_acc": round(m.acc[-1], 4),
            "best_acc": round(m.best_acc, 4),
            "spec_hash": spec.hash(),
        })
    beats = finals[0.8] > finals[0.0]
    emit("engine/topology_compensation", 0.0,
         f"comp_beats_uncomp={beats}")
    JSON_DOC["results"].append({
        "strategy": "fedat", "scenario": "topology_compensation",
        "final_acc_lam0": round(finals[0.0], 4),
        "final_acc_lam08": round(finals[0.8], 4),
        "comp_beats_uncomp": beats,
    })


def engine_sharded():
    """The scaled scenario client-sharded over every device this process
    sees (``mesh.kind=host``), next to the single-device number when
    ``engine_scaled`` ran first.  One process drives all devices; with
    fewer than two there is nothing to shard over, which is an error."""
    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError(
            f"engine_sharded needs at least 2 devices, this process sees "
            f"{n_dev} ({jax.devices()[0].platform})")
    _measure_scaled("host")
    rec = JSON_DOC["results"][-1]
    single = [r for r in JSON_DOC["results"]
              if r.get("scenario", "").startswith("scaled")
              and r.get("mesh") == "single"]
    rel = (rec["events_per_sec"] / single[-1]["events_per_sec"]
           if single else float("nan"))
    emit(f"engine/{rec['scenario']}_d{rec['n_devices']}",
         rec["us_per_event"],
         f"events_per_sec={rec['events_per_sec']:.2f}"
         f";x_vs_single={rel:.2f}")


#: set by --smoke: reduced sizes/reps for the CI push workflow
SMOKE: List[bool] = [False]


def roofline():
    """Kernel roofline (benchmarks/roofline.kernel_roofline): achieved
    FLOP/s and % of the machine roof per kernel-layer entry point,
    recorded into the JSON doc next to the engine rows.  The roof comes
    from roofline.PEAKS on TPU and is calibrated in place elsewhere, so
    CPU CI tracks a real ceiling."""
    from benchmarks.roofline import kernel_roofline
    doc = kernel_roofline(smoke=SMOKE[0])
    m = doc["machine"]
    for r in doc["kernels"]:
        emit(f"roofline/{r['kernel']}", r["us"],
             f"gflops={r['achieved_gflops']};"
             f"roofline_frac={r['roofline_frac']};"
             f"bw_frac={r['bw_frac']}")
    emit("roofline/machine", 0.0,
         f"backend={m['backend']};peak_gflops={m['peak_gflops']};"
         f"bw_gbs={m['mem_bw_gbs']}")
    JSON_DOC["roofline"] = doc


def kernels():
    """Kernel microbenches for the main-path kernels (codec, flash
    attention); Pallas runs compiled on a TPU and interpreted elsewhere,
    so the derived column names the backend."""
    from repro.kernels import ops
    key = jax.random.PRNGKey(0)
    backend = f"backend={jax.default_backend()}"

    def bench(fn, *args, n=3):
        fn(*args)  # compile
        t0 = time.perf_counter()
        for _ in range(n):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / n * 1e6

    x = jax.random.normal(key, (262_144,))
    us = bench(lambda a: ops.compress(a, 8), x)
    emit("kernels/codec_compress_256k", us, backend)
    q, s = ops.compress(x, 8)
    us = bench(lambda a, b: ops.decompress(a, b, (262_144,)), q, s)
    emit("kernels/codec_decompress_256k", us, backend)

    q4 = jax.random.normal(key, (1, 256, 4, 64))
    k4 = jax.random.normal(key, (1, 256, 4, 64))
    us = bench(lambda a, b, c: ops.flash_attention(a, b, c), q4, k4, k4)
    emit("kernels/flash_attn_256", us, backend)


def trainer():
    """Smoke-scale trainer + server throughput (CPU)."""
    from repro.launch import train as train_mod
    t0 = time.perf_counter()
    train_mod.main(["--arch", "qwen2-7b", "--smoke", "--steps", "6",
                    "--ckpt-dir", "/tmp/bench_ck"])
    us = (time.perf_counter() - t0) / 6 * 1e6
    emit("trainer/single_smoke_step", us, "arch=qwen2-7b-smoke")
    from repro.launch import serve as serve_mod
    t0 = time.perf_counter()
    done = serve_mod.main(["--arch", "rwkv6-3b", "--smoke", "--requests",
                           "4", "--slots", "4", "--prompt-len", "16",
                           "--max-new", "8"])
    dt = time.perf_counter() - t0
    toks = sum(len(r.out) for r in done)
    emit("server/decode_smoke", dt / max(toks, 1) * 1e6,
         f"tokens={toks};arch=rwkv6-smoke")


ALL = {
    "table1": table1_accuracy,
    "table2": table2_comm_cost,
    "fig2": fig2_time_to_accuracy,
    "fig5": fig5_precision_tradeoff,
    "fig6": fig6_weighted_aggregation,
    "fig7": fig7_participation,
    "codec": codec,
    "codec_e2e": codec_e2e,
    "engine": engine,
    "engine_scaled": engine_scaled,
    "engine_lm": engine_lm,
    "engine_faults": engine_faults,
    "engine_population": engine_population,
    "engine_topology": engine_topology,
    "engine_sharded": engine_sharded,
    "roofline": roofline,
    "kernels": kernels,
    "trainer": trainer,
}

#: targets whose structured results --json records
_JSON_TARGETS = ("engine", "engine_scaled", "engine_lm", "engine_faults",
                 "engine_population", "engine_topology", "engine_sharded",
                 "roofline")


def _write_json(path: str) -> None:
    """Write JSON_DOC, merging into an existing document: new records
    replace old ones with the same (strategy, scenario) key and a fresh
    roofline section replaces the old one, so ``bench-engine`` and
    ``bench-roofline`` compose into one BENCH_engine.json instead of
    clobbering each other's rows."""
    doc = JSON_DOC
    if os.path.exists(path):
        with open(path) as f:
            old = json.load(f)
        key = lambda r: (r.get("strategy"), r.get("scenario"))  # noqa: E731
        fresh = {key(r) for r in doc["results"]}
        merged = [r for r in old.get("results", [])
                  if key(r) not in fresh] + doc["results"]
        for k, v in doc.items():
            if k != "results":
                old[k] = v
        old["results"] = merged
        doc = old
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)


def _pop_flag(argv: List[str], flag: str):
    if flag not in argv:
        return argv, None
    i = argv.index(flag)
    if i + 1 >= len(argv):
        sys.exit(f"usage: benchmarks.run [targets...] {flag} VALUE")
    return argv[:i] + argv[i + 2:], argv[i + 1]


def main() -> None:
    argv, json_path = _pop_flag(sys.argv[1:], "--json")
    if "--smoke" in argv:
        argv = [a for a in argv if a != "--smoke"]
        SMOKE[0] = True
    which = argv or [t for t in ALL if t != "engine_sharded"]
    if json_path and not any(t in _JSON_TARGETS for t in which):
        sys.exit(f"--json records the structured targets "
                 f"{_JSON_TARGETS}; add one to the requested targets")
    print("name,us_per_call,derived")
    for name in which:
        ALL[name]()
    if json_path:
        _write_json(json_path)
        print(f"wrote {json_path}", file=sys.stderr)


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
