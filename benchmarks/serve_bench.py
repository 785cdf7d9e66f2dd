"""Serving-plane benchmark: latency under open-loop load, from real
federated checkpoints.

Each row trains a federated LM through the spec API, checkpoints it
(spec-hash sidecar), resolves the checkpoint back through
``repro.serve.load_checkpoint``, and serves a deterministic Poisson
request stream with the continuous-batching engine — so the measured
path is exactly the production path: no params are handed across in
memory.  Reported per row: p50/p95/p99 request latency, TTFT, queueing
delay, tok/s, and the engine's trace counts (the one-trace-per-config
contract, visible in the perf record).

Load levels: a closed burst (``rate=0``, every request queued at t=0 —
max slot pressure) and an open-loop Poisson stream (arrival gaps
independent of service time — the no-coordinated-omission latency
number).  A random-init zoo decoder row (``from_checkpoint: false``)
covers the non-toy cache layouts (GQA + tied embeddings).

  PYTHONPATH=src python -m benchmarks.serve_bench --json BENCH_serve.json
  PYTHONPATH=src python -m benchmarks.serve_bench --smoke   # CI push
"""
from __future__ import annotations

import json
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro import api
from repro.serve import (ServeEngine, ServeSpec, load_checkpoint,
                         make_requests, report)

SMOKE = [False]


def _lm_spec(model: str, seq_len: int, total: int) -> api.ExperimentSpec:
    """The federated training run whose checkpoint gets served."""
    return api.ExperimentSpec().with_overrides({
        "data.model": model, "data.seq_len": seq_len,
        "data.n_clients": 8, "data.samples_per_client": 12,
        "tiers.n_tiers": 2, "tiers.clients_per_round": 2,
        "tiers.n_unstable": 0, "engine.local_epochs": 1,
        "engine.total_updates": total,
        "engine.eval_every": max(total // 2, 1),
    }).validate()


def _serve_row(tag: str, cfg, params, serve_spec: ServeSpec, *,
               rate: float, n_requests: int,
               spec_hash: Optional[str] = None,
               step: Optional[int] = None) -> Dict[str, Any]:
    reqs = make_requests(n_requests, rate, serve_spec.prefill_len,
                         serve_spec.max_new, cfg.vocab_size,
                         seed=serve_spec.seed)
    engine = ServeEngine(cfg, params, serve_spec)
    done = engine.run(reqs)
    rep = report(done)
    rec: Dict[str, Any] = {
        "scenario": tag, "arch": cfg.name, "rate_req_s": rate,
        "slots": serve_spec.slots, "max_new": serve_spec.max_new,
        "from_checkpoint": spec_hash is not None,
        "traces": dict(engine.trace_counts),
    }
    if spec_hash is not None:
        rec.update(spec_hash=spec_hash, step=step)
    rec.update({k: (round(v, 5) if isinstance(v, float) else v)
                for k, v in rep.items()})
    print(f"{tag},rate={rate:g},tok_per_s={rep['tok_per_s']:.1f},"
          f"p50={rep['latency_p50_s']:.3f}s,p95={rep['latency_p95_s']:.3f}s,"
          f"p99={rep['latency_p99_s']:.3f}s", flush=True)
    return rec


def _checkpointed_rows(results: List[Dict[str, Any]]) -> None:
    total = 2 if SMOKE[0] else 6
    n_req = 8 if SMOKE[0] else 24
    max_new = 8 if SMOKE[0] else 16
    rate = 25.0 if SMOKE[0] else 10.0

    with tempfile.TemporaryDirectory() as d:
        spec = _lm_spec("tiny_lm", seq_len=16, total=total)
        t0 = time.perf_counter()
        api.build(spec).run(checkpoint_dir=d)
        loaded = load_checkpoint(d, expect_spec=spec)
        print(f"# tiny_lm trained+checkpointed in "
              f"{time.perf_counter() - t0:.1f}s (spec {loaded.spec_hash})",
              flush=True)
        sspec = ServeSpec(slots=4, max_len=80, prefill_len=16,
                          max_new=max_new)
        # two load levels over the same checkpoint
        for r in (0.0, rate):
            results.append(_serve_row(
                "serve/tiny_lm", loaded.config, loaded.lm_params, sspec,
                rate=r, n_requests=n_req, spec_hash=loaded.spec_hash,
                step=loaded.step))

    with tempfile.TemporaryDirectory() as d:
        spec = _lm_spec("tiny_lm_long", seq_len=128, total=total)
        api.build(spec).run(checkpoint_dir=d)
        loaded = load_checkpoint(d, expect_spec=spec)
        sspec = ServeSpec(slots=4, max_len=128, prefill_len=32,
                          max_new=max_new)
        results.append(_serve_row(
            "serve/tiny_lm_long", loaded.config, loaded.lm_params, sspec,
            rate=rate, n_requests=max(n_req // 2, 4),
            spec_hash=loaded.spec_hash, step=loaded.step))


def _zoo_row(results: List[Dict[str, Any]]) -> None:
    """One zoo decoder (GQA + SWA-free dense stack) at smoke scale,
    random-init: the cache-layout coverage row, not a checkpoint row."""
    from repro.configs.registry import get_smoke_config
    from repro.models import lm

    cfg = get_smoke_config("qwen2-7b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0), 1, jnp.float32)
    sspec = ServeSpec(slots=4, max_len=64, prefill_len=16,
                      max_new=8 if SMOKE[0] else 16)
    results.append(_serve_row("serve/qwen2-7b-smoke", cfg, params, sspec,
                              rate=0.0,
                              n_requests=6 if SMOKE[0] else 12))


def main(argv: Optional[List[str]] = None) -> Dict[str, Any]:
    argv = list(sys.argv[1:] if argv is None else argv)
    json_path = None
    if "--json" in argv:
        i = argv.index("--json")
        json_path = argv[i + 1]
        del argv[i:i + 2]
    if "--smoke" in argv:
        argv.remove("--smoke")
        SMOKE[0] = True
    if argv:
        sys.exit(f"unknown args {argv}; usage: benchmarks.serve_bench "
                 f"[--smoke] [--json PATH]")

    print("scenario,rate,tok_per_s,p50,p95,p99")
    results: List[Dict[str, Any]] = []
    _checkpointed_rows(results)
    _zoo_row(results)
    doc = {"bench": "serve", "smoke": SMOKE[0], "results": results}
    if json_path:
        with open(json_path, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {json_path}", file=sys.stderr)
    return doc


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
