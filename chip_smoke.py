#!/usr/bin/env python3
"""Chip smoke test: FedAT's main path end to end on a TPU.

    python chip_smoke.py             # phases A-C on one chip
    python chip_smoke.py --chips 4   # phase D only: the client-sharded round

One process drives everything, through the entry points a user calls:
``api.ExperimentSpec`` -> ``api.build`` -> engine -> ``RoundExecutor``,
then ``serve.ServeEngine``.

  A  FedAT with the paper's CNN at CIFAR-10 input (32x32x3) and the
     spec's defaults otherwise (100 clients x 60 samples, 5 tiers, K=10,
     3 local epochs, batch 10), 10 committed updates with the paper's
     polyline codec and again with ``quantize8``.  Checks: finite
     parameters, recorded accuracy, one trace per fused step, the
     compiled quantize8 step holds the Pallas kernel (``tpu_custom_call``),
     and one fused round agrees with the same round on the CPU device.
  B  Federated ``tiny_lm`` through the flash kernel for 2 updates, with
     a checkpoint; checks the Pallas call in the step and agreement with
     an ``attention_backend=reference`` run.
  C  The checkpoint loaded by spec hash and served to 4 requests; one
     trace each for prefill, decode and reset, and the first token of
     each request agrees with the model's full forward pass.
  D  (``--chips 4``) Phase A's spec at K=12 (the paper's K=10 does not
     split over 4 chips) for 40 updates, both codecs, on a host mesh over
     4 chips
     against the same spec on one device in this process: event times
     bitwise equal, one round within tolerance, accuracy within 0.1, one
     trace per sharded step.

Seconds printed per phase come from a smoke run, not a benchmark.  The
last line of stdout is ``{"ok": true, "device": {...}}`` only when every
check passed on a TPU; on any other platform, or after any failed check,
the script exits 1 without it.  Under ``JAX_PLATFORMS=cpu`` every phase
still runs (a rehearsal) and the platform check fails at the end.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# the CPU backend is the oracle of phase A: keep it next to the chip
_plats = os.environ.get("JAX_PLATFORMS")
if _plats and "cpu" not in _plats.split(","):
    os.environ["JAX_PLATFORMS"] = _plats + ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import api, serve  # noqa: E402
from repro.compress import transport  # noqa: E402
from repro.core import aggregation  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402

LABEL = "smoke run, not a benchmark"

#: one fused CNN round, chip vs the CPU device (phase A) and four chips
#: vs one (phase D).  The chip runs f32 matmuls at default precision
#: (one bf16 pass, ~3 significant digits per product).  Adam divides
#: each gradient by its own running scale, so a relative error in a
#: small gradient becomes an error of the size of the learning rate,
#: and the round's 18 local steps compound it: on a v5e the round's
#: update differs from the CPU's by 0.15 of its norm while no parameter
#: differs by more than 5e-4.  Bounds: max |diff| <= 2e-3 (the repo's
#: mesh-parity pin, tests/test_mesh_executor.py) and ||diff|| <= 0.5 *
#: ||round update|| (a dropped, doubled or sign-flipped update is >= 1).
ROUND_MAX_ABS = 2e-3
ROUND_REL = 0.5
#: the same round with matmuls at ``highest`` precision (f32 products):
#: what is left is summation order and transcendentals, which Adam
#: amplifies the same way (a v5e gives max |diff| 8e-5, rel 8e-3), so
#: the chip must agree with the CPU 20x (absolute) and 50x (relative)
#: more closely than at default precision.
EXACT_MAX_ABS = 1e-4
EXACT_REL = 1e-2
#: federated tiny_lm, flash kernel vs the reference attention, after 2
#: updates: same default-precision argument, both paths in f32.
LM_MAX_ABS = 5e-3
LM_REL = 0.1
#: accuracy gap over a chaotic multi-update run (the mesh-parity pin)
ACC_GAP = 0.1
#: serving: the served first token's logit may trail the forward pass's
#: maximum by this much (prefill and forward pad the flash kernel
#: differently, so near-ties may break either way)
LOGIT_MARGIN = 1e-2


class Checks:
    """Named pass/fail records, one JSON line each."""

    def __init__(self):
        self.failed = []

    def check(self, phase, name, ok, **detail):
        print(json.dumps({"phase": phase, "check": name, "ok": bool(ok),
                          **detail}, default=float), flush=True)
        if not ok:
            self.failed.append(f"{phase}:{name}")


def _leaves(tree):
    return [np.asarray(x, np.float32) for x in jax.tree.leaves(tree)]


def _diff(a, b, base):
    """(max |a-b|, ||a-b|| / ||b-base||) over a pytree."""
    la, lb, l0 = _leaves(a), _leaves(b), _leaves(base)
    max_abs = max(float(np.max(np.abs(x - y))) for x, y in zip(la, lb))
    num = np.sqrt(sum(float(np.sum((x - y) ** 2)) for x, y in zip(la, lb)))
    den = np.sqrt(sum(float(np.sum((y - z) ** 2)) for y, z in zip(lb, l0)))
    return max_abs, num / max(den, 1e-30)


def _finite(tree):
    return all(np.isfinite(x).all() for x in _leaves(tree))


def _server_state(strategy):
    return strategy.w_global, strategy.tier_models


def _has_kernel(ex, key, strategy):
    """Whether the compiled fused step ``key`` holds a Pallas TPU call.
    Lowers with the live server state; nothing runs, nothing is
    donated, and the trace cache is hit (no retrace)."""
    step = ex._steps[key]
    K, M = ex.K, len(strategy.counts)
    args = (strategy.w_global, strategy.tier_models,
            np.array([0, 0, K], np.int32), np.zeros(K, np.int32),
            np.zeros(K, np.float32), np.zeros(M, np.float32))
    return "tpu_custom_call" in step.lower(*args).compile().as_text()


def _timed_runs(spec, **run_kw):
    """Build and run ``spec`` twice: the first run compiles, the second
    reuses the cached steps.  Returns (run, result, compile_s, steady_s)."""
    t0 = time.perf_counter()
    api.build(spec).run()
    first = time.perf_counter() - t0
    run = api.build(spec)
    t0 = time.perf_counter()
    res = run.run(**run_kw)
    steady = time.perf_counter() - t0
    return run, res, max(first - steady, 0.0), steady


def _cnn_spec(**over):
    return api.ExperimentSpec().with_overrides({
        "data.image_hw": 32, "engine.total_updates": 10,
        "engine.eval_every": 5, **over})


def _round_inputs(env, seed=7):
    """Host copies of one FedAT round's inputs: tier 0's first K clients."""
    ex = env.executor()
    M = env.tm.n_tiers
    ids = np.asarray(env.tm.members[0][:ex.K])
    pid, ns = ex._pad_ids(ids)
    w0 = jax.tree.map(np.asarray, env.params0)
    return {
        "w": w0, "tiers": jax.tree.map(lambda l: np.stack([l] * M), w0),
        "ids": ids, "pid": pid, "w_intra": aggregation.client_weights_host(ns),
        "cw": aggregation.uniform_weights_host(M),
        "ints": np.array([0, seed, len(ids)], np.int32), "seed": seed}


def phase_a(c: Checks):
    timings = {}
    for cname in ("polyline", "quantize8"):
        spec = _cnn_spec(**({"transport.codec": "quantize8"}
                            if cname == "quantize8" else {}))
        run, res, comp, steady = _timed_runs(spec)
        m = res.metrics
        timings[cname] = {"compile_s": comp, "steady_s": steady}
        c.check("A", f"{cname}_params_finite",
                _finite(_server_state(run.strategy)))
        c.check("A", f"{cname}_accuracy_recorded",
                len(m.acc) >= 2 and all(0.0 <= a <= 1.0 for a in m.acc),
                acc=m.acc)
    env = run.env
    ex = env.executor()
    c.check("A", "one_trace_per_fused_step",
            set(ex.trace_counts.values()) == {1},
            traces={str(k): v for k, v in ex.trace_counts.items()})
    c.check("A", "quantize8_step_holds_pallas_kernel",
            _has_kernel(ex, ("fedat", "quantize8", True), run.strategy))

    # one fused round on the chip vs the same inputs on the CPU device
    # (after the trace check: a round fed its batch as data traces the
    # step once more)
    codec = transport.get_codec("polyline:4")
    step = ex._fedat_step(codec, True)
    inp = _round_inputs(env)
    data = {k: env.train[k][inp["pid"]] for k in ("x", "y", "mask")}

    def on(dev, precision="default"):
        put = lambda t: jax.device_put(t, dev)  # noqa: E731
        with jax.default_matmul_precision(precision):
            w, _ = step(put(inp["w"]), put(inp["tiers"]), put(inp["ints"]),
                        put(data), put(inp["w_intra"]), put(inp["cw"]))
        return jax.tree.map(np.asarray, w)

    cpu = on(jax.devices("cpu")[0])
    for name, prec, b_abs, b_rel in (
            ("round_matches_cpu_oracle", "default", ROUND_MAX_ABS, ROUND_REL),
            ("round_matches_cpu_oracle_highest", "highest", EXACT_MAX_ABS,
             EXACT_REL)):
        max_abs, rel = _diff(on(jax.devices()[0], prec), cpu, inp["w"])
        c.check("A", name, max_abs <= b_abs and rel <= b_rel,
                max_abs=max_abs, rel=rel, bound_max_abs=b_abs,
                bound_rel=b_rel)
    print(json.dumps({"phase": "A", "seconds": timings, "label": LABEL}),
          flush=True)


def phase_bc(c: Checks, ckdir: str):
    spec = api.ExperimentSpec().with_overrides({
        "data.model": "tiny_lm", "data.attention_backend": "flash",
        "engine.total_updates": 2, "engine.eval_every": 2})
    run, res, comp, steady = _timed_runs(spec, checkpoint_dir=ckdir)
    ex = run.env.executor()
    c.check("B", "params_finite", _finite(_server_state(run.strategy)))
    c.check("B", "one_trace_per_fused_step",
            set(ex.trace_counts.values()) == {1},
            traces={str(k): v for k, v in ex.trace_counts.items()})
    c.check("B", "flash_step_holds_pallas_kernel",
            _has_kernel(ex, ("fedat", "polyline:4", True), run.strategy))
    ref_spec = spec.with_overrides({"data.attention_backend": "reference"})
    ref = api.build(ref_spec)
    ref_res = ref.run()
    # the tier models hold the trained rounds: early on, Eq. 3 gives the
    # global model the weights of tiers that have not updated yet
    M = run.env.tm.n_tiers
    max_abs, rel = _diff(
        run.strategy.tier_models, ref.strategy.tier_models,
        jax.tree.map(lambda l: np.stack([l] * M), run.env.params0))
    gap = max(abs(a - b) for a, b in zip(res.metrics.acc,
                                         ref_res.metrics.acc))
    c.check("B", "flash_matches_reference",
            max_abs <= LM_MAX_ABS and rel <= LM_REL and gap <= ACC_GAP,
            max_abs=max_abs, rel=rel, acc_gap=gap,
            bound_max_abs=LM_MAX_ABS, bound_rel=LM_REL)
    print(json.dumps({"phase": "B", "seconds": {
        "compile_s": comp, "steady_s": steady}, "label": LABEL}),
        flush=True)

    # C: serve the checkpoint, loaded by the spec hash that wrote it
    t0 = time.perf_counter()
    loaded = serve.load_checkpoint(ckdir, expect_spec=spec)
    cfg = loaded.config
    sspec = serve.ServeSpec(slots=4, max_len=64, prefill_len=16, max_new=8)
    reqs = serve.make_requests(4, 0.0, sspec.prefill_len, sspec.max_new,
                               cfg.vocab_size, seed=0)
    engine = serve.ServeEngine(cfg, loaded.lm_params, sspec)
    done = engine.run(reqs)
    secs = time.perf_counter() - t0
    c.check("C", "loaded_by_spec_hash", loaded.spec_hash == spec.hash(),
            spec_hash=loaded.spec_hash)
    c.check("C", "served_every_request",
            len(done) == 4 and all(
                len(r.out) == sspec.max_new and not r.truncated
                and all(0 <= t < cfg.vocab_size for t in r.out)
                for r in done))
    c.check("C", "one_trace_each",
            engine.trace_counts == {"prefill": 1, "decode": 1, "reset": 1},
            traces=engine.trace_counts)
    worst = 0.0
    for r in done:
        logits = np.asarray(loaded.model.apply(
            loaded.params, np.asarray(r.prompt)[None]))[0, -1]
        worst = max(worst, float(logits.max() - logits[r.out[0]]))
    c.check("C", "first_token_matches_forward", worst <= LOGIT_MARGIN,
            worst_logit_gap=worst, bound=LOGIT_MARGIN)
    print(json.dumps({"phase": "C", "seconds": {"load_and_serve_s": secs},
                      "label": LABEL}), flush=True)


def phase_d(c: Checks):
    # 40 updates: after phase A's 10 the model still predicts the
    # majority class, and accuracies that agree there prove nothing
    base = _cnn_spec(**{"tiers.clients_per_round": 12,
                        "engine.total_updates": 40,
                        "engine.eval_every": 10})
    env0 = api.get_env(base)
    env1 = api.get_env(base.with_overrides({"mesh.kind": "host"}))
    c.check("D", "data_axis_is_4", env1.data_axis == 4,
            data_axis=env1.data_axis)
    inp = _round_inputs(env0)
    seconds = {}
    for cname in ("polyline:4", "quantize8"):
        # one round through the public executor entry, same inputs.
        # quantize8 groups its 256-value blocks per shard, so the sharded
        # round quantizes differently (within a quantization step)
        codec = transport.get_codec(cname)
        out = []
        for env in (env0, env1):
            w, _ = env.executor().fedat_round(
                env.replicate(inp["w"]), env.replicate(inp["tiers"]), 0,
                inp["ids"], inp["seed"], codec=codec, use_prox=True,
                cross_weights=inp["cw"])
            out.append(jax.tree.map(np.asarray, w))
        max_abs, rel = _diff(out[1], out[0], inp["w"])
        c.check("D", f"{cname}_sharded_round_matches_single",
                max_abs <= ROUND_MAX_ABS and rel <= ROUND_REL,
                max_abs=max_abs, rel=rel, bound_max_abs=ROUND_MAX_ABS,
                bound_rel=ROUND_REL)

        spec = base.with_overrides({"transport.codec": cname})
        _, r0, comp0, steady0 = _timed_runs(spec)
        _, r1, comp1, steady1 = _timed_runs(
            spec.with_overrides({"mesh.kind": "host"}))
        m0, m1 = r0.metrics, r1.metrics
        c.check("D", f"{cname}_event_times_bitwise", m0.times == m1.times)
        gap = max(abs(a - b) for a, b in zip(m0.acc, m1.acc))
        c.check("D", f"{cname}_accuracy_within_bound", gap < ACC_GAP,
                acc_gap=gap, acc_single=m0.acc, acc_sharded=m1.acc)
        seconds[cname] = {
            "single": {"compile_s": comp0, "steady_s": steady0},
            "sharded": {"compile_s": comp1, "steady_s": steady1}}
    tc = env1.executor().trace_counts
    c.check("D", "one_trace_per_sharded_step",
            len(tc) == 2 and all("data4" in k for k in tc)
            and set(tc.values()) == {1},
            traces={str(k): v for k, v in tc.items()})
    print(json.dumps({"phase": "D", "seconds": seconds, "label": LABEL}),
          flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the client-sharded phase D")
    args = ap.parse_args()
    cache = enable_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"device": device, "compile_cache": cache,
                      "jax": jax.__version__,
                      "jax_platforms": os.environ.get("JAX_PLATFORMS")}),
          flush=True)
    c = Checks()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as ckdir:
        phases = ([("D", phase_d, ())] if args.chips == 4 else
                  [("A", phase_a, ()), ("BC", phase_bc, (ckdir,))])
        for name, fn, extra in phases:
            try:
                fn(c, *extra)
            except Exception as e:  # noqa: BLE001 - a raising phase failed
                traceback.print_exc()
                c.check(name, "ran", False, error=repr(e)[:500])
    c.check("-", "device_count", device["count"] == args.chips,
            count=device["count"], want=args.chips)
    c.check("-", "platform_is_tpu", device["platform"] == "tpu",
            platform=device["platform"])
    if c.failed:
        print(f"FAILED: {c.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
