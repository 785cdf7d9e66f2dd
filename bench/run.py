#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``.  It names a
configuration (``bench/configs/<config>.json``: spec overrides, the
limits of its correctness numbers, what it stands for) and its own file
``bench/workloads/<cell>.json`` (traffic overrides, chips).  The run goes
through the program's normal entry path, ``api.ExperimentSpec`` ->
``api.build`` -> ``run_engine`` -> the FedAT strategy -> the fused round
step and ``SimEnv.evaluate``; the benchmark only wraps those calls.

Set-up (``setup_s``, from process start): the spec from the seed, the
environment, the benchmark's own initial weights (one jitted call from
the seed, handed to the run as ``Run.initial_params``), warm-up of the
eval program and of the per-round key shapes, and the first three
committed updates, which compile the fused step and are the steps the
reference follows.  Of those it copies to the host only the tier slot
each one wrote, and the whole server state after the last.  The window
then runs the same engine for ``--seconds``: it ends after the first
committed update past that time, with the server state drained to the
device.  ``--trace 1`` profiles the window and reports the per-layer
metrics instead of the end-to-end ones.

After the window the reference (``bench/fedat_ref.py``) recomputes the
first three updates, and ``correct`` says whether each compared number
is within its limit.  The model's file (``bench/models/<data.model>.py``)
gives the reference its weights, forward pass and, where it defines
them, its own objective and scoring (``loss``, ``metrics``) and its
placement: a ``place(tree, mesh)`` puts the benchmark's weights and the
reference's state on a mesh over the cell's chips (axis ``chips``);
without one all of it lives on one device.  The last stdout line is the
result; the compared numbers, each with its limit, are also the last
lines on stderr.  Without an accelerator, or with fewer chips than the
cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import compare, flops, peaks, trace_reduce  # noqa: E402
from bench.fedat_ref import (HIGHEST, FedATReference,  # noqa: E402
                             accuracy_and_loss)
from bench.pytree import named  # noqa: E402

#: committed updates in set-up that the reference follows
CHECK_STEPS = 3
#: a traced window lasts at most this long: the CNN cell's trace holds
#: ~150k device op events a second, and the whole run, the reading of
#: the trace with it, has to end within the run's time limit
TRACE_SECONDS = 8.0
#: compile-time and event names JAX reports (jax.monitoring)
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class WindowClosed(Exception):
    """Raised from the engine's event hook to end the window."""


def load_cell(name: str, root: str = ROOT) -> types.SimpleNamespace:
    """The cell's entries in ``BENCHMARK.json`` and its data files."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    with open(os.path.join(root, "bench", "workloads", name + ".json")) as f:
        workload = json.load(f)
    with open(os.path.join(root, "bench", "configs",
                           workload["config"] + ".json")) as f:
        config = json.load(f)

    def listed(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return types.SimpleNamespace(
        name=name, entry=cells[name], workload=workload, config=config,
        end_to_end=listed(bench["end_to_end"]),
        per_layer=listed(bench["per_layer"]))


def derive_seeds(seed: int):
    """(engine seed, weight seed), both below 2**31, from any integer."""
    state = np.random.SeedSequence(abs(int(seed))).generate_state(2)
    return int(state[0] % 2 ** 31), int(state[1] % 2 ** 31)


def flat_spec(spec) -> dict:
    """The resolved spec as dotted keys (``data.image_hw``: 32, ...)."""
    out = {}

    def walk(prefix, d):
        for k, v in d.items():
            if isinstance(v, dict) and k != "kwargs":
                walk(f"{prefix}{k}.", v)
            else:
                out[prefix + k] = v
    walk("", spec.to_dict())
    return out


def codec_module(name: str):
    family, _, arg = str(name).partition(":")
    return importlib.import_module(f"bench.codecs.{family}"), arg


def device_info(chips: int, require_tpu: bool) -> dict:
    """Platform, kind and count as JAX reports them; SystemExit(2) when
    there is no TPU or fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if require_tpu and (info["platform"] != "tpu" or len(devices) < chips):
        print(f"bench: needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {info['platform']} device(s)",
              file=sys.stderr)
        raise SystemExit(2)
    return info


class Recorder:
    """What the wrappers see of the run: the set-up steps, and per
    committed update of the window its time and live rows."""

    def __init__(self, trace: bool):
        self.trace = trace
        self.steps = []
        #: the server state after the last set-up step (named leaves)
        self.final = None
        self.t0 = None
        self.t_end = None
        self.update_times = []
        self.live_rows = []
        self.evals = 0
        #: the program's byte ledger (bytes_up + bytes_down) at the
        #: window's start and end
        self.wire = [None, None]
        self.host = {}
        self.events = {TRACE_EVENT: 0, COMPILE_EVENT: 0}

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.trace or self.t0 is None:
            yield
            return
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.host[name] = self.host.get(name, 0.0) + time.perf_counter() - t

    def on_compile_event(self, event: str, duration: float, **_):
        if self.t0 is not None and self.t_end is None and event in self.events:
            self.events[event] += 1


@contextlib.contextmanager
def wrapped(obj, attr: str, make):
    """Shadow ``obj.attr`` with ``make(original)`` for the block."""
    own = attr in vars(obj)
    original = getattr(obj, attr)
    setattr(obj, attr, make(original))
    try:
        yield
    finally:
        if own:
            setattr(obj, attr, original)
        else:
            delattr(obj, attr)


def _host(tree):
    """``tree`` with every leaf a host array."""
    import jax
    return jax.tree.map(np.asarray, jax.device_get(tree))


def _state(out) -> dict:
    """The server state ``(w_global, tiers)`` as host arrays named by
    leaf: ``global/<leaf>``, ``tiers/<leaf>``."""
    w_global, tiers = _host(out)
    return named({"global": w_global, "tiers": tiers})


@functools.cache
def _slot_fn():
    """A jitted ``(tiers, m) -> tier m`` (one program for every m)."""
    import jax
    return jax.jit(lambda tiers, m: jax.tree.map(
        lambda t: jax.lax.dynamic_index_in_dim(t, m, keepdims=False),
        tiers))


def placement(model, chips: int):
    """The model file's ``place`` over a mesh of the cell's ``chips``
    (axis ``chips``), or None where the file defines none."""
    if not hasattr(model, "place"):
        return None
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:chips]), ("chips",))
    return functools.partial(model.place, mesh=mesh)


def _rows(env, ids):
    """The padded training rows of clients ``ids`` (host arrays)."""
    if env.train is not None:
        return {k: env.train[k][ids] for k in ("x", "y", "mask")}
    return env.population.materialize(np.asarray(ids))


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root: str = ROOT, t_process: float = None,
             require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result line as a dict, plus the
    window's counts under ``window`` (printed on an earlier line)."""
    return report(measure(name, seed, seconds, trace, root, t_process,
                          require_tpu))


def measure(name: str, seed: int, seconds: float, trace: bool,
            root: str = ROOT, t_process: float = None,
            require_tpu: bool = True, matmul: str = None
            ) -> types.SimpleNamespace:
    """Set-up and window of one run; everything the report and the
    correctness check read afterwards.  The program's matmuls run at the
    precision the configuration states (``matmul_precision``, JAX's
    default where it states none), or at ``matmul``."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell = load_cell(name, root)
    chips = int(cell.entry["chips"])
    device = device_info(chips, require_tpu)

    import jax
    jax.config.update("jax_default_matmul_precision",
                      matmul or cell.config.get("matmul_precision"))
    from repro import api
    from repro.core.engine import Outcome

    if trace:
        seconds = min(seconds, TRACE_SECONDS)
    marks = {"start": t_process}
    engine_seed, weight_seed = derive_seeds(seed)
    overrides = dict(cell.config["spec"])
    overrides.update(cell.workload["traffic"])
    overrides["engine.seed"] = engine_seed
    spec = api.ExperimentSpec().with_overrides(overrides)
    flat = flat_spec(spec)
    model = importlib.import_module(f"bench.models.{flat['data.model']}")
    codec, codec_arg = codec_module(flat["transport.codec"])

    run = api.build(spec)
    marks["built"] = time.perf_counter()
    env, strategy = run.env, run.strategy
    ex = env.executor()
    place = placement(model, chips)
    init = jax.jit(lambda k: model.init(k, flat))(
        jax.random.PRNGKey(weight_seed))
    if place is not None:
        init = place(init)
    run.initial_params = init
    env.evaluate(init)
    for n in range(1, ex.K + 1):        # every live-count's key shapes
        jax.block_until_ready(ex._pad_keys(0, n))
    traces0 = dict(ex.trace_counts)
    marks["warmed"] = time.perf_counter()

    rec = Recorder(trace)
    slot = _slot_fn()
    log_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    window_note = None

    def make_round(orig):
        def fedat_round(w_global, tier_models, m, ids, seed_, **kw):
            with rec.span("dispatch"):
                out = orig(w_global, tier_models, m, ids, seed_, **kw)
            if rec.t0 is None:
                # to the host: the tier slot this step wrote, and the
                # whole server state after the last set-up step only
                marks.setdefault("first_step", time.perf_counter())
                rec.steps.append({"m": int(m), "ids": np.array(ids),
                                  "seed": int(seed_),
                                  "tier": _host(slot(out[1], int(m)))})
                if len(rec.steps) == CHECK_STEPS:
                    rec.final = _state(out)
            else:
                rec.live_rows.append(int(env.n_train_all[ids].sum()))
            return out
        return fedat_round

    def make_event(orig):
        def on_event(env_, ctx, now, actor):
            nonlocal window_note
            with rec.span("pop_strategy"):
                out = orig(env_, ctx, now, actor)
            if out is not Outcome.STEP:
                return out
            if rec.t0 is None:
                if len(rec.steps) == CHECK_STEPS:
                    if trace:
                        opts = jax.profiler.ProfileOptions()
                        opts.python_tracer_level = 0
                        opts.host_tracer_level = 1
                        jax.profiler.start_trace(log_dir,
                                                 profiler_options=opts)
                        window_note = jax.profiler.TraceAnnotation(
                            trace_reduce.WINDOW)
                        window_note.__enter__()
                    rec.wire[0] = ctx.bytes_up + ctx.bytes_down
                    rec.t0 = time.perf_counter()
                return out
            t = time.perf_counter()
            rec.update_times.append(t)
            if t - rec.t0 >= seconds:
                jax.block_until_ready((strategy.w_global,
                                       strategy.tier_models))
                rec.t_end = time.perf_counter()
                rec.wire[1] = ctx.bytes_up + ctx.bytes_down
                if window_note is not None:
                    window_note.__exit__(None, None, None)
                raise WindowClosed
            return out
        return on_event

    def make_eval(orig):
        def evaluate(params):
            # evaluate would block on the queued rounds anyway: waiting
            # first keeps that wait out of the eval span
            with rec.span("wait_device"):
                jax.block_until_ready(params)
            with rec.span("eval"):
                acc, var = orig(params)
            if rec.t0 is not None:
                rec.evals += 1
            return acc, var
        return evaluate

    def make_on_eval(orig):
        def on_eval(env_, ctx):
            with rec.span("eval"):
                return orig(env_, ctx)
        return on_eval

    def make_materialize(orig):
        def materialize(ids):
            with rec.span("materialize"):
                return orig(ids)
        return materialize

    jax.monitoring.register_event_duration_secs_listener(
        rec.on_compile_event)
    try:
        with contextlib.ExitStack() as stack:
            stack.enter_context(wrapped(ex, "fedat_round", make_round))
            stack.enter_context(wrapped(strategy, "on_event", make_event))
            stack.enter_context(wrapped(strategy, "on_eval", make_on_eval))
            stack.enter_context(wrapped(env, "evaluate", make_eval))
            if env.population is not None and env.train is None:
                stack.enter_context(wrapped(env.population, "materialize",
                                            make_materialize))
            try:
                run.run()
                raise RuntimeError("the engine stopped before the window "
                                   "closed (event queue empty or update "
                                   "budget spent)")
            except WindowClosed:
                pass
    finally:
        jax.monitoring.unregister_event_duration_listener(
            rec.on_compile_event)
        if trace and rec.t0 is not None:
            jax.profiler.stop_trace()

    # -- the window's numbers ------------------------------------------
    n_updates = len(rec.update_times)
    window_s = rec.t_end - rec.t0
    gaps = np.diff([rec.t0] + rec.update_times)
    leaves = list(named(_host(strategy.w_global)).values())
    end_to_end = {
        "setup_s": rec.t0 - t_process,
        "updates_per_s": n_updates / window_s,
        "update_ms_p95": float(np.percentile(gaps, 95)) * 1e3,
    }
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices())
    strategy.w_global = strategy.tier_models = None
    return types.SimpleNamespace(
        cell=cell, seed=seed, trace=trace, require_tpu=require_tpu,
        chips=chips, device=device, env=env, ex=ex, rec=rec, init=init,
        place=place,
        flat=flat, model=model, codec=codec, codec_arg=codec_arg,
        traces0=traces0, log_dir=log_dir, leaves=leaves,
        end_to_end=end_to_end,
        setup_parts={k: marks[k] - marks["start"]
                     for k in ("built", "warmed", "first_step")})


def report(m: types.SimpleNamespace) -> dict:
    """Correctness, metrics and the result line of a measured run."""
    cell, rec, device = m.cell, m.rec, m.device
    n_updates = len(rec.update_times)
    numbers = check(m)
    verdict = compare.judge(numbers, cell.config["limits"])
    finite = all(np.isfinite(l).all() for l in m.leaves)
    result = {
        "correct": verdict["correct"] and finite,
        "attempted": n_updates,
        "failed": 0 if finite else n_updates,
    }

    # -- metrics ---------------------------------------------------------
    if not m.trace:
        result["metrics"] = {
            e["name"]: {"value": m.end_to_end[e["name"]], "unit": e["unit"]}
            for e in cell.end_to_end}
    else:
        reduced = trace_reduce.reduce(trace_reduce.load_xplane(m.log_dir))
        shutil.rmtree(m.log_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(
            trace=reduced, updates=n_updates, spans=rec.host,
            chips=m.chips, wire_bytes=rec.wire[1] - rec.wire[0],
            peak_flops=(peaks.peak(device["kind"], "bf16_flops")
                        if m.require_tpu else None),
            train_flops=flops.training(m.model.forward_flops(m.flat))
            * m.flat["engine.local_epochs"] * sum(rec.live_rows))
        metrics = {}
        for e in cell.per_layer:
            value = read_metric(e["name"], ctx)
            if value is not None:
                metrics[e["name"]] = {"value": value, "unit": e["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": trace_reduce.top(reduced["ops"]),
            "idle_gaps": trace_reduce.top(reduced["idle"])}
    result["device"] = device
    result["checks"] = verdict["checks"]
    result["window"] = {
        "updates": n_updates, "seconds": rec.t_end - rec.t0,
        "evals": rec.evals, "setup_steps": len(rec.steps),
        "traces_in_window": rec.events[TRACE_EVENT],
        "compiles_in_window": rec.events[COMPILE_EVENT],
        "step_traces": {str(k): v - m.traces0.get(k, 0)
                        for k, v in m.ex.trace_counts.items()},
        "setup_parts_s": m.setup_parts,
        "numbers": numbers,
        "end_to_end": m.end_to_end}
    return result


def reference(m, dtype=None, precision=HIGHEST) -> FedATReference:
    """A fresh reference server from the run's initial weights."""
    import jax.numpy as jnp
    hp = {"epochs": m.flat["engine.local_epochs"],
          "batch": m.flat["engine.batch_size"], "lr": m.flat["engine.lr"],
          "lam": m.flat["engine.prox_lambda"]}
    lossy = functools.partial(m.codec.lossy, arg=m.codec_arg)
    return FedATReference(_host(m.init), m.flat["tiers.n_tiers"],
                          m.model, lossy, hp, dtype or jnp.float32,
                          precision, m.place)


def follow(m, ref: FedATReference, half: bool = False) -> list:
    """Drive ``ref`` through the set-up steps with the program's inputs;
    the tier model it wrote in each.  ``half`` trains only the first half
    of each round's clients (a fault)."""
    tiers = []
    for step in m.rec.steps:
        ids = step["ids"]
        if half:
            ids = ids[:(len(ids) + 1) // 2]
        ref.round(step["m"], ids, step["seed"], _rows(m.env, ids))
        tiers.append(ref.tiers[step["m"]])
    return tiers


def readings(m, state=None, tiers=None) -> dict:
    """What the compared numbers are taken from.  The reference follows
    the set-up steps from the same weights and inputs; by default the
    program's results stand against it, or the server state after the
    last step and the tier model of each step given (a control or a
    fault in the program's place).  Per step: each tier model's mean
    loss (the model's ``metrics``) over the live training rows of the
    step's clients, and its change from the initial weights, leaf by
    leaf (norms)."""
    ref = reference(m)
    start = ref.state()
    ref_tiers = follow(m, ref)
    mine_tiers = ([s["tier"] for s in m.rec.steps]
                  if tiers is None else tiers)
    w0 = {k.split("/", 1)[1]: v for k, v in start.items()
          if k.startswith("global/")}
    steps = []
    for step, mine, theirs in zip(m.rec.steps, mine_tiers, ref_tiers):
        rows = _rows(m.env, step["ids"])
        steps.append({
            "loss": [accuracy_and_loss(mine, rows, m.model)[1],
                     accuracy_and_loss(theirs, rows, m.model)[1]],
            "leaves": compare.change_norms(w0, mine, theirs)})
    final = m.rec.final if state is None else state
    return {"steps": steps,
            "final": compare.change_norms(start, final, ref.state())}


def check(m, state=None, tiers=None) -> dict:
    """The compared numbers (``bench/compare.py``) of the program, or of
    what stands in its place (see ``readings``)."""
    return compare.numbers(readings(m, state, tiers))


def read_metric(name: str, ctx) -> float:
    """The per-layer metric ``name`` from its reader,
    ``bench/metrics/<name>.py`` (None when it finds nothing to read)."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    value = module.read(ctx)
    return None if value is None or not math.isfinite(value) else value


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, holding every program of a run: the fused CNN step carries
    the resident train stack as a constant (~0.8 GB compressed), so a
    cap on entry size would recompile it in every run."""
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    device_info(int(cell.entry["chips"]), require_tpu=True)
    enable_cache()

    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace), t_process=T_PROCESS)
    print(json.dumps({"window": result.pop("window")}), flush=True)
    checks = result.pop("checks")
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
