"""Roofline analysis: three terms per (arch x shape) cell on the single-pod
production mesh (EXPERIMENTS.md §Roofline).

    compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / ICI link_bw

XLA's cost model counts while-loop bodies ONCE, so whole-graph numbers
under-count scanned layers.  This module therefore *composes* the cell cost:

    total = n_calls_layer * cost(one layer)
          + cost(full model, n_layers=1) - 1 * cost(one layer)   [embed/loss]
          (+ n_apps * cost(shared block) for the zamba2 hybrid)

Per-layer costs at the cell's full sequence length would need the inner
chunk-scans unrolled (prohibitive at 32k+), so each layer is lowered with
unrolled scans at S in {512, 1024, 2048} and fitted to the exact cost basis

    cost(S) = c0 + c1 * S + c2 * S * K(S),   K = min(S, window) else S

which is closed-form for linear-scan (SSM/RWKV/MoE), sliding-window and
full quadratic attention alike; the fit is then evaluated at the cell's
true S.  Decode cells have no inner scans and are lowered directly.

Each component is lowered on the single-pod production mesh with the cell's
real shardings, so per-device numbers compose exactly (verified: SPMD
cost_analysis is per-device).  The FedAT cross-tier term (the compressed
pod collective) is measured separately from the multi-pod dry-run.
"""
from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, Optional

if __name__ == "__main__":  # set BEFORE jax init when run as a script
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import flops as flops_mod
from repro.configs import SHAPES, applicable
from repro.configs.base import ModelConfig
from repro.configs.registry import get_config
from repro.configs.shapes import ShapeConfig
from repro.launch.mesh import make_production_mesh
from repro.models import attention as attn_mod
from repro.models import common, lm, mamba2, rwkv6, transformer
from repro.models.common import PSpec
from repro.runtime import sharding as shd
from repro.runtime.hlo import collective_bytes

FIT_S = (512, 1024, 2048)
METRICS = ("flops", "bytes", "coll_bytes")

#: per-chip peaks keyed by ``jax.devices()[0].device_kind``.  Source:
#: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over 4 links).  A
#: device kind missing here is an error, never a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
#: the chip the analytic production-mesh cells are costed for
TARGET_KIND = "TPU v5 lite"


def peaks(kind: str) -> Dict[str, float]:
    """The peak table row for ``kind`` (see :data:`PEAKS`)."""
    if kind not in PEAKS:
        raise KeyError(f"no peak FLOP/s and bandwidth recorded for device "
                       f"kind {kind!r}; known kinds: {sorted(PEAKS)}")
    return PEAKS[kind]


def _unstack(specs):
    """Drop the leading stacked-layer dim from a spec tree."""
    def f(s: PSpec):
        if s.axes and s.axes[0] == "layers":
            return PSpec(s.shape[1:], s.axes[1:], s.init, s.scale)
        return s
    return jax.tree.map(f, specs, is_leaf=common.is_pspec)


def _cost_of(lowered) -> Dict[str, float]:
    comp = lowered.compile()
    ca = comp.cost_analysis()
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": float(collective_bytes(comp.as_text())),
    }


def _sub_clip(a, b):
    return {k: max(a[k] - b[k], 0.0) for k in METRICS}


def _kfun(cfg: ModelConfig) -> Callable[[float], float]:
    if cfg.swa_window:
        return lambda s: min(s, cfg.swa_window)
    return lambda s: s


def _fit_eval(points: Dict[int, Dict[str, float]], s_target: int,
              K: Callable[[float], float],
              quadratic: bool = True) -> Dict[str, float]:
    """Per-metric basis.  flops/bytes get the quadratic attention term only
    for components that actually contain attention (``quadratic``) — for
    linear-scan layers (SSM/RWKV backbone) and for collective bytes (always
    activation psums + constant weight gathers) a spurious quadratic
    coefficient would explode x(S_target/S_fit)^2 at extrapolation."""
    ss = sorted(points)
    out = {}
    for m in METRICS:
        y = np.array([points[s][m] for s in ss])
        if m == "coll_bytes" or not quadratic:
            A = np.array([[1.0, s] for s in ss])
            basis_t = np.array([1.0, s_target])
        else:
            A = np.array([[1.0, s, s * K(s)] for s in ss])
            basis_t = np.array([1.0, s_target, s_target * K(s_target)])
        coef, *_ = np.linalg.lstsq(A, y, rcond=None)
        coef = np.maximum(coef, 0.0)
        out[m] = float(coef @ basis_t)
    return out


def _layer_params(cfg: ModelConfig, tp: int, which: str = "layers"):
    if cfg.family == "hybrid":
        from repro.models import zamba2 as z
        sp = z.param_specs(cfg, tp)
        specs = _unstack(sp["backbone"]) if which == "layers" else sp["shared"]
    elif cfg.family == "ssm":
        specs = _unstack(rwkv6.layer_specs(cfg, tp, 1))
    else:
        specs = _unstack(transformer.param_specs(cfg, tp)["layers"])
    abstract = common.shapes_from_specs(specs, jnp.bfloat16)
    shardings = common.shardings_from_specs(specs)
    return abstract, shardings


def _x_sharding():
    return shd.logical_sharding(("batch", None, None))


# ---------------------------------------------------------------------------
# raw per-component lowering at explicit (B, S)
# ---------------------------------------------------------------------------

def _raw_layer_cost(cfg: ModelConfig, mesh, kind: str, which: str,
                    B: int, S: int, cache_len: int) -> Dict[str, float]:
    tp = mesh.shape["model"]
    ccfg = cfg.replace(unroll_scans=True)
    lp, lp_sh = _layer_params(ccfg, tp, which)
    Sx = 1 if kind == "decode" else S
    x = jax.ShapeDtypeStruct((B, Sx, cfg.d_model), jnp.bfloat16)
    positions = jnp.arange(Sx, dtype=jnp.int32)

    hybrid_shared = cfg.family == "hybrid" and which == "shared"
    if cfg.family in lm.TRANSFORMER_FAMILIES or hybrid_shared:
        cax = attn_mod.cache_axes(ccfg, tp)
        c_sh = attn_mod.KVCache(
            k=shd.logical_sharding(cax), v=shd.logical_sharding(cax),
            positions=shd.logical_sharding(("cache_batch", cax[1])))
        if hybrid_shared:
            from repro.models.zamba2 import _shared_block
            blk_train = lambda p, xx: _shared_block(
                ccfg, p, xx, positions, tp, "train")[0]
            blk_prefill = lambda p, xx, c: _shared_block(
                ccfg, p, xx, positions, tp, "prefill", attn_mod.KVCache(*c))
            blk_decode = lambda p, xx, po, c: _shared_block(
                ccfg, p, xx, None, tp, "decode", attn_mod.KVCache(*c), po)
        else:
            blk_train = lambda p, xx: transformer._block_train(
                ccfg, tp, 0, xx, positions, p)[0]
            blk_prefill = lambda p, xx, c: transformer._block_prefill(
                ccfg, tp, 0, xx, positions, p, attn_mod.KVCache(*c))
            blk_decode = lambda p, xx, po, c: transformer._block_decode(
                ccfg, tp, xx, po, p, attn_mod.KVCache(*c))
        if kind == "train":
            def fn(p, xx):
                f = jax.checkpoint(blk_train) if cfg.remat else blk_train
                return jnp.sum(f(p, xx).astype(jnp.float32))
            lowered = jax.jit(jax.grad(fn, argnums=(0, 1)),
                              in_shardings=(lp_sh, _x_sharding())
                              ).lower(lp, x)
        elif kind == "prefill":
            cache = jax.eval_shape(
                lambda: attn_mod.init_cache(ccfg, B, S, tp))
            lowered = jax.jit(blk_prefill,
                              in_shardings=(lp_sh, _x_sharding(),
                                            tuple(c_sh))
                              ).lower(lp, x, tuple(cache))
        else:
            cache = jax.eval_shape(
                lambda: attn_mod.init_cache(ccfg, B, cache_len, tp))
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jax.jit(blk_decode,
                              in_shardings=(lp_sh, _x_sharding(), None,
                                            tuple(c_sh))
                              ).lower(lp, x, pos, tuple(cache))
    elif cfg.family == "hybrid":  # mamba backbone layer
        state = jax.eval_shape(lambda: mamba2.init_state(ccfg, B))
        s_sh = mamba2.MambaState(
            conv=shd.logical_sharding(("cache_batch", None, None)),
            h=shd.logical_sharding(("cache_batch", "tp", None, None)))
        single = kind == "decode"
        blk = lambda p, xx, st: mamba2.block(
            ccfg, p, xx, mamba2.MambaState(*st), tp, single)
        lowered = _lower_block_kind(cfg, blk, kind, lp, lp_sh, x,
                                    tuple(state), tuple(s_sh))
    else:  # rwkv6
        state = jax.eval_shape(lambda: rwkv6.init_state(ccfg, B, tp))
        s_sh = rwkv6.RWKVState(
            tshift=shd.logical_sharding(("cache_batch", None)),
            cshift=shd.logical_sharding(("cache_batch", None)),
            wkv=shd.logical_sharding(("cache_batch", "tp", None, None)))
        single = kind == "decode"
        blk = lambda p, xx, st: rwkv6.block(
            ccfg, p, xx, rwkv6.RWKVState(*st), tp, single)
        lowered = _lower_block_kind(cfg, blk, kind, lp, lp_sh, x,
                                    tuple(state), tuple(s_sh))
    return _cost_of(lowered)


def _lower_block_kind(cfg, blk, kind, lp, lp_sh, x, state, s_sh):
    if kind == "train":
        def fn(p, xx, st):
            f = (jax.checkpoint(lambda pp, xxx: blk(pp, xxx, st)[0])
                 if cfg.remat else (lambda pp, xxx: blk(pp, xxx, st)[0]))
            return jnp.sum(f(p, xx).astype(jnp.float32))
        return jax.jit(jax.grad(fn, argnums=(0, 1)),
                       in_shardings=(lp_sh, _x_sharding(), s_sh)
                       ).lower(lp, x, state)
    return jax.jit(blk, in_shardings=(lp_sh, _x_sharding(), s_sh)
                   ).lower(lp, x, state)


def _raw_full_cost(cfg: ModelConfig, mesh, kind: str, B: int, S: int,
                   cache_len: int) -> Dict[str, float]:
    """Whole model with n_layers=1 (trip-1 loops counted correctly)."""
    tp = mesh.shape["model"]
    overrides = {"n_layers": 1, "unroll_scans": True}
    if cfg.family == "hybrid":
        overrides["attn_every"] = 1
    ccfg = cfg.replace(**overrides)
    params = lm.abstract_params(ccfg, tp, jnp.bfloat16)
    p_sh = jax.tree.map(lambda a: shd.logical_sharding(a),
                        lm.param_axes(ccfg, tp),
                        is_leaf=lambda l: isinstance(l, tuple))
    shp = ShapeConfig("fit", S, B, kind)
    is_ax = lambda l: isinstance(l, tuple) and all(
        x is None or isinstance(x, str) for x in l)
    if kind == "train":
        batch = lm.input_specs(ccfg, shp)
        b_sh = {k: shd.logical_sharding(a)
                for k, a in lm.input_axes(ccfg, shp).items()}
        fn = jax.grad(lambda p, b: lm.loss_fn(ccfg, p, b, tp)[0])
        lowered = jax.jit(fn, in_shardings=(p_sh, b_sh)).lower(params, batch)
    elif kind == "prefill":
        cache = lm.abstract_cache(ccfg, B, S, tp)
        c_sh = jax.tree.map(lambda a: shd.logical_sharding(a),
                            lm.cache_axes_tree(ccfg, tp), is_leaf=is_ax)
        batch = lm.input_specs(ccfg, shp)
        b_sh = {k: shd.logical_sharding(a)
                for k, a in lm.input_axes(ccfg, shp).items()}
        fn = lambda p, b, c: lm.serve_prefill(ccfg, p, b, tp, c)
        lowered = jax.jit(fn, in_shardings=(p_sh, b_sh, c_sh)
                          ).lower(params, batch, cache)
    else:
        cache = lm.abstract_cache(ccfg, B, cache_len, tp)
        c_sh = jax.tree.map(lambda a: shd.logical_sharding(a),
                            lm.cache_axes_tree(ccfg, tp), is_leaf=is_ax)
        toks = jax.ShapeDtypeStruct((B,), jnp.int32)
        t_sh = shd.logical_sharding(("batch",))
        fn = lambda p, t, po, c: lm.serve_step(ccfg, p, t, po, tp, c)
        lowered = jax.jit(fn, in_shardings=(p_sh, t_sh, None, c_sh)
                          ).lower(params, toks,
                                  jax.ShapeDtypeStruct((), jnp.int32), cache)
    return _cost_of(lowered)


# ---------------------------------------------------------------------------
# cell composition + roofline terms
# ---------------------------------------------------------------------------

def composed_cell_cost(arch: str, shape_name: str,
                       overrides: Optional[dict] = None,
                       rules_override: Optional[dict] = None
                       ) -> Dict[str, Any]:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    if not applicable(cfg, shape):
        return {"skipped": True, "arch": arch, "shape": shape_name}
    mesh = make_production_mesh(multi_pod=False)
    dp = mesh.shape.get("data", 1)
    rules = dict(rules_override or {})
    if shape.global_batch < dp:
        rules.update({"batch": None, "cache_batch": None})
    rules = rules or None
    kind = shape.kind
    B = shape.global_batch
    if kind == "train" and cfg.microbatch:
        B = max(B // cfg.microbatch, 1)
    K = _kfun(cfg)
    S = shape.seq_len
    napps = cfg.n_layers // cfg.attn_every if cfg.family == "hybrid" else 0

    with mesh, shd.use_mesh(mesh, rules):
        if kind == "decode":
            lcost = _raw_layer_cost(cfg, mesh, kind, "layers", B, 1, S)
            c1 = _raw_full_cost(cfg, mesh, kind, B, 1, S)
            scost = (_raw_layer_cost(cfg, mesh, kind, "shared", B, 1, S)
                     if cfg.family == "hybrid" else None)
        else:
            pts, spts, fpts = {}, {}, {}
            for s_i in FIT_S:
                pts[s_i] = _raw_layer_cost(cfg, mesh, kind, "layers",
                                           B, s_i, s_i)
                if cfg.family == "hybrid":
                    spts[s_i] = _raw_layer_cost(cfg, mesh, kind, "shared",
                                                B, s_i, s_i)
                fpts[s_i] = _raw_full_cost(cfg, mesh, kind, B, s_i, s_i)
            # quadratic-in-S cost only where attention lives: transformer
            # layers and the zamba2 shared block; mamba/rwkv scans are linear
            layer_quad = cfg.family in lm.TRANSFORMER_FAMILIES
            lcost = _fit_eval(pts, S, K, quadratic=layer_quad)
            scost = _fit_eval(spts, S, K, quadratic=True) \
                if cfg.family == "hybrid" else None
            top_pts = {s: _sub_clip(
                fpts[s], pts[s] if not spts else
                {m: pts[s][m] + spts[s][m] for m in METRICS})
                for s in FIT_S}
            c1 = None

        if kind == "decode":
            if scost is not None:
                top = _sub_clip(_sub_clip(c1, lcost), scost)
            else:
                top = _sub_clip(c1, lcost)
        else:
            top = _fit_eval(top_pts, S, K, quadratic=False)

        total = {m: top[m] + cfg.n_layers * lcost[m] +
                 (napps * scost[m] if scost else 0.0) for m in METRICS}
        if kind == "train" and cfg.microbatch:
            total = {k: v * cfg.microbatch for k, v in total.items()}
            per_dev_params = cfg.param_count() / mesh.size
            total["flops"] += 10 * per_dev_params     # AdamW update
            total["bytes"] += 20 * per_dev_params
    return {"arch": arch, "shape": shape_name, "kind": kind,
            "per_layer": lcost, "per_shared": scost, "top": top,
            "total": total, "n_devices": mesh.size}


def roofline_terms(cell: Dict[str, Any], cfg: ModelConfig,
                   shape: ShapeConfig) -> Dict[str, Any]:
    t = cell["total"]
    chip = peaks(TARGET_KIND)
    compute_s = t["flops"] / chip["flops"]
    memory_s = t["bytes"] / chip["hbm_bw"]
    coll_s = t["coll_bytes"] / chip["ici_bw"]
    dominant = max(("compute", compute_s), ("memory", memory_s),
                   ("collective", coll_s), key=lambda kv: kv[1])[0]
    mf = flops_mod.model_flops(cfg, shape)
    hlo_global = t["flops"] * cell["n_devices"]
    bound = max(compute_s, memory_s, coll_s)
    return {
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": coll_s, "dominant": dominant,
        "model_flops": mf, "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "attn_flops": flops_mod.attention_flops(cfg, shape),
        # roofline fraction: useful model FLOP/s at the bound vs chip peak
        "roofline_frac": (mf / cell["n_devices"] / chip["flops"]) / bound
        if bound else 0.0,
        "step_time_bound_s": bound,
    }


def analyze(arch: str, shape_name: str, overrides=None,
            rules_override=None) -> Dict[str, Any]:
    cell = composed_cell_cost(arch, shape_name, overrides, rules_override)
    if cell.get("skipped"):
        return cell
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    out = dict(cell)
    out.update(roofline_terms(cell, cfg, SHAPES[shape_name]))
    return out


# ---------------------------------------------------------------------------
# kernel roofline: measured achieved FLOP/s per kernel vs the machine roof
# ---------------------------------------------------------------------------
# The arch x shape cells above are *analytic* (lowered costs on the
# production mesh, never executed).  The kernel roofline is *measured*:
# each kernel-layer entry point runs on this host and its achieved
# FLOP/s is pinned against the classic ceiling min(peak, AI * bw) — peak
# and bandwidth from the PEAKS table on TPU, calibrated in place on
# anything else (a big matmul and a big stream, so CPU CI numbers are a
# fraction of a *real* roof, not of a TPU constant they can never hit).

def _calibrate_machine(reps: int = 3):
    """(peak FLOP/s, memory bytes/s) for the backend the bench runs on."""
    if jax.default_backend() == "tpu":
        chip = peaks(jax.devices()[0].device_kind)
        return chip["flops"], chip["hbm_bw"]
    n = 1024
    a = jnp.ones((n, n), jnp.float32)
    mm = jax.jit(lambda x: x @ x)
    jax.block_until_ready(mm(a))
    best = min(_timed_call(mm, (a,)) for _ in range(reps))
    peak = 2.0 * n ** 3 / best
    big = jnp.ones((16 * 1024 * 1024,), jnp.float32)   # 64 MB stream
    add = jax.jit(lambda x: x + 1.0)
    jax.block_until_ready(add(big))
    best = min(_timed_call(add, (big,)) for _ in range(reps))
    bw = 2.0 * big.nbytes / best                       # read + write
    return peak, bw


def _timed_call(fn, args) -> float:
    import time
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


def kernel_roofline(smoke: bool = False):
    """Measure the main-path kernel entry points (attention, codec)
    against the machine roof.  The chunked-scan kernels (``wkv6``,
    ``ssd``) are left out: no model calls them and they do not compile
    for TPU v5e.

    Returns ``{"machine": {...}, "kernels": [row, ...]}`` where each row
    has the kernel's HLO flops/bytes (cost_analysis of the exact lowered
    call), measured best-of-N wall time, achieved FLOP/s and GB/s, and
    its fraction of the roofline ceiling ``min(peak, AI * bw)`` (compute
    kernels) / of the bandwidth roof (streaming kernels read the
    ``bw_frac`` column).  ``smoke`` halves sizes and reps for CI.
    """
    from repro.kernels import ops, ref
    key = jax.random.PRNGKey(0)
    S = 128 if smoke else 256
    reps = 2 if smoke else 5
    peak, bw = _calibrate_machine(reps=2 if smoke else 3)

    B, H, KV, hd = 2, 4, 2, 64
    kq, kk, kv2 = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(kk, (B, S, KV, hd), jnp.float32)
    v = jax.random.normal(kv2, (B, S, KV, hd), jnp.float32)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = jnp.repeat(k, H // KV, axis=2).transpose(0, 2, 1, 3) \
        .reshape(B * H, S, hd)

    flat = jax.random.normal(key, (262_144,), jnp.float32)
    cq, cs = ops.compress(flat, 8)

    impl = ops.default_attention_impl()
    cases = [
        # the flash backend exactly as models/attention routes it here
        (f"attention_flash[{impl}]",
         jax.jit(lambda a, b, c: ops.attention(a, b, c, causal=True)),
         (q, k, v)),
        # the naive materialized oracle: the contrast row
        ("attention_reference",
         jax.jit(lambda a, b, c: ref.attention(a, b, c, causal=True)),
         (qf, kf, kf)),
        ("codec_compress", jax.jit(lambda a: ops.compress(a, 8)), (flat,)),
        ("codec_decompress",
         jax.jit(lambda a, b: ops.decompress(a, b, (262_144,))), (cq, cs)),
    ]

    rows = []
    for name, fn, args in cases:
        cost = _cost_of(fn.lower(*args))
        jax.block_until_ready(fn(*args))   # compile outside the clock
        dt = min(_timed_call(fn, args) for _ in range(reps))
        flops, nbytes = cost["flops"], cost["bytes"]
        ai = flops / nbytes if nbytes else 0.0
        ceiling = min(peak, ai * bw) if ai else peak
        achieved = flops / dt
        rows.append({
            "kernel": name, "seq_len": S,
            "us": round(dt * 1e6, 1),
            "flops": flops, "bytes": nbytes,
            "arith_intensity": round(ai, 3),
            "achieved_gflops": round(achieved / 1e9, 3),
            "achieved_gbs": round(nbytes / dt / 1e9, 3),
            "roofline_frac": round(achieved / ceiling, 4) if ceiling
            else 0.0,
            "bw_frac": round(nbytes / dt / bw, 4) if bw else 0.0,
        })
    return {
        "machine": {
            "backend": jax.default_backend(),
            "peak_gflops": round(peak / 1e9, 2),
            "mem_bw_gbs": round(bw / 1e9, 2),
            "calibrated": jax.default_backend() != "tpu",
            # the bw roof is a DRAM stream; kernels whose working set
            # fits in cache can legitimately exceed frac 1.0 on CPU
            "note": "min(peak, AI*bw) ceiling; cache-resident kernels "
                    "may exceed 1.0 on calibrated (non-TPU) hosts",
        },
        "kernels": rows,
    }


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--out", default="experiments/roofline.json")
    args = ap.parse_args()
    from repro.configs.registry import ARCH_IDS
    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    results = []
    for a in archs:
        for s in shapes:
            try:
                r = analyze(a, s)
            except Exception as e:  # noqa: BLE001
                import traceback
                traceback.print_exc()
                r = {"arch": a, "shape": s, "failed": repr(e)[:200]}
            results.append(r)
            if "dominant" in r:
                print(f"[roofline] {a:22s} {s:12s} "
                      f"C={r['compute_s']*1e3:9.2f}ms "
                      f"M={r['memory_s']*1e3:9.2f}ms "
                      f"N={r['collective_s']*1e3:9.2f}ms "
                      f"dom={r['dominant']:10s} "
                      f"useful={r['useful_ratio']:.3f} "
                      f"roofline={r['roofline_frac']:.3f}", flush=True)
            else:
                print(f"[roofline] {a:22s} {s:12s} "
                      f"{'skip' if r.get('skipped') else 'FAILED'}",
                      flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
