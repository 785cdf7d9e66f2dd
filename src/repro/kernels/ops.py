"""Jit'd public wrappers around the Pallas kernels.

Handles shape massaging (padding to tile multiples, flattening batch/head
dims) and decides interpret mode in one place, :func:`interpret_mode`:
every wrapper takes ``interpret=None`` and resolves it from the backend
the code observes — the compiled kernel on a TPU, the Pallas interpreter
everywhere else.  Tests pass ``interpret=True`` explicitly.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention as fa
from repro.kernels import polyline_codec as codec
from repro.kernels import ref
from repro.kernels import rwkv6_scan
from repro.kernels import ssd as ssd_mod


def _pad_to(x: jax.Array, axis: int, mult: int):
    n = x.shape[axis]
    target = -(-n // mult) * mult
    if target == n:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - n)
    return jnp.pad(x, pad), n


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.  ``None`` (every
    wrapper's default) decides from the backend: compiled on a TPU, so
    the main path there never interprets; interpreted elsewhere, where
    the TPU kernels cannot lower.  An explicit bool wins."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


# --- codec -------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("bits", "interpret"))
def compress(x: jax.Array, bits: int = 8, interpret: Optional[bool] = None):
    """x: any shape -> (q (nb,256) int, scale (nb,1) f32)."""
    flat = x.reshape(-1).astype(jnp.float32)
    tile = codec.BLOCK * codec.TILE_B
    flat, _ = _pad_to(flat, 0, tile)
    blocks = flat.reshape(-1, codec.BLOCK)
    return codec.compress_blocks(blocks, bits,
                                 interpret=interpret_mode(interpret))


@functools.partial(jax.jit, static_argnames=("shape", "interpret"))
def decompress(q, scale, shape: Tuple[int, ...],
               interpret: Optional[bool] = None):
    blocks = codec.decompress_blocks(q, scale,
                                     interpret=interpret_mode(interpret))
    n = 1
    for s in shape:
        n *= s
    return blocks.reshape(-1)[:n].reshape(shape)


# --- attention ---------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "interpret"))
def flash_attention(q, k, v, causal: bool = True,
                    window: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q: (B, S, H, hd); k/v: (B, T, KV, hd) with H % KV == 0 (GQA).
    Returns (B, S, H, hd).

    The forward pass is the Pallas kernel.  The kernel has no backward of
    its own, so the gradient is the VJP of :func:`blocked_attention`
    (same masks, same math), recomputed from the saved inputs."""
    return _flash(q, k, v, causal, window, interpret_mode(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, window, interpret):
    return _flash_forward(q, k, v, causal, window, interpret)


def _flash_fwd(q, k, v, causal, window, interpret):
    return _flash_forward(q, k, v, causal, window, interpret), (q, k, v)


def _flash_bwd(causal, window, interpret, res, g):
    _, vjp = jax.vjp(functools.partial(blocked_attention, causal=causal,
                                       window=window), *res)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _flash_forward(q, k, v, causal, window, interpret):
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    # expand KV heads to query heads, flatten (B, H) -> BH
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    qf, S0 = _pad_to(qf, 1, fa.BQ)
    kf, T0 = _pad_to(kf, 1, fa.BK)
    vf, _ = _pad_to(vf, 1, fa.BK)
    hd_pad = -(-hd // 128) * 128
    if hd_pad != hd:
        qf, _ = _pad_to(qf, 2, 128)
        kf, _ = _pad_to(kf, 2, 128)
        vf, _ = _pad_to(vf, 2, 128)
    out = fa.flash_attention(qf, kf, vf, causal=causal, window=window,
                             interpret=interpret,
                             scale=1.0 / (hd ** 0.5), kv_len=T0)
    out = out[:, :S0, :hd]
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


def blocked_attention(q, k, v, causal: bool = True,
                      window: Optional[int] = None, block: int = 64,
                      prefix_len: int = 0):
    """Flash-style streaming attention in pure jnp (any backend).

    Same (B, S, H, hd) / (B, T, KV, hd) GQA contract and mask semantics
    as :func:`flash_attention`, same O(block * T) working set: queries are
    processed in static blocks and each block only touches the K/V rows it
    can see — the causal upper bound clips at ``(i+1) * block`` and a
    sliding window clips the lower bound — so the (S, T) logits matrix
    never materializes and causal configs do ~half the FLOPs of the naive
    path.  The block loop is unrolled at trace time (shapes are static),
    which keeps one trace per config under jit/vmap.  This is the flash
    backend's fallback wherever the Pallas kernel can't run (CPU/GPU
    hosts, interpret-free tests) — and it is *fast* there, not a stub.
    """
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / (hd ** 0.5)
    C = min(block, S)
    n = -(-S // C)
    out_blocks = []
    for i in range(n):
        s0, s1 = i * C, min((i + 1) * C, S)
        hi = T
        if causal and not prefix_len:
            hi = min(s1, T)
        lo = 0
        if window is not None:
            lo = max(0, s0 + 1 - window)
        qi = q[:, s0:s1].astype(jnp.float32) * scale   # (B, c, H, hd)
        qi = qi.reshape(B, s1 - s0, KV, G, hd)         # kv-major grouping
        ki = k[:, lo:hi].astype(jnp.float32)           # (B, t, KV, hd)
        vi = v[:, lo:hi].astype(jnp.float32)
        logits = jnp.einsum("bckgd,btkd->bckgt", qi, ki)
        # masks are static (numpy, never staged): all-visible blocks skip
        # the where() entirely, so the common causal interior is mask-free
        qpos = np.arange(s0, s1)[:, None]
        kpos = np.arange(lo, hi)[None, :]
        mask = None
        if causal:
            m = qpos >= kpos
            if prefix_len:
                m = m | (kpos < prefix_len)
            mask = m
        if window is not None:
            m = (qpos - kpos) < window
            mask = m if mask is None else (mask & m)
        if mask is not None and not mask.all():
            logits = jnp.where(jnp.asarray(mask)[None, :, None, None, :],
                               logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        o = jnp.einsum("bckgt,btkd->bckgd", probs, vi)
        out_blocks.append(o.reshape(B, s1 - s0, H, hd))
    out = out_blocks[0] if n == 1 else jnp.concatenate(out_blocks, axis=1)
    return out.astype(q.dtype)


def default_attention_impl() -> str:
    """The flash-attention implementation ``attention(impl="auto")``
    resolves to on this backend: the compiled Pallas kernel on TPU, the
    blocked jnp path everywhere else (interpret-mode Pallas is a
    correctness vehicle, never a perf default)."""
    return "pallas" if jax.default_backend() == "tpu" else "blocked"


def attention(q, k, v, causal: bool = True, window: Optional[int] = None,
              impl: str = "auto", block: int = 64, prefix_len: int = 0):
    """One entry point for fast attention: q (B, S, H, hd); k/v
    (B, T, KV, hd) with H % KV == 0.  ``impl`` is ``auto`` (backend
    availability, :func:`default_attention_impl`) | ``pallas`` |
    ``pallas_interpret`` | ``blocked``."""
    if impl == "auto":
        impl = default_attention_impl()
    if impl in ("pallas", "pallas_interpret"):
        if prefix_len:
            raise NotImplementedError(
                "prefix-LM masks need impl='blocked' (the Pallas kernel "
                "only knows causal/window masks)")
        return flash_attention(q, k, v, causal=causal, window=window,
                               interpret=(impl == "pallas_interpret"))
    if impl == "blocked":
        return blocked_attention(q, k, v, causal=causal, window=window,
                                 block=block, prefix_len=prefix_len)
    raise ValueError(f"unknown attention impl {impl!r}; expected "
                     f"auto | pallas | pallas_interpret | blocked")


# --- chunked scans (no model calls these yet) -------------------------------

def _require_interpret(name: str, why: str, interpret: Optional[bool]):
    """The chunked-scan kernels do not compile for TPU v5e, so they run
    only in interpret mode; asking for the compiled kernel raises."""
    if not interpret_mode(interpret):
        raise NotImplementedError(
            f"the {name} Pallas kernel does not compile for TPU ({why}); "
            f"it runs only with interpret=True")


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, logw, u, chunk: int = 64, interpret: Optional[bool] = None):
    """r/k/v/logw: (BH, S, N); u: (BH, N) -> y (BH, S, N)."""
    _require_interpret(
        "wkv6", "the (1, N) block of u breaks the (8, 128) tiling rule",
        interpret)
    S = r.shape[1]
    rp, _ = _pad_to(r, 1, chunk)
    kp, _ = _pad_to(k, 1, chunk)      # k = 0 on padding: no state effect
    vp, _ = _pad_to(v, 1, chunk)
    lp, _ = _pad_to(logw, 1, chunk)   # logw = 0: decay 1 on padding
    y = rwkv6_scan.wkv6(rp, kp, vp, lp, u, chunk=chunk, interpret=True)
    return y[:, :S]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(x, Bm, Cm, da, chunk: int = 64, interpret: Optional[bool] = None):
    """x: (BH, S, P); Bm/Cm: (BH, S, N); da: (BH, S, 1) -> y (BH, S, P)."""
    _require_interpret("ssd", "cumsum has no Pallas TPU lowering",
                       interpret)
    S = x.shape[1]
    xp, _ = _pad_to(x, 1, chunk)
    bp, _ = _pad_to(Bm, 1, chunk)
    cp, _ = _pad_to(Cm, 1, chunk)
    dp, _ = _pad_to(da, 1, chunk)
    y = ssd_mod.ssd_scan(xp, bp, cp, dp, chunk=chunk, interpret=True)
    return y[:, :S]
