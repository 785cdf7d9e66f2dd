"""A small dense causal language model, trained on next-token prediction.

Pre-norm decoder layers: token embedding; per layer RMSNorm, causal
grouped-query attention with rotary position embeddings (the rotate-half
form: the first and second half of each head's dimensions are a pair),
a residual, RMSNorm, a SwiGLU feed-forward and a residual; a final
RMSNorm and an untied output head.  Written from those definitions at
the sizes below (the program's ``tiny_lm``: one layer, width 32, 2 heads
of 16, 2 key/value heads, feed-forward 96); the vocabulary and sequence
length come from the spec.  The parameter names and the stacking of
layers on a leading axis are the program's layout, which the benchmark
needs to hand it these weights.

The objective is the next-token cross-entropy averaged over each
sequence's positions, then weighted by the sample mask; ``metrics``
scores next-token accuracy the same way.

Where this departs from the program's forward pass (none of these changes
what is computed beyond rounding):

* every matmul runs at the precision it is given (``highest`` for the
  reference), the program's at the configuration's;
* attention is one causal softmax over the whole sequence with masked
  scores at -inf; the program runs it in query chunks or through its
  blocked flash path with masked scores at -1e9;
* layers are a Python loop, not a scan; there are no sharding
  annotations and no padded heads or vocabulary (one chip has none);
* the logits stay in the parameters' dtype, where the program casts
  them to float32 (the same for float32 parameters).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from bench import flops

N_LAYERS = 1
D_MODEL = 32
N_HEADS = 2
N_KV_HEADS = 2
HEAD_DIM = 16
D_FF = 96
ROPE_THETA = 10_000.0
RMS_EPS = 1e-5


def _dims(spec: dict):
    return int(spec["data.vocab_size"]), int(spec["data.seq_len"])


def init(key, spec: dict) -> dict:
    """Normal weights scaled by 1/sqrt(fan-in), 0.02 for the embedding
    and the head, norms at one; float32."""
    vocab, _ = _dims(spec)
    L, d, q, kv = N_LAYERS, D_MODEL, N_HEADS * HEAD_DIM, N_KV_HEADS * HEAD_DIM
    ks = iter(jax.random.split(key, 9))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def dense(*shape):
        return normal(shape, 1.0 / math.sqrt(shape[-2]))

    return {
        "embed": normal((vocab, d), 0.02),
        "layers": {
            "attn": {"wq": dense(L, d, q), "wk": dense(L, d, kv),
                     "wv": dense(L, d, kv), "wo": dense(L, q, d)},
            "ln1": jnp.ones((L, d), jnp.float32),
            "ln2": jnp.ones((L, d), jnp.float32),
            "ffn": {"w_gate": dense(L, d, D_FF), "w_in": dense(L, d, D_FF),
                    "w_out": dense(L, D_FF, d)}},
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": normal((d, vocab), 0.02),
    }


def _mm(eq, a, b, precision):
    return jnp.einsum(eq, a, b, precision=precision)


def _rms_norm(x, gamma):
    x32 = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                          + RMS_EPS)
    return (x32 * scale * gamma.astype(jnp.float32)).astype(x.dtype)


def _rope(x):
    """x: (B, S, H, hd), rotated by position (rotate-half pairs)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / ROPE_THETA ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _attention(lp, h, precision):
    B, S, _ = h.shape
    mm = functools.partial(_mm, precision=precision)
    q = mm("bsd,dh->bsh", h, lp["wq"]).reshape(B, S, N_HEADS, HEAD_DIM)
    k = mm("bsd,dh->bsh", h, lp["wk"]).reshape(B, S, N_KV_HEADS, HEAD_DIM)
    v = mm("bsd,dh->bsh", h, lp["wv"]).reshape(B, S, N_KV_HEADS, HEAD_DIM)
    q, k = _rope(q), _rope(k)
    group = N_HEADS // N_KV_HEADS   # query head i reads kv head i // group
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = mm("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        / math.sqrt(HEAD_DIM)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = mm("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return mm("bsh,hd->bsd", out.reshape(B, S, N_HEADS * HEAD_DIM), lp["wo"])


def apply(p: dict, x, precision=jax.lax.Precision.HIGHEST):
    """x: (B, S) int32 tokens -> logits (B, S, vocab)."""
    mm = functools.partial(_mm, precision=precision)
    h = jnp.take(p["embed"], x, axis=0)
    for i in range(p["layers"]["ln1"].shape[0]):
        lp = jax.tree.map(lambda a: a[i], p["layers"])
        h = h + _attention(lp["attn"], _rms_norm(h, lp["ln1"]), precision)
        f = lp["ffn"]
        g = _rms_norm(h, lp["ln2"])
        y = jax.nn.silu(mm("bsd,df->bsf", g, f["w_gate"])) \
            * mm("bsd,df->bsf", g, f["w_in"])
        h = h + mm("bsf,fd->bsd", y, f["w_out"])
    return mm("bsd,dv->bsv", _rms_norm(h, p["final_norm"]), p["lm_head"])


def _next_token(logits, x, dtype):
    """Per sequence: mean next-token cross-entropy (log-softmax in
    ``dtype``) and accuracy."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(dtype))
    nll = -jnp.take_along_axis(logp, x[:, 1:, None], axis=-1)[..., 0]
    hit = (jnp.argmax(logits[:, :-1], axis=-1) == x[:, 1:])
    return jnp.mean(nll, axis=-1), jnp.mean(hit.astype(jnp.float32), axis=-1)


def loss(p, x, y, mask, precision, dtype):
    """Masked mean over sequences of the per-sequence next-token
    cross-entropy; ``y`` (a class label) only shaped the data."""
    ce, _ = _next_token(apply(p, x, precision), x, dtype)
    return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def metrics(p, x, y, mask, precision, dtype):
    """(summed next-token accuracy, summed cross-entropy, live) over
    the masked sequences, cross-entropy in float32."""
    ce, acc = _next_token(apply(p, x, precision), x, jnp.float32)
    return jnp.sum(acc * mask), jnp.sum(ce * mask), jnp.sum(mask)


def place(tree, mesh):
    """Each leaf split over the mesh along its last axis that the
    mesh's size divides, replicated where none does."""
    n, axis = mesh.size, mesh.axis_names[0]

    def one(a):
        spec = [None] * a.ndim
        for i in reversed(range(a.ndim)):
            if a.shape[i] % n == 0:
                spec[i] = axis
                break
        return jax.device_put(a, NamedSharding(mesh, PartitionSpec(*spec)))
    return jax.tree.map(one, tree)


def forward_flops(spec: dict) -> int:
    """Operations of one sequence through the forward pass: projections,
    causal attention, feed-forward and head."""
    vocab, seq = _dims(spec)
    q, kv = N_HEADS * HEAD_DIM, N_KV_HEADS * HEAD_DIM
    layer = seq * (flops.dense(D_MODEL, q) + 2 * flops.dense(D_MODEL, kv)
                   + flops.dense(q, D_MODEL) + 3 * flops.dense(D_MODEL, D_FF))
    layer += flops.causal_attention(seq, N_HEADS, HEAD_DIM)
    return N_LAYERS * layer + seq * flops.dense(D_MODEL, vocab)
