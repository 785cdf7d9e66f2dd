"""A plain reference of FedAT's tier round and eval (FedAT Algorithm 1).

One committed global update, as the configuration states it:

  1. downlink: the global model through the link codec's lossy step;
  2. each live client of the round trains locally from that model: Adam
     (b1 0.9, b2 0.999, eps 1e-8, bias-corrected) on the masked mean
     cross-entropy plus the proximal term (lambda/2)||w - w_sent||^2
     (Eq. 5), ``local_epochs`` passes in batches of ``batch_size`` over
     the client's padded row buffer, in the order of a permutation drawn
     per epoch from the round's seed;
  3. uplink: each client model through the codec's lossy step;
  4. Eq. 4: the tier model is the average of the client models weighted
     by their live training rows;
  5. the tier's slot is replaced and its update count raised;
  6. Eq. 3: the global model is the sum over tiers m of
     T_{M+1-m} / T times tier model m.

The round's inputs are what the server decides on the host: the tier,
the live client ids, the round seed and the client rows.  Everything else
is computed here, in float32 with matmuls at ``highest`` precision, or
one step below it for a control (``high``: three bf16 passes, or
bfloat16 throughout).  It imports nothing of the program.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
HIGH = jax.lax.Precision.HIGH


def _tree(fn, *trees):
    return {k: fn(*(t[k] for t in trees)) for k in trees[0]}


@functools.partial(jax.jit, static_argnames=(
    "apply", "lossy", "epochs", "batch", "lr", "lam", "dtype", "precision"))
def _train_client(w_sent, x, y, mask, key, *, apply, lossy, epochs, batch,
                  lr, lam, dtype, precision):
    """Local training of one client; returns its uplinked model."""
    n = x.shape[0]
    steps = max(n // batch, 1)
    x = x.astype(dtype)
    mask = mask.astype(dtype)

    def objective(p, xb, yb, mb):
        logits = apply(p, xb, precision)
        logp = jax.nn.log_softmax(logits.astype(dtype))
        ce = -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
        data = jnp.sum(ce * mb) / jnp.maximum(jnp.sum(mb), 1.0)
        prox = sum(jnp.sum((p[k] - w_sent[k]) ** 2) for k in p)
        return data + (0.5 * lam) * prox

    grad = jax.grad(objective)

    def step(carry, idx):
        p, m, v, t = carry
        g = grad(p, x[idx], y[idx], mask[idx])
        t = t + 1
        m = _tree(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = _tree(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        c1 = (1.0 - 0.9 ** t).astype(dtype)
        c2 = (1.0 - 0.999 ** t).astype(dtype)
        p = _tree(lambda a, b, c: a - lr * (b / c1) / (jnp.sqrt(c / c2) + 1e-8),
                  p, m, v)
        return (p, m, v, t), None

    def epoch(carry, k):
        order = jax.random.permutation(k, n)[:steps * batch]
        carry, _ = jax.lax.scan(step, carry, order.reshape(steps, batch))
        return carry, None

    zeros = _tree(jnp.zeros_like, w_sent)
    carry = (w_sent, zeros, zeros, jnp.zeros((), jnp.float32))
    (p, _, _, _), _ = jax.lax.scan(epoch, carry,
                                   jax.random.split(key, epochs))
    return _tree(lossy, p)


class FedATReference:
    """Server state of the reference (global model, tier models, update
    counts) and the round that advances it."""

    def __init__(self, params0: Dict[str, np.ndarray], n_tiers: int,
                 apply: Callable, lossy: Callable, hp: Dict,
                 dtype=jnp.float32, precision=HIGHEST):
        self.dtype = dtype
        self.precision = precision
        self.apply = apply
        self.lossy = lossy
        self.hp = hp
        w0 = {k: jnp.asarray(v, dtype) for k, v in params0.items()}
        self.w_global = w0
        self.tiers: List[Dict] = [w0] * n_tiers
        self.counts = np.zeros(n_tiers, np.int64)

    def round(self, m: int, ids: np.ndarray, seed: int,
              rows: Dict[str, np.ndarray]) -> None:
        """One committed update of tier ``m`` by the live clients ``ids``
        whose padded rows are ``rows`` (x, y, mask stacked per client)."""
        n = len(ids)
        keys = jax.random.split(jax.random.PRNGKey(seed), n)
        w_sent = _tree(self.lossy, self.w_global)
        live = np.asarray(rows["mask"], np.float64).sum(axis=1)
        weights = live / max(live.sum(), 1.0)
        tier = None
        for i in range(n):
            client = _train_client(
                w_sent, jnp.asarray(rows["x"][i]), jnp.asarray(rows["y"][i]),
                jnp.asarray(rows["mask"][i]), keys[i], apply=self.apply,
                lossy=self.lossy, dtype=self.dtype,
                precision=self.precision, **self.hp)
            part = _tree(lambda a: a * jnp.asarray(weights[i], self.dtype),
                         client)
            tier = part if tier is None else _tree(jnp.add, tier, part)
        self.tiers[m] = tier
        self.counts[m] += 1
        cross = self.counts[::-1] / self.counts.sum()
        glob = None
        for t, c in zip(self.tiers, cross):
            part = _tree(lambda a: a * jnp.asarray(c, self.dtype), t)
            glob = part if glob is None else _tree(jnp.add, glob, part)
        self.w_global = glob

    def state(self) -> Dict[str, np.ndarray]:
        """The server state as named float32 host arrays."""
        out = {f"global/{k}": np.asarray(v, np.float32)
               for k, v in self.w_global.items()}
        for k in self.w_global:
            out[f"tiers/{k}"] = np.stack(
                [np.asarray(t[k], np.float32) for t in self.tiers])
        return out


@functools.partial(jax.jit, static_argnames=("apply", "dtype"))
def _correct_and_loss(params, x, y, mask, *, apply, dtype):
    precision = HIGHEST if dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    p = {k: v.astype(dtype) for k, v in params.items()}
    logits = apply(p, x.astype(dtype), precision)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ce = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    hit = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
    return jnp.sum(hit * mask), jnp.sum(ce * mask), jnp.sum(mask)


def accuracy_and_loss(params, rows: Dict[str, np.ndarray], apply: Callable,
                      dtype=jnp.float32):
    """(accuracy, mean cross-entropy) of ``params`` over every live row of
    ``rows`` (x, y, mask stacked per client), one client at a time."""
    hits = loss = live = 0.0
    for i in range(rows["y"].shape[0]):
        h, l, n = _correct_and_loss(
            params, jnp.asarray(rows["x"][i]), jnp.asarray(rows["y"][i]),
            jnp.asarray(rows["mask"][i], jnp.float32), apply=apply,
            dtype=dtype)
        hits, loss, live = hits + float(h), loss + float(l), live + float(n)
    return hits / max(live, 1.0), loss / max(live, 1.0)
