"""A plain reference of FedAT's tier round and eval (FedAT Algorithm 1).

One committed global update, as the configuration states it:

  1. downlink: the global model through the link codec's lossy step;
  2. each live client of the round trains locally from that model: Adam
     (b1 0.9, b2 0.999, eps 1e-8, bias-corrected) on the model's masked
     mean objective plus the proximal term (lambda/2)||w - w_sent||^2
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

The model is its file under ``bench/models/``, and parameters are any
pytree of arrays.  The objective is the file's ``loss(p, x, y, mask,
precision, dtype)`` (the masked mean that local training minimises) and
the score its ``metrics(p, x, y, mask, precision, dtype)`` -> ``(hits,
loss_sum, live)``; a file that defines neither is scored as a classifier
(``classification_loss``, ``classification_metrics``: the cross-entropy
of ``apply``'s logits against class labels).  Floating inputs reach the
objective in the reference's dtype, integer inputs (tokens) as they are.
Where the harness gives a ``place`` (a model file's ``place`` over the
cell's chips), the server state lives where ``place`` puts it, and each
client's training follows it; otherwise all of it is on one device.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.pytree import named

HIGHEST = jax.lax.Precision.HIGHEST
HIGH = jax.lax.Precision.HIGH


def classification_loss(apply, p, x, y, mask, precision, dtype):
    """Masked mean cross-entropy of ``apply``'s logits on class labels."""
    logits = apply(p, x, precision)
    logp = jax.nn.log_softmax(logits.astype(dtype))
    ce = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def classification_metrics(apply, p, x, y, mask, precision, dtype):
    """(correct, summed cross-entropy, live) over masked rows."""
    logits = apply(p, x.astype(dtype), precision)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    ce = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    hit = (jnp.argmax(logits, axis=-1) == y).astype(jnp.float32)
    return jnp.sum(hit * mask), jnp.sum(ce * mask), jnp.sum(mask)


@functools.partial(jax.jit, static_argnames=(
    "model", "lossy", "epochs", "batch", "lr", "lam", "dtype", "precision"))
def _train_client(w_sent, x, y, mask, key, *, model, lossy, epochs, batch,
                  lr, lam, dtype, precision):
    """Local training of one client; returns its uplinked model."""
    n = x.shape[0]
    steps = max(n // batch, 1)
    if jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(dtype)
    mask = mask.astype(dtype)
    loss = (getattr(model, "loss", None)
            or functools.partial(classification_loss, model.apply))

    def total(p, xb, yb, mb):
        data = loss(p, xb, yb, mb, precision, dtype)
        prox = sum(jnp.sum((a - b) ** 2) for a, b in zip(
            jax.tree.leaves(p), jax.tree.leaves(w_sent)))
        return data + (0.5 * lam) * prox

    grad = jax.grad(total)

    def step(carry, idx):
        p, m, v, t = carry
        g = grad(p, x[idx], y[idx], mask[idx])
        t = t + 1
        m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
        v = jax.tree.map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
        c1 = (1.0 - 0.9 ** t).astype(dtype)
        c2 = (1.0 - 0.999 ** t).astype(dtype)
        p = jax.tree.map(
            lambda a, b, c: a - lr * (b / c1) / (jnp.sqrt(c / c2) + 1e-8),
            p, m, v)
        return (p, m, v, t), None

    def epoch(carry, k):
        order = jax.random.permutation(k, n)[:steps * batch]
        carry, _ = jax.lax.scan(step, carry, order.reshape(steps, batch))
        return carry, None

    zeros = jax.tree.map(jnp.zeros_like, w_sent)
    carry = (w_sent, zeros, zeros, jnp.zeros((), jnp.float32))
    (p, _, _, _), _ = jax.lax.scan(epoch, carry,
                                   jax.random.split(key, epochs))
    return jax.tree.map(lossy, p)


class FedATReference:
    """Server state of the reference (global model, tier models, update
    counts) and the round that advances it."""

    def __init__(self, params0, n_tiers: int, model, lossy: Callable,
                 hp: Dict, dtype=jnp.float32, precision=HIGHEST,
                 place: Optional[Callable] = None):
        self.dtype = dtype
        self.precision = precision
        self.model = model
        self.lossy = lossy
        self.hp = hp
        w0 = jax.tree.map(lambda v: jnp.asarray(v, dtype), params0)
        self.w_global = place(w0) if place is not None else w0
        self.tiers: List = [self.w_global] * n_tiers
        self.counts = np.zeros(n_tiers, np.int64)

    def round(self, m: int, ids: np.ndarray, seed: int,
              rows: Dict[str, np.ndarray]) -> None:
        """One committed update of tier ``m`` by the live clients ``ids``
        whose padded rows are ``rows`` (x, y, mask stacked per client)."""
        n = len(ids)
        keys = jax.random.split(jax.random.PRNGKey(seed), n)
        w_sent = jax.tree.map(self.lossy, self.w_global)
        live = np.asarray(rows["mask"], np.float64).sum(axis=1)
        weights = live / max(live.sum(), 1.0)
        tier = None
        for i in range(n):
            client = _train_client(
                w_sent, jnp.asarray(rows["x"][i]), jnp.asarray(rows["y"][i]),
                jnp.asarray(rows["mask"][i]), keys[i], model=self.model,
                lossy=self.lossy, dtype=self.dtype,
                precision=self.precision, **self.hp)
            part = jax.tree.map(
                lambda a: a * jnp.asarray(weights[i], self.dtype), client)
            tier = part if tier is None else jax.tree.map(jnp.add, tier,
                                                          part)
        self.tiers[m] = tier
        self.counts[m] += 1
        cross = self.counts[::-1] / self.counts.sum()
        glob = None
        for t, c in zip(self.tiers, cross):
            part = jax.tree.map(lambda a: a * jnp.asarray(c, self.dtype), t)
            glob = part if glob is None else jax.tree.map(jnp.add, glob,
                                                          part)
        self.w_global = glob

    def state(self) -> Dict[str, np.ndarray]:
        """The server state as float32 host arrays named by leaf
        (``global/<leaf>``, ``tiers/<leaf>`` stacked over tiers)."""
        host = lambda a: np.asarray(a, np.float32)  # noqa: E731
        return named({"global": jax.tree.map(host, self.w_global),
                      "tiers": jax.tree.map(
                          lambda *t: np.stack([host(a) for a in t]),
                          *self.tiers)})


@functools.partial(jax.jit, static_argnames=("model", "dtype"))
def _correct_and_loss(params, x, y, mask, *, model, dtype):
    precision = HIGHEST if dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    p = jax.tree.map(lambda v: v.astype(dtype), params)
    metrics = (getattr(model, "metrics", None)
               or functools.partial(classification_metrics, model.apply))
    return metrics(p, x, y, mask, precision, dtype)


def accuracy_and_loss(params, rows: Dict[str, np.ndarray], model,
                      dtype=jnp.float32):
    """(accuracy, mean loss) of ``params`` over every live row of
    ``rows`` (x, y, mask stacked per client), one client at a time."""
    hits = loss = live = 0.0
    for i in range(rows["y"].shape[0]):
        h, l, n = _correct_and_loss(
            params, jnp.asarray(rows["x"][i]), jnp.asarray(rows["y"][i]),
            jnp.asarray(rows["mask"][i], jnp.float32), model=model,
            dtype=dtype)
        hits, loss, live = hits + float(h), loss + float(l), live + float(n)
    return hits / max(live, 1.0), loss / max(live, 1.0)
