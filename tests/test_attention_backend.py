"""The attention_backend knob: kernel-layer attention vs. the reference
oracle, from the raw ops up through a federated tiny_lm run.

Covers (ISSUE: flash-attention routing PR)

  * raw parity: ``kops.attention`` blocked vs. Pallas-interpret,
  * model-layer parity: ``full_attention`` flash vs. reference — forward
    and gradients, fp32 and bf16, across chunked/windowed/prefix/bidir
    mask configs,
  * backend resolution (auto/flash/reference x tp) and validation,
  * the federated path: a 2-round tiny_lm run per backend stays close in
    accuracy, traces each fused step exactly once, and the spec field
    changes the provenance hash.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro import kernels as K
from repro.configs.base import ATTENTION_BACKENDS
from repro.configs.tiny_lm import config as tiny_lm_config
from repro.models import attention as A
from repro.models import registry as model_registry


# ---------------------------------------------------------------------------
# raw kernel-layer parity
# ---------------------------------------------------------------------------

def _qkv(key, B=2, S=48, H=4, KV=2, hd=16, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, hd), dtype)
    k = jax.random.normal(kk, (B, S, KV, hd), dtype)
    v = jax.random.normal(kv, (B, S, KV, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("window", [None, 16])
def test_blocked_matches_pallas_interpret(window):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    ob = K.attention(q, k, v, window=window, impl="blocked", block=16)
    op = K.attention(q, k, v, window=window, impl="pallas_interpret")
    assert float(jnp.max(jnp.abs(ob - op))) < 1e-5


@pytest.mark.parametrize("window", [None, 16])
def test_flash_kernel_grad_matches_blocked(window):
    """The Pallas kernel's custom VJP (blocked-attention backward from the
    saved inputs) gives the streaming path's gradients."""
    q, k, v = _qkv(jax.random.PRNGKey(2))
    loss = lambda impl: lambda *a: jnp.sum(  # noqa: E731
        K.attention(*a, window=window, impl=impl, block=16) ** 2)
    gp = jax.grad(loss("pallas_interpret"), argnums=(0, 1, 2))(q, k, v)
    gb = jax.grad(loss("blocked"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gb):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4


def test_attention_impl_validation():
    q, k, v = _qkv(jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match="unknown attention impl"):
        K.attention(q, k, v, impl="cuda")
    with pytest.raises(NotImplementedError, match="prefix"):
        K.attention(q, k, v, impl="pallas_interpret", prefix_len=4)
    assert K.default_attention_impl() in ("pallas", "blocked")
    # auto is callable end to end on whatever backend the tests run on
    out = K.attention(q, k, v, impl="auto")
    assert out.shape == q.shape


# ---------------------------------------------------------------------------
# model-layer parity: full_attention flash vs. reference
# ---------------------------------------------------------------------------

def _attn_params(cfg, key, dtype=jnp.float32):
    p = {}
    for name, s in A.attn_specs(cfg, tp=1).items():
        key, k2 = jax.random.split(key)
        p[name] = (jax.random.normal(k2, s.shape, jnp.float32) * 0.05
                   ).astype(dtype)
    return p


CASES = {
    "single-chunk": dict(),
    "chunked": dict(attn_chunk=16),
    "windowed": dict(swa_window=16, attn_chunk=16),
    "bidir": dict(causal=False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_full_attention_parity_fp32(case):
    cfg = tiny_lm_config().replace(**CASES[case])
    p = _attn_params(cfg, jax.random.PRNGKey(2))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 48, cfg.d_model))
    pos = jnp.arange(48)

    def run(backend, xx):
        c = cfg.replace(attention_backend=backend)
        return A.full_attention(c, p, xx, pos, tp=1)

    ref = run("reference", x)
    fl = run("flash", x)
    assert float(jnp.max(jnp.abs(ref - fl))) < 1e-5
    gr = jax.grad(lambda xx: jnp.sum(run("reference", xx) ** 2))(x)
    gf = jax.grad(lambda xx: jnp.sum(run("flash", xx) ** 2))(x)
    assert float(jnp.max(jnp.abs(gr - gf))) < 1e-4


def test_full_attention_parity_bf16():
    cfg = tiny_lm_config().replace(attn_chunk=16)
    p = _attn_params(cfg, jax.random.PRNGKey(4), dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 48, cfg.d_model),
                          jnp.bfloat16)
    pos = jnp.arange(48)

    def run(backend, xx):
        c = cfg.replace(attention_backend=backend)
        return A.full_attention(c, p, xx, pos, tp=1).astype(jnp.float32)

    ref = run("reference", x)
    fl = run("flash", x)
    # both paths accumulate softmax in fp32; bf16 rounding of inputs and
    # intermediates bounds the divergence at a few ulps of the output scale
    assert float(jnp.max(jnp.abs(ref - fl))) < 3e-2
    gr = jax.grad(lambda xx: jnp.sum(run("reference", xx) ** 2))(x)
    gf = jax.grad(lambda xx: jnp.sum(run("flash", xx) ** 2))(x)
    assert float(jnp.max(jnp.abs((gr - gf).astype(jnp.float32)))) < 1e-1


def test_prefix_lm_parity():
    cfg = tiny_lm_config().replace(attn_chunk=16)
    p = _attn_params(cfg, jax.random.PRNGKey(6))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 48, cfg.d_model))
    pos = jnp.arange(48)
    ref = A.full_attention(cfg.replace(attention_backend="reference"),
                           p, x, pos, tp=1, prefix_len=8)
    fl = A.full_attention(cfg.replace(attention_backend="flash"),
                          p, x, pos, tp=1, prefix_len=8)
    assert float(jnp.max(jnp.abs(ref - fl))) < 1e-5


# ---------------------------------------------------------------------------
# resolution + validation
# ---------------------------------------------------------------------------

def test_backend_resolution():
    cfg = tiny_lm_config()
    assert A.resolve_attention_backend(cfg, tp=1) == "flash"       # auto
    assert A.resolve_attention_backend(
        cfg.replace(attention_backend="reference"), tp=1) == "reference"
    assert A.resolve_attention_backend(
        cfg.replace(attention_backend="flash"), tp=1) == "flash"
    # the TP contract: flash falls back to the reference path (it owns the
    # padded-head / kv_seq sharding story)
    for be in ATTENTION_BACKENDS:
        assert A.resolve_attention_backend(
            cfg.replace(attention_backend=be), tp=2) == "reference"
    with pytest.raises(ValueError, match="unknown attention_backend"):
        A.resolve_attention_backend(
            cfg.replace(attention_backend="fused"), tp=1)


def test_spec_validates_attention_backend():
    with pytest.raises(api.SpecError, match="attention_backend"):
        api.ExperimentSpec().with_overrides(
            {"data.attention_backend": "cuda"}).validate()
    for be in ATTENTION_BACKENDS:
        spec = api.ExperimentSpec().with_overrides(
            {"data.attention_backend": be}).validate()
        assert spec.to_sim_config().attention_backend == be
    # the backend is part of provenance: changing it changes the hash
    a = api.ExperimentSpec()
    b = a.with_overrides({"data.attention_backend": "reference"})
    assert a.hash() != b.hash()


def test_dims_reach_the_bound_model():
    dims = model_registry.DataDims(vocab_size=32, seq_len=12)
    for name in ("tiny_lm", "tiny_lm_long"):
        m = model_registry.build_model(
            name, model_registry.DataDims(
                vocab_size=32, seq_len=12, attention_backend="reference"))
        assert m.name == name
        assert m.batch_shape == (12,)
    # non-attention models ignore the knob
    m = model_registry.build_model("cnn", dims)
    assert m.data_kind == "image"


# ---------------------------------------------------------------------------
# the federated path: 2-round tiny_lm per backend
# ---------------------------------------------------------------------------

def _lm_spec(backend):
    return api.ExperimentSpec().with_overrides({
        "data.model": "tiny_lm", "data.n_clients": 8,
        "data.samples_per_client": 12, "data.vocab_size": 32,
        "data.seq_len": 12, "data.attention_backend": backend,
        "tiers.n_tiers": 2, "tiers.clients_per_round": 3,
        "tiers.n_unstable": 0, "engine.local_epochs": 1,
        "engine.total_updates": 2, "engine.eval_every": 1,
    }).validate()


@pytest.fixture(scope="module")
def fed_runs():
    out = {}
    for be in ("flash", "reference"):
        run = api.build(_lm_spec(be))
        out[be] = (run, run.run())
    return out


def test_federated_run_per_backend(fed_runs):
    for be, (run, res) in fed_runs.items():
        assert np.isfinite(res.metrics.acc).all(), be
        # one trace per fused-step configuration, flash included
        assert all(v == 1 for v in run.env.executor().trace_counts.values())


def test_federated_backends_agree(fed_runs):
    (_, res_f), (_, res_r) = fed_runs["flash"], fed_runs["reference"]
    assert res_f.metrics.rounds == res_r.metrics.rounds
    # identical data/schedule; only the attention math differs, so the
    # 2-round trajectories must agree to numerical-noise level
    np.testing.assert_allclose(np.asarray(res_f.metrics.acc),
                               np.asarray(res_r.metrics.acc),
                               rtol=0, atol=5e-3)
