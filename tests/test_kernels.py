"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape).astype(dtype)


CODEC_SHAPES = [(8, 256), (16, 256), (64, 256)]


@pytest.mark.parametrize("nblocks", [8, 16, 64])
@pytest.mark.parametrize("bits", [8, 16])
def test_codec_kernel_matches_ref(nblocks, bits):
    x = _rand(nblocks, (nblocks, 256), jnp.float32) * 5
    from repro.kernels import polyline_codec as pc
    q, s = pc.compress_blocks(x, bits, interpret=True)
    qr, sr = ref.compress_blocks(x, bits)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    xr = pc.decompress_blocks(q, s, interpret=True)
    xref = ref.decompress_blocks(qr, sr)
    np.testing.assert_allclose(np.asarray(xr), np.asarray(xref), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_codec_roundtrip_bound(dtype):
    x = (_rand(3, (2000,), jnp.float32) * 2).astype(dtype)
    q, s = ops.compress(x, 8, interpret=True)
    xr = ops.decompress(q, s, (2000,), interpret=True)
    tol = float(jnp.max(jnp.abs(x.astype(jnp.float32)))) / 127 * 0.51 + 0.01
    assert float(jnp.max(jnp.abs(xr - x.astype(jnp.float32)))) <= tol


ATTN_CASES = [
    # (S, T, H, KV, hd, causal, window)
    (128, 128, 4, 4, 64, True, None),
    (256, 256, 4, 2, 64, True, None),
    (200, 200, 4, 2, 80, True, None),       # unaligned S, hd
    (128, 128, 8, 1, 128, True, None),      # MQA
    (128, 384, 2, 2, 64, False, None),      # cross/bidirectional
    (256, 256, 4, 4, 64, True, 100),        # sliding window
    (512, 512, 2, 2, 64, True, 128),        # window == block
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    S, T, H, KV, hd, causal, window = case
    q = _rand(1, (2, S, H, hd), dtype)
    k = _rand(2, (2, T, KV, hd), dtype)
    v = _rand(3, (2, T, KV, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              interpret=True)
    G = H // KV
    kr = jnp.repeat(k, G, 2).transpose(0, 2, 1, 3).reshape(2 * H, T, hd)
    vr = jnp.repeat(v, G, 2).transpose(0, 2, 1, 3).reshape(2 * H, T, hd)
    qr = q.transpose(0, 2, 1, 3).reshape(2 * H, S, hd)
    oref = ref.attention(qr, kr, vr, causal=causal, window=window)
    oref = oref.reshape(2, H, S, hd).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) -
                                oref.astype(jnp.float32))))
    assert err < tol, err


WKV_CASES = [(2, 64, 16, 32), (3, 100, 16, 32), (1, 256, 32, 64),
             (4, 33, 8, 16)]


@pytest.mark.parametrize("case", WKV_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_matches_ref(case, dtype):
    BH, S, N, chunk = case
    r = _rand(1, (BH, S, N), dtype)
    k = _rand(2, (BH, S, N), dtype)
    v = _rand(3, (BH, S, N), dtype)
    logw = (-jnp.exp(_rand(4, (BH, S, N), jnp.float32))).astype(jnp.float32)
    u = _rand(5, (BH, N), jnp.float32)
    y = ops.wkv6(r, k, v, logw, u, chunk=chunk, interpret=True)
    yr = ref.wkv6(r, k, v, logw, u)
    tol = 5e-4 if dtype == jnp.float32 else 5e-2
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32) -
                                yr.astype(jnp.float32))))
    assert err < tol, err


def test_wkv6_strong_decay_stable():
    # strong decays overflow a naive exp factorization; ours must not
    BH, S, N = 2, 128, 16
    r = _rand(1, (BH, S, N), jnp.float32)
    k = _rand(2, (BH, S, N), jnp.float32)
    v = _rand(3, (BH, S, N), jnp.float32)
    logw = jnp.full((BH, S, N), -8.0)
    u = jnp.zeros((BH, N))
    y = ops.wkv6(r, k, v, logw, u, chunk=64, interpret=True)
    assert bool(jnp.all(jnp.isfinite(y)))


SSD_CASES = [(2, 64, 16, 8, 32), (3, 100, 32, 16, 32), (1, 256, 64, 64, 64)]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_matches_ref(case, dtype):
    BH, S, P, N, chunk = case
    x = _rand(1, (BH, S, P), dtype)
    Bm = _rand(2, (BH, S, N), dtype)
    Cm = _rand(3, (BH, S, N), dtype)
    da = -jnp.abs(_rand(4, (BH, S, 1), jnp.float32))
    y = ops.ssd(x, Bm, Cm, da, chunk=chunk, interpret=True)
    yr = ref.ssd(x, Bm, Cm, da)
    tol = 5e-4 if dtype == jnp.float32 else 1e-1
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32) -
                                yr.astype(jnp.float32))))
    assert err < tol, err


def test_model_rwkv_block_matches_kernel():
    """models/rwkv6.py chunked-jnp path == the Pallas kernel semantics."""
    from repro.models.rwkv6 import _wkv_chunked, _wkv_step
    BH, S, H, N = 1, 64, 2, 16
    key = jax.random.PRNGKey(0)
    r = jax.random.normal(key, (BH, S, H, N))
    k = jax.random.normal(jax.random.PRNGKey(1), (BH, S, H, N))
    v = jax.random.normal(jax.random.PRNGKey(2), (BH, S, H, N))
    lw = -jnp.exp(jax.random.normal(jax.random.PRNGKey(3), (BH, S, H, N)))
    u = jax.random.normal(jax.random.PRNGKey(4), (H, N))
    state0 = jnp.zeros((BH, H, N, N))
    y_model, _ = _wkv_chunked(r, k, v, lw, u, state0)
    # kernel path: flatten (BH, H) -> BH*H
    rf = r.transpose(0, 2, 1, 3).reshape(BH * H, S, N)
    kf = k.transpose(0, 2, 1, 3).reshape(BH * H, S, N)
    vf = v.transpose(0, 2, 1, 3).reshape(BH * H, S, N)
    lwf = lw.transpose(0, 2, 1, 3).reshape(BH * H, S, N)
    uf = jnp.tile(u, (BH, 1))
    y_kern = ops.wkv6(rf, kf, vf, lwf, uf, chunk=32, interpret=True)
    y_kern = y_kern.reshape(BH, H, S, N).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_kern),
                               atol=5e-4)


def test_interpret_mode_follows_backend():
    """``interpret=None`` resolves from the backend: compiled Pallas on a
    TPU, the interpreter elsewhere; an explicit bool wins."""
    assert ops.interpret_mode(None) == (jax.default_backend() != "tpu")
    assert ops.interpret_mode(True) is True
    assert ops.interpret_mode(False) is False


@pytest.mark.parametrize("kernel", ["wkv6", "ssd"])
def test_chunked_scans_refuse_compiled_mode(kernel):
    """The chunked-scan kernels do not compile for TPU v5e; asking for the
    compiled kernel raises instead of silently interpreting."""
    x = _rand(0, (2, 64, 16), jnp.float32)
    with pytest.raises(NotImplementedError, match="does not compile"):
        if kernel == "wkv6":
            ops.wkv6(x, x, x, -jnp.abs(x), x[:, 0], chunk=32,
                     interpret=False)
        else:
            ops.ssd(x, x, x, -jnp.abs(x[..., :1]), chunk=32,
                    interpret=False)
