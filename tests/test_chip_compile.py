"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

No chip is attached: the TPU compiler builds each program for a v5e that
is only described (``jax.experimental.topologies``), which refuses what
interpret mode accepts — misaligned tiles, more VMEM than a kernel may
use.  Each test asserts the compiled program holds the Pallas kernel
(``tpu_custom_call``), i.e. the kernel is compiled and not interpreted.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and test
collection must be the same in every worker.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import polyline_codec as pc


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip can be written to the
    # persistent cache but never read back: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


#: the paper CNN (models/cnn.py) at CIFAR-10 input: 122,570 parameters,
#: which the codec pads to 480 blocks of 256
CNN_PARAMS = 122_570
CNN_BLOCKS = -(-CNN_PARAMS // (pc.BLOCK * pc.TILE_B)) * pc.TILE_B


def test_cnn_param_count():
    from repro.models import cnn
    p = jax.eval_shape(lambda: cnn.cnn_init(jax.random.PRNGKey(0),
                                            (32, 32, 3), 10))
    assert sum(int(np.prod(l.shape)) for l in jax.tree.leaves(p)) \
        == CNN_PARAMS


@pytest.mark.parametrize("bits", [8, 16])
def test_compress_blocks_compiles_for_v5e(one_chip, bits):
    c = _compile(lambda x: pc.compress_blocks(x, bits),
                 _spec(one_chip, (CNN_BLOCKS, pc.BLOCK)))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("bits", [8, 16])
def test_decompress_blocks_compiles_for_v5e(one_chip, bits):
    qdt = jnp.int8 if bits == 8 else jnp.int16
    c = _compile(pc.decompress_blocks,
                 _spec(one_chip, (CNN_BLOCKS, pc.BLOCK), qdt),
                 _spec(one_chip, (CNN_BLOCKS, 1)))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("B,S,H,KV,hd,dtype", [
    (10, 16, 2, 2, 16, jnp.float32),       # tiny_lm: K=10 clients, seq 16
    (1, 4096, 8, 8, 128, jnp.bfloat16),    # published-width head, 4k seq
], ids=["tiny_lm", "bf16_hd128_s4096"])
def test_flash_attention_compiles_for_v5e(one_chip, B, S, H, KV, hd, dtype):
    q = _spec(one_chip, (B, S, H, hd), dtype)
    kv = _spec(one_chip, (B, S, KV, hd), dtype)
    c = _compile(lambda a, b, v: ops.flash_attention(a, b, v,
                                                     interpret=False),
                 q, kv, kv)
    assert "tpu_custom_call" in c.as_text()


def test_fused_cnn_round_compiles_for_v5e(one_chip):
    """The whole fused FedAT round at the paper's shapes (CNN at 32x32x3,
    K=10, 3 local epochs) with the quantize8 codec compiles for one v5e,
    holds the codec kernel, and fits the chip's 16 GB."""
    from repro import api
    from repro.compress import transport
    spec = api.ExperimentSpec().with_overrides({"data.image_hw": 32})
    env = api.get_env(spec)
    ex = env.executor()
    step = ex._fedat_step(transport.QuantizeCodec(8, interpret=False), True)
    K, M = ex.K, env.tm.n_tiers
    data = {k: _spec(one_chip, (K,) + env.train[k].shape[1:],
                     env.train[k].dtype) for k in ("x", "y", "mask")}
    w = jax.tree.map(lambda l: _spec(one_chip, l.shape, l.dtype),
                     env.params0)
    tiers = jax.tree.map(lambda l: _spec(one_chip, (M,) + l.shape, l.dtype),
                         env.params0)
    c = step.lower(w, tiers, _spec(one_chip, (3,), jnp.int32), data,
                   _spec(one_chip, (K,)), _spec(one_chip, (M,))).compile()
    assert "tpu_custom_call" in c.as_text()
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
