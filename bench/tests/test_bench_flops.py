import jax
import numpy as np

from bench import flops
from bench.models import cnn, logreg, tiny_lm

CNN = {"data.image_hw": 32, "data.n_classes": 10}


def test_cnn_forward_flops_at_cifar10_shapes():
    convs = (flops.conv2d(32, 32, 3, 32, 3), flops.conv2d(16, 16, 32, 64, 3),
             flops.conv2d(8, 8, 64, 64, 3))
    assert [round(c / 1e6, 2) for c in convs] == [1.77, 9.44, 4.72]
    assert round(flops.dense(4 * 4 * 64, 64) / 1e6, 2) == 0.13
    assert cnn.forward_flops(CNN) == 16_057_600
    assert flops.training(cnn.forward_flops(CNN)) == 3 * 16_057_600


def test_logreg_forward_flops():
    spec = {"data.n_features": 300, "data.n_classes": 2}
    assert logreg.forward_flops(spec) == 1200


def test_cnn_reference_has_the_papers_parameter_count():
    p = cnn.init(jax.random.PRNGKey(0), CNN)
    assert sum(int(np.prod(v.shape)) for v in p.values()) == 122_570
    assert cnn.apply(p, np.zeros((2, 32, 32, 3), np.float32)).shape == (2, 10)


def test_tiny_lm_forward_flops_count_attention():
    spec = {"data.vocab_size": 64, "data.seq_len": 16}
    per_token = (flops.dense(32, 32) * 4          # q, k, v, o projections
                 + flops.dense(32, 96) * 3)       # gate, in, out
    attention = 2 * (2 * 2 * 16) * (16 * 17 // 2)  # scores + values, causal
    head = 16 * flops.dense(32, 64)
    assert tiny_lm.forward_flops(spec) == 16 * per_token + attention + head
    assert flops.causal_attention(16, 2, 16) == attention == 17_408


def test_tiny_lm_reference_has_the_programs_layout():
    from repro.models.registry import DataDims, build_model
    spec = {"data.vocab_size": 64, "data.seq_len": 16}
    mine = tiny_lm.init(jax.random.PRNGKey(0), spec)
    prog = build_model("tiny_lm", DataDims(vocab_size=64, seq_len=16))
    theirs = jax.eval_shape(prog.init_params, jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(theirs)
    assert [a.shape for a in jax.tree.leaves(mine)] == [
        a.shape for a in jax.tree.leaves(theirs)]
    x = np.zeros((2, 16), np.int32)
    assert tiny_lm.apply(mine, x).shape == (2, 16, 64)
