import jax
import numpy as np

from bench import flops
from bench.models import cnn, logreg

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
