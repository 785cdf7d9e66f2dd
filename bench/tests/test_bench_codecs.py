"""The benchmark's own lossy step of each link codec, against the
value the program's receiver decodes, to the last bit of float32
(one divides by 10**p, the other multiplies by its reciprocal)."""
import numpy as np
import pytest

from bench.codecs import polyline
from repro.compress import transport


@pytest.mark.parametrize("precision", [3, 4])
def test_polyline_lossy_matches_the_programs_decode(precision):
    rng = np.random.default_rng(0)
    leaves = [rng.normal(0, s, n).astype(np.float32)
              for s, n in ((0.05, 4000), (1.0, 333), (3e-5, 64))]
    codec = transport.get_codec(f"polyline:{precision}")
    for leaf in leaves:
        np.testing.assert_allclose(
            np.asarray(polyline.lossy(leaf, str(precision))),
            np.asarray(codec.lossy(leaf)), rtol=3e-7, atol=0)


def test_polyline_lossy_rounds_to_its_decimals():
    x = np.array([0.123456, -0.00004, 2.5], np.float32)
    np.testing.assert_allclose(np.asarray(polyline.lossy(x, "4")),
                               [0.1235, -0.0, 2.5], atol=1e-7)
