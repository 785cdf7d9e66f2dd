"""The reference under a model's ``place`` that splits its leaves over
four devices agrees with its run on one device within round-off.  The
four devices are the host's CPU, forced in a child process (a process
that has started JAX cannot change its device count)."""
import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CHIPS = 4
#: rounds of the FedAT reference: (tier, round seed)
ROUNDS = ((0, 11), (1, 12), (0, 13))


def _states():
    """Both runs' server states after ROUNDS, and how the sharded run's
    global leaves were laid out."""
    import functools

    import jax
    import numpy as np
    from jax.sharding import Mesh

    from bench.codecs import polyline
    from bench.fedat_ref import FedATReference
    from bench.models import tiny_lm

    spec = {"data.vocab_size": 64, "data.seq_len": 16}
    params0 = jax.device_get(tiny_lm.init(jax.random.PRNGKey(5), spec))
    rng = np.random.default_rng(7)
    rows = {"x": rng.integers(0, 64, (4, 20, 16), dtype=np.int32),
            "y": np.zeros((4, 20), np.int32),
            "mask": (rng.random((4, 20)) < 0.8).astype(np.float32)}
    hp = {"epochs": 1, "batch": 10, "lr": 1e-3, "lam": 0.4}
    lossy = functools.partial(polyline.lossy, arg="4")
    mesh = Mesh(np.array(jax.devices()[:CHIPS]), ("chips",))
    out, spans = {"start": None}, []
    for name, place in (("single", None),
                        ("sharded", functools.partial(tiny_lm.place,
                                                      mesh=mesh))):
        ref = FedATReference(params0, 3, tiny_lm, lossy, hp, place=place)
        out["start"] = ref.state()
        for m, seed in ROUNDS:
            ref.round(m, np.arange(4), seed, rows)
        if place is not None:
            spans = [len(a.sharding.device_set) for a in
                     jax.tree.leaves(ref.w_global)]
        out[name] = ref.state()
    return out, spans


def _child():
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import numpy as np

    from bench import compare

    assert len(jax.devices()) == CHIPS, jax.devices()
    out, spans = _states()
    one, four = out["single"], out["sharded"]
    diff = np.concatenate([np.abs(one[k] - four[k]).ravel() for k in one])
    print(json.dumps({
        "spans": spans,
        "max_abs": float(diff.max()),
        "share_over_1e-6": float(np.mean(diff > 1e-6)),
        "change_gap": max(compare.leaf_gaps(compare.change_norms(
            out["start"], four, one)).values())}))


def test_a_sharding_place_agrees_with_one_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={CHIPS}")
    out = subprocess.run([sys.executable, os.path.abspath(__file__)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    # every leaf of the sharded run lies on all four devices
    assert got["spans"] and set(got["spans"]) == {CHIPS}, got
    # round-off, and where it meets the codec's rounding (to 1e-4) on a
    # value at a rounding boundary, one step of it on a few elements
    assert got["max_abs"] <= 1.01e-4, got
    assert got["share_over_1e-6"] <= 1e-3, got
    assert got["change_gap"] <= 1e-3, got


if __name__ == "__main__":
    _child()
