"""Fused round executor: fixed-shape compile-cache behaviour and the
zero-weight padding contract (DESIGN.md §Perf).

The executor's trace counters increment every time a fused step's Python
body is traced, so they measure compiles directly: a fixed-shape step must
trace exactly once per (strategy, codec, prox) configuration no matter how
dropout shrinks the per-event client sample.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.compress import transport
from repro.core import aggregation
from repro.core.baselines import BaselineConfig, run_fedasync, run_fedavg, \
    run_tifl
from repro.core.executor import _round_keys, pack_round, unpack_round
from repro.core.fedat import FedATConfig, fake_polyline, run_fedat
from repro.core.simulation import SimConfig, SimEnv

K = 4                                   # the env's clients_per_round


@pytest.fixture(scope="module")
def env():
    return SimEnv(SimConfig(n_clients=12, n_tiers=3, samples_per_client=20,
                            classes_per_client=2, image_hw=8,
                            clients_per_round=K, local_epochs=1,
                            n_unstable=2))


def _bitwise_equal(a, b):
    return all(bool((np.asarray(x) == np.asarray(y)).all())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


# ---------------------------------------------------------------------------
# compile-cache regression: one trace per configuration, across shapes
# ---------------------------------------------------------------------------

def test_fedat_step_compiles_once_across_sample_sizes(env):
    """Full and dropout-shrunken samples reuse one compiled step."""
    ex = env.executor()
    codec = transport.get_codec("polyline:4")
    M = env.tm.n_tiers
    key = ("fedat", codec.name, True)
    before = ex.trace_counts.get(key, 0)
    w = jax.tree.map(jnp.array, env.params0)
    tms = jax.tree.map(lambda l: jnp.stack([l] * M), env.params0)
    cw = aggregation.uniform_weights(M)
    for ids in (np.arange(4), np.arange(3), np.arange(2), np.asarray([7])):
        w, tms = ex.fedat_round(w, tms, 0, ids.astype(np.int32), 1,
                                codec=codec, use_prox=True, cross_weights=cw)
    assert ex.trace_counts[key] - before == 1


def test_engine_run_with_dropouts_never_retraces(env):
    """A full engine run whose events include dropout-shrunken samples
    compiles each fused step exactly once (zero shape-driven retraces)."""
    ex = env.executor()
    before = dict(ex.trace_counts)
    # long enough to pass the earliest dropout times (uniform(50, 400))
    run_fedat(env, FedATConfig(total_updates=40, eval_every=20))
    run_fedavg(env, BaselineConfig(total_updates=12, eval_every=6))
    run_tifl(env, BaselineConfig(total_updates=12, eval_every=6))
    run_fedasync(env, BaselineConfig(total_updates=20, eval_every=10))
    for key, count in ex.trace_counts.items():
        assert count - before.get(key, 0) <= 1, (key, count)
    # repeated runs over the same env reuse the compile cache entirely
    snapshot = dict(ex.trace_counts)
    run_fedat(env, FedATConfig(total_updates=6, eval_every=6))
    run_fedavg(env, BaselineConfig(total_updates=4, eval_every=4))
    assert ex.trace_counts == snapshot


def test_distinct_codecs_compile_distinct_steps(env):
    ex = env.executor()
    run_fedat(env, FedATConfig(total_updates=2, eval_every=2,
                               codec="quantize8"))
    run_fedat(env, FedATConfig(total_updates=2, eval_every=2, codec="none"))
    assert ex.trace_counts[("fedat", "quantize8", True)] == 1
    assert ex.trace_counts[("fedat", "none", True)] == 1


# ---------------------------------------------------------------------------
# one host buffer per FedAT round
# ---------------------------------------------------------------------------

def _plane_spec(plane):
    """A dropout-laden FedAT run: unstable clients drop out past
    uniform(50, 400), the indexed planes add availability and completion
    processes, and ``gated`` adds the fault plane's validation gate."""
    over = {"data.n_clients": 48, "data.samples_per_client": 20,
            "data.image_hw": 8, "tiers.n_tiers": 3,
            "tiers.clients_per_round": K, "tiers.n_unstable": 24,
            "engine.local_epochs": 1, "engine.total_updates": 40,
            "engine.eval_every": 20}
    if plane == "gated":
        over.update({"faults.nan_rate": 0.5, "faults.update_clip": 25.0})
    else:
        over["population.plane"] = plane
    if plane in ("stacked", "streaming"):
        over.update({"population.availability": "bernoulli:0.9:20",
                     "population.completion": "bernoulli:0.8:20",
                     "population.seed": 3})
    return api.ExperimentSpec().with_overrides(over)


@pytest.mark.parametrize("plane", ["legacy", "stacked", "streaming",
                                   "gated"])
def test_one_host_transfer_per_fedat_round(plane, monkeypatch):
    """Every committed FedAT round hands the device exactly one host
    buffer, on every data plane and on the gated step, and a run whose
    rounds shrink under dropout compiles each step once."""
    run = api.build(_plane_spec(plane))
    ex = run.env.executor()
    assert ex.streaming == (plane == "streaming")
    rounds = []
    fedat_round = ex.fedat_round

    def counted(w, tms, m, ids, seed, **kw):
        before = ex.h2d_transfers
        out = fedat_round(w, tms, m, ids, seed, **kw)
        rounds.append((len(ids), ex.h2d_transfers - before,
                       kw.get("gate") is not None))
        return out

    monkeypatch.setattr(ex, "fedat_round", counted)
    run.run()
    assert len(rounds) >= 40
    assert [t for _, t, _ in rounds] == [1] * len(rounds)
    assert any(n < K for n, _, _ in rounds)      # dropout-shrunken rounds
    assert all(g == (plane == "gated") for _, _, g in rounds)
    assert ex.trace_counts and set(ex.trace_counts.values()) == {1}


def _f32(*bits):
    return np.asarray(bits, np.uint32).view(np.float32)


def test_pack_round_keeps_every_bit():
    """Float32 payloads travel through the int32 carrier as their bits —
    NaN payloads (quiet and signalling, either sign), -0.0, subnormals,
    +-inf — and int32 values whose bits are float NaN patterns come back
    as the same integers; bools come back as bools.  On the CPU
    backend, through a jitted unpack."""
    f = _f32(0x7FC00000, 0xFFC00000, 0x7FC00001, 0x7F800001, 0xFF800001,
             0x7FFFFFFF, 0x80000000, 0x00000000, 0x00000001, 0x807FFFFF,
             0x007FFFFF, 0x7F800000, 0xFF800000, 0x3FC00000, 0x7F7FFFFF,
             0x00800000).reshape(2, 4, 2)
    i = np.asarray([0x7FC00000, 0x7F800001, -1, -8388607, -4194304,
                    0x7FFFFFFF, -2 ** 31, 0], np.int32)
    fields = {"ints": i, "x": f, "mask": np.asarray([[1, 0, 1], [0, 0, 1]],
                                                    bool),
              "w": _f32(0x80000000, 0x00000001, 0xFFC12345)}
    buf, layout = pack_round(fields)
    assert buf.dtype == np.int32 and buf.shape == (8 + 16 + 6 + 3,)
    cpu = jax.devices("cpu")[0]
    out = jax.jit(lambda b: unpack_round(b, layout))(
        jax.device_put(buf, cpu))
    assert sorted(out) == sorted(fields)
    for k, a in fields.items():
        b = np.asarray(out[k])
        assert b.dtype == a.dtype and b.shape == a.shape, k
        if a.dtype == np.bool_:
            np.testing.assert_array_equal(b, a)
        else:
            np.testing.assert_array_equal(b.view(np.uint32),
                                          a.view(np.uint32))


def test_pack_round_refuses_other_widths():
    with pytest.raises(TypeError, match="'w'"):
        pack_round({"w": np.zeros(3, np.float64)})


# ---------------------------------------------------------------------------
# fixed-shape padding contract
# ---------------------------------------------------------------------------

def test_padded_round_matches_eager_reference_bitwise(env):
    """A dropout-shrunken sample padded to clients_per_round with
    zero-weight slots reproduces the eager variable-shape pipeline
    bit-for-bit (the engine-parity contract, checked here directly)."""
    ex = env.executor()
    codec = transport.get_codec("polyline:4")
    M = env.tm.n_tiers
    m, seed = 1, 20260801
    ids = np.asarray([5, 9], np.int32)           # shrunken: 2 of 4 slots
    keys = jax.random.split(jax.random.PRNGKey(seed), len(ids))

    w_sent = fake_polyline(env.params0, 4)
    cp, _ = env.update_fn(w_sent, env.client_batch(ids), keys)
    cp = fake_polyline(cp, 4)
    tier_model = aggregation.intra_tier_average(cp, env.n_samples(ids))
    tms0 = jax.tree.map(lambda l: jnp.stack([l] * M), env.params0)
    stack_ref = jax.tree.map(lambda s, nw: s.at[m].set(nw), tms0, tier_model)
    cw = aggregation.cross_tier_weights(jnp.asarray([2, 1, 1]))
    wg_ref = aggregation.weighted_average(stack_ref, cw)

    wg, stack = ex.fedat_round(
        jax.tree.map(jnp.array, env.params0),
        jax.tree.map(lambda l: jnp.stack([l] * M), env.params0),
        m, ids, seed, codec=codec, use_prox=True, cross_weights=cw)
    assert _bitwise_equal(stack_ref, stack)
    assert _bitwise_equal(wg_ref, wg)


@pytest.mark.parametrize("seed", [20260801, 2 ** 31 - 1])
@pytest.mark.parametrize("n", range(1, K + 1))
def test_in_graph_round_keys_match_host_padding_bitwise(env, n, seed):
    """The steps' in-graph keys (one K-way split, dead rows zeroed) are
    the host-side split to the live count padded with zero rows, bit for
    bit: the partitionable threefry's split is prefix-stable."""
    ex = env.executor()
    keys = jax.jit(_round_keys, static_argnums=2)(np.int32(seed),
                                                  np.int32(n), ex.K)
    ref = np.asarray(ex._pad_keys(seed, n))
    assert keys.dtype == ref.dtype and keys.shape == ref.shape == (K, 2)
    assert (np.asarray(keys) == ref).all()


@pytest.mark.parametrize("seed", [20260801, 2 ** 31 - 1])
def test_fedasync_in_graph_key_matches_split(seed):
    key = jax.jit(lambda s: _round_keys(s, 1, 1))(np.int32(seed))
    ref = np.asarray(jax.random.split(jax.random.PRNGKey(seed), 1))
    assert key.dtype == ref.dtype and (np.asarray(key) == ref).all()


def test_zero_weight_slots_are_bitwise_neutral():
    """Adding zero-count slots to Eq. 4 changes nothing, bit for bit."""
    rng = np.random.default_rng(0)
    models = {"w": jnp.asarray(rng.normal(0, 0.1, (3, 64)).astype(np.float32))}
    padded = {"w": jnp.concatenate(
        [models["w"], models["w"][:1], models["w"][:1]], axis=0)}
    ns = jnp.asarray([17.0, 40.0, 23.0])
    ns_pad = jnp.asarray([17.0, 40.0, 23.0, 0.0, 0.0])
    a = aggregation.intra_tier_average(models, ns)
    b = aggregation.intra_tier_average(padded, ns_pad)
    assert _bitwise_equal(a, b)


def test_host_weight_twins_are_bitwise_identical():
    """The numpy hot-path weight helpers must match the jnp originals
    bit for bit (exact-integer inputs, correctly-rounded division)."""
    for counts in ([0, 0, 0], [1, 0, 2], [7, 13, 1], [123, 456, 789, 1]):
        a = np.asarray(aggregation.cross_tier_weights(jnp.asarray(counts)))
        b = aggregation.cross_tier_weights_host(np.asarray(counts))
        np.testing.assert_array_equal(a, b)
    for ns in ([40, 40, 40, 0], [17, 0, 0, 0], [0, 0], [3, 5, 60]):
        a = np.asarray(aggregation.client_weights(jnp.asarray(ns)))
        b = aggregation.client_weights_host(np.asarray(ns))
        np.testing.assert_array_equal(a, b)
    for n in (2, 3, 5, 7):
        np.testing.assert_array_equal(
            np.asarray(aggregation.uniform_weights(n)),
            aggregation.uniform_weights_host(n))


def test_alive_vectorized_matches_dropout_schedule(env):
    for now in (0.0, 49.9, 120.0, 1e9, *env.dropout_time.values()):
        expected = np.ones(env.sc.n_clients, bool)
        for c, t in env.dropout_time.items():
            if now >= t:
                expected[c] = False
        np.testing.assert_array_equal(env.alive(now), expected)
