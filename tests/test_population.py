"""Population plane (core/population.py + the population spec section):

* the parity contract — the streaming/gather plane bitwise-equals the
  stacked plane at small N on the full engine-parity oracle surface
  (times, acc trajectory, wire bytes), with exactly one trace per step
  configuration and zero recompiles across rounds;
* the flat-memory invariant — a 100k-client streaming smoke run's peak
  data-plane bytes stay flat vs N=1k (device buffer shapes are a
  function of the config, not of N);
* the stochastic client-state processes (FLGo-style availability /
  responsiveness / completion) — determinism, spec-parameter
  convergence, and sampler interaction — both directly and as
  hypothesis property tests (tests/_hypothesis.py: the @given tests
  skip when hypothesis is not installed; the direct tests still run).
"""
import functools

import numpy as np
import pytest

from _hypothesis import given, settings, st
from repro import api
from repro.core import population as population_mod
from repro.core.population import Population, PopulationConfig
from repro.core.simulation import SimConfig, SimEnv


def _pop_spec(plane, n_clients=512, **over):
    spec = api.ExperimentSpec().with_overrides({
        "data.n_clients": n_clients, "data.samples_per_client": 20,
        "data.image_hw": 8, "tiers.n_tiers": 3,
        "tiers.clients_per_round": 8, "tiers.n_unstable": 16,
        "engine.local_epochs": 1, "engine.total_updates": 10,
        "engine.eval_every": 5,
        "population.plane": plane,
        "population.availability": "bernoulli:0.9:20",
        "population.completion": "bernoulli:0.95:20",
        "population.responsiveness": "lognormal:0.25",
        "population.eval_clients": 32, "population.seed": 3})
    return spec.with_overrides(over) if over else spec


def _pop(n=200, sc_over=None, **cfg_over):
    base = dict(plane="stacked", seed=3)
    base.update(cfg_over)
    sc_kw = dict(n_clients=n, samples_per_client=20, image_hw=8,
                 n_tiers=3, clients_per_round=8, n_unstable=8)
    sc_kw.update(sc_over or {})
    sc = SimConfig(population=PopulationConfig(**base), **sc_kw)
    from repro.models import registry as model_registry
    model = model_registry.build_model(sc.model, model_registry.DataDims(
        n_classes=sc.n_classes, image_hw=sc.image_hw,
        n_features=sc.n_features, vocab_size=sc.vocab_size,
        seq_len=sc.seq_len, attention_backend=sc.attention_backend))
    return Population(sc.population, sc, model)


# ---------------------------------------------------------------------------
# the parity contract: streaming bitwise-equals stacked at N <= 512
# ---------------------------------------------------------------------------

def test_streaming_bitwise_equals_stacked():
    """The tentpole oracle: at N=512 the streaming/gather plane must be
    bitwise-identical to the stacked plane on the whole metrics surface,
    with one trace per step configuration (zero recompiles across a
    10-update run) and a distinct ("stream",) trace-key tag."""
    api.clear_env_cache()
    res_stack = api.run_spec(_pop_spec("stacked"))
    env_stack = api.get_env(_pop_spec("stacked"))
    api.clear_env_cache()
    res_stream = api.run_spec(_pop_spec("streaming"))
    env_stream = api.get_env(_pop_spec("streaming"))

    ms, mr = res_stack.metrics, res_stream.metrics
    assert ms.times == mr.times
    assert ms.rounds == mr.rounds
    assert ms.acc == mr.acc
    assert ms.acc_var == mr.acc_var
    assert ms.bytes_up == mr.bytes_up
    assert ms.bytes_down == mr.bytes_down

    for tc in (env_stack._executor.trace_counts,
               env_stream._executor.trace_counts):
        assert tc and all(v == 1 for v in tc.values())
    assert all("stream" not in k for k in env_stack._executor.trace_counts)
    assert all("stream" in k for k in env_stream._executor.trace_counts)
    api.clear_env_cache()


@pytest.mark.parametrize("strategy", ["fedavg", "fedasync"])
def test_streaming_parity_other_strategies(strategy):
    """The shared _select step body keeps every strategy's streaming path
    bitwise, not just FedAT's."""
    over = {"strategy.name": strategy, "engine.total_updates": 6,
            "engine.eval_every": 3}
    api.clear_env_cache()
    m1 = api.run_spec(_pop_spec("stacked", n_clients=64, **over)).metrics
    api.clear_env_cache()
    m2 = api.run_spec(_pop_spec("streaming", n_clients=64, **over)).metrics
    assert m1.times == m2.times and m1.acc == m2.acc
    assert m1.bytes_up == m2.bytes_up
    api.clear_env_cache()


def test_population_runs_are_deterministic():
    spec = _pop_spec("streaming", n_clients=64)
    m1 = api.run_spec(spec).metrics
    m2 = api.run_spec(spec).metrics
    assert m1.times == m2.times and m1.acc == m2.acc
    api.clear_env_cache()


def test_default_population_section_is_legacy_plane():
    """All-defaults population == no population at all: same SimConfig
    (population=None), same environment, golden trajectories untouched."""
    spec = api.ExperimentSpec()
    assert spec.to_sim_config().population is None
    assert spec.population.to_config() is None
    # plane alone flips it on; seed alone does not
    on = spec.with_overrides({"population.plane": "stacked"})
    assert on.to_sim_config().population is not None
    seeded = spec.with_overrides({"population.seed": 9})
    assert seeded.to_sim_config().population is None


# ---------------------------------------------------------------------------
# the flat-memory invariant: 100k clients, flat peak device memory
# ---------------------------------------------------------------------------

def test_streaming_100k_smoke_flat_memory():
    """A 100k-client streaming run works and its peak data-plane bytes
    stay within 10% of the 1k-client run's (the acceptance bound): batch
    and eval buffer shapes depend on the config only, never on N."""
    def run(n):
        spec = _pop_spec("streaming", n_clients=n,
                         **{"tiers.n_unstable": n // 100,
                            "engine.total_updates": 2,
                            "engine.eval_every": 2})
        res = api.run_spec(spec)
        env = api.get_env(spec)
        bytes_peak = env.data_plane_bytes()
        api.clear_env_cache()
        return res.metrics, bytes_peak

    m1k, b1k = run(1_000)
    m100k, b100k = run(100_000)
    assert np.isfinite(m100k.acc).all() and len(m100k.acc) >= 1
    assert b100k <= 1.1 * b1k
    # ... while the population itself really is 100x bigger
    assert len(m100k.acc) == len(m1k.acc)


def test_batch_nbytes_is_n_independent():
    p_small, p_big = _pop(n=100), _pop(n=10_000)
    assert p_small.cap == p_big.cap
    assert p_small.batch_nbytes(8) == p_big.batch_nbytes(8)
    batch = p_big.materialize(np.arange(8))
    assert sum(a.nbytes for a in batch.values()) == p_big.batch_nbytes(8)


# ---------------------------------------------------------------------------
# indexed generator: lazy, order-independent, reproducible
# ---------------------------------------------------------------------------

def test_indexed_content_is_order_independent():
    """materialize(ids) must not depend on which clients were generated
    before — the property the legacy sequential generator lacks."""
    p = _pop(n=50)
    a = p.materialize(np.asarray([7, 3, 7, 40]))
    b = _pop(n=50).materialize(np.asarray([40, 7, 3, 7]))
    assert np.array_equal(a["x"][0], b["x"][1])   # client 7
    assert np.array_equal(a["x"][1], b["x"][2])   # client 3
    assert np.array_equal(a["x"][3], b["x"][0])   # client 40
    assert np.array_equal(a["x"][0], a["x"][2])   # duplicate id, one draw


def test_stack_matches_streamed_rows():
    """The stacked plane's resident stack is row-for-row the batches the
    streaming plane materializes (the data-level half of the parity)."""
    p = _pop(n=40)
    stack = p.materialize_stack()
    ids = np.asarray([0, 13, 39])
    batch = p.materialize(ids)
    for k in ("x", "y", "mask"):
        assert np.array_equal(stack[k][ids], batch[k])
    assert np.array_equal(stack["n_samples"], p.n_train)


def test_sizes_obey_static_cap_and_floor():
    p = _pop(n=5_000)
    assert p.cap == max(population_mod.CAP_FACTOR * 20,
                        population_mod.MIN_SAMPLES)
    assert (p.sizes >= population_mod.MIN_SAMPLES).all()
    assert (p.sizes <= p.cap).all()
    assert (p.n_train >= 1).all()
    assert p.cap_train + p.cap_test == p.cap


def test_class_pools_honor_partitioner():
    p = _pop(n=300)
    assert p.pools is not None and p.pools.shape == (300, 2)
    batch = p.materialize(np.arange(20))
    for c in range(20):
        got = set(np.unique(batch["y"][c][batch["mask"][c]]))
        assert got <= set(p.pools[c])
    pd = _pop(n=300, sc_over={"partitioner": "dirichlet:0.3"})
    assert pd.probs is not None and pd.probs.shape == (300, 10)
    assert np.allclose(pd.probs.sum(1), 1.0)


def test_tokens_kind_population():
    p = _pop(n=30, sc_over={"model": "tiny_lm"})
    batch = p.materialize(np.arange(4))
    assert batch["x"].dtype == np.int32
    assert batch["x"].shape[2:] == (16,)
    assert (batch["x"][batch["mask"]] >= 0).all()
    assert (batch["x"][batch["mask"]] < 64).all()


# ---------------------------------------------------------------------------
# stochastic client-state processes
# ---------------------------------------------------------------------------

def test_process_grammar_parses_and_rejects():
    assert population_mod.parse_process("always", "a", "always") is None
    assert population_mod.parse_process("bernoulli:0.9", "a", "always") \
        == (0.9, population_mod.DEFAULT_PERIOD)
    assert population_mod.parse_process("bernoulli:0.5:7", "a", "always") \
        == (0.5, 7.0)
    for bad in ("poisson:1", "bernoulli:2", "bernoulli:0.5:0",
                "bernoulli:x"):
        with pytest.raises(ValueError):
            population_mod.parse_process(bad, "a", "always")
    assert population_mod.parse_responsiveness("none") is None
    assert population_mod.parse_responsiveness("lognormal:0.5") \
        == ("lognormal", 0.5)
    assert population_mod.parse_responsiveness("uniform:0.5,2") \
        == ("uniform", (0.5, 2.0))
    for bad in ("gamma:1", "lognormal:x", "uniform:2,1", "uniform:0,1"):
        with pytest.raises(ValueError):
            population_mod.parse_responsiveness(bad)


def test_sine_grammar_parses_and_rejects():
    assert population_mod.parse_process("sine:0.7,0.25,240", "a", "always") \
        == ("sine", 0.7, 0.25, 240.0)
    for bad in ("sine:0.7", "sine:0.7,0.25", "sine:1.5,0.25,240",
                "sine:0.7,-0.1,240", "sine:0.7,0.25,0", "sine:x,y,z"):
        with pytest.raises(ValueError):
            population_mod.parse_process(bad, "a", "always")


def test_profile_grammar_parses_and_rejects():
    assert population_mod.parse_profile("none") is None
    assert population_mod.parse_profile("phone:0.3") == 0.3
    assert population_mod.parse_profile("phone:1") == 1.0
    for bad in ("tablet:0.5", "phone:0", "phone:1.5", "phone:x", "phone"):
        with pytest.raises(ValueError):
            population_mod.parse_profile(bad)


def test_sine_availability_is_diurnal():
    """The slot probability rides the sine wave: the high half-cycle of
    a period-240 wave has visibly more availability than the low half,
    and the mask stays a deterministic function of (seed, slot)."""
    p = _pop(n=20_000, availability="sine:0.5,0.4,240")
    # slot midpoints at t=60 (peak, p=0.9) and t=180 (trough, p=0.1)
    hi = p.availability_mask(60.0).mean()
    lo = p.availability_mask(180.0).mean()
    assert abs(hi - 0.9) < 0.02 and abs(lo - 0.1) < 0.02
    q = _pop(n=20_000, availability="sine:0.5,0.4,240")
    assert np.array_equal(p.availability_mask(60.0),
                          q.availability_mask(60.0))


def test_phone_profile_gates_only_the_phone_class():
    """profile='phone:0.5' applies the preset processes to a seeded half
    of the population; the other half stays always-on, always-complete,
    unit-latency."""
    p = _pop(n=20_000, profile="phone:0.5")
    phone = p._phone
    assert abs(phone.mean() - 0.5) < 0.02
    avail = p.availability_mask(10.0)
    compl = p.completion_mask(10.0)
    assert avail[~phone].all() and compl[~phone].all()
    assert not avail[phone].all()       # the sine process gates phones
    assert (p.resp_factors[~phone] == 1.0).all()
    assert not (p.resp_factors[phone] == 1.0).all()


def test_phone_profile_runs_end_to_end():
    res = api.build(api.ExperimentSpec().with_overrides({
        "data.n_clients": 64, "data.samples_per_client": 20,
        "data.image_hw": 8, "tiers.n_tiers": 2,
        "tiers.clients_per_round": 4, "tiers.n_unstable": 0,
        "engine.local_epochs": 1, "engine.total_updates": 6,
        "engine.eval_every": 3,
        "population.profile": "phone:0.3"})).run()
    assert res.metrics.times


def test_profile_owns_the_process_fields():
    with pytest.raises(api.SpecError, match="profile"):
        api.PopulationSpec(profile="phone:0.3",
                           responsiveness="lognormal:0.5").validate(100)
    with pytest.raises(api.SpecError, match="phone"):
        api.PopulationSpec(profile="watch:0.3").validate(100)


def test_availability_deterministic_and_slotted():
    p = _pop(n=400, availability="bernoulli:0.7:20")
    q = _pop(n=400, availability="bernoulli:0.7:20")
    m1, m2 = p.availability_mask(25.0), q.availability_mask(25.0)
    assert np.array_equal(m1, m2)                       # identical specs
    assert np.array_equal(m1, p.availability_mask(39.9))  # same slot
    assert not np.array_equal(m1, p.availability_mask(45.0))  # next slot
    assert _pop(n=400, availability="always").availability_mask(25.0) is None


def test_availability_rate_converges_to_spec():
    p = _pop(n=20_000, availability="bernoulli:0.8:20")
    rates = [p.availability_mask(t).mean() for t in (0.0, 30.0, 70.0)]
    assert all(abs(r - 0.8) < 0.02 for r in rates)


def test_completion_rate_converges_to_spec():
    p = _pop(n=20_000, completion="bernoulli:0.6:20")
    assert abs(p.completion_mask(10.0).mean() - 0.6) < 0.02
    assert _pop(n=100).completion_mask(10.0) is None


def test_responsiveness_factors_reshape_tiers():
    sc_kw = dict(n_clients=128, samples_per_client=20, image_hw=8,
                 n_tiers=3, clients_per_round=8, n_unstable=8)
    e0 = SimEnv(SimConfig(population=PopulationConfig(plane="stacked"),
                          **sc_kw))
    e1 = SimEnv(SimConfig(population=PopulationConfig(
        plane="stacked", responsiveness="lognormal:0.5"), **sc_kw))
    assert not np.array_equal(e0.tm.latencies, e1.tm.latencies)
    assert e1.population.resp_factors.shape == (128,)
    assert (e1.population.resp_factors > 0).all()
    # uniform grammar bounds the factors
    e2 = SimEnv(SimConfig(population=PopulationConfig(
        plane="stacked", responsiveness="uniform:0.5,2.0"), **sc_kw))
    f = e2.population.resp_factors
    assert (f >= 0.5).all() and (f <= 2.0).all()


def test_streams_are_independent():
    """Dedicated-stream contract: turning one knob never reshuffles
    another family's draws."""
    a = _pop(n=200, availability="bernoulli:0.9:20")
    b = _pop(n=200, availability="bernoulli:0.5:5",
             responsiveness="lognormal:0.5")
    assert np.array_equal(a.sizes, b.sizes)
    assert np.array_equal(a.pools, b.pools)
    ba, bb = a.materialize(np.arange(4)), b.materialize(np.arange(4))
    assert np.array_equal(ba["x"], bb["x"])
    # ... but a different population seed reshuffles everything
    c = _pop(n=200, seed=4)
    assert not np.array_equal(a.sizes, c.sizes)


def test_sampler_honors_availability_and_tier_membership():
    """sample_clients over alive() picks without replacement, only
    available clients, and only from the given tier's members."""
    sc_kw = dict(n_clients=256, samples_per_client=20, image_hw=8,
                 n_tiers=4, clients_per_round=8, n_unstable=16)
    env = SimEnv(SimConfig(population=PopulationConfig(
        plane="stacked", availability="bernoulli:0.6:20", seed=3), **sc_kw))
    rng = np.random.default_rng(0)
    for now in (0.0, 100.0, 333.0):
        alive = env.alive(now)
        avail = env.population.availability_mask(now)
        assert not alive[~avail].any()        # the mask is folded in
        for m in range(env.tm.n_tiers):
            members = env.tm.members[m]
            pool = members[alive[members]]
            ids = env.sample_clients(pool, 8, rng)
            assert len(ids) == len(set(ids.tolist()))  # no replacement
            assert alive[ids].all()
            assert np.isin(ids, members).all()


# ---------------------------------------------------------------------------
# property-based versions (skip cleanly without hypothesis)
# ---------------------------------------------------------------------------

@given(p=st.floats(0.1, 0.9), slot_seed=st.integers(0, 2**20),
       seed=st.integers(0, 2**20))
@settings(max_examples=20, deadline=None)
def test_prop_identical_specs_identical_draws(p, slot_seed, seed):
    cfg = dict(availability=f"bernoulli:{p}:20", seed=seed)
    now = float(slot_seed % 1000)
    m1 = _pop(n=300, **cfg).availability_mask(now)
    m2 = _pop(n=300, **cfg).availability_mask(now)
    assert np.array_equal(m1, m2)


@given(p=st.floats(0.2, 0.95), seed=st.integers(0, 2**20))
@settings(max_examples=10, deadline=None)
def test_prop_availability_rate_converges(p, seed):
    pop = _pop(n=20_000, availability=f"bernoulli:{p}:20", seed=seed)
    assert abs(pop.availability_mask(0.0).mean() - p) < 0.025


@functools.lru_cache(maxsize=None)
def _sampler_env():
    return SimEnv(SimConfig(
        population=PopulationConfig(
            plane="stacked", availability="bernoulli:0.6:20", seed=3),
        n_clients=256, samples_per_client=20, image_hw=8, n_tiers=4,
        clients_per_round=8, n_unstable=16))


@given(now=st.floats(0, 500), k=st.integers(1, 16),
       rng_seed=st.integers(0, 2**20))
@settings(max_examples=20, deadline=None)
def test_prop_sampler_respects_masks(now, k, rng_seed):
    env = _sampler_env()
    alive = env.alive(now)
    rng = np.random.default_rng(rng_seed)
    for m in range(env.tm.n_tiers):
        members = env.tm.members[m]
        pool = members[alive[members]]
        ids = env.sample_clients(pool, k, rng)
        assert len(ids) == min(k, len(pool))
        assert len(ids) == len(set(ids.tolist()))
        assert alive[ids].all() and np.isin(ids, members).all()
