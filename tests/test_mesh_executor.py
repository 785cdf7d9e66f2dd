"""Client-sharded round executor on a device mesh (DESIGN.md
§Scale-mapping).

The mesh parity contract has two sides:

* **D == 1 is bitwise.**  A one-device host mesh builds the exact
  single-device steps — same trace keys, bit-identical trajectory — so
  turning the mesh machinery on cannot perturb the engine-parity oracle.
* **D > 1 is tolerance-pinned.**  A multi-device host mesh (forced via
  ``--xla_force_host_platform_device_count``, exercised in a subprocess so
  the device count doesn't leak into other tests) must reproduce the
  single-device trajectory within the tolerances pinned here: identical
  event times (the host-side event order never depends on the mesh),
  one-round parameters to float rounding, accuracies to a few percent
  after many chaotic Adam rounds.

Plus: the pad-to-axis-multiple validation surfaces (static for
``production``, build-time for ``host``), and the trace counters prove
meshing adds no shape-driven recompiles.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import api
from repro.core.fedat import FedATConfig, run_fedat
from repro.core.simulation import SimConfig, SimEnv
from repro.launch import mesh as mesh_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BASE = dict(n_clients=16, n_tiers=3, samples_per_client=20,
             classes_per_client=2, image_hw=8, clients_per_round=8,
             local_epochs=1, n_unstable=2)


# ---------------------------------------------------------------------------
# mesh name grammar + static (spec-level) validation
# ---------------------------------------------------------------------------

def test_mesh_name_grammar_round_trips():
    for spec in (api.MeshSpec(), api.MeshSpec(kind="host"),
                 api.MeshSpec(kind="host", n_pods=2),
                 api.MeshSpec(kind="production"),
                 api.MeshSpec(kind="production", n_pods=2)):
        back = api.MeshSpec.from_name(spec.to_name())
        assert (back.kind, back.n_pods) == (spec.kind, spec.n_pods)
    assert mesh_mod.parse_mesh_name(None) == ("single", 1)
    assert mesh_mod.parse_mesh_name("host:4") == ("host", 4)
    for bad in ("cluster", "host:x", "host:0", "production:3"):
        with pytest.raises(ValueError):
            mesh_mod.parse_mesh_name(bad)


def test_mesh_spec_validation_errors():
    with pytest.raises(api.SpecError, match=r"mesh\.kind"):
        api.ExperimentSpec(mesh=api.MeshSpec(kind="cluster")).validate()
    with pytest.raises(api.SpecError, match=r"pod axis"):
        api.ExperimentSpec(mesh=api.MeshSpec(n_pods=2)).validate()
    with pytest.raises(api.SpecError, match=r"shard_tiers"):
        api.ExperimentSpec(
            mesh=api.MeshSpec(kind="host", shard_tiers=True)).validate()


def test_production_pad_validation_is_static():
    """The production data axis (16) is known without devices: a
    clients_per_round that doesn't divide fails at validate()."""
    spec = api.ExperimentSpec(mesh=api.MeshSpec(kind="production"))
    with pytest.raises(api.SpecError,
                       match=r"clients_per_round=10.*multiple of 16"):
        spec.validate()
    spec.tiers.clients_per_round = 32
    spec.validate()


def test_host_pad_validation_at_build_time():
    """With one local device the host data axis is 1, so any
    clients_per_round builds; the divisibility error for D > 1 is covered
    by the subprocess test below."""
    sc = SimConfig(**{**_BASE, "clients_per_round": 7}, mesh="host")
    assert SimEnv(sc).data_axis == len(__import__("jax").devices())


def test_no_mesh_env_ignores_ambient_mesh():
    """A no-mesh environment built inside a use_mesh() context must stay
    single-device: data_axis sizes from the env's own mesh, never the
    thread-local ambient one."""
    from repro.runtime import sharding as shd
    with shd.use_mesh(mesh_mod.make_host_mesh()):
        env = SimEnv(SimConfig(**_BASE))
    assert env.mesh is None and env.data_axis == 1
    assert not env.executor().shard_tiers


def test_resolve_mesh_host_pods_must_divide_devices():
    """The declarative path is strict: host:N with an indivisible device
    count fails loudly (make_host_mesh's silent fallback is only for
    direct callers like the trainer)."""
    n = len(__import__("jax").devices())
    with pytest.raises(ValueError, match="divisible"):
        mesh_mod.resolve_mesh(f"host:{n + 1}")


def test_mesh_is_part_of_provenance():
    base = api.ExperimentSpec()
    meshed = base.with_overrides({"mesh.kind": "host"})
    assert meshed.hash() != base.hash()
    assert meshed.env_hash() != base.env_hash()   # distinct cached envs


# ---------------------------------------------------------------------------
# D == 1: the mesh machinery is bitwise-invisible
# ---------------------------------------------------------------------------

@pytest.mark.skipif(len(__import__("jax").devices()) != 1,
                    reason="bitwise D==1 contract needs exactly 1 device")
def test_one_device_host_mesh_is_bitwise_single_device():
    env0 = SimEnv(SimConfig(**_BASE))
    env1 = SimEnv(SimConfig(**_BASE, mesh="host"))
    cfg = FedATConfig(total_updates=8, eval_every=4)
    m0, m1 = run_fedat(env0, cfg), run_fedat(env1, cfg)
    assert m0.times == m1.times and m0.acc == m1.acc
    assert m0.acc_var == m1.acc_var
    # same trace keys: the single-device steps, no "dataD" suffix
    assert set(env1.executor().trace_counts) \
        == set(env0.executor().trace_counts)
    assert all(len(k) == 3 for k in env1.executor().trace_counts)


@pytest.mark.skipif(len(__import__("jax").devices()) != 1,
                    reason="bitwise D==1 contract needs exactly 1 device")
@pytest.mark.parametrize("plane", ["stacked", "streaming"])
def test_one_device_host_mesh_bitwise_under_population(plane):
    """Population x mesh: the D == 1 bitwise contract holds under both
    indexed population planes too — a one-device host mesh reproduces
    the no-mesh trajectory exactly, whether the round data arrives via
    the resident gather or the streamed batch."""
    from repro.core.population import PopulationConfig
    pop = PopulationConfig(plane=plane, availability="bernoulli:0.9:20",
                           eval_clients=8, seed=3)
    base = {**_BASE, "n_clients": 64, "n_unstable": 6}
    env0 = SimEnv(SimConfig(**base, population=pop))
    env1 = SimEnv(SimConfig(**base, mesh="host", population=pop))
    cfg = FedATConfig(total_updates=8, eval_every=4)
    m0, m1 = run_fedat(env0, cfg), run_fedat(env1, cfg)
    assert m0.times == m1.times and m0.acc == m1.acc
    assert m0.acc_var == m1.acc_var
    assert set(env1.executor().trace_counts) \
        == set(env0.executor().trace_counts)
    want_stream = plane == "streaming"
    assert all(("stream" in k) == want_stream
               for k in env1.executor().trace_counts)


# ---------------------------------------------------------------------------
# D > 1: forced multi-device host mesh in a subprocess
# ---------------------------------------------------------------------------

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, numpy as np
    from repro import api
    from repro.core.fedat import FedATConfig, run_fedat
    from repro.core.simulation import SimConfig, SimEnv

    base = dict(n_clients=16, n_tiers=3, samples_per_client=20,
                classes_per_client=2, image_hw=8, clients_per_round=8,
                local_epochs=1, n_unstable=2)
    env0 = SimEnv(SimConfig(**base))
    env1 = SimEnv(SimConfig(**base, mesh="host"))
    out = {"n_devices": len(jax.devices()), "data_axis": env1.data_axis}

    # one fused round, executor-level: tight numerical agreement
    from repro.compress import transport
    from repro.core import aggregation
    import jax.numpy as jnp
    codec = transport.get_codec("polyline:4")
    M = env0.tm.n_tiers
    cw = aggregation.uniform_weights(M)
    args = lambda env: (jax.tree.map(jnp.array, env.params0),
                        jax.tree.map(lambda l: jnp.stack([l] * M),
                                     env.params0))
    ids = np.arange(8, dtype=np.int32)
    w0, _ = env0.executor().fedat_round(*args(env0), 0, ids, 7, codec=codec,
                                        use_prox=True, cross_weights=cw)
    w1, _ = env1.executor().fedat_round(*args(env1), 0, ids, 7, codec=codec,
                                        use_prox=True, cross_weights=cw)
    out["round_maxdiff"] = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(w0), jax.tree.leaves(w1)))

    # engine-level trajectory: past the earliest dropouts (uniform(50,400))
    cfg = FedATConfig(total_updates=30, eval_every=6)
    m0, m1 = run_fedat(env0, cfg), run_fedat(env1, cfg)
    out["times_equal"] = m0.times == m1.times
    out["acc_maxdiff"] = max(abs(a - b) for a, b in zip(m0.acc, m1.acc))
    out["keys0"] = sorted(map(str, env0.executor().trace_counts))
    out["keys1"] = sorted(map(str, env1.executor().trace_counts))
    # no shape-driven recompiles: dropouts shrank samples, yet each
    # sharded step traced exactly once
    out["trace_counts1"] = list(env1.executor().trace_counts.values())

    # pad-to-axis-multiple build error under the real 4-device mesh
    try:
        api.get_env(api.ExperimentSpec(
            data=api.DataSpec(n_clients=16, samples_per_client=20,
                              image_hw=8),
            tiers=api.TierSpec(n_tiers=3, clients_per_round=10,
                               n_unstable=2),
            mesh=api.MeshSpec(kind="host")))
        out["pad_error"] = None
    except api.SpecError as e:
        out["pad_error"] = str(e)
    # a resident train stack whose client count does not split over the
    # data axis is an error, not a silently replicated stack
    try:
        SimEnv(SimConfig(**{**base, "n_clients": 18}, mesh="host"))
        out["stack_error"] = None
    except ValueError as e:
        out["stack_error"] = str(e)
    print("RESULT" + json.dumps(out))
""")


@pytest.fixture(scope="module")
def mesh4():
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], capture_output=True, text=True,
        timeout=900,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
             "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def test_sharded_matches_single_device_within_tolerance(mesh4):
    assert mesh4["n_devices"] == 4 and mesh4["data_axis"] == 4
    # host-side event order never depends on the mesh: times are bitwise
    assert mesh4["times_equal"]
    # pinned tolerances: one fused round agrees to float rounding (each
    # client trains bitwise as on one device; only the psum reassociates
    # the Eq. 4 sum — a round's update is ~1e-3, so a looser bound would
    # pass clients whose gradients leaked across shards); a 30-update
    # chaotic Adam trajectory stays within a few percent of accuracy
    assert mesh4["round_maxdiff"] < 1e-5, mesh4["round_maxdiff"]
    assert mesh4["acc_maxdiff"] < 0.1, mesh4["acc_maxdiff"]


def test_sharded_steps_have_distinct_keys_and_no_retraces(mesh4):
    assert all("data4" in k for k in mesh4["keys1"])
    assert not any("data4" in k for k in mesh4["keys0"])
    # meshing adds no recompiles: one trace per configuration, across the
    # dropout-shrunken samples of a 30-update run
    assert all(c == 1 for c in mesh4["trace_counts1"])


def test_host_pad_validation_under_forced_devices(mesh4):
    assert mesh4["pad_error"] is not None
    assert "multiple of 4" in mesh4["pad_error"]


def test_indivisible_resident_stack_is_an_error(mesh4):
    assert mesh4["stack_error"] is not None
    assert "18 clients" in mesh4["stack_error"]
    assert "multiple of 4" in mesh4["stack_error"]
