"""Dry-run machinery in a subprocess with forced host devices.

The real 512-device dry-run is exercised by ``python -m repro.launch.dryrun``
(EXPERIMENTS.md §Dry-run); here a reduced mesh proves the same code path —
lower + compile + memory/cost/collective extraction — inside the test suite
without forcing 512 devices on every other test.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, jax, jax.numpy as jnp
    from repro.configs import registry, TrainConfig
    from repro.core import steps
    from repro.models import lm
    from repro.runtime import sharding as shd
    from repro.runtime.hlo import collective_bytes, count_collectives

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = registry.get_smoke_config("{arch}")
    tcfg = TrainConfig(fedat_enabled=True, fedat_sync_every=2,
                       fedat_compress_bits=8)
    with mesh, shd.use_mesh(mesh):
        fns = steps.make_fedat_step(cfg, tcfg, mesh)
        batch = {{"tokens": jax.ShapeDtypeStruct((2, 4, 128), jnp.int32)}}
        state = jax.eval_shape(fns.init_state, jax.random.PRNGKey(0))
        comp = jax.jit(fns.train_step,
                       in_shardings=(fns.state_shardings,
                                     fns.batch_shardings),
                       out_shardings=(fns.state_shardings, None)
                       ).lower(state, batch).compile()
    txt = comp.as_text()
    ca = comp.cost_analysis()
    out = {{
        "colls": count_collectives(txt),
        "coll_bytes": collective_bytes(txt),
        "temp": comp.memory_analysis().temp_size_in_bytes,
        "flops": ca.get("flops", 0),
    }}
    print("RESULT" + json.dumps(out))
""")


@pytest.mark.parametrize("arch", ["qwen2-7b", "rwkv6-3b"])
def test_multipod_fedat_compiles_on_8_devices(arch):
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(arch=arch)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    out = json.loads(line[len("RESULT"):])
    # the compressed cross-tier collective must exist on the pod axis
    assert out["colls"].get("all-gather", 0) + \
        out["colls"].get("all-reduce", 0) > 0
    assert out["coll_bytes"] > 0
    assert out["flops"] > 0


INT_WIRE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import re, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.configs import registry, TrainConfig
    from repro.core import steps
    from repro.models import lm
    from repro.runtime import sharding as shd

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = registry.get_smoke_config("qwen2-7b")
    tcfg = TrainConfig(fedat_enabled=True, fedat_sync_every=1,
                       fedat_compress_bits=8)
    with mesh, shd.use_mesh(mesh):
        fns = steps.make_fedat_step(cfg, tcfg, mesh)
        batch = {"tokens": jax.ShapeDtypeStruct((2, 4, 128), jnp.int32)}
        state = jax.eval_shape(fns.init_state, jax.random.PRNGKey(0))
        txt = jax.jit(fns.train_step,
                      in_shardings=(fns.state_shardings,
                                    fns.batch_shardings),
                      out_shardings=(fns.state_shardings, None)
                      ).lower(state, batch).compile().as_text()
    # the optimization barriers must keep the pod collective on int8
    print("INTWIRE", bool(re.search(r"s8\\[[0-9,]*\\][^=]*all-gather", txt)))
""")


def test_compressed_wire_stays_int8():
    """Regression guard for the §Perf cell C lesson: without barriers XLA
    silently gathers the dequantized f32 payload."""
    proc = subprocess.run(
        [sys.executable, "-c", INT_WIRE_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "INTWIRE True" in proc.stdout, proc.stdout[-500:]
