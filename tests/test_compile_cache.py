"""The entry points' persistent compilation cache (runtime/compile_cache.py):
``JAX_COMPILATION_CACHE_DIR`` wins when set, else ``<checkout>/.jax_cache``.

Each case runs in a fresh interpreter: the helper changes process-global
JAX configuration, which must not leak into the other tests."""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = textwrap.dedent("""
    import json, os
    import jax, jax.numpy as jnp
    from repro.runtime.compile_cache import CHECKOUT, enable_compile_cache
    path = enable_compile_cache()
    # cache even a fast compile, so an entry appears
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)).block_until_ready()
    print("RESULT" + json.dumps({
        "path": path, "checkout": CHECKOUT,
        "config": jax.config.jax_compilation_cache_dir,
        "entries": sorted(os.listdir(path)) if os.path.isdir(path) else []}))
""")


def _run(env_extra, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu",
               **env_extra)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")]
    return json.loads(line[0][len("RESULT"):])


def test_env_var_directory_wins(tmp_path):
    out = _run({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert out["path"] == str(tmp_path) == out["config"]
    assert out["entries"], "no compiled entry landed in the env-var dir"


def test_default_is_fixed_checkout_path():
    out = _run({}, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert out["checkout"] == REPO
    assert out["path"] == os.path.join(REPO, ".jax_cache") == out["config"]
    assert out["entries"]
