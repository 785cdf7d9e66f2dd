"""The harness end to end on the CPU, at small sizes."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import run
from bench.tests.conftest import ROOT, small_cells


def _cli(cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "logreg_sent140.stream_660k", "--seed", "3000000019",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    out = _cli(ROOT)
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_exits_nonzero_with_only_its_own_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _cli(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("cell", small_cells())
def test_a_cell_added_as_files_runs_and_is_correct(small_root, cell):
    """A cell that exists only as new data files (see conftest) runs
    through the harness, reports its end-to-end metrics and passes its
    correctness check against the reference."""
    res = run.run_cell(cell, 3_000_000_019, 1.0, False, root=small_root,
                       require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    listed = {m["name"] for m in run.load_cell(cell, small_root).end_to_end}
    assert set(res["metrics"]) == listed
    assert res["metrics"]["updates_per_s"]["value"] > 0
    win = res["window"]
    assert win["compiles_in_window"] == 0 and win["traces_in_window"] == 0
    assert all(v <= 1 for v in win["step_traces"].values())
    json.dumps(res)
