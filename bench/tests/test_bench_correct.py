"""``correct`` comes out false when the timed path is broken underneath
(each fault a one-chip training cell can have) and when the control,
the reference one precision below the configuration's, stands in the
program's place.  Both cells
at small sizes on the CPU, held to their real configurations' limits."""
import jax
import jax.numpy as jnp
import pytest

from bench import calibrate, compare, run
from bench.tests.conftest import small_cells
from repro.core.executor import RoundExecutor

SEED = 3_000_000_037


def _unchanged(monkeypatch):
    def fedat_round(self, w_global, tier_models, m, ids, seed, **kw):
        return jax.tree.map(jnp.copy, (w_global, tier_models))
    monkeypatch.setattr(RoundExecutor, "fedat_round", fedat_round)


def _half_batch(monkeypatch):
    real = RoundExecutor.fedat_round

    def fedat_round(self, w_global, tier_models, m, ids, seed, **kw):
        return real(self, w_global, tier_models, m,
                    ids[:(len(ids) + 1) // 2], seed, **kw)
    monkeypatch.setattr(RoundExecutor, "fedat_round", fedat_round)


def _answer_altered(monkeypatch):
    real = RoundExecutor.fedat_round

    def fedat_round(self, w_global, tier_models, m, ids, seed, **kw):
        w_global, tiers = real(self, w_global, tier_models, m, ids, seed,
                               **kw)
        return w_global, jax.tree.map(lambda t: t.at[m].add(1e-2), tiers)
    monkeypatch.setattr(RoundExecutor, "fedat_round", fedat_round)


@pytest.mark.parametrize("cell", small_cells())
@pytest.mark.parametrize("fault", [_unchanged, _half_batch, _answer_altered],
                         ids=["state_unchanged", "half_batch",
                              "answer_altered"])
def test_a_broken_timed_path_is_not_correct(small_root, cell, fault,
                                            monkeypatch):
    fault(monkeypatch)
    res = run.run_cell(cell, SEED, 0.5, False, root=small_root,
                       require_tpu=False)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", small_cells())
def test_the_bfloat16_control_is_not_correct(small_root, cell):
    m = run.measure(cell, SEED, 0.5, False, root=small_root,
                    require_tpu=False)
    values = calibrate.planted(m, "control")
    verdict = compare.judge(values, m.cell.config["limits"])
    assert not verdict["correct"], verdict["checks"]
    assert run.report(m)["correct"]
