"""The check fails a run whose timed path is broken underneath: for each
fault a cell can have, the whole run is driven on the CPU (the look for a
chip skipped) and ``correct`` must come out false."""
import pytest

from bench import harness
from bench.tests.tiny import make_root

SEED = 2**33 + 99


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("faults"))


def run(root, cell):
    result, lines = harness.run_cell(root, cell, SEED, 1.0, False,
                                     require_chip=False)
    return result, lines


def broken_train_step(monkeypatch, fault):
    from repro.engine.rungs import Rung
    orig = Rung.train_step_fn

    def train_step_fn(self, *a, **k):
        step = orig(self, *a, **k)

        def broken(state, batch):
            if fault == "unchanged":
                _, metrics = step(state, batch)
                return state, metrics
            half = {"tokens": batch["tokens"][:batch["tokens"].shape[0] // 2]}
            return step(state, half)

        return broken

    monkeypatch.setattr(Rung, "train_step_fn", train_step_fn)


def test_sound_run_is_correct(root):
    result, lines = run(root, "tiny-train-cell")
    assert result["correct"] is True, lines


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_train_faults_are_caught(root, monkeypatch, fault):
    broken_train_step(monkeypatch, fault)
    result, lines = run(root, "tiny-train-cell")
    assert result["correct"] is False, lines
