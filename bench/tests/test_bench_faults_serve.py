"""The check fails a run whose timed path is broken underneath: for each
fault a cell can have, the whole run is driven on the CPU (the look for a
chip skipped) and ``correct`` must come out false."""
import jax
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


def broken_decode(monkeypatch, fault):
    import repro.launch.serve as serve
    orig = serve.build_paged_decode_step

    def build(model, **kw):
        step = orig(model, jit=False)

        def broken(params, cache, tokens, cache_len, table):
            tok, logits, new = step(params, cache, tokens, cache_len, table)
            if fault == "token":
                return (tok + 1) % model.cfg.vocab_size, logits, new
            return tok, logits, cache

        return jax.jit(broken, donate_argnums=(1,))

    monkeypatch.setattr(serve, "build_paged_decode_step", build)


def test_sound_run_is_correct(root):
    result, lines = run(root, "tiny-chat-cell")
    assert result["correct"] is True, lines


@pytest.mark.parametrize("cell", ["tiny-chat-cell", "tiny-docqa-cell",
                                  "tiny-offline-cell"])
@pytest.mark.parametrize("fault", ["token", "cache_unchanged"])
def test_serve_faults_are_caught(root, monkeypatch, cell, fault):
    broken_decode(monkeypatch, fault)
    result, lines = run(root, cell)
    assert result["correct"] is False, lines
    assert result["checks"]["logit_gap"]["value"] > \
        result["checks"]["logit_gap"]["limit"]
