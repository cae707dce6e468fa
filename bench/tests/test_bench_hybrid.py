"""The layer-pattern hybrid against its plain reference on the CPU at a
tiny size: the program's loss, every leaf's first gradient and three Adam
steps; the whole cell through the harness (sound, and with the state left
unchanged); the FLOP count at the cell's shapes; ``hybrid_train_mfu``
refusing a record whose layers differ from the configuration's; and the
dense path's Granite multipliers against ``bench/reference.py``."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench import reference as dense_reference
from bench import weights as dense_weights
from bench.counts import Spec
from bench.drivers import common
from bench.drivers.train_hybrid import canonical, program_config, program_params
from bench.hybrid import Dims, counts, reference, weights
from bench.tests.conftest import REPO

SEED = 2**33 + 17
TINY = {"hidden_size": 64, "intermediate_size": 128, "shared_intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
        "num_hidden_layers": 3, "layer_types": ["mamba", "attention", "mamba"],
        "mamba_n_heads": 2, "mamba_d_state": 32}
TINY_SPEC = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 3,
             "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256}


def _real_config() -> dict:
    return json.loads((REPO / "bench" / "configs" /
                       "granite-4.0-h-micro-train.json").read_text())


def tiny_config() -> dict:
    cfg = _real_config()
    cfg.update(TINY)
    cfg["model"].update(TINY_SPEC)
    return cfg


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A benchmark root holding one tiny hybrid cell on the cell's own
    traffic, limits, driver and readers."""
    tmp = tmp_path_factory.mktemp("hybrid")
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    for sub in ("configs", "traffic", "limits"):
        (tmp / "bench" / sub).mkdir(parents=True)
    (tmp / "bench" / "configs" / "tiny-h.json").write_text(json.dumps(tiny_config()))
    mix = json.loads((REPO / "bench" / "traffic" / "train-8k.json").read_text())
    mix.update(batch=2, seq=64, trace_seconds=0.5)
    (tmp / "bench" / "traffic" / "tiny-8k.json").write_text(json.dumps(mix))
    (tmp / "bench" / "limits" / "tiny-h-cell.json").write_text(
        (REPO / "bench" / "limits" / "granite4h-train-8k.json").read_text())
    bench = {k: real[k] for k in ("command", "paths", "run_seconds")}
    bench["configs"] = [{"name": "tiny-h", "source": "test", "reduced": [], "why": "test",
                         "file": "bench/configs/tiny-h.json"}]
    bench["workloads"] = [{"name": "tiny-h-cell", "config": "tiny-h", "traffic": "tiny-8k",
                           "chips": 1, "why": "test"}]
    cell = "granite4h-train-8k"
    bench["end_to_end"] = [dict(e, workloads=["tiny-h-cell"])
                           if cell in e.get("workloads", ()) else e
                           for e in real["end_to_end"]]
    bench["per_layer"] = [dict(e, workloads=["tiny-h-cell"]) for e in real["per_layer"]
                          if cell in e.get("workloads", ())]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def test_program_matches_the_reference_leaf_by_leaf():
    from repro.launch.steps import build_train_step
    from repro.models.registry import build_model
    from repro.optim.optimizers import adam

    cfg = tiny_config()
    m = Dims.from_config(cfg)
    w = weights.make(m, SEED)
    model = build_model(program_config(cfg), impl="pallas", remat="full")
    params = program_params(dict(w), m, model)
    # 300 positions: two SSD chunks, the second ragged, so the state carries
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 300), 0, m.vocab)
    loss, grads = jax.value_and_grad(model.loss)(params, {"tokens": tokens})
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(reference.Model(cfg).loss)(w, tokens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    grads = canonical(grads)
    assert set(grads) == set(ref_grads)
    for k, g in grads.items():
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref_grads[k]), rtol=2e-4,
                                   atol=2e-6, err_msg=k)

    o = cfg["optimizer"]
    step = jax.jit(build_train_step(model, adam(b1=o["b1"], b2=o["b2"], eps=o["eps"]),
                                    lr=o["lr"]))
    opt = adam(b1=o["b1"], b2=o["b2"], eps=o["eps"])
    state = {"params": params, "opt": opt.init(params), "err": (),
             "step": jnp.zeros((), jnp.int32)}
    batches = [{"tokens": jax.random.randint(jax.random.PRNGKey(i), (2, 48), 0, m.vocab)}
               for i in range(3)]
    for b in batches:
        state, _ = step(state, b)
    ref = reference.train(cfg, weights.make(m, SEED), batches)
    w0 = weights.make(m, SEED)
    # Adam moves every element by about lr: one whose gradient is near zero
    # moves by what rounding decides, so the floor is a fiftieth of lr
    for k, v in canonical(state["params"]).items():
        np.testing.assert_allclose(np.asarray(v - w0[k]),
                                   np.asarray(ref["weights"][k] - w0[k]),
                                   rtol=1e-3, atol=o["lr"] / 50, err_msg=k)


def test_sound_cell_is_correct_and_copies_the_gauges(root):
    result, lines = harness.run_cell(root, "tiny-h-cell", SEED, 1.0, False,
                                     require_chip=False)
    assert result["correct"] is True, lines
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert result["checks"]["grad_gap"]["value"] < 1e-4, lines
    note = next(line for line in lines if line.startswith("train_hybrid:"))
    assert "'hybrid_layers': {'mamba': 2, 'attention': 1}" in note
    assert "'ssd_chunks': {'64': 1}" in note


def test_unchanged_state_is_caught(root, monkeypatch):
    from repro.engine.rungs import Rung
    orig = Rung.train_step_fn

    def train_step_fn(self, *a, **k):
        step = orig(self, *a, **k)
        return lambda state, batch: (state, step(state, batch)[1])

    monkeypatch.setattr(Rung, "train_step_fn", train_step_fn)
    result, lines = harness.run_cell(root, "tiny-h-cell", SEED, 1.0, False,
                                     require_chip=False)
    assert result["correct"] is False, lines


def test_cell_flops_by_hand():
    m = Dims.from_config(_real_config())
    mamba = 2048 * (2 * 4096 + 2 * 128 + 64) + 4096 * 2048 + 3 * 2048 * 8192
    attn = 2048 * (2048 + 2 * 512) + 2048 * 2048 + 3 * 2048 * 8192
    assert counts.layer_matmul_params(m, "mamba") == mamba == 76_152_832
    assert counts.layer_matmul_params(m, "attention") == attn == 60_817_408
    pairs = 256 * 257 // 2
    ssd = 32 * (2 * pairs * (128 + 4096) + 4 * 256 * 128 * 4096)
    assert counts.ssd_flops(m, 8192) == ssd
    fwd = 2 * 8192 * (9 * mamba + attn) + 9 * ssd \
        + 4 * 64 * 32 * (8192 * 8193 // 2) + 2 * 8191 * 2048 * 12544
    assert counts.train_step_flops(m, 1, 8192) == 3 * fwd
    assert counts.train_step_flops(m, 1, 8192) == pytest.approx(39.47e12, rel=1e-3)


def test_mfu_reader_refuses_a_model_unlike_the_config():
    """The count's layers and chunk are checked against the gauges the
    driver copied; a disagreement reads nothing."""
    reader = harness.find(REPO, "metrics", "hybrid_train_mfu")
    cfg = _real_config()
    record = {"steps": 60, "window_s": 45.0, "batch": 1, "seq": 8192,
              "hybrid_layers": {"mamba": 9, "attention": 1}, "ssd_chunks": {"256": 32}}
    view = harness.RunView(cfg=cfg, mix={}, spec=None, trace=None,
                           peaks={"bf16_flops_per_s": 197e12}, record=record)
    flops = counts.train_step_flops(Dims.from_config(cfg), 1, 8192)
    assert reader.read(view) == pytest.approx(100 * 60 * flops / 45.0 / 197e12)
    for held in ({"mamba": 10}, {"mamba": 8, "attention": 2}, None):
        bad = harness.RunView(**dict(vars(view), record=dict(record, hybrid_layers=held)))
        assert reader.read(bad) is None
    for scanned in ({"128": 64}, {}, None):
        bad = harness.RunView(**dict(vars(view), record=dict(record, ssd_chunks=scanned)))
        assert reader.read(bad) is None


def _dense(multipliers: dict):
    """granite-3-2b at a tiny size, its bench config and a program config
    with ``multipliers`` (Hugging Face names)."""
    from repro.configs import get_config
    cfg = json.loads((REPO / "bench" / "configs" / "granite-3-2b-train.json").read_text())
    cfg["model"].pop("attention_multiplier")  # the file's 1/sqrt(64); head_dim is 16 here
    cfg["model"].update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=2, vocab_size=256,
                        **multipliers)
    names = {"attention_multiplier": "attention_multiplier",
             "embedding_multiplier": "embedding_multiplier",
             "residual_multiplier": "residual_multiplier", "logits_scaling": "logits_scaling"}
    pcfg = dataclasses.replace(
        get_config("granite-3-2b"), d_model=64, d_ff=128, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, vocab_size=256,
        **{names[k]: v for k, v in multipliers.items()})
    return cfg, pcfg


@pytest.mark.parametrize("multipliers", [
    {}, {"attention_multiplier": 0.25, "embedding_multiplier": 1.0,
         "residual_multiplier": 1.0, "logits_scaling": 1.0},
    {"attention_multiplier": 1 / 64, "embedding_multiplier": 12.0,
     "residual_multiplier": 0.22, "logits_scaling": 8.0}],
    ids=["default", "neutral", "granite"])
def test_dense_multipliers_match_the_dense_reference(multipliers):
    from repro.models.registry import build_model
    cfg, pcfg = _dense(multipliers)
    spec = Spec.from_config(cfg)
    w = dense_weights.make(spec, SEED)
    model = build_model(pcfg, impl="pallas")
    params = common.program_params(w, model)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0, spec.vocab)
    loss, grads = jax.value_and_grad(model.loss)(params, {"tokens": tokens})
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grads = jax.value_and_grad(
            dense_reference.Model(cfg).loss)(w, tokens)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for k, g in common.canonical(grads).items():
        np.testing.assert_allclose(np.asarray(g), np.asarray(ref_grads[k]), rtol=2e-4,
                                   atol=2e-6, err_msg=k)
    if multipliers and multipliers["embedding_multiplier"] == 1.0:
        # neutral multipliers trace the same program as the defaults
        _, default = _dense({})
        text = lambda c: str(jax.make_jaxpr(build_model(c, impl="pallas").loss)(  # noqa: E731
            params, {"tokens": tokens}))
        assert text(pcfg) == text(default)
