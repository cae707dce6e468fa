"""The control (the reference one precision below the configuration's, in
the program's place) comes out not correct at a size a test run holds."""
import json

from bench import harness
from bench.tests.tiny import make_root


def test_serving_control_fails_its_limit(tmp_path):
    root = make_root(tmp_path)
    path = root / "bench" / "traffic" / "tiny-chat.json"
    mix = json.loads(path.read_text())
    mix["output_len"] = {"dist": "uniform", "min": 24, "max": 32}
    path.write_text(json.dumps(mix))
    result, lines = harness.run_cell(root, "tiny-chat-cell", 5, 1.0, False,
                                     require_chip=False, control=True)
    check = result["checks"]["logit_gap"]
    assert result["correct"] is True, lines
    assert result["control"]["logit_gap"] > check["limit"] > check["value"]


def test_training_control_and_half_batch_fail_their_limits(tmp_path):
    root = make_root(tmp_path)
    result, lines = harness.run_cell(root, "tiny-train-cell", 5, 1.0, False,
                                     require_chip=False, control=True)
    assert result["correct"] is True, lines
    limits = {k: v["limit"] for k, v in result["checks"].items()}
    for reading in ("control", "half_batch"):
        gaps = result["control"][reading]
        assert any(gaps[k] > limits[k] for k in limits), (reading, gaps, limits)
