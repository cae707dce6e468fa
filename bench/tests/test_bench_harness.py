"""The harness finds a cell by name from files alone, and refuses to run
without the chips a cell asks for."""
import json
import os
import shutil
import subprocess
import sys

from bench import harness
from bench.tests.conftest import REPO
from bench.tests.tiny import make_root

NEW_METRIC = '''"""Steps the window ran (a metric that exists only as this file)."""


def read(run):
    return float(run.record["steps"])
'''


def test_a_cell_made_of_new_files_runs_by_name(tmp_path):
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "tiny.window_steps", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": ["tiny-train-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "metrics").mkdir()
    (root / "bench" / "metrics" / "tiny.window_steps.py").write_text(NEW_METRIC)

    result, lines = harness.run_cell(root, "tiny-train-cell", 2**33 + 1, 1.0,
                                     False, require_chip=False)
    assert result["correct"] is True, lines
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(result)[-1] == "checks"
    checks = [line for line in lines if line.startswith("check ")]
    assert len(checks) == len(result["checks"]) and lines[-len(checks):] == checks

    names = [m["name"] for m in harness.layer_metrics(bench, "tiny-train-cell")]
    assert "tiny.window_steps" in names and "train_mfu" in names
    assert "serve_step_mfu" not in names
    reader = harness.find(root, "metrics", "tiny.window_steps")
    view = harness.RunView(cfg={}, mix={}, spec=None, trace=None, peaks={},
                           record={"steps": 7})
    assert reader.read(view) == 7.0
    # drivers and the repository's readers are found beside the harness
    assert harness.find(root, "drivers", "train").__name__ == "bench_drivers_train"


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "granite-train-2k",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_tpu_no_result():
    p = _run(REPO)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert "{" not in p.stdout
