"""The harness finds everything by name: a new configuration, mix or
metric is files and entries only; and it never runs on the CPU."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _load(path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(f"copy_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_benchmark_json_names_only_what_exists():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"]: c for c in spec["configs"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    for w in cells.values():
        cfg = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
        assert cfg["name"] == w["config"]
        assert (BENCH / "families" / f"{cfg['family']}.py").is_file()
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in spec["per_layer"]:
        run = _load(BENCH / "run.py")
        assert callable(run.reader(BENCH / "metrics", m["name"]))
        moved = e2e[m["moves"]]
        for c in m.get("workloads", cells):
            assert c in cells
            assert c in moved.get("workloads", cells), (m["name"], c)


def test_a_new_config_mix_and_metric_are_found_from_files_alone(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "tests",
                                                  "__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "resnet50-224.json").read_text())
    cfg["name"] = "resnet50-112"
    cfg["image"] = 112
    (b / "configs" / "resnet50-112.json").write_text(json.dumps(cfg))
    (b / "traffic" / "trickle.json").write_text(json.dumps(
        {"loop": "open", "rate_per_s": 3.0, "rows": {"1": 2, "4": 1}}))
    (b / "metrics" / "answer.per_s.py").write_text(
        "def read(r):\n    return r['window']['n_batches'] / 2\n")
    spec["configs"].append({"name": "resnet50-112", "source": "s",
                            "file": "bench/configs/resnet50-112.json",
                            "reduced": ["image"], "why": "w"})
    spec["workloads"].append({"name": "resnet50-112.trickle",
                              "config": "resnet50-112",
                              "traffic": "trickle", "chips": 1, "why": "w"})
    spec["per_layer"].append({"name": "answer.per_s", "unit": "1/s",
                              "better": "higher", "source": "program_counter",
                              "layer": "serving", "moves": "setup_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    run = _load(b / "run.py")
    cell, got, mix, family, metrics = run.resolve(
        tmp_path, "resnet50-112.trickle", True)
    assert cell["traffic"] == "trickle" and got["image"] == 112
    assert mix["rows"] == {1: 2, 4: 1} and mix["rate_per_s"] == 3.0
    assert Path(family.__file__) == b / "families" / "cnn.py"
    names = [m["name"] for m in metrics]
    assert names == ["answer.per_s"]      # the others list their cells
    assert metrics[0]["read"]({"window": {"n_batches": 8}}) == 4


@pytest.mark.parametrize("workload", ["resnet50.bulk-mixed",
                                      "no-such-cell"])
def test_without_a_tpu_the_run_fails_and_prints_no_result(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"),
                        "--workload", workload, "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
