"""The harness is driven by data, and refuses to measure without a chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.util import BENCH, REPO

SPEC = json.loads((REPO / "BENCHMARK.json").read_text())


def test_new_workload_file_is_listed_without_code(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    assert "qwen2-0.5b.throwaway" not in harness.list_workloads(bench)
    w = json.loads((bench / "workloads" / "qwen2-0.5b.chat-open.json")
                   .read_text())
    w["traffic"] = "throwaway"
    (bench / "workloads" / "qwen2-0.5b.throwaway.json").write_text(
        json.dumps(w))
    mix = json.loads((bench / "traffic" / "chat-open.json").read_text())
    mix["rate_per_s"] = 0.5
    (bench / "traffic" / "throwaway.json").write_text(json.dumps(mix))
    assert "qwen2-0.5b.throwaway" in harness.list_workloads(bench)
    cell = harness.load_cell("qwen2-0.5b.throwaway", bench)
    assert cell.traffic["rate_per_s"] == 0.5 and cell.chips == 1
    # metrics with no `workloads` list apply to every cell
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]


def test_every_cell_has_its_files_and_metrics():
    names = {w["name"] for w in SPEC["workloads"]}
    assert names <= set(harness.list_workloads())
    for w in SPEC["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.chips == w["chips"]
        assert cell.workload["config"] == w["config"]
        assert cell.workload["traffic"] == w["traffic"]
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert (BENCH / "drivers" / f"{cell.workload['driver']}.py").is_file()
    for c in SPEC["configs"]:
        assert (REPO / c["file"]).is_file()


def test_unknown_device_kind_raises():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        harness.load_peaks("TPU v99 imaginary")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2-0.5b.train-2k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_accelerator_exits_nonzero_with_no_result():
    p = _run(REPO)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())


def test_benchmark_alone_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
