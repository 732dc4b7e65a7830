"""run.py off the chip, a cell added by data files alone, and a sound
run of every cell end to end at a tiny size."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_helpers as bh
from chipbench.common import BENCH_DIR, ROOT, load_json


def _run_py(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "elevators_gp.batch_score", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_py_refuses_without_a_tpu_and_prints_no_result():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "{" not in p.stdout
    assert "TPU" in p.stderr


def test_run_py_fails_in_a_tree_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run_py(tmp_path)
    assert p.returncode != 0 and "metrics" not in p.stdout


@pytest.mark.parametrize("workload", [w["name"] for w in load_json(
    ROOT / "BENCHMARK.json")["workloads"]])
def test_sound_run_is_correct(workload):
    res = bh.run(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    names = set(res["metrics"])
    assert "setup_s" in names and len(names) >= 2
    assert list(res)[-2:] == ["checks", "_lines"]
    assert all(v["value"] <= v["limit"] for v in res["checks"].values())
    json.dumps({k: v for k, v in res.items() if k != "_lines"})


def test_a_mix_added_by_files_alone_is_picked_up(tmp_path):
    bench = load_json(ROOT / "BENCHMARK.json")
    bench["workloads"].append({
        "name": "elevators_gp.tiny_mix", "config": "elevators_gp",
        "traffic": "tiny_mix", "chips": 1, "why": "a throwaway mix"})
    for m in bench["end_to_end"]:
        if m["name"] == "decisions_per_s":
            m["workloads"].append("elevators_gp.tiny_mix")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for sub in ("configs", "traffic", "limits"):
        (tmp_path / "chipbench" / sub).mkdir(parents=True)
    shutil.copy(BENCH_DIR / "configs" / "elevators_gp.json",
                tmp_path / "chipbench" / "configs")
    (tmp_path / "chipbench" / "traffic" / "tiny_mix.json").write_text(
        json.dumps({"loop": "closed_batch", "batch": 96, "judge_share": 0.5,
                    "threshold_presample": 16, "check_sample": 64,
                    "check_hardest": 8}))
    shutil.copy(BENCH_DIR / "limits" / "elevators_gp.batch_score.json",
                tmp_path / "chipbench" / "limits"
                / "elevators_gp.tiny_mix.json")
    res = bh.run("elevators_gp.tiny_mix", root=tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    assert any("median of 16 float64 truths" in line for line in res["_lines"])
    assert "decisions_per_s" in res["metrics"]

