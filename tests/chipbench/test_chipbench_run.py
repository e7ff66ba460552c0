"""``chipbench/run.py`` measures on a TPU or not at all: on a CPU, and in a
directory that holds only the benchmark's own files, it exits non-zero and
prints no result line."""
import json
import os
import shutil
import subprocess
import sys

import chipbench_testkit as kit

ROOT = kit.ROOT


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen3_train_rollback", "--seed", str(2**31 + 12345), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        assert "correct" not in doc


def test_refuses_to_measure_on_a_cpu():
    r = _run(ROOT, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    _no_result(r.stdout)


def test_refuses_without_the_system_under_test(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    r = _run(tmp_path, env)
    assert r.returncode != 0
    _no_result(r.stdout)
