"""Without a TPU the benchmark exits non-zero and prints no result; in a
directory with only BENCHMARK.json and the benchmark's files, too."""
import os
import shutil
import subprocess
import sys

import common

ARGS = ["--workload", "qwen3-4b.docqa-chat", "--seed", "3000000001",
        "--seconds", "10", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(common.ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_fail(tmp_path):
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
