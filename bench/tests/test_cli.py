"""bench/run.py refuses to run without a TPU, and without the program."""
import os
import shutil
import subprocess
import sys

from harness import spec

ARGS = ["--workload", "smollm_chat_open", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = _run(spec.ROOT, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
