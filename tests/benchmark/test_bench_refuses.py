"""The benchmark command refuses to measure where it cannot: no result
line and a non-zero exit on a CPU-only backend, and in a directory that
holds only BENCHMARK.json and the benchmark's own files."""
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "internlm2-1.8b.fedtrain", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *ARGS], cwd=cwd,
        env=env, capture_output=True, text=True, timeout=300)


def test_cpu_only_backend_gives_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_path_knob_gives_no_result():
    p = _run(ROOT, {"REPRO_EFF_PATH": "1"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "path knobs" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no program" in p.stderr
