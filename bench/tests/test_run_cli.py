"""``run.py`` exits non-zero and prints no result where it cannot run on
the chip: on a host without a TPU, and in a checkout that holds only the
benchmark's files."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
ARGS = ["--workload", "adj64.search", "--seed", "3000000001",
        "--seconds", "1", "--trace", "0"]


def run_in(root: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *ARGS], cwd=root, env=env, capture_output=True,
                          text=True, timeout=300)


def test_no_tpu_no_result():
    out = run_in(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_in(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
