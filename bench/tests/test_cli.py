"""Without a TPU the benchmark exits non-zero and prints no result."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]


def run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_no_tpu_means_a_nonzero_exit_and_no_result_line():
    res = run(ROOT, "--workload", "hymba.train.1chip", "--seed",
              str(2 ** 31 + 9), "--seconds", "10", "--trace", "0")
    assert res.returncode != 0
    assert no_result(res.stdout)
    assert "no TPU" in res.stderr


def test_an_unknown_cell_is_refused(tmp_path):
    res = run(ROOT, "--workload", "no.such.cell", "--seed", "1",
              "--seconds", "10", "--trace", "0")
    assert res.returncode != 0 and no_result(res.stdout)


def test_a_checkout_of_only_the_benchmark_files_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    res = run(tmp_path, "--workload", "hymba.train.1chip", "--seed", "3",
              "--seconds", "10", "--trace", "0")
    assert res.returncode != 0 and no_result(res.stdout)
