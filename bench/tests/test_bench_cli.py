"""``bench/run.py`` refuses to print a result where it cannot measure: on
a machine whose JAX finds no TPU, and in a directory that holds only the
benchmark's own files. ``--rehearse`` drives a whole run at tiny widths
on the CPU and prints no result line."""
import os
import shutil
import subprocess
import sys

from bench import common

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "train_fd_8k", "--seed", str(2**40 + 1),
        "--seconds", "1", "--trace", "0"]


def run(cwd, args=ARGS):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_no_tpu_no_result():
    p = run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_rehearsal_runs_the_harness_and_prints_no_result():
    cell = common.cell_names("train")[0]
    p = run(ROOT, ["--workload", cell, "--seed", "3", "--seconds", "1",
                   "--rehearse"])
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert f"rehearsal of {cell}" in p.stderr
    assert "check loss_gap" in p.stderr
