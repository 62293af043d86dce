"""Off the chip the benchmark refuses: exit code not 0, no result line."""

import os
import shutil
import subprocess
import sys

from benchmark import cells

BENCH = cells.load_benchmark()


def run(cwd, *argv, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, *BENCH["command"][1].split("/")),
         *argv],
        cwd=cwd, env=dict(os.environ, **(env or {})), capture_output=True,
        text=True, timeout=300,
    )


def result_lines(proc) -> list:
    return [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_any_platform_but_tpu_exits_nonzero_and_prints_no_result():
    cell = BENCH["workloads"][0]["name"]
    proc = run(cells.REPO_ROOT, "--workload", cell, "--seed", str(2**31 + 7),
               "--seconds", "1", "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert not result_lines(proc)
    assert "no chip, no number" in proc.stderr


def test_an_unknown_cell_exits_nonzero_before_touching_jax():
    proc = run(cells.REPO_ROOT, "--workload", "no_such_cell", "--seed", "1",
               "--seconds", "1", "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0 and not result_lines(proc)


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths`: the system under test is not there to measure."""
    shutil.copy(os.path.join(cells.REPO_ROOT, "BENCHMARK.json"), tmp_path)
    for rel in BENCH["paths"]:
        shutil.copytree(os.path.join(cells.REPO_ROOT, rel), tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = run(str(tmp_path), "--workload", BENCH["workloads"][0]["name"],
               "--seed", "1", "--seconds", "1", "--trace", "0",
               env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0 and not result_lines(proc)
