"""Without a TPU, and without the program beside it, a run exits non-zero
and prints no result line."""

import json
import os
import shutil
import subprocess
import sys

import run


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "onn506.batch", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def _printed_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_cpu_only_exits_nonzero():
    proc = _run(run.ROOT)
    assert proc.returncode != 0
    assert not _printed_result(proc.stdout)
    assert "needs a TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert not _printed_result(proc.stdout)
