"""The benchmark's command, off the chip: it names the platform it found,
exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

from benchutil import REPO

ARGS = ["--workload", "qwen2-1.5b.longgen", "--seed", str(2 ** 31 + 3),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env or {})
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=cwd,
                          env=e, capture_output=True, text=True, timeout=120)


def test_no_tpu_exits_nonzero_naming_the_platform(tmp_path):
    p = _run(REPO, {"HOME": str(tmp_path)})
    assert p.returncode == 1
    assert "'cpu'" in p.stderr and "not a TPU" in p.stderr
    assert p.stdout == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory with BENCHMARK.json and the benchmark's own paths but no
    program: no result."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "not in this checkout" in p.stderr


def test_command_and_paths_are_the_benchmarks_own():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    assert bench["command"] == ["python3", "bench/run.py"]
    for p in bench["paths"]:
        assert (REPO / p).is_dir()
    assert bench["command"][1].startswith(bench["paths"][0] + "/")
