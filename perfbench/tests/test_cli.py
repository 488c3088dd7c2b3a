"""The command line: no result without a TPU, none without the
program."""
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH_DIR, ROOT

ARGS = ["--workload", "gcn-b2.flickr.full", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, *argv, env=None):
    return subprocess.run([sys.executable, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_a_device_that_is_not_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(ROOT, "perfbench/run.py", *ARGS, env=env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_has_no_peaks():
    import pytest
    from harness import common
    with pytest.raises(KeyError):
        common.peaks_for("cpu")


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's own
    files holds no program to measure: past the look for a chip, the
    run fails and prints no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".jax_cache",
                                                  "__pycache__"))
    code = ("import sys; sys.path.insert(0, 'perfbench'); import run; "
            "from harness import common; "
            "cell = common.Cell(common.load_spec(), 'gcn-b2.flickr.full'); "
            "print(run.execute(cell, 1, 1.0, False, {}))")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = _run(tmp_path, "-c", code, env=env)
    assert p.returncode != 0
    assert "No module named 'repro'" in p.stderr
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")
