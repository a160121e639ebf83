"""``bench/run.py`` end to end on the CPU: the rehearsal at tiny sizes
with every kernel in Pallas interpret mode, and the refusal to measure
without a chip."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _run(*args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("workload,trace", [("phi3-mini.chat", "1"),
                                            ("phi3-mini.chat", "0")])
def test_rehearsal_runs_the_cell(workload, trace):
    r = _run("--workload", workload, "--seed", str(2**31 + 7),
             "--seconds", "2", "--trace", trace, "--rehearsal")
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True
    assert "metrics" not in line and "device" not in line
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # the compared numbers are the last lines of standard error
    tail = r.stderr.strip().splitlines()[-len(line["checks"]):]
    assert [t.split()[1] for t in tail] == list(line["checks"])


def test_no_chip_no_result():
    r = _run("--workload", "phi3-mini.chat", "--seed", "1", "--seconds",
             "1", "--trace", "0")
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_unknown_workload_no_result():
    r = _run("--workload", "nope", "--seed", "1", "--seconds", "1")
    assert r.returncode != 0 and r.stdout.strip() == ""


def test_alone_without_the_program_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's paths
    cannot run: the program under test is not there."""
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for p in spec["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run("--workload", "phi3-mini.chat", "--seed", "1", "--seconds",
             "1", "--rehearsal", cwd=tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""
