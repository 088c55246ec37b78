"""The command's behaviour where there is nothing to measure on: no TPU,
no such cell.  (The measuring path has no CPU mode, so what a run prints
with a chip is held to the contract on the chip, not here.)"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(*args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return subprocess.run(
        [sys.executable if w == "python3" else w for w in bench["command"]]
        + list(args), cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def _result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and CONTRACT_KEYS <= set(obj):
            out.append(obj)
    return out


@pytest.mark.parametrize("cell", ["opt13b-serve-chat", "opt67b-zero3-4chip"])
def test_without_a_tpu_it_fails_and_prints_no_result(cell):
    p = _run("--workload", cell, "--seed", "3000000019", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0
    assert "no TPU" in p.stderr and "no CPU mode" in p.stderr
    assert _result_lines(p.stdout) == []
    assert "memory_peak_bytes" not in p.stdout and "busy_s" not in p.stdout


def test_an_unknown_cell_fails_with_the_known_names():
    p = _run("--workload", "no-such-cell", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert p.returncode != 0 and _result_lines(p.stdout) == []
    assert "opt13b-serve-chat" in p.stderr


def test_in_a_bare_checkout_it_fails_and_prints_no_result(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: there is no
    program to measure."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run("--workload", "opt13b-sft-1chip", "--seed", "1", "--seconds",
             "1", "--trace", "0", cwd=str(tmp_path))
    assert p.returncode != 0 and _result_lines(p.stdout) == []
    assert "deepspeed_tpu" in p.stderr
