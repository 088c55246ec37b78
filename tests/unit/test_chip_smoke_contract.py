"""``chip_smoke.py``'s output contract, checked where there is no chip.

The driver reads the LAST line of the script's stdout and refuses anything
but one JSON object with exactly the contract's keys (PR 21 was refused for
this).  The CPU rehearsal runs every phase at toy size through the same
code, so it proves the shape of that line, that nothing — the library
logger, a server thread, teardown — writes to stdout after it, and that a
run without a TPU can never be read as a pass.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               # its own cache: never the suite's (see fault_driver.py)
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)          # the suite's 8 virtual devices
    return subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=600)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    return _run("--rehearse", tmp_path=tmp_path_factory.mktemp("smoke"))


def test_rehearsal_passes_but_never_reports_ok(rehearsal):
    assert rehearsal.returncode != 0, "a run without a TPU must exit non-zero"
    lines = rehearsal.stdout.splitlines()
    assert any("rehearsal passed" in line for line in lines[:-1]), \
        rehearsal.stdout[-2000:] + rehearsal.stderr[-4000:]
    assert '"ok": true' not in rehearsal.stdout
    phases = [json.loads(line).get("phase") for line in lines[:-1]]
    assert {"env", "fence", "serve", "train", "total"} <= set(phases)


def test_last_stdout_line_is_exactly_the_contract(rehearsal):
    last = json.loads(rehearsal.stdout.splitlines()[-1])
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == 1
    assert rehearsal.stdout.endswith("\n")


def test_library_logger_stays_off_stdout(rehearsal):
    # every stdout line is one JSON object; the logger's lines (which this
    # run certainly produced) are all on stderr
    for line in rehearsal.stdout.splitlines():
        assert isinstance(json.loads(line), dict), line
    assert "[DeepSpeedTPU]" not in rehearsal.stdout
    assert "[DeepSpeedTPU]" in rehearsal.stderr


def test_default_run_without_tpu_prints_no_result(tmp_path):
    proc = _run(tmp_path=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_launchers_import_without_creating_a_backend():
    """One process per chip: a parent that has created a JAX backend holds
    the chip, and the child it spawns then fails or hangs.  The launchers
    import the package (and so jax) but must not touch a device."""
    code = ("import deepspeed_tpu.launcher.runner, "
            "deepspeed_tpu.elasticity.elastic_agent, "
            "deepspeed_tpu.autotuning.autotuner; "
            "from jax._src import xla_bridge; "
            "assert not xla_bridge._backends, 'launcher created a backend'")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
