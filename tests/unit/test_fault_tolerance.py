"""Fault-tolerance tests — crash-atomic checkpoints, fault injection,
auto-resume (docs/fault_tolerance.md).

The centerpiece is the kill-and-resume proof: a subprocess driver
(``fault_driver.py``) is killed via ``os._exit`` at every registered
checkpoint injection seam, relaunched, and its merged loss trajectory must
be bitwise-identical to an uninterrupted run — the property that makes
preemptible TPU capacity usable for training at all.
"""

import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.runtime.fault import inject
from deepspeed_tpu.runtime.fault.manifest import (
    MANIFEST_NAME, build_manifest, gc_checkpoints, list_tags,
    newest_valid_tag, read_manifest, verify_manifest, write_manifest)
from deepspeed_tpu.runtime.fault.retry import backoff_delay, retry_call
from deepspeed_tpu.runtime.fault.supervisor import (run_resilient,
                                                    elastic_resume_config)
from simple_model import SimpleModel, random_batch

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
DRIVER = os.path.join(REPO, "tests", "unit", "fault_driver.py")


@pytest.fixture(autouse=True)
def _disarm_injection():
    inject.reset_injection()
    yield
    inject.reset_injection()


def fault_config(**over):
    fault = {"enabled": True, "checksum": "crc32",
             "backoff_base_secs": 0.01, "backoff_max_secs": 0.05}
    fault.update(over.pop("fault", {}))
    cfg = {
        "train_micro_batch_size_per_gpu": 2,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": 2},
        "seed": 7,
        "fault": fault,
    }
    cfg.update(over)
    return cfg


def make_engine(**over):
    engine, *_ = deepspeed_tpu.initialize(model=SimpleModel(hidden_dim=16),
                                          config=fault_config(**over))
    return engine


def train_steps(engine, n):
    for _ in range(n):
        loss = engine(random_batch(batch_size=16, seed=engine.global_steps))
        engine.backward(loss)
        engine.step()
    return loss


def fresh_engine(**over):
    from deepspeed_tpu.parallel.topology import reset_topology
    reset_topology()
    return make_engine(**over)


# --------------------------------------------------------------------- #
# Manifest + atomic primitives
# --------------------------------------------------------------------- #
def test_manifest_build_verify_corrupt(tmp_path):
    d = tmp_path / "tag1"
    (d / "sub").mkdir(parents=True)
    (d / "a.bin").write_bytes(b"x" * 1000)
    (d / "sub" / "b.bin").write_bytes(b"y" * 500)
    m = build_manifest(str(d), "tag1", step_meta={"global_steps": 3})
    write_manifest(str(d), m)
    assert set(m["files"]) == {"a.bin", os.path.join("sub", "b.bin")}
    assert read_manifest(str(d))["step"]["global_steps"] == 3
    assert verify_manifest(str(d)) == []
    # same-size corruption: only the checksum notices
    with open(d / "sub" / "b.bin", "r+b") as f:
        f.seek(100)
        f.write(b"Z")
    assert verify_manifest(str(d), deep=False) == []
    problems = verify_manifest(str(d), deep=True)
    assert len(problems) == 1 and "b.bin" in problems[0]
    # truncation: the shallow size scan catches it
    with open(d / "a.bin", "r+b") as f:
        f.truncate(10)
    assert any("a.bin" in p for p in verify_manifest(str(d), deep=False))
    # a missing manifest is its own problem
    os.remove(d / MANIFEST_NAME)
    assert verify_manifest(str(d)) == [f"{MANIFEST_NAME} missing or "
                                       "unparseable"]


def test_newest_valid_tag_walkback(tmp_path):
    for i, tag in enumerate(["global_step2", "global_step4"]):
        d = tmp_path / tag
        d.mkdir()
        (d / "data.bin").write_bytes(bytes(100 + i))
        write_manifest(str(d), build_manifest(
            str(d), tag, step_meta={"global_steps": 2 * (i + 1)}))
    assert newest_valid_tag(str(tmp_path)) == "global_step4"
    # corrupt the newest -> walk back
    with open(tmp_path / "global_step4" / "data.bin", "r+b") as f:
        f.seek(0)
        f.write(b"\xff")
    assert newest_valid_tag(str(tmp_path)) == "global_step2"
    # staging orphans are never candidates
    (tmp_path / "global_step9.tmp").mkdir()
    assert newest_valid_tag(str(tmp_path)) == "global_step2"


def test_backoff_delay_capped_and_jittered():
    assert backoff_delay(1, base=1.0, jitter=0.0) == 1.0
    assert backoff_delay(4, base=1.0, max_delay=5.0, jitter=0.0) == 5.0
    d = backoff_delay(2, base=1.0, jitter=0.5)
    assert 2.0 <= d <= 3.0
    # deterministic for a fixed (attempt, pid)
    assert d == backoff_delay(2, base=1.0, jitter=0.5)


def test_permanent_os_errors_not_retried():
    """A typo'd path or permissions problem does not heal with backoff:
    retry_call re-raises permanent errno classes immediately."""
    calls = []

    def missing():
        calls.append(1)
        raise FileNotFoundError("no such file")

    with pytest.raises(FileNotFoundError):
        retry_call(missing, retries=3, base=0.0, jitter=0.0)
    assert len(calls) == 1, "permanent errors must not be retried"


def test_supervisor_surfaces_permanent_step_errors(tmp_path):
    """A deterministic FileNotFoundError inside step_fn is a BUG — the
    supervisor must surface it, not mask it behind resume churn."""
    engine = make_engine()
    train_steps(engine, 1)

    def broken_step(engine):
        raise FileNotFoundError("/nonexistent/data.bin")

    with pytest.raises(FileNotFoundError):
        run_resilient(engine, broken_step, str(tmp_path), max_steps=3)


def test_side_tags_only_dir_is_fresh_start(tmp_path):
    """A directory holding ONLY save_latest=False side checkpoints is a
    fresh start for auto-resume (warn + nothing loaded), not a 'no valid
    checkpoint' crash."""
    engine = make_engine()
    train_steps(engine, 2)
    engine.save_checkpoint(str(tmp_path), tag="debug_only",
                           save_latest=False)
    e2 = fresh_engine()
    path, state = e2.load_checkpoint(str(tmp_path))
    assert path is None and state == {}
    # run_resilient on the same dir trains from scratch instead of dying
    status, info = run_resilient(e2, _step_fn, str(tmp_path), max_steps=2)
    assert status == "done" and e2.global_steps == 2


def test_retry_call_bounded():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise IOError("transient")
        return "ok"

    assert retry_call(flaky, retries=3, base=0.0, jitter=0.0) == "ok"
    assert len(calls) == 3
    calls.clear()
    with pytest.raises(IOError):
        retry_call(flaky, retries=1, base=0.0, jitter=0.0)
    assert len(calls) == 2  # 1 call + 1 retry, then give up


# --------------------------------------------------------------------- #
# Engine checkpoint protocol
# --------------------------------------------------------------------- #
def test_atomic_save_layout_and_latest(tmp_path):
    engine = make_engine()
    train_steps(engine, 2)
    engine.save_checkpoint(str(tmp_path))
    names = sorted(os.listdir(tmp_path))
    assert names == ["global_step2", "latest"]
    assert (tmp_path / "latest").read_text() == "global_step2"
    # no staging or temp droppings anywhere
    for dirpath, dirnames, filenames in os.walk(tmp_path):
        for n in dirnames + filenames:
            assert ".tmp" not in n and ".old." not in n, n
    assert verify_manifest(str(tmp_path / "global_step2")) == []
    fp = read_manifest(str(tmp_path / "global_step2"))["fingerprint"]
    assert fp["device_count"] == jax.device_count()


def test_load_missing_arrays_is_clear_error_not_typeerror(tmp_path):
    """Satellite: the seed indexed arrays["module"] with arrays=None and
    died on a TypeError when the 'arrays' dir was missing.  The error is
    CheckpointCorrupt (NOT an OSError): the retry policy treats OSErrors
    as transient, and this condition is permanent."""
    import shutil
    from deepspeed_tpu.runtime.fault.manifest import CheckpointCorrupt
    engine = make_engine(fault={"enabled": False})
    train_steps(engine, 1)
    engine.save_checkpoint(str(tmp_path))
    shutil.rmtree(tmp_path / "global_step1" / "state" / "arrays")
    e2 = fresh_engine(fault={"enabled": False})
    with pytest.raises(CheckpointCorrupt, match="arrays"):
        e2.load_checkpoint(str(tmp_path))


def test_reserved_tag_names_rejected(tmp_path):
    """Tags colliding with the staging namespace would be destroyed by
    the next GC pass — save refuses them up front."""
    engine = make_engine()
    train_steps(engine, 1)
    with pytest.raises(ValueError, match="staging namespace"):
        engine.save_checkpoint(str(tmp_path), tag="run1.tmp")
    with pytest.raises(ValueError, match="staging namespace"):
        engine.save_checkpoint(str(tmp_path), tag="v1.old.2")


def test_save_latest_false_tags_do_not_hijack_resume(tmp_path):
    """A side checkpoint saved with save_latest=False (debug dump) must
    not be picked by auto-resume even though it is newer."""
    engine = make_engine()
    train_steps(engine, 2)
    engine.save_checkpoint(str(tmp_path))                      # step 2
    train_steps(engine, 2)
    engine.save_checkpoint(str(tmp_path), tag="debug_dump",
                           save_latest=False)                  # step 4
    e2 = fresh_engine()
    e2.load_checkpoint(str(tmp_path))
    assert e2.global_steps == 2, \
        "auto-resume must skip advance_latest=false tags"
    # the side tag stays explicitly loadable
    e3 = fresh_engine()
    e3.load_checkpoint(str(tmp_path), tag="debug_dump")
    assert e3.global_steps == 4


def test_corrupt_and_partial_tags_walk_back_on_load(tmp_path):
    """Acceptance: a corrupted-shard checkpoint is detected by manifest
    verification and load falls back to the previous valid tag; a
    data-partial tag (missing arrays) walks back the same way."""
    import shutil
    engine = make_engine()
    train_steps(engine, 2)
    engine.save_checkpoint(str(tmp_path))
    w2 = np.asarray(jax.tree.leaves(engine.params)[0], np.float32)
    train_steps(engine, 2)
    engine.save_checkpoint(str(tmp_path))

    # corrupt one array shard of the newest tag (size-preserving)
    newest = tmp_path / "global_step4"
    target, size = None, -1
    for dirpath, _d, filenames in os.walk(newest / "state" / "arrays"):
        for n in filenames:
            p = os.path.join(dirpath, n)
            if os.path.getsize(p) > size:
                target, size = p, os.path.getsize(p)
    with open(target, "r+b") as f:
        f.seek(size // 2)
        f.write(b"\xde\xad\xbe\xef")

    e2 = fresh_engine()
    e2.load_checkpoint(str(tmp_path))
    assert e2.global_steps == 2, "load must walk back to global_step2"
    np.testing.assert_array_equal(
        np.asarray(jax.tree.leaves(e2.params)[0], np.float32), w2)

    # now ALSO gut the older tag's arrays -> no valid tag at all
    shutil.rmtree(tmp_path / "global_step2" / "state" / "arrays")
    with open(target, "r+b") as f:   # keep newest corrupt
        f.seek(0)
        f.write(b"\xff")
    e3 = fresh_engine()
    with pytest.raises(RuntimeError, match="no valid checkpoint"):
        e3.load_checkpoint(str(tmp_path))


def test_transient_save_ioerror_retries(tmp_path):
    engine = make_engine()
    train_steps(engine, 1)
    specs = inject.configure_injection(
        {"point": "ckpt.save_io", "action": "raise", "times": 2})
    assert engine.save_checkpoint(str(tmp_path)) is True
    assert specs[0].fired == 2, "save must have retried through 2 faults"
    assert verify_manifest(str(tmp_path / "global_step1")) == []


def test_keep_last_n_retention_and_orphan_gc(tmp_path):
    engine = make_engine(fault={"keep_last_n": 2})
    (tmp_path / "global_step99.tmp").mkdir(parents=True)  # stale orphan
    for _ in range(4):
        train_steps(engine, 1)
        engine.save_checkpoint(str(tmp_path))
    tags = list_tags(str(tmp_path))
    assert tags == ["global_step4", "global_step3"]
    assert not (tmp_path / "global_step99.tmp").exists()
    assert (tmp_path / "latest").read_text() == "global_step4"


def test_explicit_tag_failure_raises_not_walks_back(tmp_path):
    """An explicitly requested tag that fails verification must raise —
    silently substituting an older tag's weights would poison evals;
    walk-back is the auto-resume (tag=None) contract only."""
    from deepspeed_tpu.runtime.fault.manifest import CheckpointCorrupt
    engine = make_engine()
    train_steps(engine, 2)
    engine.save_checkpoint(str(tmp_path))
    train_steps(engine, 2)
    engine.save_checkpoint(str(tmp_path))
    target, size = None, -1
    for dirpath, _d, filenames in os.walk(
            tmp_path / "global_step4" / "state" / "arrays"):
        for n in filenames:
            p = os.path.join(dirpath, n)
            if os.path.getsize(p) > size:
                target, size = p, os.path.getsize(p)
    with open(target, "r+b") as f:
        f.seek(size // 2)
        f.write(b"\xbe\xef")
    e2 = fresh_engine()
    with pytest.raises(CheckpointCorrupt, match="global_step4"):
        e2.load_checkpoint(str(tmp_path), tag="global_step4")
    # auto-resume still walks back fine
    e2.load_checkpoint(str(tmp_path))
    assert e2.global_steps == 2


def test_gc_never_deletes_last_valid_tag(tmp_path):
    """Retention must not leave the directory without a loadable
    checkpoint: when corrupt newer tags outrank a valid older one, the
    newest valid tags survive too."""
    for step in (2, 4):
        d = tmp_path / f"global_step{step}"
        d.mkdir()
        (d / "data.bin").write_bytes(b"x" * 64)
        write_manifest(str(d), build_manifest(
            str(d), d.name, step_meta={"global_steps": step}))
    # newest tag truncated -> invalid (shallow-detectable)
    with open(tmp_path / "global_step4" / "data.bin", "r+b") as f:
        f.truncate(3)
    removed = gc_checkpoints(str(tmp_path), keep_last_n=1)
    assert "global_step2" not in removed
    assert newest_valid_tag(str(tmp_path)) == "global_step2"


def test_gc_restores_orphaned_backup(tmp_path):
    """A same-tag re-publish that dies between moving the old tag aside
    and promoting the new one leaves only <tag>.old.<pid> — GC must
    restore the valid backup, never delete the only copy; the dry-run
    plan must match."""
    d = tmp_path / "global_step2.old.1234"
    d.mkdir()
    (d / "data.bin").write_bytes(b"y" * 32)
    write_manifest(str(d), build_manifest(
        str(d), "global_step2", step_meta={"global_steps": 2}))
    plan = gc_checkpoints(str(tmp_path), keep_last_n=0, dry_run=True)
    assert plan == ["restore:global_step2.old.1234"]
    assert list_tags(str(tmp_path)) == []          # dry run touched nothing
    actions = gc_checkpoints(str(tmp_path), keep_last_n=0)
    assert actions == plan, "dry-run plan must match the real run"
    assert list_tags(str(tmp_path)) == ["global_step2"]
    assert verify_manifest(str(tmp_path / "global_step2")) == []


def test_gc_collects_stray_tmp_files(tmp_path):
    """A crashed atomic_write_bytes leaves '<file>.tmp.<pid>' — the
    orphan pass collects files too, not just staging dirs."""
    (tmp_path / "latest.tmp.4242").write_text("global_step9")
    (tmp_path / "latest").write_text("global_step1")
    d = tmp_path / "global_step1"
    d.mkdir()
    (d / "f").write_bytes(b"z")
    write_manifest(str(d), build_manifest(
        str(d), "global_step1", step_meta={"global_steps": 1}))
    actions = gc_checkpoints(str(tmp_path), keep_last_n=0)
    assert actions == ["latest.tmp.4242"]
    assert not (tmp_path / "latest.tmp.4242").exists()
    assert (tmp_path / "latest").read_text() == "global_step1"


def test_gc_checkpoints_protects(tmp_path):
    for step in (1, 2, 3):
        d = tmp_path / f"global_step{step}"
        d.mkdir()
        (d / "f").write_bytes(b"z")
        write_manifest(str(d), build_manifest(
            str(d), d.name, step_meta={"global_steps": step}))
    removed = gc_checkpoints(str(tmp_path), keep_last_n=1,
                             protect=("global_step1",))
    assert sorted(removed) == ["global_step2"]
    assert sorted(list_tags(str(tmp_path))) == ["global_step1",
                                                "global_step3"]


# --------------------------------------------------------------------- #
# Checkpoint engine ordering (satellites)
# --------------------------------------------------------------------- #
def test_orbax_meta_write_is_atomic(tmp_path):
    from deepspeed_tpu.runtime.checkpoint_engine.checkpoint_engine import \
        OrbaxCheckpointEngine
    eng = OrbaxCheckpointEngine()
    eng.save(None, {"k": 1}, str(tmp_path / "state"))
    files = os.listdir(tmp_path / "state")
    assert "meta.pkl" in files
    assert not any(".tmp" in f for f in files)


def test_nebula_async_meta_lands_only_at_commit(tmp_path):
    """Satellite: async save must not leave a metadata-complete but
    data-incomplete checkpoint — meta.pkl durability is established at
    commit(), after the array shards'."""
    import jax.numpy as jnp
    from deepspeed_tpu.runtime.checkpoint_engine.checkpoint_engine import \
        NebulaCheckpointEngine
    eng = NebulaCheckpointEngine()
    arrays = {"module": {"w": jnp.arange(8, dtype=jnp.float32)}}
    path = str(tmp_path / "state")
    eng.save(arrays, {"global_steps": 5}, path)
    assert not os.path.exists(os.path.join(path, "meta.pkl")), \
        "meta.pkl must not exist before commit() in async mode"
    eng.commit("tag")
    assert os.path.exists(os.path.join(path, "meta.pkl"))
    loaded, meta = eng.load(path)
    assert meta["global_steps"] == 5
    np.testing.assert_array_equal(np.asarray(loaded["module"]["w"]),
                                  np.arange(8, dtype=np.float32))


# --------------------------------------------------------------------- #
# Supervisor: preemption, hang watchdog, resume
# --------------------------------------------------------------------- #
def _step_fn(engine):
    loss = engine(random_batch(batch_size=16, seed=engine.global_steps))
    engine.backward(loss)
    engine.step()
    return float(jax.device_get(loss))


def _reference_losses(n):
    engine = fresh_engine()
    return [_step_fn(engine) for _ in range(n)]


def test_run_resilient_plain_completion_and_resume(tmp_path):
    engine = make_engine()
    status, info = run_resilient(engine, _step_fn, str(tmp_path),
                                 max_steps=3, save_interval=2)
    assert status == "done" and info["steps"] == 3
    assert newest_valid_tag(str(tmp_path)) == "global_step3"
    # a restarted process resumes from the final checkpoint and runs the
    # remaining steps only
    e2 = fresh_engine()
    status, info = run_resilient(e2, _step_fn, str(tmp_path), max_steps=5)
    assert status == "done" and e2.global_steps == 5


def test_run_resilient_sigterm_preempt_then_resume_bitwise(tmp_path):
    losses = {}

    def recording_step(engine):
        step = engine.global_steps + 1
        losses[step] = _step_fn(engine)

    engine = make_engine()
    inject.configure_injection(
        {"point": "train.step_begin", "action": "sigterm", "at": 3})
    status, info = run_resilient(engine, recording_step, str(tmp_path),
                                 max_steps=6, save_interval=10)
    assert status == "preempted"
    assert engine.global_steps == 3
    tags = list_tags(str(tmp_path))
    assert any(t.startswith("preempt_") for t in tags), tags
    inject.reset_injection()

    # resume in a fresh engine (simulated restart) and finish
    e2 = fresh_engine()
    status, info = run_resilient(e2, recording_step, str(tmp_path),
                                 max_steps=6, save_interval=10)
    assert status == "done" and e2.global_steps == 6
    ref = _reference_losses(6)
    assert [losses[s] for s in range(1, 7)] == ref, \
        "resumed trajectory must be bitwise-identical to uninterrupted"


def test_run_resilient_hang_watchdog_recovers(tmp_path):
    losses = {}

    def recording_step(engine):
        step = engine.global_steps + 1
        losses[step] = _step_fn(engine)

    engine = make_engine(fault={"heartbeat_timeout_secs": 1.0})
    # step 1 runs OUTSIDE the supervisor: it pays the XLA compile, which
    # would otherwise trip a 1s heartbeat (production: warm up first or
    # size heartbeat_timeout_secs to cover the worst compile)
    recording_step(engine)
    inject.configure_injection(
        {"point": "train.step_begin", "action": "hang", "at": 2,
         "times": 1, "seconds": 30})
    status, info = run_resilient(engine, recording_step, str(tmp_path),
                                 max_steps=4, save_interval=1)
    assert status == "done", info
    assert info["hangs"] == 1 and info["resumes"] >= 1
    assert any(t.startswith("hang_step") for t in list_tags(str(tmp_path)))
    assert [losses[s] for s in range(1, 5)] == _reference_losses(4)


def test_run_resilient_transient_step_fault_reloads(tmp_path):
    engine = make_engine()
    inject.configure_injection(
        {"point": "train.step_begin", "action": "raise", "at": 3,
         "times": 1})
    status, info = run_resilient(engine, _step_fn, str(tmp_path),
                                 max_steps=4, save_interval=2)
    assert status == "done" and info["resumes"] == 1
    assert engine.global_steps == 4


def test_run_resilient_gives_up_after_max_resumes(tmp_path):
    engine = make_engine(fault={"max_resumes": 2})
    inject.configure_injection(
        {"point": "train.step_begin", "action": "raise", "at": 2,
         "times": 0})                      # every step from 2 on faults
    status, info = run_resilient(engine, _step_fn, str(tmp_path),
                                 max_steps=10, save_interval=1)
    assert status == "failed"
    assert info["resumes"] == 2


def test_elastic_resume_config_preserves_global_batch():
    cfg = {
        "elasticity": {"enabled": True, "max_train_batch_size": 64,
                       "micro_batch_sizes": [2, 4], "min_gpus": 1,
                       "max_gpus": 64, "version": 0.1},
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
    }
    c8 = elastic_resume_config(cfg, world_size=8)
    c4 = elastic_resume_config(cfg, world_size=4)
    assert c8["train_batch_size"] == c4["train_batch_size"]
    for c, w in ((c8, 8), (c4, 4)):
        assert c["train_micro_batch_size_per_gpu"] * \
            c["gradient_accumulation_steps"] * w == c["train_batch_size"]
    # no elasticity block -> unchanged
    assert elastic_resume_config({"train_batch_size": 16}) == \
        {"train_batch_size": 16}


# --------------------------------------------------------------------- #
# The kill-and-resume proof (subprocess: os._exit at every seam)
# --------------------------------------------------------------------- #
KILL_POINTS = (
    "ckpt.arrays_write",        # mid-save: data written, metadata absent
    "ckpt.before_manifest",     # staging complete, manifest absent
    "ckpt.before_commit_rename",  # manifest durable, tag not promoted
    "ckpt.before_latest_swap",  # tag promoted, pointer still on previous
)


def _run_driver(ckpt_dir, losses_path, inject_spec=None, max_steps=6,
                save_interval=2):
    env = dict(os.environ)
    env["DSTPU_REPO_ROOT"] = REPO
    # drivers get their own compile cache (shared across the launches of
    # one scenario, isolated from the suite's): an os._exit mid-cache-
    # write would otherwise poison the suite's cache for every later
    # process (native abort loading the truncated executable)
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        os.path.dirname(str(ckpt_dir)), ".jax_driver_cache")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0.5"
    env.pop("DSTPU_FAULT_INJECT", None)
    env.pop("BENCH_MODEL", None)
    if inject_spec:
        env["DSTPU_FAULT_INJECT"] = inject_spec
    return subprocess.run(
        [sys.executable, DRIVER, "--ckpt-dir", str(ckpt_dir),
         "--max-steps", str(max_steps), "--save-interval",
         str(save_interval), "--losses", str(losses_path)],
        env=env, capture_output=True, text=True, timeout=240)


def _merged_losses(path):
    """step -> last recorded loss repr (a resumed run re-records the steps
    it replays; last write wins and must equal the first bitwise)."""
    out = {}
    with open(path) as f:
        for line in f:
            step, _, loss = line.strip().partition(",")
            out[int(step)] = loss
    return out


def test_kill_at_every_seam_resumes_bitwise(tmp_path):
    """Acceptance: with fault injection killing the run at EACH registered
    checkpoint seam (including mid-arrays write and pre-latest swap),
    run_resilient restarts from the newest valid checkpoint and the
    resumed loss trajectory is bitwise-identical to an uninterrupted run
    (CPU, fixed seeds)."""
    ref_dir = tmp_path / "ref"
    ref_losses = ref_dir / "losses.txt"
    ref_dir.mkdir()
    proc = _run_driver(ref_dir / "ckpt", ref_losses)
    assert proc.returncode == 0, proc.stderr[-3000:]
    ref = _merged_losses(ref_losses)
    assert sorted(ref) == [1, 2, 3, 4, 5, 6]

    for point in KILL_POINTS:
        d = tmp_path / point.replace(".", "_")
        d.mkdir()
        losses = d / "losses.txt"
        # the SECOND save (step 4) dies: step-2 state is committed, the
        # kill lands in the middle of writing step 4's checkpoint
        proc = _run_driver(d / "ckpt", losses,
                           inject_spec=f"point={point},action=exit,at=2")
        assert proc.returncode == 17, \
            f"{point}: expected injected exit, got rc={proc.returncode}\n" \
            + proc.stderr[-3000:]
        # relaunch clean: resume from the newest valid checkpoint
        proc = _run_driver(d / "ckpt", losses)
        assert proc.returncode == 0, \
            f"{point}: resume failed\n" + proc.stderr[-3000:]
        got = _merged_losses(losses)
        assert got == ref, \
            f"{point}: resumed trajectory diverged from uninterrupted run"


# --------------------------------------------------------------------- #
# ds_ckpt CLI
# --------------------------------------------------------------------- #
def test_ds_ckpt_cli_verify_list_gc(tmp_path, capsys):
    from deepspeed_tpu.runtime.fault import ckpt_cli
    engine = make_engine()
    for _ in range(3):
        train_steps(engine, 1)
        engine.save_checkpoint(str(tmp_path))

    assert ckpt_cli.main(["list", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "global_step3" in out and "<- latest" in out

    assert ckpt_cli.main(["verify", str(tmp_path)]) == 0

    # corrupt the middle tag: verify fails loudly, exit code 1
    target = None
    for dirpath, _d, filenames in os.walk(tmp_path / "global_step2"):
        for n in filenames:
            if n != MANIFEST_NAME:
                target = os.path.join(dirpath, n)
    with open(target, "r+b") as f:
        f.write(b"\x00\x01\x02\x03")
    assert ckpt_cli.main(["verify", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "INVALID" in out

    # gc --dry-run touches nothing
    assert ckpt_cli.main(["gc", str(tmp_path), "--keep", "1",
                          "--dry-run"]) == 0
    assert len(list_tags(str(tmp_path))) == 3
    assert ckpt_cli.main(["gc", str(tmp_path), "--keep", "1"]) == 0
    assert list_tags(str(tmp_path)) == ["global_step3"]


# --------------------------------------------------------------------- #
# Config plumbing
# --------------------------------------------------------------------- #
def test_fault_config_defaults_off():
    cfg = deepspeed_tpu.DeepSpeedConfig(
        {"train_micro_batch_size_per_gpu": 2}, mesh_world_size=8)
    assert cfg.fault.enabled is False
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    assert DeepSpeedInferenceConfig().fault.enabled is False


def test_injection_env_spec_parsing(monkeypatch):
    specs = inject.configure_injection(
        "point=ckpt.save_io,action=raise,at=2,times=3")
    assert specs[0].point == "ckpt.save_io"
    assert (specs[0].at, specs[0].times) == (2, 3)
    with pytest.raises(ValueError, match="unknown injection point"):
        inject.configure_injection({"point": "nope"})
    with pytest.raises(ValueError, match="unknown injection action"):
        inject.configure_injection({"point": "ckpt.save_io",
                                    "action": "nope"})
