"""Persistent compile/executable cache (runtime/compile_cache.py): hit/miss
accounting, executable round-trips, fingerprint-mismatch fallback, and the
acceptance contract — a warm-cache second invocation of the train-step +
prefill + decode compile paths skips XLA compilation, asserted via the
framework's cache-hit counters on CPU."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.runtime import compile_cache as cc
from deepspeed_tpu.models.transformer import Transformer, TransformerConfig

from simple_model import SimpleModel, random_batch


@pytest.fixture
def cache_dir(tmp_path):
    """tmp cache dir + guaranteed restore: the persistent XLA cache is
    process-wide and the suite's own cache dir (tests/conftest.py) must
    come back for the tests that run after this module."""
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield str(tmp_path)
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
    cc._configured_dir = prev_dir


def _snap():
    return cc.stats().snapshot()


def _delta(after, before, key):
    return after[key] - before[key]


# --------------------------------------------------------------------- #
# Placement: $JAX_COMPILATION_CACHE_DIR, else one fixed path in the checkout
# --------------------------------------------------------------------- #
def test_env_variable_is_the_only_placement(cache_dir, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set, JAX's own handling of it
    places the cache: the config key is ignored and NOTHING gives
    ``jax_compilation_cache_dir`` a path (the executable store lives
    under the variable's directory too)."""
    placed = os.path.join(cache_dir, "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(jax.config, "update", lambda k, v: (
        updates.append((k, v)), real_update(k, v)))
    got = cc.configure_persistent_cache(os.path.join(cache_dir, "from_key"))
    assert got == placed and os.path.isdir(placed)
    assert not os.path.exists(os.path.join(cache_dir, "from_key"))
    assert "jax_compilation_cache_dir" not in [k for k, _ in updates]
    pc = cc.ProgramCache(cc.CompileCacheConfig(
        enabled=True, cache_dir=os.path.join(cache_dir, "from_key")))
    assert pc.store.directory == os.path.join(placed, "executables")


def test_unset_variable_places_the_cache_in_the_checkout(cache_dir,
                                                         monkeypatch):
    """Unset, the one path code ever chooses is ``.jax_cache`` at the
    root of the checkout — never ``~``, never a temp name."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    assert cc.default_cache_dir() == os.path.join(repo, ".jax_cache")
    assert cc.configure_persistent_cache() == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == cc.default_cache_dir()


# --------------------------------------------------------------------- #
# ExecutableStore unit behavior
# --------------------------------------------------------------------- #
def test_executable_store_roundtrip_and_accounting(cache_dir):
    store = cc.ExecutableStore(cache_dir)
    x = jnp.arange(8.0)
    compiled = jax.jit(lambda v: v * 2 + 1).lower(x).compile()
    key = cc.cache_key("roundtrip", cc.abstract_signature((x,)))

    s0 = _snap()
    assert store.load(key) is None                  # cold → miss
    s1 = _snap()
    assert _delta(s1, s0, "executable_misses") == 1
    assert store.save(key, compiled)
    s2 = _snap()
    assert _delta(s2, s1, "executable_saves") == 1

    reloaded = store.load(key)
    assert reloaded is not None
    s3 = _snap()
    assert _delta(s3, s2, "executable_hits") == 1
    np.testing.assert_array_equal(np.asarray(reloaded(x)),
                                  np.asarray(compiled(x)))


def test_fingerprint_mismatch_falls_back_to_fresh_compile(cache_dir):
    store = cc.ExecutableStore(cache_dir)
    x = jnp.arange(4.0)
    compiled = jax.jit(lambda v: v + 1).lower(x).compile()
    key = cc.cache_key("fp-mismatch", cc.abstract_signature((x,)))
    assert store.save(key, compiled)

    # a cache written by a different jaxlib build must be IGNORED, not
    # deserialized into a crash
    meta_path = os.path.join(cache_dir, key + ".json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["fingerprint"]["jaxlib"] = "0.0.0-other-build"
    with open(meta_path, "w") as f:
        json.dump(meta, f)

    s0 = _snap()
    assert store.load(key) is None
    s1 = _snap()
    assert _delta(s1, s0, "executable_mismatches") == 1
    assert _delta(s1, s0, "executable_misses") == 1
    # the graceful path end-to-end: get_or_compile recompiles and reports
    # a miss, never an error to the caller
    pc = cc.ProgramCache(cc.CompileCacheConfig(
        enabled=True, cache_dir=cache_dir, min_compile_time_secs=0.0))
    exe, secs, hit = pc.get_or_compile(
        "fp-mismatch-recompile", (cc.abstract_signature((x,)),),
        lambda: jax.jit(lambda v: v + 1).lower(x).compile())
    assert not hit and secs > 0
    np.testing.assert_array_equal(np.asarray(exe(x)), np.asarray(x + 1))


def test_corrupt_payload_is_a_miss_not_a_crash(cache_dir):
    store = cc.ExecutableStore(cache_dir)
    x = jnp.arange(4.0)
    key = cc.cache_key("corrupt", cc.abstract_signature((x,)))
    assert store.save(key, jax.jit(lambda v: v * 3).lower(x).compile())
    with open(os.path.join(cache_dir, key + ".bin"), "wb") as f:
        f.write(b"\x00garbage")
    s0 = _snap()
    assert store.load(key) is None
    s1 = _snap()
    assert _delta(s1, s0, "executable_errors") == 1
    assert _delta(s1, s0, "executable_misses") == 1


def test_cache_key_separates_shapes_and_tags():
    fp = {"pin": "fixed"}
    a = cc.cache_key("t", ((4,), "float32"), fingerprint=fp)
    assert a == cc.cache_key("t", ((4,), "float32"), fingerprint=fp)
    assert a != cc.cache_key("t", ((8,), "float32"), fingerprint=fp)
    assert a != cc.cache_key("other", ((4,), "float32"), fingerprint=fp)
    assert a != cc.cache_key("t", ((4,), "float32"), fingerprint={"pin": "x"})


# --------------------------------------------------------------------- #
# Acceptance: warm second invocation skips XLA compilation
# --------------------------------------------------------------------- #
def _train_config(cache_dir):
    return {"train_micro_batch_size_per_gpu": 2,   # x 8 virtual devices
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "compile_cache": {"enabled": True, "cache_dir": cache_dir,
                              "min_compile_time_secs": 0.0}}


def test_train_step_warm_cache_skips_compile(cache_dir):
    """Two fresh engines, same config: the second's fused train step must
    come from the executable store (hit counter), not an XLA compile."""
    batch = jax.tree.map(lambda x: x[None], random_batch(batch_size=16))

    def run():
        engine, *_ = deepspeed_tpu.initialize(model=SimpleModel(),
                                              config=_train_config(cache_dir))
        loss = engine.train_batch(batch=batch)
        return float(jax.device_get(engine.train_batch(batch=batch)))

    s0 = _snap()
    l1 = run()
    s1 = _snap()
    assert _delta(s1, s0, "executable_saves") >= 1     # cold: compiled+saved
    assert "train_step" in s1["compile_seconds"]
    l2 = run()
    s2 = _snap()
    assert _delta(s2, s1, "executable_hits") >= 1      # warm: reloaded
    assert _delta(s2, s1, "executable_saves") == 0     # nothing recompiled
    assert l1 == l2                                    # identical trajectory


def _tiny_model():
    cfg = TransformerConfig(vocab_size=97, hidden_size=64, num_layers=2,
                            num_heads=4, max_seq_len=64,
                            use_flash_attention=False, dtype="float32")
    model = Transformer(cfg)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 97, (2, 12)),
                      jnp.int32)
    params = model.init(jax.random.key(0), {"input_ids": ids})
    return model, params, ids


def test_prefill_decode_warm_cache_skips_compile(cache_dir):
    """Two fresh inference engines on the split-prefill path (prefill-chunk
    executable + decode-only program): the second generates entirely from
    store hits and reproduces the first's tokens."""
    model, params, ids = _tiny_model()

    def run():
        eng = deepspeed_tpu.init_inference(
            model, config={"dtype": "float32", "prefill_chunk_size": 8,
                           "compile_cache": {"enabled": True,
                                             "cache_dir": cache_dir,
                                             "min_compile_time_secs": 0.0}})
        eng.set_params(params)
        return np.asarray(eng.generate(ids, max_new_tokens=4))

    s0 = _snap()
    out1 = run()
    s1 = _snap()
    # split path = two programs, both persisted cold
    assert _delta(s1, s0, "executable_saves") >= 2
    out2 = run()
    s2 = _snap()
    assert _delta(s2, s1, "executable_hits") >= 2
    assert _delta(s2, s1, "executable_saves") == 0
    np.testing.assert_array_equal(out1, out2)


def test_warmup_precompiles_and_reports(cache_dir):
    """warmup() compiles every bucket up front (with per-program compile
    times), generate() then compiles nothing, and a second engine's warmup
    is all store hits (0.0s entries)."""
    model, params, ids = _tiny_model()
    conf = {"dtype": "float32", "prefill_chunk_size": 8,
            "compile_cache": {"enabled": True, "cache_dir": cache_dir,
                              "min_compile_time_secs": 0.0}}

    eng = deepspeed_tpu.init_inference(model, config=conf)
    eng.set_params(params)
    report = eng.warmup(12, 4, batch_sizes=(2,))
    # split-prefill bucket: the chunk program AND the decode-only program
    assert any(k.startswith("prefill_chunk:") for k in report)
    assert any(k.startswith("decode:") for k in report)
    assert all(dt > 0 for dt in report.values())       # cold: real compiles

    s0 = _snap()
    out = np.asarray(eng.generate(ids, max_new_tokens=4))
    s1 = _snap()
    # generate after warmup touches NO compile path at all
    assert _delta(s1, s0, "executable_hits") == 0
    assert _delta(s1, s0, "executable_misses") == 0
    assert _delta(s1, s0, "executable_saves") == 0
    assert out.shape == (2, 16)

    eng2 = deepspeed_tpu.init_inference(model, config=conf)
    eng2.set_params(params)
    report2 = eng2.warmup(12, 4, batch_sizes=(2,))
    assert report2                                     # same buckets
    s2 = _snap()
    assert _delta(s2, s1, "executable_hits") >= 2      # warm: all hits
    np.testing.assert_array_equal(
        out, np.asarray(eng2.generate(ids, max_new_tokens=4)))


def test_engine_warmup_reports_through_monitor(cache_dir, tmp_path):
    """DeepSpeedEngine.warmup: compile time lands in the monitor stream
    (Compile/train_step_secs) and train_batch() reuses the warmed
    executable."""
    config = _train_config(cache_dir)
    config["csv_monitor"] = {"enabled": True, "output_path": str(tmp_path),
                             "job_name": "warmup_test"}
    config["steps_per_print"] = 1
    engine, *_ = deepspeed_tpu.initialize(model=SimpleModel(), config=config)
    batch = jax.tree.map(lambda x: x[None], random_batch(batch_size=16))
    report = engine.warmup(batch=batch)
    assert "train_step" in report
    csv = os.path.join(str(tmp_path), "warmup_test",
                       "Compile_train_step_secs.csv")
    assert os.path.exists(csv)
    s0 = _snap()
    engine.train_batch(batch=batch)
    s1 = _snap()
    assert s1["compile_seconds"] == s0["compile_seconds"]  # nothing new


def test_disabled_cache_keeps_plain_jit_path(tmp_path):
    """compile_cache off (the default): no store traffic, engines behave
    exactly like the seed."""
    s0 = _snap()
    engine, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(),
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    assert engine._program_cache is None
    batch = jax.tree.map(lambda x: x[None], random_batch(batch_size=16))
    engine.train_batch(batch=batch)
    s1 = _snap()
    for k in ("executable_hits", "executable_misses", "executable_saves"):
        assert _delta(s1, s0, k) == 0


# --------------------------------------------------------------------- #
# Opt-in minimal repro: the serving executable-reload corruption
# (ROADMAP item 4) — a harness for the future root-cause PR, skipped by
# default and xfail(non-strict) when opted in because the corruption is
# NONDETERMINISTIC (~50% of warm runs in the serving kill-harness).
# --------------------------------------------------------------------- #
@pytest.mark.skipif(
    os.environ.get("DSTPU_RUN_CACHE_CORRUPTION_REPRO") != "1",
    reason="opt-in repro harness (ROADMAP item 4): set "
           "DSTPU_RUN_CACHE_CORRUPTION_REPRO=1 to run")
@pytest.mark.xfail(
    strict=False,
    reason="ROADMAP item 4: donated dynamic_update_slice programs reloaded "
           "through jax.stages.Compiled serialization nondeterministically "
           "corrupt the donated workspace (serving opts out of both cache "
           "layers as mitigation; see docs/compile_cache.md)")
def test_repro_donated_dus_chain_through_executable_serialization(tmp_path):
    """Minimal distillation of the serving corruption: TWO donated
    programs chained over ONE workspace — an admit-like
    ``dynamic_update_slice`` lane insert (slot index traced) and a
    decode-like per-row scatter write — both run from
    ``ExecutableStore``-reloaded (serialize/deserialize round-tripped)
    executables, against a fresh-jit reference.  Greedy-deterministic
    math: any divergence is the reload corrupting the donated buffer."""
    N, S, D, ROUNDS = 4, 16, 8, 12

    def admit(big, lane, slot):
        return jax.lax.dynamic_update_slice(big, lane, (slot, 0, 0))

    def decode_step(big, tok, pos):
        row = jnp.arange(N)
        big = big.at[row, pos, :].set(tok)
        out = big.sum(axis=(1, 2))
        return big, out

    store = cc.ExecutableStore(str(tmp_path / "exe"))

    def reloaded(fn, donate, args):
        compiled = jax.jit(fn, donate_argnums=donate).lower(
            *jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        ).compile()
        key = cc.cache_key(fn.__name__, "repro")
        assert store.save(key, compiled)
        exe = store.load(key)
        assert exe is not None, "executable did not round-trip the store"
        return exe

    rng = np.random.default_rng(0)
    lane0 = jnp.asarray(rng.standard_normal((1, S, D)), jnp.float32)
    big0 = jnp.zeros((N, S, D), jnp.float32)
    tok0 = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)

    warm_admit = reloaded(admit, (0,), (big0, lane0, jnp.asarray(0)))
    warm_decode = reloaded(decode_step, (0,),
                           (big0, tok0, jnp.asarray(0, jnp.int32)))
    ref_admit = jax.jit(admit, donate_argnums=(0,))
    ref_decode = jax.jit(decode_step, donate_argnums=(0,))

    def drive(admit_fn, decode_fn):
        big = jnp.zeros((N, S, D), jnp.float32)
        outs = []
        r = np.random.default_rng(7)
        for i in range(ROUNDS):
            lane = jnp.asarray(r.standard_normal((1, S, D)), jnp.float32)
            big = admit_fn(big, lane, jnp.asarray(i % N))
            tok = jnp.asarray(r.standard_normal((N, D)), jnp.float32)
            big, out = decode_fn(big, tok,
                                 jnp.asarray((2 * i) % S, jnp.int32))
            outs.append(np.asarray(out))
        return np.stack(outs), np.asarray(big)

    ref_outs, ref_big = drive(ref_admit, ref_decode)
    warm_outs, warm_big = drive(warm_admit, warm_decode)
    np.testing.assert_array_equal(warm_outs, ref_outs)
    np.testing.assert_array_equal(warm_big, ref_big)


def test_compile_phase_sums_count_nested_traces_once(monkeypatch):
    """JAX reports a jit traced inside another's trace first, and its
    time lies inside the outer's: ``trace_seconds`` counts it once —
    also when one outer trace holds thousands of inner ones (24
    unrolled layers do) — and ``compile_events`` keeps what each event
    ADDED, so a reader only sums."""
    from deepspeed_tpu.runtime import compile_cache as cc
    T = "/jax/core/compile/jaxpr_trace_duration"
    B = "/jax/core/compile/backend_compile_duration"
    clock = [1000.0]
    monkeypatch.setattr(cc.time, "monotonic", lambda: clock[0])
    monkeypatch.setattr(cc, "_STATS", cc.CacheStats())
    monkeypatch.setattr(cc, "_open_traces", [])
    for i in range(3000):                 # siblings of 10 ms, back to back
        clock[0] = 1000.0 + 0.01 * (i + 1)
        cc._on_jax_duration(T, 0.01)
    clock[0] = 1031.0                     # their outer trace: 1000-1031
    cc._on_jax_duration(T, 31.0)
    cc._on_jax_duration(B, 7.0)
    cc._on_jax_duration("/jax/some/other_duration", 99.0)
    clock[0] = 1040.0                     # a later top-level trace
    cc._on_jax_duration(T, 2.0)
    snap = cc.stats().snapshot()
    assert snap["trace_seconds"] == pytest.approx(33.0)
    assert snap["backend_compile_seconds"] == 7.0
    assert snap["lower_seconds"] == 0.0
    kept = snap["compile_events"]
    assert len(kept) == 3003 and kept[3000] == (1031.0, T, pytest.approx(1.0))
    assert sum(e[2] for e in kept if e[1] == T) == pytest.approx(33.0)
    cc._on_jax_duration(T, 0.0005)        # under a millisecond: summed only
    assert len(cc.stats().compile_events) == 3003
