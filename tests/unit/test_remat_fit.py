"""The remat policy ``"fit"``: the model names what a rematerialized block
can keep (``models/transformer.py`` ``REMAT_LADDER``), the training engine
keeps the richest rung that fits the device memory its compiled step leaves
(``runtime/engine.py`` ``_fit_train_exe``).  The CPU backend reports no
memory limit, so every fit here runs against injected readings."""

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import transformer as tf
from deepspeed_tpu.models.transformer import (
    REMAT_LADDER, Transformer, TransformerConfig, remat_rung_names,
    resolve_remat_policy)
from deepspeed_tpu.monitor import trace
from deepspeed_tpu.runtime import compile_cache as cc

TOP = len(REMAT_LADDER)
SEQ = 16


def _config(**kw):
    return TransformerConfig(**{**dict(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        max_seq_len=SEQ, dtype="float32"), **kw})


def _ids(rows=8):
    return jnp.asarray(np.random.default_rng(0).integers(0, 64, (rows, SEQ)),
                       jnp.int32)


def _engine(cache_dir=None, **model_kw):
    config = {"train_micro_batch_size_per_gpu": 1,      # x 8 virtual devices
              "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}}
    if cache_dir is not None:
        config["compile_cache"] = {"enabled": True, "cache_dir": cache_dir,
                                   "executables": False,
                                   "min_compile_time_secs": 0.0}
    engine, *_ = deepspeed_tpu.initialize(model=Transformer(_config(**model_kw)),
                                          config=config)
    return engine


class Readings:
    """Injected device memory: a limit, and what each compile reads.  The
    step's own arguments count as nothing, so the reckoning before the
    first compile is ``saved bytes <= limit less the reserve``."""

    def __init__(self, engine, limit, step_reads=()):
        self.engine, self.reads = engine, list(step_reads)
        self.compiled = []                  # every rung compiled, in order
        engine._device_memory = lambda: (limit, 0)
        engine._step_memory = self._read
        real = engine._get_fused_step
        engine._get_fused_step = lambda rung=0: (
            self.compiled.append(rung), real(rung))[1]

    def _read(self, compiled):
        return self.reads.pop(0) if self.reads else 0

    def warm(self):
        self.engine.warmup(batch={"input_ids": _ids()[None]})
        # warmup() itself asks for rung 0's jit first, and compiles nothing
        assert self.compiled[0] == 0
        return self.engine.remat_choice(), self.compiled[1:]


@pytest.fixture(autouse=True)
def _arguments_weigh_nothing(monkeypatch):
    from deepspeed_tpu.runtime import engine as engine_mod
    monkeypatch.setattr(engine_mod, "_bytes_on_first_device", lambda tree: 0)


def _budget(limit):
    return int(limit * (1 - deepspeed_tpu.DeepSpeedEngine.REMAT_FIT_RESERVE))


def _limit_for(rung):
    """The least limit under which the reckoning admits ``rung``."""
    limit = max(1, _config().remat_saved_bytes(SEQ, rung))
    while _budget(limit) < _config().remat_saved_bytes(SEQ, rung):
        limit += 1
    return limit


@pytest.fixture
def cache_dir(tmp_path):
    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    yield str(tmp_path)
    jax.config.update("jax_compilation_cache_dir", prev_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_min)
    cc._configured_dir = prev_dir


# --------------------------------------------------------------------- #
# The ladder
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("rung,names", [
    (0, ()),
    (1, ("flash_out", "flash_lse")),
    (2, ("flash_out", "flash_lse", "mlp_up", "mlp_gate")),
    (3, ("flash_out", "flash_lse", "mlp_up", "mlp_gate",
         "attn_q", "attn_k", "attn_v")),
    (4, ("flash_out", "flash_lse", "mlp_up", "mlp_gate",
         "attn_q", "attn_k", "attn_v", "attn_o")),
])
def test_ladder_order_and_each_rungs_names(rung, names):
    assert remat_rung_names(rung) == names
    assert TOP == 4


@pytest.mark.parametrize("rung", [-1, TOP + 1])
def test_a_rung_off_the_ladder_is_refused(rung):
    with pytest.raises(ValueError, match="ladder"):
        remat_rung_names(rung)
    with pytest.raises(ValueError, match="ladder"):
        resolve_remat_policy(f"fit:{rung}")


def test_saved_bytes_by_the_shapes_at_opt13b():
    """OPT-1.3B, 4,096 tokens, bf16: a unit is tokens x h x 2 B x layers;
    (a) is one unit and the f32 lse, (b) four, (c) three, (d) one."""
    cfg = TransformerConfig(hidden_size=2048, num_layers=24, num_heads=32)
    unit = 4096 * 2048 * 2 * 24
    lse = 4096 * 32 * 4 * 24
    got = [cfg.remat_saved_bytes(4096, r) for r in range(TOP + 1)]
    assert got == [0, unit + lse, 5 * unit + lse, 8 * unit + lse,
                   9 * unit + lse]
    gated = dataclasses.replace(cfg, gated_mlp=True, activation="silu")
    assert gated.remat_saved_bytes(4096, 2) == 9 * unit + lse


def test_default_policy_is_fit_and_rung_zero_is_nothing_saveable():
    assert TransformerConfig().remat_policy == "fit"
    nothing = jax.checkpoint_policies.nothing_saveable
    assert resolve_remat_policy("fit") is nothing
    assert resolve_remat_policy("fit:0") is nothing
    assert resolve_remat_policy("nothing_saveable") is nothing
    assert resolve_remat_policy("fit:2") is not nothing


# --------------------------------------------------------------------- #
# The names in the model
# --------------------------------------------------------------------- #
def _name_eqns(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "name":
            out.append(eqn)
        for sub in eqn.params.values():
            for j in sub if isinstance(sub, (list, tuple)) else [sub]:
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    _name_eqns(inner, out)
    return out


@pytest.mark.parametrize("gated,names", [(False, {"mlp_up"}),
                                         (True, {"mlp_up", "mlp_gate"})])
def test_down_projection_output_is_never_a_name(gated, names):
    cfg = _config(gated_mlp=gated, activation="silu" if gated else "relu")
    mlp = tf.MLP(cfg)
    x = jnp.ones((2, SEQ, cfg.hidden_size), jnp.float32)
    params = mlp.init(jax.random.key(0), x)
    jaxpr = jax.make_jaxpr(lambda p: mlp.apply(p, x))(params).jaxpr
    tagged = _name_eqns(jaxpr, [])
    assert {e.params["name"] for e in tagged} == names
    # every named value has the up-projection's width; the block's output
    # (the down-projection's) is no tag's result
    assert all(e.outvars[0].aval.shape[-1] == cfg.ffn_size for e in tagged)
    assert jaxpr.outvars[0] not in [e.outvars[0] for e in tagged]
    assert not {n for step in REMAT_LADDER for n in step} & {
        "mlp_down", "down_proj", "mlp_out"}


def test_attention_names_are_lane_dense():
    """q/k/v are named as [B, S, heads * D]: a stacked [.., heads, D=64]
    copy is padded to 128 lanes in HBM, twice its bytes."""
    cfg = _config()
    attn = tf.Attention(cfg)
    x = jnp.ones((2, SEQ, cfg.hidden_size), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    params = attn.init(jax.random.key(0), x, pos)
    jaxpr = jax.make_jaxpr(lambda p: attn.apply(p, x, pos)[0])(params).jaxpr
    shapes = {e.params["name"]: e.outvars[0].aval.shape
              for e in _name_eqns(jaxpr, [])}
    for n in ("attn_q", "attn_k", "attn_v", "attn_o"):
        assert shapes[n] == (2, SEQ, cfg.hidden_size)
    if "flash_out" in shapes:            # the kernel's forward rule, if traced
        assert shapes["flash_out"] == (2, SEQ, cfg.hidden_size)


@pytest.mark.parametrize("program", ["serving_decode_step",
                                     "serving_prefill_chunk"])
def test_serving_programs_lower_to_the_same_text_without_the_tags(
        program, monkeypatch):
    from deepspeed_tpu.tools.lint import entry_points

    def lowered():
        ep = getattr(entry_points, program)()
        # private functions are numbered by a counter of the process
        return re.sub(r"@(\w+?)_\d+\b", r"@\1",
                      ep.fn.lower(*ep.args).as_text())

    with_tags = lowered()
    monkeypatch.setattr(tf, "checkpoint_name", lambda x, name: x)
    assert lowered() == with_tags


# --------------------------------------------------------------------- #
# Same numbers on every rung
# --------------------------------------------------------------------- #
def _loss_and_grads(cfg, params=None):
    model = Transformer(cfg)
    batch = {"input_ids": _ids(2)}
    if params is None:
        params = model.init(jax.random.key(0), batch)
    return params, jax.value_and_grad(lambda p: model.apply(p, batch))(params)


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unscanned"])
@pytest.mark.parametrize("rung", range(1, TOP + 1))
def test_loss_and_gradients_equal_on_every_rung(scan, rung):
    base = _config(scan_layers=scan, remat_policy="fit:0")
    params, (loss0, grads0) = _loss_and_grads(base)
    _, (loss, grads) = _loss_and_grads(
        dataclasses.replace(base, remat_policy=f"fit:{rung}"), params)
    np.testing.assert_allclose(loss, loss0, rtol=1e-4, atol=1e-6)
    for g, g0 in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        np.testing.assert_allclose(g, g0, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unscanned"])
def test_fit_without_an_engine_is_nothing_saveables_jaxpr(scan):
    batch = {"input_ids": _ids(2)}

    def grad_jaxpr(policy):
        model = Transformer(_config(scan_layers=scan, remat_policy=policy))
        params = model.init(jax.random.key(0), batch)
        return str(jax.make_jaxpr(
            jax.grad(lambda p: model.apply(p, batch)))(params))

    assert grad_jaxpr("fit") == grad_jaxpr("nothing_saveable")
    assert grad_jaxpr("fit:4") != grad_jaxpr("nothing_saveable")


# --------------------------------------------------------------------- #
# The engine's fit
# --------------------------------------------------------------------- #
def test_no_bytes_limit_is_rung_zero_and_todays_program():
    """The CPU backend reports no limit: "fit" compiles rung 0, whose
    lowered step is the named ``nothing_saveable``'s."""
    texts = {}
    for policy in ("fit", "nothing_saveable"):
        engine = _engine(remat_policy=policy)
        assert engine._device_memory()[0] == 0
        batch = {"input_ids": _ids()[None]}
        engine.warmup(batch=batch)
        texts[policy] = engine._train_aot[next(iter(engine._train_aot))] \
            .as_text()
        if policy == "fit":
            choice = engine.remat_choice()
            assert choice["rung"] == 0 and choice["remat_saved"] == []
            assert choice["rungs_tried"] == [0]
            assert choice["step_peak_bytes"] is None
        else:
            assert engine.remat_choice() is None
    assert texts["fit"] == texts["nothing_saveable"]


@pytest.mark.parametrize("rung", range(TOP + 1))
def test_fit_takes_the_richest_rung_that_fits(rung):
    engine = _engine()
    probe = Readings(engine, _limit_for(rung))
    choice, compiled = probe.warm()
    assert choice["rung"] == rung and choice["rungs_tried"] == [rung]
    assert compiled == [rung]
    assert choice["remat_saved"] == list(remat_rung_names(rung))
    assert choice["remat_saved_bytes"] == \
        engine.module.config.remat_saved_bytes(SEQ, rung)
    assert choice["bytes_limit"] == _limit_for(rung)


def test_a_first_compile_that_reads_over_steps_down_one_rung():
    engine = _engine()
    limit = 10 ** 9
    budget = _budget(limit)
    probe = Readings(engine, limit, step_reads=[budget + 1, budget - 5])
    choice, compiled = probe.warm()
    assert choice["rungs_tried"] == [TOP, TOP - 1] == compiled
    assert choice["rung"] == TOP - 1
    assert choice["step_peak_bytes"] == budget - 5
    assert choice["bytes_limit"] == limit
    # the step that runs is the kept one: nothing else is built
    built = set(engine._compiled)
    loss = engine.train_batch(batch={"input_ids": _ids()[None]})
    assert np.isfinite(float(loss)) and set(engine._compiled) == built
    assert len(engine._train_aot) == 1


def test_a_reading_far_over_steps_down_by_what_it_read_and_once_only():
    """The second rung is the richest that fits by the FIRST reading, and is
    kept whatever it reads: at most one compile is thrown away."""
    engine = _engine()
    saved = lambda r: engine.module.config.remat_saved_bytes(SEQ, r)
    limit = 10 ** 9
    budget = _budget(limit)
    over = saved(TOP) - saved(1)            # only rungs 0 and 1 would fit
    probe = Readings(engine, limit, step_reads=[budget + over, budget + 7])
    choice, compiled = probe.warm()
    assert choice["rungs_tried"] == [TOP, 1] == compiled
    assert choice["rung"] == 1 and choice["step_peak_bytes"] == budget + 7


@pytest.mark.parametrize("policy", ["nothing_saveable", "flash_only_saveable",
                                    "dots_and_attn_saveable", "fit:2"])
def test_a_policy_given_by_name_is_honoured_and_nothing_is_searched(policy):
    engine = _engine(remat_policy=policy)

    def no_reading(*a):
        raise AssertionError("a named policy read the device's memory")
    engine._device_memory = engine._step_memory = no_reading
    batch = {"input_ids": _ids()[None]}
    engine.warmup(batch=batch)
    assert engine.remat_choice() is None
    assert list(engine._compiled) == ["fused_step"]
    assert np.isfinite(float(engine.train_batch(batch=batch)))
    assert engine.module.config.remat_policy == policy


def test_three_call_path_and_remat_off_are_not_fitted():
    engine = _engine()

    def no_reading(*a):
        raise AssertionError("the 3-call path read the device's memory")
    engine._device_memory = engine._step_memory = no_reading
    batch = {"input_ids": _ids()}
    loss = engine(batch)
    engine.backward(loss)
    engine.step()
    assert engine.remat_choice() is None
    off = _engine(remat=False)
    off._device_memory = no_reading
    off.warmup(batch={"input_ids": _ids()[None]})
    assert off.remat_choice() is None


def test_hybrid_engine_keeps_rung_zero():
    from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine
    assert DeepSpeedHybridEngine._remat_fit_enabled is False
    assert deepspeed_tpu.DeepSpeedEngine._remat_fit_enabled is True


def test_second_engine_reads_the_memoised_rung_and_compiles_once(cache_dir):
    limit = 10 ** 9
    first = _engine(cache_dir)
    budget = _budget(limit)
    choice, compiled = Readings(
        first, limit, step_reads=[budget + 1, budget - 9]).warm()
    assert compiled == [TOP, TOP - 1]
    second = _engine(cache_dir)
    probe = Readings(second, limit)

    def no_reading(*a):
        raise AssertionError("a memoised rung was measured again")
    second._step_memory = no_reading
    again, compiled = probe.warm()
    assert compiled == [TOP - 1] and again["rungs_tried"] == [TOP - 1]
    for key in ("rung", "remat_saved", "remat_saved_bytes",
                "step_peak_bytes", "bytes_limit"):
        assert again[key] == choice[key]
    # another device (another limit) does not inherit the choice
    third = _engine(cache_dir)
    other, compiled = Readings(third, 2 * limit).warm()
    assert compiled == [TOP] and other["bytes_limit"] == 2 * limit


def test_choice_is_in_the_compile_span_and_the_accessor():
    engine = _engine()
    limit = 10 ** 9
    budget = _budget(limit)
    probe = Readings(engine, limit, step_reads=[budget - 3])
    ring = trace.enable()
    try:
        choice, _ = probe.warm()
        spans = [s for s in ring.span_snapshot()[0]
                 if s[0] == "dstpu.train.compile"]
    finally:
        trace.disable()
    assert len(spans) == 1
    args = spans[0][-1]
    assert args["rung"] == TOP == choice["rung"]
    assert args["remat_saved"] == ",".join(remat_rung_names(TOP))
    assert args["remat_saved_bytes"] == choice["remat_saved_bytes"]
    assert args["step_peak_bytes"] == budget - 3
    assert args["bytes_limit"] == limit and args["rungs_tried"] == str(TOP)
