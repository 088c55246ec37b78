"""Device time by the program's own parts: the table of parts
(``profiler.SCOPE_PARTS``), the table the profiler stores in a trace
(``profiler.trace_scopes``), the one join
(``profiler.device_time_by_scope``) and the operator's tool on top of it."""

import contextlib
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import transformer
from deepspeed_tpu.models.transformer import Transformer, TransformerConfig
from deepspeed_tpu.profiling.flops_profiler import profiler
from deepspeed_tpu.runtime import compile_cache as cc

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata",
                     "small_trace.xplane.pb")     # record_small_trace.py's
OP_NAME = re.compile(r'op_name="([^"]+)"')


# --------------------------------------------------------------------- #
# tracing off: the compile seam reads nothing of an executable
# --------------------------------------------------------------------- #
class FakeExe:
    """An executable that counts how often its text is asked for."""
    calls = 0

    def as_text(self):
        self.calls += 1
        return "HloModule jit_fake"


class FakeJit:
    def __init__(self, exe):
        self.exe = exe

    def lower(self, *args):
        return self

    def compile(self):
        return self.exe


class FakeStore:
    """A program cache whose store always hits."""

    def __init__(self, exe):
        self.exe = exe

    def get_or_compile(self, tag, key_parts, compile_fn, program=None):
        return self.exe, 0.0, True


def test_the_compile_seam_never_asks_an_executable_for_its_text():
    miss, hit = FakeExe(), FakeExe()
    exe, _, was_hit = cc.aot_compile_with_store(
        None, "train_step", (), FakeJit(miss), ())
    assert exe is miss and not was_hit
    exe, _, was_hit = cc.aot_compile_with_store(
        FakeStore(hit), "infer:gen", (), FakeJit(None), ())
    assert exe is hit and was_hit
    assert miss.calls == hit.calls == 0
    # nor does anything else of the package, outside the tools a user runs
    # on one program by hand
    root = os.path.dirname(os.path.dirname(cc.__file__))
    for sub in ("runtime", "inference", "profiling"):
        for d, _, files in os.walk(os.path.join(root, sub)):
            for f in files:
                if f.endswith(".py"):
                    with open(os.path.join(d, f)) as fh:
                        assert ".as_text(" not in fh.read(), (d, f)


def tiny_model(**kw):
    cfg = TransformerConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=4, max_seq_len=32, dtype="float32",
                            use_flash_attention=False, remat=False,
                            scan_layers=False, **kw)
    return Transformer(cfg)


def test_a_real_steps_matmuls_carry_their_modules_names():
    """The ``op_name``s of a CPU-compiled flax step, as the compiled
    module's text has them, through the table of parts."""
    model = tiny_model()
    ids = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.key(0), {"input_ids": ids})

    def scoped_step(params, ids):
        return jax.grad(lambda p: model.apply(p, {"input_ids": ids}))(params)

    exe, _, _ = cc.aot_compile_with_store(
        None, "train_step", (), jax.jit(scoped_step), (params, ids))
    text = exe.as_text()
    assert "HloModule jit_scoped_step" in text[:200]
    every = set(OP_NAME.findall(text))
    dots = [o for o in every if o.endswith("dot_general")]
    for module in ("layers_0/attn/o_proj", "layers_1/mlp/down_proj",
                   "layers_0/mlp/up_proj", "lm_head"):
        assert any(module + "/dot_general" in o for o in dots), module
    kinds = {profiler.part_of(o) for o in dots}
    # (the score and value matmuls of the non-flash fallback: attn.core)
    assert {("mlp", "fwd"), ("mlp", "bwd"), ("attn.proj", "bwd"),
            ("attn.core", "fwd"), ("head", "fwd")} <= kinds
    assert any(profiler.part_of(o)[0] == "loss" for o in every)


# --------------------------------------------------------------------- #
# the table of parts
# --------------------------------------------------------------------- #
T = "jit(train_step)/transpose(jvp(Transformer))/Transformer.hidden_states/"
F = "jit(train_step)/jvp(Transformer)/Transformer.hidden_states/"
D = "jit(decode_block)/while/body/closed_call/"


@pytest.mark.parametrize("op_name, part, phase", [
    (F + "embed_tokens/jit(_take)/gather", "embed", "fwd"),
    (T + "embed_positions/jit(_take)/scatter-add", "embed", "bwd"),
    (F + "layers_0/attn/qkv_proj/dot_general", "attn.proj", "fwd"),
    (F + "layers_3/attn/q_norm/mul", "attn.proj", "fwd"),
    (D + "Transformer.decode/layers_0/attn/attn.rope/mul", "attn.proj",
     "fwd"),
    # the attention module outside its projections: attn.core
    (F + "layers_0/attn/reshape", "attn.core", "fwd"),
    (F + "layers_0/attn/bqhd,bkhd->bhqk/dot_general", "attn.core", "fwd"),
    (T + "layers_0/attn/reduce_precision", "attn.core", "bwd"),
    (D + "Lfm2Model.decode/layers_2/self_attn/out_proj/dot_general",
     "attn.proj", "fwd"),
    (D + "Dots3Model.decode/layers_0/attn.step/attn._project/tr,rhd->htd/"
     "dot_general", "attn.proj", "fwd"),
    (F + "layers_0/attn/shard_map/attn.flash_fwd/pallas_call",
     "attn.core", "fwd"),
    (T + "jvp(Transformer)/Transformer.hidden_states/checkpoint/layers_0/"
     "attn/shard_map/attn.flash_dq_dkv/pallas_call", "attn.core", "bwd"),
    (D + "Transformer.decode/layers_0/attn/attn.paged_decode/pallas_call",
     "attn.core", "fwd"),
    ("jit(chunk_step)/Dots3Model.decode/layers_1/attn.chunk/"
     "attn._chunk_full/attn.dsa_topk/cumsum", "attn.core", "fwd"),
    ("jit(chunk_step)/Dots3Model.decode/layers_1/attn.chunk/"
     "attn._chunk_full/attn._attend/attn.mla_decompress/lr,rhd->hld/"
     "dot_general", "attn.mla_decompress", "fwd"),
    ("jit(chunk_step)/Dots3Model.decode/layers_1/attn.chunk/"
     "attn._chunk_full/attn._attend/attn._kv_up/reshape",
     "attn.mla_decompress", "fwd"),
    # the kernel of that name under the scope (a cached full layer)
    ("jit(chunk_step)/Dots3Model.decode/layers_1/attn.chunk/"
     "attn._chunk_full/attn._attend/attn.mla_decompress/"
     "attn.mla_decompress/pallas_call", "attn.mla_decompress", "fwd"),
    ("jit(chunk_step)/Transformer.decode/layers_0/attn/cache.write/scatter",
     "cache.write", "fwd"),
    # the chunk's K/V as page runs (the name the compiled step carries)
    ("jit(chunk_step)/Transformer.decode/Transformer.hidden_states/"
     "checkpoint/layers_23/attn/cache.write/dynamic_update_slice",
     "cache.write", "fwd"),
    ("jit(chunk_step)/Lfm2Model.decode/layers_2/self_attn/cache.write/"
     "dynamic_update_slice", "cache.write", "fwd"),
    (F + "layers_0/mlp/down_proj/dot_general", "mlp", "fwd"),
    (T + "jvp(Transformer)/Transformer.hidden_states/checkpoint/layers_0/"
     "mlp/up_proj/dot_general", "mlp", "bwd"),
    (T + "jvp(Transformer)/Transformer.hidden_states/checkpoint/"
     "rematted_computation/layers_1/mlp/jit(relu)/max", "mlp", "replay"),
    (D + "Dots3Model.decode/layers_1/moe_mlp/moe_mlp._scored/shared_up/"
     "dot_general", "mlp", "fwd"),
    (D + "Lfm2Model.decode/layers_0/feed_forward/gate_proj/dot_general",
     "mlp", "fwd"),
    (D + "Lfm2Model.decode/layers_2/moe_mlp/moe_mlp._scored/moe.route/"
     "jit(take_along_axis)/gather", "moe.route", "fwd"),
    (D + "Transformer.decode/layers_0/moe_mlp/moe.route/pallas_call",
     "moe.route", "fwd"),
    (D + "Transformer.decode/layers_0/moe_mlp/ExpertsMLP_0/"
     "moe.experts_gmm/pallas_call", "moe.experts", "fwd"),
    (D + "Lfm2Model.decode/layers_2/moe_mlp/moe_mlp._scored/"
     "jit(_one_hot)/eq", "moe.experts", "fwd"),
    (D + "Lfm2Model.decode/layers_0/conv/conv.short/mul", "conv.short",
     "fwd"),
    (D + "Lfm2Model.decode/layers_0/conv/out_proj/dot_general",
     "conv.short", "fwd"),
    (D + "Lfm2Model.decode/layers_0/operator_norm/rsqrt", "norm", "fwd"),
    (T + "jvp(Transformer)/Transformer.hidden_states/checkpoint/layers_0/"
     "post_attn_norm/mul", "norm", "bwd"),
    (F + "layers_0/add", "residual", "fwd"),
    (F + "final_norm/mul", "head", "fwd"),
    (D + "Lfm2Model.decode/Lfm2Model._head/embedding_norm/mul", "head",
     "fwd"),
    ("jit(train_step)/jvp(Transformer)/Transformer._head/lm_head/"
     "dot_general", "head", "fwd"),
    ("jit(train_step)/transpose(jvp(Transformer))/loss/while/body/"
     "checkpoint/head/dot_general", "head", "bwd"),
    (D + "head.sample/argmax", "head", "fwd"),
    ("jit(train_step)/jvp(Transformer)/loss/reduce_max", "loss", "fwd"),
    ("jit(train_step)/transpose(jvp(Transformer))/loss/"
     "jit(take_along_axis)/scatter-add", "loss", "bwd"),
    ("jit(train_step)/optim.clip/reduce_sum", "optim", "fwd"),
    ("jit(train_step)/optim.update/sqrt", "optim", "fwd"),
    ("jit(train_step)/while/body/optim.accumulate/add", "optim", "fwd"),
    # the layer scan's own operations, and nobody else's loop
    (T + "while/body/squeeze", "scan.stack", "bwd"),
    (F + "while/body/dynamic_update_slice", "scan.stack", "fwd"),
    (T + "broadcast_in_dim", "scan.stack", "bwd"),
    (T + "while", "scan.stack", "bwd"),
    ("jit(train_step)/jvp(Transformer)/loss/while/body/dynamic_slice",
     "loss", "fwd"),
    (D + "slots.state/select_n", "slots", "fwd"),
    ("jit(decode_block)/slots.expert_load/reduce_sum", "slots", "fwd"),
    # a multi-token-prediction module's scopes: the deepest known frame
    # decides, so its block's attention is attention and its experts experts
    ("jit(spec_block)/while/body/closed_call/Glm5Model.draft/mtp.combine/"
     "mtp.combine/eh_proj/dot_general", "mtp.combine", "fwd"),
    ("jit(spec_block)/while/body/closed_call/Glm5Model.draft/mtp.combine/"
     "mtp.combine/concatenate", "mtp.combine", "fwd"),
    ("jit(spec_block)/while/body/closed_call/Glm5Model.draft/mtp.combine/"
     "mtp.combine/hidden_norm/reduce_sum", "norm", "fwd"),
    ("jit(spec_block)/while/body/closed_call/Glm5Model.draft/mtp.block/"
     "Glm5Model._blocks/block/attn.step/attn._out/dot_general", "attn.proj",
     "fwd"),
    ("jit(spec_block)/while/body/closed_call/Glm5Model.draft/mtp.block/"
     "Glm5Model._blocks/block/moe_mlp/moe_mlp._scored/moe.route/div",
     "moe.route", "fwd"),
    ("jit(spec_block)/while/body/closed_call/Glm5Model.draft/mtp.block/"
     "Glm5Model._blocks/block/add", "residual", "fwd"),
    ("jit(chunk_step)/Glm5Model.draft/mtp.head/head_norm/mul", "head",
     "fwd"),
    ("jit(chunk_step)/Glm5Model.draft/mtp.head/lm_head/dot_general", "head",
     "fwd"),
    # a sandwich-norm block with gated attention over a ring and lane pages
    (D + "TrinityModel.decode/embed.scale/mul", "embed", "fwd"),
    (D + "TrinityModel.decode/layers_1/norm.post_attn/"
     "post_attention_layernorm/mul", "norm", "fwd"),
    (D + "TrinityModel.decode/layers_1/pre_mlp_layernorm/rsqrt", "norm",
     "fwd"),
    (D + "TrinityModel.decode/layers_1/self_attn/gate_proj/dot_general",
     "attn.proj", "fwd"),
    (D + "TrinityModel.decode/layers_1/self_attn/attn.qk_norm/mul",
     "attn.proj", "fwd"),
    (D + "TrinityModel.decode/layers_1/self_attn/attn.out_gate/logistic",
     "attn.proj", "fwd"),
    (D + "TrinityModel.decode/layers_1/self_attn/attn.window/cache.write/"
     "scatter", "cache.write", "fwd"),
    (D + "TrinityModel.decode/layers_1/self_attn/attn.window/"
     "attn.paged_decode", "attn.core", "fwd"),
    ("jit(chunk_step)/TrinityModel.decode/layers_2/self_attn/attn.window/"
     "attn.gqa_window_chunk", "attn.core", "fwd"),
    (D + "TrinityModel.decode/layers_4/self_attn/attn.full/reshape",
     "attn.core", "fwd"),
    (D + "TrinityModel.decode/layers_0/mlp/gate_proj/dot_general", "mlp",
     "fwd"),
    (D + "TrinityModel.decode/slots.tables/slice", "slots", "fwd"),
    (D + "TrinityModel.decode/head.logits/norm/mul", "head", "fwd"),
    (D + "TrinityModel.decode/head.logits/lm_head/dot_general", "head",
     "fwd"),
    # gated delta-rule linear attention on a matrix state a slot: the
    # mixer's own rows, its two kernels, and what stays where it always was
    (D + "SolarOpen2Model.decode/layers_1/linear_attn/attn.kda/kda.scan/"
     "rsqrt", "attn.kda", "fwd"),
    (D + "SolarOpen2Model.decode/layers_1/linear_attn/attn.kda/kda.scan/"
     "kda.decode_step", "attn.kda", "fwd"),
    ("jit(chunk_step)/SolarOpen2Model.decode/layers_2/linear_attn/attn.kda/"
     "kda.scan/kda.chunk_scan", "attn.kda", "fwd"),
    (D + "SolarOpen2Model.decode/layers_1/linear_attn/attn.kda/f_b_proj/"
     "dot_general", "attn.kda", "fwd"),
    (D + "SolarOpen2Model.decode/layers_1/linear_attn/attn.kda/b_proj/"
     "dot_general", "attn.kda", "fwd"),
    (D + "SolarOpen2Model.decode/layers_1/linear_attn/attn.kda/kda.out_gate/"
     "logistic", "attn.kda", "fwd"),
    (D + "SolarOpen2Model.decode/layers_1/linear_attn/attn.kda/concatenate",
     "attn.kda", "fwd"),
    (D + "SolarOpen2Model.decode/layers_1/linear_attn/attn.kda/conv.short/"
     "mul", "conv.short", "fwd"),
    (D + "SolarOpen2Model.decode/layers_1/linear_attn/attn.kda/q_proj/"
     "dot_general", "attn.proj", "fwd"),
    (D + "SolarOpen2Model.decode/layers_0/self_attn/attn.full/"
     "attn.paged_decode", "attn.core", "fwd"),
    (D + "SolarOpen2Model.decode/layers_0/self_attn/gate_proj/dot_general",
     "attn.proj", "fwd"),
    (D + "SolarOpen2Model.decode/layers_3/moe_mlp/moe.experts_gmm",
     "moe.experts", "fwd"),
    (D + "SolarOpen2Model.decode/SolarOpen2Model._head/head.logits/lm_head/"
     "dot_general", "head", "fwd"),
    # Mamba-2 state-space layers on a matrix state a slot: the mixer's own
    # rows, its two kernels, and what stays where it always was
    (D + "GraniteHybridModel.decode/layers_1/mamba/attn.ssd/ssd.scan/"
     "softplus", "attn.ssd", "fwd"),
    (D + "GraniteHybridModel.decode/layers_1/mamba/attn.ssd/ssd.scan/"
     "ssd.decode_step", "attn.ssd", "fwd"),
    ("jit(chunk_step)/GraniteHybridModel.decode/layers_2/mamba/attn.ssd/"
     "ssd.scan/ssd.chunk_scan", "attn.ssd", "fwd"),
    (D + "GraniteHybridModel.decode/layers_1/mamba/attn.ssd/ssd.gate_norm/"
     "mul", "attn.ssd", "fwd"),
    (D + "GraniteHybridModel.decode/layers_1/mamba/attn.ssd/split",
     "attn.ssd", "fwd"),
    (D + "GraniteHybridModel.decode/layers_1/mamba/attn.ssd/conv.short/"
     "mul", "conv.short", "fwd"),
    # the convolution rows' two kernels (``ops/transformer/short_conv.py``)
    # lie under the scope of the mixer that calls them, whichever it is
    (D + "GraniteHybridModel.decode/layers_1/mamba/attn.ssd/conv.short/"
     "jit(_rows_pallas)/conv.rows_write/pallas_call", "conv.short", "fwd"),
    (D + "SolarOpen2Model.decode/layers_1/linear_attn/attn.kda/conv.short/"
     "jit(_rows_pallas)/conv.rows_read/pallas_call", "conv.short", "fwd"),
    (D + "Lfm2Model.decode/layers_0/conv/conv.short/jit(_rows_pallas)/"
     "conv.rows_write/pallas_call", "conv.short", "fwd"),
    (D + "GraniteHybridModel.decode/layers_1/mamba/attn.ssd/in_proj/"
     "dot_general", "attn.proj", "fwd"),
    (D + "GraniteHybridModel.decode/layers_1/mamba/attn.ssd/out_proj/"
     "dot_general", "attn.proj", "fwd"),
    (D + "GraniteHybridModel.decode/layers_5/self_attn/attn.full/"
     "attn.paged_decode", "attn.core", "fwd"),
    (D + "GraniteHybridModel.decode/layers_3/moe_mlp/moe.experts_gmm",
     "moe.experts", "fwd"),
    (D + "GraniteHybridModel.decode/layers_3/mul", "residual", "fwd"),
    (D + "GraniteHybridModel.decode/GraniteHybridModel._embed/embed.scale/"
     "mul", "embed", "fwd"),
    (D + "GraniteHybridModel.decode/GraniteHybridModel._head/head.logits/"
     "div", "head", "fwd"),
    # LFM2's projections beside its convolution keep their row
    (D + "Lfm2Model.decode/layers_0/conv/in_proj/dot_general", "conv.short",
     "fwd"),
    # nothing the table knows: unattributed
    ("jit(train_step)/mul", None, "fwd"),
    ("jit(train_step)/transpose(jvp(Transformer))/broadcast_in_dim", None,
     "bwd"),
    ("jit(decode_block)/while/body/frobnicate_3/mul", None, "fwd"),
    ("", None, "fwd"),
    (None, None, "fwd"),
])
def test_part_and_phase_of_an_op_name(op_name, part, phase):
    assert profiler.part_of(op_name) == (part, phase)
    assert part is None or part in profiler.PARTS
    assert phase in profiler.PHASES


def test_the_table_is_a_fixed_literal_set_of_known_parts():
    assert {part for _, part in profiler.SCOPE_PARTS} <= set(profiler.PARTS)
    # never a size or an index in a scope name the programs add
    for rx, _ in profiler.SCOPE_PARTS[:30]:
        assert not any(ch.isdigit() for ch in rx)


def test_a_renamed_scope_costs_both_caches_one_miss(tmp_path, monkeypatch):
    """Both cache layers key a program without its instructions' metadata:
    a program whose scope was renamed is handed the executable — and the
    ``op_name``s — of whoever compiled that computation first.
    ``SCOPES_VERSION`` is in both keys: raised with the rename, the program
    compiles once more and carries its own names."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(jax.config, k) for k in keys}
    configured = cc._configured_dir
    x = jnp.ones((64, 64))

    def build(scope):
        def renamed(x):
            with jax.named_scope(scope):
                return jnp.sin(x) @ x
        return jax.jit(renamed).lower(x).compile()

    entries = lambda: sorted(f for f in os.listdir(tmp_path)
                             if f.startswith("jit_renamed-"))
    names = lambda exe: " ".join(OP_NAME.findall(exe.as_text()))
    try:
        cc.configure_persistent_cache(str(tmp_path), min_compile_time_secs=0)
        cc._reset_jax_cache_state()
        assert "old.name" in names(build("old.name"))
        first = entries()
        assert len(first) == 1
        build("new.name")                       # the same key: no new entry
        assert entries() == first
        store = cc.ExecutableStore(str(tmp_path / "exe"))
        key = cc.cache_key("step", "shape")
        assert store.save(key, build("old.name"))
        assert store.load(key) is not None
        monkeypatch.setattr(cc, "SCOPES_VERSION", cc.SCOPES_VERSION + 1)
        exe = build("new.name")
        assert len(entries()) == 2 and first[0] in entries()
        assert "new.name" in names(exe) and "old.name" not in names(exe)
        assert cc.cache_key("step", "shape") != key
        mismatches = cc.stats().executable_mismatches
        assert cc.ExecutableStore(str(tmp_path / "exe")).load(key) is None
        assert cc.stats().executable_mismatches == mismatches + 1
    finally:
        for k, v in prev.items():
            jax.config.update(k, v)
        cc._configured_dir = configured
        cc._reset_jax_cache_state()


# --------------------------------------------------------------------- #
# the join
# --------------------------------------------------------------------- #
def _ev(name, start_ms, dur_ms, opcode="fusion"):
    return (f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} %p)", start_ms * 1e-3,
            dur_ms * 1e-3)


def _run(module, program, start_ms, dur_ms):
    return (f"{module}({program})", start_ms * 1e-3, dur_ms * 1e-3)


def test_own_time_of_nested_events_inside_the_programs_executions():
    scopes = {
        7: {"while.1": "jit(train_step)/while",                  # no part
            "fusion.1": F + "layers_0/mlp/up_proj/dot_general",
            "fusion.2": T + "layers_0/mlp/up_proj/dot_general",
            "fusion.3": "jit(train_step)/optim.update/mul",
            "slice-done.1": "jit(train_step)/while",              # no part
            "all-gather.1": F + "layers_0/mlp/up_proj/dot_general"},
        # another signature of the same module: the same NAMES, other scopes
        8: {"fusion.1": F + "layers_0/attn/o_proj/dot_general",
            "fusion.3": "jit(train_step)/jvp(Transformer)/loss/reduce_max"},
        9: {"fusion.1": "jit(eval_step)/optim.update/mul"}}
    modules = [_run("jit_train_step", 7, 0, 20), _run("jit_train_step", 8, 20, 10),
               _run("jit_eval_step", 9, 50, 10)]
    events = [
        _ev("while.1", 0, 10, "while"),       # holds its body
        _ev("fusion.1", 1, 3), _ev("fusion.2", 5, 4),
        _ev("fusion.3", 10, 2),
        _ev("fusion.4", 12, 1),               # no op_name at all
        _ev("all-gather.1", 13, 2, "all-gather"),
        _ev("copy-done.3", 17, 1, "copy-done"),     # XLA's own prefetch
        _ev("slice-done.1", 18, 1, "slice-done"),   # ... under a scope
        _ev("fusion.1", 20, 6), _ev("fusion.3", 26, 4),    # program 8's
        _ev("fusion.1", 50, 7),               # another module's execution
        _ev("fusion.1", 70, 7),               # outside every execution
    ]
    out = profiler.device_time_by_scope(events, modules, scopes,
                                        ("jit_train_step",))
    ms = lambda s: round(1e3 * s, 6)
    assert {k: ms(v) for k, v in out["parts"].items()} == {
        ("mlp", "fwd"): 3.0, ("mlp", "bwd"): 4.0, ("optim", "fwd"): 2.0,
        ("comm", "fwd"): 2.0, ("xla.prefetch", "fwd"): 2.0,
        ("attn.proj", "fwd"): 6.0, ("loss", "fwd"): 4.0}
    assert ms(out["unattributed_s"]) == 4.0      # while's own 3, fusion.4
    assert ms(out["total_s"]) == 27.0            # nothing is lost
    assert ms(sum(out["parts"].values()) + out["unattributed_s"]) == 27.0
    assert out["executions"] == 2
    top = {n: (ms(s), o) for n, s, o in out["top_unattributed"]}
    assert top == {
        "jit_train_step:while.1": (3.0, "jit(train_step)/while"),
        "jit_train_step:fusion.4": (1.0, None)}
    assert ms(out["by_op_name"]["jit(train_step)/optim.update/mul"]) == 2.0
    # both modules; a module that never executes; a program with no table
    both = profiler.device_time_by_scope(
        events, modules, scopes, ("jit_train_step", "jit_eval_step"))
    assert ms(both["total_s"]) == 34.0 and both["executions"] == 3
    assert ms(both["parts"][("optim", "fwd")]) == 9.0
    assert profiler.device_time_by_scope(events, modules, scopes,
                                         ("jit_decode_block",)) is None
    bare = profiler.device_time_by_scope(events, modules, {},
                                         ("jit_eval_step",))
    assert ms(bare["unattributed_s"]) == ms(bare["total_s"]) == 7.0


def test_the_recorded_trace_is_read_with_jax_alone():
    ops, modules = profiler.read_device_events(TRACE)
    assert len(ops) == 40 and len(modules) == 4
    (program,) = {profiler.program_of(n) for n, _, _ in modules}
    assert program[0] == "jit_small_step" and program[1] > 0
    names = {profiler.instruction_name(n) for n, _, _ in ops}
    scopes = {program[1]: {
        n: "jit(small_step)/layers_0/mlp/up_proj/dot_general"
        for n in names if n.startswith("fusion")}}
    out = profiler.device_time_by_scope(ops, modules, scopes,
                                        ("jit_small_step",))
    busy = sum(d for _, _, d in modules)
    assert 0.9 * busy < out["total_s"] <= busy * 1.0001
    assert out["parts"][("mlp", "fwd")] > 0.9 * out["total_s"]
    assert {n.partition(":")[2] for n, _, _ in out["top_unattributed"]} \
        <= names
    # no device plane (the CPU backend): nothing, not an error
    assert profiler.read_device_events(os.path.dirname(cc.__file__)) \
        == ([], [])


def test_the_profiler_stores_the_table_in_the_trace():
    """``trace_scopes``: each instruction's op_name and program id, read
    off the wire of the recorded trace (``ProfileData`` shows no metadata
    stats)."""
    _, modules = profiler.read_device_events(TRACE)
    (program,) = {profiler.program_of(n)[1] for n, _, _ in modules}
    stored = profiler.trace_scopes(TRACE)
    assert set(stored) == {program}
    assert stored[program]["fusion.7"] == "jit(small_step)/dot_general"
    assert len(stored[program]) == 8          # the eight matmul fusions
    assert profiler.trace_scopes(os.path.dirname(cc.__file__)) == {}
    assert profiler.trace_scopes(os.path.join(TRACE, "no-such-dir")) == {}


def test_a_finished_trace_is_joined_against_its_own_table():
    assert profiler.traced_device_time(TRACE, ("jit_train_step",)) is None
    out = profiler.traced_device_time(TRACE, ("jit_small_step",))
    assert out["executions"] == 4
    assert set(out["parts"]) == {("xla.prefetch", "fwd")}    # copy-start
    assert set(out["by_op_name"]) == {"jit(small_step)/dot_general"}
    assert out["unattributed_s"] == pytest.approx(out["total_s"], rel=1e-3)
    assert "unattributed" in profiler.format_device_time(out)
    assert profiler.traced_device_time(os.path.dirname(cc.__file__),
                                       ("jit_small_step",)) is None


# --- a trace written by hand, field by field as xplane.proto numbers them
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _xspace(programs, executions):
    """One device plane.  ``programs``: ``{id: (module, {instruction:
    op_name})}``; ``executions``: ``[(id, start_us, [(instruction,
    offset_us, dur_us)])]``."""
    stat_ids = {"tf_op": 1, "program_id": 2}
    stat_meta = b"".join(
        _field(5, _field(1, i) + _field(2, _field(1, i) + _field(2, n)))
        for n, i in stat_ids.items())
    meta, ids = b"", {}
    for program, (module, table) in programs.items():
        ids[program] = len(ids) + 1
        meta += _field(4, _field(1, ids[program]) + _field(2, _field(
            1, ids[program]) + _field(2, f"{module}({program})")))
        for instruction, op_name in table.items():
            ids[program, instruction] = i = len(ids) + 1
            stats = _field(5, _field(1, 2) + _field(3, program))
            if op_name:
                stats += _field(5, _field(1, 1) + _field(5, op_name + ":Op"))
            meta += _field(4, _field(1, i) + _field(2, _field(1, i) + _field(
                2, f"%{instruction} = f32[8]{{0}} fusion(f32[8]{{0}} %p)")
                + _field(4, instruction) + stats))
    event = lambda i, start_us, dur_us: _field(4, _field(1, i) + _field(
        2, start_us * 10 ** 6) + _field(3, dur_us * 10 ** 6))
    ops = b"".join(event(ids[p, n], t0 + off, dur)
                   for p, t0, body in executions for n, off, dur in body)
    runs = b"".join(event(ids[p], t0, max(o + d for _, o, d in body))
                    for p, t0, body in executions)
    plane = (_field(1, 1) + _field(2, "/device:TPU:0")
             + _field(3, _field(1, 1) + _field(2, "XLA Modules") + runs)
             + _field(3, _field(1, 2) + _field(2, "XLA Ops") + ops)
             + meta + stat_meta)
    return _field(1, plane)


def test_two_signatures_of_one_module_in_a_trace_written_by_hand(tmp_path):
    """The whole path from a file — ``ProfileData`` for the events, the
    wire reader for the tables — on known numbers: two programs named
    ``jit_chunk_step`` whose ``fusion.1`` means different things."""
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_xspace(
        {11: ("jit_chunk_step", {
            "fusion.1": "jit(chunk_step)/M.decode/layers_0/mlp/up_proj/dot",
            "fusion.2": None}),
         12: ("jit_chunk_step", {
             "fusion.1": "jit(chunk_step)/M.decode/layers_0/attn/o_proj/dot"}),
         13: ("jit_admit", {"fusion.1": "jit(admit)/head.sample/argmax"})},
        [(11, 0, [("fusion.1", 0, 30), ("fusion.2", 30, 10)]),
         (12, 100, [("fusion.1", 0, 50)]),
         (13, 200, [("fusion.1", 0, 5)]),
         (11, 300, [("fusion.1", 0, 30), ("fusion.2", 30, 10)])]))
    assert profiler.trace_scopes(str(path)) == {
        11: {"fusion.1": "jit(chunk_step)/M.decode/layers_0/mlp/up_proj/dot"},
        12: {"fusion.1": "jit(chunk_step)/M.decode/layers_0/attn/o_proj/dot"},
        13: {"fusion.1": "jit(admit)/head.sample/argmax"}}
    out = profiler.traced_device_time(str(path), ("jit_chunk_step",))
    us = lambda s: round(1e6 * s, 3)
    assert {k: us(v) for k, v in out["parts"].items()} == {
        ("mlp", "fwd"): 60.0, ("attn.proj", "fwd"): 50.0}
    assert us(out["unattributed_s"]) == 20.0 and us(out["total_s"]) == 130.0
    assert out["executions"] == 3
    assert out["top_unattributed"] == [
        ("jit_chunk_step:fusion.2", pytest.approx(20e-6), None)]


# --------------------------------------------------------------------- #
# the operator's tool
# --------------------------------------------------------------------- #
def test_the_tree_takes_its_latency_from_the_join():
    model = tiny_model()
    batch = {"input_ids": np.zeros((2, 16), np.int32)}
    device_time = {
        "executions": 2, "total_s": 0.010, "unattributed_s": 0.0,
        "parts": {}, "top_unattributed": [],
        "by_op_name": {
            F + "layers_0/mlp/up_proj/dot_general": 0.002,
            T + "jvp(Transformer)/Transformer.hidden_states/checkpoint/"
            "layers_0/mlp/up_proj/dot_general": 0.004,
            "jit(train_step)/optim.update/mul": 0.004}}
    root, total_ps = profiler.model_profile_tree(
        model, jax.random.key(0), batch, device_time=device_time)
    assert total_ps == root.latency_ps == 5_000_000_000    # per execution
    up = root.children["layers_0"].children["mlp"].children["up_proj"]
    assert up.latency_ps == 3_000_000_000
    assert dict(up.latency_by_phase) == {"fwd": 1_000_000_000,
                                         "bwd": 2_000_000_000}
    assert root.children["layers_1"].latency_ps == 0
    text = profiler.format_profile_tree(root, total_ps)
    assert "3.000 ms = 60.00% latency (fwd 1.000 / bwd 2.000)" in text
    assert "latency" in profiler.aggregate_by_depth(root, max_depth=1)


def test_print_model_profile_compiles_no_program_of_its_own(tmp_path):
    import inspect
    import deepspeed_tpu
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_model(),
        config={"train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "flops_profiler": {"enabled": True, "profile_step": 2,
                                   "output_file": str(tmp_path / "p.txt")}})
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(0, 64, (1, 8, 16)).astype(np.int32)}
    engine.train_batch(batch=batch)               # compiles the step
    compiles = []
    listen = lambda event, secs, **kw: compiles.append(event) \
        if event == "/jax/core/compile/backend_compile_duration" else None
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        engine.train_batch(batch=batch)           # the profiled step
        assert compiles == []
    finally:
        from jax._src import monitoring as _m
        _m.unregister_event_duration_listener(listen)
    report = (tmp_path / "p.txt").read_text()
    assert "profile step: 2" in report and "(layers_0): Block(" in report
    for fn in (profiler.FlopsProfiler.print_model_profile,
               profiler.model_profile_tree, profiler.traced_device_time):
        assert ".lower(" not in inspect.getsource(fn)
    with open(profiler.__file__) as f:
        assert "tensorflow" not in f.read()


@pytest.mark.parametrize("positions", ["learned", "rope"])
def test_the_scopes_are_metadata_only(monkeypatch, positions):
    """The fused step with every ``jax.named_scope`` taken out lowers to
    the same program, text for text (StableHLO without its locations): the
    compiler is handed the same module — which is also why JAX's persistent
    cache, keyed without debug info, hands a scoped process an unscoped
    one's executable and its ``op_name``s."""
    import deepspeed_tpu

    def lowered(scoped):
        if not scoped:
            monkeypatch.setattr(
                jax, "named_scope", lambda name: contextlib.nullcontext())
            monkeypatch.setattr(transformer, "_rope",     # a decorator's
                                transformer._rope.__wrapped__)
        engine, *_ = deepspeed_tpu.initialize(
            model=tiny_model(position_embedding=positions),
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_clipping": 1.0,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
        ids = np.zeros((1, 8, 16), np.int32)
        engine._lazy_init(({"input_ids": ids[0]},), {})
        args = (engine._params, engine._opt_state, engine._scaler_state,
                jnp.asarray(1e-3, jnp.float32), jnp.asarray(1, jnp.int32),
                engine._rng, {"input_ids": jnp.asarray(ids)})
        out = engine._get_fused_step().lower(*args)
        monkeypatch.undo()
        return out

    a, b = lowered(True), lowered(False)
    assert "optim.update" in a.as_text(debug_info=True)
    assert "optim.update" not in b.as_text(debug_info=True)
    assert ("attn.rope" in a.as_text(debug_info=True)) == (positions == "rope")
    assert "attn.rope" not in b.as_text(debug_info=True)
    assert a.as_text() == b.as_text()
