"""The one declared contract between a model and the slot engine
(``deepspeed_tpu/models/contract.py``, ``docs/serving.md`` "What a model
declares"): what each served family returns, what ``serve()`` refuses by
class and field, and that nothing under ``serving/`` probes a module any
more.  No server is run: modules are built without weights (or with a
two-layer toy's), nothing is compiled."""

import ast
import dataclasses
import os

import pytest

import jax
import jax.numpy as jnp
import flax.linen as nn

import deepspeed_tpu
from benchmark import spec
from deepspeed_tpu.inference.serving import slots
from deepspeed_tpu.models import contract as slot_contract
from deepspeed_tpu.models.contract import SlotContract
from deepspeed_tpu.models.transformer import Transformer, TransformerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SERVING = os.path.join(ROOT, "deepspeed_tpu", "inference", "serving")

DSA = ("dsa_keys_scored", "dsa_keys_kept", "latent_rows_read")
TILES = ("flash_tiles_live", "flash_tiles_whole")
EVA = ("eva_ring_rows", "eva_summary_rows", "eva_local_pairs",
       "eva_remote_pairs", "eva_summaries_written")

# (family, the cell whose published config builds the module, overrides of
#  the config's HF keys, every field that is not the default, ring pages at
#  the cell's page of 64)
FAMILIES = [
    ("opt", "opt13b-serve-chat", {}, dict(
        vocab_size=50272, max_seq_len=2048, num_layers=24), 0),
    ("olmoe", "olmoe-serve-gen-batch", {}, dict(
        vocab_size=50304, max_seq_len=4096, num_layers=8,
        routes_experts=True, expert_layers=8, experts=64), 0),
    ("dots3", "dots3-serve-longdoc-batch", {}, dict(
        vocab_size=19008, max_seq_len=524288, num_layers=5, kv_pages=False,
        row_kinds=("latent + index rows", "window rows"), chunk_cap=2048,
        own_chunk_path=True, routes_experts=True, holds_share=True,
        expert_layers=4, experts=32, work_counters=DSA + ("window_keys",) + TILES,
        work_levels=("latent_rows_decompressed", "window_pages")), 9),
    ("lfm2", "lfm2-serve-widegen-batch", {}, dict(
        vocab_size=65536, max_seq_len=128000, num_layers=10,
        state_kinds=("conv",), routes_experts=True, expert_layers=8,
        experts=64), 0),
    ("evabyte", "evabyte-serve-bytedoc-batch", {}, dict(
        vocab_size=320, max_seq_len=32768, num_layers=8, lane_stride=16,
        row_kinds=("summary rows", "ring rows"), chunk_cap=2048,
        own_chunk_path=True, work_counters=EVA,
        work_levels=("ring_bytes_held", "summary_bytes_mapped")), 32),
    ("glm5", "glm5-serve-reasongen-batch", {"num_nextn_predict_layers": 0},
     dict(vocab_size=19360, max_seq_len=202752, num_layers=5, kv_pages=False,
          chunk_cap=2048, own_chunk_path=True, routes_experts=True,
          holds_share=True, expert_layers=4, experts=16,
          work_counters=DSA + TILES,
          work_levels=("latent_rows_decompressed",)), 0),
    ("glm5", "glm5-serve-reasongen-batch", {}, dict(
        vocab_size=19360, max_seq_len=202752, num_layers=5, kv_pages=False,
        chunk_cap=2048, own_chunk_path=True, routes_experts=True,
        holds_share=True, expert_layers=4, experts=16, draft_layers=1,
        work_counters=DSA + TILES,
        work_levels=("latent_rows_decompressed",)), 0),
]
CALLABLES = ("ring_pages", "chunk_fault", "chunk_work", "block_work")


@pytest.mark.parametrize(
    "family,cell,keys,declared,ring", FAMILIES,
    ids=["opt", "olmoe", "dots3", "lfm2", "evabyte", "glm5",
         "glm5_self_drafting"])
def test_each_family_declares_this(family, cell, keys, declared, ring):
    """The value ``slot_contract()`` returns at the cell's published config:
    every plain field (the defaults are ``SlotContract``'s own, written
    once), and what the callables say at the cell's sizes."""
    bench = spec.Benchmark(ROOT)
    cell = bench.cell(cell)
    module = bench.family(family).program_model({**cell["config"], **keys})
    got = module.slot_contract()
    want = SlotContract(**{"dtype": "bfloat16", **declared})
    plain = lambda c: {f.name: getattr(c, f.name)
                       for f in dataclasses.fields(c)
                       if f.name not in CALLABLES}
    assert plain(got) == plain(want)
    assert got.drafts_itself == bool(declared.get("draft_layers"))
    assert got.ring_pages(64) == ring
    assert (got.chunk_work is None) == (got.block_work is None) \
        == (not got.work_counters)
    s = cell["system"]["serving"]
    chunk = slots.admission_chunk(got, s["prefill_chunk"])
    assert chunk == s["prefill_chunk"]         # the cell's chunk fits
    if family == "evabyte":                    # a chunk may not straddle
        assert "divides window_size=2048" in got.chunk_fault(768)
    # what serve() holds it to, at the cell's page and chunk
    slot_contract.check(got, module, s["page_size"], chunk,
                        got.num_layers + got.draft_layers)
    # one chunk program a geometry: rows where nothing but the K/V pages
    # ties them — dropless experts do not (OLMoE), a state or a chunk
    # geometry of the model's own does
    assert slots.chunk_rows(got, chunk, s["page_size"]) \
        == (4 if family in ("opt", "olmoe") else 1)
    assert slots.chunk_write_form(got, chunk, s["page_size"]) \
        == ("page_runs" if got.kv_pages else None)


# ---- what serve() refuses, by class and field ----------------------------- #
def _tiny(vocab_size=97):
    return TransformerConfig(vocab_size=vocab_size, hidden_size=32,
                             num_layers=2, num_heads=4, max_seq_len=128,
                             dtype="float32", scan_layers=False)


class _Bare(nn.Module):
    """A model with no word for the slot engine."""

    @nn.compact
    def __call__(self, batch):
        return nn.Dense(4)(batch["input_ids"].astype(jnp.float32))


def _declares(**fields):
    class Declares(Transformer):
        def slot_contract(self):
            return dataclasses.replace(Transformer.slot_contract(self),
                                       **fields)
    return Declares(_tiny())


def _with_state_rows(**fields):
    """... whose cache takes ``state_rows`` and holds no state pool."""
    module = _declares(**fields)
    plain = type(module).init_paged_cache
    type(module).init_paged_cache = \
        lambda self, num_pages, page_size, dtype=None, state_rows=1: \
        plain(self, num_pages, page_size, dtype)
    return module


class _Misspelt(Transformer):
    def slot_contract(self):
        return SlotContract(vocab_size=97, max_seq_len=128, dtype="float32",
                            num_layers=2, chunk_capp=256)


def _counts(names):
    return lambda *args: dict.fromkeys(names, 0)


REFUSED = {
    "no_method": (lambda: _Bare(), TypeError,
                  r"_Bare has no slot_contract\(\)"),
    "unknown_field": (lambda: _Misspelt(_tiny()), TypeError,
                      r"_Misspelt\.slot_contract\(\).*chunk_capp"),
    "not_a_contract": (
        lambda: type("Says", (Transformer,),
                     {"slot_contract": lambda self: {"chunk_cap": 512}})(
                         _tiny()),
        TypeError, r"Says\.slot_contract\(\) returned dict"),
    "state_kind_not_in_the_cache": (
        lambda: _with_state_rows(state_kinds=("conv",)), ValueError,
        r"Declares\.slot_contract\(\): state_kinds names \['conv'\]"),
    "state_kind_the_cache_has_no_rows_for": (
        lambda: _declares(state_kinds=("conv",)), ValueError,
        r"Declares\.init_paged_cache\(\) does not take \['state_rows'\]"),
    "kv_pages_against_the_cache": (
        lambda: _declares(kv_pages=False), ValueError,
        r"Declares\.slot_contract\(\): kv_pages=False"),
    "counter_outside_the_list": (
        lambda: _declares(chunk_work=_counts(["keys_scored", "keys_keptt"]),
                          block_work=_counts(["keys_scored"]),
                          work_counters=("keys_scored", "keys_kept")),
        ValueError,
        r"Declares\.slot_contract\(\): chunk_work returns \['keys_keptt'\]"),
    "counter_nothing_returns": (
        lambda: _declares(chunk_work=_counts(["keys_scored"]),
                          block_work=_counts(["keys_scored"]),
                          work_counters=("keys_scored", "keys_kept")),
        ValueError,
        r"Declares\.slot_contract\(\): work_counters names \['keys_kept'\]"),
}


def _engine(module):
    eng = deepspeed_tpu.init_inference(module, config={
        "dtype": "float32", "serving": {
            "enabled": True, "num_slots": 2, "max_cache_len": 64,
            "page_size": 16, "prefill_chunk": 16}})
    eng.set_params(module.init(
        jax.random.key(0), {"input_ids": jnp.zeros((1, 8), jnp.int32)}))
    return eng


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_serve_refuses_a_faulty_declaration(case):
    """Each fault raises AT ``serve()`` — before a pool is allocated or a
    program built — with the model's class and the field in the message;
    none is taken for a default."""
    build, error, match = REFUSED[case]
    eng = _engine(build())
    with pytest.raises(error, match=match):
        eng.serve()


def test_declaring_a_default_changes_no_program():
    """The ``hasattr`` trap: the engine used to take the mere PRESENCE of a
    chunk cap or a chunk-fault hook for a chunk geometry of the model's own
    and give it the one-row chunk program — a model that set 512, the
    default's own value, got a different program than one that left it
    out.  Now the program is picked by what the contract SAYS."""
    plain = _engine(Transformer(_tiny())).serve()
    said = _engine(_declares(chunk_cap=512,
                             chunk_fault=lambda chunk: None)).serve()
    try:
        assert said.contract.chunk_cap == plain.contract.chunk_cap == 512
        assert said.chunk_rows == plain.chunk_rows == 32 and \
            said.chunk == plain.chunk
        args = lambda srv: (
            srv.engine._params,
            jax.eval_shape(lambda: srv._new_pools(jnp.float32)),
            jax.ShapeDtypeStruct((32, srv.table_width), jnp.int32),
            jax.ShapeDtypeStruct((32, 16), jnp.int32),
            jax.ShapeDtypeStruct((32,), jnp.int32),
            jax.ShapeDtypeStruct((32,), jnp.int32))
        assert said._chunk_fn.lower(*args(said)).as_text() \
            == plain._chunk_fn.lower(*args(plain)).as_text()
        # ... and a model that SAYS its chunk path is its own gets one row
        own = _engine(_declares(own_chunk_path=True)).serve()
        assert own.chunk_rows == 1
        own.close()
    finally:
        plain.close()
        said.close()


def test_the_draft_model_of_speculation_is_read_through_its_contract():
    """Separate-draft speculation compares vocabularies by the two
    contracts — a draft module without one is refused like a target."""
    eng = _engine(Transformer(_tiny()))
    with pytest.raises(TypeError, match=r"_Bare has no slot_contract\(\)"):
        eng.serve(speculative=True, spec_k=2, draft_module=_Bare(),
                  draft_params={})
    other = Transformer(_tiny(vocab_size=101))
    with pytest.raises(ValueError, match="vocab_size=101 != target"):
        eng.serve(speculative=True, spec_k=2, draft_module=other,
                  draft_params=other.init(
                      jax.random.key(0),
                      {"input_ids": jnp.zeros((1, 8), jnp.int32)}))


# ---- nothing under serving/ probes a module ------------------------------- #
def _probes(path):
    """``getattr`` / ``hasattr`` calls of ``path`` whose subject is a module,
    a module's type, a module's config or the contract itself: ``(line,
    source)``."""
    with open(path) as f:
        source = f.read()
    found = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name) \
                and node.func.id in ("getattr", "hasattr"):
            subject = ast.unparse(node.args[0])
            if any(word in subject for word in
                   ("module", "contract", "getattr(", "type(")) \
                    or subject.endswith(".config") \
                    or subject in ("cfg", "tcfg", "mc", "config"):
                found.append((node.lineno, ast.unparse(node)))
    return found


@pytest.mark.parametrize("name", ["paging.py", "slots.py", "engine.py"])
def test_serving_asks_a_module_nothing_by_getattr(name):
    """What a model tells the slot engine is the contract's fields, read as
    attributes: an absent name is an error at ``serve()``, never another
    model's behaviour by default."""
    assert _probes(os.path.join(SERVING, name)) == []


def test_the_probe_finder_finds_probes(tmp_path):
    path = tmp_path / "old.py"
    path.write_text(
        "a = getattr(module, 'lane_stride', 1)\n"
        "b = hasattr(type(self.module), 'draft')\n"
        "c = getattr(getattr(module, 'config', None), 'vocab_size', 50272)\n"
        "d = getattr(self.module.config, 'held_experts', None)\n"
        "e = getattr(mon, 'enabled', True)\n"
        "f = getattr(engine._config, 'serving', None)\n")
    assert sorted(line for line, _ in _probes(str(path))) == [1, 2, 3, 3, 4]
