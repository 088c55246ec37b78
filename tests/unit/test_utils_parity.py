"""Tests for utils parity components: state_dict_factory TP reshard,
tensor_fragment, OnDevice, debug, groups, SparseTensor, elastic agent
(analogs of reference tests/unit/{checkpoint/test_checkpoint_sharding,
utils,runtime/sparse_tensor,elasticity})."""

import os
import signal

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from simple_model import SimpleModel, random_batch


# ------------------------------------------------------------------ #
# state_dict_factory
# ------------------------------------------------------------------ #
def _fake_megatron_shards(tmp_path, tp=2, din=8, dout=12):
    """Write tp .npz shards of a toy megatron-ish layer set."""
    rng = np.random.default_rng(0)
    full = {
        "attn.query_key_value.weight": rng.standard_normal((3 * dout, din)).astype(np.float32),
        "attn.query_key_value.bias": rng.standard_normal(3 * dout).astype(np.float32),
        "attn.dense.weight": rng.standard_normal((din, dout)).astype(np.float32),
        "attn.dense.bias": rng.standard_normal(din).astype(np.float32),
        "ln.weight": rng.standard_normal(din).astype(np.float32),
    }
    paths = []
    for r in range(tp):
        shard = {
            # column-parallel: outputs split (torch layout axis 0)
            "attn.query_key_value.weight": np.split(full["attn.query_key_value.weight"], tp, 0)[r],
            "attn.query_key_value.bias": np.split(full["attn.query_key_value.bias"], tp, 0)[r],
            # row-parallel: inputs split (torch layout axis 1); bias replicated
            "attn.dense.weight": np.split(full["attn.dense.weight"], tp, 1)[r],
            "attn.dense.bias": full["attn.dense.bias"],
            "ln.weight": full["ln.weight"],
        }
        p = str(tmp_path / f"mp_rank_{r:02d}_model_states.npz")
        np.savez(p, **shard)
        paths.append(p)
    return full, paths


def test_sd_loader_merge(tmp_path):
    from deepspeed_tpu.runtime.state_dict_factory import MegatronSDLoader
    full, paths = _fake_megatron_shards(tmp_path, tp=2)
    merged = MegatronSDLoader(paths).merge_state_dict()
    for k, v in full.items():
        np.testing.assert_array_equal(merged[k], v, err_msg=k)


def test_sd_loader_split_roundtrip(tmp_path):
    from deepspeed_tpu.runtime.state_dict_factory import MegatronSDLoader
    full, paths = _fake_megatron_shards(tmp_path, tp=2)
    loader = MegatronSDLoader(paths)
    # 2 shards → 4-way TP: each target rank gets half of one source shard
    r0 = loader.load(mp_world_size=4, mp_rank=0)
    r1 = loader.load(mp_world_size=4, mp_rank=1)
    both = np.concatenate([r0["attn.query_key_value.weight"],
                           r1["attn.query_key_value.weight"]], axis=0)
    np.testing.assert_array_equal(
        both, np.split(full["attn.query_key_value.weight"], 2, 0)[0])
    # 2 shards → 1: full merge
    whole = loader.load(mp_world_size=1, mp_rank=0)
    np.testing.assert_array_equal(whole["attn.dense.weight"],
                                  full["attn.dense.weight"])


def test_sd_loader_factory_json(tmp_path):
    from deepspeed_tpu.runtime.state_dict_factory import SDLoaderFactory
    _, paths = _fake_megatron_shards(tmp_path, tp=2)
    t, lst, ver = SDLoaderFactory.get_sd_loader_json(
        {"type": "Megatron", "checkpoints": paths, "version": 1.0})
    assert t == "Megatron" and len(lst) == 2 and ver == 1.0
    loader = SDLoaderFactory.get_sd_loader(lst)
    assert len(loader) == 2


# ------------------------------------------------------------------ #
# tensor_fragment / OnDevice / debug
# ------------------------------------------------------------------ #
def _engine():
    e, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=16),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                "zero_optimization": {"stage": 3}})
    loss = e(random_batch())
    e.backward(loss)
    e.step()
    return e


def test_tensor_fragment_full_views():
    from deepspeed_tpu.utils.tensor_fragment import (
        get_local_fragment, safe_get_full_fp32_param,
        safe_get_full_optimizer_state, safe_set_full_fp32_param)
    e = _engine()
    path = "params/linear_0/kernel"
    w = safe_get_full_fp32_param(e, path)
    assert w.shape == (16, 16)
    m = safe_get_full_optimizer_state(e, path, "exp_avg")
    assert m is not None and m.shape == (16, 16)
    # ZeRO-3: the param is genuinely sharded → local fragment is a slice
    leaf = e._params["params"]["linear_0"]["kernel"]
    frags = get_local_fragment(leaf)
    assert len(frags) >= 1
    new = np.zeros_like(w)
    safe_set_full_fp32_param(e, path, new)
    np.testing.assert_array_equal(safe_get_full_fp32_param(e, path), new)


def test_on_device_meta_init():
    from deepspeed_tpu.utils.init_on_device import OnDevice, abstract_init
    model = SimpleModel(hidden_dim=16)
    with OnDevice(dtype=jnp.bfloat16, device="meta"):
        tree = abstract_init(model.init, jax.random.key(0), random_batch())
    leaves = jax.tree.leaves(tree)
    assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
    assert all(l.dtype == jnp.bfloat16 for l in leaves)


def test_debug_name_maps():
    from deepspeed_tpu.utils.debug import debug_extract_module_and_param_names
    e = _engine()
    names = debug_extract_module_and_param_names(jax.device_get(e.params))
    assert "params/linear_0/kernel" in names
    assert names["params/linear_0/kernel"] == (16, 16)


def test_groups_getters():
    from deepspeed_tpu.utils import groups
    deepspeed_tpu.initialize_topology(tp=2)
    assert groups._get_model_parallel_world_size() == 2
    assert groups._get_data_parallel_world_size() == 4
    assert groups._get_model_parallel_group()


# ------------------------------------------------------------------ #
# SparseTensor + sparse allreduce
# ------------------------------------------------------------------ #
def test_sparse_tensor_roundtrip():
    from deepspeed_tpu.runtime.sparse_tensor import SparseTensor
    d = np.zeros((10, 4), np.float32)
    d[2] = 1.0
    d[7] = -2.0
    st = SparseTensor.from_dense(d)
    assert st.indices.shape == (2,)
    np.testing.assert_array_equal(np.asarray(st.to_dense()), d)
    nnz, total = st.sparse_size()
    assert nnz == 8 and total == 40


def test_sparse_allreduce(eight_devices):
    import functools
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    from deepspeed_tpu.runtime.sparse_tensor import SparseTensor, sparse_allreduce
    mesh = Mesh(np.array(eight_devices), ("dp",))
    # each device contributes one row (row = device index), duplicates add
    idx = jnp.arange(8, dtype=jnp.int32).reshape(8, 1) % 4
    vals = jnp.ones((8, 1, 4), jnp.float32)

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("dp"), P("dp")),
                       out_specs=(P(), P()), check_vma=False)
    def run(i, v):
        st = SparseTensor(i[0], v[0], (10, 4))
        red = sparse_allreduce(st, "dp")
        return red.indices, red.values

    gi, gv = run(idx, vals)
    st = SparseTensor(gi, gv, (10, 4))
    dense = np.asarray(st.to_dense())
    # rows 0..3 each hit by 2 devices, mean-reduced values 1/8 → sum 2/8
    np.testing.assert_allclose(dense[:4], np.full((4, 4), 0.25))
    np.testing.assert_allclose(dense[4:], 0.0)


# ------------------------------------------------------------------ #
# elastic agent
# ------------------------------------------------------------------ #
def test_elastic_agent_preemption(tmp_path):
    from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent
    e = _engine()
    agent = DSElasticAgent({}, checkpoint_dir=str(tmp_path))
    calls = {"n": 0}

    def step():
        calls["n"] += 1
        if calls["n"] == 3:
            os.kill(os.getpid(), signal.SIGTERM)

    status, steps = agent.run(step, e, max_steps=10)
    assert status == "preempted" and steps == 3
    assert os.path.exists(os.path.join(str(tmp_path), "latest"))


def test_elastic_agent_config_resize():
    from deepspeed_tpu.elasticity.elastic_agent import DSElasticAgent
    ds_cfg = {"elasticity": {"enabled": True, "micro_batch_sizes": [2, 4],
                             "max_train_batch_size": 64, "min_gpus": 1,
                             "max_gpus": 64, "version": 0.1}}
    agent = DSElasticAgent(ds_cfg, world_size=8)
    cfg4 = agent.elastic_config_for(4)
    cfg8 = agent.elastic_config_for(8)
    # global batch preserved across slice resize
    assert cfg4["train_batch_size"] == cfg8["train_batch_size"]
    for cfg, n in ((cfg4, 4), (cfg8, 8)):
        assert cfg["train_micro_batch_size_per_gpu"] * \
            cfg["gradient_accumulation_steps"] * n == cfg["train_batch_size"]

def test_megatron_v1_qkv_split_merge_roundtrip(tmp_path):
    """Version-aware fused-QKV shard handling (reference
    ``merge_query_key_value``): v1 shards are [q_r|k_r|v_r]; naive concat
    would interleave per-rank blocks."""
    import numpy as np
    from deepspeed_tpu.runtime.state_dict_factory import MegatronSDLoader

    rng = np.random.default_rng(0)
    w = rng.standard_normal((12, 4)).astype(np.float32)   # [3h=12, in]
    b = rng.standard_normal((12,)).astype(np.float32)
    full = {"transformer.layers.0.attention.query_key_value.weight": w,
            "transformer.layers.0.attention.query_key_value.bias": b}
    p0 = tmp_path / "full.npz"
    np.savez(p0, **full)

    loader = MegatronSDLoader([str(p0)], version=1.0)
    shard_paths = []
    for r in range(2):
        shard = loader.split_state_dict(2, r)
        # v1 rank shard really is [q_r|k_r|v_r]
        np.testing.assert_array_equal(
            shard["transformer.layers.0.attention.query_key_value.weight"],
            np.concatenate([np.split(t, 2)[r] for t in np.split(w, 3)]))
        p = tmp_path / f"rank{r}.npz"
        np.savez(p, **shard)
        shard_paths.append(str(p))

    merged = MegatronSDLoader(shard_paths, version=1.0).merge_state_dict()
    np.testing.assert_array_equal(
        merged["transformer.layers.0.attention.query_key_value.weight"], w)
    np.testing.assert_array_equal(
        merged["transformer.layers.0.attention.query_key_value.bias"], b)


def test_megatron_vocab_parallel_embedding_merge(tmp_path):
    """VocabParallelEmbedding shards (differing across ranks) concatenate on
    the vocab dim; replicated embeddings pass through."""
    import numpy as np
    from deepspeed_tpu.runtime.state_dict_factory import MegatronSDLoader

    rng = np.random.default_rng(1)
    emb = rng.standard_normal((8, 4)).astype(np.float32)
    pos = rng.standard_normal((6, 4)).astype(np.float32)
    paths = []
    for r in range(2):
        p = tmp_path / f"r{r}.npz"
        np.savez(p, **{"word_embeddings.weight": np.split(emb, 2)[r],
                       "position_embeddings.weight": pos})
        paths.append(str(p))
    merged = MegatronSDLoader(paths, version=2.0).merge_state_dict()
    np.testing.assert_array_equal(merged["word_embeddings.weight"], emb)
    np.testing.assert_array_equal(merged["position_embeddings.weight"], pos)


def test_megatron_vocab_embedding_uneven_and_split_symmetry(tmp_path):
    """Unevenly-split vocab shards must concatenate (no broadcast crash);
    split_state_dict shards the vocab dim so merge∘split is the identity."""
    import numpy as np
    from deepspeed_tpu.runtime.state_dict_factory import MegatronSDLoader

    rng = np.random.default_rng(2)
    emb = rng.standard_normal((10, 4)).astype(np.float32)
    paths = []
    for r, sl in enumerate((slice(0, 6), slice(6, 10))):   # 6 + 4 rows
        p = tmp_path / f"u{r}.npz"
        np.savez(p, **{"word_embeddings.weight": emb[sl]})
        paths.append(str(p))
    merged = MegatronSDLoader(paths, version=2.0).merge_state_dict()
    np.testing.assert_array_equal(merged["word_embeddings.weight"], emb)

    # split from a single full checkpoint shards the vocab dim
    full = tmp_path / "full.npz"
    np.savez(full, **{"word_embeddings.weight": emb})
    loader = MegatronSDLoader([str(full)], version=2.0)
    s0 = loader.split_state_dict(2, 0)["word_embeddings.weight"]
    s1 = loader.split_state_dict(2, 1)["word_embeddings.weight"]
    np.testing.assert_array_equal(np.concatenate([s0, s1]), emb)
