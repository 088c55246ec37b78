"""The control of ``correct``, kept at a size a test run can hold: the plain
reference put in the program's place and computed in float8 — the nearest
precision below the configurations' bfloat16 — must come out as NOT correct
by the same two comparisons the benchmark makes, while bfloat16 (what a
sound program computes) passes.  The limits here are this toy's; the cells'
own limits are read on the chip (benchmark/calibrate.py, PERF.md)."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import spec, stats

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOY = dict(hidden_size=256, num_attention_heads=4, ffn_dim=1024,
           num_hidden_layers=8, vocab_size=4096, max_position_embeddings=64)
SEED = 3000000019


@pytest.fixture(scope="module")
def opt():
    return spec.Benchmark(ROOT).family("opt")


def test_reference_is_the_programs_model_in_float32(opt):
    """Same seed -> the program's own module, in float32 at ``highest``,
    and the plain reference agree to rounding: the adapter hands the program
    exactly the weights the reference draws."""
    import jax
    z = opt.sizes_of(TOY)
    ids = np.random.default_rng(0).integers(0, 4096, (2, 32)).astype(np.int32)
    pos = np.tile(np.arange(31), (2, 1))
    for scan in (True, False):
        module = opt.program_model(TOY, dtype="float32", scan_layers=scan,
                                   use_flash_attention=False)
        params = jax.tree.map(lambda x: x.astype(jnp.float32),
                              opt.program_params(module, TOY, SEED))
        with jax.default_matmul_precision("highest"):
            loss = float(module.apply(params, {"input_ids": jnp.asarray(ids)}))
        ref = float(opt.nll_at(z, SEED, ids, pos).mean())
        assert loss == pytest.approx(ref, abs=2e-5)
    other = float(opt.nll_at(z, SEED + 1, ids, pos).mean())
    assert abs(other - ref) > 1e-3          # the seed makes the weights


def test_float8_control_fails_the_loss_comparison(opt):
    z = opt.sizes_of(TOY)
    ids = np.random.default_rng(1).integers(0, 4096, (2, 48)).astype(np.int32)
    pos = np.tile(np.arange(0, 47, 3), (2, 1))           # 16 a row
    ref = np.asarray(opt.nll_at(z, SEED, ids, pos))
    calls = lambda nll: np.asarray(nll).reshape(2, 4, 4).mean(axis=(0, 2))
    sound = stats.rms(calls(opt.nll_at(z, SEED, ids, pos, "bfloat16"))
                      - calls(ref))
    control = stats.rms(calls(opt.nll_at(z, SEED, ids, pos, "float8"))
                        - calls(ref))
    limit = 4e-3                     # this toy's: above sound, below control
    assert sound < limit / 2, sound
    assert control > 2 * limit, control
    assert control > 3 * sound


def test_float8_control_fails_the_logit_gap_comparison(opt):
    z = opt.sizes_of(TOY)
    worst = {}
    for precision in ("bfloat16", "float8"):
        gaps = []
        for i in range(2):
            prompt = np.random.default_rng([4, i]).integers(0, 4096, 24)
            toks = opt.greedy(z, 4, prompt, 32, 64, precision)
            assert (toks[:24] == prompt).all() and len(toks) == 56
            gaps.append(opt.chosen_gaps(z, 4, toks, 24, 32, 64))
        worst[precision] = float(np.max(gaps))
    limit = 0.025                    # this toy's
    assert worst["bfloat16"] < limit / 2, worst
    assert worst["float8"] > 2 * limit, worst


def _toy_batch(rows, seq, seed):
    rng = np.random.default_rng([seed, 5])
    symbols = rng.choice(4096, 64, replace=False)
    return symbols[rng.integers(0, 64, (rows, seq))].astype(np.int32)


@pytest.mark.parametrize("scan", [True, False])
def test_reference_backward_is_the_programs_gradient_in_float32(opt, scan):
    """The reference's layer-by-layer backward (rows in uneven groups) and
    ``jax.grad`` of the program's own module agree element by element, and
    ``program_tensor`` finds each sampled tensor in the program's tree."""
    import jax
    z = opt.sizes_of(TOY)
    ids = _toy_batch(3, 32, 1)
    ref = opt.loss_and_gradients(z, SEED, ids, group=2)
    module = opt.program_model(TOY, dtype="float32", scan_layers=scan,
                               use_flash_attention=False)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          opt.program_params(module, TOY, SEED))
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: module.apply(
            p, {"input_ids": jnp.asarray(ids)}))(params)

    def leaf_of(path):
        node = grads
        for part in path.split("/"):
            if part not in node:
                return None
            node = node[part]
        return np.asarray(node)

    got = {n: opt.program_tensor(leaf_of, n, z) for n in ref["gradients"]}
    assert set(got) == set(opt.gradient_sample(z))
    assert float(loss) == pytest.approx(ref["loss"], abs=2e-5)
    norm = float(jnp.sqrt(sum(jnp.sum(jnp.square(g))
                              for g in jax.tree.leaves(grads))))
    assert norm == pytest.approx(ref["grad_norm"], rel=1e-5)
    assert opt.relative_error(got, ref["gradients"]) < 1e-4


def test_float8_control_fails_the_gradient_comparison(opt):
    z = opt.sizes_of(TOY)
    ids = _toy_batch(4, 64, 2)
    ref = opt.loss_and_gradients(z, SEED, ids)["gradients"]
    error = {p: opt.relative_error(
        opt.loss_and_gradients(z, SEED, ids, p)["gradients"], ref)
        for p in ("bfloat16", "float8")}
    limit = 0.06                     # this toy's
    assert error["bfloat16"] < limit / 1.5, error
    assert error["float8"] > 1.5 * limit, error
    assert error["float8"] > 3 * error["bfloat16"]


def test_moments_after_one_step_give_the_gradients_back():
    """The arithmetic between Adam's moments after one step from zero and
    the gradient, clipped or not; and the checked batch's repeated rows."""
    import types
    driver = spec.Benchmark(ROOT).driver("train_steps")
    b1, b2 = driver.ADAM_BETAS
    g = {"w": np.array([[3.0, -4.0], [0.5, 0.0]])}
    for norm in (0.5, 5.0):                   # under and over the clip
        c = min(1.0, driver.CLIP / (norm + 1e-6))
        m = {"w": (1 - b1) * c * g["w"]}
        v = {"w": (1 - b2) * np.square(c * g["w"])}
        g1, g2 = driver.implied_gradients(m["w"], v["w"], norm)
        np.testing.assert_allclose(g1, g["w"], rtol=1e-6)
        np.testing.assert_allclose(g2, np.abs(g["w"]), rtol=1e-6)
        # in pieces, and with every gradient a tenth too large
        assert driver.gradient_errors(m.get, v.get, g, norm, chunk=3) == \
            pytest.approx((0.0, 0.0), abs=1e-6)
        off = {"w": 1.1 * m["w"]}
        assert driver.gradient_errors(off.get, v.get, g, norm)[0] == \
            pytest.approx(0.1, rel=1e-5)
    ctx = types.SimpleNamespace(cell={"traffic": {"step_check_rows": 4}})
    first = np.arange(16 * 3).reshape(16, 3)
    batch, unique = driver.step_check_batch(ctx, first)
    assert batch.shape == first.shape and (unique == first[:4]).all()
    assert all((batch[4 * c:4 * c + 4] == first[c]).all() for c in range(4))
    batch, unique = driver.step_check_batch(ctx, first[:2])
    assert (batch == first[:2]).all() and (unique == first[:2]).all()
