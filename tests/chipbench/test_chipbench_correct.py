"""`correct` at tiny size, in this process on the CPU: the reference
agrees with the program in float32; the statistics pass at the stated
precision and fail for the lower one; the serve deficit is zero for
reference-greedy tokens and large for random ones; a broken timed path
comes out as not correct."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cbhelp import RESULT_KEYS, ROOT
from chipbench import correct
from chipbench import run as cb_run
from chipbench.refs import transformer_lm as ref

sys.path.insert(0, os.path.join(ROOT, "model_zoo"))

TINY = {"vocab_size": 96, "seq_len": 256, "embed_dim": 64, "num_heads": 4,
        "num_kv_heads": 2, "num_layers": 2, "pos_emb": "rope",
        "attn_window": 48}
RCFG = dict(TINY, qk_gain=2.0)


def _program_logits(dtype, tokens, seed=3):
    from transformer_lm.transformer_lm import custom_model

    from chipbench.drivers.open_loop import _unflatten

    model = custom_model(**dict(TINY, dtype=dtype))
    params = _unflatten(ref.make_leaves(RCFG, seed, ref.all_leaves(RCFG)))
    return model.apply({"params": params}, {"tokens": tokens})


def _reference_logits(tokens, mm=ref.matmul, seed=3):
    w = ref.make_leaves(RCFG, seed, ref.all_leaves(RCFG))
    x = ref.embed(w, tokens)
    for i in range(RCFG["num_layers"]):
        x = ref.layer(RCFG, ref.block_weights(w, i), x, mm, rows=64)
    return ref.head_logits(w, x.reshape(-1, x.shape[-1]), mm).reshape(
        tokens.shape + (-1,))


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(0, 96, (2, 128)),
                       jnp.int32)


def test_reference_agrees_with_the_program_in_float32(tokens):
    with jax.default_matmul_precision("highest"):
        got = _program_logits("fp32", tokens)
    want = _reference_logits(tokens)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4
    assert float(jnp.std(want)) > 0.5  # the logits say something


def test_window_and_gqa_are_in_the_reference(tokens):
    want = _reference_logits(tokens)
    wide = dict(RCFG, attn_window=0)
    w = ref.make_leaves(wide, 3, ref.all_leaves(wide))
    x = ref.embed(w, tokens)
    for i in range(2):
        x = ref.layer(wide, ref.block_weights(w, i), x, ref.matmul, rows=64)
    full = ref.head_logits(w, x.reshape(-1, 64)).reshape(want.shape)
    # the first 48 positions see the same keys either way
    assert float(jnp.max(jnp.abs(full[:, :48] - want[:, :48]))) < 1e-4
    assert float(jnp.max(jnp.abs(full[:, 48:] - want[:, 48:]))) > 1e-2


def test_reference_loss_matches_the_programs(tokens):
    from transformer_lm.transformer_lm import loss as program_loss

    labels = jnp.roll(tokens, -1, axis=1)
    w = ref.make_leaves(RCFG, 3, ref.all_leaves(RCFG))
    ours = ref.loss(RCFG, w, tokens, labels, rows=64, head_rows=64)
    theirs = program_loss(labels, _reference_logits(tokens))
    assert float(abs(ours - theirs)) < 1e-5


def test_weights_depend_on_seed_and_path_only():
    a = ref.make_leaves(RCFG, 7, ref.layer_leaves(RCFG, 1))
    b = ref.make_leaves(RCFG, 7, ref.all_leaves(RCFG))
    c = ref.make_leaves(RCFG, 8, ref.layer_leaves(RCFG, 1))
    for path in a:
        assert np.array_equal(a[path], b[path])
        assert not np.array_equal(a[path], c[path])
    big = ref.make_leaves(RCFG, 2**31 + 5, ref.outer_leaves(RCFG))
    assert np.isfinite(np.asarray(big["head/kernel"])).all()


def test_serve_deficit_zero_for_reference_greedy_large_for_random(tokens):
    logits = np.asarray(_reference_logits(tokens))[0]
    greedy = logits.argmax(-1)
    numbers, info = correct.serve_numbers([logits], [greedy])
    assert numbers == {"deficit_max": 0.0, "deficit_mean_sigma": 0.0}
    assert info == {"tokens": 128, "agreement": 1.0}
    rand = np.random.default_rng(1).integers(0, 96, 128)
    numbers, info = correct.serve_numbers([logits], [rand])
    assert numbers["deficit_mean_sigma"] > 1.0 and info["agreement"] < 0.1


def test_serve_statistic_separates_bf16_from_the_fp8_control(tokens):
    want = np.asarray(_reference_logits(tokens)).reshape(-1, 96)
    bf16 = np.asarray(_program_logits("bf16", tokens)).reshape(-1, 96)
    fp8 = np.asarray(_reference_logits(tokens, ref.matmul_fp8)).reshape(
        -1, 96)
    sound, _ = correct.serve_numbers([want], [bf16.argmax(-1)])
    control, _ = correct.serve_numbers([want], [fp8.argmax(-1)])
    assert control["deficit_mean_sigma"] > 3 * sound["deficit_mean_sigma"]
    limit = json.load(open(os.path.join(
        ROOT, "chipbench", "cells", "serve-gen-steady.json")))
    limit = limit["rehearsal"]["limits"]["deficit_mean_sigma"]
    assert sound["deficit_mean_sigma"] < limit < control[
        "deficit_mean_sigma"]


def test_judge_wants_a_limit_for_every_number():
    said = []
    assert correct.judge({"a": 0.0, "b": 1.0}, {"a": 0, "b": 2}, said.append)
    assert not correct.judge({"a": 0.1}, {"a": 0}, said.append)
    assert not correct.judge({"a": float("nan")}, {"a": 1}, said.append)
    assert "OVER" in said[-1] and "limit" in said[0]
    with pytest.raises(KeyError):
        correct.judge({"a": 0.0}, {"b": 1}, said.append)


def test_norm_gap_is_against_the_leaf_or_the_median_leaf():
    want = {"a": 1.0, "b": 1.0, "c": 1e-9}
    got = {"a": 1.01, "b": 1.0, "c": 2e-9}
    # c's own norm is all but zero: held against the median leaf
    assert correct._worst_norm_gap(got, want) == pytest.approx(0.01)


def _main(capsys, argv):
    """chipbench.run.main in this process (the look for a chip is
    answered by --rehearsal); returns (printed lines, result)."""
    assert cb_run.main(argv + ["--rehearsal"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return lines, json.loads(lines[-1])


def _numbers(lines, tag):
    return {l.split()[1]: float(l.split()[2]) for l in lines
            if l.startswith(tag)}


def test_train_passes_and_the_fp8_control_fails_a_limit(capsys):
    lines, result = _main(capsys, ["--workload", "train-4k", "--seed", "21",
                                   "--seconds", "1", "--control"])
    assert result["correct"] is True and RESULT_KEYS <= set(result)
    limits = json.load(open(os.path.join(
        ROOT, "chipbench", "cells", "train-4k.json")))["rehearsal"]["limits"]
    control = _numbers(lines, "control(fp8):")
    sound = _numbers(lines, "correct:")
    assert all(sound[k] <= limits[k] for k in sound)
    assert any(control[k] > limits[k] for k in control)
    assert control["grad_rel_rms"] > 3 * sound["grad_rel_rms"]


def test_a_step_that_leaves_the_state_unchanged_is_not_correct(
        capsys, monkeypatch):
    import optax
    from elasticdl_tpu.common import model_utils

    real = model_utils.get_model_spec

    def frozen(*args, **kwargs):
        spec = real(*args, **kwargs)
        spec.optimizer = lambda: optax.adamw(0.0)  # updates of zero
        return spec

    monkeypatch.setattr(model_utils, "get_model_spec", frozen)
    lines, result = _main(capsys, ["--workload", "train-4k", "--seed", "22",
                                   "--seconds", "1"])
    assert result["correct"] is False
    assert any("update_norm_gap" in l and "OVER" in l for l in lines)


def test_a_part_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    from elasticdl_tpu.training import trainer as trainer_mod

    real = trainer_mod._make_weights

    def half(batch_size, true_count):
        w = np.array(real(batch_size, true_count))
        w[batch_size // 2:] = 0.0
        return w

    monkeypatch.setattr(trainer_mod, "_make_weights", half)
    lines, result = _main(capsys, ["--workload", "train-4k", "--seed", "23",
                                   "--seconds", "1"])
    assert result["correct"] is False
    assert any("OVER" in l for l in lines)


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        capsys, monkeypatch):
    from elasticdl_tpu.serving import engine as engine_mod

    real = engine_mod.PagedContinuousBatchingEngine.step

    def off_by_one(self, *args, **kwargs):
        out = []
        for slot, request, tokens, finished in real(self, *args, **kwargs):
            tokens = [(t + 1) % 96 for t in tokens]
            request.generated[-len(tokens):] = tokens
            out.append((slot, request, tokens, finished))
        return out

    monkeypatch.setattr(engine_mod.PagedContinuousBatchingEngine, "step",
                        off_by_one)
    lines, result = _main(capsys, ["--workload", "serve-gen-steady",
                                   "--seed", "24", "--seconds", "2"])
    assert result["correct"] is False
