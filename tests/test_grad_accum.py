"""Gradient accumulation (the reference worker's local-update mode,
--get_model_steps: accumulate minibatch gradients, sync every Nth —
reference worker.py:1007-1089). TPU-native form: optax.MultiSteps inside
the compiled step — N train_step calls, one averaged dense update."""

import numpy as np

import jax

from elasticdl_tpu.common.args import parse_worker_args
from elasticdl_tpu.common.model_utils import load_model_spec_from_module
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.training.trainer import Trainer
from model_zoo.transformer_lm import transformer_lm as zoo

import pytest

# CI drills shard (make test-drills): the sub-5-min per-commit gate excludes this file.
pytestmark = pytest.mark.slow

PARAMS = (
    "vocab_size=32; seq_len=16; embed_dim=32; num_heads=2; num_layers=1"
)


def _tokens(bsz, seed):
    rs = np.random.RandomState(seed)
    return rs.randint(0, 32, size=(bsz, 17)).astype(np.int32)


def _as_batch(tokens):
    return {"tokens": tokens[:, :-1]}, tokens[:, 1:]


def test_two_microbatches_match_one_big_batch():
    import optax

    spec = load_model_spec_from_module(zoo)
    # SGD is linear in the gradient, so mean-of-microbatch-grads must
    # reproduce the big-batch update exactly (adamw's rsqrt normalization
    # amplifies fp32 reassociation noise on near-zero gradients).
    spec.optimizer = lambda: optax.sgd(0.1)
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    tokens = _tokens(8, seed=0)

    big = Trainer(spec, mesh=mesh, model_params=PARAMS)
    s_big = big.init_state(_as_batch(tokens))
    s_big, _ = big.train_step(s_big, _as_batch(tokens))

    accum = Trainer(spec, mesh=mesh, model_params=PARAMS,
                    grad_accum_steps=2)
    s_acc = accum.init_state(_as_batch(tokens[:4]))
    params0 = jax.tree.map(np.asarray, s_acc.params)
    s_acc, _ = accum.train_step(s_acc, _as_batch(tokens[:4]))
    # non-boundary microbatch: dense params must not move
    for a, b in zip(
        jax.tree.leaves(params0), jax.tree.leaves(s_acc.params)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    s_acc, _ = accum.train_step(s_acc, _as_batch(tokens[4:]))

    # boundary: averaged-gradient update == one big-batch update
    for a, b in zip(
        jax.tree.leaves(s_big.params), jax.tree.leaves(s_acc.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )


def test_accum_training_reduces_loss():
    spec = load_model_spec_from_module(zoo)
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(spec, mesh=mesh, model_params=PARAMS,
                      grad_accum_steps=4)
    batch = _as_batch(_tokens(8, seed=1))
    state = trainer.init_state(batch)
    first = None
    for _ in range(24):
        state, loss = trainer.train_step(state, batch)
        if first is None:
            first = float(loss)
    assert float(loss) < first, (first, float(loss))
    assert int(state.step) == 24


def _sparse_spec():
    import optax
    from flax import linen as nn

    from elasticdl_tpu.common.model_utils import ModelSpec
    from elasticdl_tpu.embedding.layer import Embedding

    class Rec(nn.Module):
        @nn.compact
        def __call__(self, features, training=False):
            emb = Embedding(
                input_dim=64, output_dim=8, sparse_grads=True, name="cat"
            )(features["ids"])
            return nn.Dense(1, name="out")(emb.mean(axis=1))[:, 0]

    return ModelSpec(
        model_fn=Rec,
        dataset_fn=lambda ds, mode, meta: ds,
        loss=lambda y, p, w: (w * (p - y) ** 2).sum() / w.sum(),
        optimizer=lambda: optax.sgd(0.1),
        eval_metrics_fn=lambda: {},
    )


def test_accum_sparse_row_parity():
    """Sparse-tapped tables under accumulation: k microbatches stage
    their dedup'd row grads and apply once per macro step — the final
    table, dense params, AND row-optimizer slots must equal the one
    big-batch update (reference local-update
    semantics, worker.py:822-828)."""
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 16, size=(8, 4)).astype(np.int32)
    labels = rs.rand(8).astype(np.float32)

    big = Trainer(_sparse_spec(), mesh=mesh_lib.local_mesh())
    s_big = big.init_state(({"ids": ids}, labels))
    s_big, _ = big.train_step(s_big, ({"ids": ids}, labels))

    acc = Trainer(_sparse_spec(), mesh=mesh_lib.local_mesh(),
                  grad_accum_steps=2)
    s_acc = acc.init_state(({"ids": ids[:4]}, labels[:4]))
    table0 = np.asarray(
        jax.tree.leaves(s_acc.params["cat"])[0]
    ).copy()
    s_acc, _ = acc.train_step(s_acc, ({"ids": ids[:4]}, labels[:4]))
    # non-boundary microbatch: the embedding table must not move
    np.testing.assert_array_equal(
        table0, np.asarray(jax.tree.leaves(s_acc.params["cat"])[0])
    )
    s_acc, _ = acc.train_step(s_acc, ({"ids": ids[4:]}, labels[4:]))

    for a, b in zip(
        jax.tree.leaves(s_big.params), jax.tree.leaves(s_acc.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )
    for a, b in zip(
        jax.tree.leaves(s_big.embed_opt_state),
        jax.tree.leaves(s_acc.embed_opt_state),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )


def test_accum_host_spill_parity():
    """Host-spill tables under accumulation: staged row grads (weighted
    1/k) apply through the engines once per macro step; the trained
    host rows must equal one big-batch step's."""
    from elasticdl_tpu.common.model_utils import (
        format_params_str,
        load_model_spec_from_module,
    )
    from elasticdl_tpu.embedding.host_bridge import (
        HostEmbeddingManager,
    )
    from elasticdl_tpu.embedding.host_spill import HostSpillEmbeddingEngine
    from model_zoo.deepfm_host_embedding import deepfm_host_embedding as z

    def build(accum):
        spec = load_model_spec_from_module(z)
        tr = Trainer(
            spec, mesh=mesh_lib.local_mesh(),
            model_params=format_params_str(
                dict(input_length=5, fc_unit=4)
            ),
            grad_accum_steps=accum,
        )
        mgr = HostEmbeddingManager()
        mgr.register(
            "edl_embedding", "feature",
            HostSpillEmbeddingEngine(8, optimizer="sgd", lr=0.1),
        )
        mgr.register(
            "edl_id_bias", "feature",
            HostSpillEmbeddingEngine(1, optimizer="sgd", lr=0.1),
        )
        tr.attach_host_embeddings(mgr)
        return tr, mgr

    rs = np.random.RandomState(3)
    ids = rs.randint(0, 40, size=(8, 5)).astype(np.int32)
    labels = rs.randint(0, 2, size=(8,)).astype(np.int32)

    big, big_mgr = build(1)
    s_big = big.init_state(({"feature": ids}, labels))
    s_big, _ = big.train_step(s_big, ({"feature": ids}, labels))

    acc, acc_mgr = build(2)
    s_acc = acc.init_state(({"feature": ids[:4]}, labels[:4]))
    s_acc, _ = acc.train_step(s_acc, ({"feature": ids[:4]}, labels[:4]))
    # mid-cycle: engines untouched, step counters unmoved
    assert acc_mgr.tables()["edl_embedding"].engine._step == 0
    s_acc, _ = acc.train_step(s_acc, ({"feature": ids[4:]}, labels[4:]))
    assert acc_mgr.tables()["edl_embedding"].engine._step == 1

    for table in ("edl_embedding", "edl_id_bias"):
        bids, bvals = big_mgr.tables()[table].engine.param.export_rows()
        aids, avals = acc_mgr.tables()[table].engine.param.export_rows()
        bmap = dict(zip(bids.tolist(), bvals))
        amap = dict(zip(aids.tolist(), avals))
        assert sorted(bmap) == sorted(amap)
        for i in bmap:
            np.testing.assert_allclose(
                amap[i], bmap[i], rtol=1e-5, atol=1e-7
            )
    for a, b in zip(
        jax.tree.leaves(s_big.params), jax.tree.leaves(s_acc.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
        )


def test_get_model_steps_cli_alias():
    base = [
        "--worker_id", "0", "--model_zoo", "model_zoo",
        "--model_def", "m.m.custom_model", "--master_addr", "x:1",
    ]
    args = parse_worker_args(base + ["--grad_accum_steps", "4"])
    assert args.grad_accum_steps == 4
    args = parse_worker_args(base + ["--get_model_steps", "3"])
    assert args.grad_accum_steps == 3
    args = parse_worker_args(base)
    assert args.grad_accum_steps == 1
