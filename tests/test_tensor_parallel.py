"""Tensor parallelism is real: transformer
kernels annotated with nn.with_partitioning over `tp` actually shard over
a tp>1 mesh, the compiled train step contains the Megatron all-reduces,
and the math matches the single-device model."""

import numpy as np

import jax
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common.constants import MeshAxis
from elasticdl_tpu.common.model_utils import (
    format_params_str,
    load_model_spec_from_module,
)
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.training.trainer import Trainer

import pytest

# CI drills shard (make test-drills): the sub-5-min per-commit gate excludes this file.
pytestmark = pytest.mark.slow


def _trainer(mesh, seq_len=32, extra=None):
    from model_zoo.transformer_lm import transformer_lm as zoo

    cfg = dict(vocab_size=64, seq_len=seq_len, embed_dim=32, num_heads=4,
               num_layers=1, attn_impl="xla")
    if extra:
        cfg.update(extra)
    return Trainer(
        load_model_spec_from_module(zoo),
        mesh=mesh,
        model_params=format_params_str(cfg),
    )


def _batch(seq_len=32, batch=8, seed=0):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, 64, size=(batch, seq_len + 1)).astype(np.int32)
    return ({"tokens": tokens[:, :-1]}, tokens[:, 1:])


def test_params_sharded_over_tp():
    mesh = mesh_lib.build_mesh({"dp": 2, "tp": 4})
    trainer = _trainer(mesh)
    state = trainer.init_state(_batch())
    p = state.params["block_0"]

    def spec(leaf):
        return leaf.sharding.spec

    # column-parallel: output dim over tp
    assert spec(p["attn"]["qkv"]["kernel"]) == P(None, MeshAxis.TP)
    assert spec(p["mlp_up"]["kernel"]) == P(None, MeshAxis.TP)
    # row-parallel: input dim over tp
    assert spec(p["attn"]["proj"]["kernel"]) == P(MeshAxis.TP, None)
    assert spec(p["mlp_down"]["kernel"]) == P(MeshAxis.TP, None)
    assert spec(state.params["head"]["kernel"]) == P(None, MeshAxis.TP)
    # every device holds only its shard of an annotated kernel
    kernel = p["mlp_up"]["kernel"]
    shard_shape = kernel.sharding.shard_shape(kernel.shape)
    assert shard_shape[1] == kernel.shape[1] // 4


def test_optimizer_state_co_sharded():
    """optax moments mirror their param's tp spec (suffix matching in
    infer_state_pspec)."""
    mesh = mesh_lib.build_mesh({"tp": 8})
    trainer = _trainer(mesh)
    state = trainer.init_state(_batch())
    found = []

    def check(path, leaf):
        keys = tuple(
            str(getattr(k, "key", getattr(k, "name", k))) for k in path
        )
        if keys[-2:] == ("qkv", "kernel") and hasattr(leaf, "sharding"):
            found.append(leaf.sharding.spec)

    jax.tree_util.tree_map_with_path(check, state.opt_state)
    # adamw: mu and nu both carry the annotation
    assert len(found) >= 2
    assert all(s == P(None, MeshAxis.TP) for s in found)


def test_compiled_step_contains_tp_collectives():
    """On a tp-ONLY mesh (dp=fsdp=1) any all-reduce in the compiled step
    is TP-induced: the row-parallel matmuls' partial-sum reductions. A
    replicated (unannotated) model compiles with no such collective."""
    mesh = mesh_lib.build_mesh({"tp": 8})
    trainer = _trainer(mesh)
    batch = _batch()
    state = trainer.init_state(batch)
    trainer._train_step = trainer._build_train_step()
    features, labels = batch
    weights = trainer.make_weights(8, None)
    with trainer.mesh:
        hlo = (
            trainer._train_step.lower(state, features, labels, weights)
            .compile().as_text()
        )
    assert "all-reduce" in hlo or "all-gather" in hlo

    # control: tp annotations off -> no tp collectives on the same mesh
    trainer_off = _trainer(mesh, extra={"tp_shard": False})
    state_off = trainer_off.init_state(batch)
    trainer_off._train_step = trainer_off._build_train_step()
    with trainer_off.mesh:
        hlo_off = (
            trainer_off._train_step.lower(
                state_off, features, labels, weights
            ).compile().as_text()
        )
    assert "all-reduce" not in hlo_off


def test_tp_fused_head_matches_plain():
    """fused_head's chunked cross-entropy must compose with the
    tp-sharded (vocab-split) head kernel: same losses as the plain-head
    tp trainer from the same init."""
    batch = _batch()
    mesh = mesh_lib.build_mesh({"tp": 8})

    plain = _trainer(mesh)
    p_state = plain.init_state(batch)
    fused = _trainer(mesh_lib.build_mesh({"tp": 8}),
                     extra={"fused_head": True})
    f_state = fused.init_state(batch)

    for _ in range(2):
        p_state, lp = plain.train_step(p_state, batch)
        f_state, lf = fused.train_step(f_state, batch)
        np.testing.assert_allclose(float(lf), float(lp), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p_state.params),
                    jax.tree.leaves(f_state.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )


def test_tp_loss_matches_single_device():
    """The tp=8 compiled step computes the same loss and updates as the
    single-device model from the same init."""
    batch = _batch()

    single = _trainer(mesh_lib.build_mesh(
        {"dp": 1}, devices=jax.devices()[:1]))
    s_state = single.init_state(batch)

    tp = _trainer(mesh_lib.build_mesh({"tp": 8}))
    t_state = tp.init_state(batch)

    # same seed -> same init values regardless of mesh
    for a, b in zip(jax.tree.leaves(s_state.params),
                    jax.tree.leaves(t_state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)

    losses_s, losses_t = [], []
    for _ in range(3):
        s_state, ls = single.train_step(s_state, batch)
        t_state, lt = tp.train_step(t_state, batch)
        losses_s.append(float(ls))
        losses_t.append(float(lt))
    np.testing.assert_allclose(losses_t, losses_s, rtol=1e-5, atol=1e-6)
