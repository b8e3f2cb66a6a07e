"""Row-sparse embedding update engine (embedding/sparse_update.py).

Covers the per-step cost of training a model with
a big embedding table must not scale with vocab (the reference's whole
point: only touched rows move, ps/optimizer_wrapper.py:70-351 /
go/pkg/ps/optimizer.go per-row kernels), while the numerics must match the
dense-update-then-mask oracle (embedding/sparse_optim.py) exactly.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.common.model_utils import ModelSpec
from elasticdl_tpu.embedding.layer import Embedding
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.training.trainer import Trainer


def _make_model(vocab, dim, sparse, combiner="sum"):
    class Rec(nn.Module):
        @nn.compact
        def __call__(self, features, training=False):
            emb = Embedding(
                input_dim=vocab, output_dim=dim, combiner=combiner,
                sparse_grads=sparse, name="cat",
            )(features["ids"])
            return nn.Dense(1, name="out")(emb)[:, 0]

    return Rec


def _loss(labels, predictions, weights=None):
    per = optax.sigmoid_binary_cross_entropy(
        predictions, labels.astype(jnp.float32)
    )
    if weights is None:
        return jnp.mean(per)
    return jnp.sum(per * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def _spec(model_fn, optimizer):
    return ModelSpec(
        model_fn=model_fn,
        dataset_fn=lambda ds, mode, meta: ds,
        loss=_loss,
        optimizer=optimizer,
        eval_metrics_fn=lambda: {},
    )


def _batch(vocab, bsz=8, width=4, seed=0):
    rng = np.random.RandomState(seed)
    # only ids < vocab // 4: plenty of untouched rows
    ids = rng.randint(0, max(vocab // 4, 2), size=(bsz, width))
    ids = ids.astype(np.int32)
    labels = rng.randint(0, 2, size=(bsz,)).astype(np.int32)
    return ({"ids": ids}, labels)


def _train(sparse, optimizer, vocab=64, dim=8, steps=3):
    trainer = Trainer(
        _spec(_make_model(vocab, dim, sparse), optimizer),
        mesh=mesh_lib.local_mesh(),
    )
    batches = [_batch(vocab, seed=s) for s in range(steps)]
    state = trainer.init_state(batches[0])
    losses = []
    for b in batches:
        state, loss = trainer.train_step(state, b)
        losses.append(float(loss))
    return trainer, state, losses


@pytest.mark.parametrize(
    "optimizer",
    [
        lambda: optax.sgd(0.1),
        lambda: optax.adam(1e-2),
        lambda: optax.adamw(1e-2, weight_decay=0.01),
        lambda: optax.adagrad(0.1),
    ],
    ids=["sgd", "adam", "adamw", "adagrad"],
)
def test_matches_dense_masked_oracle(optimizer):
    """The tapped path takes the exact same trajectory as the dense
    update + row mask (make_row_sparse) on every optimizer family the
    reference's Go PS ships kernels for."""
    _, s_sparse, l_sparse = _train(True, optimizer)
    _, s_dense, l_dense = _train(False, optimizer)
    np.testing.assert_allclose(l_sparse, l_dense, rtol=1e-5)
    t_sparse = s_sparse.params["cat"]["embedding_table"]
    t_dense = s_dense.params["cat"]["embedding_table"]
    np.testing.assert_allclose(
        np.asarray(t_sparse), np.asarray(t_dense), rtol=1e-5, atol=1e-6
    )
    # dense layers identical too
    np.testing.assert_allclose(
        np.asarray(s_sparse.params["out"]["kernel"]),
        np.asarray(s_dense.params["out"]["kernel"]),
        rtol=1e-5, atol=1e-6,
    )


def test_untouched_rows_and_slots_frozen():
    """Adam must not move rows (or their moments) the batch never
    touched — the OptimizerWrapper contract."""
    trainer, state, _ = _train(True, lambda: optax.adam(1e-2), vocab=64)
    init_trainer = Trainer(
        _spec(_make_model(64, 8, True), lambda: optax.adam(1e-2)),
        mesh=mesh_lib.local_mesh(),
    )
    state0 = init_trainer.init_state(_batch(64))
    table0 = np.asarray(state0.params["cat"]["embedding_table"])
    table = np.asarray(state.params["cat"]["embedding_table"])
    # ids were all < 16; rows 16+ must be bit-identical
    np.testing.assert_array_equal(table[16:], table0[16:])
    assert not np.allclose(table[:16], table0[:16])
    (slots,) = [
        v for k, v in state.embed_opt_state.items()
        if k.endswith("embedding_table")
    ]
    mu = np.asarray(jax.tree.leaves(slots)[1])  # (count, mu, nu)
    assert mu.shape[0] == 64
    np.testing.assert_array_equal(mu[16:], np.zeros_like(mu[16:]))


def test_eval_path_unaffected():
    """forward() (no perturbations passed) must produce the same
    predictions as a dense-path model with the same params."""
    trainer, state, _ = _train(True, lambda: optax.adam(1e-2))
    batch = _batch(64, seed=9)
    preds = trainer.forward(state, batch[0])
    dense_model = _make_model(64, 8, False)()
    manual = dense_model.apply(
        {"params": state.params, **state.model_state},
        batch[0], training=False,
    )
    np.testing.assert_allclose(
        np.asarray(preds), np.asarray(manual), rtol=1e-5
    )


def _compiled_hlo(vocab, sparse):
    trainer = Trainer(
        _spec(_make_model(vocab, 16, sparse), lambda: optax.adam(1e-3)),
        mesh=mesh_lib.local_mesh(),
    )
    batch = _batch(vocab)
    state = trainer.init_state(batch)
    trainer._train_step = trainer._build_train_step()
    features, labels = batch
    weights = trainer.make_weights(8, None)
    with trainer.mesh:
        lowered = trainer._train_step.lower(
            state, features, labels, weights
        )
    return lowered.compile().as_text()


def _vocab_sized_compute_ops(hlo, vocab, dim=16):
    """HLO ops producing a [vocab, dim] result, excluding parameters,
    tuples/get-tuple-element plumbing, and in-place row updates.
    Depending on the XLA version the sparse row writes lower either to
    named `scatter` ops or to `dynamic-update-slice` (and
    `select_dynamic-update-slice` fusions); both touch only the updated
    rows at runtime when the destination buffer is donated, so both are
    O(touched rows), not O(vocab). Anything else vocab-sized (adds,
    selects, multiplies, zeros broadcasts) is real O(vocab) per-step
    traffic."""
    import re

    pat = re.compile(r"= f32\[%d,%d\]\{[0-9,]*\} ([\w-]+)" % (vocab, dim))
    ops = []
    for line in hlo.splitlines():
        m = pat.search(line)
        if not m:
            continue
        kind = m.group(1)
        if kind in ("parameter", "tuple", "get-tuple-element"):
            continue
        if "scatter" in line or "dynamic-update-slice" in line:
            continue
        ops.append(line.strip()[:120])
    return ops


def test_cost_does_not_scale_with_vocab():
    """The whole point: the compiled step's only
    vocab-sized operations are the in-place row scatters into the
    donated table + slot buffers — every other op is O(touched rows).
    The dense-masked oracle by contrast runs vocab-sized compute every
    step (Adam over the full table, then the mask)."""
    vocab = 16 * 1024
    hlo = _compiled_hlo(vocab, True)
    assert "input_output_alias" in hlo  # donation: scatters are in-place
    leftovers = _vocab_sized_compute_ops(hlo, vocab)
    assert not leftovers, (
        "O(vocab) compute survived in the sparse path:\n%s"
        % "\n".join(leftovers)
    )
    dense_hlo = _compiled_hlo(vocab, False)
    dense_big = _vocab_sized_compute_ops(dense_hlo, vocab)
    assert len(dense_big) >= 3, (
        "dense-masked oracle should run vocab-sized compute (got %d big "
        "ops) — if it stopped, the assertion above is vacuous"
        % len(dense_big)
    )


def test_auto_threshold_taps_big_tables(monkeypatch):
    """sparse_grads=None: tables over the partition threshold tap
    automatically (model_handler.py:98-102's 2 MB rule)."""
    from elasticdl_tpu.common import constants

    monkeypatch.setattr(
        constants, "EMBEDDING_PARTITION_THRESHOLD_BYTES", 1024
    )
    trainer = Trainer(
        _spec(_make_model(64, 8, None), lambda: optax.sgd(0.1)),
        mesh=mesh_lib.local_mesh(),
    )
    state = trainer.init_state(_batch(64))
    assert trainer._sparse_paths, "64*8*4B > 1KiB: tap expected"
    state, loss = trainer.train_step(state, _batch(64))
    assert np.isfinite(float(loss))


def test_double_call_raises():
    class DoubleCall(nn.Module):
        @nn.compact
        def __call__(self, features, training=False):
            layer = Embedding(
                input_dim=32, output_dim=4, combiner="sum",
                sparse_grads=True, name="shared",
            )
            return nn.Dense(1)(
                layer(features["ids"]) + layer(features["ids"])
            )[:, 0]

    trainer = Trainer(
        _spec(DoubleCall, lambda: optax.sgd(0.1)),
        mesh=mesh_lib.local_mesh(),
    )
    with pytest.raises(ValueError, match="more than once"):
        trainer.init_state(_batch(32))
