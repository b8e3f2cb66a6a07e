"""Export parity for the host-resident embedding tier (review weak
#6): the exported artifact carries host rows, serving reproduces
training-time predictions exactly, and the mesh handler validates the
artifact (the reference's model_handler_test export-parity coverage)."""

import numpy as np
import pytest

from elasticdl_tpu.api import exporter
from elasticdl_tpu.common.constants import DistributionStrategy
from elasticdl_tpu.common.model_handler import (
    MeshModelHandler,
    ModelHandler,
)
from elasticdl_tpu.embedding.host_bridge import HostEmbeddingManager
from elasticdl_tpu.embedding.host_spill import HostSpillEmbeddingEngine
from tests.test_host_bridge import _batches, _host_trainer


def _fresh_manager():
    manager = HostEmbeddingManager()
    manager.register(
        "edl_embedding", "feature",
        HostSpillEmbeddingEngine(8, optimizer="sgd", lr=0.1),
    )
    manager.register(
        "edl_id_bias", "feature",
        HostSpillEmbeddingEngine(1, optimizer="sgd", lr=0.1),
    )
    return manager


def _train(n=3):
    trainer, manager = _host_trainer()
    batches = _batches(n)
    state = trainer.init_state(batches[0])
    for b in batches:
        state, _ = trainer.train_step(state, b)
    return trainer, manager, state, batches


def test_export_and_serve_parity(tmp_path):
    trainer, manager, state, batches = _train()
    export_dir = str(tmp_path / "export")
    exporter.export_model(
        trainer.model, state, export_dir, host_manager=manager
    )

    payload, meta = exporter.load_exported(export_dir)
    assert set(payload["host_embeddings"]) == {
        "edl_embedding", "edl_id_bias",
    }
    assert meta["version"] == int(state.step)

    # a FRESH manager (as a serving process would build from the spec)
    serving_manager = _fresh_manager()
    serve = exporter.make_serving_fn(
        trainer.model, payload, host_manager=serving_manager
    )
    features = batches[0][0]
    want = trainer.forward(state, dict(features))
    got = serve(dict(features))
    for key in want:
        np.testing.assert_allclose(
            np.asarray(got[key]), np.asarray(want[key]), atol=1e-6
        )


def test_serving_without_manager_raises(tmp_path):
    trainer, manager, state, _ = _train(1)
    export_dir = str(tmp_path / "export")
    exporter.export_model(
        trainer.model, state, export_dir, host_manager=manager
    )
    payload, _ = exporter.load_exported(export_dir)
    with pytest.raises(ValueError, match="host-resident tables"):
        exporter.make_serving_fn(trainer.model, payload)
    # strict table-set equality: a manager table absent from the
    # artifact would serve lazily-initialized random rows
    bigger = _fresh_manager()
    bigger.register(
        "extra", "feature", HostSpillEmbeddingEngine(2, optimizer="sgd")
    )
    with pytest.raises(ValueError, match="host-table mismatch"):
        exporter.make_serving_fn(trainer.model, payload,
                                 host_manager=bigger)
    # artifact written WITHOUT the manager + host-tier manager at serve
    # time -> clear construction-time error, not a KeyError inside jit
    bare_dir = str(tmp_path / "bare")
    exporter.export_model(trainer.model, state, bare_dir)
    bare_payload, _ = exporter.load_exported(bare_dir)
    with pytest.raises(ValueError, match="artifact carries none"):
        exporter.make_serving_fn(trainer.model, bare_payload,
                                 host_manager=_fresh_manager())


def test_serving_never_mutates_callers_manager(tmp_path):
    """make_serving_fn seeds a fresh clone: a live training manager
    passed in keeps its rows (slots/step stay aligned)."""
    trainer, manager, state, batches = _train(2)
    export_dir = str(tmp_path / "export")
    exporter.export_model(
        trainer.model, state, export_dir, host_manager=manager
    )
    # train one more step: live rows move past the exported ones
    state, _ = trainer.train_step(state, batches[0])
    engine = manager.tables()["edl_embedding"].engine
    ids_live, vals_live = engine.param.export_rows()
    ids_live, vals_live = ids_live.copy(), vals_live.copy()

    payload, _ = exporter.load_exported(export_dir)
    serve = exporter.make_serving_fn(
        trainer.model, payload, host_manager=manager
    )
    serve(dict(batches[0][0]))  # serving works...
    ids_after, vals_after = engine.param.export_rows()
    # ...and the live engine is bit-identical to before
    np.testing.assert_array_equal(np.sort(ids_after), np.sort(ids_live))
    np.testing.assert_allclose(
        vals_after[np.argsort(ids_after)],
        vals_live[np.argsort(ids_live)], atol=0,
    )


def test_mesh_handler_validates_and_exports(tmp_path):
    trainer, manager, state, batches = _train(1)
    handler = ModelHandler.get_model_handler(
        DistributionStrategy.PARAMETER_SERVER
    )
    assert isinstance(handler, MeshModelHandler)
    export_dir = str(tmp_path / "export")
    handler.get_model_to_export(
        trainer.model, state, export_dir, host_manager=manager
    )
    payload, _ = exporter.load_exported(export_dir)
    assert set(payload["host_embeddings"]) == set(manager.tables())

    # validation: a manager expecting MORE tables than the artifact has
    bigger = _fresh_manager()
    bigger.register(
        "extra", "feature", HostSpillEmbeddingEngine(2, optimizer="sgd")
    )
    with pytest.raises(RuntimeError, match="host-table mismatch"):
        handler._validate_export(state, export_dir, bigger)


def test_export_from_checkpoint_with_host_state(tmp_path):
    """Handler export prefers the checkpoint AND restores host rows from
    the same version."""
    from elasticdl_tpu.checkpoint import CheckpointSaver

    trainer, manager, state, batches = _train(2)
    ckpt_dir = str(tmp_path / "ckpt")
    saver = CheckpointSaver(ckpt_dir, checkpoint_steps=1,
                           extra_state_fn=manager.flat_state)
    ckpt_version = int(state.step)
    saver.save(state, ckpt_version)
    # export the saved manager's rows now: the extra train step below
    # mutates the live engines in place
    ids_b, vals_b = (
        manager.tables()["edl_embedding"].engine.param.export_rows()
    )
    ids_b, vals_b = ids_b.copy(), vals_b.copy()

    # train further: live state is now AHEAD of the checkpoint
    state_live, _ = trainer.train_step(state, batches[0])

    # live engine rows AFTER the extra step (to prove no mutation below)
    live_ids, live_vals = (
        manager.tables()["edl_embedding"].engine.param.export_rows()
    )
    live_ids, live_vals = live_ids.copy(), live_vals.copy()

    handler = MeshModelHandler(checkpoint_dir=ckpt_dir)
    export_dir = str(tmp_path / "export")
    handler.get_model_to_export(
        trainer.model, state_live, export_dir, host_manager=manager
    )
    payload, meta = exporter.load_exported(export_dir)
    # exported the checkpointed version, not the live step
    assert meta["version"] == ckpt_version
    # artifact host rows == rows at CHECKPOINT time (not the further-
    # trained live rows), id-aligned
    rec = payload["host_embeddings"]["edl_embedding"]
    ids_a, vals_a = np.asarray(rec["ids"]), np.asarray(rec["values"])
    np.testing.assert_array_equal(np.sort(ids_a), np.sort(ids_b))
    np.testing.assert_allclose(
        vals_a[np.argsort(ids_a)], vals_b[np.argsort(ids_b)], atol=1e-6
    )
    # ...and the LIVE engines were NOT rewound by the export (restore
    # goes into a throwaway clone)
    ids_now, vals_now = (
        manager.tables()["edl_embedding"].engine.param.export_rows()
    )
    np.testing.assert_array_equal(np.sort(ids_now), np.sort(live_ids))
    np.testing.assert_allclose(
        vals_now[np.argsort(ids_now)], live_vals[np.argsort(live_ids)],
        atol=0,
    )
