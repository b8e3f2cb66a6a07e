"""Host-spill embedding tier, integrated end-to-end (review round-1
item #5): deepfm trains with tables in the host store, loss matches the
HBM path on the same data, and engine state rides the checkpoint."""

import numpy as np
import pytest

import jax

from elasticdl_tpu.api.local_executor import LocalExecutor
from elasticdl_tpu.common.model_utils import (
    format_params_str,
    get_model_spec,
    load_model_spec_from_module,
)
from elasticdl_tpu.data import recordio_gen
from elasticdl_tpu.embedding.host_bridge import (
    HostEmbeddingManager,
    build_manager_from_spec,
    restore_host_state,
)
from elasticdl_tpu.embedding.host_spill import HostSpillEmbeddingEngine
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.training.trainer import Trainer

MODEL_ZOO = "model_zoo"
VOCAB, DIM, LENGTH, FC = 100, 8, 5, 4


def _batches(n, batch=8, seed=3):
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        ids = rng.randint(0, VOCAB, size=(batch, LENGTH)).astype(np.int32)
        labels = rng.randint(0, 2, size=(batch,)).astype(np.int32)
        out.append(({"feature": ids}, labels))
    return out


def _host_trainer():
    from model_zoo.deepfm_host_embedding import deepfm_host_embedding as zoo

    spec = load_model_spec_from_module(zoo)
    trainer = Trainer(
        spec,
        mesh=mesh_lib.local_mesh(),
        model_params=format_params_str(
            dict(input_length=LENGTH, fc_unit=FC)
        ),
    )
    manager = HostEmbeddingManager()
    manager.register(
        "edl_embedding", "feature",
        HostSpillEmbeddingEngine(DIM, optimizer="sgd", lr=0.1),
    )
    manager.register(
        "edl_id_bias", "feature",
        HostSpillEmbeddingEngine(1, optimizer="sgd", lr=0.1),
    )
    trainer.attach_host_embeddings(manager)
    return trainer, manager


def _hbm_trainer():
    from model_zoo.deepfm_edl_embedding import deepfm_edl_embedding as zoo

    spec = load_model_spec_from_module(zoo)
    return Trainer(
        spec,
        mesh=mesh_lib.local_mesh(),
        model_params=format_params_str(
            dict(input_dim=VOCAB, embedding_dim=DIM,
                 input_length=LENGTH, fc_unit=FC)
        ),
    )


def test_parity_with_hbm_path():
    """Same data, same init, same optimizer: host-tier deepfm's loss
    trajectory matches the HBM-tier deepfm (the reference proved its PS
    path this way — worker_ps_interaction_test.py:197-265 trains against
    a local baseline)."""
    batches = _batches(6)

    hbm = _hbm_trainer()
    hbm_state = hbm.init_state(batches[0])
    hbm_params = jax.tree.map(np.asarray, jax.device_get(hbm_state.params))

    host, manager = _host_trainer()
    host_state = host.init_state(batches[0])

    # Seed the host engines with the HBM model's initial tables, and copy
    # the dense (Dense_*) params so both models start identically.
    all_ids = np.arange(VOCAB, dtype=np.int64)
    tables = manager.tables()
    tables["edl_embedding"].engine.param.set_rows(
        all_ids, hbm_params["edl_embedding"]["embedding_table"]
    )
    tables["edl_id_bias"].engine.param.set_rows(
        all_ids, hbm_params["edl_id_bias"]["embedding_table"]
    )
    new_params = {
        k: hbm_params[k] for k in host_state.params
    }
    host_state = host_state.replace(
        params=jax.device_put(
            new_params,
            jax.tree.map(lambda x: x.sharding, dict(host_state.params)),
        )
    )

    hbm_losses, host_losses = [], []
    for b in batches:
        hbm_state, l1 = hbm.train_step(hbm_state, b)
        host_state, l2 = host.train_step(host_state, b)
        hbm_losses.append(float(l1))
        host_losses.append(float(l2))
    np.testing.assert_allclose(host_losses, hbm_losses, rtol=2e-4,
                               atol=2e-5)

    # and the trained tables themselves match
    ids, values = tables["edl_embedding"].engine.param.export_rows()
    order = np.argsort(ids)
    final_hbm = np.asarray(
        jax.device_get(hbm_state.params["edl_embedding"]["embedding_table"])
    )
    np.testing.assert_allclose(
        values[order], final_hbm[np.sort(ids)], rtol=2e-4, atol=2e-5
    )


def test_gradients_only_touch_pulled_rows():
    """Untouched host rows never move (reference OptimizerWrapper
    semantics: only looked-up rows and slots are written back)."""
    host, manager = _host_trainer()
    batches = _batches(1)
    state = host.init_state(batches[0])
    engine = manager.tables()["edl_embedding"].engine

    all_ids = np.arange(VOCAB, dtype=np.int64)
    before = engine.param.lookup(all_ids).copy()
    state, _ = host.train_step(state, batches[0])
    after = engine.param.lookup(all_ids)

    touched = np.unique(batches[0][0]["feature"])
    untouched = np.setdiff1d(all_ids, touched)
    np.testing.assert_array_equal(after[untouched], before[untouched])
    assert np.abs(after[touched] - before[touched]).max() > 0


def test_engine_failure_counts_dropped_rows():
    """A host-engine apply failure is contained (the step still
    completes — the state was donated, so there is no retry) AND
    observable: tier_health counts the failed cycle and the row updates
    that were dropped, and a recovered engine stops the counters."""
    host, manager = _host_trainer()
    batches = _batches(3)
    state = host.init_state(batches[0])
    state, _ = host.train_step(state, batches[0])
    assert host.tier_health == {
        "host_failed_cycles": 0, "host_dropped_row_updates": 0,
    }

    engine = manager.tables()["edl_embedding"].engine
    real_apply = engine.apply_gradients

    def broken(*a, **kw):
        raise RuntimeError("injected engine failure")

    engine.apply_gradients = broken
    state, loss = host.train_step(state, batches[1])
    assert np.isfinite(float(loss))  # contained, not propagated
    assert host.tier_health["host_failed_cycles"] == 1
    expect_rows = manager.pending_row_count()
    assert expect_rows > 0
    assert host.tier_health["host_dropped_row_updates"] == expect_rows

    engine.apply_gradients = real_apply
    state, _ = host.train_step(state, batches[2])
    assert host.tier_health["host_failed_cycles"] == 1


def test_engine_failure_in_accum_cycle_counts_all_staged_rows():
    """With gradient accumulation, a macro-boundary apply_staged
    failure drops EVERY staged microbatch's row updates — the counter
    must cover the whole cycle, not just the last microbatch."""
    from model_zoo.deepfm_host_embedding import deepfm_host_embedding as zoo

    spec = load_model_spec_from_module(zoo)
    host = Trainer(
        spec,
        mesh=mesh_lib.local_mesh(),
        model_params=format_params_str(
            dict(input_length=LENGTH, fc_unit=FC)
        ),
        grad_accum_steps=2,
    )
    manager = HostEmbeddingManager()
    for name, dim in (("edl_embedding", DIM), ("edl_id_bias", 1)):
        manager.register(
            name, "feature",
            HostSpillEmbeddingEngine(dim, optimizer="sgd", lr=0.1),
        )
    host.attach_host_embeddings(manager)
    batches = _batches(2)
    state = host.init_state(batches[0])

    for t in manager.tables().values():
        t.engine.apply_gradients = lambda *a, **kw: (_ for _ in ()).throw(
            RuntimeError("injected")
        )
    state, _ = host.train_step(state, batches[0])  # microbatch 1: stages
    rows_mb1 = manager.staged_row_count()
    assert rows_mb1 > 0
    assert host.tier_health["host_failed_cycles"] == 0  # no apply yet
    state, _ = host.train_step(state, batches[1])  # boundary: fails
    assert host.tier_health["host_failed_cycles"] == 1
    # both microbatches' staged rows counted, not just the last pull
    assert (host.tier_health["host_dropped_row_updates"]
            > manager.pending_row_count())
    assert (host.tier_health["host_dropped_row_updates"]
            >= rows_mb1 + manager.pending_row_count())


def test_zoo_e2e_local_executor(tmp_path):
    """The deepfm_host_embedding zoo family trains + evaluates through
    the LocalExecutor like every other family (test_model_zoo pattern)."""
    train_dir, val_dir = str(tmp_path / "train"), str(tmp_path / "val")
    recordio_gen.gen_frappe_like(train_dir, num_files=1,
                                 records_per_file=32)
    recordio_gen.gen_frappe_like(val_dir, num_files=1,
                                 records_per_file=32, seed=7)
    spec = get_model_spec(
        MODEL_ZOO, "deepfm_host_embedding.deepfm_host_embedding.custom_model"
    )
    executor = LocalExecutor(
        spec,
        training_data=train_dir,
        validation_data=val_dir,
        minibatch_size=8,
        num_epochs=1,
        records_per_task=32,
    )
    state, metrics = executor.run()
    assert int(state.step) == 4
    assert np.isfinite(executor.losses).all()
    assert 0.0 <= metrics["logits_accuracy"] <= 1.0
    # the engines actually hold trained rows
    ids, _ = (
        executor._host_manager.tables()["edl_embedding"]
        .engine.param.export_rows()
    )
    assert ids.size > 0


def test_checkpoint_roundtrip_and_resume(tmp_path):
    """Engine state rides the sharded checkpoint: a fresh manager
    restored from disk equals the trained one, and a resumed executor
    continues from the saved version."""
    train_dir = str(tmp_path / "train")
    ckpt_dir = str(tmp_path / "ckpt")
    recordio_gen.gen_frappe_like(train_dir, num_files=1,
                                 records_per_file=32)
    spec = get_model_spec(
        MODEL_ZOO, "deepfm_host_embedding.deepfm_host_embedding.custom_model"
    )
    executor = LocalExecutor(
        spec,
        training_data=train_dir,
        minibatch_size=8,
        num_epochs=1,
        records_per_task=32,
        checkpoint_dir=ckpt_dir,
        checkpoint_steps=4,  # == final step: last save captures the end
    )
    executor.run()
    trained_flat = executor._host_manager.flat_state()

    manager2 = build_manager_from_spec(spec)
    version = restore_host_state(manager2, ckpt_dir)
    assert version == 4
    restored_flat = manager2.flat_state()
    assert set(restored_flat) == set(trained_flat)

    def rows_by_id(flat, base):
        ids = np.asarray(flat[base + ".ids"])
        values = np.asarray(flat[base + ".values"])
        return values[np.argsort(ids)], np.sort(ids)

    for key in trained_flat:
        if key.endswith(".values"):
            continue  # compared id-aligned below
        if key.endswith(".ids"):
            base = key[: -len(".ids")]
            got_v, got_i = rows_by_id(restored_flat, base)
            want_v, want_i = rows_by_id(trained_flat, base)
            np.testing.assert_array_equal(got_i, want_i)
            # id-aligned row compare: catches restores that re-associate
            # rows with the wrong ids (column-wise sorting would not)
            np.testing.assert_allclose(got_v, want_v)
        else:
            assert restored_flat[key] == trained_flat[key]

    resumed = LocalExecutor(
        spec,
        training_data=train_dir,
        minibatch_size=8,
        num_epochs=1,
        records_per_task=32,
        checkpoint_dir_for_init=ckpt_dir,
    )
    resumed.run()
    assert int(resumed.state.step) > 4  # continued past the restore
    assert np.isfinite(resumed.losses).all()


def test_lr_scale_reaches_engine():
    """The scheduler multiplier scales host-row updates (Trainer passes
    lr_scale so every parameter tier sees the same schedule)."""
    eng_a = HostSpillEmbeddingEngine(4, optimizer="sgd", lr=0.5)
    eng_b = HostSpillEmbeddingEngine(4, optimizer="sgd", lr=0.5)
    ids = np.array([1, 2], np.int64)
    _, rows_a, _ = eng_a.pull(ids)
    eng_b.pull(ids)
    grads = np.ones((2, 4), np.float32)
    eng_a.apply_gradients(ids, grads, lr_scale=1.0)
    eng_b.apply_gradients(ids, grads, lr_scale=0.5)
    np.testing.assert_allclose(
        eng_a.param.lookup(ids), rows_a - 0.5, atol=1e-6
    )
    np.testing.assert_allclose(
        eng_b.param.lookup(ids), rows_a - 0.25, atol=1e-6
    )


class _FakeSPMDCtx(object):
    """Emulates a 2-host SPMDContext inside one process: the test sets
    `gathered` to the stacked per-host id tensors before each prepare,
    and rows_positions pretends host p's rows occupy the contiguous
    block [p*cap, (p+1)*cap) — consistent with how the test assembles
    the global rows feature by concatenation."""

    def __init__(self, process_index, num_processes=2):
        self.num_processes = num_processes
        self.process_index = process_index
        self.is_multiprocess = True
        self.batch_partitions = 1
        self.gathered = None

    def allgather(self, local_np):
        return self.gathered

    def rows_positions(self, global_len):
        cap = global_len // self.num_processes
        return {
            p: np.arange(p * cap, (p + 1) * cap)
            for p in range(self.num_processes)
        }


def _spmd_host_manager(ctx):
    manager = HostEmbeddingManager()
    manager.register(
        "edl_embedding", "feature",
        HostSpillEmbeddingEngine(DIM, optimizer="sgd", lr=0.1),
    )
    manager.register(
        "edl_id_bias", "feature",
        HostSpillEmbeddingEngine(1, optimizer="sgd", lr=0.1),
    )
    manager.enable_spmd(ctx)
    return manager


def test_spmd_host_embedding_parity():
    """Two emulated hosts with id-partitioned host tables train to
    exactly the single-process result: same per-step losses, and the
    union of the hosts' owned rows equals the single-store table (the
    reference's PS scatter — each id lives on one pod — reproduced as
    owner_of partitioning)."""
    from model_zoo.deepfm_host_embedding import deepfm_host_embedding as zoo
    from elasticdl_tpu.embedding.host_bridge import (
        IDX_SUFFIX,
        ROWS_SUFFIX,
        owner_of,
    )

    spec = load_model_spec_from_module(zoo)
    mp = format_params_str(dict(input_length=LENGTH, fc_unit=FC))
    batches = _batches(5, batch=8)

    # ---- baseline: one process, one store
    base = Trainer(spec, mesh=mesh_lib.local_mesh(), model_params=mp)
    base_mgr = HostEmbeddingManager()
    base_mgr.register(
        "edl_embedding", "feature",
        HostSpillEmbeddingEngine(DIM, optimizer="sgd", lr=0.1),
    )
    base_mgr.register(
        "edl_id_bias", "feature",
        HostSpillEmbeddingEngine(1, optimizer="sgd", lr=0.1),
    )
    base.attach_host_embeddings(base_mgr)
    base_state = base.init_state(batches[0])
    base_losses = []
    for b in batches:
        base_state, loss = base.train_step(base_state, b)
        base_losses.append(float(loss))

    # ---- emulated 2-host SPMD over the same global batches
    ctxs = [_FakeSPMDCtx(0), _FakeSPMDCtx(1)]
    mgrs = [_spmd_host_manager(c) for c in ctxs]
    spmd = Trainer(spec, mesh=mesh_lib.local_mesh(), model_params=mp)
    spmd.attach_host_embeddings(mgrs[0])

    def run_round(state, batch, init_only=False):
        (features, labels) = batch
        ids = np.asarray(features["feature"])
        half = ids.shape[0] // 2
        locals_ = [ids[:half], ids[half:]]
        stacked = np.stack(locals_)
        prepped = []
        for p in range(2):
            ctxs[p].gathered = stacked
            prepped.append(mgrs[p].prepare({"feature": locals_[p]}))
        cap = prepped[0]["edl_embedding" + ROWS_SUFFIX].shape[0]
        gf = {
            "feature": ids,
        }
        for key in ("edl_embedding", "edl_id_bias"):
            gf[key + ROWS_SUFFIX] = np.concatenate(
                [pr[key + ROWS_SUFFIX] for pr in prepped]
            )
            gf[key + IDX_SUFFIX] = np.concatenate(
                [pr[key + IDX_SUFFIX] for pr in prepped]
            )
        if init_only:
            return gf
        gw = np.ones((ids.shape[0],), np.float32)
        state, loss, host_grads, _ = spmd._run_train_step(
            state, gf, labels, gw
        )
        for p in range(2):
            mgrs[p].apply(host_grads)
        return state, float(loss)

    gf0 = run_round(None, batches[0], init_only=True)
    spmd_state = spmd.init_state((gf0, batches[0][1]))
    spmd_losses = []
    for b in batches:
        spmd_state, loss = run_round(spmd_state, b)
        spmd_losses.append(loss)

    np.testing.assert_allclose(spmd_losses, base_losses, rtol=1e-5,
                               atol=1e-6)

    # ownership is disjoint+exhaustive and the union matches the baseline
    for table in ("edl_embedding", "edl_id_bias"):
        base_ids, base_vals = (
            base_mgr.tables()[table].engine.param.export_rows()
        )
        merged = {}
        for p in range(2):
            ids_p, vals_p = (
                mgrs[p].tables()[table].engine.param.export_rows()
            )
            assert np.all(owner_of(ids_p, 2) == p)
            merged.update(zip(ids_p.tolist(), vals_p))
        assert sorted(merged) == sorted(base_ids.tolist())
        base_map = dict(zip(base_ids.tolist(), base_vals))
        for i in merged:
            np.testing.assert_allclose(
                merged[i], base_map[i], rtol=1e-5, atol=1e-6
            )


def test_spmd_host_state_repartitions_on_load():
    """A checkpoint written by 2 partitioned hosts restores onto 1 host
    (merge) and back onto a 2-host manager (filter to owned) — the
    host-tier analogue of the re-shardable dense checkpoint."""
    ctxs = [_FakeSPMDCtx(0), _FakeSPMDCtx(1)]
    mgrs = [_spmd_host_manager(c) for c in ctxs]
    # touch disjoint owned rows on each "host"
    for p, mgr in enumerate(mgrs):
        eng = mgr.tables()["edl_embedding"].engine
        ids = np.asarray([i for i in range(20) if i % 2 == p], np.int64)
        eng.pull(ids)
        eng.apply_gradients(ids, np.ones((ids.size, DIM), np.float32))
    flat = {}
    for mgr in mgrs:
        flat.update(mgr.flat_state())

    # restore into a single-process manager: gets ALL rows
    single = HostEmbeddingManager()
    single.register(
        "edl_embedding", "feature",
        HostSpillEmbeddingEngine(DIM, optimizer="sgd", lr=0.1),
    )
    single.register(
        "edl_id_bias", "feature",
        HostSpillEmbeddingEngine(1, optimizer="sgd", lr=0.1),
    )
    single.load_flat_state(flat)
    ids, vals = single.tables()["edl_embedding"].engine.param.export_rows()
    assert sorted(ids.tolist()) == list(range(20))

    # restore the single-process state back into partitioned managers:
    # each keeps only its owned ids
    single_flat = single.flat_state()
    for p in range(2):
        fresh = _spmd_host_manager(_FakeSPMDCtx(p))
        fresh.load_flat_state(single_flat)
        got, _ = fresh.tables()["edl_embedding"].engine.param.export_rows()
        assert sorted(got.tolist()) == [i for i in range(20) if i % 2 == p]


def test_apply_before_prepare_raises():
    manager = HostEmbeddingManager()
    manager.register(
        "t", "feature", HostSpillEmbeddingEngine(4, optimizer="sgd")
    )
    with pytest.raises(RuntimeError):
        manager.apply({"t.rows": np.zeros((8, 4), np.float32)})
