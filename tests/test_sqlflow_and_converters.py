"""census_model_sqlflow zoo family + real-dataset converters (review
round-1 missing #4/#5): the transform-op graph interpreter, both sqlflow
variants training e2e, and the image/CSV -> TRec converters."""

import os

import numpy as np
import pytest

from elasticdl_tpu.api.local_executor import LocalExecutor
from elasticdl_tpu.common.model_utils import get_model_spec
from elasticdl_tpu.data import recordio_gen
from elasticdl_tpu.data.record_format import Scanner, get_record_count
from elasticdl_tpu.data.example_codec import decode_example
from model_zoo.census_model_sqlflow import feature_configs as cfg
from model_zoo.census_model_sqlflow import transform_ops as ops

# CI drills shard (make test-drills): the sub-5-min per-commit gate excludes this file.
pytestmark = pytest.mark.slow

MODEL_ZOO = "model_zoo"


# ------------------------------------------------------- transform graph


def test_topo_sort_orders_dependencies():
    sources = [s.name for s in cfg.INPUT_SCHEMAS]
    ordered = ops.topo_sort(cfg.FEATURE_TRANSFORM_INFO, sources)
    seen = set(sources)
    for op in ordered:
        assert all(i in seen for i in op.inputs), (
            "%s ran before its inputs" % op.name
        )
        seen.add(op.output)
    assert len(ordered) == len(cfg.FEATURE_TRANSFORM_INFO)


def test_topo_sort_rejects_unknown_inputs():
    bad = [ops.Hash("h", "nonexistent_column", "h", 8)]
    with pytest.raises(ValueError, match="unknown inputs|unresolvable"):
        ops.topo_sort(bad, ["a"])


def test_execute_host_ops_offsets_and_groups():
    sources = [s.name for s in cfg.INPUT_SCHEMAS]
    ordered = ops.topo_sort(cfg.FEATURE_TRANSFORM_INFO, sources)
    example = {
        "education": np.array(b"Bachelors"),
        "occupation": np.array(b"Sales"),
        "native-country": np.array(b"United-States"),
        "workclass": np.array(b"Private"),
        "marital-status": np.array(b"Divorced"),
        "relationship": np.array(b"Wife"),
        "race": np.array(b"White"),
        "sex": np.array(b"Female"),
        "age": np.array(38.0, np.float32),
        "capital-gain": np.array(6200.0, np.float32),
        "capital-loss": np.array(0.0, np.float32),
        "hours-per-week": np.array(40.0, np.float32),
    }
    values = ops.execute_host_ops(ordered, example)
    # group1 = workclass lookup + 3 bucketized numerics, offset into one
    # id space of sum([9, 7, 6, 6]) ids (vocab 8 + 1 OOV, boundaries+1)
    g1 = values["group1"]
    assert g1.shape == (4,)
    dim1 = cfg.group1_embedding_wide.input_dim
    assert (0 <= g1).all() and (g1 < dim1).all()
    # workclass "Private" is vocab index 0; offsets put it at 0
    assert g1[0] == 0
    # hours 40 -> bucket 4 of boundaries [10,20,30,40,50,60] + offset 9
    assert g1[1] == 9 + 4
    # capital-gain 6200 -> bucket 1 + offset 9+7
    assert g1[2] == 16 + 1
    for name in ("group2", "group3"):
        g = values[name]
        emb = {"group2": cfg.group2_embedding_deep,
               "group3": cfg.group3_embedding_deep}[name]
        assert g.shape == (4,)
        assert (0 <= g).all() and (g < emb.input_dim).all()


# ----------------------------------------------------------- e2e training


def _run(spec_key, tmp_path):
    train_dir, val_dir = str(tmp_path / "train"), str(tmp_path / "val")
    recordio_gen.gen_census_raw(train_dir, num_files=1, records_per_file=32)
    recordio_gen.gen_census_raw(val_dir, num_files=1, records_per_file=32,
                                seed=7)
    spec = get_model_spec(MODEL_ZOO, spec_key)
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
    return metrics


def test_sqlflow_wide_and_deep_e2e(tmp_path):
    metrics = _run(
        "census_model_sqlflow.wide_and_deep.census_wide_and_deep"
        ".custom_model",
        tmp_path,
    )
    assert 0.0 <= metrics["logits_accuracy"] <= 1.0
    assert 0.0 <= metrics["probs_auc"] <= 1.0


def test_sqlflow_dnn_e2e(tmp_path):
    metrics = _run(
        "census_model_sqlflow.dnn.census_dnn.custom_model", tmp_path
    )
    assert 0.0 <= metrics["accuracy"] <= 1.0


# ------------------------------------------------------------- converters


def test_convert_arrays_sharding(tmp_path):
    x = np.arange(25 * 4 * 4, dtype=np.float32).reshape(25, 4, 4)
    y = np.arange(25) % 3
    paths = recordio_gen.convert_arrays(
        str(tmp_path), x, y, records_per_shard=10
    )
    assert [os.path.basename(p) for p in paths] == [
        "data-00000.trec", "data-00001.trec", "data-00002.trec",
    ]
    assert [get_record_count(p) for p in paths] == [10, 10, 5]
    ex = decode_example(next(iter(Scanner(paths[1]))))
    np.testing.assert_allclose(ex["image"], x[10])
    assert int(ex["label"]) == y[10]
    # fraction keeps the leading slice (reference image_label.py args)
    paths = recordio_gen.convert_arrays(
        str(tmp_path / "frac"), x, y, records_per_shard=10, fraction=0.4
    )
    assert sum(get_record_count(p) for p in paths) == 10


def test_convert_image_dir(tmp_path):
    PIL = pytest.importorskip("PIL")
    from PIL import Image

    img_root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (img_root / cls).mkdir(parents=True)
        for i in range(3):
            arr = np.full((8, 8), 40 * i, np.uint8)
            Image.fromarray(arr).save(img_root / cls / ("%d.png" % i))
    out = str(tmp_path / "rec")
    paths, classes = recordio_gen.convert_image_dir(str(img_root), out)
    assert classes == ["cat", "dog"]
    records = [decode_example(r) for p in paths for r in Scanner(p)]
    assert len(records) == 6
    labels = sorted(int(r["label"]) for r in records)
    assert labels == [0, 0, 0, 1, 1, 1]
    assert records[0]["image"].shape == (8, 8)


def test_convert_csv(tmp_path):
    csv_path = tmp_path / "heart.csv"
    csv_path.write_text(
        "age,chol,thal,target\n"
        "63,233,fixed,1\n"
        "37,250.5,normal,0\n"
        "41,204,reversible,1\n"
    )
    out = str(tmp_path / "rec")
    paths = recordio_gen.convert_csv(
        str(csv_path), out, records_per_shard=2, label_column="target"
    )
    assert [get_record_count(p) for p in paths] == [2, 1]
    records = [decode_example(r) for p in paths for r in Scanner(p)]
    assert int(records[0]["age"]) == 63
    assert records[1]["chol"].dtype == np.float32  # column sniffed float
    assert records[0]["thal"] == b"fixed"
    assert records[2]["target"] == 1 and records[2]["target"].dtype == np.int64


def test_convert_image_dir_mixed_shapes(tmp_path):
    pytest.importorskip("PIL")
    from PIL import Image

    img_root = tmp_path / "imgs"
    (img_root / "a").mkdir(parents=True)
    Image.fromarray(np.zeros((8, 8), np.uint8)).save(img_root / "a" / "g.png")
    Image.fromarray(np.zeros((8, 8, 3), np.uint8)).save(
        img_root / "a" / "rgb.png"
    )
    with pytest.raises(ValueError, match="image_size and/or image_mode"):
        recordio_gen.convert_image_dir(str(img_root), str(tmp_path / "o"))
    # normalizing the mode fixes it
    paths, _ = recordio_gen.convert_image_dir(
        str(img_root), str(tmp_path / "o2"), image_mode="RGB"
    )
    records = [decode_example(r) for p in paths for r in Scanner(p)]
    assert all(r["image"].shape == (8, 8, 3) for r in records)
    # stray non-image files and nested dirs are skipped, not fatal
    (img_root / "a" / ".DS_Store").write_bytes(b"\x00junk")
    (img_root / "a" / "nested").mkdir()
    paths, _ = recordio_gen.convert_image_dir(
        str(img_root), str(tmp_path / "o3"), image_mode="RGB"
    )
    assert sum(1 for p in paths for _ in Scanner(p)) == 2


def test_convert_csv_ragged_row_and_long_strings(tmp_path):
    p = tmp_path / "r.csv"
    p.write_text("a,b\n1,2\n3\n")
    with pytest.raises(ValueError, match="line 3"):
        recordio_gen.convert_csv(str(p), str(tmp_path / "o"))
    # >64-byte strings survive exactly (no fixed-width truncation)
    long = "x" * 200
    p2 = tmp_path / "s.csv"
    p2.write_text("a,s\n1,%s\n" % long)
    paths = recordio_gen.convert_csv(str(p2), str(tmp_path / "o2"))
    rec = decode_example(next(iter(Scanner(paths[0]))))
    assert rec["s"] == long.encode()


def test_convert_csv_empty_and_bad_label(tmp_path):
    p = tmp_path / "empty.csv"
    p.write_text("a,b\n")
    assert recordio_gen.convert_csv(str(p), str(tmp_path / "o")) == []
    p2 = tmp_path / "x.csv"
    p2.write_text("a,b\n1,2\n")
    with pytest.raises(ValueError, match="label column"):
        recordio_gen.convert_csv(str(p2), str(tmp_path / "o2"),
                                 label_column="nope")
