"""ODPS write path + k-v table tools:
writer round-trips a table through the reader; flattening tools match the
reference UDTF protocol."""

import threading

import pytest

from elasticdl_tpu.data.odps_writer import ODPSWriter
from elasticdl_tpu.data.reader.odps_reader import ODPSDataReader
from elasticdl_tpu.tools import odps_table_tools as kv


# ----------------------------------------------------------- fake ODPS


class _FakeColumn(object):
    def __init__(self, name, type_):
        self.name = name
        self.type = type_


class _FakeSchema(object):
    def __init__(self, names):
        self.columns = [_FakeColumn(n, "string") for n in names]


class _FakeWriterCtx(object):
    def __init__(self, store, fail_times, lock):
        self._store = store
        self._fail = fail_times
        self._lock = lock

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def write(self, records):
        with self._lock:
            if self._fail and self._fail[0] > 0:
                self._fail[0] -= 1
                raise IOError("transient write failure")
            self._store.extend(records)


class _FakeReaderCtx(object):
    def __init__(self, rows):
        self._rows = rows
        self.count = len(rows)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def read(self, start, count):
        return self._rows[start:start + count]


class _FakeTable(object):
    name = "sink"

    def __init__(self, fail_times=None):
        self.schema = _FakeSchema(["a", "b"])
        self.partitions = {}  # partition spec -> record list
        self._fail = fail_times
        self._lock = threading.Lock()

    def open_writer(self, partition=None, create_partition=False):
        assert create_partition
        store = self.partitions.setdefault(partition, [])
        return _FakeWriterCtx(store, self._fail, self._lock)

    def open_reader(self):
        rows = []
        for part in sorted(self.partitions):
            rows.extend(self.partitions[part])
        return _FakeReaderCtx(rows)


# -------------------------------------------------------------- writer


def test_from_iterator_writes_worker_partition():
    table = _FakeTable()
    writer = ODPSWriter(table=table)
    writer.from_iterator(
        iter([[(1, "x")], [(2, "y"), (3, "z")]]), worker_index=7
    )
    assert table.partitions == {"worker=7": [(1, "x"), (2, "y"), (3, "z")]}


def test_write_records_windows_and_parallel():
    table = _FakeTable()
    writer = ODPSWriter(table=table, window_size=10, num_parallel=3)
    records = [(i, str(i)) for i in range(95)]
    n = writer.write_records(records, worker_index=0)
    assert n == 95
    written = table.partitions["worker=0"]
    # parallel threads interleave windows; content must be complete
    assert sorted(written) == sorted(records)


def test_write_retry_recovers_transient_failures():
    table = _FakeTable(fail_times=[2])
    writer = ODPSWriter(table=table, window_size=5, num_parallel=2)
    records = [(i, "v") for i in range(20)]
    writer.write_records(records)
    assert sorted(table.partitions["worker=0"]) == sorted(records)


def test_write_permanent_failure_raises():
    table = _FakeTable(fail_times=[10_000])
    writer = ODPSWriter(table=table, window_size=5, num_parallel=1,
                        max_retries=2)
    with pytest.raises(IOError):
        writer.write_records([(1, "v")] * 8)


def test_round_trip_through_reader():
    """Writer -> reader round-trip (the env-gated integration the
    reference exercised on a real cluster, run here on the fake)."""
    table = _FakeTable()
    ODPSWriter(table=table, window_size=4).write_records(
        [(i, i * 2) for i in range(30)]
    )
    reader = ODPSDataReader(table=table, records_per_task=10)
    shards = reader.create_shards()
    assert sum(n for _, n in shards.values()) == 30

    class _Task(object):
        def __init__(self, start, end):
            self.start, self.end = start, end

    rows = list(reader.read_records(_Task(0, 30)))
    # parallel writer sessions interleave windows: row ORDER across
    # sessions is not part of the contract (shards re-slice by range,
    # training shuffles); content completeness is.
    assert sorted(rows) == [(i, i * 2) for i in range(30)]


def test_missing_pyodps_raises():
    writer = ODPSWriter(table_name="proj.t", columns=["a"],
                        column_types=["string"])
    assert writer._project == "proj"
    with pytest.raises(RuntimeError, match="odps package"):
        writer.write_records([("x",)])


# ------------------------------------------------------------ kv tools


def test_parse_and_flatten():
    assert kv.parse_kv_string("k1:v1,k2:v2") == {"k1": "v1", "k2": "v2"}
    # malformed pairs skipped
    assert kv.parse_kv_string("k1:v1,junk,k3:v3:x") == {"k1": "v1"}
    assert kv.flatten_kv_record("b:2,a:1", ["a", "b", "c"]) == ["1", "2", ""]


def test_analyze_feature_names():
    records = [
        {"kv": "f2:1,f1:2"},
        {"kv": "f3:9"},
        {"kv": "f1:0"},
    ]
    names = kv.analyze_feature_names(records, kv_value_fn=lambda r: r["kv"])
    assert names == ["f1", "f2", "f3"]
    # max_records honored
    assert kv.analyze_feature_names(
        records, kv_value_fn=lambda r: r["kv"], max_records=1
    ) == ["f1", "f2"]


def test_kv_flatter_udtf_protocol():
    """args = (kv value, *append columns, names csv, pair sep, kv sep) —
    the reference normalize_kv_udf.KVFlatter contract."""
    f = kv.KVFlatter()
    f.process("age:30,wage:10.5", 1, "age,wage,unknown", ",", ":")
    assert f.collected == [["30", "10.5", "", "1"]]
    with pytest.raises(ValueError):
        f.process("a:1", ",", ":")


def test_generate_transform_sql():
    sql = kv.generate_transform_sql(
        input_table="src",
        output_table="dst",
        feature_names=["f1", "f2"],
        kv_column="features",
        udf_function="my_udf",
        append_columns=["label"],
        input_table_partition="dt=20200101",
    )
    assert sql.startswith("CREATE TABLE IF NOT EXISTS dst")
    assert 'my_udf(features,label,\n    "f1,f2", ",", ":")' in sql
    assert "as (f1,f2,label)" in sql
    assert "FROM src" in sql
    assert sql.endswith("where dt=20200101")


def test_transform_kv_table_end_to_end_fake():
    """Driver wiring against a fake ODPS entry: analyze -> register UDTF
    -> run SQL -> cleanup, including cleanup on SQL failure."""

    class _FakeInstance(object):
        def wait_for_success(self):
            pass

    class _FakeSrcTable(object):
        def head(self, n, partition=None):
            return [{"features": "f1:1,f2:2"}, {"features": "f2:3,f3:4"}]

    class _FakeEntry(object):
        def __init__(self):
            self.resources = set()
            self.functions = set()
            self.sql = []

        def get_table(self, name):
            return _FakeSrcTable()

        def create_resource(self, name, type=None, file_obj=None):
            self.resources.add(name)
            self.resource_content = file_obj.read()
            return name

        def delete_resource(self, name):
            self.resources.discard(name)

        def create_function(self, name, class_type=None, resources=None):
            self.functions.add(name)
            return name

        def delete_function(self, name):
            self.functions.discard(name)

        def run_sql(self, sql):
            self.sql.append(sql)
            return _FakeInstance()

    entry = _FakeEntry()
    names = kv.transform_kv_table(
        entry, "src", "dst", kv_column="features", append_columns=["label"]
    )
    assert names == ["f1", "f2", "f3"]
    assert len(entry.sql) == 1 and "FROM src" in entry.sql[0]
    # the uploaded resource is a real cluster-side UDTF (BaseUDTF with
    # a forwarding process()), not the local test twin
    assert "BaseUDTF" in entry.resource_content
    assert "def process" in entry.resource_content
    # temporaries cleaned up
    assert not entry.resources and not entry.functions


# ------------------------------- round-2 depth: import seam, schema fn


class _NumFakeTable(_FakeTable):
    """Fake table with a numeric schema and preloaded rows."""

    def __init__(self, names, rows):
        super().__init__()
        self.schema = _FakeSchema(names)
        self.partitions = {"worker=0": list(rows)}


def _install_fake_pyodps(monkeypatch, table):
    """Inject a fake `odps` package into sys.modules so the REAL import
    seams (`from odps import ODPS`, `from odps.models import Schema`)
    execute — the paths a live pyodps install would take."""
    import sys
    import types

    created = {}

    class _Client(object):
        def __init__(self, access_id, access_key, project, endpoint):
            self.args = (access_id, access_key, project, endpoint)

        def get_table(self, name, project=None):
            return table

        def exist_table(self, name, project=None):
            return False

        def create_table(self, name, schema):
            created["name"] = name
            created["schema"] = schema
            return table

    class _Schema(object):
        @staticmethod
        def from_lists(cols, types, part_cols, part_types):
            return ("schema", tuple(cols), tuple(types),
                    tuple(part_cols), tuple(part_types))

    odps_mod = types.ModuleType("odps")
    odps_mod.ODPS = _Client
    models_mod = types.ModuleType("odps.models")
    models_mod.Schema = _Schema
    odps_mod.models = models_mod
    monkeypatch.setitem(sys.modules, "odps", odps_mod)
    monkeypatch.setitem(sys.modules, "odps.models", models_mod)
    return created


def test_reader_import_seam_with_fake_pyodps(monkeypatch):
    """ODPSDataReader given credentials (no table object) must go
    through the real `from odps import ODPS` seam."""
    table = _NumFakeTable(["a", "b"], [(1, 2), (3, 4)])
    _install_fake_pyodps(monkeypatch, table)
    reader = ODPSDataReader(
        table="mytable", project="p", access_id="id", access_key="key",
        endpoint="http://e", records_per_task=1,
    )
    assert reader.create_shards() == {
        "sink:0": (0, 1), "sink:1": (1, 1)
    }
    from elasticdl_tpu.master.task_dispatcher import Task, TaskType

    rows = list(reader.read_records(
        Task("sink:0", 0, 2, TaskType.TRAINING)
    ))
    assert rows == [(1, 2), (3, 4)]


def test_writer_import_seam_creates_table(monkeypatch):
    """ODPSWriter without a table object exercises the real pyodps
    import + Schema.from_lists + create_table path (reference
    _initialize_table, odps_io.py:490-506)."""
    table = _FakeTable()
    created = _install_fake_pyodps(monkeypatch, table)
    writer = ODPSWriter(
        table_name="proj.sink", access_id="i", access_key="k",
        endpoint="http://e", columns=["a", "b"],
        column_types=["bigint", "string"],
    )
    writer.write_records([(1, "x"), (2, "y")])
    assert created["name"] == "sink"
    assert created["schema"][1] == ("a", "b")
    assert created["schema"][3] == ("worker",)
    assert sorted(table.partitions["worker=0"]) == [(1, "x"), (2, "y")]


def test_default_dataset_fn_schema_driven():
    """Reader-derived dataset_fn (reference odps_reader.py:140-192):
    label_col becomes the label, remaining columns the float32 feature
    vector; prediction mode drops the label; a missing label column
    fails loudly in training."""
    import numpy as np

    from elasticdl_tpu.common.constants import Mode
    from elasticdl_tpu.data.dataset import Dataset

    table = _NumFakeTable(
        ["f0", "label", "f1"],
        [(0.5, 1, 2.0), (1.5, 0, 3.0)],
    )
    reader = ODPSDataReader(table=table, label_col="label")
    fn = reader.default_dataset_fn()

    ds = fn(
        Dataset.from_list(list(table.partitions["worker=0"])),
        Mode.EVALUATION, reader.metadata,
    )
    got = list(ds)
    assert len(got) == 2
    feats, label = got[0]
    np.testing.assert_allclose(feats["feature"], [0.5, 2.0])
    assert label == 1.0

    ds = fn(
        Dataset.from_list(list(table.partitions["worker=0"])),
        Mode.PREDICTION, reader.metadata,
    )
    pred = list(ds)[0]
    np.testing.assert_allclose(pred["feature"], [0.5, 2.0])

    bad = ODPSDataReader(
        table=_NumFakeTable(["f0", "f1"], [(1.0, 2.0)]),
        label_col="label",
    )
    with pytest.raises(ValueError, match="label"):
        bad.default_dataset_fn()(
            Dataset.from_list([(1.0, 2.0)]), Mode.TRAINING, bad.metadata
        )

    with pytest.raises(ValueError, match="label_col"):
        ODPSDataReader(table=table).default_dataset_fn()


def test_spec_falls_back_to_reader_default_dataset_fn():
    """Specs may omit dataset_fn when the reader derives one
    (reference worker.py:194-205)."""
    from elasticdl_tpu.common.model_utils import (
        ModelSpec,
        resolve_dataset_fn,
    )

    table = _NumFakeTable(["x", "label"], [(1.0, 0)])
    reader = ODPSDataReader(table=table, label_col="label")
    spec = ModelSpec(
        model_fn=lambda: None, dataset_fn=None, loss=lambda y, p: 0,
        optimizer=lambda: None, eval_metrics_fn=lambda: {},
    )
    fn = resolve_dataset_fn(spec, reader)
    assert callable(fn)
    assert resolve_dataset_fn(spec, reader) is fn  # cached on the spec

    class _NoDefault(object):
        pass

    spec2 = ModelSpec(
        model_fn=lambda: None, dataset_fn=None, loss=lambda y, p: 0,
        optimizer=lambda: None, eval_metrics_fn=lambda: {},
    )
    with pytest.raises(ValueError, match="dataset_fn is required"):
        resolve_dataset_fn(spec2, _NoDefault())


def test_to_iterator_covers_table_across_workers():
    """The standalone consumption surface (reference odps_io.py
    to_iterator): two workers' batch streams together cover every row
    exactly once per epoch."""
    rows = [(i,) for i in range(100)]
    table = _NumFakeTable(["v"], rows)
    from elasticdl_tpu.data.reader.odps_reader import ODPSReader

    seen = []
    for w in range(2):
        r = ODPSReader(table, window_size=16)
        for batch in r.to_iterator(2, w, batch_size=7):
            assert len(batch) <= 7
            seen.extend(batch)
    assert sorted(seen) == sorted(rows)

    r = ODPSReader(table, window_size=16)
    two_epochs = []
    for batch in r.to_iterator(1, 0, batch_size=10, epochs=2):
        two_epochs.extend(batch)
    assert len(two_epochs) == 200

    with pytest.raises(ValueError, match="worker"):
        next(r.to_iterator(2, 5, batch_size=4))
