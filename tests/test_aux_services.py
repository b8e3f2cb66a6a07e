"""ODPS reader (fake table), TensorBoard event writer (byte-level
verification of the TFRecord/Event encoding), TensorBoard service, and
the collective communicator contract."""

import glob
import struct

import numpy as np
import pytest

from elasticdl_tpu.common.tb_events import (
    EventFileWriter,
    crc32c,
    encode_scalar_event,
    frame_record,
)
from elasticdl_tpu.data.reader.odps_reader import ODPSDataReader, ODPSReader
from elasticdl_tpu.master.tensorboard_service import TensorboardService
from elasticdl_tpu.parallel.collective import (
    CollectiveCommunicator,
    CollectiveCommunicatorStatus,
)


# ------------------------------------------------------------- fake ODPS


class _FakeColumn(object):
    def __init__(self, name, type_):
        self.name = name
        self.type = type_


class _FakeSchema(object):
    def __init__(self):
        self.columns = [
            _FakeColumn("age", "bigint"), _FakeColumn("wage", "double"),
        ]


class _FakeReaderCtx(object):
    def __init__(self, rows, fail_times=None):
        self._rows = rows
        self._fail = fail_times
        self.count = len(rows)

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def read(self, start, count):
        if self._fail and self._fail[0] > 0:
            self._fail[0] -= 1
            raise IOError("transient")
        return self._rows[start:start + count]


class _FakeTable(object):
    name = "census"
    schema = _FakeSchema()

    def __init__(self, rows, fail_times=None):
        self._rows = rows
        self._fail = fail_times

    def open_reader(self):
        return _FakeReaderCtx(self._rows, self._fail)


class _Task(object):
    def __init__(self, start, end):
        self.start, self.end = start, end


def test_odps_create_shards():
    table = _FakeTable([(i, i * 2.0) for i in range(25)])
    reader = ODPSDataReader(table=table, records_per_task=10)
    shards = reader.create_shards()
    assert shards == {
        "census:0": (0, 10), "census:10": (10, 10), "census:20": (20, 5),
    }


def test_odps_read_records_with_windows():
    rows = [(i, float(i)) for i in range(57)]
    table = _FakeTable(rows)
    reader = ODPSDataReader(table=table, records_per_task=100,
                            window_size=8)
    got = list(reader.read_records(_Task(5, 41)))
    assert got == rows[5:41]


def test_odps_window_retry():
    rows = [(i,) for i in range(20)]
    table = _FakeTable(rows, fail_times=[2])  # first two opens fail
    reader = ODPSReader(table, window_size=50)
    assert list(reader.read_range(0, 20)) == rows


def test_odps_parse_fn_and_metadata():
    rows = [(30, 1000.0), (40, 2000.0)]
    table = _FakeTable(rows)
    reader = ODPSDataReader(
        table=table, records_per_task=10,
        parse_fn=lambda row: {"age": row[0]},
    )
    assert list(reader.read_records(_Task(0, 2))) == [
        {"age": 30}, {"age": 40},
    ]
    meta = reader.metadata
    assert meta.column_names == ["age", "wage"]


def test_factory_odps_env(monkeypatch, tmp_path):
    from elasticdl_tpu.data.reader import data_reader_factory

    monkeypatch.setenv("MAXCOMPUTE_AK", "ak")
    monkeypatch.setenv("MAXCOMPUTE_SK", "sk")
    monkeypatch.setenv("MAXCOMPUTE_PROJECT", "proj")
    # table name (not a local path) + creds -> ODPS reader; no pyodps
    # installed -> a clear gating error, not a crash elsewhere
    with pytest.raises(RuntimeError, match="odps package"):
        data_reader_factory.create_data_reader("some_table", 10)


# ------------------------------------------------------------ tb events


def test_crc32c_known_vectors():
    # RFC 3720 test vector: 32 bytes of zeros -> 0x8a9136aa
    assert crc32c(b"\x00" * 32) == 0x8A9136AA
    assert crc32c(b"123456789") == 0xE3069283


def test_frame_record_roundtrip():
    payload = b"hello world"
    rec = frame_record(payload)
    (length,) = struct.unpack("<Q", rec[:8])
    assert length == len(payload)
    assert rec[12:12 + length] == payload


def test_scalar_event_contains_tag():
    event = encode_scalar_event("loss", 1.5, step=7)
    assert b"loss" in event
    assert struct.pack("<f", 1.5) in event


def test_event_file_writer(tmp_path):
    writer = EventFileWriter(str(tmp_path))
    writer.add_scalar("accuracy", 0.93, 12)
    writer.close()
    files = glob.glob(str(tmp_path / "events.out.tfevents.*"))
    assert len(files) == 1
    data = open(files[0], "rb").read()
    assert b"brain.Event:2" in data
    assert b"accuracy" in data


def test_tensorboard_service_writes_metrics(tmp_path):
    service = TensorboardService(str(tmp_path))
    service.write_dict_to_summary({"auc": 0.8, "loss": 0.1}, version=5)
    service.write_dict_to_summary({"auc": "not-a-number"}, version=6)
    service.stop()
    files = glob.glob(str(tmp_path / "events.out.tfevents.*"))
    assert files
    data = open(files[0], "rb").read()
    assert b"auc" in data and b"loss" in data


def test_tier_health_counters_reach_tensorboard(tmp_path):
    """Worker-reported tier/ exec counters (host-tier dropped-row
    gauges) become TensorBoard scalars through the master servicer —
    the observability contract for the by-design 'rows miss one
    update' degradation of the host embedding tier."""
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.proto import elasticdl_pb2 as pb

    task_d = TaskDispatcher(
        {"shard": (0, 8)}, {}, {}, records_per_task=8, num_epochs=1
    )
    tb = TensorboardService(str(tmp_path))
    servicer = MasterServicer(4, task_d, tensorboard_service=tb)
    task = servicer.get_task(pb.GetTaskRequest(worker_id=0))
    req = pb.ReportTaskResultRequest(task_id=task.task_id)
    req.exec_counters["tier/host_dropped_row_updates"] = 37
    req.exec_counters["tier/host_failed_cycles"] = 2
    req.exec_counters["unrelated"] = 5
    servicer.report_task_result(req)
    tb.stop()
    files = glob.glob(str(tmp_path / "events.out.tfevents.*"))
    assert files
    data = open(files[0], "rb").read()
    # per-worker tags: cumulative counters from different workers must
    # not interleave on one scalar
    assert b"tier/host_dropped_row_updates/worker-0" in data
    assert b"tier/host_failed_cycles/worker-0" in data
    assert b"unrelated" not in data


def test_tier_gauges_distinct_steps_no_data_loss(tmp_path):
    """Every report's cumulative counters land at a strictly
    increasing per-worker step: no duplicate points at one step (the
    sawtooth/overwrite artifact some TB backends render), and the tail
    of a cumulative counter is never dropped — the last report between
    version bumps is the freshest value."""
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    class SpyTB(object):
        def __init__(self):
            self.writes = []

        def write_dict_to_summary(self, gauges, version):
            self.writes.append((dict(gauges), version))

    task_d = TaskDispatcher(
        {"shard": (0, 32)}, {}, {}, records_per_task=8, num_epochs=1
    )
    tb = SpyTB()
    servicer = MasterServicer(4, task_d, tensorboard_service=tb)
    for value in (1, 2, 6):  # cumulative counter grows within a version
        servicer._write_tier_gauges(
            {"tier/host_failed_cycles": value}, worker_id=0)
    servicer._write_tier_gauges(
        {"tier/host_failed_cycles": 9}, worker_id=1)
    assert len(tb.writes) == 4  # nothing dropped
    w0 = [(g, s) for g, s in tb.writes
          if "tier/host_failed_cycles/worker-0" in g]
    assert [s for _, s in w0] == [0, 1, 2]  # distinct increasing steps
    assert w0[-1][0]["tier/host_failed_cycles/worker-0"] == 6
    w1 = [(g, s) for g, s in tb.writes
          if "tier/host_failed_cycles/worker-1" in g]
    assert [s for _, s in w1] == [0]  # independent per-worker counter


# ----------------------------------------------------------- collective


def test_collective_single_process_identity():
    comm = CollectiveCommunicator()
    assert not comm.has_backend()
    data = np.arange(4.0)
    status, out = comm.allreduce(data)
    assert status == CollectiveCommunicatorStatus.SUCCEEDED
    np.testing.assert_array_equal(out, data)
    status, out = comm.broadcast(data, 0)
    assert status == CollectiveCommunicatorStatus.SUCCEEDED
    assert comm.barrier() == CollectiveCommunicatorStatus.SUCCEEDED


def test_collective_rejects_bad_op():
    comm = CollectiveCommunicator()
    status, _ = comm.allreduce(np.ones(2), op="MAX")
    assert status == CollectiveCommunicatorStatus.FAILED
    status, _ = comm.allreduce(None)
    assert status == CollectiveCommunicatorStatus.FAILED


def test_validate_job_status_fake_api():
    from scripts.validate_job_status import validate

    class FakeApi(object):
        def __init__(self, phases):
            self._phases = phases

        def read_namespaced_pod(self, namespace, name):
            phase = (
                self._phases.pop(0) if len(self._phases) > 1
                else self._phases[0]
            )
            return {"status": {"phase": phase}}

    ok = validate("j", core_api=FakeApi(["Running", "Succeeded"]),
                  poll_interval=0)
    assert ok == 0
    bad = validate("j", core_api=FakeApi(["Failed"]), poll_interval=0)
    assert bad == 1
