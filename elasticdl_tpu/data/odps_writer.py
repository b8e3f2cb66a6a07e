"""MaxCompute/ODPS table writer (reference data/odps_io.py:444-515
`ODPSWriter`), completing the read path in data/reader/odps_reader.py.

Behavior parity:
* lazy table initialization: an existing table is used as-is; a missing
  one is created from (columns, column_types) with a `worker` string
  partition column — reference `_initialize_table` (odps_io.py:490-506);
* `from_iterator(records_iter, worker_index)` writes each batch into the
  `worker=<index>` partition with create_partition=True (odps_io.py:508-515);
* `write_records` adds what the reference reader had but its writer
  lacked and a round-1 review asked to mirror: WINDOWED PARALLEL writes
  with per-window retry (the write-side twin of ODPSReader's prefetch
  windows + record_generator_with_retry);
* `project.table` names split into (project, table) — odps_io.py:474-475.

Like the reader, the `odps` package is import-gated: tests (and any
caller that already holds a table handle) pass a `table` object
implementing `open_writer(partition=..., create_partition=True)`;
otherwise pyodps credentials are required.
"""

import threading

from elasticdl_tpu.common.log_utils import default_logger as logger

_DEFAULT_WINDOW = 1000
_MAX_RETRIES = 3


class ODPSWriter(object):
    def __init__(
        self,
        table=None,
        columns=None,
        column_types=None,
        project=None,
        access_id=None,
        access_key=None,
        endpoint=None,
        table_name=None,
        window_size=_DEFAULT_WINDOW,
        num_parallel=2,
        max_retries=_MAX_RETRIES,
    ):
        if table_name and table_name.find(".") > 0:
            project, table_name = table_name.split(".", 1)
        self._table = table
        self._columns = columns
        self._column_types = column_types
        self._project = project
        self._access_id = access_id
        self._access_key = access_key
        self._endpoint = endpoint
        self._table_name = table_name
        self._window_size = int(window_size)
        self._num_parallel = max(1, int(num_parallel))
        self._max_retries = max(1, int(max_retries))

    # ----------------------------------------------------- table creation

    def _ensure_table(self):
        if self._table is not None:
            return self._table
        try:
            from odps import ODPS
            from odps.models import Schema
        except ImportError as e:
            raise RuntimeError(
                "The odps package is not installed; pass a `table` object "
                "or install pyodps"
            ) from e
        client = ODPS(
            self._access_id, self._access_key, self._project, self._endpoint
        )
        if client.exist_table(self._table_name, self._project):
            self._table = client.get_table(self._table_name, self._project)
        else:
            if self._columns is None or self._column_types is None:
                raise ValueError(
                    "columns and column_types need to be specified for a "
                    "non-existing table."
                )
            schema = Schema.from_lists(
                self._columns, self._column_types, ["worker"], ["string"]
            )
            self._table = client.create_table(self._table_name, schema)
        return self._table

    # ------------------------------------------------------------ writing

    def from_iterator(self, records_iter, worker_index=0):
        """Stream pre-batched records into this worker's partition
        (reference from_iterator, odps_io.py:508-515: one writer session,
        sequential batch writes)."""
        table = self._ensure_table()
        with table.open_writer(
            partition="worker=%s" % worker_index, create_partition=True
        ) as writer:
            for records in records_iter:
                writer.write(records)

    def write_records(self, records, worker_index=0):
        """Write a record list as parallel windows with per-window retry.

        Windows are dealt round-robin to `num_parallel` writer threads,
        each with its own writer session; a window that raises is retried
        up to max_retries times (the write-side mirror of the reader's
        windowed prefetch + retry)."""
        records = list(records)
        if not records:
            return 0
        table = self._ensure_table()
        windows = [
            records[i:i + self._window_size]
            for i in range(0, len(records), self._window_size)
        ]
        errors = []
        lock = threading.Lock()

        def write_windows(thread_id):
            try:
                with table.open_writer(
                    partition="worker=%s" % worker_index,
                    create_partition=True,
                ) as writer:
                    for w in range(thread_id, len(windows),
                                   self._num_parallel):
                        self._write_window_with_retry(writer, windows[w], w)
            except Exception as e:  # noqa: BLE001 - collected and re-raised
                with lock:
                    errors.append(e)

        n_threads = min(self._num_parallel, len(windows))
        if n_threads == 1:
            write_windows(0)
        else:
            threads = [
                threading.Thread(
                    target=write_windows, args=(t,), daemon=True
                )
                for t in range(n_threads)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        if errors:
            raise errors[0]
        return len(records)

    def _write_window_with_retry(self, writer, window, window_idx):
        for attempt in range(self._max_retries):
            try:
                writer.write(window)
                return
            except Exception:  # noqa: BLE001 - retried, then re-raised
                if attempt == self._max_retries - 1:
                    raise
                logger.warning(
                    "ODPS write window %d failed (attempt %d/%d); retrying",
                    window_idx, attempt + 1, self._max_retries,
                )
