"""Per-phase wall-clock accumulation (reference: common/timing_utils.py:16-56).

Keeps the reference's phase taxonomy {task_process, batch_process, get_model,
report_gradient} and adds TPU phases {compile, device_put, step}.
"""

import time
from contextlib import contextmanager


class Timing(object):
    def __init__(self, enabled=True, logger=None):
        self._enabled = enabled
        self._logger = logger
        self.reset()

    def reset(self):
        self._start = {}
        self.totals = {}
        self.counts = {}

    def start_record_time(self, phase):
        if self._enabled:
            self._start[phase] = time.time()

    def end_record_time(self, phase):
        if self._enabled and phase in self._start:
            dt = time.time() - self._start.pop(phase)
            self.totals[phase] = self.totals.get(phase, 0.0) + dt
            self.counts[phase] = self.counts.get(phase, 0) + 1

    @contextmanager
    def record(self, phase):
        self.start_record_time(phase)
        try:
            yield
        finally:
            self.end_record_time(phase)

    def report_timing(self, reset=False):
        if self._enabled and self._logger:
            for phase, total in sorted(self.totals.items()):
                self._logger.debug(
                    "Timing %s: total=%.3fs count=%d avg=%.1fms",
                    phase,
                    total,
                    self.counts[phase],
                    1000.0 * total / max(1, self.counts[phase]),
                )
        if reset:
            self.reset()


def fetch_sync(tree):
    """Wait until every array in `tree` is ready — the sync that ends a
    timed region in the scripts/bench_* microbenchmarks (dispatch is
    asynchronous; without it a timing measures the enqueue)."""
    import jax

    jax.block_until_ready(tree)
