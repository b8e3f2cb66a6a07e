"""The watcher of open phases: a thread other than the one that is
stuck says where it is stuck, and whether the whole process was.

`tracing` seals a phase when it ENDS; a phase that is taking seconds
(or will never end) is invisible until then, and what the thread was
doing inside it is gone by the time it is. `PhaseWatcher.wake()`, run
every quarter of a second by a thread that is not the hot loop's,
closes both gaps:

* ``watch.sample``: when the innermost open phase of some thread is
  already slow by `tracing`'s rule (`SpanRecorder.is_slow`), the
  innermost ten frames of that thread (`sys._current_frames()`) are
  kept as one entry of the recorder's retained tier, under the
  phase's name, seq and start. At most one a wake (the phase open the
  longest), and of one phase at 0.25, 0.5, 1, 2, ... s of its life, so
  a phase that hangs for a minute costs nine samples;
* ``watch.late`` (`cpu_ms`): when the watcher itself wakes more than
  0.1 s after it was due, that lateness is stamped as a phase of the
  ring, with the CPU time the whole process used meanwhile
  (`time.process_time_ns`, every thread's). Nothing of this process
  ran Python in it: a slow phase that holds as much `watch.late` as
  its own length was not waiting for the device. `cpu_ms` near the
  lateness says a thread of this process held the interpreter lock
  and worked; `cpu_ms` near 0 says the process was not run at all
  (the machine, its hypervisor, a stopped container).

The serving process runs it on its `runtime-health` thread
(`RuntimeHealth._run`); `LocalExecutor.train`, which has no health
plane, gives it a thread of its own for the length of `train()`."""

import os
import sys
import threading
import time

from elasticdl_tpu.observability import tracing

#: waking this much after the due time is `watch.late`
LATE_SECS = 0.1
#: frames of a sample, innermost first
FRAMES = 10


class PhaseWatcher(object):
    def __init__(self, period_secs=0.25, clock=time.monotonic):
        self.period_secs = float(period_secs)
        self._clock = clock
        self._due = None
        self._cpu_ns = 0
        self._stop = threading.Event()
        self._thread = None

    def sleep(self, stop, secs):
        """Wait on the event `stop` for `secs`, remembering when the
        wait should end: the next `wake` measures itself against it."""
        self._due = self._clock() + secs
        self._cpu_ns = time.process_time_ns()
        return stop.wait(secs)

    def wake(self):
        """One look: stamps `watch.late` if this call comes late, and
        samples the slow open phase that has been open the longest, if
        it is due a sample. Returns the sample or None."""
        now = self._clock()
        if self._due is not None and now - self._due > LATE_SECS:
            wall = time.time_ns()
            tracing.stamp(
                "watch.late", wall - int((now - self._due) * 1e9), wall,
                cpu_ms=(time.process_time_ns() - self._cpu_ns) // 10**6)
        self._due = None
        wall = time.time_ns()
        rec = tracing.recorder()
        worst = None
        for ident, thread, opened in tracing.open_threads():
            ph = opened[-1]
            lasted = wall - ph.start_ns
            if (lasted >= getattr(ph, "sample_at_ns", 0)
                    and rec.is_slow(ph.name, lasted)
                    and (worst is None or lasted > worst[0])):
                worst = (lasted, ident, thread, ph)
        if worst is None:
            return None
        lasted, ident, thread, ph = worst
        # the next one when the phase has been open twice as long
        ph.sample_at_ns = 2 * lasted
        sample = tracing.Phase(
            "watch.sample", wall, wall, ph.seq, ph.name, ph.trace_id,
            {"thread": thread, "open_ms": lasted * 1e-6,
             "phase_start_ns": ph.start_ns, "frames": _frames(ident)})
        rec._note_sample(sample)
        return sample

    def start(self):
        """A thread of its own, for a process without a health plane."""
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="phase-watch")
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None

    def _run(self):
        while not self.sleep(self._stop, self.period_secs):
            self.wake()


def _frames(ident):
    """["file.py:line function", ...] of the thread, innermost first."""
    frame = sys._current_frames().get(ident)
    out = []
    while frame is not None and len(out) < FRAMES:
        code = frame.f_code
        out.append("%s:%d %s" % (os.path.basename(code.co_filename),
                                 frame.f_lineno, code.co_name))
        frame = frame.f_back
    return out
