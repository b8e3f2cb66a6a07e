"""What the harness puts around the program: a count of programs
lowered (none may be lowered inside a window), the profiler around the
traced part of a window, and the release of the program's device
memory before the reference runs."""

import gc
import glob
import os
import shutil
import threading
import time


class CompileCounter(object):
    """Counts lowerings of new programs (every jit miss lowers, whether
    or not the persistent cache then has the executable)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1


class WindowTrace(object):
    """The profiler over the first `seconds` of a window (`--trace 1`
    only). `poll()` is called from the measuring loop; the trace stops
    at the first poll after its time is up, on a thread of its own:
    writing the trace out takes seconds, and the loop that polls is the
    one that offers the load. Off, every call is a no-op."""

    def __init__(self, on, seconds, workdir):
        self.on = bool(on)
        self.seconds = seconds
        self.dir = os.path.join(workdir, "trace")
        self.t0 = self.t1 = None
        self._stopper = None

    def start(self):
        if self.on:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.t0 = time.time()

    def poll(self, force=False):
        if self.on and self.t0 is not None and self.t1 is None and (
                force or time.time() - self.t0 >= self.seconds):
            import jax

            self.t1 = time.time()
            self._stopper = threading.Thread(target=jax.profiler.stop_trace)
            self._stopper.start()

    def reduce(self):
        """The trace's summary (trace_reduce.summarize), or None."""
        if not self.on:
            return None
        from chipbench import trace_reduce

        self.poll(force=True)
        self._stopper.join()
        paths = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            raise RuntimeError("the profiler wrote no trace")
        return trace_reduce.summarize(
            trace_reduce.load_events(paths[0]), self.t1 - self.t0)


def free_device_memory():
    """Delete every live device array: the program's state must be
    gone before the reference takes its place."""
    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()
