"""One run of one cell.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s>
                             --trace <0|1> [--rehearsal]

A new process that owns the cell's chips: it finds the cell's files by
name from BENCHMARK.json, makes weights and traffic from the seed, warms
the cell's shapes, measures for `--seconds`, decides `correct` against
the plain reference, and prints one JSON object as the last line of its
standard output. Without a TPU of a known kind it exits nonzero and
prints no result; `--rehearsal` (the tests') runs each file's
`rehearsal` sizes on the CPU, and its result says `cpu`.
"""

import time

_T0 = time.time()  # process start, as near as Python lets us see it

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402


def _merged(data, rehearsal):
    """A data file's contents; under --rehearsal its `rehearsal` entry
    is laid over it (one level into dicts)."""
    over = data.get("rehearsal") if rehearsal else None
    out = {k: v for k, v in data.items() if k != "rehearsal"}
    for k, v in (over or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = dict(out[k], **v)
        else:
            out[k] = v
    return out


class Run(object):
    """What a driver is handed: the cell's files, the devices, a place
    to write, and the clock of set-up."""

    def __init__(self, manifest, args, devices, peaks):
        wl = manifest.workload(args.workload)
        self.root = manifest.root
        self.workload = wl["name"]
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearsal = bool(args.trace), args.rehearsal
        self.control = args.control
        self.sweep = [float(r) for r in args.sweep.split(",") if r]
        self.cfg = _merged(manifest.config(wl["config"]), args.rehearsal)
        self.mix = _merged(manifest.traffic(wl["traffic"]), args.rehearsal)
        self.cell = _merged(manifest.cell(wl["name"]), args.rehearsal)
        self.reference = manifest.reference(self.cfg)
        self.devices, self.peaks = devices, peaks
        self.workdir = tempfile.mkdtemp(prefix="chipbench-")
        self.setup_s = None

    def say(self, msg):
        print(msg, flush=True)

    def mark_window_open(self, excluded_s=0.0):
        """Set-up ends here: process start to the window's start, less
        the time `correct` took inside it."""
        self.setup_s = time.time() - _T0 - excluded_s

    def describe_devices(self):
        from chipbench import device

        return device.describe(self.devices)


def _metrics(manifest, kind, folder, workload, measured, say):
    """{name: {value, unit}} of this cell's metrics of one kind, each
    read by the reader its file names; a reader that finds nothing to
    read returns None and the metric is left out."""
    from chipbench import readers

    out = {}
    for entry in manifest.metrics_of(kind, workload):
        spec = manifest.metric_spec(folder, entry["name"])
        module, _, fn = spec["reader"].rpartition(":")
        read = getattr(importlib.import_module(module) if module
                       else readers, fn)
        value = read(measured, **spec.get("args", {}))
        if value is None:
            say("metric %s: nothing to read" % entry["name"])
            continue
        out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearsal", action="store_true",
                        help="tests only: tiny sizes on the CPU")
    parser.add_argument("--control", action="store_true",
                        help="also print what the lower-precision control "
                             "reads (calibration of the limits)")
    parser.add_argument("--sweep", default="",
                        help="open_loop only: rates (per s) to run a "
                             "window at, before the cell's own")
    args = parser.parse_args(argv)

    from chipbench import device
    from chipbench.manifest import Manifest

    manifest = Manifest()
    wl = manifest.workload(args.workload)
    sys.path.insert(0, manifest.root)
    # the program's own choice of compile-cache directory (inside the
    # checkout unless JAX_COMPILATION_CACHE_DIR says otherwise)
    try:
        from elasticdl_tpu.common.platform_utils import (
            configure_compile_cache,
        )
    except ImportError as e:
        print("chipbench: the program is not in this checkout: %s" % e,
              file=sys.stderr)
        return 3
    configure_compile_cache()
    try:
        devices, peaks = device.claim(wl["chips"], args.rehearsal)
    except device.NoChip as e:
        print("chipbench: %s" % e, file=sys.stderr)
        return 3
    run = Run(manifest, args, devices, peaks)
    run.say("chipbench: %s seed %d on %d x %s (%s)%s" % (
        run.workload, run.seed, len(devices), devices[0].device_kind,
        devices[0].platform,
        "  REHEARSAL, not a chip run" if args.rehearsal else ""))
    try:
        measured = manifest.driver(run.mix).run_cell(run)
    finally:
        shutil.rmtree(run.workdir, ignore_errors=True)
    measured["counters"]["setup_s"] = run.setup_s
    measured["cfg"] = dict(run.cfg["model"]["params"])
    measured["peaks"] = peaks
    from chipbench import stats

    for name, values in sorted(measured["samples"].items()):
        run.say("samples %s: n=%d median %s p95 %s max %s" % (
            name, len(values), stats.percentile(values, 50),
            stats.percentile(values, 95), max(values, default=None)))
    result = {
        "correct": bool(measured["correct"]),
        "attempted": measured["attempted"], "failed": measured["failed"],
        "device": measured["device"],
    }
    trace = measured.get("trace")
    if args.trace:
        result["metrics"] = _metrics(manifest, "per_layer", "layers",
                                     run.workload, measured, run.say)
        if trace is not None:
            for name, (secs, count) in sorted(trace["programs"].items()):
                run.say("trace program %s: %d launches, %.6f s"
                        % (name, count, secs))
            for key, (secs, count) in sorted(
                    trace["ops"].items(), key=lambda kv: -kv[1][0])[:12]:
                run.say("trace op %s: %d events, %.6f s" % (key, count, secs))
            result["device"]["busy_s"] = trace["busy_s"]
            result["device"]["window_s"] = trace["window_s"]
            result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                                   "idle_gaps": trace["idle_gaps"][:10]}
    else:
        result["metrics"] = _metrics(manifest, "end_to_end", "metrics",
                                     run.workload, measured, run.say)
    if args.rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
