"""From a profiler trace to numbers: device busy time as the union of
the intervals in which an operation ran, the idle gaps and what the
host was doing in them, and time summed by operation name. Works on a
neutral list of events so that it can be checked on a small recorded
trace (`testdata/`):

    {"plane": str, "line": str, "name": str, "meta": str,
     "start_ns": int, "dur_ns": int}

`load_events` reads an `.xplane.pb` through jax.profiler.ProfileData,
or a `.json` list of such events."""

import json
import re

COLLECTIVE = re.compile(
    r"all-reduce|reduce-scatter|all-gather|all-to-all|collective-permute")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MIN_HOST_NS = 20000  # host events shorter than this explain no gap
CONTAINERS = ("while", "conditional", "call")  # their bodies are events too
DISPATCH = re.compile(r"PjitFunction|Execute")  # the thread that drives


def is_device_plane(name):
    return name.startswith("/device:") and "TPU" in name.upper()


def load_events(path):
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = is_device_plane(plane.name)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                dur = int(ev.duration_ns)
                if not device and dur < MIN_HOST_NS:
                    continue
                events.append({
                    "plane": plane.name, "line": line.name,
                    "name": short_name(ev.name), "meta": kind_of(ev.name),
                    "start_ns": int(ev.start_ns), "dur_ns": dur,
                })
    return events


def short_name(name):
    """An XLA op's event carries the whole HLO instruction:
    '%attn.16 = (bf16[...]) custom-call(...), ...' -> 'attn.16'."""
    return name.split(" = ", 1)[0].lstrip("%")


def kind_of(name):
    """What an HLO instruction is, for matching: the target of a
    custom call ('tpu_custom_call' is a Mosaic kernel), else ''."""
    found = re.search(r'custom_call_target="([^"]+)"', name)
    return found.group(1) if found else ""


def _program_at(modules, t):
    """Name of the XLA module running on the plane at time t."""
    for start, end, name in modules:
        if start <= t < end:
            return name
    return ""


def _span(event):
    return event["start_ns"], event["start_ns"] + event["dur_ns"]


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _innermost(host, t):
    """Name of the shortest event that contains time t, on the host
    threads that dispatch programs (all threads if none is seen to)."""
    drivers = {(e["plane"], e["line"]) for e in host
               if DISPATCH.search(e["name"])}
    best = None
    for ev in host:
        if drivers and (ev["plane"], ev["line"]) not in drivers:
            continue
        if ev["start_ns"] <= t <= ev["start_ns"] + ev["dur_ns"]:
            if best is None or ev["dur_ns"] < best["dur_ns"]:
                best = ev
    return best["name"] if best else "(no host span)"


def summarize(events, window_s, top=10, gaps_looked_at=200):
    """busy_s: seconds in which an operation ran on the device, averaged
    over the device planes; collective_s and collective_exposed_s: time
    of collective operations, and the part of it during which nothing
    else ran on that device; window_s: the traced window; ops: seconds
    and count by "program|op|custom-call target" over all planes (divide by `planes` for a
    chip's share); programs: the same by XLA module; device_ops and
    idle_gaps: the breakdown the result line carries."""
    planes = sorted({e["plane"] for e in events
                     if is_device_plane(e["plane"])})
    host = [e for e in events if not is_device_plane(e["plane"])]
    busy_ns, ops, programs, gaps = 0, {}, {}, []
    coll_ns = exposed_ns = 0
    for plane in planes:
        mine = [e for e in events if e["plane"] == plane]
        op_events = [e for e in mine if e["line"] == OPS_LINE]
        merged = union(_span(e) for e in op_events)
        busy_ns += sum(e - s for s, e in merged)
        gaps += [(b[0] - a[1], a[1], b[0])
                 for a, b in zip(merged, merged[1:])]
        coll = union(_span(e) for e in op_events
                     if COLLECTIVE.search(e["name"]))
        rest = union(_span(e) for e in op_events
                     if not COLLECTIVE.search(e["name"]))
        coll_ns += sum(e - s for s, e in coll)
        both = sum(e - s for s, e in union(coll + rest))
        exposed_ns += both - sum(e - s for s, e in rest)
        # "jit_train_step(123456)" -> "jit_train_step"
        modules = sorted(
            (e["start_ns"], e["start_ns"] + e["dur_ns"],
             re.sub(r"\(\d+\)$", "", e["name"]))
            for e in mine if e["line"] == MODULES_LINE)
        for _, _, name in modules:
            programs.setdefault(name, [0.0, 0])
        for start, end, name in modules:
            programs[name][0] += (end - start) * 1e-9
            programs[name][1] += 1
        for e in op_events:
            key = "|".join((_program_at(modules, e["start_ns"]),
                            e["name"], e["meta"]))
            slot = ops.setdefault(key, [0.0, 0])
            slot[0] += e["dur_ns"] * 1e-9
            slot[1] += 1
    n = max(1, len(planes))
    by_host = {}
    for dur, s, e in sorted(gaps, reverse=True)[:gaps_looked_at]:
        name = _innermost(host, (s + e) // 2)
        by_host[name] = by_host.get(name, 0.0) + dur * 1e-9 / n
    by_name = {}
    for key, (secs, _) in ops.items():
        name = key.split("|")[1]
        if name.split(".")[0] not in CONTAINERS:
            by_name[name] = by_name.get(name, 0.0) + secs / n

    def ranked(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "planes": len(planes), "busy_s": busy_ns * 1e-9 / n,
        "window_s": window_s, "ops": ops, "programs": programs,
        "collective_s": coll_ns * 1e-9 / n,
        "collective_exposed_s": exposed_ns * 1e-9 / n,
        "device_ops": ranked(by_name), "idle_gaps": ranked(by_host),
    }


def seconds_matching(summary, pattern, table="ops"):
    """(seconds per chip, count per chip) of the operations (or
    `programs`) whose key matches the regular expression."""
    rx = re.compile(pattern)
    secs = count = 0.0
    for key, (s, c) in summary[table].items():
        if rx.search(key):
            secs += s
            count += c
    n = max(1, summary["planes"])
    return secs / n, count / n
