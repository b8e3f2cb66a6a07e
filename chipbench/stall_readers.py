"""Readers of what the program keeps about its own stalls (`"reader":
"chipbench.stall_readers:<function>"`): the retained tier of slow
phases (`tracing.recorder().slow_phases()`: a phase that lasted over
0.25 s and over three times its name's median, with the ring's entries
that lay inside it), the phases `gc`, `compile` and `watch.late` that
may lie beneath one, how much of the window the ring still holds, and
the mean of a counter that is written once a launch. They run in the
process that ran the program and cut to the timed window as
`span_readers._window_ns` does. A program from before the tier (or
before a phase's name) has nothing to read: the reader returns None and
the line leaves the metric out."""

from chipbench import span_readers


def _tracing():
    try:
        from elasticdl_tpu.observability import tracing
    except ImportError:
        return None
    return tracing


def _slow_in_window(m, prefixes):
    """The retained slow phases named `prefixes`* that start inside the
    window; None without the tier, a ring or a window."""
    tracing, window = _tracing(), span_readers._window_ns(m)
    slow = getattr(tracing.recorder(), "slow_phases", None) if tracing else None
    if slow is None or window is None or not span_readers._ring():
        return None
    return [r for r in slow()
            if r["phase"].name.startswith(tuple(prefixes))
            and window[0] <= r["phase"].start_ns < window[1]]


def _names(m, name):
    """The window's entries called `name`; None without a ring, a
    window, or a program that declares the name."""
    tracing = _tracing()
    declared = (getattr(tracing, "PHASES", ()) + getattr(tracing, "COUNTERS", ())
                if tracing else ())
    phases = span_readers._in_window(m)
    if phases is None or name not in declared:
        return None
    return [p for p in phases if p.name == name]


def window_coverage(m):
    """The share of the window that lies after the ring's oldest
    entry: 1.0, or the ring dropped part of the window before the
    readers ran."""
    phases, window = span_readers._ring(), span_readers._window_ns(m)
    if not phases or window is None:
        return None
    held_from = min(max(phases[0].end_ns, window[0]), window[1])
    return (window[1] - held_from) / (window[1] - window[0])


def worst_slow_ms(m, prefixes):
    """The longest slow phase that starts inside the window, in ms (0
    when none was slow); the line the program logged for it is said."""
    mine = _slow_in_window(m, prefixes)
    if mine is None:
        return None
    if not mine:
        return 0.0
    worst = max(mine, key=lambda r: r["phase"].end_ns - r["phase"].start_ns)
    print("worst of %d: %s" % (len(mine), worst["line"]), flush=True)
    return span_readers._ms(worst["phase"].end_ns - worst["phase"].start_ns)


def beneath_share(m, prefixes, causes=("gc", "compile", "watch.late")):
    """Of the time of the window's slow phases, the share (%) covered
    by the `causes` recorded inside them (their union, cut to the slow
    phase). With no slow phase none of it is left to the device or the
    runtime: 100."""
    mine = _slow_in_window(m, prefixes)
    if mine is None:
        return None
    if not mine:
        return 100.0
    total = covered = 0
    for r in mine:
        lo, hi = r["phase"].start_ns, r["phase"].end_ns
        total += hi - lo
        reach = lo
        for p in sorted((p for p in r["beneath"] if p.name in causes),
                        key=lambda p: p.start_ns):
            a, b = max(p.start_ns, reach), min(p.end_ns, hi)
            if b > a:
                covered += b - a
                reach = b
    return 100.0 * covered / total if total else None


def phase_share(m, name):
    """Time of the window's `name` phases over the window, in %."""
    mine, window = _names(m, name), span_readers._window_ns(m)
    if mine is None:
        return None
    return 100.0 * sum(p.end_ns - p.start_ns for p in mine) / (
        window[1] - window[0])


def before_window_s(m, name):
    """Seconds of the `name` phases that ended before the window
    opened: of `compile`, the part of set-up the compile cache decides."""
    tracing = _tracing()
    phases, window = span_readers._ring(), span_readers._window_ns(m)
    if (not phases or window is None
            or name not in getattr(tracing, "PHASES", ())):
        return None
    return 1e-9 * sum(p.end_ns - p.start_ns for p in phases
                      if p.name == name and p.end_ns <= window[0])


def mean_count(m, name):
    """The mean of the window's entries of the counter `name`: each
    entry being one launch, the share of launches it counted."""
    mine = _names(m, name)
    if not mine:
        return None
    return sum(p.attrs.get("n", 0) for p in mine) / len(mine)
