"""Readers of the program's own phase spans (`"reader":
"chipbench.span_readers:<function>"`). They run in the process that ran
the program, so they read its phase ring directly
(`elasticdl_tpu.observability.tracing.recorder().phases()`: name,
start_ns, end_ns, seq, parent, trace_id, attrs on the wall clock) and
cut it to the timed window themselves, from what every driver already
hands over:

    t_open  = <process start> + counters.setup_s + check_s
    t_close = t_open + window_s

`setup_s` is the clock of `Run.mark_window_open`, so for `train_job` the
cut is the driver's own window to the microsecond; for `open_loop` the
window opens a moment later (the schedule is made and, under `--trace
1`, the profiler started in between), a moment in which the server only
idles: no tick is misplaced at the open, and the close falls that much
early. A phase belongs to the window if it starts inside it, and a
tick's phases go where the tick does. Where the program has no phase
ring, or the ring holds no phase of the kind, a reader returns None."""

import sys

from chipbench import stats


def _ring():
    """The program's phases, oldest first, or None where the program
    records none (a checkout from before the phase ring)."""
    try:
        from elasticdl_tpu.observability import tracing
    except ImportError:
        return None
    phases = getattr(tracing.recorder(), "phases", None)
    return phases() if callable(phases) else None


def _window_ns(m):
    """(t_open, t_close) of the timed window in wall-clock ns, or None
    when the harness's clock of process start cannot be found."""
    t0 = getattr(sys.modules.get("__main__"), "_T0", None)
    if t0 is None:
        t0 = getattr(sys.modules.get("chipbench.run"), "_T0", None)
    setup_s = m["counters"].get("setup_s")
    if t0 is None or setup_s is None:
        return None
    t_open = t0 + setup_s + m.get("check_s", 0.0)
    return int(t_open * 1e9), int((t_open + m["window_s"]) * 1e9)


def _in_window(m):
    """The ring's entries that start inside the window, or None when
    the ring or the window is missing."""
    phases, window = _ring(), _window_ns(m)
    if not phases or window is None:
        return None
    return [p for p in phases if window[0] <= p.start_ns < window[1]]


def _ms(ns):
    return ns * 1e-6


def decode_ticks(phases):
    """[{seq, active, <child name>: (start_ns, end_ns)}] of the ticks
    that ran a decode step (they have a `tick.dispatch`), in time
    order. `active` is the root's: slots still seated when it ended."""
    ticks = {}
    for p in phases:
        if p.name == "tick":
            ticks.setdefault(p.seq, {}).update(
                seq=p.seq, start_ns=p.start_ns,
                active=p.attrs.get("active", 0))
        elif p.name.startswith("tick.") and p.parent == "tick":
            ticks.setdefault(p.seq, {})[p.name] = (p.start_ns, p.end_ns)
    return sorted((t for t in ticks.values()
                   if "tick.dispatch" in t and "start_ns" in t),
                  key=lambda t: t["start_ns"])


def _window_ticks(m):
    """The decode ticks that start inside the window, and the window;
    (None, None) when the ring or the window is missing."""
    phases, window = _ring(), _window_ns(m)
    if not phases or window is None:
        return None, None
    return [t for t in decode_ticks(phases)
            if window[0] <= t["start_ns"] < window[1]], window


def tick_phase_ms(m, phases, q=50):
    """The q-th percentile (exact), over the window's decode ticks, of
    the time a tick spent in the named child phases, in ms."""
    ticks, _ = _window_ticks(m)
    if not ticks:
        return None
    return _ms(stats.percentile(
        [sum(t[n][1] - t[n][0] for n in phases if n in t) for t in ticks],
        q))


def holds_ns(ticks):
    """Time in which slots were seated and no decode tick was in
    flight: from the end of tick n's `tick.stream` to the start of
    tick n+1's `tick.ensure`, for consecutive decode ticks of which
    the first left slots seated."""
    out = []
    for a, b in zip(ticks, ticks[1:]):
        if a["active"] > 0 and "tick.stream" in a and "tick.ensure" in b:
            out.append(max(0, b["tick.ensure"][0] - a["tick.stream"][1]))
    return out


def hold_share(m):
    ticks, window = _window_ticks(m)
    if not ticks or len(ticks) < 2:
        return None
    return 100.0 * sum(holds_ns(ticks)) / (window[1] - window[0])


def hold_ms(m, q=95):
    ticks, _ = _window_ticks(m)
    holds = holds_ns(ticks) if ticks else []
    return _ms(stats.percentile(holds, q)) if holds else None


def count_ratio(m, num, den):
    """Σ n of the counter `num` over Σ n of `den`, inside the window."""
    phases = _in_window(m)
    if phases is None:
        return None
    total = {num: 0, den: 0}
    for p in phases:
        if p.name in total:
            total[p.name] += p.attrs.get("n", 0)
    return total[num] / total[den] if total[den] else None


def between_ms(m, phase, q=50):
    """The q-th percentile of the time between one `phase` ending and
    the next one starting, inside the window: what the loop around it
    costs a step."""
    phases = _in_window(m)
    if phases is None:
        return None
    mine = sorted((p for p in phases if p.name == phase),
                  key=lambda p: p.start_ns)
    gaps = [b.start_ns - a.end_ns for a, b in zip(mine, mine[1:])]
    return _ms(stats.percentile(gaps, q)) if gaps else None


def longest_ms(m, prefix, other_than):
    """The longest single phase named `prefix`* other than
    `other_than` inside the window, in ms; its name and seq are said on
    a line of their own."""
    phases = _in_window(m)
    if phases is None:
        return None
    mine = [p for p in phases
            if p.name.startswith(prefix) and p.name != other_than
            and p.end_ns > p.start_ns]
    if not mine:
        return None
    worst = max(mine, key=lambda p: p.end_ns - p.start_ns)
    print("longest phase: %s seq %s, %.3f ms" % (
        worst.name, worst.seq, _ms(worst.end_ns - worst.start_ns)),
        flush=True)
    return _ms(worst.end_ns - worst.start_ns)
