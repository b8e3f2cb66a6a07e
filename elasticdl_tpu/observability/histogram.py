"""Fixed-bucket log-linear latency histograms (HDR-style).

One bucket scheme for the WHOLE system, frozen at import time, so any
two histograms — a replica's TTFT recorder, the router's merge of
three replicas, the serving bench's client-side samples — are
mergeable by elementwise bucket-count addition and comparable without
unit negotiation. Replacing point-gauges/EWMAs with these is what lets
`ServerStatus`/`router_status` answer "what is p99 right now" and lets
the drills' client-side samples and the live telemetry compute
percentiles from the SAME code path (definitionally identical
numbers).

Scheme (values are non-negative floats; the system records
milliseconds): the value is scaled by ``1/RESOLUTION`` to an integer
``n``; the first ``SUBBUCKETS`` buckets are linear (width =
RESOLUTION), above that each power-of-two "decade" is split into
``SUBBUCKETS/2`` linear subbuckets — so relative error is bounded by
``2/SUBBUCKETS`` (~3.1% at 64) at EVERY magnitude, from a 10 us queue
pop to an hours-long stall, with ``NUM_BUCKETS`` (= 832) total
buckets. Record cost is O(1): one divide + ``int.bit_length`` + two
shifts — cheap enough for the decode loop.

Thread-safety: none here, by design — every histogram in the system
lives behind its owner's telemetry lock (serving/telemetry.py), and
the bench records from a single aggregation thread. Keeping the lock
out of the hot `record` keeps the overhead bound honest.

EXEMPLARS: a histogram can answer "p99 is 1.2 s" but not "WHICH
request" — the gap between a burning SLO gauge and a trace an operator
can open. `record(value, trace_id=...)` optionally attaches a
per-bucket exemplar (trace_id, value, unix_ts), bounded to
``EXEMPLAR_SLOTS`` buckets with the HIGHEST-value buckets winning (the
tail is what forensics wants; nobody debugs the p10 bucket) and the
max-value sample winning within a bucket — which also makes the merge
associative, so exemplars survive bucket-addition aggregation the same
way counts do. The wire form (`exemplars_wire`/`from_counts`) rides
next to `to_counts()` and the Prometheus renderer emits OpenMetrics
exemplar syntax on `_bucket` lines; observability/promparse.py
validates it independently.
"""

import math
import time

#: smallest distinguishable value (0.01 => 10 us when recording ms)
RESOLUTION = 0.01
#: linear subbuckets per power-of-two decade (power of two)
SUBBUCKETS = 64
_SUB_BITS = SUBBUCKETS.bit_length() - 1  # log2(SUBBUCKETS)
_HALF = SUBBUCKETS // 2
#: decades above the linear range (covers ~2.8 hours in ms)
_DECADES = 24
NUM_BUCKETS = SUBBUCKETS + _DECADES * _HALF
#: max buckets carrying an exemplar per histogram; the HIGHEST-value
#: buckets win a slot (tail forensics), the max-value sample wins
#: within a bucket (merge stays associative)
EXEMPLAR_SLOTS = 16


def bucket_index(value):
    """O(1) bucket index for a non-negative value."""
    try:
        n = int(value / RESOLUTION)
    except (OverflowError, ValueError):  # inf: clamp to the top
        return NUM_BUCKETS - 1
    if n < SUBBUCKETS:
        return n if n >= 0 else 0
    e = n.bit_length() - _SUB_BITS  # >= 1
    if e > _DECADES:  # beyond the top decade: clamp
        return NUM_BUCKETS - 1
    m = n >> e  # in [SUBBUCKETS/2, SUBBUCKETS)
    return SUBBUCKETS + (e - 1) * _HALF + (m - _HALF)


def bucket_bounds(idx):
    """(lower, upper) value bounds of bucket `idx` (upper exclusive)."""
    if idx < SUBBUCKETS:
        return idx * RESOLUTION, (idx + 1) * RESOLUTION
    k = idx - SUBBUCKETS
    e = k // _HALF + 1
    m = _HALF + k % _HALF
    return (m << e) * RESOLUTION, ((m + 1) << e) * RESOLUTION


class LogLinearHistogram(object):
    """Mergeable fixed-bucket histogram with exact count/sum/min/max.

    ``counts`` is a dense list of ``NUM_BUCKETS`` ints; `to_counts()`
    trims trailing zeros for wire transport (the `repeated int64`
    histogram fields on the status protos) and `from_counts()`
    rebuilds — merge is elementwise addition, so per-replica
    histograms aggregate at the router without losing percentile
    fidelity (percentiles of merged counts, never averages of
    percentiles)."""

    __slots__ = ("counts", "count", "sum", "min", "max", "exemplars")

    def __init__(self):
        self.counts = [0] * NUM_BUCKETS
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = 0.0
        #: bucket index -> (trace_id, value, unix_ts); bounded to
        #: EXEMPLAR_SLOTS entries, highest-index buckets win a slot
        self.exemplars = {}

    def record(self, value, trace_id=None, ts=None):
        value = float(value)
        if not 0.0 <= value < math.inf:  # negative/NaN/inf: refuse
            return
        idx = bucket_index(value)
        self.counts[idx] += 1
        self.count += 1
        self.sum += value
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value
        if trace_id:
            self._note_exemplar(
                idx, str(trace_id), value,
                time.time() if ts is None else float(ts),
            )

    def _note_exemplar(self, idx, trace_id, value, ts):
        """Keep at most EXEMPLAR_SLOTS exemplar-carrying buckets, the
        HIGHEST-value buckets winning a slot and the max-value sample
        winning within a bucket — the ordering that makes merge
        associative and keeps the p99 tail covered."""
        cur = self.exemplars.get(idx)
        if cur is not None:
            if value >= cur[1]:
                self.exemplars[idx] = (trace_id, value, ts)
            return
        if len(self.exemplars) >= EXEMPLAR_SLOTS:
            low = min(self.exemplars)
            if idx <= low:
                return  # a lower bucket never evicts a higher one
            del self.exemplars[low]
        self.exemplars[idx] = (trace_id, value, ts)

    def merge(self, other):
        """Fold `other` in (elementwise bucket addition); exemplars
        merge keep-max-per-bucket under the same slot bound."""
        for i, c in enumerate(other.counts):
            if c:
                self.counts[i] += c
        self.count += other.count
        self.sum += other.sum
        if other.count:
            self.min = min(self.min, other.min)
            self.max = max(self.max, other.max)
        for idx, (tid, value, ts) in other.exemplars.items():
            self._note_exemplar(idx, tid, value, ts)
        return self

    def percentile(self, q):
        """Value at percentile `q` (0..100): the midpoint of the
        bucket where the cumulative count crosses rank ceil(q% * n),
        clamped into the exact [min, max] envelope (so a one-sample
        histogram answers that sample's bucket, not a bucket edge).
        0.0 when empty — proto-friendly: absent percentile == 0."""
        if not self.count:
            return 0.0
        rank = max(1, int(math.ceil(q / 100.0 * self.count)))
        cum = 0
        for i, c in enumerate(self.counts):
            cum += c
            if cum >= rank:
                lo, hi = bucket_bounds(i)
                mid = (lo + hi) / 2.0
                return min(max(mid, self.min), self.max)
        return self.max  # unreachable unless counts were tampered

    def snapshot(self, qs=(50, 90, 99)):
        """{"p50": ..., "p90": ..., "p99": ..., "count": n} — the
        status-RPC shape."""
        out = {"p%d" % q: self.percentile(q) for q in qs}
        out["count"] = self.count
        return out

    def to_counts(self):
        """Dense counts with trailing zeros trimmed (wire form)."""
        last = 0
        for i, c in enumerate(self.counts):
            if c:
                last = i + 1
        return self.counts[:last]

    def exemplars_wire(self):
        """Exemplar wire form riding next to to_counts():
        {bucket_index: [trace_id, value, unix_ts]} — JSON-safe (lists,
        not tuples; from_counts re-accepts string keys a JSON
        round-trip produces)."""
        return {
            idx: [tid, value, ts]
            for idx, (tid, value, ts) in self.exemplars.items()
        }

    @classmethod
    def from_counts(cls, counts, exemplars=None):
        """Rebuild from wire-form counts (+ optional exemplar map).
        min/max/sum degrade to bucket-midpoint estimates (bounded by
        the scheme's relative error) — good enough for percentile
        math, which only needs the counts."""
        h = cls()
        for i, c in enumerate(counts):
            c = int(c)
            if c <= 0 or i >= NUM_BUCKETS:
                continue
            h.counts[i] = c
            h.count += c
            lo, hi = bucket_bounds(i)
            mid = (lo + hi) / 2.0
            h.sum += mid * c
            h.min = min(h.min, mid)
            h.max = max(h.max, mid)
        for idx, ex in (exemplars or {}).items():
            tid, value, ts = ex
            h._note_exemplar(int(idx), str(tid), float(value),
                             float(ts))
        return h


def percentiles(values, qs=(50, 90, 99)):
    """Percentiles of `values` through the shared histogram — THE
    entry point the drills and the tests use, so a drill's client-side
    numbers and live status-RPC numbers come from one definition.
    {"p50": ...} with None entries when `values` is empty (a run
    with no completions has no percentile, unlike a live histogram
    where 0 means "no data yet")."""
    if not values:
        return {"p%d" % q: None for q in qs}
    h = LogLinearHistogram()
    for v in values:
        h.record(v)
    return {"p%d" % q: round(h.percentile(q), 3) for q in qs}
