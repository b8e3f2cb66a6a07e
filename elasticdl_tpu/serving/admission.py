"""Request admission: the bounded queue in front of the decode pool.

The serving twin of the master's task queue, with the elastic-training
DNA inverted: training workers PULL tasks and the queue is unbounded
(the job is finite); serving requests are PUSHED by clients and the
queue must be bounded, because decode capacity is fixed (the slot pool)
and an unbounded queue converts overload into unbounded latency for
everyone. Admission policy:

* full queue        -> reject NOW with RESOURCE_EXHAUSTED (backpressure:
                       the client retries against another replica; the
                       retry semantics mirror common/retry.py — the
                       rejection is transient and retryable)
* invalid request   -> INVALID_ARGUMENT (prompt/output budget cannot fit
                       the model's cache; never enters the queue)
* expired deadline  -> DEADLINE_EXCEEDED, whether it expires while
                       queued or while decoding (the scheduler evicts
                       mid-flight expirations between steps)

Thread-safe: gRPC handler threads submit; the single scheduler thread
pops. Completion plumbing rides on each request's event queue so a
handler can stream tokens as the scheduler produces them.
"""

import collections
import threading
import time


class AdmissionError(Exception):
    """Rejected at (or after) admission. `code` is the gRPC status name
    the servicer maps to: RESOURCE_EXHAUSTED (queue full / shutdown),
    INVALID_ARGUMENT (malformed), DEADLINE_EXCEEDED (expired)."""

    def __init__(self, code, message):
        super().__init__(message)
        self.code = code


class ServingRequest(object):
    """One in-flight generation request.

    Client-facing fields mirror proto GenerateRequest; the rest is
    scheduler state. Events flow through `events` as tuples:
        ("tokens", [ids], model_version, [reveal steps])
                                          new tokens (first event also
                                          marks TTFT); the last part is
                                          empty but for a block-
                                          diffusion model: the
                                          denoising pass that revealed
                                          each token
        ("done", model_version)           completed; all tokens emitted
        ("error", code, message)          terminal failure

    `span` (observability/tracing.py) is the request's serve span: the
    servicer opens it at admission (parenting under the router's
    dispatch span when the RPC carried trace context) and the
    scheduler/engine annotate the lifecycle through `trace_event` —
    both guard on span being None so direct/off-path construction
    (tests, benches) costs nothing."""

    _ids = iter(range(1, 2 ** 62))
    _ids_lock = threading.Lock()

    def __init__(self, prompt, max_new_tokens, temperature=0.0, seed=0,
                 deadline_ms=0, clock=time.monotonic, trace_id="",
                 parent_span_id="", prefill_only=False):
        with ServingRequest._ids_lock:
            self.request_id = next(ServingRequest._ids)
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        # disaggregated cache warming (serving/disagg.py): seat, run
        # the prompt's prefill, register the chain, release — the
        # blocks park refcount-0 cached, matchable and exportable
        self.prefill_only = bool(prefill_only)
        self.temperature = float(temperature)
        self.seed = int(seed)
        self.submitted_at = clock()
        self.deadline = (
            self.submitted_at + deadline_ms / 1000.0
            if deadline_ms and deadline_ms > 0 else None
        )
        self.events = collections.deque()
        self._event_cv = threading.Condition()
        # tracing context (empty = untraced caller; the servicer mints)
        self.trace_id = trace_id or ""
        self.parent_span_id = parent_span_id or ""
        self.span = None
        # scheduler-side state
        self.generated = []
        # parallel to `generated` for a block-diffusion model (the
        # engine's commit pass fills it), else left empty
        self.reveal_steps = []
        self.first_token_at = None
        self.seated_at = None  # set when the scheduler seats a slot
        self.model_version = -1

    # ---- tracing (no-ops until the servicer attaches a span)

    def trace_event(self, name, **attrs):
        if self.span is not None:
            self.span.event(name, **attrs)

    def finish_span(self, status="ok"):
        if self.span is not None:
            self.span.finish(status)

    def expired(self, now):
        return self.deadline is not None and now > self.deadline

    def queue_wait_secs(self, now=None):
        """Time spent queued before seating (None until seated). The
        router folds this — via the telemetry EWMA and the
        ServerStatus queue_wait_ms field — into its load signal: two
        replicas with equal queue DEPTH can hide very different queue
        TIME when their requests differ in length."""
        if self.seated_at is None:
            return None
        return self.seated_at - self.submitted_at

    # ---- event plumbing (scheduler -> handler thread)

    def push(self, event):
        with self._event_cv:
            self.events.append(event)
            self._event_cv.notify_all()

    def next_event(self, timeout=None):
        """Block for the next event; None on timeout (the caller re-checks
        its own deadline and keeps waiting — used as a liveness bound so
        a lost scheduler can never hang a handler forever)."""
        with self._event_cv:
            if not self.events:
                self._event_cv.wait(timeout)
            if not self.events:
                return None
            return self.events.popleft()


class RequestQueue(object):
    """Bounded FIFO with deadline-aware pop; the admission controller.

    `capacity` bounds only the QUEUED backlog — requests move out of the
    queue when the scheduler seats them in a slot. total_budget(seq_len)
    validation happens at submit so a request that can never fit fails
    fast instead of poisoning a slot.

    `max_cached_tokens` is the paged pool's never-fits bound (engine.
    max_cached_tokens()): a request whose prompt + decode cache rows
    exceed the WHOLE block budget is invalid at submit — it could queue
    forever. Requests that fit the pool but not the blocks free right
    now are a different thing entirely: they stay queued and seat when
    completions release blocks (the `fit` predicate on pop_ready).
    """

    def __init__(self, capacity, seq_len, clock=time.monotonic,
                 max_cached_tokens=None, refuse=None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1, got %d" % capacity)
        self.capacity = int(capacity)
        self.seq_len = int(seq_len)
        self.max_cached_tokens = (
            int(max_cached_tokens) if max_cached_tokens else None
        )
        self._clock = clock
        # the engine's own word on a request (engine.refuse_request:
        # a message, or None): what this model cannot serve at all
        self._refuse = refuse
        self._q = collections.deque()
        self._cv = threading.Condition()
        self._closed = False

    def __len__(self):
        with self._cv:
            return len(self._q)

    def submit(self, request):
        """Admit or raise AdmissionError. Never blocks: backpressure is
        an immediate REJECT, not a wait (a waiting client holds a gRPC
        thread; a rejected one retries with backoff against capacity
        that may have moved elsewhere)."""
        self.validate(request)
        with self._cv:
            if self._closed:
                raise AdmissionError(
                    "RESOURCE_EXHAUSTED", "server is shutting down"
                )
            if len(self._q) >= self.capacity:
                raise AdmissionError(
                    "RESOURCE_EXHAUSTED",
                    "request queue full (%d queued)" % len(self._q),
                )
            self._q.append(request)
            self._cv.notify_all()

    def validate(self, request):
        p = len(request.prompt)
        if p < 1:
            raise AdmissionError("INVALID_ARGUMENT", "empty prompt")
        if request.max_new_tokens < 1:
            raise AdmissionError(
                "INVALID_ARGUMENT",
                "max_new_tokens must be >= 1, got %d"
                % request.max_new_tokens,
            )
        if p + request.max_new_tokens > self.seq_len:
            raise AdmissionError(
                "INVALID_ARGUMENT",
                "prompt %d + max_new_tokens %d exceeds the model's "
                "seq_len %d" % (p, request.max_new_tokens, self.seq_len),
            )
        cached = p + request.max_new_tokens - 1
        caches = (request.max_new_tokens > 1
                  or getattr(request, "prefill_only", False))
        if (self.max_cached_tokens is not None
                and caches
                and cached > self.max_cached_tokens):
            raise AdmissionError(
                "INVALID_ARGUMENT",
                "request needs %d KV rows > the pool's total budget of "
                "%d tokens" % (cached, self.max_cached_tokens),
            )
        why = self._refuse(request) if self._refuse else None
        if why:
            raise AdmissionError("INVALID_ARGUMENT", why)
        if request.expired(self._clock()):
            raise AdmissionError(
                "DEADLINE_EXCEEDED", "deadline expired before admission"
            )

    def pop_ready(self, fit=None):
        """Next admissible request, expiring stale ones on the way out.
        Returns (request, expired_list); request is None when empty.

        `fit` (optional predicate): the engine's can_seat — when the
        head-of-line request cannot seat RIGHT NOW (paged pool out of
        blocks), it STAYS at the head and pop returns None. FIFO order
        is preserved deliberately: skipping ahead to smaller requests
        would starve long ones under sustained short-request load."""
        expired = []
        now = self._clock()
        with self._cv:
            while self._q:
                req = self._q[0]
                if req.expired(now):
                    self._q.popleft()
                    expired.append(req)
                    continue
                if fit is not None and not fit(req):
                    return None, expired
                self._q.popleft()
                return req, expired
        return None, expired

    def wait_for_work(self, timeout):
        """Scheduler idle wait: returns once a request is queued or the
        timeout lapses (the scheduler then runs its periodic duties —
        hot-reload poll, telemetry flush)."""
        with self._cv:
            if not self._q:
                self._cv.wait(timeout)
            return bool(self._q)

    def wake(self):
        """Wake any wait_for_work sleeper (shutdown path)."""
        with self._cv:
            self._cv.notify_all()

    def close(self):
        """Stop admitting; drain-and-reject the backlog. Returns the
        requests that were still queued so the caller can fail them
        cleanly (RESOURCE_EXHAUSTED, never a hang)."""
        with self._cv:
            self._closed = True
            backlog = list(self._q)
            self._q.clear()
            self._cv.notify_all()
        return backlog
