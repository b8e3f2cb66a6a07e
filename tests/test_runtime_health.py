"""Runtime health plane unit tests (observability/runtime_health.py):
the recompile sentry's compile accounting + steady boundary, the
progress watchdog state machine (idle healthy, compile-is-progress,
transition-edged bundle dump), the flight recorder's bound, the
device-memory accountant's reconciliation math + the deliberate-leak
conviction, the diagnostic bundle's schema/atomicity, the SIGUSR2
dump registration, and the end-to-end self-report through a real
in-process GenerationServer (ServerStatus fields + /metrics family).
"""

import glob
import json
import os
import signal
import threading
import time

import pytest

from elasticdl_tpu.common.fault_injection import FaultInjector
from elasticdl_tpu.observability.runtime_health import (
    BUNDLE_SCHEMA,
    DeviceMemoryAccountant,
    FlightRecorder,
    ProgressWatchdog,
    RecompileSentry,
    RuntimeHealth,
    install_sigusr2_dump,
    tracked_jit,
    validate_bundle,
    write_bundle,
)
from elasticdl_tpu.serving.telemetry import ServingTelemetry


class FakeClock(object):
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------- recompile sentry


def test_tracked_jit_counts_compiles_not_calls():
    import jax.numpy as jnp

    sentry = RecompileSentry()
    fn = tracked_jit(lambda x: x + 1, "add", lambda: sentry)
    fn(jnp.zeros(3))
    fn(jnp.zeros(3))  # cache hit: no new compile
    snap = sentry.snapshot()
    assert snap["compiles"] == {"add": 1}
    assert snap["recompiles"] == 0


def test_recompile_vs_steady_anomaly():
    import jax.numpy as jnp

    sentry = RecompileSentry()
    fn = tracked_jit(lambda x: x * 2, "mul", lambda: sentry)
    fn(jnp.zeros(3))
    fn(jnp.zeros(4))  # new signature: a recompile, pre-boundary
    assert sentry.snapshot()["recompiles"] == 1
    assert sentry.snapshot()["steady_recompiles"] == 0
    sentry.mark_steady()
    # a FIRST compile of a new name after the boundary is the cold
    # path working as designed — never an anomaly
    other = tracked_jit(lambda x: x - 1, "sub", lambda: sentry)
    other(jnp.zeros(3))
    assert sentry.snapshot()["steady_recompiles"] == 0
    # a recompile of an existing name after the boundary IS one
    fn(jnp.zeros(5))
    snap = sentry.snapshot()
    assert snap["steady_recompiles"] == 1
    assert snap["anomalies"][-1]["fn"] == "mul"


def test_tracked_jit_without_sentry_is_plain_jit():
    import jax.numpy as jnp

    fn = tracked_jit(lambda x: x + 1, "loose", lambda: None)
    assert float(fn(jnp.asarray(1.0))) == 2.0


def test_tracked_jit_static_argnames_resolve_through_wrapper():
    import jax.numpy as jnp

    sentry = RecompileSentry()

    def slice_k(x, k):
        return x[:k]

    fn = tracked_jit(slice_k, "slice", lambda: sentry,
                     static_argnames=("k",))
    assert list(fn(jnp.arange(8), k=3)) == [0, 1, 2]
    fn(jnp.arange(8), k=3)
    assert sentry.snapshot()["compiles"]["slice"] == 1


def test_sentry_prometheus_family_shape():
    sentry = RecompileSentry()
    sentry.record_compile("a")
    sentry.record_compile("b")
    sentry.record_compile("b")
    fams = sentry.prometheus()
    assert len(fams) == 1
    name, mtype, _help, samples = fams[0]
    assert name == "edl_serving_recompiles_total"
    assert mtype == "counter"
    by_fn = {labels["fn"]: value for _s, labels, value in samples}
    assert by_fn == {"a": 1, "b": 2}


# ---------------------------------------------------------- watchdog


def test_watchdog_idle_is_healthy_forever():
    clock = FakeClock()
    wd = ProgressWatchdog(stall_after_secs=2.0, clock=clock)
    for _ in range(10):
        assert wd.observe(work=0, progress_counter=0) is False
        clock.advance(5.0)
    assert wd.state == "ok"
    assert wd.last_progress_age_ms() == 0.0


def test_watchdog_stalls_only_on_frozen_progress_with_work():
    clock = FakeClock()
    wd = ProgressWatchdog(stall_after_secs=2.0, clock=clock)
    wd.observe(work=1, progress_counter=5)
    clock.advance(1.0)
    # progress moving: healthy
    assert wd.observe(work=1, progress_counter=6) is False
    clock.advance(1.9)
    assert wd.observe(work=1, progress_counter=6) is False
    assert wd.state == "ok"
    clock.advance(0.2)  # age crosses the budget
    assert wd.observe(work=1, progress_counter=6) is True  # edge
    assert wd.state == "stalled"
    assert wd.stalls == 1
    # sustained stall: no second edge
    clock.advance(5.0)
    assert wd.observe(work=1, progress_counter=6) is False
    assert wd.stalls == 1
    # recovery: tokens flow again
    assert wd.observe(work=1, progress_counter=7) is False
    assert wd.state == "ok"
    assert wd.last_progress_age_ms() == 0.0


def test_watchdog_compile_counts_as_progress():
    """A long cold jit compile must never read as a stall: the caller
    folds compiles into the progress counter, so a moving compile
    count resets the age exactly like a committed token."""
    clock = FakeClock()
    wd = ProgressWatchdog(stall_after_secs=2.0, clock=clock)
    wd.observe(work=1, progress_counter=0)
    for _ in range(5):
        clock.advance(1.5)
        # tokens frozen, but the compile half of the counter moves
        assert wd.observe(work=1, progress_counter=_ + 1) is False
    assert wd.state == "ok"


# ----------------------------------------------------- flight recorder


def test_flight_recorder_bound_and_drop_accounting():
    ring = FlightRecorder(capacity=4)
    for i in range(10):
        ring.record({"tick": i})
    snap = ring.snapshot()
    assert [s["tick"] for s in snap] == [6, 7, 8, 9]  # drop-oldest
    assert ring.recorded == 10
    assert ring.dropped == 6


# --------------------------------------------------- memory accountant


class LedgerEngine(object):
    """Fake engine with a scripted ledger (no jax)."""

    def __init__(self):
        self.kv = {"kv_bytes_total": 1000, "kv_host_bytes": 200}

    def kv_stats(self):
        return dict(self.kv)


def test_accountant_reconciles_drift_since_baseline():
    eng = LedgerEngine()
    live = {"bytes": 1500}
    acct = DeviceMemoryAccountant(
        eng, live_bytes_fn=lambda: (live["bytes"], None)
    )
    view = acct.reconcile()
    # first reconcile baselines the gap: no drift yet
    assert view["unaccounted_bytes"] == 0
    live["bytes"] = 1900  # 400 bytes nothing in the ledger explains
    view = acct.reconcile()
    assert view["unaccounted_bytes"] == 400
    assert view["unaccounted_peak_bytes"] == 400
    # the drift clears (a transient): current drops, the PEAK holds —
    # monotone by construction
    live["bytes"] = 1500
    view = acct.reconcile()
    assert view["unaccounted_bytes"] == 0
    assert view["unaccounted_peak_bytes"] == 400
    # ledger growth the runtime CAN name is not drift
    live["bytes"] = 2000
    eng.kv["kv_bytes_total"] = 1500
    view = acct.reconcile()
    assert view["unaccounted_bytes"] == 0


def test_accountant_rebase_absorbs_presteady_drift():
    eng = LedgerEngine()
    live = {"bytes": 5000}
    acct = DeviceMemoryAccountant(
        eng, live_bytes_fn=lambda: (live["bytes"], None)
    )
    acct.reconcile()
    live["bytes"] = 9000  # warmup junk
    acct.reconcile()
    assert acct.snapshot()["unaccounted_peak_bytes"] == 4000
    acct.rebase()  # the steady boundary forgives it, peak included
    snap = acct.snapshot()
    assert snap["unaccounted_bytes"] == 0
    assert snap["unaccounted_peak_bytes"] == 0
    live["bytes"] = 9100  # ... but post-steady drift convicts
    acct.reconcile()
    assert acct.snapshot()["unaccounted_peak_bytes"] == 100


def test_accountant_param_and_draft_lines_with_real_engine_attrs():
    import jax.numpy as jnp

    class Eng(object):
        def __init__(self):
            self.variables = {"params": {"w": jnp.zeros((4, 4))}}
            self._exec_variables = self.variables  # non-quantized
            self._d_pool = {"k": jnp.zeros((2, 2))}

        def kv_stats(self):
            return {"kv_bytes_total": 0, "kv_host_bytes": 0}

    acct = DeviceMemoryAccountant(Eng(),
                                  live_bytes_fn=lambda: (0, None))
    ledger = acct.ledger()
    # exec IS variables: the shared leaves count once
    assert ledger["param_bytes"] == 4 * 4 * 4
    assert ledger["draft_pool_bytes"] == 2 * 2 * 4


# ------------------------------------------------------------ bundles


def test_bundle_write_is_atomic_and_schema_valid(tmp_path):
    bundle = {
        "schema": BUNDLE_SCHEMA, "reason": "progress_stall",
        "pid": os.getpid(), "seq": 1, "unix_ts": time.time(),
        "health": {"state": "stalled"}, "ring": [{"tick": 1}],
        "kv_ledger": {"kv_bytes_total": 1},
        "memory": {"unaccounted_bytes": 0},
        "recompiles": {"compiles": {}},
        "stacks": {"faulthandler": "Thread 0x1", "threads": []},
    }
    assert validate_bundle(bundle) == []
    path = write_bundle(str(tmp_path), bundle)
    assert os.path.exists(path)
    assert not glob.glob(str(tmp_path / "*.tmp"))  # no torn remnant
    with open(path) as f:
        assert json.load(f)["reason"] == "progress_stall"


def test_validate_bundle_rejects_malformed():
    assert validate_bundle([]) == ["bundle is not a dict"]
    problems = validate_bundle({"schema": "wrong"})
    assert any("missing key" in p for p in problems)
    assert any("schema" in p for p in problems)
    # stacks must actually carry something
    good = {
        "schema": BUNDLE_SCHEMA, "reason": "r", "pid": 1,
        "unix_ts": 1.0, "health": {}, "ring": [], "kv_ledger": {},
        "memory": {}, "recompiles": {},
        "stacks": {"faulthandler": "", "threads": []},
    }
    assert any("stacks" in p for p in validate_bundle(good))


# --------------------------------------------------- RuntimeHealth owner


class TickQueue(object):
    def __init__(self):
        self.n = 0

    def __len__(self):
        return self.n


class StubEngine(LedgerEngine):
    def __init__(self):
        super().__init__()
        self.active = 0

    def active_count(self):
        return self.active


def build_health(tmp_path=None, injector=None, stall_after=2.0):
    clock = FakeClock()
    engine = StubEngine()
    queue = TickQueue()
    telemetry = ServingTelemetry(clock=clock)
    health = RuntimeHealth(
        engine, queue, telemetry,
        stall_after_secs=stall_after,
        health_dir=str(tmp_path) if tmp_path is not None else "",
        injector=injector, clock=clock,
        live_bytes_fn=lambda: (0, None),
    )
    return health, engine, queue, telemetry, clock


def test_health_stall_transition_counts_and_dumps(tmp_path):
    health, engine, queue, telemetry, clock = build_health(tmp_path)
    health.record_tick(0, 1, 0.01, 3)
    engine.active = 1
    health.check()  # work present, counter frozen: window opens
    clock.advance(2.5)
    assert health.check() is True  # the ok->stalled edge
    assert telemetry.counters["stalls"] == 1
    assert health.snapshot()["health_state"] == "stalled"
    assert health.snapshot()["last_progress_age_ms"] >= 2000.0
    paths = glob.glob(str(tmp_path / "health-bundle-*.json"))
    assert len(paths) == 1
    with open(paths[0]) as f:
        bundle = json.load(f)
    assert validate_bundle(bundle) == []
    assert bundle["reason"] == "progress_stall"
    assert bundle["ring"][0]["tokens_committed"] == 3
    # this very test thread is in the stacks
    assert bundle["stacks"]["faulthandler"] or \
        bundle["stacks"]["threads"]
    # sustained stall: one bundle, not one per check
    clock.advance(5.0)
    assert health.check() is False
    assert len(glob.glob(str(tmp_path / "health-bundle-*.json"))) == 1


def test_the_bundle_carries_the_open_phases_and_the_slow_ones(tmp_path):
    """What the watchdog cannot say: which phase the stuck thread is
    in (a phase is sealed only when it ends), what the watcher saw
    there, and the slow phases kept before it came to this."""
    from elasticdl_tpu.observability import tracing

    health, engine, _queue, _telemetry, clock = build_health(tmp_path)
    tracing.recorder().clear_phases()
    slow = tracing.begin("tick.commit", seq=8)
    slow.start_ns -= 10**9
    tracing.end(slow)
    tick = tracing.begin("tick", seq=9)
    fetch = tracing.begin("tick.fetch")
    fetch.start_ns -= 2 * 10**9  # two seconds into a fetch
    try:
        sample = health.phase_watch.wake()
        assert sample.parent == "tick.fetch" and sample.seq == 9
        engine.active = 1
        health.check()
        clock.advance(2.5)
        assert health.check() is True
    finally:
        tracing.end(fetch)
        tracing.end(tick)
        tracing.recorder().clear_phases()
    [path] = glob.glob(str(tmp_path / "health-bundle-*.json"))
    with open(path) as f:
        bundle = json.load(f)
    assert validate_bundle(bundle) == []
    [mine] = [v for v in bundle["open_phases"].values()
              if [p[0] for p in v] == ["tick", "tick.fetch"]]
    assert mine[1][1] == 9
    kept = bundle["slow_phases"]
    assert [r["phase"]["name"] for r in kept["slow"]] == ["tick.commit"]
    assert kept["slow"][0]["line"].startswith("slow phase tick.commit seq 8")
    [open_sample] = kept["open_samples"]
    assert open_sample["name"] == "watch.sample"
    assert any("test_the_bundle_carries" in f
               for f in open_sample["attrs"]["frames"])


def test_record_tick_takes_the_kv_stats_the_tick_has_read():
    health, engine, _queue, _telemetry, _clock = build_health()
    reads = []
    stats = engine.kv_stats
    engine.kv_stats = lambda: reads.append(1) or stats()
    health.record_tick(2, 1, 0.01, 3, kv={"kv_blocks_free": 7,
                                          "kv_bytes_in_use": 96})
    assert reads == []
    last = health.recorder.snapshot()[-1]
    assert last["kv_blocks_free"] == 7 and last["kv_bytes_in_use"] == 96
    assert last["kv_host_blocks"] == 0 and last["queue_depth"] == 2
    health.record_tick(0, 1, 0.01, 3)  # a caller without one
    assert reads == [1]


def test_the_health_thread_wakes_the_watcher_every_check():
    health, _engine, _queue, _telemetry, _clock = build_health()
    health.check_secs = 0.01
    woken = []
    wake = health.phase_watch.wake
    health.phase_watch.wake = lambda: woken.append(1) or wake()
    health.start()
    try:
        deadline = time.time() + 10
        while len(woken) < 3 and time.time() < deadline:
            time.sleep(0.01)
    finally:
        health.stop()
    assert len(woken) >= 3
    assert health._thread is None


def test_health_tokens_recover_the_state(tmp_path):
    health, engine, _queue, telemetry, clock = build_health(tmp_path)
    engine.active = 1
    health.check()
    clock.advance(3.0)
    health.check()
    assert health.snapshot()["health_state"] == "stalled"
    telemetry.counters["tokens_generated"] += 1  # progress returns
    health.check()
    assert health.snapshot()["health_state"] == "ok"


def test_health_reconcile_mirrors_gauges_and_anomalies(tmp_path):
    health, _e, _q, telemetry, clock = build_health(tmp_path)
    health.sentry.record_compile("f")
    health.mark_steady()
    health.sentry.record_compile("f")  # anomaly
    clock.advance(1.0)
    health.reconcile()
    assert telemetry.counters["steady_recompiles"] == 1
    assert "last_progress_age_ms" in telemetry.gauges
    # delta mirror: a second reconcile must not double-count
    health.reconcile()
    assert telemetry.counters["steady_recompiles"] == 1


def test_health_leak_hook_fires_once_and_is_convicted():
    pytest.importorskip("jax")
    injector = FaultInjector(spec="health_leak:drop:1")
    health, _e, _q, _t, clock = build_health(injector=injector)
    # pre-steady: the hook must NOT fire (rebase would absorb it)
    health.reconcile()
    assert health.accountant.snapshot()["leaked_buffers"] == 0
    health.mark_steady()
    health.reconcile()  # the armed rule fires exactly once
    snap = health.accountant.snapshot()
    assert snap["leaked_buffers"] == 1
    health.reconcile()
    assert health.accountant.snapshot()["leaked_buffers"] == 1
    assert injector.injected == {"health_leak": 1}


# ------------------------------------------------------------ SIGUSR2


def test_sigusr2_dump_registers_and_fires(tmp_path, monkeypatch):
    monkeypatch.setenv("EDL_HEALTH_DIR", str(tmp_path))
    target = install_sigusr2_dump()
    assert target and target.startswith(str(tmp_path))
    signal.raise_signal(signal.SIGUSR2)
    # faulthandler writes synchronously on delivery in the main thread
    with open(target) as f:
        text = f.read()
    assert "Thread" in text or "File" in text
    # re-registration is safe (entrypoints call unconditionally)
    install_sigusr2_dump()


# ----------------------------------------- end-to-end through a server


@pytest.mark.slow
def test_server_self_reports_health_end_to_end(tmp_path):
    """A real in-process GenerationServer with the plane on: compiles
    counted, ServerStatus carries the self-report, /metrics carries
    the per-fn recompile family, and an injected engine_step delay
    turns into a stalled self-report + bundle while server_status
    stays answerable."""
    np = pytest.importorskip("numpy")
    from elasticdl_tpu.common.model_utils import get_model_spec
    from elasticdl_tpu.observability.metrics import render_prometheus
    from elasticdl_tpu.observability.promparse import (
        parse_prometheus_text,
    )
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.serving.server import (
        GenerationServer,
        ServingConfig,
    )
    from elasticdl_tpu.training.trainer import Trainer

    import jax

    spec = get_model_spec("model_zoo",
                          "transformer_lm.transformer_lm.custom_model")
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        spec, mesh=mesh,
        model_params="vocab_size=32; seq_len=32; embed_dim=32; "
                     "num_heads=2; num_layers=1",
    )
    seq_len = int(trainer.model.seq_len)
    dummy = np.zeros((1, seq_len), np.int32)
    state = trainer.init_state(({"tokens": dummy}, dummy))
    injector = FaultInjector(
        spec="engine_step:delay:1:secs=30,skip=2"
    )
    server = GenerationServer(
        trainer, state,
        ServingConfig(
            num_slots=2, kv_block_size=4,
            runtime_health=True, stall_after_secs=0.5,
            health_dir=str(tmp_path), idle_wait_secs=0.01,
            handler_poll_secs=0.05,
        ),
        injector=injector,
    ).start(grpc_server=False)
    try:
        server.raw_servicer.generate(
            pb.GenerateRequest(prompt=[1, 2], max_new_tokens=3)
        )
        server.mark_steady()
        st = server.raw_servicer.server_status(
            pb.ServerStatusRequest()
        )
        assert st.health_state == "ok"
        assert st.jit_compiles >= 2  # prefill + paged step at least
        assert st.steady_recompiles == 0

        # the armed delay wedges the scheduler on this request's 3rd
        # tick; the watchdog (own thread) must flip to stalled and
        # the STATUS RPC must keep answering
        done = threading.Event()

        def wedged_request():
            try:
                server.raw_servicer.generate(
                    pb.GenerateRequest(prompt=[3, 4],
                                       max_new_tokens=16,
                                       deadline_ms=20000)
                )
            except Exception:  # noqa: BLE001 - expiry is fine here
                pass
            done.set()

        t = threading.Thread(target=wedged_request, daemon=True)
        t.start()

        deadline = time.monotonic() + 20.0
        st = None
        while time.monotonic() < deadline:
            st = server.raw_servicer.server_status(
                pb.ServerStatusRequest()
            )
            if st.health_state == "stalled":
                break
            time.sleep(0.1)
        assert st is not None and st.health_state == "stalled", (
            "watchdog never declared the injected stall"
        )
        assert st.last_progress_age_ms >= 500.0
        # the bundle landed
        paths = glob.glob(str(tmp_path / "health-bundle-*.json"))
        assert paths
        with open(paths[0]) as f:
            assert not validate_bundle(json.load(f))
        # the scrape surface carries the per-fn family
        text = render_prometheus(server._metrics_families())
        fams = parse_prometheus_text(text)
        assert "edl_serving_recompiles_total" in fams
        assert "edl_serving_stalls_total" in fams
    finally:
        # the scheduler is sleeping inside the injected delay; don't
        # wait for a graceful drain
        server.scheduler._stop_requested.set()
        server.queue.wake()
        if server.health is not None:
            server.health.stop()
        server.telemetry.close()


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))
