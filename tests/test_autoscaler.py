"""Replica supervisor/autoscaler unit tests (tier-1: no jax, no
sockets, no real processes — a fake launcher + fake replica stubs
drive serving/autoscaler.py against a real Router).

Locks the ISSUE's elasticity semantics: spawn-to-min + adoption,
sustained-pressure scale-up with hysteresis/cooldown (flapping
structurally impossible), drain-based scale-down that closes the
retired replica's channel, crash replacement with full-jitter backoff
and the max-restarts circuit, wedged-replica (lease-decay) kill and
replace, supervisor crash-recovery from the journal (re-adopt, no
double-spawn, no orphan — including mid-scale-up), and the
SUPERVISOR_RPCS fault-injection boundary."""

import random

import pytest

from elasticdl_tpu.common.fault_injection import FaultInjector
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.serving.autoscaler import (
    DRAINING,
    LIVE,
    STARTING,
    AutoscalerConfig,
    ReplicaSupervisor,
)
from elasticdl_tpu.serving.router import Router, RouterConfig


class FakeClock(object):
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class FakeReplicaStub(object):
    """ServingStub-shaped fake: scripted status + a close() recorder
    (the retire path must close the channel exactly once)."""

    def __init__(self):
        self.poll_ok = True
        self.draining = False
        self.queue_depth = 0
        self.active_slots = 0
        self.kv_blocks_free = 8
        self.kv_blocks_cached = 0
        self.queue_wait_ms = 0.0
        # runtime-health self-report: "" = a pre-health replica (the
        # lease-decay fallback's whole constituency)
        self.health_state = ""
        self.last_progress_age_ms = 0.0
        self.closed = 0

    def server_status(self, request, timeout=None):
        if not self.poll_ok:
            raise RuntimeError("poll down")
        return pb.ServerStatusResponse(
            queue_depth=self.queue_depth,
            active_slots=self.active_slots,
            kv_blocks_free=self.kv_blocks_free,
            kv_blocks_cached=self.kv_blocks_cached,
            queue_wait_ms=self.queue_wait_ms,
            draining=self.draining,
            health_state=self.health_state,
            last_progress_age_ms=self.last_progress_age_ms,
        )

    def close(self):
        self.closed += 1


class FakeHandle(object):
    """A fake replica process: the test scripts readiness and death."""

    def __init__(self, pid, seat_id, launcher):
        self.pid = pid
        self.seat_id = seat_id
        self.launcher = launcher
        self.rc = None
        self.address = None
        self.log_path = "log-%d" % seat_id
        self.terminated = False
        self.killed = False
        # emulate a fast graceful drain by default; drain tests flip
        # this off to hold the seat mid-drain
        self.exit_on_terminate = True

    def poll(self):
        return self.rc

    def ready(self):
        return self.address

    def terminate(self):
        self.terminated = True
        if self.exit_on_terminate and self.rc is None:
            self.rc = 0

    def kill(self):
        self.killed = True
        if self.rc is None:
            self.rc = -9


class FakeLauncher(object):
    def __init__(self, stubs):
        self.stubs = stubs  # address -> FakeReplicaStub (router view)
        self.spawned = []
        self.auto_ready = True
        self._pid = 4000

    def make_ready(self, handle):
        address = "rep%d" % handle.pid
        self.stubs[address] = FakeReplicaStub()
        handle.address = address
        return address

    def spawn(self, seat_id):
        self._pid += 1
        handle = FakeHandle(self._pid, seat_id, self)
        if self.auto_ready:
            self.make_ready(handle)
        self.spawned.append(handle)
        return handle

    def attach(self, seat_id, pid, log_path):
        # "the process is still running": hand back the same handle a
        # previous supervisor spawned, like a pid re-attach would
        for handle in self.spawned:
            if handle.pid == pid:
                return handle
        dead = FakeHandle(pid, seat_id, self)
        dead.rc = 1
        return dead


def build(journal_dir="", injector=None, lease_secs=1000.0, **cfg_kw):
    clock = FakeClock()
    stubs = {}
    launcher = FakeLauncher(stubs)
    kw = dict(
        min_replicas=1, max_replicas=3, decide_secs=0.1,
        up_queue_wait_ms=100.0, up_queue_depth=4, up_window_secs=1.0,
        idle_queue_wait_ms=20.0, down_window_secs=2.0,
        down_free_kv_blocks=1, cooldown_secs=3.0,
        ready_timeout_secs=30.0, drain_timeout_secs=10.0,
        wedged_after_secs=2.0, max_restarts=3,
        base_delay_secs=0.1, max_delay_secs=1.0,
        journal_dir=journal_dir,
    )
    kw.update(cfg_kw)
    router = Router(
        [], config=RouterConfig(lease_secs=lease_secs),
        stub_factory=lambda a: stubs[a], clock=clock,
        sleep=lambda s: None,
    )
    sup = ReplicaSupervisor(
        router, launcher, AutoscalerConfig(**kw), clock=clock,
        injector=injector, rng=random.Random(0),
    )
    router.set_autoscaler(sup)
    return sup, router, launcher, clock


def settle(sup, router, ticks=4):
    """A few decide ticks with heartbeats in between: enough for
    spawn -> adopt -> signals to flow."""
    for _ in range(ticks):
        sup.decide_once()
        router.poll_once()


def live_addresses(sup):
    return [s["address"] for s in sup.roster() if s["state"] == LIVE]


# -------------------------------------------------------- spawn/adopt


def test_spawns_to_min_and_adopts():
    sup, router, launcher, _ = build()
    sup.decide_once()  # reconcile: deficit -> spawn
    assert [s["state"] for s in sup.roster()] == [STARTING]
    sup.decide_once()  # poll: ready -> adopt + register with router
    assert [s["state"] for s in sup.roster()] == [LIVE]
    addrs = [r.address for r in router.replicas()]
    assert addrs == live_addresses(sup)
    st = sup.status_block()
    assert st.enabled and st.target == 1 and st.live == 1
    assert len(launcher.spawned) == 1


def test_status_block_rides_router_status():
    sup, router, _launcher, _ = build()
    settle(sup, router)
    resp = router.status_response()
    assert resp.HasField("autoscaler")
    assert resp.autoscaler.enabled and resp.autoscaler.live == 1
    wire = pb.RouterStatusResponse.FromString(resp.SerializeToString())
    assert wire.autoscaler.target == 1
    # a static-fleet router has no autoscaler block at all
    bare = Router([], stub_factory=lambda a: None)
    assert not bare.status_response().HasField("autoscaler")


# ------------------------------------------------------------ scale up


def _pressure(launcher, router, on=True):
    for stub in launcher.stubs.values():
        # real pressure = high measured waits AND work present (a
        # frozen EWMA over an empty queue is history, not pressure)
        stub.queue_wait_ms = 500.0 if on else 0.0
        stub.queue_depth = 1 if on else 0
    router.poll_once()


def test_scale_up_needs_a_sustained_window():
    sup, router, launcher, clock = build()
    settle(sup, router)
    _pressure(launcher, router)
    sup.decide_once()  # pressure seen; window starts
    assert sup.target == 1
    # pressure breaks before the window elapses: no decision, and the
    # window must RESTART (hysteresis, not accumulation)
    _pressure(launcher, router, on=False)
    sup.decide_once()
    clock.advance(2.0)
    _pressure(launcher, router)
    sup.decide_once()  # window restarts now
    assert sup.target == 1
    clock.advance(1.1)
    _pressure(launcher, router)
    sup.decide_once()
    assert sup.target == 2 and sup.scale_ups == 1
    assert sup.last_decision == "scale_up"


def test_scale_up_cooldown_and_max_bound():
    sup, router, launcher, clock = build()
    settle(sup, router)
    _pressure(launcher, router)
    sup.decide_once()  # window opens
    clock.advance(1.1)
    _pressure(launcher, router)
    sup.decide_once()
    assert sup.target == 2
    settle(sup, router)  # second replica spawns + adopts
    assert sup.status_block().live == 2
    # pressure persists, but the cooldown holds the next decision
    _pressure(launcher, router)
    clock.advance(1.2)
    _pressure(launcher, router)
    sup.decide_once()
    assert sup.target == 2
    # cooldown elapses: third replica; then the max bound caps it
    clock.advance(3.0)
    _pressure(launcher, router)
    sup.decide_once()
    clock.advance(1.1)
    _pressure(launcher, router)
    sup.decide_once()
    assert sup.target == 3
    settle(sup, router)
    for _ in range(3):
        clock.advance(5.0)
        _pressure(launcher, router)
        sup.decide_once()
    assert sup.target == 3  # max_replicas is a hard ceiling


def test_no_decision_while_fleet_unsettled():
    """A scale decision while a spawn is still starting would be
    acting blind: the settled-fleet gate blocks it."""
    sup, router, launcher, clock = build()
    launcher.auto_ready = False
    settle(sup, router)
    assert [s["state"] for s in sup.roster()] == [STARTING]
    _pressure(launcher, router)
    clock.advance(5.0)
    sup.decide_once()
    assert sup.target == 1 and sup.scale_ups == 0


# ---------------------------------------------------------- scale down


def test_scale_down_drains_gracefully_and_closes_channel():
    sup, router, launcher, clock = build(min_replicas=1)
    sup.target = 2
    settle(sup, router, ticks=6)
    assert sup.status_block().live == 2
    # on an idle tie the NEWEST seat drains (load tie-break); hold it
    # mid-drain so the DRAINING state is observable
    roster = sup.roster()
    victim_addr = roster[1]["address"]
    victim_handle = launcher.spawned[1]
    victim_handle.exit_on_terminate = False
    router.poll_once()
    sup.decide_once()  # idle window starts
    clock.advance(2.1)
    router.poll_once()
    sup.decide_once()  # sustained idle -> target 1, drain begins
    assert sup.target == 1 and sup.scale_downs == 1
    assert victim_handle.terminated and not victim_handle.killed
    roster = {s["seat"]: s for s in sup.roster()}
    assert roster[1]["state"] == DRAINING
    # still registered (its in-flight streams finish through the
    # router's drain advertisement), channel still open
    assert victim_addr in [r.address for r in router.replicas()]
    assert launcher.stubs[victim_addr].closed == 0
    # the replica finishes draining and exits 0 -> retire: channel
    # closed, registry entry gone
    victim_handle.rc = 0
    sup.decide_once()
    assert victim_addr not in [r.address for r in router.replicas()]
    assert launcher.stubs[victim_addr].closed == 1
    assert sup.status_block().live == 1


def test_scale_down_after_burst_with_stale_ewma():
    """After a burst stops DEAD, the queue-wait EWMA freezes at its
    last (high) value — no samples flow to decay it. Zero routed
    traffic across the idle window must satisfy the gate anyway, or a
    post-burst fleet could never scale down."""
    sup, router, launcher, clock = build()
    sup.target = 2
    settle(sup, router, ticks=6)
    for stub in launcher.stubs.values():
        stub.queue_wait_ms = 5000.0  # the burst's frozen EWMA
    router.poll_once()
    sup.decide_once()  # quiet tick: routed baseline recorded
    sup.decide_once()  # routed unchanged -> idle window opens
    clock.advance(2.1)
    router.poll_once()
    sup.decide_once()
    assert sup.target == 1 and sup.scale_downs == 1


def test_scale_down_requires_kv_headroom():
    sup, router, launcher, clock = build(down_free_kv_blocks=100)
    sup.target = 2
    settle(sup, router, ticks=6)
    # idle, but the fleet has no free-KV headroom: hold the capacity
    for stub in launcher.stubs.values():
        stub.kv_blocks_free = 10  # sum 20 < 100
    router.poll_once()
    sup.decide_once()
    clock.advance(3.0)
    router.poll_once()
    sup.decide_once()
    assert sup.target == 2 and sup.scale_downs == 0
    # reclaimable cached blocks ARE headroom: with prefix sharing on,
    # a drained fleet parks everything in the refcount-0 cache and
    # kv_blocks_free alone reads zero forever
    for stub in launcher.stubs.values():
        stub.kv_blocks_free = 0
        stub.kv_blocks_cached = 60  # sum 120 >= 100
    router.poll_once()
    sup.decide_once()  # idle window opens now that the gate passes
    clock.advance(2.1)
    router.poll_once()
    sup.decide_once()
    assert sup.target == 1 and sup.scale_downs == 1


def test_drain_timeout_escalates_to_kill():
    sup, router, launcher, clock = build()
    sup.target = 2
    settle(sup, router, ticks=6)
    victim_handle = launcher.spawned[1]
    victim_handle.exit_on_terminate = False
    router.poll_once()
    sup.decide_once()
    clock.advance(2.1)
    router.poll_once()
    sup.decide_once()  # drain begins
    assert victim_handle.terminated
    clock.advance(10.1)  # drain_timeout_secs
    sup.decide_once()
    assert victim_handle.killed
    sup.decide_once()  # the kill's exit retires the seat
    assert sup.status_block().live == 1


# -------------------------------------------------- crash replacement


def test_crashed_replica_is_replaced():
    sup, router, launcher, _clock = build()
    settle(sup, router)
    dead_addr = live_addresses(sup)[0]
    launcher.spawned[0].rc = -9  # SIGKILLed from outside
    sup.decide_once()  # reap + respawn in one tick
    assert sup.replacements == 1
    assert dead_addr not in [r.address for r in router.replicas()]
    settle(sup, router)
    assert sup.status_block().live == 1
    assert len(launcher.spawned) == 2


def test_spawn_failures_back_off_then_open_the_circuit():
    sup, router, launcher, clock = build()
    launcher.auto_ready = False

    def fail_current_spawn():
        launcher.spawned[-1].rc = 1  # dies before ready

    sup.decide_once()  # spawn 1
    fail_current_spawn()
    sup.decide_once()  # reap: failure 1, backoff armed
    assert sup.spawn_failures == 1
    spawns = len(launcher.spawned)
    sup.decide_once()  # inside the backoff window: no spawn
    assert len(launcher.spawned) == spawns
    clock.advance(1.1)  # past max_delay_secs
    sup.decide_once()  # spawn 2
    assert len(launcher.spawned) == spawns + 1
    fail_current_spawn()
    sup.decide_once()  # failure 2
    clock.advance(1.1)
    sup.decide_once()  # spawn 3
    fail_current_spawn()
    sup.decide_once()  # failure 3 == max_restarts -> circuit OPEN
    assert sup.circuit_open
    assert sup.last_decision == "circuit_open"
    spawns = len(launcher.spawned)
    for _ in range(5):
        clock.advance(5.0)
        sup.decide_once()
    assert len(launcher.spawned) == spawns  # no hot respawn loop
    assert sup.status_block().circuit_open


def test_successful_adoption_resets_the_failure_streak():
    sup, router, launcher, clock = build()
    launcher.auto_ready = False
    sup.decide_once()
    launcher.spawned[-1].rc = 1
    sup.decide_once()
    clock.advance(1.1)
    sup.decide_once()  # respawn
    launcher.make_ready(launcher.spawned[-1])
    sup.decide_once()  # adopt
    assert sup.status_block().live == 1
    assert sup._consec_failures == 0


def test_wedged_replica_is_killed_and_replaced():
    """LEASE-DECAY FALLBACK path (pre-health replicas: the stub's
    health_state is ""): a SIGSTOPped/hung replica never exits, but
    its lease decays — the supervisor must kill and replace it on the
    conservative wedged_after_secs window."""
    sup, router, launcher, clock = build(lease_secs=5.0)
    settle(sup, router)
    wedged = launcher.spawned[0]
    assert launcher.stubs[wedged.address].health_state == ""
    launcher.stubs[wedged.address].poll_ok = False
    clock.advance(6.0)  # lease decays un-renewed
    router.poll_once()
    sup.decide_once()  # unhealthy window starts
    assert not wedged.killed
    clock.advance(2.1)  # wedged_after_secs
    sup.decide_once()
    assert wedged.killed
    sup.decide_once()  # the kill's exit -> reap + respawn
    assert sup.replacements == 1
    settle(sup, router)
    assert sup.status_block().live == 1


def test_self_reported_stall_beats_the_lease_heuristic():
    """SELF-REPORT path (runtime health plane): a replica whose
    watchdog says `stalled` keeps renewing its lease (the gRPC
    threads are fine — only the scheduler is wedged), so the lease
    path would need wedged_after_secs of silence that never comes.
    The supervisor must kill it on the seconds-scale
    stalled_kill_after_secs budget instead, while the lease stays
    VALID the whole way."""
    sup, router, launcher, clock = build(
        wedged_after_secs=30.0, stalled_kill_after_secs=1.0,
    )
    settle(sup, router)
    wedged = launcher.spawned[0]
    stub = launcher.stubs[wedged.address]
    stub.health_state = "stalled"
    stub.last_progress_age_ms = 4000.0
    router.poll_once()
    # the stalled replica leaves the dispatch rotation immediately
    # (still registered, lease still valid)
    rep = {r.address: r for r in router.replicas()}[wedged.address]
    assert rep.lease_ok(clock()) and not rep.in_rotation(clock())
    assert rep.health_state == "stalled"
    sup.decide_once()  # stalled window opens
    assert not wedged.killed
    clock.advance(1.1)  # stalled_kill_after_secs — NOT 30 s
    router.poll_once()
    sup.decide_once()
    assert wedged.killed
    sup.decide_once()
    assert sup.replacements == 1
    settle(sup, router)
    assert sup.status_block().live == 1


def test_stall_self_report_recovery_cancels_the_kill():
    """A stall that RECOVERS (tokens flow again — e.g. a pathological
    but finite compile) before the kill budget elapses must reset the
    window: transient pain is not grounds for execution."""
    sup, router, launcher, clock = build(stalled_kill_after_secs=2.0)
    settle(sup, router)
    seat = launcher.spawned[0]
    stub = launcher.stubs[seat.address]
    stub.health_state = "stalled"
    router.poll_once()
    sup.decide_once()  # window opens
    clock.advance(1.0)
    stub.health_state = "ok"  # recovered
    router.poll_once()
    sup.decide_once()  # window must reset
    clock.advance(5.0)
    router.poll_once()
    sup.decide_once()
    assert not seat.killed
    assert sup.replacements == 0
    # a replica back to "ok" rejoins the rotation
    rep = {r.address: r for r in router.replicas()}[seat.address]
    assert rep.in_rotation(clock())


# ------------------------------------------------------ fault injection


def test_spawn_fail_injection_backs_off_and_recovers():
    injector = FaultInjector(spec="supervisor_spawn:drop:1")
    sup, router, launcher, clock = build(injector=injector)
    sup.decide_once()  # injected spawn failure
    assert sup.spawn_failures == 1 and not launcher.spawned
    clock.advance(1.1)
    settle(sup, router)
    assert sup.status_block().live == 1
    assert injector.injected == {"supervisor_spawn": 1}


def test_adopt_drop_injection_reaps_and_respawns():
    injector = FaultInjector(spec="supervisor_adopt:drop:1")
    sup, router, launcher, clock = build(injector=injector)
    sup.decide_once()  # spawn
    sup.decide_once()  # ready, but the adoption is dropped
    assert sup.spawn_failures == 1
    assert launcher.spawned[0].killed
    assert not router.replicas()
    clock.advance(1.1)
    settle(sup, router)
    assert sup.status_block().live == 1
    assert len(launcher.spawned) == 2


def test_slow_ready_injection_delays_adoption_only():
    injector = FaultInjector(spec="supervisor_ready:delay:1:secs=0.01")
    sup, router, _launcher, _ = build(injector=injector)
    settle(sup, router)
    assert sup.status_block().live == 1
    assert injector.injected == {"supervisor_ready": 1}


# ------------------------------------------------------ crash recovery


def test_supervisor_crash_recovery_readopts_live_fleet(tmp_path):
    journal = str(tmp_path / "fleet")
    sup, router, launcher, clock = build(
        journal_dir=journal, min_replicas=2,
    )
    settle(sup, router, ticks=6)
    pids = sorted(s["pid"] for s in sup.roster())
    assert sup.status_block().live == 2
    sup.abandon()  # process death: journal + replicas left as-is

    sup2 = ReplicaSupervisor(
        router, launcher,
        AutoscalerConfig(min_replicas=2, max_replicas=3,
                         journal_dir=journal),
        clock=clock, rng=random.Random(1),
    )
    assert sorted(s["pid"] for s in sup2.roster()) == pids
    assert sup2.supervisor_restarts == 1
    spawned_before = len(launcher.spawned)
    settle(sup2, router, ticks=4)
    # re-adopted, never re-spawned: same pids, no new processes
    assert len(launcher.spawned) == spawned_before
    assert sorted(s["pid"] for s in sup2.roster()) == pids
    assert sup2.status_block().live == 2
    assert sup2.status_block().supervisor_restarts == 1


def test_recovery_mid_scale_up_finishes_the_spawn_without_doubling(
        tmp_path):
    """Killed between launch and adoption: the new supervisor must
    attach to the half-started process and adopt it when it becomes
    ready — not spawn a second one."""
    journal = str(tmp_path / "fleet")
    sup, router, launcher, clock = build(
        journal_dir=journal, min_replicas=2,
    )
    launcher.auto_ready = False
    sup.decide_once()
    sup.decide_once()  # two seats launched, neither ready yet
    assert [s["state"] for s in sup.roster()] == [STARTING, STARTING]
    sup.abandon()

    sup2 = ReplicaSupervisor(
        router, launcher,
        AutoscalerConfig(min_replicas=2, max_replicas=3,
                         journal_dir=journal),
        clock=clock, rng=random.Random(1),
    )
    assert [s["state"] for s in sup2.roster()] == [STARTING, STARTING]
    for _ in range(3):
        sup2.decide_once()
    assert len(launcher.spawned) == 2  # no double-spawn
    # the half-started replicas become ready under the NEW supervisor
    for handle in launcher.spawned:
        launcher.make_ready(handle)
    settle(sup2, router)
    assert sup2.status_block().live == 2
    assert sorted(s["pid"] for s in sup2.roster()) == sorted(
        h.pid for h in launcher.spawned
    )


def test_recovery_reaps_dead_seats_and_respawns(tmp_path):
    journal = str(tmp_path / "fleet")
    sup, router, launcher, clock = build(
        journal_dir=journal, min_replicas=2,
    )
    settle(sup, router, ticks=6)
    dead = launcher.spawned[0]
    dead_addr = dead.address
    sup.abandon()
    dead.rc = -9  # dies during the supervisor outage

    sup2 = ReplicaSupervisor(
        router, launcher,
        AutoscalerConfig(min_replicas=2, max_replicas=3,
                         journal_dir=journal),
        clock=clock, rng=random.Random(1),
    )
    # only the survivor is re-adopted; the dead seat was reaped
    assert [s["pid"] for s in sup2.roster()] == [
        launcher.spawned[1].pid
    ]
    settle(sup2, router, ticks=6)
    assert sup2.status_block().live == 2
    assert len(launcher.spawned) == 3  # exactly one respawn
    assert dead_addr not in [r.address for r in router.replicas()]


def test_stop_terminates_and_retires_the_fleet(tmp_path):
    journal = str(tmp_path / "fleet")
    sup, router, launcher, _clock = build(
        journal_dir=journal, min_replicas=2,
    )
    settle(sup, router, ticks=6)
    sup.stop(grace=1.0)
    assert sup.roster() == []
    assert not router.replicas()
    assert all(h.terminated for h in launcher.spawned)
    # a successor sees an empty roster, not ghosts
    sup2 = ReplicaSupervisor(
        router, launcher,
        AutoscalerConfig(min_replicas=2, journal_dir=journal),
    )
    assert sup2.roster() == []


def test_recovery_replays_decision_counters(tmp_path):
    """Scale decisions and replacements made BEFORE the crash survive
    it: the journal's target/reap events recount them on replay, so a
    recovered supervisor reports the roster's history, not just what
    happened since the last snapshot."""
    journal = str(tmp_path / "fleet")
    sup, router, launcher, clock = build(journal_dir=journal)
    settle(sup, router)
    _pressure(launcher, router)
    sup.decide_once()
    clock.advance(1.1)
    _pressure(launcher, router)
    sup.decide_once()
    assert sup.scale_ups == 1
    settle(sup, router)
    launcher.spawned[0].rc = -9
    sup.decide_once()  # reap + replace
    assert sup.replacements == 1
    settle(sup, router)
    sup.abandon()

    sup2 = ReplicaSupervisor(
        router, launcher,
        AutoscalerConfig(min_replicas=1, max_replicas=3,
                         journal_dir=journal),
        clock=clock, rng=random.Random(1),
    )
    st = sup2.status_block()
    assert st.scale_ups == 1 and st.replacements == 1


def test_journal_is_wal_compacted(tmp_path):
    """Snapshot compaction keeps replay bounded without losing the
    roster (snapshot_every=3 forces compactions in a short run)."""
    journal = str(tmp_path / "fleet")
    sup, router, launcher, clock = build(
        journal_dir=journal, min_replicas=2, snapshot_every=3,
    )
    settle(sup, router, ticks=6)
    assert sup._store.compactions >= 1
    sup.abandon()
    sup2 = ReplicaSupervisor(
        router, launcher,
        AutoscalerConfig(min_replicas=2, journal_dir=journal,
                         snapshot_every=3),
        clock=clock,
    )
    assert sup2.status_block().live == 2


if __name__ == "__main__":
    raise SystemExit(pytest.main([__file__, "-q"]))


# ------------------------------------------- the autoscale drill's load


@pytest.mark.parametrize("spec", [
    "", "8", "8:", ":0.5", "0:1", "8:0", "-2:1", "8:0.5,", "a:b",
])
def test_parse_ramp_refuses_what_is_not_rate_colon_seconds(spec):
    from scripts.run_autoscale_drill import parse_ramp

    with pytest.raises(ValueError):
        parse_ramp(spec)


def test_ramp_arrivals_respect_phase_boundaries_and_rates():
    """scripts/run_autoscale_drill.py's load: 'r1:t1,r2:t2' parses to
    (rate, seconds) phases, and the seeded piecewise-Poisson plan puts
    every arrival inside its own phase's span, in time order, at about
    the phase's rate — the same plan for the same seed."""
    import numpy as np

    from scripts.run_autoscale_drill import parse_ramp, ramp_arrivals

    phases = parse_ramp("40:5, 4:10,80:2.5")
    assert phases == [(40.0, 5.0), (4.0, 10.0), (80.0, 2.5)]
    plan = ramp_arrivals(phases, np.random.RandomState(7))
    assert plan == ramp_arrivals(phases, np.random.RandomState(7))
    times = [t for t, _ in plan]
    assert times == sorted(times)
    starts = [0.0, 5.0, 15.0, 17.5]
    counts = [0, 0, 0]
    for t, idx in plan:
        assert starts[idx] <= t < starts[idx + 1], (t, idx)
        counts[idx] += 1
    # Poisson counts: 200, 40, 200 expected; five sigma either way
    for n, (rate, secs) in zip(counts, phases):
        mean = rate * secs
        assert abs(n - mean) < 5 * mean ** 0.5, (n, mean)

