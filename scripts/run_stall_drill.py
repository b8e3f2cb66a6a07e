#!/usr/bin/env python
"""Runtime-health STALL drill: a wedged scheduler is self-reported,
bundled, and replaced in seconds — not the 30 s lease heuristic.

Runs the REAL stack: an in-process Router (real gRPC transport) whose
two-replica fleet is owned by the replica supervisor
(serving/autoscaler.py), spawning `elasticdl_tpu.serving.main`
subprocesses. The FIRST replica is armed with an `engine_step` delay
fault (common/fault_injection.py HEALTH_RPCS) injected through the
environment only that seat sees — after a few healthy decode ticks its
scheduler thread goes to sleep for 600 s mid-loop with work SEATED:
the exact silent-wedge failure mode the progress watchdog
(observability/runtime_health.py) exists to catch. Replacement seats
get a clean environment, so the drill converges.

What must then happen, and what the drill asserts:

  * DETECTION — the replica's own watchdog (its own thread; the gRPC
    status path, NOT the wedged scheduler) declares `stalled` within
    its `--stall_after_secs` budget and self-reports through
    ServerStatus -> ReplicaStatus `health_state` /
    `last_progress_age_ms`. Detection latency is measured from the
    stalling request's dispatch and must come in FAR under the 30 s
    `wedged_after_secs` lease heuristic (which stays at its
    conservative default here — the point is to beat it, not to tune
    it away). The router also drops the stalled replica from its
    dispatch rotation.

  * FLIGHT RECORDER — the ok->stalled transition atomically dumps a
    diagnostic bundle to $EDL_HEALTH_DIR: all-thread stacks
    (faulthandler — the sleeping scheduler is VISIBLE in them), the
    per-tick snapshot ring, the two-tier KV ledger, the memory
    accountant's view and the recompile counters. The drill loads it
    back and gates it through `validate_bundle` (schema, stacks
    present, non-empty ring).

  * REPLACEMENT — the supervisor's self-report path
    (`stalled_kill_after_secs`, seconds) kills and replaces the
    replica while its LEASE IS STILL VALID (the gRPC threads renew it
    happily — that is why lease decay alone needs 30 s of deliberate
    conservatism). Time from dispatch to SIGKILL must beat
    `wedged_after_secs`.

  * ZERO ACCEPTED-REQUEST LOSS — the fleet is TWO replicas (one
    armed, one clean), so every request wedged mid-decode on the
    stalled replica re-dispatches to its healthy sibling and
    completes OK while the replacement spawns; post-replacement
    traffic completes OK; every outcome is OK, never a raw transport
    code, never a shed, never a hang.

  * MEMORY ACCOUNTANT — `health_leak:drop:1` is armed on the clean
    sibling: once past its steady boundary its health thread leaks
    one 8 MiB device buffer the byte ledger cannot name, and the next
    reconcile must CONVICT it (ServerStatus
    `memory_unaccounted_bytes` >= the leak).

Timeline + outcomes archive at STALL_DRILL_REPORT.json (repo root).

Usage: python scripts/run_stall_drill.py
Exit 0 = every invariant holds."""

import glob
import json
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: the injected stall: after SKIP healthy decode ticks the scheduler
#: sleeps STALL_SECS mid-loop. SKIP outlives the replica's own warmup
#: (4 tokens = 3 decode ticks) so readiness is honest, and lands the
#: wedge inside the drill's long request.
STALL_SPEC = "engine_step:delay:1:secs=600,skip=5"
LEAK_SPEC = "health_leak:drop:1"
LEAK_BYTES = 8 << 20

STALL_AFTER_SECS = 2.0       # the replica watchdog's budget
STALLED_KILL_AFTER_SECS = 1.5  # supervisor's self-report kill budget
WEDGED_AFTER_SECS = 30.0     # the conservative lease heuristic, KEPT

DRILL_MODEL_PARAMS = (
    "vocab_size=32; seq_len=64; embed_dim=32; num_heads=2; "
    "num_layers=1"
)


def replica_args():
    return [
        "--model_zoo", os.path.join(REPO, "model_zoo"),
        "--model_def", "transformer_lm.transformer_lm.custom_model",
        "--model_params", DRILL_MODEL_PARAMS,
        "--port", "0", "--num_slots", "2", "--queue_capacity", "32",
        "--kv_block_size", "4", "--max_workers", "64",
        "--warmup_tokens", "4",
        "--runtime_health", "1",
        "--stall_after_secs", str(STALL_AFTER_SECS),
    ]


def wait_for(cond, timeout, what, poll=0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(poll)
    raise AssertionError("timed out after %.0fs waiting for %s"
                         % (timeout, what))


def main():
    import tempfile

    from elasticdl_tpu.observability.runtime_health import (
        validate_bundle,
    )
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import RouterStub, build_channel
    from elasticdl_tpu.serving.autoscaler import (
        AutoscalerConfig,
        ReplicaSupervisor,
        SubprocessReplicaLauncher,
    )
    from elasticdl_tpu.serving.router import Router, RouterConfig

    tmp_root = tempfile.mkdtemp(prefix="edl_stall_")
    journal_dir = os.path.join(tmp_root, "journal")
    health_dir = os.path.join(tmp_root, "health")
    os.makedirs(health_dir, exist_ok=True)

    base_env = dict(os.environ)
    base_env["JAX_PLATFORMS"] = "cpu"
    base_env["EDL_HEALTH_DIR"] = health_dir
    base_env.pop("PYTHONPATH", None)
    base_env.pop("EDL_FAULT_SPEC", None)

    class FaultPerSeatLauncher(SubprocessReplicaLauncher):
        """Seat 0 is born with the stall fault armed; seat 1 (the
        clean sibling that absorbs the re-dispatches) with the
        deliberate post-steady memory leak; later seats (the
        replacement) come up clean — a fleet-wide EDL_FAULT_SPEC
        would stall every replacement forever."""

        SEAT_SPECS = {0: STALL_SPEC, 1: LEAK_SPEC}

        def spawn(self, seat_id):
            env = dict(base_env)
            spec = self.SEAT_SPECS.get(seat_id)
            if spec:
                env["EDL_FAULT_SPEC"] = spec
            self.env = env
            return super().spawn(seat_id)

    launcher = FaultPerSeatLauncher(
        replica_args(), log_dir=os.path.join(tmp_root, "logs"),
        env=base_env, cwd=REPO,
    )
    router = Router([], RouterConfig(
        poll_secs=0.25, poll_timeout_secs=2.0, lease_secs=2.0,
        breaker_cooldown_secs=1.0, redispatch_window_secs=120.0,
        dispatch_timeout_secs=150.0, max_workers=96,
    )).start(grpc_server=True)
    sup = ReplicaSupervisor(router, launcher, AutoscalerConfig(
        min_replicas=2, max_replicas=2, decide_secs=0.25,
        ready_timeout_secs=300.0, drain_timeout_secs=60.0,
        wedged_after_secs=WEDGED_AFTER_SECS,
        stalled_kill_after_secs=STALLED_KILL_AFTER_SECS,
        max_restarts=3, journal_dir=journal_dir,
    ))
    router.set_autoscaler(sup)
    sup.start()
    stub = RouterStub(build_channel("localhost:%d" % router.port))

    outcomes = {}
    lock = threading.Lock()

    def call(tag, max_new, timeout=150.0):
        try:
            stub.router_generate(
                pb.GenerateRequest(prompt=[1, 2, 3],
                                   max_new_tokens=max_new),
                timeout=timeout,
            )
            code = "OK"
        except Exception as e:  # noqa: BLE001 - status is the datum
            code_fn = getattr(e, "code", None)
            code = (code_fn().name if callable(code_fn)
                    else type(e).__name__)
        with lock:
            outcomes[tag] = code

    def fleet():
        return stub.router_status(pb.RouterStatusRequest(),
                                  timeout=20)

    def replica_health():
        try:
            st = fleet()
        except Exception:  # noqa: BLE001 - transient starvation
            return None
        return {r.address: (r.health_state, r.last_progress_age_ms,
                            r.healthy)
                for r in st.replica}

    report = {"timeline": {}, "bounds": {
        "stall_after_secs": STALL_AFTER_SECS,
        "stalled_kill_after_secs": STALLED_KILL_AFTER_SECS,
        "wedged_after_secs": WEDGED_AFTER_SECS,
    }}
    t0 = time.monotonic()

    def stamp(name):
        report["timeline"][name] = round(time.monotonic() - t0, 2)
        print("[stall] %-22s t=%.2fs" % (name, time.monotonic() - t0))

    try:
        # ---- phase 0: both replicas (seat 0 armed with the stall,
        # seat 1 clean) come up and serve
        wait_for(
            lambda: (fleet().autoscaler.live >= 2
                     if _safe(fleet) else False),
            300, "both replicas live",
        )
        stamp("fleet_live")

        # ---- phase 1: a burst of long requests spreads across both
        # replicas (least-loaded + inflight tie-break); seat 0's
        # armed delay fires after skip=5 decode ticks (warmup burned
        # 3), wedging its scheduler with several requests SEATED
        long_reqs = []
        for i in range(6):
            t = threading.Thread(
                target=call, args=("long_%d" % i, 48), daemon=True
            )
            t.start()
            long_reqs.append(t)
        stamp("burst_dispatched")
        t_dispatch = time.monotonic()

        # ---- detection: the replica SELF-REPORTS stalled while its
        # lease stays healthy (the gRPC threads renew it)
        def stalled_rep():
            view = replica_health() or {}
            for addr, (state, age_ms, _healthy) in view.items():
                if state == "stalled":
                    return (addr, age_ms)
            return None

        addr, age_ms = wait_for(
            stalled_rep, WEDGED_AFTER_SECS,
            "the replica to self-report stalled",
        )
        t_detect = time.monotonic()
        stamp("stall_detected")
        detect_secs = t_detect - t_dispatch
        assert detect_secs < WEDGED_AFTER_SECS, (
            "detection took %.1fs — no faster than the lease "
            "heuristic" % detect_secs
        )
        print("[stall] %s self-reported stalled (age %.0fms) after "
              "%.1fs — lease still valid" % (addr, age_ms,
                                             detect_secs))
        # the stalled replica must be OUT of the dispatch rotation
        # while still registered
        view = replica_health()
        assert view and view[addr][2] is False, (
            "stalled replica still marked healthy in router_status"
        )

        # ---- replacement off the self-report, beating the 30 s path
        wait_for(
            lambda: (fleet().autoscaler.replacements >= 1
                     if _safe(fleet) else False),
            WEDGED_AFTER_SECS, "the stalled replica to be killed",
        )
        t_killed = time.monotonic()
        stamp("replica_killed")
        kill_secs = t_killed - t_dispatch
        assert kill_secs < WEDGED_AFTER_SECS, (
            "dispatch->kill took %.1fs; the self-report path must "
            "beat the %.0fs lease heuristic"
            % (kill_secs, WEDGED_AFTER_SECS)
        )
        wait_for(
            lambda: (fleet().autoscaler.live >= 2
                     if _safe(fleet) else False),
            300, "the replacement to go live",
        )
        stamp("replacement_live")

        # ---- the bundle the stalled replica left behind
        def bundle_path():
            paths = glob.glob(
                os.path.join(health_dir, "health-bundle-*.json")
            )
            return paths[0] if paths else None

        path = wait_for(bundle_path, 30, "the diagnostic bundle")
        with open(path) as f:
            bundle = json.load(f)
        problems = validate_bundle(bundle)
        assert not problems, "bundle schema: %s" % problems
        assert bundle["reason"] == "progress_stall"
        assert bundle["ring"], "flight-recorder ring is empty"
        assert "serving-scheduler" in json.dumps(
            bundle["stacks"]
        ) or bundle["stacks"]["faulthandler"], (
            "the wedged scheduler thread is not visible in the stacks"
        )
        report["bundle"] = {
            "path": path,
            "ring_ticks": len(bundle["ring"]),
            "recompiles": bundle["recompiles"]["total_compiles"],
            "kv_blocks_total":
                bundle["kv_ledger"].get("kv_blocks_total"),
        }
        stamp("bundle_validated")
        print("[stall] bundle OK: %d ring ticks, stacks present"
              % len(bundle["ring"]))

        # ---- zero accepted-request loss: the requests wedged on
        # the stalled replica re-dispatch to the healthy sibling and
        # complete; post-replacement traffic completes
        for i in range(3):
            call("post_%d" % i, 8)
        for t in long_reqs:
            t.join(timeout=150)
        assert not any(t.is_alive() for t in long_reqs), (
            "a wedged request HUNG: %r" % outcomes
        )
        assert set(outcomes.values()) == {"OK"}, (
            "accepted-request loss: %r" % outcomes
        )
        stamp("traffic_verified")

        # ---- phase 2: the replacement's armed health_leak fires on
        # its health thread (post-steady); reconciliation must
        # convict ~8 MiB of unaccounted device bytes
        def unaccounted():
            # the replica ServerStatus carries it; read through the
            # roster's addresses directly
            try:
                st = fleet()
            except Exception:  # noqa: BLE001
                return 0
            return max(
                (_replica_unaccounted(r.address) for r in st.replica),
                default=0,
            )

        def _replica_unaccounted(address):
            from elasticdl_tpu.proto.service import (
                ServingStub,
                build_channel as bc,
            )

            try:
                s = ServingStub(bc(address)).server_status(
                    pb.ServerStatusRequest(), timeout=5
                )
                return int(s.memory_unaccounted_bytes)
            except Exception:  # noqa: BLE001
                return 0

        leaked = wait_for(
            lambda: (unaccounted()
                     if unaccounted() >= LEAK_BYTES // 2 else None),
            60, "the memory accountant to convict the leak",
        )
        report["leak_convicted_bytes"] = int(leaked)
        stamp("leak_convicted")
        print("[stall] accountant convicted %d unaccounted bytes "
              "(leak was %d)" % (leaked, LEAK_BYTES))

        report["outcomes"] = dict(outcomes)
        report["detect_secs"] = round(detect_secs, 2)
        report["kill_secs"] = round(kill_secs, 2)
        report["beats_lease_heuristic_by_secs"] = round(
            WEDGED_AFTER_SECS - kill_secs, 2
        )
        report["pass"] = True
        out = os.path.join(REPO, "STALL_DRILL_REPORT.json")
        with open(out, "w") as f:
            json.dump(report, f, indent=1)
        print("[stall] PASS — detect %.1fs, kill %.1fs (lease "
              "heuristic: %.0fs); report -> %s"
              % (detect_secs, kill_secs, WEDGED_AFTER_SECS, out))
        return 0
    finally:
        sup.stop(grace=20.0)
        router.stop()


def _safe(fn):
    try:
        fn()
        return True
    except Exception:  # noqa: BLE001 - transient starvation
        return False


if __name__ == "__main__":
    sys.exit(main())
