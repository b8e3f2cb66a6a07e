#!/usr/bin/env python
"""Multi-replica ROUTER chaos drill: zero accepted-request loss under
replica churn.

Runs the real stack as subprocesses — three `elasticdl_tpu.serving.main`
replicas behind one `elasticdl_tpu.serving.router_main` router — fires
an open-loop Poisson stream of unary generates at the ROUTER, and while
the load is live:

  * SIGSTOPs one replica, bursts requests so the router provably has
    dispatches in flight on it (the in-flight component of the load
    score spreads a burst across all replicas), then SIGKILLs it — the
    stalled dispatches die UNAVAILABLE and MUST be re-dispatched to a
    surviving replica before anything reaches the client;
  * drops a fresh checkpoint into a second replica's --checkpoint_dir
    (the hot-reload path: the replica advertises `draining` across the
    swap and keeps its streams).

The asserted invariant is the router's contract: every request the
router ACCEPTED terminates with OK or an EXPLICIT status
(RESOURCE_EXHAUSTED shed / DEADLINE_EXCEEDED) — never a raw transport
error (UNAVAILABLE/CANCELLED), never a hang. A majority must complete
OK (two replicas survive), at least one request must have been
RE-DISPATCHED (proof the chaos path actually ran), the SIGKILL'd
replica must leave the rotation, and the reloaded replica must report
the new version.

The drill also runs TRACED (EDL_TRACE_DIR): after the graceful
teardown it merges every process's span export
(observability/dump.merge_dir) and asserts the CAUSAL story
structurally, not just by counters — every accepted request's trace
reaches a terminal root span with an explicit status; at least one
trace contains a failed dispatch span targeting the killed replica
with a successful SIBLING dispatch next to it (the re-dispatch, as
causality, not as a counter); and at least one replica `serve` span
parents under a router dispatch span (the cross-process merge
actually merged). The merged Chrome-trace JSON is archived at
ROUTER_CHAOS_TRACE.json (repo root) — open it at ui.perfetto.dev.

A second ROUTER-KILL phase then moves the chaos one tier up: three
replicas behind TWO router cells sharing a registry journal
(--cells / --cell_journal_dir), a CellFront dispatching shared-prefix
load pinned by fingerprint to one owning cell, SIGKILL of that cell
mid-load — every accepted request must reroute through the surviving
cell with zero loss, and the killed cell must restart replica-flag-
free and rebuild its whole fleet view from journal replay.

Usage: python scripts/run_router_chaos_drill.py
Exit 0 = the invariant holds in both phases."""

import os
import signal
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from run_server_kill_drill import MODEL_PARAMS, launch_ready  # noqa: E402

NUM_REPLICAS = 3
REQUESTS = 24
RATE_RPS = 10.0
MAX_NEW = 16
CLIENT_TIMEOUT = 120.0  # backstop; the drill asserts we stay far under
WARMUP_REQS = 6  # Poisson-paced requests before the chaos window
BURST_REQS = 6  # back-to-back burst fired at the SIGSTOPped victim
RELOAD_AFTER = 14  # save the hot-reload checkpoint after this many


def start_replica(ckpt_dir=None, extra_env=None):
    cmd = [
        sys.executable, "-m", "elasticdl_tpu.serving.main",
        "--model_zoo", os.path.join(REPO, "model_zoo"),
        "--model_def", "transformer_lm.transformer_lm.custom_model",
        "--model_params", MODEL_PARAMS,
        "--port", "0", "--num_slots", "2", "--queue_capacity", "16",
    ]
    if ckpt_dir:
        cmd += ["--checkpoint_dir", ckpt_dir,
                "--reload_poll_secs", "0.3"]
    return launch_ready(cmd, extra_env=extra_env)


def start_router(replica_ports, extra_env=None):
    cmd = [
        sys.executable, "-m", "elasticdl_tpu.serving.router_main",
        "--port", "0", "--poll_secs", "0.25", "--lease_secs", "1.5",
        "--breaker_cooldown_secs", "1.0",
        "--redispatch_window_secs", "60",
    ]
    for p in replica_ports:
        cmd += ["--replica", "localhost:%d" % p]
    return launch_ready(cmd, extra_env=extra_env,
                        ready_marker="ROUTER_READY")


def start_router_cell(replica_ports, cell_id, cells, journal_dir):
    """One router CELL: a full router process that shares its replica
    registry with its siblings through the write-ahead journal in
    `journal_dir`. Launched with an explicit --cell_id (no supervisor)
    so the drill controls each cell's lifetime directly."""
    cmd = [
        sys.executable, "-m", "elasticdl_tpu.serving.router_main",
        "--port", "0", "--poll_secs", "0.25", "--lease_secs", "1.5",
        "--breaker_cooldown_secs", "1.0",
        "--redispatch_window_secs", "60",
        "--cell_id", str(cell_id), "--cells", str(cells),
        "--cell_journal_dir", journal_dir,
    ]
    for p in replica_ports:
        cmd += ["--replica", "localhost:%d" % p]
    return launch_ready(cmd, ready_marker="ROUTER_READY")


def build_checkpoint_state():
    """Trainer state matching the replicas' model — the hot-reload
    payload. Built ONCE (jax import + init are the slow part); saving
    it mid-drill is just serialization."""
    import jax
    import numpy as np

    from elasticdl_tpu.common.model_utils import (
        load_model_spec_from_module,
    )
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.training.trainer import Trainer
    from model_zoo.transformer_lm import transformer_lm as zoo

    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(load_model_spec_from_module(zoo), mesh=mesh,
                      model_params=MODEL_PARAMS)
    seq_len = int(trainer.model.seq_len)
    dummy = np.zeros((1, seq_len), np.int32)
    return trainer.init_state(({"tokens": dummy}, dummy))


def warm(port):
    """One direct generate per replica outside the measurement: pays
    the jit compile so the chaos window exercises routing, not XLA."""
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import ServingStub, build_channel

    stub = ServingStub(build_channel("localhost:%d" % port))
    stub.generate(
        pb.GenerateRequest(prompt=[1, 2], max_new_tokens=2), timeout=300
    )
    return stub


def verify_traces(mode, trace_dir, killed_addr, outcomes):
    """Structural assertions over the merged trace: the drill's story
    must be READABLE from causality alone. Returns the merged spans
    for archiving."""
    from elasticdl_tpu.observability.dump import merge_dir
    from elasticdl_tpu.observability.tracing import group_by_trace

    spans, meta = merge_dir(trace_dir)
    by_trace = group_by_trace(spans)
    roots = [s for s in spans if s["name"] == "router_generate"]

    # 1. every accepted request's trace reaches a terminal root span
    # (only FINISHED spans export, so presence == termination), and
    # every terminal status is explicit — the trace-level twin of the
    # no-transport-codes client assertion
    assert len(roots) == len(outcomes), (
        "[chaos:%s] %d router_generate roots for %d accepted "
        "requests — some request left no terminal span"
        % (mode, len(roots), len(outcomes))
    )
    allowed = {"ok", "RESOURCE_EXHAUSTED", "DEADLINE_EXCEEDED"}
    statuses = {r["status"] for r in roots}
    assert statuses <= allowed, (
        "[chaos:%s] non-explicit terminal span statuses: %s"
        % (mode, statuses - allowed)
    )
    ok_roots = [r for r in roots if r["status"] == "ok"]
    n_ok = list(outcomes.values()).count("OK")
    assert len(ok_roots) == n_ok, (
        "[chaos:%s] %d ok roots != %d OK client outcomes"
        % (mode, len(ok_roots), n_ok)
    )

    # 2./3. causal re-dispatch + cross-process merge
    redispatch_trees = 0
    merged_trees = 0
    for root in ok_roots:
        tspans = by_trace[root["trace_id"]]
        dispatches = [
            s for s in tspans
            if s["name"] == "dispatch"
            and s["parent_span_id"] == root["span_id"]
        ]
        assert dispatches, (
            "[chaos:%s] OK root without dispatch children" % mode
        )
        oks = [d for d in dispatches if d["status"] == "ok"]
        assert oks, (
            "[chaos:%s] OK root whose dispatch legs all failed" % mode
        )
        killed_legs = [
            d for d in dispatches
            if d["status"] == "error"
            and d["attrs"].get("replica") == killed_addr
        ]
        if killed_legs and any(
                e["name"] == "redispatched" for e in root["events"]):
            redispatch_trees += 1
        ok_leg_ids = {d["span_id"] for d in oks}
        if any(s["name"] == "serve"
               and s["parent_span_id"] in ok_leg_ids
               for s in tspans):
            merged_trees += 1
    assert redispatch_trees >= 1, (
        "[chaos:%s] no trace shows a failed dispatch to the killed "
        "replica (%s) with a successful sibling — the re-dispatch "
        "causality is missing from the trace" % (mode, killed_addr)
    )
    assert merged_trees >= 1, (
        "[chaos:%s] no replica serve span parented under a router "
        "dispatch span — the cross-process merge merged nothing"
        % mode
    )
    print("[chaos:%s] traces: %d spans / %d trees from %d exports; "
          "%d trees carry the killed-replica re-dispatch story, "
          "%d merged across processes"
          % (mode, len(spans), len(by_trace), len(meta),
             redispatch_trees, merged_trees))
    return spans


def run_replica_kill(state, tmp_root):
    import grpc
    import numpy as np

    from elasticdl_tpu.checkpoint.saver import CheckpointSaver
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import RouterStub, build_channel

    mode = "replicas"
    print("[chaos:%s] starting %d replicas + router"
          % (mode, NUM_REPLICAS))
    reload_dir = os.path.join(tmp_root, "ckpt_%s" % mode)
    os.makedirs(reload_dir, exist_ok=True)
    # every process exports its span ring here on graceful shutdown;
    # the SIGKILL'd replica's export is LOST by design — its requests'
    # causality lives in the router's dispatch spans
    trace_dir = os.path.join(tmp_root, "traces_%s" % mode)
    os.makedirs(trace_dir, exist_ok=True)
    trace_env = {"EDL_TRACE_DIR": trace_dir}
    replicas = []
    try:
        for i in range(NUM_REPLICAS):
            proc, port = start_replica(
                ckpt_dir=reload_dir if i == 1 else None,
                extra_env=trace_env,
            )
            replicas.append([proc, port, None])
        for rep in replicas:
            rep[2] = warm(rep[1])
        router_proc, router_port = start_router(
            [r[1] for r in replicas], extra_env=trace_env
        )
        replicas.append([router_proc, router_port, None])  # for cleanup
        stub = RouterStub(build_channel("localhost:%d" % router_port))
        stub.router_status(pb.RouterStatusRequest(), timeout=10)

        rs = np.random.RandomState(0)
        outcomes = {}
        lock = threading.Lock()

        def call(i):
            try:
                stub.router_generate(
                    pb.GenerateRequest(
                        prompt=[1 + i % 5, 2],
                        max_new_tokens=4 + i % (MAX_NEW - 3),
                        seed=i,
                    ),
                    timeout=CLIENT_TIMEOUT,
                )
                code = "OK"
            except grpc.RpcError as e:
                code = e.code().name
            with lock:
                outcomes[i] = code

        threads = []
        t0 = time.monotonic()

        def launch(i, gap):
            if gap:
                time.sleep(float(rs.exponential(1.0 / RATE_RPS)))
            t = threading.Thread(target=call, args=(i,))
            t.start()
            threads.append(t)

        i = 0
        # phase A: Poisson-paced warmup through the router
        for _ in range(WARMUP_REQS):
            launch(i, gap=True)
            i += 1
        # chaos window. SIGSTOP freezes the victim: it stops answering
        # (and polling its way back to a fresh lease) but its sockets
        # stay open, so burst dispatches routed to it STALL in flight —
        # the in-flight load component spreads the burst over all three
        # replicas, so at least one request is provably stalled there.
        # The SIGKILL then tears the sockets down mid-flight:
        # UNAVAILABLE -> re-dispatch, never a client-visible loss.
        print("[chaos:%s] SIGSTOP replica 0 (port %d), bursting %d "
              "requests" % (mode, replicas[0][1], BURST_REQS))
        replicas[0][0].send_signal(signal.SIGSTOP)
        for _ in range(BURST_REQS):
            launch(i, gap=False)
            i += 1
        time.sleep(0.5)  # let burst dispatches reach the stalled victim
        print("[chaos:%s] SIGKILL replica 0 mid-flight" % mode)
        replicas[0][0].kill()
        # phase B: Poisson-paced tail over the two survivors
        reloaded = False
        while i < REQUESTS:
            launch(i, gap=True)
            i += 1
            if i >= RELOAD_AFTER and not reloaded:
                print("[chaos:%s] dropping checkpoint v1 -> replica 1 "
                      "hot reload" % mode)
                CheckpointSaver(reload_dir, checkpoint_steps=1).save(
                    state, 1
                )
                reloaded = True

        for t in threads:
            t.join(timeout=CLIENT_TIMEOUT + 30)
        elapsed = time.monotonic() - t0
        hung = [t for t in threads if t.is_alive()]
        if hung:
            raise AssertionError(
                "[chaos:%s] %d client threads HUNG" % (mode, len(hung))
            )
        codes = sorted(outcomes.values())
        ok = codes.count("OK")
        print("[chaos:%s] outcomes=%s elapsed=%.1fs" %
              (mode, {c: codes.count(c) for c in set(codes)}, elapsed))

        # THE invariant: zero accepted-request loss. Explicit statuses
        # only — a raw transport code leaking through the router means
        # a request was lost rather than re-dispatched or shed.
        allowed = {"OK", "RESOURCE_EXHAUSTED", "DEADLINE_EXCEEDED"}
        leaked = set(codes) - allowed
        assert not leaked, (
            "accepted requests LOST (transport codes leaked through "
            "the router): %s" % leaked
        )
        assert len(outcomes) == REQUESTS, (
            "only %d/%d clients terminated" % (len(outcomes), REQUESTS)
        )
        assert ok >= REQUESTS // 2, (
            "too few completions for a 2-survivor fleet: %d/%d OK"
            % (ok, REQUESTS)
        )
        assert elapsed < CLIENT_TIMEOUT - 10, "clients rode the timeout"

        # the SIGKILL'd replica must be OUT of rotation (lease decay)
        deadline = time.time() + 10
        status = None
        while time.time() < deadline:
            status = stub.router_status(
                pb.RouterStatusRequest(), timeout=10
            )
            if status.healthy <= NUM_REPLICAS - 1:
                break
            time.sleep(0.3)
        assert status.healthy <= NUM_REPLICAS - 1, (
            "router still counts the SIGKILL'd replica healthy: %s"
            % status
        )
        print("[chaos:%s] router: routed=%d completed=%d "
              "redispatched=%d shed=%d breaker_trips=%d healthy=%d/%d"
              % (mode, status.routed, status.completed,
                 status.redispatched, status.shed,
                 status.breaker_trips, status.healthy, status.replicas))
        assert status.routed >= REQUESTS
        # proof the chaos path ran: the SIGKILL caught stalled
        # dispatches, and every one of them was re-dispatched (the OK
        # outcomes above show none of it reached a client)
        assert status.redispatched >= 1, (
            "SIGKILL never caught an in-flight dispatch — the drill "
            "exercised nothing"
        )

        # the hot-reloaded replica must be serving the new version
        rep1 = replicas[1][2]
        deadline = time.time() + 20
        reloads = 0
        while time.time() < deadline:
            st = rep1.server_status(pb.ServerStatusRequest(), timeout=10)
            reloads = st.reloads
            if reloads >= 1:
                break
            time.sleep(0.3)
        assert reloads >= 1, "replica 1 never hot-reloaded"
        print("[chaos:%s] replica 1 hot-reloaded (reloads=%d) with "
              "zero request loss" % (mode, reloads))

        # graceful teardown: SIGTERM everything still alive; the
        # survivors drain and exit 0
        for proc, _port, _stub in replicas:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc, _port, _stub in replicas[1:]:
            rc = proc.wait(timeout=60)
            assert rc == 0, "graceful exit must return 0, got %s" % rc
        assert replicas[0][0].wait(timeout=10) != 0  # SIGKILL, by design

        # trace forensics: the drill's causal story must be readable
        # from the merged span exports (survivors flushed on SIGTERM)
        spans = verify_traces(
            mode, trace_dir, "localhost:%d" % replicas[0][1], outcomes
        )
        return spans
    finally:
        for entry in replicas:
            if entry[0].poll() is None:
                entry[0].kill()
    print("[chaos:%s] PASSED" % mode)


def run_cell_failover(tmp_root):
    """Router-kill phase: the router tier itself is the victim.

    Three replicas behind TWO router cells sharing one registry
    journal. Cell 1 starts with NO --replica flags — its whole fleet
    view is journal replay of cell 0's adopt events. A CellFront in
    this process dispatches a Poisson stream of shared-prefix unary
    generates (one prefix family -> one fingerprint -> one owning
    cell), the drill SIGKILLs the OWNING cell mid-load, and every
    accepted request must re-dispatch through the surviving cell with
    zero loss — then the killed cell restarts replica-flag-free and
    must rebuild the full fleet from the journal."""
    import numpy as np

    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import RouterStub, build_channel
    from elasticdl_tpu.serving.router import RouterError
    from elasticdl_tpu.serving.router_cell import CellFront

    mode = "cells"
    journal_dir = os.path.join(tmp_root, "cell_journal")
    os.makedirs(journal_dir, exist_ok=True)
    procs = []  # every subprocess, for the finally-kill backstop
    front = None
    try:
        print("[chaos:%s] starting %d replicas + 2 router cells"
              % (mode, NUM_REPLICAS))
        replica_ports = []
        for _ in range(NUM_REPLICAS):
            proc, port = start_replica()
            procs.append(proc)
            replica_ports.append(port)
        for port in replica_ports:
            warm(port)
        # cell 0 seeds the journal with the fleet; cell 1 starts BLIND
        # (no --replica flags) and must learn every replica from replay
        cell0, port0 = start_router_cell(
            replica_ports, 0, 2, journal_dir)
        procs.append(cell0)
        cell1, port1 = start_router_cell([], 1, 2, journal_dir)
        procs.append(cell1)
        stub1 = RouterStub(build_channel("localhost:%d" % port1))
        deadline = time.time() + 30
        st = None
        while time.time() < deadline:
            st = stub1.router_status(pb.RouterStatusRequest(),
                                     timeout=10)
            if st.replicas >= NUM_REPLICAS and st.healthy >= NUM_REPLICAS:
                break
            time.sleep(0.3)
        assert st is not None and st.replicas >= NUM_REPLICAS, (
            "cell 1 never learned the fleet from the journal: %s" % st
        )
        assert st.journal_replayed >= NUM_REPLICAS, (
            "cell 1 reports no adopt replay (journal_replayed=%d)"
            % st.journal_replayed
        )
        print("[chaos:%s] cell 1 learned %d replicas purely from "
              "journal replay (%d events)"
              % (mode, st.replicas, st.journal_replayed))

        front = CellFront(
            ["localhost:%d" % port0, "localhost:%d" % port1],
            reroute_window_secs=30.0, timeout_secs=CLIENT_TIMEOUT,
        )
        # one shared-prefix family: every request carries the same
        # full leading block, so every request fingerprints to the
        # same key and the ring pins the whole stream to ONE owning
        # cell — the one the drill kills.
        prefix = [3] * 16

        def prompt_for(i):
            return prefix + [1 + i % 5, 2]

        owner = front._targets(
            front._route_key(pb.GenerateRequest(prompt=prompt_for(0)))
        )[0][0]
        victim, victim_port = (
            (cell0, port0) if owner.endswith(":%d" % port0)
            else (cell1, port1)
        )
        survivor_port = port1 if victim is cell0 else port0
        print("[chaos:%s] prefix family owner is cell @ %s"
              % (mode, owner))

        rs = np.random.RandomState(7)
        outcomes = {}
        lock = threading.Lock()

        def call(i):
            try:
                # prompt is 18 tokens of the drill model's seq_len=32
                # budget: cap new tokens so prompt+new always fits
                front.generate(
                    pb.GenerateRequest(
                        prompt=prompt_for(i),
                        max_new_tokens=2 + i % 12,
                        seed=i,
                    ),
                    timeout=CLIENT_TIMEOUT,
                )
                code = "OK"
            except RouterError as e:
                code = e.code
            with lock:
                outcomes[i] = code

        threads = []
        t0 = time.monotonic()

        def launch(i):
            time.sleep(float(rs.exponential(1.0 / RATE_RPS)))
            t = threading.Thread(target=call, args=(i,))
            t.start()
            threads.append(t)

        i = 0
        for _ in range(WARMUP_REQS):
            launch(i)
            i += 1
        print("[chaos:%s] SIGKILL owning cell (port %d) mid-load"
              % (mode, victim_port))
        victim.kill()
        while i < REQUESTS:
            launch(i)
            i += 1

        for t in threads:
            t.join(timeout=CLIENT_TIMEOUT + 30)
        elapsed = time.monotonic() - t0
        hung = [t for t in threads if t.is_alive()]
        if hung:
            raise AssertionError(
                "[chaos:%s] %d client threads HUNG" % (mode, len(hung))
            )
        codes = sorted(outcomes.values())
        ok = codes.count("OK")
        print("[chaos:%s] outcomes=%s elapsed=%.1fs front=%s"
              % (mode, {c: codes.count(c) for c in set(codes)},
                 elapsed, front.counters))

        # THE invariant again, one tier up: a SIGKILL'd ROUTER CELL
        # must not lose a single accepted request — the front reroutes
        # to the surviving cell, which shares the same replica fleet.
        allowed = {"OK", "RESOURCE_EXHAUSTED", "DEADLINE_EXCEEDED"}
        leaked = set(codes) - allowed
        assert not leaked, (
            "accepted requests LOST across the cell kill: %s" % leaked
        )
        assert len(outcomes) == REQUESTS, (
            "only %d/%d clients terminated" % (len(outcomes), REQUESTS)
        )
        assert ok >= REQUESTS // 2, (
            "too few completions for a surviving cell: %d/%d OK"
            % (ok, REQUESTS)
        )
        assert elapsed < CLIENT_TIMEOUT - 10, "clients rode the timeout"
        assert front.counters["rerouted"] >= 1, (
            "the cell kill never forced a reroute — the drill "
            "exercised nothing"
        )

        # the survivor carried the rerouted tail
        surv = RouterStub(
            build_channel("localhost:%d" % survivor_port)
        ).router_status(pb.RouterStatusRequest(), timeout=10)
        assert surv.routed >= 1, "survivor cell never routed anything"

        # failover epilogue: the killed cell restarts with NO replica
        # flags and must rebuild its fleet view from the journal alone
        print("[chaos:%s] restarting killed cell from the journal"
              % mode)
        cell_id = 0 if victim is cell0 else 1
        reborn, reborn_port = start_router_cell(
            [], cell_id, 2, journal_dir)
        procs.append(reborn)
        stub_r = RouterStub(build_channel("localhost:%d" % reborn_port))
        deadline = time.time() + 30
        rst = None
        while time.time() < deadline:
            rst = stub_r.router_status(pb.RouterStatusRequest(),
                                       timeout=10)
            if rst.replicas >= NUM_REPLICAS:
                break
            time.sleep(0.3)
        assert rst is not None and rst.replicas >= NUM_REPLICAS, (
            "reborn cell did not recover the fleet from the journal: "
            "%s" % rst
        )
        assert rst.cell_restarts >= 1, (
            "journal store never counted a cold start over existing "
            "state (cell_restarts=%d)" % rst.cell_restarts
        )
        print("[chaos:%s] reborn cell recovered %d replicas from the "
              "journal (restart #%d)"
              % (mode, rst.replicas, rst.cell_restarts))

        # graceful teardown: survivors drain and exit 0; the SIGKILL'd
        # cell's nonzero rc proves the kill was real
        for proc in procs:
            if proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc in procs:
            if proc is victim:
                continue
            rc = proc.wait(timeout=60)
            assert rc == 0, "graceful exit must return 0, got %s" % rc
        assert victim.wait(timeout=10) != 0  # SIGKILL, by design
        print("[chaos:%s] PASSED" % mode)
    finally:
        if front is not None:
            front.close()
        for proc in procs:
            if proc.poll() is None:
                proc.kill()


def main():
    import json
    import tempfile

    from elasticdl_tpu.observability.tracing import chrome_trace

    state = build_checkpoint_state()
    with tempfile.TemporaryDirectory(prefix="edl_chaos_") as tmp_root:
        spans = run_replica_kill(state, tmp_root)
        # router-kill phase: same invariant one tier up — SIGKILL a
        # ROUTER CELL mid-load, zero accepted-request loss
        run_cell_failover(tmp_root)
    # archive the replica phase's merged trace as the CI artifact — one
    # real chaos run, loadable at ui.perfetto.dev / chrome://tracing
    out = os.path.join(REPO, "ROUTER_CHAOS_TRACE.json")
    with open(out, "w") as f:
        json.dump(chrome_trace(spans), f)
    print("[chaos] merged trace archived -> %s" % out)
    print("[chaos] router chaos drill PASSED (replicas + cells): "
          "zero accepted-request loss under replica SIGKILL, hot "
          "reload, AND router-cell SIGKILL with journaled failover")
    return 0


if __name__ == "__main__":
    sys.exit(main())
