#!/usr/bin/env python
"""Replayable SERVING kill drill (the inference twin of
scripts/run_master_kill_drill.py).

Runs the REAL serving stack as a subprocess (`python -m
elasticdl_tpu.serving.main`) and drills the two ways a serving process
dies, asserting the client-visible invariant both times: every
in-flight request either COMPLETES or terminates with a CLEAN status —
never a hang.

Phase 1 — graceful (SIGTERM mid-load): admission closes, queued
  requests get RESOURCE_EXHAUSTED, seated requests drain to completion,
  the process exits 0. Allowed outcomes: OK / RESOURCE_EXHAUSTED /
  DEADLINE_EXCEEDED.

Phase 2 — hard kill (EDL_FAULT_SPEC=generate:kill:1:skip=N, the same
  spec grammar the master drills use): the process SIGKILLs itself
  mid-load; surviving clients see the transport die as UNAVAILABLE /
  CANCELLED within seconds. The point is the absence of hangs, not the
  status: a SIGKILL'd server cannot promise more than a torn socket,
  and common/retry.py classifies exactly these codes as transient for
  the retry-elsewhere path.

Phase 3 — shared-prefix ledger (EDL_KV_SHARED=1): every
  request carries a COMMON prompt prefix so refcounted shared chains
  are resident (serving/kv_pool.py); a full wave completes and the
  block ledger must drain clean (every block free or cached — no
  leaked refcount, no double-free panic), then the server is SIGKILLed
  mid-load with the chains still shared and a FRESH server must come
  up, serve the same shared-prefix load, and drain to a clean ledger
  again — a crash can never corrupt block accounting across restarts
  because the ledger is process-local and rebuilt from nothing.

Phase 4 — tiered host spill (--kv_host_bytes): three
  distinct system prompts over a device pool too small for their
  chains plus an active seat, so reclaimable chains are forced to
  SPILL to the host tier and REVIVE by upload when their prefix comes
  back around. A full wave completes with revivals demonstrably
  served (`prefill_tokens_revived > 0`), the two-tier ledger drains
  clean (every device block free | cached, host bytes inside the
  budget — a spilled chain is either revived or budget-dropped,
  never leaked), then the server is SIGKILLed mid-load with spilled
  chains live and a FRESH server must come up with an EMPTY host
  tier (the tier is process-local — a crash can never leak host
  memory across restarts), serve the same load, revive again, and
  drain to a clean two-tier ledger.

Phase 5 — disaggregated handoff (serving/disagg.py): a
  role-split fleet (one prefill replica, one decode replica, a router
  orchestrating the chain handoff between them) first proves the
  success path — handoffs counted, chains exported/imported, both
  pool ledgers drain clean with zero transfers in flight — then a
  fresh prefill replica armed with EDL_FAULT_SPEC=export_chain:kill:1
  SIGKILLs itself WITH A TRANSFER IN FLIGHT: every accepted request
  must still complete (the router falls back to a cold dispatch; a
  handoff may cost the warm-start, never the request) and the
  surviving decode pool must drain to a clean ledger.

A SECOND pass runs phases 1 + 3 + 4 with INT8 arenas
(kv_cache_dtype='int8'): graceful drain, the shared-chain ledger,
the spill/revive lifecycle, SIGKILL mid-load and the fresh-restart
rebuild must all hold with scale leaves in the arenas (the hard-kill
transport semantics of phase 2 are dtype-blind and already covered).

Usage: python scripts/run_server_kill_drill.py
Exit 0 = all phases hold with both arena dtypes."""

import os
import signal
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

MODEL_PARAMS = (
    "vocab_size=16; seq_len=32; embed_dim=32; num_heads=2; num_layers=1"
)
CLIENT_TIMEOUT = 60.0  # backstop; the drill asserts we never get near it


def launch_ready(cmd, extra_env=None, ready_marker="SERVING_READY",
                 startup_secs=180):
    """Start a drill subprocess and wait for its `<marker> port=N`
    readiness line; returns (proc, port) with the pipe drained in the
    background so the child can't block on a full buffer. Shared by
    this drill and scripts/run_router_chaos_drill.py."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("PYTHONPATH", None)
    env.update(extra_env or {})
    proc = subprocess.Popen(
        cmd, cwd=REPO, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    port = None
    deadline = time.time() + startup_secs
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise RuntimeError(
                    "process died during startup (rc=%s)"
                    % proc.returncode
                )
            continue
        if line.startswith(ready_marker):
            port = int(line.strip().split("port=")[1])
            break
    if port is None:
        proc.kill()
        proc.wait(timeout=30)  # reap before bailing — no zombie
        raise RuntimeError("process never became ready: %r" % cmd)
    threading.Thread(
        target=lambda: [None for _ in proc.stdout], daemon=True
    ).start()
    return proc, port


# the common system-prompt prefix phase 3 shares (2 full blocks at
# the drill's --kv_block_size 4, so chains actually form)
SHARED_PREFIX = [1, 2, 3, 4, 5, 6, 7, 2]


def start_server(extra_env=None, num_slots=1, model_params=None,
                 extra_args=()):
    return launch_ready(
        [
            sys.executable, "-m", "elasticdl_tpu.serving.main",
            "--model_zoo", os.path.join(REPO, "model_zoo"),
            "--model_def", "transformer_lm.transformer_lm.custom_model",
            "--model_params", model_params or MODEL_PARAMS,
            "--port", "0", "--num_slots", str(num_slots),
            "--queue_capacity", "8", "--kv_block_size", "4",
            *extra_args,
        ],
        extra_env=extra_env,
    )


def fire_requests(port, n, max_new=24, shared_prefix=False,
                  prompt_fn=None):
    """n concurrent unary requests; returns (outcomes, elapsed) where
    outcomes[i] is 'OK' or a gRPC status name. Joins with a hard bound:
    any thread still alive past the client timeout = a hang = failure.
    shared_prefix=True sends the common system prompt + a per-request
    tail, so the paged+shared pool builds refcounted chains;
    prompt_fn(i) overrides the prompt outright (the host-tier phase
    rotates several distinct system prompts)."""
    import grpc

    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import ServingStub, build_channel

    stub = ServingStub(build_channel("localhost:%d" % port))
    outcomes = {}
    lock = threading.Lock()

    def call(i):
        if prompt_fn is not None:
            prompt = prompt_fn(i)
        else:
            prompt = (
                SHARED_PREFIX + [1 + i % 5] if shared_prefix
                else [1 + i % 5, 2]
            )
        try:
            stub.generate(
                pb.GenerateRequest(
                    prompt=prompt, max_new_tokens=max_new,
                ),
                timeout=CLIENT_TIMEOUT,
            )
            code = "OK"
        except grpc.RpcError as e:
            code = e.code().name
        with lock:
            outcomes[i] = code

    threads = [
        threading.Thread(target=call, args=(i,)) for i in range(n)
    ]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    return threads, outcomes, t0


def join_all(threads, outcomes, t0, n):
    for t in threads:
        t.join(timeout=CLIENT_TIMEOUT + 30)
    elapsed = time.monotonic() - t0
    hung = [t for t in threads if t.is_alive()]
    if hung:
        raise AssertionError("%d client threads HUNG" % len(hung))
    if len(outcomes) != n:
        raise AssertionError(
            "only %d/%d clients terminated" % (len(outcomes), n)
        )
    return elapsed


def phase_graceful(mode="paged", model_params=None):
    print("[drill] phase 1 (%s): SIGTERM mid-load (graceful drain)"
          % mode)
    proc, port = start_server(model_params=model_params)
    try:
        threads, outcomes, t0 = fire_requests(port, 8)
        time.sleep(0.4)  # let some seat, some queue
        proc.send_signal(signal.SIGTERM)
        elapsed = join_all(threads, outcomes, t0, 8)
        rc = proc.wait(timeout=60)
        codes = sorted(outcomes.values())
        print("[drill]   outcomes=%s elapsed=%.1fs rc=%s"
              % (codes, elapsed, rc))
        allowed = {"OK", "RESOURCE_EXHAUSTED", "DEADLINE_EXCEEDED"}
        assert set(codes) <= allowed, codes
        assert "OK" in codes, "drain completed nothing: %s" % codes
        assert elapsed < CLIENT_TIMEOUT - 10, "clients rode the timeout"
        assert rc == 0, "graceful exit must return 0, got %s" % rc
    finally:
        if proc.poll() is None:
            proc.kill()
    print("[drill] phase 1 (%s) OK" % mode)


def phase_hard_kill(mode="paged"):
    print("[drill] phase 2 (%s): EDL_FAULT_SPEC self-SIGKILL mid-load"
          % mode)
    env = {"EDL_FAULT_SPEC": "generate:kill:1:skip=3"}
    proc, port = start_server(extra_env=env)
    try:
        threads, outcomes, t0 = fire_requests(port, 8)
        elapsed = join_all(threads, outcomes, t0, 8)
        codes = sorted(outcomes.values())
        print("[drill]   outcomes=%s elapsed=%.1fs" % (codes, elapsed))
        # a SIGKILL'd transport yields UNAVAILABLE/CANCELLED for the
        # survivors; requests completed before the kill are OK. The
        # invariant is clean termination, fast.
        allowed = {"OK", "UNAVAILABLE", "CANCELLED",
                   "RESOURCE_EXHAUSTED", "DEADLINE_EXCEEDED"}
        assert set(codes) <= allowed, codes
        assert any(c != "OK" for c in codes), (
            "the kill never fired: %s" % codes
        )
        assert elapsed < CLIENT_TIMEOUT - 10, "clients rode the timeout"
        proc.wait(timeout=30)
        assert proc.returncode != 0  # SIGKILL, by design
    finally:
        if proc.poll() is None:
            proc.kill()
    print("[drill] phase 2 (%s) OK" % mode)


def _ledger(port):
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import ServingStub, build_channel

    stub = ServingStub(build_channel("localhost:%d" % port))
    return stub.server_status(pb.ServerStatusRequest(), timeout=30)


def _assert_clean_ledger(st, where):
    """Post-drain block accounting: every block free or cached —
    a leaked refcount would show as blocks_free < blocks_total, a
    double-free would have crashed the allocator long before."""
    assert st.kv_blocks_free == st.kv_blocks_total, (
        "%s: %d/%d blocks free (leaked refcount?)"
        % (where, st.kv_blocks_free, st.kv_blocks_total)
    )


def phase_shared_ledger(mode="paged", model_params=None):
    print("[drill] phase 3 (%s): shared prefixes resident through "
          "SIGKILL + restart" % mode)
    env = {"EDL_KV_SHARED": "1"}
    proc, port = start_server(extra_env=env, num_slots=3,
                              model_params=model_params)
    try:
        # wave 1: completes fully; the ledger must drain clean with
        # the prefix chains parked reclaimable (no leaked refcount)
        threads, outcomes, t0 = fire_requests(
            port, 6, max_new=16, shared_prefix=True
        )
        join_all(threads, outcomes, t0, 6)
        assert set(outcomes.values()) == {"OK"}, outcomes
        st = _ledger(port)
        assert st.kv_paged and st.kv_shared
        assert st.prefix_hit_tokens > 0, (
            "shared load never matched a prefix"
        )
        _assert_clean_ledger(st, "post-wave-1")
        # wave 2: SIGKILL lands mid-load with shared chains LIVE
        threads, outcomes, t0 = fire_requests(
            port, 6, max_new=16, shared_prefix=True
        )
        time.sleep(0.3)
        proc.kill()
        join_all(threads, outcomes, t0, 6)
        allowed = {"OK", "UNAVAILABLE", "CANCELLED",
                   "RESOURCE_EXHAUSTED", "DEADLINE_EXCEEDED"}
        assert set(outcomes.values()) <= allowed, outcomes
    finally:
        if proc.poll() is None:
            proc.kill()
    # restart: a fresh process must rebuild clean block accounting and
    # serve the same shared-prefix load — nothing about the crash can
    # poison the (process-local) ledger
    proc, port = start_server(extra_env=env, num_slots=3,
                              model_params=model_params)
    try:
        threads, outcomes, t0 = fire_requests(
            port, 6, max_new=16, shared_prefix=True
        )
        join_all(threads, outcomes, t0, 6)
        assert set(outcomes.values()) == {"OK"}, outcomes
        st = _ledger(port)
        assert st.prefix_hit_tokens > 0
        _assert_clean_ledger(st, "post-restart")
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=60)
    print("[drill] phase 3 (%s) OK" % mode)


# three distinct 2-block system prompts (kv_block_size 4): working
# set 6 blocks, deliberately more than the phase-4 device pool can
# cache beside an active seat — chains must spill and revive
HOST_PREFIXES = [
    [1, 2, 3, 4, 5, 6, 7, 2],
    [2, 3, 4, 5, 6, 7, 1, 3],
    [3, 4, 5, 6, 7, 1, 2, 4],
]
HOST_BUDGET_BYTES = 1 << 20


def _host_prompt(i):
    return HOST_PREFIXES[i % len(HOST_PREFIXES)] + [1 + i % 5]


def phase_host_tier(mode="paged", model_params=None):
    print("[drill] phase 4 (%s): host tier — spill under pressure, "
          "revive through a wave, SIGKILL with spilled chains live, "
          "fresh restart rebuilds an empty tier" % mode)
    env = {"EDL_KV_SHARED": "1"}
    # 8 device blocks: one active seat commits 6 (9 prompt rows + 15
    # decode rows), so at most one 2-block chain survives beside it —
    # the other two spill; the host budget holds them all. The wave
    # fires 12 concurrent requests, so the queue must hold the tail
    # that waits out the block backpressure (argparse keeps the last
    # --queue_capacity, overriding start_server's default of 8).
    extra = ("--kv_num_blocks", "8",
             "--kv_host_bytes", str(HOST_BUDGET_BYTES),
             "--queue_capacity", "16")
    proc, port = start_server(extra_env=env, num_slots=2,
                              model_params=model_params,
                              extra_args=extra)
    try:
        # wave 1: 12 requests rotating 3 distinct prefixes — every
        # return of a prefix finds its chain evicted (spilled) and
        # revives it by upload instead of re-prefilling
        threads, outcomes, t0 = fire_requests(
            port, 12, max_new=16, prompt_fn=_host_prompt
        )
        join_all(threads, outcomes, t0, 12)
        assert set(outcomes.values()) == {"OK"}, outcomes
        st = _ledger(port)
        assert st.kv_paged and st.kv_shared
        assert st.prefix_hit_tokens > 0
        # the spill machinery demonstrably engaged: chains were
        # demoted AND came back by upload
        assert st.revive_uploads > 0, "no revival upload served"
        assert st.prefill_tokens_revived > 0
        # two-tier ledger: device side fully free|cached, host side
        # inside its byte budget — spilled chains are revived or
        # budget-dropped, never leaked
        _assert_clean_ledger(st, "post-wave-1 (host tier)")
        assert st.kv_host_bytes <= HOST_BUDGET_BYTES, (
            "host tier over budget: %d > %d"
            % (st.kv_host_bytes, HOST_BUDGET_BYTES)
        )
        revived_before_kill = st.prefill_tokens_revived
        # wave 2: SIGKILL mid-load with spilled chains LIVE
        threads, outcomes, t0 = fire_requests(
            port, 6, max_new=16, prompt_fn=_host_prompt
        )
        time.sleep(0.3)
        proc.kill()
        join_all(threads, outcomes, t0, 6)
        allowed = {"OK", "UNAVAILABLE", "CANCELLED",
                   "RESOURCE_EXHAUSTED", "DEADLINE_EXCEEDED"}
        assert set(outcomes.values()) <= allowed, outcomes
    finally:
        if proc.poll() is None:
            proc.kill()
    # restart: the host tier is process-local — a fresh server must
    # come up EMPTY (no leaked host memory, no phantom spilled
    # chains), serve the same rotating load, revive again, and drain
    # to a clean two-tier ledger
    proc, port = start_server(extra_env=env, num_slots=2,
                              model_params=model_params,
                              extra_args=extra)
    try:
        st0 = _ledger(port)
        assert st0.kv_host_blocks == 0 and st0.kv_host_bytes == 0, (
            "fresh server has a non-empty host tier"
        )
        assert st0.prefill_tokens_revived == 0
        threads, outcomes, t0 = fire_requests(
            port, 12, max_new=16, prompt_fn=_host_prompt
        )
        join_all(threads, outcomes, t0, 12)
        assert set(outcomes.values()) == {"OK"}, outcomes
        st = _ledger(port)
        assert st.revive_uploads > 0
        assert st.prefill_tokens_revived > 0
        _assert_clean_ledger(st, "post-restart (host tier)")
        assert st.kv_host_bytes <= HOST_BUDGET_BYTES
        print("[drill]   revived %d tokens pre-kill, %d post-restart"
              % (revived_before_kill, st.prefill_tokens_revived))
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            proc.wait(timeout=60)
    print("[drill] phase 4 (%s) OK" % mode)


# two distinct 2-block system prompts for the disagg phase (one per
# leg, so the kill leg's handoff is never satisfied by leg 1's
# already-imported chain)
DISAGG_PREFIXES = [
    [1, 2, 3, 4, 5, 6, 7, 2],
    [4, 5, 6, 7, 1, 2, 3, 5],
]


def _start_disagg_router(replica_ports):
    """Router subprocess over the two-pool fleet; affinity blocks
    sized to the drill's 8-token system prompts so requests carry a
    fingerprint (no fingerprint = no handoff to drill)."""
    cmd = [
        sys.executable, "-m", "elasticdl_tpu.serving.router_main",
        "--port", "0", "--poll_secs", "0.25", "--lease_secs", "2.0",
        "--breaker_cooldown_secs", "1.0",
        "--redispatch_window_secs", "60",
        "--affinity_block_tokens", "8",
    ]
    for p in replica_ports:
        cmd += ["--replica", "localhost:%d" % p]
    return launch_ready(cmd, ready_marker="ROUTER_READY")


def _fire_routed(router_port, n, prefix, max_new=8):
    """n concurrent requests through the ROUTER (RouterStub), all
    sharing `prefix` + a per-request tail; same hang-bounded join
    contract as fire_requests."""
    import grpc

    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import RouterStub, build_channel

    stub = RouterStub(build_channel("localhost:%d" % router_port))
    outcomes = {}
    lock = threading.Lock()

    def call(i):
        try:
            stub.router_generate(
                pb.GenerateRequest(
                    prompt=prefix + [1 + i % 5],
                    max_new_tokens=max_new,
                ),
                timeout=CLIENT_TIMEOUT,
            )
            code = "OK"
        except grpc.RpcError as e:
            code = e.code().name
        with lock:
            outcomes[i] = code

    threads = [
        threading.Thread(target=call, args=(i,)) for i in range(n)
    ]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    return threads, outcomes, t0


def _router_status(port):
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import RouterStub, build_channel

    stub = RouterStub(build_channel("localhost:%d" % port))
    return stub.router_status(pb.RouterStatusRequest(), timeout=30)


def _assert_pool_settled(st, where):
    """A disagg pool's post-drain ledger: every block free|cached AND
    no transfer-family RPC still executing — a stuck inflight gauge
    would mean a handoff the two-pool ledger cannot reconcile."""
    _assert_clean_ledger(st, where)
    assert st.transfers_inflight == 0, (
        "%s: %d transfers still in flight after drain"
        % (where, st.transfers_inflight)
    )


def phase_disagg_handoff():
    """Phase 5 — disaggregated prefill/decode handoff (paged+shared):
    a dedicated prefill replica warms chains and hands them to the
    decode replica as a dense byte copy (router-orchestrated,
    serving/disagg.py). Leg 1 proves the success path end to end:
    requests complete through the router with the handoff ledger
    moving on BOTH pools and both ledgers draining clean. Leg 2 arms
    EDL_FAULT_SPEC=export_chain:kill:1 on a fresh prefill replica, so
    the replica SIGKILLs itself WITH THE TRANSFER IN FLIGHT — the
    router must fall back to a plain cold dispatch (zero accepted-
    request loss) and the surviving decode pool must still drain to a
    clean ledger with nothing in flight."""
    print("[drill] phase 5 (disagg): prefill->decode handoff, then "
          "SIGKILL the prefill replica mid-transfer")
    env = {"EDL_KV_SHARED": "1"}
    decode, decode_port = start_server(
        extra_env=env, num_slots=3,
        extra_args=("--role", "decode", "--queue_capacity", "16"),
    )
    prefill = prefill2 = router = router2 = None
    try:
        # ---- leg 1: the handoff succeeds
        prefill, prefill_port = start_server(
            extra_env=env, num_slots=2,
            extra_args=("--role", "prefill"),
        )
        router, router_port = _start_disagg_router(
            [prefill_port, decode_port]
        )
        threads, outcomes, t0 = _fire_routed(
            router_port, 4, DISAGG_PREFIXES[0]
        )
        join_all(threads, outcomes, t0, 4)
        assert set(outcomes.values()) == {"OK"}, outcomes
        rst = _router_status(router_port)
        assert rst.disagg_handoffs >= 1, (
            "no handoff happened: handoffs=%d fallbacks=%d"
            % (rst.disagg_handoffs, rst.disagg_fallbacks)
        )
        pst = _ledger(prefill_port)
        dst = _ledger(decode_port)
        assert pst.role == "prefill" and dst.role == "decode"
        assert pst.chain_exports >= 1, "prefill pool exported nothing"
        assert dst.chain_imports >= 1, "decode pool imported nothing"
        assert dst.chain_import_tokens >= 8
        _assert_pool_settled(pst, "leg-1 prefill pool")
        _assert_pool_settled(dst, "leg-1 decode pool")
        print("[drill]   leg 1: handoffs=%d exports=%d imports=%d "
              "(%d tokens)" % (rst.disagg_handoffs, pst.chain_exports,
                               dst.chain_imports,
                               dst.chain_import_tokens))
        router.send_signal(signal.SIGTERM)
        router.wait(timeout=60)
        prefill.send_signal(signal.SIGTERM)
        prefill.wait(timeout=60)
        # ---- leg 2: the prefill replica dies mid-transfer
        kill_env = dict(env)
        kill_env["EDL_FAULT_SPEC"] = "export_chain:kill:1"
        prefill2, prefill2_port = start_server(
            extra_env=kill_env, num_slots=2,
            extra_args=("--role", "prefill"),
        )
        router2, router2_port = _start_disagg_router(
            [prefill2_port, decode_port]
        )
        threads, outcomes, t0 = _fire_routed(
            router2_port, 4, DISAGG_PREFIXES[1]
        )
        join_all(threads, outcomes, t0, 4)
        # the client-visible invariant: a handoff can cost the warm
        # start, NEVER the request — every accepted request completes
        assert set(outcomes.values()) == {"OK"}, (
            "accepted requests lost to a mid-transfer kill: %s"
            % outcomes
        )
        prefill2.wait(timeout=30)
        assert prefill2.returncode != 0  # SIGKILL, by design
        rst2 = _router_status(router2_port)
        assert rst2.disagg_fallbacks >= 1, (
            "the kill never interrupted a transfer: handoffs=%d "
            "fallbacks=%d" % (rst2.disagg_handoffs,
                              rst2.disagg_fallbacks)
        )
        dst2 = _ledger(decode_port)
        _assert_pool_settled(dst2, "leg-2 decode pool")
        print("[drill]   leg 2: fallbacks=%d, all %d requests OK, "
              "decode ledger clean" % (rst2.disagg_fallbacks,
                                       len(outcomes)))
    finally:
        for proc in (router, router2, prefill, prefill2, decode):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
    print("[drill] phase 5 (disagg) OK")


def main():
    # kv_block_size 4 divides the drill model's seq_len=32; sharing
    # needs full blocks
    phase_graceful()
    phase_hard_kill()
    phase_shared_ledger()
    phase_host_tier()
    # int8 arenas: the same drain / SIGKILL-restart / shared-chain
    # ledger / spill-revive invariants must hold with scale leaves in
    # the arenas (kv_cache_dtype='int8'); the hard-kill transport
    # semantics are dtype-blind and already covered above
    int8_params = MODEL_PARAMS + "; kv_cache_dtype='int8'"
    phase_graceful(mode="paged_int8", model_params=int8_params)
    phase_shared_ledger(mode="paged_int8", model_params=int8_params)
    phase_host_tier(mode="paged_int8", model_params=int8_params)
    # disaggregated prefill/decode: clean handoff, then a SIGKILL'd
    # prefill replica mid-transfer (the handoff surface needs prefix
    # sharing)
    phase_disagg_handoff()
    print("[drill] serving kill drill PASSED (paged + paged-int8, "
          "shared-prefix ledger, host-tier spill/revive, disagg "
          "handoff)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
