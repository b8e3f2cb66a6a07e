"""End-to-end serving tests on the CPU mesh (drills shard).

The acceptance battery for the online serving subsystem: a real gRPC
server over the continuous-batching engine, ≥32 concurrent requests
with mixed prompt/output lengths whose tokens must equal the offline
`autoregressive_generate` for the same knobs, demonstrable
interleaving (slot occupancy > 1 while the queue drains), hot
checkpoint reload mid-stream without dropping in-flight requests, and
overload/shutdown semantics that terminate every request with a clean
status."""

import os
import threading
import time

import numpy as np
import pytest

import jax

from elasticdl_tpu.api.generation import autoregressive_generate
from elasticdl_tpu.checkpoint.saver import CheckpointSaver
from elasticdl_tpu.common.model_utils import load_model_spec_from_module
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.proto.service import ServingStub, build_channel
from elasticdl_tpu.serving import GenerationServer, ServingConfig
from elasticdl_tpu.training.trainer import Trainer
from model_zoo.transformer_lm import transformer_lm as zoo

pytestmark = pytest.mark.slow

PARAMS = (
    "vocab_size=8; seq_len=16; embed_dim=32; num_heads=2; num_layers=1"
)


def _trainer(seed=0):
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    return Trainer(
        load_model_spec_from_module(zoo), mesh=mesh,
        model_params=PARAMS, seed=seed,
    )


def _state(trainer):
    toks = (np.arange(17)[None, :] % 8).astype(np.int32)
    return trainer.init_state(
        ({"tokens": toks[:, :-1]}, toks[:, 1:])
    )


@pytest.fixture(scope="module")
def rig():
    trainer = _trainer()
    state = _state(trainer)
    return trainer, state


def _start(trainer, state, **cfg_kwargs):
    cfg = ServingConfig(**cfg_kwargs)
    return GenerationServer(trainer, state, cfg).start()


def test_concurrent_requests_match_offline_and_interleave(rig, tmp_path):
    """≥32 concurrent mixed-length requests; every response must be
    token-identical to the offline decoder with the same (prompt, seed,
    temperature); the pool must demonstrably interleave."""
    trainer, state = rig
    server = _start(
        trainer, state, num_slots=4, queue_capacity=64,
        telemetry_dir=str(tmp_path),
    )
    try:
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        specs = []
        for i in range(32):
            prompt = [int(x) for x in np.arange(1 + i % 4) % 8 + 1]
            specs.append({
                "prompt": prompt,
                "new": 3 + i % 7,
                "temperature": 0.0 if i % 3 == 0 else 1.0,
                "seed": i,
            })
        results = {}
        errors = {}

        def call(i, s):
            try:
                r = stub.generate(
                    pb.GenerateRequest(
                        prompt=s["prompt"], max_new_tokens=s["new"],
                        temperature=s["temperature"], seed=s["seed"],
                    ),
                    timeout=120,
                )
                results[i] = list(r.tokens)
            except Exception as e:  # noqa: BLE001
                errors[i] = e

        threads = [
            threading.Thread(target=call, args=(i, s))
            for i, s in enumerate(specs)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert len(results) == 32
        for i, s in enumerate(specs):
            off = np.asarray(autoregressive_generate(
                trainer, state, np.asarray([s["prompt"]], np.int32),
                s["new"], temperature=s["temperature"], seed=s["seed"],
                use_cache=True,
            ))[0]
            assert list(off) == results[i], (i, s, off, results[i])
        st = stub.server_status(pb.ServerStatusRequest(), timeout=10)
        # continuous batching demonstrably interleaved: more than one
        # slot decoded at once while the queue drained
        assert st.max_active_slots > 1
        assert st.completed == 32 and st.admitted == 32
        assert st.tokens_generated >= sum(s["new"] for s in specs)
    finally:
        server.stop()


def test_greedy_matches_full_recompute_offline(rig):
    """The serving path must agree with BOTH offline strategies for
    greedy decode (full-recompute == KV == serving)."""
    trainer, state = rig
    server = _start(trainer, state, num_slots=2, queue_capacity=8)
    try:
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        r = stub.generate(
            pb.GenerateRequest(prompt=[1, 2, 3], max_new_tokens=6),
            timeout=60,
        )
        off_full = np.asarray(autoregressive_generate(
            trainer, state, np.asarray([[1, 2, 3]], np.int32), 6,
        ))[0]
        assert list(off_full) == list(r.tokens)
    finally:
        server.stop()


def test_streaming_chunks_and_ttft(rig, tmp_path):
    trainer, state = rig
    server = _start(
        trainer, state, num_slots=2, queue_capacity=8,
        telemetry_dir=str(tmp_path),
    )
    try:
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        chunks = list(stub.generate_stream(
            pb.GenerateRequest(prompt=[1, 2], max_new_tokens=5),
            timeout=60,
        ))
        toks = [t for c in chunks for t in c.tokens]
        assert len(toks) == 5
        assert chunks[-1].done and not chunks[-1].tokens
        off = np.asarray(autoregressive_generate(
            trainer, state, np.asarray([[1, 2]], np.int32), 5,
            use_cache=True,
        ))[0]
        assert list(off[2:]) == toks
    finally:
        server.stop()


def test_hot_reload_swaps_params_mid_stream(rig, tmp_path):
    """A checkpoint landing mid-decode swaps params between steps: the
    in-flight stream keeps running (no drop), later requests decode
    under the new version, and the version gauge moves."""
    trainer, state = rig
    ckpt_dir = str(tmp_path / "ckpt")
    server = _start(
        trainer, state, num_slots=2, queue_capacity=8,
        checkpoint_dir=ckpt_dir, reload_poll_secs=0.05,
    )
    try:
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        # long-running stream to straddle the reload
        stream = stub.generate_stream(
            pb.GenerateRequest(prompt=[1], max_new_tokens=14),
            timeout=120,
        )
        first = next(stream)
        assert first.model_version == 0
        # new params under a new version, written mid-stream
        trainer2 = _trainer(seed=123)
        state2 = _state(trainer2).replace(step=jax.numpy.asarray(7))
        CheckpointSaver(ckpt_dir, checkpoint_steps=1).save(state2, 7)
        chunks = [first] + list(stream)
        toks = [t for c in chunks for t in c.tokens]
        assert len(toks) == 14  # nothing dropped
        # wait until the reload has landed (a straddling request can
        # legitimately mix versions — its version field reports the
        # params that produced its LAST token)...
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            r = stub.generate(
                pb.GenerateRequest(prompt=[1, 2, 3], max_new_tokens=4),
                timeout=60,
            )
            if r.model_version == 7:
                break
        assert r.model_version == 7
        # ...then a fresh request runs FULLY on the reloaded params and
        # must be token-identical to offline decode with them
        r2 = stub.generate(
            pb.GenerateRequest(prompt=[1, 2, 3], max_new_tokens=4),
            timeout=60,
        )
        assert r2.model_version == 7
        off = np.asarray(autoregressive_generate(
            trainer, state2, np.asarray([[1, 2, 3]], np.int32), 4,
            use_cache=True,
        ))[0]
        assert list(off) == list(r2.tokens)
        st = stub.server_status(pb.ServerStatusRequest(), timeout=10)
        assert st.model_version == 7 and st.reloads >= 1
    finally:
        server.stop()


def test_backpressure_rejects_overload_cleanly(rig):
    """Overload: a tiny queue must reject the excess with
    RESOURCE_EXHAUSTED immediately; admitted requests complete; no
    request rides the client timeout (no hangs)."""
    import grpc

    trainer, state = rig
    server = _start(trainer, state, num_slots=1, queue_capacity=2)
    try:
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        outcomes = []
        lock = threading.Lock()

        def call(i):
            try:
                stub.generate(
                    pb.GenerateRequest(
                        prompt=[1, 2], max_new_tokens=12,
                    ),
                    timeout=90,
                )
                code = "OK"
            except grpc.RpcError as e:
                code = e.code().name
            with lock:
                outcomes.append(code)

        threads = [
            threading.Thread(target=call, args=(i,)) for i in range(12)
        ]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        elapsed = time.monotonic() - t0
        assert len(outcomes) == 12  # every request terminated
        assert elapsed < 90  # ...and none rode the client timeout
        assert set(outcomes) <= {"OK", "RESOURCE_EXHAUSTED"}, outcomes
        assert outcomes.count("OK") >= 1
        # 12 near-simultaneous submits into 1 slot + 2 queue places
        # must shed load
        assert outcomes.count("RESOURCE_EXHAUSTED") >= 1
    finally:
        server.stop()


def test_deadline_exceeded_behind_slow_request(rig):
    """A short-deadline request queued behind a long decode must get
    DEADLINE_EXCEEDED (queued expiry or mid-decode eviction), never a
    hang; partial streams keep their tokens."""
    import grpc

    trainer, state = rig
    server = _start(trainer, state, num_slots=1, queue_capacity=8)
    try:
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        long_done = {}

        def long_call():
            r = stub.generate(
                pb.GenerateRequest(prompt=[1], max_new_tokens=14),
                timeout=90,
            )
            long_done["tokens"] = len(r.tokens)

        t = threading.Thread(target=long_call)
        t.start()
        deadline = time.monotonic() + 30
        while (server.engine.active_count() == 0
               and time.monotonic() < deadline):
            time.sleep(0.005)
        with pytest.raises(grpc.RpcError) as e:
            stub.generate(
                pb.GenerateRequest(
                    prompt=[2], max_new_tokens=14, deadline_ms=5
                ),
                timeout=90,
            )
        assert e.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
        t.join(timeout=120)
        assert long_done.get("tokens") == 15  # the long one was unharmed
        st = stub.server_status(pb.ServerStatusRequest(), timeout=10)
        assert st.expired >= 1
    finally:
        server.stop()


def test_graceful_stop_drains_active_rejects_queued(rig):
    """stop(drain=True): in-flight slots run to completion; the queued
    backlog gets RESOURCE_EXHAUSTED. The kill-drill invariant, in-proc."""
    import grpc

    trainer, state = rig
    server = _start(trainer, state, num_slots=1, queue_capacity=8)
    try:
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        outcomes = {}

        def call(i):
            try:
                r = stub.generate(
                    pb.GenerateRequest(
                        prompt=[1 + i % 3], max_new_tokens=12
                    ),
                    timeout=90,
                )
                outcomes[i] = ("OK", len(r.tokens))
            except grpc.RpcError as e:
                outcomes[i] = (e.code().name, 0)

        threads = [
            threading.Thread(target=call, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        # let the first request seat, then pull the plug
        deadline = time.monotonic() + 30
        while (server.engine.active_count() == 0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        server.stop(drain=True)
        for t in threads:
            t.join(timeout=120)
        assert len(outcomes) == 4
        codes = [c for c, _ in outcomes.values()]
        assert set(codes) <= {"OK", "RESOURCE_EXHAUSTED"}, outcomes
        # the seated request completed with its full token budget
        ok = [n for c, n in outcomes.values() if c == "OK"]
        assert ok and all(n >= 12 for n in ok)
    finally:
        server.stop()


def test_fault_injection_error_at_serving_boundary(rig):
    """EDL_FAULT_SPEC-style rules fire on the serving RPC surface over
    real gRPC: an injected error surfaces as UNAVAILABLE to the client
    and the next call succeeds."""
    import grpc

    from elasticdl_tpu.common.fault_injection import FaultInjector

    trainer, state = rig
    cfg = ServingConfig(num_slots=1, queue_capacity=4)
    server = GenerationServer(
        trainer, state, cfg,
        injector=FaultInjector(spec="generate:drop:1"),
    ).start()
    try:
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        with pytest.raises(grpc.RpcError) as e:
            stub.generate(
                pb.GenerateRequest(prompt=[1], max_new_tokens=2),
                timeout=30,
            )
        assert e.value.code() == grpc.StatusCode.UNAVAILABLE
        r = stub.generate(
            pb.GenerateRequest(prompt=[1], max_new_tokens=2), timeout=60
        )
        assert len(r.tokens) == 3
    finally:
        server.stop()


def test_paged_engine_matches_offline_concurrent(rig):
    """The block-paged pool must be TOKEN-EXACT with offline decode:
    32 concurrent mixed-length requests against a server with a tight
    block budget (more slots than the same bytes of whole-`seq_len`
    sequences) vs offline autoregressive_generate — identical streams
    per request."""
    trainer, state = rig

    def collect(server):
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        specs = []
        for i in range(32):
            prompt = [int(x) for x in np.arange(1 + i % 4) % 8 + 1]
            specs.append({
                "prompt": prompt,
                "new": 3 + i % 7,
                "temperature": 0.0 if i % 3 == 0 else 1.0,
                "seed": i,
            })
        results, errors = {}, {}

        def call(i, s):
            try:
                r = stub.generate(
                    pb.GenerateRequest(
                        prompt=s["prompt"], max_new_tokens=s["new"],
                        temperature=s["temperature"], seed=s["seed"],
                    ),
                    timeout=120,
                )
                results[i] = list(r.tokens)
            except Exception as e:  # noqa: BLE001
                errors[i] = e

        threads = [
            threading.Thread(target=call, args=(i, s))
            for i, s in enumerate(specs)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert len(results) == 32
        return specs, results

    paged = _start(
        trainer, state, num_slots=6, queue_capacity=64,
        kv_block_size=4, kv_num_blocks=16,
    )
    try:
        specs, paged_results = collect(paged)
        stub = ServingStub(build_channel("localhost:%d" % paged.port))
        st = stub.server_status(pb.ServerStatusRequest(), timeout=10)
        assert st.kv_paged and st.kv_blocks_total == 16
        assert st.max_active_slots > 1  # interleaving under paging
        assert st.kv_blocks_free == 16  # everything reclaimed
        assert st.kv_bytes_in_use_peak > 0
    finally:
        paged.stop()
    for i, s in enumerate(specs):
        off = np.asarray(autoregressive_generate(
            trainer, state, np.asarray([s["prompt"]], np.int32),
            s["new"], temperature=s["temperature"], seed=s["seed"],
            use_cache=True,
        ))[0]
        assert list(off) == paged_results[i], (i, s)


def test_paged_out_of_blocks_is_backpressure_not_crash(rig):
    """A block budget that fits ~one request at a time: excess
    requests WAIT (admission backpressure via the fit predicate) and
    complete serially as completions free blocks — nothing crashes,
    nothing is rejected below queue capacity, and the pool drains back
    to whole."""
    trainer, state = rig
    # 4 blocks x 4 tokens = 16 cache rows total; each request needs
    # 1 + 12 - 1 = 12 rows (3 blocks), so no two can overlap fully
    server = _start(
        trainer, state, num_slots=3, queue_capacity=8,
        kv_block_size=4, kv_num_blocks=4,
    )
    try:
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        outcomes = {}

        def call(i):
            r = stub.generate(
                pb.GenerateRequest(prompt=[1 + i], max_new_tokens=12),
                timeout=120,
            )
            outcomes[i] = list(r.tokens)

        threads = [
            threading.Thread(target=call, args=(i,)) for i in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert len(outcomes) == 3
        for i in range(3):
            off = np.asarray(autoregressive_generate(
                trainer, state, np.asarray([[1 + i]], np.int32), 12,
                use_cache=True,
            ))[0]
            assert list(off) == outcomes[i]
        st = stub.server_status(pb.ServerStatusRequest(), timeout=10)
        assert st.completed == 3 and st.rejected == 0
        assert st.kv_blocks_free == st.kv_blocks_total == 4
        # a request larger than the WHOLE budget is invalid, fast
        import grpc

        with pytest.raises(grpc.RpcError) as e:
            stub.generate(
                pb.GenerateRequest(prompt=[1, 2, 3], max_new_tokens=15),
                timeout=30,
            )
        assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    finally:
        server.stop()


def test_paged_blocks_reclaimed_on_deadline_eviction(rig):
    """evict_expired must return a mid-decode casualty's blocks to the
    free list (reclamation on evict), and later requests must reuse
    them correctly."""
    from elasticdl_tpu.serving.admission import ServingRequest
    from elasticdl_tpu.serving.engine import (
        PagedContinuousBatchingEngine,
    )

    trainer, state = rig
    eng = PagedContinuousBatchingEngine(
        trainer, state, num_slots=2, block_size=4, num_blocks=6,
    )
    doomed = ServingRequest([1, 2], 10, deadline_ms=1)
    eng.insert(doomed)
    eng.step()
    assert eng.kv.allocator.blocks_in_use() > 0
    evicted = eng.evict_expired(now=doomed.deadline + 1.0)
    assert evicted == [doomed]
    assert eng.kv.allocator.blocks_in_use() == 0
    assert eng.kv.allocator.num_free() == 6
    assert (eng.kv.tables == -1).all()
    # the freed blocks serve a fresh request, token-exact vs offline
    fresh = ServingRequest([3, 4], 6)
    eng.insert(fresh)
    while eng.active_count():
        eng.step()
    off = np.asarray(autoregressive_generate(
        trainer, state, np.asarray([[3, 4]], np.int32), 6,
        use_cache=True,
    ))[0]
    assert list(off[2:]) == fresh.generated
    assert eng.kv.allocator.num_free() == 6


def test_serving_telemetry_event_file_written(rig, tmp_path):
    trainer, state = rig
    server = _start(
        trainer, state, num_slots=2, queue_capacity=8,
        telemetry_dir=str(tmp_path), telemetry_flush_every=1,
    )
    try:
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        stub.generate(
            pb.GenerateRequest(prompt=[1, 2], max_new_tokens=4),
            timeout=60,
        )
    finally:
        server.stop()
    files = [f for f in os.listdir(str(tmp_path))
             if f.startswith("events.out.tfevents")]
    assert files, os.listdir(str(tmp_path))
    assert os.path.getsize(os.path.join(str(tmp_path), files[0])) > 0


def _run_paged_int8_shared_spec_32way():
    """Body of the int8-arena acceptance pin, shared by the scan-path
    test and the fused-kernel variant below (which reroutes
    paged_decode_attention before calling this)."""
    int8_params = PARAMS + "; kv_cache_dtype='int8'"
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        load_model_spec_from_module(zoo), mesh=mesh,
        model_params=int8_params,
    )
    state = _state(trainer)
    draft_trainer = _trainer(seed=321)  # float draft, mismatched
    draft_state = _state(draft_trainer)

    systems = [[1, 2, 3, 4], [5, 6, 7, 1, 2, 3, 4, 5]]
    specs = []
    for i in range(32):
        prompt = list(systems[i % 2]) + ([1 + i % 3] if i % 4 else [])
        specs.append({"prompt": prompt, "new": 3 + i % 5})

    cfg = ServingConfig(
        num_slots=6, queue_capacity=64,
        kv_block_size=4, kv_num_blocks=24, kv_shared=True, draft_k=2,
    )
    server = GenerationServer(
        trainer, state, cfg, draft=(draft_trainer, draft_state)
    ).start()
    try:
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        results, errors = {}, {}

        def call(i, s):
            try:
                r = stub.generate(
                    pb.GenerateRequest(
                        prompt=s["prompt"], max_new_tokens=s["new"],
                    ),
                    timeout=120,
                )
                results[i] = list(r.tokens)
            except Exception as e:  # noqa: BLE001
                errors[i] = e

        threads = [
            threading.Thread(target=call, args=(i, s))
            for i, s in enumerate(specs)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert len(results) == 32
        st = stub.server_status(pb.ServerStatusRequest(), timeout=10)
        assert st.kv_paged and st.kv_shared
        assert st.kv_cache_dtype == "int8"
        assert st.prefix_hit_tokens > 0  # sharing engaged over int8
        assert st.draft_k == 2 and st.draft_proposed > 0
        assert st.max_active_slots > 1
        # clean post-drain ledger with scale leaves in the arenas
        assert st.kv_blocks_free == st.kv_blocks_total == 24
        assert st.completed == 32
        # the byte accounting counts TRUE arena bytes (int8 rows + f32
        # scales): strictly between the pure-int8 and pure-f32 figures
        eng = server.engine
        rows = eng.kv.num_blocks * eng.kv.block_size
        hkv = trainer.model.num_kv_heads or trainer.model.num_heads
        d = trainer.model.embed_dim // trainer.model.num_heads
        layers = trainer.model.num_layers
        expect = rows * layers * 2 * hkv * (d + 4)  # int8 rows + scales
        assert st.kv_bytes_total == expect
    finally:
        server.stop()

    for i, s in enumerate(specs):
        off = np.asarray(autoregressive_generate(
            trainer, state, np.asarray([s["prompt"]], np.int32),
            s["new"], use_cache=True,
        ))[0]
        assert list(off) == results[i], (i, s)


def test_paged_int8_shared_spec_matches_offline_int8_32way():
    """The int8-arena acceptance pin: 32 concurrent GREEDY requests
    drawn from a small system-prompt pool against a paged + shared +
    speculative server whose arenas are INT8 (kv_cache_dtype='int8',
    mismatched draft so rollback exercises) — every token stream must
    equal offline `autoregressive_generate(use_cache=True)` on the
    SAME int8 model (the int8 dense oracle: same quantizer, so parity
    carries no quantization slack). The post-drain ledger must be
    clean with scale leaves in the arenas, and ServerStatus must
    advertise the format."""
    _run_paged_int8_shared_spec_32way()


def test_paged_int8_32way_token_exact_with_fused_kernel(monkeypatch):
    """Serving-level pin for the fused paged decode kernel: the SAME
    32-way paged + shared + spec + int8 battery, but with
    paged_decode_attention routed through _paged_decode_fused (forced
    on via use_paged_kernel; interpret_mode() makes the Pallas call
    interpret on CPU, so the real kernel body runs inside the jitted
    serving step). Token streams must stay EXACTLY equal to the dense
    int8 offline oracle — the kernel may differ from the scan only in
    fp reduction order, and greedy argmax over a real vocab gap
    doesn't flip on that. The spy proves the kernel actually traced
    into the serving step rather than silently falling back."""
    import elasticdl_tpu.ops.attention as attn_mod

    calls = {"n": 0}
    real = attn_mod._paged_decode_fused

    def spy(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(attn_mod, "_paged_decode_fused", spy)
    monkeypatch.setattr(attn_mod, "use_paged_kernel", lambda: True)
    _run_paged_int8_shared_spec_32way()
    assert calls["n"] > 0, "fused kernel never engaged in the server"


def test_host_tier_spill_revive_matches_offline_int8_32way():
    """The tiered-KV acceptance pin: 32 concurrent GREEDY requests
    over a small system-prompt pool against a paged + shared +
    speculative + INT8 server whose device pool is deliberately too
    small for the prefix working set plus the active seats — chains
    are forced to EVICT mid-run, spill to the host tier, and revive by
    upload — and every token stream must still equal offline
    `autoregressive_generate(use_cache=True)` on the same int8 model.
    The drill-grade ledger must drain clean in BOTH tiers, the host
    tier must never exceed its byte budget, and ServerStatus must show
    the spill machinery actually engaged (revive_uploads > 0)."""
    int8_params = PARAMS + "; kv_cache_dtype='int8'"
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        load_model_spec_from_module(zoo), mesh=mesh,
        model_params=int8_params,
    )
    state = _state(trainer)
    draft_trainer = _trainer(seed=321)  # float draft, mismatched
    draft_state = _state(draft_trainer)

    systems = [[1, 2, 3, 4], [5, 6, 7, 1, 2, 3, 4, 5]]
    specs = []
    for i in range(32):
        prompt = list(systems[i % 2]) + ([1 + i % 3] if i % 4 else [])
        specs.append({"prompt": prompt, "new": 3 + i % 5})

    # 8 blocks x 4 tokens: two concurrent seats of the long-prompt
    # shape (4 blocks committed each) consume the WHOLE pool, so the
    # reclaimable prefix chains (3 blocks) are forced to evict — and
    # spill — mid-run, then revive when the next wave re-matches them;
    # the host budget holds the whole working set
    host_budget = 1 << 20
    cfg = ServingConfig(
        num_slots=4, queue_capacity=64,
        kv_block_size=4, kv_num_blocks=8, kv_shared=True, draft_k=2,
        kv_host_bytes=host_budget,
    )
    server = GenerationServer(
        trainer, state, cfg, draft=(draft_trainer, draft_state)
    ).start()
    try:
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        results, errors = {}, {}

        def call(i, s):
            try:
                r = stub.generate(
                    pb.GenerateRequest(
                        prompt=s["prompt"], max_new_tokens=s["new"],
                    ),
                    timeout=120,
                )
                results[i] = list(r.tokens)
            except Exception as e:  # noqa: BLE001
                errors[i] = e

        threads = [
            threading.Thread(target=call, args=(i, s))
            for i, s in enumerate(specs)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert len(results) == 32
        st = stub.server_status(pb.ServerStatusRequest(), timeout=10)
        assert st.kv_paged and st.kv_shared
        assert st.kv_cache_dtype == "int8"
        assert st.completed == 32
        # the spill machinery demonstrably engaged mid-run: chains
        # were demoted under pressure AND came back by upload
        assert st.revive_uploads > 0
        assert st.prefill_tokens_revived > 0
        assert st.prefix_hit_tokens >= st.prefill_tokens_revived
        # the host tier never exceeded its budget (engine-side pin —
        # the peak tracks every spill, not just the final state)
        eng = server.engine
        assert eng.kv.host_blocks_peak <= eng.kv.allocator.host_blocks
        assert (eng.kv.host_blocks_peak * eng.kv.block_bytes
                <= host_budget)
        assert eng.kv.allocator.spills > 0
        # clean two-tier post-drain ledger: every device block free or
        # cached, no leaked refcount; spilled entries all accounted
        assert st.kv_blocks_free == st.kv_blocks_total == 8
        assert (eng.kv.allocator.num_spilled()
                == len(eng.kv._host_rows))
        assert st.kv_host_blocks == eng.kv.allocator.num_spilled()
    finally:
        server.stop()

    for i, s in enumerate(specs):
        off = np.asarray(autoregressive_generate(
            trainer, state, np.asarray([s["prompt"]], np.int32),
            s["new"], use_cache=True,
        ))[0]
        assert list(off) == results[i], (i, s)


def test_host_tier_reload_flushes_both_tiers(rig, tmp_path):
    """A hot reload must flush the host tier too: spilled chains were
    computed under superseded params and can never seat (or revive
    for) a new request."""
    from elasticdl_tpu.serving.admission import ServingRequest
    from elasticdl_tpu.serving.engine import (
        PagedContinuousBatchingEngine,
    )

    trainer, state = rig
    eng = PagedContinuousBatchingEngine(
        trainer, state, num_slots=2, block_size=4, num_blocks=4,
        host_bytes=1 << 20,
    )
    # seat + index a 2-block prompt chain, then evict it under
    # pressure so it spills
    prompt = [1, 2, 3, 4, 5, 6, 7, 1]
    r0 = ServingRequest(prompt, 2)
    eng.insert(r0)
    while eng.active_count():
        eng.step()
    assert eng.kv.allocator.num_cached() == 2
    r1 = ServingRequest([2, 3], 14)  # commits all 4 blocks
    eng.insert(r1)
    while eng.active_count():
        eng.step()
    # decode growth drew the cached chain out of the device tier:
    # both indexed blocks spilled instead of being forgotten
    assert eng.kv.allocator.num_spilled() == 2
    # reload: both tiers flush
    eng.set_params(state, version=1)
    assert eng.kv.allocator.num_spilled() == 0
    assert eng.kv.host_bytes_in_use() == 0
    assert eng.kv.allocator.match_prefix(prompt) == []
    # and the device ledger is whole again
    assert eng.kv.allocator.num_free() == 4


def test_shared_prefix_speculative_matches_offline_greedy_32way(rig):
    """The acceptance pin for prefix sharing + speculative decode:
    32 concurrent GREEDY requests drawn from a small system-prompt
    pool (so prefixes dedupe and full-prompt matches CoW) against a
    paged+shared server running a MISMATCHED draft (rollback actually
    exercised) — every token stream must equal offline decode's.
    Server status must show the sharing and draft
    machinery actually engaged."""
    trainer, state = rig
    draft_trainer = _trainer(seed=321)
    draft_state = _state(draft_trainer)

    # prompts share 4- and 8-token prefixes (block_size 4): pool of 2
    # system prompts + tiny per-request suffixes
    systems = [[1, 2, 3, 4], [5, 6, 7, 1, 2, 3, 4, 5]]
    specs = []
    for i in range(32):
        prompt = list(systems[i % 2]) + ([1 + i % 3] if i % 4 else [])
        specs.append({"prompt": prompt, "new": 3 + i % 5})

    def collect(server):
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        results, errors = {}, {}

        def call(i, s):
            try:
                r = stub.generate(
                    pb.GenerateRequest(
                        prompt=s["prompt"], max_new_tokens=s["new"],
                    ),
                    timeout=120,
                )
                results[i] = list(r.tokens)
            except Exception as e:  # noqa: BLE001
                errors[i] = e

        threads = [
            threading.Thread(target=call, args=(i, s))
            for i, s in enumerate(specs)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert len(results) == 32
        return results

    cfg = ServingConfig(
        num_slots=6, queue_capacity=64,
        kv_block_size=4, kv_num_blocks=24, kv_shared=True, draft_k=2,
    )
    shared = GenerationServer(
        trainer, state, cfg, draft=(draft_trainer, draft_state)
    ).start()
    try:
        shared_results = collect(shared)
        stub = ServingStub(build_channel("localhost:%d" % shared.port))
        st = stub.server_status(pb.ServerStatusRequest(), timeout=10)
        assert st.kv_paged and st.kv_shared
        assert st.prefix_hit_tokens > 0  # sharing actually engaged
        assert st.draft_k == 2 and st.draft_proposed > 0
        assert st.draft_accepted >= 0
        assert st.max_active_slots > 1
        # clean post-drain ledger: every block free or cached, none
        # leaked by a refcount
        assert st.kv_blocks_free == st.kv_blocks_total == 24
        assert st.completed == 32
    finally:
        shared.stop()

    for i, s in enumerate(specs):
        off = np.asarray(autoregressive_generate(
            trainer, state, np.asarray([s["prompt"]], np.int32),
            s["new"], use_cache=True,
        ))[0]
        assert list(off) == shared_results[i], (i, s)


def test_fused_spec_step_matches_offline_int8_32way_with_phases():
    """The metrics-plane parity pin, on the one program there is: the
    fused speculative step's token streams equal the offline int8
    oracle at 32-way paged + shared + speculative + int8 concurrency
    (mismatched draft, so rollback exercises the verify path) while
    the phase spans record. Also pins that every phase of the
    speculative tick and of seating actually recorded, and that the
    /metrics exposition of a live replica parses through the
    INDEPENDENT text-format parser with the phase histogram present —
    the acceptance criterion's "live replica serves Prometheus
    text"."""
    import urllib.request

    from elasticdl_tpu.observability import tracing
    from elasticdl_tpu.observability.promparse import (
        parse_prometheus_text,
    )

    tracing.recorder().clear_phases()

    int8_params = PARAMS + "; kv_cache_dtype='int8'"
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        load_model_spec_from_module(zoo), mesh=mesh,
        model_params=int8_params,
    )
    state = _state(trainer)
    draft_trainer = _trainer(seed=321)  # float draft, mismatched
    draft_state = _state(draft_trainer)

    systems = [[1, 2, 3, 4], [5, 6, 7, 1, 2, 3, 4, 5]]
    specs = []
    for i in range(32):
        prompt = list(systems[i % 2]) + ([1 + i % 3] if i % 4 else [])
        specs.append({"prompt": prompt, "new": 3 + i % 5})

    cfg = ServingConfig(
        num_slots=6, queue_capacity=64,
        kv_block_size=4, kv_num_blocks=24, kv_shared=True, draft_k=2,
        metrics_port=0,
    )
    server = GenerationServer(
        trainer, state, cfg, draft=(draft_trainer, draft_state)
    ).start()
    try:
        stub = ServingStub(build_channel("localhost:%d" % server.port))
        results, errors = {}, {}

        def call(i, s):
            try:
                r = stub.generate(
                    pb.GenerateRequest(
                        prompt=s["prompt"], max_new_tokens=s["new"],
                    ),
                    timeout=120,
                )
                results[i] = list(r.tokens)
            except Exception as e:  # noqa: BLE001
                errors[i] = e

        threads = [
            threading.Thread(target=call, args=(i, s))
            for i, s in enumerate(specs)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert len(results) == 32
        st = stub.server_status(pb.ServerStatusRequest(), timeout=10)
        assert st.kv_cache_dtype == "int8"
        assert st.draft_proposed > 0
        assert st.prefix_hit_tokens > 0
        # the windowed hit-rate signal is live and sane
        assert 0.0 <= st.prefix_hit_rate_window <= 1.0
        assert st.kv_blocks_free == st.kv_blocks_total == 24

        snap = tracing.recorder().phase_snapshot()
        # every phase the speculative+shared workload exercises
        for phase in ("prefill", "suffix_tile", "draft", "prompt_write",
                      "tick.ensure", "tick.upload", "tick.dispatch",
                      "tick.fetch", "tick.commit", "tick.stream"):
            assert phase in snap and snap[phase]["count"] > 0, (
                phase, snap,
            )
        assert tracing.recorder().phases_dropped == 0

        text = urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % server.metrics.port,
            timeout=10,
        ).read().decode("utf-8")
        fams = parse_prometheus_text(text)  # raises on malformation
        assert "edl_serving_phase_ms" in fams
        assert "edl_serving_ttft_ms" in fams
        assert "edl_serving_completed_total" in fams
        completed = [
            v for n, lab, v in
            fams["edl_serving_completed_total"]["samples"]
        ]
        assert completed == [32]
    finally:
        server.stop()

    for i, s in enumerate(specs):
        off = np.asarray(autoregressive_generate(
            trainer, state, np.asarray([s["prompt"]], np.int32),
            s["new"], use_cache=True,
        ))[0]
        assert list(off) == results[i], (i, s)
