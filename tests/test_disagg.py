"""Disaggregated prefill/decode serving unit tests (tier-1).

The handoff plumbing from serving/disagg.py without a fleet: the wire
codec (chain_to_proto / proto_to_blocks round-trips a real pool export
byte-exactly and refuses mismatched arena layouts), the
HandoffCoordinator's three obligations against fake stubs (export
warms then exports, empty exports and refused imports raise
HandoffError, abort swallows transport errors), and the chunked
prefill scheduler on a real CPU engine: a long prompt advances tile by
tile across calls, stays token-exact against the offline decoder, a
full-prompt prefix match collapses to zero tiles, and an aborted job
returns every block. Fleet-level behavior (router pairing, two-pool
ledgers, the 32-way handoff battery) lives on the drills shard."""

import numpy as np
import pytest

from elasticdl_tpu.proto import elasticdl_pb2 as pb
from elasticdl_tpu.serving.disagg import (
    HandoffCoordinator,
    HandoffError,
    chain_to_proto,
    proto_to_blocks,
)

# --------------------------------------------------------------- codec


def _int8_pool(num_blocks=4, block_size=4, leaves=("k", "k_scale")):
    import jax.numpy as jnp

    from elasticdl_tpu.serving.kv_pool import PagedKVPool

    hkv, d, cache_len = 2, 8, 16
    shapes = {
        "k": jnp.zeros((1, hkv, cache_len, d), jnp.int8),
        "k_scale": jnp.zeros((1, hkv, cache_len, 1), jnp.float32),
        "pos": jnp.zeros((), jnp.int32),
    }
    shapes = {k: v for k, v in shapes.items()
              if k == "pos" or k in leaves}
    return PagedKVPool(shapes, cache_len, num_slots=2,
                       num_blocks=num_blocks, block_size=block_size,
                       share_prefix=True)


def _exported_chain(pool, prompt):
    import jax.numpy as jnp

    rs = np.random.RandomState(17)
    pool.seat(0, prompt, len(prompt))
    arenas = {}
    for name, leaf in pool.pools.items():
        if getattr(leaf, "ndim", 0) == 4:
            arenas[name] = jnp.asarray(
                rs.randint(-127, 128, size=leaf.shape).astype(leaf.dtype)
            )
    pool.pools = dict(pool.pools, **arenas)
    pool.register_prefix(0, prompt)
    pool.release(0)
    return pool.export_chain(prompt)


def test_codec_round_trips_pool_export_byte_exactly():
    """chain_to_proto -> proto_to_blocks over a real int8+scale export
    must reproduce every row leaf byte-for-byte in import_chain's
    argument shape, and the decoded payload must import cleanly into a
    same-geometry pool."""
    src = _int8_pool()
    prompt = list(range(100, 116))
    chain = _exported_chain(src, prompt)
    assert len(chain) == 4

    msg = chain_to_proto(chain, src.block_size, src.leaf_dtypes(),
                         "xfer-t")
    assert msg.transfer_id == "xfer-t"
    assert msg.block_size == 4
    assert list(msg.leaf_dtypes) == ["int8", "float32"]
    assert len(msg.blocks) == 4

    dst = _int8_pool()
    blocks, dtypes = proto_to_blocks(msg, dst)
    assert dtypes == ["int8", "float32"]
    for (toks, rows), (otoks, orows) in zip(blocks, chain):
        assert tuple(toks) == tuple(otoks)
        for r, o in zip(rows, orows):
            assert r.dtype == o.dtype
            np.testing.assert_array_equal(r, o)
    assert dst.import_chain(blocks, leaf_dtypes=dtypes) == (4, 16)
    assert dst.seat(0, prompt, 16) == 16
    dst.release(0)


def test_codec_refuses_mismatched_arena_layouts():
    """Every geometry mismatch must surface as ValueError BEFORE any
    import: block_size, leaf count (payload vs pool), and a malformed
    block's leaf list."""
    src = _int8_pool()
    chain = _exported_chain(src, list(range(100, 116)))
    msg = chain_to_proto(chain, src.block_size, src.leaf_dtypes(),
                         "xfer-m")

    with pytest.raises(ValueError, match="block_size"):
        proto_to_blocks(msg, _int8_pool(block_size=8, num_blocks=2))
    with pytest.raises(ValueError, match="leaves"):
        proto_to_blocks(msg, _int8_pool(leaves=("k",)))
    bad = pb.TransferChainRequest()
    bad.CopyFrom(msg)
    del bad.blocks[0].leaves[-1]
    with pytest.raises(ValueError, match="leaves"):
        proto_to_blocks(bad, _int8_pool())


# --------------------------------------------------- coordinator units


class _FakeStub(object):
    """ServingStub surface the coordinator drives, scripted."""

    def __init__(self, payload=None, resp=None, abort_exc=None):
        self.payload = payload
        self.resp = resp
        self.abort_exc = abort_exc
        self.calls = []

    def generate(self, request, timeout=None):
        self.calls.append(("generate", request))
        return pb.GenerateResponse(tokens=list(request.prompt) + [0])

    def export_chain(self, request, timeout=None):
        self.calls.append(("export_chain", request))
        return self.payload

    def transfer_chain(self, payload, timeout=None):
        self.calls.append(("transfer_chain", payload))
        return self.resp

    def abort_transfer(self, request, timeout=None):
        self.calls.append(("abort_transfer", request))
        if self.abort_exc is not None:
            raise self.abort_exc
        return pb.TransferChainResponse(ok=True)


class _FakeRep(object):
    def __init__(self, stub):
        self.address = "fake:0"
        self.stub = stub


class _Req(object):
    def __init__(self, prompt):
        self.prompt = prompt
        self.temperature = 0.0
        self.seed = 7


def _payload(nblocks):
    return pb.TransferChainRequest(
        transfer_id="xfer-f", block_size=4, leaf_dtypes=["int8"],
        blocks=[pb.KvChainBlock(tokens=[1, 2, 3, 4], leaves=[b"x"])
                for _ in range(nblocks)],
    )


def test_coordinator_export_warms_then_exports():
    """export_chain runs ONE prefill_only generate (the warm) before
    the export RPC, forwards the request's sampling knobs, and returns
    the payload."""
    stub = _FakeStub(payload=_payload(2))
    co = HandoffCoordinator()
    payload = co.export_chain(_FakeRep(stub), _Req([1, 2, 3, 4, 5]),
                              "xfer-f")
    assert len(payload.blocks) == 2
    assert [c[0] for c in stub.calls] == ["generate", "export_chain"]
    gen = stub.calls[0][1]
    assert gen.prefill_only and gen.max_new_tokens == 1
    assert list(gen.prompt) == [1, 2, 3, 4, 5] and gen.seed == 7
    assert stub.calls[1][1].transfer_id == "xfer-f"


def test_coordinator_raises_on_empty_export():
    stub = _FakeStub(payload=_payload(0))
    with pytest.raises(HandoffError, match="empty chain"):
        HandoffCoordinator().export_chain(
            _FakeRep(stub), _Req([1, 2]), "xfer-f"
        )


def test_coordinator_import_raises_on_refusal_or_no_coverage():
    """ok=False (arena mismatch) and blocks=0 (nothing of the chain
    landed) both raise; resolved coverage > 0 succeeds even when the
    import was fully deduped on the far side."""
    co = HandoffCoordinator()
    refused = pb.TransferChainResponse(ok=False, error="dtype")
    with pytest.raises(HandoffError, match="dtype"):
        co.import_chain(_FakeRep(_FakeStub(resp=refused)),
                        _payload(1))
    empty = pb.TransferChainResponse(ok=True, blocks=0)
    with pytest.raises(HandoffError, match="no blocks"):
        co.import_chain(_FakeRep(_FakeStub(resp=empty)), _payload(1))
    warm = pb.TransferChainResponse(ok=True, blocks=3, tokens=12)
    resp = co.import_chain(_FakeRep(_FakeStub(resp=warm)),
                           _payload(1))
    assert resp.blocks == 3


def test_coordinator_abort_is_best_effort_accounting():
    """abort_transfer swallows transport errors — exports hold no pool
    references, so a lost abort leaks nothing."""
    stub = _FakeStub(abort_exc=RuntimeError("replica gone"))
    HandoffCoordinator().abort_transfer(_FakeRep(stub), "xfer-f")
    assert [c[0] for c in stub.calls] == ["abort_transfer"]


def test_transfer_ids_are_unique_across_coordinators():
    a, b = HandoffCoordinator(), HandoffCoordinator()
    ids = [a.new_transfer_id() for _ in range(3)]
    ids += [b.new_transfer_id() for _ in range(3)]
    assert len(set(ids)) == 6


# ------------------------------------------------------ chunked prefill


@pytest.fixture(scope="module")
def rig():
    import jax

    from elasticdl_tpu.common.model_utils import (
        load_model_spec_from_module,
    )
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.training.trainer import Trainer
    from model_zoo.transformer_lm import transformer_lm as zoo

    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        load_model_spec_from_module(zoo), mesh=mesh,
        model_params=("vocab_size=8; seq_len=16; embed_dim=32; "
                      "num_heads=2; num_layers=1"),
    )
    toks = (np.arange(17)[None, :] % 8).astype(np.int32)
    state = trainer.init_state(({"tokens": toks[:, :-1]},
                                toks[:, 1:]))
    return trainer, state


def _chunked_engine(rig, chunk=2, num_blocks=12):
    from elasticdl_tpu.serving.engine import (
        PagedContinuousBatchingEngine,
    )

    trainer, state = rig
    return PagedContinuousBatchingEngine(
        trainer, state, num_slots=2, block_size=4,
        num_blocks=num_blocks, prefill_chunk_tokens=chunk,
    )


def _run_chunked(eng, request):
    job = eng.begin_insert(request)
    tiles = 0
    while not job.done():
        tiles += 1
        eng.advance_prefill(job)
    while not job.finished and request in eng.active_requests():
        eng.step()
    return job, tiles


def test_chunked_prefill_is_token_exact_and_tiled(rig):
    """A 7-token prompt under a 2-token chunk budget must take ceil
    tiles (no tile runs the whole prompt) and still produce the exact
    offline token stream — tile boundaries may not perturb sampling."""
    from elasticdl_tpu.api.generation import autoregressive_generate
    from elasticdl_tpu.serving.admission import ServingRequest

    trainer, state = rig
    eng = _chunked_engine(rig, chunk=2)
    prompt = [1, 2, 3, 4, 5, 6, 7]
    req = ServingRequest(prompt, 5)
    job, tiles = _run_chunked(eng, req)
    assert tiles == 4 and job.tiles == 4  # ceil(7 / 2)
    off = np.asarray(autoregressive_generate(
        trainer, state, np.asarray([prompt], np.int32), 5,
        use_cache=True,
    ))[0]
    assert req.generated == list(off[len(prompt):])
    # the chain the first request registered answers the full-block
    # prefix (4 of 7 tokens): the repeat prompt tiles only its tail
    req2 = ServingRequest(prompt, 3)
    job2, tiles2 = _run_chunked(eng, req2)
    assert tiles2 == 2  # ceil((7 - 4) / 2)
    assert req2.generated == list(off[len(prompt):len(prompt) + 3])
    # a block-ALIGNED repeat prompt collapses to ZERO tiles: the
    # full-prompt match IS the prefill
    aligned = [1, 2, 3, 4, 5, 6, 7, 0]
    reqa = ServingRequest(aligned, 3)
    ja, ta = _run_chunked(eng, reqa)
    assert ta == 2  # shares [1,2,3,4]; ceil((8 - 4) / 2) for the tail
    reqb = ServingRequest(aligned, 3)
    jb = eng.begin_insert(reqb)
    assert jb.done() and jb.tiles == 0
    while reqb in eng.active_requests():
        eng.step()
    assert reqb.generated == reqa.generated


def test_chunked_prefill_abort_returns_every_block(rig):
    """abort_prefill between tiles must release the seat: the ledger
    returns to whole (shared ancestors excepted) and the slot frees."""
    from elasticdl_tpu.serving.admission import ServingRequest

    eng = _chunked_engine(rig, chunk=2)
    a = eng.kv.allocator
    whole = a.num_free() + a.num_cached()
    req = ServingRequest([7, 6, 5, 4, 3, 2, 1], 5)
    job = eng.begin_insert(req)
    assert not job.done()
    eng.advance_prefill(job)  # one tile in flight
    assert eng.prefilling_count() == 1
    assert a.blocks_in_use() > 0
    eng.abort_prefill(job)
    assert eng.prefilling_count() == 0
    assert a.blocks_in_use() == 0
    assert a.num_free() + a.num_cached() == whole
    assert eng.free_slots() == [0, 1]
