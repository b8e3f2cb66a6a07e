"""Disaggregated prefill/decode serving: KV chain handoff plumbing.

The two serving phases want opposite machines: prefill is compute-bound
(one long arithmetic burst over the whole prompt), decode is
memory-bound (thousands of tiny steps walking the KV cache). A unified
replica sizes for both and wastes one. This module is the glue that
lets a fleet split instead:

* replicas advertise a ROLE (``prefill`` / ``decode`` / ``unified``,
  ServingConfig.role -> ServerStatus.role -> ReplicaStatus.role): the
  router keeps ``prefill`` replicas out of normal rotation and targets
  them only for cache warming;
* a dedicated prefill replica runs a prompt to completion via
  ``GenerateRequest.prefill_only`` — seat, prefill, register the chain,
  release — leaving the chain parked refcount-0 cached (matchable,
  exportable, reclaimable);
* the finished chain moves as a DENSE BYTE COPY: ``export_chain``
  gathers the chain's blocks (int8 rows + f32 scale leaves, the same
  tree-generic gather the host spill tier reads through) into a
  ``TransferChainRequest``; ``transfer_chain`` on the decode side lands
  them in one batched upload into fresh blocks re-keyed into the
  content-addressed trie. The next generate with that prompt seats by
  prefix hit — sharing, CoW and speculative decode compose unchanged,
  so the handoff is token-exact by the same argument prefix sharing is.

HandoffCoordinator is the router-side orchestrator and the EDL501
obligation receiver: every ``export_chain`` must settle through
``import_chain`` (success) or ``abort_transfer`` (failure accounting)
on the same coordinator — the lint rule (analysis/resource_rules.py)
holds call sites to that shape. Exports hold no pool references
(chains park refcount-0), so a coordinator or replica crash mid-
transfer leaks nothing; abort is the failure's RECORD, not a resource
release.

Wire codec: rows travel as raw little-endian bytes per arena leaf
(``KvChainBlock.leaves``, jax.tree.leaves order) plus the dtype list,
so the importer can refuse a mismatched arena layout cheaply — a
mismatch downgrades to a plain cold dispatch, never an error the
client sees.
"""

import itertools
import threading

import numpy as np

from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.proto import elasticdl_pb2 as pb


class HandoffError(Exception):
    """A handoff leg failed (prefill generate, export, or import). The
    coordinator's caller falls back to a plain dispatch — a failed
    handoff costs the warm-start, never the request."""


def chain_to_proto(chain, block_size, leaf_dtypes, transfer_id):
    """Serialize a pool export (``[(block token tuple, [np rows per
    leaf])]``, kv_pool.export_chain's shape) into the wire payload the
    decode side imports verbatim."""
    return pb.TransferChainRequest(
        transfer_id=transfer_id,
        block_size=block_size,
        leaf_dtypes=list(leaf_dtypes),
        blocks=[
            pb.KvChainBlock(
                tokens=list(toks),
                leaves=[np.ascontiguousarray(r).tobytes()
                        for r in rows],
            )
            for toks, rows in chain
        ],
    )


def proto_to_blocks(msg, pool):
    """Decode a TransferChainRequest against the IMPORTING pool's own
    arena geometry: each leaf's bytes reshape to that pool's per-block
    row shape, so a size mismatch (different model dims, different
    block_size) surfaces as a ValueError the servicer downgrades to
    ok=False. Returns ``(blocks, leaf_dtypes)`` in import_chain's
    argument shape."""
    shapes = pool.row_shapes  # recorded at construction: no buffer read
    dtypes = list(msg.leaf_dtypes)
    if len(dtypes) != len(shapes):
        raise ValueError(
            "chain carries %d row leaves, this pool has %d"
            % (len(dtypes), len(shapes))
        )
    if msg.block_size != pool.block_size:
        raise ValueError(
            "chain block_size %d does not match this pool's %d"
            % (msg.block_size, pool.block_size)
        )
    blocks = []
    for blk in msg.blocks:
        if len(blk.leaves) != len(shapes):
            raise ValueError(
                "chain block carries %d leaves, expected %d"
                % (len(blk.leaves), len(shapes))
            )
        rows = [
            np.frombuffer(raw, dtype=dt).reshape(shape)
            for raw, dt, shape in zip(blk.leaves, dtypes, shapes)
        ]
        blocks.append((tuple(blk.tokens), rows))
    return blocks, dtypes


class HandoffCoordinator(object):
    """One prefill->decode handoff, three obligations. The router
    binds this as a local (``disagg = self._disagg``) so edl-lint
    EDL501 can hold every ``disagg.export_chain`` to a same-receiver
    ``disagg.import_chain`` or ``disagg.abort_transfer`` on all paths.

    Transport-agnostic like Router: replicas only need the ServingStub
    surface (generate / export_chain / transfer_chain / abort_transfer,
    each taking ``timeout=``)."""

    _ids = itertools.count(1)
    _ids_lock = threading.Lock()

    def __init__(self, timeout_secs=10.0, clock=None):
        self.timeout_secs = float(timeout_secs)

    def new_transfer_id(self):
        with HandoffCoordinator._ids_lock:
            return "xfer-%d" % next(HandoffCoordinator._ids)

    def export_chain(self, rep, request, transfer_id, timeout=None):
        """Warm the prefill replica and export the chain: one
        prefill_only generate (seat, prefill, register, release — the
        sampled token is discarded; the decode side re-derives it from
        the shared chain, which is what makes the handoff token-exact)
        followed by the export RPC. Returns the transfer payload.
        Opens the EDL501 obligation: settle with import_chain or
        abort_transfer."""
        timeout = self.timeout_secs if timeout is None else timeout
        rep.stub.generate(
            pb.GenerateRequest(
                prompt=list(request.prompt),
                max_new_tokens=1,
                temperature=request.temperature,
                seed=request.seed,
                prefill_only=True,
            ),
            timeout=timeout,
        )
        payload = rep.stub.export_chain(
            pb.ExportChainRequest(
                prompt=list(request.prompt),
                transfer_id=transfer_id,
            ),
            timeout=timeout,
        )
        if not payload.blocks:
            raise HandoffError(
                "prefill replica exported an empty chain"
            )
        return payload

    def import_chain(self, rep, payload, timeout=None):
        """Land an exported chain on the decode replica (the success
        settle). The response's ``blocks`` is the chain's RESOLVED
        coverage on the importer — imported plus already-resident
        levels, so a fully deduped transfer still succeeds (the chain
        is warm either way). Raises HandoffError when the importer
        refused the payload (arena mismatch) or none of the chain
        landed (pool exhausted) so the caller aborts and falls
        back."""
        timeout = self.timeout_secs if timeout is None else timeout
        resp = rep.stub.transfer_chain(payload, timeout=timeout)
        if not resp.ok or not resp.blocks:
            raise HandoffError(
                "decode replica refused chain import: %s"
                % (resp.error or "no blocks imported",)
            )
        return resp

    def abort_transfer(self, rep, transfer_id, timeout=None):
        """Close a failed handoff's obligation on the exporter (the
        failure settle). Best-effort: the exporter holds no references
        for this transfer, so a lost abort leaks nothing — it only
        costs the failure a ledger entry."""
        timeout = self.timeout_secs if timeout is None else timeout
        try:
            rep.stub.abort_transfer(
                pb.AbortTransferRequest(transfer_id=transfer_id),
                timeout=timeout,
            )
        except Exception as e:  # noqa: BLE001 - accounting only
            logger.debug("abort_transfer(%s) to %s failed: %r",
                         transfer_id, rep.address, e)
