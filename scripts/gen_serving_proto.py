#!/usr/bin/env python
"""Regenerate elasticdl_pb2.py with the Serving service appended.

The container ships no protoc binary, so (as with the TaskReason
addition before it — see the header of proto/elasticdl_pb2.py) the
descriptor is produced by parsing the CURRENT serialized
FileDescriptorProto, appending the serving messages + service with the
descriptor_pb2 API, and re-serializing. Idempotent: existing serving
entries are replaced, so the script can be rerun after editing the
tables below. Keep proto/elasticdl.proto (the human-readable source of
truth) in sync by hand.

BYTE-DETERMINISTIC: serving message types and services are appended
sorted by name and fields sorted by field number, so the output bytes
depend only on the CONTENT of the tables below — never on their
ordering, dict ordering, or how often the script has run. The edl-lint
proto-drift gate (EDL301, elasticdl_tpu/analysis/proto_rules.py) and
the regen-twice test in tests/test_lint.py rely on this: a flaky byte
diff would turn the CI gate into noise.

Usage: python scripts/gen_serving_proto.py [--check] [--out PATH]
  --check  regenerate in memory and exit 1 on drift, writing nothing
  --out    write somewhere other than the checked-in pb2 (drills)
"""

import argparse
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from google.protobuf import descriptor_pb2  # noqa: E402

PB2_PATH = os.path.join(REPO, "elasticdl_tpu", "proto", "elasticdl_pb2.py")

T = descriptor_pb2.FieldDescriptorProto

# message name -> [(field name, number, type, label[, type_name])]
# (type_name only for TYPE_MESSAGE fields, fully qualified)
_OPT, _REP = T.LABEL_OPTIONAL, T.LABEL_REPEATED
SERVING_MESSAGES = {
    "GenerateRequest": [
        ("prompt", 1, T.TYPE_INT32, _REP),
        ("max_new_tokens", 2, T.TYPE_INT32, _OPT),
        ("temperature", 3, T.TYPE_FLOAT, _OPT),
        ("seed", 4, T.TYPE_INT32, _OPT),
        # relative deadline budget; 0 = no deadline
        ("deadline_ms", 5, T.TYPE_INT64, _OPT),
        # distributed-tracing context (observability/tracing.py): the
        # sender's trace and span ids — a replica parents its serve
        # span under the router's dispatch span, so one request is ONE
        # span tree across processes, hedges and re-dispatches.
        # Empty = untraced sender; the receiver mints a fresh trace.
        ("trace_id", 6, T.TYPE_STRING, _OPT),
        ("parent_span_id", 7, T.TYPE_STRING, _OPT),
        # disaggregated serving (serving/disagg.py): run the prompt to
        # completion as cache-warming only — the chain is seated,
        # registered in the prefix trie and released for export; the
        # single sampled token is NOT the answer (the decode replica
        # re-derives it token-exactly from the shared chain)
        ("prefill_only", 8, T.TYPE_BOOL, _OPT),
    ],
    "GenerateResponse": [
        ("tokens", 1, T.TYPE_INT32, _REP),
        ("model_version", 2, T.TYPE_INT32, _OPT),
    ],
    "TokenChunk": [
        ("tokens", 1, T.TYPE_INT32, _REP),
        ("done", 2, T.TYPE_BOOL, _OPT),
        ("model_version", 3, T.TYPE_INT32, _OPT),
        # a block-diffusion model only (a chunk is a committed block):
        # parallel to `tokens`, the denoising pass that revealed each;
        # empty for every other model
        ("reveal_steps", 4, T.TYPE_INT32, _REP),
    ],
    "ServerStatusRequest": [],
    "ServerStatusResponse": [
        ("queue_depth", 1, T.TYPE_INT32, _OPT),
        ("active_slots", 2, T.TYPE_INT32, _OPT),
        ("num_slots", 3, T.TYPE_INT32, _OPT),
        ("model_version", 4, T.TYPE_INT32, _OPT),
        ("admitted", 5, T.TYPE_INT64, _OPT),
        ("rejected", 6, T.TYPE_INT64, _OPT),
        ("expired", 7, T.TYPE_INT64, _OPT),
        ("completed", 8, T.TYPE_INT64, _OPT),
        ("tokens_generated", 9, T.TYPE_INT64, _OPT),
        ("reloads", 10, T.TYPE_INT64, _OPT),
        ("uptime_secs", 11, T.TYPE_DOUBLE, _OPT),
        ("max_active_slots", 12, T.TYPE_INT32, _OPT),
        # KV-pool memory accounting (serving/kv_pool.py; kv_paged is
        # always true: the block-paged pool is the only layout)
        ("kv_bytes_in_use", 13, T.TYPE_INT64, _OPT),
        ("kv_bytes_total", 14, T.TYPE_INT64, _OPT),
        ("kv_blocks_free", 15, T.TYPE_INT32, _OPT),
        ("kv_blocks_total", 16, T.TYPE_INT32, _OPT),
        ("kv_block_size", 17, T.TYPE_INT32, _OPT),
        ("kv_paged", 18, T.TYPE_BOOL, _OPT),
        ("kv_bytes_in_use_peak", 19, T.TYPE_INT64, _OPT),
        # average KV bytes resident per generated token (sum-over-
        # steps of kv_bytes_in_use / tokens_generated)
        ("kv_bytes_per_token", 20, T.TYPE_DOUBLE, _OPT),
        # drain advertisement: the replica is finishing in-flight work
        # (SIGTERM drain / hot-reload swap) — routers take it out of
        # rotation for NEW requests while existing streams complete
        ("draining", 21, T.TYPE_BOOL, _OPT),
        # recent average time requests spend queued before seating (ms,
        # EWMA) — part of the router's least-loaded signal
        ("queue_wait_ms", 22, T.TYPE_DOUBLE, _OPT),
        # latency percentiles from the shared log-linear histograms
        # (observability/histogram.py) — the same code path the
        # drills compute their client-side percentiles with
        ("ttft_p50_ms", 23, T.TYPE_DOUBLE, _OPT),
        ("ttft_p90_ms", 24, T.TYPE_DOUBLE, _OPT),
        ("ttft_p99_ms", 25, T.TYPE_DOUBLE, _OPT),
        ("queue_wait_p50_ms", 26, T.TYPE_DOUBLE, _OPT),
        ("queue_wait_p90_ms", 27, T.TYPE_DOUBLE, _OPT),
        ("queue_wait_p99_ms", 28, T.TYPE_DOUBLE, _OPT),
        # raw histogram bucket counts (fixed shared bucket scheme,
        # trailing zeros trimmed): mergeable by addition, so the
        # router aggregates its replicas' histograms and reports
        # fleet-wide percentiles without percentile-averaging errors
        ("ttft_hist", 29, T.TYPE_INT64, _REP),
        ("queue_wait_hist", 30, T.TYPE_INT64, _REP),
        # prefix-shared paged pool (serving/kv_pool.py): whether
        # refcounted prefix sharing is on, blocks referenced by >1
        # table right now, refcount-0 blocks held reclaimable by the
        # prefix cache, prompt tokens seated by incref instead of
        # re-prefilling, and copy-on-write faults served
        ("kv_shared", 31, T.TYPE_BOOL, _OPT),
        ("kv_blocks_shared", 32, T.TYPE_INT32, _OPT),
        ("kv_blocks_cached", 33, T.TYPE_INT32, _OPT),
        ("prefix_hit_tokens", 34, T.TYPE_INT64, _OPT),
        ("cow_copies", 35, T.TYPE_INT64, _OPT),
        # speculative decode: tokens drafted per tick (0 = off) and
        # the proposal economy (accept rate = accepted / proposed)
        ("draft_k", 36, T.TYPE_INT32, _OPT),
        ("draft_proposed", 37, T.TYPE_INT64, _OPT),
        ("draft_accepted", 38, T.TYPE_INT64, _OPT),
        # KV arena storage format: "" = compute dtype, "int8" =
        # symmetric per-row int8 with f32 scale arenas. The byte
        # fields above count TRUE arena bytes at each leaf's own
        # dtype (int8 rows + f32 scale leaves), so equal-byte
        # comparisons across formats are honest.
        ("kv_cache_dtype", 39, T.TYPE_STRING, _OPT),
        # tiered host spill (serving/kv_pool.py): evicted prefix
        # chains demoted to bounded host-RAM buffers and revived by
        # device upload instead of re-prefill. Occupancy gauges
        # (blocks/bytes parked host-side right now) plus the monotone
        # revival economy: batched upload scatters served, prompt
        # tokens those uploads seated WITHOUT re-running prefill, and
        # spilled entries the bounded host LRU (or a reload flush)
        # dropped.
        ("kv_host_blocks", 40, T.TYPE_INT32, _OPT),
        ("kv_host_bytes", 41, T.TYPE_INT64, _OPT),
        ("revive_uploads", 42, T.TYPE_INT64, _OPT),
        ("prefill_tokens_revived", 43, T.TYPE_INT64, _OPT),
        ("host_drops", 44, T.TYPE_INT64, _OPT),
        # windowed prefix-hit-rate (time-series ring, trailing ~30 s):
        # the share of prompt tokens seated WITHOUT paying prefill
        # compute (prefix incref + spilled revival) — the warm-vs-cold
        # capacity signal prefix-affinity routing reads, as a live
        # window rather than a lifetime ratio
        ("prefix_hit_rate_window", 45, T.TYPE_DOUBLE, _OPT),
        # terminally-slow requests by dominant attributed cause
        # (observability/forensics.py CAUSES, declared order — the
        # same closed set behind edl_serving_slow_cause_total): the
        # scrapeable distribution of WHY, not just the that
        ("slow_cause_counts", 46, T.TYPE_INT64, _REP),
        # runtime health plane (observability/runtime_health.py):
        # the progress watchdog's self-report — ms since the
        # scheduler last made progress with work seated (0 = idle or
        # moving) and the watchdog state "ok" | "stalled" ("" = the
        # replica predates the health plane / runs with it off, the
        # autoscaler's cue to fall back to lease decay)
        ("last_progress_age_ms", 47, T.TYPE_DOUBLE, _OPT),
        ("health_state", 48, T.TYPE_STRING, _OPT),
        # recompile sentry: total tracked jit compilations, and the
        # post-warmup-boundary recompile anomalies ("churn never
        # recompiles" — tests/test_runtime_health.py pins
        # steady_recompiles at zero on a live server)
        ("jit_compiles", 49, T.TYPE_INT64, _OPT),
        ("steady_recompiles", 50, T.TYPE_INT64, _OPT),
        # device-memory accountant: PEAK unaccounted device-byte
        # drift since the steady baseline (ledger vs live buffers) —
        # a leak detector, monotone by construction
        ("memory_unaccounted_bytes", 51, T.TYPE_INT64, _OPT),
        # disaggregated prefill/decode (serving/disagg.py): the
        # replica's advertised phase role — "prefill" | "decode" |
        # "unified" ("" = pre-disagg replica, treated as unified) —
        # and the KV chain-transfer economy: chains exported to /
        # imported from sibling replicas, prompt tokens those imports
        # seated without re-prefill, transfers dropped via
        # abort_transfer, and exports currently awaiting their
        # import/abort resolution (0 after drain = clean handoff
        # ledger, the kill-drill's post-drain assertion)
        ("role", 52, T.TYPE_STRING, _OPT),
        ("chain_exports", 53, T.TYPE_INT64, _OPT),
        ("chain_imports", 54, T.TYPE_INT64, _OPT),
        ("chain_import_tokens", 55, T.TYPE_INT64, _OPT),
        ("transfer_aborts", 56, T.TYPE_INT64, _OPT),
        ("transfers_inflight", 57, T.TYPE_INT32, _OPT),
        # hot-reload failure advertisement (serving/hot_reload.py):
        # the watcher exhausted its retry ladder against a checkpoint
        # that would not verify/load — old params still serving, error
        # carried verbatim so the rollout controller can abort with
        # evidence instead of inferring from a version that never moves
        ("reload_failed", 58, T.TYPE_BOOL, _OPT),
        ("reload_error", 59, T.TYPE_STRING, _OPT),
    ],
    # ---- explicit checkpoint handshake (serving/rollout.py) ----
    # The rollout controller's swap RPC: unlike the poll path this
    # names an exact target version — including an OLDER one, which is
    # what a rollback is — and returns a structured verdict instead of
    # relying on the caller to notice the version never moved.
    "ReloadCheckpointRequest": [
        ("version", 1, T.TYPE_INT32, _OPT),
    ],
    "ReloadCheckpointResponse": [
        ("ok", 1, T.TYPE_BOOL, _OPT),
        ("model_version", 2, T.TYPE_INT32, _OPT),
        ("error", 3, T.TYPE_STRING, _OPT),
    ],
    # ---- disaggregated prefill/decode handoff (serving/disagg.py) ----
    # One finished prefix chain exported as a dense byte copy: the
    # same tree-generic kv_row_leaf gather the host spill tier uses,
    # one KvChainBlock per trie block in root-first chain order. The
    # decode side imports the blocks into freshly allocated device
    # blocks and re-keys them into its content-addressed trie, so
    # prefix sharing and speculative decode compose unchanged.
    "ExportChainRequest": [
        ("prompt", 1, T.TYPE_INT32, _REP),
        # coordinator-minted id correlating export -> import|abort
        ("transfer_id", 2, T.TYPE_STRING, _OPT),
    ],
    "KvChainBlock": [
        # the block's token ids (a full kv_block_size run of the
        # prompt) — with the parent chain implied by list order this
        # re-derives the (parent, tokens) trie key on the importer
        ("tokens", 1, T.TYPE_INT32, _REP),
        # raw row bytes, one entry per 4-d kv_row_leaf in
        # jax.tree.leaves order (int8 rows + f32 scale leaves travel
        # as siblings, exactly like the host spill tier)
        ("leaves", 2, T.TYPE_BYTES, _REP),
    ],
    "TransferChainRequest": [
        ("transfer_id", 1, T.TYPE_STRING, _OPT),
        ("block_size", 2, T.TYPE_INT32, _OPT),
        # leaf dtype names in the same order as KvChainBlock.leaves —
        # the importer refuses a chain whose arena format differs
        ("leaf_dtypes", 3, T.TYPE_STRING, _REP),
        ("blocks", 4, T.TYPE_MESSAGE, _REP, ".elasticdl_tpu.KvChainBlock"),
    ],
    "TransferChainResponse": [
        ("transfer_id", 1, T.TYPE_STRING, _OPT),
        ("ok", 2, T.TYPE_BOOL, _OPT),
        # blocks/tokens actually uploaded (deduped against blocks the
        # importer's trie already held)
        ("blocks", 3, T.TYPE_INT32, _OPT),
        ("tokens", 4, T.TYPE_INT32, _OPT),
        ("error", 5, T.TYPE_STRING, _OPT),
    ],
    "AbortTransferRequest": [
        ("transfer_id", 1, T.TYPE_STRING, _OPT),
    ],
    # ---- router tier (serving/router.py) ----
    "RouterStatusRequest": [],
    # the replica supervisor/autoscaler (serving/autoscaler.py):
    # desired-count target, roster by lifecycle state, decision
    # counters and the last scale decision + reason — absent (all
    # zeros / enabled=false) when the router runs a static fleet
    "AutoscalerStatus": [
        ("enabled", 1, T.TYPE_BOOL, _OPT),
        ("target", 2, T.TYPE_INT32, _OPT),
        ("live", 3, T.TYPE_INT32, _OPT),
        ("starting", 4, T.TYPE_INT32, _OPT),
        ("draining", 5, T.TYPE_INT32, _OPT),
        ("scale_ups", 6, T.TYPE_INT64, _OPT),
        ("scale_downs", 7, T.TYPE_INT64, _OPT),
        # unplanned replica losses (crash / wedged kill) replaced
        # through the deficit path
        ("replacements", 8, T.TYPE_INT64, _OPT),
        ("spawn_failures", 9, T.TYPE_INT64, _OPT),
        # max_restarts consecutive spawn failures opened the restart
        # circuit: no more respawns until the supervisor restarts
        ("circuit_open", 10, T.TYPE_BOOL, _OPT),
        ("last_decision", 11, T.TYPE_STRING, _OPT),
        ("last_reason", 12, T.TYPE_STRING, _OPT),
        ("last_decision_age_secs", 13, T.TYPE_DOUBLE, _OPT),
        # journal recoveries: how many supervisors have come up over
        # this roster's write-ahead state
        ("supervisor_restarts", 14, T.TYPE_INT64, _OPT),
    ],
    # One SLO objective's burn-rate evaluation (observability/slo.py):
    # the declared target, the error-budget goal, and the multi-window
    # (fast/slow) burn rates over the router's time-series ring.
    # alerting = both windows burning above 1.0 (spending the budget
    # faster than planned) — the signal, not an action: the autoscaler
    # consumes it read-only as a logged advisory.
    "SloObjective": [
        ("name", 1, T.TYPE_STRING, _OPT),
        ("kind", 2, T.TYPE_STRING, _OPT),
        ("threshold_ms", 3, T.TYPE_DOUBLE, _OPT),
        ("goal", 4, T.TYPE_DOUBLE, _OPT),
        ("fast_burn", 5, T.TYPE_DOUBLE, _OPT),
        ("slow_burn", 6, T.TYPE_DOUBLE, _OPT),
        ("fast_window_secs", 7, T.TYPE_DOUBLE, _OPT),
        ("slow_window_secs", 8, T.TYPE_DOUBLE, _OPT),
        ("fast_samples", 9, T.TYPE_INT64, _OPT),
        ("slow_samples", 10, T.TYPE_INT64, _OPT),
        ("alerting", 11, T.TYPE_BOOL, _OPT),
    ],
    # the fleet rollout controller (serving/rollout.py): journaled
    # canary -> judge -> progressive waves -> commit state machine.
    # phase names the wave controller's current state ("idle" when no
    # rollout has ever run); verdict carries the canary judgment
    # ("pass" | "parity_fail" | "burn_fail" | "timeout" | "" while
    # undecided); rollout_restarts counts controllers that came up over
    # this journal — the crash-recovery odometer the rollout drill
    # asserts on
    "RolloutStatus": [
        ("enabled", 1, T.TYPE_BOOL, _OPT),
        ("phase", 2, T.TYPE_STRING, _OPT),
        ("target_version", 3, T.TYPE_INT32, _OPT),
        ("old_version", 4, T.TYPE_INT32, _OPT),
        ("wave", 5, T.TYPE_INT32, _OPT),
        ("waves_total", 6, T.TYPE_INT32, _OPT),
        ("swapped", 7, T.TYPE_INT32, _OPT),
        ("fleet", 8, T.TYPE_INT32, _OPT),
        ("canary", 9, T.TYPE_STRING, _OPT),
        ("verdict", 10, T.TYPE_STRING, _OPT),
        ("last_error", 11, T.TYPE_STRING, _OPT),
        ("rollbacks", 12, T.TYPE_INT64, _OPT),
        ("rollout_restarts", 13, T.TYPE_INT64, _OPT),
    ],
    "ReplicaStatus": [
        ("address", 1, T.TYPE_STRING, _OPT),
        ("healthy", 2, T.TYPE_BOOL, _OPT),
        ("draining", 3, T.TYPE_BOOL, _OPT),
        # circuit breaker state: "closed" | "open" | "half_open"
        ("breaker", 4, T.TYPE_STRING, _OPT),
        ("lease_remaining_secs", 5, T.TYPE_DOUBLE, _OPT),
        ("queue_depth", 6, T.TYPE_INT32, _OPT),
        ("active_slots", 7, T.TYPE_INT32, _OPT),
        ("kv_blocks_free", 8, T.TYPE_INT32, _OPT),
        ("queue_wait_ms", 9, T.TYPE_DOUBLE, _OPT),
        ("dispatched", 10, T.TYPE_INT64, _OPT),
        ("failures", 11, T.TYPE_INT64, _OPT),
        # router-side dispatches currently in flight on this replica
        ("inflight", 12, T.TYPE_INT32, _OPT),
        # the replica's KV arena storage format ("" | "int8"),
        # passed through from its ServerStatus
        ("kv_cache_dtype", 13, T.TYPE_STRING, _OPT),
        # tiered host spill, passed through from ServerStatus: warm
        # prefix capacity that survived device eviction on this
        # replica — the warm-vs-cold signal prefix-affinity routing
        # and the autoscaler read
        ("kv_host_blocks", 14, T.TYPE_INT32, _OPT),
        ("kv_host_bytes", 15, T.TYPE_INT64, _OPT),
        ("revive_uploads", 16, T.TYPE_INT64, _OPT),
        ("prefill_tokens_revived", 17, T.TYPE_INT64, _OPT),
        ("host_drops", 18, T.TYPE_INT64, _OPT),
        # windowed prefix-hit-rate, passed through from ServerStatus
        ("prefix_hit_rate_window", 19, T.TYPE_DOUBLE, _OPT),
        # slow-cause distribution, passed through from ServerStatus
        # (forensics taxonomy, declared order)
        ("slow_cause_counts", 20, T.TYPE_INT64, _REP),
        # runtime health, passed through from ServerStatus: a
        # "stalled" replica leaves the dispatch rotation and the
        # supervisor replaces it on a seconds-scale budget instead
        # of the 30 s lease heuristic ("" = pre-health replica)
        ("last_progress_age_ms", 21, T.TYPE_DOUBLE, _OPT),
        ("health_state", 22, T.TYPE_STRING, _OPT),
        # prefix-cache occupancy, passed through from ServerStatus:
        # cached = refcount-0 blocks parked reclaimable, shared =
        # blocks referenced by >1 sequence — with the host tier and
        # hit-rate above, the warm-capacity ladder affinity ranks by
        ("kv_blocks_cached", 23, T.TYPE_INT32, _OPT),
        ("kv_blocks_shared", 24, T.TYPE_INT32, _OPT),
        # advertised phase role, passed through from ServerStatus:
        # "prefill" replicas leave the normal dispatch rotation and
        # serve only cache-warming prefills + chain exports
        ("role", 25, T.TYPE_STRING, _OPT),
        # checkpoint identity, passed through from ServerStatus: the
        # version this replica is serving right now plus the hot-reload
        # failure latch — together the rollout controller's per-replica
        # ground truth (a wave commits only when every member's
        # advertised version equals the target)
        ("model_version", 26, T.TYPE_INT32, _OPT),
        ("reload_failed", 27, T.TYPE_BOOL, _OPT),
    ],
    "RouterStatusResponse": [
        ("replicas", 1, T.TYPE_INT32, _OPT),
        ("healthy", 2, T.TYPE_INT32, _OPT),
        ("replica", 3, T.TYPE_MESSAGE, _REP, ".elasticdl_tpu.ReplicaStatus"),
        ("routed", 4, T.TYPE_INT64, _OPT),
        ("completed", 5, T.TYPE_INT64, _OPT),
        ("redispatched", 6, T.TYPE_INT64, _OPT),
        ("hedges", 7, T.TYPE_INT64, _OPT),
        ("hedge_wins", 8, T.TYPE_INT64, _OPT),
        ("shed", 9, T.TYPE_INT64, _OPT),
        ("breaker_trips", 10, T.TYPE_INT64, _OPT),
        ("uptime_secs", 11, T.TYPE_DOUBLE, _OPT),
        # router-observed end-to-end dispatch latency (accept ->
        # terminal outcome, re-dispatches and hedges included)
        ("e2e_p50_ms", 12, T.TYPE_DOUBLE, _OPT),
        ("e2e_p90_ms", 13, T.TYPE_DOUBLE, _OPT),
        ("e2e_p99_ms", 14, T.TYPE_DOUBLE, _OPT),
        # fleet-wide percentiles: the replicas' ttft/queue-wait
        # histogram buckets merged by addition at the router
        ("ttft_p50_ms", 15, T.TYPE_DOUBLE, _OPT),
        ("ttft_p90_ms", 16, T.TYPE_DOUBLE, _OPT),
        ("ttft_p99_ms", 17, T.TYPE_DOUBLE, _OPT),
        ("queue_wait_p50_ms", 18, T.TYPE_DOUBLE, _OPT),
        ("queue_wait_p90_ms", 19, T.TYPE_DOUBLE, _OPT),
        ("queue_wait_p99_ms", 20, T.TYPE_DOUBLE, _OPT),
        # replica supervisor/autoscaler block (serving/autoscaler.py);
        # unset when the fleet is static
        ("autoscaler", 21, T.TYPE_MESSAGE, _OPT,
         ".elasticdl_tpu.AutoscalerStatus"),
        # fleet-wide tiered-host-spill view: occupancy gauges and the
        # monotone revival economy summed across the roster
        ("kv_host_blocks", 22, T.TYPE_INT64, _OPT),
        ("kv_host_bytes", 23, T.TYPE_INT64, _OPT),
        ("revive_uploads", 24, T.TYPE_INT64, _OPT),
        ("prefill_tokens_revived", 25, T.TYPE_INT64, _OPT),
        ("host_drops", 26, T.TYPE_INT64, _OPT),
        # declared SLO objectives evaluated as multi-window burn
        # rates over the router's time-series ring (one block per
        # objective; empty when the router has no SLO engine)
        ("slo", 27, T.TYPE_MESSAGE, _REP,
         ".elasticdl_tpu.SloObjective"),
        # multi-cell router tier (serving/router_cell.py): which cell
        # answered this status and how many the tier runs; the
        # affinity counters are the prefix-affine dispatch ladder's
        # verdicts; journal_* report the shared-registry write-ahead
        # journal (events appended by this cell / replayed into it at
        # start), cell_restarts the journal dir's restart marker —
        # the crash-recovery odometer
        ("cell_id", 28, T.TYPE_INT32, _OPT),
        ("cells", 29, T.TYPE_INT32, _OPT),
        ("affinity_hits", 30, T.TYPE_INT64, _OPT),
        ("affinity_misses", 31, T.TYPE_INT64, _OPT),
        ("journal_events", 32, T.TYPE_INT64, _OPT),
        ("journal_replayed", 33, T.TYPE_INT64, _OPT),
        ("cell_restarts", 34, T.TYPE_INT64, _OPT),
        # disaggregated dispatch (serving/disagg.py): requests whose
        # prefill ran on a dedicated prefill replica with the chain
        # handed to the decode target, and handoffs that failed
        # mid-transfer and fell back to the unified path (the decode
        # replica paid prefill itself — degraded, never lost)
        ("disagg_handoffs", 35, T.TYPE_INT64, _OPT),
        ("disagg_fallbacks", 36, T.TYPE_INT64, _OPT),
        # fleet rollout controller block (serving/rollout.py); unset
        # when no controller is attached
        ("rollout", 37, T.TYPE_MESSAGE, _OPT,
         ".elasticdl_tpu.RolloutStatus"),
    ],
}

# Fields appended to messages that live in the BASE descriptor (the
# original elasticdl.proto surface, not the serving tables above).
# Same determinism rules: idempotent replace-by-name, appended sorted
# by field number. Used for the training-plane trace context: the
# master mints a trace per task and hands (trace_id, span_id) to the
# worker on the Task it dispatches, so task dispatch -> worker fetch ->
# report_task_result reassembles as one span tree keyed by task id.
EXTRA_MESSAGE_FIELDS = {
    "Task": [
        ("trace_id", 10, T.TYPE_STRING, _OPT),
        ("span_id", 11, T.TYPE_STRING, _OPT),
    ],
}

# service name -> [(method name, request, response, server_streaming)]
SERVICES = {
    "Serving": [
        ("generate", "GenerateRequest", "GenerateResponse", False),
        ("generate_stream", "GenerateRequest", "TokenChunk", True),
        ("server_status", "ServerStatusRequest", "ServerStatusResponse",
         False),
        # disaggregated handoff surface: export a finished chain as a
        # dense byte copy (the response IS the transfer payload),
        # import one on the decode side, or abandon an export whose
        # import failed so the exporter's inflight ledger settles
        ("export_chain", "ExportChainRequest", "TransferChainRequest",
         False),
        ("transfer_chain", "TransferChainRequest", "TransferChainResponse",
         False),
        ("abort_transfer", "AbortTransferRequest", "TransferChainResponse",
         False),
        # explicit checkpoint swap (rollout controller handshake):
        # load exactly this version — newer or older — on the
        # scheduler thread, draining advertised for the duration
        ("reload_checkpoint", "ReloadCheckpointRequest",
         "ReloadCheckpointResponse", False),
    ],
    # the multi-replica routing tier in front of N Serving replicas;
    # method names are distinct from the replica surface so
    # EDL_FAULT_SPEC rules can target one boundary without the other
    "Router": [
        ("router_generate", "GenerateRequest", "GenerateResponse", False),
        ("router_generate_stream", "GenerateRequest", "TokenChunk", True),
        ("router_status", "RouterStatusRequest", "RouterStatusResponse",
         False),
    ],
}

PB2_TEMPLATE = '''# -*- coding: utf-8 -*-
# Generated by the protocol buffer compiler.  DO NOT EDIT!
# source: elasticdl.proto
# (regenerated descriptor: TaskReason enum + Task.reason field, then the
# Serving service (scripts/gen_serving_proto.py), added by mutating the
# FileDescriptorProto in-process; the container ships no protoc binary —
# see docs/designs/fault_tolerance.md and docs/designs/serving.md)
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile({serialized!r})

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'elasticdl_pb2', globals())
if _descriptor._USE_C_DESCRIPTORS == False:

  DESCRIPTOR._options = None
  _TASK_EXTENDEDCONFIGENTRY._options = None
  _TASK_EXTENDEDCONFIGENTRY._serialized_options = b'8\\001'
  _REPORTTASKRESULTREQUEST_EXECCOUNTERSENTRY._options = None
  _REPORTTASKRESULTREQUEST_EXECCOUNTERSENTRY._serialized_options = b'8\\001'
# @@protoc_insertion_point(module_scope)
'''


def current_serialized_pb(src=None):
    """Extract the serialized descriptor from the committed pb2 module
    without importing it (imports would register it in the default pool
    and block re-registration elsewhere in the same process)."""
    if src is None:
        with open(PB2_PATH) as f:
            src = f.read()
    m = re.search(r"AddSerializedFile\((b'(?:[^'\\]|\\.)*')\)", src)
    if not m:
        raise RuntimeError("cannot find AddSerializedFile in %s" % PB2_PATH)
    return eval(m.group(1))  # noqa: S307 - a bytes literal from our own file


def build_descriptor(serialized):
    fdp = descriptor_pb2.FileDescriptorProto()
    fdp.ParseFromString(serialized)

    # idempotence: drop any earlier serving entries before re-adding
    keep = [m for m in fdp.message_type if m.name not in SERVING_MESSAGES]
    del fdp.message_type[:]
    fdp.message_type.extend(keep)
    keep_svc = [s for s in fdp.service if s.name not in SERVICES]
    del fdp.service[:]
    fdp.service.extend(keep_svc)

    # append the extra fields to base-descriptor messages, idempotently
    # (replace-by-name) and in field-number order — same determinism
    # contract as the serving tables
    for msg in fdp.message_type:
        extras = EXTRA_MESSAGE_FIELDS.get(msg.name)
        if not extras:
            continue
        names = {spec[0] for spec in extras}
        keep_fields = [f for f in msg.field if f.name not in names]
        del msg.field[:]
        msg.field.extend(keep_fields)
        for spec in sorted(extras, key=lambda s: s[1]):
            fname, num, ftype, label = spec[:4]
            fld = msg.field.add()
            fld.name = fname
            fld.number = num
            fld.type = ftype
            fld.label = label
            fld.json_name = _json_name(fname)
            if ftype == T.TYPE_MESSAGE:
                fld.type_name = spec[4]

    # stable ordering: names sort the tables, numbers sort the fields —
    # the serialized bytes cannot depend on dict/tuple declaration order
    for name in sorted(SERVING_MESSAGES):
        fields = SERVING_MESSAGES[name]
        msg = fdp.message_type.add()
        msg.name = name
        for spec in sorted(fields, key=lambda s: s[1]):
            fname, num, ftype, label = spec[:4]
            fld = msg.field.add()
            fld.name = fname
            fld.number = num
            fld.type = ftype
            fld.label = label
            fld.json_name = _json_name(fname)
            if ftype == T.TYPE_MESSAGE:
                fld.type_name = spec[4]

    for sname in sorted(SERVICES):
        methods = SERVICES[sname]
        svc = fdp.service.add()
        svc.name = sname
        for mname, req, resp, streaming in methods:
            meth = svc.method.add()
            meth.name = mname
            meth.input_type = ".elasticdl_tpu.%s" % req
            meth.output_type = ".elasticdl_tpu.%s" % resp
            if streaming:
                meth.server_streaming = True
    return fdp.SerializeToString()


def _json_name(snake):
    parts = snake.split("_")
    return parts[0] + "".join(p.capitalize() for p in parts[1:])


def generate_text(src=None):
    """The full pb2 file text, regenerated from `src` (the current pb2
    source text; None reads the checked-in file). Pure function of the
    tables above + the non-serving part of the existing descriptor —
    the hermetic entry point the EDL301 drift gate and the regen-twice
    determinism test call."""
    serialized = build_descriptor(current_serialized_pb(src))
    return PB2_TEMPLATE.format(serialized=serialized)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default=PB2_PATH)
    parser.add_argument("--check", action="store_true",
                        help="exit 1 on drift; write nothing")
    args = parser.parse_args(argv)
    text = generate_text()
    if args.check:
        with open(PB2_PATH) as f:
            if f.read() != text:
                print("gen_serving_proto: %s has DRIFTED from the "
                      "generator tables" % PB2_PATH, file=sys.stderr)
                return 1
        print("gen_serving_proto: %s is up to date" % PB2_PATH)
        return 0
    with open(args.out, "w") as f:
        f.write(text)
    print("wrote %s (%d chars)" % (args.out, len(text)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
