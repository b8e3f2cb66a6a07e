"""edl-lint fixture battery + gate semantics (tier-1 fast shard).

Every rule family is exercised by at least one TRIGGERING and one
CLEAN fixture under tests/lint_fixtures/; the gate semantics tests pin
exactly what CI relies on: the shipped tree lints clean, deleting a
baseline entry fails, a stale baseline entry fails, and injecting any
fixture snippet into a linted file fails. The proto-drift tests pin
byte-determinism of scripts/gen_serving_proto.py (regen-twice) and
drift detection on a tampered pb2.
"""

import json
import os
import shutil

import pytest

from elasticdl_tpu.analysis import Baseline, all_rules, run_rules
from elasticdl_tpu.analysis.lint import (
    REPO_ROOT,
    RULE_FAMILIES,
    main as lint_main,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "lint_fixtures")


def lint_file(name):
    path = os.path.join(FIXTURES, name)
    findings, errors = run_rules([path], root=None, excludes=())
    assert not errors, errors
    # repo-level rules (EDL301) don't fire with root=None
    return findings


def rule_ids(findings):
    return sorted(f.rule for f in findings)


# ----------------------------------------------------------- C1 fixtures


def test_c1_positive():
    findings = lint_file("c1_pos.py")
    assert rule_ids(findings) == ["EDL001", "EDL001", "EDL002"]
    details = {(f.scope, f.detail) for f in findings}
    assert ("Counter.bump_unlocked", "_count") in details
    assert ("Counter.append_unlocked", "_items") in details
    assert ("Counter.peek_unlocked", "_count") in details


def test_c1_negative():
    assert lint_file("c1_neg.py") == []


def test_c1_pragma_suppresses_both_placements():
    assert lint_file("c1_pragma.py") == []


# ----------------------------------------------------------- C2 fixtures


def test_c2_positive():
    findings = lint_file("c2_pos.py")
    ids = rule_ids(findings)
    assert ids.count("EDL101") == 4, findings
    assert ids.count("EDL102") == 2, findings
    assert ids.count("EDL103") == 2, findings
    details = {f.detail for f in findings}
    assert {".item()", "float()", "np.asarray",
            ".block_until_ready()"} <= details
    assert {"if", "while", "time.time", "print"} <= details


def test_c2_negative():
    assert lint_file("c2_neg.py") == []


def test_c20_positive_index_map_host_sync():
    """EDL108: np.asarray/.item()/int() inside BlockSpec index-map
    lambdas, positional and index_map= spellings both."""
    findings = lint_file("c20_pos.py")
    ids = rule_ids(findings)
    assert ids.count("EDL108") == 4, findings
    assert {f.scope for f in findings
            if f.rule == "EDL108"} == {"BlockSpec.index_map"}
    details = [f.detail for f in findings if f.rule == "EDL108"]
    assert sorted(details) == [
        ".item()", "int()", "np.array", "np.asarray",
    ], details


def test_c20_negative_index_map_clean():
    """The tracer-safe index-map idiom (jnp ops on the prefetch ref),
    host-side np.asarray BEFORE pallas_call, and non-BlockSpec lambdas
    must all stay clean."""
    findings = [f for f in lint_file("c20_neg.py")
                if f.rule in RULE_FAMILIES["EDL101"]]
    assert findings == [], findings


# ----------------------------------------------------------- C3 fixtures


def test_c3_positive():
    findings = lint_file("c3_pos.py")
    assert rule_ids(findings) == ["EDL201"] * 8, findings
    scopes = {f.scope for f in findings}
    assert "EdgeRouter.dispatch_generate" in scopes
    assert "EdgeRouter.housekeeping" not in scopes
    # the concurrent.futures coverage gap: untimed result()/wait()/
    # as_completed() in dispatch paths (the PR 4 heartbeat-poll shape)
    details = {f.detail for f in findings}
    assert {".result()", "futures.wait", "as_completed"} <= details


def test_c3_negative():
    assert lint_file("c3_neg.py") == []


# ----------------------------------------------------------- C5 fixtures


def test_c5_positive():
    findings = lint_file("c5_pos.py")
    assert rule_ids(findings) == ["EDL401"] * 8, findings
    details = {f.detail for f in findings}
    assert details == {"admittd", "rejectd", "breaker_tripz",
                       "queue_dept", "healthy_replica", "queue_wiat",
                       "steady_recompile", "last_progress_age"}
    scopes = {f.scope for f in findings}
    assert "Frontend.admit" in scopes and "module_level" in scopes
    # gauge typos report as gauges, counter typos as counters,
    # slow-cause typos as slow causes
    by_detail = {f.detail: f.message for f in findings}
    assert "gauge" in by_detail["queue_dept"]
    assert "counter" in by_detail["admittd"]
    assert "slow cause" in by_detail["queue_wiat"]
    # the runtime-health names extend the same closed sets
    assert "counter" in by_detail["steady_recompile"]
    assert "gauge" in by_detail["last_progress_age"]


def test_c5_negative():
    assert lint_file("c5_neg.py") == []


def test_c5_allowed_set_tracks_telemetry_declarations():
    """The rule reads the declared sets from serving/telemetry.py —
    one source of truth, no drift-prone second list (counters AND the
    gauge set the metrics plane closed)."""
    from elasticdl_tpu.analysis.telemetry_rules import (
        declared_counters,
        declared_gauges,
    )
    from elasticdl_tpu.serving.telemetry import (
        RouterTelemetry,
        ServingTelemetry,
    )

    assert declared_counters() == (
        frozenset(ServingTelemetry.COUNTERS)
        | frozenset(RouterTelemetry.COUNTERS)
    )
    assert "admitted" in declared_counters()
    assert declared_gauges() == (
        frozenset(ServingTelemetry.GAUGES)
        | frozenset(RouterTelemetry.GAUGES)
    )
    assert "queue_depth" in declared_gauges()
    assert "healthy_replicas" in declared_gauges()
    # the runtime-health extension rides the SAME single source: the
    # new counter/gauge names are in the unions because telemetry.py
    # declares them, not because any list here grew
    assert "steady_recompiles" in declared_counters()
    assert "stalls" in declared_counters()
    assert "last_progress_age_ms" in declared_gauges()
    assert "memory_unaccounted_bytes" in declared_gauges()
    from elasticdl_tpu.analysis.telemetry_rules import (
        declared_slow_causes,
    )
    from elasticdl_tpu.observability.forensics import CAUSES

    assert declared_slow_causes() == frozenset(CAUSES)
    assert declared_slow_causes() == frozenset(
        ServingTelemetry.SLOW_CAUSES
    )
    assert "prefill_blocked_by_other" in declared_slow_causes()


# ------------------------------------------ C6: EDL003 lock-order cycles


def test_c6_positive_flags_deadlock_cycles():
    """The synthetic PR 5 deadlock chain: report holds the dispatcher
    lock while complete_task calls back into create_tasks (a
    non-reentrant re-entry), plus a classic AB/BA cycle, plus the
    transitive self-deadlock the AB/BA chain implies."""
    findings = lint_file("c6_pos.py")
    assert rule_ids(findings) == ["EDL003"] * 4, findings
    details = {f.detail for f in findings}
    assert "Dispatcher._lock->Dispatcher._lock" in details
    assert "Dispatcher._lock->EvalSvc._lock->Dispatcher._lock" in details
    assert "PairA._a_lock->PairB._b_lock->PairA._a_lock" in details


def test_c6_negative_fixed_shapes_are_clean():
    """The PR 5 fix shape (cross-object call outside the lock),
    reentrant RLock self-nesting, and the *_locked convention."""
    assert lint_file("c6_neg.py") == []


# ------------------------------------------- C7: EDL004 wrong-lock-held


def test_c7_positive_flags_wrong_lock():
    findings = lint_file("c7_pos.py")
    assert rule_ids(findings) == ["EDL004"] * 2, findings
    assert {(f.scope, f.detail) for f in findings} == {
        ("Registry.snapshot", "_inflight"),
        ("Registry.reset", "_inflight"),
    }


def test_c7_negative_bound_accesses_are_clean():
    assert lint_file("c7_neg.py") == []


# ------------------------------------------- C8: EDL501 must-release


def test_c8_positive_flags_leaks():
    """The synthetic PR 4 probe leak (breaker slot lost on the
    non-transient re-raise), a span lost to an early return, and a
    file handle dropped by a handler branch."""
    findings = lint_file("c8_pos.py")
    assert rule_ids(findings) == ["EDL501"] * 3, findings
    details = {f.detail for f in findings}
    assert "rep.breaker.acquire" in details
    assert "span=start_span" in details
    assert "f=open" in details


def test_c8_negative_settled_paths_are_clean():
    """The PR 4 fix (three-way settle on every outcome), finally-
    guarded release, and the ownership-transfer escapes."""
    assert lint_file("c8_neg.py") == []


def test_c11_positive_flags_refcount_leaks():
    """The prefix-shared KV pool's refcount pairs: an incref'd chain
    lost to an early return, a share() seat dropped on the exception
    path, and an abandoned CoW copy."""
    findings = lint_file("c11_pos.py")
    assert rule_ids(findings) == ["EDL501"] * 3, findings
    details = {f.detail for f in findings}
    assert {"allocator.incref", "allocator.share",
            "allocator.cow"} == details


def test_c11_negative_settled_refcounts_are_clean():
    """finally-guarded decref, slot-level free settles on every
    branch, and the ownership-transfer escape."""
    assert lint_file("c11_neg.py") == []


def test_c12_positive_flags_supervisor_lifecycle_leaks():
    """The replica supervisor's seat pairs (serving/autoscaler.py): a
    spawned seat never adopted nor reaped (an orphan process), a drain
    begun that an exception path never retires, and a launcher Popen
    handle killed but never waited on (a zombie)."""
    findings = lint_file("c12_pos.py")
    assert rule_ids(findings) == ["EDL501"] * 3, findings
    assert {f.detail for f in findings} == {
        "supervisor.spawn", "supervisor.begin_drain", "proc=Popen",
    }


def test_c12_negative_settled_lifecycles_are_clean():
    """Reap on the failure branch, finally-guarded retire, waited
    kills, and the roster ownership-transfer escape."""
    assert lint_file("c12_neg.py") == []


def test_c13_positive_flags_spill_lifecycle_leaks():
    """The tiered KV cache's spill pair (serving/kv_pool.py): a block
    spilled to the host tier must REVIVE or DROP on every path — an
    early return, an exception path, and a budget bail-out that each
    lose the spilled entry are convicted leaks."""
    findings = lint_file("c13_pos.py")
    assert rule_ids(findings) == ["EDL501"] * 3, findings
    assert {f.detail for f in findings} == {"tier.spill"}
    scopes = {f.scope for f in findings}
    assert scopes == {"ChainSpiller.demote",
                      "ChainSpiller.demote_checked",
                      "ChainSpiller.demote_budgeted"}


def test_c13_negative_settled_spills_are_clean():
    """finally-guarded drop, revive-or-drop on every branch, and the
    host-store ownership-transfer escape."""
    assert lint_file("c13_neg.py") == []


def test_c18_positive_flags_cell_lifecycle_leaks():
    """The cell supervisor's router-cell pair (serving/router_main.py
    CellRoster): a spawned cell never adopted nor retired (an orphan
    router process), and a failed-adoption exception path that leaks
    the pid past the raise."""
    findings = lint_file("c18_pos.py")
    assert rule_ids(findings) == ["EDL501"] * 2, findings
    assert {f.detail for f in findings} == {"roster.spawn_cell"}
    assert {f.scope for f in findings} == {
        "CellScaler.grow", "CellScaler.grow_checked",
    }


def test_c18_negative_settled_cells_are_clean():
    """Adopt on the happy path, retire on the not-ready branch and on
    the exception path — every spawn settles, EDL501 stays silent."""
    assert lint_file("c18_neg.py") == []


def test_c19_positive_flags_unsettled_handoff_exports():
    """The disaggregated transfer pair (serving/disagg.py
    HandoffCoordinator): an exported chain that an early return
    neither imports nor aborts, and a failed-import exception path
    that records no abort past the raise."""
    findings = lint_file("c19_pos.py")
    assert rule_ids(findings) == ["EDL501"] * 2, findings
    assert {f.detail for f in findings} == {"disagg.export_chain"}
    assert {f.scope for f in findings} == {
        "HandoffDriver.warm", "HandoffDriver.warm_checked",
    }


def test_c19_negative_settled_handoffs_are_clean():
    """import_chain on the happy path, abort_transfer on the not-ready
    branch and the exception path — and the pool-level export_chain
    (no "disagg" receiver spelling) stays untracked, because pool
    exports return plain data and owe nothing."""
    assert lint_file("c19_neg.py") == []


def test_c21_positive_flags_rollout_lifecycle_leaks():
    """The rollout controller's pairs (serving/rollout.py): a wave
    abandoned by a not-converged early return, a burn alert that
    raises past the rollback, and a staged checkpoint whose failed
    verification is never discarded."""
    findings = lint_file("c21_pos.py")
    assert rule_ids(findings) == ["EDL501"] * 3, findings
    assert {f.detail for f in findings} == {
        "ctl.begin_wave", "stager.stage_checkpoint",
    }
    assert {f.scope for f in findings} == {
        "RolloutDriver.advance", "RolloutDriver.advance_checked",
        "RolloutDriver.prepare",
    }


def test_c21_negative_settled_rollouts_are_clean():
    """commit_wave on the soaked path, rollback_wave on the failure
    branches and the exception path, activate/discard closing both
    staging outcomes — every lifecycle settles, EDL501 stays silent."""
    assert lint_file("c21_neg.py") == []


# -------------- C22/C23: EDL701-EDL704 journal-protocol typestate (v4)


def test_c22_positive_write_replay_closure_and_payload_drift():
    """The closure half of a declared journal protocol: an emit of an
    undeclared kind, a replay branch for an unknown kind, a replay
    branch no emit produces (EDL701), plus an emit dropping a
    `requires` key and one missing a key the replay reads
    unconditionally (EDL702)."""
    findings = lint_file("c22_pos.py")
    assert rule_ids(findings) == [
        "EDL701", "EDL701", "EDL701", "EDL702", "EDL702",
    ], findings
    assert {(f.scope, f.detail) for f in findings} == {
        ("Meter.purge", "undeclared-kind:purge"),
        ("Meter._apply_event", "dead-replay:compact"),
        ("Meter._apply_event", "never-emitted:rotate"),
        ("Meter.record", "sample.value"),
        ("Meter.flush", "flushed.count"),
    }


def test_c22_negative_closed_protocol_is_clean():
    """Alphabet == emit sites == replay branches, payload contracts
    satisfied, optional keys read via .get(): the whole EDL701-EDL704
    family stays silent."""
    assert lint_file("c22_neg.py") == []


def test_c23_positive_typestate_and_crash_windows():
    """The machine half: 'finish' journaled from the terminal state
    its from-set forbids (EDL703), and 'start' parking the machine in
    an unrecoverable state while another journal write is still
    reachable (EDL704)."""
    findings = lint_file("c23_pos.py")
    assert rule_ids(findings) == ["EDL703", "EDL704"], findings
    assert {(f.rule, f.scope, f.detail) for f in findings} == {
        ("EDL703", "Oven.run", "finish@done"),
        ("EDL704", "Oven.run", "start@baking"),
    }


def test_c23_negative_recoverable_machine_is_clean():
    """Same machine with the defects repaired — 'baking' declares a
    resume action and 'finish' fires exactly once, from 'baking'."""
    assert lint_file("c23_neg.py") == []


# ------------------- C14: EDL105 recompile hazard (value-origin v3)


def test_c14_positive_flags_unstable_signatures():
    """Calls to jit-wrapped executables whose argument origins vary
    per execution: loop-derived shapes, len() of a growing attribute
    container (cross-method self._fn wrapper), wall-clock and env
    reads in the signature."""
    findings = lint_file("c14_pos.py")
    assert rule_ids(findings) == ["EDL105"] * 4, findings
    assert {(f.scope, f.detail) for f in findings} == {
        ("churn_loop", "step(loop)"),
        ("BatchRunner.run", "self._fn(len)"),
        ("stamped", "fn(clock)"),
        ("env_sized", "fn(config)"),
    }


def test_c14_negative_stabilizers_are_clean():
    """The engine/kv_pool bucketing idioms are stabilizers, not
    hazards: *_bucket helpers, ceil-to-multiple pads, power-of-two
    tiles, min clamps, scalar device binding (jnp.asarray of a loop
    counter), and per-shape wrappers rebuilt inside the loop."""
    assert lint_file("c14_neg.py") == []


# ----------------------- C15: EDL106 captured-constant bloat


def test_c15_positive_flags_captured_arrays():
    findings = lint_file("c15_pos.py")
    assert rule_ids(findings) == ["EDL106"] * 3, findings
    assert {(f.scope, f.detail) for f in findings} == {
        ("lookup", "VOCAB_TABLE"),
        ("step", "weights"),
        ("apply", "mask"),
    }


def test_c15_negative_threaded_params_are_clean():
    """Arrays threaded as proper arguments, scalar/config captures,
    call-result bindings (never guessed) and untraced closures."""
    assert lint_file("c15_neg.py") == []


# ------------------------- C16: EDL107 PRNG-key discipline


def test_c16_positive_flags_key_reuse():
    """One key feeding two sampler sinks, an in-loop sink re-consuming
    the same key every iteration, and per-iteration closures sharing a
    pre-loop key."""
    findings = lint_file("c16_pos.py")
    assert rule_ids(findings) == ["EDL107"] * 3, findings
    scopes = {f.scope for f in findings}
    assert scopes == {"double_sink", "loop_reconsume",
                      "closure_shares_key"}
    assert {f.detail for f in findings} == {"key"}


def test_c16_negative_split_fold_idioms_are_clean():
    """split-then-consume-once, the generation.py fold_in(rng,
    position) idiom, rebind-between-sinks, per-iteration fold_in
    closures, and non-sampler consumers."""
    assert lint_file("c16_neg.py") == []


# ------------------- C17: EDL601 sharding discipline (born gated)


def test_c17_positive_flags_sharding_drift():
    findings = lint_file("c17_pos.py")
    assert rule_ids(findings) == ["EDL601"] * 4, findings
    details = {f.detail for f in findings}
    assert details == {"with_sharding_constraint", "axis:ddp",
                       "axis:tpx", "donate:step_fn"}
    by_detail = {f.detail: f.scope for f in findings}
    assert by_detail["with_sharding_constraint"] == "pin_after_the_fact"
    assert by_detail["axis:ddp"] == "typo_against_mesh"


def test_c17_negative_disciplined_sharding_is_clean():
    """Constraints inside jit contexts (decorator/wrap/nested helper),
    mesh-declared and canonical axis names, constant-derived axes,
    and donate with out_shardings re-declared."""
    assert lint_file("c17_neg.py") == []


def test_edl601_axis_canon_tracks_mesh_constants():
    """The fallback axis union is MeshAxis.ALL — one source of truth
    with the mesh builder, so a new axis name there is automatically
    sanctioned here."""
    from elasticdl_tpu.analysis.sharding_rules import canonical_axes
    from elasticdl_tpu.common.constants import MeshAxis

    assert canonical_axes() == frozenset(MeshAxis.ALL)
    assert {"dp", "fsdp", "ep", "tp", "sp"} <= canonical_axes()


# ------------------ the EDL105 <-> runtime recompile sentry contract


def test_edl105_conviction_set_matches_runtime_sentry():
    """Cross-check of the static rule against the PR 14 runtime
    sentry: the serving decode paths (engine, kv_pool, offline
    generation) compile exclusively through tracked_jit-adopted sites,
    and tests/test_runtime_health.py pins a live server's
    steady_recompiles at ZERO. The static
    conviction set over those files must therefore be EMPTY — any
    EDL105 finding here would be a shape the runtime sentry could
    observe as a steady-state recompile (conviction set is a subset
    of sentry-observable shapes, and the sentry's record says there
    are none)."""
    sentry_files = [
        os.path.join(REPO_ROOT, "elasticdl_tpu", "serving",
                     "engine.py"),
        os.path.join(REPO_ROOT, "elasticdl_tpu", "serving",
                     "kv_pool.py"),
        os.path.join(REPO_ROOT, "elasticdl_tpu", "api",
                     "generation.py"),
    ]
    for path in sentry_files:
        with open(path) as f:
            assert "tracked_jit" in f.read(), (
                "%s lost its sentry adoption — the cross-check below "
                "is vacuous without it" % path
            )
    from elasticdl_tpu.analysis import all_rules

    rules = [r for r in all_rules() if r.id == "EDL105"]
    findings, errors = run_rules(sentry_files, rules=rules,
                                 root=REPO_ROOT, excludes=())
    assert errors == []
    assert findings == [], (
        "EDL105 convicts a serving decode path the runtime sentry "
        "holds at steady_recompiles == 0 — fix the code (and add a "
        "regression test) or teach the analysis the stabilizer: %s"
        % [f.format() for f in findings]
    )


# ------------------------------ C9: EDL202/EDL203 deadline propagation


def test_c9_positive_flags_dropped_and_replaced_deadlines():
    findings = lint_file("c9_pos.py")
    assert rule_ids(findings) == ["EDL202", "EDL203", "EDL203",
                                  "EDL203"], findings
    by_scope = {f.scope: f.rule for f in findings}
    assert by_scope["BackendClient.call_backend"] == "EDL202"
    assert by_scope["BackendClient.call_backend_static"] == "EDL203"
    assert by_scope["FrontendServicer.generate"] == "EDL203"
    assert by_scope["EdgeRouter.dispatch"] == "EDL203"


def test_c9_negative_derived_timeouts_are_clean():
    """Decremented budgets, closure-over-budget stream generators, and
    non-dispatch heartbeat polls with static bounds: all sanctioned."""
    assert lint_file("c9_neg.py") == []


# -------------------------------- C10: EDL104 donated-buffer aliasing


def test_c10_positive_flags_read_after_donation():
    findings = lint_file("c10_pos.py")
    assert rule_ids(findings) == ["EDL104"] * 2, findings
    assert {(f.scope, f.detail) for f in findings} == {
        ("train_loop", "state"),
        ("apply_updates", "opt_state"),
    }


def test_c10_negative_rebind_idioms_are_clean():
    assert lint_file("c10_neg.py") == []


def test_new_rules_pragma_suppression(tmp_path):
    """The pragma layer applies to CFG-based rules like any other."""
    src = os.path.join(FIXTURES, "c7_pos.py")
    with open(src) as f:
        text = f.read()
    text = text.replace(
        "return dict(self._entries), self._inflight",
        "return dict(self._entries), self._inflight"
        "  # edl-lint: disable=EDL004",
    )
    mod = tmp_path / "pragma_mod.py"
    mod.write_text(text)
    findings, errors = run_rules([str(mod)], root=None, excludes=())
    assert not errors
    assert {(f.scope, f.detail) for f in findings} == {
        ("Registry.reset", "_inflight"),
    }


# --------------------------------------------------- every-rule coverage


#: checker family -> (triggering fixtures, clean fixture). EVERY
#: registered family must appear here with BOTH halves — the
#: meta-test below fails a new rule until its fixtures exist.
FAMILY_FIXTURES = {
    "EDL000": (("c0_pos.py",), "c1_pragma.py"),
    "EDL001": (("c1_pos.py",), "c1_neg.py"),
    "EDL003": (("c6_pos.py",), "c6_neg.py"),
    "EDL004": (("c7_pos.py",), "c7_neg.py"),
    "EDL101": (("c2_pos.py", "c20_pos.py"), "c2_neg.py"),
    "EDL104": (("c10_pos.py",), "c10_neg.py"),
    "EDL105": (("c14_pos.py",), "c14_neg.py"),
    "EDL106": (("c15_pos.py",), "c15_neg.py"),
    "EDL107": (("c16_pos.py",), "c16_neg.py"),
    "EDL201": (("c3_pos.py",), "c3_neg.py"),
    "EDL202": (("c9_pos.py",), "c9_neg.py"),
    "EDL401": (("c5_pos.py",), "c5_neg.py"),
    "EDL501": (("c8_pos.py", "c11_pos.py", "c12_pos.py",
                "c13_pos.py", "c18_pos.py", "c19_pos.py",
                "c21_pos.py"), "c8_neg.py"),
    "EDL601": (("c17_pos.py",), "c17_neg.py"),
    # the closure half fires in c22, the typestate half in c23; both
    # negatives are pinned clean by their dedicated tests above
    "EDL701": (("c22_pos.py", "c23_pos.py"), "c22_neg.py"),
    # EDL301 is repo-level; its trigger/clean pair is the tampered/
    # pristine pb2 in the proto tests below
    "EDL301": ((), None),
}


def test_every_rule_has_fixture_coverage():
    """Meta-test: EVERY registered rule family is proven live by at
    least one triggering fixture and kept honest by a clean one. A
    new rule family cannot register without growing FAMILY_FIXTURES
    (KeyError here) and shipping fixtures that actually fire."""
    assert set(FAMILY_FIXTURES) == {r.id for r in all_rules()}
    emitted = set()
    for rule in all_rules():
        pos_names, neg_name = FAMILY_FIXTURES[rule.id]
        if not pos_names:  # repo-level: proto tests own it
            continue
        family_hits = set()
        for name in pos_names:
            hits = {f.rule for f in lint_file(name)}
            family_hits |= hits
            emitted |= hits
        assert family_hits & set(RULE_FAMILIES[rule.id]), (
            "family %s has no triggering fixture evidence" % rule.id
        )
        assert neg_name is not None
        neg_findings = [
            f for f in lint_file(neg_name)
            if f.rule in RULE_FAMILIES[rule.id]
        ]
        assert neg_findings == [], (
            "clean fixture for %s is not clean: %r"
            % (rule.id, neg_findings)
        )
    ast_rule_ids = set()
    for rule in all_rules():
        ast_rule_ids.update(RULE_FAMILIES[rule.id])
    # EDL301 is repo-level, covered by the proto tests below
    assert emitted == ast_rule_ids - {"EDL301"}


# -------------------------------------------------------- baseline gate


def test_baseline_round_trip(tmp_path):
    src = os.path.join(FIXTURES, "c1_pos.py")
    findings, _ = run_rules([src], root=None, excludes=())
    assert findings
    base_path = str(tmp_path / "baseline.json")
    Baseline.from_findings(
        findings, reason="vetted in test", path=base_path
    ).save()

    reloaded = Baseline.load(base_path)
    remaining, stale = reloaded.apply(findings)
    assert remaining == [] and stale == []

    # deleting any one entry un-suppresses its finding
    with open(base_path) as f:
        data = json.load(f)
    dropped = data["entries"].pop(0)
    with open(base_path, "w") as f:
        json.dump(data, f)
    remaining, stale = Baseline.load(base_path).apply(findings)
    assert len(remaining) >= 1 and stale == []
    assert any(
        (f.rule, f.scope, f.detail)
        == (dropped["rule"], dropped["scope"], dropped["detail"])
        for f in remaining
    )


def test_stale_baseline_entry_fails():
    findings_fp_free = Baseline(entries=[{
        "rule": "EDL001", "path": "gone.py", "scope": "X.y",
        "detail": "_z", "reason": "the code this vetted was deleted",
    }])
    remaining, stale = findings_fp_free.apply([])
    assert remaining == [] and len(stale) == 1


def test_baseline_rejects_missing_reason():
    with pytest.raises(Exception):
        Baseline(entries=[{
            "rule": "EDL001", "path": "a.py", "scope": "X.y",
            "detail": "_z",
        }])


# ------------------------------------------------------------- CLI gate


def test_shipped_tree_is_clean_within_ci_budget():
    """The CI contract, both halves in one run: `make lint`'s analyzer
    half exits 0 on the shipped tree with the checked-in baseline,
    and the full-repo SINGLE-PROCESS sweep stays under the documented
    60 s budget (docs/ci.md) — the v3 value-origin pass must not blow
    the pre-shard gate's latency."""
    import time

    t0 = time.monotonic()
    assert lint_main(["--no-cache"]) == 0
    elapsed = time.monotonic() - t0
    assert elapsed < 60.0, (
        "full-repo single-process COLD lint took %.1fs (budget 60s); "
        "profile the newest rules" % elapsed
    )


def test_cache_cold_warm_parity_and_no_cache_bypass(tmp_path):
    """The incremental-cache contract, all three legs in one scenario:
    a warm run replays byte-identical SARIF to the cold run; the warm
    run genuinely READS the cache (a tampered entry with a matching
    content hash surfaces in the output — proof of hits, not re-
    analysis); and --no-cache bypasses the tampered cache back to the
    cold bytes."""
    srcdir = tmp_path / "pkg"
    srcdir.mkdir()
    for name in ("c1_pos.py", "c22_pos.py"):
        shutil.copy(
            os.path.join(FIXTURES, name),
            str(srcdir / name.replace("_pos", "_mod")),
        )
    root = str(tmp_path)
    cache_path = tmp_path / ".edl-lint-cache.json"

    def run(extra, out):
        rc = lint_main(
            [str(srcdir), "--root", root,
             "--format", "sarif", "--output", str(out)] + extra
        )
        with open(str(out), "rb") as f:
            return rc, f.read()

    rc, cold = run([], tmp_path / "cold.sarif")
    assert rc == 1
    assert cache_path.exists(), "cold run must write the cache"

    rc, warm = run([], tmp_path / "warm.sarif")
    assert rc == 1
    assert warm == cold, "warm run is not byte-identical to cold"

    with open(str(cache_path)) as f:
        data = json.load(f)
    entry = next(e for e in data["files"].values() if e["findings"])
    entry["findings"][0][5] = "TAMPERED-CACHE-SENTINEL"
    with open(str(cache_path), "w") as f:
        json.dump(data, f)
    rc, tampered = run([], tmp_path / "tampered.sarif")
    assert b"TAMPERED-CACHE-SENTINEL" in tampered, (
        "warm run re-analyzed instead of reading the cache"
    )

    rc, bypass = run(["--no-cache"], tmp_path / "bypass.sarif")
    assert bypass == cold, "--no-cache did not bypass the cache"


def test_cache_invalidated_by_file_edit(tmp_path):
    """Editing a linted file invalidates exactly its entry: the next
    run re-analyzes it and reports the new findings."""
    srcdir = tmp_path / "pkg"
    srcdir.mkdir()
    target = srcdir / "c1_mod.py"
    shutil.copy(os.path.join(FIXTURES, "c1_pos.py"), str(target))
    root = str(tmp_path)
    args = [str(srcdir), "--root", root, "--select", "EDL001"]
    assert lint_main(args) == 1
    with open(str(target), "w") as f:
        f.write("X = 1\n")
    assert lint_main(args) == 0, (
        "stale cache entry survived a content change"
    )


def test_shipped_baseline_entries_are_all_live(tmp_path):
    """Deleting ANY entry from the shipped baseline makes the run fail:
    every entry suppresses a live finding (no rot)."""
    shipped = os.path.join(REPO_ROOT, ".edl-lint-baseline.json")
    with open(shipped) as f:
        data = json.load(f)
    assert data["entries"], "shipped baseline unexpectedly empty"
    for e in data["entries"]:
        assert e["reason"].strip(), "entry without justification: %r" % e
    pruned = str(tmp_path / "pruned.json")
    for i in range(len(data["entries"])):
        dropped = dict(data)
        dropped["entries"] = (
            data["entries"][:i] + data["entries"][i + 1:]
        )
        with open(pruned, "w") as f:
            json.dump(dropped, f)
        assert lint_main(["--baseline", pruned]) == 1, (
            "baseline entry %d (%s) is not live" % (i, data["entries"][i])
        )


def test_injected_fixture_snippet_fails(tmp_path):
    """Copying any triggering fixture into a linted source tree flips
    the gate to non-zero (with the shipped baseline)."""
    srcdir = tmp_path / "pkg"
    srcdir.mkdir()
    shutil.copy(
        os.path.join(FIXTURES, "c1_pos.py"),
        str(srcdir / "injected_module.py"),
    )
    rc = lint_main([
        str(srcdir),
        "--baseline", os.path.join(REPO_ROOT, ".edl-lint-baseline.json"),
        "--select", "EDL001",
    ])
    assert rc == 1


def test_select_limits_rules(tmp_path):
    srcdir = tmp_path / "pkg"
    srcdir.mkdir()
    shutil.copy(
        os.path.join(FIXTURES, "c1_pos.py"),
        str(srcdir / "injected_module.py"),
    )
    # only the jit family selected: the C1 violation is out of scope
    rc = lint_main([
        str(srcdir),
        "--baseline", str(tmp_path / "absent.json"),
        "--select", "EDL101",
    ])
    assert rc == 0


# ------------------------------------------------ driver modes (v2 CLI)


def test_parallel_jobs_output_parity():
    """--jobs fans per-file analysis over a process pool; findings
    must be byte-identical to the serial run (same order, same
    fingerprints) so CI can use either."""
    paths = [os.path.join(FIXTURES, n)
             for n in ("c1_pos.py", "c6_pos.py", "c8_pos.py",
                       "c9_pos.py", "c10_pos.py")]
    serial, es = run_rules(paths, root=None, excludes=(), jobs=1)
    fanned, ep = run_rules(paths, root=None, excludes=(), jobs=2)
    assert not es and not ep
    assert [f.format() for f in serial] == [f.format() for f in fanned]
    assert serial, "parity test needs a non-empty finding set"


def test_github_format_annotations(tmp_path, capsys):
    srcdir = tmp_path / "pkg"
    srcdir.mkdir()
    shutil.copy(
        os.path.join(FIXTURES, "c7_pos.py"),
        str(srcdir / "injected_module.py"),
    )
    rc = lint_main([
        str(srcdir),
        "--baseline", str(tmp_path / "absent.json"),
        "--select", "EDL004", "--format", "github",
    ])
    assert rc == 1
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.startswith("::error ")]
    assert len(lines) == 2
    assert "file=" in lines[0] and "line=" in lines[0]
    assert "title=EDL004" in lines[0]


def test_explicit_file_paths_respect_excludes(tmp_path):
    """--changed-only hands individual FILES to the runner; excluded
    paths (fixtures, generated pb2) must stay excluded even when
    named explicitly, or a fixture edit would fail the gate."""
    fixture = os.path.join(FIXTURES, "c1_pos.py")
    findings, errors = run_rules([fixture], root=None)  # default excludes
    assert findings == [] and errors == []


def test_changed_only_merge_base_diff(tmp_path):
    """changed_files returns tracked-modified plus untracked .py files
    vs the merge base, as absolute paths."""
    import subprocess

    from elasticdl_tpu.analysis.lint import changed_files

    repo = str(tmp_path / "repo")
    os.makedirs(repo)

    def git(*args):
        subprocess.run(
            ("git", "-C", repo) + args, check=True,
            capture_output=True,
            env={**os.environ,
                 "GIT_AUTHOR_NAME": "t", "GIT_AUTHOR_EMAIL": "t@t",
                 "GIT_COMMITTER_NAME": "t", "GIT_COMMITTER_EMAIL": "t@t"},
        )

    git("init", "-b", "main")
    with open(os.path.join(repo, "a.py"), "w") as f:
        f.write("A = 1\n")
    with open(os.path.join(repo, "b.py"), "w") as f:
        f.write("B = 1\n")
    git("add", "-A")
    git("commit", "-m", "seed")
    with open(os.path.join(repo, "a.py"), "w") as f:
        f.write("A = 2\n")          # tracked, modified
    with open(os.path.join(repo, "c.py"), "w") as f:
        f.write("C = 1\n")          # untracked
    changed = changed_files(repo, base="main")
    assert changed == [
        os.path.join(repo, "a.py"), os.path.join(repo, "c.py"),
    ]


# --------------------------------- EDL000 / --fix-pragmas gate semantics


# @PRAGMA@ is substituted below so the scratch module's pragmas are
# invisible to the line-based pragma scanner when THIS file is linted
_PRAGMA_MOD = '''\
"""Scratch module: one used pragma, one unused trailing pragma, one
unused whole-line pragma."""
import threading


class Counter(object):
    def __init__(self):
        self._lock = threading.Lock()
        self._count = 0

    def bump(self):
        with self._lock:
            self._count += 1

    def peek(self):
        return self._count  # @PRAGMA@ disable=EDL002

    def fine(self):
        with self._lock:
            return self._count  # @PRAGMA@ disable=EDL002

    # @PRAGMA@ disable=EDL101
    def also_fine(self):
        return 1
'''.replace("@PRAGMA@", "edl-lint:")


def _write_pragma_pkg(tmp_path):
    srcdir = tmp_path / "pkg"
    srcdir.mkdir()
    (srcdir / "mod.py").write_text(_PRAGMA_MOD)
    return srcdir


def test_unused_pragma_is_a_finding(tmp_path):
    """A pragma that suppresses zero findings is itself an EDL000
    finding (the suppression mirror of the stale-baseline failure);
    the USED pragma on the same file stays silent."""
    srcdir = _write_pragma_pkg(tmp_path)
    findings, errors = run_rules([str(srcdir)], root=str(tmp_path),
                                 excludes=())
    assert not errors
    edl000 = [f for f in findings if f.rule == "EDL000"]
    assert [f.detail for f in edl000] == [
        "disable=EDL002", "disable=EDL101",
    ]
    assert {f.line for f in edl000} == {20, 22}
    # the used pragma (line 16) suppressed the real EDL002 — neither
    # that finding nor an EDL000 for it appears
    assert not any(f.rule == "EDL002" for f in findings)


def test_unused_pragma_skipped_when_rule_not_selected(tmp_path):
    """--select subsets cannot vindicate a pragma for an unselected
    rule, so they must not convict it either; disable=all needs the
    full registry."""
    from elasticdl_tpu.analysis.lint import _selected_rules

    srcdir = _write_pragma_pkg(tmp_path)
    rules = _selected_rules("EDL001,EDL000")
    findings, errors = run_rules([str(srcdir)], rules=rules,
                                 root=str(tmp_path), excludes=())
    assert not errors
    # only the EDL101-naming pragma escapes judgment (its rule did
    # not run); the unused EDL002 pragma is still convicted because
    # the lock-discipline checker DID run
    assert [f.detail for f in findings if f.rule == "EDL000"] == [
        "disable=EDL002",
    ]


def test_fix_pragmas_deletes_only_unused(tmp_path):
    srcdir = _write_pragma_pkg(tmp_path)
    rc = lint_main([
        str(srcdir), "--root", str(tmp_path),
        "--baseline", str(tmp_path / "absent.json"),
        "--fix-pragmas",
    ])
    assert rc == 0
    text = (srcdir / "mod.py").read_text()
    # the used pragma survives; the trailing one is stripped in
    # place; the whole-line one is deleted entirely
    assert text.count("edl-lint: disable") == 1
    assert "return self._count  # edl-lint: disable=EDL002\n" in text
    assert "# edl-lint: disable=EDL101" not in text
    assert "\n\n    def also_fine" in text
    # and the re-run is clean (root=None: module rules only — the
    # scratch tree has no pb2 for the repo-level EDL301 pass)
    findings, errors = run_rules([str(srcdir)], root=None,
                                 excludes=())
    assert not errors and findings == []


def test_shipped_tree_has_no_unused_pragmas():
    """The one-time repo sweep stays done: every pragma in the shipped
    tree suppresses a live finding (the full-tree run above would
    carry EDL000 findings otherwise, but pin it explicitly)."""
    from elasticdl_tpu.analysis.lint import DEFAULT_PATHS

    paths = [os.path.join(REPO_ROOT, p) for p in DEFAULT_PATHS]
    findings, errors = run_rules(paths, root=REPO_ROOT)
    assert not errors
    assert [f for f in findings if f.rule == "EDL000"] == []


# ------------------------------------------------- SARIF output (v3 CLI)


def test_sarif_output_is_byte_deterministic(tmp_path):
    """--format sarif must be byte-identical across runs AND across
    --jobs fan-out (same contract as the github/human formats), so
    the code-scanning upload can never flake on ordering."""
    srcdir = tmp_path / "pkg"
    srcdir.mkdir()
    shutil.copy(os.path.join(FIXTURES, "c7_pos.py"),
                str(srcdir / "injected_module.py"))
    outs = []
    for jobs in ("1", "2", "1"):
        out = tmp_path / ("out_%s_%d.sarif" % (jobs, len(outs)))
        rc = lint_main([
            str(srcdir),
            "--baseline", str(tmp_path / "absent.json"),
            "--select", "EDL004", "--format", "sarif",
            "--jobs", jobs, "--output", str(out),
        ])
        assert rc == 1
        outs.append(out.read_bytes())
    assert outs[0] == outs[1] == outs[2]


def test_sarif_document_structure(tmp_path):
    srcdir = tmp_path / "pkg"
    srcdir.mkdir()
    shutil.copy(os.path.join(FIXTURES, "c7_pos.py"),
                str(srcdir / "injected_module.py"))
    out = tmp_path / "edl-lint.sarif"
    rc = lint_main([
        str(srcdir),
        "--baseline", str(tmp_path / "absent.json"),
        "--select", "EDL004", "--format", "sarif",
        "--output", str(out),
    ])
    assert rc == 1
    doc = json.loads(out.read_text())
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "edl-lint"
    results = run["results"]
    assert len(results) == 2
    for res in results:
        assert res["ruleId"] == "EDL004"
        assert res["level"] == "error"
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith(
            "injected_module.py"
        )
        assert loc["region"]["startLine"] >= 1
        assert "edlLintFingerprint/v1" in res["partialFingerprints"]
    rule_ids_meta = [r["id"] for r in run["tool"]["driver"]["rules"]]
    assert rule_ids_meta == sorted(rule_ids_meta)
    assert "EDL004" in rule_ids_meta
    for meta in run["tool"]["driver"]["rules"]:
        assert meta["helpUri"] == (
            "docs/designs/static_analysis.md#%s" % meta["id"].lower()
        )


def test_sarif_carries_protocol_family_descriptors(tmp_path):
    """The EDL701-EDL704 family ships one reportingDescriptor per
    emitted id, each with a helpUri anchored to its catalogue row —
    without the descriptor the uploader drops the alert's rule link."""
    srcdir = tmp_path / "pkg"
    srcdir.mkdir()
    for name in ("c22_pos.py", "c23_pos.py"):
        shutil.copy(os.path.join(FIXTURES, name), str(srcdir / name))
    out = tmp_path / "protocol.sarif"
    rc = lint_main([
        str(srcdir),
        "--baseline", str(tmp_path / "absent.json"),
        "--select", "EDL701", "--format", "sarif",
        "--output", str(out),
    ])
    assert rc == 1
    with open(str(out)) as f:
        doc = json.load(f)
    run = doc["runs"][0]
    metas = {r["id"]: r for r in run["tool"]["driver"]["rules"]}
    for fid in ("EDL701", "EDL702", "EDL703", "EDL704"):
        assert metas[fid]["helpUri"] == (
            "docs/designs/static_analysis.md#%s" % fid.lower()
        )
    assert {res["ruleId"] for res in run["results"]} == {
        "EDL701", "EDL702", "EDL703", "EDL704",
    }


def test_sarif_clean_tree_writes_empty_results(tmp_path):
    srcdir = tmp_path / "pkg"
    srcdir.mkdir()
    (srcdir / "ok.py").write_text("X = 1\n")
    out = tmp_path / "clean.sarif"
    rc = lint_main([
        str(srcdir),
        "--baseline", str(tmp_path / "absent.json"),
        "--format", "sarif", "--output", str(out),
    ])
    assert rc == 0
    doc = json.loads(out.read_text())
    assert doc["runs"][0]["results"] == []


# ------------------------------------------------- C4: proto drift gate


def test_proto_regen_twice_is_byte_identical():
    """Determinism satellite: regenerating from the regenerated text
    yields identical bytes — field/table ordering is stable, so the
    drift gate can never flake."""
    from scripts.gen_serving_proto import generate_text

    once = generate_text()
    twice = generate_text(once)
    assert once == twice
    with open(os.path.join(
        REPO_ROOT, "elasticdl_tpu", "proto", "elasticdl_pb2.py"
    )) as f:
        assert f.read() == once, (
            "checked-in pb2 drifted: rerun scripts/gen_serving_proto.py"
        )


def test_proto_drift_detected_on_tampered_pb2(tmp_path):
    from elasticdl_tpu.analysis.proto_rules import ProtoDriftRule

    pb2 = os.path.join(
        REPO_ROOT, "elasticdl_tpu", "proto", "elasticdl_pb2.py"
    )
    with open(pb2) as f:
        text = f.read()
    tampered = str(tmp_path / "elasticdl_pb2.py")
    with open(tampered, "w") as f:
        f.write("# tampered by test\n" + text)
    findings = ProtoDriftRule().check_repo(REPO_ROOT, pb2_path=tampered)
    assert [f.rule for f in findings] == ["EDL301"]
    assert findings[0].detail == "drift"

    clean = ProtoDriftRule().check_repo(REPO_ROOT, pb2_path=pb2)
    assert clean == []
