# CI entry points (reference analogue: scripts/travis/run_job.sh wired
# into .travis.yml — here the same stages run locally or under any CI
# runner via `make ci`, and .github/workflows/ci.yml calls these exact
# targets).
#
# The suite is sharded by pytest markers (pytest.ini):
#   lint          — static analysis, runs BEFORE the shards: edl-lint
#                   (python -m elasticdl_tpu.analysis.lint — lock-
#                   discipline races, lock-order deadlock cycles,
#                   wrong-lock-held bindings, jit hazards, donated-
#                   buffer aliasing, blocking calls + deadline
#                   propagation in servicers/dispatch paths, must-
#                   release resource tracking, proto drift, the v3
#                   compile-discipline family on the value-origin
#                   dataflow — EDL105 recompile hazards, EDL106
#                   captured-constant bloat, EDL107 PRNG-key
#                   discipline — the born-gated EDL601 sharding
#                   discipline, and EDL000 unused-pragma policing;
#                   baseline in .edl-lint-baseline.json) + ruff
#                   (pinned in ci.yml; skipped with a notice when
#                   absent locally).
#                   Useful flags (pass via LINT_FLAGS): --jobs N fans
#                   per-file analysis over N processes (0 = one per
#                   CPU; output byte-identical to serial — worth it on
#                   multi-core runners), --format github emits GitHub
#                   Actions ::error annotations, --format sarif
#                   [--output F] writes byte-deterministic SARIF 2.1.0
#                   (CI uploads it to GitHub code scanning), and
#                   --fix-pragmas deletes unused suppressions.
#                   `make lint-changed` = --changed-only: lint only
#                   files changed vs the git merge base plus untracked
#                   ones — the pre-commit hook mode, sub-second on
#                   typical diffs (stale-baseline enforcement is
#                   skipped there; only full runs police baseline
#                   rot). Install the hook: bash
#                   scripts/install-hooks.sh.
#   default/fast  — everything NOT marked slow/integration (< 5 min,
#                   the per-commit gate)
#   drills        — the slow + integration shard: multi-process SPMD
#                   parity, elastic e2e (SIGKILL mid-job), gRPC
#                   master/worker, re-formation, elasticity bench
#   drill         — one real local training job + status validation,
#                   then the master SIGKILL/journal-recovery drill, the
#                   serving SIGTERM/SIGKILL drill, the multi-replica
#                   router chaos drill (SIGKILL + hot reload under live
#                   load, zero accepted-request loss, plus the router-
#                   kill phase: two journal-sharing router cells, the
#                   ring-owning cell SIGKILLed mid-load, its traffic
#                   rerouted by the CellFront and the corpse restarted
#                   from the journal), and the elastic-
#                   fleet autoscale drill (ramped Poisson load forces a
#                   scale-up, a SIGKILL forces a replacement, idle
#                   forces a drain-based scale-down; supervisor
#                   kill+restart re-adopts from its journal; p99 TTFT
#                   SLO held across every replica-count change), and
#                   the runtime-health stall drill (an injected
#                   scheduler wedge is self-reported, flight-recorder
#                   bundled, and replaced in seconds — beating the
#                   30 s lease heuristic — with zero accepted-request
#                   loss; a deliberate device-buffer leak is convicted
#                   by the memory accountant)
#   cluster-smoke — kind/minikube manifests smoke, env-gated
#                   (EDL_CLUSTER_FULL=1 + a reachable cluster)

PY ?= python
MESH_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
# keep in sync with the `lint` job in .github/workflows/ci.yml
RUFF_VERSION = 0.8.4
LINT_PATHS = elasticdl_tpu scripts tests

.PHONY: native lint lint-changed test-fast test-drills drill ci ci-fast \
	cluster-smoke clean

native:
	$(MAKE) -C elasticdl_tpu/native

lint:
	$(PY) -m elasticdl_tpu.analysis.lint $(LINT_FLAGS) $(LINT_PATHS)
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check $(LINT_PATHS); \
	elif $(PY) -m ruff --version >/dev/null 2>&1; then \
		$(PY) -m ruff check $(LINT_PATHS); \
	else \
		echo "ruff not installed (CI pins ruff==$(RUFF_VERSION)); skipping generic lint"; \
	fi

lint-changed:
	$(PY) -m elasticdl_tpu.analysis.lint \
		--changed-only $(LINT_FLAGS) $(LINT_PATHS)

test-fast: native
	$(MESH_ENV) $(PY) -m pytest tests/ -q \
		-m "not slow and not integration"

test-drills: native
	$(MESH_ENV) $(PY) -m pytest tests/ -q \
		-m "slow or integration"

drill:
	bash scripts/run_local_job_drill.sh
	JAX_PLATFORMS=cpu $(PY) scripts/run_master_kill_drill.py
	JAX_PLATFORMS=cpu $(PY) scripts/run_server_kill_drill.py
	JAX_PLATFORMS=cpu $(PY) scripts/run_router_chaos_drill.py
	JAX_PLATFORMS=cpu EDL_KV_CACHE_DTYPE=int8 $(PY) scripts/run_autoscale_drill.py
	JAX_PLATFORMS=cpu $(PY) scripts/run_stall_drill.py
	JAX_PLATFORMS=cpu $(PY) scripts/run_rollout_drill.py

ci-fast: lint test-fast

ci: lint test-fast test-drills drill

cluster-smoke:
	bash scripts/run_cluster_job_smoke.sh

clean:
	$(MAKE) -C elasticdl_tpu/native clean
