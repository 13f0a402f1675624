# Top-level build orchestration (counterpart of the reference's GNU-make
# driver; the device "build" is XLA tracing at runtime, so make targets
# cover the native library, tests, and checks; speed is measured by
# benchmark/run.py alone, see PERF.md).

PY ?= python
TEST_ENV ?= JAX_PLATFORMS=cpu

.PHONY: all native capi test test-fast scratch-tests boundary-tests \
        stages-tests mode-tests faultcheck commcheck \
        cachecheck servecheck obscheck telemetrycheck examples clean \
        list-stencils lint check conformance conformance-quick loadcheck \
        pushcheck

all: native test

native:
	$(MAKE) -C yask_tpu/native

capi:
	$(MAKE) -C yask_tpu/native capi

test:
	$(TEST_ENV) $(PY) -m pytest tests/ -q

test-fast:
	$(TEST_ENV) $(PY) -m pytest tests/ -q -x -k "not stencil_validates"

# focused suites (reference scratch-tests/boundary-tests/stages-tests,
# src/kernel/Makefile:1186-1192)
scratch-tests:
	$(TEST_ENV) $(PY) -m pytest tests/ -q -k "scratch"

boundary-tests:
	$(TEST_ENV) $(PY) -m pytest tests/ -q -k "boundary"

stages-tests:
	$(TEST_ENV) $(PY) -m pytest tests/ -q -k "stages or stage"

mode-tests:
	$(TEST_ENV) $(PY) -m pytest tests/test_modes.py tests/test_pallas.py -q

# repo-specific AST rules always run; ruff runs when installed (the
# container does not ship it — the config in pyproject.toml is for
# hosts that do)
lint:
	$(PY) tools/repo_lint.py
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check .; \
	else \
		echo "ruff not installed; skipped (repo_lint ran)"; \
	fi

# the persistent AOT compile cache end-to-end: digest/memo/disk units,
# the cross-process reuse acceptance test (second process lowers ZERO
# times), eviction bounds, corrupt-entry and injected cache.load /
# cache.store fault fallback (see docs/performance.md)
cachecheck: lint
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) -m pytest \
		tests/test_cache.py tests/test_ensemble.py -q

# the serving layer end-to-end on the CPU mesh: the multi-tenant
# acceptance path (two prepared stencils, 8 concurrent tenants,
# bit-identity + occupancy > 1 + warm-restart zero lowerings), the
# injected serve.run degradation ladder, sanity quarantine on release,
# journal schema, the SERVE-* checker rules, shape-bucket co-batching
# bit-identity, streaming/preemption, and the warm-cache worker fleet
# (see docs/serving.md)
servecheck: lint
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) -m pytest \
		tests/test_serve.py tests/test_serve_buckets.py \
		tests/test_fleet.py -q

# the observability spine: tracer no-op guarantee (YT_TRACE unset =>
# bit-identical run, no file), span nesting/attrs, metrics percentile
# parity with the old server quantiles, end-to-end trace_id joins
# across journal/trace artifacts, Perfetto export validity,
# trace compaction bounds (see docs/observability.md)
obscheck: lint
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) -m pytest \
		tests/test_obs.py -q

# the telemetry plane over the obs spine: fleet snapshot merging
# (pooled histogram samples, never averaged percentiles), Prometheus
# exposition + name stability, SLO burn-rate breach/non-breach
# windows, and the no-op guarantee with YT_TRACE unset (see
# docs/observability.md)
telemetrycheck: lint
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) -m pytest \
		tests/test_telemetry.py -q

# seeded deterministic elastic-fleet closed loop on CPU: latency-burn
# spike -> journaled scale_up -> warm spawn (zero lowerings) ->
# admission recovery -> idle drain scale_down with sessions migrated
# zero-lost (see docs/serving.md "Autoscaling"; the chaos soak and
# trace replay are the slow-marked pytest side of the same harness)
loadcheck: lint
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) tools/load_harness.py --check

# push-memory tile-graph fusion + device-resident bulk serving: the
# eligibility oracle, pallas push bit-equality vs the host-chained
# oracle, plan_only byte pin, PIPELINE-PUSH-* checker rules, tuner
# push A/B, the resident-queue bit-identity/journal/fault-site
# acceptance, and the push matrix axis (see docs/performance.md
# "Push-memory tile-graph fusion")
pushcheck: lint
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) -m pytest \
		tests/test_pipeline.py tests/test_resident.py -q
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) -m pytest \
		tests/test_config_matrix.py -q -k "pipeline"

# static checker over the flagship configs: Mosaic legality, VMEM
# feasibility (incl. the round-3 spill-OOM class), races, explain.
# See docs/checking.md; nonzero exit on any error-severity finding.
check: cachecheck servecheck obscheck telemetrycheck conformance-quick \
       loadcheck pushcheck
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) -m yask_tpu.checker \
		-stencil iso3dfd -radius 8 -g 256 -mode pallas -wf_steps 2
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) -m yask_tpu.checker -all_stencils

# differential checker-soundness harness (docs/checking.md): random
# solution+config per seed, static verdict vs an actual pallas-vs-jit
# run on the interpret host; nonzero exit on any unsound/overstrict
# disagreement (minimized repro JSONs land under tools/logs/).
# `check` carries the 16-seed quick subset; the 200-seed sweep is the
# pre-merge / nightly gate.
conformance:
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) tools/checker_conformance.py

conformance-quick: lint
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) tools/checker_conformance.py --quick

# the resilience layer end-to-end on the CPU mesh: fault classes /
# guards / journal / checkpoint units plus the acceptance paths —
# all-zero quarantine, SIGKILL-mid-run
# kill-resume (same-mode and cross-mode restore), the injected
# device-hang pallas → jit degradation ladder, and the fleet failover
# chaos acceptance (chaos-killed worker → checkpoint-backed session
# failover bit-identical to an uninterrupted twin, exactly-once
# in-flight retry, heartbeat-miss replacement — see docs/resilience.md)
faultcheck: lint
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) -m pytest \
		tests/test_resilience.py tests/test_fleet_failover.py -q

# the communication scheduler end-to-end on the CPU mesh: plan
# construction, coalescing/order bit-equality, corner composition,
# measured collective rounds, COMM-* checker rules, multihost launcher
# (see docs/performance.md "ICI/DCN comm scheduling")
commcheck: lint
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) -m pytest \
		tests/test_comm_schedule.py -q

examples:
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) examples/swe_main.py
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) examples/wave_eq_main.py

list-stencils:
	$(TEST_ENV) JAX_PLATFORMS=cpu $(PY) -m yask_tpu.compiler -list

clean:
	$(MAKE) -C yask_tpu/native clean
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
