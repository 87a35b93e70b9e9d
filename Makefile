# Build orchestration (reference parity: `justfile` recipes).

.PHONY: all native test test-slow test-faults test-farm test-farm-proc test-gateway fixtures setup-committee setup-step lint lint-fast lint-deep report-ci

all: native

native:
	$(MAKE) -C spectre_tpu/native

# the driver's tier-1 form (ROADMAP "Tier-1 verify"): what a builder runs
# here is what is counted there. `test-slow` below is the whole ladder.
test: native lint lint-deep test-faults test-farm test-farm-proc test-gateway
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' -p xdist -n 6 --dist loadfile

# fault-injection tier (PR 3, grown in PR 6): deterministic resilience
# suite — beacon retry/backoff + circuit breaker, device-prove -> CPU
# fallback byte-equality, job-journal crash replay, MSM table-budget
# degrade, admission-control shed/recover, stalled-worker replacement
# (injectable clock keeps it seconds-scale), artifact-store quarantine,
# SRS checksum refusal, overload RPC contract (429/-32001/Retry-After),
# and the observability tier (PR 7): /metrics exposition parity,
# getTrace span trees, peak-RSS attribution, broken-metrics-sink
# tolerance. PR 8 adds the provenance-manifest tier (test_manifest.py):
# end-to-end manifest pins, compile telemetry, queue-wait parity,
# manifest.write fault tolerance, crash-replay without a manifest.
# PR 9 adds the output-integrity tier (test_integrity.py): verify-
# before-serve SDC matrix, artifact scrubber, readiness self-check,
# diskfull fault kind. PR 10 adds the follower tier (test_follower.py):
# unbroken update chain across period boundaries, kill-mid-prove
# byte-identical replay, cache-hit-never-touches-prover, beacon-outage
# degrade/recover, corrupt-stored-update quarantine + re-prove.
# PR 14 adds the gateway tier (test_gateway.py): pack corruption
# quarantine -> rebuild, gateway.pack_write ioerror, torn pack-journal
# tail, and the fault-scheduled 10^4-client acceptance drill.
# The device-boundary span tests are tests/test_device_prove.py
# (one file for everything that proves the tiny circuit on TpuBackend), and
# the MSM table-budget degrade is tests/test_msm_modes.py's, beside the
# kernels it falls back to.
# Also part of the full pytest ladder above.
test-faults: native
	JAX_PLATFORMS=cpu python -m pytest tests/test_faults.py tests/test_service.py tests/test_observability.py tests/test_device_prove.py tests/test_manifest.py tests/test_integrity.py tests/test_follower.py tests/test_farm.py tests/test_gateway.py -q

# proof-farm failover matrix (PR 11, tests/test_farm.py): replica crash
# mid-prove -> lease takeover with a byte-identical proof, breaker-open
# replica receives no work, SDC re-prove on a DIFFERENT replica
# (cross-host verification), dispatcher restart replays leases without
# double-proving, beacon quorum ignores a lone dissenting head, and the
# UpdateStore 10k-period RSS bound.
test-farm: native
	JAX_PLATFORMS=cpu python -m pytest tests/test_farm.py -q

# real-process failover drill (PR 18, tests/test_farm_proc.py): three
# actual serve() subprocesses announce themselves to an empty dispatcher
# head, one is SIGKILLed mid-prove -> exactly one lease takeover, a
# byte-identical final proof, and TTL deregistration of the corpse; plus
# lease-journal replay across a killed dispatcher PROCESS. Skips cleanly
# where fork+HTTP is unavailable; the `timeout` wrapper is the hard
# wall-clock budget (subprocesses each pay a jax import).
test-farm-proc: native
	timeout -k 10 600 env JAX_PLATFORMS=cpu python -m pytest tests/test_farm_proc.py -q

# light-client serving gateway (PR 14, tests/test_gateway.py): HTTP
# cache semantics (digest ETags stable across restarts, 304s, immutable
# only below tip), pack byte-identity vs direct UpdateStore reads, pack
# survival across restart + scrubber, and the follower -> loadgen
# end-to-end drill with the fault schedule armed.
test-gateway: native
	JAX_PLATFORMS=cpu python -m pytest tests/test_gateway.py -q

test-slow: native
	RUN_SLOW=1 python -m pytest tests/ -q

fixtures:
	python -c "from spectre_tpu.test_utils import generate_fixtures; \
	from spectre_tpu import spec; generate_fixtures(spec.TINY); \
	generate_fixtures(spec.MINIMAL)"

setup-committee:
	python -m spectre_tpu.prover_service.cli --spec tiny circuit committee-update setup --k 17

setup-step:
	python -m spectre_tpu.prover_service.cli --spec tiny circuit sync-step setup --k 17

# manifest CI gate (PR 10): diff a candidate provenance manifest against
# a baseline and exit 3 on a prove_s regression (> 10% by default) or any
# new backend compile. Point the vars at manifest files or job ids:
#   make report-ci BASELINE_MANIFEST=base.manifest.json CANDIDATE_MANIFEST=cand.manifest.json
BASELINE_MANIFEST ?= baseline.manifest.json
CANDIDATE_MANIFEST ?= candidate.manifest.json
report-ci:
	JAX_PLATFORMS=cpu python -m spectre_tpu.observability report $(BASELINE_MANIFEST) --diff $(CANDIDATE_MANIFEST) --ci

# static analysis: compile check + the soundness auditor / kernel lint /
# trace-lint AST scan (spectre_tpu/analysis). Fails on any non-baselined
# error finding; accepted findings live in spectre_tpu/analysis/baseline.json
# (see README). --no-probes: the dynamic retrace probes are the lint-deep
# tier below, so `make test` (which runs both) compiles them only once.
lint:
	python -m compileall -q spectre_tpu tests __graft_entry__.py chip_smoke.py
	JAX_PLATFORMS=cpu python -m spectre_tpu.analysis --fail-on error --no-probes

# kernel-lint only (seconds; the full `lint` builds three tiny circuits)
lint-fast:
	JAX_PLATFORMS=cpu python -m spectre_tpu.analysis --engine kernel --fail-on error

# deep tier: trace-cache hygiene — static AST scan of jit/shard_map/
# pallas_call sites vs the declared runner registry (TC-FRESH-JIT,
# TC-CONST-CAPTURE, TC-UNSTABLE-STATIC, TC-UNCACHED-RUNNER) plus dynamic
# double-call probes over every runner family asserting zero recompiles on
# the second call (TC-RETRACE-DYN — the historical rc=124 class). Budgeted
# under 120s on a 1-core CPU host (tests/test_analysis.py pins it).
lint-deep:
	JAX_PLATFORMS=cpu python -m spectre_tpu.analysis --engine trace --fail-on error
