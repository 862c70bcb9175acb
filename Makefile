# CI entry points — the reference's three-tier test strategy in miniature
# (SURVEY.md §4; reference: .buildkite/gen-pipeline.sh):
#   tier 1  unit suites on an 8-device virtual CPU mesh (tests/conftest.py)
#   tier 2  multi-process collective correctness over loopback
#   tier 3  end-to-end launcher/elastic jobs + the driver entry hooks
#
#   make test        everything (what CI runs)
#   make test-fast   tier 1 only, minus the slow e2e suites
#   make chaos       fault-injection suite: elastic jobs under injected
#                    rendezvous outages / worker kills / flapping hosts
#                    (tests marked `faults`; see docs/resilience.md)
#   make metrics     observability smoke: registry/exporter units + a
#                    scraped 2-process elastic job (docs/observability.md)
#   make doctor-smoke flight-recorder + hvddoctor: unit suite plus the
#                    2-process chaos e2e (injected silent staller /
#                    SIGKILL) asserting the doctor names the stalled
#                    rank and the last-agreed collective
#                    (docs/observability.md, docs/troubleshooting.md)
#   make watch-smoke hvdwatch online anomaly detection + hvdtop
#                    (docs/observability.md): the fake-clock detector
#                    unit suite plus the 2-process elastic e2e — a
#                    mid-run one-rank slowdown injected via
#                    testing/faults.py must be detected within the
#                    step budget, with a flight dump, an on-demand
#                    device trace and a `watch` KV record left behind,
#                    hvddoctor naming the rank+detector, hvdtop showing
#                    the live anomaly, and a clean run reporting zero
#   make serve-smoke serving tier (docs/serving.md): the deterministic
#                    unit suite plus the 2-process elastic serving e2e
#                    — SIGKILL one replica under continuous load; zero
#                    accepted requests dropped, p99 bounded through the
#                    failover, hvddoctor names the dead replica
#   make trace-smoke hvdtrace causal tracing (docs/observability.md):
#                    span model / cross-process propagation / doctor
#                    join unit suite plus the traced serving e2e — a
#                    requeued-after-SIGKILL request's trace must carry
#                    BOTH dispatch attempts, and the slowest request
#                    must split into queue/dispatch/device time
#   make ckpt-smoke  async checkpointing + exactly-once elastic resume
#                    (docs/checkpointing.md): the manifest/commit-
#                    protocol + sharded-snapshot + AsyncCheckpointer +
#                    TrainLoopState unit suite, then the chaos e2e —
#                    a 2-process elastic job whose EVERY worker is
#                    SIGKILL'd mid-epoch must resume from the last
#                    COMMITTED step (not epoch start), finish with a
#                    final state bit-identical to the uninterrupted
#                    twin, and leave a doctor-readable [ckpt] trail
#   make lint        hvdlint static analysis: collective-consistency +
#                    concurrency rules + env-knob docs drift, gating on
#                    findings NEW relative to the checked-in baseline
#                    (docs/static_analysis.md)
#   make hlo-lint    hvdhlo compile-time lint (docs/static_analysis.md):
#                    lower the canonical DP train step under the current
#                    fusion config on the 8-rank virtual mesh and run
#                    the HVD2xx program rules (giant-allreduce /
#                    host-sync / donation / padding / upcast) against
#                    scripts/hvdhlo_baseline.json — the regression guard
#                    that keeps ops/fusion.py reverts out of the HLO
#   make shard-lint  hvdshard static sharding & per-device memory lint
#                    (docs/static_analysis.md): the HVD3xx fixture/
#                    liveness unit suite, then the canonical 2-D
#                    (batch x model) mesh LM step lowered pre- AND
#                    post-SPMD under a 1 GiB per-device HBM budget,
#                    gated against scripts/hvdshard_baseline.json —
#                    the static gate in front of the GSPMD backend
#                    (replicated tables, partitioner-inserted
#                    resharding, compile-time OOM)
#   make gspmd-smoke GSPMD hybrid-parallel backend (docs/parallelism.md):
#                    hybrid-vs-DP loss-trajectory numerics on the
#                    8-device mesh (tp=4 x dp=2, moe and pipeline axis
#                    variants) incl. the slow-marked canonical-program
#                    lowering tests, and a 2-process mesh/sharding-
#                    decision agreement scenario under
#                    HOROVOD_CHECK_COLLECTIVES=1 (the runtime
#                    lm_runtime step is CLI-gated in `make shard-lint`)
#   make race        hvdrace: the concurrency/hammer suites (timeline,
#                    metrics, elastic driver, rendezvous KV, verifier)
#                    run under the runtime lockset race detector
#                    (HOROVOD_RACE_CHECK=1); any guarded-by violation
#                    fails the run (docs/static_analysis.md)
#   make native      build the native control-plane library

PYTHON ?= python
PYTEST ?= $(PYTHON) -m pytest -q

.PHONY: test test-fast test-unit test-multiprocess test-e2e chaos entry native lint lint-baseline hlo-lint hlo-lint-baseline shard-lint shard-lint-baseline sched-lint sched-lint-baseline num-lint num-lint-baseline gspmd-smoke metrics race doctor-smoke serve-smoke trace-smoke watch-smoke ckpt-smoke kv-ha-smoke fusion-smoke conv-smoke

test: lint hlo-lint shard-lint sched-lint num-lint gspmd-smoke test-unit test-multiprocess test-e2e chaos doctor-smoke serve-smoke trace-smoke watch-smoke ckpt-smoke kv-ha-smoke fusion-smoke conv-smoke entry

test-fast:
	$(PYTEST) tests/ --ignore=tests/test_multiprocess.py \
	    --ignore=tests/test_elastic_e2e.py -x

test-unit:
	$(PYTEST) tests/ --ignore=tests/test_multiprocess.py \
	    --ignore=tests/test_elastic_e2e.py

test-multiprocess:
	$(PYTEST) tests/test_multiprocess.py

test-e2e:
	$(PYTEST) tests/test_elastic_e2e.py

# Only the `faults`-marked e2e jobs: the fast resilience/fault unit tests
# already run in test-unit, so `make test` doesn't run them twice.
chaos:
	$(PYTEST) tests/test_faults.py --run-faults -m faults

metrics:
	$(PYTEST) tests/test_metrics.py tests/test_metrics_e2e.py \
	    tests/test_timeline.py

# Flight recorder + hvddoctor (docs/observability.md): the unit suites
# run in tier 1 too; the e2e chaos jobs (faults marker) only run here.
# test_perfscope_e2e rides along: its slow-input straggler e2e is a
# doctor acceptance (the perf section names the rank + dominant phase).
doctor-smoke:
	$(PYTEST) tests/test_flight.py tests/test_perfscope.py
	$(PYTEST) tests/test_flight_e2e.py tests/test_perfscope_e2e.py \
	    --run-faults -m faults

# hvdwatch + hvdtop (docs/observability.md): the fake-clock detector
# unit suite runs in tier 1 too; the 2-process slowdown-injection e2e
# (faults marker) only here.
watch-smoke:
	$(PYTEST) tests/test_watch.py
	$(PYTEST) tests/test_watch_e2e.py --run-faults -m faults

# Serving tier (docs/serving.md): the fake-clock batcher/engine/pool
# unit suite runs in tier 1 too; the 2-process elastic serving e2e
# (faults marker — SIGKILL a replica mid-flight under load) only here.
serve-smoke:
	$(PYTEST) tests/test_serve.py
	$(PYTEST) tests/test_serve_e2e.py --run-faults -m faults

# hvdtrace causal tracing (docs/observability.md): the span-model /
# propagation / doctor-join unit suite runs in tier 1 too; the traced
# 2-process serving e2e (faults marker — requeue-after-SIGKILL must
# carry both dispatch attempts) only here.
trace-smoke:
	$(PYTEST) tests/test_tracing.py
	$(PYTEST) tests/test_serve_e2e.py --run-faults -m faults \
	    -k trace

# Async checkpointing + exactly-once elastic resume
# (docs/checkpointing.md): the deterministic unit suite runs in tier 1
# too; the whole-job-SIGKILL chaos e2e (faults marker) only here.
ckpt-smoke:
	$(PYTEST) tests/test_ckpt.py
	$(PYTEST) tests/test_ckpt_e2e.py --run-faults -m faults

# Replicated rendezvous control plane (docs/resilience.md): the fencing/
# replication/failover unit suite runs in tier 1 too; the host_kill
# chaos e2e (faults marker — SIGKILL the PRIMARY KV replica's process
# group mid-training and mid-serving-load) only here.
kv-ha-smoke:
	$(PYTEST) tests/test_kv_ha.py
	$(PYTEST) tests/test_kv_ha_e2e.py --run-faults -m faults

# Conv fast path (docs/perf.md): the fused-vs-reference equivalence
# suite for the conv+BN+ReLU block kernels + the layout pass, then the
# hvdhlo lint of the lane-padded ResNet-block step program — the
# C=64 50%-waste fixture's live twin must lower CLEAN (zero HVD204)
# under the default layout config; HOROVOD_LAYOUT_PAD=0 or a layout
# regression trips it, on CPU-only CI.
conv-smoke:
	$(PYTEST) tests/test_conv_block.py tests/test_layout.py
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    $(PYTHON) -m horovod_tpu.analysis --hlo-step resnet_block \
	    --baseline scripts/hvdhlo_baseline.json

# Fusion-cliff guard (docs/perf.md): interleaved threshold sweep on the
# 8-rank virtual mesh asserting no >1.5x latency cliff between adjacent
# bucket sizes (the r05 16-64MB regression the bucket cap + oversize
# chunking fixed). Wall-clock — excluded from tier-1 via the perf marker.
fusion-smoke:
	$(PYTEST) tests/test_fusion_smoke.py --run-perf -m perf

# scripts/ and the training-shaped test workers issue collectives too —
# they carry the same stall risks the HVD0xx rules exist to catch.
LINT_PATHS = horovod_tpu/ examples/ scripts/ \
    tests/mp_worker.py tests/elastic_worker.py \
    tests/serve_replica.py tests/ckpt_writer.py

lint:
	$(PYTHON) -m horovod_tpu.analysis $(LINT_PATHS) \
	    --baseline scripts/hvdlint_baseline.json

# Regenerate the accepted-findings baseline (review the diff before
# committing: every entry is a finding future lint runs stop gating on).
lint-baseline:
	$(PYTHON) -m horovod_tpu.analysis $(LINT_PATHS) \
	    --format json > scripts/hvdlint_baseline.json || true

# hvdhlo compile-time program lint (docs/static_analysis.md,
# docs/perf.md). The env forces the virtual CPU mesh; the analyzer also
# sets the CPU platform itself (a lint never takes a chip).
hlo-lint:
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    $(PYTHON) -m horovod_tpu.analysis --hlo-step lm \
	    --baseline scripts/hvdhlo_baseline.json

hlo-lint-baseline:
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    $(PYTHON) -m horovod_tpu.analysis --hlo-step lm \
	    --format json > scripts/hvdhlo_baseline.json || true

# hvdshard static sharding & per-device memory lint
# (docs/static_analysis.md): the fixture/liveness unit suite pins every
# HVD3xx rule both ways (incl. the replicated-twin acceptance: forced
# fully-replicated params trip HVD301+HVD302 on CPU CI), then the
# canonical 2-D-mesh LM step is lowered pre- and post-SPMD and gated
# against the checked-in EMPTY baseline. The 1 GiB budget arms HVD303:
# the canonical program's static per-device peak is ~25 MB — a 40x
# regression margin before the compile-time OOM gate trips.
shard-lint:
	$(PYTEST) tests/test_hvdshard.py
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    HOROVOD_HLO_LINT_HBM_BUDGET=1G \
	    $(PYTHON) -m horovod_tpu.analysis --hlo-step lm_sharded \
	    --baseline scripts/hvdshard_baseline.json
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    HOROVOD_HLO_LINT_HBM_BUDGET=1G \
	    $(PYTHON) -m horovod_tpu.analysis --hlo-step lm_runtime \
	    --baseline scripts/hvdshard_baseline.json

# hvdsched static collective-schedule lint (docs/static_analysis.md):
# the HVD4xx fixture suite pins every rule both ways (the misordered
# two-program pair trips HVD401, the broken permute ring HVD402, the
# hierarchical twin HVD404 under a declared slice boundary) plus the
# cost-model unit suite, then the canonical step programs' post-SPMD
# schedules are gated against the checked-in EMPTY baseline. --select
# keeps this gate on the HVD4xx family; the same programs' HVD2xx/3xx
# coverage lives in `make shard-lint`.
sched-lint:
	$(PYTEST) tests/test_hvdsched.py tests/test_sched_cost.py
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    $(PYTHON) -m horovod_tpu.analysis --hlo-step lm_sharded \
	    --select HVD401,HVD402,HVD403,HVD404,HVD405 \
	    --baseline scripts/hvdsched_baseline.json
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    $(PYTHON) -m horovod_tpu.analysis --hlo-step lm_runtime \
	    --select HVD401,HVD402,HVD403,HVD404,HVD405 \
	    --baseline scripts/hvdsched_baseline.json

sched-lint-baseline:
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    $(PYTHON) -m horovod_tpu.analysis --hlo-step lm_sharded \
	    --select HVD401,HVD402,HVD403,HVD404,HVD405 \
	    --format json > scripts/hvdsched_baseline.json || true

# hvdnum (HVD5xx): the numerics & reduction-semantics wall. The fixture
# suite pins every rule both ways (bf16-accumulating dot vs the
# preferred_element_type=f32 twin, downcast-then-reduce vs
# reduce-then-downcast, the baked world-size divisor vs the true group
# mean, the determinism-hazard trio vs the keyed twin, the
# different-mesh sum pair vs the mean pair) plus the group_axis_label
# edge-case suite the scale table's axis attribution rides on, then
# the canonical step programs' post-SPMD dtype-flow and gradient-scale
# invariants are gated against the checked-in EMPTY baseline.
num-lint:
	$(PYTEST) tests/test_hvdnum.py tests/test_group_axis_label.py
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    $(PYTHON) -m horovod_tpu.analysis --hlo-step lm_sharded \
	    --select HVD501,HVD502,HVD503,HVD504,HVD505 \
	    --baseline scripts/hvdnum_baseline.json
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    $(PYTHON) -m horovod_tpu.analysis --hlo-step lm_runtime \
	    --select HVD501,HVD502,HVD503,HVD504,HVD505 \
	    --baseline scripts/hvdnum_baseline.json

num-lint-baseline:
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    $(PYTHON) -m horovod_tpu.analysis --hlo-step lm_sharded \
	    --select HVD501,HVD502,HVD503,HVD504,HVD505 \
	    --format json > scripts/hvdnum_baseline.json || true

shard-lint-baseline:
	env JAX_PLATFORMS=cpu \
	    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	    HOROVOD_HLO_LINT_HBM_BUDGET=1G \
	    $(PYTHON) -m horovod_tpu.analysis --hlo-step lm_sharded \
	    --format json > scripts/hvdshard_baseline.json || true

# GSPMD hybrid-parallel backend (docs/parallelism.md): the hybrid-vs-DP
# numerics suite on the 8-device CPU mesh (tp=4 x dp=2 loss trajectory
# matches the pure-DP run within documented tolerance; moe/pipeline
# axis variants match their dense/unsplit references) INCLUDING the
# slow-marked canonical-program lm_runtime lowering tests tier-1
# skips, and the 2-process mesh/sharding-decision agreement scenario
# under the fingerprint verifier. (The lm_runtime CLI gate itself
# lives in `make shard-lint` — not duplicated here.)
gspmd-smoke:
	$(PYTEST) tests/test_gspmd.py --run-slow
	$(PYTEST) tests/test_multiprocess.py -k mesh_shard_sync

# The warm-compile-cache test is a wall-clock subprocess benchmark, not
# a concurrency test — load-sensitive, and none of its work runs through
# the instrumented classes, so it only adds noise to this gate.
race:
	env HOROVOD_RACE_CHECK=1 $(PYTEST) tests/test_race.py \
	    tests/test_timeline.py tests/test_metrics.py \
	    tests/test_flight.py tests/test_perfscope.py \
	    tests/test_tracing.py tests/test_watch.py \
	    tests/test_elastic.py tests/test_runner.py tests/test_secret.py \
	    tests/test_hvdlint.py tests/test_hvdnum.py \
	    tests/test_group_axis_label.py \
	    tests/test_serve.py tests/test_ckpt.py \
	    tests/test_kv_ha.py \
	    --deselect tests/test_elastic.py::test_elastic_reset_warm_compile_cache

entry:
	$(PYTHON) __graft_entry__.py

native:
	$(MAKE) -C horovod_tpu/native
