# quokka-tpu developer entry points.  The lint gate also runs inside tier-1
# (tests/test_lint_clean.py), so `make test` implies `make lint`.

PY ?= python
export JAX_PLATFORMS ?= cpu

.PHONY: lint lint-baseline verify-static plan-fuzz test test-slow sanitize-demo chaos-smoke obs-smoke shuffle-smoke fusion-smoke warmup-smoke stream-smoke mem-smoke explain-smoke health-smoke adapt-smoke resume-smoke durability-smoke chip-smoke-rehearse verify

# engine-invariant static analysis; exits nonzero on findings beyond the
# checked-in baseline (quokka_tpu/analysis/baseline.json)
lint:
	$(PY) -m quokka_tpu.analysis.lint quokka_tpu/

# shrink the baseline after fixing findings (never grows it silently: new
# findings still fail `make lint` until fixed or hand-added with a rationale)
lint-baseline:
	$(PY) -m quokka_tpu.analysis.lint quokka_tpu/ --write-baseline

# the full static-analysis plane, exactly as tier-1 runs it: the lint gate
# (baseline'd, wall-time budgeted), the control-store protocol verifier
# (QK014-QK017, NO baseline — violations fail outright), and the qkflow
# engine's known-answer self-check.  The schedex race explorer also proves
# the shipped rewind rule closes the recovery race over a seeded batch.
verify-static:
	$(PY) -m pytest tests/test_lint_clean.py tests/test_lint_rules.py \
		tests/test_flow.py tests/test_protocol.py tests/test_schedex.py \
		tests/test_planck.py \
		-q -p no:cacheprovider
	$(PY) -m quokka_tpu.analysis.protocol quokka_tpu/
	$(PY) -m quokka_tpu.analysis.schedex --seeds 120
	$(PY) -m quokka_tpu.analysis.planck
	$(MAKE) plan-fuzz

# differential optimizer fuzzer: 200 seeded random plans, each planned
# under the full pass pipeline vs every cumulative pass prefix vs
# QK_STAGE_FUSE=0; plans must verify statically (planck QK021-QK024) and
# execute bit-identically to the unoptimized plan on tiny int data.  A
# failing seed prints a ddmin-shrunk 1-minimal repro op list.
plan-fuzz:
	$(PY) -m quokka_tpu.analysis.planfuzz --seeds 200

test:
	$(PY) -m pytest tests/ -q -m 'not slow'

test-slow:
	$(PY) -m pytest tests/ -q -m slow

# watch the deadlock watchdog shoot a wedged two-worker run (exits nonzero
# in seconds, with every thread's stack on stderr)
sanitize-demo:
	QK_SANITIZE=1 QK_SANITIZE_DEADLINE=5 $(PY) tests/sanitize_deadlock_case.py

# watch the stall detector dump the merged flight-recorder timeline for the
# same wedged run: Chrome trace (Perfetto-loadable) + stall report naming
# the stuck worker and its in-flight task, in QK_DUMP_DIR
stall-demo:
	QK_COORD_TIMEOUT=20 $(PY) tests/sanitize_deadlock_case.py

# observability smoke: a profiled query's critical-path buckets must sum to
# the measured wall time within 10%, and /metrics + /status must serve a
# live 2-query service run (Prometheus text with per-query histograms)
obs-smoke:
	$(PY) -m quokka_tpu.obs.smoke

# shuffle data-plane smoke: a seeded Q3-shaped join+aggregate (two hash
# exchanges) run twice; the warm run must show ZERO blocking host readbacks
# on the push path (shuffle.host_syncs flat) and ZERO real recompiles (the
# sanitizer sentinel), with nonzero shuffle.bytes proving the exchange ran
shuffle-smoke:
	$(PY) -m quokka_tpu.runtime.shuffle_smoke

# whole-stage-fusion smoke: a Q3-shaped linear join chain must plan into a
# FusedStageExecutor (stagefuse.exec > 0), run warm with ZERO real
# recompiles and ZERO blocking host syncs, and match the QK_STAGE_FUSE=0
# re-plan BIT-EXACTLY on integer-valued data (ops/stagefuse.py)
fusion-smoke:
	$(PY) -m quokka_tpu.runtime.fusion_smoke

# compile-plane smoke: run a Q3-shaped query in one process (populating the
# XLA + AOT executable caches and the plan ledger), then again in a FRESH
# process against the populated cache — the fresh replica must pay zero
# real backend compiles and show AOT prewarm/cache hits (cross-restart
# executable persistence, runtime/compileplane.py)
warmup-smoke:
	$(PY) -m quokka_tpu.runtime.warmup_smoke

# CPU rehearsal of chip_smoke.py (the chip itself is reached only through the
# builder's chip tool: `chiprun -- python chip_smoke.py`): every phase at
# SF 0.01 with x64 off and the kernel strategies the TPU picks (sort
# group-by, sorted join build, sort asof), so the chip's branches
# run here first.  Its last line names the platform jax reported (cpu) and
# "rehearsal": true — it cannot pass for a chip run.
chip-smoke-rehearse:
	JAX_PLATFORMS=cpu \
	QK_KERNEL_STRATEGY=groupby=sort,join_build=sort,asof=sort \
		$(PY) chip_smoke.py --rehearse --sf 0.01

# streaming-plane smoke: a continuous asof join + a continuous windowed
# aggregate over tailed CSV sources, under a seeded QK_CHAOS kill plan AND
# a SIGKILL of the hosting service mid-stream; the parent resumes both
# streams from their incremental-checkpoint manifests and the merged pane
# deltas must be BIT-EXACT vs the one-shot batch runs, with the resume
# replaying only the post-frontier segment tail (never the whole stream)
stream-smoke:
	$(PY) -m quokka_tpu.streaming.smoke

# memory-plane smoke: a Q3-shaped service query must GC with ZERO leaked
# ledger entries, the device-buffer ledger must reconcile with
# jax.live_arrays() within QK_MEM_RECONCILE (10%), and a second submission
# of the same plan must be admitted on the MEASURED footprint persisted
# under the plan fingerprint, not the size_hint() guess
mem-smoke:
	$(PY) -m quokka_tpu.obs.mem_smoke

# EXPLAIN ANALYZE smoke: a Q3-shaped service query's operator-statistics
# snapshot must reconcile rows end-to-end (scans == parquet rows, every
# exec intake == its in-edges' delivered totals), carry the per-edge skew
# report, add ZERO shuffle.host_syncs, and a second submission of the same
# plan must be admitted on the MEASURED source cardinalities persisted
# under the plan fingerprint
explain-smoke:
	$(PY) -m quokka_tpu.obs.explain_smoke

# adaptive-planning smoke: a cold plan decides from hints/samples, the warm
# re-plan must FLIP >= 1 decision from the persisted cardinality profile
# (measured basis, visible in explain's planner-decision section), a seeded
# zipfian build must trigger the mid-query skew re-partition, and both the
# flipped plan and the adapted run must be BIT-EXACT vs their static
# counterparts (QK_ADAPT=0) with ZERO added host syncs (planner/adapt.py)
adapt-smoke:
	$(PY) -m quokka_tpu.planner.adapt_smoke

# chaos plane soak: >= 20 seeded mixed-fault runs (RPC drops/delays, flaky
# store calls, worker kills, spill + checkpoint corruption) each asserting
# BIT-EXACT results vs an undisturbed baseline; every injected corruption
# must be detected via checksum.  A failing run prints its QK_CHAOS spec
# and an exact replay command.  Bounded for the 1-core CI box (~1 min).
chaos-smoke:
	QK_COORD_TIMEOUT=240 $(PY) -m quokka_tpu.chaos.soak --runs 20

# durable-batch smoke: two TPC-H-shaped durable queries SIGKILLed mid-run
# in a child service process; a fresh supervisor must re-admit both from
# their crash-consistent resume manifests and finish BIT-EXACT vs the
# undisturbed run with BOUNDED replay (checkpointed frontiers honored,
# skipped input segments > 0), zero added host syncs, zero admission-byte
# or manifest residue
resume-smoke:
	QK_COORD_TIMEOUT=240 $(PY) -m quokka_tpu.service.resume_smoke

# the durability aggregate: every process-death story in one command —
# batch resume, streaming resume, and the full chaos soak (whose cycle
# includes the batch-resume-under-corruption mode)
durability-smoke: resume-smoke stream-smoke chaos-smoke

# health-plane smoke: two service queries polled live — progress must run
# monotone 0->1 (cold on the size_hint basis, warm on the measured
# cardprofile basis with a finite ETA), /history must accumulate samples
# with derived rates, /health must degrade under an injected skew fault and
# recover when it clears, and the whole plane must add ZERO host syncs
health-smoke:
	$(PY) -m quokka_tpu.obs.health_smoke

# the pre-merge aggregate: static analysis, tier-1 tests, and the
# observability smokes a PR most often touches.  Heavier planes (chaos,
# resume, streaming) keep their own entry points above.
verify: verify-static test explain-smoke
