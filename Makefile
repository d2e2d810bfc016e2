# Test/verify entry points (the reference's build-scripts plane,
# paddle/scripts/travis/, as make targets).
#
#   make lint    — static analysis: AST self-lint over paddle_tpu + bench.py
#                  (analysis/ast_rules), graph-lint over every shipped
#                  demo config (tests/configs/), the T106 buffer-
#                  donation audit over the step builders, the C-rules
#                  lock-discipline lint over the threaded planes
#                  (analysis/concurrency_lint), and the N-rules
#                  precision-flow lint (analysis/numerics_lint) in
#                  four legs: package probes at f32, the demo-config
#                  corpus at f32, the flagship corpus at bf16, and the
#                  package probes at bf16 — the last leg is the pragma-
#                  hygiene pass (every `# num:` pragma must be justified
#                  AND still suppressing something, package-wide).
#                  Fixes + justified pragmas keep all four at zero.
#                  Zero findings = pass.
#   make test    — fast tier: lint, then every test not marked `slow`;
#                  < 6 min on the virtual 8-device CPU mesh.  The CI gate.
#   make verify  — the full suite, then the decode-speed gate (beam-5
#                  nmt_generate + spec-decode/prefix-cache A/B under the
#                  bench regression guard — any >5%-worse-than-history
#                  metric fails the target), a bench smoke (one metric)
#                  and the 8-device multichip dry-run compile.
#   make bench   — the full benchmark set (one JSON line per metric).
#   make chip-smoke — chip_smoke.py: one process drives trainer.SGD and
#                  ServingEngine at the NMT flagship's full width (plus
#                  ResNet-50, the Pallas flash kernels,
#                  data parallelism when several chips are visible) on the
#                  TPU; fails at once where jax sees no TPU.  No CPU_ENV:
#                  it never sets a platform itself.
#   make tier1-check / tier1-update — diff (or re-snapshot) the tier-1
#                  failing-test SET against tests/tier1_failures_baseline.txt
#                  (scripts/tier1_failset.py), so CI catches a newly broken
#                  test even when another fix keeps the count unchanged.
#                  tier1-check also verifies the multi-process e2e files
#                  stay slow-marked (--slow-guard) — they must never creep
#                  into the fast tier.
#   make chaos   — the fault-injection drills: the single-process subset
#                  (NaN-inject, torn checkpoint, subprocess kill -9 +
#                  --resume), the elastic kill-one-of-N scenarios
#                  (tests/test_elastic_e2e.py: 4 worker processes, one
#                  SIGKILLed mid-pass holding a shard lease — leases
#                  requeue, params stay bit-for-bit), the master-
#                  failover drill (tests/test_master_failover_e2e.py:
#                  kill -9 the LEADER mid-pass under a 4-worker fleet —
#                  the standby takes over warm from the journal, zero
#                  recomputed tasks, bit-for-bit params), the serving
#                  drills (tests/test_serving_e2e.py: open-loop load +
#                  poisoned-request rejection + slow-client isolation,
#                  lock-sanitizer armed), the production-gate fleet
#                  scenarios (tests/test_scenarios_e2e.py: kill a worker
#                  AND bounce the master under LIVE train+serve traffic;
#                  SIGTERM graceful drain of `paddle-tpu serve`), and the
#                  hostile-network drills (tests/test_netem_e2e.py: a
#                  worker partitioned mid-pass rejoins bit-for-bit, and
#                  the leader<->standby asymmetric-partition split-brain
#                  ends with exactly one fenced leader, zero tasks lost,
#                  a clean surviving journal), and the decode-speed
#                  drills (tests/test_decode_speed_e2e.py: shared-prefix
#                  open-loop load over the COW cache, speculative decode
#                  under load, cancel-mid-speculation page drain), plus
#                  the chaos-composition fuzzer batch (paddle-tpu fuzz:
#                  25 seeded compositions over the fault vocabulary must
#                  run invariant-clean, and a planted-bug canary must be
#                  detected, ddmin-shrunk to a spec, and replayed).
#   make scenarios — the fast production-gate scenario subset
#                  (robustness/scenarios.py via `paddle-tpu scenario
#                  --all-fast`), sanitizer-armed: overload shed-not-
#                  collapse, burst arrivals, chaos-under-load recovery,
#                  mixed train+serve.  Runs as the last step of `make
#                  test`, so the fast tier reports the SLO gates too.
#   make serve-bench — the serving-plane headline (bench_serving).
#   make trace-demo — the obs-plane acceptance drill: run the fast
#                  mixed_train_serve scenario with span tracing armed
#                  (`paddle-tpu scenario mixed_train_serve --trace`) and
#                  assert ONE merged, schema-valid Chrome-trace timeline
#                  lands, correlating spans from >= 2 processes and >= 3
#                  planes (serving request lifecycle, trainer step,
#                  master RPC) — tests/test_obs_e2e.py.

PY ?= python
CPU_ENV = XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu

.PHONY: test verify bench chip-smoke test-all lint tier1-check tier1-update chaos serve-bench scenarios trace-demo

lint:
	$(CPU_ENV) $(PY) -m paddle_tpu lint --extra bench.py
	$(CPU_ENV) $(PY) -m paddle_tpu lint \
		$(foreach c,$(wildcard tests/configs/*.py),--config $(c))
	$(CPU_ENV) $(PY) -m paddle_tpu lint --donation
	$(CPU_ENV) $(PY) -m paddle_tpu lint --concurrency
	$(CPU_ENV) $(PY) -m paddle_tpu lint --protocol
	$(CPU_ENV) $(PY) -m paddle_tpu lint --numerics
	$(CPU_ENV) $(PY) -m paddle_tpu lint --numerics \
		$(foreach c,$(wildcard tests/configs/*.py),--config $(c))
	$(CPU_ENV) $(PY) -m paddle_tpu lint --numerics --compute-dtype bfloat16 \
		$(foreach c,$(wildcard tests/configs/*.py),--config $(c))
	$(CPU_ENV) $(PY) -m paddle_tpu lint --numerics --compute-dtype bfloat16

test: lint
	$(CPU_ENV) $(PY) -m pytest tests/ -q -m "not slow" --durations=20
	$(MAKE) scenarios

# the fast production-gate scenario subset, SANITIZER-ARMED (each measured
# window doubles as a runtime lock-order drill on the scheduler's new
# shed/cancel/drain paths); one JSON metrics line per scenario
scenarios:
	$(CPU_ENV) PADDLE_TPU_LOCK_SANITIZER=1 $(PY) -m paddle_tpu scenario --all-fast

tier1-check:
	$(CPU_ENV) $(PY) scripts/tier1_failset.py --slow-guard
	$(CPU_ENV) $(PY) scripts/tier1_failset.py --check

tier1-update:
	$(CPU_ENV) $(PY) scripts/tier1_failset.py --update

# chaos drills run SANITIZER-ARMED: every lock constructed through the
# analysis/lock_sanitizer factories is instrumented, so each failover /
# kill-one-of-N fleet drill doubles as a runtime lock-order race detector
# (a cycle raises DeadlockReport and fails the drill)
# the single-process drills also arm the NUMERICS sanitizer: the
# nan_batch drill's flight-recorder postmortem must name the first
# non-finite-producing eqn (analysis/num_sanitizer.py), not just skip
chaos:
	$(CPU_ENV) PADDLE_TPU_LOCK_SANITIZER=1 PADDLE_TPU_NUM_SANITIZER=1 $(PY) -m pytest tests/test_chaos_e2e.py tests/test_robustness.py tests/test_num_sanitizer.py -q
	$(CPU_ENV) PADDLE_TPU_LOCK_SANITIZER=1 $(PY) -m pytest tests/test_elastic_e2e.py -q
	$(CPU_ENV) PADDLE_TPU_LOCK_SANITIZER=1 $(PY) -m pytest tests/test_master_failover_e2e.py -q
	$(CPU_ENV) PADDLE_TPU_LOCK_SANITIZER=1 $(PY) -m pytest tests/test_serving_e2e.py -q
	$(CPU_ENV) PADDLE_TPU_LOCK_SANITIZER=1 $(PY) -m pytest tests/test_scenarios_e2e.py -q
	$(CPU_ENV) PADDLE_TPU_LOCK_SANITIZER=1 $(PY) -m pytest tests/test_netem_e2e.py -q
	$(CPU_ENV) PADDLE_TPU_LOCK_SANITIZER=1 $(PY) -m pytest tests/test_decode_speed_e2e.py -q
	$(CPU_ENV) PADDLE_TPU_LOCK_SANITIZER=1 $(PY) -m pytest tests/test_fleet_serving_e2e.py -q
	$(CPU_ENV) PADDLE_TPU_LOCK_SANITIZER=1 $(PY) -m pytest tests/test_explore_e2e.py -q
	# interleaving explorer batch: seeded (replayable) schedules over the
	# real router/master/HA planes must come back clean...
	$(CPU_ENV) $(PY) -m paddle_tpu explore --model router --schedules 200 --seed 0 --dfs-depth 3
	$(CPU_ENV) $(PY) -m paddle_tpu explore --model ha --schedules 200 --seed 0 --dfs-depth 4
	$(CPU_ENV) $(PY) -m paddle_tpu explore --model master --schedules 60 --seed 0
	# ...and the planted-bug canary proves the harness can still see:
	# detect (exit 1) -> shrunk spec on disk -> replay reproduces (exit 0)
	$(CPU_ENV) $(PY) -m paddle_tpu explore --model router --schedules 200 \
		--seed 7 --max-events 12 --plant double_serve \
		--out /tmp/paddle_tpu_canary.spec.json; test $$? -eq 1
	$(CPU_ENV) $(PY) -m paddle_tpu explore --replay /tmp/paddle_tpu_canary.spec.json
	# chaos-composition fuzzer (robustness/fuzz.py): the record/replay +
	# fuzz CLI drills, then a seeded 25-composition batch over the real
	# engine/scheduler must come back clean...
	$(CPU_ENV) PADDLE_TPU_LOCK_SANITIZER=1 $(PY) -m pytest tests/test_fuzz_e2e.py -q
	$(CPU_ENV) $(PY) -m paddle_tpu fuzz --count 25 --seed 0
	# ...and the planted-bug canary proves the fuzzer can still see:
	# detect (exit 1) -> ddmin-shrunk spec on disk -> replay reproduces
	$(CPU_ENV) $(PY) -m paddle_tpu fuzz --count 25 --seed 7 \
		--plant ledger_skew \
		--out /tmp/paddle_tpu_fuzz_canary.spec.json; test $$? -eq 1
	$(CPU_ENV) $(PY) -m paddle_tpu fuzz --replay /tmp/paddle_tpu_fuzz_canary.spec.json
	$(MAKE) trace-demo

# the obs-plane acceptance drill (sanitizer-armed: the traced scenario
# doubles as a lock-order drill on the instrumented scheduler/master paths)
trace-demo:
	$(CPU_ENV) PADDLE_TPU_LOCK_SANITIZER=1 $(PY) -m pytest tests/test_obs_e2e.py -q

# the serving-plane headline under the bench regression guard: continuous
# batching + block-paged decode cache vs the one-shot path, open-loop load
# (sustained req/s, p50/p99 per-token latency; bench.bench_serving)
serve-bench:
	$(CPU_ENV) $(PY) -c "import bench, json; \
		[print(json.dumps(r)) for r in bench.bench_serving()]"

test-all:
	$(CPU_ENV) $(PY) -m pytest tests/ -q

verify: test-all
	$(CPU_ENV) $(PY) -c "import bench; bench.run_gated('nmt_generate', 'decode_speed')"
	$(CPU_ENV) $(PY) -c "import bench; print(bench.bench_allreduce_virtual8())"
	$(CPU_ENV) $(PY) -c "import bench; print(bench.bench_scaling_virtual8())"
	$(CPU_ENV) $(PY) -c "import bench; [print(r) for r in bench.bench_quantized()]"
	$(CPU_ENV) $(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

bench:
	$(PY) bench.py

chip-smoke:
	$(PY) chip_smoke.py
