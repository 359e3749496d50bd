# Developer entry points.  Every test target pins JAX to CPU (tests
# virtualize 8 devices via XLA flags in tests/conftest.py).

PY ?= python
PYTEST = env JAX_PLATFORMS=cpu $(PY) -m pytest -p no:cacheprovider

.PHONY: test tier1 lint chaos chaos-multi-gateway chaos-soak \
	distill-smoke bench-kv bench-mixed bench-megastep bench-fused \
	bench-autopilot bench-swarm bench-spec-rtt trace-demo obs-demo \
	chip-smoke

# Full suite (slow soaks included).  Runs lint + the chaos matrix FIRST:
# swarmlint finishes in seconds and the fault-injection scenarios are the
# cheapest way to catch a request-plane regression, so they gate the
# long tail instead of trailing it.
test: lint chaos chaos-soak
	$(PYTEST) tests/ -q -m 'not chaos'

# The tier-1 gate: what CI (and ROADMAP.md) holds the repo to.
tier1: lint
	$(PYTEST) tests/ -q -m 'not slow' --continue-on-collection-errors

# CPU rehearsal of chip_smoke.py (the chip check: DHT + worker + gateway
# as three processes, /api/chat requests, kernel parity) at tiny-test size
# with interpret-mode kernels.  Never prints "ok": true — the real run is
# `python chip_smoke.py` on a machine with a TPU.
chip-smoke:
	env JAX_PLATFORMS=cpu CROWDLLAMA_PALLAS_INTERPRET=1 \
		$(PY) chip_smoke.py --rehearse

# swarmlint (docs/STATIC_ANALYSIS.md): async-hotpath / jax-purity /
# contract-exhaustiveness checkers over the package.  Exit 1 on any
# finding not waived by crowdllama_tpu/analysis/baseline.toml.
lint:
	env JAX_PLATFORMS=cpu $(PY) -m crowdllama_tpu.analysis

# Deterministic fault-injection matrix (docs/ROBUSTNESS.md): seeded
# FaultPlans from crowdllama_tpu/testing/faults.py kill streams, fail
# handshakes, exhaust budgets, drain workers mid-stream, and drop/delay/
# partition gossip frames; assertions check the request plane heals
# (mid-stream failover, live migration with KV handoff, 504 budgets,
# jittered 503 shedding, gateway-crash failover across replicas).
chaos: chaos-multi-gateway
	$(PYTEST) tests/ -q -m chaos

# Replicated-gateway slice of the matrix (tests/test_gossip.py): a
# gateway replica killed mid-burst with survivors byte-identical plus
# the gossiped-pin continuation, gossip convergence through a seeded
# drop/delay/partition plan, and per-tenant shedding over HTTP.
chaos-multi-gateway:
	$(PYTEST) tests/test_gossip.py -q \
		-k 'two_gateways or converges_under or tenant_quota_sheds'

# Seeded chaos soak (docs/ROBUSTNESS.md "Gray failures"): 200 streams
# against a 5-worker loopback swarm under a mixed kill/stall/slow/
# hedge-delay/drain/partition schedule; every stream must come back
# byte-identical to its fault-free control with exactly one clean
# terminal, stalled streams must recover within the stall budget +
# failover slack, and hedge_launched == hedge_won + hedge_cancelled.
# Deterministic schedule, < 120 s; artifact under benchmarks/results/.
chaos-soak:
	env JAX_PLATFORMS=cpu $(PY) -m crowdllama_tpu.testing.soak \
		--seed 42 --streams 200

# Draft-distillation training tests (docs/SPECULATIVE.md): 30-step CPU
# distillation smoke + native-checkpoint round-trip + the trained-draft
# greedy-exactness regression.  Runs in tier 1 too; this target is the
# standalone loop for iterating on train/distill.py.
distill-smoke:
	$(PYTEST) tests/ -q -m train

# Stitched-trace demo (docs/OBSERVABILITY.md): boots a loopback relay
# swarm in process, sends one chat request, and prints its cross-node
# trace as a waterfall — gateway, relay hop, and worker on one timeline.
trace-demo:
	env JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/trace_demo.py

# Swarm-observatory demo (docs/OBSERVABILITY.md): boots a loopback
# 2-worker swarm in process, pushes a few requests, and prints the
# `crowdllama-tpu top` table plus a /metrics/cluster excerpt.
obs-demo:
	env JAX_PLATFORMS=cpu PYTHONPATH=. $(PY) examples/obs_demo.py

# KV-shipping benchmark (docs/KV_TRANSFER.md): fetch-vs-recompute TTFT
# over real p2p streams with an injected-RTT sweep; writes the artifact
# under benchmarks/results/.
bench-kv:
	env JAX_PLATFORMS=cpu CROWDLLAMA_BENCH_PHASES=kv_transfer $(PY) bench.py

# Gateway-drafted speculative pipeline vs worker-paced stop-and-wait vs
# plain streaming across injected swarm RTT (docs/SPECULATIVE.md).
bench-spec-rtt:
	env JAX_PLATFORMS=cpu CROWDLLAMA_BENCH_PHASES=spec_rtt $(PY) bench.py

# Unified-ragged-batch benchmark (docs/RAGGED_BATCH.md): decode-step p95
# while a long prefill chunks through the same jitted step (swept over
# step_token_budget, with the retired alternating loop as the control),
# plus a 32k-token prefill the monolithic one-shot path could not fit.
bench-mixed:
	env JAX_PLATFORMS=cpu CROWDLLAMA_BENCH_PHASES=mixed_batch,ctx32k \
		$(PY) bench.py

# Kernel-looped decode megastep (docs/MEGASTEP.md): decode steps/sec and
# host dispatches per token, swept over K in {1,2,4,8} against the
# per-step dispatch+readback control.
bench-megastep:
	env JAX_PLATFORMS=cpu CROWDLLAMA_BENCH_PHASES=decode_megastep \
		$(PY) bench.py

# Fused ragged megastep (docs/MEGASTEP.md "Fused ragged megastep"): the
# mixed-batch phase's fused-vs-gated arms (decode-step p95 during a long
# prefill, tokens per dispatch, host-gap share) plus the megastep K
# sweep — the two phases that price megastep x ragged fusion.
bench-fused:
	env JAX_PLATFORMS=cpu \
		CROWDLLAMA_BENCH_PHASES=mixed_batch,decode_megastep \
		$(PY) bench.py

# Closed-loop performance autopilot (docs/AUTOTUNE.md): three scenario
# shapes under grid-search-best static dials vs the autotuner walking
# from defaults — steps/sec ratio, moves-to-converge, dial trajectory
# (artifact: benchmarks/results/AUTOTUNE_cpu_*.json).
# Native data-plane arms (docs/NATIVE.md): the swarm_scaling phase run
# twice — native fast path vs CROWDLLAMA_NO_NATIVE=1 — one subprocess
# per arm; writes benchmarks/results/SWARM_SCALING_cpu_<date>.json with
# req/s, cpu_us_per_request, loop lag, and the serde+aead share per arm.
bench-swarm:
	env JAX_PLATFORMS=cpu $(PY) benchmarks/swarm_scaling.py --arms

bench-autopilot:
	env JAX_PLATFORMS=cpu CROWDLLAMA_BENCH_PHASES=autopilot \
		$(PY) bench.py
