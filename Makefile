# Developer entry points.  Every test target pins JAX to CPU (tests
# virtualize 8 devices via XLA flags in tests/conftest.py).

PY ?= python
PYTEST = env JAX_PLATFORMS=cpu $(PY) -m pytest -p no:cacheprovider

.PHONY: test tier1 lint chaos chaos-multi-gateway chaos-soak \
	distill-smoke trace-demo obs-demo chip-smoke

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
# Deterministic schedule, < 120 s; the report goes to a temporary
# directory (--out-dir places it), its path on the last line printed.
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
