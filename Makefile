# Tier-1 verification and smoke benchmarks (see ROADMAP.md / DESIGN.md).

PY ?= python
export PYTHONPATH := src:.

.PHONY: test bench-smoke bench bench-sharded-search bench-drift \
	bench-serving bench-ordered bench-chaos check-docs

# tier-1: the full pytest suite (ROADMAP "Tier-1 verify")
test:
	$(PY) -m pytest -x -q

# quick perf smoke: tiered-kernel timing (checked against the oracle) +
# aggregation + refresh-path races (host vs device_index; sharded vs
# replicated); writes BENCH_kernels.json
bench-smoke:
	$(PY) benchmarks/run.py --only kernels_bench

# full benchmark harness (paper-scale sizes)
bench:
	$(PY) benchmarks/run.py --full

# routed sharded-search bench on a forced 1x4 host mesh, written to its
# own (gitignored) JSON — the committed trajectory entry lives in the
# search_sharded key of BENCH_kernels.json (via kernels_bench).  The
# parity battery runs once, via tests/test_sharded_search.py's
# subprocess.  The CI parity step and the nightly bench job both invoke
# exactly this target, so local and CI runs can't drift.
bench-sharded-search:
	$(PY) benchmarks/sharded_search_probe.py --bench --routed \
	  --width 4096 --nq 8192 | tee BENCH_search_sharded.json

# drift-recovery battery (DESIGN.md §5.7): the routing controller raced
# through the drift scenarios on a forced 1x4 host mesh — bit-identity
# with the replicated loop, <=1% spill within the ladder-length bound of
# every transition, controller-off contrast, steady-state hysteresis.
# Self-asserting (exits nonzero on violation); the CI "Drift recovery"
# step and the nightly bench job both invoke exactly this target.  The
# committed trajectory entry lives in the routing_controller key of
# BENCH_kernels.json (via kernels_bench's drift_probe --bench subprocess).
bench-drift:
	$(PY) benchmarks/drift_probe.py --parity

# serving-engine parity battery (DESIGN.md §5.9): device-indexed
# serving (routed sharded search + route controller) bit-identical to
# the host-SplayList pool on recorded request traces and end-to-end
# engine runs, meshless and on a forced 1x4 host mesh, page-exhaustion
# backpressure included.  Self-asserting (exits nonzero on violation);
# the CI "Serving parity + bench" step and the nightly bench job both
# invoke exactly this target.  The committed metrics entry lives in the
# serving_engine key of BENCH_kernels.json (via kernels_bench's
# serving_probe --bench subprocess).
bench-serving:
	$(PY) benchmarks/serving_probe.py --parity

# ordered-operation parity battery (DESIGN.md §5.10): predecessor/
# successor, rank/select, range_count/range_scan, top_k bit-identical
# across the host oracle, the replicated plane, and the routed sharded
# plane (equal-lane + mass splits) on a forced 1x4 host mesh —
# boundary-exact and boundary-straddling ranges, int32-extreme
# endpoints, and the counted-truncation contract included.
# Self-asserting (exits nonzero on violation); the CI "Ordered-op
# parity" step and the nightly bench job both invoke exactly this
# target.  The committed metrics entry lives in the search_ordered key
# of BENCH_kernels.json (via kernels_bench's ordered_search_probe
# --bench subprocess).
bench-ordered:
	$(PY) benchmarks/ordered_search_probe.py --parity

# chaos-injection recovery battery (DESIGN.md §5.11): plane fsck
# detects every injected fault family within one audit epoch, degraded
# serving (routed -> masked -> host oracle) never serves a wrong
# verdict and recovers to routed within the bound, crash-consistent
# snapshots replay the pending-op buffer exactly once, and restores
# are bit-identical across host / meshless / 1x4-mesh backends
# (shrunk-mesh restores included).  Self-asserting; the CI "Chaos
# recovery" step and the nightly bench job invoke exactly this target.
# The committed metrics entry lives in the chaos_recovery key of
# BENCH_kernels.json (via kernels_bench's chaos_probe --bench
# subprocess).
bench-chaos:
	$(PY) benchmarks/chaos_probe.py --parity

# docs gate: docs/API.md names resolve against the modules; the README
# quickstart blocks execute (scripts/check_api_docs.py, CI `docs` job)
check-docs:
	$(PY) scripts/check_api_docs.py
