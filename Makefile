# Convenience targets; the source of truth for the tier-1 line is
# ROADMAP.md ("Tier-1 verify"), mirrored in scripts/verify.sh.

.PHONY: verify analyze lint test bench chipsmoke perfcheck perfreport

# The pre-merge gate: static analysis + the full tier-1 suite with the
# DOTS_PASSED count the driver compares against the seed.
verify:
	bash scripts/verify.sh

# graftlint: registry + jit-hygiene + lock-discipline vs the committed
# analysis_baseline.json (docs/ANALYSIS.md). Exit 1 on any new finding.
analyze:
	JAX_PLATFORMS=cpu python -m automerge_tpu.analysis

# The analyzer plus its pytest surface (registry lint + analyzer tests).
lint: analyze
	JAX_PLATFORMS=cpu python -m pytest tests/test_metrics_lint.py \
	    tests/test_analysis_core.py tests/test_analysis_jit.py \
	    tests/test_analysis_locks.py -q -p no:cacheprovider

# The tier-1 suite without the lint-first staging or dots accounting.
test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly

# The benchmark harness: one final JSON line; needs the chip and exits
# non-zero without one (python bench.py --force-cpu runs it on the CPU).
bench:
	python bench.py

# The served path end to end on one TPU chip (fails at once without one;
# from a sandbox: chiprun -- python chip_smoke.py).
chipsmoke:
	python chip_smoke.py

# The perf regression gate: the latest bench_history.jsonl record vs the
# rolling same-backend median. Nonzero exit on throughput regression or
# compile-count growth (docs/OBSERVABILITY.md "Performance plane").
perfcheck:
	JAX_PLATFORMS=cpu python -m automerge_tpu.perf check

# The bench-history trajectory + latest compile telemetry + the
# contention & convergence-lag section (per-lock wait/hold, sampled
# op-lag stages) + the perf-doctor ranked root-cause post-mortem over
# the last bench detail, human-readable.
perfreport:
	JAX_PLATFORMS=cpu python -m automerge_tpu.perf report
	JAX_PLATFORMS=cpu python -m automerge_tpu.perf contention
	JAX_PLATFORMS=cpu python -m automerge_tpu.perf doctor
