# Build-time verification targets (ISSUE 11 satellite: `tpucfn check
# --diff` belongs in the builder loop, not the review loop — it costs
# ~2 s and is jax-free).  `make verify` is the full tier-1 recipe from
# ROADMAP.md with the static gate in front.

# `set -o pipefail` in the tier1 recipe needs bash, not POSIX sh.
SHELL := /bin/bash

.PHONY: check tier1 verify chip-smoke bench-smoke bench-rl trace-smoke

# Static analysis over the files changed vs origin/main (the whole
# package is still parsed, so cross-module rules keep context).  Falls
# back to the full-package check when the ref is absent (fresh clone
# without the seed remote).
check:
	@if git rev-parse --verify -q origin/main >/dev/null 2>&1; then \
		python -m tpucfn.cli check --diff origin/main; \
	else \
		python -m tpucfn.cli check; \
	fi

# Tier-1 test suite (the ROADMAP.md recipe, verbatim semantics).
tier1:
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
		-m 'not slow' --continue-on-collection-errors \
		-p no:cacheprovider -p no:xdist -p no:randomly 2>&1 \
		| tee /tmp/_t1.log

verify: check tier1

# The first command on the chip (fails unless JAX's first device is a
# TPU; sent through the chip tool, one command per call).
chip-smoke:
	python chip_smoke.py

# Flagship perf drill on the synthetic input-bound workload (ISSUE 18):
# a real launch fan-out — 1 input host + trainer + compile-artifact
# server — rc-gated on served-step and warm-TTFS ratios.  CPU-only,
# ~1 min; `--repeat 3` is the acceptance run.
bench-smoke:
	timeout -k 10 600 env JAX_PLATFORMS=cpu \
		python benches/flagship_bench.py --quick

# Fleet timeline plane (ISSUE 20): launch fan-out (1 input host +
# trainer), merged Perfetto export — rc-gated on >=95% of remote
# data_wait spans resolving a cross-host parent link and critical-path
# plane shares summing to within 10% of step wall.  CPU-only, ~15s;
# `--repeat 3` is the acceptance run.
trace-smoke:
	timeout -k 10 300 env JAX_PLATFORMS=cpu \
		python benches/trace_smoke.py --quick

# Podracer RL plane (ISSUE 19): co-located act->learn->refresh vs the
# host-roundtrip reference on the same mesh — rc-gated on the
# co-location ratio and the d2d refresh latency budget.  CPU-only, ~30s.
bench-rl:
	timeout -k 10 300 env JAX_PLATFORMS=cpu \
		python benches/rl_bench.py --quick
