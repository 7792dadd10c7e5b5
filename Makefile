# Build-time verification targets (ISSUE 11 satellite: `tpucfn check
# --diff` belongs in the builder loop, not the review loop — it costs
# ~2 s and is jax-free).  `make verify` is the tier-1 suite as the driver
# runs it, with the static gate in front.  Speed is measured on the chip
# by `python -m benchmark.run` (README "Developing"), not by a target here.

# `set -o pipefail` in the tier1 recipe needs bash, not POSIX sh.
SHELL := /bin/bash

.PHONY: check tier1 verify chip-smoke bench-rl

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

# Tier-1 test suite, as the driver runs it after every PR: six workers
# under 1,470 s (the note under ROADMAP.md's "Tier-1 verify" line).  One
# process does not finish inside any limit on record; while working, run
# the tests of what you touch.
tier1:
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
		python -m pytest tests/ -q \
		-m 'not slow' --continue-on-collection-errors \
		-p no:cacheprovider -p xdist -n 6 --dist load -p no:randomly 2>&1 \
		| tee /tmp/_t1.log

verify: check tier1

# The first command on the chip (fails unless JAX's first device is a
# TPU; sent through the chip tool, one command per call).
chip-smoke:
	python chip_smoke.py

# Podracer RL plane (ISSUE 19): co-located act->learn->refresh vs the
# host-roundtrip reference on the same mesh — rc-gated on the
# co-location ratio and the d2d refresh latency budget.  CPU-only, ~30s.
bench-rl:
	timeout -k 10 300 env JAX_PLATFORMS=cpu \
		python benches/rl_bench.py --quick
