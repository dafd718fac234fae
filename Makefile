PYTHON ?= python

.PHONY: test analyze chaos-gate debug-dump

test: analyze
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q -m 'not slow'

# Project-invariant static analysis (lock discipline, counter balance,
# exception discipline, RAY_TPU_* flag hygiene, thread hygiene) gated
# against scripts/raylint_baseline.json — fails on any NEW finding, on
# stale baseline entries, and on the baseline budget being exceeded
# (the baseline only ever shrinks). Also enforced inside tier-1 via
# tests/test_raylint.py.
analyze:
	$(PYTHON) scripts/raylint.py ray_tpu/

# One-command postmortem collection from a live cluster: pulls every
# process's flight bundle (all-thread stacks, event rings, profile
# aggregates, metrics/chaos snapshots) into one incident directory.
# Usage: make debug-dump ADDRESS=host:port  (omit ADDRESS for a local
# runtime; requires RAY_TPU_FLIGHT=1 / RAY_TPU_PROFILE=1 in the
# processes being dumped).
debug-dump:
	JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.scripts.cli debug \
		$(if $(ADDRESS),--address $(ADDRESS),) \
		$(if $(OUTPUT),--output $(OUTPUT),)

# Deterministic chaos slice inside tier-1 time: the seeded fault-
# injection / NodeKiller / shedding matrix cells (pytest -m chaos,
# excluding the slow full-sweep cells).
chaos-gate:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_chaos_matrix.py \
		-q -m 'chaos and not slow'
