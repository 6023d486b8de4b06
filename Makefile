# Task runner: one documented command per environment, the counterpart
# of the reference's tox.ini (reference: tox.ini:1 — py312/integration/
# docs/static envs). No tox dependency: plain make + the baked-in
# toolchain. Every target runs from a clean checkout with no install
# step (pytest picks up src/ via pyproject pythonpath).

PY ?= python

.PHONY: test unit integration browser benchmarks bench bench-all multichip native docs lint lint-fix all

# Default quick gate: everything CI runs per-commit.
test: unit

# Unit + fast integration (the repo's default pytest selection).
unit:
	$(PY) -m pytest tests/ -x -q

# Multi-process integration scenarios only (slower: real subprocesses
# over the file broker).
integration:
	$(PY) -m pytest tests/integration/ -q -m "integration or not integration"

# Browser-level UI suite (needs playwright; CI-only by default, mirrors
# the reference's excluded-by-default browser marker).
browser:
	$(PY) -m pytest tests/dashboard/browser_ui_test.py -q

# In-repo perf harnesses (excluded from the default run).
benchmarks:
	$(PY) -m pytest tests/benchmarks/ -q --run-benchmarks

# The graded headline bench (one JSON line on stdout).
bench:
	$(PY) bench.py

# Full bench: headline + BASELINE configs + latency decomposition.
bench-all:
	$(PY) bench.py --all

# 8-virtual-device sharding dryrun (what the driver gate runs).
multichip:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# Force-rebuild the native ingest shim (normally compile-on-demand).
native:
	rm -f src/esslivedata_tpu/native/_ingest*.so
	$(PY) -c "import sys; sys.path.insert(0, 'src'); \
		from esslivedata_tpu import native; assert native.available()"

# Docs are plain markdown; this validates internal links resolve.
docs:
	$(PY) scripts/check_docs_links.py

# Static gates, cheapest first: syntax (compileall), style/bug families
# (ruff, when installed — the container image does not bake it in), then
# the JAX-hazard/concurrency pass (tools/graftlint, docs/graftlint.md):
# per-file rules + the whole-program thread/lock/jit-key pass, gated
# against the known-findings baseline (currently empty — keep it that
# way for core/; see docs/adr/0112) — plus the trace pass (ADR 0123):
# every registered tick program is AOT-lowered (CPU backend, no
# device) and its contract fingerprint is diffed against
# tickcontract-baseline.json, with the lowering cache under build/
# replaying an unchanged tree without importing jax — and the protocol
# pass (ADR 0124): the checkpoint/replay/relay/fleet/epoch protocols
# are model-checked over every interleaving and crash point, bound to
# the real source by structural probes. No jax in the environment = a
# visible SKIPPED notice from the trace pass and the protocol codec
# leg, never a silent green.
lint:
	$(PY) -m compileall -q src/ tests/ tools/ bench.py __graft_entry__.py
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check src/ tests/ tools/ bench.py __graft_entry__.py; \
	else \
		echo "lint: ruff not installed, skipping (config in pyproject.toml)"; \
	fi
	$(PY) -m tools.graftlint src/ --jobs 0 --baseline graftlint-baseline.json \
		--trace --trace-baseline tickcontract-baseline.json \
		--trace-cache build/graftlint-trace-cache.json --protocol

# Apply ruff autofixes, then report what graftlint still sees (graftlint
# never rewrites code — its fixes are reviewed hunks by design).
lint-fix:
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check --fix src/ tests/ tools/ bench.py __graft_entry__.py; \
	else \
		echo "lint-fix: ruff not installed, nothing to autofix"; \
	fi
	$(PY) -m tools.graftlint src/ --jobs 0 --baseline graftlint-baseline.json

all: lint unit integration docs
