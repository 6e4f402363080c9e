PY      ?= python
PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
export PYTHONPATH

.PHONY: test test-slow test-multidevice lint lint-contracts sanitize-smoke \
	bench-smoke bench eval eval-smoke

# tier-1: fast suite, slow-marked tests deselected (pyproject addopts)
test:
	$(PY) -m pytest -q

# everything, including @pytest.mark.slow integration/perf tests
test-slow:
	$(PY) -m pytest -q -m ""

# sharding + recon-engine suites on a fake 8-device host platform: runs the
# mesh-parallel engine parity tests that skip on a single device
test-multidevice:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m pytest -q tests/test_recon_engine.py tests/test_sharding.py

# ruff gate (same as the CI lint job; needs ruff on PATH)
lint:
	ruff check .

# repo-contract static analysis: AST rules over src/tests (host-sync,
# jit-cache, env-read, donation-guard, spec-conformance, pallas-contract,
# alias-push, pragma grammar) plus the compiled-artifact HLO lint, which
# lowers the jitted scheduler decode step and the sharded recon step on a
# forced 8-device host platform and asserts zero host transfers and only
# the one contracted fused all-gather
lint-contracts:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m tools.reprolint src tests --hlo

# the runtime half: recompile detector + transfer-guard tests, including the
# scheduler decode loop and the recon engine end-to-end under
# sanitized(transfer_guard=True)
sanitize-smoke:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m pytest -q tests/test_sanitize.py tests/test_reprolint.py

# executes the reconstruction-engine speed benchmark end-to-end with tiny
# step counts — catches perf-path breakage on every CI run; emits
# BENCH_recon.json (the CI perf trajectory artifact)
bench-smoke:
	$(PY) -m benchmarks.recon_speed --dryrun

# one-command quality harness: FP vs RTN/AWQ/TesseraQ perplexity + choice
# accuracy + packed-model eval + xla/pallas logits-parity gate; emits
# EVAL.json
eval:
	$(PY) -m repro.eval.harness --reduced

eval-smoke:
	$(PY) -m repro.eval.harness --smoke

# full benchmark suite (paper tables) + the recon engine speed report
bench:
	$(PY) -m benchmarks.recon_speed
	$(PY) -m benchmarks.run
