# Tier-1 verification + static invariant analysis.
#
# `make check` is the CI entry point: `make lint` runs the AST-based
# invariant analyzer (src/repro/analysis — rule families RA1 compat
# isolation, RA2 dispatch layering, RA3 bitwise contract, RA4 kernel
# hygiene, RA5 plan-cache determinism) plus ruff when available, then
# the full test suite runs.  The analyzer replaced the four grep gates
# (compat/eig/seq/serve): it resolves import aliases, walks pallas_call
# kernel bodies, and suppresses via `# repro-lint: disable=RAx` — see
# `python -m repro.analysis --list-rules`.

.PHONY: check lint analyze ruff docs-check test obs-report

check: lint test

lint: analyze ruff docs-check

# Dead relative links in docs/*.md + README.md.  Stdlib-only on
# purpose: the CI lint job installs no project dependencies.
docs-check:
	python tools/check_docs.py .

# Mtime-cached AST walk (REPRO_LINT_CACHE=off disables); exits 1 on any
# non-baselined violation.
analyze:
	PYTHONPATH=src python -m repro.analysis

# ruff is optional locally (CI installs it via requirements-dev.txt);
# config in ruff.toml.
ruff:
	@command -v ruff >/dev/null 2>&1 \
		&& ruff check . \
		|| echo 'ruff not installed; skipping (CI runs it)'

# pytest.ini promotes the library's own DeprecationWarnings to errors
# when they originate *from repro internals* (module regex; a -W flag
# cannot express this because it escapes+anchors the module field):
# internal callers must stay on the typed RotationSequence API, while
# external callers of the compat wrappers only get the warning.
#
# Parallelism: pytest-xdist (`-n auto`) when installed — CI installs it
# via requirements-dev.txt; environments without it degrade to serial.
# Fail-fast is --maxfail=1 rather than -x because -x is unreliable
# across xdist workers.
PYTEST_PAR := $(shell python -c 'import xdist' 2>/dev/null && echo '-n auto')
test:
	PYTHONPATH=src python -m pytest -q --maxfail=1 $(PYTEST_PAR)

# Observability report: obs-enabled rotation-serving runs writing the
# metrics snapshot (OBS_metrics.json) and a Perfetto-loadable
# Chrome trace (trace.jsonl — load at ui.perfetto.dev), once through the
# synchronous service and once through the streaming engine
# (OBS_stream_metrics.json / trace_stream.jsonl, bit-checked against the
# synchronous drain).  See the README "Observability" section for the
# metric catalogue.
obs-report:
	PYTHONPATH=src python -m repro.launch.serve --rotations \
		--requests 24 --slots 8 --check \
		--metrics-json OBS_metrics.json --trace trace.jsonl
	PYTHONPATH=src python -m repro.launch.serve --rotations --stream \
		--requests 24 --slots 8 --check \
		--metrics-json OBS_stream_metrics.json --trace trace_stream.jsonl
