# Common workflows.  The test harness forces the CPU backend and 8
# simulated devices whatever the machine has (see tests/conftest.py);
# `chaos` is a CPU gate too; only `chip-smoke` uses the device JAX gives.
# Speed is measured by benchmark/run.py on the chip, not by a target here.

.PHONY: test soak chip-smoke chaos check dryrun example coldcheck lint analyze plan-cert asan

test:
	python -m pytest tests/ -x -q

# The standing local gate: unit suite, static analysis, chaos
# differential — the set a change must keep green before review.
check: test lint plan-cert chaos

# Static analysis gate (docs/ANALYSIS.md).  The repo AST lint (ctypes
# boundary + jit retrace rules) always runs; ruff and mypy run when
# installed (the baked toolchain image may not carry them) and their
# configs live in pyproject.toml.  A tool that RUNS and finds issues
# fails the target; a tool that is absent is reported and skipped.
lint:
	python -m csvplus_tpu.analysis
	@if python -c "import ruff" >/dev/null 2>&1; then \
		python -m ruff check csvplus_tpu tests; \
	else echo "ruff not installed -- skipped"; fi
	@if python -c "import mypy" >/dev/null 2>&1; then \
		python -m mypy csvplus_tpu; \
	else echo "mypy not installed -- skipped"; fi

# Lint + the --json analysis payload (plan-IR verifier reports over the
# example chains on the hermetic 8-device CPU mesh), snapshot-compared
# against tests/data/analyze_snapshot.json.  Diagnostic drift exits 3;
# regenerate deliberately with:
#   JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
#     python -m csvplus_tpu.analysis --write-snapshot tests/data/analyze_snapshot.json
analyze: lint
	JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m csvplus_tpu.analysis --json --snapshot tests/data/analyze_snapshot.json >/dev/null

# Exhaustive plan-space rewrite certification (docs/ANALYSIS.md, ISSUE
# 20): enumerate EVERY plan chain up to CSVPLUS_PLANCERT_N (default 3;
# a few hundred plans) over the canonical corpus, verify -> optimize
# each, and discharge the four obligations — verdict equality, licensed
# recipe steps, bitwise execution parity, real refusal stages.  Exits
# nonzero on any failed obligation or when the run exceeds
# CSVPLUS_PLANCERT_BUDGET_S (default 60s) — the make check budget.
plan-cert:
	JAX_PLATFORMS=cpu python -m csvplus_tpu.analysis plan-cert

# Native scanner under AddressSanitizer + UBSan: rebuilds scanner.cpp
# with -fsanitize into a separate artifact (CSVPLUS_NATIVE_SO, so the
# -O3 cache is untouched) and runs the byte-fuzzer subset of
# tests/test_native.py under it.  LD_PRELOAD is required because the
# host interpreter (python) is not asan-linked; leak checking is off
# for the same reason (the interpreter itself "leaks" at exit).  Skips
# cleanly when g++ lacks sanitizer runtimes.
asan:
	@if g++ -fsanitize=address,undefined -shared -fPIC -x c++ /dev/null -o /tmp/_csvplus_asan_probe.so >/dev/null 2>&1; then \
		rm -f /tmp/_csvplus_asan_probe.so csvplus_tpu/native/_scanner_asan.so; \
		CSVPLUS_NATIVE_CFLAGS="-fsanitize=address,undefined -fno-sanitize-recover=all" \
		CSVPLUS_NATIVE_SO=_scanner_asan.so \
		LD_PRELOAD="$$(g++ -print-file-name=libasan.so) $$(g++ -print-file-name=libubsan.so)" \
		ASAN_OPTIONS=detect_leaks=0 \
		JAX_PLATFORMS=cpu python -m pytest tests/test_native.py -q -k fuzz; \
		rm -f csvplus_tpu/native/_scanner_asan.so; \
	else echo "g++ lacks asan/ubsan support -- skipped"; fi

soak:
	CSVPLUS_HYPOTHESIS_EXAMPLES=1000 python -m pytest tests/ -q

# The main path once on the chip, every leg checked (run it through the
# chip tool); exits nonzero without a TPU.
chip-smoke:
	python chip_smoke.py

# Fault-injection differential gate (docs/RESILIENCE.md): seeded fault
# schedules against serve load, K-worker streamed ingest, and the
# 8-way mesh join.  Recoverable faults must yield bitwise-equal
# results with zero warm recompiles; unrecoverable ones must surface
# typed (dispatcher crashes fail every pending future with
# ServerCrashed in <1s); every case runs under a watchdog so a hang is
# a failure; the DISARMED injection hooks must cost <=1% of a served
# request.  Also covers the views:refresh crash window (a dead view
# refresh leaves the prior epoch-pinned snapshot served and retries).
# The ISSUE 13 extension asserts both crash windows leave a parseable
# flight-recorder dump naming the firing fault site.  The full record
# goes to a temporary file; the unit-level chaos suite (tests/test_chaos.py)
# runs first.
chaos:
	JAX_PLATFORMS=cpu timeout -k 10 600 python -m pytest tests/test_chaos.py -q
	timeout -k 10 600 python chaos.py

dryrun:
	python __graft_entry__.py

example:
	python examples/quickstart.py
	python examples/quickstart.py --device
	python examples/sharded_join.py

# clone to a temp dir and run the suite there: verifies the committed
# state is self-contained (native scanner builds on demand, no stray
# uncommitted dependencies)
coldcheck:
	rm -rf /tmp/csvplus_coldcheck
	git clone -q . /tmp/csvplus_coldcheck
	cd /tmp/csvplus_coldcheck && python -m pytest tests/ -x -q
