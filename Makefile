# Common workflows.  The test harness forces the CPU backend and 8
# simulated devices whatever the machine has (see tests/conftest.py);
# every bench-*/smoke/chaos target below is a CPU gate too.  Only
# `bench` and `chip-smoke` use the device JAX gives.

.PHONY: test soak chip-smoke bench bench-micro bench-mesh bench-ingest bench-serve bench-delta bench-wal bench-view bench-opt bench-macro trace-smoke obs-smoke skew-smoke multiway-smoke fuse-smoke chaos check dryrun example coldcheck lint analyze plan-cert asan

test:
	python -m pytest tests/ -x -q

# The standing local gate: unit suite, static analysis, chaos
# differential, mutable-index storage bench, materialized-view bench,
# telemetry-plane smoke, skew-aware-join smoke — the set a change must
# keep green before review.
check: test lint plan-cert chaos bench-delta bench-wal bench-view bench-opt obs-smoke skew-smoke multiway-smoke fuse-smoke

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

# One process on the device JAX gives, named in the record; refuses a
# non-TPU backend unless the caller set JAX_PLATFORMS=cpu; exits nonzero
# when a tier fails.
bench:
	python bench.py

# The main path once on the chip, every leg checked (run it through the
# chip tool); exits nonzero without a TPU.
chip-smoke:
	python chip_smoke.py

# Seconds-long CPU smoke of the batched point-lookup engine: one JSON
# line with batched find_many lookups/s on the 1M-row big-index shape;
# exits nonzero on a >2x regression vs bench_micro_floor.json.
bench-micro:
	JAX_PLATFORMS=cpu python bench.py --micro-lookup

# Minutes-long gate of the SHARDED north-star pipeline (virtual 8-device
# CPU mesh, 10M rows by default): one JSON line with the warm sharded
# 3-way join rows/s; exits nonzero on a >2x regression vs
# bench_mesh_floor.json.  An artifact is written only where
# CSVPLUS_BENCH_MESH_OUT names a path.
# A second SKEW tier then reruns the pipeline over a Zipf(s=1.1)
# orders stream, skew-aware vs CSVPLUS_JOIN_SKEW=0 in the same child,
# gated by warm_join_rows_per_sec_zipf with the same half-floor rule
# and bitwise parity enforced in-run; its checked-in record
# (NORTHSTAR_MESH_r07.json) is only (re)written when
# CSVPLUS_BENCH_MESH_OUT_ZIPF is set.  CSVPLUS_BENCH_MESH_SKEW=0
# skips the tier.  A third MULTIWAY tier (ISSUE 17) runs the
# cost-chosen single-pass multiway operator vs the cascaded-skew path
# in one child over the same Zipf bytes — per-leg RSS watermarks,
# bitwise parity, obs-diff stage attribution — gated by
# join_rows_per_sec_warm_multiway with the same half-floor rule; its
# checked-in record (NORTHSTAR_MESH_r08.json) is only (re)written when
# CSVPLUS_BENCH_MESH_OUT_MULTIWAY is set.
# CSVPLUS_BENCH_MESH_MULTIWAY=0 skips the tier.
bench-mesh:
	python bench.py --bench-mesh

# Streamed-ingest gate (10M rows by default): runs the staged
# multi-worker ingest pipeline at workers=1 and workers=auto over the
# same file, requires bitwise-equal full-result checksums, prints one
# JSON line with the auto-worker ingest rows/s; exits nonzero on a >2x
# regression vs bench_ingest_floor.json.  The checked-in record
# artifact (BENCH_INGEST_r07.json) is only (re)written when
# CSVPLUS_BENCH_INGEST_OUT is set.
bench-ingest:
	JAX_PLATFORMS=cpu python bench.py --bench-ingest

# Serving-tier gate (docs/SERVING.md): closed-loop coalesced lookups,
# 32 OS-thread clients, open-loop fixed-rate latency (p50/p99), zipf
# keys, plan-cache cold/warm (asserts zero warm recompiles), and an
# overload shed scenario — all on the 1M-row big-index micro shape.
# One compact JSON line last; exits nonzero on a >2x regression vs
# bench_serve_floor.json.  The checked-in record (BENCH_SERVE_r08.json)
# is only (re)written when CSVPLUS_BENCH_SERVE_OUT is set.
bench-serve:
	JAX_PLATFORMS=cpu python bench_serve.py

# Mutable-index storage gate (docs/STORAGE.md): append rows/s through
# the delta-tier write path, single-probe lookup p50/p99 at 0/4/16
# live deltas, and reader-observed latency during a concurrent
# compaction — with the ISSUE 9 hard contract enforced in-bench
# (checksum parity vs a from-scratch rebuild after every compaction
# step, zero warm recompiles).  One compact JSON line last; exits
# nonzero on a >2x regression vs bench_delta_floor.json.  The
# checked-in record (BENCH_DELTA_r10.json) is only (re)written when
# CSVPLUS_BENCH_DELTA_OUT is set.
bench-delta:
	JAX_PLATFORMS=cpu python bench_delta.py

# Durable mutable-index (WAL) bench: ack-after-fsync append throughput
# (sync=always vs batch), 200K-row WAL-tail recovery, lookup latency
# with live tombstone tiers, the read-amplification scenario (>=128
# live delta tiers must stay within 3x of the fully-compacted floor —
# the pruning contract), and read-amp-aware Compactor convergence —
# with recovered-state checksum parity and zero warm recompiles
# enforced in-bench.  CSVPLUS_MICRO_DIST=zipf skews the read-amp probe
# stream.  One compact JSON line last; exits nonzero on a >2x
# regression vs bench_wal_floor.json.  The checked-in record
# (BENCH_WAL_r12.json) is only (re)written when CSVPLUS_BENCH_WAL_OUT
# is set.
bench-wal:
	JAX_PLATFORMS=cpu python bench_wal.py

# Live materialized-view bench (docs/VIEWS.md): incremental
# maintenance of the 3-way join view over a 1M-row mutable source —
# refresh ms per <=1K-row batch vs a from-scratch recompute (the gated
# >=20x speedup), and view-read latency from the epoch-pinned
# snapshot — with the ISSUE 12 hard contract enforced in-bench
# (positional checksum parity vs a from-scratch execution after EVERY
# batch, zero warm recompiles per refresh).  One compact JSON line
# last; exits nonzero on a >2x regression vs bench_view_floor.json.
# The checked-in record (BENCH_VIEW_r13.json) is only (re)written when
# CSVPLUS_BENCH_VIEW_OUT is set.
bench-view:
	JAX_PLATFORMS=cpu python bench_view.py

# Plan-rewriter bench (docs/ANALYSIS.md, ISSUE 16): the filter+map+
# join serving chain runs warm through two plan caches over identical
# data — one admitted with CSVPLUS_OPTIMIZE=0 — so the measured delta
# is exactly the provenance-proven rewrite (predicate pushdown below
# the join, projection pushdown dropping dead payload columns at the
# scan).  Gated in-bench: the rewriter must fire (permute +
# drop_after_leaf recipe), bitwise positional-checksum parity on both
# uniform and Zipf(s=1.1) key distributions, zero warm recompiles on
# the optimized path, and the optimized rate must stay above half
# bench_opt_floor.json.  Per-stage attribution (obs-diff stage
# tables) lands in the artifact only when CSVPLUS_BENCH_OPT_OUT is
# set (record: BENCH_OPT_r16.json).  One JSON line; exits nonzero on
# any gate failure.
bench-opt:
	JAX_PLATFORMS=cpu python bench.py --bench-opt

# Tracing-subsystem smoke (docs/OBSERVABILITY.md): a traced serving
# pass on the micro lookup shape must produce per-request span trees,
# the Chrome-trace export must pass the schema validator, and the
# DISABLED instrumentation path must cost <=2% of the bare batched
# lookup pass (CSVPLUS_TRACE_SMOKE_MAX_PCT to override).  One JSON
# line; exits nonzero on any gate failure.
trace-smoke:
	JAX_PLATFORMS=cpu python bench.py --trace-smoke

# Telemetry-plane smoke (docs/OBSERVABILITY.md): a served pass with a
# planted Zipf heavy hitter must surface that key in the Prometheus
# scrape's csvplus_skew_topk series (scraped over real HTTP from the
# plane's endpoint), the tail sampler must retain only its bounded
# slice, the metric surface must carry serve/index/process families,
# zero warm recompiles — and the plane's per-request overhead must be
# <=2% of the bare serving pass (CSVPLUS_OBS_SMOKE_MAX_PCT to
# override).  One JSON line; exits nonzero on any gate failure.
obs-smoke:
	JAX_PLATFORMS=cpu python bench.py --obs-smoke

# Skew-aware partitioned-join smoke (ISSUE 15): a sharded Zipf(s=1.3)
# join on the hermetic 8-device mesh must be BITWISE equal (positional
# per-column checksums) to the CSVPLUS_JOIN_SKEW=0 run over the same
# data, the broadcast tier must engage (hot keys detected, rows
# broadcast, counters in the process-global registry), and repeated
# warm skew-aware joins must lower nothing (RecompileWatch).  Seconds
# long; one JSON line; exits nonzero on any gate failure.  The perf
# floor for the skew path lives in the bench-mesh skew tier.
skew-smoke:
	python bench.py --skew-smoke

# Single-pass multiway join smoke (ISSUE 17): the cost-chosen fused
# 3-way join on the hermetic 8-device mesh — the rewriter must FUSE
# the Join->Join run (plan-cache `fused` counter, not the env flag),
# the result must be BITWISE equal (positional per-column checksums)
# to the CSVPLUS_MULTIWAY=0 cascade over the same Zipf-both-dims data,
# the csvplus_join_multiway_* counter family must ride a metrics
# scrape, and repeated warm fused executions must lower nothing
# (RecompileWatch).  Seconds long; one JSON line; exits nonzero on any
# gate failure.  The perf targets live in the bench-mesh multiway tier.
multiway-smoke:
	python bench.py --multiway-smoke

# Probe-pass fusion smoke (ISSUE 19): a 200K-row Zipf Filter->Map->Join
# chain on the hermetic 8-device mesh, served through the PlanCache —
# the rewriter must fuse the run (plan-cache `fused_chains` counter, a
# `fuse_chain` recipe step), the result must be BITWISE equal
# (positional per-column checksums) to the CSVPLUS_FUSE=0 staged run
# over the same bytes, the csvplus_plan_fusion_* families must ride a
# metrics scrape, and repeated warm fused executions must lower nothing
# (RecompileWatch).  Seconds long; one JSON line; exits nonzero on any
# gate failure.  The perf targets live in bench-macro.
fuse-smoke:
	python bench.py --fuse-smoke

# TPC-H-flavored macro-bench (ISSUE 19, ROADMAP item 1's workload):
# five named query chains (multi-join stars, filters, projection, Top;
# uniform and Zipf(s=1.1) keys; one on the 8-device mesh) run through
# the PlanCache with the optimizer fused vs CSVPLUS_FUSE=0 in the SAME
# child over identical bytes.  In-run gates: bitwise positional-
# checksum parity per query, zero warm recompiles on the fused leg,
# fused_chains >= 1, mesh-leg peak RSS within 10% of staged, at least
# one query >= 1.25x fused-over-staged, and the q1 headline above half
# bench_macro_floor.json.  Minutes long (1M-row facts; scale with
# CSVPLUS_BENCH_MACRO_ROWS).  The checked-in record
# (BENCH_MACRO_r18.json, with per-stage obs-diff attribution per
# query) is only (re)written when CSVPLUS_BENCH_MACRO_OUT is set.
bench-macro:
	python bench_macro.py

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
# flight-recorder dump naming the firing fault site.  Writes
# CHAOS_r13.json; the unit-level chaos suite (tests/test_chaos.py)
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
