"""CPU correctness gate (re-execs onto 8 simulated CPU devices before
JAX is touched): the mesh-scale north-star run, the 3-way join
end-to-end SHARDED.

VERDICT r4 next #4: the at-scale record must exist for the MESH path,
not just single-device — sharded streamed ingest (chunks land on their
shard, ingest.py `_finalize_sharded`) → broadcast joins over the
row-sharded stream → per-column checksum parity vs the host executor,
with per-stage wall times and placement evidence in the JSON.

Runs on the virtual 8-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) — re-execs
itself into that environment if the current process lacks 8 devices.

r06: headline join rates are measured with telemetry DISABLED (so the
collector's per-stage barriers can't perturb them), then one extra
warm-join pass runs with telemetry ENABLED to produce the per-stage
attribution table (join:translate/pack/probe/expand/merge, plus
partition/all_to_all when that tier engages) that the artifact
carries.  Ingest telemetry (ingest:scan/place/seal/shard-assemble) is
collected during the single streaming-ingest pass itself — its
accounting is pure perf_counter accumulation, no barriers.

Usage: python examples/northstar_mesh.py [n_orders]   (default 10M)
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

N_SHARDS = 8


def _ensure_mesh_env() -> None:
    """Re-exec onto 8 simulated CPU devices, before JAX is touched."""
    if os.environ.get("NORTHSTAR_MESH_HERMETIC") == "1":
        return
    env = dict(os.environ)
    env["NORTHSTAR_MESH_HERMETIC"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={N_SHARDS}"
        ).strip()
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def _rss_mb() -> float:
    from csvplus_tpu.obs.memory import peak_rss_mb

    return peak_rss_mb()


def main() -> None:
    _ensure_mesh_env()
    # the sharded-ingest path lives in the streamed tier; engage it at
    # any file size for this run (recorded in the JSON)
    os.environ.setdefault("CSVPLUS_STREAM_MIN_BYTES", "1")

    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    n_orders = int(args[0]) if args else 10_000_000
    if "--skew" in sys.argv:
        _skew_main(n_orders)
        return
    if "--multiway" in sys.argv:
        _multiway_main(n_orders)
        return
    from northstar import DATA_DIR, generate  # same generator/cache

    opath = generate(n_orders)
    print(
        f"orders file: {opath} ({os.path.getsize(opath) / 1e9:.2f} GB)",
        file=sys.stderr,
    )

    import jax

    from csvplus_tpu import FromFile, Take
    from csvplus_tpu.native.scanner import _ingest_workers
    from csvplus_tpu.obs.memory import host_header
    from csvplus_tpu.utils.observe import telemetry

    assert len(jax.devices()) >= N_SHARDS, jax.devices()

    t0 = time.perf_counter()
    with telemetry.collect() as records:
        orders = FromFile(opath).OnDevice(shards=N_SHARDS)
        orders.plan.table.sync()
    t_ingest = time.perf_counter() - t0
    table = orders.plan.table
    # the collector's record list is reset in place by the next
    # ``collect()`` — copy the ingest stages out first
    ingest_records = list(records)
    assemble = next(
        (r for r in ingest_records if r.stage == "ingest:shard-assemble"), None
    )
    pre_sharded = bool(getattr(table, "_pre_sharded", False))
    shard_counts = {
        name: len(col.storage.sharding.device_set)
        for name, col in table.columns.items()
    }
    print(
        f"ingest (sharded): {n_orders / t_ingest:,.0f} rows/s ({t_ingest:,.1f}s),"
        f" pre_sharded={pre_sharded}, per-column shard counts={shard_counts},"
        f" rss {_rss_mb():,.0f} MB",
        file=sys.stderr,
    )
    assert pre_sharded, "sharded ingest did not engage"
    assert all(v == N_SHARDS for v in shard_counts.values()), shard_counts

    t0 = time.perf_counter()
    cust_idx = (
        FromFile(os.path.join(DATA_DIR, "customers.csv")).OnDevice().UniqueIndexOn("id")
    )
    prod_idx = (
        FromFile(os.path.join(DATA_DIR, "products.csv"))
        .OnDevice()
        .UniqueIndexOn("prod_id")
    )
    t_index = time.perf_counter() - t0
    print(f"index build: {t_index:,.1f}s", file=sys.stderr)

    joined = orders.Join(cust_idx, "cust_id").Join(prod_idx)
    t0 = time.perf_counter()
    result = joined.to_device_table().sync()
    t_join = time.perf_counter() - t0
    assert result.nrows == n_orders, result.nrows
    print(
        f"3-way join (sharded stream, broadcast build): "
        f"{n_orders / t_join:,.0f} rows/s ({t_join:,.2f}s)",
        file=sys.stderr,
    )
    # steady-state warm rate: best of 3 passes, the previous pass's
    # result RELEASED first so XLA reuses its buffers (at 100M rows a
    # retained 3.2GB result forces every warm pass to fault in a fresh
    # copy and dominates the measurement with page faults, not join
    # work; bench.py's reps contract likewise holds no extra result).
    # The verification copy is re-materialized afterwards.
    result = None
    from csvplus_tpu.obs.recompile import RecompileWatch

    warm_times = []
    # warm passes must lower NOTHING: every registered kernel's jit
    # cache is snapshotted before and asserted unchanged after (the r05
    # regression was exactly warm-path eager/retrace work)
    with RecompileWatch() as recompiles:
        for _ in range(3):
            t0 = time.perf_counter()
            r = joined.to_device_table().sync()
            warm_times.append(time.perf_counter() - t0)
            r = None
    recompiles.assert_zero("mesh warm joins")
    t_warm = min(warm_times)
    print(
        f"3-way join (warm, best of {len(warm_times)}):"
        f" {n_orders / t_warm:,.0f} rows/s ({t_warm:,.2f}s;"
        f" passes {', '.join(f'{t:,.2f}s' for t in warm_times)});"
        f" rss {_rss_mb():,.0f} MB",
        file=sys.stderr,
    )

    # ---- per-stage attribution table (r06): one extra warm pass with
    # telemetry enabled.  Its per-stage barriers serialize dispatch, so
    # this pass is NOT the headline number — it is the breakdown that
    # says where the wall time goes. ----
    t0 = time.perf_counter()
    with telemetry.collect() as jrecords:
        joined.to_device_table().sync()
        join_records = list(jrecords)
    t_instrumented = time.perf_counter() - t0
    telemetry.records[:] = ingest_records + join_records
    stage_table = telemetry.to_json()["stage_table"]
    telemetry.reset()
    print(
        f"3-way join (instrumented warm pass): {t_instrumented:,.2f}s;"
        " per-stage table:",
        file=sys.stderr,
    )
    for row in stage_table:
        print(f"  {row}", file=sys.stderr)
    print(f"rss after timed joins: {_rss_mb():,.0f} MB", file=sys.stderr)

    # ---- verification: positional checksums vs the host executor on a
    # 1M-row prefix + full-result checksums for cross-run comparison.
    # Host side FIRST: the 1M-row host join holds ~2GB of Row dicts, so
    # it runs (and is released) before the device verification copy is
    # re-materialized — the two memory peaks must not overlap. ----
    from csvplus_tpu import StopPipeline, take_rows
    from csvplus_tpu.utils.checksum import (
        checksum_device_table,
        checksum_host_rows,
    )

    sample = min(1_000_000, n_orders)
    head: list = []

    def collect(row):
        head.append(row)
        if len(head) >= sample:
            raise StopPipeline

    Take(FromFile(opath))(collect)
    h_cust = Take(FromFile(os.path.join(DATA_DIR, "customers.csv"))).UniqueIndexOn("id")
    h_prod = Take(FromFile(os.path.join(DATA_DIR, "products.csv"))).UniqueIndexOn(
        "prod_id"
    )
    t0 = time.perf_counter()
    host_rows = take_rows(head).Join(h_cust, "cust_id").Join(h_prod).to_rows()
    cols = sorted(host_rows[0].header()) if host_rows else []
    want = checksum_host_rows(host_rows, cols, positional=True)
    head.clear()
    host_rows = None
    # the oracle's ~2GB of Row dicts are freed but allocator-retained;
    # return them to the OS before the device verification copy and the
    # checksum transients stack on top of that base
    from csvplus_tpu.columnar.ingest import _trim_host_staging

    _trim_host_staging()
    print(f"rss after host oracle join: {_rss_mb():,.0f} MB", file=sys.stderr)

    # the verification copy (released before the warm passes above)
    result = joined.to_device_table().sync()
    assert result.nrows == n_orders, result.nrows
    assert sorted(result.columns) == cols, (sorted(result.columns), cols)
    got = checksum_device_table(result, cols, limit=sample, positional=True)
    assert got == want, f"checksum mismatch over the first {sample} rows"
    t_verify = time.perf_counter() - t0
    print(
        f"parity: positional checksums over the first {sample:,} rows match"
        f" the host executor ({t_verify:,.1f}s)",
        file=sys.stderr,
    )
    _trim_host_staging()  # parity-pass leftovers, before the peak phase
    full_sums = checksum_device_table(result, cols, positional=True)
    print(f"rss after full checksums: {_rss_mb():,.0f} MB", file=sys.stderr)

    print(
        json.dumps(
            {
                "metric": "northstar_mesh_threeway_join",
                "rows": n_orders,
                "n_shards": N_SHARDS,
                "ingest_workers": _ingest_workers(),
                "backend": jax.default_backend(),
                **host_header(),
                "recompiles_warm": recompiles.delta(),
                "ingest_rows_per_sec": round(n_orders / t_ingest, 1),
                "join_rows_per_sec": round(n_orders / t_join, 1),
                "join_rows_per_sec_warm": round(n_orders / t_warm, 1),
                "end_to_end_sec": round(t_ingest + t_index + t_join, 1),
                "peak_host_rss_mb": round(_rss_mb(), 1),
                "pre_sharded_ingest": pre_sharded,
                "max_shard_rows": assemble.extra.get("max_shard_rows")
                if assemble
                else None,
                "column_shard_counts": shard_counts,
                "parity_checked_rows": sample,
                "full_result_checksums": full_sums,
                "instrumented_warm_sec": round(t_instrumented, 2),
                "stage_table": stage_table,
                "note": (
                    "virtual 8-device CPU mesh: rates measure the sharded "
                    "EXECUTION PATH (placement, collectives, assembly), not "
                    "chip throughput; chunks land on their shard at ingest "
                    "(typed columns seal per shard as the scan passes them — "
                    "no full-table single-device buffer) and the joins run "
                    "broadcast over the row-sharded stream; stage_table is "
                    "from one extra warm pass with telemetry barriers on, "
                    "headline rates are telemetry-off"
                ),
                "history": {
                    "pre_fused": {
                        "ingest_rows_per_sec": 2719144.7,
                        "join_rows_per_sec_warm": 15081187.1,
                    },
                    "r05_fused_ingest": {
                        "rows": 10_000_000,
                        "ingest_rows_per_sec": 4193327.1,
                        "join_rows_per_sec_warm": 13895781.1,
                        "diagnosis": (
                            "warm-join regression vs pre_fused DIAGNOSED "
                            "(r06, was flagged unexplained): the fused typed "
                            "ingest switched probe keys to typed int lanes "
                            "whose per-execution value->code translation ran "
                            "as ~6 unfused eager passes per key column, plus "
                            "an eager per-column query-key pack loop; fixed "
                            "by module-level jitted kernels "
                            "(columnar/typed.py _translate_*_kernel, "
                            "ops/join.py _pack_qk_kernel, columnar/table.py "
                            "_apply_code_translation) — see ROADMAP.md "
                            "decision note"
                        ),
                    },
                },
            }
        )
    )


def _skew_main(n_orders: int) -> None:
    """The ``--skew`` tier (ISSUE 15): the 3-way join over a Zipf-skewed
    orders stream, skew-aware vs skew-naive IN THE SAME RUN.

    Same measurement discipline as the uniform tier — cold pass, warm
    best-of-3 with telemetry off and zero recompiles asserted, then one
    instrumented pass for the per-stage table — executed twice: once
    with ``CSVPLUS_JOIN_SKEW=0`` (hash-repartition only) and once with
    the skew tier on.  Both legs see identical bytes, and the artifact
    carries bitwise parity (full positional per-column checksums, not a
    prefix) plus the routing counters that say how many rows the
    broadcast tier absorbed.
    """
    # the partition tier must engage on the 1.5M-key customer index
    # (class attr is read when ops/join.py is imported — set first),
    # and the detection sample/threshold are sized for a 1.1-exponent
    # tail where single keys hold only ~0.1-12% each: a 1/(2n) default
    # threshold would catch the top couple of keys, which shrinks the
    # exchange barely at all.  All overrides land in the artifact.
    os.environ.setdefault("CSVPLUS_PARTITION_MIN_KEYS", "1000000")
    os.environ.setdefault("CSVPLUS_JOIN_SKEW_SAMPLE", "16384")
    os.environ.setdefault("CSVPLUS_JOIN_SKEW_THRESHOLD", "0.002")
    n_cust = int(os.environ.get("CSVPLUS_BENCH_MESH_ZIPF_CUSTOMERS", 1_500_000))
    zipf_s = float(os.environ.get("CSVPLUS_BENCH_MESH_ZIPF_S", 1.1))

    import bench  # repo root is on sys.path (header insert)

    opath, cpath = bench.zipf_fact_table(n_orders, n_cust, s=zipf_s)
    print(
        f"zipf orders file: {opath} ({os.path.getsize(opath) / 1e9:.2f} GB),"
        f" s={zipf_s}, {n_cust:,} customers",
        file=sys.stderr,
    )

    import jax

    from csvplus_tpu import FromFile
    from csvplus_tpu.native.scanner import _ingest_workers
    from csvplus_tpu.obs.joinskew import joinskew
    from csvplus_tpu.obs.memory import host_header
    from csvplus_tpu.obs.recompile import RecompileWatch
    from csvplus_tpu.utils.checksum import checksum_device_table
    from csvplus_tpu.utils.observe import telemetry

    assert len(jax.devices()) >= N_SHARDS, jax.devices()

    t0 = time.perf_counter()
    orders = FromFile(opath).OnDevice(shards=N_SHARDS)
    orders.plan.table.sync()
    t_ingest = time.perf_counter() - t0
    table = orders.plan.table
    assert getattr(table, "_pre_sharded", False), "sharded ingest did not engage"
    shard_rows = table.shard_row_counts()
    print(
        f"ingest (sharded): {n_orders / t_ingest:,.0f} rows/s"
        f" ({t_ingest:,.1f}s), shard rows={shard_rows},"
        f" rss {_rss_mb():,.0f} MB",
        file=sys.stderr,
    )

    from northstar import DATA_DIR  # products.csv lives in the same cache

    t0 = time.perf_counter()
    cust_idx = FromFile(cpath).OnDevice().UniqueIndexOn("id")
    prod_idx = (
        FromFile(os.path.join(DATA_DIR, "products.csv"))
        .OnDevice()
        .UniqueIndexOn("prod_id")
    )
    t_index = time.perf_counter() - t0
    print(f"index build: {t_index:,.1f}s", file=sys.stderr)

    joined = orders.Join(cust_idx, "cust_id").Join(prod_idx)
    joinskew.reset()

    legs = {}
    stage_tables = {}
    checksums = {}
    for mode, flag in (("naive", "0"), ("skew", "1")):
        os.environ["CSVPLUS_JOIN_SKEW"] = flag
        t0 = time.perf_counter()
        result = joined.to_device_table().sync()
        t_cold = time.perf_counter() - t0
        assert result.nrows == n_orders, result.nrows
        cols = sorted(result.columns)
        checksums[mode] = checksum_device_table(result, cols, positional=True)
        result = None  # release before the warm passes (see main())
        warm_times = []
        with RecompileWatch() as recompiles:
            for _ in range(3):
                t0 = time.perf_counter()
                r = joined.to_device_table().sync()
                warm_times.append(time.perf_counter() - t0)
                r = None
        recompiles.assert_zero(f"mesh warm zipf joins ({mode})")
        t_warm = min(warm_times)
        with telemetry.collect() as jrecords:
            joined.to_device_table().sync()
            join_records = list(jrecords)
        telemetry.records[:] = join_records
        stage_tables[mode] = telemetry.to_json()["stage_table"]
        telemetry.reset()
        legs[mode] = {
            "cold_sec": round(t_cold, 2),
            "warm_sec": round(t_warm, 2),
            "warm_passes_sec": [round(t, 2) for t in warm_times],
            "rows_per_sec_warm": round(n_orders / t_warm, 1),
            "recompiles_warm": recompiles.delta(),
        }
        print(
            f"3-way zipf join [{mode}]: warm best-of-3"
            f" {n_orders / t_warm:,.0f} rows/s ({t_warm:,.2f}s; passes"
            f" {', '.join(f'{t:,.2f}s' for t in warm_times)});"
            f" rss {_rss_mb():,.0f} MB",
            file=sys.stderr,
        )

    assert checksums["skew"] == checksums["naive"], (
        "bitwise parity broke: skew-aware checksums differ from the"
        " CSVPLUS_JOIN_SKEW=0 run"
    )
    # counters are labelled by the INDEX key columns ("id" for the
    # customer dimension), not the probe-side column name
    snap = joinskew.counters_snapshot()
    counters = snap.get("id")
    assert counters and counters["hot_keys_detected"] > 0, (
        f"skew tier never engaged on the Zipf stream: {snap}"
    )
    speedup = legs["naive"]["warm_sec"] / legs["skew"]["warm_sec"]
    print(
        f"parity: full positional checksums identical across modes;"
        f" skew routing: {counters}; speedup {speedup:,.2f}x",
        file=sys.stderr,
    )

    print(
        json.dumps(
            {
                "metric": "northstar_mesh_threeway_join_zipf",
                "rows": n_orders,
                "n_shards": N_SHARDS,
                "n_customers": n_cust,
                "zipf_s": zipf_s,
                "ingest_workers": _ingest_workers(),
                "backend": jax.default_backend(),
                **host_header(),
                "env_overrides": {
                    k: os.environ[k]
                    for k in (
                        "CSVPLUS_PARTITION_MIN_KEYS",
                        "CSVPLUS_JOIN_SKEW_SAMPLE",
                        "CSVPLUS_JOIN_SKEW_THRESHOLD",
                        "CSVPLUS_STREAM_MIN_BYTES",
                    )
                },
                "ingest_rows_per_sec": round(n_orders / t_ingest, 1),
                "join_rows_per_sec_warm_zipf": legs["skew"]["rows_per_sec_warm"],
                "join_rows_per_sec_warm_naive": legs["naive"]["rows_per_sec_warm"],
                "skew_speedup": round(speedup, 2),
                "legs": legs,
                "skew_counters": counters,
                "parity_bitwise": True,
                "full_result_checksums": checksums["skew"],
                "shard_rows": shard_rows,
                "peak_host_rss_mb": round(_rss_mb(), 1),
                "stage_table_naive": stage_tables["naive"],
                "stage_table_skew": stage_tables["skew"],
                "note": (
                    "both legs in ONE process over identical bytes; naive ="
                    " CSVPLUS_JOIN_SKEW=0 (hash-repartition only), skew ="
                    " detection + broadcast tier for heavy keys + shrunken"
                    " exchange capacity for the tail; parity is FULL-result"
                    " positional per-column checksums, not a prefix"
                ),
            }
        )
    )


def _multiway_main(n_orders: int) -> None:
    """The ``--multiway`` tier (ISSUE 17): the cascaded 3-way join vs
    the single-pass multiway operator over the SAME Zipf-skewed bytes,
    both legs in ONE process.

    Same measurement discipline as the skew tier — cold pass, warm
    best-of-3 with telemetry off and zero recompiles asserted, then one
    instrumented pass for the per-stage table — with two additions:

    * both legs execute through :class:`PlanCache` (the production
      serving path), differing ONLY in ``CSVPLUS_MULTIWAY``: the
      cascaded leg admits with the fuse pass off (optimizer otherwise
      on, skew tier on), the multiway leg must actually FUSE
      (``stats()["fused"] >= 1`` is asserted, not assumed);
    * each leg runs under its own fresh :class:`MemoryWatermark`
      sampler (VmHWM is process-lifetime and cannot be reset), with a
      gc + host-staging trim between legs, so the artifact carries a
      per-leg RSS peak — the number the tentpole's
      "kill the intermediate" claim is judged on.

    Parity is FULL-result positional per-column checksums between the
    legs (hard assert); the RSS-below and throughput-at-least targets
    are recorded as booleans plus a per-stage ``obs diff`` attribution
    table (which stages the fusion removed or shrank).
    """
    # same knobs as the skew tier: partition tier must engage on the
    # 1.5M-key customer index, detection sized for the s=1.1 tail
    os.environ.setdefault("CSVPLUS_PARTITION_MIN_KEYS", "1000000")
    os.environ.setdefault("CSVPLUS_JOIN_SKEW_SAMPLE", "16384")
    os.environ.setdefault("CSVPLUS_JOIN_SKEW_THRESHOLD", "0.002")
    os.environ["CSVPLUS_JOIN_SKEW"] = "1"  # BOTH legs skew-aware
    n_cust = int(os.environ.get("CSVPLUS_BENCH_MESH_ZIPF_CUSTOMERS", 1_500_000))
    zipf_s = float(os.environ.get("CSVPLUS_BENCH_MESH_ZIPF_S", 1.1))

    import gc

    import bench  # repo root is on sys.path (header insert)

    opath, cpath = bench.zipf_fact_table(n_orders, n_cust, s=zipf_s)
    print(
        f"zipf orders file: {opath} ({os.path.getsize(opath) / 1e9:.2f} GB),"
        f" s={zipf_s}, {n_cust:,} customers",
        file=sys.stderr,
    )

    import jax

    from csvplus_tpu import FromFile
    from csvplus_tpu.columnar.ingest import _trim_host_staging
    from csvplus_tpu.native.scanner import _ingest_workers
    from csvplus_tpu.obs.diff import diff_stage_tables
    from csvplus_tpu.obs.joinskew import joinskew
    from csvplus_tpu.obs.memory import MemoryWatermark, host_header
    from csvplus_tpu.obs.recompile import RecompileWatch
    from csvplus_tpu.serve.plancache import PlanCache
    from csvplus_tpu.utils.checksum import checksum_device_table
    from csvplus_tpu.utils.observe import telemetry

    assert len(jax.devices()) >= N_SHARDS, jax.devices()

    t0 = time.perf_counter()
    orders = FromFile(opath).OnDevice(shards=N_SHARDS)
    orders.plan.table.sync()
    t_ingest = time.perf_counter() - t0
    table = orders.plan.table
    assert getattr(table, "_pre_sharded", False), "sharded ingest did not engage"
    print(
        f"ingest (sharded): {n_orders / t_ingest:,.0f} rows/s"
        f" ({t_ingest:,.1f}s), rss {_rss_mb():,.0f} MB",
        file=sys.stderr,
    )

    from northstar import DATA_DIR  # products.csv lives in the same cache

    t0 = time.perf_counter()
    cust_idx = FromFile(cpath).OnDevice().UniqueIndexOn("id")
    prod_idx = (
        FromFile(os.path.join(DATA_DIR, "products.csv"))
        .OnDevice()
        .UniqueIndexOn("prod_id")
    )
    t_index = time.perf_counter() - t0
    print(f"index build: {t_index:,.1f}s", file=sys.stderr)

    # the SAME submitted plan for both legs: Scan -> Join(cust) ->
    # Join(prod); only the admission-time CSVPLUS_MULTIWAY flag differs
    plan = orders.Join(cust_idx, "cust_id").Join(prod_idx).plan
    joinskew.reset()

    legs = {}
    stage_tables = {}
    checksums = {}
    recipes = {}
    for mode, flag in (("cascaded", "0"), ("multiway", "1")):
        os.environ["CSVPLUS_MULTIWAY"] = flag
        cache = PlanCache()
        # level the memory baseline before each leg's sampler starts:
        # drop the previous leg's released buffers and return freed
        # host staging to the OS, so each watermark measures its own
        # leg's working set, not the other's allocator retention
        gc.collect()
        _trim_host_staging()
        wm = MemoryWatermark(interval_s=0.02).start()
        t0 = time.perf_counter()
        result = cache.execute(plan)  # cold: verify+optimize+compile
        t_cold = time.perf_counter() - t0
        assert result.nrows == n_orders, result.nrows
        cols = sorted(result.columns)
        checksums[mode] = checksum_device_table(result, cols, positional=True)
        result = None  # release before the warm passes (see main())
        warm_times = []
        with RecompileWatch() as recompiles:
            for _ in range(3):
                t0 = time.perf_counter()
                r = cache.execute(plan)
                warm_times.append(time.perf_counter() - t0)
                r = None
        recompiles.assert_zero(f"mesh warm multiway-tier joins ({mode})")
        t_warm = min(warm_times)
        with telemetry.collect() as jrecords:
            cache.execute(plan)
            join_records = list(jrecords)
        telemetry.records[:] = join_records
        stage_tables[mode] = telemetry.to_json()["stage_table"]
        telemetry.reset()
        wm.stop()
        stats = cache.stats()
        if mode == "multiway":
            assert stats["fused"] >= 1, f"multiway leg did not fuse: {stats}"
        else:
            assert stats["fused"] == 0, f"cascaded leg fused: {stats}"
        recipe = cache.executable_for(plan).recipe  # warm hit
        recipes[mode] = {
            "steps": [
                [s[0]]
                + [list(a) if isinstance(a, (list, tuple)) else a for a in s[1:]]
                for s in (recipe.steps if recipe is not None else ())
            ],
            "join_order": list(getattr(recipe, "join_order", ()) or ()),
        }
        legs[mode] = {
            "cold_sec": round(t_cold, 2),
            "warm_sec": round(t_warm, 2),
            "warm_passes_sec": [round(t, 2) for t in warm_times],
            "rows_per_sec_warm": round(n_orders / t_warm, 1),
            "recompiles_warm": recompiles.delta(),
            "peak_host_rss_mb": round(wm.rss_peak_mb, 1),
            "rss_start_mb": wm.attrs()["rss_start_mb"],
            "plancache_fused": stats["fused"],
        }
        print(
            f"3-way join [{mode}]: warm best-of-3"
            f" {n_orders / t_warm:,.0f} rows/s ({t_warm:,.2f}s; passes"
            f" {', '.join(f'{t:,.2f}s' for t in warm_times)});"
            f" leg rss peak {wm.rss_peak_mb:,.0f} MB"
            f" (start {legs[mode]['rss_start_mb']:,.0f} MB)",
            file=sys.stderr,
        )

    assert checksums["multiway"] == checksums["cascaded"], (
        "bitwise parity broke: multiway checksums differ from the"
        " CSVPLUS_MULTIWAY=0 cascade over the same bytes"
    )
    snap = joinskew.counters_snapshot()
    # multiway engagement counters are labelled by the fused dims' key
    # columns joined with '+'; routing counters by the customer index's
    # key column ("id")
    mw_counters = snap.get("id+prod_id")
    assert mw_counters and mw_counters.get("multiway_joins", 0) >= 5, (
        f"multiway counters never landed: {snap}"
    )
    skew_counters = snap.get("id")

    rss_below = (
        legs["multiway"]["peak_host_rss_mb"] < legs["cascaded"]["peak_host_rss_mb"]
    )
    thr_at_least = (
        legs["multiway"]["rows_per_sec_warm"] >= legs["cascaded"]["rows_per_sec_warm"]
    )
    speedup = legs["cascaded"]["warm_sec"] / legs["multiway"]["warm_sec"]
    # per-stage obs-diff attribution: which stages the fusion removed
    # (the interior probe/gather/merge) and which it grew (expand)
    stage_diff = diff_stage_tables(
        stage_tables["cascaded"], stage_tables["multiway"]
    )
    for check, ok in (("rss below cascaded", rss_below),
                      ("throughput >= cascaded", thr_at_least)):
        if not ok:
            print(f"WARNING: multiway target missed: {check}", file=sys.stderr)
    print(
        f"parity: full positional checksums identical across operators;"
        f" multiway {speedup:,.2f}x vs cascaded, rss"
        f" {legs['multiway']['peak_host_rss_mb']:,.0f} vs"
        f" {legs['cascaded']['peak_host_rss_mb']:,.0f} MB;"
        f" counters: {mw_counters}",
        file=sys.stderr,
    )

    print(
        json.dumps(
            {
                "metric": "northstar_mesh_threeway_join_multiway",
                "rows": n_orders,
                "n_shards": N_SHARDS,
                "n_customers": n_cust,
                "zipf_s": zipf_s,
                "ingest_workers": _ingest_workers(),
                "backend": jax.default_backend(),
                **host_header(),
                "env_overrides": {
                    k: os.environ[k]
                    for k in (
                        "CSVPLUS_PARTITION_MIN_KEYS",
                        "CSVPLUS_JOIN_SKEW_SAMPLE",
                        "CSVPLUS_JOIN_SKEW_THRESHOLD",
                        "CSVPLUS_JOIN_SKEW",
                        "CSVPLUS_STREAM_MIN_BYTES",
                    )
                },
                "ingest_rows_per_sec": round(n_orders / t_ingest, 1),
                "join_rows_per_sec_warm_multiway": legs["multiway"][
                    "rows_per_sec_warm"
                ],
                "join_rows_per_sec_warm_cascaded": legs["cascaded"][
                    "rows_per_sec_warm"
                ],
                "multiway_speedup": round(speedup, 2),
                "rss_below_cascaded": rss_below,
                "throughput_ge_cascaded": thr_at_least,
                "legs": legs,
                "recipes": recipes,
                "multiway_counters": mw_counters,
                "skew_counters": skew_counters,
                "parity_bitwise": True,
                "full_result_checksums": checksums["multiway"],
                "peak_host_rss_mb": round(_rss_mb(), 1),
                "stage_table_cascaded": stage_tables["cascaded"],
                "stage_table_multiway": stage_tables["multiway"],
                "stage_diff_cascaded_vs_multiway": stage_diff,
                "note": (
                    "both legs in ONE process over identical bytes, both"
                    " through PlanCache with the skew tier on; cascaded ="
                    " CSVPLUS_MULTIWAY=0 (Join->Join with a materialized"
                    " intermediate), multiway = the rewriter's cost-chosen"
                    " fused single-pass operator; per-leg RSS peaks are"
                    " fresh-sampler watermarks (VmHWM is process-lifetime),"
                    " cascaded leg runs first; parity is FULL-result"
                    " positional per-column checksums"
                ),
            }
        )
    )


if __name__ == "__main__":
    main()
