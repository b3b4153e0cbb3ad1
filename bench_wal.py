#!/usr/bin/env python
"""`make bench-wal`: durable mutable-index (WAL) bench + gate.

Drives the ISSUE 10 durability layer under
:class:`csvplus_tpu.storage.MutableIndex` and measures the three
numbers docs/STORAGE.md promises for it:

- append-rows/s        rows/s through ``append_rows`` on a DURABLE
                       index under ``CSVPLUS_WAL_SYNC=always`` (every
                       record fsynced before the ack) vs ``batch``
                       (fsync deferred to the serving tier's per-cycle
                       ``wal_sync``) — the price of the ack contract
- recovery             wall time for ``MutableIndex.open`` to replay a
                       ~200K-row WAL tail through the same delta-encode
                       path live appends ride
- lookup p50/p99       per-probe ``find_rows`` latency with live
                       tombstone tiers on the read path (the shadowing
                       masks are on the hot path; they must stay cheap)
- read-amp floor       per-probe ``find_rows`` throughput at >= 128
                       LIVE tiers (row deltas + tombstone tiers) with
                       host-side fence/filter pruning (ISSUE 11) vs the
                       same probes against the fully compacted base —
                       the r11 cliff was 47x; the pruned ratio must
                       stay within 3x (asserted in-bench), results
                       bitwise-equal to the compacted truth, parity
                       held at EVERY compaction step, zero warm
                       recompiles
- readamp compactor    a sustained append+lookup mix under the
                       ``policy="readamp"`` Compactor: the observed
                       mean tiers-probed must fall under the target
                       with NO manual compaction call (asserted)

The hard contract is enforced IN-BENCH: the recovered index must
checksum-match the live one (bitwise, ``index_checksums``) and the
from-scratch logical rebuild, and warm lookups against the recovered,
tombstone-bearing index must record zero recompiles
(``RecompileWatch.assert_zero``).  A breach raises — never a
postmortem.

Contract (matches the other benches): diagnostics go to stderr, stdout
carries ONE compact JSON record line re-printed last; the run exits
nonzero only when a gated rate falls under HALF the checked-in floor
(bench_wal_floor.json) — record-or-postmortem, so a miss of the
aspirational targets embeds evidence instead of failing the gate.

Env knobs: CSVPLUS_BENCH_WAL_ROWS (base rows, default 100K),
_APPEND_ROWS (rows per append batch, default 2000), _RECOVERY_ROWS
(WAL-tail rows for the recovery scenario, default 200K), _LOOKUPS
(probes for the latency scenario, default 1000), _OUT (artifact path;
no file by default so a gate run cannot overwrite the checked-in
record).  ``CSVPLUS_MICRO_DIST=zipf`` switches the read-amp tier's
probe draws to the shared Zipf hot-key distribution
(``bench.zipf_probe_values``, the same helper ``make bench-serve``
uses); the default stays uniform so the gated floor is
apples-to-apples with the checked-in record.  Seeds are fixed: same
shape -> same probe sequence.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _base_source(n: int):
    """The bench-serve key shape as an ingest source (host-built; the
    durable ctor persists the base tier to the directory)."""
    import numpy as np

    from csvplus_tpu.row import Row
    from csvplus_tpu.source import take_rows

    ids = np.arange(n, dtype=np.int64) * 7 % (n * 3)
    rows = [
        Row({"cust_id": f"c{int(v)}", "v": str(i)})
        for i, v in enumerate(ids)
    ]
    return take_rows(rows), ids


def _delta_rows(n_rows: int, start: int):
    return [
        {"cust_id": f"w{start + i}", "v": f"d{start + i}"}
        for i in range(n_rows)
    ]


def _uniform_probes(ids, n_probes: int, seed: int = 0):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [f"c{int(v)}" for v in rng.choice(ids, n_probes)]


def _append_scenario(directory, src, sync: str, n_batches: int,
                     batch_rows: int) -> dict:
    """Append *n_batches* durable delta batches under fsync policy
    *sync*, timing only the append calls.  ``batch`` mode pays one
    explicit ``wal_sync()`` at the end (the serving tier's per-cycle
    flush), kept ON the clock — an unsynced append is not durable yet,
    so it has not finished."""
    from csvplus_tpu.storage import MutableIndex

    mi = MutableIndex.create(
        src, ["cust_id"], mode="append", ingest_device="cpu",
        directory=directory, wal_sync=sync,
    )
    batches = [
        _delta_rows(batch_rows, b * batch_rows) for b in range(n_batches)
    ]
    dt = 0.0
    for rows in batches:
        t0 = time.perf_counter()
        mi.append_rows(rows)
        dt += time.perf_counter() - t0
    t0 = time.perf_counter()
    wal_delta = mi.wal_sync()
    dt += time.perf_counter() - t0
    total = n_batches * batch_rows
    return {
        "sync": sync,
        "batches": n_batches,
        "rows_per_batch": batch_rows,
        "rows": total,
        "seconds": round(dt, 4),
        "rows_per_sec": round(total / dt, 1),
        "wal": mi.snapshot()["wal"],
        "fsyncs_in_flight": wal_delta["fsyncs"],
    }


def _recovery_scenario(directory, src, tail_rows: int,
                       batch_rows: int) -> dict:
    """Build a durable index whose WAL tail carries *tail_rows* rows
    (plus a sprinkle of tombstones), then time a cold
    ``MutableIndex.open`` — recovery replays the tail through the same
    delta-encode path appends ride.  The recovered state must be
    bitwise-equal to the live writer's."""
    from csvplus_tpu.storage import MutableIndex, index_checksums

    mi = MutableIndex.create(
        src, ["cust_id"], mode="append", ingest_device="cpu",
        directory=directory, wal_sync="batch",
    )
    n_batches = max(1, tail_rows // batch_rows)
    for b in range(n_batches):
        mi.append_rows(_delta_rows(batch_rows, b * batch_rows))
        if b % 16 == 0:  # tombstones ride the same replay path
            mi.delete((f"w{b * batch_rows}",))
    mi.wal_sync()
    live = index_checksums(mi.to_index())
    records = mi.snapshot()["wal"]["records"]

    t0 = time.perf_counter()
    re1 = MutableIndex.open(directory)
    dt = time.perf_counter() - t0
    if index_checksums(re1.to_index()) != live:
        raise AssertionError(
            "bench[wal] PARITY BREACH: recovered index does not"
            " checksum-match the live writer"
        )
    rows = n_batches * batch_rows
    return {
        "wal_records": records,
        "recovered_records": re1.recovered_records,
        "truncated_bytes": re1.recovery_info["truncated_bytes"],
        "rows": rows,
        "seconds": round(dt, 4),
        "rows_per_sec": round(rows / dt, 1),
    }, re1


def _tombstone_lookup_scenario(mi, probes, n_tombs: int) -> dict:
    """Per-probe find_rows latency with *n_tombs* live tombstone tiers
    shadowing the read path (every probe pays the tomb-mask check)."""
    import numpy as np

    deleted = []
    for i, p in enumerate(probes):
        if len(deleted) >= n_tombs:
            break
        if i % 7 == 0 and p not in deleted:
            mi.delete((p,))
            deleted.append(p)
    mi.find_rows_many([(p,) for p in probes[:64]])  # warm off the clock
    lats = []
    t_all0 = time.perf_counter()
    for p in probes:
        t0 = time.perf_counter()
        mi.find_rows(p)
        lats.append(time.perf_counter() - t0)
    dt = time.perf_counter() - t_all0
    a = np.asarray(lats, dtype=np.float64)
    return {
        "tombstone_tiers": len(deleted),
        "deltas_live": mi.delta_count,
        "n": len(probes),
        "seconds": round(dt, 4),
        "lookups_per_sec": round(len(probes) / dt, 1),
        "p50_ms": round(float(np.percentile(a, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(a, 99)) * 1e3, 3),
        "max_ms": round(float(a.max()) * 1e3, 3),
    }


def _zero_recompile_gate(mi, probes) -> dict:
    from csvplus_tpu.obs.recompile import RecompileWatch

    norm = [(p,) for p in probes]
    mi.find_rows_many(norm)
    with RecompileWatch() as w:
        for _ in range(3):
            mi.find_rows_many(norm)
    w.assert_zero("bench-wal warm recovered-index lookups")
    return {"recompiles": 0}


def _readamp_probe_values(ids, n_probes: int):
    """The read-amp tier's probe draw: uniform by default, the shared
    Zipf hot-key distribution under CSVPLUS_MICRO_DIST=zipf."""
    dist = os.environ.get("CSVPLUS_MICRO_DIST", "uniform")
    if dist == "zipf":
        from bench import zipf_probe_values

        return dist, [f"c{int(v)}" for v in zipf_probe_values(ids, n_probes)]
    return dist, _uniform_probes(ids, n_probes, seed=11)


def _timed_single_probes(mi, probes) -> float:
    """Per-probe find_rows loop (the serving single-probe shape the r11
    cliff was measured on), returning lookups/s."""
    t0 = time.perf_counter()
    for p in probes:
        mi.find_rows((p,))
    return len(probes) / (time.perf_counter() - t0)


def _readamp_scenario(directory, src, ids, n_probes: int) -> dict:
    """The ISSUE 11 tentpole number: lookup throughput at >=128 live
    tiers (row deltas AND tombstone tiers) with host fence/filter
    pruning, vs the SAME probes against the fully compacted base.

    Hard contracts, asserted in-bench:

    - pruned layered results are bitwise-equal to the compacted truth
      (per-probe row compare) and checksum-parity holds vs the
      from-scratch logical rebuild;
    - the ``to_index`` checksum is invariant at EVERY leveled
      compaction step on the way down;
    - warm pruned lookups recompile nothing;
    - layered throughput stays within 3x of the compacted floor (the
      r11 cliff was 47x).
    """
    from csvplus_tpu.obs.recompile import RecompileWatch
    from csvplus_tpu.storage import (
        MutableIndex,
        index_checksums,
        rebuild_reference,
    )

    mi = MutableIndex.create(
        src, ["cust_id"], mode="append", ingest_device="cpu",
        directory=directory, wal_sync="batch",
    )
    # 120 row tiers + 20 tombstone tiers = 140 live tiers (>= 128)
    for b in range(120):
        mi.append_rows(_delta_rows(120, 500_000 + b * 120))
        if b % 6 == 0:
            mi.delete((f"c{int(ids[(b * 131) % len(ids)])}",))
    mi.wal_sync()
    tiers_live = mi.delta_count
    if tiers_live < 128:
        raise AssertionError(
            f"bench[wal] shape bug: only {tiers_live} live tiers"
        )

    dist, probes = _readamp_probe_values(ids, n_probes)
    norm = [(p,) for p in probes]
    mi.find_rows_many(norm[:64])  # warm off the clock
    mi.readamp.take_window()  # report the mean over the timed loop only
    layered_rate = _timed_single_probes(mi, probes)
    mean_tiers = mi.readamp.take_window()
    layered_rows = [[dict(r) for r in mi.find_rows((p,))] for p in probes]
    with RecompileWatch() as w:
        mi.find_rows_many(norm[:256])
    w.assert_zero("bench-wal warm pruned lookups")
    frozen = index_checksums(mi.to_index())
    prune_stats = mi.snapshot()["prune"]

    # compact to the floor, holding the checksum at every step
    steps = 0
    while True:
        if mi.compact_step() is None:
            break
        steps += 1
        if index_checksums(mi.to_index()) != frozen:
            raise AssertionError(
                f"bench[wal] PARITY BREACH at compaction step {steps}"
            )
    mi.compact_once()
    if index_checksums(mi.to_index()) != frozen:
        raise AssertionError("bench[wal] PARITY BREACH at full compaction")
    if index_checksums(mi.to_index()) != index_checksums(
        rebuild_reference(mi)
    ):
        raise AssertionError(
            "bench[wal] PARITY BREACH vs from-scratch logical rebuild"
        )
    compacted_rows = [[dict(r) for r in mi.find_rows((p,))] for p in probes]
    if layered_rows != compacted_rows:
        raise AssertionError(
            "bench[wal] PRUNE BREACH: layered pruned results differ from"
            " the compacted truth"
        )
    mi.find_rows_many(norm[:64])
    floor_rate = _timed_single_probes(mi, probes)
    ratio = floor_rate / layered_rate
    if ratio > 3.0:
        raise AssertionError(
            f"bench[wal] READ-AMP BREACH: compacted/layered throughput"
            f" ratio {ratio:.2f}x exceeds the 3x bound"
            f" ({layered_rate:,.0f}/s layered vs {floor_rate:,.0f}/s"
            f" compacted at {tiers_live} tiers)"
        )
    return {
        "dist": dist,
        "tiers_live": tiers_live,
        "tombstone_tiers": 20,
        "n": len(probes),
        "lookups_per_sec_layered": round(layered_rate, 1),
        "lookups_per_sec_compacted": round(floor_rate, 1),
        "compacted_over_layered": round(ratio, 3),
        "mean_tiers_probed": (
            round(mean_tiers, 3) if mean_tiers is not None else None
        ),
        "compaction_steps": steps,
        "prune": prune_stats,
    }


def _readamp_compactor_scenario(timeout_s: float = 60.0) -> dict:
    """Sustained append+lookup mix under the ``readamp`` Compactor
    policy, NO manual compaction: the policy must observe the window
    mean, compact, and drive it under the target.  A non-convergence is
    a raise, not a recorded miss — the scheduler IS the feature."""
    from csvplus_tpu.row import Row
    from csvplus_tpu.source import take_rows
    from csvplus_tpu.storage import Compactor, MutableIndex

    rows = [Row({"cust_id": f"h{i % 11}", "v": str(i)}) for i in range(256)]
    mi = MutableIndex.create(
        take_rows(rows), ["cust_id"], mode="append", ingest_device="cpu",
    )
    # the hot key lives in EVERY tier, so pruning cannot mask the
    # amplification — only the compactor can fix it
    for b in range(32):
        mi.append_rows([{"cust_id": "h0", "v": f"hot{b}"}])
    probes = [("h0",)] * 8
    mi.find_rows_many(probes)
    pre_mean = mi.readamp.take_window()
    target = 4.0
    c = Compactor(
        mi, min_deltas=1, interval_s=0.005, policy="readamp",
        readamp_target=target,
    )
    t0 = time.perf_counter()
    converged_s = None
    with c:
        while time.perf_counter() - t0 < timeout_s:
            mi.append_rows([{"cust_id": "h0", "v": "more"}])
            mi.find_rows_many(probes)
            snap = c.snapshot()
            if (
                snap["last_readamp"] is not None
                and snap["last_readamp"] <= target
                and snap["compactions"] >= 1
            ):
                converged_s = time.perf_counter() - t0
                break
            time.sleep(0.01)
    if converged_s is None:
        raise AssertionError(
            f"bench[wal] READ-AMP BREACH: readamp compactor never"
            f" converged under target {target} in {timeout_s}s:"
            f" {c.snapshot()}"
        )
    snap = c.snapshot()
    return {
        "policy": "readamp",
        "target": target,
        "pre_mean_tiers_probed": round(pre_mean, 2),
        "converged_seconds": round(converged_s, 3),
        "final_mean_tiers_probed": snap["last_readamp"],
        "compactions": snap["compactions"],
        "deltas_live_after": mi.delta_count,
    }


def main() -> int:
    import jax

    from csvplus_tpu.obs.memory import host_header
    from csvplus_tpu.storage import index_checksums, rebuild_reference

    n = _env_int("CSVPLUS_BENCH_WAL_ROWS", 100_000)
    batch_rows = _env_int("CSVPLUS_BENCH_WAL_APPEND_ROWS", 2_000)
    recovery_rows = _env_int("CSVPLUS_BENCH_WAL_RECOVERY_ROWS", 200_000)
    n_lookups = _env_int("CSVPLUS_BENCH_WAL_LOOKUPS", 1_000)
    out_path = os.environ.get("CSVPLUS_BENCH_WAL_OUT")
    host_cpus = os.cpu_count() or 1

    sys.stderr.write(
        f"bench[wal]: {n:,}-row base, {batch_rows:,}-row batches"
        f" (backend={jax.default_backend()}, host_cpus={host_cpus})\n"
    )
    scenarios: dict = {}
    tmp_root = tempfile.mkdtemp(prefix="csvplus-bench-wal-")
    try:
        # -- append throughput: the price of ack-after-fsync ---------------
        for sync in ("always", "batch"):
            src, ids = _base_source(n)
            d = os.path.join(tmp_root, f"append-{sync}")
            scenarios[f"append_{sync}"] = _append_scenario(
                d, src, sync, 8, batch_rows
            )
            s = scenarios[f"append_{sync}"]
            sys.stderr.write(
                f"bench[wal]: append sync={sync} {s['rows_per_sec']:,.0f}"
                f" rows/s ({s['wal']['fsyncs']} fsyncs)\n"
            )
        always_rate = scenarios["append_always"]["rows_per_sec"]
        batch_rate = scenarios["append_batch"]["rows_per_sec"]

        # -- recovery: replay a ~200K-row WAL tail -------------------------
        src, ids = _base_source(n)
        d = os.path.join(tmp_root, "recovery")
        scenarios["recovery"], recovered = _recovery_scenario(
            d, src, recovery_rows, batch_rows
        )
        rec = scenarios["recovery"]
        sys.stderr.write(
            f"bench[wal]: recovery of {rec['rows']:,} WAL-tail rows"
            f" ({rec['recovered_records']} records) in"
            f" {rec['seconds']}s ({rec['rows_per_sec']:,.0f} rows/s)\n"
        )

        # -- tombstone lookups on the recovered index ----------------------
        probes = _uniform_probes(ids, n_lookups)
        scenarios["lookup_tombstones"] = _tombstone_lookup_scenario(
            recovered, probes, n_tombs=32
        )
        lk = scenarios["lookup_tombstones"]
        sys.stderr.write(
            f"bench[wal]: lookups with {lk['tombstone_tiers']} tombstone"
            f" tiers p50 {lk['p50_ms']}ms p99 {lk['p99_ms']}ms"
            f" ({lk['lookups_per_sec']:,.0f}/s)\n"
        )

        # -- hard contract on the recovered index --------------------------
        if index_checksums(recovered.to_index()) != index_checksums(
            rebuild_reference(recovered)
        ):
            raise AssertionError(
                "bench[wal] PARITY BREACH: recovered tier set does not"
                " checksum-match the from-scratch logical rebuild"
            )
        sys.stderr.write("bench[wal]: recovered-index parity ok\n")
        scenarios["zero_recompile_gate"] = _zero_recompile_gate(
            recovered, probes[:256]
        )
        sys.stderr.write(
            "bench[wal]: warm recovered-index lookups recompiled nothing\n"
        )

        # -- read amplification at >=128 live tiers (ISSUE 11) -------------
        src, ids = _base_source(n)
        d = os.path.join(tmp_root, "readamp")
        scenarios["readamp"] = _readamp_scenario(d, src, ids, n_lookups)
        ra = scenarios["readamp"]
        sys.stderr.write(
            f"bench[wal]: read-amp dist={ra['dist']}"
            f" {ra['lookups_per_sec_layered']:,.0f}/s at"
            f" {ra['tiers_live']} live tiers vs"
            f" {ra['lookups_per_sec_compacted']:,.0f}/s compacted"
            f" ({ra['compacted_over_layered']}x, mean"
            f" {ra['mean_tiers_probed']} tiers probed)\n"
        )
        scenarios["readamp_compactor"] = _readamp_compactor_scenario()
        rc = scenarios["readamp_compactor"]
        sys.stderr.write(
            f"bench[wal]: readamp compactor converged"
            f" {rc['pre_mean_tiers_probed']} ->"
            f" {rc['final_mean_tiers_probed']} mean tiers probed in"
            f" {rc['converged_seconds']}s ({rc['compactions']}"
            f" compactions, no manual compact)\n"
        )
    finally:
        shutil.rmtree(tmp_root, ignore_errors=True)

    # -- record ------------------------------------------------------------
    record = {
        "metric": "wal_append_rows_per_sec_always",
        "value": always_rate,
        "unit": "rows/s",
        "n_rows": n,
        "rows_per_batch": batch_rows,
        "recovery_rows": recovery_rows,
        "n_lookups": n_lookups,
        "backend": jax.default_backend(),
        **host_header(),
        "wal_append_rows_per_sec_batch": batch_rate,
        "recovery_rows_per_sec": rec["rows_per_sec"],
        "recovery_seconds": rec["seconds"],
        "lookups_per_sec_tombstones": lk["lookups_per_sec"],
        "lookup_p50_ms_tombstones": lk["p50_ms"],
        "lookups_per_sec_readamp": ra["lookups_per_sec_layered"],
        "readamp_tiers_live": ra["tiers_live"],
        "readamp_compacted_over_layered": ra["compacted_over_layered"],
        "scenarios": scenarios,
    }
    try:
        record["commit"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, cwd=REPO, timeout=10,
        ).stdout.strip() or None
    except Exception:
        pass

    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
        sys.stderr.write(f"bench[wal]: artifact written to {out_path}\n")

    # -- floor gate (record-or-postmortem: fail only under HALF floor) -----
    floors = {}
    try:
        with open(os.path.join(REPO, "bench_wal_floor.json")) as f:
            floors = json.load(f)
    except (OSError, ValueError):
        pass
    status = 0
    for key, got in (
        ("wal_append_rows_per_sec_always", always_rate),
        ("wal_append_rows_per_sec_batch", batch_rate),
        ("recovery_rows_per_sec", rec["rows_per_sec"]),
        ("lookups_per_sec_tombstones", lk["lookups_per_sec"]),
        ("lookups_per_sec_readamp", ra["lookups_per_sec_layered"]),
    ):
        floor = float(floors.get(key, 0.0) or 0.0)
        if floor and got < floor / 2:
            sys.stderr.write(
                f"bench[wal] REGRESSION: {key} {got:,.0f} is under half"
                f" the floor ({floor:,.0f})\n"
            )
            status = 1
        else:
            sys.stderr.write(
                f"bench[wal] ok: {key} {got:,.0f} (floor {floor:,.0f})\n"
            )
    compact = {
        k: record[k]
        for k in (
            "metric", "value", "unit", "n_rows", "rows_per_batch",
            "recovery_rows", "host_cpus", "wal_append_rows_per_sec_batch",
            "recovery_rows_per_sec", "recovery_seconds",
            "lookups_per_sec_tombstones", "lookup_p50_ms_tombstones",
            "lookups_per_sec_readamp", "readamp_tiers_live",
            "readamp_compacted_over_layered",
        )
        if k in record
    }
    print(json.dumps(compact), flush=True)
    return status


if __name__ == "__main__":
    sys.exit(main())
