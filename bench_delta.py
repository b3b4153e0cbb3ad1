#!/usr/bin/env python
"""`make bench-delta`: mutable-index (LSM delta-tier) bench + gate.

Drives :class:`csvplus_tpu.storage.MutableIndex` over the big-index
micro shape (same key distribution as `make bench-serve`), measuring the
three numbers the storage tier's docs promise (docs/STORAGE.md):

- append-rows/s        rows/s through ``append_rows`` — each batch rides
                       the staged streamed-ingest encode path and lands
                       as one sorted delta tier
- lookup p50/p99       per-probe ``find_rows`` latency at 0, 4 and 16
                       live delta tiers (the read amplification curve a
                       serving deployment actually sits on)
- compaction pause     reader-observed lookup latency while a full
                       compaction merges and swaps concurrently, plus
                       the compaction's own wall time — the "no lock on
                       the probe hot path" claim, measured

The ISSUE 9 hard contract is enforced INSIDE the bench, not just in the
unit suite: after EVERY compaction step the live tier set must
checksum-match a from-scratch host rebuild of the same logical rows
(bitwise), and warm lookups against the compacted index must record
zero recompiles (``RecompileWatch.assert_zero``).  ISSUE 10 extends the
stream with interleaved deletes: a tombstone cycle (deletes + appends,
one leveled fold, one full merge) must hold the same parity at every
step.  A contract breach raises — it is never a postmortem.

Contract (matches the other benches): diagnostics go to stderr, stdout
carries ONE compact JSON record line re-printed last; the run exits
nonzero only when a gated rate falls under HALF the checked-in floor
(bench_delta_floor.json) — record-or-postmortem, so a miss of the
aspirational targets embeds evidence instead of failing the gate.

Env knobs: CSVPLUS_BENCH_DELTA_ROWS (base rows, default 200K),
_APPEND_ROWS (rows per delta batch, default 2000), _LOOKUPS (probes per
latency scenario, default 1500), _OUT (artifact path; no file by
default so a gate run cannot overwrite the checked-in record).  Seeds
are fixed: same shape -> same probe sequence.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _build_mutable(n: int):
    """A device-backed base tier on the bench-serve key shape, wrapped
    as an append-mode MutableIndex."""
    import numpy as np

    import csvplus_tpu as cp
    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu.storage import MutableIndex

    ids = np.arange(n, dtype=np.int64) * 7 % (n * 3)
    keys = np.char.add("c", ids.astype(np.str_))
    t = DeviceTable.from_pylists(
        {"cust_id": keys.tolist(), "v": np.arange(n).astype(np.str_).tolist()},
        device="cpu",
    )
    idx = cp.take(t).index_on("cust_id").sync()
    return MutableIndex(idx, mode="append", ingest_device="cpu"), ids


def _delta_rows(n_rows: int, start: int):
    """Fresh-key rows for one delta tier (keys beyond the base range,
    so append batches grow the keyspace the way live writes would)."""
    from csvplus_tpu.row import Row

    return [
        Row({"cust_id": f"d{start + i}", "v": f"dv{start + i}"})
        for i in range(n_rows)
    ]


def _uniform_probes(ids, n_probes: int, seed: int = 0):
    import numpy as np

    rng = np.random.default_rng(seed)
    return [f"c{int(v)}" for v in rng.choice(ids, n_probes)]


def _assert_parity(mi, label: str) -> None:
    """The hard contract, enforced in-bench: live tier set bitwise ==
    from-scratch rebuild, after every compaction step."""
    from csvplus_tpu.storage import index_checksums, rebuild_reference

    t0 = time.perf_counter()
    got = index_checksums(mi.to_index())
    ref = index_checksums(rebuild_reference(mi))
    if got != ref:
        raise AssertionError(
            f"bench[delta] PARITY BREACH at {label}: live tier set does"
            f" not checksum-match the from-scratch rebuild"
        )
    sys.stderr.write(
        f"bench[delta]: parity ok at {label}"
        f" ({time.perf_counter() - t0:.1f}s to verify)\n"
    )


def _append_scenario(mi, n_batches: int, batch_rows: int, start: int) -> dict:
    """Append *n_batches* delta batches, timing only the append calls
    (row construction is off the clock, like probe prep in the lookup
    benches)."""
    batches = [
        _delta_rows(batch_rows, start + b * batch_rows) for b in range(n_batches)
    ]
    dt = 0.0
    for rows in batches:
        t0 = time.perf_counter()
        mi.append_rows(rows)
        dt += time.perf_counter() - t0
    total = n_batches * batch_rows
    return {
        "batches": n_batches,
        "rows_per_batch": batch_rows,
        "rows": total,
        "seconds": round(dt, 4),
        "rows_per_sec": round(total / dt, 1),
        "deltas_live_after": mi.delta_count,
    }


def _lookup_scenario(mi, probes) -> dict:
    """Per-probe find_rows latency (p50/p99) at the CURRENT delta
    count.  One warm find_rows_many pays any cold lowering off the
    clock; the timed loop is one probe per call, the serving tier's
    worst-case (uncoalesced) shape."""
    import numpy as np

    mi.find_rows_many([(p,) for p in probes[:64]])
    lats = []
    t_all0 = time.perf_counter()
    for p in probes:
        t0 = time.perf_counter()
        mi.find_rows(p)
        lats.append(time.perf_counter() - t0)
    dt = time.perf_counter() - t_all0
    a = np.asarray(lats, dtype=np.float64)
    return {
        "deltas_live": mi.delta_count,
        "n": len(probes),
        "seconds": round(dt, 4),
        "lookups_per_sec": round(len(probes) / dt, 1),
        "p50_ms": round(float(np.percentile(a, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(a, 99)) * 1e3, 3),
        "max_ms": round(float(a.max()) * 1e3, 3),
    }


def _compaction_pause_scenario(mi, probes, n_readers: int = 2) -> dict:
    """Reader threads hammer find_rows while compact_once merges and
    swaps.  Pause = the latency of reads overlapping the compaction
    window vs reads outside it — the snapshot-swap design says the
    probe hot path never blocks on the compactor's locks."""
    import numpy as np

    stop = threading.Event()
    started = threading.Barrier(n_readers + 1)
    samples = []  # (t_start, latency) appended per-thread, merged after
    per_thread = [[] for _ in range(n_readers)]
    errs = []

    def reader(slot: int):
        local = per_thread[slot]
        try:
            started.wait()
            i = slot
            while not stop.is_set():
                p = probes[i % len(probes)]
                t0 = time.perf_counter()
                mi.find_rows(p)
                local.append((t0, time.perf_counter() - t0))
                i += n_readers
        except BaseException as e:  # surfaced after join
            errs.append(e)
            stop.set()

    threads = [
        threading.Thread(target=reader, args=(i,)) for i in range(n_readers)
    ]
    for t in threads:
        t.start()
    started.wait()
    time.sleep(0.05)  # let readers reach steady state first
    t_c0 = time.perf_counter()
    stats = mi.compact_once()
    t_c1 = time.perf_counter()
    time.sleep(0.05)  # and a post-compaction tail for the baseline
    stop.set()
    for t in threads:
        t.join()
    if errs:
        raise errs[0]
    for local in per_thread:
        samples.extend(local)

    during = np.asarray(
        [lat for (ts, lat) in samples if t_c0 <= ts <= t_c1], dtype=np.float64
    )
    outside = np.asarray(
        [lat for (ts, lat) in samples if ts < t_c0 or ts > t_c1],
        dtype=np.float64,
    )
    out = {
        "readers": n_readers,
        "reads_total": len(samples),
        "reads_during_compaction": int(during.size),
        "compact_seconds": round(t_c1 - t_c0, 4),
        "compact_stats": stats,
    }
    if during.size:
        out["during_p50_ms"] = round(float(np.percentile(during, 50)) * 1e3, 3)
        out["during_p99_ms"] = round(float(np.percentile(during, 99)) * 1e3, 3)
        out["during_max_ms"] = round(float(during.max()) * 1e3, 3)
    if outside.size:
        out["outside_p50_ms"] = round(float(np.percentile(outside, 50)) * 1e3, 3)
        out["outside_p99_ms"] = round(float(np.percentile(outside, 99)) * 1e3, 3)
    return out


def _zero_recompile_gate(mi, probes) -> dict:
    """Warm lookups against the compacted index must recompile nothing
    — the merge path promises plain-numpy merges + one device_put per
    column, never a fresh jitted shape."""
    from csvplus_tpu.obs.recompile import RecompileWatch

    norm = [(p,) for p in probes]
    mi.find_rows_many(norm)  # warm-up pays any cold lowering once
    with RecompileWatch() as w:
        for _ in range(3):
            mi.find_rows_many(norm)
    w.assert_zero("bench-delta warm post-compaction lookups")
    return {"recompiles": 0}


def main() -> int:
    import jax

    from csvplus_tpu.obs.memory import host_header

    n = _env_int("CSVPLUS_BENCH_DELTA_ROWS", 200_000)
    batch_rows = _env_int("CSVPLUS_BENCH_DELTA_APPEND_ROWS", 2_000)
    n_lookups = _env_int("CSVPLUS_BENCH_DELTA_LOOKUPS", 1_500)
    out_path = os.environ.get("CSVPLUS_BENCH_DELTA_OUT")
    host_cpus = os.cpu_count() or 1

    sys.stderr.write(
        f"bench[delta]: building {n:,}-row base tier"
        f" (backend={jax.default_backend()}, host_cpus={host_cpus})\n"
    )
    t0 = time.perf_counter()
    mi, ids = _build_mutable(n)
    sys.stderr.write(
        f"bench[delta]: base ready in {time.perf_counter() - t0:.1f}s\n"
    )
    probes = _uniform_probes(ids, n_lookups)

    scenarios: dict = {}

    # -- read amplification curve: 0 -> 4 -> 16 live deltas ---------------
    scenarios["lookup_0_deltas"] = _lookup_scenario(mi, probes)
    sys.stderr.write(
        "bench[delta]: lookups @0 deltas"
        f" p50 {scenarios['lookup_0_deltas']['p50_ms']}ms"
        f" p99 {scenarios['lookup_0_deltas']['p99_ms']}ms\n"
    )

    scenarios["append"] = _append_scenario(mi, 4, batch_rows, start=0)
    append_rate = scenarios["append"]["rows_per_sec"]
    sys.stderr.write(
        f"bench[delta]: append {append_rate:,.0f} rows/s"
        f" ({scenarios['append']['batches']} batches of"
        f" {batch_rows:,})\n"
    )

    scenarios["lookup_4_deltas"] = _lookup_scenario(mi, probes)
    sys.stderr.write(
        "bench[delta]: lookups @4 deltas"
        f" p50 {scenarios['lookup_4_deltas']['p50_ms']}ms"
        f" p99 {scenarios['lookup_4_deltas']['p99_ms']}ms\n"
    )

    scenarios["append_to_16"] = _append_scenario(
        mi, 12, batch_rows, start=4 * batch_rows
    )
    scenarios["lookup_16_deltas"] = _lookup_scenario(mi, probes)
    lookup16 = scenarios["lookup_16_deltas"]["lookups_per_sec"]
    sys.stderr.write(
        "bench[delta]: lookups @16 deltas"
        f" p50 {scenarios['lookup_16_deltas']['p50_ms']}ms"
        f" p99 {scenarios['lookup_16_deltas']['p99_ms']}ms"
        f" ({lookup16:,.0f}/s)\n"
    )

    # -- compaction: concurrent-reader pause + hard contract --------------
    scenarios["compaction_pause"] = _compaction_pause_scenario(mi, probes)
    cp_s = scenarios["compaction_pause"]
    sys.stderr.write(
        f"bench[delta]: compaction {cp_s['compact_seconds']}s with"
        f" {cp_s['reads_during_compaction']} concurrent reads"
        f" (during p99 {cp_s.get('during_p99_ms')}ms,"
        f" outside p99 {cp_s.get('outside_p99_ms')}ms)\n"
    )
    _assert_parity(mi, "compaction step 1")

    # a second append+compact cycle: parity must hold at EVERY step
    mi.append_rows(_delta_rows(batch_rows, start=16 * batch_rows))
    stats2 = mi.compact_once()
    scenarios["second_compaction"] = stats2
    _assert_parity(mi, "compaction step 2")

    # -- tombstone cycle (ISSUE 10): interleaved appends and deletes -------
    # hold the same checksum parity through a partial (leveled) fold
    # and the full merge that drops the tombstones for good
    for i in range(8):
        mi.delete((f"d{16 * batch_rows + i}",))
    mi.append_rows(_delta_rows(64, start=17 * batch_rows))
    mi.delete((probes[0],))
    mi.append_rows(_delta_rows(64, start=17 * batch_rows + 64))
    _assert_parity(mi, "live tombstone tiers")
    step_stats = mi.compact_step(ratio=2)
    _assert_parity(mi, "leveled fold with tombstones")
    stats3 = mi.compact_once()
    scenarios["tombstone_cycle"] = {
        "deletes": 10,
        "leveled_fold": step_stats,
        "full_merge": stats3,
    }
    _assert_parity(mi, "tombstones applied and dropped")

    scenarios["zero_recompile_gate"] = _zero_recompile_gate(mi, probes[:256])
    sys.stderr.write(
        "bench[delta]: warm post-compaction lookups recompiled nothing\n"
    )

    # fence+filter pruning accounting for the whole run (ISSUE 11):
    # cumulative tiers probed/pruned and the read-amp window the
    # "readamp" Compactor policy schedules from
    prune_stats = mi.snapshot()["prune"]
    sys.stderr.write(
        f"bench[delta]: prune enabled={prune_stats.get('enabled')}"
        f" tier_probes={prune_stats.get('tier_probes')}"
        f" tiers_pruned={prune_stats.get('tiers_pruned')}"
        f" mean_tiers_probed={prune_stats.get('mean_tiers_probed')}\n"
    )

    # -- record ------------------------------------------------------------
    record = {
        "metric": "delta_append_rows_per_sec",
        "value": append_rate,
        "unit": "rows/s",
        "n_rows": n,
        "rows_per_batch": batch_rows,
        "n_lookups": n_lookups,
        "backend": jax.default_backend(),
        **host_header(),
        "lookups_per_sec_16_deltas": lookup16,
        "lookup_p99_ms_0_deltas": scenarios["lookup_0_deltas"]["p99_ms"],
        "lookup_p99_ms_16_deltas": scenarios["lookup_16_deltas"]["p99_ms"],
        "compact_seconds": cp_s["compact_seconds"],
        "prune": prune_stats,
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
        sys.stderr.write(f"bench[delta]: artifact written to {out_path}\n")

    # -- floor gate (record-or-postmortem: fail only under HALF floor) -----
    floors = {}
    try:
        with open(os.path.join(REPO, "bench_delta_floor.json")) as f:
            floors = json.load(f)
    except (OSError, ValueError):
        pass
    status = 0
    for key, got in (
        ("delta_append_rows_per_sec", append_rate),
        ("lookups_per_sec_16_deltas", lookup16),
    ):
        floor = float(floors.get(key, 0.0) or 0.0)
        if floor and got < floor / 2:
            sys.stderr.write(
                f"bench[delta] REGRESSION: {key} {got:,.0f} is under half"
                f" the floor ({floor:,.0f})\n"
            )
            status = 1
        else:
            sys.stderr.write(
                f"bench[delta] ok: {key} {got:,.0f} (floor {floor:,.0f})\n"
            )
    # compact record re-printed LAST on stdout (the machine-readable line)
    compact = {
        k: record[k]
        for k in (
            "metric", "value", "unit", "n_rows", "rows_per_batch",
            "n_lookups", "host_cpus", "lookups_per_sec_16_deltas",
            "lookup_p99_ms_0_deltas", "lookup_p99_ms_16_deltas",
            "compact_seconds",
        )
        if k in record
    }
    print(json.dumps(compact), flush=True)
    return status


if __name__ == "__main__":
    sys.exit(main())
