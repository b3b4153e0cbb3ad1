#!/usr/bin/env python
"""`make bench-view`: live materialized-view maintenance bench + gate.

Registers the headline ISSUE 12 view — the 3-way orders x customers x
products join (docs/VIEWS.md) — over a 1M-row append-mode
:class:`csvplus_tpu.storage.MutableIndex` and drives coalesced write
batches (<=1K rows each, plus interleaved key deletes) through
:meth:`MaterializedView.refresh`, measuring the numbers the views tier
promises:

- refresh ms/batch       incremental maintenance cost per applied batch
                         (per-tier plan execution through the WARM
                         plan-cache executable + host retraction)
- incremental speedup    from-scratch recompute seconds / mean refresh
                         seconds — the gated >=20x claim
- view read p50/p99      per-key ``view.read()`` latency against the
                         epoch-pinned snapshot (the sub-ms serving path)

The ISSUE 12 hard contract is enforced INSIDE the bench, not just in
the unit suite: after EVERY batch the view's positional per-column
checksums must equal a from-scratch execution of the registered plan
over the source's merged stream (bitwise), and every warm refresh runs
under its own ``RecompileWatch`` that must record ZERO new lowerings —
kernel counters and the plan cache's ``lowered`` both (the recompute
baseline executes at a different, growing table shape by design, so it
runs OUTSIDE the watch).  A contract breach raises — never a
postmortem.

Batches are generated with deterministic per-batch dictionary
cardinalities (round-robin draws -> exactly the same number of unique
values per column every batch) and fixed string widths, so every warm
batch shares one trace-cache entry — the fixed-shape discipline the
zero-recompile contract rides on.

Contract (matches the other benches): diagnostics go to stderr, stdout
carries ONE compact JSON record line re-printed last; the run exits
nonzero only when a gated number falls under HALF the checked-in floor
(bench_view_floor.json) — record-or-postmortem.

Env knobs: CSVPLUS_BENCH_VIEW_ROWS (source rows, default 1M),
_BATCH_ROWS (rows per write batch, default 1000), _BATCHES (timed
batches, default 8), _READS (read probes, default 2000), _OUT
(artifact path; no file by default so a gate run cannot overwrite the
checked-in record).  Seeds are fixed: same shape -> same stream.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

N_CUST = 5_000
N_PROD = 500


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _build_source(n: int):
    """A 1M-row (by default) append-mode orders MutableIndex, keyed by
    order id, with customer/product foreign keys striped round-robin."""
    import numpy as np

    import csvplus_tpu as cp
    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu.storage import MutableIndex

    oid = np.char.add("o", np.char.zfill(np.arange(n).astype(np.str_), 8))
    cust = np.char.add(
        "c", np.char.zfill((np.arange(n) % N_CUST).astype(np.str_), 5)
    )
    prod = np.char.add(
        "p", np.char.zfill((np.arange(n) % N_PROD).astype(np.str_), 4)
    )
    t = DeviceTable.from_pylists(
        {"oid": oid.tolist(), "cust_id": cust.tolist(),
         "prod_id": prod.tolist()},
        device="cpu",
    )
    base = cp.take(t).index_on("oid").sync()
    return MutableIndex(base, mode="append", ingest_device="cpu")


def _build_dims():
    from csvplus_tpu.index import create_index
    from csvplus_tpu.row import Row
    from csvplus_tpu.source import take_rows

    cust = create_index(
        take_rows([
            Row({"cust_id": f"c{i:05d}", "name": f"nm{i:05d}"})
            for i in range(N_CUST)
        ]),
        ["cust_id"],
    )
    cust.on_device("cpu")
    prod = create_index(
        take_rows([
            Row({"prod_id": f"p{i:04d}", "label": f"lb{i:04d}"})
            for i in range(N_PROD)
        ]),
        ["prod_id"],
    )
    prod.on_device("cpu")
    return cust, prod


def _batch(b: int, batch_rows: int):
    """Write batch *b*: fresh order keys, dimension keys drawn
    round-robin from a per-batch base — every batch has EXACTLY
    min(batch_rows, dim) unique values per column at fixed widths, so
    all warm batches share one probe-dictionary trace shape."""
    from csvplus_tpu.row import Row

    base = b * batch_rows
    return [
        Row({
            "oid": f"w{base + j:08d}",
            "cust_id": f"c{(base + j) % N_CUST:05d}",
            "prod_id": f"p{(base + j) % N_PROD:04d}",
        })
        for j in range(batch_rows)
    ]


def _assert_parity(view, label: str, t_recompute: list) -> None:
    """The hard contract, enforced in-bench after EVERY batch: the
    incrementally maintained contents checksum-match (positionally) a
    from-scratch execution of the registered plan."""
    from csvplus_tpu.utils.checksum import checksum_host_rows

    t0 = time.perf_counter()
    out = view.recompute()
    t_rec = time.perf_counter() - t0
    ref = checksum_host_rows(
        out.to_rows(), list(view.columns), positional=True
    )
    if view.checksums() != ref:
        raise AssertionError(
            f"bench[view] PARITY BREACH at {label}: incremental contents"
            f" do not checksum-match the from-scratch execution"
        )
    t_recompute.append(t_rec)
    sys.stderr.write(
        f"bench[view]: parity ok at {label}"
        f" (from-scratch {t_rec:.3f}s)\n"
    )


def _read_scenario(view, n_reads: int) -> dict:
    """Per-key ``view.read()`` latency against the pinned snapshot —
    the serving path a registered view answers on (no dispatcher)."""
    import numpy as np

    rng = np.random.default_rng(0)
    snap = view.snapshot()
    # probe keys that exist: sample source keys from the live segments
    pool = [seg.keys[i][0]
            for seg in snap.segments[:4]
            for i in range(0, len(seg.keys), max(1, len(seg.keys) // 64))]
    probes = [pool[int(v)] for v in rng.integers(0, len(pool), n_reads)]
    view.read(probes[0])  # warm the path
    lats = []
    t_all0 = time.perf_counter()
    for p in probes:
        t0 = time.perf_counter()
        view.read(p)
        lats.append(time.perf_counter() - t0)
    dt = time.perf_counter() - t_all0
    a = np.asarray(lats, dtype=np.float64)
    return {
        "n": n_reads,
        "seconds": round(dt, 4),
        "reads_per_sec": round(n_reads / dt, 1),
        "p50_ms": round(float(np.percentile(a, 50)) * 1e3, 4),
        "p99_ms": round(float(np.percentile(a, 99)) * 1e3, 4),
        "max_ms": round(float(a.max()) * 1e3, 4),
    }


def main() -> int:
    import jax

    from csvplus_tpu import plan as P
    from csvplus_tpu.obs.memory import host_header
    from csvplus_tpu.obs.recompile import RecompileWatch
    from csvplus_tpu.serve.plancache import PlanCache
    from csvplus_tpu.views import MaterializedView

    n = _env_int("CSVPLUS_BENCH_VIEW_ROWS", 1_000_000)
    batch_rows = _env_int("CSVPLUS_BENCH_VIEW_BATCH_ROWS", 1_000)
    n_batches = _env_int("CSVPLUS_BENCH_VIEW_BATCHES", 8)
    n_reads = _env_int("CSVPLUS_BENCH_VIEW_READS", 2_000)
    out_path = os.environ.get("CSVPLUS_BENCH_VIEW_OUT")
    host_cpus = os.cpu_count() or 1

    sys.stderr.write(
        f"bench[view]: building {n:,}-row orders source + dimensions"
        f" (backend={jax.default_backend()}, host_cpus={host_cpus})\n"
    )
    t0 = time.perf_counter()
    mi = _build_source(n)
    cust, prod = _build_dims()
    sys.stderr.write(
        f"bench[view]: source ready in {time.perf_counter() - t0:.1f}s\n"
    )

    pc = PlanCache()
    root = P.Join(
        P.Join(P.Scan(None), cust, ("cust_id",)), prod, ("prod_id",)
    )
    t0 = time.perf_counter()
    view = MaterializedView("orders_enriched", root, mi, plancache=pc)
    t_init = time.perf_counter() - t0
    sys.stderr.write(
        f"bench[view]: initial snapshot ({view.snapshot().nrows:,} rows)"
        f" in {t_init:.1f}s\n"
    )

    # warmup batch: pays the per-tier executable's cold lowering once,
    # off the clock (every later batch shares its trace shape)
    mi.append_rows(_batch(0, batch_rows))
    view.refresh()
    t_recompute: list = []
    _assert_parity(view, "warmup", t_recompute)

    # -- timed incremental maintenance -------------------------------------
    refresh_s: list = []
    append_s: list = []
    deletes = 0
    for b in range(1, n_batches + 1):
        rows = _batch(b, batch_rows)
        t0 = time.perf_counter()
        mi.append_rows(rows)
        append_s.append(time.perf_counter() - t0)
        if b % 3 == 0:
            # interleave a retraction event: delete one key from the
            # PREVIOUS batch (host bisects, no plan execution)
            mi.delete((f"w{(b - 1) * batch_rows:08d}",))
            deletes += 1
        with RecompileWatch(plancache=pc) as w:
            t0 = time.perf_counter()
            applied = view.refresh()
            refresh_s.append(time.perf_counter() - t0)
        # zero warm recompiles, checked per refresh BEFORE the parity
        # recompute below runs at its own (growing) table shape
        w.assert_zero(f"bench-view warm refresh batch {b}")
        if applied < 1:
            raise AssertionError(f"bench[view]: batch {b} applied nothing")
        _assert_parity(view, f"batch {b}", t_recompute)

    import numpy as np

    mean_refresh = float(np.mean(refresh_s))
    mean_recompute = float(np.mean(t_recompute[1:]))  # timed batches only
    speedup = mean_recompute / mean_refresh
    sys.stderr.write(
        f"bench[view]: refresh mean {mean_refresh * 1e3:.2f}ms/batch"
        f" vs from-scratch {mean_recompute:.3f}s"
        f" -> {speedup:,.0f}x incremental speedup\n"
    )

    reads = _read_scenario(view, n_reads)
    sys.stderr.write(
        f"bench[view]: reads p50 {reads['p50_ms']}ms"
        f" p99 {reads['p99_ms']}ms ({reads['reads_per_sec']:,.0f}/s)\n"
    )

    stats = view.stats()
    record = {
        "metric": "view_incremental_speedup_x",
        "value": round(speedup, 1),
        "unit": "x",
        "n_rows": n,
        "rows_per_batch": batch_rows,
        "n_batches": n_batches,
        "deletes": deletes,
        "backend": jax.default_backend(),
        **host_header(),
        "initial_snapshot_seconds": round(t_init, 3),
        "refresh_mean_ms": round(mean_refresh * 1e3, 3),
        "refresh_max_ms": round(max(refresh_s) * 1e3, 3),
        "append_mean_ms": round(float(np.mean(append_s)) * 1e3, 3),
        "recompute_mean_seconds": round(mean_recompute, 3),
        "read_p50_ms": reads["p50_ms"],
        "read_p99_ms": reads["p99_ms"],
        "reads_per_sec": reads["reads_per_sec"],
        "view_stats": stats,
        "plancache": pc.stats(),
        "scenarios": {"reads": reads},
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
        sys.stderr.write(f"bench[view]: artifact written to {out_path}\n")

    # -- floor gate (record-or-postmortem: fail only under HALF floor) -----
    floors = {}
    try:
        with open(os.path.join(REPO, "bench_view_floor.json")) as f:
            floors = json.load(f)
    except (OSError, ValueError):
        pass
    status = 0
    for key, got in (
        ("view_incremental_speedup_x", speedup),
        ("view_reads_per_sec", reads["reads_per_sec"]),
    ):
        floor = float(floors.get(key, 0.0) or 0.0)
        if floor and got < floor / 2:
            sys.stderr.write(
                f"bench[view] REGRESSION: {key} {got:,.1f} is under half"
                f" the floor ({floor:,.1f})\n"
            )
            status = 1
        else:
            sys.stderr.write(
                f"bench[view] ok: {key} {got:,.1f} (floor {floor:,.1f})\n"
            )
    compact = {
        k: record[k]
        for k in (
            "metric", "value", "unit", "n_rows", "rows_per_batch",
            "n_batches", "host_cpus", "refresh_mean_ms",
            "recompute_mean_seconds", "read_p50_ms", "read_p99_ms",
            "reads_per_sec",
        )
        if k in record
    }
    print(json.dumps(compact), flush=True)
    return status


if __name__ == "__main__":
    sys.exit(main())
