#!/usr/bin/env python
"""`make bench-serve`: latency/throughput bench for the r08 serving tier.

Drives :class:`csvplus_tpu.serve.LookupServer` over the same 1M-row
big-index micro shape as `make bench-micro`, so the coalesced numbers
are directly comparable to the batched `find_many` floor
(bench_micro_floor.json) and the looped single-`find` baseline.

Scenarios (each on a fresh server so metrics snapshots don't blend):

- sequential-single-find  the no-server baseline: one `find` per key
- coalesced-closed-loop   HEADLINE: 32 logical clients, each with one
  request in flight, resubmitting from its completion callback.  The
  dispatcher's previous batch is the coalescing window (adaptive tick),
  so the steady-state batch size == the number of clients.
- coalesced-threads       the same offered load from 32 real OS
  threads doing blocking submit().result() — kept for honesty: on a
  1-CPU host the GIL + wakeup latency dominate this shape.
- open-loop               fixed arrival rates from a precomputed
  schedule; per-request latency is measured from the SCHEDULED arrival
  (not the actual submit), so queue buildup is charged to the requests
  it delays — no coordinated omission.
- zipf                    closed-loop with Zipf(1.1)-skewed keys
  (bench.zipf_probe_values): the hot-key shape where the decoded-row
  LRU earns its keep.
- plancache               cold vs warm plan-IR queries through the
  verified-executable cache; asserts the warm pass re-lowers NOTHING
  (`lowered` counter flat, every warm query a structural hit).
- overload                a deliberately tiny admission bound under a
  held-open fixed tick; asserts load is SHED with ServerOverloaded and
  that every admitted request still completes.

Contract (matches the other benches): diagnostics go to stderr, stdout
carries ONE compact JSON record line re-printed last; the run exits
nonzero only when the headline rate falls under HALF the checked-in
floor (bench_serve_floor.json) — record-or-postmortem, so a miss of
the aspirational targets embeds evidence instead of failing the gate.

Env knobs: CSVPLUS_BENCH_SERVE_ROWS (default 1M), _LOOKUPS (default
60K per closed-loop scenario), _CLIENTS (default 32), _RATES (default
"20000,60000" req/s for the open-loop tier), _OUT (artifact path; no
file by default so a gate run cannot overwrite the checked-in record).
Seeds are fixed: same shape -> same probe sequence.
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


def _build_index(n: int):
    import numpy as np

    import csvplus_tpu as cp
    from csvplus_tpu.columnar.table import DeviceTable

    ids = np.arange(n, dtype=np.int64) * 7 % (n * 3)
    keys = np.char.add("c", ids.astype(np.str_))
    t = DeviceTable.from_pylists(
        {"cust_id": keys.tolist(), "v": np.arange(n).astype(np.str_).tolist()},
        device="cpu",
    )
    idx = cp.take(t).index_on("cust_id").sync()
    return idx, ids


def _uniform_probes(ids, n_probes: int):
    import numpy as np

    rng = np.random.default_rng(0)
    return [f"c{int(v)}" for v in rng.choice(ids, n_probes)]


def _sequential_single(idx, probes) -> dict:
    t0 = time.perf_counter()
    for p in probes:
        idx.find(p).to_rows()
    dt = time.perf_counter() - t0
    return {
        "n": len(probes),
        "seconds": round(dt, 4),
        "lookups_per_sec": round(len(probes) / dt, 1),
    }


def _closed_loop_callbacks(idx, probes, n_clients: int) -> dict:
    """The headline shape: n_clients logical clients, one request in
    flight each, the next request submitted from the completion
    callback — i.e. resubmission happens ON the dispatcher thread, so
    on a 1-CPU host no cross-thread wakeup sits on the critical path."""
    from csvplus_tpu.serve import LookupServer

    per = len(probes) // n_clients
    slices = [probes[i * per:(i + 1) * per] for i in range(n_clients)]
    total = per * n_clients
    done = threading.Event()
    remaining = [total]

    with LookupServer(idx) as srv:
        def make_cb(slot: int, pos: int):
            def cb(fut):
                if fut.error is not None:
                    remaining[0] = -1  # poison: surface below
                    done.set()
                    return
                remaining[0] -= 1
                if remaining[0] == 0:
                    done.set()
                    return
                nxt = pos + 1
                if nxt < len(slices[slot]):
                    srv.submit(slices[slot][nxt], callback=make_cb(slot, nxt))
            return cb

        t0 = time.perf_counter()
        for c in range(n_clients):
            srv.submit(slices[c][0], callback=make_cb(c, 0))
        done.wait()
        dt = time.perf_counter() - t0
        snap = srv.snapshot()
    if remaining[0] < 0:
        raise RuntimeError("closed-loop client saw a request error")
    return {
        "clients": n_clients,
        "n": total,
        "seconds": round(dt, 4),
        "lookups_per_sec": round(total / dt, 1),
        "metrics": snap,
    }


def _closed_loop_threads(idx, probes, n_threads: int) -> dict:
    from csvplus_tpu.serve import LookupServer

    per = len(probes) // n_threads
    total = per * n_threads
    errs = []

    with LookupServer(idx) as srv:
        def worker(slot: int):
            try:
                for p in probes[slot * per:(slot + 1) * per]:
                    srv.submit(p).result()
            except BaseException as e:  # surfaced after join
                errs.append(e)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(n_threads)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        snap = srv.snapshot()
    if errs:
        raise errs[0]
    return {
        "threads": n_threads,
        "n": total,
        "seconds": round(dt, 4),
        "lookups_per_sec": round(total / dt, 1),
        "metrics": snap,
    }


def _open_loop(idx, probes, rate_rps: int) -> dict:
    """Fixed-rate arrivals from a precomputed schedule.  Latency is
    measured from the scheduled arrival time, so when the server falls
    behind, the delay lands on the requests that suffered it instead of
    silently stretching the inter-arrival gaps (coordinated omission)."""
    import numpy as np

    from csvplus_tpu.serve import LookupServer

    n = len(probes)
    offsets = [i / rate_rps for i in range(n)]
    lats = []  # appended from the dispatcher thread; list.append is atomic
    done = threading.Event()

    with LookupServer(idx) as srv:
        def make_cb(sched_t: float):
            def cb(fut):
                if fut.error is None:
                    lats.append(time.perf_counter() - sched_t)
                if len(lats) >= n:
                    done.set()
            return cb

        shed = 0
        t0 = time.perf_counter()
        for i, p in enumerate(probes):
            sched = t0 + offsets[i]
            now = time.perf_counter()
            if sched > now:
                time.sleep(sched - now)
            try:
                srv.submit(p, callback=make_cb(sched))
            except Exception:
                shed += 1
                lats.append(float("nan"))  # keep the completion count honest
        done.wait(timeout=120.0)
        dt = time.perf_counter() - t0
        snap = srv.snapshot()
    good = np.asarray([v for v in lats if v == v], dtype=np.float64)
    out = {
        "offered_rps": rate_rps,
        "n": n,
        "completed": int(good.size),
        "shed": shed,
        "achieved_rps": round(good.size / dt, 1),
        "metrics": snap,
    }
    if good.size:
        out["p50_ms"] = round(float(np.percentile(good, 50)) * 1e3, 3)
        out["p99_ms"] = round(float(np.percentile(good, 99)) * 1e3, 3)
        out["max_ms"] = round(float(good.max()) * 1e3, 3)
    return out


def _plancache_scenario(idx, probes) -> dict:
    """Plan-IR queries through the verified-executable cache: every
    probe's Lookup plan shares one structural shape, so the cold pass
    verifies+lowers exactly once and the warm pass recompiles nothing."""
    from csvplus_tpu.serve import LookupServer

    plans = [idx.find(p).plan for p in probes]
    if any(pl is None for pl in plans):
        return {"skipped": "index carries no device plans"}

    with LookupServer(idx) as srv:
        t0 = time.perf_counter()
        futs = [srv.submit_plan(pl) for pl in plans[: len(plans) // 2]]
        for f in futs:
            f.result()
        cold_dt = time.perf_counter() - t0
        cold = dict(srv.plancache.stats())

        t0 = time.perf_counter()
        futs = [srv.submit_plan(pl) for pl in plans[len(plans) // 2:]]
        for f in futs:
            f.result()
        warm_dt = time.perf_counter() - t0
        warm = dict(srv.plancache.stats())

    n_cold = len(plans) // 2
    n_warm = len(plans) - n_cold
    recompiles_warm = warm["lowered"] - cold["lowered"]
    assert recompiles_warm == 0, (
        f"warm plan-cache pass recompiled {recompiles_warm} shapes"
    )
    assert warm["hits"] - cold["hits"] == n_warm, "warm pass was not all hits"
    return {
        "n_cold": n_cold,
        "n_warm": n_warm,
        "cold_qps": round(n_cold / cold_dt, 1),
        "warm_qps": round(n_warm / warm_dt, 1),
        "lowered_cold": cold["lowered"],
        "recompiles_warm": recompiles_warm,
        "stats": warm,
    }


def _overload_scenario(idx, probes) -> dict:
    """A 40ms held-open tick with a 256-deep admission bound: blasting
    submits during the hold MUST shed with ServerOverloaded, and every
    request that was admitted must still complete."""
    from csvplus_tpu.serve import LookupServer, ServerOverloaded

    shed = 0
    futs = []
    with LookupServer(
        idx, max_pending=256, tick_us=40_000, max_batch=1 << 20
    ) as srv:
        for p in probes:
            try:
                futs.append(srv.submit(p))
            except ServerOverloaded:
                shed += 1
        for f in futs:
            f.result(timeout=60.0)
        snap = srv.snapshot()
    assert shed > 0, "overload scenario failed to shed any load"
    assert snap["shed"] == shed, "metrics shed counter != raised ServerOverloaded"
    return {
        "offered": len(probes),
        "admitted": len(futs),
        "shed": shed,
        "queue_bound": 256,
        "metrics": snap,
    }


def main() -> int:
    import jax

    from bench import zipf_probe_values
    from csvplus_tpu.obs.memory import host_header

    n = _env_int("CSVPLUS_BENCH_SERVE_ROWS", 1_000_000)
    n_lookups = _env_int("CSVPLUS_BENCH_SERVE_LOOKUPS", 60_000)
    n_clients = _env_int("CSVPLUS_BENCH_SERVE_CLIENTS", 32)
    rates = [
        int(r)
        for r in os.environ.get(
            "CSVPLUS_BENCH_SERVE_RATES", "20000,60000"
        ).split(",")
        if r.strip()
    ]
    out_path = os.environ.get("CSVPLUS_BENCH_SERVE_OUT")
    host_cpus = os.cpu_count() or 1

    sys.stderr.write(
        f"bench[serve]: building {n:,}-row index"
        f" (backend={jax.default_backend()}, host_cpus={host_cpus})\n"
    )
    t0 = time.perf_counter()
    idx, ids = _build_index(n)
    sys.stderr.write(
        f"bench[serve]: index ready in {time.perf_counter() - t0:.1f}s\n"
    )
    probes = _uniform_probes(ids, n_lookups)
    # warm the dispatch path + decoded-row mirror once, off the clock
    import csvplus_tpu as cp

    cp.to_rows_many(idx.find_many(probes[:64]))

    scenarios: dict = {}

    scenarios["sequential_single_find"] = _sequential_single(
        idx, probes[: min(3000, n_lookups)]
    )
    single_rate = scenarios["sequential_single_find"]["lookups_per_sec"]
    sys.stderr.write(
        f"bench[serve]: sequential single-find {single_rate:,.0f}/s\n"
    )

    # headline: best of 2 passes (scheduler noise on a 1-CPU host)
    best = None
    for _rep in range(2):
        run = _closed_loop_callbacks(idx, probes, n_clients)
        if best is None or run["lookups_per_sec"] > best["lookups_per_sec"]:
            best = run
    scenarios["coalesced_closed_loop"] = best
    headline = best["lookups_per_sec"]
    sys.stderr.write(
        f"bench[serve]: coalesced closed-loop {headline:,.0f}/s"
        f" (mean batch"
        f" {best['metrics']['batch']['mean']})\n"
    )

    scenarios["coalesced_threads"] = _closed_loop_threads(
        idx, probes[: min(8000, n_lookups)], n_clients
    )
    sys.stderr.write(
        "bench[serve]: 32 OS-thread closed-loop"
        f" {scenarios['coalesced_threads']['lookups_per_sec']:,.0f}/s\n"
    )

    scenarios["open_loop"] = [
        _open_loop(idx, probes[: min(rate, n_lookups)], rate) for rate in rates
    ]
    for ol in scenarios["open_loop"]:
        sys.stderr.write(
            f"bench[serve]: open-loop offered {ol['offered_rps']:,}/s ->"
            f" achieved {ol['achieved_rps']:,.0f}/s"
            f" p50 {ol.get('p50_ms')}ms p99 {ol.get('p99_ms')}ms\n"
        )

    zipf_probes = [f"c{int(v)}" for v in zipf_probe_values(ids, n_lookups)]
    scenarios["zipf"] = _closed_loop_callbacks(idx, zipf_probes, n_clients)
    sys.stderr.write(
        "bench[serve]: zipf closed-loop"
        f" {scenarios['zipf']['lookups_per_sec']:,.0f}/s\n"
    )

    scenarios["plancache"] = _plancache_scenario(idx, probes[:2000])
    if "skipped" not in scenarios["plancache"]:
        sys.stderr.write(
            "bench[serve]: plancache cold"
            f" {scenarios['plancache']['cold_qps']:,.0f} q/s -> warm"
            f" {scenarios['plancache']['warm_qps']:,.0f} q/s"
            f" (recompiles_warm={scenarios['plancache']['recompiles_warm']})\n"
        )

    scenarios["overload"] = _overload_scenario(idx, probes[:4000])
    sys.stderr.write(
        f"bench[serve]: overload shed {scenarios['overload']['shed']}"
        f" of {scenarios['overload']['offered']} offered\n"
    )

    # -- targets (record-or-postmortem, not gate) --------------------------
    batched_floor = 0.0
    try:
        with open(os.path.join(REPO, "bench_micro_floor.json")) as f:
            batched_floor = float(
                json.load(f).get("big_index_lookups_per_sec_batched", 0.0)
            )
    except (OSError, ValueError):
        pass
    targets = {
        "batched_find_many_floor": batched_floor,
        "coalesced_vs_batched_floor_min": 0.5,
        "coalesced_vs_single_find_min": 5.0,
        "met_half_batched_floor": bool(
            batched_floor and headline >= 0.5 * batched_floor
        ),
        "met_5x_single_find": bool(headline >= 5.0 * single_rate),
    }
    record = {
        "metric": "serve_coalesced_lookups_per_sec",
        "value": headline,
        "unit": "lookups/s",
        "n_rows": n,
        "n_lookups": n_lookups,
        "clients": n_clients,
        "backend": jax.default_backend(),
        **host_header(),
        "single_find_lookups_per_sec": single_rate,
        "coalesced_speedup_vs_single": round(headline / single_rate, 2),
        "targets": targets,
        "scenarios": scenarios,
    }
    if not (targets["met_half_batched_floor"] and targets["met_5x_single_find"]):
        record["postmortem"] = {
            "note": (
                "this host exposes a single CPU, so the dispatcher, the"
                " clients, and the JAX runtime share one core under the"
                " GIL; the coalesced rate is bounded by per-batch"
                " dispatch overhead at batch≈clients rather than the"
                " vectorized engine's 10K-batch amortization the floor"
                " was recorded at"
                if host_cpus < 2
                else "targets missed on a multi-core host — compare the"
                " batch-size histogram against the find_many floor's"
                " 10K-probe shape"
            ),
            "host_cpus": host_cpus,
            "mean_batch": best["metrics"]["batch"]["mean"],
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
        sys.stderr.write(f"bench[serve]: artifact written to {out_path}\n")

    floor = 0.0
    try:
        with open(os.path.join(REPO, "bench_serve_floor.json")) as f:
            floor = float(
                json.load(f).get("serve_coalesced_lookups_per_sec", 0.0)
            )
    except (OSError, ValueError):
        pass
    status = 0
    if floor and headline < floor / 2:
        sys.stderr.write(
            f"bench[serve] REGRESSION: coalesced {headline:,.0f} lookups/s"
            f" is under half the floor ({floor:,.0f})\n"
        )
        status = 1
    else:
        sys.stderr.write(
            f"bench[serve] ok: coalesced {headline:,.0f} lookups/s"
            f" (floor {floor:,.0f}) | single {single_rate:,.0f}/s\n"
        )
    # compact record re-printed LAST on stdout (the machine-readable line)
    compact = {
        k: record[k]
        for k in (
            "metric", "value", "unit", "n_rows", "n_lookups", "clients",
            "host_cpus", "single_find_lookups_per_sec",
            "coalesced_speedup_vs_single", "targets",
        )
    }
    print(json.dumps(compact), flush=True)
    return status


if __name__ == "__main__":
    sys.exit(main())
