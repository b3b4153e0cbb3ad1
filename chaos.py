#!/usr/bin/env python
"""`make chaos`: a CPU correctness gate (8 simulated CPU devices, set
before JAX is touched) — the seeded fault-injection differential gate
(ISSUE 8, r09).

Runs seeded fault schedules against the four workload shapes —
serve load, K-worker streamed ingest, the 8-way mesh join, and the
mutable-index compactor — and holds the recovery ladder to the
differential contract:

* when recovery is possible (transient device faults within the retry
  budget, breaker fallback, crashed ingest workers) the results must be
  BITWISE-EQUAL to the fault-free oracle, with zero warm recompiles on
  the retry path (``RecompileWatch.assert_zero``);
* when it is not (fatal faults, dispatcher death, I/O errors) the
  failure must surface as its TYPED error — ``ServerCrashed`` for every
  pending future within 1s of a dispatcher crash, row-numbered
  ``DataSourceError`` for source I/O — never a hang or a silent wrong
  answer.  Every case runs under a watchdog timeout, so a hang IS a
  failure, not a stuck CI job.
* the disarmed injection hooks must cost <= 1% of a served request
  (measured here, recorded in the artifact).

The ISSUE 12 window extends the matrix to the materialized-view tier:
a crash at ``views:refresh`` inside a serving write cycle must leave
the PRIOR epoch-pinned snapshot live (same epoch, same checksums),
every unapplied tier event queued, and the dispatcher alive — and the
disarmed retry must converge the view back to bitwise parity with a
from-scratch execution of its registered plan.

The ISSUE 13 extension asserts the crash FLIGHT RECORDER on the two
terminal windows: a dispatcher crash and a ``views:refresh`` crash
must each leave an atomically-written flight dump that parses and
names the firing fault site in its event timeline.

Contract: diagnostics go to stderr, stdout carries ONE compact JSON
line; a temporary file, named on stderr, records the full
evidence — per-case injection counts (``FaultPlan.snapshot``), recovery
outcomes, serve retry/degrade metrics, telemetry counters
(``ingest.worker_recovered``), flight-dump evidence, and the overhead
measurement.  Exits nonzero when any case fails its contract.
"""

from __future__ import annotations

import contextlib
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# 8 simulated CPU devices, same recipe as tests/conftest.py
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

#: Watchdog bound per chaos case: a case that cannot finish inside this
#: is a hang, which is exactly what the resilience layer must prevent.
CASE_TIMEOUT_S = float(os.environ.get("CSVPLUS_CHAOS_CASE_TIMEOUT", 120))
#: Disarmed-hook budget: injection sites on the serve path may cost at
#: most this fraction of one served request.
OVERHEAD_BUDGET_PCT = 1.0


def _with_timeout(name: str, fn):
    """Run one chaos case under the watchdog.  Returns the case record;
    a timeout or an escape is a recorded failure, never a hang of the
    gate itself."""
    box: dict = {}

    def run():
        try:
            box["result"] = fn()
        except BaseException as e:  # recorded + failed, gate must finish
            box["error"] = f"{type(e).__name__}: {e}"

    t0 = time.perf_counter()
    th = threading.Thread(target=run, name=f"chaos-{name}", daemon=True)
    th.start()
    th.join(CASE_TIMEOUT_S)
    elapsed = time.perf_counter() - t0
    if th.is_alive():
        rec = {"ok": False, "error": f"timeout after {CASE_TIMEOUT_S}s (hang)"}
    elif "error" in box:
        rec = {"ok": False, "error": box["error"]}
    else:
        rec = dict(box["result"])
        rec.setdefault("ok", True)
    rec["seconds"] = round(elapsed, 3)
    status = "ok" if rec["ok"] else f"FAIL ({rec.get('error', 'contract')})"
    sys.stderr.write(f"chaos[{name}]: {status} in {elapsed:.2f}s\n")
    return rec


def _build_index(n=20_000):
    import numpy as np

    import csvplus_tpu as cp
    from csvplus_tpu.columnar.table import DeviceTable

    ids = np.arange(n, dtype=np.int64) * 7 % (n * 3)
    t = DeviceTable.from_pylists(
        {
            "id": np.char.add("c", ids.astype(np.str_)).tolist(),
            "v": np.arange(n).astype(np.str_).tolist(),
        },
        device="cpu",
    )
    return cp.take(t).index_on("id").sync(), ids


def _probes(ids, n, seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)
    ps = [f"c{int(v)}" for v in rng.choice(ids, n)]
    ps[::17] = ["nope"] * len(ps[::17])
    return ps


# ---- serve load under faults ---------------------------------------------


def case_serve_retry(idx, ids):
    """Transient device faults on the coalesced lookup: absorbed by
    retries, bitwise-equal results, zero warm recompiles."""
    from csvplus_tpu.obs.recompile import RecompileWatch
    from csvplus_tpu.resilience import faults
    from csvplus_tpu.resilience.faults import FaultPlan
    from csvplus_tpu.resilience.retry import RetryPolicy
    from csvplus_tpu.serve import LookupServer

    probes = _probes(ids, 600, seed=1)
    serial = [idx.find(p).to_rows() for p in probes]
    with LookupServer(idx) as srv:
        srv.retry_policy = RetryPolicy(max_attempts=3, base_s=1e-4, cap_s=1e-3)
        for f in [srv.submit(p) for p in probes[:50]]:  # warm off-watch
            f.result(timeout=30.0)
        with RecompileWatch() as w:
            with faults.active(
                FaultPlan(
                    [{"site": "serve:bounds", "at": [0, 2, 5], "error": "device"}],
                    seed=9,
                )
            ) as plan:
                futs = [srv.submit(p) for p in probes]
                got = [f.result(timeout=30.0) for f in futs]
        w.assert_zero("chaos serve retries")
        snap = srv.snapshot()
    return {
        "ok": got == serial and snap["retried"] >= 1 and snap["failed"] == 0,
        "bitwise_equal": got == serial,
        "injections": plan.snapshot(),
        "metrics": {k: snap[k] for k in ("retried", "degraded", "failed")},
    }


def case_serve_degrade(idx, ids):
    """Retries exhaust under a 100% device-fault schedule: the breaker
    trips onto the host oracle (bitwise parity), then half-open probing
    recovers the device path once faults stop."""
    from csvplus_tpu.resilience import faults
    from csvplus_tpu.resilience.degrade import CircuitBreaker
    from csvplus_tpu.resilience.faults import FaultPlan
    from csvplus_tpu.resilience.retry import RetryPolicy
    from csvplus_tpu.serve import LookupServer

    probes = _probes(ids, 300, seed=2)
    serial = [idx.find(p).to_rows() for p in probes]
    with LookupServer(idx) as srv:
        srv.retry_policy = RetryPolicy(max_attempts=2, base_s=1e-4, cap_s=1e-3)
        srv.breaker = CircuitBreaker(threshold=2, cooldown_s=0.05)
        with faults.active(
            FaultPlan([{"site": "serve:bounds", "every": 1, "error": "device"}])
        ) as plan:
            futs = [srv.submit(p) for p in probes]
            got = [f.result(timeout=30.0) for f in futs]
        snap = srv.snapshot()
        opened = srv.breaker.state == "open"
        time.sleep(0.06)  # cooldown: next route is the half-open probe
        again = [srv.submit(p) for p in probes[:20]]
        recovered = [f.result(timeout=30.0) for f in again] == serial[:20]
        closed = srv.breaker.state == "closed"
    return {
        "ok": got == serial
        and snap["failed"] == 0
        and snap["degraded"] >= len(probes)
        and opened
        and recovered
        and closed,
        "bitwise_equal_degraded": got == serial,
        "breaker_opened": opened,
        "breaker_recovered": closed,
        "injections": plan.snapshot(),
        "metrics": {k: snap[k] for k in ("retried", "degraded", "failed")},
    }


@contextlib.contextmanager
def _flight_dir():
    """Point the crash flight recorder at a fresh scratch dir for one
    case, restoring the prior CSVPLUS_FLIGHT_DIR on exit."""
    d = tempfile.mkdtemp(prefix="chaos_flight_")
    prev = os.environ.get("CSVPLUS_FLIGHT_DIR")
    os.environ["CSVPLUS_FLIGHT_DIR"] = d
    try:
        yield d
    finally:
        if prev is None:
            os.environ.pop("CSVPLUS_FLIGHT_DIR", None)
        else:
            os.environ["CSVPLUS_FLIGHT_DIR"] = prev


def _flight_evidence(flight_dir, site, timeout_s=10.0):
    """Parse every flight dump a crash window left in *flight_dir* and
    report whether one names *site* as a fired fault in its timeline —
    the ISSUE 13 post-mortem contract.  Waits out the crash thread's
    in-flight write: futures unblock before the dump finishes."""
    deadline = time.perf_counter() + timeout_s
    names: list = []
    while not names and time.perf_counter() < deadline:
        names = sorted(
            f for f in os.listdir(flight_dir)
            if f.startswith("csvplus_flight.") and f.endswith(".json")
        )
        if not names:
            time.sleep(0.01)
    parsed = 0
    named = False
    reasons = []
    for name in names:
        try:
            with open(os.path.join(flight_dir, name)) as f:
                payload = json.load(f)
        except (OSError, ValueError) as err:
            reasons.append(f"unparseable: {type(err).__name__}")
            continue
        parsed += 1
        reasons.append(payload.get("reason"))
        for ev in payload.get("events", ()):
            if ev.get("kind") == "fault:fired" and ev.get("site") == site:
                named = True
    return {
        "ok": bool(names) and parsed == len(names) and named,
        "dumps": len(names),
        "parsed": parsed,
        "reasons": reasons,
        "names_fault_site": named,
    }


def case_dispatcher_crash(idx, ids):
    """A fatal fault in the dispatcher: every pending future fails with
    typed ServerCrashed in under a second; post-mortem submits fail
    fast at admission; the flight recorder leaves a parseable dump that
    names the firing fault site."""
    from csvplus_tpu.resilience import faults
    from csvplus_tpu.resilience.faults import FaultPlan
    from csvplus_tpu.resilience.retry import ServerCrashed
    from csvplus_tpu.serve import LookupServer

    with _flight_dir() as flight_dir:
        srv = LookupServer(idx, tick_us=20_000)  # hold the doomed batch open
        srv.start()
        try:
            with faults.active(
                FaultPlan(
                    [{"site": "serve:dispatch", "at": [0], "error": "fatal"}]
                )
            ) as plan:
                futs = []
                for v in ids[:16]:
                    try:
                        futs.append(srv.submit(f"c{int(v)}"))
                    except ServerCrashed:
                        break
                t0 = time.perf_counter()
                typed = 0
                for f in futs:
                    try:
                        f.result(timeout=1.0)
                    except ServerCrashed:
                        typed += 1
                    except BaseException:
                        pass
                unblock_s = time.perf_counter() - t0
            try:
                srv.submit(f"c{int(ids[0])}")
                post_typed = False
            except ServerCrashed:
                post_typed = True
            flight = _flight_evidence(flight_dir, "serve:dispatch")
            return {
                "ok": bool(futs)
                and typed == len(futs)
                and unblock_s < 1.0
                and post_typed
                and flight["ok"],
                "pending_futures": len(futs),
                "typed_failures": typed,
                "unblock_seconds": round(unblock_s, 4),
                "post_crash_submit_typed": post_typed,
                "flight": flight,
                "injections": plan.snapshot(),
            }
        finally:
            srv.stop()


# ---- K-worker streamed ingest under faults -------------------------------


def _chaos_csv(root, rows=2000):
    path = os.path.join(root, "chaos_ingest.csv")
    with open(path, "w") as f:
        f.write("k,v\n")
        for i in range(rows):
            f.write(f"k{i},v{i * 3}\n")
    return path


def _stream_fold(path, workers, chunk_bytes=512):
    import numpy as np

    from csvplus_tpu import DataSourceError, from_file
    from csvplus_tpu.native import scanner as native

    out = []
    try:
        for names, encoded, n in native.stream_encoded_chunks(
            from_file(path), path, chunk_bytes=chunk_bytes, workers=workers
        ):
            chunk = {}
            for c, enc in encoded.items():
                if len(enc) == 3 and enc[0] == "int":
                    chunk[c] = ("typed", enc[1], enc[2].tolist())
                else:
                    chunk[c] = (
                        "dict",
                        [bytes(x) for x in enc[0].tolist()],
                        np.asarray(enc[1]).tolist(),
                    )
            out.append((tuple(names), chunk, n))
    except DataSourceError as e:
        return ("exc", type(e).__name__, str(e), out)
    return ("ok", out)


def case_ingest_crash_recovery(tmp_root):
    """Crashed scan+encode workers re-execute their chunks: the emitted
    stream is bitwise-identical to the fault-free run for every K."""
    from csvplus_tpu.resilience import faults
    from csvplus_tpu.resilience.faults import FaultPlan

    path = _chaos_csv(tmp_root)
    oracle = _stream_fold(path, workers=1)
    per_k = {}
    ok = oracle[0] == "ok" and len(oracle[1]) > 4
    for k in (1, 2, 4):
        with faults.active(
            FaultPlan(
                [{"site": "ingest:worker", "at": [1, 3, 4, 9], "error": "crash"}],
                seed=5,
            )
        ) as plan:
            got = _stream_fold(path, workers=k)
        snap = plan.snapshot()
        per_k[str(k)] = {
            "bitwise_equal": got == oracle,
            "injections": snap,
        }
        ok = ok and got == oracle and snap["fired"].get("ingest:worker", 0) >= 1
    return {"ok": ok, "chunks": len(oracle[1]), "per_workers": per_k}


def case_ingest_read_fault_typed(tmp_root):
    """Unrecoverable read I/O faults surface as row-numbered
    DataSourceError with a K-independent outcome (emitted prefix +
    message), never a partial silent stream."""
    from csvplus_tpu.resilience import faults
    from csvplus_tpu.resilience.faults import FaultPlan

    path = _chaos_csv(tmp_root)
    outcomes = {}
    for k in (1, 2):
        with faults.active(
            FaultPlan([{"site": "ingest:read", "at": [2], "error": "io"}])
        ) as plan:
            outcomes[k] = _stream_fold(path, workers=k)
        snap = plan.snapshot()
    typed = outcomes[1][0] == "exc" and outcomes[1][1] == "DataSourceError"
    return {
        "ok": typed and outcomes[1] == outcomes[2],
        "typed": typed,
        "k_independent": outcomes[1] == outcomes[2],
        "error": outcomes[1][2] if typed else None,
        "injections": snap,
    }


# ---- mesh join under faults ----------------------------------------------


def case_mesh_join_under_ingest_faults(tmp_root):
    """The 8-way sharded mesh join with crashing ingest workers under
    its streamed build: recovered ingest keeps the join bitwise-equal
    to the fault-free run."""
    from csvplus_tpu import Take, from_file
    from csvplus_tpu.resilience import faults
    from csvplus_tpu.resilience.faults import FaultPlan

    cust_path = os.path.join(tmp_root, "cust.csv")
    with open(cust_path, "w") as f:
        f.write("id,name\n")
        for i in range(120):
            f.write(f"u{i},name{i % 12}\n")
    orders_path = os.path.join(tmp_root, "orders.csv")
    with open(orders_path, "w") as f:
        f.write("oid,cust_id,amount\n")
        for i in range(4000):
            f.write(f"o{i},u{(i * 13) % 120},{i % 97}\n")

    def run_join():
        cust = Take(from_file(cust_path)).unique_index_on("id")
        cust.on_device("cpu")
        stream = from_file(orders_path).on_device(shards=8)
        return stream.join(cust, "cust_id").to_rows()

    # shrink the stream chunk so the ~60KB orders file really flows
    # through the staged multi-chunk ingest (default chunks are 64MB —
    # the whole file would be one establishment chunk with no worker
    # executions to crash)
    prev_env = {
        k: os.environ.get(k)
        for k in ("CSVPLUS_STREAM_CHUNK_BYTES", "CSVPLUS_STREAM_MIN_BYTES")
    }
    os.environ["CSVPLUS_STREAM_CHUNK_BYTES"] = "4096"
    os.environ["CSVPLUS_STREAM_MIN_BYTES"] = "1"  # tier gate: stream always
    try:
        oracle = run_join()
        with faults.active(
            FaultPlan(
                [{"site": "ingest:worker", "at": [1, 2], "error": "crash"}],
                seed=11,
            )
        ) as plan:
            got = run_join()
    finally:
        for k, v in prev_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    snap = plan.snapshot()
    return {
        "ok": got == oracle
        and len(oracle) == 4000
        and snap["fired"].get("ingest:worker", 0) >= 1,
        "bitwise_equal": got == oracle,
        "rows": len(oracle),
        "injections": snap,
    }


# ---- storage: compactor crash safety (ISSUE 9) ---------------------------


def case_storage_compact_crash():
    """A compactor crash — at entry or in the pre-swap window — must
    leave the pre-compaction tier set intact (same epoch, same deltas,
    same answers) and a retry must compact to full rebuild parity."""
    from csvplus_tpu.resilience import faults
    from csvplus_tpu.resilience.faults import FaultPlan, InjectedFatalError
    from csvplus_tpu.row import Row
    from csvplus_tpu.source import take_rows
    from csvplus_tpu.storage import (
        MutableIndex,
        index_checksums,
        rebuild_reference,
    )

    mi = MutableIndex.create(
        take_rows([Row({"k": f"k{i % 41:03d}", "v": f"v{i}"}) for i in range(800)]),
        ["k"],
        ingest_device="cpu",
    )
    mi.append_rows([{"k": f"n{j}", "v": "x"} for j in range(30)])
    mi.append_rows([{"k": f"m{j}", "v": "y"} for j in range(20)])
    probes = [(f"k{i:03d}",) for i in range(0, 41, 3)] + [("n5",), ("zz",)]
    before = [
        [dict(r) for r in b] for b in mi.find_rows_many(probes)
    ]
    epoch0, deltas0 = mi.epoch, mi.delta_count
    injections = {}
    intact = True
    for hit, label in ((0, "at_entry"), (1, "pre_swap")):
        with faults.active(
            FaultPlan(
                [{"site": "storage:compact", "at": [hit], "error": "fatal"}],
                seed=13,
            )
        ) as plan:
            try:
                mi.compact_once()
                crashed = False
            except InjectedFatalError:
                crashed = True
            injections[label] = plan.snapshot()
        after = [
            [dict(r) for r in b] for b in mi.find_rows_many(probes)
        ]
        intact = (
            intact
            and crashed
            and mi.epoch == epoch0
            and mi.delta_count == deltas0
            and after == before
        )
    # disarmed retry compacts clean, bitwise-equal to the rebuild
    stats = mi.compact_once()
    parity = index_checksums(mi.tiers().base) == index_checksums(
        rebuild_reference(mi)
    )
    answers = [
        [dict(r) for r in b] for b in mi.find_rows_many(probes)
    ] == before
    return {
        "ok": intact and stats is not None and parity and answers,
        "tier_set_intact_after_crashes": intact,
        "retry_compacted_deltas": None if stats is None else stats["deltas"],
        "rebuild_parity": parity,
        "injections": injections,
    }


def case_wal_crash_matrix(tmp_root):
    """The ISSUE 10 crash-restart matrix: a subprocess child plays a
    fixed append/delete/compact op list over a durable MutableIndex
    under ``CSVPLUS_WAL_SYNC=always`` and is killed (injected fatal) at
    every fsync boundary of the write path — mid WAL append, mid
    segment seal, post-merge/pre-manifest-rename, post-rename/pre-WAL-
    truncate — plus a torn-tail partial frame.  Each window must
    recover checksums bitwise-equal to a fresh in-memory replay of
    exactly the acked ops (no acked-then-lost record), with zero warm
    recompiles on the recovered index."""
    import importlib.util

    from csvplus_tpu.obs.recompile import RecompileWatch
    from csvplus_tpu.storage import MutableIndex, index_checksums

    child_path = os.path.join(REPO, "tests", "wal_crash_child.py")
    spec = importlib.util.spec_from_file_location(
        "wal_crash_child", child_path
    )
    child = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(child)

    windows = {}
    for name, (fault, n_acked, n_replay) in sorted(
        child.CRASH_WINDOWS.items()
    ):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        env["CSVPLUS_WAL_SYNC"] = "always"
        env.pop("CSVPLUS_FAULTS", None)
        env.pop("CSVPLUS_WAL_CHILD_TEAR", None)
        if fault is not None:
            env["CSVPLUS_FAULTS"] = json.dumps({"faults": [fault]})
        if name == "torn_tail":
            env["CSVPLUS_WAL_CHILD_TEAR"] = "1"
        workdir = os.path.join(tmp_root, f"wal-{name}", "idx")
        acked_path = os.path.join(tmp_root, f"wal-{name}", "acked.json")
        os.makedirs(os.path.dirname(workdir), exist_ok=True)
        proc = subprocess.run(
            [sys.executable, child_path, workdir, acked_path],
            env=env, capture_output=True, text=True,
            timeout=CASE_TIMEOUT_S,
        )
        rec: dict = {"exit": proc.returncode}
        try:
            with open(acked_path) as f:
                acked = json.load(f)
            mi = MutableIndex.open(workdir)
            ref = child.replay_reference(acked["ops"])
            probes = [("k003",), ("a05",), ("b02",), ("zz",)]
            mi.find_rows_many(probes)  # warm-up
            with RecompileWatch() as w:
                got = mi.find_rows_many(probes)
            rec.update(
                crashed=acked["crashed"] is not None,
                acked=len(acked["ops"]),
                recovered_records=mi.recovered_records,
                truncated_bytes=mi.recovery_info["truncated_bytes"],
                parity=index_checksums(mi.to_index())
                == index_checksums(ref.to_index()),
                answers=[[dict(r) for r in b] for b in got]
                == [[dict(r) for r in b] for b in ref.find_rows_many(probes)],
                warm_recompiles=sum(w.delta().values()),
            )
            rec["ok"] = bool(
                proc.returncode == (3 if fault is not None else 0)
                and rec["crashed"] == (fault is not None)
                and rec["acked"] == n_acked
                and rec["recovered_records"] == n_replay
                and rec["parity"]
                and rec["answers"]
                and rec["warm_recompiles"] == 0
            )
        except Exception as exc:  # a window that cannot recover at all
            rec["ok"] = False
            rec["error"] = f"{type(exc).__name__}: {exc}"
            rec["stderr_tail"] = proc.stderr[-500:]
        windows[name] = rec
    return {
        "ok": all(v["ok"] for v in windows.values()),
        "windows_total": len(windows),
        "windows_failed": sorted(
            k for k, v in windows.items() if not v["ok"]
        ),
        "windows": windows,
    }


# ---- materialized views: refresh crash window (ISSUE 12) -----------------


def case_view_refresh_crash():
    """A fatal fault at the top of the view-refresh pass inside a
    serving write cycle: the prior epoch-pinned snapshot stays live,
    the events stay queued, the dispatcher survives — and the disarmed
    retry converges back to from-scratch parity.  The crash window
    leaves a flight dump naming the views:refresh fault site."""
    from csvplus_tpu import plan as P
    from csvplus_tpu.index import create_index
    from csvplus_tpu.resilience import faults
    from csvplus_tpu.resilience.faults import FaultPlan
    from csvplus_tpu.row import Row
    from csvplus_tpu.serve import LookupServer
    from csvplus_tpu.source import take_rows
    from csvplus_tpu.storage import MutableIndex

    n_cust, n_prod = 40, 12

    def order(i):
        return Row({
            "oid": f"o{i:05d}",
            "cust_id": f"c{i % n_cust:03d}",
            "prod_id": f"p{i % n_prod:03d}",
        })

    mi = MutableIndex.create(
        take_rows([order(i) for i in range(1500)]), ["oid"],
        ingest_device="cpu",
    )
    cust = create_index(
        take_rows([Row({"cust_id": f"c{i:03d}", "name": f"n{i:03d}"})
                   for i in range(n_cust)]),
        ["cust_id"],
    )
    cust.on_device("cpu")
    prod = create_index(
        take_rows([Row({"prod_id": f"p{i:03d}", "label": f"l{i:03d}"})
                   for i in range(n_prod)]),
        ["prod_id"],
    )
    prod.on_device("cpu")
    root = P.Join(
        P.Join(P.Scan(None), cust, ("cust_id",)), prod, ("prod_id",)
    )
    with _flight_dir() as flight_dir, \
            LookupServer(indexes={"orders": mi}) as srv:
        view = srv.register_view("enriched", root, source="orders")
        base_cs = view.checksums()
        snap0, epoch0 = view.snapshot(), view.epoch
        with faults.active(
            FaultPlan(
                [{"site": "views:refresh", "at": [0], "error": "fatal"}],
                seed=17,
            )
        ) as plan:
            # the write cycle lands its tier + tombstone, then its
            # refresh pass crashes (caught by the dispatcher's sweep)
            fa = srv.submit_append([order(2000)], index="orders")
            fd = srv.submit_delete(("o00007",), index="orders")
            acked = fa.result(timeout=30.0) == 1 and fd.result(timeout=30.0) == 1
            deadline = time.perf_counter() + 30.0
            failures = 0
            while time.perf_counter() < deadline:
                cell = srv.snapshot()["by_view"].get("enriched", {})
                failures = int(cell.get("failures", 0))
                if failures:
                    break
                time.sleep(0.01)
            # the prior snapshot is still the live one: same object,
            # same epoch, same contents; the events are still queued
            intact = (
                view.snapshot() is snap0
                and view.epoch == epoch0
                and view.checksums() == base_cs
                and view.pending >= 1
            )
            injections = plan.snapshot()
        # dispatcher alive: this lookup's cycle also retries the (now
        # disarmed) refresh and drains the queue
        alive = srv.lookup("o00005", index="orders") != []
        deadline = time.perf_counter() + 30.0
        while view.pending and time.perf_counter() < deadline:
            time.sleep(0.01)
        converged = view.pending == 0
        parity = view.checksums() == view.recompute_checksums()
        resurrect_gone = view.read("o00007") == []
        cell = srv.snapshot()["by_view"]["enriched"]
        flight = _flight_evidence(flight_dir, "views:refresh")
    return {
        "ok": acked
        and failures >= 1
        and intact
        and alive
        and converged
        and parity
        and resurrect_gone
        and injections["fired"].get("views:refresh", 0) == 1
        and flight["ok"],
        "write_futures_acked": acked,
        "refresh_failures_recorded": failures,
        "prior_snapshot_intact": intact,
        "dispatcher_alive": alive,
        "retry_converged": converged,
        "from_scratch_parity": parity,
        "flight": flight,
        "injections": injections,
        "view_cell": {
            k: cell[k] for k in ("refreshes", "events", "failures", "epoch")
        },
    }


# ---- disarmed-hook overhead gate -----------------------------------------


def case_disarmed_overhead(idx, ids):
    """The disarmed inject() fast path, priced against served requests
    in BOTH regimes the sites actually run in.
    The two serve-path sites (`serve:dispatch`,
    `serve:bounds`) each fire once per dispatch CYCLE, so:

    - coalesced regime: the per-cycle site cost, amortized over the
      observed mean batch, vs the amortized per-request time;
    - isolated regime (batch of one): the full two-site cost vs one
      warm isolated submit->result round trip.

    The original formulation charged the per-cycle sites per REQUEST
    against the amortized per-request time — a worst-case numerator
    over a best-case denominator — and only stayed under budget while
    the measured loop was still half-cold, so the verdict flipped with
    case ordering.  The serve path is now fully warmed (one complete
    probe-set pass) before anything is timed, and each regime compares
    like with like."""
    from csvplus_tpu.resilience import faults
    from csvplus_tpu.serve import LookupServer

    assert faults.current() is None
    reps = 200_000
    t0 = time.perf_counter()
    for _ in range(reps):
        faults.inject("serve:bounds")
    per_call_s = (time.perf_counter() - t0) / reps

    probes = _probes(ids, 2000, seed=3)
    sites_per_cycle = 2  # serve:dispatch + serve:bounds
    with LookupServer(idx) as srv:
        for f in [srv.submit(p) for p in probes]:  # full warm pass
            f.result(timeout=30.0)
        ticks_before = srv.snapshot()["ticks"]
        t0 = time.perf_counter()
        for f in [srv.submit(p) for p in probes]:
            f.result(timeout=30.0)
        per_request_s = (time.perf_counter() - t0) / len(probes)
        cycles = max(1, srv.snapshot()["ticks"] - ticks_before)
        mean_batch = len(probes) / cycles
        iso = probes[:64]
        t0 = time.perf_counter()
        for p in iso:
            srv.submit(p).result(timeout=30.0)
        iso_rt_s = (time.perf_counter() - t0) / len(iso)

    pct_coalesced = (
        100.0 * sites_per_cycle * per_call_s / (mean_batch * per_request_s)
    )
    pct_isolated = 100.0 * sites_per_cycle * per_call_s / iso_rt_s
    pct = max(pct_coalesced, pct_isolated)
    return {
        "ok": pct <= OVERHEAD_BUDGET_PCT,
        "per_call_ns": round(per_call_s * 1e9, 2),
        "per_request_us": round(per_request_s * 1e6, 2),
        "isolated_rt_us": round(iso_rt_s * 1e6, 2),
        "mean_batch": round(mean_batch, 1),
        "sites_per_cycle": sites_per_cycle,
        "overhead_pct_coalesced": round(pct_coalesced, 4),
        "overhead_pct_isolated": round(pct_isolated, 4),
        "overhead_pct": round(pct, 4),
        "budget_pct": OVERHEAD_BUDGET_PCT,
    }


def main() -> int:
    import tempfile

    import jax

    from csvplus_tpu.obs.memory import host_header
    from csvplus_tpu.utils.observe import telemetry

    sys.stderr.write(
        f"chaos: backend={jax.default_backend()}"
        f" devices={jax.device_count()}\n"
    )
    idx, ids = _build_index()
    cases: dict = {}
    telemetry.enabled = True
    telemetry.reset()
    try:
        with tempfile.TemporaryDirectory(prefix="csvplus-chaos-") as tmp_root:
            cases["serve_retry"] = _with_timeout(
                "serve_retry", lambda: case_serve_retry(idx, ids)
            )
            cases["serve_degrade"] = _with_timeout(
                "serve_degrade", lambda: case_serve_degrade(idx, ids)
            )
            cases["dispatcher_crash"] = _with_timeout(
                "dispatcher_crash", lambda: case_dispatcher_crash(idx, ids)
            )
            cases["ingest_crash_recovery"] = _with_timeout(
                "ingest_crash_recovery",
                lambda: case_ingest_crash_recovery(tmp_root),
            )
            cases["ingest_read_fault_typed"] = _with_timeout(
                "ingest_read_fault_typed",
                lambda: case_ingest_read_fault_typed(tmp_root),
            )
            cases["mesh_join_under_ingest_faults"] = _with_timeout(
                "mesh_join", lambda: case_mesh_join_under_ingest_faults(tmp_root)
            )
            cases["storage_compact_crash"] = _with_timeout(
                "storage_compact_crash", case_storage_compact_crash
            )
            cases["wal_crash_matrix"] = _with_timeout(
                "wal_crash_matrix",
                lambda: case_wal_crash_matrix(tmp_root),
            )
            cases["view_refresh_crash"] = _with_timeout(
                "view_refresh_crash", case_view_refresh_crash
            )
            cases["disarmed_overhead"] = _with_timeout(
                "disarmed_overhead", lambda: case_disarmed_overhead(idx, ids)
            )
    finally:
        telemetry_json = telemetry.to_json()
        telemetry.enabled = False

    failed = sorted(k for k, v in cases.items() if not v.get("ok"))
    record = {
        "metric": "chaos_cases_passed",
        "value": len(cases) - len(failed),
        "cases_total": len(cases),
        "failed": failed,
        "case_timeout_s": CASE_TIMEOUT_S,
        "backend": jax.default_backend(),
        **host_header(),
        "cases": cases,
        "telemetry": telemetry_json,
    }
    try:
        record["commit"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, cwd=REPO, timeout=10,
        ).stdout.strip() or None
    except Exception:
        pass

    with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    sys.stderr.write(f"chaos: artifact written to {f.name}\n")

    compact = {
        k: record[k]
        for k in ("metric", "value", "cases_total", "failed", "backend")
    }
    compact["overhead_pct"] = cases.get("disarmed_overhead", {}).get(
        "overhead_pct"
    )
    print(json.dumps(compact), flush=True)
    if failed:
        sys.stderr.write(f"chaos FAIL: {', '.join(failed)}\n")
        return 1
    sys.stderr.write(f"chaos ok: {len(cases)}/{len(cases)} cases\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
