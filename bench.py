"""Headline benchmark: 3-way lookup join throughput (BASELINE config 3/5).

Workload: orders ⋈ customers(unique id) ⋈ products(unique prod_id) — the
reference README's flagship pipeline (README.md:54-65), whose reference
hot loop does 2 host binary searches + 2 map merges per row
(csvplus.go:552-583, SURVEY.md §3.3).

What is timed:

* **device**: the fused flagship step (two vectorized binary-search
  probes + validity mask) + attribute gathers + match compaction — i.e.
  a materialized *columnar* join result resident on device.  String
  decode to host dicts is sink cost, not join cost, and is excluded.
* **baseline**: this framework's host executor (the comparable CPU
  row-dict path per BASELINE.md: Go toolchain is not installed) running
  the same join with dict merges, timed on a subsample and scaled.

The default mode runs in this one process on the device JAX gives and
names it in the record (``platform``, ``device_kind``, device count).
It refuses to start when that device is not a TPU unless the caller set
``JAX_PLATFORMS=cpu`` itself, and it exits non-zero when a tier fails
or is abandoned, or when the wall-clock budget runs out.

Output: ONE JSON line {"metric", "value", "unit", "vs_baseline", ...},
printed right after the device + host measurements and again as the
last stdout line; the informational tiers (end-to-end, secondary,
micro) write to stderr.

Baseline honesty (VERDICT r3 next #6): ``vs_baseline`` is explicitly
labeled ``baseline_kind: python_host_executor`` (Go is not installed),
and the record also carries ``go_class_proxy_rows_per_sec`` /
``vs_go_class_proxy`` — a compiled C++ re-creation of the reference's
exact hot-loop shape (bench_oracle.cpp) bounding the Go-class multiple.

Env knobs: CSVPLUS_BENCH_ROWS (override the auto-sized order count),
CSVPLUS_BENCH_CUSTOMERS (100_000), CSVPLUS_BENCH_PRODUCTS (1_000),
CSVPLUS_BENCH_HOST_SAMPLE (200_000), CSVPLUS_BENCH_REPS (5),
CSVPLUS_BENCH_BUDGET (540 s), CSVPLUS_BENCH_TIER_DEADLINE (120 s),
CSVPLUS_BENCH_GO_PROXY (=0 skips the C++ proxy).

The other modes (``--micro-lookup`` ... ``--fuse-smoke``) are CPU
correctness gates: the Makefile recipes pass ``JAX_PLATFORMS=cpu``, and
the mesh smokes re-exec themselves onto 8 simulated CPU devices before
JAX is touched.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

_METRIC = "threeway_join_rows_per_sec_chip"


class _Recorder:
    """Holds the best benchmark record so far; prints it exactly once.

    The watchdog and the main flow race to print; the lock + flag make
    that safe."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._record: "dict | None" = None
        self.printed = False

    def register(self, record: dict) -> None:
        from csvplus_tpu.obs.memory import host_header

        record = dict(host_header(), **record)
        with self._lock:
            if not self.printed:
                self._record = record

    def print_once(self) -> None:
        with self._lock:
            if self.printed:
                return
            if self._record is None:
                self._record = {
                    "metric": _METRIC,
                    "value": 0.0,
                    "unit": "rows/s",
                    "vs_baseline": 0.0,
                    "note": "watchdog fired before the first measurement",
                }
            print(json.dumps(self._record), flush=True)
            self.printed = True

    def reprint_last(self) -> None:
        """Echo the already-printed record again, so it is the TRUE last
        stdout line (the driver parses the last line; anything the
        informational tiers may have leaked to stdout must not be it)."""
        with self._lock:
            if self.printed:
                print(json.dumps(self._record), flush=True)


_recorder = _Recorder()

_DEADLINE = time.time() + float(os.environ.get("CSVPLUS_BENCH_BUDGET", 540))


def _remaining() -> float:
    return _DEADLINE - time.time()


def _start_watchdog() -> None:
    def watch() -> None:
        while True:
            rem = _remaining()
            if rem <= 0:
                break
            time.sleep(min(rem, 1.0))
        sys.stderr.write("bench: global budget exhausted; emitting best-so-far\n")
        _recorder.print_once()
        os._exit(124)  # a run that did not finish is not a success

    threading.Thread(target=watch, daemon=True, name="bench-watchdog").start()


def _gen_data(n_orders: int, n_cust: int, n_prod: int):
    """Synthetic string-keyed tables, reference-shaped (csvplus_test.go
    generators: random cust/prod ids, qty, price)."""
    import numpy as np

    rng = np.random.default_rng(20160914)
    cust_ids = np.char.add("c", np.arange(n_cust).astype(np.str_))
    prod_ids = np.char.add("p", np.arange(n_prod).astype(np.str_))
    orders_cust = cust_ids[rng.integers(0, n_cust, n_orders)]
    orders_prod = prod_ids[rng.integers(0, n_prod, n_orders)]
    qty = rng.integers(1, 101, n_orders).astype(np.str_)
    names = np.char.add("name", (np.arange(n_cust) % 9973).astype(np.str_))
    prices = np.char.mod("%.2f", rng.uniform(0.01, 99.0, n_prod))
    products = np.char.add("prod", (np.arange(n_prod)).astype(np.str_))
    return {
        "orders": {"cust_id": orders_cust, "prod_id": orders_prod, "qty": qty},
        "customers": {"id": cust_ids, "name": names},
        "products": {"prod_id": prod_ids, "product": products, "price": prices},
    }


def _bench_device(data, reps: int) -> "tuple[float, float]":
    """(joined rows per second — median over reps, total wall seconds)."""
    import jax

    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu.models.flagship import ThreewayJoin
    from csvplus_tpu.ops.join import DeviceIndex
    from csvplus_tpu.ops.sort import sort_table

    wall0 = time.perf_counter()
    dev = jax.devices()[0]

    def table(d):
        # numpy str arrays feed encode_strings' fast path directly
        return DeviceTable.from_pylists(dict(d), device=dev)

    cust_t = sort_table(table(data["customers"]), ["id"])
    prod_t = sort_table(table(data["products"]), ["prod_id"])
    orders_t = table(data["orders"])
    cust = DeviceIndex.build(cust_t, ["id"])
    prod = DeviceIndex.build(prod_t, ["prod_id"])

    tw = ThreewayJoin.build(orders_t, cust, prod)

    def once():
        t = tw.run()  # probe + gathers + compaction, columnar result
        t.sync()  # force every output column with one scalar round trip
        return t.nrows

    nrows = once()  # warmup + compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        once()
        times.append(time.perf_counter() - t0)
    med = sorted(times)[len(times) // 2]
    n_orders = len(next(iter(data["orders"].values())))
    assert nrows == n_orders  # all keys hit by construction
    return n_orders / med, time.perf_counter() - wall0


def _bench_host(data, sample: int) -> float:
    """The host row-dict executor on a subsample; rows per second."""
    from csvplus_tpu import Row, take_rows

    orders_rows = [
        Row({"cust_id": c, "prod_id": p, "qty": q})
        for c, p, q in zip(
            data["orders"]["cust_id"][:sample].tolist(),
            data["orders"]["prod_id"][:sample].tolist(),
            data["orders"]["qty"][:sample].tolist(),
        )
    ]
    cust_rows = [
        Row({"id": i, "name": n})
        for i, n in zip(
            data["customers"]["id"].tolist(), data["customers"]["name"].tolist()
        )
    ]
    prod_rows = [
        Row({"prod_id": i, "product": pr, "price": p})
        for i, pr, p in zip(
            data["products"]["prod_id"].tolist(),
            data["products"]["product"].tolist(),
            data["products"]["price"].tolist(),
        )
    ]
    cust_idx = take_rows(cust_rows).unique_index_on("id")
    prod_idx = take_rows(prod_rows).unique_index_on("prod_id")

    src = take_rows(orders_rows).join(cust_idx, "cust_id").join(prod_idx)
    count = 0

    def sink(row):
        nonlocal count
        count += 1

    t0 = time.perf_counter()
    src(sink)
    dt = time.perf_counter() - t0
    assert count == len(orders_rows)
    return count / dt


def _pick_full_tier(backend: str, coarse_n: int, coarse_wall: float) -> int:
    """Largest order-count tier whose wall time, scaled linearly from
    the measured coarse run, fits in just over half the remaining
    budget."""
    tiers = [10_000_000, 5_000_000, 2_000_000] if backend != "cpu" else [2_000_000]
    for n in tiers:
        if coarse_wall * (n / coarse_n) * 1.25 <= _remaining() * 0.55:
            return n
    return coarse_n


def _go_class_proxy(data) -> "float | None":
    """rows/s of the reference's 3-way join loop shape in compiled C++
    (bench_oracle.cpp: sorted-vector binary searches + per-row hash-map
    merges — the Go map[string]string performance class), bounding the
    honest "vs Go" multiple where no Go toolchain exists (VERDICT r3
    missing #4).  None when the toolchain or run fails."""
    import subprocess
    import tempfile

    if os.environ.get("CSVPLUS_BENCH_GO_PROXY") == "0":
        return None
    try:
        import numpy as np

        src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "bench_oracle.cpp")
        with tempfile.TemporaryDirectory() as td:
            # compile into the run-private dir: a fixed world-shared path
            # could execute another user's binary or race a concurrent run
            exe = os.path.join(td, "bench_oracle")
            subprocess.run(
                ["g++", "-O2", "-o", exe, src], check=True, capture_output=True,
                timeout=60,
            )
            o, c, p = data["orders"], data["customers"], data["products"]
            n = len(o["cust_id"])
            cap = min(n, 1_000_000)  # the proxy loop is O(n log n); cap it
            with open(f"{td}/orders.csv", "w") as f:
                f.write("cust_id,prod_id,qty\n")
                body = np.char.add(
                    np.char.add(np.char.add(o["cust_id"][:cap], ","),
                                np.char.add(o["prod_id"][:cap], ",")),
                    o["qty"][:cap],
                )
                f.write("\n".join(body.tolist()) + "\n")
            with open(f"{td}/customers.csv", "w") as f:
                f.write("id,name\n")
                f.write("\n".join(np.char.add(np.char.add(c["id"], ","), c["name"]).tolist()) + "\n")
            with open(f"{td}/products.csv", "w") as f:
                f.write("prod_id,product,price\n")
                body = np.char.add(
                    np.char.add(np.char.add(p["prod_id"], ","), np.char.add(p["product"], ",")),
                    p["price"],
                )
                f.write("\n".join(body.tolist()) + "\n")
            out = subprocess.run(
                [exe, f"{td}/orders.csv", f"{td}/customers.csv", f"{td}/products.csv"],
                capture_output=True,
                text=True,
                timeout=min(120, max(10, _remaining() * 0.25)),
            )
        rate = float(out.stdout.split()[0])
        sys.stderr.write(f"bench: go-class C++ proxy {rate:,.0f} rows/s (n={cap})\n")
        return rate
    except Exception as e:  # noqa: BLE001 — informational tier only
        sys.stderr.write(f"bench: go-class proxy unavailable ({e})\n")
        return None


def main() -> int:
    _start_watchdog()
    import jax

    from csvplus_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    dev = jax.devices()[0]
    backend = dev.platform
    if backend != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.stderr.write(
            f"bench: the default device is {dev} ({backend}), not a TPU, and the"
            " caller did not set JAX_PLATFORMS=cpu; nothing was run\n"
        )
        return 2
    sys.stderr.write(
        f"bench: platform={backend} device_kind={dev.device_kind}"
        f" devices={jax.device_count()} remaining={_remaining():.0f}s\n"
    )
    n_cust = int(os.environ.get("CSVPLUS_BENCH_CUSTOMERS", 100_000))
    n_prod = int(os.environ.get("CSVPLUS_BENCH_PRODUCTS", 1_000))
    sample = int(os.environ.get("CSVPLUS_BENCH_HOST_SAMPLE", 200_000))
    reps = int(os.environ.get("CSVPLUS_BENCH_REPS", 5))
    rows_override = os.environ.get("CSVPLUS_BENCH_ROWS")

    # -- stage 1: host baseline + coarse device number --
    coarse_n = min(int(rows_override), 1_000_000) if rows_override else 1_000_000
    data = _gen_data(coarse_n, n_cust, n_prod)
    host_rps = _bench_host(data, min(sample, coarse_n))
    go_rps = _go_class_proxy(data)
    dev_rps, coarse_wall = _bench_device(data, max(2, reps // 2))
    record = {
        "metric": _METRIC,
        "value": round(dev_rps, 1),
        "unit": "rows/s",
        "vs_baseline": round(dev_rps / host_rps, 2),
        "baseline_kind": "python_host_executor",
        "backend": backend,
        "n_orders": coarse_n,
    }
    if go_rps:
        record["go_class_proxy_rows_per_sec"] = round(go_rps, 1)
        record["vs_go_class_proxy"] = round(dev_rps / go_rps, 2)
    _recorder.register(record)
    sys.stderr.write(
        f"bench: coarse tier n={coarse_n} -> {dev_rps:,.0f} rows/s"
        f" ({coarse_wall:.1f}s wall, remaining={_remaining():.0f}s)\n"
    )

    # -- stage 2: full-scale tier, sized from the coarse run --
    n_orders = (
        int(rows_override) if rows_override
        else _pick_full_tier(backend, coarse_n, coarse_wall)
    )
    if n_orders > coarse_n:
        data = _gen_data(n_orders, n_cust, n_prod)
        dev_rps_full, full_wall = _bench_device(data, reps)
        record = dict(
            record,
            value=round(dev_rps_full, 1),
            vs_baseline=round(dev_rps_full / host_rps, 2),
            n_orders=n_orders,
        )
        if go_rps:
            record["vs_go_class_proxy"] = round(dev_rps_full / go_rps, 2)
        _recorder.register(record)
        sys.stderr.write(
            f"bench: full tier n={n_orders} -> {dev_rps_full:,.0f} rows/s"
            f" ({full_wall:.1f}s wall)\n"
        )

    _recorder.print_once()

    tier_deadline = float(os.environ.get("CSVPLUS_BENCH_TIER_DEADLINE", 120))
    n = len(next(iter(data["orders"].values())))
    ok = (
        _run_tier("end-to-end", lambda: _end_to_end_metrics(data, n), tier_deadline)
        and _run_tier("secondary", lambda: _secondary_metrics(n), tier_deadline)
        and _run_tier("micro", _micro_benchmarks, tier_deadline)
    )
    # the compact record again as the TRUE last stdout line: the driver
    # parses the last line, and the tiers above must not be able to
    # leave anything after it
    _recorder.reprint_last()
    return 0 if ok else 1


def _run_tier(name: str, fn, deadline: float) -> bool:
    """Run an informational tier on a thread with a deadline.  False when
    the tier raised or had to be abandoned — either fails the run, and
    the tiers after it are not started (an abandoned tier's thread still
    holds the device)."""
    deadline = min(deadline, max(0.0, _remaining() - 10))
    failure: list = []

    def body() -> None:
        try:
            fn()
        except Exception:  # reported below, and fails the run
            import traceback

            failure.append(traceback.format_exc())

    t = threading.Thread(target=body, daemon=True, name=f"bench-{name}")
    t0 = time.perf_counter()
    t.start()
    t.join(deadline)
    if t.is_alive():
        sys.stderr.write(
            f"bench[{name}] FAILED: abandoned after"
            f" {time.perf_counter() - t0:.0f}s deadline\n"
        )
        return False
    if failure:
        sys.stderr.write(f"bench[{name}] FAILED:\n{failure[0]}")
        return False
    return True


def _end_to_end_metrics(data, n_orders: int) -> None:
    """The honest tiers next to the columnar headline (to stderr): the
    same join carried through (a) the vectorized CSV byte encoder and
    (b) full host-row materialization — so the headline can't be read as
    end-to-end.  Sink tiers run on a capped subsample (decode throughput
    is row-bound, not join-bound)."""
    import jax

    from csvplus_tpu.columnar.csvenc import encode_csv_body
    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu.models.flagship import ThreewayJoin
    from csvplus_tpu.ops.join import DeviceIndex
    from csvplus_tpu.ops.sort import sort_table

    n = min(n_orders, int(os.environ.get("CSVPLUS_BENCH_SINK_ROWS", 1_000_000)))
    dev = jax.devices()[0]
    sub = {
        "orders": {k: v[:n] for k, v in data["orders"].items()},
        "customers": data["customers"],
        "products": data["products"],
    }
    table = lambda d: DeviceTable.from_pylists(dict(d), device=dev)
    cust = DeviceIndex.build(sort_table(table(sub["customers"]), ["id"]), ["id"])
    prod = DeviceIndex.build(
        sort_table(table(sub["products"]), ["prod_id"]), ["prod_id"]
    )
    tw = ThreewayJoin.build(table(sub["orders"]), cust, prod)
    joined = tw.run()  # warm (compiled above in the headline run)

    cols = sorted(joined.columns)
    t0 = time.perf_counter()
    body = encode_csv_body(joined, cols)
    t_csv = time.perf_counter() - t0
    nbytes = len(body.encode("utf-8")) if body is not None else 0

    t0 = time.perf_counter()
    rows = joined.to_rows()
    t_rows = time.perf_counter() - t0
    assert len(rows) == n
    sys.stderr.write(
        f"bench[end-to-end]: join->csv-bytes {n / t_csv:,.0f} rows/s"
        f" ({nbytes / 1e6:.0f} MB) | join->to_rows {n / t_rows:,.0f} rows/s"
        f" (n={n})\n"
    )


def _micro_benchmarks() -> None:
    """Analogues of the reference's Go micro-benchmarks
    (csvplus_test.go:1052-1186) at the reference's own scales, to stderr:
    index build small (120 rows, unique) / big (10K rows, multi-col),
    Find small/big, and the lookup join in BOTH directions
    (10K orders ⋈ 120 people and 120 people ⋈ 10K orders)."""
    import numpy as np

    from csvplus_tpu import Row, take_rows

    rng = np.random.default_rng(42)
    people = [
        Row({"id": str(i), "name": f"name{i % 10}", "surname": f"sur{i % 12}"})
        for i in range(120)
    ]
    orders = [
        Row(
            {
                "cust_id": str(int(rng.integers(0, 120))),
                "prod_id": f"p{int(rng.integers(0, 8))}",
                "qty": str(int(rng.integers(1, 100))),
            }
        )
        for i in range(10_000)
    ]

    def rate(fn, reps=5):
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            times.append(time.perf_counter() - t0)
        return sorted(times)[len(times) // 2]

    t_small = rate(lambda: take_rows(people).unique_index_on("id"))
    t_big = rate(lambda: take_rows(orders).index_on("cust_id", "prod_id"))
    small_idx = take_rows(people).unique_index_on("id")
    big_idx = take_rows(orders).index_on("cust_id", "prod_id")
    t_find_small = rate(lambda: [small_idx.find(str(i)).to_rows() for i in range(120)])
    t_find_big = rate(
        lambda: [big_idx.find(str(i)).to_rows() for i in range(120)]
    )
    # batched columns: the same probe sets through find_many
    from csvplus_tpu import to_rows_many

    small_probes = [str(i) for i in range(120)]
    t_fm_small = rate(lambda: to_rows_many(small_idx.find_many(small_probes)))
    t_fm_big = rate(lambda: to_rows_many(big_idx.find_many(small_probes)))
    t_join_fwd = rate(
        lambda: take_rows(orders).join(small_idx, "cust_id").to_rows()
    )
    orders_by_cust = take_rows(orders).index_on("cust_id")
    t_join_rev = rate(
        lambda: take_rows(people).join(orders_by_cust, "id").to_rows()
    )
    sys.stderr.write(
        "bench[micro]: index build 120u "
        f"{120 / t_small:,.0f} rows/s | index build 10k multi "
        f"{10_000 / t_big:,.0f} rows/s | find small "
        f"{120 / t_find_small:,.0f} lookups/s | find big "
        f"{120 / t_find_big:,.0f} lookups/s | find_many small "
        f"{120 / t_fm_small:,.0f} lookups/s | find_many big "
        f"{120 / t_fm_big:,.0f} lookups/s | join 10k>120 "
        f"{10_000 / t_join_fwd:,.0f} rows/s | join 120>10k "
        f"{120 / t_join_rev:,.0f} probe rows/s\n"
    )


def zipf_probe_values(ids, n_probes: int, *, s: float = 1.1, seed: int = 0):
    """Deterministic Zipf(s)-skewed draws from ``ids`` (an int array).

    Rank-k of ``ids`` (in array order) is drawn with weight 1/k^s, the
    classic hot-key serving distribution: a handful of keys absorb most
    of the traffic, so coalesced batches repeat keys and the decoded-row
    LRU actually earns its keep.  Shared by the ``make bench-serve``
    zipf scenario (bench_serve.py imports it) and the optional
    CSVPLUS_MICRO_DIST=zipf micro-lookup tier; the default uniform
    micro path is untouched.  Same (ids, n, s, seed) -> same draws.
    """
    import numpy as np

    ranks = np.arange(1, len(ids) + 1, dtype=np.float64)
    weights = ranks ** -float(s)
    weights /= weights.sum()
    rng = np.random.default_rng(seed)
    return rng.choice(np.asarray(ids), size=n_probes, p=weights)


def zipf_fact_table(
    n_orders: int,
    n_customers: int,
    *,
    s: float = 1.1,
    seed: int = 20160914,
    data_dir: "str | None" = None,
    n_products: int = 1000,
):
    """Zipf(s)-skewed orders fact table + matching customers dimension
    (ISSUE 15) — :func:`zipf_probe_values` extended from probe streams
    to a full on-disk fact table.

    The fact table's ``cust_id`` foreign keys are Zipf(s) draws over a
    PERMUTED rank->customer mapping, so the heavy customers scatter
    across the id space instead of clustering inside one range shard's
    key slice (a consecutive hot block would make the skew trivially
    range-local and understate the repartition hot-spot the skew tier
    exists to fix).  Same (n_orders, n_customers, s, seed) -> same
    bytes; files are cached in NORTHSTAR_DIR and written atomically
    (.tmp + rename) so an interrupted generation can't leave a short
    file for the next run to ingest.

    Returns ``(orders_path, customers_path)``; products.csv rides along
    in the same dir (shared with the uniform northstar tiers).
    """
    import numpy as np

    ddir = data_dir or os.environ.get("NORTHSTAR_DIR", "/tmp/northstar_data")
    os.makedirs(ddir, exist_ok=True)
    tag = f"{n_orders}_{n_customers}_s{s}"
    opath = os.path.join(ddir, f"orders_zipf_{tag}.csv")
    cpath = os.path.join(ddir, f"customers_z{n_customers}.csv")
    ppath = os.path.join(ddir, "products.csv")
    chunk = 2_000_000
    if not os.path.exists(cpath):
        tmp = cpath + ".tmp"
        with open(tmp, "w") as f:
            f.write("id,name\n")
            for base in range(0, n_customers, chunk):
                n = min(chunk, n_customers - base)
                ids = np.arange(base, base + n)
                lines = np.char.add(
                    np.char.add("c", ids.astype(np.str_)),
                    np.char.add(",name", (ids % 9973).astype(np.str_)),
                )
                f.write("\n".join(lines.tolist()))
                f.write("\n")
        os.replace(tmp, cpath)
    if not os.path.exists(ppath):
        tmp = ppath + ".tmp"
        with open(tmp, "w") as f:
            f.write("prod_id,product,price\n")
            for i in range(n_products):
                f.write(f"p{i},prod{i},{(i % 9900) / 100 + 0.99:.2f}\n")
        os.replace(tmp, ppath)
    if not os.path.exists(opath):
        rng = np.random.default_rng(seed)
        cust = zipf_probe_values(
            rng.permutation(n_customers), n_orders, s=s, seed=seed
        )
        tmp = opath + ".tmp"
        t0 = time.perf_counter()
        with open(tmp, "w") as f:
            f.write("order_id,cust_id,prod_id,qty\n")
            for base in range(0, n_orders, chunk):
                n = min(chunk, n_orders - base)
                oid = np.arange(base, base + n)
                prod = rng.integers(0, n_products, n)
                qty = rng.integers(1, 101, n)
                lines = np.char.add(
                    np.char.add(
                        np.char.add("o", oid.astype(np.str_)),
                        np.char.add(
                            ",c", cust[base : base + n].astype(np.str_)
                        ),
                    ),
                    np.char.add(
                        np.char.add(",p", prod.astype(np.str_)),
                        np.char.add(",", qty.astype(np.str_)),
                    ),
                )
                f.write("\n".join(lines.tolist()))
                f.write("\n")
                print(
                    f"  gen zipf {base + n:,}/{n_orders:,} rows"
                    f" ({time.perf_counter() - t0:,.0f}s)",
                    file=sys.stderr,
                )
        os.replace(tmp, opath)
    return opath, cpath


def _micro_lookup() -> int:
    """The `make bench-micro` smoke tier: CPU-only, seconds, hermetic.

    Builds the 1M-row big-index micro shape (CSVPLUS_MICRO_ROWS to
    shrink), measures batched ``find_many`` vs looped single ``find``
    lookups/s, prints ONE JSON line, and exits nonzero when the batched
    rate regresses more than 2x below the checked-in floor
    (bench_micro_floor.json).  Parity between the two paths is asserted
    as part of the smoke."""
    import numpy as np

    import csvplus_tpu as cp
    from csvplus_tpu.columnar.table import DeviceTable

    n = int(os.environ.get("CSVPLUS_MICRO_ROWS", 1_000_000))
    n_probes = int(os.environ.get("CSVPLUS_MICRO_PROBES", 10_000))
    ids = np.arange(n, dtype=np.int64) * 7 % (n * 3)
    keys = np.char.add("c", ids.astype(np.str_))
    t = DeviceTable.from_pylists(
        {"cust_id": keys.tolist(), "v": np.arange(n).astype(np.str_).tolist()},
        device="cpu",
    )
    idx = cp.take(t).index_on("cust_id").sync()
    dist = os.environ.get("CSVPLUS_MICRO_DIST", "uniform")
    rng = np.random.default_rng(0)
    if dist == "zipf":
        probes = [f"c{int(v)}" for v in zipf_probe_values(ids, n_probes)]
    else:
        probes = [f"c{int(v)}" for v in rng.choice(ids, n_probes)]
    _ = cp.to_rows_many(idx.find_many(probes[:10]))  # warm mirror + dispatch
    # best-of-3 with the decoded-block LRU dropped between passes: every
    # pass pays the full vectorized search + gather-decode, so the best
    # pass measures the engine, not the cache (or scheduler noise)
    mirror = idx._impl.dev.table
    t_batch = float("inf")
    # the recompile watch opens AFTER the first timed rep: the 10-probe
    # warmup and the full-probe reps are different shapes, so rep 1 may
    # legitimately lower — reps 2..3 must lower nothing
    from csvplus_tpu.obs.recompile import RecompileWatch

    recompiles = None
    for _rep in range(3):
        mirror._mirror_lru = None
        if _rep == 1:
            recompiles = RecompileWatch().__enter__()
        t0 = time.perf_counter()
        groups = cp.to_rows_many(idx.find_many(probes))
        t_batch = min(t_batch, time.perf_counter() - t0)
    recompiles.assert_zero("micro-lookup warm reps")
    n_single = min(1000, n_probes)
    t0 = time.perf_counter()
    singles = [idx.find(p).to_rows() for p in probes[:n_single]]
    t_single = time.perf_counter() - t0
    assert groups[:n_single] == singles, "find_many != looped find"
    from csvplus_tpu.obs.memory import host_header

    record = {
        "metric": "big_index_lookups_per_sec_batched",
        "value": round(n_probes / t_batch, 1),
        "unit": "lookups/s",
        "single_find_lookups_per_sec": round(n_single / t_single, 1),
        "n_rows": n,
        "n_probes": n_probes,
        "dist": dist,
        **host_header(),
        "recompiles_warm": recompiles.delta(),
    }
    print(json.dumps(record), flush=True)
    floor_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_micro_floor.json"
    )
    floor = 0.0
    try:
        with open(floor_path) as f:
            floor = float(
                json.load(f).get("big_index_lookups_per_sec_batched", 0.0)
            )
    except (OSError, ValueError):
        pass
    # the floor was recorded on the uniform distribution; a zipf run is
    # an exploratory tier, not a regression gate
    if dist == "uniform" and floor and record["value"] < floor / 2:
        sys.stderr.write(
            f"bench[micro-lookup] REGRESSION: batched {record['value']:,.0f}"
            f" lookups/s is under half the floor ({floor:,.0f})\n"
        )
        return 1
    sys.stderr.write(
        f"bench[micro-lookup] ok: batched {record['value']:,.0f} lookups/s"
        f" (floor {floor:,.0f}) | single {record['single_find_lookups_per_sec']:,.0f}"
        f" lookups/s (n={n})\n"
    )
    return 0


def _trace_smoke() -> int:
    """The `make trace-smoke` tier: the tracing subsystem end-to-end on
    the micro lookup shape, seconds, hermetic CPU.

    Three gates, ONE JSON line on stdout, nonzero exit on any failure:

    1. a traced pass through the serving tier must produce per-request
       span trees (serve:queue-wait / serve:dispatch per request, and
       the batch's serve:cycle with the serve:bounds +
       serve:gather-decode phases as children);
    2. the Chrome-trace export of those spans must pass the schema
       validator (``csvplus_tpu.obs.export.validate_chrome_trace``) so
       the artifact actually opens in Perfetto;
    3. the DISABLED instrumentation path must stay under
       ``CSVPLUS_TRACE_SMOKE_MAX_PCT`` (default 2%) of the bare batched
       lookup pass: per-hook cost is measured directly (open/close with
       no active trace) and scaled by the span count a traced pass
       actually records — the exact number of hook sites on this path.
    """
    import tempfile

    import numpy as np

    import csvplus_tpu as cp
    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu.obs.export import export_chrome_trace, validate_chrome_trace
    from csvplus_tpu.obs.memory import host_header
    from csvplus_tpu.obs.span import tracer
    from csvplus_tpu.serve import LookupServer

    n = int(os.environ.get("CSVPLUS_TRACE_SMOKE_ROWS", 100_000))
    n_probes = int(os.environ.get("CSVPLUS_TRACE_SMOKE_PROBES", 2_000))
    max_pct = float(os.environ.get("CSVPLUS_TRACE_SMOKE_MAX_PCT", 2.0))
    ids = np.arange(n, dtype=np.int64) * 7 % (n * 3)
    keys = np.char.add("c", ids.astype(np.str_))
    t = DeviceTable.from_pylists(
        {"cust_id": keys.tolist(), "v": np.arange(n).astype(np.str_).tolist()},
        device="cpu",
    )
    idx = cp.take(t).index_on("cust_id").sync()
    rng = np.random.default_rng(0)
    probes = [f"c{int(v)}" for v in rng.choice(ids, n_probes)]
    _ = cp.to_rows_many(idx.find_many(probes[:10]))  # warm dispatch

    # bare pass (no trace active: every hook takes its disabled path)
    t_pass = float("inf")
    for _rep in range(3):
        t0 = time.perf_counter()
        cp.to_rows_many(idx.find_many(probes))
        t_pass = min(t_pass, time.perf_counter() - t0)

    # traced pass through the serving tier: per-request span trees
    tracer.reset()
    n_requests = 64
    with LookupServer(idx) as srv:
        with tracer.trace("trace-smoke:lookup", probes=n_requests):
            futs = [srv.submit(p) for p in probes[:n_requests]]
            for f in futs:
                f.result(timeout=60)
    traces = tracer.finished()
    if len(traces) != 1:
        sys.stderr.write(f"trace-smoke FAILED: {len(traces)} traces != 1\n")
        return 1
    spans = traces[0].snapshot()
    names = [s.name for s in spans]
    by_id = {s.span_id: s for s in spans}
    want_counts = {"serve:queue-wait": n_requests, "serve:dispatch": n_requests}
    for name, count in want_counts.items():
        if names.count(name) != count:
            sys.stderr.write(
                f"trace-smoke FAILED: {names.count(name)} x {name},"
                f" wanted {count}\n"
            )
            return 1
    phases = [s for s in spans if s.name in ("serve:bounds", "serve:gather-decode")]
    if not phases or any(
        by_id[s.parent_id].name != "serve:cycle" for s in phases
    ):
        sys.stderr.write(
            "trace-smoke FAILED: batch phases missing or mis-parented\n"
        )
        return 1

    # exporter + schema validation
    log_dir = tempfile.mkdtemp(prefix="csvplus-trace-smoke-")
    trace_path = export_chrome_trace(log_dir, traces)
    with open(trace_path) as f:
        obj = json.load(f)
    errors = validate_chrome_trace(obj)
    if errors:
        sys.stderr.write(
            f"trace-smoke FAILED: chrome-trace schema: {errors[:5]}\n"
        )
        return 1
    n_events = len(obj["traceEvents"])

    # disabled-path overhead: per-hook cost x the span count a traced
    # pass records (= hook sites on this path), vs the bare pass
    hook_reps = 50_000
    t0 = time.perf_counter()
    for _ in range(hook_reps):
        tracer.close_span(tracer.open_span("noop"))
    per_hook = (time.perf_counter() - t0) / hook_reps
    overhead_pct = 100.0 * per_hook * len(spans) / t_pass
    record = {
        "metric": "trace_smoke",
        "value": round(overhead_pct, 4),
        "unit": "pct_disabled_overhead",
        "max_pct": max_pct,
        "spans": len(spans),
        "trace_events": n_events,
        "validation_errors": 0,
        "per_hook_ns": round(per_hook * 1e9, 1),
        "bare_pass_ms": round(t_pass * 1e3, 3),
        "n_rows": n,
        "n_probes": n_probes,
        **host_header(),
    }
    print(json.dumps(record), flush=True)
    if overhead_pct > max_pct:
        sys.stderr.write(
            f"trace-smoke FAILED: disabled-path overhead {overhead_pct:.3f}%"
            f" > {max_pct}% budget\n"
        )
        return 1
    sys.stderr.write(
        f"trace-smoke ok: {len(spans)} spans, {n_events} chrome-trace events"
        f" validated, disabled overhead {overhead_pct:.4f}%"
        f" (budget {max_pct}%)\n"
    )
    return 0


def _obs_smoke() -> int:
    """The `make obs-smoke` tier: the telemetry plane end-to-end on the
    micro lookup shape, seconds, hermetic CPU.

    Four gates, ONE JSON line on stdout, nonzero exit on any failure:

    1. a served pass with Zipf-skewed probes must surface the planted
       heavy hitter in the Prometheus scrape's ``csvplus_skew_topk``
       series — scraped over REAL HTTP from the plane's endpoint, not
       read from the registry in-process — and (ISSUE 15) a planted
       BUILD-side hitter (5% duplicate-key rows in the index table)
       must surface in the same scrape with ``side="build"``, fed by
       the join-time build sample the partitioned planner offers;
    2. the scrape must carry the serve / index / tail / flight /
       process metric families (the always-on surface an operator
       would dashboard);
    3. zero warm recompiles across the telemetered warm pass
       (``RecompileWatch.assert_zero`` — the plane must not perturb
       the compile caches);
    4. the always-on hook cost (per-probe sketch offer + per-cycle
       ``on_cycle``) scaled by the counts the served pass actually
       recorded must stay under ``CSVPLUS_OBS_SMOKE_MAX_PCT`` (default
       2%) of the bare batched lookup pass — the trace-smoke
       discipline applied to the metrics plane.
    """
    import urllib.request

    import numpy as np

    import csvplus_tpu as cp
    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu.obs.memory import host_header
    from csvplus_tpu.obs.metrics import (
        MetricRegistry,
        TelemetryPlane,
    )
    from csvplus_tpu.obs.flight import FlightRecorder
    from csvplus_tpu.obs.recompile import RecompileWatch
    from csvplus_tpu.serve import LookupServer

    n = int(os.environ.get("CSVPLUS_OBS_SMOKE_ROWS", 100_000))
    n_probes = int(os.environ.get("CSVPLUS_OBS_SMOKE_PROBES", 2_000))
    n_requests = 64
    max_pct = float(os.environ.get("CSVPLUS_OBS_SMOKE_MAX_PCT", 2.0))
    ids = np.arange(n, dtype=np.int64) * 7 % (n * 3)
    keys = np.char.add("c", ids.astype(np.str_)).tolist()
    vvals = np.arange(n).astype(np.str_).tolist()
    # planted BUILD-side heavy hitter (ISSUE 15): 5% duplicate-key rows
    # appended (not overwritten — every probed key stays present), so
    # the join-time build-side sample must surface "hotcust" under
    # side="build" in the same scrape the probe hitter rides
    n_hot_rows = n // 20
    keys += ["hotcust"] * n_hot_rows
    vvals += ["0"] * n_hot_rows
    t = DeviceTable.from_pylists(
        {"cust_id": keys, "v": vvals},
        device="cpu",
    )
    idx = cp.take(t).index_on("cust_id").sync()
    # reset BEFORE the index's first lookup: offer_build_sample is
    # once-per-index, so a reset after it fired would wipe the sketch
    # for the rest of the process
    from csvplus_tpu.obs.joinskew import joinskew

    joinskew.reset()
    draws = zipf_probe_values(ids, n_probes)
    probes = [f"c{int(v)}" for v in draws]
    # the planted heavy hitter: the empirically most frequent key of
    # the 64 draws the served pass will actually submit
    vals, counts = np.unique(draws[:n_requests], return_counts=True)
    hitter = f"c{int(vals[counts.argmax()])}"
    _ = cp.to_rows_many(idx.find_many(probes[:10]))  # warm dispatch

    # bare pass: the engine with no serving tier and no plane hooks
    t_pass = float("inf")
    for _rep in range(3):
        t0 = time.perf_counter()
        cp.to_rows_many(idx.find_many(probes))
        t_pass = min(t_pass, time.perf_counter() - t0)

    srv = LookupServer(idx)
    srv.start()
    try:
        # cold pass compiles; the watched warm pass must not
        for p in probes[:8]:
            srv.submit(p).result(timeout=60)
        watch = RecompileWatch().__enter__()
        futs = [srv.submit(p) for p in probes[:n_requests]]
        for f in futs:
            f.result(timeout=60)
        recompiles = watch.delta()
        if recompiles:
            sys.stderr.write(
                f"obs-smoke FAILED: warm recompiles {recompiles}\n"
            )
            return 1

        # join-time build-side offer (ISSUE 15): one small device join
        # against the same index makes the planner sample its build
        # keys into the process-global joinskew sketch, which the
        # plane's scrape merges under side="build"
        from csvplus_tpu.columnar.ingest import source_from_table

        probe_t = DeviceTable.from_pylists(
            {"cust_id": probes[:512]}, device="cpu"
        )
        source_from_table(probe_t).join(idx, "cust_id").to_rows()

        # the scrape, over real HTTP
        port = srv.plane.serve_http()
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as resp:
            text = resp.read().decode()
        want_families = (
            "csvplus_serve_completed_total",
            "csvplus_serve_cycles_total",
            "csvplus_serve_latency_ms",
            'csvplus_index_lookups{index="default"}',
            "csvplus_tail_offered_total",
            "csvplus_flight_events",
            "csvplus_process_peak_rss_mb",
            "csvplus_skew_observed_total",
        )
        missing = [w for w in want_families if w not in text]
        if missing:
            sys.stderr.write(
                f"obs-smoke FAILED: scrape missing {missing}\n"
            )
            return 1
        topk_lines = [
            ln for ln in text.splitlines()
            if ln.startswith("csvplus_skew_topk{")
        ]
        hit_lines = [
            ln for ln in topk_lines
            if f'key="{hitter}"' in ln and 'side="probe"' in ln
        ]
        if not hit_lines:
            sys.stderr.write(
                f"obs-smoke FAILED: heavy hitter {hitter} not in "
                f"csvplus_skew_topk ({len(topk_lines)} top-K lines)\n"
            )
            return 1
        build_lines = [
            ln for ln in topk_lines
            if 'key="hotcust"' in ln and 'side="build"' in ln
        ]
        if not build_lines:
            sys.stderr.write(
                "obs-smoke FAILED: planted build-side hitter 'hotcust'"
                f" not in csvplus_skew_topk ({len(topk_lines)} top-K"
                " lines)\n"
            )
            return 1

        # always-on hook cost, measured directly on a scratch plane and
        # scaled by the counts the served pass recorded
        plane_snap = srv.plane.registry.sample_dict()
        cycles = int(plane_snap.get("csvplus_serve_cycles_total", 0))
        observed = int(
            plane_snap.get(
                'csvplus_skew_observed_total{index="default",side="probe"}',
                0,
            )
        )
    finally:
        srv.plane.close()
        srv.stop()

    scratch = TelemetryPlane(
        registry=MetricRegistry(), flight_recorder=FlightRecorder()
    )
    reps = 20_000
    # the dispatcher calls offer_probes ONCE per cycle with the whole
    # sub-batch — measure that call shape, not a per-probe call
    avg_batch = max(1, observed // max(1, cycles))
    batch_probes = [("c1",)] * avg_batch
    t0 = time.perf_counter()
    for _ in range(reps):
        scratch.offer_probes("default", batch_probes)
    per_offer_call = (time.perf_counter() - t0) / reps
    sample = (0.001, 0.0001, "ok", "lookup", "default", None)
    t0 = time.perf_counter()
    for _ in range(reps):
        scratch.on_cycle(avg_batch, 0.001, [sample] * avg_batch)
    per_cycle = (time.perf_counter() - t0) / reps
    hooks_s = cycles * (per_cycle + per_offer_call)
    overhead_pct = 100.0 * hooks_s / t_pass

    record = {
        "metric": "obs_smoke",
        "value": round(overhead_pct, 4),
        "unit": "pct_always_on_overhead",
        "max_pct": max_pct,
        "heavy_hitter": hitter,
        "hitter_in_topk": True,
        "build_hitter_in_topk": True,
        "topk_series": len(topk_lines),
        "cycles": cycles,
        "probes_sketched": observed,
        "avg_batch": avg_batch,
        "per_offer_call_ns": round(per_offer_call * 1e9, 1),
        "per_cycle_ns": round(per_cycle * 1e9, 1),
        "warm_recompiles": 0,
        "bare_pass_ms": round(t_pass * 1e3, 3),
        "n_rows": n,
        "n_probes": n_probes,
        **host_header(),
    }
    print(json.dumps(record), flush=True)
    if overhead_pct > max_pct:
        sys.stderr.write(
            f"obs-smoke FAILED: always-on overhead {overhead_pct:.3f}%"
            f" > {max_pct}% budget\n"
        )
        return 1
    sys.stderr.write(
        f"obs-smoke ok: hitter {hitter} in top-K ({len(topk_lines)}"
        f" series), build hitter 'hotcust' in side=\"build\" top-K,"
        f" {cycles} cycles / {observed} probes sketched,"
        f" always-on overhead {overhead_pct:.4f}% (budget {max_pct}%),"
        f" zero warm recompiles\n"
    )
    return 0


def _skew_smoke() -> int:
    """The `make skew-smoke` tier: the skew-aware partitioned join's
    correctness contract in seconds, hermetic 8-device CPU mesh
    (ISSUE 15; the perf floor lives in the `make bench-mesh` skew
    tier — this gate is the cheap every-`make check` correctness leg).

    Gates, ONE JSON line on stdout, nonzero exit on any failure:

    1. bitwise parity: positional per-column checksums of a sharded
       Zipf(s=1.3) join are identical to the ``CSVPLUS_JOIN_SKEW=0``
       run's over the same data;
    2. the broadcast tier ENGAGED: heavy keys detected, rows routed
       through the broadcast tier, and the routing counters landed in
       the process-global registry (the telemetry-plane families);
    3. zero warm recompiles across repeated skew-aware joins
       (``RecompileWatch.assert_zero``).
    """
    if os.environ.get("CSVPLUS_SKEW_SMOKE_HERMETIC") != "1":
        env = dict(os.environ)
        env["CSVPLUS_SKEW_SMOKE_HERMETIC"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        os.execve(sys.executable, [sys.executable] + sys.argv, env)

    import numpy as np

    import csvplus_tpu as cp
    import csvplus_tpu.ops.join as J
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu.obs.joinskew import joinskew
    from csvplus_tpu.obs.memory import host_header
    from csvplus_tpu.obs.recompile import RecompileWatch
    from csvplus_tpu.parallel.mesh import make_mesh
    from csvplus_tpu.utils.checksum import checksum_device_table

    n_rows = int(os.environ.get("CSVPLUS_SKEW_SMOKE_ROWS", 200_000))
    n_keys = int(os.environ.get("CSVPLUS_SKEW_SMOKE_KEYS", 20_000))
    # engage the partition tier at smoke scale (dedicated process: the
    # class-level override can't leak anywhere)
    J.DeviceIndex.PARTITION_MIN_KEYS = 1

    t0_all = time.perf_counter()
    rng = np.random.default_rng(20160914)
    # permute rank->key so the hot keys don't cluster in one shard's range
    cust = zipf_probe_values(
        rng.permutation(n_keys), n_rows, s=1.3, seed=20260805
    )
    mesh = make_mesh(8)
    stream = DeviceTable.from_pylists(
        {
            "k": [f"c{int(v)}" for v in cust],
            "qty": [str(int(v) % 9) for v in cust],
        },
        device="cpu",
    ).with_sharding(mesh)
    build = DeviceTable.from_pylists(
        {
            "k": [f"c{i}" for i in range(n_keys)],
            "name": [f"n{i % 97}" for i in range(n_keys)],
        },
        device="cpu",
    )
    idx = cp.take(build).index_on("k").sync()
    joinskew.reset()

    def sums():
        out = source_from_table(stream).join(idx, "k").to_device_table()
        out = out.sync()
        assert out.nrows == n_rows, out.nrows
        return checksum_device_table(
            out, sorted(out.columns), positional=True
        )

    os.environ["CSVPLUS_JOIN_SKEW"] = "0"
    naive_sums = sums()
    os.environ["CSVPLUS_JOIN_SKEW"] = "1"
    skew_sums = sums()  # cold skew pass compiles the hot-tier variant
    if skew_sums != naive_sums:
        sys.stderr.write(
            f"skew-smoke FAILED: checksum parity broke:"
            f" {skew_sums} != {naive_sums}\n"
        )
        return 1
    with RecompileWatch() as watch:
        for _ in range(2):
            if sums() != naive_sums:
                sys.stderr.write(
                    "skew-smoke FAILED: warm skew pass diverged\n"
                )
                return 1
        recompiles = watch.delta()
    if recompiles:
        sys.stderr.write(
            f"skew-smoke FAILED: warm recompiles {recompiles}\n"
        )
        return 1

    counters = joinskew.counters_snapshot().get("k")
    if (
        counters is None
        or counters["hot_keys_detected"] < 1
        or counters["rows_broadcast"] <= 0
    ):
        sys.stderr.write(
            f"skew-smoke FAILED: broadcast tier never engaged on a"
            f" Zipf(1.3) stream (counters: {counters})\n"
        )
        return 1
    # per-join routing must cover the stream exactly (3 engaged joins:
    # cold naive ran with the tier disabled and records nothing)
    if (
        counters["rows_broadcast"] + counters["rows_repartitioned"]
        != counters["joins"] * n_rows
    ):
        sys.stderr.write(
            f"skew-smoke FAILED: routing split does not cover the"
            f" stream (counters: {counters})\n"
        )
        return 1
    record = {
        "metric": "skew_smoke",
        "value": round(counters["rows_broadcast"] / counters["joins"], 1),
        "unit": "rows_broadcast_per_join",
        "rows": n_rows,
        "n_keys": n_keys,
        "zipf_s": 1.3,
        "hot_keys_detected": counters["hot_keys_detected"],
        "rows_repartitioned_per_join": round(
            counters["rows_repartitioned"] / counters["joins"], 1
        ),
        "parity_bitwise": True,
        "warm_recompiles": 0,
        "wall_sec": round(time.perf_counter() - t0_all, 1),
        **host_header(),
    }
    print(json.dumps(record), flush=True)
    sys.stderr.write(
        f"skew-smoke ok: {counters['hot_keys_detected']} hot keys,"
        f" {record['value']:,.0f}/{n_rows} rows broadcast per join,"
        f" bitwise parity vs CSVPLUS_JOIN_SKEW=0, zero warm recompiles"
        f" ({record['wall_sec']}s)\n"
    )
    return 0


def _multiway_smoke() -> int:
    """The `make multiway-smoke` tier (ISSUE 17): the single-pass
    multiway join's correctness contract in seconds, hermetic 8-device
    CPU mesh (the perf targets live in the `make bench-mesh` multiway
    tier — this gate is the cheap every-`make check` correctness leg).

    Gates, ONE JSON line on stdout, nonzero exit on any failure:

    1. the rewriter actually FUSED: the cost model chooses the multiway
       operator for the sharded 3-way chain and the plan cache's
       ``fused`` counter records it (not assumed from the env flag);
    2. bitwise parity: positional per-column checksums of the fused
       3-way join are identical to the ``CSVPLUS_MULTIWAY=0`` cascade's
       over the same Zipf(s=1.3)-both-dims data (hot keys in both
       dimensions, partition tier engaged);
    3. zero warm recompiles across repeated fused executions
       (``RecompileWatch.assert_zero``);
    4. the ``csvplus_join_multiway_*`` counter family landed in the
       process-global registry and rides a metrics scrape.
    """
    if os.environ.get("CSVPLUS_MULTIWAY_SMOKE_HERMETIC") != "1":
        env = dict(os.environ)
        env["CSVPLUS_MULTIWAY_SMOKE_HERMETIC"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        os.execve(sys.executable, [sys.executable] + sys.argv, env)

    import numpy as np

    import csvplus_tpu as cp
    import csvplus_tpu.ops.join as J
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu.obs.joinskew import joinskew
    from csvplus_tpu.obs.memory import host_header
    from csvplus_tpu.obs.metrics import TelemetryPlane
    from csvplus_tpu.obs.recompile import RecompileWatch
    from csvplus_tpu.parallel.mesh import make_mesh
    from csvplus_tpu.serve.plancache import PlanCache
    from csvplus_tpu.utils.checksum import checksum_device_table

    n_rows = int(os.environ.get("CSVPLUS_MULTIWAY_SMOKE_ROWS", 200_000))
    n_keys = int(os.environ.get("CSVPLUS_MULTIWAY_SMOKE_KEYS", 20_000))
    n_prods = 1_000
    # engage the partition tier at smoke scale (dedicated process: the
    # class-level override can't leak anywhere)
    J.DeviceIndex.PARTITION_MIN_KEYS = 1

    t0_all = time.perf_counter()
    rng = np.random.default_rng(20160914)
    # BOTH dimension keys are Zipf-skewed (permuted rank->key so hot
    # keys don't cluster in one shard's range): the fused pass must
    # route each dimension's heavy keys through its broadcast tier
    cust = zipf_probe_values(
        rng.permutation(n_keys), n_rows, s=1.3, seed=20260806
    )
    prod = zipf_probe_values(
        rng.permutation(n_prods), n_rows, s=1.3, seed=20260807
    )
    mesh = make_mesh(8)
    stream = DeviceTable.from_pylists(
        {
            "k": [f"c{int(v)}" for v in cust],
            "p": [f"p{int(v)}" for v in prod],
            "qty": [str(int(v) % 9) for v in cust],
        },
        device="cpu",
    ).with_sharding(mesh)
    cust_build = DeviceTable.from_pylists(
        {
            "k": [f"c{i}" for i in range(n_keys)],
            "name": [f"n{i % 97}" for i in range(n_keys)],
        },
        device="cpu",
    )
    prod_build = DeviceTable.from_pylists(
        {
            "p": [f"p{i}" for i in range(n_prods)],
            "price": [f"{(i % 990) / 10:.1f}" for i in range(n_prods)],
        },
        device="cpu",
    )
    cust_idx = cp.take(cust_build).index_on("k").sync()
    prod_idx = cp.take(prod_build).index_on("p").sync()
    plan = (
        source_from_table(stream).join(cust_idx, "k").join(prod_idx, "p").plan
    )
    joinskew.reset()

    def sums(cache):
        out = cache.execute(plan)
        assert out.nrows == n_rows, out.nrows
        return checksum_device_table(out, sorted(out.columns), positional=True)

    os.environ["CSVPLUS_MULTIWAY"] = "0"
    cascade_sums = sums(PlanCache())
    os.environ["CSVPLUS_MULTIWAY"] = "1"
    cache = PlanCache()
    fused_sums = sums(cache)  # cold fused pass compiles the multiway kernels
    stats = cache.stats()
    if stats.get("fused", 0) < 1:
        sys.stderr.write(
            f"multiway-smoke FAILED: rewriter did not fuse the 3-way"
            f" chain (plan cache stats: {stats})\n"
        )
        return 1
    if fused_sums != cascade_sums:
        sys.stderr.write(
            f"multiway-smoke FAILED: checksum parity broke:"
            f" {fused_sums} != {cascade_sums}\n"
        )
        return 1
    with RecompileWatch() as watch:
        for _ in range(2):
            if sums(cache) != cascade_sums:
                sys.stderr.write(
                    "multiway-smoke FAILED: warm fused pass diverged\n"
                )
                return 1
        recompiles = watch.delta()
    if recompiles:
        sys.stderr.write(
            f"multiway-smoke FAILED: warm recompiles {recompiles}\n"
        )
        return 1

    # engagement evidence: the fused executions folded their counters
    # under the '+'-joined dim label, and the family rides a scrape
    counters = joinskew.counters_snapshot().get("k+p")
    if (
        counters is None
        or counters.get("multiway_joins", 0) < 3
        or counters.get("multiway_rows_out", 0)
        != counters["multiway_joins"] * n_rows
    ):
        sys.stderr.write(
            f"multiway-smoke FAILED: multiway counters never landed"
            f" (counters: {counters})\n"
        )
        return 1
    scrape = TelemetryPlane().registry.render()
    missing = [
        fam
        for fam in (
            "csvplus_join_multiway_total",
            "csvplus_join_multiway_rows_in_total",
            "csvplus_join_multiway_rows_out_total",
            "csvplus_join_multiway_intermediate_rows_avoided_total",
        )
        if fam not in scrape
    ]
    if missing:
        sys.stderr.write(
            f"multiway-smoke FAILED: scrape is missing {missing}\n"
        )
        return 1
    record = {
        "metric": "multiway_smoke",
        "value": round(
            counters["multiway_intermediate_rows_avoided"]
            / counters["multiway_joins"],
            1,
        ),
        "unit": "intermediate_rows_avoided_per_join",
        "rows": n_rows,
        "n_keys": n_keys,
        "n_prods": n_prods,
        "zipf_s": 1.3,
        "multiway_joins": counters["multiway_joins"],
        "multiway_dims": counters["multiway_dims"],
        "plancache_fused": stats["fused"],
        "parity_bitwise": True,
        "warm_recompiles": 0,
        "wall_sec": round(time.perf_counter() - t0_all, 1),
        **host_header(),
    }
    print(json.dumps(record), flush=True)
    sys.stderr.write(
        f"multiway-smoke ok: 3-way chain fused by the rewriter,"
        f" {record['value']:,.0f} intermediate rows avoided per join,"
        f" bitwise parity vs CSVPLUS_MULTIWAY=0, zero warm recompiles"
        f" ({record['wall_sec']}s)\n"
    )
    return 0


def _fuse_smoke() -> int:
    """The `make fuse-smoke` tier (ISSUE 19): the probe-pass fusion's
    correctness contract in seconds, hermetic 8-device CPU mesh (the
    perf targets live in `make bench-macro` — this gate is the cheap
    every-`make check` correctness leg).

    Gates, ONE JSON line on stdout, nonzero exit on any failure:

    1. the rewriter actually FUSED: pass 5 absorbs the Filter->Map run
       into the probe (a ``fuse_chain`` recipe step, the plan cache's
       ``fused_chains`` counter — not assumed from the env flag);
    2. bitwise parity: positional per-column checksums of the fused
       serving identical to the disarmed ``CSVPLUS_FUSE=0`` staged run
       over the same Zipf(s=1.1) bytes, region-restricted dimension
       (probe misses engage the composed-emit path);
    3. zero warm recompiles across repeated fused executions
       (``RecompileWatch.assert_zero``);
    4. the ``csvplus_plan_fusion_*`` counter family landed in the
       process-global registry and rides a metrics scrape.
    """
    if os.environ.get("CSVPLUS_FUSE_SMOKE_HERMETIC") != "1":
        env = dict(os.environ)
        env["CSVPLUS_FUSE_SMOKE_HERMETIC"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        os.execve(sys.executable, [sys.executable] + sys.argv, env)

    import numpy as np

    import csvplus_tpu as cp
    from csvplus_tpu import plan as P
    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu.exprs import SetValue
    from csvplus_tpu.obs.memory import host_header
    from csvplus_tpu.obs.metrics import TelemetryPlane
    from csvplus_tpu.obs.recompile import RecompileWatch
    from csvplus_tpu.parallel.mesh import make_mesh
    from csvplus_tpu.predicates import Like, Not
    from csvplus_tpu.serve.plancache import PlanCache
    from csvplus_tpu.utils.checksum import checksum_device_table

    n_rows = int(os.environ.get("CSVPLUS_FUSE_SMOKE_ROWS", 200_000))
    n_keys = 2_000

    t0_all = time.perf_counter()
    rng = np.random.default_rng(20260807)
    cust = zipf_probe_values(rng.permutation(n_keys), n_rows, s=1.1, seed=1)
    arange = np.arange(n_rows)
    stream = DeviceTable.from_pylists(
        {
            "cust_id": [f"c{int(v)}" for v in cust],
            "cat": np.char.add("k", (arange % 16).astype(np.str_)).tolist(),
            "qty": (arange % 100).astype(np.str_).tolist(),
        },
        device="cpu",
    ).with_sharding(make_mesh(8))
    # region-restricted dimension (every 7th customer): most probes
    # miss, so the fused merge takes the composed-emit path rather
    # than the all-matched identity shape
    ids = [i for i in range(n_keys) if i % 7 == 1]
    cust_idx = cp.take(DeviceTable.from_pylists(
        {
            "cust_id": [f"c{i}" for i in ids],
            "name": [f"n{i % 97}" for i in ids],
        },
        device="cpu",
    )).index_on("cust_id").sync()
    plan = P.SelectCols(
        P.Join(
            P.MapExpr(
                P.Filter(P.Scan(stream), Not(Like({"cat": "k1"}))),
                SetValue("flag", "y"),
            ),
            cust_idx,
            ("cust_id",),
        ),
        ("cust_id", "name", "qty", "flag"),
    )

    def sums(cache):
        out = cache.execute(plan)
        assert out.nrows > 0
        return checksum_device_table(out, sorted(out.columns), positional=True)

    # disarmed leg first: CSVPLUS_FUSE=0 must restore the staged
    # execution byte-for-byte, through the same PlanCache surface
    os.environ["CSVPLUS_FUSE"] = "0"
    try:
        staged_sums = sums(PlanCache())
    finally:
        os.environ.pop("CSVPLUS_FUSE", None)
    cache = PlanCache()
    fused_sums = sums(cache)  # cold fused pass compiles the kernels
    stats = cache.stats()
    exe = cache.executable_for(plan)
    steps = [s[0] for s in (exe.recipe.steps if exe and exe.recipe else ())]
    if stats.get("fused_chains", 0) < 1 or "fuse_chain" not in steps:
        sys.stderr.write(
            f"fuse-smoke FAILED: pass 5 did not fuse the chain (plan"
            f" cache stats: {stats}, recipe steps: {steps})\n"
        )
        return 1
    if fused_sums != staged_sums:
        sys.stderr.write(
            f"fuse-smoke FAILED: checksum parity broke:"
            f" {fused_sums} != {staged_sums}\n"
        )
        return 1
    with RecompileWatch() as watch:
        for _ in range(2):
            if sums(cache) != staged_sums:
                sys.stderr.write(
                    "fuse-smoke FAILED: warm fused pass diverged\n"
                )
                return 1
        recompiles = watch.delta()
    if recompiles:
        sys.stderr.write(
            f"fuse-smoke FAILED: warm recompiles {recompiles}\n"
        )
        return 1

    scrape = TelemetryPlane().registry.render()
    missing = [
        fam
        for fam in (
            "csvplus_plan_fusion_total",
            "csvplus_plan_fusion_rows_full_total",
            "csvplus_plan_fusion_rows_selected_total",
            "csvplus_plan_fusion_rows_out_total",
        )
        if fam not in scrape
    ]
    if missing:
        sys.stderr.write(
            f"fuse-smoke FAILED: scrape is missing {missing}\n"
        )
        return 1
    record = {
        "metric": "fuse_smoke",
        "value": stats["fused_chains"],
        "unit": "fused_chains",
        "rows": n_rows,
        "n_keys": n_keys,
        "zipf_s": 1.1,
        "recipe_steps": steps,
        "fusion_refused": stats.get("fusion_refused", 0),
        "parity_bitwise": True,
        "warm_recompiles": 0,
        "wall_sec": round(time.perf_counter() - t0_all, 1),
        **host_header(),
    }
    print(json.dumps(record), flush=True)
    sys.stderr.write(
        f"fuse-smoke ok: Filter->Map->Join fused by pass 5"
        f" (fused_chains={stats['fused_chains']}), bitwise parity vs"
        f" CSVPLUS_FUSE=0, fusion families on the scrape, zero warm"
        f" recompiles ({record['wall_sec']}s)\n"
    )
    return 0


def _bench_mesh() -> int:
    """The `make bench-mesh` tier: the sharded north-star pipeline on
    the virtual 8-device CPU mesh, with the same floor contract as
    `make bench-micro`.

    Runs examples/northstar_mesh.py as a subprocess (a CPU gate: it
    re-execs itself onto 8 simulated CPU devices; this parent never
    touches JAX), parses its final JSON line, prints ONE compact JSON
    line, and exits nonzero when the warm sharded join regresses more
    than 2x below the checked-in floor (bench_mesh_floor.json).

    Env knobs: CSVPLUS_BENCH_MESH_ROWS (default 10M — the gate tier),
    CSVPLUS_BENCH_MESH_OUT (artifact path; no file is written unless it
    is set, so a gate run cannot overwrite a checked-in record),
    CSVPLUS_BENCH_BUDGET."""
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    rows = int(os.environ.get("CSVPLUS_BENCH_MESH_ROWS", 10_000_000))
    out_path = os.environ.get("CSVPLUS_BENCH_MESH_OUT")

    cmd = [
        sys.executable,
        os.path.join(repo, "examples", "northstar_mesh.py"),
        str(rows),
    ]
    try:
        child = subprocess.run(
            cmd,
            capture_output=True,
            text=True,
            timeout=max(_remaining() - 20, 120),
        )
    except subprocess.TimeoutExpired as e:
        tail = (e.stderr.decode() if isinstance(e.stderr, bytes) else e.stderr) or ""
        sys.stderr.write(
            f"bench[mesh] FAILED: run timed out; stderr tail: {tail[-600:]}\n"
        )
        return 1
    for line in (child.stderr or "").splitlines():
        sys.stderr.write(f"bench[mesh] {line}\n")
    record = None
    for line in reversed((child.stdout or "").splitlines()):
        try:
            rec = json.loads(line)
            if isinstance(rec, dict) and rec.get("metric") == "northstar_mesh_threeway_join":
                record = rec
                break
        except ValueError:
            continue
    if record is None or child.returncode != 0:
        sys.stderr.write(
            f"bench[mesh] FAILED: rc={child.returncode}, no record line;"
            f" stderr tail: {(child.stderr or '')[-600:]}\n"
        )
        return 1

    try:
        record["commit"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, cwd=repo, timeout=10,
        ).stdout.strip() or None
    except Exception:
        pass
    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
        sys.stderr.write(f"bench[mesh]: artifact written to {out_path}\n")

    floor = 0.0
    floor_rows = None
    try:
        with open(os.path.join(repo, "bench_mesh_floor.json")) as f:
            fl = json.load(f)
            floor = float(fl.get("join_rows_per_sec_warm", 0.0))
            floor_rows = fl.get("rows")
    except (OSError, ValueError):
        pass
    warm = float(record.get("join_rows_per_sec_warm", 0.0))
    # the compact gate line (full telemetry table stays in the artifact
    # file / stderr: the driver parses the last stdout line)
    print(
        json.dumps(
            {
                "metric": "northstar_mesh_threeway_join",
                "rows": record.get("rows"),
                "value": warm,
                "unit": "rows/s",
                "ingest_rows_per_sec": record.get("ingest_rows_per_sec"),
                "join_rows_per_sec": record.get("join_rows_per_sec"),
                "peak_host_rss_mb": record.get("peak_host_rss_mb"),
                "backend": record.get("backend"),
                "floor": floor,
            }
        ),
        flush=True,
    )
    if floor and warm < floor / 2:
        sys.stderr.write(
            f"bench[mesh] REGRESSION: warm sharded join {warm:,.0f} rows/s"
            f" is under half the floor ({floor:,.0f} rows/s at"
            f" {floor_rows or '?'} rows)\n"
        )
        return 1
    sys.stderr.write(
        f"bench[mesh] ok: warm sharded join {warm:,.0f} rows/s"
        f" (floor {floor:,.0f}) | ingest"
        f" {record.get('ingest_rows_per_sec', 0):,.0f} rows/s | rss"
        f" {record.get('peak_host_rss_mb', 0):,.0f} MB (n={rows})\n"
    )

    # ---- skew tier (ISSUE 15): the same pipeline over a Zipf(s=1.1)
    # orders stream, skew-aware vs CSVPLUS_JOIN_SKEW=0 in the SAME
    # child run, gated by the warm_join_rows_per_sec_zipf floor with
    # the identical half-floor rule.  CSVPLUS_BENCH_MESH_ZIPF_ROWS
    # sizes it (default = the uniform tier's rows);
    # CSVPLUS_BENCH_MESH_OUT_ZIPF names the artifact (default none, so
    # a CI gate run cannot overwrite the checked-in
    # NORTHSTAR_MESH_r07.json record); CSVPLUS_BENCH_MESH_SKEW=0
    # skips the tier. ----
    if os.environ.get("CSVPLUS_BENCH_MESH_SKEW", "1") == "0":
        sys.stderr.write("bench[mesh] skew tier skipped (env)\n")
        return 0
    zrows = int(os.environ.get("CSVPLUS_BENCH_MESH_ZIPF_ROWS", rows))
    zout = os.environ.get("CSVPLUS_BENCH_MESH_OUT_ZIPF")
    cmd = [
        sys.executable,
        os.path.join(repo, "examples", "northstar_mesh.py"),
        str(zrows),
        "--skew",
    ]
    try:
        child = subprocess.run(
            cmd,
            capture_output=True,
            text=True,
            timeout=max(_remaining() - 20, 120),
        )
    except subprocess.TimeoutExpired as e:
        tail = (e.stderr.decode() if isinstance(e.stderr, bytes) else e.stderr) or ""
        sys.stderr.write(
            f"bench[mesh:zipf] FAILED: run timed out; stderr tail:"
            f" {tail[-600:]}\n"
        )
        return 1
    for line in (child.stderr or "").splitlines():
        sys.stderr.write(f"bench[mesh:zipf] {line}\n")
    zrec = None
    for line in reversed((child.stdout or "").splitlines()):
        try:
            rec = json.loads(line)
            if (
                isinstance(rec, dict)
                and rec.get("metric") == "northstar_mesh_threeway_join_zipf"
            ):
                zrec = rec
                break
        except ValueError:
            continue
    if zrec is None or child.returncode != 0:
        sys.stderr.write(
            f"bench[mesh:zipf] FAILED: rc={child.returncode}, no record"
            f" line; stderr tail: {(child.stderr or '')[-600:]}\n"
        )
        return 1
    try:
        zrec["commit"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, cwd=repo, timeout=10,
        ).stdout.strip() or None
    except Exception:
        pass
    if zout:
        with open(zout, "w") as f:
            json.dump(zrec, f, indent=1)
            f.write("\n")
        sys.stderr.write(f"bench[mesh:zipf]: artifact written to {zout}\n")

    floor_z = 0.0
    floor_z_rows = None
    try:
        with open(os.path.join(repo, "bench_mesh_floor.json")) as f:
            fl = json.load(f)
            floor_z = float(fl.get("warm_join_rows_per_sec_zipf", 0.0))
            floor_z_rows = fl.get("zipf_rows")
    except (OSError, ValueError):
        pass
    warm_z = float(zrec.get("join_rows_per_sec_warm_zipf", 0.0))
    speedup = float(zrec.get("skew_speedup", 0.0))
    print(
        json.dumps(
            {
                "metric": "northstar_mesh_threeway_join_zipf",
                "rows": zrec.get("rows"),
                "value": warm_z,
                "unit": "rows/s",
                "join_rows_per_sec_warm_naive": zrec.get(
                    "join_rows_per_sec_warm_naive"
                ),
                "skew_speedup": speedup,
                "hot_keys_per_join": (
                    round(
                        zrec["skew_counters"]["hot_keys_detected"]
                        / max(zrec["skew_counters"]["joins"], 1),
                        1,
                    )
                    if zrec.get("skew_counters")
                    else None
                ),
                "parity_bitwise": zrec.get("parity_bitwise"),
                "backend": zrec.get("backend"),
                "floor": floor_z,
            }
        ),
        flush=True,
    )
    if floor_z and warm_z < floor_z / 2:
        sys.stderr.write(
            f"bench[mesh:zipf] REGRESSION: warm skew-aware join"
            f" {warm_z:,.0f} rows/s is under half the floor"
            f" ({floor_z:,.0f} rows/s at {floor_z_rows or '?'} rows)\n"
        )
        return 1
    if speedup < 2.0:
        sys.stderr.write(
            f"bench[mesh:zipf] WARNING: skew speedup {speedup:,.2f}x is"
            f" under the 2x record bar at this tier (record runs gate on"
            f" the r07 artifact; the hard floor here is"
            f" warm_join_rows_per_sec_zipf)\n"
        )
    sys.stderr.write(
        f"bench[mesh:zipf] ok: warm skew-aware join {warm_z:,.0f} rows/s"
        f" (naive {zrec.get('join_rows_per_sec_warm_naive', 0):,.0f},"
        f" speedup {speedup:,.2f}x, floor {floor_z:,.0f}) | bitwise"
        f" parity | (n={zrows})\n"
    )

    # ---- multiway tier (ISSUE 17): the cost-chosen single-pass
    # multiway operator vs the cascaded-skew path in the SAME child
    # run over the same Zipf bytes, gated by the
    # join_rows_per_sec_warm_multiway floor with the identical
    # half-floor rule.  CSVPLUS_BENCH_MESH_MULTIWAY_ROWS sizes it
    # (default = the uniform tier's rows); CSVPLUS_BENCH_MESH_OUT_MULTIWAY
    # names the artifact (default none, so a CI gate run cannot
    # overwrite the checked-in NORTHSTAR_MESH_r08.json record);
    # CSVPLUS_BENCH_MESH_MULTIWAY=0 skips the tier. ----
    if os.environ.get("CSVPLUS_BENCH_MESH_MULTIWAY", "1") == "0":
        sys.stderr.write("bench[mesh] multiway tier skipped (env)\n")
        return 0
    mrows = int(os.environ.get("CSVPLUS_BENCH_MESH_MULTIWAY_ROWS", rows))
    mw_out = os.environ.get("CSVPLUS_BENCH_MESH_OUT_MULTIWAY")
    cmd = [
        sys.executable,
        os.path.join(repo, "examples", "northstar_mesh.py"),
        str(mrows),
        "--multiway",
    ]
    try:
        child = subprocess.run(
            cmd,
            capture_output=True,
            text=True,
            timeout=max(_remaining() - 20, 120),
        )
    except subprocess.TimeoutExpired as e:
        tail = (e.stderr.decode() if isinstance(e.stderr, bytes) else e.stderr) or ""
        sys.stderr.write(
            f"bench[mesh:multiway] FAILED: run timed out; stderr tail:"
            f" {tail[-600:]}\n"
        )
        return 1
    for line in (child.stderr or "").splitlines():
        sys.stderr.write(f"bench[mesh:multiway] {line}\n")
    mrec = None
    for line in reversed((child.stdout or "").splitlines()):
        try:
            rec = json.loads(line)
            if (
                isinstance(rec, dict)
                and rec.get("metric") == "northstar_mesh_threeway_join_multiway"
            ):
                mrec = rec
                break
        except ValueError:
            continue
    if mrec is None or child.returncode != 0:
        sys.stderr.write(
            f"bench[mesh:multiway] FAILED: rc={child.returncode}, no record"
            f" line; stderr tail: {(child.stderr or '')[-600:]}\n"
        )
        return 1
    try:
        mrec["commit"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, cwd=repo, timeout=10,
        ).stdout.strip() or None
    except Exception:
        pass
    if mw_out:
        with open(mw_out, "w") as f:
            json.dump(mrec, f, indent=1)
            f.write("\n")
        sys.stderr.write(
            f"bench[mesh:multiway]: artifact written to {mw_out}\n"
        )

    floor_m = 0.0
    floor_m_rows = None
    try:
        with open(os.path.join(repo, "bench_mesh_floor.json")) as f:
            fl = json.load(f)
            floor_m = float(fl.get("join_rows_per_sec_warm_multiway", 0.0))
            floor_m_rows = fl.get("multiway_rows")
    except (OSError, ValueError):
        pass
    warm_m = float(mrec.get("join_rows_per_sec_warm_multiway", 0.0))
    warm_c = float(mrec.get("join_rows_per_sec_warm_cascaded", 0.0))
    print(
        json.dumps(
            {
                "metric": "northstar_mesh_threeway_join_multiway",
                "rows": mrec.get("rows"),
                "value": warm_m,
                "unit": "rows/s",
                "join_rows_per_sec_warm_cascaded": warm_c,
                "multiway_speedup": mrec.get("multiway_speedup"),
                "rss_below_cascaded": mrec.get("rss_below_cascaded"),
                "peak_host_rss_mb_multiway": (mrec.get("legs", {}).get(
                    "multiway", {}
                )).get("peak_host_rss_mb"),
                "peak_host_rss_mb_cascaded": (mrec.get("legs", {}).get(
                    "cascaded", {}
                )).get("peak_host_rss_mb"),
                "parity_bitwise": mrec.get("parity_bitwise"),
                "backend": mrec.get("backend"),
                "floor": floor_m,
            }
        ),
        flush=True,
    )
    if floor_m and warm_m < floor_m / 2:
        sys.stderr.write(
            f"bench[mesh:multiway] REGRESSION: warm multiway join"
            f" {warm_m:,.0f} rows/s is under half the floor"
            f" ({floor_m:,.0f} rows/s at {floor_m_rows or '?'} rows)\n"
        )
        return 1
    if not mrec.get("rss_below_cascaded"):
        sys.stderr.write(
            "bench[mesh:multiway] WARNING: multiway leg RSS peak was not"
            " below the cascaded leg's at this tier (record runs gate on"
            " the r08 artifact; the hard floor here is"
            " join_rows_per_sec_warm_multiway)\n"
        )
    if warm_c and warm_m < warm_c:
        sys.stderr.write(
            f"bench[mesh:multiway] WARNING: multiway warm rate"
            f" {warm_m:,.0f} rows/s under the cascaded leg's"
            f" {warm_c:,.0f} at this tier\n"
        )
    sys.stderr.write(
        f"bench[mesh:multiway] ok: warm multiway join {warm_m:,.0f} rows/s"
        f" (cascaded {warm_c:,.0f}, floor {floor_m:,.0f}) | rss"
        f" {(mrec.get('legs', {}).get('multiway', {})).get('peak_host_rss_mb', 0):,.0f}"
        f" vs {(mrec.get('legs', {}).get('cascaded', {})).get('peak_host_rss_mb', 0):,.0f}"
        f" MB | bitwise parity | (n={mrows})\n"
    )
    return 0


def _bench_ingest() -> int:
    """The `make bench-ingest` tier: streamed CSV ingest through the
    staged multi-worker pipeline, with the same floor contract as the
    other gate tiers (fails when the measured rate drops under half
    the checked-in floor in bench_ingest_floor.json).

    Two in-process runs over the SAME cached orders file, both forced
    onto the chunk-streamed tier: CSVPLUS_INGEST_WORKERS=1 (the serial
    degenerate case of the staged pipeline) and the auto worker count.
    Full-result positional per-column checksums of the two device
    tables must be bitwise-equal — worker count must be unobservable
    in the output — or the tier fails regardless of speed.

    Record-or-postmortem contract (mirroring bench-mesh): the artifact
    either records a >=2x parallel speedup over serial or carries the
    postmortem evidence that this host cannot show one (host_cpus,
    the resolved auto worker count, and the speedup actually seen).
    The per-stage worker table (ingest:cut / ingest:encode /
    ingest:reorder-stall with per-worker busy seconds) from
    telemetry.merged_stages() is embedded per run.

    Env knobs: CSVPLUS_BENCH_INGEST_ROWS (default 10M — the gate
    tier), CSVPLUS_BENCH_INGEST_OUT (artifact path; no file by
    default so a gate run cannot overwrite the checked-in record)."""
    import gc
    import subprocess

    repo = os.path.dirname(os.path.abspath(__file__))
    rows = int(os.environ.get("CSVPLUS_BENCH_INGEST_ROWS", 10_000_000))
    out_path = os.environ.get("CSVPLUS_BENCH_INGEST_OUT")
    # force the chunk-streamed tier even when the file is under the
    # 256MB default threshold (the 10M-row orders file is borderline)
    os.environ.setdefault("CSVPLUS_STREAM_MIN_BYTES", "1000000")

    sys.path.insert(0, repo)
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_northstar_gen", os.path.join(repo, "examples", "northstar.py")
    )
    gen_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(gen_mod)
    opath = gen_mod.generate(rows)
    sys.stderr.write(
        f"bench[ingest]: orders file {opath}"
        f" ({os.path.getsize(opath) / 1e9:.2f} GB)\n"
    )

    import jax

    from csvplus_tpu import FromFile
    from csvplus_tpu.native.scanner import _ingest_workers
    from csvplus_tpu.obs.memory import host_header, peak_rss_mb
    from csvplus_tpu.utils.checksum import checksum_device_table
    from csvplus_tpu.utils.observe import telemetry

    backend = jax.default_backend()
    host_cpus = os.cpu_count() or 1

    def _run(workers_env):
        if workers_env is None:
            os.environ.pop("CSVPLUS_INGEST_WORKERS", None)
        else:
            os.environ["CSVPLUS_INGEST_WORKERS"] = str(workers_env)
        with telemetry.collect():
            t0 = time.perf_counter()
            pipe = FromFile(opath).OnDevice()
            pipe.plan.table.sync()
            dt = time.perf_counter() - t0
            stages = [
                {
                    k: (round(v, 4) if isinstance(v, float) else v)
                    for k, v in row.items()
                }
                for row in telemetry.to_json()["stage_table"]
                if row["stage"].startswith("ingest")
            ]
        table = pipe.plan.table
        cols = sorted(table.columns)
        sums = checksum_device_table(table, cols, positional=True)
        rss = peak_rss_mb()
        del pipe, table
        gc.collect()
        return dt, sums, stages, rss

    try:
        _run(1)  # warmup: pay the one-time XLA compiles outside the clock
        t_serial, sums_serial, stages_serial, rss_serial = _run(1)
        k_auto = _ingest_workers()
        t_auto, sums_auto, stages_auto, rss_peak = _run(None)
    except Exception as e:
        sys.stderr.write(f"bench[ingest] FAILED: {type(e).__name__}: {e}\n")
        return 1
    serial_rate = rows / t_serial
    auto_rate = rows / t_auto
    speedup = auto_rate / serial_rate

    if sums_auto != sums_serial:
        sys.stderr.write(
            "bench[ingest] FAILED: worker count is OBSERVABLE — checksums"
            f" diverge between workers=1 and workers={k_auto}:"
            f" {sums_serial} != {sums_auto}\n"
        )
        return 1
    sys.stderr.write(
        f"bench[ingest]: checksums bitwise-equal across workers=1 and"
        f" workers={k_auto} ({len(sums_serial)} columns)\n"
    )

    record = {
        "metric": "stream_ingest_parallel",
        "rows": rows,
        "backend": backend,
        "value": round(auto_rate, 1),
        "unit": "rows/s",
        "serial_rows_per_sec": round(serial_rate, 1),
        "speedup_vs_serial": round(speedup, 3),
        "workers": k_auto,
        **host_header(),
        "peak_host_rss_mb": round(rss_peak, 1),
        "serial_rss_mb": round(rss_serial, 1),
        "full_result_checksums": sums_auto,
        "stage_table_serial": stages_serial,
        "stage_table_auto": stages_auto,
    }
    if speedup < 2.0:
        if host_cpus < 2:
            record["parallelism_evidence"] = {
                "note": (
                    "postmortem: this host exposes a single CPU, so the"
                    " auto worker count resolves to 1 and no parallel"
                    " speedup is observable here; the >=2x target needs"
                    " a multi-core host (workers scale via"
                    " CSVPLUS_INGEST_WORKERS)"
                ),
                "host_cpus": host_cpus,
                "auto_workers": k_auto,
            }
        else:
            record["parallelism_evidence"] = {
                "note": (
                    f"speedup {speedup:.2f}x on {host_cpus} cpus missed"
                    " the 2x target — investigate reorder-stall vs"
                    " encode seconds in stage_table_auto"
                ),
                "host_cpus": host_cpus,
                "auto_workers": k_auto,
            }
    try:
        record["commit"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, cwd=repo, timeout=10,
        ).stdout.strip() or None
    except Exception:
        pass
    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")
        sys.stderr.write(f"bench[ingest]: artifact written to {out_path}\n")

    floor = 0.0
    floor_rows = None
    try:
        with open(os.path.join(repo, "bench_ingest_floor.json")) as f:
            fl = json.load(f)
            floor = float(fl.get("ingest_rows_per_sec", 0.0))
            floor_rows = fl.get("rows")
    except (OSError, ValueError):
        pass
    print(
        json.dumps(
            {
                "metric": "stream_ingest_parallel",
                "rows": rows,
                "value": round(auto_rate, 1),
                "unit": "rows/s",
                "serial_rows_per_sec": round(serial_rate, 1),
                "speedup_vs_serial": round(speedup, 3),
                "workers": k_auto,
                "host_cpus": host_cpus,
                "peak_host_rss_mb": round(rss_peak, 1),
                "backend": backend,
                "floor": floor,
            }
        ),
        flush=True,
    )
    if floor and auto_rate < floor / 2:
        sys.stderr.write(
            f"bench[ingest] REGRESSION: streamed ingest {auto_rate:,.0f}"
            f" rows/s is under half the floor ({floor:,.0f} rows/s at"
            f" {floor_rows or '?'} rows)\n"
        )
        return 1
    sys.stderr.write(
        f"bench[ingest] ok: {auto_rate:,.0f} rows/s with workers={k_auto}"
        f" (serial {serial_rate:,.0f} rows/s, {speedup:.2f}x,"
        f" floor {floor:,.0f}) | rss {rss_peak:,.0f} MB (n={rows})\n"
    )
    return 0


def _bench_opt() -> int:
    """The `make bench-opt` tier: the verifier-checked plan rewriter
    (ISSUE 16) on the filter+map+join serving chain — hermetic CPU,
    seconds, uniform AND Zipf(s=1.1) fact keys.

    Both legs run warm through the plan cache over identical data; the
    ONLY difference is ``CSVPLUS_OPTIMIZE`` at admission, so the delta
    is the rewrite (predicate pushdown moves the 1-in-16 filter below
    the join; projection pushdown drops the dead payload columns at the
    scan, so the join's materialize never gathers them).

    Gates, ONE JSON line on stdout, nonzero exit on failure:

    * the rewriter must actually fire on this shape (predicate AND
      projection pushdown applied, recipe stored);
    * bitwise parity per distribution: positional per-column checksums
      of the optimized output equal the unrewritten leg's;
    * zero warm recompiles across repeated optimized executions (the
      recipe replays as data — same optimized jaxpr every submission);
    * the uniform optimized rate must stay above half the checked-in
      floor (bench_opt_floor.json).

    CSVPLUS_BENCH_OPT_ROWS scales the fact table (default 200K).
    CSVPLUS_BENCH_OPT_OUT names the artifact (default none): the
    record plus per-stage attribution — marginal per-stage seconds for
    both legs, diffed with ``obs.diff.diff_stage_tables`` (the
    ``obs diff`` engine), so WHERE the win lands (the join's gather vs
    the filter) is in the artifact, not folklore.
    """
    import dataclasses

    import numpy as np

    import csvplus_tpu as cp
    from csvplus_tpu import plan as P
    from csvplus_tpu.columnar.exec import execute_plan_view
    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu.exprs import SetValue
    from csvplus_tpu.obs.diff import diff_stage_tables, format_diff
    from csvplus_tpu.obs.memory import host_header
    from csvplus_tpu.obs.recompile import RecompileWatch
    from csvplus_tpu.predicates import Like
    from csvplus_tpu.serve import PlanCache
    from csvplus_tpu.utils.checksum import checksum_device_table

    n = int(os.environ.get("CSVPLUS_BENCH_OPT_ROWS", 200_000))
    n_cust = 2_000
    reps = 3

    dim = DeviceTable.from_pylists(
        {
            "id": [f"c{i}" for i in range(n_cust)],
            "name": [f"name{i % 997}" for i in range(n_cust)],
            "region": [f"r{i % 7}" for i in range(n_cust)],
        },
        device="cpu",
    )
    cust_idx = cp.take(dim).index_on("id").sync()

    def fact(dist):
        rng = np.random.default_rng(7)
        if dist == "zipf":
            cust = zipf_probe_values(np.arange(n_cust), n, s=1.1, seed=7)
        else:
            cust = rng.integers(0, n_cust, n)
        arange = np.arange(n)
        return DeviceTable.from_pylists(
            {
                "cust_id": np.char.add("c", cust.astype(np.str_)).tolist(),
                "cat": np.char.add(
                    "k", (arange % 16).astype(np.str_)
                ).tolist(),
                "qty": (arange % 100).astype(np.str_).tolist(),
                # dead payload: projection pushdown drops these at the
                # scan; the join's materialize never gathers them
                "pad1": arange.astype(np.str_).tolist(),
                "pad2": np.char.add("x", arange.astype(np.str_)).tolist(),
                "pad3": ["payload"] * n,
            },
            device="cpu",
        )

    def chain(t):
        return P.SelectCols(
            P.Filter(
                P.Join(
                    P.MapExpr(P.Scan(t), SetValue("flag", "y")),
                    cust_idx,
                    ("cust_id",),
                ),
                Like({"cat": "k1"}),
            ),
            ("cust_id", "name", "qty", "flag"),
        )

    def timed(cache, pl):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = cache.execute(pl)
            best = min(best, time.perf_counter() - t0)
        return best, out

    def stage_seconds(root):
        """Marginal per-stage seconds via prefix execution: prefix k's
        best-of-2 wall time minus prefix k-1's.  Crude but honest, and
        exactly the shape ``diff_stage_tables`` wants."""
        nodes = list(P.linearize(root))
        rows, prev_t, prev_rows = [], 0.0, 0
        for k in range(len(nodes)):
            node = nodes[0]
            for stage in nodes[1 : k + 1]:
                node = dataclasses.replace(stage, child=node)
            best = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                out = execute_plan_view(node).materialize()
                best = min(best, time.perf_counter() - t0)
            rows.append(
                {
                    # op name, not stage_label: the rewrite PERMUTES
                    # positions, and the diff aligns rows by label —
                    # every op is unique in this chain, so the bare
                    # name lines Join up with Join across both legs
                    "stage": type(nodes[k]).__name__,
                    "seconds": round(max(best - prev_t, 0.0), 6),
                    "rows_in": prev_rows if k else out.nrows,
                    "rows_out": out.nrows,
                }
            )
            prev_t, prev_rows = best, out.nrows
        return rows

    record: dict = {"rows": n}
    stage_tables = {}
    recompiles = None
    for dist in ("uniform", "zipf"):
        t = fact(dist)
        pl = chain(t)
        os.environ["CSVPLUS_OPTIMIZE"] = "0"
        try:
            cache_off = PlanCache(size=4)
            cache_off.execute(pl)  # cold admit, unrewritten
        finally:
            os.environ.pop("CSVPLUS_OPTIMIZE", None)
        cache_on = PlanCache(size=4)
        cache_on.execute(pl)  # cold admit, optimizes + lowers
        exe = cache_on.executable_for(pl)
        kinds = {s[0] for s in (exe.recipe.steps if exe.recipe else ())}
        if kinds != {"permute", "drop_after_leaf"}:
            sys.stderr.write(
                f"bench[opt] FAIL({dist}): rewriter did not fire "
                f"(recipe steps {sorted(kinds)}, stats "
                f"{cache_on.stats()})\n"
            )
            return 1
        t_off, out_off = timed(cache_off, pl)
        with RecompileWatch() as watch:
            t_on, out_on = timed(cache_on, pl)
        # parity AFTER the watch: checksum kernels jit on first use
        if list(out_on.columns) != list(out_off.columns) or (
            checksum_device_table(out_on, positional=True)
            != checksum_device_table(out_off, positional=True)
        ):
            sys.stderr.write(
                f"bench[opt] FAIL({dist}): optimized output is not "
                f"bitwise-equal to the unrewritten plan's\n"
            )
            return 1
        watch.assert_zero(f"warm optimized serving ({dist})")
        recompiles = watch.delta()
        record[dist] = {
            "optimized_rows_per_sec_warm": round(n / t_on, 1),
            "unoptimized_rows_per_sec_warm": round(n / t_off, 1),
            "speedup": round(t_off / t_on, 3),
            "out_rows": out_on.nrows,
        }
        stage_tables[dist] = {
            "unoptimized": stage_seconds(pl),
            "optimized": stage_seconds(
                __import__(
                    "csvplus_tpu.analysis.rewrite", fromlist=["apply_recipe"]
                ).apply_recipe(pl, exe.recipe)
            ),
        }
    record.update(
        {
            "metric": "opt_chain_rows_per_sec_warm",
            "value": record["uniform"]["optimized_rows_per_sec_warm"],
            "unit": "rows/s",
            "applied_recipe_steps": sorted(kinds),
            "recompiles_warm": recompiles,
            **host_header(),
        }
    )
    print(json.dumps(record), flush=True)

    out_path = os.environ.get("CSVPLUS_BENCH_OPT_OUT")
    if out_path:
        artifact = dict(record)
        artifact["attribution_note"] = (
            "read the share columns: the rewrite moves the filter below "
            "the join, so downstream stages in leg B see ~1/16 the rows "
            "— their ns/row RISES (fixed dispatch overhead over fewer "
            "rows) even as their absolute seconds and share fall"
        )
        artifact["stage_tables"] = stage_tables
        artifact["stage_diff"] = {
            dist: diff_stage_tables(
                stage_tables[dist]["unoptimized"],
                stage_tables[dist]["optimized"],
            )
            for dist in stage_tables
        }
        artifact["stage_diff_text"] = {
            dist: format_diff(
                artifact["stage_diff"][dist], "unoptimized", "optimized"
            )
            for dist in stage_tables
        }
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, out_path)
        sys.stderr.write(f"bench[opt] artifact -> {out_path}\n")

    floor = 0.0
    floor_rows = None
    try:
        repo = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(repo, "bench_opt_floor.json")) as f:
            fl = json.load(f)
            floor = float(fl.get("opt_chain_rows_per_sec_warm", 0.0))
            floor_rows = fl.get("rows")
    except (OSError, ValueError):
        pass
    if floor and record["value"] < floor / 2:
        sys.stderr.write(
            f"bench[opt] REGRESSION: optimized chain {record['value']:,.0f}"
            f" rows/s is under half the floor ({floor:,.0f} rows/s at"
            f" {floor_rows or '?'} rows)\n"
        )
        return 1
    sys.stderr.write(
        f"bench[opt] ok: optimized {record['value']:,.0f} rows/s"
        f" (speedup {record['uniform']['speedup']:,.2f}x uniform,"
        f" {record['zipf']['speedup']:,.2f}x zipf; floor {floor:,.0f})"
        f" | bitwise parity both distributions, zero warm recompiles"
        f" (n={n})\n"
    )
    return 0


def _secondary_metrics(n_orders: int) -> None:
    """Informational numbers for the other BASELINE configs, to stderr
    (the driver contract is ONE json line on stdout)."""
    import tempfile

    import numpy as np

    from csvplus_tpu import from_file
    rng = np.random.default_rng(7)
    n = min(n_orders, 1_000_000)
    with tempfile.TemporaryDirectory() as td:
        path = f"{td}/orders.csv"
        with open(path, "w") as f:
            f.write("order_id,cust_id,qty\n")
            ids = rng.integers(0, 100_000, n)
            f.write(
                "".join(
                    f"{i},c{int(c)},{int(q)}\n"
                    for i, (c, q) in enumerate(
                        zip(ids, rng.integers(1, 101, n))
                    )
                )
            )
        # warm the dispatch path on a 2K-row slice so the tier times
        # ingest itself, not the process's first jax trace/compile
        wpath = f"{td}/warm.csv"
        with open(wpath, "w") as f:
            f.write("order_id,cust_id,qty\n")
            f.write("".join(f"{i},c{i % 97},{i % 9}\n" for i in range(2000)))
        from_file(wpath).on_device().plan.table.sync()
        t0 = time.perf_counter()
        src = from_file(path).on_device()
        # sync the ingested code arrays (async dispatch would stop the
        # clock before upload/encode completes) without materializing
        # a redundant copy of the table
        src.plan.table.sync()
        t_ingest = time.perf_counter() - t0
        t0 = time.perf_counter()
        idx = src.index_on("cust_id")
        idx.sync()  # the async device build must land in THIS timer
        t_index = time.perf_counter() - t0
        # BASELINE config 2's lookup half: point Find()s against the
        # device index (host-mirrored key search + range decode);
        # probe keys sampled from the generated ids so every lookup
        # is a guaranteed hit at any row count.  A short warmup pays
        # the one-time host mirror transfer outside the steady-state
        # rate (it is reported separately).
        lookups = 1000
        probes = [f"c{int(v)}" for v in ids[:lookups]]
        t0 = time.perf_counter()
        warm_hits = sum(len(idx.find(p).to_rows()) > 0 for p in probes[:10])
        t_mirror = time.perf_counter() - t0
        t0 = time.perf_counter()
        hits = sum(len(idx.find(p).to_rows()) > 0 for p in probes)
        t_find = time.perf_counter() - t0
        assert hits == len(probes) and warm_hits == 10
        # the batched column on the SAME 1M-row big-index shape:
        # one vectorized bounds pass + one amortized decode for 10K
        # probes (the find_many engine's headline tier)
        from csvplus_tpu import to_rows_many

        many = min(10_000, n)
        many_probes = [f"c{int(v)}" for v in ids[:many]]
        t0 = time.perf_counter()
        groups = to_rows_many(idx.find_many(many_probes))
        t_find_many = time.perf_counter() - t0
        assert sum(1 for g in groups if g) == many
        t0 = time.perf_counter()
        idx.resolve_duplicates("first")
        _ = len(idx)
        t_dedup = time.perf_counter() - t0
    sys.stderr.write(
        f"bench[secondary]: ingest {n / t_ingest:,.0f} rows/s | "
        f"index build {n / t_index:,.0f} rows/s | "
        f"device find {lookups / t_find:,.0f} lookups/s "
        f"(one-time mirror {t_mirror * 1000:,.0f}ms) | "
        f"device find_many {many / t_find_many:,.0f} lookups/s "
        f"({many} probes batched) | "
        f"policy dedup {n / t_dedup:,.0f} rows/s (n={n})\n"
    )


if __name__ == "__main__":
    # every mode but the default is a CPU correctness gate; the Makefile
    # recipes pass JAX_PLATFORMS=cpu, and the mesh modes re-exec
    # themselves onto 8 simulated CPU devices before JAX is touched
    _MODES = {
        "--micro-lookup": _micro_lookup,
        "--bench-mesh": _bench_mesh,
        "--bench-ingest": _bench_ingest,
        "--trace-smoke": _trace_smoke,
        "--obs-smoke": _obs_smoke,
        "--bench-opt": _bench_opt,
        "--skew-smoke": _skew_smoke,
        "--multiway-smoke": _multiway_smoke,
        "--fuse-smoke": _fuse_smoke,
    }
    for _flag, _mode in _MODES.items():
        if _flag in sys.argv:
            sys.exit(_mode())
    sys.exit(main())
