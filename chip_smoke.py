"""chip_smoke.py — does the system still start on the chip?

One process drives the main path once, through the entry points a user
calls, at a deployment's size: CSV bytes -> ingest -> 3-way join ->
filter/map/join/sink -> 20M-key index -> served lookups and durable
appends -> lookup join; and, on a host with >= 4 chips, the sharded
ingest, join, sample-sort and all_to_all probe again with ``shards=4``.

    python chip_smoke.py [--rows N] [--seed S]

Every leg is checked against a plain reference outside any timed span:
values computed from the generator's own numpy arrays on the full set,
and the host executor (the row-dict path) on a prefix by positional
checksum.  It exits non-zero when JAX's default backend is not a TPU,
when the native scanner cannot be built, when any array of any leg sits
on another platform, when a leg raises or when a comparison differs.
No leg is wrapped in a handler that lets the run continue.

``--allow-cpu`` is the rehearsal mode for a machine without a chip: the
caller passes it, the script never decides it.  Seconds printed here
are set-up facts of one run, not a benchmark.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from contextlib import contextmanager

import numpy as np

N_CUST = 100_000
N_PROD = 1_000
FIRST = (b"Amelia", b"Olivia", b"Emily", b"Ava", b"Isla", b"Oliver", b"Jack",
         b"Harry", b"Jacob", b"Charlie")
LAST = (b"Smith", b"Jones", b"Taylor", b"Williams", b"Brown", b"Davies", b"Evans",
        b"Wilson", b"Thomas", b"Roberts", b"Johnson", b"Lewis")
REASONS = (
    b"damaged", b"late", b"wrong-item", b"no-longer-needed",
    b"defective", b"duplicate", b"gift", b"other",
)
PREFIX_ROWS = 1_000_000  # host-executor reference prefix
FILTER = {"prod_id": "p7", "qty": "3"}  # two columns: the fused mask kernel
N_SHARDS = 4


class SmokeFailure(Exception):
    """A comparison differed or an invariant of the run did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# data: made from --seed, in bulk, by code that shares nothing with the
# engine under test


def _ndigits(v: np.ndarray, width: int) -> np.ndarray:
    """Decimal digit count of each nonnegative int64 in *v* (< 10**width)."""
    ndig = np.ones(v.shape, dtype=np.int64)
    for k in range(1, width):
        ndig += v >= 10**k
    return ndig


def _digits(v: np.ndarray, width: int) -> np.ndarray:
    """uint8[len(v), width]: canonical decimal digits of nonnegative *v*,
    left-aligned, 0 where the number has fewer digits."""
    v = v.astype(np.int64)
    ndig = _ndigits(v, width)
    pow10 = 10 ** np.arange(width, dtype=np.int64)
    out = np.zeros((v.shape[0], width), dtype=np.uint8)
    for j in range(width):
        p = ndig - 1 - j
        d = (v // pow10[np.maximum(p, 0)]) % 10
        out[:, j] = np.where(p >= 0, d + 48, 0)
    return out


def csv_lines(fields) -> bytes:
    """CSV body for one block of rows.  *fields*: per column either
    ``(prefix bytes, int array)`` or a fixed-width uint8 matrix whose 0
    bytes are padding.  Builds one zero-padded byte matrix and drops
    the padding in a single pass."""
    parts = []
    for i, f in enumerate(fields):
        if isinstance(f, tuple):
            prefix, v = f
            width = len(str(int(v.max()))) if v.size else 1
            if prefix:
                parts.append(
                    np.broadcast_to(
                        np.frombuffer(prefix, dtype=np.uint8), (v.shape[0], len(prefix))
                    )
                )
            parts.append(_digits(v, width))
        else:
            parts.append(f)
        sep = b"," if i < len(fields) - 1 else b"\n"
        parts.append(np.full((parts[-1].shape[0], 1), sep[0], dtype=np.uint8))
    mat = np.concatenate(parts, axis=1)
    return mat[mat != 0].tobytes()


class Data:
    """The generated deployment: numpy arrays (the reference) and the
    CSV files written from them."""

    def __init__(self, rows: int, seed: int, root: str):
        rng = np.random.default_rng(seed)
        self.n = int(rows)
        self.n_ret = max(self.n // 10, 1)
        self.prefix_n = min(PREFIX_ROWS, self.n)
        # order ids are unique and shuffled: the index build sorts for real
        self.oid = rng.permutation(self.n).astype(np.int32)
        self.cust = rng.integers(0, N_CUST, self.n, dtype=np.int32)
        self.prod = rng.integers(0, N_PROD, self.n, dtype=np.int32)
        self.qty = rng.integers(1, 101, self.n, dtype=np.int32)
        # ~1% of returns name an order that does not exist (probe misses)
        self.ret_oid = rng.integers(0, self.n + self.n // 100 + 1, self.n_ret).astype(
            np.int32
        )
        self.ret_reason = rng.integers(0, len(REASONS), self.n_ret).astype(np.int32)
        self.row_of = np.empty(self.n, dtype=np.int32)  # order id -> row
        self.row_of[self.oid] = np.arange(self.n, dtype=np.int32)
        # ~10K distinct names: a dictionary column, not a typed lane
        self.cust_name = np.array(
            [
                b"%s %s %d" % (FIRST[i % 10], LAST[i % 12], i % 83)
                for i in range(N_CUST)
            ],
            dtype="S",
        )
        self.prod_name = np.array([b"prod%d" % i for i in range(N_PROD)], dtype="S")
        self.prod_price = np.array(
            [b"%.2f" % ((i % 9900) / 100 + 0.99) for i in range(N_PROD)], dtype="S"
        )
        self.paths = {
            k: os.path.join(root, f"{k}.csv")
            for k in ("orders", "orders_prefix", "customers", "products", "returns")
        }
        self._write()

    def _write(self) -> None:
        step = 2_000_000
        with open(self.paths["orders"], "wb") as f, open(
            self.paths["orders_prefix"], "wb"
        ) as fp:
            head = b"order_id,cust_id,prod_id,qty\n"
            f.write(head)
            fp.write(head)
            for lo in range(0, self.n, step):
                hi = min(lo + step, self.n)
                body = csv_lines(
                    [
                        (b"o", self.oid[lo:hi]),
                        (b"c", self.cust[lo:hi]),
                        (b"p", self.prod[lo:hi]),
                        (b"", self.qty[lo:hi]),
                    ]
                )
                f.write(body)
                if lo < self.prefix_n:
                    k = min(hi, self.prefix_n) - lo
                    cut = len(body) if k == hi - lo else _nth_newline(body, k)
                    fp.write(body[:cut])
        with open(self.paths["customers"], "wb") as f:
            f.write(b"id,name\n")
            f.write(
                b"".join(b"c%d,%s\n" % (i, n) for i, n in enumerate(self.cust_name.tolist()))
            )
        with open(self.paths["products"], "wb") as f:
            f.write(b"prod_id,product,price\n")
            f.write(
                b"".join(
                    b"p%d,%s,%s\n" % (i, n, p)
                    for i, (n, p) in enumerate(
                        zip(self.prod_name.tolist(), self.prod_price.tolist())
                    )
                )
            )
        rw = max(len(r) for r in REASONS)
        rtab = np.zeros((len(REASONS), rw), dtype=np.uint8)
        for i, r in enumerate(REASONS):
            rtab[i, : len(r)] = np.frombuffer(r, dtype=np.uint8)
        with open(self.paths["returns"], "wb") as f:
            f.write(b"order_id,reason\n")
            for lo in range(0, self.n_ret, step):
                hi = min(lo + step, self.n_ret)
                f.write(
                    csv_lines([(b"o", self.ret_oid[lo:hi]), rtab[self.ret_reason[lo:hi]]])
                )


def _nth_newline(body: bytes, k: int) -> int:
    """Offset just past the k-th newline of *body*."""
    nl = np.flatnonzero(np.frombuffer(body, dtype=np.uint8) == 10)
    return int(nl[k - 1]) + 1


# ---------------------------------------------------------------------------
# reading results back for comparison (host side, outside timed spans)


def col_ints(col, n: int, prefix: bytes) -> np.ndarray:
    """First *n* rows of *col* as ints, given every cell is
    ``prefix + decimal``; works for a typed lane or a dictionary column."""
    if getattr(col, "kind", "str") == "int":
        check(col.prefix == prefix, f"typed prefix {col.prefix!r} != {prefix!r}")
        return np.asarray(col.values)[:n]
    d = np.asarray(col.dictionary)
    w, p = d.dtype.itemsize, len(prefix)
    mat = np.frombuffer(d.tobytes(), dtype=np.uint8).reshape(d.shape[0], w)
    check(bool((mat[:, :p] == np.frombuffer(prefix, np.uint8)).all()), "prefix differs")
    vals = np.ascontiguousarray(mat[:, p:]).view(f"S{w - p}").reshape(-1).astype(np.int64)
    return vals[np.asarray(col.codes)[:n]]


def col_bytes(col, n: int) -> np.ndarray:
    """First *n* rows of a dictionary column as an 'S' array."""
    return np.asarray(col.dictionary)[np.asarray(col.codes)[:n]]


def expect_columns(table, n: int, want: dict, what: str) -> None:
    """Every column of *table* equals the reference on all *n* rows.
    *want*: name -> (prefix, int array) or an 'S' array."""
    check(table.nrows == n, f"{what}: {table.nrows} rows, expected {n}")
    check(sorted(table.columns) == sorted(want), f"{what}: columns {sorted(table.columns)}")
    for name, w in want.items():
        col = table.columns[name]
        if isinstance(w, tuple):
            ok = np.array_equal(col_ints(col, n, w[0]), w[1])
        else:
            ok = np.array_equal(col_bytes(col, n), w)
        check(ok, f"{what}: column {name!r} differs from the generator's arrays")


def column_kinds(table) -> dict:
    return {
        name: "int-lane" if getattr(c, "kind", "str") == "int" else "dictionary"
        for name, c in table.columns.items()
    }


def placed_on(table, platform: str, what: str, n_devices=None) -> None:
    """Every column's storage sits on devices of *platform* (exactly
    *n_devices* of them when given) — nothing quietly stayed on, or fell
    back to, the host."""
    for name, c in table.columns.items():
        devs = c.storage.sharding.device_set
        check(
            all(d.platform == platform for d in devs)
            and n_devices in (None, len(devs)),
            f"{what}: column {name!r} sits on {sorted(str(d) for d in devs)}, "
            f"expected {n_devices or 'only'} {platform} device(s)",
        )


def string_order(n: int) -> np.ndarray:
    """0..n-1 in the order of their decimal strings ("1" < "10" < "2"):
    the key order of an index over ``prefix + number`` cells."""
    v = np.arange(n, dtype=np.int64)
    width = len(str(max(n - 1, 1)))
    ndig = _ndigits(v, width)
    return np.lexsort((ndig, v * 10 ** (width - ndig))).astype(np.int32)


# ---------------------------------------------------------------------------
# the run


class Run:
    """State one smoke run threads through its legs."""

    def __init__(self, data: Data, root: str, platform: str, out=sys.stdout):
        from csvplus_tpu.utils.observe import telemetry

        self.data = data
        self.root = root
        self.platform = platform
        self.out = out
        self.telemetry = telemetry
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.sums: dict = {}  # one-chip checksums leg 6 compares against
        self._mark = 0  # telemetry records before the current leg
        self._host_indexes = None

    def say(self, line: str) -> None:
        print(line, file=self.out, flush=True)

    @contextmanager
    def leg(self, name: str):
        """Wall and compile seconds of one leg.  ``compile`` is what jax
        spent tracing, lowering and compiling (or fetching from the
        persistent cache) inside the span."""
        self._mark = len(self.telemetry.records)
        c0, h0, m0, t0 = self.compile_s, self.cache_hits, self.cache_misses, time.perf_counter()
        info: dict = {}
        yield info
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        demotes = [r for r in self.records() if r.stage == "typed:demote"]
        if demotes:
            # a typed lane re-encoded as a dictionary column: the slow path
            info["demoted"] = (
                f"{len(demotes)}col/{sum(r.seconds for r in demotes):.2f}s"
            )
        extra = "".join(f" {k}={v}" for k, v in info.items())
        self.say(
            f"{name}: wall={wall:.2f}s compile={comp:.2f}s run={max(wall - comp, 0.0):.2f}s"
            f" cache_hits={self.cache_hits - h0} cache_misses={self.cache_misses - m0}{extra}"
        )

    def records(self) -> list:
        """Stage records of the current leg so far."""
        return self.telemetry.records[self._mark :]

    def plan_stages(self) -> str:
        """The plan nodes the executor ran in the current leg."""
        return "+".join(r.stage for r in self.records() if r.stage[:1].isupper())

    def host_indexes(self):
        """The dimensions' unique indexes built by the host executor."""
        if self._host_indexes is None:
            from csvplus_tpu import FromFile, Take

            paths = self.data.paths
            self._host_indexes = (
                Take(FromFile(paths["customers"])).UniqueIndexOn("id"),
                Take(FromFile(paths["products"])).UniqueIndexOn("prod_id"),
            )
        return self._host_indexes


@contextmanager
def listening_for_compiles(run: Run):
    """Feed jax's compile-duration and cache hit/miss events to *run*."""
    import jax.monitoring as mon

    def on_duration(event: str, seconds: float, **kw) -> None:
        if event.startswith("/jax/core/compile/"):
            run.compile_s += seconds

    def on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            run.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            run.cache_misses += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)
    try:
        yield
    finally:
        mon.unregister_event_duration_listener(on_duration)
        mon.unregister_event_listener(on_event)


def build_scanner() -> str:
    """Remove any scanner binary lying in the tree and build from
    scanner.cpp; a failure raises (ingest must not drop to the Python
    parser unannounced)."""
    import csvplus_tpu.native as native_pkg

    here = os.path.dirname(os.path.abspath(native_pkg.__file__))
    for stale in glob.glob(os.path.join(here, "*.so")) + glob.glob(
        os.path.join(here, "*.so.*")
    ):
        os.remove(stale)
    from csvplus_tpu.native import scanner

    so = scanner._build()  # g++ on scanner.cpp; raises when that fails
    scanner._load()
    return so


def ingest(run: Run, key: str, shards=None):
    """FromFile(...).OnDevice(<platform>): returns (source, tier stage)."""
    from csvplus_tpu import FromFile

    mark = len(run.telemetry.records)
    src = FromFile(run.data.paths[key]).OnDevice(run.platform, shards=shards)
    src.plan.table.sync()
    recs = run.telemetry.records[mark:]
    tiers = [
        r.stage
        for r in recs
        if r.stage
        in ("ingest:streamed", "ingest:device-parsed", "ingest:native-encoded", "ingest:python")
    ]
    check(len(tiers) == 1, f"{key}: ingest tiers recorded: {tiers}")
    check(tiers[0] != "ingest:python", f"{key}: ingest fell to the Python parser")
    workers = [r.extra.get("workers") for r in recs if r.stage == "ingest:encode"]
    return src, tiers[0], (workers[-1] if workers else None)


def leg1_ingest(run: Run, shards=None):
    d = run.data
    n_dev = shards or 1
    tag = f"leg1 ingest shards={n_dev}"
    with run.leg(tag) as info:
        orders, tier, k = ingest(run, "orders", shards)
        info["rows"] = orders.plan.table.nrows
        info["orders"] = tier
        info["K"] = k
        cust, ctier, _ = ingest(run, "customers")
        prod, ptier, _ = ingest(run, "products")
        info["customers"] = ctier
        info["products"] = ptier
    for name, src in (("orders", orders), ("customers", cust), ("products", prod)):
        run.say(f"  {name}: {column_kinds(src.plan.table)}")
    t = orders.plan.table
    if shards:
        check(getattr(t, "_pre_sharded", False), "sharded ingest did not land pre-sharded")
    placed_on(t, run.platform, "orders", n_dev)
    placed_on(cust.plan.table, run.platform, "customers", 1)
    placed_on(prod.plan.table, run.platform, "products", 1)
    expect_columns(
        t, d.n,
        {"order_id": (b"o", d.oid), "cust_id": (b"c", d.cust),
         "prod_id": (b"p", d.prod), "qty": (b"", d.qty)},
        "orders",
    )
    expect_columns(
        cust.plan.table, N_CUST,
        {"id": (b"c", np.arange(N_CUST)), "name": d.cust_name}, "customers",
    )
    expect_columns(
        prod.plan.table, N_PROD,
        {"prod_id": (b"p", np.arange(N_PROD)), "product": d.prod_name,
         "price": d.prod_price}, "products",
    )
    from csvplus_tpu.obs.memory import device_memory_stats

    stats = device_memory_stats()
    if stats:
        in_use = sum(s.get("bytes_in_use", 0) for s in stats.values())
        resident = 4 * 4 * d.n
        run.say(
            f"  device memory in use after ingest: {in_use:,} bytes "
            f"(resident orders table: {resident:,})"
        )
        check(in_use >= resident, "device memory in use is below the resident table's size")
    else:
        run.say("  device memory in use after ingest: not reported by this backend")
    return orders, cust, prod


def leg2_join(run: Run, orders, cust, prod, shards=None):
    """orders.Join(cust_idx, "cust_id").Join(prod_idx) through PlanCache:
    verifier -> rewriter -> executor, cold then warm."""
    from csvplus_tpu.obs.recompile import RecompileWatch
    from csvplus_tpu.serve.plancache import PlanCache
    from csvplus_tpu.utils.checksum import checksum_device_table, checksum_host_rows

    d = run.data
    n_dev = shards or 1
    with run.leg(f"leg2 index-build dims shards={n_dev}"):
        cust_idx = cust.UniqueIndexOn("id").sync()
        prod_idx = prod.UniqueIndexOn("prod_id").sync()
    plan = orders.Join(cust_idx, "cust_id").Join(prod_idx).plan
    cache = PlanCache()
    with run.leg(f"leg2 join cold shards={n_dev}") as info:
        table = cache.execute(plan).sync()
        info["rows_out"] = table.nrows
        info["stages"] = run.plan_stages()
    cols = sorted(table.columns)
    with run.leg(f"leg2 join warm shards={n_dev}") as info:
        with RecompileWatch(cache) as watch:
            warm = cache.execute(plan).sync()
        watch.assert_zero("the warm 3-way join")
        info["rows_out"] = warm.nrows
        info["lowered"] = 0
    warm_sums = checksum_device_table(warm, cols, positional=True)
    del warm  # two 8-column results need not both stay resident
    stats = cache.stats()
    check(stats["optimize_failed"] == 0, f"the rewriter failed: {stats}")
    run.say(f"  plan cache: {stats}")
    placed_on(table, run.platform, "3-way join result")
    want = {
        "order_id": (b"o", d.oid), "cust_id": (b"c", d.cust),
        "prod_id": (b"p", d.prod), "qty": (b"", d.qty),
        "id": (b"c", d.cust), "name": d.cust_name[d.cust],
        "product": d.prod_name[d.prod], "price": d.prod_price[d.prod],
    }
    expect_columns(table, d.n, want, "3-way join")
    full = checksum_device_table(table, cols, positional=True)
    check(warm_sums == full, "warm join result differs from the cold one")
    if shards:
        check(full == run.sums["join"], "sharded join differs bitwise from the one-chip leg")
        return cust_idx, prod_idx
    run.sums["join"] = full
    # the host executor on the prefix, by positional checksum
    from csvplus_tpu import FromFile, Take

    h_cust, h_prod = run.host_indexes()
    host_rows = (
        Take(FromFile(d.paths["orders_prefix"])).Join(h_cust, "cust_id").Join(h_prod).ToRows()
    )
    check(len(host_rows) == d.prefix_n, "host executor row count on the prefix")
    check(
        checksum_device_table(table, cols, limit=d.prefix_n, positional=True)
        == checksum_host_rows(host_rows, cols, positional=True),
        "3-way join differs from the host executor on the prefix",
    )
    run.say(
        f"  3-way join: {d.n:,} rows equal the generator's arrays; first "
        f"{d.prefix_n:,} equal the host executor by positional checksum"
    )
    return cust_idx, prod_idx


def leg3_filter_sink(run: Run, orders, cust_idx):
    """Filter(Like{2 cols}) -> Map(SetValue) -> Join -> SelectColumns ->
    ToCsvFile: the fused probe pass, the Pallas mask compiled, the sink."""
    from csvplus_tpu import FromFile, Like, SetValue, Take
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.ops import pallas_mask
    from csvplus_tpu.serve.plancache import PlanCache

    d = run.data
    out_cols = ("order_id", "cust_id", "name", "qty", "flag")

    def chain(src, idx):
        return (
            src.Filter(Like(FILTER)).Map(SetValue("flag", "y"))
            .Join(idx, "cust_id").SelectColumns(*out_cols)
        )

    fused_path = os.path.join(run.root, "leg3_fused.csv")
    staged_path = os.path.join(run.root, "leg3_staged.csv")
    host_path = os.path.join(run.root, "leg3_host.csv")
    interpreted = pallas_mask._use_interpret()
    check(
        interpreted == (run.platform != "tpu"),
        "the Pallas mask would be interpreted on a TPU",
    )
    calls0 = pallas_mask._fused_mask_call._cache_size()
    cache = PlanCache()
    with run.leg("leg3 filter-map-join-select-csv (rewritten plan)") as info:
        table = cache.execute(chain(orders, cust_idx).plan)
        source_from_table(table).ToCsvFile(fused_path, *out_cols)
        info["rows_out"] = table.nrows
        info["stages"] = run.plan_stages()
    with run.leg("leg3 the same chain straight to ToCsvFile"):
        chain(orders, cust_idx).ToCsvFile(staged_path, *out_cols)
    check(
        pallas_mask._fused_mask_call._cache_size() > calls0,
        "the fused mask kernel was never lowered",
    )
    stats = cache.stats()
    check(stats["optimize_failed"] == 0, f"the rewriter failed: {stats}")
    check(stats["fused_chains"] >= 1, f"the probe pass was not fused: {stats}")
    placed_on(table, run.platform, "filtered join result")
    # full set: the bytes, from the generator's arrays
    hit = np.flatnonzero(
        (d.prod == int(FILTER["prod_id"][1:])) & (d.qty == int(FILTER["qty"]))
    )
    want = b",".join(c.encode() for c in out_cols) + b"\n" + b"".join(
        b"o%d,c%d,%s,%d,y\n" % (d.oid[i], d.cust[i], d.cust_name[d.cust[i]], d.qty[i])
        for i in hit.tolist()
    )
    with open(fused_path, "rb") as f:
        got = f.read()
    check(got == want, "fused chain's CSV differs from the generator's arrays")
    with open(staged_path, "rb") as f:
        check(f.read() == want, "staged chain's CSV differs from the generator's arrays")
    # prefix: byte-equal to the host executor running the same chain
    h_cust, _ = run.host_indexes()
    chain(Take(FromFile(d.paths["orders_prefix"])), h_cust).ToCsvFile(host_path, *out_cols)
    with open(host_path, "rb") as f:
        host = f.read()
    check(
        got.startswith(host) and host.count(b"\n") == 1 + int((hit < d.prefix_n).sum()),
        "CSV prefix differs from the host executor's bytes",
    )
    run.say(
        f"  filter chain: {hit.size:,} rows, {len(got):,} bytes equal the generator's "
        f"arrays; the prefix's {host.count(b'\n') - 1} rows equal the host executor's "
        f"bytes; mask kernel {'interpreted' if interpreted else 'compiled'}"
    )


def _order_row(d: Data, k: int) -> dict:
    i = int(d.row_of[k])
    return {
        "order_id": f"o{k}", "cust_id": f"c{d.cust[i]}",
        "prod_id": f"p{d.prod[i]}", "qty": str(d.qty[i]),
    }


def leg4_index_and_serving(run: Run, orders, cust, shards=None):
    """UniqueIndexOn(order_id) over every order, find_many, then a
    LookupServer over it from four threads, and a durable MutableIndex
    over customers behind the same server."""
    from csvplus_tpu import to_rows_many
    from csvplus_tpu.ops.join import DeviceIndex
    from csvplus_tpu.utils.checksum import checksum_device_table

    d = run.data
    n_dev = shards or 1
    with run.leg(f"leg4 UniqueIndexOn(order_id) shards={n_dev}") as info:
        order_idx = orders.UniqueIndexOn("order_id").sync()
        info["keys"] = len(order_idx)
        info["dsort"] = any(r.stage == "dsort" for r in run.records())
        if shards:
            check(info["dsort"], "the distributed sample-sort did not run")
    itab = order_idx._impl.dev.table
    placed_on(itab, run.platform, "order index")
    srt = string_order(d.n)
    rows = d.row_of[srt]  # source row of each index slot, in key (string) order
    expect_columns(
        itab, d.n,
        {"order_id": (b"o", srt), "cust_id": (b"c", d.cust[rows]),
         "prod_id": (b"p", d.prod[rows]), "qty": (b"", d.qty[rows])},
        "order index",
    )
    full = checksum_device_table(itab, sorted(itab.columns), positional=True)
    if shards:
        check(full == run.sums["index"], "sharded index differs bitwise from the one-chip leg")
        return order_idx
    run.sums["index"] = full
    mirrored = d.n <= DeviceIndex.POINT_MIRROR_MAX_KEYS
    rng = np.random.default_rng(d.n)
    keys = rng.integers(0, d.n, 3000).tolist() + [d.n + 5, d.n + 6]
    with run.leg("leg4 find_many") as info:
        got = to_rows_many(order_idx.find_many([f"o{k}" for k in keys]))
        info["probes"] = len(keys)
        info["bounds"] = "host-mirror" if mirrored else "device-searchsorted"
    want = [[_order_row(d, k)] if k < d.n else [] for k in keys]
    check([[dict(r) for r in g] for g in got] == want, "find_many differs from the generator")

    # served lookups + durable appends
    from csvplus_tpu.serve import LookupServer
    from csvplus_tpu.storage import MutableIndex

    wal_dir = os.path.join(run.root, "customers_wal")
    with run.leg("leg4 MutableIndex.create(customers)"):
        cust_mi = MutableIndex.create(cust, ["id"], directory=wal_dir)
    served = rng.integers(0, d.n, 400).tolist()
    answers: dict = {}
    errors: list = []
    batches = [
        [{"id": f"c{N_CUST + 100 * b + j}", "name": f"late{b}-{j}"} for j in range(100)]
        for b in range(2)
    ]
    with run.leg("leg4 serve") as info, LookupServer(
        order_idx, indexes={"customers": cust_mi}
    ) as srv:

        def client(part):
            try:
                for k in part:
                    answers[k] = srv.lookup(f"o{k}")
            except Exception as e:  # re-raised below, on the main thread
                errors.append(e)

        threads = [
            threading.Thread(target=client, args=(served[i::4],)) for i in range(4)
        ]
        for t in threads:
            t.start()
        acked = [srv.append(b, index="customers") for b in batches]
        for t in threads:
            t.join(600)
            check(not t.is_alive(), "a lookup client did not finish")
        if errors:
            raise errors[0]
        read_back = {
            r["id"]: srv.lookup(r["id"], index="customers") for b in batches for r in b
        }
        snap = srv.snapshot()
        info["lookups"] = len(served) + len(read_back)
        info["appended"] = sum(acked)
    check(acked == [100, 100], f"append acks: {acked}")
    for k in served:
        check([dict(r) for r in answers[k]] == [_order_row(d, k)], f"served lookup o{k} differs")
    for b in batches:
        for r in b:
            check([dict(x) for x in read_back[r["id"]]] == [r], f"acked row {r['id']} not read back")
    check(
        snap["degraded"] == 0 and snap["failed"] == 0 and snap["retried"] == 0,
        f"the server degraded, failed or retried: "
        f"{ {k: snap[k] for k in ('degraded', 'failed', 'retried')} }",
    )
    wal = snap["by_index"]["customers"]
    check(wal.get("wal_fsyncs", 0) >= 2, f"sync=always made fewer fsyncs than acks: {wal}")
    # durability: every acknowledged row is there after close + recovery
    cust_mi.close()
    reopened = MutableIndex.open(wal_dir)
    for b in batches:
        for r in b:
            check(
                [dict(x) for x in reopened.find_rows([r["id"]])] == [r],
                f"acked row {r['id']} lost across recovery",
            )
    check(
        [dict(x) for x in reopened.find_rows(["c7"])]
        == [{"id": "c7", "name": d.cust_name[7].decode()}],
        "base row lost across recovery",
    )
    reopened.close()
    run.say(
        f"  index: {d.n:,} keys equal the generator; {len(keys)} find_many + "
        f"{len(served)} served lookups right; degraded=0; 200 acked rows read back "
        f"live and after recovery ({wal.get('wal_fsyncs')} fsyncs, "
        f"{reopened.recovered_records} WAL records replayed)"
    )
    return order_idx


def leg5_lookup_join(run: Run, order_idx, shards=None):
    """returns.Join(order_idx): a lookup join against the big index."""
    from csvplus_tpu.serve.plancache import PlanCache
    from csvplus_tpu.utils.checksum import checksum_device_table

    d = run.data
    n_dev = shards or 1
    returns, tier, _ = ingest(run, "returns", shards)
    cache = PlanCache()
    with run.leg(f"leg5 returns.Join(order_idx) shards={n_dev}") as info:
        table = cache.execute(returns.Join(order_idx).plan).sync()
        info["rows_in"] = d.n_ret
        info["rows_out"] = table.nrows
        info["returns"] = tier
        info["all_to_all"] = any(r.stage == "join:all_to_all" for r in run.records())
    if shards:
        check(info["all_to_all"], "the all_to_all probe did not run")
    check(cache.stats()["optimize_failed"] == 0, "the rewriter failed")
    placed_on(table, run.platform, "lookup join result")
    hit = np.flatnonzero(d.ret_oid < d.n)
    rows = d.row_of[d.ret_oid[hit]]
    reasons = np.array(REASONS, dtype="S")
    expect_columns(
        table, hit.size,
        {"order_id": (b"o", d.ret_oid[hit]), "reason": reasons[d.ret_reason[hit]],
         "cust_id": (b"c", d.cust[rows]), "prod_id": (b"p", d.prod[rows]),
         "qty": (b"", d.qty[rows])},
        "lookup join",
    )
    full = checksum_device_table(table, sorted(table.columns), positional=True)
    if shards:
        check(full == run.sums["lookup"], "sharded lookup join differs bitwise from one chip")
    else:
        run.sums["lookup"] = full
    run.say(f"  lookup join: {hit.size:,} of {d.n_ret:,} returns matched, all equal the generator")


def run_legs(run: Run, n_devices: int) -> None:
    orders, cust, prod = leg1_ingest(run)
    cust_idx, _ = leg2_join(run, orders, cust, prod)
    leg3_filter_sink(run, orders, cust_idx)
    order_idx = leg4_index_and_serving(run, orders, cust)
    leg5_lookup_join(run, order_idx)
    if n_devices >= N_SHARDS:
        del orders, order_idx
        run.say(f"leg6: the sharded path over {N_SHARDS} of {n_devices} devices")
        orders, cust, prod = leg1_ingest(run, shards=N_SHARDS)
        leg2_join(run, orders, cust, prod, shards=N_SHARDS)
        order_idx = leg4_index_and_serving(run, orders, cust, shards=N_SHARDS)
        leg5_lookup_join(run, order_idx, shards=N_SHARDS)
    else:
        run.say(f"leg6: not run ({n_devices} device(s); needs {N_SHARDS})")


def main(argv=None, out=sys.stdout) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=20_000_000, help="orders rows")
    ap.add_argument("--seed", type=int, default=20160914)
    ap.add_argument(
        "--allow-cpu", action="store_true",
        help="rehearse on a machine without a chip (never the default)",
    )
    args = ap.parse_args(argv)

    import jax
    import jaxlib

    from csvplus_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.allow_cpu:
        print(
            f"chip_smoke: JAX's default backend is {platform!r}, not a TPU; "
            "nothing was run (pass --allow-cpu only to rehearse)",
            file=sys.stderr,
        )
        return 2
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    t_all = time.perf_counter()
    print(
        f"chip_smoke: platform={platform} device_kind={devices[0].device_kind} "
        f"devices={len(devices)} jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu} host_cpus={os.cpu_count()} rows={args.rows:,} seed={args.seed}",
        file=out, flush=True,
    )
    print(f"chip_smoke: compile cache at {cache_dir or 'none (CPU backend)'}", file=out, flush=True)

    t0 = time.perf_counter()
    so = build_scanner()
    print(f"setup: native scanner built from scanner.cpp in {time.perf_counter() - t0:.1f}s ({so})",
          file=out, flush=True)
    root = tempfile.mkdtemp(prefix="csvplus_smoke_")
    try:
        t0 = time.perf_counter()
        data = Data(args.rows, args.seed, root)
        size = os.path.getsize(data.paths["orders"])
        print(
            f"setup: generated {args.rows:,} orders ({size / 1e6:,.0f} MB), "
            f"{N_CUST:,} customers, {N_PROD:,} products, {data.n_ret:,} returns "
            f"in {time.perf_counter() - t0:.1f}s under {root}",
            file=out, flush=True,
        )
        run = Run(data, root, platform, out)
        with listening_for_compiles(run), run.telemetry.collect():
            run_legs(run, len(devices))
            host_sync = run.telemetry.host_sync_elements
    finally:
        shutil.rmtree(root, ignore_errors=True)
    print(
        f"chip_smoke: all legs passed in {time.perf_counter() - t_all:.1f}s; "
        f"compile={run.compile_s:.1f}s cache_hits={run.cache_hits} "
        f"cache_misses={run.cache_misses} host_sync_elements={host_sync}",
        file=out, flush=True,
    )
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": platform,
                    "kind": devices[0].device_kind,
                    "count": len(devices),
                },
            }
        ),
        file=out, flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
