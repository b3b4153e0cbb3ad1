"""Upstream's corpus (README.md and csvplus_test.go): orders(cust_id,
prod_id, qty, ts), people(id, name, surname), stock(prod_id, product,
price), as numpy arrays (the reference) and the CSV files written from
them.  Copied from ``chip_smoke.py`` (which passed on the
chip in PR 21) so that the yardstick does not depend on a file a later
PR may edit; it shares nothing with the engine under test.

Every array length is a function of the configuration's ``tables``; the
seed moves values and their order only.  Where a count would follow the
draw (the rows the ``filter`` matches, the lookup keys that do not
exist) the count is fixed by the configuration and the seed draws the
positions.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

FIRST = (b"Amelia", b"Olivia", b"Emily", b"Ava", b"Isla", b"Oliver", b"Jack",
         b"Harry", b"Jacob", b"Charlie")
LAST = (b"Smith", b"Jones", b"Taylor", b"Williams", b"Brown", b"Davies", b"Evans",
        b"Wilson", b"Thomas", b"Roberts", b"Johnson", b"Lewis")

ORDERS_HEAD = b"cust_id,prod_id,qty,ts\n"
PEOPLE_HEAD = b"id,name,surname\n"
TS_YEAR = 2016  # the README's example order is dated 2016-09-14T08:48:22+01:00
TS_ZONE = b"+01:00"
TS_BYTES = 25
BLOCK_ROWS = 1_000_000
WRITE_THREADS = 6


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


def _nth_newline(body: bytes, k: int) -> int:
    """Offset just past the k-th newline of *body*."""
    nl = np.flatnonzero(np.frombuffer(body, dtype=np.uint8) == 10)
    return int(nl[k - 1]) + 1


def _classes(lo: int, hi: int):
    """[(digits, first, past-last)] for the values lo..hi-1."""
    out, k = [], 1
    while 10 ** (k - 1) < hi or k == 1:
        a, b = max(lo, 0 if k == 1 else 10 ** (k - 1)), min(hi, 10**k)
        if b > a:
            out.append((k, a, b))
        k += 1
    return out


def _uniform_keeping_lengths(lo: int, hi: int, n: int, sk, rng):
    """int32[n] uniform over lo..hi-1 in which row i has as many decimal
    digits as in the skeleton's draw, and (when n allows) every value
    occurs: distinct counts are the configuration's too.  Also returns
    the digit counts."""
    skeleton = sk.integers(lo, hi, n, dtype=np.int32)
    if hi - lo <= n:  # the skeleton holds every value, so every class has room
        skeleton[sk.choice(n, hi - lo, replace=False)] = np.arange(lo, hi, dtype=np.int32)
    nd = np.ones(n, dtype=np.int8)
    for k, a, _ in _classes(lo, hi)[1:]:
        nd += skeleton >= a
    v = np.empty(n, dtype=np.int32)
    for k, a, b in _classes(lo, hi):
        rows = np.flatnonzero(nd == k)
        v[rows] = rng.integers(a, b, rows.size, dtype=np.int32)
        if hi - lo <= n:
            v[rows[rng.choice(rows.size, b - a, replace=False)]] = np.arange(a, b, dtype=np.int32)
    return v, nd


def _permutation_keeping_lengths(n: int, sk, rng) -> np.ndarray:
    """A seeded permutation of 0..n-1 in which row i has as many decimal
    digits as in the skeleton's permutation."""
    width = len(str(max(n - 1, 1)))
    labels = np.empty(n, dtype=np.int8)
    for k, a, b in _classes(0, n):
        labels[a:b] = k
    sk.shuffle(labels)
    out = np.empty(n, dtype=np.int32)
    for k, a, b in _classes(0, n):
        block = np.arange(a, b, dtype=np.int32)
        rng.shuffle(block)
        out[labels == k] = block
    return out


def _names(rows: np.ndarray, pool, per: int) -> np.ndarray:
    """uint8[len(rows), width]: ``pool[(row // per) % len(pool)]``, 0-padded."""
    table = np.array(pool, dtype="S")
    w = table.dtype.itemsize
    mat = np.frombuffer(table.tobytes(), dtype=np.uint8).reshape(len(pool), w)
    return mat[(rows // per) % len(pool)]


def _ts_table(secs: np.ndarray) -> np.ndarray:
    """'S25'[len(secs)]: RFC 3339 timestamps, *secs* seconds into TS_YEAR."""
    days = np.array(
        [str(np.datetime64(f"{TS_YEAR}-01-01") + k).encode() for k in range(366)], dtype="S10"
    )
    days = np.frombuffer(days.tobytes(), dtype=np.uint8).reshape(366, 10)
    two = np.frombuffer(b"".join(b"%02d" % i for i in range(60)), dtype=np.uint8).reshape(60, 2)
    sod = secs % 86400
    mat = np.empty((secs.shape[0], TS_BYTES), dtype=np.uint8)
    mat[:, 0:10] = days[secs // 86400]
    mat[:, 10] = ord("T")
    mat[:, 11:13] = two[sod // 3600]
    mat[:, 13] = ord(":")
    mat[:, 14:16] = two[(sod % 3600) // 60]
    mat[:, 16] = ord(":")
    mat[:, 17:19] = two[sod % 60]
    mat[:, 19:25] = np.frombuffer(TS_ZONE, dtype=np.uint8)
    return mat.view(f"S{TS_BYTES}").reshape(-1)


def _timestamps(n: int, distinct: int, sk, rng):
    """(ts_table 'S25'[distinct], int32[n] indices into it): *distinct*
    different seconds of TS_YEAR drawn from the seed, each on one row at
    least and the other rows uniform over them.  Which rows share a
    timestamp comes from the skeleton, so the count of different values
    in the file, and in any stretch of its rows, is the configuration's
    and not the draw's: the program keeps a dictionary per chunk."""
    secs = rng.choice(366 * 86400, distinct, replace=False)
    idx = np.empty(n, dtype=np.int32)
    idx[:distinct] = np.arange(distinct, dtype=np.int32)
    idx[distinct:] = sk.integers(0, distinct, n - distinct, dtype=np.int32)
    sk.shuffle(idx)
    return _ts_table(secs), idx


class Data:
    """The generated deployment.  *cfg*: the configuration; *files*:
    which CSV files this cell reads (only those are written); *rows*
    overrides the row count of the configuration's ``fact`` table
    (rehearsal only)."""

    def __init__(self, cfg: dict, seed: int, root: str, files, rows=None):
        tables = cfg["tables"]
        fact = cfg["fact"]
        self.files = tuple(files)
        self.paths = {
            k: os.path.join(root, f"{k}.csv")
            for k in ("orders", "orders_prefix", "people", "stock")
        }

        def nrows(name):
            full = int(tables[name]["rows"])
            return int(rows) if rows is not None and name == fact else full

        # The byte length of every row is the configuration's, not the
        # seed's: a skeleton drawn from the fixed ``layout_seed`` gives each
        # cell its count of decimal digits, and the seed draws the values
        # within that count.  So every row starts at the same byte offset
        # whatever the seed, the ingest cuts its chunks at the same rows,
        # and no array shape in the program follows the seed.
        layout = int(cfg["layout_seed"])

        def streams(i):
            return np.random.default_rng([layout, i]), np.random.default_rng([seed, i])

        self.n_people = nrows("people")
        people = tables["people"]
        if people["id"].startswith("shuffled"):  # unique and shuffled: the index build sorts for real
            self.people_id = _permutation_keeping_lengths(self.n_people, *streams(0))
        else:
            self.people_id = np.arange(self.n_people, dtype=np.int32)
        self._row_of = None
        self.n = self.n_people
        if "stock" in tables:
            self.n_stock = int(tables["stock"]["rows"])
            self.stock_name = np.array([b"prod%d" % i for i in range(self.n_stock)], dtype="S")
            self.stock_price = np.array(
                [b"%.2f" % ((i % 9900) / 100 + 0.99) for i in range(self.n_stock)], dtype="S"
            )
        if "orders" in tables:
            self._orders(tables["orders"], nrows("orders"), seed, streams)
        self._write()

    def _orders(self, orders: dict, n: int, seed: int, streams) -> None:
        self.n = n
        self.prefix_n = min(int(orders.get("host_prefix_rows", 0)), n)
        distinct = max(1, n * int(orders["ts_distinct"]) // int(orders["rows"]))
        draws = (  # one thread and one pair of streams a column
            (_uniform_keeping_lengths, 0, self.n_people, n),
            (_uniform_keeping_lengths, 0, self.n_stock, n),
            (_uniform_keeping_lengths, 1, 101, n),
        )
        with ThreadPoolExecutor(max_workers=len(draws) + 1) as pool:
            futures = [pool.submit(fn, *a, *streams(i + 1)) for i, (fn, *a) in enumerate(draws)]
            ts = pool.submit(_timestamps, n, distinct, *streams(9))
            (self.cust, _), (self.prod, self._nd_prod), (self.qty, self._nd_qty) = (
                f.result() for f in futures
            )
            self.ts_table, self.ts_idx = ts.result()
        self.filter = orders.get("filter")
        if self.filter:
            self._place_filter_hits(np.random.default_rng(seed))

    def people_name(self, rows: np.ndarray) -> np.ndarray:
        """'S' names of the people on *rows* (upstream's 10 names x 12
        surnames, by the row's number, so a row's length is not the seed's)."""
        return np.array(FIRST, dtype="S")[rows % len(FIRST)]

    def people_surname(self, rows: np.ndarray) -> np.ndarray:
        return np.array(LAST, dtype="S")[(rows // len(FIRST)) % len(LAST)]

    @property
    def ts(self) -> np.ndarray:
        """'S25'[n]: every order's timestamp (made when a check asks)."""
        return self.ts_table[self.ts_idx]

    def _place_filter_hits(self, rng) -> None:
        """Exactly ``filter['hits']`` rows carry the filter's (prod_id,
        qty) pair, at seeded positions among the rows whose two cells
        already have the pair's digit counts (so no row changes length),
        one of them inside the host executor's prefix when there is one;
        a row that drew the pair by chance gets a neighbouring quantity."""
        prod, qty, hits = (int(self.filter[k]) for k in ("prod_id", "qty", "hits"))
        hits = min(hits, self.n)
        other = qty + 1 if len(str(qty + 1)) == len(str(qty)) else qty - 1
        self.qty[(self.prod == prod) & (self.qty == qty)] = other
        fits = np.flatnonzero(
            (self._nd_prod == len(str(prod))) & (self._nd_qty == len(str(qty)))
        )
        if fits.size < hits:  # a rehearsal's few rows: lengths may move, nothing cuts there
            fits = np.arange(self.n)
        at = rng.choice(fits, hits, replace=False)
        in_prefix = fits[fits < self.prefix_n]
        if in_prefix.size and hits and not (at < self.prefix_n).any():
            at[0] = rng.choice(in_prefix)
        self.prod[at] = prod
        self.qty[at] = qty
        self.filter_hits = np.sort(at)

    def _write_blocks(self, path: str, head: bytes, n: int, block, prefix_path=None, prefix_n=0):
        """Blocks are formatted on a few threads (numpy releases the GIL)
        and written in order: generation is most of set-up."""
        blocks = [(lo, min(lo + BLOCK_ROWS, n)) for lo in range(0, n, BLOCK_ROWS)]
        prefix = [head]
        with ThreadPoolExecutor(max_workers=WRITE_THREADS) as pool, open(path, "wb") as f:
            f.write(head)
            for (lo, hi), body in zip(blocks, pool.map(block, blocks)):
                f.write(body)
                if prefix_path and lo < prefix_n:
                    k = min(hi, prefix_n) - lo
                    prefix.append(body if k == hi - lo else body[: _nth_newline(body, k)])
        if prefix_path:
            with open(prefix_path, "wb") as fp:
                fp.write(b"".join(prefix))

    def _write(self) -> None:
        if "orders" in self.files:
            want_prefix = "orders_prefix" in self.files and self.prefix_n
            self._write_blocks(
                self.paths["orders"], ORDERS_HEAD, self.n, self._orders_block,
                self.paths["orders_prefix"] if want_prefix else None, self.prefix_n,
            )
        if "people" in self.files:
            self._write_blocks(self.paths["people"], PEOPLE_HEAD, self.n_people, self._people_block)
        if "stock" in self.files:
            with open(self.paths["stock"], "wb") as f:
                f.write(b"prod_id,product,price\n")
                f.write(
                    b"".join(
                        b"p%d,%s,%s\n" % (i, n, p)
                        for i, (n, p) in enumerate(
                            zip(self.stock_name.tolist(), self.stock_price.tolist())
                        )
                    )
                )

    @property
    def row_of(self) -> np.ndarray:
        """person id -> row; built when a cell first asks for it."""
        if self._row_of is None:
            self._row_of = np.empty(self.n_people, dtype=np.int32)
            self._row_of[self.people_id] = np.arange(self.n_people, dtype=np.int32)
        return self._row_of

    def _orders_block(self, span) -> bytes:
        lo, hi = span
        ts = self.ts_table[self.ts_idx[lo:hi]]
        return csv_lines(
            [
                (b"c", self.cust[lo:hi]), (b"p", self.prod[lo:hi]), (b"", self.qty[lo:hi]),
                np.frombuffer(ts.tobytes(), dtype=np.uint8).reshape(hi - lo, TS_BYTES),
            ]
        )

    def _people_block(self, span) -> bytes:
        lo, hi = span
        rows = np.arange(lo, hi)
        return csv_lines(
            [(b"c", self.people_id[lo:hi]), _names(rows, FIRST, 1), _names(rows, LAST, len(FIRST))]
        )


def lookup_keys(data: Data, seed: int, n_keys: int, miss_share: float):
    """*n_keys* person ids for the lookup clients: uniform over the ids
    that exist, with exactly ``round(n_keys * miss_share)`` ids that do
    not (>= data.n_people), at seeded positions.  Returns an int64 array."""
    rng = np.random.default_rng([seed, 0x6C6F6F6B])
    n = data.n_people
    keys = rng.integers(0, n, n_keys, dtype=np.int64)
    n_miss = int(round(n_keys * miss_share))
    at = rng.choice(n_keys, n_miss, replace=False)
    keys[at] = n + rng.integers(0, max(n // 100, 1), n_miss)
    return keys
