"""Every customer's statement, on resident tables: upstream's README
tables joined the master-detail way round through ``IndexOn`` + ``Join``
(csvplus.go:552-568; the scan at :559 emits one merged row per matching
index row, and ``IndexOn``, unlike ``UniqueIndexOn``, admits many):

    by_cust  = orders.IndexOn("cust_id")        # 10M rows, 100,000 keys, NOT unique
    prod_idx = stock.UniqueIndexOn("prod_id")
    people.Join(by_cust, "id").Join(prod_idx)   # 100,000 probes -> 10M rows x 9 columns

through one ``PlanCache``.  The result holds ``queries/star3.py``'s nine
columns, one row per order, in customer-major order: customers in
``people.csv``'s row order, within a customer the orders in
``orders.csv``'s row order (this project's index is stable; upstream
leaves ties to ``sort.Sort``), a customer without orders absent.

Nothing here selects a path: ``FromFile(...).OnDevice(platform)`` and the
public ``DataSource`` / ``Index`` calls at the program's defaults (no
``CSVPLUS_*`` variable, no class attribute).

**The set-up refusal**, as ``queries/star3_selective.py`` has it.  This
cell is the deployment in which tables, indexes, probe answers, the
expansion and the result stay on the device: inside an execution the
host reads scalars only.  ``run_once``, on its first call (the driver's
``first_execution`` phase, where an exception ends the run), reads its
own stage records and refuses a program that is no run of this cell:
``join:expand`` must have recorded exactly ``fan-out`` then
``unique-identity``, both on the ``device`` tier, and the ``join:*``
stages' ``host_sync_elements`` must sum to at most
``HOST_ELEMENTS_ALLOWED``.  ``run.py`` then exits non-zero in set-up and
prints no result line.
"""

from __future__ import annotations

import numpy as np

import reference as ref

STAGES = "join:"
PATHS = [("fan-out", "device"), ("unique-identity", "device")]
HOST_ELEMENTS_ALLOWED = 64  # scalars (the stats that size the result); nothing row-proportional


def refuse_host_tier(stages) -> None:
    """Raise ``reference.Mismatch`` unless *stages* (``(name, extra)`` of
    one execution) show the fan-out join, then the product join, resident
    on the device."""
    ours = [(s, extra) for s, extra in stages if s.startswith(STAGES)]
    expands = [(extra.get("path"), extra.get("tier")) for s, extra in ours if s == "join:expand"]
    crossed = sum(int(extra.get("host_sync_elements", 0)) for _, extra in ours)
    ref.check(
        expands == PATHS and crossed <= HOST_ELEMENTS_ALLOWED,
        "statements-fanout-resident refused in set-up: the one-to-many join did not run on the "
        f"device (join:expand recorded (path, tier) {expands}, want {PATHS}; join: stages read "
        f"{crossed} elements to the host, at most {HOST_ELEMENTS_ALLOWED} allowed; stages "
        f"{[s for s, _ in stages]}): a tree that expands the matches on the host, or that does not "
        "expand them, is not a run of this cell",
    )


def build(h, state) -> None:
    from csvplus_tpu.serve.plancache import PlanCache
    from csvplus_tpu.utils.observe import telemetry

    with h.phase("ingest"):
        orders, people, stock = (state.ingest(h, k) for k in ("orders", "people", "stock"))
    with h.phase("index"):
        by_cust = orders.IndexOn("cust_id").sync()
        prod_idx = stock.UniqueIndexOn("prod_id").sync()
    plan = people.Join(by_cust, "id").Join(prod_idx).plan
    cache = PlanCache()
    state.data = h.data  # what the control (tests/control_fanout.py) reads
    state.first_stages = None

    def run_once():
        mark = len(telemetry.records)
        with h.annotate("plancache.execute"):
            table = cache.execute(plan)
        with h.annotate("result.sync"):
            table = table.sync()
        if state.first_stages is None:  # the driver collects stages around the first execution
            state.first_stages = [(r.stage, dict(r.extra)) for r in telemetry.records[mark:]]
            refuse_host_tier(state.first_stages)
            h.say("  first execution's stages " + " ".join(
                f"{s}{extra}" if s.startswith(STAGES) else s for s, extra in state.first_stages
            ))
        return table

    state.run_once = run_once
    state.digest = ref.TableDigest()


def statement_order(d, n=None) -> np.ndarray:
    """The first *n* orders' row numbers in the result's order: by the
    row of ``people.csv`` that holds the order's customer, and within a
    customer by the order's own row (a stable sort keeps it)."""
    return np.argsort(d.row_of[d.cust[:n]], kind="stable")


def want(d, n=None) -> dict:
    """The nine result columns from the generator's arrays alone, for the
    first *n* orders: name -> (prefix, ints) or an 'S' array."""
    order = statement_order(d, n)
    cust, prod = d.cust[order], d.prod[order]
    person = d.row_of[cust]
    return {
        "cust_id": (b"c", cust), "prod_id": (b"p", prod), "qty": (b"", d.qty[order]),
        "ts": d.ts_table[d.ts_idx[order]],
        "id": (b"c", cust), "name": d.people_name(person), "surname": d.people_surname(person),
        "product": d.stock_name[prod], "price": d.stock_price[prod],
    }


def verify(h, state, last, digests) -> None:
    """The window's last result equals the reference in full (every value
    of all nine columns, one row per order, customer-major) and sits on
    the device; the host executor agrees on the prefix."""
    d = h.data
    ref.placed_on(last, h.platform, "join result", 1)
    ref.expect_columns(last, d.n, want(d), "statements (one-to-many join)")
    _host_prefix(h)


def _host_prefix(h) -> None:
    from csvplus_tpu import FromFile, Take

    d = h.data
    n = d.prefix_n
    if not n:
        return
    h_by_cust = Take(FromFile(d.paths["orders_prefix"])).IndexOn("cust_id")
    h_prod = Take(FromFile(d.paths["stock"])).UniqueIndexOn("prod_id")
    rows = Take(FromFile(d.paths["people"])).Join(h_by_cust, "id").Join(h_prod).ToRows()
    ref.check(len(rows) == n, "host executor row count on the prefix")
    for name, w in want(d, n).items():
        if isinstance(w, tuple):
            col = [w[0].decode() + str(v) for v in w[1].tolist()]
        else:
            col = [v.decode() for v in w.tolist()]
        ref.check(
            [r[name] for r in rows] == col,
            f"host executor differs from the generator on the prefix, column {name!r}",
        )
    h.say(f"check: host executor equals the generator on the first {n:,} orders against the people file")
