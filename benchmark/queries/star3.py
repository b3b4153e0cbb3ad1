"""upstream's README 3-table example on resident tables:
``orders.Join(cust_idx, "cust_id").Join(prod_idx)`` through one
``PlanCache``; nine result columns (``prod_id`` is shared by the
natural join), one row per order, every probe matching."""

from __future__ import annotations

import reference as ref


def build(h, state) -> None:
    from csvplus_tpu.serve.plancache import PlanCache

    with h.phase("ingest"):
        orders, people, stock = (state.ingest(h, k) for k in ("orders", "people", "stock"))
    with h.phase("index"):
        cust_idx = people.UniqueIndexOn("id").sync()
        prod_idx = stock.UniqueIndexOn("prod_id").sync()
    plan = orders.Join(cust_idx, "cust_id").Join(prod_idx).plan
    cache = PlanCache()

    def run_once():
        with h.annotate("plancache.execute"):
            table = cache.execute(plan)
        with h.annotate("result.sync"):
            return table.sync()

    state.run_once = run_once
    state.digest = ref.TableDigest()


def want(d, n=None) -> dict:
    """The nine result columns of the first *n* orders from the
    generator's arrays: name -> (prefix, ints) or an 'S' array."""
    s = slice(0, n)
    cust, prod = d.cust[s], d.prod[s]
    person = d.row_of[cust]  # the row of people that holds each order's customer
    return {
        "cust_id": (b"c", cust), "prod_id": (b"p", prod), "qty": (b"", d.qty[s]),
        "ts": d.ts_table[d.ts_idx[s]],
        "id": (b"c", cust), "name": d.people_name(person), "surname": d.people_surname(person),
        "product": d.stock_name[prod], "price": d.stock_price[prod],
    }


def verify(h, state, last, digests) -> None:
    """The window's last result equals the generator in full and sits on
    the device; the host executor agrees on the prefix."""
    d = h.data
    ref.placed_on(last, h.platform, "join result", 1)
    ref.expect_columns(last, d.n, want(d), "3-way join")
    _host_prefix(h)


def _host_prefix(h) -> None:
    from csvplus_tpu import FromFile, Take

    d = h.data
    n = d.prefix_n
    if not n:
        return
    h_cust = Take(FromFile(d.paths["people"])).UniqueIndexOn("id")
    h_prod = Take(FromFile(d.paths["stock"])).UniqueIndexOn("prod_id")
    rows = Take(FromFile(d.paths["orders_prefix"])).Join(h_cust, "cust_id").Join(h_prod).ToRows()
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
    h.say(f"check: host executor equals the generator on the first {n:,} rows")
