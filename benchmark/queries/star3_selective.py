"""upstream's README 3-table example against a customer index restricted
by the README's own filter, on resident tables:

    cust_idx = people.Filter(Like({"name": "Amelia"})).UniqueIndexOn("id")
    orders.Join(cust_idx, "cust_id").Join(prod_idx)

through one ``PlanCache``.  Upstream's ``Join`` is an inner join
(csvplus.go:552-568): an order whose customer is not in the index is
dropped, so of the configuration's 10,000,000 orders exactly
``orders.segment_rows`` survive, in the source's row order, with the nine
columns of ``queries/star3.py`` (``prod_id`` is shared by the natural
join; the stream's value wins).

Nothing here selects a path: ``FromFile(...).OnDevice(platform)`` and the
public ``DataSource`` / ``Index`` calls at the program's defaults (no
``CSVPLUS_*`` variable, no class attribute).

**The set-up refusal**, as ``queries/dedup.py`` has it.  This cell is the
deployment in which tables, indexes, probe answers and the result stay on
the device: inside an execution the host reads scalars only.
``run_once``, on its first call (the driver's ``first_execution`` phase,
where an exception ends the run), reads its own stage records and refuses
a program whose join carries the probe answers through the host: there
must be one ``join:expand`` stage, on the path
``multiway-unique-partial``, whose ``tier`` is ``device``, and the
``join:*`` stages' ``host_sync_elements`` must sum to at most
``HOST_ELEMENTS_ALLOWED``.  Such a program is not a slower run of this
cell, it is no run of it: ``run.py`` exits non-zero in set-up and prints
no result line.
"""

from __future__ import annotations

import reference as ref

STAGES = "join:"
PATH = "multiway-unique-partial"
HOST_ELEMENTS_ALLOWED = 64  # scalars (the stats that size the result); nothing row-proportional


def refuse_host_tier(stages) -> None:
    """Raise ``reference.Mismatch`` unless *stages* (``(name, extra)`` of
    one execution) show the selective join resident on the device."""
    ours = [(s, extra) for s, extra in stages if s.startswith(STAGES)]
    expands = [(extra.get("path"), extra.get("tier")) for s, extra in ours if s == "join:expand"]
    crossed = sum(int(extra.get("host_sync_elements", 0)) for _, extra in ours)
    ref.check(
        expands == [(PATH, "device")] and crossed <= HOST_ELEMENTS_ALLOWED,
        "star3-selective-resident refused in set-up: the join's compaction did not stay on the "
        f"device (join:expand recorded (path, tier) {expands}, want [{(PATH, 'device')}]; join: stages "
        f"read {crossed} elements to the host, at most {HOST_ELEMENTS_ALLOWED} allowed; stages "
        f"{[s for s, _ in stages]}): a tree that carries the probe answers through the host is not a run of this cell",
    )


def segment_name(cfg) -> bytes:
    return cfg["tables"]["orders"]["segment"]["name"].encode()


def build(h, state) -> None:
    from csvplus_tpu import Like
    from csvplus_tpu.serve.plancache import PlanCache
    from csvplus_tpu.utils.observe import telemetry

    name = segment_name(h.cfg).decode()
    with h.phase("ingest"):
        orders, people, stock = (state.ingest(h, k) for k in ("orders", "people", "stock"))
    with h.phase("index"):
        cust_idx = people.Filter(Like({"name": name})).UniqueIndexOn("id").sync()
        prod_idx = stock.UniqueIndexOn("prod_id").sync()
    plan = orders.Join(cust_idx, "cust_id").Join(prod_idx).plan
    cache = PlanCache()
    state.data = h.data  # what the control (tests/control_selective.py) reads
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


def want(d, name: bytes, n=None) -> dict:
    """The nine result columns from the generator's arrays alone: of the
    first *n* orders those whose customer carries the segment's *name*,
    in order; name -> (prefix, ints) or an 'S' array."""
    s = slice(0, n)
    person = d.row_of[d.cust[s]]  # the row of people that holds each order's customer
    keep = d.people_name(person) == name
    cust, prod, person = d.cust[s][keep], d.prod[s][keep], person[keep]
    return {
        "cust_id": (b"c", cust), "prod_id": (b"p", prod), "qty": (b"", d.qty[s][keep]),
        "ts": d.ts_table[d.ts_idx[s][keep]],
        "id": (b"c", cust), "name": d.people_name(person), "surname": d.people_surname(person),
        "product": d.stock_name[prod], "price": d.stock_price[prod],
    }


def verify(h, state, last, digests) -> None:
    """The window's last result equals the reference in full (every value
    of all nine columns, the surviving orders in order, none more, none
    fewer) and sits on the device; the generator placed the
    configuration's count; the host executor agrees on the prefix."""
    d = h.data
    orders = h.cfg["tables"]["orders"]
    w = want(d, segment_name(h.cfg))
    rows = int(w["cust_id"][1].shape[0])
    ref.check(rows == d.segment_rows, f"the generator's segment rows: {rows}, dealt {d.segment_rows}")
    if d.n == int(orders["rows"]):
        ref.check(rows == int(orders["segment_rows"]), f"the generator's segment rows at full size: {rows}")
    ref.placed_on(last, h.platform, "join result", 1)
    ref.expect_columns(last, rows, w, "selective 3-way join")
    _host_prefix(h)


def _host_prefix(h) -> None:
    from csvplus_tpu import FromFile, Like, Take

    d = h.data
    n = d.prefix_n
    if not n:
        return
    name = segment_name(h.cfg)
    h_cust = Take(FromFile(d.paths["people"])).Filter(Like({"name": name.decode()})).UniqueIndexOn("id")
    h_prod = Take(FromFile(d.paths["stock"])).UniqueIndexOn("prod_id")
    rows = Take(FromFile(d.paths["orders_prefix"])).Join(h_cust, "cust_id").Join(h_prod).ToRows()
    w = want(d, name, n)
    ref.check(len(rows) == w["cust_id"][1].shape[0], "host executor row count on the prefix")
    for col_name, col_w in w.items():
        if isinstance(col_w, tuple):
            col = [col_w[0].decode() + str(v) for v in col_w[1].tolist()]
        else:
            col = [v.decode() for v in col_w.tolist()]
        ref.check(
            [r[col_name] for r in rows] == col,
            f"host executor differs from the generator on the prefix, column {col_name!r}",
        )
    h.say(f"check: host executor keeps {len(rows):,} of the first {n:,} rows and equals the generator on them")
