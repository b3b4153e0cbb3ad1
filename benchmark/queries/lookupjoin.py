"""upstream's README 3-table example, its first join, on the four-chip
host (BASELINE.json config 5): ``orders.Join(custIndex, "cust_id")``
with orders row-sharded over a 1-D mesh and a people index of 20M
unique keys past the program's partition threshold, through one
``PlanCache``; seven result columns, one row per order, every probe
matching.

Nothing here selects a path: both files go through
``FromFile(...).OnDevice(platform, shards=N)`` and the index through
``UniqueIndexOn``, at the program's defaults (no ``CSVPLUS_*`` variable,
no class attribute).  ``verify`` fails the run where the first execution
recorded no ``join:all_to_all`` stage or the index build no ``dsort``.
"""

from __future__ import annotations

import reference as ref

INGEST_TIERS = (
    "ingest:streamed", "ingest:device-parsed", "ingest:native-encoded", "ingest:python",
)


def ingest(h, key: str):
    """FromFile(path).OnDevice(platform, shards=chips), synced: one
    ingest tier, not the Python parser, landed pre-sharded, every column
    on exactly that many devices."""
    from csvplus_tpu import FromFile
    from csvplus_tpu.utils.observe import telemetry

    chips = int(h.cfg["chips"])
    with telemetry.collect() as records:
        src = FromFile(h.data.paths[key]).OnDevice(h.platform, shards=chips)
        src.plan.table.sync()
        tiers = [r.stage for r in records if r.stage in INGEST_TIERS]
        workers = [r.extra.get("workers") for r in records if r.stage == "ingest:encode"]
    ref.check(len(tiers) == 1, f"{key}: ingest tiers recorded: {tiers}")
    ref.check(tiers[0] != "ingest:python", f"{key}: ingest fell to the Python parser")
    table = src.plan.table
    full_size = h.data.n == int(h.cfg["tables"][h.cfg["fact"]]["rows"])  # not a rehearsal's few rows
    ref.check(
        bool(getattr(table, "_pre_sharded", False)) or not full_size,
        f"{key}: the table did not land pre-sharded (it was re-uploaded from one device)",
    )
    ref.placed_on(table, h.platform, key, chips)
    h.say(
        f"  {key}: {table.nrows:,} rows via {tiers[0]} K={workers[-1] if workers else None} "
        f"on {chips} devices {ref.column_kinds(table)}"
    )
    return src


def build(h, state) -> None:
    from csvplus_tpu.serve.plancache import PlanCache
    from csvplus_tpu.utils.observe import telemetry

    with h.phase("ingest"):
        orders, people = ingest(h, "orders"), ingest(h, "people")
    with h.phase("index"), telemetry.collect() as records:
        cust_idx = people.UniqueIndexOn("id").sync()
        state.index_stages = [r.stage for r in records]
    plan = orders.Join(cust_idx, "cust_id").plan
    cache = PlanCache()
    state.first_stages = None

    def run_once():
        mark = len(telemetry.records)
        with h.annotate("plancache.execute"):
            table = cache.execute(plan)
        with h.annotate("result.sync"):
            table = table.sync()
        if state.first_stages is None:  # the driver collects stages around the first execution
            state.first_stages = [(r.stage, dict(r.extra)) for r in telemetry.records[mark:]]
        return table

    state.run_once = run_once
    state.digest = ref.TableDigest()


def want(d, n=None) -> dict:
    """The seven result columns of the first *n* orders from the
    generator's arrays alone: name -> (prefix, ints) or an 'S' array."""
    s = slice(0, n)
    cust = d.cust[s]
    person = d.row_of[cust]  # the row of people that holds each order's customer
    return {
        "cust_id": (b"c", cust), "prod_id": (b"p", d.prod[s]), "qty": (b"", d.qty[s]),
        "ts": d.ts_table[d.ts_idx[s]],
        "id": (b"c", cust), "name": d.people_name(person), "surname": d.people_surname(person),
    }


def verify(h, state, last, digests) -> None:
    """The window's last result equals the generator in full and sits on
    the configuration's chips; the partition tier and the mesh sort ran
    at the program's defaults."""
    chips = int(h.cfg["chips"])
    ref.check(
        "dsort" in state.index_stages,
        f"UniqueIndexOn recorded no dsort stage: {state.index_stages}",
    )
    exchanges = [extra for stage, extra in state.first_stages or () if stage == "join:all_to_all"]
    ref.check(
        len(exchanges) >= 1,
        "the first execution recorded no join:all_to_all stage: "
        f"{[s for s, _ in state.first_stages or ()]}",
    )
    h.say(
        "check: first execution's stages "
        + " ".join(s for s, _ in state.first_stages) + f"; join:all_to_all {exchanges}"
    )
    ref.placed_on(last, h.platform, "join result", chips)
    ref.expect_columns(last, h.data.n, want(h.data), "lookup join")
