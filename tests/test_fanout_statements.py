"""The one-to-many join at a small size: every customer's orders from a
NON-unique index,

    by_cust = orders.IndexOn("cust_id")
    people.Join(by_cust, "id")[.Join(stock.UniqueIndexOn("prod_id"))]

upstream's ``Join`` proper (csvplus.go:552-568): for each stream row a
binary search of the sorted index and a forward scan (:559) that emits
one merged row per matching index row, the stream's value winning a name
collision.  Upstream sorts its index with ``sort.Sort``, which leaves the
order of equal keys unspecified; this project's index is stable, so
within a customer the orders keep the file's order, and that is what the
reference below — written from that description over row dicts, sharing
nothing with the engine — holds the ``PlanCache`` path to: rows, row
order, column order, and the ``join:expand`` record of the ``fan-out``
path.  The last cases run the chip's kernels in interpret mode — the
build side's lanes a run at a time (``csvplus.join.gather_runs``, ISSUE
47), people's through the VMEM gather — against the same scan.  The
full-size deployment is ``benchmark/configs/orders-by-customer-10m.json``.
"""

import bisect

import numpy as np
import pytest

from csvplus_tpu import FromFile, Like
from csvplus_tpu.analysis import optimize_plan
from csvplus_tpu.ops import gather as G
from csvplus_tpu.ops import join as J
from csvplus_tpu.serve.plancache import PlanCache
from csvplus_tpu.utils.observe import telemetry

from conftest import PEOPLE_NAMES, PEOPLE_SURNAMES

ORDERS, PEOPLE, STOCK = 6_000, 200, 20
# a join puts the build side's columns first, then the stream's; a name
# both carry keeps the build side's place and the stream's value
ONE_JOIN = ["cust_id", "prod_id", "qty", "ts", "id", "name", "surname"]
CASCADE = ["prod_id", "product", "price", "cust_id", "qty", "ts", "id", "name", "surname"]


def _customers(case: str, rng) -> np.ndarray:
    """Each order's customer number."""
    if case == "one-holds-half":
        cust = rng.integers(0, PEOPLE, ORDERS)
        cust[rng.choice(ORDERS, ORDERS // 2, replace=False)] = 7
        return cust
    if case == "some-without-orders":  # the odd customers place none
        return 2 * rng.integers(0, PEOPLE // 2, ORDERS)
    return rng.integers(0, PEOPLE, ORDERS)


def _files(tmp_path, case: str, seed: int):
    """(paths, the three files' rows as dicts, in file order)."""
    rng = np.random.default_rng(seed)
    cust, prod = _customers(case, rng), rng.integers(0, STOCK, ORDERS)
    qty, secs = rng.integers(1, 101, ORDERS), rng.integers(0, 86_400, ORDERS)
    orders = [
        {"cust_id": f"c{c}", "prod_id": f"p{p}", "qty": str(q),
         "ts": f"2016-09-14T{s // 3600:02d}:{s % 3600 // 60:02d}:{s % 60:02d}+01:00"}
        for c, p, q, s in zip(cust.tolist(), prod.tolist(), qty.tolist(), secs.tolist())
    ]
    ids = rng.permutation(PEOPLE).tolist()  # people.csv is in no key order
    people = [
        {"id": f"c{i}", "name": PEOPLE_NAMES[r % 10], "surname": PEOPLE_SURNAMES[(r // 10) % 12]}
        for r, i in enumerate(ids)
    ]
    stock = [{"prod_id": f"p{i}", "product": f"prod{i}", "price": f"{i % 99}.99"} for i in range(STOCK)]
    paths = {}
    for key, rows in (("orders", orders), ("people", people), ("stock", stock)):
        paths[key] = str(tmp_path / f"{key}.csv")
        with open(paths[key], "w") as f:
            f.write(",".join(rows[0]) + "\n")
            f.writelines(",".join(r.values()) + "\n" for r in rows)
    return paths, orders, people, stock


def _join(stream: list, index_rows: list, key: str, column: str) -> list:
    """Upstream's ``Join`` over row dicts: the index is *index_rows*
    sorted by *key* (stably: ties keep the file's order); each stream
    row's *column* is searched for and the run of equal keys scanned
    forward, one merged row a match, the stream's value winning."""
    index = sorted(index_rows, key=lambda r: r[key])
    keys = [r[key] for r in index]
    out = []
    for row in stream:
        i = bisect.bisect_left(keys, row[column])
        while i < len(index) and keys[i] == row[column]:
            out.append({**index[i], **row})
            i += 1
    return out


CASES = [  # (groups, the stream is empty, the second join too, seed, the chip's kernels interpreted)
    pytest.param("uniform", False, False, 45, False, id="uniform-groups"),
    pytest.param("one-holds-half", False, False, 45, False, id="one-customer-holds-half"),
    pytest.param("some-without-orders", False, False, 45, False, id="customers-without-orders"),
    pytest.param("uniform", True, False, 45, False, id="empty-stream"),
    pytest.param("uniform", False, True, 45, False, id="cascade"),
    pytest.param("uniform", False, True, 4_500_000_045, False, id="second-seed"),
    # half the probes match nothing (runs of 0 between runs of ~60), then the whole cell
    pytest.param("some-without-orders", False, False, 47, True, id="run-copy-customers-without-orders"),
    pytest.param("one-holds-half", False, True, 4_700_000_047, True, id="run-copy-cascade-one-holds-half"),
    pytest.param("uniform", True, False, 47, True, id="run-copy-empty-stream"),
]


@pytest.fixture(autouse=True, scope="module")
def _journal_of_its_own():
    """As ``tests/test_gather_small.py``: the interpreted kernels' traces
    (hundreds of ``compile`` spans a case) stay out of the process
    journal, whose ``dropped`` ``tests/test_journal.py`` pins at 0."""
    from csvplus_tpu.obs.span import Journal, tracer

    kept, tracer.journal = tracer.journal, Journal(trace_id=-47)
    yield
    tracer.journal = kept


@pytest.fixture
def programs(monkeypatch):
    """The programs the emit dispatched, by name, off its own records."""
    calls = {"join.gather_lane": 0, "join.gather_cols": 0, "join.gather_runs": 0}

    def counted(groups, _real=G.emit):
        done = _real(groups)
        for name in done.programs:
            if name in calls:
                calls[name] += 1
        return done

    monkeypatch.setattr(J, "emit", counted)
    return calls


@pytest.mark.parametrize("case, empty, cascade, seed, kernels", CASES)
def test_plancache_equals_upstreams_scan(tmp_path, programs, monkeypatch, case, empty, cascade, seed, kernels):
    paths, orders_rows, people_rows, stock_rows = _files(tmp_path, case, seed)
    if empty:
        people_rows = []
    want = _join(people_rows, orders_rows, "cust_id", "id")
    runs = {}
    for r in want:
        runs[r["id"]] = runs.get(r["id"], 0) + 1
    if cascade:
        want = _join(want, stock_rows, "prod_id", "prod_id")
    if case == "some-without-orders":
        assert len(runs) == PEOPLE // 2 and len(want) == ORDERS  # the others are nowhere
    elif not empty:
        assert len(runs) == PEOPLE and len(want) == ORDERS

    orders, people, stock = (FromFile(paths[k]).OnDevice("cpu") for k in ("orders", "people", "stock"))
    if empty:
        people = people.Filter(Like({"name": "nobody of that name"}))
    by_cust = orders.IndexOn("cust_id").sync()
    src = people.Join(by_cust, "id")
    if cascade:
        src = src.Join(stock.UniqueIndexOn("prod_id").sync())
    cache = PlanCache()
    programs.update(dict.fromkeys(programs, 0))  # the index builds are over
    if kernels:  # as the chip would choose them: only the rules' backend test is answered
        monkeypatch.setattr(G, "_kernel_mode", lambda: "interpret")
    with telemetry.collect() as recs:
        table = cache.execute(src.plan).sync()
    emit_programs = dict(programs)
    assert list(table.columns) == (CASCADE if cascade else ONE_JOIN)  # column order
    assert [dict(r) for r in table.to_rows()] == want  # row order and every value
    assert [dict(r) for r in cache.execute(src.plan).sync().to_rows()] == want  # the cached plan, again

    expands = [r.extra for r in recs if r.stage == "join:expand"]
    joins = [r.extra for r in recs if r.stage.startswith("join:")]
    assert sum(int(e.get("host_sync_elements", 0)) for e in joins) <= 64  # scalars only
    if empty:  # nothing probes: no expansion, and no error (csvplus.go:553-556)
        assert expands == [] and table.nrows == 0
        return
    fan = expands[0]
    assert (fan["path"], fan["tier"], fan["form"]) == ("fan-out", "device", "prefix-scatter")
    assert (fan["probes"], fan["max_run"]) == (PEOPLE, max(runs.values()))
    assert fan["emitted"] == ORDERS and fan["padded"] == 8192 and fan["host_sync_elements"] == 2
    merges = [r.extra for r in recs if r.stage == "join:merge"]
    assert (merges[0]["build_gathers"], merges[0]["stream_gathers"], merges[0]["row_gathers"]) == (4, 3, 7)
    if kernels:
        # the orders' four lanes move by ONE run copy (its mean run is 30 or
        # 60 rows), people's three by one VMEM gather; no lane a program
        assert (merges[0]["run_copies"], merges[0]["vmem_gathers"]) == (4, 3)
        # (stock's two composed tables ride a VMEM gather of their own in the cascade)
        assert emit_programs == {"join.gather_lane": 0, "join.gather_cols": 2 if cascade else 1, "join.gather_runs": 1}
        assert [m["run_copies"] for m in merges[1:]] == [0] * cascade  # unique-identity: no runs
    else:
        # every lane whole on one device moves in a program of its own: four of
        # the orders, three of the people, and stock's two composed tables
        assert emit_programs == {"join.gather_lane": 9 if cascade else 7, "join.gather_cols": 0, "join.gather_runs": 0}
        assert merges[0]["run_copies"] == 0
    if cascade:
        # prod_id is born from the first join's build side, so the two
        # joins may not fuse into one pass: the rule says so and two run
        result = optimize_plan(src.plan)
        assert any(d.rule == "multiway-fuse" for d in result.blocked)
        assert not any(r.startswith("multiway-fuse") for r in result.applied)
        assert [(e["path"], e["tier"]) for e in expands] == [("fan-out", "device"), ("unique-identity", "device")]
        assert (merges[1]["build_gathers"], merges[1]["stream_gathers"]) == (2, 0)
    else:
        assert len(expands) == 1
