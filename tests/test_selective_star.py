"""The selective star at a small size: upstream's 3-table join against a
customer index restricted by the README's own filter,

    cust_idx = people.Filter(Like({"name": "Amelia"})).UniqueIndexOn("id")
    orders.Join(cust_idx, "cust_id").Join(prod_idx)

where upstream's inner ``Join`` (csvplus.go:552-568) drops the nine
orders in ten whose customer is not in the index.  The ``PlanCache`` path
over device tables, the host executor and a plain numpy reference agree
on row order, column order and every value — the ``prod_id`` both the
stream and stock carry included — on three seeds.  The full-size
deployment is ``benchmark/configs/orders-star-10m-selective.json``.
"""

import numpy as np
import pytest

from csvplus_tpu import FromFile, Like, Take
from csvplus_tpu.serve.plancache import PlanCache
from csvplus_tpu.utils.observe import telemetry

from conftest import PEOPLE_NAMES, PEOPLE_SURNAMES

ORDERS, PEOPLE, STOCK = 20_000, 1_000, 50
SEGMENT = PEOPLE_NAMES[0]  # "Amelia": upstream's names go by the row's number
# level d of the cascade puts build side d's columns first; a name both
# sides carry keeps the build side's place and the stream's value
COLUMNS = ["prod_id", "product", "price", "id", "name", "surname", "cust_id", "qty", "ts"]


def _files(tmp_path, seed: int):
    """(paths, numpy arrays): orders whose customers are uniform over
    the people (so about one in ten is the segment's), every ``prod_id``
    in stock."""
    rng = np.random.default_rng(seed)
    cust, prod = rng.integers(0, PEOPLE, ORDERS), rng.integers(0, STOCK, ORDERS)
    qty = rng.integers(1, 101, ORDERS)
    secs = rng.integers(0, 86_400, ORDERS)
    ts = [f"2016-09-14T{s // 3600:02d}:{s % 3600 // 60:02d}:{s % 60:02d}+01:00" for s in secs.tolist()]
    paths = {k: str(tmp_path / f"{k}.csv") for k in ("orders", "people", "stock")}
    with open(paths["orders"], "w") as f:
        f.write("cust_id,prod_id,qty,ts\n")
        f.writelines(f"c{c},p{p},{q},{t}\n" for c, p, q, t in zip(cust.tolist(), prod.tolist(), qty.tolist(), ts))
    with open(paths["people"], "w") as f:
        f.write("id,name,surname\n")
        f.writelines(
            f"c{i},{PEOPLE_NAMES[i % 10]},{PEOPLE_SURNAMES[(i // 10) % 12]}\n" for i in range(PEOPLE)
        )
    with open(paths["stock"], "w") as f:
        f.write("prod_id,product,price\n")
        f.writelines(f"p{i},prod{i},{i % 99}.99\n" for i in range(STOCK))
    return paths, dict(cust=cust, prod=prod, qty=qty, ts=ts)


def _reference(a) -> list:
    """The result from the arrays alone: the orders of the segment's
    customers, in order, with their customer's and product's cells."""
    rows = []
    for i in np.flatnonzero(a["cust"] % 10 == 0).tolist():  # row r of people is named PEOPLE_NAMES[r % 10]
        c, p = int(a["cust"][i]), int(a["prod"][i])
        rows.append({
            "prod_id": f"p{p}", "product": f"prod{p}", "price": f"{p % 99}.99",
            "id": f"c{c}", "name": SEGMENT, "surname": PEOPLE_SURNAMES[(c // 10) % 12],
            "cust_id": f"c{c}", "qty": str(int(a["qty"][i])), "ts": a["ts"][i],
        })
    return rows


@pytest.mark.parametrize("seed", (43, 2_016_0914, 4_300_000_007))
def test_plancache_host_executor_and_reference_agree(tmp_path, seed):
    paths, arrays = _files(tmp_path, seed)
    want = _reference(arrays)
    assert 0.08 * ORDERS < len(want) < 0.12 * ORDERS  # about one order in ten survives

    orders, people, stock = (FromFile(paths[k]).OnDevice("cpu") for k in ("orders", "people", "stock"))
    cust_idx = people.Filter(Like({"name": SEGMENT})).UniqueIndexOn("id").sync()
    prod_idx = stock.UniqueIndexOn("prod_id").sync()
    assert len(cust_idx) == PEOPLE // 10
    plan = orders.Join(cust_idx, "cust_id").Join(prod_idx).plan
    cache = PlanCache()
    with telemetry.collect() as recs:
        table = cache.execute(plan).sync()
    (expand,) = [r.extra for r in recs if r.stage == "join:expand"]
    assert (expand["path"], expand["tier"]) == ("multiway-unique-partial", "device")
    assert expand["host_sync_elements"] == 3 and expand["emitted"] == len(want)
    assert list(table.columns) == COLUMNS  # column order
    got = [dict(r) for r in table.to_rows()]
    assert got == want  # row order and every value
    assert [dict(r) for r in cache.execute(plan).sync().to_rows()] == want  # the cached plan, again

    h_cust = Take(FromFile(paths["people"])).Filter(Like({"name": SEGMENT})).UniqueIndexOn("id")
    h_prod = Take(FromFile(paths["stock"])).UniqueIndexOn("prod_id")
    host = Take(FromFile(paths["orders"])).Join(h_cust, "cust_id").Join(h_prod).ToRows()
    assert [dict(r) for r in host] == want
    # an unmatched order is nowhere, a matched one is not missing
    assert {r["name"] for r in got} == {SEGMENT}
