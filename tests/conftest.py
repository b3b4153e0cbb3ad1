"""Shared fixtures: the reference's synthetic test corpus.

Mirrors the reference's generated-at-startup temp CSVs
(csvplus_test.go:1188-1357): people = 10 names x 12 surnames = 120 rows
with random birth years; stock = 8 products; orders = 10 000 random rows.
Parallel in-memory oracles serve to check pipeline outputs, exactly as the
reference does (csvplus_test.go:440-451, 559-571).

Device/sharding tests run on the CPU backend with 8 simulated devices,
whatever the machine has: the lines below force both before JAX
initialises, so the suite never takes (or waits for) an accelerator.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# jax reads JAX_PLATFORMS into its config at import; cover an import
# that happened before this file ran
jax.config.update("jax_platforms", "cpu")

import random
from dataclasses import dataclass
from datetime import datetime, timedelta, timezone
from typing import Dict, List

import pytest

SEED = 20160914  # deterministic corpus

PEOPLE_NAMES = [
    "Amelia", "Olivia", "Emily", "Ava", "Isla",
    "Oliver", "Jack", "Harry", "Jacob", "Charlie",
]

PEOPLE_SURNAMES = [
    "Smith", "Jones", "Taylor", "Williams", "Brown", "Davies",
    "Evans", "Wilson", "Thomas", "Roberts", "Johnson", "Lewis",
]

STOCK_ITEMS = [
    ("banana", 0.01), ("apple", 0.02), ("orange", 0.03), ("pea", 0.04),
    ("tomato", 0.05), ("potato", 0.06), ("cucumber", 0.07), ("iPhone", 0.08),
]

NUM_ORDERS = 10_000


@dataclass
class Person:
    name: str
    surname: str
    born: int


@dataclass
class Order:
    cust_id: int
    prod_id: int
    qty: int
    ts: str


def _csv_quote(field: str) -> str:
    from csvplus_tpu.csvio import _field_needs_quotes

    if _field_needs_quotes(field, ","):
        return '"' + field.replace('"', '""') + '"'
    return field


def _write_csv(path, header: List[str], rows: List[List[str]]) -> None:
    with open(path, "w", encoding="utf-8", newline="") as f:
        for rec in [header] + rows:
            f.write(",".join(_csv_quote(x) for x in rec) + "\n")


@pytest.fixture(scope="session")
def corpus(tmp_path_factory):
    """Generate people/stock/orders CSVs + in-memory oracles."""
    rng = random.Random(SEED)
    root = tmp_path_factory.mktemp("corpus")

    # people.csv (csvplus_test.go:1220-1253)
    people: List[Person] = []
    people_rows = []
    for i, name in enumerate(PEOPLE_NAMES):
        for j, surname in enumerate(PEOPLE_SURNAMES):
            pid = i * len(PEOPLE_SURNAMES) + j
            p = Person(name, surname, 1916 + rng.randrange(90))
            people.append(p)
            people_rows.append([str(pid), p.name, p.surname, str(p.born)])
    people_path = root / "people.csv"
    _write_csv(people_path, ["id", "name", "surname", "born"], people_rows)

    # stock.csv (csvplus_test.go:1277-1295)
    stock_rows = [
        [str(i), name, f"{price:.2f}"] for i, (name, price) in enumerate(STOCK_ITEMS)
    ]
    stock_path = root / "stock.csv"
    _write_csv(stock_path, ["prod_id", "product", "price"], stock_rows)

    # orders.csv (csvplus_test.go:1300-1333)
    now = datetime(2026, 7, 28, 12, 0, 0, tzinfo=timezone.utc)
    orders: List[Order] = []
    orders_rows = []
    for i in range(NUM_ORDERS):
        o = Order(
            cust_id=rng.randrange(len(people)),
            prod_id=rng.randrange(len(STOCK_ITEMS)),
            qty=rng.randrange(100) + 1,
            ts=(now - timedelta(seconds=rng.randrange(100000) + 1)).strftime(
                "%Y-%m-%dT%H:%M:%S+00:00"
            ),
        )
        orders.append(o)
        orders_rows.append([str(i), str(o.cust_id), str(o.prod_id), str(o.qty), o.ts])
    orders_path = root / "orders.csv"
    _write_csv(
        orders_path, ["order_id", "cust_id", "prod_id", "qty", "ts"], orders_rows
    )

    # CSVPLUS_SAVE_TEMPS=dir keeps a copy of the generated corpus for
    # inspection — the reference's -save-temps flag (csvplus_test.go:1347)
    save_dir = os.environ.get("CSVPLUS_SAVE_TEMPS")
    if save_dir:
        import shutil

        os.makedirs(save_dir, exist_ok=True)
        for p in (people_path, stock_path, orders_path):
            shutil.copy2(p, save_dir)

    return {
        "people_csv": str(people_path),
        "stock_csv": str(stock_path),
        "orders_csv": str(orders_path),
        "people": people,
        "stock": STOCK_ITEMS,
        "orders": orders,
        "root": root,
    }


@pytest.fixture()
def people_csv(corpus) -> str:
    return corpus["people_csv"]


@pytest.fixture()
def stock_csv(corpus) -> str:
    return corpus["stock_csv"]


@pytest.fixture()
def orders_csv(corpus) -> str:
    return corpus["orders_csv"]


# hypothesis scale knob: CSVPLUS_HYPOTHESIS_EXAMPLES=N runs the property
# suites at N examples (soak testing); the default "ci" profile stays
# fast.  Per-test @settings must NOT pin max_examples or they would
# override these profiles.  hypothesis is an optional test dependency:
# without it the property tests skip (tests/hypo_compat.py) and the
# profiles are moot.
try:
    import hypothesis as _hyp
except ModuleNotFoundError:
    _hyp = None

if _hyp is not None:
    _hyp.settings.register_profile("ci", max_examples=100, deadline=None)
    _n = os.environ.get("CSVPLUS_HYPOTHESIS_EXAMPLES")
    if _n:
        _hyp.settings.register_profile("soak", max_examples=int(_n), deadline=None)
        _hyp.settings.load_profile("soak")
    else:
        _hyp.settings.load_profile("ci")
