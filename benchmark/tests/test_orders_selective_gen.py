"""``gen/orders_selective.py``: what is the configuration's stays the
same for every seed — every row's byte length, the files' sizes, the
segment's rows, the result's length, every dictionary's size — and the
orders file has ``gen/orders.py``'s row lengths at the same
``layout_seed``.  By hand, with the other tests of this directory (CPU).
"""

from __future__ import annotations

import os

import numpy as np

import run

CONFIG = "orders-star-10m-selective"
ROWS = 300_000


def row_bytes(path: str) -> list:
    with open(path, "rb") as f:
        return np.diff(np.flatnonzero(np.frombuffer(f.read(), dtype=np.uint8) == 10)).tolist()


def test_nothing_the_program_sees_follows_the_seed(tmp_path):
    gen = run.load_module("gen", "orders_selective")
    cfg = run.load_json("configs", f"{CONFIG}.json")
    query = run.load_module("queries", "star3_selective")
    name = query.segment_name(cfg)
    seen, custs = [], []
    for seed in (7, 2_200_000_027, 4_300_000_123):
        root = tmp_path / str(seed)
        root.mkdir()
        d = gen.Data(cfg, seed, str(root), ("orders", "orders_prefix", "people", "stock"), rows=ROWS)
        keep = d.people_name(d.row_of[d.cust]) == name
        assert np.array_equal(keep, d.in_segment)  # the reference's own predicate finds the dealt rows
        assert keep.sum() == d.segment_rows == ROWS // 10
        result_rows = len(query.want(d, name)["cust_id"][1])
        seen.append((
            row_bytes(d.paths["orders"]),
            [os.path.getsize(d.paths[k]) for k in ("orders", "orders_prefix", "people", "stock")],
            np.flatnonzero(d.in_segment).tolist(), result_rows,
            len(np.unique(d.cust)), len(np.unique(d.ts)), len(np.unique(d.prod)), len(np.unique(d.qty)),
            int(keep[: d.prefix_n].sum()),
        ))
        custs.append(d.cust)
        # each side is drawn over its own customers only, all of them where the rows allow
        members = np.flatnonzero(d.people_name(d.row_of[np.arange(d.n_people)]) == name)
        assert np.isin(d.cust[d.in_segment], members).all()
        assert not np.isin(d.cust[~d.in_segment], members).any()
        # (at this size the one-digit class may hold no row of the segment: c0 then never occurs)
        assert members.size - 1 <= len(np.unique(d.cust[d.in_segment])) <= members.size == d.n_people // 10
    assert seen[0] == seen[1] == seen[2]
    assert seen[0][3] == ROWS // 10
    assert not np.array_equal(custs[0], custs[1])  # the seed does move the values


def test_the_orders_file_has_the_unrestricted_deployments_row_lengths(tmp_path):
    """``orders-star-10m``'s bytes-per-row layout to the byte: the chunk
    cuts of the streamed ingest, and so its compiled shapes, are shared."""
    base_cfg = run.load_json("configs", "orders-star-10m.json")
    cfg = run.load_json("configs", f"{CONFIG}.json")
    assert cfg["layout_seed"] == base_cfg["layout_seed"]
    for table in ("orders", "people", "stock"):
        for key in ("columns", "rows", "ts_distinct", "host_prefix_rows", "filter"):
            assert cfg["tables"][table].get(key) == base_cfg["tables"][table].get(key), (table, key)
    lengths = []
    for gen_name, c, seed in (("orders", base_cfg, 11), ("orders_selective", cfg, 4_300_000_222)):
        root = tmp_path / gen_name
        root.mkdir()
        d = run.load_module("gen", gen_name).Data(c, seed, str(root), ("orders", "people"), rows=ROWS)
        lengths.append((row_bytes(d.paths["orders"]), row_bytes(d.paths["people"])))
    assert lengths[0] == lengths[1]


def test_the_segment_at_full_size_is_the_configurations():
    cfg = run.load_json("configs", f"{CONFIG}.json")
    orders, people = cfg["tables"]["orders"], cfg["tables"]["people"]
    assert int(orders["segment_rows"]) * 10 == int(orders["rows"]) == 10_000_000
    gen = run.load_module("gen", "orders_selective")
    names = np.array(gen.base.FIRST, dtype="S")[np.arange(int(people["rows"])) % len(gen.base.FIRST)]
    assert int((names == orders["segment"]["name"].encode()).sum()) == 10_000
    assert cfg["reduced"] == []
