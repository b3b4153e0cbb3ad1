"""``gen/orders_zipf.py``: what is the configuration's stays the same
for every seed, down to what the program's hot-key detection reads; the
first rank's share at full size is the configuration's.  By hand, with
the other tests of this directory (CPU; no file is written for the
full-size count)."""

from __future__ import annotations

import json
import os

import numpy as np

import run

CONFIG = "orders-people-mesh4-zipf"
SAMPLE_CAP = 4096  # the program's default strided sample (parallel/pjoin.py:_skew_sample_cap)


def full_config() -> dict:
    """The configuration as it is on disk (the rehearsal's ``load_json``
    cuts a four-chip configuration's dimension tables)."""
    with open(os.path.join(run.HERE, "configs", f"{CONFIG}.json")) as f:
        return json.load(f)


def test_nothing_the_program_sees_follows_the_seed(tmp_path):
    gen = run.load_module("gen", "orders_zipf")
    cfg = run.load_json("configs", f"{CONFIG}.json")
    seen, custs = [], []
    for seed in (5, 2_200_000_027, 4_100_000_123):
        root = tmp_path / str(seed)
        root.mkdir()
        d = gen.Data(cfg, seed, str(root), ("orders", "people"), rows=300_000)
        with open(d.paths["orders"], "rb") as f:
            row_bytes = np.diff(np.flatnonzero(np.frombuffer(f.read(), dtype=np.uint8) == 10))
        at = np.arange(0, d.n, -(-d.n // SAMPLE_CAP))
        sample_counts = np.sort(np.unique(d.cust[at], return_counts=True)[1])
        seen.append((
            row_bytes.tolist(), [os.path.getsize(d.paths[k]) for k in ("orders", "people")],
            len(np.unique(d.cust)), d.rank_rows.tolist(), sample_counts.tolist(),
            len(np.unique(d.ts)), len(np.unique(d.prod)),
        ))
        custs.append(d.cust)
        # the draw is what it says: rank r's customer places rank_rows[r] orders, all of them exist
        assert (np.bincount(d.cust, minlength=d.n_people)[d.customer_of_rank] == d.rank_rows).all()
        assert np.array_equal(np.sort(d.customer_of_rank), np.arange(d.n_people))
        assert d.rank_rows.sum() == d.n == 300_000
    assert seen[0] == seen[1] == seen[2]
    assert seen[0][2] == int((np.asarray(seen[0][3]) > 0).sum())
    assert not np.array_equal(custs[0], custs[1])  # the seed does move the values
    # the heaviest customer is another one for every seed
    assert len({int(np.bincount(c).argmax()) for c in custs}) == 3


def test_the_first_ranks_at_full_size_are_the_configurations():
    gen = run.load_module("gen", "orders_zipf")
    tables = full_config()["tables"]
    orders = tables["orders"]
    rows, people = int(orders["rows"]), int(tables["people"]["rows"])
    counts = gen.rank_counts(rows, people, float(orders["cust_id_zipf_s"]))
    assert counts.sum() == rows and (counts >= 0).all() and (np.diff(counts) <= 1).all()
    assert int(counts[0]) == int(orders["hot_rows"])
    assert abs(counts[0] / rows - 0.1146) < 0.001  # ISSUE 39: 1 / H(20M, 1.1) = 11.46%
    assert int((counts > 0).sum()) == int(orders["cust_id_distinct"])
    # each count is within one of its expectation
    expected = rows * np.arange(1, 101, dtype=np.float64) ** -1.1 / np.sum(
        np.arange(1, people + 1, dtype=np.float64) ** -1.1
    )
    assert np.abs(counts[:100] - expected).max() <= 1.0
