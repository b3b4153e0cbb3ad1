"""The skewed four-chip cell ``lookupjoin-mesh4-zipf`` rehearsed on the
CPU's simulated mesh: the hot-key tier engages at the program's
defaults, the cell's per-layer metrics read its stages, the result is
exact, and the cell's own control (``control_mesh.py``) is caught.  By
hand, with the other tests of this directory."""

from __future__ import annotations

import run
from control_mesh import swap_two_answer_rows
from test_benchmark import ROWS, rehearse

CELL = "lookupjoin-mesh4-zipf"


def test_the_hot_key_tier_does_its_work_in_the_window():
    gen = run.load_module("gen", "orders_zipf")
    cfg = run.load_json("configs", "orders-people-mesh4-zipf.json")
    counts = gen.rank_counts(
        int(ROWS), int(cfg["tables"]["people"]["rows"]), cfg["tables"]["orders"]["cust_id_zipf_s"]
    )
    seen = []
    for seed in (2_900_000_039, 39):
        rc, lines, result = rehearse(CELL, seed, trace=1)
        assert rc == 0 and result["correct"] is True and result["failed"] == 0
        m = {k: v["value"] for k, v in result["metrics"].items()}
        hot = int(m["join.hot_keys.zipf"])
        assert hot >= 1
        assert m["join.rows_broadcast.zipf"] == counts[:hot].sum()
        assert m["join.exchange_retries"] == 0
        assert m["join.exchange_slot_fill"] == int(ROWS) / (16 * m["join.exchange_capacity.zipf"])
        assert m["join.skew_detect_host_s.zipf"] > 0 and m["join.broadcast_host_s.zipf"] > 0
        assert m["process.host_sync_elems.mesh"] <= 4096 + 2  # the sample and one (overflow, hits) read
        for stage in ("join:skew-detect", "join:broadcast", "join:skew", "join:all_to_all"):
            assert any(ln.startswith(f"check: first execution's {stage} ") for ln in lines), stage
        seen.append((hot, m["join.rows_broadcast.zipf"], m["join.exchange_capacity.zipf"]))
    assert seen[0] == seen[1]  # the configuration's, not the seed's


def test_two_swapped_answers_make_the_run_incorrect():
    rc, _, result = rehearse(CELL, 2_900_000_040, tamper=swap_two_answer_rows)
    assert rc == 0 and result["correct"] is False and result["failed"] >= 1
