"""Sharded streamed ingest: chunks land on their shard (VERDICT r4 #3).

With ``OnDevice(shards=k)`` on a streamed-tier file, each chunk's
arrays upload straight to the mesh device that owns those rows; finalize
stitches per-device segments into one row-sharded global array with only
boundary slivers moving between devices.  No full-table single-device
buffer may exist at any point.
"""

import numpy as np
import pytest

import jax

from csvplus_tpu import FromFile, Like, Take
from csvplus_tpu.columnar.typed import IntColumn
from csvplus_tpu.utils.observe import telemetry

pytest.importorskip("csvplus_tpu.native.scanner")

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU mesh"
)


@pytest.fixture(autouse=True)
def _stream_small_files(monkeypatch):
    monkeypatch.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")
    monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", "1024")


def _dicts(rows):
    return [dict(r) for r in rows]


def _write(tmp_path, text, name="s.csv"):
    p = tmp_path / name
    p.write_text(text)
    return str(p)


@needs_mesh
def test_chunks_land_on_shards(tmp_path):
    path = _write(
        tmp_path,
        "order_id,cust_id,qty\n"
        + "".join(f"o{i},c{i % 11},{i % 50}\n" for i in range(3000)),
    )
    with telemetry.collect() as records:
        src = FromFile(path).on_device(shards=8)
        t = src.plan.table
    stages = {r.stage for r in records}
    # the sharded finalize ran (and therefore no post-hoc with_sharding
    # re-upload of a full single-device table)
    assert "ingest:shard-assemble" in stages
    assert getattr(t, "_pre_sharded", False)
    assemble = next(r for r in records if r.stage == "ingest:shard-assemble")
    assert assemble.extra["n_shards"] == 8
    # the placement bound: no shard may hold more than ~n/k (+pad) rows
    assert assemble.extra["max_shard_rows"] <= -(-3000 // 8)
    for c in t.columns.values():
        assert len(c.storage.sharding.device_set) == 8
    assert _dicts(t.to_rows()) == _dicts(Take(FromFile(path)).to_rows())


@needs_mesh
def test_sharded_ingest_parity_mixed_kinds(tmp_path):
    """Dict + typed columns, mid-stream demotion, row count not
    divisible by the mesh — the padded assembly must stay invisible."""
    body = "".join(f"v{i},name{i % 5},{i % 30}\n" for i in range(800))
    body += "NOT_NUM,name0,0\n"
    body += "".join(f"v{i},name{i % 5},{i % 30}\n" for i in range(436))
    path = _write(tmp_path, "a,b,c\n" + body)
    import os

    src = FromFile(path).on_device(shards=8)
    t = src.plan.table
    assert not isinstance(t.columns["a"], IntColumn)  # demoted mid-stream
    if os.environ.get("CSVPLUS_TYPED_LANES", "1") != "0":
        assert isinstance(t.columns["b"], IntColumn)
    host = Take(FromFile(path)).to_rows()
    assert len(host) == 1237
    assert _dicts(t.to_rows()) == _dicts(host)


@needs_mesh
def test_sharded_ingest_pipeline_parity(tmp_path):
    rng = np.random.default_rng(3)
    opath = _write(
        tmp_path,
        "order_id,cust_id,qty\n"
        + "".join(
            f"o{i},c{int(rng.integers(0, 30))},{int(rng.integers(1, 99))}\n"
            for i in range(2500)
        ),
        "orders.csv",
    )
    cpath = _write(
        tmp_path,
        "id,name\n" + "".join(f"c{i},n{i % 7}\n" for i in range(30)),
        "cust.csv",
    )
    cust_h = Take(FromFile(cpath)).unique_index_on("id")
    want = (
        Take(FromFile(opath))
        .filter(Like({"qty": "42"}))
        .join(cust_h, "cust_id")
        .to_rows()
    )
    cust_d = FromFile(cpath).on_device().unique_index_on("id")
    got = (
        FromFile(opath)
        .on_device(shards=8)
        .filter(Like({"qty": "42"}))
        .join(cust_d, "cust_id")
        .to_rows()
    )
    assert _dicts(want) == _dicts(got)


@needs_mesh
def test_tiny_table_trailing_devices_all_padding(tmp_path):
    """9 rows over 8 shards: trailing devices' blocks are pure padding
    (review r5 regression: the pad buffer overflowed the block size)."""
    path = _write(tmp_path, "a,b\n" + "".join(f"x{i},{i}\n" for i in range(9)))
    t = FromFile(path).on_device(shards=8).plan.table
    assert getattr(t, "_pre_sharded", False)
    assert _dicts(t.to_rows()) == _dicts(Take(FromFile(path)).to_rows())


@needs_mesh
def test_lane_threshold_lands_pre_sharded_under_mesh(tmp_path, monkeypatch):
    """A string column crossing the lane threshold under sharded ingest
    keeps its codes on their shards and ships the deferred lane
    dictionary; settling the dictionary afterwards leaves the values
    and the placement as they were."""
    monkeypatch.setenv("CSVPLUS_DICT_DEVICE_MIN_DISTINCT", "50")
    monkeypatch.setenv("CSVPLUS_TYPED_LANES", "0")  # force dictionary mode
    want = [f"u{(i * 7) % 400}x" for i in range(400)]  # unsorted, so settling moves codes
    path = _write(tmp_path, "k\n" + "".join(v + "\n" for v in want))
    with telemetry.collect() as records:
        t = FromFile(path).on_device(shards=8).plan.table
    stages = [r.stage for r in records]
    assert "ingest:streamed" in stages and "ingest:shard-assemble" in stages
    assert getattr(t, "_pre_sharded", False)
    col = t.columns["k"]
    assert col._dictionary is None and col.dev_dictionary is not None
    assert not col._dev_dict_sorted  # several chunks: the union is deferred
    assert len(col.storage.sharding.device_set) == 8
    assert [r["k"] for r in t.to_rows()] == want
    col._ensure_sorted_lanes()
    assert col._dev_dict_sorted and col.dict_size == 400
    assert len(col.storage.sharding.device_set) == 8
    assert [r["k"] for r in t.to_rows()] == want
    assert col.dictionary.tolist() == sorted(v.encode() for v in set(want))
