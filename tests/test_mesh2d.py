"""Executor-level 2-D (slice, chip) mesh coverage (VERDICT r4 next #5).

The partitioned join and distributed sample-sort kernels are written
over ``tuple(mesh.axis_names)`` — on a 2-D mesh their exchanges span
both axes (ICI within a slice, DCN across).  These tests pin that the
EXECUTOR actually routes over a (2, 4) mesh — ``sort_table`` through
dsort, ``join_tables`` through the partitioned probe — with parity
against the host oracle, and that the capacity-retry and hot-key
machinery fire on skewed shapes (previously only exercised on 1-D).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from csvplus_tpu.columnar.table import DeviceTable
from csvplus_tpu.ops.join import DeviceIndex, join_tables
from csvplus_tpu.ops import sort as sort_mod
from csvplus_tpu.parallel.dsort import distributed_sort
from csvplus_tpu.parallel.mesh import make_mesh_2d
from csvplus_tpu.parallel.pjoin import partitioned_probe
from csvplus_tpu.utils.observe import telemetry

needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs the 8-device CPU mesh"
)


@pytest.fixture
def mesh2():
    return make_mesh_2d(2, 4)


def _probe_oracle(index_keys, queries):
    lo = np.searchsorted(index_keys, queries, side="left")
    ct = np.searchsorted(index_keys, queries, side="right") - lo
    ct[queries < 0] = 0
    return lo, ct


@needs8
def test_partitioned_probe_2d_narrow(mesh2):
    rng = np.random.default_rng(21)
    index_keys = np.sort(rng.integers(0, 500, size=4000).astype(np.int32))
    queries = rng.integers(-5, 600, size=2048).astype(np.int32)
    queries[queries < 0] = -1
    lo, ct = partitioned_probe(mesh2, queries, index_keys)
    olo, oct_ = _probe_oracle(index_keys, queries)
    assert (ct == oct_).all()
    hit = ct > 0
    assert (lo[hit] == olo[hit]).all()


@needs8
def test_partitioned_probe_2d_wide(mesh2):
    rng = np.random.default_rng(22)
    index_keys = np.sort(
        rng.integers(0, 1 << 40, size=3000).astype(np.int64)
    )
    queries = index_keys[rng.integers(0, 3000, size=1024)].copy()
    queries[::7] = -1
    lo, ct = partitioned_probe(mesh2, queries, index_keys)
    olo, oct_ = _probe_oracle(index_keys, queries)
    assert (ct == oct_).all()
    hit = ct > 0
    assert (lo[hit] == olo[hit]).all()


@needs8
def test_partitioned_probe_2d_capacity_retry(mesh2):
    """Every probe routes into ONE shard's key range with a tiny initial
    capacity: the overflow retry must fire (observed via the per-attempt
    sync counter) and still answer exactly."""
    index_keys = np.sort(np.arange(0, 800, dtype=np.int32))
    # 512 probes, every source shard routing ALL its 64 probes into the
    # first shard's key range with capacity 8 -> per-source overflow.
    # 64 distinct values (~8 sample hits each, under the hot threshold
    # of 16) keep the hot shortcut out of the way.
    queries = (np.arange(512, dtype=np.int32) % 64).astype(np.int32)
    with telemetry.collect():
        lo, ct = partitioned_probe(mesh2, queries, index_keys, capacity=8)
        syncs = telemetry.host_sync_elements
    # syncs = 512-element sample + the route count beside it + one
    # boolean per attempt under the counted pair_max (64): two or more
    # is a retry
    assert syncs >= 512 + 1 + 2, f"capacity retry never fired ({syncs})"
    olo, oct_ = _probe_oracle(index_keys, queries)
    assert (ct == oct_).all() and (lo[ct > 0] == olo[ct > 0]).all()


@needs8
def test_partitioned_probe_2d_hot_key_short_circuit(mesh2):
    """A 30%-heavy probe key would blow the sketch's tail capacity if it
    crossed the exchange; the hot-key short circuit must absorb it in
    ONE attempt (syncs == sample + count + one (overflow, hits) read)."""
    rng = np.random.default_rng(23)
    index_keys = np.sort(rng.integers(0, 2000, size=8000).astype(np.int32))
    hot_val = np.int32(index_keys[4000])
    queries = rng.integers(0, 2000, size=8192).astype(np.int32)
    queries[rng.random(8192) < 0.3] = hot_val
    with telemetry.collect():
        lo, ct = partitioned_probe(mesh2, queries, index_keys)
        syncs = telemetry.host_sync_elements
    # strided sample (<= 4096 elements) with the route count beside it
    # (the count holds the hot rows, so the sketch's smaller capacity is
    # not guaranteed by it) + exactly one launch syncing the overflow
    # flag and the broadcast-tier hit count together (2 scalars, one host
    # round): the skew never needed a capacity retry
    assert syncs <= 4096 + 1 + 2, f"hot short-circuit did not absorb the skew ({syncs})"
    olo, oct_ = _probe_oracle(index_keys, queries)
    assert (ct == oct_).all() and (lo[ct > 0] == olo[ct > 0]).all()


@needs8
def test_dsort_2d_parity_and_skew(mesh2):
    rng = np.random.default_rng(24)
    xs = rng.integers(0, 5000, size=4096).astype(np.int32)
    vals, perm = distributed_sort(mesh2, xs)
    assert (vals == np.sort(xs)).all()
    assert (xs[perm] == vals).all()
    # heavy skew: 60% one value — routing must survive via the retry
    xs[rng.random(4096) < 0.6] = 777
    vals, perm = distributed_sort(mesh2, xs, capacity=16)
    assert (vals == np.sort(xs)).all()
    assert (xs[perm] == vals).all()


@needs8
def test_executor_join_routes_partitioned_on_2d_mesh(mesh2, monkeypatch):
    """join_tables over a 2-D-mesh-sharded stream with a large build
    side must route through the partitioned tier (not broadcast) and
    match the host oracle."""
    monkeypatch.setattr(DeviceIndex, "PARTITION_MIN_KEYS", 100)
    rng = np.random.default_rng(25)
    n_build, n_probe = 4000, 2048
    build_ids = [f"k{i:05d}" for i in range(n_build)]
    build = DeviceTable.from_pylists(
        {"id": build_ids, "val": [f"v{i % 97}" for i in range(n_build)]}
    )
    from csvplus_tpu.ops.sort import sort_table

    dev_index = DeviceIndex.build(sort_table(build, ["id"]), ["id"])
    probe_keys = [f"k{int(rng.integers(0, n_build * 2)):05d}" for _ in range(n_probe)]
    stream = DeviceTable.from_pylists({"id": probe_keys}).with_sharding(mesh2)
    with telemetry.collect():
        joined = join_tables(stream, dev_index, ["id"])
        syncs = telemetry.host_sync_elements
    assert syncs >= 1, "partitioned tier (device orchestration) never ran"
    got = sorted(
        (r["id"], r.get("val")) for r in joined.to_rows()
    )
    want = sorted(
        (k, f"v{int(k[1:]) % 97}") for k in probe_keys if int(k[1:]) < n_build
    )
    assert got == want


@needs8
def test_sort_table_routes_dsort_on_2d_mesh(mesh2, monkeypatch):
    monkeypatch.setattr(sort_mod, "DSORT_MIN_ROWS", 100)
    rng = np.random.default_rng(26)
    n = 4096
    keys = [f"s{int(rng.integers(0, 500)):03d}" for _ in range(n)]
    table = DeviceTable.from_pylists(
        {"k": keys, "p": [str(i) for i in range(n)]}
    ).with_sharding(mesh2)
    with telemetry.collect() as records:
        out = sort_mod.sort_table(table, ["k"])
    assert any(r.stage == "dsort" for r in records), "dsort did not route"
    got = [r["k"] for r in out.to_rows()]
    assert got == sorted(keys)
    # stability: payload order within equal keys preserved
    got_pairs = [(r["k"], int(r["p"])) for r in out.to_rows()]
    want_pairs = sorted(
        ((k, i) for i, k in enumerate(keys)), key=lambda t: (t[0], t[1])
    )
    assert got_pairs == want_pairs
