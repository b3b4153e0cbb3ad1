"""Sharded execution layer (M4): mesh sharding, broadcast + partitioned
all-to-all probes, and the flagship 3-way join — differential vs
host oracle, on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

import jax

from csvplus_tpu import Take, from_file
from csvplus_tpu.parallel.mesh import make_mesh, replicate, shard_rows
from csvplus_tpu.parallel.pjoin import (
    broadcast_probe,
    partition_build_keys,
    partitioned_probe,
)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def test_eight_devices_available():
    assert len(jax.devices()) == 8


def test_make_mesh_refuses_more_shards_than_devices(people_csv):
    """8 simulated devices: a 9-shard mesh raises instead of slicing to
    a shorter mesh the caller did not ask for."""
    assert make_mesh(8).devices.size == 8 and make_mesh(4).devices.size == 4
    with pytest.raises(ValueError, match="only 8"):
        make_mesh(9)
    from csvplus_tpu import FromFile

    with pytest.raises(ValueError, match="only 8"):
        FromFile(people_csv).OnDevice(shards=9)


def test_sharded_table_roundtrip(people_csv, mesh):
    """with_sharding (the one sharded-table abstraction) pads to shard
    divisibility without leaking padding into results."""
    from csvplus_tpu import from_file as ff

    dev = ff(people_csv).on_device("cpu")
    from csvplus_tpu.columnar.exec import execute_plan

    table = execute_plan(dev.plan)
    st = table.with_sharding(mesh)
    assert st.nrows == 120
    col = next(iter(st.columns.values()))
    assert len(col) % 8 == 0  # stored length padded for the mesh
    assert st.to_rows() == table.to_rows()


def test_partition_build_keys_covers_all():
    keys = np.sort(np.random.default_rng(1).integers(0, 100, 1000).astype(np.int32))
    local, lower, count, splits = partition_build_keys(keys, 8)
    sent = np.iinfo(np.int32).max
    real = local != sent
    # every unique key appears exactly once across shards, with its
    # (global lower, run length) payload reconstructing the full array
    got = local[real]
    assert np.array_equal(np.sort(got), np.unique(keys))
    for s in range(8):
        for k, lo, ct in zip(local[s][real[s]], lower[s][real[s]], count[s][real[s]]):
            assert (keys[lo : lo + ct] == k).all()
            assert ct == (keys == k).sum()


def test_partition_build_keys_heavy_key_balanced():
    """Build-side skew: one key owning 50% of the rows costs one slot —
    per-shard slot use stays balanced (VERDICT round-1 weak #6)."""
    rng = np.random.default_rng(3)
    heavy = np.full(5000, 77, dtype=np.int32)
    rest = rng.integers(0, 1000, 5000).astype(np.int32)
    keys = np.sort(np.concatenate([heavy, rest]))
    local, lower, count, splits = partition_build_keys(keys, 8)
    sent = np.iinfo(np.int32).max
    sizes = (local != sent).sum(axis=1)
    assert sizes.max() - sizes.min() <= 1  # equal unique-key slices
    # the heavy key's payload is exact
    s, j = np.argwhere(local == 77)[0]
    assert count[s, j] == 5000 + (rest == 77).sum()
    assert (keys[lower[s, j] : lower[s, j] + count[s, j]] == 77).all()


def test_partitioned_probe_differential(mesh):
    rng = np.random.default_rng(2)
    keys = np.sort(rng.integers(0, 5000, size=20_000).astype(np.int32))
    queries = rng.integers(-10, 6000, size=30_001).astype(np.int32)
    queries[queries < 0] = -1
    lo, ct = partitioned_probe(mesh, queries, keys)
    olo = np.searchsorted(keys, queries, side="left").astype(np.int32)
    oct_ = (np.searchsorted(keys, queries, side="right") - olo).astype(np.int32)
    oct_[queries < 0] = 0
    assert (ct == oct_).all()
    hit = ct > 0
    assert (lo[hit] == olo[hit]).all()


def test_partitioned_probe_heavy_build_key(mesh):
    """End-to-end exchange with 50% build-side skew: exact answers."""
    rng = np.random.default_rng(7)
    heavy = np.full(10_000, 1234, dtype=np.int32)
    rest = rng.integers(0, 3000, 10_000).astype(np.int32)
    keys = np.sort(np.concatenate([heavy, rest]))
    queries = rng.integers(-5, 3500, size=20_001).astype(np.int32)
    queries[queries < 0] = -1
    lo, ct = partitioned_probe(mesh, queries, keys)
    olo = np.searchsorted(keys, queries, side="left").astype(np.int32)
    oct_ = (np.searchsorted(keys, queries, side="right") - olo).astype(np.int32)
    oct_[queries < 0] = 0
    assert (ct == oct_).all()
    hit = ct > 0
    assert (lo[hit] == olo[hit]).all()


def test_partitioned_probe_2d_mesh_differential():
    """The all-to-all exchange spans BOTH axes of a (slice, chip) mesh —
    routing uses the flattened device index, so no probe is misrouted
    (review regression: 2-D meshes silently dropped matches)."""
    from csvplus_tpu.parallel.mesh import make_mesh_2d

    mesh2 = make_mesh_2d(2, 4)
    rng = np.random.default_rng(5)
    keys = np.sort(rng.integers(0, 5000, size=20_000).astype(np.int32))
    queries = rng.integers(-10, 6000, size=30_001).astype(np.int32)
    queries[queries < 0] = -1
    lo, ct = partitioned_probe(mesh2, queries, keys)
    olo = np.searchsorted(keys, queries, side="left").astype(np.int32)
    oct_ = (np.searchsorted(keys, queries, side="right") - olo).astype(np.int32)
    oct_[queries < 0] = 0
    assert (ct == oct_).all()
    hit = ct > 0
    assert (lo[hit] == olo[hit]).all()


def test_partitioned_probe_skew_retry(mesh):
    """The geometric capacity retry engages for moderate multi-key skew
    that stays BELOW the hot-key sampling threshold (explicit capacity=64
    start), and results stay exact."""
    rng = np.random.default_rng(3)
    keys = np.sort(rng.integers(0, 100_000, size=40_000).astype(np.int32))
    # 500 distinct moderately-repeated keys: none individually hot, but
    # together they overload single-destination slots at capacity=64
    repeats = rng.choice(keys, 500, replace=False)
    queries = np.concatenate(
        [np.repeat(repeats, 30), rng.integers(0, 110_000, 15_000).astype(np.int32)]
    ).astype(np.int32)
    rng.shuffle(queries)
    lo, ct = partitioned_probe(mesh, queries, keys, capacity=64)
    olo = np.searchsorted(keys, queries, side="left")
    oct_ = np.searchsorted(keys, queries, side="right") - olo
    assert (ct == oct_).all()
    hit = ct > 0
    assert (lo[hit] == olo[hit]).all()


def test_partitioned_probe_single_heavy_key(mesh):
    """A single fully-heavy key is absorbed by the hot-key cache."""
    rng = np.random.default_rng(3)
    keys = np.sort(rng.integers(0, 1000, size=8_000).astype(np.int32))
    heavy = np.full(4_000, keys[50], dtype=np.int32)
    lo, ct = partitioned_probe(mesh, heavy, keys)
    want = np.searchsorted(keys, keys[50], "right") - np.searchsorted(keys, keys[50])
    assert (ct == want).all()


def test_partitioned_probe_empty_index(mesh):
    lo, ct = partitioned_probe(mesh, np.arange(100, dtype=np.int32), np.empty(0, np.int32))
    assert (ct == 0).all()


def test_broadcast_probe_sharded(mesh):
    rng = np.random.default_rng(4)
    keys = np.sort(rng.integers(0, 500, size=2_000).astype(np.int32))
    queries = rng.integers(0, 700, size=8_000).astype(np.int32)
    lo, ct = broadcast_probe(replicate(mesh, keys), shard_rows(mesh, queries))
    oct_ = np.searchsorted(keys, queries, "right") - np.searchsorted(keys, queries)
    assert (np.asarray(ct) == oct_).all()


def test_flagship_threeway_matches_host(people_csv, stock_csv, orders_csv):
    """The flagship join on the device reproduces the host 3-way join."""
    host_rows = (
        Take(from_file(orders_csv).select_columns("cust_id", "prod_id", "qty", "ts"))
        .join(
            Take(
                from_file(people_csv).select_columns("id", "name", "surname")
            ).unique_index_on("id"),
            "cust_id",
        )
        .join(
            Take(
                from_file(stock_csv).select_columns("prod_id", "product", "price")
            ).unique_index_on("prod_id")
        )
        .to_rows()
    )

    cust = (
        from_file(people_csv)
        .on_device("cpu")
        .select_columns("id", "name", "surname")
        .unique_index_on("id")
    )
    prod = (
        from_file(stock_csv)
        .on_device("cpu")
        .select_columns("prod_id", "product", "price")
        .unique_index_on("prod_id")
    )
    dev_rows = (
        from_file(orders_csv)
        .on_device("cpu")
        .select_columns("cust_id", "prod_id", "qty", "ts")
        .join(cust, "cust_id")
        .join(prod, "prod_id")
        .to_rows()
    )
    assert dev_rows == host_rows


def test_dryrun_multichip_runs():
    import sys

    sys.path.insert(0, "/root/repo")
    import __graft_entry__

    __graft_entry__.dryrun_multichip(8)
    fn, args = __graft_entry__.entry()
    out = jax.jit(fn)(*args)
    assert len(out) == 3


def test_threeway_step_matches_numpy_searchsorted(corpus):
    """The exported probe step on the people/stock/orders fixture: the
    positions and validity mask a numpy ``searchsorted`` over the same
    keys gives, with build keys missing and the -1 miss code present."""
    from csvplus_tpu.models.flagship import threeway_step

    people = np.arange(len(corpus["people"]), dtype=np.int32)
    cust_keys = people[people % 7 != 0]  # every seventh customer absent
    prod_keys = np.arange(len(corpus["stock"]), dtype=np.int32)
    qk_c = np.array([o.cust_id for o in corpus["orders"]], dtype=np.int32)
    qk_p = np.array([o.prod_id for o in corpus["orders"]], dtype=np.int32)
    qk_p[::11] = -1  # untranslatable probe keys

    lo_c, lo_p, valid = map(np.asarray, threeway_step(cust_keys, prod_keys, qk_c, qk_p))

    def oracle(keys, qk):
        lo = np.minimum(np.searchsorted(keys, qk, side="left"), len(keys) - 1)
        return lo, (keys[lo] == qk) & (qk >= 0)

    olo_c, hit_c = oracle(cust_keys, qk_c)
    olo_p, hit_p = oracle(prod_keys, qk_p)
    assert (lo_c == olo_c).all() and (lo_p == olo_p).all()
    assert (valid == (hit_c & hit_p)).all()
    assert 0 < valid.sum() < len(valid) and not hit_c.all() and not hit_p.all()
    assert lo_c.dtype == lo_p.dtype == np.int32


def test_two_d_mesh_pipeline_parity(people_csv, orders_csv):
    """(slice, chip) mesh: rows shard over both axes; filter/select/join
    parity with the host path (VERDICT round-1 item 10)."""
    from csvplus_tpu.parallel.mesh import make_mesh_2d, row_spec

    mesh2 = make_mesh_2d(2, 4)
    assert mesh2.axis_names == ("slice", "shards")
    assert row_spec(mesh2) == jax.sharding.PartitionSpec(("slice", "shards"))
    idx = Take(from_file(people_csv)).unique_index_on("id")
    idx.on_device("cpu")
    host = (
        Take(from_file(orders_csv))
        .select_columns("cust_id", "qty")
        .join(idx, "cust_id")
        .top(500)
        .to_rows()
    )
    dev = (
        from_file(orders_csv)
        .on_device("cpu", mesh=mesh2)
        .select_columns("cust_id", "qty")
        .join(idx, "cust_id")
        .top(500)
        .to_rows()
    )
    assert dev == host


# -- SPMD pipeline via sharded DeviceTables (OnDevice(shards=N)) ----------


def test_sharded_pipeline_parity(people_csv, orders_csv, mesh):
    """The generic executor runs SPMD when codes carry a NamedSharding:
    full pipeline (filter+select+join+except) matches the host oracle."""
    from csvplus_tpu import Like, Take, from_file

    host = Take(from_file(people_csv))
    dev = from_file(people_csv).on_device("cpu", shards=8)

    # codes actually sharded over the mesh
    from csvplus_tpu.columnar.exec import execute_plan

    table = execute_plan(dev.plan)
    sh = next(iter(table.columns.values())).codes.sharding
    assert len(sh.device_set) == 8

    p = Like({"name": "Amelia"})
    assert dev.filter(p).to_rows() == host.filter(p).to_rows()
    assert (
        dev.select_columns("id", "name").top(17).to_rows()
        == host.select_columns("id", "name").top(17).to_rows()
    )

    cust = Take(
        from_file(people_csv).select_columns("id", "name", "surname")
    ).unique_index_on("id")
    cust.on_device("cpu")
    ho = Take(from_file(orders_csv).select_columns("cust_id", "qty"))
    do = from_file(orders_csv).on_device("cpu", shards=8).select_columns(
        "cust_id", "qty"
    )
    assert do.join(cust, "cust_id").to_rows() == ho.join(cust, "cust_id").to_rows()
    assert (
        do.except_(cust, "cust_id").to_rows() == ho.except_(cust, "cust_id").to_rows()
    )


def test_sharded_index_build_parity(people_csv, mesh):
    """Device index build (lax.sort) over sharded codes == host build."""
    from csvplus_tpu import Take, from_file

    host_idx = Take(from_file(people_csv)).index_on("surname", "name")
    dev_idx = from_file(people_csv).on_device("cpu", shards=8).index_on(
        "surname", "name"
    )
    assert Take(dev_idx).to_rows() == Take(host_idx).to_rows()
    assert dev_idx.find("Jones").to_rows() == host_idx.find("Jones").to_rows()


def test_sharded_unique_and_dedup(people_csv, mesh):
    from csvplus_tpu import CsvPlusError, Take, from_file

    dev = from_file(people_csv).on_device("cpu", shards=8)
    assert len(dev.unique_index_on("id")) == 120
    import pytest as _pytest

    with _pytest.raises(CsvPlusError):
        dev.unique_index_on("name")
    idx = dev.index_on("name")
    idx.resolve_duplicates("first")
    assert len(idx) == 10


def test_sharded_non_divisible_rows(people_csv):
    """Row counts that don't divide the mesh size get padded; padding
    rows are invisible to every stage (review/verify regression)."""
    from csvplus_tpu import Like, Not, Take, from_file

    dev = from_file(people_csv).on_device("cpu", shards=7)  # 120 % 7 != 0
    host = Take(from_file(people_csv))
    assert len(dev.to_rows()) == 120
    f = Not(Like({"name": "Nobody"}))  # passes every real row
    assert dev.filter(f).to_rows() == host.filter(f).to_rows()
    idx = dev.index_on("id")
    assert len(idx) == 120


def test_sharded_setvalue_then_filter(people_csv):
    """Constant columns match the sharded layout of their table (review
    regression: mixing a single-device constant with mesh-sharded columns
    crashed the jitted mask)."""
    from csvplus_tpu import All, Like, SetValue, Take, from_file

    host = (
        Take(from_file(people_csv))
        .map(SetValue("flag", "1"))
        .filter(All(Like({"name": "Amelia"}), Like({"flag": "1"})))
        .to_rows()
    )
    dev = (
        from_file(people_csv)
        .on_device("cpu", shards=8)
        .map(SetValue("flag", "1"))
        .filter(All(Like({"name": "Amelia"}), Like({"flag": "1"})))
        .to_rows()
    )
    assert dev == host and len(dev) == 12


def test_unsupported_plan_memoized(people_csv):
    """A plan that fails to lower is only attempted once per source."""
    import csvplus_tpu.columnar.exec as ex

    calls = {"n": 0}
    orig = ex.execute_plan

    def counting(plan):
        calls["n"] += 1
        return orig(plan)

    ex.execute_plan = counting
    try:
        from csvplus_tpu import from_file

        dev = from_file(people_csv).on_device("cpu").transform(lambda r: r)
        # transform with opaque callable breaks the plan anyway (plan None),
        # so craft an unsupported-but-planned source: join vs host-only index
        from csvplus_tpu import Take, TakeRows, Row

        idx = TakeRows([Row({"id": "1", "v": "x"})]).index_on("id")
        idx.device_table = object.__new__(type("F", (), {"supported": False}))
        src = from_file(people_csv).on_device("cpu").join(idx, "id")
        n0 = calls["n"]
        src.to_rows()
        src.to_rows()
        # run 1: join plan attempted once (fails) + upstream prefix for
        # the host fallback; run 2: join plan SKIPPED (memo), upstream
        # prefix only.  Without the memo this would be 4.
        assert calls["n"] - n0 == 3
        assert src._plan_unsupported
    finally:
        ex.execute_plan = orig


def test_wide_key_partitioned_probe_differential(mesh):
    """int64 (62-bit) packed keys ride the SAME all_to_all exchange via
    dual 31-bit lanes — differential vs numpy (VERDICT round-1 item 5)."""
    rng = np.random.default_rng(9)
    # keys above the 31-bit packed range force the wide tier
    keys = np.sort(
        rng.integers(1 << 32, 1 << 40, size=20_000).astype(np.int64)
    )
    queries = rng.choice(
        np.concatenate([keys, rng.integers(1 << 32, 1 << 40, size=5000)]),
        size=30_001,
    ).astype(np.int64)
    queries[::97] = -1  # invalid probes answer (lo=-1, ct=0)
    lo, ct = partitioned_probe(mesh, queries, keys)
    olo = np.searchsorted(keys, queries, side="left").astype(np.int32)
    oct_ = (np.searchsorted(keys, queries, side="right") - olo).astype(np.int32)
    oct_[queries < 0] = 0
    assert (ct == oct_).all()
    hit = ct > 0
    assert (lo[hit] == olo[hit]).all()


def test_wide_composite_key_join_sharded(monkeypatch):
    """A 2x64K-cardinality composite key (>31-bit packed) joins through
    the device wide tier AND the partitioned path on a sharded stream,
    matching the host oracle (VERDICT round-1 item 5's done criterion)."""
    import csvplus_tpu.ops.join as J
    import csvplus_tpu.parallel.pjoin as PJ
    from csvplus_tpu import Row, TakeRows
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    calls = {"n": 0}
    orig = PJ.partitioned_probe_device_wide

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(PJ, "partitioned_probe_device_wide", counting)

    rng = np.random.default_rng(13)
    n = 66_000  # cardinality past 64K so each column needs 17 bits
    a_vals = [f"a{i:06d}" for i in range(n)]
    b_vals = [f"b{i:06d}" for i in range(n)]
    rows = [
        Row({"a": a_vals[i], "b": b_vals[i], "v": str(i)}) for i in range(n)
    ]
    idx = TakeRows(rows).index_on("a", "b")
    idx.on_device("cpu")
    assert idx.device_table.packed_hi is not None  # wide tier engaged

    pa = rng.integers(0, n, size=4000)
    probes = {
        "a": [a_vals[i] for i in pa],
        "b": [b_vals[i if i % 3 else (i + 1) % n] for i in pa],
    }
    host_rows = [Row({"a": x, "b": y}) for x, y in zip(probes["a"], probes["b"])]
    host = TakeRows(host_rows).join(idx, "a", "b").to_rows()

    from csvplus_tpu.parallel.mesh import make_mesh

    table = DeviceTable.from_pylists(probes, device="cpu").with_sharding(make_mesh(8))
    dev = source_from_table(table).join(idx, "a", "b").to_rows()
    assert dev == host
    assert calls["n"] >= 1  # the wide partitioned path actually ran

    # carry regression: a PREFIX probe (join on "a" only) whose code has
    # its low 14 bits all ones (16383) makes the upper-bound lane sum hit
    # exactly 2^31 — the carry must not sign-fill (review regression)
    edge = [Row({"a": a_vals[16383]}), Row({"a": a_vals[16384]})]
    host_edge = TakeRows(edge).join(idx, "a").to_rows()
    dev_edge = source_from_table(
        DeviceTable.from_rows(edge, device="cpu")
    ).join(idx, "a").to_rows()
    assert dev_edge == host_edge and len(dev_edge) == 2


def test_executor_join_partitioned_path(people_csv, orders_csv, monkeypatch):
    """With a low partition threshold and a SHARDED stream, the generic
    executor's join probes via the all_to_all partitioned path — proven
    by counting partitioned_probe calls — and stays identical."""
    import csvplus_tpu.ops.join as J
    import csvplus_tpu.parallel.pjoin as PJ
    from csvplus_tpu import Take, from_file

    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    calls = {"n": 0}
    orig = PJ.partitioned_probe_device

    def counting(*a, **k):
        calls["n"] += 1
        # the probe and its retry orchestration must not implicitly sync
        # device data to host — only the explicit device_get of the hot
        # sample and the overflow scalar are allowed (VERDICT weak #3)
        with jax.transfer_guard_device_to_host("disallow"):
            return orig(*a, **k)

    # ops.join imports partitioned_probe_device from the module at call
    # time, so patching the module attribute intercepts the executor
    monkeypatch.setattr(PJ, "partitioned_probe_device", counting)

    cust = Take(
        from_file(people_csv).select_columns("id", "name", "surname")
    ).unique_index_on("id")
    host_rows = (
        Take(from_file(orders_csv).select_columns("cust_id", "qty"))
        .join(cust, "cust_id")
        .to_rows()
    )
    cust.on_device("cpu")
    dev_rows = (
        from_file(orders_csv)
        .on_device("cpu", shards=8)  # sharded stream engages partitioning
        .select_columns("cust_id", "qty")
        .join(cust, "cust_id")
        .to_rows()
    )
    assert dev_rows == host_rows
    assert calls["n"] >= 1  # the partitioned path actually ran
    # an UNSHARDED stream keeps broadcasting (placement-respecting gate)
    n0 = calls["n"]
    dev2 = (
        from_file(orders_csv)
        .on_device("cpu")
        .select_columns("cust_id", "qty")
        .join(cust, "cust_id")
        .to_rows()
    )
    assert dev2 == host_rows and calls["n"] == n0
    # prefix probes (Find) keep using broadcast and stay correct
    assert cust.find("55").to_rows() == [r for r in Take(cust) if r["id"] == "55"]


def test_partitioned_probe_hot_key_short_circuit(mesh, monkeypatch):
    """Heavy probe keys are answered via the sampled hot-key cache: one
    launch of the hot values' own answers (no capacity retries), exact
    results on a hot/cold mix."""
    import csvplus_tpu.parallel.pjoin as PJ

    calls = {"n": 0}
    orig = PJ._hot_answers_spmd

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(PJ, "_hot_answers_spmd", counting)

    rng = np.random.default_rng(9)
    keys = np.sort(rng.integers(0, 2000, size=16_000).astype(np.int32))
    heavy_val = keys[777]
    cold = rng.integers(-5, 2500, size=6_000).astype(np.int32)
    cold[cold < 0] = -1
    queries = np.concatenate([np.full(10_000, heavy_val, np.int32), cold])
    rng.shuffle(queries)

    lo, ct = PJ.partitioned_probe(mesh, queries, keys)
    olo = np.searchsorted(keys, queries, side="left")
    oct_ = np.searchsorted(keys, queries, side="right") - olo
    oct_[queries < 0] = 0
    assert (ct == oct_).all()
    hit = ct > 0
    assert (lo[hit] == olo[hit]).all()
    assert calls["n"] == 1  # hot keys bypassed routing; no retry needed


def test_flagship_partial_matches(people_csv, stock_csv):
    """The flagship join with unmatched stream keys compacts exactly
    like the host join (the non-all-valid path)."""
    from csvplus_tpu import Row, Take, TakeRows, from_file
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    orders_rows = [
        Row({"cust_id": "5", "prod_id": "1", "qty": "2"}),
        Row({"cust_id": "99999", "prod_id": "1", "qty": "3"}),  # no customer
        Row({"cust_id": "7", "prod_id": "777", "qty": "4"}),  # no product
        Row({"cust_id": "8", "prod_id": "0", "qty": "5"}),
    ]
    cust = Take(
        from_file(people_csv).select_columns("id", "name", "surname")
    ).unique_index_on("id")
    prod = Take(
        from_file(stock_csv).select_columns("prod_id", "product", "price")
    ).unique_index_on("prod_id")
    host = TakeRows(orders_rows).join(cust, "cust_id").join(prod).to_rows()
    cust.on_device("cpu")
    prod.on_device("cpu")
    orders_t = DeviceTable.from_rows(orders_rows, device="cpu")
    dev = source_from_table(orders_t).join(cust, "cust_id").join(prod, "prod_id")
    assert dev.to_rows() == host
    assert len(host) == 2


def test_flagship_padded_sharded_stream(people_csv, stock_csv, mesh):
    """The flagship join over a mesh-sharded (padded) orders table
    stays exact: no pad row joins (review regression)."""
    from csvplus_tpu import Row, Take, TakeRows, from_file
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    orders_rows = [
        Row({"cust_id": str(i % 120), "prod_id": str(i % 8), "qty": str(i)})
        for i in range(6)  # 6 % 8 != 0 -> padding on the mesh
    ]
    cust = Take(
        from_file(people_csv).select_columns("id", "name")
    ).unique_index_on("id")
    prod = Take(
        from_file(stock_csv).select_columns("prod_id", "product")
    ).unique_index_on("prod_id")
    host = TakeRows(orders_rows).join(cust, "cust_id").join(prod).to_rows()
    cust.on_device("cpu")
    prod.on_device("cpu")
    orders_t = DeviceTable.from_rows(orders_rows, device="cpu").with_sharding(mesh)
    dev = source_from_table(orders_t).join(cust, "cust_id").join(prod, "prod_id")
    assert dev.to_rows() == host and len(host) == 6


def test_partitioned_executor_join_randomized(monkeypatch, mesh):
    """Seeded random sweep: sharded streams x non-unique indexes through
    the partitioned all_to_all executor path == host, 25 shapes."""
    import random

    import csvplus_tpu.ops.join as J
    from csvplus_tpu import Row, Take, TakeRows
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable

    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    rng = random.Random(13)
    # fixed shape grid (SPMD kernels compile per shape; content random)
    shapes = [(8, 0), (8, 16), (40, 16), (40, 64), (8, 64)] * 2
    for trial, (n_idx, n_stream) in enumerate(shapes):
        vocab = [f"k{v}" for v in range(rng.randint(1, 20))]
        idx_rows = [
            Row({"k": rng.choice(vocab), "v": str(i)}) for i in range(n_idx)
        ]
        stream_rows = [
            Row({"k": rng.choice(vocab + ["miss1", "miss2"]), "s": str(i)})
            for i in range(n_stream)
        ]
        idx = TakeRows(idx_rows).index_on("k")
        host = TakeRows(stream_rows).join(idx, "k").to_rows()
        idx.on_device("cpu")
        table = DeviceTable.from_rows(stream_rows, device="cpu")
        if table.nrows:
            table = table.with_sharding(mesh)
        dev = source_from_table(table).join(idx, "k").to_rows()
        assert dev == host, f"trial {trial}: {len(dev)} vs {len(host)}"


def test_partitioned_probe_device_differential(mesh):
    """The device-resident orchestration (pad + hot-merge + retry on
    device) answers exactly like numpy, with device-array results."""
    from csvplus_tpu.parallel.pjoin import (
        partitioned_probe_device,
        prepare_partitioned,
    )

    rng = np.random.default_rng(23)
    keys = np.sort(rng.integers(0, 5000, size=20_000).astype(np.int32))
    queries = rng.integers(-10, 6000, size=30_001).astype(np.int32)
    queries[queries < 0] = -1
    prepared = prepare_partitioned(mesh, keys)
    qk_dev = shard_rows(mesh, queries[:30_000])  # divisible: sharded input
    lo, ct = partitioned_probe_device(mesh, qk_dev, prepared)
    assert isinstance(lo, jax.Array) and isinstance(ct, jax.Array)
    olo = np.searchsorted(keys, queries[:30_000], side="left")
    oct_ = np.searchsorted(keys, queries[:30_000], side="right") - olo
    oct_[queries[:30_000] < 0] = 0
    lo, ct = np.asarray(lo), np.asarray(ct)
    assert (ct == oct_).all()
    hit = ct > 0
    assert (lo[hit] == olo[hit]).all()
    # non-divisible, uncommitted input: device-side padding handles it
    qk2 = jax.device_put(queries)  # 30_001 rows, single device
    lo2, ct2 = partitioned_probe_device(mesh, qk2, prepared)
    oct2 = np.searchsorted(keys, queries, side="right") - np.searchsorted(
        keys, queries, side="left"
    )
    oct2[queries < 0] = 0
    assert (np.asarray(ct2) == oct2).all()


def test_partitioned_probe_device_hot_keys_one_attempt(mesh, monkeypatch):
    """Heavy probe keys: the device path answers them via the tiny hot
    probe + merge, so the MAIN exchange runs exactly once (no capacity
    retries), and results stay exact."""
    import csvplus_tpu.parallel.pjoin as PJ

    calls = {"n": 0}
    orig = PJ._probe_spmd_dev

    def counting(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    monkeypatch.setattr(PJ, "_probe_spmd_dev", counting)

    rng = np.random.default_rng(29)
    keys = np.sort(rng.integers(0, 2000, size=16_000).astype(np.int32))
    heavy_val = keys[777]
    cold = rng.integers(-5, 2500, size=6_000).astype(np.int32)
    cold[cold < 0] = -1
    queries = np.concatenate([np.full(10_000, heavy_val, np.int32), cold])
    rng.shuffle(queries)

    prepared = PJ.prepare_partitioned(mesh, keys)
    lo, ct = PJ.partitioned_probe_device(mesh, shard_rows(mesh, queries), prepared)
    olo = np.searchsorted(keys, queries, side="left")
    oct_ = np.searchsorted(keys, queries, side="right") - olo
    oct_[queries < 0] = 0
    lo, ct = np.asarray(lo), np.asarray(ct)
    assert (ct == oct_).all()
    hit = ct > 0
    assert (lo[hit] == olo[hit]).all()
    assert calls["n"] == 1  # hot short circuit: no geometric retries


def test_partitioned_join_sync_telemetry(people_csv, orders_csv, monkeypatch):
    """VERDICT round-2 #2's done criterion: a mesh-sharded filter->join
    pipeline through the partitioned path syncs only the hot-key sample
    and the exchange's route count beside it — counted at the actual
    device_get sites.  The capacity is the count's, so the attempt
    cannot overflow and no overflow scalar is read at all."""
    import csvplus_tpu.ops.join as J
    from csvplus_tpu import Like, Not, Take, from_file
    from csvplus_tpu.utils.observe import telemetry

    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    cust = Take(
        from_file(people_csv).select_columns("id", "name", "surname")
    ).unique_index_on("id")
    host_rows = (
        Take(from_file(orders_csv).select_columns("cust_id", "qty"))
        .filter(Not(Like({"qty": "never"})))
        .join(cust, "cust_id")
        .to_rows()
    )
    cust.on_device("cpu")
    with telemetry.collect() as records:
        dev_rows = (
            from_file(orders_csv)
            .on_device("cpu", shards=8)
            .select_columns("cust_id", "qty")
            .filter(Not(Like({"qty": "never"})))
            .join(cust, "cust_id")
            .to_rows()
        )
        synced = telemetry.host_sync_elements
    assert dev_rows == host_rows
    assert any(r.stage == "Join" for r in records)
    (detect,) = [r.extra for r in records if r.stage == "join:skew-detect"]
    (exchange,) = [r.extra for r in records if r.stage == "join:all_to_all"]
    # hot-key sample (<=4096) + the route count, one read; an O(n) sync
    # of the 10_000-row probe would trip this bound
    assert detect["sample"] + 1 <= detect["host_sync_elements"] <= 4096 + 1
    assert detect["host_sync_elements"] <= synced <= 4096 + 16
    assert exchange["capacity_from"] == "count" and exchange["pair_max"] <= exchange["capacity"]
    assert exchange["attempts"] == 1 and exchange["host_sync_elements"] == 0


# -- the exchange's capacity is counted (ISSUE 40) -------------------------


def _owner_skewed_queries(rng, per_owner_keys: int, rows_per_shard: int, to_first: int):
    """8 source shards of *rows_per_shard* probes each over the dense keys
    ``0 .. 8 * per_owner_keys``: every source sends owner 0 exactly
    *to_first* rows and spreads the rest evenly over the other seven,
    keys uniform within an owner's run — skew of OWNERS, no key anywhere
    near the hot bar."""
    shards = []
    for _ in range(8):
        rest = rows_per_shard - to_first
        sizes = [to_first] + [rest // 7 + (o < rest % 7) for o in range(7)]
        rows = np.concatenate(
            [o * per_owner_keys + rng.integers(0, per_owner_keys, n) for o, n in enumerate(sizes)]
        )
        rng.shuffle(rows)
        shards.append(rows)
    return np.concatenate(shards).astype(np.int32)


def _count_case(name: str):
    """(sorted build keys, probe keys, what the exchange must say)."""
    rng = np.random.default_rng(40)
    dense = np.arange(8000, dtype=np.int32)  # owner o holds [1000 o, 1000 (o + 1))
    if name == "uniform":
        keys = np.sort(rng.integers(0, 5000, size=20_000).astype(np.int32))
        q = rng.integers(-50, 6000, size=16_384).astype(np.int32)
        q[q < 0] = -1
        return keys, q, dict(capacity_from="count")
    if name == "fullest-pair-1.75x-the-mean":  # mean pair 256: twice the mean held it too
        return dense, _owner_skewed_queries(rng, 1000, 2048, 448), dict(
            capacity_from="count", pair_max=448, capacity=512
        )
    if name == "fullest-pair-2.5x-the-mean":  # twice the mean (512) overflowed, then retried
        return dense, _owner_skewed_queries(rng, 1000, 2048, 640), dict(
            capacity_from="count", pair_max=640, capacity=1024
        )
    if name in ("one-key-over-60-percent", "one-key-over-60-percent-skew-off"):
        q = rng.integers(0, 8000, size=16_384).astype(np.int32)
        q[rng.random(q.size) < 0.62] = 4321
        off = name.endswith("skew-off")
        return dense, q, dict(capacity_from="count" if off else "sketch")
    if name == "all-probes-invalid":
        return dense, np.full(4096, -1, np.int32), dict(
            capacity_from="count", pair_max=0, capacity=64
        )
    if name == "rows-not-a-multiple-of-the-mesh":
        return dense, rng.integers(0, 8000, size=16_381).astype(np.int32), dict(capacity_from="count")
    assert name == "wide-keys"
    keys = np.sort(rng.integers(0, 1 << 40, size=6000).astype(np.int64))
    q = keys[rng.integers(0, 6000, size=8192)].copy()
    q[::7] = -1
    q[1::7] += 1  # mostly misses, routed all the same
    return keys, q, dict(capacity_from="count")


@pytest.mark.parametrize("mesh_kind", ["1d-8", "2d-2x4"])
@pytest.mark.parametrize(
    "case",
    [
        "uniform",
        "fullest-pair-1.75x-the-mean",
        "fullest-pair-2.5x-the-mean",
        "one-key-over-60-percent",
        "one-key-over-60-percent-skew-off",
        "all-probes-invalid",
        "rows-not-a-multiple-of-the-mesh",
        "wide-keys",
    ],
)
def test_exchange_capacity_is_counted(case, mesh_kind, monkeypatch):
    """The capacity of the ``(N, C)`` slot buffers comes from a count of
    the exchange itself: ``pair_max`` equals a numpy count of the same
    routing, its pow2 bucket settles the FIRST attempt with no overflow
    flag read (where twice the mean overflowed and retried), the sketch
    shrinks it only where a key is hot, and the answers are the host
    oracle's."""
    import csvplus_tpu.parallel.pjoin as PJ
    from csvplus_tpu.parallel.mesh import make_mesh_2d
    from csvplus_tpu.utils.observe import telemetry

    mesh = make_mesh(8) if mesh_kind == "1d-8" else make_mesh_2d(2, 4)
    keys, q, want = _count_case(case)
    if case.endswith("skew-off"):
        monkeypatch.setenv("CSVPLUS_JOIN_SKEW", "0")

    with telemetry.collect() as records:
        lo, ct = partitioned_probe(mesh, q, keys)
        synced = telemetry.host_sync_elements
    (x,) = [r.extra for r in records if r.stage == "join:all_to_all"]
    detect = [r.extra for r in records if r.stage == "join:skew-detect"]

    # the count, by numpy: route as the exchange does, shard as it does
    splits = partition_build_keys(keys, 8)[3]
    dest = np.clip(np.searchsorted(splits, q, side="right") - 1, 0, 7)
    dest = np.concatenate([np.where(q >= 0, dest, 8), np.full((-q.size) % 8, 8)])
    pair_max = max(int(np.bincount(rows, minlength=9)[:8].max()) for rows in dest.reshape(8, -1))
    assert x["pair_max"] == pair_max == want.get("pair_max", pair_max)

    counted = PJ._pow2(max(64, pair_max))
    assert x["capacity_from"] == want["capacity_from"]
    assert x["attempts"] == 1 and x["retries"] == 0
    if want["capacity_from"] == "count":
        assert x["capacity"] == counted == want.get("capacity", counted)
        assert x["host_sync_elements"] == 0  # guaranteed: the flag is not read
        assert not any(r.stage in ("join:broadcast", "join:skew") for r in records)
    else:  # the count holds the hot rows: the sketch's tail capacity is the smaller
        assert x["capacity"] == PJ._skew_capacity(q.size, 8, detect[0]["hot_share"]) < counted
        assert detect[0]["hot_keys"] == 1 and x["host_sync_elements"] == 2
    lanes = 2 if keys.dtype == np.int64 else 1
    if detect:  # the count rides the sample's read
        assert detect[0]["host_sync_elements"] == lanes * -(-q.size // max(1, -(-q.size // 4096))) + 1
        assert synced == detect[0]["host_sync_elements"] + x["host_sync_elements"]
    else:  # CSVPLUS_JOIN_SKEW=0: read alone
        assert synced == 1

    olo = np.searchsorted(keys, q, side="left")
    oct_ = np.searchsorted(keys, q, side="right") - olo
    oct_[q < 0] = 0
    assert (ct == oct_).all()
    assert (lo[ct > 0] == olo[ct > 0]).all()


# -- distributed sample-sort (explicit all_to_all scale-out path) ---------


def test_distributed_sort_random(mesh):
    """Sample-sort matches np.sort on random data; the payload carries
    the sort permutation."""
    from csvplus_tpu.parallel.dsort import distributed_sort

    rng = np.random.default_rng(11)
    x = rng.integers(0, 10_000, 4096).astype(np.int32)
    vals, perm = distributed_sort(mesh, x)
    assert (vals == np.sort(x)).all()
    assert (x[perm] == vals).all()  # payload = original positions


def test_distributed_sort_skewed_retries(mesh):
    """One value owning 60% of the rows overflows the balanced slot
    estimate and exercises the geometric capacity retry."""
    from csvplus_tpu.parallel.dsort import distributed_sort

    rng = np.random.default_rng(12)
    x = rng.integers(0, 1000, 2048).astype(np.int32)
    x[: int(0.6 * x.size)] = 77
    rng.shuffle(x)
    vals, perm = distributed_sort(mesh, x)
    assert (vals == np.sort(x)).all()
    assert (x[perm] == vals).all()


def test_distributed_sort_with_payload(mesh):
    """An explicit payload column is permuted alongside the keys —
    the building block for sorting a full table by key column."""
    from csvplus_tpu.parallel.dsort import distributed_sort

    rng = np.random.default_rng(13)
    x = rng.integers(0, 50, 1000).astype(np.int32)
    payload = np.arange(1000, 2000, dtype=np.int32)
    vals, pays = distributed_sort(mesh, x, payload)
    order = np.argsort(x, kind="stable")
    assert (vals == x[order]).all()
    # key groups may permute within themselves across shards; the
    # (key, payload) multiset must survive exactly
    got = sorted(zip(vals.tolist(), pays.tolist()))
    want = sorted(zip(x[order].tolist(), payload[order].tolist()))
    assert got == want


def test_distributed_sort_tiny_and_empty(mesh):
    from csvplus_tpu.parallel.dsort import distributed_sort

    vals, perm = distributed_sort(mesh, np.array([], dtype=np.int32))
    assert vals.size == 0 and perm.size == 0
    x = np.array([5, 3, 9], dtype=np.int32)
    vals, perm = distributed_sort(mesh, x)
    assert (vals == np.sort(x)).all()
    assert (x[perm] == vals).all()


def test_distributed_sort_feeds_partitioned_probe(mesh):
    """End-to-end scale-out index build: distributed-sort the build keys,
    then answer probes through the partitioned all_to_all join — no
    single-device global sort anywhere."""
    from csvplus_tpu.parallel.dsort import distributed_sort

    rng = np.random.default_rng(14)
    keys = rng.integers(0, 500, 3000).astype(np.int32)
    sorted_keys, _ = distributed_sort(mesh, keys)
    queries = rng.integers(-5, 520, 777).astype(np.int32)
    queries[queries < 0] = -1
    lo, ct = partitioned_probe(mesh, queries, sorted_keys)
    want_lo = np.searchsorted(sorted_keys, queries, side="left")
    want_ct = np.searchsorted(sorted_keys, queries, side="right") - want_lo
    want_ct[queries < 0] = 0
    hit = ct > 0
    assert (ct == want_ct).all()
    assert (lo[hit] == want_lo[hit]).all()


def test_distributed_sort_int32_max_is_a_value(mesh):
    """INT32_MAX is an ordinary sortable key, not a sentinel: validity
    travels as its own exchanged lane (review regression)."""
    from csvplus_tpu.parallel.dsort import distributed_sort

    x = np.array([5, np.iinfo(np.int32).max, 3, np.iinfo(np.int32).max],
                 dtype=np.int32)
    vals, perm = distributed_sort(mesh, x)
    assert (vals == np.sort(x)).all()
    assert (x[perm] == vals).all()


def test_distributed_sort_wide_int64(mesh):
    """int64 (62-bit packed) keys ride the dual-lane exchange, exactly
    like the wide join tier (VERDICT round-2 #3's done criterion)."""
    from csvplus_tpu.parallel.dsort import distributed_sort

    rng = np.random.default_rng(17)
    x = rng.integers(1 << 32, 1 << 45, size=3000).astype(np.int64)
    vals, perm = distributed_sort(mesh, x)
    assert (vals == np.sort(x)).all()
    assert (x[perm] == vals).all()
    # beyond 62 bits (or negative) still fails loudly
    with pytest.raises(TypeError):
        distributed_sort(mesh, np.array([1 << 62, 1], dtype=np.int64))
    with pytest.raises(TypeError):
        distributed_sort(mesh, np.array([-5, 1], dtype=np.int64))


def test_sharded_index_build_routes_dsort(people_csv, monkeypatch):
    """A mesh-sharded table's index build sorts through the distributed
    sample-sort — proven by the telemetry stage record — and matches the
    host build exactly (VERDICT round-2 #3's done criterion)."""
    import csvplus_tpu.ops.sort as S
    from csvplus_tpu import Take, from_file
    from csvplus_tpu.utils.observe import telemetry

    monkeypatch.setattr(S, "DSORT_MIN_ROWS", 1)
    host_idx = Take(from_file(people_csv)).index_on("surname", "name")
    with telemetry.collect() as records:
        dev_idx = from_file(people_csv).on_device("cpu", shards=8).index_on(
            "surname", "name"
        )
        assert Take(dev_idx).to_rows() == Take(host_idx).to_rows()
    assert any(r.stage == "dsort" for r in records)
    # unique build over the same path
    with telemetry.collect() as records:
        uniq = from_file(people_csv).on_device("cpu", shards=8).unique_index_on("id")
        assert len(uniq) == 120
    assert any(r.stage == "dsort" for r in records)


def test_sharded_index_build_dsort_wide_keys(monkeypatch):
    """Composite keys past 31 packed bits sort through the dual-lane
    distributed sample-sort on a sharded table, matching the host."""
    import csvplus_tpu.ops.sort as S
    from csvplus_tpu import Row, Take, TakeRows
    from csvplus_tpu.columnar.ingest import source_from_table
    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu.parallel.mesh import make_mesh
    from csvplus_tpu.utils.observe import telemetry

    monkeypatch.setattr(S, "DSORT_MIN_ROWS", 1)
    rng = np.random.default_rng(31)
    n = 66_000  # cardinality past 64K: each column needs 17 bits
    perm = rng.permutation(n)
    rows_data = {
        "a": [f"a{int(v):06d}" for v in perm],  # all n values: 17 bits
        "b": [f"b{int((v * 7) % n):06d}" for v in perm],
    }
    host_rows = [Row({"a": x, "b": y}) for x, y in zip(rows_data["a"], rows_data["b"])]
    host_idx = TakeRows(host_rows).index_on("a", "b")
    table = DeviceTable.from_pylists(rows_data, device="cpu").with_sharding(
        make_mesh(8)
    )
    # the packed key must overflow one int32 lane -> dual-lane dsort tier
    key_cols = [table.columns["a"], table.columns["b"]]
    assert len(S._packed_sort_lanes(key_cols)) == 2
    with telemetry.collect() as records:
        dev_idx = source_from_table(table).index_on("a", "b")
        assert Take(dev_idx).to_rows() == Take(host_idx).to_rows()
    assert any(r.stage == "dsort" for r in records)
