"""Skew-aware sharded join: differential/parity + evidence tests (ISSUE 15).

The contract under test (pjoin.py module docstring, "Skew (ISSUE 15)"):

* probe-side heavy hitters are detected by a SOUND sketch predicate
  (SpaceSaving count-err lower bound vs CSVPLUS_JOIN_SKEW_THRESHOLD)
  and answered through the replicated broadcast tier, the tail riding
  the hash-repartition exchange unchanged;
* the result is BITWISE-identical (positional per-column checksums) to
  the unsharded reference AND to the CSVPLUS_JOIN_SKEW=0 run — the
  "salt" is the existing row placement and the positional scatter-back
  at emit folds it out;
* uniform data is a pure passthrough: n_hot=0, the capacity counted
  from the exchange itself (the pow2 bucket of its fullest pair), the
  no-hot executable, no skew stages;
* warm re-executions recompile nothing (RecompileWatch over the
  registered pjoin.* kernels).
"""

import jax.numpy as jnp
import numpy as np
import pytest

import csvplus_tpu.ops.join as J
import csvplus_tpu.parallel.pjoin as PJ
from csvplus_tpu import Row, TakeRows
from csvplus_tpu.columnar.ingest import source_from_table
from csvplus_tpu.columnar.table import DeviceTable
from csvplus_tpu.obs.joinskew import JoinSkewStats, joinskew
from csvplus_tpu.obs.recompile import RecompileWatch
from csvplus_tpu.obs.sketch import SpaceSaving
from csvplus_tpu.parallel.mesh import make_mesh, shard_rows
from csvplus_tpu.utils.checksum import checksum_device_table
from csvplus_tpu.utils.observe import telemetry


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)


def _zipf_cust(n_rows: int, n_keys: int, s: float, seed: int) -> np.ndarray:
    """Zipf(s) key draws with a PERMUTED rank->key mapping, so the hot
    keys scatter across the build key space instead of clustering in
    one shard's range slice (same shape as the bench generator)."""
    rng = np.random.default_rng(seed)
    perm = rng.permutation(n_keys)
    w = np.arange(1, n_keys + 1, dtype=np.float64) ** -float(s)
    w /= w.sum()
    return perm[rng.choice(n_keys, size=n_rows, p=w)]


def _single_key_cust(n_rows: int, n_keys: int, share: float, seed: int):
    """Adversarial stream: key 0 owns *share* of the rows, the tail is
    uniform over [1, n_keys)."""
    rng = np.random.default_rng(seed)
    n_heavy = int(n_rows * share)
    cust = np.concatenate(
        [
            np.zeros(n_heavy, dtype=np.int64),
            rng.integers(1, n_keys, size=n_rows - n_heavy),
        ]
    )
    rng.shuffle(cust)
    return cust, 0


def _stream_table(cust: np.ndarray) -> DeviceTable:
    return DeviceTable.from_pylists(
        {
            "k": [f"c{int(v)}" for v in cust],
            "qty": [str(int(v) % 9) for v in cust],
        },
        device="cpu",
    )


def _build_index(n_keys: int, drop=frozenset()):
    rows = [
        Row({"k": f"c{i}", "name": f"n{i % 97}"})
        for i in range(n_keys)
        if i not in drop
    ]
    idx = TakeRows(rows).index_on("k")
    idx.on_device("cpu")
    return idx


def _join_checksums(table: DeviceTable, idx, shard_mesh=None):
    t = table.with_sharding(shard_mesh) if shard_mesh is not None else table
    result = source_from_table(t).join(idx, "k").to_device_table().sync()
    cols = sorted(result.columns)
    return checksum_device_table(result, cols, positional=True), result.nrows


@pytest.mark.parametrize("s", [1.05, 1.3])
@pytest.mark.parametrize("n_shards", [1, 2, 8])
def test_zipf_parity_vs_unsharded_and_disabled(monkeypatch, s, n_shards):
    """Seeded Zipf streams: the sharded skew-aware join is bitwise-equal
    (positional per-column checksums) to the unsharded reference and to
    the CSVPLUS_JOIN_SKEW=0 run, across 1/2/8-shard meshes.  At s=1.05
    the rank-1 share (~13%) only clears the threshold at 8 shards
    (tau=6.25%), so the 2-shard leg doubles as passthrough parity."""
    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    n_rows, n_keys = 16_000, 1_500
    cust = _zipf_cust(n_rows, n_keys, s, seed=17)
    idx = _build_index(n_keys)
    table = _stream_table(cust)

    want, n_ref = _join_checksums(table, idx)  # unsharded reference
    m = make_mesh(n_shards) if n_shards > 1 else None
    got_skew, n1 = _join_checksums(table, idx, shard_mesh=m)
    monkeypatch.setenv("CSVPLUS_JOIN_SKEW", "0")
    got_naive, n2 = _join_checksums(table, idx, shard_mesh=m)
    assert n_ref == n1 == n2 == n_rows
    assert got_skew == want, f"skew-aware vs unsharded ({s}, {n_shards})"
    assert got_naive == want, f"skew-disabled vs unsharded ({s}, {n_shards})"


def test_adversarial_single_key_engages_and_matches(monkeypatch, mesh):
    """90% of the stream on ONE key: the broadcast tier must engage
    (join:skew stage with rows_broadcast covering the heavy rows) and
    the answers stay exact vs the host executor."""
    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    n_rows, n_keys = 16_000, 400
    cust, _ = _single_key_cust(n_rows, n_keys, 0.9, seed=23)
    idx = _build_index(n_keys)
    table = _stream_table(cust)

    host_rows = TakeRows(table.to_rows()).join(idx, "k").to_rows()
    with telemetry.collect() as records:
        dev_rows = (
            source_from_table(table.with_sharding(mesh))
            .join(idx, "k")
            .to_rows()
        )
    assert dev_rows == host_rows
    skew = [r for r in records if r.stage == "join:skew"]
    assert skew, "broadcast tier did not engage on a 90%-single-key stream"
    extra = skew[0].extra
    assert extra["hot_keys"] >= 1
    # the heavy key owns 90% of the rows; the broadcast tier must carry
    # at least those (sampling can add a few more hot keys)
    assert extra["rows_broadcast"] >= int(0.85 * n_rows)
    assert extra["rows_broadcast"] + extra["rows_repartitioned"] == n_rows


def test_heavy_key_absent_on_build_side(monkeypatch, mesh):
    """The heavy key is tombstoned/absent on the build side: its probes
    translate to never-match, the detector's sample filters the
    negatives, and parity holds whichever tier answers the tail."""
    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    n_rows, n_keys = 16_000, 400
    cust, heavy = _single_key_cust(n_rows, n_keys, 0.9, seed=29)
    idx = _build_index(n_keys, drop=frozenset({heavy}))
    table = _stream_table(cust)

    host_rows = TakeRows(table.to_rows()).join(idx, "k").to_rows()
    dev_rows = (
        source_from_table(table.with_sharding(mesh)).join(idx, "k").to_rows()
    )
    assert dev_rows == host_rows
    # the inner join drops every heavy row: exactly the uniform tail
    # survives
    assert len(host_rows) == int((cust != heavy).sum())
    assert len(host_rows) < int(0.2 * n_rows)


def test_uniform_stream_is_pure_passthrough(monkeypatch, mesh):
    """Uniform keys: no hot tier (n_hot=0), the COUNTED capacity — the
    pow2 bucket of the fullest (source, owner) pair, settled on the
    first attempt with no overflow flag read — and no skew stages."""
    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    n_rows, n_keys = 16_000, 2_000
    rng = np.random.default_rng(31)
    cust = rng.integers(0, n_keys, size=n_rows)
    idx = _build_index(n_keys)
    table = _stream_table(cust)

    seen = []
    orig = PJ._probe_spmd_dev

    def capture(mesh_, n_shards, capacity, n_hot, positional, qk, *rest):
        seen.append((n_hot, capacity, int(qk.shape[0])))
        return orig(mesh_, n_shards, capacity, n_hot, positional, qk, *rest)

    monkeypatch.setattr(PJ, "_probe_spmd_dev", capture)
    with telemetry.collect() as records:
        source_from_table(table.with_sharding(mesh)).join(idx, "k").to_rows()
    assert seen, "partition tier did not engage"
    exchanges = [r.extra for r in records if r.stage == "join:all_to_all"]
    assert len(exchanges) == len(seen)  # one launch an exchange: no retry
    for (n_hot, capacity, m), x in zip(seen, exchanges):
        assert n_hot == 0
        assert m / 64 < x["pair_max"] < 2 * m / 64  # uniform: near the mean pair
        assert capacity == x["capacity"] == PJ._pow2(max(64, x["pair_max"]))
        assert x["capacity_from"] == "count" and x["host_sync_elements"] == 0
    stages = {r.stage for r in records}
    assert "join:broadcast" not in stages
    assert "join:skew" not in stages


def test_skew_disabled_hatch_no_detection(monkeypatch, mesh):
    """CSVPLUS_JOIN_SKEW=0: even a 90%-single-key stream runs the naive
    path (n_hot=0 launches only) and still answers exactly."""
    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    monkeypatch.setenv("CSVPLUS_JOIN_SKEW", "0")
    n_rows, n_keys = 16_000, 400
    cust, _ = _single_key_cust(n_rows, n_keys, 0.9, seed=37)
    idx = _build_index(n_keys)
    table = _stream_table(cust)

    seen = []
    orig = PJ._probe_spmd_dev

    def capture(mesh_, n_shards, capacity, n_hot, *rest):
        seen.append(n_hot)
        return orig(mesh_, n_shards, capacity, n_hot, *rest)

    monkeypatch.setattr(PJ, "_probe_spmd_dev", capture)
    host_rows = TakeRows(table.to_rows()).join(idx, "k").to_rows()
    dev_rows = (
        source_from_table(table.with_sharding(mesh)).join(idx, "k").to_rows()
    )
    assert dev_rows == host_rows
    assert seen and all(h == 0 for h in seen)


def test_warm_skew_join_zero_recompiles(monkeypatch, mesh):
    """Warm re-executions of a skew-engaged join lower NOTHING: the
    detection is deterministic per dataset, so the n_hot/capacity
    statics repeat and every pjoin.* kernel hits its jit cache."""
    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    n_rows, n_keys = 16_000, 1_500
    cust = _zipf_cust(n_rows, n_keys, 1.3, seed=41)
    idx = _build_index(n_keys)
    table = _stream_table(cust).with_sharding(mesh)

    def run():
        out = source_from_table(table).join(idx, "k").to_device_table()
        return checksum_device_table(out.sync(), positional=True)

    want = run()  # cold pass compiles
    with RecompileWatch() as watch:
        for _ in range(2):
            assert run() == want
    watch.assert_zero("warm skew-aware joins")


def test_wide_key_skew_differential(mesh):
    """62-bit packed keys (dual 31-bit lanes) through the skew tier: a
    30%-heavy int64 probe key is detected by the wide lane-split sample,
    broadcast, and the answers match numpy exactly — invalid (-1)
    probes included."""
    rng = np.random.default_rng(43)
    keys = np.sort(
        rng.integers(1 << 32, 1 << 40, size=20_000).astype(np.int64)
    )
    queries = rng.choice(keys, size=30_000).astype(np.int64)
    heavy = np.int64(keys[123])
    queries[rng.random(30_000) < 0.3] = heavy
    queries[::97] = -1
    with telemetry.collect() as records:
        lo, ct = PJ.partitioned_probe(mesh, queries, keys)
    olo = np.searchsorted(keys, queries, side="left").astype(np.int32)
    oct_ = (np.searchsorted(keys, queries, side="right") - olo).astype(
        np.int32
    )
    oct_[queries < 0] = 0
    assert (np.asarray(ct) == oct_).all()
    hit = np.asarray(ct) > 0
    assert (np.asarray(lo)[hit] == olo[hit]).all()
    skew = [r for r in records if r.stage == "join:skew"]
    assert skew and skew[0].extra["hot_keys"] >= 1
    assert skew[0].extra["rows_broadcast"] >= int(0.25 * queries.size)


def test_composite_key_skew_parity(monkeypatch, mesh):
    """Composite (two-column) keys through the skew tier: Zipf draws on
    the joint key, parity vs the unsharded reference and the disabled
    hatch — and the build-side sketch decodes hot keys to TUPLES."""
    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    joinskew.reset()
    n_rows, n_keys = 16_000, 1_200
    cust = _zipf_cust(n_rows, n_keys, 1.3, seed=47)
    rows = [
        Row({"a": f"c{i}", "b": f"x{i % 31:02d}", "name": f"n{i % 97}"})
        for i in range(n_keys)
    ]
    idx = TakeRows(rows).index_on("a", "b")
    idx.on_device("cpu")
    table = DeviceTable.from_pylists(
        {
            "a": [f"c{int(v)}" for v in cust],
            "b": [f"x{int(v) % 31:02d}" for v in cust],
            "qty": [str(int(v) % 9) for v in cust],
        },
        device="cpu",
    )

    def checks(t):
        out = source_from_table(t).join(idx, "a", "b").to_device_table()
        return checksum_device_table(
            out.sync(), sorted(out.columns), positional=True
        )

    want = checks(table)
    got_skew = checks(table.with_sharding(mesh))
    monkeypatch.setenv("CSVPLUS_JOIN_SKEW", "0")
    got_naive = checks(table.with_sharding(mesh))
    assert got_skew == want
    assert got_naive == want
    sketches = joinskew.build_sketches()
    assert "a,b" in sketches
    top = sketches["a,b"].topk(1)
    assert top and isinstance(top[0][0], tuple) and len(top[0][0]) == 2


# -- detection + sketch units ---------------------------------------------


def test_offer_counts_matches_offer_many():
    """offer_counts over np.unique output == offer_many over the raw
    stream: same counts, same observed total, native (JSON-clean) keys."""
    rng = np.random.default_rng(53)
    draws = rng.integers(0, 50, size=4_000)
    a, b = SpaceSaving(64), SpaceSaving(64)
    a.offer_many(draws.tolist())
    vals, cnts = np.unique(draws, return_counts=True)
    b.offer_counts(vals, cnts)
    assert a.observed == b.observed == draws.size
    assert dict((k, c) for k, c, _ in a.topk()) == dict(
        (k, c) for k, c, _ in b.topk()
    )
    assert all(type(k) is int for k, _, _ in b.topk())


def test_detect_hot_sound_predicate(monkeypatch, mesh):
    """A key holding 30% of the probes (>> tau = 1/16 at 8 shards) is
    ALWAYS detected; raising the threshold above its share suppresses
    it; the disabled hatch and negative (never-match) probes yield no
    detection."""
    rng = np.random.default_rng(59)
    m = 64_000
    qk = rng.integers(0, 10_000, size=m).astype(np.int32)
    qk[: int(m * 0.3)] = 777
    rng.shuffle(qk)
    qk_dev = shard_rows(mesh, qk)

    count = jnp.int32(4321)  # the route count's scalar rides every read
    hot, share, pair_max = PJ._detect_hot(qk_dev, 8, False, count)
    assert hot is not None and 777 in hot.tolist()
    assert 0.2 < share < 0.45 and pair_max == 4321

    monkeypatch.setenv("CSVPLUS_JOIN_SKEW_THRESHOLD", "0.8")
    hot2, _, pair_max = PJ._detect_hot(qk_dev, 8, False, count)
    assert hot2 is None and pair_max == 4321

    monkeypatch.delenv("CSVPLUS_JOIN_SKEW_THRESHOLD")
    monkeypatch.setenv("CSVPLUS_JOIN_SKEW", "0")
    hot3, _, pair_max = PJ._detect_hot(qk_dev, 8, False, count)
    assert hot3 is None and pair_max == 4321  # read alone

    monkeypatch.delenv("CSVPLUS_JOIN_SKEW")
    neg = np.full(m, -1, np.int32)  # all never-match: nothing to detect
    hot4, _, pair_max = PJ._detect_hot(shard_rows(mesh, neg), 8, False, count)
    assert hot4 is None and pair_max == 4321


def test_skew_capacity_bounds():
    """The sketch-informed tail capacity never exceeds the skew-naive
    default (a bad share estimate can only shrink the exchange) and
    shrinks roughly with the tail share."""
    m, n = 10_000_000, 8
    full = PJ._default_capacity(m, n)
    # 1.5x slack vs the default's 2x: never larger, even at share 0
    assert 64 <= PJ._skew_capacity(m, n, 0.0) <= full
    assert PJ._skew_capacity(m, n, 0.5) <= full // 2
    assert PJ._skew_capacity(m, n, 1.0) == 64  # floor
    assert PJ._skew_capacity(m, n, 2.0) == 64  # clamped share


# -- telemetry plane export -----------------------------------------------


def test_joinskew_registry_and_plane_export(monkeypatch, mesh):
    """A skew-engaged join lands counters in the process-global registry
    and the TelemetryPlane exports them (csvplus_join_* families) plus
    the build-side sketch (csvplus_skew_*{side="build"}) in the same
    scrape cycle."""
    from csvplus_tpu.obs.metrics import TelemetryPlane

    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    joinskew.reset()
    n_rows, n_keys = 16_000, 400
    cust, _ = _single_key_cust(n_rows, n_keys, 0.9, seed=61)
    idx = _build_index(n_keys)
    source_from_table(_stream_table(cust).with_sharding(mesh)).join(
        idx, "k"
    ).to_rows()

    snap = joinskew.counters_snapshot()
    assert "k" in snap, snap
    c = snap["k"]
    assert c["joins"] >= 1 and c["hot_keys_detected"] >= 1
    assert c["rows_broadcast"] + c["rows_repartitioned"] == c["joins"] * n_rows
    # the probe() entry offered a build-side sample exactly once
    assert "k" in joinskew.build_sketches()

    plane = TelemetryPlane()
    text = plane.registry.render()
    assert 'csvplus_join_hot_keys_detected_total{index="k"}' in text
    assert 'csvplus_join_rows_broadcast_total{index="k"}' in text
    assert 'csvplus_join_rows_repartitioned_total{index="k"}' in text
    assert 'csvplus_skew_observed_total{index="k",side="build"}' in text
    assert "csvplus_skew_topk" in text and 'side="build"' in text


def test_joinskew_stats_isolated_instance():
    """JoinSkewStats unit: counter folding and sketch creation."""
    st = JoinSkewStats(sketch_k=8)
    st.on_join("a", 2, 100, 900)
    st.on_join("a", 1, 50, 950)
    st.on_join("b", 0, 0, 10)
    snap = st.counters_snapshot()
    assert snap["a"] == {
        "joins": 2,
        "hot_keys_detected": 3,
        "rows_broadcast": 150,
        "rows_repartitioned": 1850,
    }
    st.offer_build("a", ["x", "y"], [3, 1])
    assert st.build_sketches()["a"].observed == 4
    st.reset()
    assert st.counters_snapshot() == {} and st.build_sketches() == {}


def test_merged_stages_sums_skew_extras():
    """join:skew rows from a multi-join pipeline merge by SUMMING the
    routing counts (not last-wins), so artifacts report totals."""
    with telemetry.collect():
        telemetry.add_stage(
            "join:skew", 100, 100, 0.0,
            hot_keys=2, rows_broadcast=60, rows_repartitioned=40,
            capacity=128,
        )
        telemetry.add_stage(
            "join:skew", 200, 200, 0.0,
            hot_keys=1, rows_broadcast=50, rows_repartitioned=150,
            capacity=256,
        )
        merged = {r.stage: r for r in telemetry.merged_stages()}
    row = merged["join:skew"]
    assert row.rows_in == 300
    assert row.extra["hot_keys"] == 3
    assert row.extra["rows_broadcast"] == 110
    assert row.extra["rows_repartitioned"] == 190
    assert row.extra["capacity"] == 256  # config-shaped: last wins


# -- single-pass multiway join (ISSUE 17) ------------------------------
#
# The contract under test (ops/join.py multiway_join docstring): one
# pass over the fact table resolves bounds against EVERY dimension's
# DeviceIndex, the cross-product fanout is composed via cumsum offsets,
# and the emitted table is bitwise-identical (row order, column order,
# values) to ``join_tables`` applied left to right — without
# materializing any intermediate.


def _two_dim_stream(cust: np.ndarray, prod: np.ndarray) -> DeviceTable:
    return DeviceTable.from_pylists(
        {
            "k": [f"c{int(v)}" for v in cust],
            "p": [f"p{int(v)}" for v in prod],
            "qty": [str(int(v) % 9) for v in cust],
        },
        device="cpu",
    )


def _mw_dim(prefix, key, payload, n_keys, dup_every=0):
    """A dimension DeviceIndex keyed on *key*; ``dup_every`` adds a
    second build row for every dup_every-th key (cross-product fanout).
    ``DeviceIndex.build`` expects the build table key-sorted (the
    ``index_on`` path sorts before building) — the stable sort keeps
    duplicate-key payloads in insertion order."""
    pairs = [(f"{prefix}{i}", f"v{i % 37}") for i in range(n_keys)]
    if dup_every:
        pairs += [(f"{prefix}{i}", f"dup{i}") for i in range(0, n_keys, dup_every)]
    pairs.sort(key=lambda kv: kv[0])
    return J.DeviceIndex.build(
        DeviceTable.from_pylists(
            {key: [p[0] for p in pairs], payload: [p[1] for p in pairs]},
            device="cpu",
        ),
        [key],
    )


def _cascade(stream: DeviceTable, specs) -> DeviceTable:
    out = stream
    for dev_index, cols in specs:
        out = J.join_tables(out, dev_index, cols)
    return out


def _mw_sums(t: DeviceTable):
    return checksum_device_table(t, sorted(t.columns), positional=True), t.nrows


@pytest.mark.parametrize("dist", ["uniform", "zipf"])
@pytest.mark.parametrize("n_shards", [1, 8])
def test_multiway_parity_vs_cascade(monkeypatch, dist, n_shards):
    """The ISSUE 17 hard contract: full-result positional per-column
    checksums of the single-pass multiway join equal the cascaded
    reference on uniform AND Zipf keys, K in {1, 8} shards."""
    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    n_rows, n_cust, n_prod = 4_000, 500, 60
    if dist == "zipf":
        cust = _zipf_cust(n_rows, n_cust, 1.3, seed=31)
        prod = _zipf_cust(n_rows, n_prod, 1.3, seed=32)
    else:
        rng = np.random.default_rng(33)
        cust = rng.integers(0, n_cust, size=n_rows)
        prod = rng.integers(0, n_prod, size=n_rows)
    table = _two_dim_stream(cust, prod)
    specs = [
        (_mw_dim("c", "k", "name", n_cust), ("k",)),
        (_mw_dim("p", "p", "price", n_prod), ("p",)),
    ]
    t = table.with_sharding(make_mesh(n_shards)) if n_shards > 1 else table
    want_t = _cascade(t, specs)
    got_t = J.multiway_join(t, specs)
    # the hard contract: positional per-column checksums bitwise-equal
    # to the cascaded path over the SAME (sharded) bytes ...
    assert _mw_sums(got_t) == _mw_sums(want_t), (
        f"multiway vs cascade ({dist}, K={n_shards})"
    )
    # ... and the decoded rows equal the unsharded cascade reference
    assert got_t.to_rows() == _cascade(table, specs).to_rows()


def test_multiway_empty_dimension():
    """A zero-row dimension: the fused pass reproduces the cascade's
    empty early-out — zero rows AND the cascade's exact column order."""
    table = _two_dim_stream(np.arange(50) % 13, np.arange(50) % 7)
    empty = J.DeviceIndex.build(
        DeviceTable.from_pylists({"p": [], "price": []}, device="cpu"),
        ["p"],
    )
    specs = [(_mw_dim("c", "k", "name", 100), ("k",)), (empty, ("p",))]
    want_t = _cascade(table, specs)
    got_t = J.multiway_join(table, specs)
    assert got_t.nrows == want_t.nrows == 0
    assert list(got_t.columns) == list(want_t.columns)
    assert _mw_sums(got_t) == _mw_sums(want_t)


def test_multiway_zero_matches_in_one_dim():
    """Every probe of the SECOND dimension misses: the inner join drops
    every row, exactly like the cascade (no phantom fanout)."""
    cust = np.arange(200) % 40
    prod = np.arange(200) + 10_000  # p10000... never built
    table = _two_dim_stream(cust, prod)
    specs = [
        (_mw_dim("c", "k", "name", 40), ("k",)),
        (_mw_dim("p", "p", "price", 60), ("p",)),
    ]
    want = _mw_sums(_cascade(table, specs))
    got = _mw_sums(J.multiway_join(table, specs))
    assert got == want
    assert got[1] == 0


def test_multiway_duplicate_build_keys_cross_product():
    """Duplicate build keys in BOTH dimensions: the per-row fanout is the
    PRODUCT of the per-dimension match counts, emitted in the cascade's
    nesting order (outer dim varies slower)."""
    cust = np.arange(300) % 20
    prod = np.arange(300) % 10
    table = _two_dim_stream(cust, prod)
    specs = [
        (_mw_dim("c", "k", "name", 20, dup_every=4), ("k",)),
        (_mw_dim("p", "p", "price", 10, dup_every=3), ("p",)),
    ]
    want = _mw_sums(_cascade(table, specs))
    got = _mw_sums(J.multiway_join(table, specs))
    assert got == want
    assert got[1] > table.nrows  # fanout actually expanded


def test_multiway_hot_key_in_both_dims_sharded(monkeypatch, mesh):
    """90% of the stream on ONE key in EACH dimension simultaneously:
    the sketch samples every dimension's fact key column, both hot keys
    ride the broadcast tier (per-dim routing counters), and the fused
    result stays bitwise-equal to the unsharded cascade."""
    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    monkeypatch.setenv("CSVPLUS_JOIN_SKEW", "1")
    n_rows = 16_000
    cust, _ = _single_key_cust(n_rows, 400, 0.9, seed=41)
    prod, _ = _single_key_cust(n_rows, 60, 0.9, seed=43)
    table = _two_dim_stream(cust, prod)
    specs = [
        (_mw_dim("c", "k", "name", 400), ("k",)),
        (_mw_dim("p", "p", "price", 60), ("p",)),
    ]
    host_rows = _cascade(table, specs).to_rows()
    joinskew.reset()
    got_t = J.multiway_join(table.with_sharding(mesh), specs)
    assert got_t.to_rows() == host_rows
    snap = joinskew.counters_snapshot()
    for label in ("k", "p"):
        assert snap[label]["hot_keys_detected"] >= 1, label
        assert snap[label]["rows_broadcast"] > 0, label
    mw = snap["k+p"]
    assert mw["multiway_joins"] == 1
    assert mw["multiway_dims"] == 2
    assert mw["multiway_rows_in"] == n_rows


def test_multiway_warm_zero_recompiles(monkeypatch, mesh):
    """Warm re-executions of a sharded Zipf multiway join lower NOTHING:
    the offsets/select/expand kernel statics repeat, so every registered
    kernel hits its jit cache (RecompileWatch.assert_zero)."""
    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    cust = _zipf_cust(8_000, 300, 1.3, seed=51)
    prod = _zipf_cust(8_000, 40, 1.3, seed=52)
    table = _two_dim_stream(cust, prod).with_sharding(mesh)
    specs = [
        (_mw_dim("c", "k", "name", 300), ("k",)),
        (_mw_dim("p", "p", "price", 40), ("p",)),
    ]
    want = _mw_sums(J.multiway_join(table, specs))  # cold pass compiles
    with RecompileWatch() as watch:
        for _ in range(2):
            assert _mw_sums(J.multiway_join(table, specs)) == want
    watch.assert_zero("warm multiway joins")
