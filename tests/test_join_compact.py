"""The unique-partial compaction of ``ops/join.py`` (ISSUE 43): where a
unique index answers only some of the stream's rows, upstream's inner
``Join`` drops the others (csvplus.go:552-568), and the surviving rows'
ids are formed ON THE DEVICE by one program,
``csvplus.join.compact_partial``, whatever the placement.

``np.flatnonzero`` is the arbiter, bit for bit: the kernel alone, the
binary join and the multiway join, at no survivor, one, all but one and
at totals on both sides of a power of two, on one device and on a
row-sharded stream.  ``join:expand``'s extras, the elements the join
reads to the host (the stats' scalars and nothing row-proportional) and
the zero lowerings of a warm execution are pinned too.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from csvplus_tpu import Row, TakeRows
from csvplus_tpu.columnar.ingest import source_from_table
from csvplus_tpu.columnar.table import DeviceTable, StringColumn
from csvplus_tpu.columnar.typed import IntColumn
from csvplus_tpu.obs.recompile import RecompileWatch, compile_counts
from csvplus_tpu.ops import join as J
from csvplus_tpu.ops.join import DeviceIndex
from csvplus_tpu.ops.sort import sort_table
from csvplus_tpu.parallel.mesh import make_mesh, row_spec
from csvplus_tpu.serve.plancache import PlanCache
from csvplus_tpu.utils.observe import telemetry

N = 96  # stream rows: a multiple of the 8 simulated devices
KEPT = {"none": 0, "one": 1, "all-but-one": N - 1, "pow2": 64, "pow2+1": 65}
PLACEMENTS = ("one-device", "row-sharded")
EXTRAS = {
    "path", "tier", "form", "padded", "row_gathers", "host_sync_elements", "emitted",
    "synced", "wait_s",
}

needs8 = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs the 8 simulated devices")


def _keep(kept: int, seed: int = 43) -> np.ndarray:
    keep = np.zeros(N, dtype=bool)
    keep[np.random.default_rng(seed).choice(N, kept, replace=False)] = True
    return keep


def _placed(array: np.ndarray, placement: str):
    if placement == "one-device":
        return jnp.asarray(array)
    from jax.sharding import NamedSharding

    mesh = make_mesh(8)
    return jax.device_put(array, NamedSharding(mesh, row_spec(mesh)))


def _padded(total: int) -> int:
    return 1 << max(total - 1, 0).bit_length() if total else 1


# ---- the program alone ----------------------------------------------------


@needs8
@pytest.mark.parametrize("placement", PLACEMENTS)
@pytest.mark.parametrize("dims", (1, 2))
@pytest.mark.parametrize("kept", list(KEPT))
def test_the_program_gives_flatnonzero_bit_for_bit(kept, dims, placement):
    rng = np.random.default_rng(KEPT[kept] + dims)
    keep = _keep(KEPT[kept])
    # two dimensions: each misses rows of its own, the product keeps `keep`
    masks = [keep] if dims == 1 else [keep | (rng.random(N) < 0.3), keep | (rng.random(N) < 0.3)]
    if dims == 2:
        masks[1] &= ~(masks[0] & ~keep)  # nothing beside `keep` matches in both
    lowers = [rng.integers(0, 1000, N).astype(np.int32) for _ in masks]
    total, padded = int(keep.sum()), _padded(int(keep.sum()))
    sel, build = J._compact_partial_kernel(
        tuple(_placed(lo, placement) for lo in lowers),
        tuple(_placed(m.astype(np.int32), placement) for m in masks),
        padded,
    )
    want = np.flatnonzero(keep)
    sel = np.asarray(sel)
    assert sel.dtype == np.int32 and sel.shape == (padded,)
    assert np.array_equal(sel[:total], want) and not sel[total:].any()
    for got, lo in zip(build, lowers):
        got = np.asarray(got)
        assert got.dtype == np.int32 and got.shape == (padded,)
        assert np.array_equal(got[:total], lo[want]) and not got[total:].any()
    # ONE registered program for both placements; the old forms are gone
    assert "join.compact_partial" in compile_counts()
    assert not hasattr(J, "_host_compact_ids") and not hasattr(J, "_multiway_select_kernel")


# ---- the joins ------------------------------------------------------------


def _index(data, keys):
    return DeviceIndex.build(sort_table(DeviceTable.from_pylists(data), keys), keys)


def _people(ids):
    return {
        "id": [f"c{i}" for i in ids],
        "name": [f"n{i % 7}" for i in ids],
        "surname": [f"s{i % 11}" for i in ids],
    }


def _stock(ids):
    return {
        "prod_id": [f"p{i}" for i in ids],
        "product": [f"prod{i}" for i in ids],
        "price": [f"{i}.99" for i in ids],
    }


def _orders(cust, prod, placement: str) -> DeviceTable:
    table = DeviceTable(
        {
            "cust_id": IntColumn(b"c", jnp.asarray(np.asarray(cust, dtype=np.int32))),
            "prod_id": IntColumn(b"p", jnp.asarray(np.asarray(prod, dtype=np.int32))),
            "qty": StringColumn.from_values([str(1 + i % 9) for i in range(len(cust))], None),
        },
        len(cust),
        None,
    )
    return table if placement == "one-device" else table.with_sharding(make_mesh(8))


def _deployment(kept: str, placement: str):
    """(stream, specs, cust, prod, keep): people holds the even ids
    0..198 and the stream names an even id exactly on the rows of
    ``keep``; every ``prod_id`` exists."""
    rng = np.random.default_rng(KEPT[kept])
    keep = _keep(KEPT[kept])
    cust = np.where(keep, 2 * rng.integers(0, 100, N), 2 * rng.integers(0, 100, N) + 1)
    prod = rng.integers(0, 20, N)
    people, stock = _index(_people(range(0, 200, 2)), ["id"]), _index(_stock(range(20)), ["prod_id"])
    specs = [(people, ("cust_id",)), (stock, ("prod_id",))]
    return _orders(cust, prod, placement), specs, cust, prod, keep


def _want_rows(cust, prod, keep, dims: int):
    rows = []
    for i in np.flatnonzero(keep).tolist():
        c, p = int(cust[i]), int(prod[i])
        row = {"id": f"c{c}", "name": f"n{c % 7}", "surname": f"s{c % 11}",
               "cust_id": f"c{c}", "prod_id": f"p{p}", "qty": str(1 + i % 9)}
        if dims == 2:
            row.update(product=f"prod{p}", price=f"{p}.99")
        rows.append(row)
    return rows


@needs8
@pytest.mark.parametrize("placement", PLACEMENTS)
@pytest.mark.parametrize("dims", (1, 2), ids=("binary", "multiway"))
@pytest.mark.parametrize("kept", list(KEPT))
def test_a_partial_join_keeps_the_matched_rows_in_order(kept, dims, placement):
    stream, specs, cust, prod, keep = _deployment(kept, placement)
    total = int(keep.sum())
    J.multiway_join(stream, specs[:dims])  # an index's set-up reads (its build sample) happen once
    with telemetry.collect() as recs:
        got = J.multiway_join(stream, specs[:dims])  # one spec: the binary join_tables
        synced = telemetry.host_sync_elements
    (expand,) = [r for r in recs if r.stage == "join:expand"]
    extra = expand.extra
    assert got.nrows == total == expand.rows_out == extra["emitted"]
    assert [dict(r) for r in got.to_rows()] == _want_rows(cust, prod, keep, dims)
    assert extra["path"] == ("unique-partial" if dims == 1 else "multiway-unique-partial")
    assert extra["tier"] == "device" and extra["form"] == J.COMPACT_FORM
    assert extra["padded"] == _padded(total)
    # the stats' scalars are ALL the join reads to the host
    assert extra["host_sync_elements"] == (2 if dims == 1 else 3) == synced
    assert EXTRAS <= set(extra)


# ---- the stage's extras on every path --------------------------------------


def _path_case(path: str):
    rng = np.random.default_rng(len(path))
    people, stock = _index(_people(range(100)), ["id"]), _index(_stock(range(20)), ["prod_id"])
    cust, prod = rng.integers(0, 100, N), rng.integers(0, 20, N)
    if path.endswith("unique-partial"):
        cust = rng.integers(-5, 120, N)
    if path.endswith("fan-out"):
        people = _index(_people([i for i in range(100) for _ in range(1 + i % 3)]), ["id"])
    specs = [(people, ("cust_id",)), (stock, ("prod_id",))]
    return _orders(cust, prod, "one-device"), specs


@pytest.mark.parametrize(
    "path,form",
    [
        ("unique-identity", "identity"), ("unique-partial", "sort"), ("fan-out", "prefix-scatter"),
        ("multiway-unique-identity", "identity"), ("multiway-unique-partial", "sort"),
        ("multiway-fan-out", "prefix-scatter"),
    ],
)
def test_join_expand_records_its_extras_on_every_path(path, form):
    stream, specs = _path_case(path)
    multiway = path.startswith("multiway")
    dims = 2 if multiway else 1
    J.multiway_join(stream, specs[:dims])  # an index's set-up reads happen once
    with telemetry.collect() as recs:
        got = J.multiway_join(stream, specs[:dims])  # one spec: the binary join_tables
        synced = telemetry.host_sync_elements
    (expand,) = [r for r in recs if r.stage == "join:expand"]
    extra = expand.extra
    assert EXTRAS <= set(extra), sorted(EXTRAS - set(extra))
    assert (extra["path"], extra["tier"], extra["form"]) == (path, "device", form)
    assert extra["emitted"] == expand.rows_out == got.nrows
    assert extra["host_sync_elements"] == (3 if multiway else 2) == synced
    assert extra["synced"] is True and extra["wait_s"] >= 0.0
    if form == "identity":
        assert extra["padded"] == 0 and extra["row_gathers"] == 0
    else:
        assert extra["padded"] == _padded(got.nrows)
        # the sort gathers nothing (a depth-2 dimension's slots -> rows: one each);
        # the expansion scan gathers its segment starts and bounds
        assert extra["row_gathers"] in (0, 1, 2) if form == "sort" else extra["row_gathers"] >= 2


def test_a_host_answering_tier_says_so(monkeypatch):
    """The partitioned tier's numpy answers expand on the host: the one
    path whose ``tier`` is ``host``."""
    stream, specs = _path_case("unique-partial")
    real = DeviceIndex.probe

    def numpy_probe(self, probe_cols, nrows, part_info=None):
        lower, counts = real(self, probe_cols, nrows, part_info=part_info)
        return np.asarray(lower), np.asarray(counts)

    monkeypatch.setattr(DeviceIndex, "_composed_for", lambda self, pc, nrows: None)
    monkeypatch.setattr(DeviceIndex, "probe", numpy_probe)
    with telemetry.collect() as recs:
        got = J.join_tables(stream, specs[0][0], specs[0][1])
    (extra,) = [r.extra for r in recs if r.stage == "join:expand"]
    assert (extra["path"], extra["tier"], extra["form"]) == ("host-expand", "host", "numpy")
    assert extra["host_sync_elements"] == 0 and extra["emitted"] == got.nrows
    assert EXTRAS <= set(extra)


# ---- nothing row-proportional crosses to the host ---------------------------


class _SmallReadsOnly:
    """``numpy`` as ``ops/join.py`` sees it, with an ``asarray`` that
    refuses more than 64 elements: the stats' scalars pass, a count lane
    does not."""

    def __init__(self):
        self.read = []

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kwargs):
        out = np.asarray(a, *args, **kwargs)
        assert out.size <= 64, f"the join read {out.size} elements to the host"
        self.read.append(out.size)
        return out


@pytest.mark.parametrize("dims", (1, 2), ids=("binary", "multiway"))
def test_the_join_reads_only_its_stats_to_the_host(dims, monkeypatch):
    stream, specs = _path_case("unique-partial")
    # warm: compositions and the index's own set-up reads happen once
    J.multiway_join(stream, specs[:dims])
    shim = _SmallReadsOnly()
    monkeypatch.setattr(J, "np", shim)
    with telemetry.collect() as recs:
        got = J.multiway_join(stream, specs[:dims])  # one spec: the binary join
        synced = telemetry.host_sync_elements
    stages = {r.stage: r.extra for r in recs if r.stage.startswith("join:")}
    assert stages["join:expand"]["path"].endswith("unique-partial")
    assert shim.read == [2 if dims == 1 else 3]
    assert sum(e.get("host_sync_elements", 0) for e in stages.values()) == synced == shim.read[0]
    assert 0 < got.nrows < N


# ---- a warm execution lowers nothing ----------------------------------------


def test_five_warm_executions_lower_nothing():
    _, _, cust, prod, keep = _deployment("pow2+1", "one-device")
    people = TakeRows([Row({k: v[0] for k, v in _people([i]).items()}) for i in range(0, 200, 2)])
    stock = TakeRows([Row({k: v[0] for k, v in _stock([i]).items()}) for i in range(20)])
    people, stock = people.index_on("id"), stock.index_on("prod_id")
    people.on_device("cpu")
    stock.on_device("cpu")
    plan = source_from_table(_orders(cust, prod, "one-device")).join(people, "cust_id").join(stock).plan
    cache = PlanCache()
    first = cache.execute(plan).sync()
    assert [dict(r) for r in first.to_rows()] == _want_rows(cust, prod, keep, 2)
    with RecompileWatch(plancache=cache) as watch, telemetry.collect() as recs:
        for _ in range(5):
            again = cache.execute(plan).sync()
        paths = [r.extra["path"] for r in recs if r.stage == "join:expand"]
    watch.assert_zero("five warm executions of a partial join")
    assert paths == ["multiway-unique-partial"] * 5
    assert again.to_rows() == first.to_rows()
