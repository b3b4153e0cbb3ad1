"""``ops/gather.py`` (ISSUE 44): a small table is gathered from VMEM.

``jnp.take`` is the arbiter, bit for bit: the kernel alone over every
shape class of table, lane count and stream length (interpret mode, so
small N), then the rule that selects it, then the programs that call
it — the emit's ``csvplus.join.gather_cols`` (``ops/gather.py``'s one
entry point and its decision table, ISSUE 48), ``csvplus.join.probe_composed``
(ISSUE 44) and ``csvplus.join.expand`` (ISSUE 46) — with the kernel forced by a fixture
against the same joins without it, and the ``vmem_gathers`` counter of
``join:probe`` / ``join:expand`` / ``join:merge``.  Beside each, the run
copy's (``ops/run_copy.py``, ISSUE 47; the kernel alone is in
``tests/test_gather_runs.py``): its rule, the fan-out's build side
through it with ``join:merge``'s ``run_copies``, and every bypass.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from csvplus_tpu.columnar.table import DeviceTable, StringColumn
from csvplus_tpu.columnar.typed import IntColumn
from csvplus_tpu.ops import gather as G
from csvplus_tpu.ops import join as J
from csvplus_tpu.ops import run_copy as RC
from csvplus_tpu.ops.sort import sort_table
from csvplus_tpu.utils.observe import telemetry

from test_composed_probe import (
    JOIN_CASES, PROBE_CASES, _assert_same_table, _index, _join_case, _staged_only, _stock,
)
from test_join_compact import _deployment, needs8

I32 = np.iinfo(np.int32)


@pytest.fixture(autouse=True, scope="module")
def _journal_of_its_own():
    """The interpreter traces hundreds of sub-programs a case, and every
    trace is a ``compile`` span of the process journal: kept in a
    journal of this file's own, the process's is not trimmed by them
    (``tests/test_journal.py`` pins its ``dropped`` at 0)."""
    from csvplus_tpu.obs.span import Journal, tracer

    kept, tracer.journal = tracer.journal, Journal(trace_id=-44)
    yield
    tracer.journal = kept


def _tables(rng, T: int, L: int):
    out = []
    for _ in range(L):
        t = rng.integers(I32.min, I32.max, T, dtype=np.int32, endpoint=True)
        t[rng.integers(0, T, 4)] = [I32.min, -1, 0, I32.max]
        out.append(jnp.asarray(t))
    return tuple(out)


def _indices(rng, T: int, N: int):
    idx = rng.integers(-T - 2, T + 2, N).astype(np.int32)  # mostly in range, both signs
    special = np.array([0, T - 1, -1, -T, -T - 1, T, I32.max, I32.min], dtype=np.int32)
    at = rng.permutation(N)[: len(special)]
    idx[at] = special[: len(at)]
    return jnp.asarray(idx)


@pytest.mark.parametrize("N", [1, 1023, 1024, 5 * 1024 + 77])
@pytest.mark.parametrize("L", [1, 2, 3])
@pytest.mark.parametrize("T", [1, 127, 128, 129, 1000, 1024, 10000, 131073])
def test_take_small_is_jnp_take_bit_for_bit(T, L, N):
    rng = np.random.default_rng(T * 31 + L * 7 + N)
    tabs, idx = _tables(rng, T, L), _indices(rng, T, N)
    got = jax.jit(lambda t, i: G.take_small(t, i, vmem="interpret"))(tabs, idx)
    assert len(got) == L
    for g, t in zip(got, tabs):
        want = jnp.take(t, idx, axis=0)
        assert g.dtype == want.dtype == jnp.int32 and g.shape == want.shape == (N,)
        assert np.array_equal(np.asarray(g), np.asarray(want))


def test_the_fill_and_the_wrap_are_jnp_takes():
    """The issue's own example: ``take(arange(10, 20), [-1, -10, -11, 10])``."""
    t = jnp.arange(10, 20, dtype=jnp.int32)
    idx = jnp.asarray([-1, -10, -11, 10], dtype=jnp.int32)
    want = [19, 10, I32.min, I32.min]
    assert np.asarray(jnp.take(t, idx, axis=0)).tolist() == want
    assert np.asarray(G.take_small((t,), idx, vmem="interpret")[0]).tolist() == want
    assert np.asarray(G.take_small((t,), idx)[0]).tolist() == want  # the bypass IS jnp.take


def test_more_tables_than_one_call_reads_are_served_in_turn():
    rng = np.random.default_rng(5)
    tabs, idx = _tables(rng, 300, 2 * G._MAX_TABLES + 1), _indices(rng, 300, 2000)
    got = G.take_small(tabs, idx, vmem="interpret")
    assert len(got) == len(tabs)
    for g, t in zip(got, tabs):
        assert np.array_equal(np.asarray(g), np.asarray(jnp.take(t, idx, axis=0)))


def test_no_table_and_no_index_bypass_the_kernel():
    t = jnp.arange(5, dtype=jnp.int32)
    assert G.take_small((), jnp.zeros(3, jnp.int32), vmem="interpret") == ()
    (got,) = G.take_small((t,), jnp.zeros(0, jnp.int32), vmem="interpret")
    assert got.shape == (0,) and got.dtype == jnp.int32


# ---- the rule ---------------------------------------------------------------


@pytest.fixture
def kernel_forced(monkeypatch):
    """The kernel as the chip would choose it, run by the interpreter:
    only the backend test of the rule is answered for it."""
    monkeypatch.setattr(G, "_kernel_mode", lambda: "interpret")


def test_off_the_tpu_the_rule_chooses_jnp_take():
    t, idx = jnp.zeros(1000, jnp.int32), jnp.zeros(4096, jnp.int32)
    assert jax.default_backend() != "tpu"
    assert G.vmem_gather_selected((t,), idx) is False


def test_the_rule_reads_size_dtype_and_placement(kernel_forced):
    idx = jnp.zeros(4096, jnp.int32)
    fits = jnp.zeros(G.VMEM_GATHER_MAX_ENTRIES, jnp.int32)
    assert G.vmem_gather_selected((fits, fits), idx) == "interpret"
    assert G.vmem_gather_selected((jnp.zeros(G.VMEM_GATHER_MAX_ENTRIES + 1, jnp.int32),), idx) is False
    assert G.vmem_gather_selected((), idx) is False
    assert G.vmem_gather_selected((jnp.zeros(10, jnp.int8),), idx) is False
    assert G.vmem_gather_selected((fits,), np.zeros(4096, np.int32)) is False  # a host array: no placement
    # no index yet (the program that reads the tables forms it where they are)
    assert G.vmem_gather_selected((fits, fits)) == "interpret"
    assert G.vmem_gather_selected((jnp.zeros(G.VMEM_GATHER_MAX_ENTRIES + 1, jnp.int32),) * 2) is False
    assert G.vmem_gather_selected((fits, np.zeros(10, np.int32))) is False


@needs8
def test_a_mesh_sharded_stream_keeps_jnp_take(kernel_forced):
    from jax.sharding import NamedSharding

    from csvplus_tpu.parallel.mesh import make_mesh, row_spec

    mesh = make_mesh(8)
    idx = jax.device_put(np.zeros(4096, np.int32), NamedSharding(mesh, row_spec(mesh)))
    assert G.vmem_gather_selected((jnp.zeros(1000, jnp.int32),), idx) is False
    # ...and the run copy's rule its gathers: the probe's answer is the mesh's
    lane, whole = jnp.zeros(50_000, jnp.int32), jnp.zeros(4096, jnp.int32)
    total = 4096 * RC.RUN_COPY_MIN_MEAN_RUN
    assert RC.run_copy_selected((lane,), whole, whole, total) == "interpret"
    assert RC.run_copy_selected((lane,), idx, idx, total) is False
    assert RC.run_copy_selected((lane,), whole, idx, total) is False


def _described(n: int, dtype=jnp.int32):
    """A lane by its shape alone, placed on the first device: all the
    rule reads (a 50M-row lane is 200 MB this test never fills)."""
    from jax.sharding import SingleDeviceSharding

    return jax.ShapeDtypeStruct((n,), dtype, sharding=SingleDeviceSharding(jax.devices()[0]))


def test_off_the_tpu_the_run_copys_rule_chooses_the_gathers():
    lane, probe = _described(10_000_000), _described(100_000)
    assert jax.default_backend() != "tpu"
    assert RC.run_copy_selected((lane,) * 4, probe, probe, 10_000_000) is False


def test_the_run_copys_rule_reads_runs_size_dtype_and_placement(kernel_forced, monkeypatch):
    lane, probe = _described(10_000_000), _described(100_000)
    # the cell's first join: four 10M-row lanes, 100,000 runs of 100
    assert RC.run_copy_selected((lane,) * 4, probe, probe, 10_000_000) == "interpret"
    # the mean run, total / probes, against the constant
    at = 100_000 * RC.RUN_COPY_MIN_MEAN_RUN
    assert RC.run_copy_selected((lane,), probe, probe, at) == "interpret"
    assert RC.run_copy_selected((lane,), probe, probe, at - 1) is False
    assert RC.run_copy_selected((lane,), _described(0), _described(0), 0) is False
    # one int32 dimension a table, one length, one device
    assert RC.run_copy_selected((), probe, probe, at) is False
    assert RC.run_copy_selected((_described(1000, jnp.int8),), probe, probe, at) is False
    assert RC.run_copy_selected((lane, _described(9_999_999)), probe, probe, at) is False
    assert RC.run_copy_selected((jax.ShapeDtypeStruct((10, 10), jnp.int32, sharding=lane.sharding),), probe, probe, at) is False
    assert RC.run_copy_selected((lane,), np.zeros(100_000, np.int32), probe, at) is False  # a host array: no placement
    assert RC.run_copy_selected((np.zeros(1000, np.int32),), probe, probe, at) is False
    # a lane whole in the kernel's share of VMEM: dedup's 50M rows (200 MB) never
    assert RC._tables_per_call(10_000_000) == 2 and RC._tables_per_call(50_000_000) == 0
    assert RC.run_copy_selected((_described(50_000_000),), probe, probe, at) is False
    fits = int(RC._V5E_VMEM_BYTES * RC._VMEM_SHARE) // 4 - (2 * RC._BLOCK_ROWS + 8) * 128
    assert RC.run_copy_selected((_described(fits - 128),), probe, probe, at) == "interpret"
    assert RC.run_copy_selected((_described(fits + 128),), probe, probe, at) is False
    monkeypatch.setattr(RC, "_vmem_capacity_bytes", lambda: 16 * 1024 * 1024)  # another chip's core
    assert RC.run_copy_selected((lane,), probe, probe, at) is False
    assert RC.run_copy_selected((_described(2_000_000),), probe, probe, at) == "interpret"


# ---- the emit: one entry point, one decision table (ISSUE 48) ---------------


def _runs(rng, entries: int, probes: int, mean: int):
    """A fan-out's answer ``(first, counts, total)`` — runs of 0 to
    ``2 * mean`` rows anywhere they fit — and the ``build_ids`` it expands to."""
    counts = rng.integers(0, 2 * mean + 1, probes).astype(np.int32)
    first = (rng.integers(0, entries, probes) % np.maximum(entries - counts + 1, 1)).astype(np.int32)
    total = int(counts.sum())
    _, build_ids = J.expand_matches_device(jnp.asarray(first), jnp.asarray(counts), total)
    return (jnp.asarray(first), jnp.asarray(counts), total), build_ids


def _emit_case(case: str, rng):
    """One row of the table: ``(groups, forms, programs)``."""
    big = G.VMEM_GATHER_MAX_ENTRIES + 1
    small = G.Lanes(_tables(rng, 1000, 3), _indices(rng, 1000, 3000))
    if case == "mixed-placement":  # the partitioned tier's host ids
        return [G.Lanes(small.tables, np.asarray(small.idx))], ("eager",), ()
    if case in ("small-tables", "off-the-tpu"):  # two dimensions share the one program
        groups = [small, G.Lanes(_tables(rng, 300, 2), _indices(rng, 300, 3000))]
        if case == "off-the-tpu":
            return groups, ("lane", "lane"), ("join.gather_lane",) * 5
        return groups, ("vmem", "vmem"), ("join.gather_cols",)
    if case == "large-lanes-one-device":
        return [G.Lanes(_tables(rng, big, 2), _indices(rng, big, 3000))], ("lane",), ("join.gather_lane",) * 2
    if case == "mesh-sharded-lanes":
        from jax.sharding import NamedSharding, PartitionSpec

        from csvplus_tpu.parallel.mesh import make_mesh, row_spec

        mesh = make_mesh(8)
        idx = jax.device_put(np.asarray(_indices(rng, 1000, 4096)), NamedSharding(mesh, row_spec(mesh)))
        tabs = tuple(jax.device_put(t, NamedSharding(mesh, PartitionSpec())) for t in small.tables)
        return [G.Lanes(tabs, idx)], ("cols",), ("join.gather_cols",)
    if case in ("runs-admitted", "runs-of-mean-under-8", "statements-first-join"):
        tabs = _tables(rng, 5000, 3)
        runs, build_ids = _runs(rng, 5000, 40, 3 if case == "runs-of-mean-under-8" else 60)
        build = G.Lanes(tabs, build_ids, runs)
        if case == "runs-of-mean-under-8":  # the gathers' price a run: these tables fit VMEM
            assert runs[2] < 40 * RC.RUN_COPY_MIN_MEAN_RUN
            return [build], ("vmem",), ("join.gather_cols",)
        copied = ("join.gather_runs", "join.expand_head")
        if case == "runs-admitted":
            return [build], ("runs",), copied
        return [build, small], ("runs", "vmem"), copied + ("join.gather_cols",)
    if case == "selective-star":  # the dimensions' one program stands where the first of them does
        stream = G.Lanes(_tables(rng, big, 2), _indices(rng, big, 3000))
        dim = G.Lanes(_tables(rng, 300, 2), _indices(rng, 300, 3000))
        return [small, stream, dim], ("vmem", "lane", "vmem"), ("join.gather_cols",) + ("join.gather_lane",) * 2
    if case == "empty-index":
        return [G.Lanes(small.tables, jnp.zeros(0, jnp.int32))], ("vmem",), ("join.gather_cols",)
    assert case == "zero-tables"
    return [G.Lanes((), small.idx), small], ("none", "vmem"), ("join.gather_cols",)


@pytest.mark.parametrize("case", [
    "mixed-placement", "small-tables", "off-the-tpu", "large-lanes-one-device",
    pytest.param("mesh-sharded-lanes", marks=needs8), "runs-admitted", "runs-of-mean-under-8",
    "statements-first-join", "selective-star", "empty-index", "zero-tables",
])
def test_the_emit_is_jnp_take_and_says_what_it_did(case, monkeypatch):
    """Every form of ``ops/gather.py``'s decision table — the kernels as
    the chip would choose them, run by the interpreter — gives
    ``jnp.take``'s lanes bit for bit, and the record names the form of
    each group, the programs dispatched, and the lanes each form moved."""
    if case != "off-the-tpu":
        monkeypatch.setattr(G, "_kernel_mode", lambda: "interpret")
    groups, forms, programs = _emit_case(case, np.random.default_rng(len(case)))
    done = G.emit(groups)
    assert (done.forms, done.programs) == (forms, programs)
    assert len(done.lanes) == len(groups)
    for got, (tables, idx, _) in zip(done.lanes, groups):
        assert len(got) == len(tables)
        for g, t in zip(got, tables):
            want = jnp.take(t, jnp.asarray(idx), axis=0)
            assert g.dtype == want.dtype and np.array_equal(np.asarray(g), np.asarray(want))
    for form in ("eager", "runs", "vmem", "lane", "cols"):
        assert done.moved(form) == sum(len(g.tables) for g, f in zip(groups, forms) if f == form)


# ---- the join through it ----------------------------------------------------


def _stages(recs, name):
    return [r for r in recs if r.stage == name]


def _joined(stream, specs):
    with telemetry.collect() as recs:
        got = J.multiway_join(stream, specs)
        return got, list(recs)


@pytest.mark.parametrize("case", JOIN_CASES)
def test_multiway_join_through_the_kernel_equals_jnp_take(case, monkeypatch):
    stream, specs, _, _ = _join_case(case)
    want, ref_recs = _joined(stream, specs)
    with monkeypatch.context() as m:
        m.setattr(G, "_kernel_mode", lambda: "interpret")
        got, recs = _joined(stream, specs)
    _assert_same_table(got, want)
    (merge,), (ref_merge,) = _stages(recs, "join:merge"), _stages(ref_recs, "join:merge")
    lanes = sum(len(J._kept_build_names(di, stream.columns)) for di, _ in specs)
    streamed = 0 if got.nrows == stream.nrows else len(stream.columns)
    # every build lane of these small dimensions is served by the kernel, and so
    # are the survivors of a stream this short (at the cells' length: a program a lane)
    assert merge.extra["vmem_gathers"] == lanes + streamed
    assert merge.extra["row_gathers"] == ref_merge.extra["row_gathers"] == lanes + streamed
    assert ref_merge.extra["vmem_gathers"] == 0
    probes, ref_probes = _stages(recs, "join:probe"), _stages(ref_recs, "join:probe")
    assert len(probes) == len(ref_probes) == len(specs)
    for r, ref, (di, cols) in zip(probes, ref_probes, specs):
        assert ref.extra["vmem_gathers"] == 0
        assert r.extra["row_gathers"] == ref.extra["row_gathers"]
        # depth 2 is a range test; depth 1 reads one table (unique) or two
        entry = di._composed_for(stream.columns[cols[0]], stream.nrows)
        tables = 1 if entry.cnt_tab is None else 2
        assert r.extra["vmem_gathers"] == (0 if r.extra["depth"] == 2 else tables)


@pytest.mark.parametrize("case", sorted(PROBE_CASES))
def test_composed_probe_through_the_kernel_equals_jnp_take(case, kernel_forced):
    di, pc, expect = PROBE_CASES[case]()
    n = len(pc)
    entry = di._composed_for(pc, n)
    tabs = (entry.lower_tab,) + (() if expect["unique"] else (entry.cnt_tab,))
    with telemetry.collect() as recs:
        lower, counts = di.probe([pc], n)
        (probe,) = _stages(recs, "join:probe")
    assert probe.extra["tier"] == "direct-composed" and probe.extra["depth"] == 1
    assert probe.extra["vmem_gathers"] == len(tabs)
    ref_lower, ref_counts = J._probe_composed_kernel(pc.storage, entry.base, entry.lower_tab, entry.cnt_tab)
    assert np.array_equal(np.asarray(lower), np.asarray(ref_lower))
    assert np.array_equal(np.asarray(counts), np.asarray(ref_counts))


@needs8
@pytest.mark.parametrize("dims", [2, 1], ids=["multiway", "binary"])
def test_a_row_sharded_join_records_no_vmem_gather(dims, kernel_forced):
    stream, specs, _, _, _ = _deployment("pow2+1", "row-sharded")
    got, recs = _joined(stream, specs[:dims])
    assert 0 < got.nrows < stream.nrows
    for r in _stages(recs, "join:probe") + _stages(recs, "join:expand") + _stages(recs, "join:merge"):
        assert r.extra["vmem_gathers"] == 0, r.stage
    assert [r.extra.get("run_copies", 0) for r in _stages(recs, "join:merge")] == [0]


@pytest.mark.parametrize("staged", [False, True], ids=["composed", "staged"])
@pytest.mark.parametrize("dims", [1, 2], ids=["binary", "multiway"])
@pytest.mark.parametrize("case", ["misses", "fan-out"])
def test_probe_and_merge_always_carry_the_key(case, dims, staged, monkeypatch):
    """...and ``join:expand``; off the TPU every one of them reads 0."""
    stream, specs, _, _ = _join_case(case)
    if staged:
        with _staged_only(monkeypatch):
            _, recs = _joined(stream, specs[:dims])
    else:
        _, recs = _joined(stream, specs[:dims])
    probes, merges, expands = (_stages(recs, "join:" + s) for s in ("probe", "merge", "expand"))
    assert len(probes) == dims and len(merges) == len(expands) == 1
    assert {r.extra["tier"] for r in probes} == {"direct" if staged else "direct-composed"}
    for r in probes + merges + expands:
        assert r.extra["vmem_gathers"] == 0 and "row_gathers" in r.extra


# ---- the binary join through it (ISSUE 46) ----------------------------------


def _binary(stream, di, cols):
    with telemetry.collect() as recs:
        got = J.join_tables(stream, di, cols)
        return got, list(recs)


@pytest.mark.parametrize("dim", [0, 1], ids=["people", "stock"])
@pytest.mark.parametrize("case", JOIN_CASES)
def test_join_tables_through_the_kernel_equals_jnp_take(case, dim, monkeypatch):
    """Every path of the binary join — ``unique-identity`` over composed
    tables (depth 2) and over build rows, ``unique-partial``, ``fan-out``
    with runs of 0, 1, 2 and 3 — with both sides' lanes and the
    expansion's two reads served by the kernel: these tables all fit."""
    stream, specs, path, _ = _join_case(case)
    di, cols = specs[dim]
    want, ref_recs = _binary(stream, di, cols)
    with monkeypatch.context() as m:
        m.setattr(G, "_kernel_mode", lambda: "interpret")
        got, recs = _binary(stream, di, cols)
    _assert_same_table(got, want)
    (expand,), (ref_expand,) = _stages(recs, "join:expand"), _stages(ref_recs, "join:expand")
    (merge,), (ref_merge,) = _stages(recs, "join:merge"), _stages(ref_recs, "join:merge")
    assert ref_expand.extra["vmem_gathers"] == ref_merge.extra["vmem_gathers"] == 0
    fan = expand.extra["path"] == "fan-out"
    assert fan == (dim == 0 and path == "fan-out")
    if fan:  # the padded tail is cut: the total is no power of two
        assert expand.extra["padded"] > got.nrows > expand.extra["padded"] // 2
    assert expand.extra["vmem_gathers"] == (2 if fan else 0)
    for key in ("path", "tier", "form", "padded", "row_gathers", "host_sync_elements"):
        assert expand.extra[key] == ref_expand.extra[key], key
    for key in ("row_gathers", "build_gathers", "stream_gathers"):
        assert merge.extra[key] == ref_merge.extra[key], key
    assert merge.extra["vmem_gathers"] == merge.extra["row_gathers"] > 0
    # runs of 0 to 3 are under the run copy's constant; the other paths have no runs
    assert merge.extra["run_copies"] == ref_merge.extra["run_copies"] == 0


def _statements(n_orders: int, n_people: int = 40, n_stock: int = 20):
    """The statements cell's tables at a small size: people — the
    stream, in no key order —, the orders under a NON-unique index on
    ``cust_id`` (customer 0 places none, customer 1 one, the others
    many) and stock under its unique index."""
    rng = np.random.default_rng(46)
    cust = rng.integers(2, n_people, n_orders)
    cust[0] = 1
    prod = rng.integers(0, n_stock, n_orders)
    orders = DeviceTable(
        {
            "cust_id": IntColumn(b"c", jnp.asarray(cust.astype(np.int32))),
            "prod_id": IntColumn(b"p", jnp.asarray(prod.astype(np.int32))),
            "qty": StringColumn.from_values([str(1 + i % 9) for i in range(n_orders)], None),
            "ts": StringColumn.from_values([f"t{i % 977:03d}" for i in range(n_orders)], None),
        },
        n_orders, None,
    )
    ids = rng.permutation(n_people)
    people = DeviceTable(
        {
            "id": IntColumn(b"c", jnp.asarray(ids.astype(np.int32))),
            "name": StringColumn.from_values([f"n{i % 7}" for i in ids], None),
            "surname": StringColumn.from_values([f"s{i % 11}" for i in ids], None),
        },
        n_people, None,
    )
    by_cust = J.DeviceIndex.build(sort_table(orders, ["cust_id"]), ["cust_id"])
    return people, by_cust, _index(_stock(range(n_stock)), ["prod_id"])


def _cascade(people, by_cust, stock):
    with telemetry.collect() as recs:
        first = J.join_tables(people, by_cust, ("id",))
        got = J.join_tables(first, stock, ("prod_id",))
        return got, list(recs)


def test_the_statements_cascade_counts_what_the_cell_counts(monkeypatch):
    """``people.Join(by_cust, "id").Join(stock)`` with an orders table of
    ``VMEM_GATHER_MAX_ENTRIES + 1`` rows, one over what the VMEM gather
    takes: the first join's build side (four orders lanes, 40 runs of
    ~3,400 rows) moves by the run copy and no longer a program a lane,
    its stream side (people's three lanes) and the expansion's two
    reads go through the VMEM gather, and so do the stock join's two
    composed tables — the counters of the cell (ISSUE 46, ISSUE 47)."""
    people, by_cust, stock = _statements(G.VMEM_GATHER_MAX_ENTRIES + 1)
    want, ref_recs = _cascade(people, by_cust, stock)
    calls = []
    with monkeypatch.context() as m:
        m.setattr(G, "_kernel_mode", lambda: "interpret")
        m.setattr(G, "_gather_lane", lambda c, i, _real=G._gather_lane: calls.append(1) or _real(c, i))
        got, recs = _cascade(people, by_cust, stock)
    assert got.nrows == want.nrows == G.VMEM_GATHER_MAX_ENTRIES + 1
    assert list(got.columns) == list(want.columns) and len(got.columns) == 9
    for name, col in got.columns.items():
        assert np.array_equal(np.asarray(col.storage), np.asarray(want.columns[name].storage)), name
    assert got.to_rows()[:500] == want.to_rows()[:500]
    expands, merges = _stages(recs, "join:expand"), _stages(recs, "join:merge")
    assert [e.extra["path"] for e in expands] == ["fan-out", "unique-identity"]
    assert [e.extra["vmem_gathers"] for e in expands] == [2, 0]
    assert expands[0].extra["padded"] == 262_144 and expands[0].extra["row_gathers"] == 2
    assert sum(e.extra["host_sync_elements"] for e in expands) == 4
    first, second = (m.extra for m in merges)
    assert (first["build_gathers"], first["stream_gathers"], first["vmem_gathers"]) == (4, 3, 3)
    assert (second["build_gathers"], second["stream_gathers"], second["vmem_gathers"]) == (2, 0, 2)
    assert (first["run_copies"], second["run_copies"]) == (4, 0)  # the second join is unique-identity: no runs
    assert len(calls) == 0  # no lane moves a program a lane any more...
    assert all(r.extra["vmem_gathers"] == 0 for r in _stages(ref_recs, "join:expand") + _stages(ref_recs, "join:merge"))
    # ...where the kernels run: without them the orders' four still do
    assert [m.extra["run_copies"] for m in _stages(ref_recs, "join:merge")] == [0, 0]


def _no_run_copy(monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("csvplus.join.gather_runs ran")

    monkeypatch.setattr(G, "_gather_runs_kernel", refuse)


@pytest.mark.parametrize("why, lane_programs, vmem_gathers", [
    ("off-the-tpu", 7, 0), ("short-runs", 0, 7), ("past-the-vmem-share", 0, 7), ("a-table-of-int8", 4, 3),
])
def test_a_fan_out_the_rule_refuses_emits_by_the_parents_programs(why, lane_programs, vmem_gathers, monkeypatch):
    """Long runs (40 customers' ~125 orders each) — and one reason each
    why the run copy does not serve them: ``join:merge`` counts 0
    ``run_copies``, ``csvplus.join.gather_runs`` never runs, the lanes
    move as the parent moved them (``csvplus.join.gather_lane``, a
    program a lane, or the VMEM gather: these tables are small), and
    the rows are the parent's."""
    people, by_cust, _ = _statements(5000)
    want, ref_recs = _binary(people, by_cust, ("id",))
    assert _stages(ref_recs, "join:expand")[0].extra["path"] == "fan-out"
    calls = []
    _no_run_copy(monkeypatch)
    monkeypatch.setattr(G, "_gather_lane", lambda c, i, _real=G._gather_lane: calls.append(1) or _real(c, i))
    if why != "off-the-tpu":
        monkeypatch.setattr(G, "_kernel_mode", lambda: "interpret")
    if why == "short-runs":
        monkeypatch.setattr(RC, "RUN_COPY_MIN_MEAN_RUN", 126)  # 5000 rows / 40 probes
    elif why == "past-the-vmem-share":
        monkeypatch.setattr(RC, "_vmem_capacity_bytes", lambda: 2 * 1024 * 1024)
    elif why == "a-table-of-int8":
        qty = by_cust.table.columns["qty"]
        by_cust.table.columns["qty"] = qty.with_storage(qty.storage.astype(jnp.int8))
        want, _ = _binary(people, by_cust, ("id",))
        calls.clear()
    got, recs = _binary(people, by_cust, ("id",))
    _assert_same_table(got, want)
    (merge,) = _stages(recs, "join:merge")
    assert (merge.extra["run_copies"], merge.extra["build_gathers"]) == (0, 4)
    assert (len(calls), merge.extra["vmem_gathers"]) == (lane_programs, vmem_gathers)


def test_the_fan_out_through_the_run_copy_equals_the_gathers(kernel_forced):
    """...and with nothing in its way the same join copies runs: the
    rows are the gathers', ``build_gathers`` is still 4."""
    people, by_cust, _ = _statements(5000)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(G, "_kernel_mode", lambda: False)
        want, _ = _binary(people, by_cust, ("id",))
    got, recs = _binary(people, by_cust, ("id",))
    _assert_same_table(got, want)
    (merge,) = _stages(recs, "join:merge")
    assert (merge.extra["run_copies"], merge.extra["build_gathers"], merge.extra["vmem_gathers"]) == (4, 4, 3)
    assert merge.extra["row_gathers"] == 7


@pytest.mark.parametrize("case", ["all-matched", "misses", "holes"])
def test_the_unique_paths_carry_no_runs(case, kernel_forced, monkeypatch):
    """``unique-identity`` and ``unique-partial`` never reach the rule:
    there are no runs to read it off."""
    _no_run_copy(monkeypatch)
    monkeypatch.setattr(RC, "run_copy_selected", lambda *a: pytest.fail("the rule was asked"))
    stream, specs, path, _ = _join_case(case)
    for di, cols in specs:
        _, recs = _binary(stream, di, cols)
        assert _stages(recs, "join:expand")[0].extra["path"] in ("unique-identity", "unique-partial")
        assert _stages(recs, "join:merge")[0].extra["run_copies"] == 0


def test_a_probe_one_row_over_the_limit_expands_by_jnp_take(kernel_forced):
    n = G.VMEM_GATHER_MAX_ENTRIES + 1
    counts = jnp.asarray((np.arange(n) % 3).astype(np.int32))
    lower = jnp.cumsum(counts) - counts
    total, rec = int(counts.sum()), {}
    probe_ids, build_ids = J.expand_matches_device(lower, counts, total, rec)
    assert rec == {"vmem_gathers": 0}
    want = np.repeat(np.arange(n), np.asarray(counts))
    assert np.array_equal(np.asarray(probe_ids), want)
    assert np.array_equal(np.asarray(build_ids), np.arange(total))  # runs laid end to end
    rec = {}
    J.expand_matches_device(lower[:-1], counts[:-1], None, rec)
    assert rec == {"vmem_gathers": 2}


def _as_it_was(name, fn, **jit_kwargs):
    """The parent's program under the program's name."""
    fn.__name__ = fn.__qualname__ = "csvplus." + name
    return jax.jit(fn, **jit_kwargs)


def test_without_the_kernel_both_programs_lower_to_the_parents_hlo():
    """``vmem`` False — off the TPU, over the limit, under a mesh — is
    ``jnp.take`` a lane and nothing else, to the letter."""
    def lane(n):
        return jax.ShapeDtypeStruct((n,), jnp.int32)

    def gather_cols(tables, ids):  # a group of lanes an index, since ISSUE 48
        return tuple(
            tuple(jnp.take(c, jnp.asarray(i, dtype=jnp.int32), axis=0) for c in t) for t, i in zip(tables, ids)
        )

    args = (((lane(5000),) * 3, (lane(700),) * 2), (lane(20000), lane(300)))
    was = _as_it_was("join.gather_cols", gather_cols).lower(*args).as_text()
    assert G._gather_cols.lower(*args, vmem=(False, False)).as_text() == was

    # the program a lane that the build side's lanes move by wherever the run
    # copy's rule says no (ISSUE 47): off the TPU, short runs, a lane past the
    # VMEM share, a mesh — and the unique paths, which have no runs
    def gather_lane(storage, ids):
        return jnp.take(storage, ids, axis=0)

    was = _as_it_was("join.gather_lane", gather_lane).lower(lane(5000), lane(20000)).as_text()
    assert G._gather_lane.lower(lane(5000), lane(20000)).as_text() == was

    def expand(lower, counts, padded_total: int):
        counts = counts.astype(jnp.int32)
        ends = jnp.cumsum(counts)
        starts = ends - counts
        ids = jnp.arange(counts.shape[0], dtype=jnp.int32)
        mark_pos = jnp.where(counts > 0, starts, padded_total)
        seg = jnp.zeros(padded_total, dtype=jnp.int32)
        seg = seg.at[mark_pos].max(ids, mode="drop")
        probe_ids = jax.lax.cummax(seg)
        out_pos = jnp.arange(padded_total, dtype=jnp.int32)
        group_base = jnp.take(starts, probe_ids, axis=0)
        build_ids = jnp.take(lower.astype(jnp.int32), probe_ids, axis=0) + (out_pos - group_base)
        return probe_ids, build_ids

    was = _as_it_was("join.expand", expand, static_argnames=("padded_total",))
    was = was.lower(lane(1000), lane(1000), padded_total=4096).as_text()
    assert J._expand_kernel.lower(lane(1000), lane(1000), padded_total=4096).as_text() == was
    forced = J._expand_kernel.lower(lane(1000), lane(1000), padded_total=4096, vmem="interpret").as_text()
    assert forced != was


# ---- the chip's compiler, without the chip ----------------------------------
#
# Interpret mode cannot refuse a block shape or a lowering Mosaic lacks;
# the TPU's compiler, which is installed here, can.  The topology is
# described inside a fixture (one process may load the TPU's library,
# and only the worker that runs this file does).


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _lane(n, sharding):
    return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)


@pytest.mark.parametrize("rows,people", [(10_000_000, 100_000), (1_000_003, 10_000)],
                         ids=["star3-resident", "star3-selective-resident"])
def test_the_emit_compiles_for_the_chip_at_the_cells_shapes(one_chip, rows, people):
    codes = ((_lane(people, one_chip),) * 3, (_lane(1000, one_chip),) * 2)
    ids = (_lane(rows, one_chip),) * 2
    text = G._gather_cols.lower(codes, ids, vmem=(True, True)).compile().as_text()
    assert text.count("tpu_custom_call") == 2  # a kernel a dimension
    plain = G._gather_cols.lower(codes, ids, vmem=(False, False)).compile().as_text()
    assert "tpu_custom_call" not in plain


@pytest.mark.parametrize("side,tables,entries,rows", [
    ("people", 3, 100_000, 10_000_000), ("stock", 2, 1000, 10_000_000), ("limit", 3, 131_073, 1_000_003),
])
def test_the_binary_emit_compiles_for_the_chip_at_the_cells_shapes(one_chip, side, tables, entries, rows):
    """``statements-fanout-resident``: people's three lanes and stock's
    two composed tables at 10,000,000 ids, one kernel call a side."""
    codes, ids = ((_lane(entries, one_chip),) * tables,), (_lane(rows, one_chip),)
    text = G._gather_cols.lower(codes, ids, vmem=(True,)).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "tpu_custom_call" not in G._gather_cols.lower(codes, ids, vmem=(False,)).compile().as_text()


def test_the_expansion_compiles_for_the_chip_at_the_cells_shapes(one_chip, probes=100_000, padded=16_777_216):
    args = (_lane(probes, one_chip), _lane(probes, one_chip))
    text = J._expand_kernel.lower(*args, padded_total=padded, vmem=True).compile().as_text()
    assert text.count("tpu_custom_call") == 1  # one call reads both tables
    plain = J._expand_kernel.lower(*args, padded_total=padded).compile().as_text()
    assert "tpu_custom_call" not in plain


def test_the_run_copy_compiles_for_the_chip_at_the_cells_shapes(one_chip, rows=10_000_000, probes=100_000, padded=16_777_216):
    """``statements-fanout-resident``'s first join: four 10M-row lanes of
    the sorted orders, two a call — 2 x 40 MB whole in VMEM, which
    interpret mode cannot refuse and the chip's compiler can."""
    args = ((_lane(rows, one_chip),) * 4, _lane(probes, one_chip), _lane(probes, one_chip))
    text = G._gather_runs_kernel.lower(*args, padded=padded, kernel=True).compile().as_text()
    assert text.count("tpu_custom_call") == 4 // RC._MAX_TABLES
    assert RC._call_bytes(rows, RC._MAX_TABLES) < RC._V5E_VMEM_BYTES * RC._VMEM_SHARE


@pytest.mark.parametrize("tables", [1, 2], ids=["unique", "counted"])
def test_the_composed_probe_compiles_for_the_chip(one_chip, tables):
    tab = _lane(G.VMEM_GATHER_MAX_ENTRIES, one_chip)
    base = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    args = (_lane(10_000_000, one_chip), base, tab, tab if tables == 2 else None)
    text = J._probe_composed_kernel.lower(*args, vmem=True).compile().as_text()
    assert text.count("tpu_custom_call") == 1  # one call reads both tables
