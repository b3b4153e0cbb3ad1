"""``ops/gather.py`` (ISSUE 44): a small table is gathered from VMEM.

``jnp.take`` is the arbiter, bit for bit: the kernel alone over every
shape class of table, lane count and stream length (interpret mode, so
small N), then the rule that selects it, then the two programs that
call it — ``csvplus.join.gather_multiway`` and
``csvplus.join.probe_composed`` — with the kernel forced by a fixture
against the same joins without it, and the ``vmem_gathers`` counter of
``join:probe`` / ``join:merge``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from csvplus_tpu.ops import gather as G
from csvplus_tpu.ops import join as J
from csvplus_tpu.utils.observe import telemetry

from test_composed_probe import (
    JOIN_CASES, PROBE_CASES, _assert_same_table, _join_case, _staged_only,
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


@needs8
def test_a_mesh_sharded_stream_keeps_jnp_take(kernel_forced):
    from jax.sharding import NamedSharding

    from csvplus_tpu.parallel.mesh import make_mesh, row_spec

    mesh = make_mesh(8)
    idx = jax.device_put(np.zeros(4096, np.int32), NamedSharding(mesh, row_spec(mesh)))
    assert G.vmem_gather_selected((jnp.zeros(1000, jnp.int32),), idx) is False


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
    # every build lane of these small dimensions is served by the kernel; the
    # stream's own survivors (a program a lane, at the stream's length) never are
    assert merge.extra["vmem_gathers"] == lanes
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
def test_a_row_sharded_join_records_no_vmem_gather(kernel_forced):
    stream, specs, _, _, _ = _deployment("pow2+1", "row-sharded")
    got, recs = _joined(stream, specs)
    assert 0 < got.nrows < stream.nrows
    for r in _stages(recs, "join:probe") + _stages(recs, "join:merge"):
        assert r.extra["vmem_gathers"] == 0, r.stage


@pytest.mark.parametrize("staged", [False, True], ids=["composed", "staged"])
@pytest.mark.parametrize("dims", [1, 2], ids=["binary", "multiway"])
def test_probe_and_merge_always_carry_the_key(dims, staged, monkeypatch):
    stream, specs, _, _ = _join_case("misses")
    if staged:
        with _staged_only(monkeypatch):
            _, recs = _joined(stream, specs[:dims])
    else:
        _, recs = _joined(stream, specs[:dims])
    probes, merges = _stages(recs, "join:probe"), _stages(recs, "join:merge")
    assert len(probes) == dims and len(merges) == 1
    assert {r.extra["tier"] for r in probes} == {"direct" if staged else "direct-composed"}
    for r in probes + merges:
        assert r.extra["vmem_gathers"] == 0 and "row_gathers" in r.extra


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
    text = J._gather_multiway.lower(codes, ids, vmem=(True, True)).compile().as_text()
    assert text.count("tpu_custom_call") == 2  # a kernel a dimension
    plain = J._gather_multiway.lower(codes, ids, vmem=(False, False)).compile().as_text()
    assert "tpu_custom_call" not in plain


@pytest.mark.parametrize("tables", [1, 2], ids=["unique", "counted"])
def test_the_composed_probe_compiles_for_the_chip(one_chip, tables):
    tab = _lane(G.VMEM_GATHER_MAX_ENTRIES, one_chip)
    base = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    args = (_lane(10_000_000, one_chip), base, tab, tab if tables == 2 else None)
    text = J._probe_composed_kernel.lower(*args, vmem=True).compile().as_text()
    assert text.count("tpu_custom_call") == 1  # one call reads both tables
