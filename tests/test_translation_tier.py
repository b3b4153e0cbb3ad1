"""Which table a typed probe key is translated through (ISSUE 32).

``IntColumn._build_translation`` keeps two tiers, chosen per build side
by the pure predicate ``IntColumn._dense_admitted(size, lo, hi)``: one
dense table read by position (``table[value - lo]``, one gather a row)
where it is small or no larger than the pair it replaces, else the
sorted pair (a ~log2(U)-round search and two more gathers).  Here:

(a) the predicate on shapes alone, no array allocated;
(b) both tiers forced on the same inputs: the same codes for hits, for
    misses below ``lo`` and above ``hi``, for negative values and for
    sharding pads;
(c) a build side of 2**23 + 1 keys over 2**24 + 1 slots — past the old
    2**24 cap, admitted only by the bytes it places — through
    ``DeviceIndex.probe`` on one device (staged and composed) and
    through the partitioned probe of a stream sharded over four of the
    simulated devices, held to a plain numpy reference (``row_of[key]``
    over the generator's own arrays).  The host executor, the second
    oracle of the small mesh tests, would build 8.4M Python rows here.
"""

from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypo_compat import given, settings, st

from csvplus_tpu.columnar.table import DeviceTable
from csvplus_tpu.columnar.typed import PAD_VALUE, IntColumn, format_affix
from csvplus_tpu.ops import join as J
from csvplus_tpu.ops.join import DeviceIndex
from csvplus_tpu.ops.sort import sort_table
from csvplus_tpu.parallel.mesh import make_mesh
from csvplus_tpu.utils.observe import telemetry

INT32_MIN, INT32_MAX = -(2**31), 2**31 - 1
M20 = 20_000_000

# ---- (a) the predicate ------------------------------------------------------

PREDICATE_CASES = {
    # the benchmark's people ids: a permutation of 0..19,999,999
    "cell-20M-over-20M": (M20, 0, M20 - 1, True),
    "cell-shifted-negative": (M20, -M20 // 2, M20 // 2 - 1, True),
    # the old cap's edge: both sides of 2**24 slots are dense now
    "2^24-over-2^24": (1 << 24, 0, (1 << 24) - 1, True),
    "2^24+1-over-2^24+1": ((1 << 24) + 1, 0, 1 << 24, True),
    # past 2**24 slots the table is never larger than the sorted pair
    "20M-over-twice": (M20, 0, 2 * M20 - 1, True),
    "20M-over-twice-plus-one": (M20, 0, 2 * M20, False),
    "20M-over-2^31": (M20, -(2**30), 2**30 - 1, False),
    "2^23-over-2^24+1": (1 << 23, 0, 1 << 24, False),
    # the slot index must fit an int32 whatever the bytes say
    "range-2^31-of-2^31-keys": (2**31, INT32_MIN + 1, INT32_MAX, False),
    "range-2^31-1-of-2^30-keys": (2**30, INT32_MIN + 1, -1, True),
    # small sides, as before: 16 x the distinct count, or 1024 slots
    "small-sparse-16x": (1000, 5, 5 + 16_000 - 1, True),
    "small-sparse-16x-plus-one": (1000, 5, 5 + 16_000, False),
    "tiny-over-1024": (3, 0, 1023, True),
    "tiny-over-1025": (3, 0, 1024, False),
    "one-key": (1, INT32_MAX, INT32_MAX, True),
    "2^20-keys-over-2^24": (1 << 20, 0, (1 << 24) - 1, True),
    "2^20-keys-over-2^24+1": (1 << 20, 0, 1 << 24, False),
    "empty": (0, 0, -1, False),
}


@pytest.mark.parametrize("case", sorted(PREDICATE_CASES))
def test_dense_table_is_admitted_by_shape_alone(case):
    size, lo, hi, dense = PREDICATE_CASES[case]
    assert IntColumn._dense_admitted(size, lo, hi) is dense
    if dense and hi - lo + 1 > 1 << 24:
        # above the small-table bound: no more bytes than the sorted pair
        # (two int32 arrays of `size`), and every slot an int32
        assert 4 * (hi - lo + 1) <= 8 * size and hi - lo + 1 < 2**31


def test_an_empty_build_side_keeps_the_sorted_state():
    state = IntColumn._build_translation(np.empty(0, np.int32), np.empty(0, np.int32))
    assert state[0] == "sorted" and state[1].shape == state[2].shape == (0,)


# ---- (b) the two tiers give the same codes ----------------------------------


def _forced(dense: bool):
    return mock.patch.object(IntColumn, "_dense_admitted", staticmethod(lambda *a: dense))


def _both_tiers(build_vals, cand, probe_vals):
    """Codes of *probe_vals* through the dense and through the sorted
    state of one build side, and by a Python dict."""
    build_vals = np.asarray(build_vals, dtype=np.int32)
    cand = np.asarray(cand, dtype=np.int32)
    pc = IntColumn(b"c", jnp.asarray(np.asarray(probe_vals, dtype=np.int32)))
    out = {}
    for kind, dense in (("dense", True), ("sorted", False)):
        with _forced(dense):
            state = IntColumn._build_translation(build_vals, cand)
        assert state[0] == kind
        out[kind] = np.asarray(pc._translate_by_values(state))
    code_of = dict(zip(build_vals.tolist(), cand.tolist()))
    want = [
        -2 if v == int(PAD_VALUE) else code_of.get(int(v), -1) for v in np.asarray(probe_vals)
    ]
    return out["dense"], out["sorted"], np.asarray(want, dtype=np.int32)


def _probes_around(build_vals, rng, n=64):
    lo, hi = int(build_vals.min()), int(build_vals.max())
    near = [lo - 1, lo - 2, hi + 1, hi + 2, lo, hi, -1, 0, -7, INT32_MAX, INT32_MIN + 1]
    vals = np.concatenate([
        rng.choice(build_vals, n // 2),
        rng.integers(lo - 5, hi + 6, n // 4, dtype=np.int64),
        rng.integers(INT32_MIN + 1, INT32_MAX, n // 4 - 2, dtype=np.int64),
        np.asarray(near, dtype=np.int64),
    ])
    vals = np.clip(vals, INT32_MIN + 1, INT32_MAX)
    vals = np.concatenate([vals, [int(PAD_VALUE), int(PAD_VALUE)]])
    return vals.astype(np.int32)


@pytest.mark.parametrize(
    "lo",
    [0, -500, 1_000_000, INT32_MIN + 1, INT32_MAX - 999, -(2**30), 2**30],
    ids=lambda v: f"lo={v}",
)
def test_dense_and_sorted_tiers_translate_alike(lo):
    """A span of 1,000 slots anywhere in int32, a third of them keys (a
    hole beside most keys): ``value - lo`` wraps for far misses at the
    ends of int32 and must still read as a miss."""
    rng = np.random.default_rng(abs(lo) % 9973)
    inner = lo + 1 + rng.choice(998, 330, replace=False)
    build = np.concatenate([[lo, lo + 999], inner]).astype(np.int32)
    cand = rng.permutation(5000)[: build.size]
    dense, by_search, want = _both_tiers(build, cand, _probes_around(build, rng))
    assert np.array_equal(dense, want)
    assert np.array_equal(by_search, want)
    assert (want >= 0).any() and (want == -1).any() and (want == -2).sum() == 2


@settings(max_examples=40, deadline=None)
@given(
    lo=st.one_of(
        st.integers(INT32_MIN + 1, INT32_MAX - 1100),
        st.sampled_from([INT32_MIN + 1, -1000, -1, 0, INT32_MAX - 1100]),
    ),
    span=st.sampled_from([1, 2, 37, 1000]),  # few shapes: each compiles once
    count=st.sampled_from([1, 2, 5, 30]),
    seed=st.integers(0, 2**32 - 1),
)
def test_tiers_translate_alike_property(lo, span, count, seed):
    rng = np.random.default_rng(seed)
    count = min(count, span)
    build = lo + rng.choice(span, count, replace=False)
    build[0] = lo  # the table's first slot is a key
    build = np.unique(np.concatenate([build, [lo + span - 1]])).astype(np.int32)
    cand = rng.permutation(100)[: build.size]
    dense, by_search, want = _both_tiers(build, cand, _probes_around(build, rng, n=32))
    assert np.array_equal(dense, want)
    assert np.array_equal(by_search, want)


# ---- (c) a build side past 2**24 slots --------------------------------------

N_BIG = (1 << 23) + 1  # keys; every second slot of 0..2**24 holds one
N_PROBE = 200_000
NAMES = np.array([b"n0", b"n1", b"n2", b"n3", b"n4", b"n5", b"n6"])


class Big:
    """8,388,609 people with shuffled even ids ``c0, c2, .. c16777216``
    (numpy arrays, no CSV: the ingest is not what is tested), their
    device table and index, and a typed probe lane with misses in the
    holes, below and above the range, and a pad."""

    def __init__(self):
        rng = np.random.default_rng(32)
        self.ids = (2 * rng.permutation(N_BIG)).astype(np.int32)
        strs = format_affix(b"c", self.ids)
        order = np.argsort(strs, kind="stable")
        codes = np.empty(N_BIG, dtype=np.int32)
        codes[order] = np.arange(N_BIG, dtype=np.int32)
        self.name_codes = (np.arange(N_BIG) % len(NAMES)).astype(np.int32)
        self.people = DeviceTable.from_encoded(
            {"id": (strs[order], codes), "name": (NAMES, self.name_codes)}, N_BIG
        )
        self.cust = rng.integers(-10, 2 * N_BIG + 10, N_PROBE).astype(np.int32)
        self.cust[: N_PROBE // 2] &= ~np.int32(1)  # at least half of them hit
        self.cust[17] = PAD_VALUE
        row_of = np.full(2 * N_BIG - 1, -1, dtype=np.int64)
        row_of[self.ids] = np.arange(N_BIG)
        inside = (self.cust >= 0) & (self.cust < row_of.size)
        self.person = np.where(inside, row_of[np.clip(self.cust, 0, row_of.size - 1)], -1)
        # as built by default; one host parse of its 8.4M-entry id
        # dictionary (20 s here) serves every test that probes it
        self.di = DeviceIndex.build(sort_table(self.people, ["id"]), ["id"])

    def probe_column(self, mesh=None):
        values = jnp.asarray(self.cust)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            values = jax.device_put(values, NamedSharding(mesh, P(mesh.axis_names[0])))
        return IntColumn(b"c", values)

    def check_answers(self, di, lower, counts):
        """(lower, counts) name, for every matched order, the index row
        that holds its person; every other order counts 0."""
        counts, lower = np.asarray(counts), np.asarray(lower)
        hit = self.person >= 0
        assert np.array_equal(counts, hit.astype(np.int32))
        assert hit.sum() > N_PROBE // 2 and (~hit).sum() > N_PROBE // 8
        got_id = np.asarray(di.table.columns["id"].storage)[lower[hit]]
        want_id = np.asarray(self.people.columns["id"].storage)[self.person[hit]]
        assert np.array_equal(got_id, want_id)
        got_name = np.asarray(di.table.columns["name"].storage)[lower[hit]]
        assert np.array_equal(got_name, self.name_codes[self.person[hit]])


@pytest.fixture(scope="module")
def big():
    return Big()


def test_a_side_past_2_24_slots_is_dense_and_probes_staged(big):
    """Refused by the old cap (16,777,217 slots), admitted by its bytes
    (one 64 MB table against a sorted pair of 2 x 32 MB): the staged
    probe translates in one gather, not bit_length(8.4M) + 2 = 26."""
    assert IntColumn._dense_admitted(N_BIG, 0, 2 * (N_BIG - 1))
    di = big.di
    assert di.direct_bits is None  # 24 bits: past the direct tier, so nothing composes
    pc = big.probe_column()
    kind, lo, table = pc.translation_state_to(di.table.columns["id"])
    assert (kind, lo, table.shape) == ("dense", 0, ((1 << 24) + 1,))
    with telemetry.collect() as recs:
        lower, counts = di.probe([pc], N_PROBE)
    (tr,) = [r.extra for r in recs if r.stage == "join:translate"]
    assert (tr["row_gathers"], tr["tier"]) == (1, "dense")
    assert [r.extra["tier"] for r in recs if r.stage == "join:probe"] == ["broadcast-i32"]
    big.check_answers(di, lower, counts)
    # the search it replaces finds the same codes (its pair rebuilt from
    # the table's own entries: a second host parse takes 20 s here)
    slots = np.asarray(table)
    vals = np.flatnonzero(slots >= 0).astype(np.int32)
    with _forced(False):
        state = IntColumn._build_translation(vals, slots[vals])
    assert state[0] == "sorted" and state[1].shape == (N_BIG,)
    assert np.array_equal(
        np.asarray(pc._translate_by_values(state)),
        np.asarray(pc.renumbered_to_col(di.table.columns["id"])),
    )


def test_a_side_past_2_24_slots_composes_over_its_dense_range(big, monkeypatch):
    """The composed probe needs the direct tier, which stops at 2**23
    keys by default: only an operator who raised
    ``CSVPLUS_DIRECT_PROBE_MAX_BITS`` composes over such a side.  With
    it raised, the universe is the dense range (a scalar base, one walk
    for a unique index) where the sorted tier searched it (25 more) and
    ruled depth 2 out."""
    monkeypatch.setattr(DeviceIndex, "DIRECT_MAX_BITS", 24)
    monkeypatch.setattr(J, "_COMPOSE_ROWS_PER_SLOT", 0)  # a 200,000-row stream composes
    di = DeviceIndex.build(big.di.table, ["id"])  # the sorted table and its parsed dictionary again
    assert di.direct_bits == 24
    pc = big.probe_column()
    entry = di._composed_for(pc, N_PROBE)
    assert entry is not None and entry.base.ndim == 0 and entry.size == (1 << 24) + 1
    assert entry.walks == 1 and entry.cnt_tab is None
    assert entry.emit_ok is False  # every odd slot is a hole
    with telemetry.collect() as recs:
        lower, counts = di.probe([pc], N_PROBE)
    (probe,) = [r.extra for r in recs if r.stage == "join:probe"]
    assert (probe["tier"], probe["depth"], probe["row_gathers"]) == ("direct-composed", 1, 1)
    assert not [r for r in recs if r.stage == "join:translate"]
    big.check_answers(di, lower, counts)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 of the simulated CPU devices")
def test_a_side_past_2_24_slots_through_the_partitioned_probe_on_four_devices(big):
    """``lookupjoin-mesh4``'s path at a size only rule 2 admits: the
    typed lane row-sharded over four devices, the dense table replicated
    on them once, the 8.4M-key index range-partitioned (past
    ``PARTITION_MIN_KEYS`` as it stands) and probed over all_to_all."""
    mesh = make_mesh(4)
    di = big.di
    pc = big.probe_column(mesh)
    state = pc.translation_state_to(di.table.columns["id"])
    assert state[0] == "dense" and state[2].shape == ((1 << 24) + 1,)
    on = pc.values.sharding.device_set
    assert len(on) == 4 and state[2].sharding.device_set == on
    assert state[2].committed and state[2].sharding.is_fully_replicated
    assert pc.translation_state_to(di.table.columns["id"])[2] is state[2]
    with telemetry.collect() as recs:
        lower, counts = di.probe([pc], N_PROBE)
    (tr,) = [r.extra for r in recs if r.stage == "join:translate"]
    assert (tr["row_gathers"], tr["tier"]) == (1, "dense")
    (exchange,) = [r.extra for r in recs if r.stage == "join:all_to_all"]
    assert exchange["owner_tier"] == "positional" and exchange["retries"] == 0
    big.check_answers(di, lower, counts)
