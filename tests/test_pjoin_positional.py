"""The partitioned probe's positional owner tier (ISSUE 28), on the
simulated 8-device CPU mesh: where every shard's slice of unique keys
spans at most ``2 ** DIRECT_MAX_BITS`` values the owner reads a received
key's answer at ``key - first``; elsewhere it binary-searches.  The two
forms must give the same ``(lower, count)`` bit for bit, and numpy's.

The search form of the same keys is prepared by lowering the span bound
in the test (``DIRECT_MAX_BITS`` is the one-chip direct tier's own
constant; no option selects the owner's tier)."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import csvplus_tpu.ops.join as J
import csvplus_tpu.parallel.pjoin as PJ
from csvplus_tpu.parallel.mesh import make_mesh, shard_rows
from csvplus_tpu.utils.observe import telemetry

N = 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(N)


def prepare(mesh, keys, monkeypatch, max_bits=None):
    """``prepare_partitioned`` under a span bound of ``2 ** max_bits``
    (-1: no slice with a key in it fits, so the search form)."""
    with monkeypatch.context() as patch, telemetry.collect() as records:
        if max_bits is not None:
            patch.setattr(J.DeviceIndex, "DIRECT_MAX_BITS", max_bits)
        prepared = PJ.prepare_partitioned(mesh, keys)
    (part,) = [r for r in records if r.stage == "join:partition"]
    assert part.extra["positional"] == prepared.positional
    return prepared, part.extra


def probe(mesh, queries, prepared, capacity=None):
    with telemetry.collect() as records:
        lo, ct = PJ.partitioned_probe_device(
            mesh, jax.device_put(queries), prepared, capacity
        )
        lo, ct = np.asarray(lo), np.asarray(ct)
    return lo, ct, {r.stage: r.extra for r in records}


def oracle(keys, queries):
    lo = np.searchsorted(keys, queries, side="left").astype(np.int32)
    ct = (np.searchsorted(keys, queries, side="right") - lo).astype(np.int32)
    ct[queries < 0] = 0
    return np.where(ct > 0, lo, -1).astype(np.int32), ct


def _dense_unique(rng):
    keys = np.arange(40_000, dtype=np.int32)
    return keys, rng.integers(0, 40_000, 30_000).astype(np.int32)


def _dense_duplicate_runs(rng):
    keys = np.repeat(np.arange(5_000, dtype=np.int32), rng.integers(1, 6, 5_000))
    return keys, rng.integers(0, 5_000, 30_000).astype(np.int32)


def _holes_in_the_span(rng):
    uniq = np.sort(rng.choice(60_000, 40_000, replace=False)).astype(np.int32)
    keys = np.repeat(uniq, rng.integers(1, 3, uniq.size))
    return keys, rng.integers(0, 60_000, 30_000).astype(np.int32)  # a third fall in holes


def _every_kind_of_miss(rng):
    uniq = 100 + 2 * np.arange(4_000, dtype=np.int32)  # every odd offset is a hole
    queries = np.concatenate([
        np.full(500, -1), rng.integers(0, 100, 500),  # invalid; below the first key
        rng.integers(8_100, 20_000, 500), [np.iinfo(np.int32).max - 1],  # past the last
        101 + 2 * rng.integers(0, 3_999, 2_000), uniq[rng.integers(0, 4_000, 2_000)],
    ]).astype(np.int32)
    return uniq, rng.permutation(queries)


def _uneven_slices(rng):
    keys = np.arange(7, 7 + 1_003, dtype=np.int32)  # 1,003 = 8 * 125 + 3: sentinel pads
    return keys, rng.integers(0, 1_100, 9_001).astype(np.int32)


def _empty_shards(rng):
    keys = np.repeat(np.array([3, 4, 5, 9, 10], np.int32), 400)  # 5 unique keys on 8 shards
    return keys, rng.integers(-1, 14, 8_000).astype(np.int32)


def _one_shard_routes_everything(rng):
    keys = np.arange(16_000, dtype=np.int32)
    return keys, rng.integers(0, 2_000, 12_000).astype(np.int32)  # all in shard 0's slice


def _hot_key(rng):
    # tests/test_skew_join.py's adversarial stream: 90% of the probes on one key
    keys = np.repeat(np.arange(1_500, dtype=np.int32), 2)
    queries = rng.integers(1, 1_500, 16_000).astype(np.int32)
    queries[rng.random(16_000) < 0.9] = 0
    return keys, queries


CASES = {
    "dense-unique": (_dense_unique, None),
    "dense-duplicate-runs": (_dense_duplicate_runs, None),
    "holes-in-the-span": (_holes_in_the_span, None),
    "every-kind-of-miss": (_every_kind_of_miss, None),
    "uneven-slices-sentinel-padding": (_uneven_slices, None),
    "empty-shards": (_empty_shards, None),
    "capacity-overflow-retries": (_one_shard_routes_everything, 512),
    "hot-key-broadcast-tier": (_hot_key, None),
}


@pytest.mark.parametrize("case", CASES)
def test_positional_and_search_owners_answer_alike(case, mesh, monkeypatch):
    make, capacity = CASES[case]
    keys, queries = make(np.random.default_rng(28))
    if case == "empty-shards":
        # 15 probe values, each past the hot threshold: keep them on the exchange
        monkeypatch.setenv("CSVPLUS_JOIN_SKEW", "0")
    positional, info = prepare(mesh, keys, monkeypatch)
    search, _ = prepare(mesh, keys, monkeypatch, max_bits=-1)
    assert positional.owner_tier == "positional" and positional.search_rounds == 0
    assert positional.uniq == () and search.first is None
    assert search.owner_tier == "search" and search.search_rounds >= 1
    assert 1 <= info["span_max"] <= int(keys[-1]) - int(keys[0]) + 1

    lo_p, ct_p, stages_p = probe(mesh, queries, positional, capacity)
    lo_s, ct_s, stages_s = probe(mesh, queries, search, capacity)
    np.testing.assert_array_equal(lo_p, lo_s)
    np.testing.assert_array_equal(ct_p, ct_s)
    want_lo, want_ct = oracle(keys, queries)
    np.testing.assert_array_equal(ct_p, want_ct)
    np.testing.assert_array_equal(lo_p, want_lo)

    for stages, prepared in ((stages_p, positional), (stages_s, search)):
        exchange = stages["join:all_to_all"]
        assert exchange["owner_tier"] == prepared.owner_tier
        assert exchange["search_rounds"] == prepared.search_rounds
        assert exchange["retries"] == stages_p["join:all_to_all"]["retries"]
    if case == "capacity-overflow-retries":
        assert stages_p["join:all_to_all"]["retries"] >= 1
    if case == "hot-key-broadcast-tier":
        assert stages_p["join:skew"]["hot_keys"] == stages_s["join:skew"]["hot_keys"] >= 1
        assert stages_p["join:skew"]["rows_broadcast"] >= int(0.85 * queries.size)
    else:
        assert "join:skew" not in stages_p


def test_one_slice_past_the_bound_sends_the_whole_probe_to_the_search(mesh, monkeypatch):
    """Seven slices span 500 keys each and one spans 1,500 (a gap in
    it): under a bound of 2**10 the index is prepared in the search
    form, says so, and answers as the positional form of a wider bound."""
    keys = np.arange(4_000, dtype=np.int32)
    keys[2_750:] += 1_000  # inside shard 5's slice [2500, 3000)
    queries = np.random.default_rng(5).integers(-1, 5_200, 20_000).astype(np.int32)
    prepared, info = prepare(mesh, keys, monkeypatch, max_bits=10)
    assert info["span_max"] == 1_500 and info["positional"] is False
    assert prepared.owner_tier == "search"
    assert prepared.search_rounds == J._searchsorted_rounds(500) == 9
    wider, info = prepare(mesh, keys, monkeypatch, max_bits=11)
    assert info["span_max"] == 1_500 and info["positional"] and wider.owner_tier == "positional"

    lo, ct, stages = probe(mesh, queries, prepared)
    assert stages["join:all_to_all"]["owner_tier"] == "search"
    assert stages["join:all_to_all"]["search_rounds"] == 9
    lo_w, ct_w, _ = probe(mesh, queries, wider)
    np.testing.assert_array_equal(lo, lo_w)
    np.testing.assert_array_equal(ct, ct_w)
    np.testing.assert_array_equal((lo, ct), oracle(keys, queries))


def test_wide_keys_keep_the_search_form(mesh, monkeypatch):
    keys = (np.int64(1) << 40) + np.arange(4_000, dtype=np.int64)  # dense, but 62-bit lanes
    prepared, info = prepare(mesh, keys, monkeypatch)
    assert prepared.wide and prepared.owner_tier == "search" and len(prepared.uniq) == 2
    assert info["span_max"] == 500 and info["positional"] is False
    queries = keys[np.random.default_rng(6).integers(0, 4_000, 5_000)]
    queries[::7] = -1
    with telemetry.collect() as records:
        lo, ct = PJ.partitioned_probe(mesh, queries, keys, prepared=prepared)
    np.testing.assert_array_equal((lo, ct), oracle(keys, queries))
    (exchange,) = [r.extra for r in records if r.stage == "join:all_to_all"]
    assert exchange["owner_tier"] == "search" and exchange["search_rounds"] == 9
    assert exchange["bytes_exchanged"] == 4 * 4 * N * N * exchange["capacity"]


def test_positional_tables_lay_the_answers_out_by_offset():
    keys = np.array([10, 10, 11, 13, 13, 13, 20, 21, 22, 23], np.int32)
    local, lower, count, _ = PJ.partition_build_keys(keys, 4)  # slices of 1, 2, 2, 2 unique keys
    span_max, (first, lower_tab, count_tab) = PJ.positional_tables(local, lower, count, 8)
    assert span_max == 3  # {11, 13}
    assert first.tolist() == [10, 11, 20, 22]
    assert lower_tab.tolist() == [[0, -1, -1], [2, -1, 3], [6, 7, -1], [8, 9, -1]]
    assert count_tab.tolist() == [[2, 0, 0], [1, 0, 3], [1, 1, 0], [1, 1, 0]]
    assert PJ.positional_tables(local, lower, count, 2) == (3, None)
    # a dense slice of unique keys gives back its own payload
    dense = np.arange(100, 140, dtype=np.int32)
    local, lower, count, _ = PJ.partition_build_keys(dense, 4)
    span_max, (first, lower_tab, count_tab) = PJ.positional_tables(local, lower, count, 10)
    assert span_max == 10 and first.tolist() == [100, 110, 120, 130]
    np.testing.assert_array_equal(lower_tab, lower)
    np.testing.assert_array_equal(count_tab, count)
    # no keys at all: every shard is empty, nothing is in range
    local, lower, count, _ = PJ.partition_build_keys(np.empty(0, np.int32), 4)
    span_max, (first, lower_tab, count_tab) = PJ.positional_tables(local, lower, count, 8)
    assert span_max == 0 and lower_tab.tolist() == [[-1]] * 4 and count_tab.tolist() == [[0]] * 4


def _whiles_over(text: str, length: int) -> int:
    """``stablehlo.while`` operations that carry a ``tensor<{length}xi32>``."""
    carried = [
        line for line in text.splitlines() if re.search(r"stablehlo\.while\b", line)
    ]
    return sum(f"tensor<{length}xi32>" in line for line in carried)


def test_the_positional_program_has_no_loop_over_the_received_slots(mesh, monkeypatch):
    """``pjoin.probe_spmd_dev`` lowered for a positional index: no
    ``while`` carries the ``N * C`` received slots (the search form's
    does, once: its ``searchsorted``); the three exchanges stay."""
    keys = np.arange(24_000, dtype=np.int32)
    qk = shard_rows(mesh, np.arange(16_000, dtype=np.int32))
    capacity = 512  # N * C = 4,096 slots a shard: no other array has that length
    z = jnp.zeros(1, jnp.int32)
    texts = {}
    for max_bits in (None, -1):
        p, _ = prepare(mesh, keys, monkeypatch, max_bits=max_bits)
        texts[p.owner_tier] = PJ._probe_spmd_dev.lower(
            mesh, N, capacity, 0, p.positional,
            qk, p.owner, p.lower, p.count, *p.splits, z, z, z,
        ).as_text()
    assert _whiles_over(texts["search"], N * capacity) == 1
    assert _whiles_over(texts["positional"], N * capacity) == 0
    for text in texts.values():
        assert len(re.findall(r"stablehlo\.all_to_all\b", text)) == 3
    # routing is N - 1 compares (``_route_dest``), in the exchange and in the
    # count that sizes it: no loop anywhere in either program, and the count
    # exchanges nothing
    count = PJ._route_count_spmd.lower(mesh, qk, *p.splits).as_text()
    for text in (texts["positional"], count):
        assert not re.search(r"stablehlo\.while\b", text)
    assert not re.search(r"stablehlo\.all_to_all\b", count)
