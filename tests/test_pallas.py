"""Pallas fused-mask kernel: interpret-mode differential tests (CPU CI;
the same kernel compiles natively on TPU)."""

import numpy as np
import pytest

import jax.numpy as jnp

from csvplus_tpu import Like, Row, Take, from_file
from csvplus_tpu.ops.pallas_mask import fused_equality_mask


def test_fused_mask_matches_jnp():
    rng = np.random.default_rng(0)
    n = 5000  # not tile-aligned on purpose
    a = jnp.asarray(rng.integers(0, 7, n).astype(np.int32))
    b = jnp.asarray(rng.integers(0, 3, n).astype(np.int32))
    got = fused_equality_mask([a, b], [4, 1], n, mode="all")
    assert got is not None
    want = (np.asarray(a) == 4) & (np.asarray(b) == 1)
    assert np.array_equal(np.asarray(got), want)

    got_or = fused_equality_mask([a, b], [4, 1], n, mode="any")
    want_or = (np.asarray(a) == 4) | (np.asarray(b) == 1)
    assert np.array_equal(np.asarray(got_or), want_or)


def test_fused_mask_absent_cells():
    """-1 (absent) codes never match a real target."""
    a = jnp.asarray(np.array([0, -1, 2, -1], dtype=np.int32))
    b = jnp.asarray(np.array([5, 5, 5, 5], dtype=np.int32))
    got = fused_equality_mask([a, b], [2, 5], 4, mode="all")
    assert np.asarray(got).tolist() == [False, False, True, False]


def test_fused_mask_width_limits():
    a = jnp.zeros(10, dtype=jnp.int32)
    assert fused_equality_mask([a] * 9, [0] * 9, 10) is None  # > MAX_COLS
    assert fused_equality_mask([], [], 10) is None
    assert fused_equality_mask([a], [0], 0) is None


def test_multi_column_like_uses_fused_path(people_csv):
    """End-to-end: a 2-column Like on a device source stays correct."""
    dev = from_file(people_csv).on_device("cpu")
    host = Take(from_file(people_csv))
    p = Like({"name": "Amelia", "surname": "Jones"})
    assert dev.filter(p).to_rows() == host.filter(p).to_rows()
    q = Like({"name": "Amelia", "surname": "NoSuch"})
    assert dev.filter(q).to_rows() == host.filter(q).to_rows() == []


def test_any_of_likes_fused_parity(people_csv):
    """Any(Like, Like, ...) of single-column equalities fuses to one
    'any' kernel and matches the host, including missing columns/values."""
    from csvplus_tpu import Any, Take, from_file

    dev = from_file(people_csv).on_device("cpu")
    host = Take(from_file(people_csv))
    for pred in [
        Any(Like({"surname": "Jones"}), Like({"surname": "Lewis"}), Like({"name": "Ava"})),
        Any(Like({"surname": "Jones"}), Like({"nope": "x"})),
        Any(Like({"nope": "x"}), Like({"name": "NoSuchValue"})),
        Any(Like({"name": "Amelia", "surname": "Smith"}), Like({"name": "Jack"})),  # multi-col branch: recursive path
    ]:
        assert dev.filter(pred).to_rows() == host.filter(pred).to_rows()


def test_in_list_grouping_streams_column_once(people_csv):
    """A 12-value IN-list on one column groups into a single streamed
    column (fusion survives beyond MAX_COLS terms) and stays correct."""
    from csvplus_tpu import Any, Take, from_file
    from conftest import PEOPLE_SURNAMES

    dev = from_file(people_csv).on_device("cpu")
    host = Take(from_file(people_csv))
    pred = Any(*[Like({"surname": s}) for s in PEOPLE_SURNAMES])  # 12 terms
    got = dev.filter(pred).to_rows()
    assert got == host.filter(pred).to_rows()
    assert len(got) == 120  # every surname matches
    mixed = Any(
        Like({"surname": "Jones"}),
        Like({"surname": "Lewis"}),
        Like({"name": "Ava"}),
        Like({"surname": "Jones"}),  # duplicate value, same column
    )
    assert dev.filter(mixed).to_rows() == host.filter(mixed).to_rows()


def test_a_kernel_failure_is_an_error_not_a_fallback(monkeypatch):
    """A Pallas compile or run failure propagates; returning None would
    hand the predicate to the jnp path without a word."""
    from csvplus_tpu.ops import pallas_mask

    def broken(*args, **kwargs):
        raise RuntimeError("Mosaic failed to compile the kernel")

    monkeypatch.setattr(pallas_mask, "_fused_mask_call", broken)
    a = jnp.asarray([0, 1, 2, 1], dtype=jnp.int32)
    with pytest.raises(RuntimeError, match="Mosaic failed"):
        fused_equality_mask([a, a], [1, 1], 4)
    # the shape-doesn't-fit early returns stay
    assert fused_equality_mask([], [], 4) is None
