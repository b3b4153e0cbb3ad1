"""``ops/run_copy.py`` (ISSUE 47): the fan-out's build side moves a run
at a time.

``jnp.take`` over the expansion's own ``build_ids`` is the arbiter, bit
for bit: ``gather_runs(tables, first, counts, total)`` — the program
``csvplus.join.gather_runs`` and its cut — over every shape class of
run (interpret mode, so small sizes; the blocks and chunks are shrunk
where a case is about their boundaries), then the work items' own
invariants.  The rule that selects the kernel and the joins through it
are in ``tests/test_gather_small.py``, beside the VMEM gather's.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from csvplus_tpu.ops import gather as G
from csvplus_tpu.ops import join as J
from csvplus_tpu.ops import run_copy as RC

I32 = np.iinfo(np.int32)


@pytest.fixture(autouse=True, scope="module")
def _journal_of_its_own():
    """As ``tests/test_gather_small.py``: the interpreter's traces stay
    out of the process journal."""
    from csvplus_tpu.obs.span import Journal, tracer

    kept, tracer.journal = tracer.journal, Journal(trace_id=-47)
    yield
    tracer.journal = kept


@pytest.fixture
def small_blocks(monkeypatch):
    """Output blocks of 8 rows (1,024 slots) and chunks of 128 runs, so
    that a few thousand rows cross many of both."""
    monkeypatch.setattr(RC, "_BLOCK_ROWS", 8)
    monkeypatch.setattr(RC, "_CHUNK_RUNS", 128)
    G._gather_runs_kernel.clear_cache()  # the constants are no part of jit's key
    yield
    G._gather_runs_kernel.clear_cache()


def _tables(rng, n: int, tables: int):
    out = []
    for _ in range(tables):
        t = rng.integers(I32.min, I32.max, n, dtype=np.int32, endpoint=True)
        t[[0, n - 1]] = [I32.min, I32.max]
        out.append(jnp.asarray(t))
    return tuple(out)


def _firsts(rng, n: int, counts: np.ndarray) -> np.ndarray:
    """A first row a run, anywhere the run fits (they may overlap: the
    copy reads, it does not partition)."""
    return (rng.integers(0, n, len(counts)) % np.maximum(n - counts + 1, 1)).astype(np.int32)


def _runs(case: str, rng, n: int):
    """(first, counts) of one shape class of runs over an *n*-row lane."""
    if case == "all-ones":
        counts = np.ones(3000, np.int32)
    elif case == "mean-100-with-zeros":
        counts = rng.binomial(10_000, 0.01, 120).astype(np.int32)
        counts[rng.choice(120, 30, replace=False)] = 0
        counts[[0, -1]] = 0  # the first and the last probe matched nothing
    elif case == "one-long-run":  # a Zipf head: one customer holds most of the file
        counts = rng.integers(0, 12, 200).astype(np.int32)
        counts[77] = 9_000  # nine output blocks of 1,024
    elif case == "every-lane-offset":
        # run j starts at output lane (sum of the counts before) and source
        # lane first % 128: 129 and 1 step the output by every residue,
        # the firsts below the source by every residue against it
        counts = np.tile(np.array([129, 1, 127, 2], np.int32), 128)
    elif case == "crosses-a-block":
        counts = np.array([1000, 48, 1, 2048, 5], np.int32)  # 1,024 falls inside the second run
    elif case == "ragged-total":
        counts = rng.integers(0, 40, 333).astype(np.int32)
        counts[-1] += int(counts.sum()) % 128 == 0  # the last output row is ragged
    else:
        raise AssertionError(case)
    first = _firsts(rng, n, counts)
    if case == "every-lane-offset":
        first = ((np.arange(len(counts)) * 131) % (n - 200)).astype(np.int32)
    return first, counts


def _want(tables, first, counts, total):
    _, build_ids = J.expand_matches_device(jnp.asarray(first), jnp.asarray(counts), total)
    return build_ids, [np.asarray(jnp.take(t, build_ids, axis=0)) for t in tables]


CASES = ["all-ones", "mean-100-with-zeros", "one-long-run", "every-lane-offset", "crosses-a-block", "ragged-total"]


@pytest.mark.parametrize("tables", [1, 3, 4])
@pytest.mark.parametrize("case", CASES)
def test_gather_runs_is_jnp_take_of_the_expansions_ids(case, tables, small_blocks):
    rng = np.random.default_rng(47 + 7 * CASES.index(case) + tables)
    n = 20_011  # no multiple of 128: the lane's last row is ragged
    first, counts = _runs(case, rng, n)
    total = int(counts.sum())
    if case == "ragged-total":
        assert total % 128
    tabs = _tables(rng, n, tables)
    build_ids, want = _want(tabs, first, counts, total)
    assert np.array_equal(np.asarray(build_ids), np.concatenate([np.arange(f, f + c) for f, c in zip(first, counts)]))
    got = G.gather_runs(tabs, jnp.asarray(first), jnp.asarray(counts), total, kernel="interpret")
    assert len(got) == tables
    for g, w in zip(got, want):
        assert g.dtype == jnp.int32 and g.shape == (total,)
        assert np.array_equal(np.asarray(g), w)


@pytest.mark.parametrize("n, probes, mean", [(10_000, 64, 100), (100_000, 3000, 30), (300_000, 40, 9000)])
def test_at_the_blocks_the_chip_uses(n, probes, mean):
    """The constants as they stand (2,048-row blocks, 4,096-run chunks):
    one block and one chunk, several chunks, several blocks."""
    rng = np.random.default_rng(n + probes)
    counts = rng.poisson(mean, probes).astype(np.int32)
    first = _firsts(rng, n, counts)
    total = int(counts.sum())
    tabs = _tables(rng, n, 2)
    _, want = _want(tabs, first, counts, total)
    got = G.gather_runs(tabs, jnp.asarray(first), jnp.asarray(counts), total, kernel="interpret")
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), w)


def test_the_last_rows_of_the_lane_are_reached(small_blocks):
    """A run that ends on the lane's last row reads source row ``q + 1``
    one past the lane: the scratch's spare row, under an empty mask."""
    n = 1024  # whole rows of 128: no pad behind the last
    tab = jnp.arange(n, dtype=jnp.int32) * 3 + 1
    first = np.array([n - 5, 0, n - 300, 1023], np.int32)
    counts = np.array([5, 7, 300, 1], np.int32)
    (got,) = G.gather_runs((tab,), jnp.asarray(first), jnp.asarray(counts), 313, kernel="interpret")
    want = np.concatenate([np.arange(f, f + c) for f, c in zip(first, counts)]) * 3 + 1
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("probes", [0, 5], ids=["empty-probe", "nothing-matched"])
def test_no_run_writes_nothing(probes):
    tabs = _tables(np.random.default_rng(1), 500, 3)
    zeros = jnp.zeros(probes, jnp.int32)
    got = G.gather_runs(tabs, zeros, zeros, 0, kernel="interpret")
    assert len(got) == 3 and all(g.shape == (0,) and g.dtype == jnp.int32 for g in got)
    assert G.gather_runs((), zeros, zeros, 0, kernel="interpret") == ()


def test_the_work_items_cover_every_run_once(small_blocks):
    """One item a boundary (blocks + chunks of them, whatever the data),
    blocks in order — an output block is kept across the items that
    share it —, and every slot of every run inside exactly one item."""
    rng = np.random.default_rng(3)
    counts = rng.integers(0, 60, 1024).astype(np.int32)
    counts[200:500] = 0  # two chunks and more that emit nothing
    counts[700] = 5000
    ends = np.cumsum(counts)
    starts = ends - counts
    out_rows = 512  # 64 blocks of 8 rows; the runs end in the 29th
    blk, chk, lo, hi = (np.asarray(a) for a in RC._work_items(jnp.asarray(starts), jnp.asarray(ends), out_rows, 8, 128))
    assert len(blk) == 64 + 8
    assert (np.diff(blk) >= 0).all() and blk.max() == 63
    covered = np.zeros(int(ends[-1]), np.int32)
    for b, c, p0, p1 in zip(blk, chk, lo, hi):
        for p in range(p0, p1):
            assert c * 128 <= p < (c + 1) * 128  # the item's chunk holds its runs
            a, z = max(starts[p], b * 1024), min(ends[p], (b + 1) * 1024)
            covered[a:z] += 1
    assert (covered == 1).all()
