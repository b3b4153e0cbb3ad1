"""Pallas TPU kernel: copy runs of consecutive rows out of lanes held in VMEM.

``copy_runs(tables, first, counts, padded)`` is, for every table of one
dimension, ``jnp.take(t, build_ids)`` over the ``build_ids`` the fan-out
expansion forms (``ops/join.py``'s ``csvplus.join.expand``) — bit for
bit in the first ``sum(counts)`` slots, anything past them (the caller
cuts those off, as it cuts the expansion's).  Probe *p* matched the
build rows ``first[p] .. first[p] + counts[p] - 1`` and owns the output
slots ``starts[p] .. starts[p] + counts[p] - 1`` (``starts`` the
exclusive prefix sum): a RUN, not ``counts[p]`` indices.  XLA's gather
costs per index walked whatever it reads (7.5 ns; PERF.md section 6,
PR 44/46); here the cost is per run and per output row of 128.

A source lane, viewed as rows of 128, is copied whole into a VMEM
scratch once (single-buffered, ``_DMA_ROWS`` rows a DMA).  The grid walks WORK ITEMS: the
pairs of (output block of ``_BLOCK_ROWS`` rows, chunk of ``_CHUNK_RUNS``
runs) that overlap, in output order — two sorted lists of boundaries
merged by a small XLA sort, so that the pipeline brings each item its
output block (kept across the items that share it) and its chunk of
``(first, starts, counts)`` in SMEM, whatever the runs' lengths: a run
of a million rows and a million runs of one row are the same program.
Per run, per output row it touches: with ``d = first - start`` the row's
128 slots read source positions ``128 k + d ..``, which lie in source
rows ``q = (128 k + d) >> 7`` and ``q + 1``; select between the two at
lane ``d & 127``, rotate by it, store under the run's lane mask.  No
masked lane's value is used, so ``q`` is clamped and the lane is padded
to whole tiles of 8 rows with at least a row to spare.

Which joins take this path is read off the input by
``run_copy_selected``, at dispatch: the one rule.
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from . import gather

_LANES = 128
# output rows of 128 a grid step holds, and the runs of one SMEM chunk
_BLOCK_ROWS = 2048
_CHUNK_RUNS = 4096
# rows one DMA brings of a lane (2 MB)
_DMA_ROWS = 4096
# runs a loop iteration holds in straight-line code: their chains overlap
# (a 10M-row lane at 100,000 runs: 15.2 ms as two nested loops, 9.7, 5.7, 4.5
# and 4.3 ms at 1, 2, 4 and 8; PERF.md section 6, PR 47)
_UNROLL = 8
# lanes one call holds in VMEM where they fit: they share the scalar work,
# which is most of it (two 10M-row lanes a call 5.0 ms, one 4.3)
_MAX_TABLES = 2
# the shortest mean run (matches a probe) the copy serves: fixed from the chip
# (PERF.md section 6, PR 47).  A run costs 36 ns a lane (22 with two lanes a
# call), an index of XLA's gather 7.6, so they cross at a mean run of 5 (3);
# at 8 the copy is 1.6 (2.6) times faster
RUN_COPY_MIN_MEAN_RUN = 8
# the share of ``vmem_capacity_bytes`` one call's lanes and blocks may take
_VMEM_SHARE = 0.8
_V5E_VMEM_BYTES = 128 * 1024 * 1024


def _vmem_capacity_bytes() -> int:
    """The core's VMEM; off the chip (interpret mode, the tests) the
    v5e's, on which the constants above were fixed."""
    if jax.default_backend() != "tpu":
        return _V5E_VMEM_BYTES
    from jax.experimental.pallas import tpu as pltpu

    return int(pltpu.get_tpu_info().vmem_capacity_bytes)


def _call_bytes(entries: int, tables: int) -> int:
    """VMEM one call takes for *tables* lanes of *entries* int32: each
    lane whole (and a row), its output block twice."""
    rows = -(-entries // _LANES) + 8
    return tables * (rows + 2 * _BLOCK_ROWS) * _LANES * 4


def _tables_per_call(entries: int) -> int:
    """Lanes one call holds: as many as fit the share, at most
    ``_MAX_TABLES``; 0 where not even one does."""
    budget = int(_vmem_capacity_bytes() * _VMEM_SHARE)
    return min(_MAX_TABLES, budget // _call_bytes(entries, 1))


def run_copy_selected(tables: Sequence[jax.Array], first, counts, total: int):
    """The rule, read off the input at dispatch (outside the jit): the
    run copy serves *tables* read at the runs ``(first, counts)`` of
    *total* rows in all when every table is one int32 dimension of one
    length whose bytes fit the kernel's share of VMEM, every array is
    whole on ONE device, the backend is a TPU, and the mean run
    ``total / probes`` is at least ``RUN_COPY_MIN_MEAN_RUN``.  The answer
    is ``copy_runs``'s static *kernel* flag: False, or
    ``gather._kernel_mode()``'s."""
    if not tables or any(t.ndim != 1 or t.dtype != jnp.int32 for t in tables):
        return False
    entries, probes = tables[0].shape[0], counts.shape[0]
    if not entries or any(t.shape[0] != entries for t in tables):
        return False
    if not 0 < probes * RUN_COPY_MIN_MEAN_RUN <= total:
        return False
    if not gather.whole_device(first, counts, *tables):
        return False
    return _tables_per_call(entries) > 0 and gather._kernel_mode()


def _kernel(blk_ref, chk_ref, lo_ref, hi_ref, first_ref, starts_ref, counts_ref, *refs,
            tables: int, block_rows: int, chunk: int, src_rows: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    srcs, outs = refs[:tables], refs[tables : 2 * tables]
    bufs, sem = refs[2 * tables : 3 * tables], refs[3 * tables]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():  # the lanes, whole, once: they stay for every item
        for t, (src, buf) in enumerate(zip(srcs, bufs)):  # analysis: allow[EAGER001] traced inside the pallas_call, never eager
            for at in range(0, src.shape[0], _DMA_ROWS):  # analysis: allow[EAGER001] as above
                rows = min(_DMA_ROWS, src.shape[0] - at)
                copy = pltpu.make_async_copy(src.at[pl.ds(at, rows), :], buf.at[pl.ds(at, rows), :], sem.at[t])
                copy.start()
                copy.wait()

    row0 = blk_ref[i] * block_rows
    blo = row0 * _LANES
    bhi = blo + block_rows * _LANES
    cbase = chk_ref[i] * chunk
    plo, phi = lo_ref[i], hi_ref[i]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def scalars(p):
        """Run *p* cut to this block: its slots ``[lo, hi)`` (none where
        *p* is past the item's runs), and the way from slot to source."""
        j = jnp.clip(p - cbase, 0, chunk - 1)
        f, s, c = first_ref[j], starts_ref[j], counts_ref[j]
        lo = jnp.maximum(s, blo)
        hi = jnp.where(p < phi, jnp.minimum(s + c, bhi), lo)
        return lo, hi, f - s

    def piece(k, lo, hi, d):
        """Output row *k*'s slots of ``[lo, hi)``: the store's mask is
        empty where the run does not reach the row."""
        base = k * _LANES
        q = (base + d) >> 7
        r = d & (_LANES - 1)
        a = jnp.clip(q, 0, src_rows)
        b = jnp.clip(q + 1, 0, src_rows)
        mask = (lane >= lo - base) & (lane < hi - base)
        kk = jnp.clip(k - row0, 0, block_rows - 1)
        for buf, out in zip(bufs, outs):  # analysis: allow[EAGER001] traced inside the pallas_call, never eager
            x = jnp.where(lane >= r, buf[pl.ds(a, 1), :], buf[pl.ds(b, 1), :])
            pltpu.store(out.at[pl.ds(kk, 1), :], pltpu.roll(x, (_LANES - r) & (_LANES - 1), 1), mask=mask)

    def group(g, carry):
        # Mosaic unrolls a loop fully or not at all: ``_UNROLL`` runs by hand,
        # two rows each (a run of up to 129 slots touches no more)
        p0 = plo + g * _UNROLL
        long = False
        for u in range(_UNROLL):  # analysis: allow[EAGER001] as above
            lo, hi, d = scalars(p0 + u)
            k0 = lo >> 7
            piece(k0, lo, hi, d)
            piece(k0 + 1, lo, hi, d)
            long = long | (hi > ((k0 + 2) << 7))

        @pl.when(long)
        def _():  # the rows past a run's second: a loop a run
            for u in range(_UNROLL):  # analysis: allow[EAGER001] as above
                lo, hi, d = scalars(p0 + u)

                def one_row(k, carry, lo=lo, hi=hi, d=d):
                    piece(k, lo, hi, d)
                    return carry

                jax.lax.fori_loop((lo >> 7) + 2, (hi + _LANES - 1) >> 7, one_row, 0)

        return carry

    jax.lax.fori_loop(0, (phi - plo + _UNROLL - 1) // _UNROLL, group, 0)


def _work_items(starts, ends, out_rows: int, block_rows: int, chunk: int):
    """The (output block, run chunk) pairs that overlap, in output order,
    each with its runs ``[lo, hi)``: the boundaries of the blocks and of
    the chunks (in output slots), merged.  One item a boundary, so
    ``blocks + chunks`` of them whatever the data; an item past the last
    run has none."""
    block = block_rows * _LANES
    blocks, chunks = -(-out_rows // block_rows), starts.shape[0] // chunk
    chunk_starts = starts[::chunk]
    x = jnp.sort(jnp.concatenate([jnp.arange(blocks, dtype=jnp.int32) * block, chunk_starts]))
    nxt = jnp.concatenate([x[1:], jnp.full((1,), blocks * block, jnp.int32)])
    blk = jnp.minimum(x // block, blocks - 1)
    chk = jnp.clip(jnp.searchsorted(chunk_starts, x, side="right").astype(jnp.int32) - 1, 0, chunks - 1)
    lo = jnp.searchsorted(ends, x, side="right").astype(jnp.int32)  # the first run that ends past x
    hi = jnp.searchsorted(starts, nxt, side="left").astype(jnp.int32)  # the first that starts at the next
    return blk, chk, lo, jnp.maximum(hi, lo)


def _out_rows(padded: int) -> int:
    """Output rows of 128 for *padded* slots: whole tiles of 8."""
    return -(-padded // (8 * _LANES)) * 8


def _run_copy(tables, items, runs, padded: int, chunk: int, *, interpret: bool):
    """One call: *tables* (as many as ``_tables_per_call`` allows) whole
    in VMEM, the work *items* prefetched, the *runs*' three arrays in
    SMEM a *chunk* at a time."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = tables[0].shape[0]
    src_rows = pl.cdiv(n, _LANES)
    held = (src_rows // 8 + 1) * 8  # whole tiles of 8 rows, and a row past the lane's last
    srcs = [jnp.pad(t, (0, held * _LANES - n)).reshape(held, _LANES) for t in tables]
    out_rows = _out_rows(padded)
    block_rows = min(_BLOCK_ROWS, out_rows)
    run_spec = pl.BlockSpec((chunk,), lambda i, blk, chk, lo, hi: (chk[i],), memory_space=pltpu.SMEM)
    out_spec = pl.BlockSpec((block_rows, _LANES), lambda i, blk, chk, lo, hi: (blk[i], 0))
    outs = pl.pallas_call(
        functools.partial(_kernel, tables=len(tables), block_rows=block_rows, chunk=chunk, src_rows=src_rows),
        out_shape=[jax.ShapeDtypeStruct((out_rows, _LANES), jnp.int32)] * len(tables),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(items[0].shape[0],),
            in_specs=[run_spec] * 3 + [pl.BlockSpec(memory_space=pl.ANY)] * len(tables),
            out_specs=[out_spec] * len(tables),
            scratch_shapes=[pltpu.VMEM((held, _LANES), jnp.int32) for _ in tables]
            + [pltpu.SemaphoreType.DMA((len(tables),))],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),  # an output block is kept across its items
            vmem_limit_bytes=_call_bytes(n, len(tables)) + (4 << 20),
        ),
        interpret=interpret,
    )(*items, *runs, *srcs)
    return tuple(o.reshape(out_rows * _LANES)[:padded] for o in outs)


def copy_runs(
    tables: Sequence[jax.Array], first: jax.Array, counts: jax.Array, padded: int, *, kernel=True
) -> Tuple[jax.Array, ...]:
    """Each table's rows ``first[p] .. first[p] + counts[p] - 1``, probe
    after probe, in *padded* slots (``>= sum(counts)``; those past the
    sum hold anything) — traceable.  *kernel* (static) is
    ``run_copy_selected``'s answer: True the kernel, ``"interpret"`` the
    kernel in interpret mode (tests, off the chip)."""
    tables = tuple(tables)
    probes = first.shape[0]
    chunk = min(_CHUNK_RUNS, -(-probes // _LANES) * _LANES)
    pad = (0, -(-probes // chunk) * chunk - probes)  # whole chunks: the pad's runs are empty
    counts, first = jnp.pad(counts.astype(jnp.int32), pad), jnp.pad(first.astype(jnp.int32), pad)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    out_rows = _out_rows(padded)
    items = _work_items(starts, ends, out_rows, min(_BLOCK_ROWS, out_rows), chunk)
    each = max(_tables_per_call(tables[0].shape[0]), 1)
    return tuple(
        out
        for at in range(0, len(tables), each)
        for out in _run_copy(
            tables[at : at + each], items, (first, starts, counts), padded, chunk, interpret=kernel == "interpret"
        )
    )
