"""Pallas TPU kernel: gather from tables that stay in VMEM.

``take_small(tables, idx)`` is ``jnp.take(t, idx, axis=0)`` for every
table of one dimension — all the same length ``T``, all read by the same
``idx`` — bit for bit over every int32 index (``-T <= idx < 0`` wraps,
any other index out of range reads ``INT32_MIN``, jnp's fill for int32).

XLA's gather on the TPU costs per index walked whatever the table's
size (PERF.md section 6, PR 44): 10M indices take 56-75 ms from a
1,000-entry table.  Here the tables are padded to ``C = ceil(T / 128)``
rows of 128 lanes and copied to VMEM once (a constant ``index_map``);
the indices stream through in ``(R, 128)`` blocks.  Per index vreg:
``lo = idx & 127``, ``hi = idx >> 7``; a ``fori_loop`` over the ``C``
chunks compares ``hi == c`` once for the dimension, lane-gathers chunk
``c`` of each table by ``lo`` (``tpu.dynamic_gather``) and selects.  No
index ever addresses memory, so no input can read out of bounds.

The work is ``C`` lane gathers an index vreg, so it pays only while the
table is small: past ``VMEM_GATHER_MAX_ENTRIES`` (and off the TPU, and
for a stream that is not whole on one device: a ``pallas_call`` is not
partitioned by GSPMD) the entry point IS ``jnp.take``, the same HLO as
ever.  Which way a call goes is read off shapes and placement by
``vmem_gather_selected``, at dispatch, and passed as a static flag: the
one rule for every caller — the multiway joins' emit and the composed
probe (PR 44), the binary join's emit (either side) and the fan-out
expansion's two segment reads (PR 46; ``ops/join.py``).
"""

from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# the largest table the kernel takes: fixed from the chip (PERF.md §6, PR 44)
VMEM_GATHER_MAX_ENTRIES = 131_073
# index rows of 128 a grid step streams (the last block may be ragged)
_BLOCK_ROWS = 256
# index rows one chunk loop carries (its vregs share each table row's load
# and the loop's scalar work: 128 measured fastest, PERF.md §6) and the
# tables one call reads (rows / 8 accumulator vregs a table: more spill more)
_GROUP_ROWS = 128
_MAX_TABLES = 3
_LANES = 128
# chunk bodies a loop iteration holds (a table is padded to whole iterations)
_UNROLL = 8
_FILL = np.iinfo(np.int32).min


def _kernel_mode():
    """How the kernel runs on this backend: compiled (True) on a TPU,
    not at all (False) anywhere else.  A test's fixture answers
    ``"interpret"`` to run it off the chip."""
    return jax.default_backend() == "tpu"


def whole_device(*arrays) -> bool:
    """True when every array sits whole on a single device."""
    for a in arrays:
        sh = getattr(a, "sharding", None)
        if sh is None or len(sh.device_set) != 1:
            return False
    return True


def vmem_gather_selected(tables: Sequence[jax.Array], idx=None):
    """The rule, read off the input at dispatch (outside the jit): the
    kernel serves these *tables* read by *idx* when they fit
    (``VMEM_GATHER_MAX_ENTRIES`` int32 entries), every array is whole on
    ONE device, and the backend is a TPU.  *idx* None: the index does
    not exist yet — the program that reads the tables forms it, on the
    device they are on (``csvplus.join.expand``; *tables* are then the
    program's inputs, of the tables' length, dtype and placement).  The
    answer is ``take_small``'s static *vmem* flag: False, or
    ``_kernel_mode()``'s."""
    if not tables or any(t.ndim != 1 or t.dtype != jnp.int32 for t in tables):
        return False
    if not 0 < tables[0].shape[0] <= VMEM_GATHER_MAX_ENTRIES:
        return False
    placed = tuple(tables) if idx is None else (idx, *tables)
    return whole_device(*placed) and _kernel_mode()


def _kernel(idx_ref, *refs, size: int):
    from jax.experimental import pallas as pl

    tabs, outs = refs[: len(refs) // 2], refs[len(refs) // 2 :]
    group = _GROUP_ROWS

    def one_group(g, carry):
        r0 = pl.multiple_of(g * group, group)
        idx = idx_ref[pl.ds(r0, group), :]
        idx = jnp.where(idx < 0, idx + size, idx)  # jnp.take's negative wrap
        lo = idx & (_LANES - 1)
        hi = idx >> 7  # negative or >= chunks: never hit, reads the fill

        def eight_chunks(c8, accs):
            # Mosaic unrolls a loop fully or not at all: eight bodies by hand
            accs = list(accs)
            for j in range(_UNROLL):  # analysis: allow[EAGER001] traced inside the pallas_call, never eager
                c = c8 * _UNROLL + j
                hit = hi == c
                for k, tab in enumerate(tabs):  # analysis: allow[EAGER001] as above
                    row = jnp.broadcast_to(tab[pl.ds(c, 1), :], (group, _LANES))
                    got = jnp.take_along_axis(row, lo, axis=1, mode="promise_in_bounds")
                    accs[k] = jnp.where(hit, got, accs[k])
            return tuple(accs)

        accs = jax.lax.fori_loop(
            0, tabs[0].shape[0] // _UNROLL, eight_chunks,
            tuple(jnp.full((group, _LANES), _FILL, jnp.int32) for _ in tabs),
        )
        for out, acc in zip(outs, accs):
            out[pl.ds(r0, group), :] = acc
        return carry

    jax.lax.fori_loop(0, idx_ref.shape[0] // group, one_group, 0)


def _vmem_take(tables, idx, *, interpret: bool):
    from jax.experimental import pallas as pl

    size, n = tables[0].shape[0], idx.shape[0]
    chunks = pl.cdiv(size, _LANES * _UNROLL) * _UNROLL
    # the pad reads the fill: an index in [T, 128 C) is out of range
    tabs = [
        jnp.pad(t, (0, chunks * _LANES - size), constant_values=_FILL).reshape(chunks, _LANES)
        for t in tables
    ]
    rows = pl.cdiv(n, _LANES)
    if rows * _LANES != n:
        idx = jnp.pad(idx, (0, rows * _LANES - n))
    block = min(_BLOCK_ROWS, pl.cdiv(rows, _GROUP_ROWS) * _GROUP_ROWS)  # whole groups
    stream = pl.BlockSpec((block, _LANES), lambda i: (i, 0))
    whole = pl.BlockSpec((chunks, _LANES), lambda i: (0, 0))  # copied once, stays
    outs = pl.pallas_call(
        functools.partial(_kernel, size=size),
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), jnp.int32)] * len(tabs),
        grid=(pl.cdiv(rows, block),),
        in_specs=[stream] + [whole] * len(tabs),
        out_specs=[stream] * len(tabs),
        interpret=interpret,
    )(idx.reshape(rows, _LANES), *tabs)
    return tuple(o.reshape(rows * _LANES)[:n] for o in outs)


def take_small(tables: Sequence[jax.Array], idx: jax.Array, *, vmem=False) -> Tuple[jax.Array, ...]:
    """``tuple(jnp.take(t, idx, axis=0) for t in tables)`` — traceable.
    *vmem* (static) is ``vmem_gather_selected``'s answer: False is
    ``jnp.take`` itself, True the VMEM kernel, ``"interpret"`` the
    kernel in interpret mode (tests, off the chip)."""
    idx = jnp.asarray(idx, dtype=jnp.int32)
    if not (vmem and tables and idx.shape[0]):
        return tuple(jnp.take(t, idx, axis=0) for t in tables)
    tables = tuple(tables)
    return tuple(
        out
        for at in range(0, len(tables), _MAX_TABLES)
        for out in _vmem_take(tables[at : at + _MAX_TABLES], idx, interpret=vmem == "interpret")
    )
