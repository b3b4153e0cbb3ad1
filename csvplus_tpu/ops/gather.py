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
``vmem_gather_selected``, at dispatch, and passed as a static flag.

Every row a join moves goes through :func:`emit`, below the kernel: the
one place that chooses between this kernel, the run copy
(``ops/run_copy.py``), a program a lane, one program a group and eager
takes, and that reports what it chose.  The two table reads inside a
program that are no emit — the composed probe's and the fan-out
expansion's (``ops/join.py``) — ask ``vmem_gather_selected`` themselves.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.table import same_placement
from ..obs.recompile import register_kernel
from . import run_copy  # it reads ``whole_device`` and ``_kernel_mode`` here, at call time

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


# -- the emit: every row a join moves -------------------------------------------


@register_kernel("join.gather_lane")
def _gather_lane(storage, ids):
    """One lane's rows at *ids*: a program of its own, because a program
    gets one or two cross-program prefetches — a lane gathered here is
    read from fast memory, where of four lanes gathered by one program
    two or three are read where they lie, at a third of the rate
    (``PERF.md`` §6: PR 43, the stream's lanes; PR 45, the build side's)."""
    return jnp.take(storage, ids, axis=0)


@register_kernel("join.gather_cols", static_argnames=("vmem",))
def _gather_cols(tables, ids, vmem):  # analysis: allow[JIT001] — arity fixed per pipeline shape, three gather forms a group
    """Groups of lanes, each group read by its one index, in ONE
    program.  *vmem* says per group whether its tables are read from
    VMEM (``vmem_gather_selected``'s answers); False is ``jnp.take`` a
    lane."""
    return tuple(take_small(t, i, vmem=v) for t, i, v in zip(tables, ids, vmem))


@register_kernel("join.expand_head", static_argnames=("total",))
def _expand_head_kernel(ids: Tuple[jax.Array, ...], total: int) -> Tuple[jax.Array, ...]:  # analysis: allow[JIT001] retrace is per id-lane count, not per data length
    """The first *total* slots of every padded lane.  A program of its
    own (``ops/sort.py``'s ``dedup.head``) so that what pads — the
    fan-out expansion, the run copy — compiles once per power of two,
    not per total, and so that the cut runs under a ``csvplus.`` name,
    not as an eager slice a lane."""
    return tuple(lane[:total] for lane in ids)


@register_kernel("join.gather_runs", static_argnames=("padded", "kernel"))
def _gather_runs_kernel(tables, first, counts, padded: int, kernel=True):  # analysis: allow[JIT001] retrace is per build-lane count and per power of two, not per total
    """The build side's lanes at the fan-out's runs, *padded* slots a
    lane (``ops/run_copy.py``): the expansion's padded length, so it
    compiles per power of two, as ``csvplus.join.expand`` does."""
    return run_copy.copy_runs(tables, first, counts, padded, kernel=kernel)


def gather_runs(tables, first, counts, total: int, *, kernel=True) -> Tuple[jax.Array, ...]:
    """``tuple(jnp.take(t, build_ids) for t in tables)`` for the
    ``build_ids`` of the fan-out expansion of ``(first, counts)``, bit
    for bit, without them: output rows ``starts[p] .. starts[p] +
    counts[p] - 1`` (``starts`` the exclusive prefix sum) hold
    ``t[first[p] .. first[p] + counts[p] - 1]``, and a probe that matched
    nothing writes nothing.  *kernel*: ``run_copy_selected``'s answer."""
    tables = tuple(tables)
    if not tables or not total:
        return tuple(t[:0] for t in tables)
    padded = 1 << (total - 1).bit_length()
    lanes = _gather_runs_kernel(tables, jnp.asarray(first), jnp.asarray(counts), padded=padded, kernel=kernel)
    return _expand_head_kernel(lanes, total=total)


class Lanes(NamedTuple):
    """One group of an emit: *tables* all read by the one *idx*.  *runs*,
    on the fan-out's build side only, is the probe's answer ``(first,
    counts, total)`` that *idx* expands: probe *p* matched the build
    rows ``first[p] .. first[p] + counts[p] - 1``."""

    tables: Tuple[jax.Array, ...]
    idx: Any
    runs: Optional[tuple] = None


class Emitted(NamedTuple):
    """What :func:`emit` did: per group its *lanes* and the *form* that
    moved them (``eager`` | ``runs`` | ``vmem`` | ``lane`` | ``cols``;
    ``none`` for a group of no table), and the registered *programs* it
    dispatched, in order."""

    lanes: Tuple[Tuple[jax.Array, ...], ...]
    forms: Tuple[str, ...]
    programs: Tuple[str, ...]

    def moved(self, form: str) -> int:
        """Lanes *form* moved (``join:merge``'s ``vmem_gathers`` and
        ``run_copies`` are ``moved("vmem")`` and ``moved("runs")``)."""
        return sum(len(g) for g, f in zip(self.lanes, self.forms) if f == form)


def _form(tables, idx, runs):
    """``(form, the kernel's static flag)`` for one group: the decision
    table of :func:`emit`, in its order."""
    if not tables:
        return "none", False
    if not same_placement(tables + (idx,)):
        return "eager", False
    if runs is not None and (kernel := run_copy.run_copy_selected(tables, *runs)):
        return "runs", kernel
    if vmem := vmem_gather_selected(tables, idx):
        return "vmem", vmem
    if whole_device(idx, *tables):
        return "lane", False
    return "cols", False


def emit(groups: Sequence[Lanes]) -> Emitted:
    """``tuple(jnp.take(t, idx, axis=0) for t in tables)`` for every
    group, bit for bit, in the form the chip taught for what the group
    IS — read off placement, table length, dtype, lane count and the
    runs' shape here, at the dispatch, outside any jit.  In this order:

    * arrays of mixed placement (the partitioned tier's host ids over a
      sharded stream) — eager takes, each free to resolve its own;
    * *runs* that ``run_copy_selected`` admits — the lanes are copied a
      run at a time (``csvplus.join.gather_runs``) and *idx* is not read;
    * tables ``vmem_gather_selected`` admits — ``take_small``, every such
      group of the call in ONE ``csvplus.join.gather_cols``, dispatched
      where the first of them stands;
    * lanes whole on one device — a program a lane
      (``csvplus.join.gather_lane``);
    * else (lanes sharded over a mesh: a ``pallas_call`` is not
      partitioned) — one ``csvplus.join.gather_cols`` for the group."""
    plan = [_form(*g) for g in groups]
    shared = [at for at, (form, _) in enumerate(plan) if form == "vmem"]
    out, programs = [() for _ in groups], []
    for at, ((tables, idx, runs), (form, flag)) in enumerate(zip(groups, plan)):
        if form == "eager":
            i32 = jnp.asarray(idx, dtype=jnp.int32)
            out[at] = tuple(jnp.take(t, i32, axis=0) for t in tables)
        elif form == "runs":
            out[at] = gather_runs(tables, *runs, kernel=flag)
            programs += ["join.gather_runs", "join.expand_head"]
        elif form == "lane":
            out[at] = tuple(_gather_lane(t, idx) for t in tables)
            programs += ["join.gather_lane"] * len(tables)
        elif form == "cols" or (form == "vmem" and at == shared[0]):
            ats = shared if form == "vmem" else [at]
            got = _gather_cols(
                tuple(groups[a].tables for a in ats),
                tuple(groups[a].idx for a in ats),
                vmem=tuple(plan[a][1] for a in ats),  # analysis: allow[RETRACE002] the rule's answers, read off shapes and placement: three values a group
            )
            for a, lanes in zip(ats, got):
                out[a] = lanes
            programs.append("join.gather_cols")
    return Emitted(tuple(out), tuple(form for form, _ in plan), tuple(programs))
