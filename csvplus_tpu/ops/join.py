"""Device lookup join: sorted packed keys + vectorized binary-search probe.

The reference's join is a per-row binary search over sorted string rows
(csvplus.go:552-568, 869-920).  The device design replaces it wholesale:

* the build side (an :class:`~csvplus_tpu.index.Index`) is columnarized
  and its key columns **packed into one integer per row** — each key
  column's dictionary codes occupy a bit field sized to its cardinality.
  Because each dictionary is sorted, the packed integer order equals the
  reference's multi-column lexicographic string order, and because index
  rows are already key-sorted, the packed array is sorted too;
* the probe side translates its key columns into the build side's
  dictionary spaces (host translation tables built by binary search over
  the dictionaries, then one device gather), packs the same way, and a
  single vectorized ``searchsorted`` finds every row's match range at
  once — one fused device pass instead of ``n`` host binary searches;
* match fan-out (non-unique indices) is data-dependent, so expansion is
  two-phase: counts are computed on device, ONLY the total match count is
  synced to host (it sizes the static output shape), and the gather
  index vectors are built by a jitted prefix-sum + searchsorted kernel
  on device — the count -> prefix-sum -> scatter pattern from
  SURVEY.md §7 with O(1) host transfer.

Key-width tiers (TPUs are 32-bit-native; JAX int64 needs global x64):

* <= 31 bits packed — ``int32`` keys, probe fully on device (covers the
  benchmark configs: single join column up to ~1B cardinality, or e.g.
  two columns of 32K x 32K);
* <= 62 bits — keys split into TWO nonnegative 31-bit ``int32`` lanes
  (hi, lo); the probe is a vectorized branchless binary search with a
  lexicographic two-lane compare, fully on device with no x64 — e.g. a
  composite key of two 64K-cardinality columns;
* wider — not packable; the planner falls back to the host join.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, ClassVar, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..columnar.table import (
    DeviceTable,
    StringColumn,
    _gather_take,
    merge_with_fallback,
    same_placement,
)
from ..columnar.typed import PAD_VALUE
from ..obs.recompile import register_kernel
from ..obs.span import tracer
from ..utils.env import env_int
from ..utils.observe import telemetry
from .gather import Lanes, _expand_head_kernel, _gather_cols, emit, take_small, vmem_gather_selected, whole_device


def _bits_for(n: int) -> int:
    """Bits needed to store codes 0..n-1 plus the sentinel 0 slot."""
    return max(int(n + 1).bit_length(), 1)


_MASK31 = (1 << 31) - 1


def pack_lanes(codes, shifts, bits):
    """Pack per-column code arrays into two nonnegative 31-bit int32
    lanes (hi = key >> 31, lo = key & 0x7FFFFFFF) without 64-bit math:
    each column's contribution lands in one lane or straddles both.
    Works on jnp or numpy arrays alike.  Plain signed (hi, lo) compare
    equals the 62-bit key order because both lanes are nonnegative."""
    hi = None
    lo = None

    def _or(acc, v):
        return v if acc is None else acc | v

    for c, s, b in zip(codes, shifts, bits):
        c = c.astype(jnp.int32) if isinstance(c, jax.Array) else c.astype(np.int32)
        if s >= 31:
            hi = _or(hi, c << (s - 31))
        elif s + b <= 31:
            lo = _or(lo, c << s)
        else:  # straddles the lane boundary
            k = 31 - s
            lo = _or(lo, (c & ((1 << k) - 1)) << s)
            hi = _or(hi, c >> k)
    zeros = (jnp.zeros_like if isinstance(lo, jax.Array) else np.zeros_like)
    if hi is None:
        hi = zeros(lo)
    if lo is None:
        lo = zeros(hi)
    return hi, lo


def _searchsorted2(keys_hi, keys_lo, q_hi, q_lo, side: str = "left"):
    """Vectorized binary search over (hi, lo) lane pairs — branchless,
    static trip count (runs under jit; n is a trace-time constant from
    the key shapes).  *side* follows numpy searchsorted semantics."""
    n = keys_hi.shape[0]
    lo_idx = jnp.zeros(q_hi.shape, jnp.int32)
    hi_idx = jnp.full(q_hi.shape, n, jnp.int32)
    for _ in range(max(int(n).bit_length(), 1)):
        active = lo_idx < hi_idx
        mid = (lo_idx + hi_idx) >> 1
        safe = jnp.clip(mid, 0, max(n - 1, 0))
        kh = jnp.take(keys_hi, safe, axis=0)
        kl = jnp.take(keys_lo, safe, axis=0)
        if side == "left":
            descend = (kh < q_hi) | ((kh == q_hi) & (kl < q_lo))
        else:
            descend = (kh < q_hi) | ((kh == q_hi) & (kl <= q_lo))
        lo_idx = jnp.where(active & descend, mid + 1, lo_idx)
        hi_idx = jnp.where(active & ~descend, mid, hi_idx)
    return lo_idx


@register_kernel("join.probe_i32pair")
def _probe_kernel_i32pair(keys_hi, keys_lo, q_hi, q_lo, r_hi, r_lo, ok):
    """Wide-key range probe: two lane-pair binary searches (lower at the
    query, upper at query + range with a 31-bit carry)."""
    n = keys_hi.shape[0]
    lower = _searchsorted2(keys_hi, keys_lo, q_hi, q_lo)
    lo2 = q_lo + r_lo
    # two 31-bit values can sum to 2^31, wrapping int32 negative; the
    # carry must be the unsigned bit 31, not the arithmetic sign fill
    carry = (lo2 >> 31) & 1
    lo2 = lo2 & _MASK31
    hi2 = q_hi + r_hi + carry
    upper = _searchsorted2(keys_hi, keys_lo, hi2, lo2)
    upper = jnp.where(hi2 < 0, n, upper)  # range walked off the 62-bit top
    counts = jnp.where(ok, upper - lower, 0)
    return lower.astype(jnp.int32), counts.astype(jnp.int32)


def direct_probe_parts(
    cum: jax.Array, qk: jax.Array, range_size
) -> Tuple[jax.Array, jax.Array]:
    """Dictionary-direct range probe (traceable; call under jit): O(1)
    gathers instead of binary search — the ONE definition of the direct
    tier's semantics, shared by the generic probe kernel and the fused
    flagship join.

    ``cum[j]`` = number of build keys < j over the packed-key universe
    ``U`` (``cum`` has U+1 slots).  Because build keys are sorted,
    ``cum[q]`` IS searchsorted-left(keys, q), so a probe is two gathers
    in place of the ~log2(n) sequential gather rounds XLA emits for
    ``searchsorted`` (the gain on a TPU is not measured).

    Per ROW this is the staged form: a probe of one key column walks
    the rows three times (translate, then these two gathers).  Where
    the probe column's universe is small beside the stream
    (``DeviceIndex._composed_for``) the same function runs once over
    the UNIVERSE instead (``_compose_probe_kernel``), and a row reads
    its answer from the composed tables in one or two walks (tier
    ``direct-composed``) — same ``(lower, counts)``, bit for bit.
    """
    U = cum.shape[0] - 1
    q = jnp.clip(qk, 0, U)
    lower = jnp.take(cum, q, axis=0)
    upper = jnp.take(cum, jnp.minimum(q + range_size, U), axis=0)
    valid = qk >= 0
    counts = jnp.where(valid, upper - lower, 0)
    return lower.astype(jnp.int32), counts.astype(jnp.int32)


@register_kernel("join.probe_direct")
def _probe_kernel_direct(
    cum: jax.Array, qk: jax.Array, range_size: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    return direct_probe_parts(cum, qk, range_size)


@register_kernel("join.probe_i32")
def _probe_kernel_i32(
    keys: jax.Array, qk: jax.Array, range_size: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Vectorized range probe on device (int32 packed keys).

    *range_size* widens the probe to a key-prefix range: 1 for full-width
    keys, ``1 << shift_of_last_probed_column`` for prefix probes (the
    reference's prefix ``find``, csvplus.go:870-891, and prefix joins).
    """
    lower = jnp.searchsorted(keys, qk, side="left")
    upper = jnp.searchsorted(keys, qk + range_size, side="left")
    valid = qk >= 0
    counts = jnp.where(valid, upper - lower, 0)
    return lower.astype(jnp.int32), counts.astype(jnp.int32)


@register_kernel("serve.bounds_search")
def _bounds_search_kernel(keys: jax.Array, queries: jax.Array) -> jax.Array:
    """A lookup batch's lower and upper bounds in one pass over the
    packed key array (``point_bounds_many``'s device tier): a named
    program where an eager ``jnp.searchsorted`` shows as ``jit_searchsorted``."""
    return jnp.searchsorted(keys, queries, side="left")


@register_kernel("join.build_direct_cum", static_argnames=("total_bits",))
def _build_direct_cum(keys: jax.Array, total_bits: int) -> jax.Array:
    """cum[j] = number of build keys strictly below j, for every packed
    key value j in the universe [0, 2^total_bits] — one scatter-add and
    one cumsum at index-build time."""
    U = 1 << total_bits
    hist = jnp.zeros(U + 1, dtype=jnp.int32)
    hist = hist.at[keys.astype(jnp.int32) + 1].add(1, mode="drop")
    return jnp.cumsum(hist)


# -- composed probe (ISSUE 26) ----------------------------------------------
#
# An XLA gather on the TPU costs per INDEX walked, not per byte: 10M
# indices take 0.05-0.075 s whether the table has 1,000 entries or
# 100,000 (PERF.md section 5).  That no longer sets the price where a
# small table is read — by any join's emit (``gather.emit``), by the
# composed probe or by the fan-out expansion's segment reads:
# ``take_small`` (``gather.py``) serves tables of up to
# ``VMEM_GATHER_MAX_ENTRIES`` entries from VMEM, at a cost that grows
# with the TABLE (PERF.md section 6, PRs 44 and 46); a walk saved is
# still a walk saved.  The staged probe of one key column walks
# the rows three times — ``trans[v - lo]``, ``cum[q]``, ``cum[q + range]``
# — and each build column is one more walk through the row id.  Every
# one of those pointers is a function of the key's VALUE alone, and the
# value's universe (the dense range of an IntColumn's translation, or
# the probe column's dictionary) is small beside the stream.  So the
# chain is composed once over the universe, at table size:
#
#   depth 1   lower_tab[u], cnt_tab[u] = direct_probe_parts(cum, q(trans[u]))
#             (one table, ``rid_tab``, where the index is unique): a row
#             reads its answer in one or two walks;
#   depth 2   col_tab[u] = column.storage[rid_tab[u]] where the index is
#             unique and every slot of the universe holds a build row:
#             "matched" is a range test and the emit is one walk per
#             emitted lane with no probe walk at all.
#
# Which depth runs is read off shapes and placement, never configured.


# a composition costs one table-size walk per table, so it pays only
# where the universe is small beside the rows it saves walks over
_COMPOSE_ROWS_PER_SLOT = 4
# depth 2 holds one table per build column over the universe: only where
# the universe is no sparser than twice the build rows
_EMIT_SLOTS_PER_BUILD_ROW = 2
# composed entries kept per index (one per probe prefix / dictionary)
_COMPOSED_KEPT = 8


@dataclass
class _Composed:
    """One probe column's composed tables over its universe of ``size``
    slots (see the section comment).  ``base`` says how a row finds its
    slot: an int32 scalar ``lo`` for a dense typed range (``v - lo``),
    the sorted values for a sparse typed one (position by search), None
    for a string column (its dictionary code)."""

    base: Any
    lower_tab: jax.Array  # the build row (-1: none) where the index is unique
    cnt_tab: Optional[jax.Array]  # None where the index is unique
    emit_ok: bool  # depth 2 allowed: unique, no hole, dense enough
    key_ref: Any  # the probe dictionary composed over (its id is the cache key)
    walks: int  # full-length gathers one probe of this entry dispatches
    col_tabs: Dict[str, Tuple[jax.Array, jax.Array]]  # name -> (source storage, table)

    @property
    def size(self) -> int:
        return int(self.lower_tab.shape[0])


def _searchsorted_rounds(n: int) -> int:
    """Gather rounds of one ``jnp.searchsorted`` over *n* sorted entries."""
    return max(int(n).bit_length(), 1)


def _translation_walks(pc, ic) -> Tuple[int, str]:
    """(full-length gathers of one staged ``pc.renumbered_to_col(ic)``,
    the tier that runs them: ``codes`` for a string column, else the
    typed state's ``dense`` | ``sorted``)."""
    if pc.kind != "int":
        return (1 if pc.dict_size else 0), "codes"
    kind, _, table = pc.translation_state_to(ic)  # cached on ic
    if kind == "dense":
        return 1, kind
    n = int(table.shape[0])
    return (_searchsorted_rounds(n) + 2 if n else 0), kind


def _universe_slot(storage, base, size):
    """(slot clipped into the universe, row has a slot) — traceable; the
    per-row half of the translation kernels with the table read left
    out.  Pads and absent cells have no slot.  *base* as in
    :class:`_Composed`."""
    if base is None:
        return jnp.clip(storage, 0, size - 1), storage >= 0
    is_pad = storage == jnp.int32(PAD_VALUE)
    if base.ndim == 0:
        # pads masked BEFORE the subtraction (``_translate_dense_kernel``)
        idx = jnp.where(is_pad, base, storage) - base
        ok = (idx >= 0) & (idx < size) & ~is_pad
        return jnp.clip(idx, 0, size - 1), ok
    pos = jnp.minimum(jnp.searchsorted(base, storage), size - 1).astype(jnp.int32)
    return pos, (jnp.take(base, pos, axis=0) == storage) & ~is_pad


@register_kernel("join.compose_probe")
def _compose_probe_kernel(trans, cum, shift, range_size):
    """The staged chain over the UNIVERSE (table size, set-up only):
    ``_pack_qk_kernel`` of one column, then ``direct_probe_parts``.
    Returns (lower_tab, cnt_tab, rid_tab, [max count, min count])."""
    qk = jnp.where(trans >= 0, jnp.maximum(trans, 0).astype(jnp.int32) << shift, -1)
    lower, counts = direct_probe_parts(cum, qk, range_size)
    rid = jnp.where(counts > 0, lower, -1)
    return lower, counts, rid, jnp.stack([jnp.max(counts), jnp.min(counts)])


@register_kernel("join.probe_composed", static_argnames=("vmem",))
def _probe_composed_kernel(storage, base, lower_tab, cnt_tab, vmem=False):  # analysis: allow[JIT001] retrace is per slot rule (three: the rank of ``base``), per table arity (two) and per gather form (``vmem``), not per data length
    """Depth 1: a row's (lower, counts) from the composed tables — one
    walk where the index is unique (*cnt_tab* None; ``lower`` of an
    unmatched row then reads 0, not the insertion point: no consumer
    reads it), two otherwise.  *vmem*: ``vmem_gather_selected``'s answer
    for the tables (``ops/gather.py``)."""
    slot, ok = _universe_slot(storage, base, lower_tab.shape[0])
    if cnt_tab is None:
        (rid,) = take_small((lower_tab,), slot, vmem=vmem)
        rid = jnp.where(ok, rid, -1)
        return jnp.maximum(rid, 0), (rid >= 0).astype(jnp.int32)
    lower, counts = take_small((lower_tab, cnt_tab), slot, vmem=vmem)
    return jnp.where(ok, lower, 0), jnp.where(ok, counts, 0)


@register_kernel("join.probe_composed_range")
def _probe_range_kernel(storage, base, size):  # analysis: allow[JIT001] retrace is per slot rule (two: a dense range or a code), not per data length
    """Depth 2: (slot, counts) by a range test alone — every slot of the
    universe holds exactly one build row, so a row matched when it has
    a slot."""
    slot, ok = _universe_slot(storage, base, size)
    return slot, ok.astype(jnp.int32)


def device_index_static_info(index):
    """Static shape of an index's device copy, for the plan verifier:
    ``(column -> lane kind, key column tuple, supported, meta)`` — or
    ``None`` when the index carries no device table (the executor then
    raises ``UnsupportedPlan`` and the chain falls back to the host
    path).  ``meta`` feeds the verifier's placement domain:

    * ``placement`` — where the packed key array lives (a
      :class:`~csvplus_tpu.analysis.schema.Placement`; unknown on fakes
      that carry no packed arrays);
    * ``packed_keys`` — build-side key count (``None`` when unknown);
    * ``partition_min_keys`` — the probe tier threshold, read through
      the live class so test overrides flow into the model.

    Reads only metadata the :class:`DeviceIndex` already holds; never
    touches device arrays, so verification stays O(plan), not O(rows).
    """
    dev = getattr(index, "device_table", None)
    if dev is None:
        return None
    if not getattr(dev, "supported", False):
        # an unsupported device copy may hold no packed table at all —
        # report the flag without assuming any further structure
        return ({}, (), False, None)
    from ..analysis.schema import placement_of_array

    packed = getattr(dev, "packed_i32", None)
    if packed is None:
        packed = getattr(dev, "packed_hi", None)
    meta = {
        "placement": placement_of_array(packed),
        "packed_keys": int(packed.shape[0]) if packed is not None else None,
        "partition_min_keys": int(
            getattr(dev, "PARTITION_MIN_KEYS", DeviceIndex.PARTITION_MIN_KEYS)
        ),
    }
    return (
        {n: c.kind for n, c in dev.table.columns.items()},
        tuple(dev.key_columns),
        True,
        meta,
    )


class DeviceBounds:
    """One lookup batch's bounds, left on the device.

    Opaque handle between :meth:`DeviceIndex.point_bounds_many` and
    ``IndexImpl.rows_for_bounds`` (the precedent: ``storage/lsm.py``'s
    ``MultiBounds``): *res* is the search's raw ``int32[2, B]`` answer
    (``lower`` over ``upper``; B the batch length's power-of-two bucket)
    and feeds the rows' gather un-read; the masks the host applies to
    read bounds — a value the dictionary lacks, the one-past-top probe
    of a 31-bit universe — wait in *ok* / *over* for :meth:`settle`.
    Iterates as the batch's ``(lower, upper)`` pairs once settled."""

    __slots__ = ("res", "ok", "over", "n", "lower", "upper")

    def __init__(self, res: jax.Array, ok: np.ndarray, over: np.ndarray, n: int):
        self.res, self.ok, self.over, self.n = res, ok, over, n
        self.lower = self.upper = None

    def settle(self, head: np.ndarray) -> None:
        """*head*: the host copy of ``res`` that came back with the rows."""
        m = self.ok.shape[0]
        upper = np.where(self.over, self.n, head[1, :m])
        self.lower = np.where(self.ok, head[0, :m], 0).astype(np.int64)
        self.upper = np.where(self.ok, upper, 0).astype(np.int64)

    def __iter__(self):
        return zip(self.lower.tolist(), self.upper.tolist())


@dataclass
class DeviceIndex:
    """Columnar build side of a join: table + packed sorted keys."""

    table: DeviceTable
    key_columns: List[str]
    packed_i32: Optional[jax.Array]  # int32[n] sorted, device (narrow keys)
    packed_i64: Optional[np.ndarray]  # int64[n] sorted, host (wide keys)
    shifts: Optional[List[int]]  # bit offset per key column
    bits: Optional[List[int]] = None  # bit width per key column
    packed_hi: Optional[jax.Array] = None  # wide keys: 31-bit hi lane, device
    packed_lo: Optional[jax.Array] = None  # wide keys: 31-bit lo lane, device
    direct_bits: Optional[int] = None  # packed-key universe bits (direct tier)

    # Packed-key universes up to 2^DIRECT_MAX_BITS get the dictionary-
    # direct probe table (2^23+1 int32 = 32MB of HBM at the cap); larger
    # universes binary-search the sorted keys as before.
    DIRECT_MAX_BITS: ClassVar[int] = env_int("CSVPLUS_DIRECT_PROBE_MAX_BITS", 23)

    # Build sides with at least this many keys probe via the range-
    # partitioned lax.all_to_all path (parallel/pjoin.py) instead of
    # replicating onto every shard; below it, broadcast wins.  ClassVar:
    # NOT a dataclass field, so tests/operators can override on the class.
    PARTITION_MIN_KEYS: ClassVar[int] = env_int("CSVPLUS_PARTITION_MIN_KEYS", 4_000_000)

    # Point lookups (find/sub_index/has) mirror the sorted key array to
    # host once, up to this many keys (64MB), and binary-search there —
    # the reference's own O(log n) host search (csvplus.go:881-887) —
    # instead of paying a device round trip per lookup.
    POINT_MIRROR_MAX_KEYS: ClassVar[int] = env_int(
        "CSVPLUS_POINT_MIRROR_MAX_KEYS", 16_000_000
    )

    @classmethod
    def build(cls, table: DeviceTable, key_columns: Sequence[str]) -> "DeviceIndex":
        with telemetry.stage("index:pack", table.nrows) as st:
            dev = cls._build(table, list(key_columns))
            # wide keys mirror every key code to a host int64 (_build)
            mirrored = 0 if dev.packed_i64 is None else table.nrows * len(dev.key_columns)
            st.update(
                rows=table.nrows, keys=len(dev.key_columns), row_gathers=0,
                host_sync_elements=mirrored,
            )
            telemetry.barrier([dev.packed_i32, dev.packed_hi, dev.packed_lo])
        return dev

    @classmethod
    def _build(cls, table: DeviceTable, key_columns: List[str]) -> "DeviceIndex":
        cols = [table.columns[c] for c in key_columns]
        for c in cols:
            # packed keys assume code order == value order and one code
            # per value; deferred-union lane dictionaries settle here
            c._ensure_sorted_lanes()
        bits = [_bits_for(c.dict_size) for c in cols]
        total = sum(bits)
        if total > 62:
            return cls(table, key_columns, None, None, None)

        shifts: List[int] = []
        acc = 0
        for b in reversed(bits):
            shifts.insert(0, acc)
            acc += b

        if total <= 31:
            # one fused pack kernel (shared with the probe side); build
            # codes are never negative so the kernel's miss-masking is
            # the identity here
            key = _pack_qk_kernel(
                tuple(c.codes for c in cols), tuple(shifts)
            )
            direct_bits = total if total <= cls.DIRECT_MAX_BITS else None
            return cls(
                table, key_columns, key, None, shifts, bits, direct_bits=direct_bits
            )

        # wide keys: dual 31-bit int32 lanes on device; the host int64
        # copy serves point_bounds and the partitioned-path preparation
        hi, lo = pack_lanes([c.codes for c in cols], shifts, bits)
        key64 = np.zeros(table.nrows, dtype=np.int64)
        for c, s in zip(cols, shifts):
            key64 |= np.asarray(c.codes).astype(np.int64) << s
        return cls(table, key_columns, None, key64, shifts, bits, hi, lo)

    def __post_init__(self):
        # serializes the lazy probe-side builds (_packed_host mirror,
        # _direct_cum table) under the serving tier's concurrent
        # callers.  Both builds are idempotent — a race would only waste
        # a duplicate O(n) transfer/cumsum, never corrupt — but at
        # serving rates the duplicate work is a real latency spike, so
        # first-touch is serialized like IndexImpl's lazy caches.
        self._aux_lock = threading.Lock()
        self._composed: Dict[tuple, _Composed] = {}
        self._compositions = 0  # table-size compositions run (set-up work)
        # set by create_unique_index once its adjacent-duplicate check has
        # passed: a full-key hit is then exactly one row (_chains)
        self.unique = False

    @property
    def supported(self) -> bool:
        return self.shifts is not None

    @property
    def direct_cum(self) -> Optional[jax.Array]:
        """The dictionary-direct probe table (``cum[j]`` = build keys
        < j), built lazily on first probe — indexes used only for
        ``find``/``point_bounds`` never pay the scatter+cumsum or the
        up-to-32MB of HBM.  None when the universe exceeds
        ``DIRECT_MAX_BITS``."""
        if self.direct_bits is None:
            return None
        cum = getattr(self, "_direct_cum", None)
        if cum is None:
            with self._aux_lock:
                cum = getattr(self, "_direct_cum", None)
                if cum is None:
                    cum = self._direct_cum = _build_direct_cum(
                        self.packed_i32, self.direct_bits
                    )
        return cum

    def _packed_host_mirror(self) -> np.ndarray:
        """Host mirror of the sorted packed keys, built once under the
        lock (the point-lookup tiers' searchsorted target)."""
        host = getattr(self, "_packed_host", None)
        if host is None:
            with self._aux_lock:
                host = getattr(self, "_packed_host", None)
                if host is None:
                    host = self._packed_host = np.asarray(self.packed_i32)
        return host

    def _decode_packed(self, packed: np.ndarray) -> list:
        """Decode packed build keys back to their column values (the
        rendering the skew surfaces show operators): each key column's
        code is its bit field, decoded selectively through the column
        dictionary (string columns) or the typed lane dictionary (int
        columns) — only the sampled codes, never the full table.
        Single-column keys unwrap to the scalar, matching
        ``TelemetryPlane.offer_probes``' convention."""
        parts = []
        p64 = packed.astype(np.int64)
        for name, s, b in zip(self.key_columns, self.shifts, self.bits):
            codes = ((p64 >> s) & ((1 << b) - 1)).astype(np.int64)
            parts.append(self.table.columns[name].decode_codes(codes))
        if len(parts) == 1:
            return list(parts[0])
        return [tuple(vs) for vs in zip(*parts)]

    def offer_build_sample(self) -> None:
        """Once per index: a bounded strided sample of the SORTED packed
        build keys, decoded and offered into the process-global
        build-side skew sketch (``obs/joinskew.py``) — the evidence
        ``csvplus_skew_topk{side="build"}`` exports.  Sorted order makes
        the strided sample a share estimator: a key owning fraction f of
        the build rows owns ~f of the stride positions.  The once-guard
        is double-checked under the aux lock (serving-tier callers race
        here); after the first call this is one attribute read."""
        if getattr(self, "_skew_offered", False) or not self.supported:
            return
        with self._aux_lock:
            if getattr(self, "_skew_offered", False):
                return
            self._skew_offered = True
        n = int(self.table.nrows)
        if n == 0:
            return
        step = max(1, -(-n // 4096))
        if self.packed_i64 is not None:
            sample = self.packed_i64[::step]
        else:
            # EXPLICIT bounded transfer (<= 4096 elements), accounted
            # like the probe-side hot sample — transfer-guard safe
            sample = jax.device_get(self.packed_i32[::step])
            telemetry.count_sync(sample.size)
        vals, cnts = np.unique(sample, return_counts=True)
        from ..obs.joinskew import joinskew

        joinskew.offer_build(
            ",".join(self.key_columns), self._decode_packed(vals), cnts
        )

    def point_bounds(self, values: List[str]) -> Tuple[int, int]:
        """[lower, upper) range for one key-prefix probe — the device form
        of the reference's two binary searches (csvplus.go:881-887).

        Values are translated to codes via host dictionary lookups (a few
        binary searches over host arrays), then the packed key array is
        searched; only two scalars cross back from device.
        """
        if len(values) > len(self.key_columns):
            raise ValueError("too many columns in Index.find()")
        assert self.supported
        if not values:
            return 0, self.table.nrows
        qk = 0
        for v, name, s in zip(values, self.key_columns, self.shifts):
            code = self.table.columns[name].find_code(v)
            if code < 0:
                return 0, 0  # value not in the index at all
            qk |= code << s
        range_size = 1 << self.shifts[len(values) - 1]
        if self.packed_i32 is not None:
            # point lookups search a lazily-mirrored HOST copy of the
            # sorted key array: a one-time O(n) transfer, after which
            # every find is a microsecond numpy binary search instead of
            # a device dispatch+sync round trip per lookup.  Above the
            # size cap the mirror would cost more than it saves, so the
            # device searchsorted remains.
            if int(self.packed_i32.shape[0]) <= self.POINT_MIRROR_MAX_KEYS:
                host = self._packed_host_mirror()
                # keys must match the array dtype: a python-int key makes
                # numpy promote (copy) the whole array per lookup.  The
                # one-past-top probe qk + range_size can equal 2^31; it
                # then bounds nothing, so the upper is simply n.
                lower = int(host.searchsorted(np.int32(qk), side="left"))
                top = qk + range_size
                if top > np.iinfo(np.int32).max:
                    return lower, int(host.shape[0])
                upper = int(host.searchsorted(np.int32(top), side="left"))
                return lower, upper
            top = qk + range_size
            if top > np.iinfo(np.int32).max:
                # one-past-top probe of a 31-bit universe bounds nothing
                lower = jnp.searchsorted(
                    self.packed_i32, jnp.int32(qk), side="left"
                )
                return int(lower), int(self.packed_i32.shape[0])
            res = jnp.searchsorted(
                self.packed_i32,
                jnp.asarray([qk, top], dtype=jnp.int32),
                side="left",
            )
            res = np.asarray(res)
            return int(res[0]), int(res[1])
        lower = int(np.searchsorted(self.packed_i64, np.int64(qk), side="left"))
        upper = int(
            np.searchsorted(self.packed_i64, np.int64(qk + range_size), side="left")
        )
        return lower, upper

    def point_bounds_many(
        self, probes: Sequence[Sequence[str]], chain: bool = False
    ) -> "List[Tuple[int, int]] | DeviceBounds":
        """Batched :meth:`point_bounds`: one vectorized code translation
        per key column (``find_codes``) and ONE searchsorted pass per
        storage tier over all probes, instead of per-probe binary
        searches and device dispatches.  Semantics match a loop of
        single ``point_bounds`` calls exactly.

        *chain*: the caller hands the answer straight to
        ``DeviceTable.take_rows``.  Where a hit is then exactly the row
        at ``lower`` — a unique index past the mirror cap, every probe
        naming the full key — the search's answer stays on the device in
        a :class:`DeviceBounds` and nothing is read here; anywhere else,
        the list as ever.
        """
        assert self.supported
        self.offer_build_sample()
        m = len(probes)
        if m == 0:
            return []
        n = int(self.table.nrows)
        with tracer.span("serve:bounds:encode"):
            # the host half: probe strings -> dictionary codes -> packed keys
            karr = np.array([len(p) for p in probes], dtype=np.int64)
            if karr.size and int(karr.max()) > len(self.key_columns):
                raise ValueError("too many columns in Index.find()")
            qk = np.zeros(m, dtype=np.int64)
            ok = np.ones(m, dtype=bool)
            for j, (name, s) in enumerate(zip(self.key_columns, self.shifts)):
                col = self.table.columns[name]
                if int(karr.min()) > j:  # every probe has column j
                    codes = col.find_codes([p[j] for p in probes])
                    ok &= codes >= 0
                    qk |= np.where(codes >= 0, codes, 0) << s
                    continue
                sel = np.flatnonzero(karr > j)
                if sel.size == 0:
                    break
                codes = col.find_codes([probes[i][j] for i in sel])
                ok[sel] &= codes >= 0
                qk[sel] |= np.where(codes >= 0, codes, 0) << s
            shifts = np.array(self.shifts, dtype=np.int64)
            range_size = np.where(karr > 0, 1 << shifts[np.maximum(karr, 1) - 1], 0)
            top = qk + range_size
        with tracer.span("serve:bounds:search") as span:
            # upload, searchsorted, read back (or the host mirror's numpy)
            if self.packed_i32 is not None:
                over = top > np.iinfo(np.int32).max  # one-past-top: upper = n
                if int(self.packed_i32.shape[0]) <= self.POINT_MIRROR_MAX_KEYS:
                    host = self._packed_host_mirror()
                    lower = host.searchsorted(qk.astype(np.int32), side="left")
                    upper = host.searchsorted(
                        np.where(over, 0, top).astype(np.int32), side="left"
                    )
                else:
                    # padded to the batch length's power-of-two bucket (a
                    # pad query searches key 0 and is dropped by position),
                    # so a length compiles once a bucket
                    qt = np.zeros((2, 1 << max(m - 1, 0).bit_length()), dtype=np.int32)
                    qt[0, :m], qt[1, :m] = qk, np.where(over, 0, top)
                    res = _bounds_search_kernel(self.packed_i32, qt)
                    if chain and int(karr.min()) == len(self.key_columns) and self._chains():
                        # a hit is the row at ``lower``: the rows' gather
                        # takes the answer where it lies, and the one read
                        # after it brings the bounds along
                        span["host_syncs"] = 0
                        return DeviceBounds(res, ok, over, n)
                    res = np.asarray(res)
                    telemetry.count_sync(2 * m)
                    span["host_syncs"], span["elements"] = 1, 2 * m
                    lower, upper = res[0, :m], res[1, :m]
                upper = np.where(over, n, upper)
            else:
                lower = np.searchsorted(self.packed_i64, qk, side="left")
                upper = np.searchsorted(self.packed_i64, top, side="left")
        lower = np.where(ok, lower, 0).astype(np.int64)
        upper = np.where(ok, upper, 0).astype(np.int64)
        empty = karr == 0  # empty prefix bounds the whole table
        lower = np.where(empty, 0, lower)
        upper = np.where(empty, n, upper)
        # tolist() converts to native ints in C — a python int() pair per
        # probe costs more than the searchsorted itself at 10K probes
        return list(zip(lower.tolist(), upper.tolist()))

    def _chains(self) -> bool:
        """Whether a full-key batch's bounds may stay on the device: the
        index is unique (so a hit is one row, the one at ``lower``) and
        the search's answer can enter one program with every column
        (one device set: a fact of the build, read once)."""
        if not self.unique:
            return False
        got = getattr(self, "_one_device_set", None)
        if got is None:
            lanes = [c.storage for c in self.table.columns.values()]
            got = self._one_device_set = same_placement([self.packed_i32] + lanes)
        return got

    def _partitioned_for(self, qk_sh):
        """Range-partitioned build keys for *qk_sh*'s mesh, cached per
        device set (mirrors _keys_for's replication cache — the O(n)
        host partitioning and device upload happen once, not per probe)."""
        cached = getattr(self, "_part_cache", None)
        if cached is not None and cached[0] == qk_sh.device_set:
            return cached[1]
        from ..parallel.pjoin import prepare_partitioned

        keys = (
            np.asarray(self.packed_i32)
            if self.packed_i32 is not None
            else self.packed_i64
        )
        prepared = prepare_partitioned(qk_sh.mesh, keys)
        self._part_cache = (qk_sh.device_set, prepared)
        return prepared

    def _keys_for(self, qk: jax.Array) -> jax.Array:
        """The packed int32 key array, replicated onto the probe's mesh
        when the probe side is row-sharded (broadcast-join layout: the
        small build side goes everywhere, the probe stays put — no
        collectives in the probe itself)."""
        return self._lanes_for(qk, "packed_i32")

    def _lanes_for(self, qk: jax.Array, attr: str) -> jax.Array:
        """A packed key array (``packed_i32``/``packed_hi``/``packed_lo``),
        replicated onto the probe's mesh when the probe is row-sharded;
        the replicated copy is cached per (attribute, device set)."""
        keys = getattr(self, attr)
        qk_sh = getattr(qk, "sharding", None)
        if qk_sh is None or len(qk_sh.device_set) <= 1:
            return keys
        keys_sh = getattr(keys, "sharding", None)
        if keys_sh is not None and keys_sh.device_set == qk_sh.device_set:
            return keys
        cache = getattr(self, "_lane_repl", None)
        if cache is None:
            cache = self._lane_repl = {}
        hit = cache.get(attr)
        if hit is not None and hit[0] == qk_sh.device_set:
            return hit[1]
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = jax.device_put(keys, NamedSharding(qk_sh.mesh, P()))
        cache[attr] = (qk_sh.device_set, repl)
        return repl

    def _translated(self, probe_cols: List[StringColumn], n_key_cols: int):
        """(per-column probe codes translated into the build
        dictionaries, the full-length gathers that took, the tier of
        each column's translation joined by ``,``)."""
        out = []
        walks = 0
        tiers = []
        for pc, ic_name in zip(probe_cols, self.key_columns[:n_key_cols]):
            ic = self.table.columns[ic_name]
            out.append(pc.renumbered_to_col(ic))
            n, tier = _translation_walks(pc, ic)
            walks += n
            tiers.append(tier)
        return out, walks, ",".join(tiers)

    def _composed_for(self, pc, nrows: int) -> "Optional[_Composed]":
        """The composed tables for probing this index by the one column
        *pc* (full key or prefix), or None where the staged path stands:
        no direct tier, a stream or tables not whole on one device, or a
        universe over a quarter of the stream's rows.  Composed once per
        probe prefix (typed column) or probe dictionary (string column)
        and kept beside ``_direct_cum`` — the index is immutable, so
        nothing invalidates them."""
        if self.packed_i32 is None or self.direct_bits is None:
            return None
        if not whole_device(pc.storage):
            return None
        ic = self.table.columns[self.key_columns[0]]
        if pc.kind == "int":
            state = pc.translation_state_to(ic)
            key_ref, key = None, ("int", pc.prefix)
            size = int(state[2].shape[0])
        else:
            pc._ensure_sorted_lanes()  # the dictionary's final identity
            state = None
            st = pc._lane_state
            key_ref = st.lanes if st is not None else pc._dictionary
            key = ("str", id(key_ref))
            size = pc.dict_size
        if size == 0 or size * _COMPOSE_ROWS_PER_SLOT > nrows:
            return None
        cum = self.direct_cum  # takes _aux_lock itself on first touch
        if not same_placement((pc.storage, cum)):
            return None
        with self._aux_lock:
            cache = self._composed
            hit = cache.get(key)
            if hit is None or hit.key_ref is not key_ref:
                hit = self._compose(pc, ic, state, cum, key_ref)
                cache.pop(key, None)
                while len(cache) >= _COMPOSED_KEPT:
                    cache.pop(next(iter(cache)))
                cache[key] = hit
        return hit

    def _compose(self, pc, ic, state, cum, key_ref) -> "_Composed":
        """Build one :class:`_Composed` (caller holds ``_aux_lock``):
        one table-size kernel and ONE two-scalar read (the largest and
        smallest count over the universe decide ``rid_tab`` and depth
        2).  Set-up: runs in a plan's first execution, never after."""
        kind = "codes" if state is None else state[0]
        if kind == "codes":
            base, trans = None, pc.code_translation_to(ic)
        elif kind == "dense":
            base, trans = jnp.int32(state[1]), state[2]
        else:
            base, trans = state[1], state[2]
        size = int(trans.shape[0])
        with telemetry.stage("join:compose", size) as out:
            shift = self.shifts[0]
            lower, counts, rid, stats = _compose_probe_kernel(
                trans, cum, jnp.int32(shift), jnp.int32(1) << shift
            )
            most, least = (int(v) for v in np.asarray(stats))
            unique = most <= 1
            out["unique"] = unique
            out["holes"] = least < 1
        self._compositions += 1
        return _Composed(
            base,
            lower_tab=rid if unique else lower,
            cnt_tab=None if unique else counts,
            emit_ok=unique
            and least >= 1
            and kind != "sorted"
            and size <= _EMIT_SLOTS_PER_BUILD_ROW * self.table.nrows,
            key_ref=key_ref,
            walks=(1 if unique else 2)
            + (_searchsorted_rounds(size) + 1 if kind == "sorted" else 0),
            col_tabs={},
        )

    def composed_columns(self, entry: "_Composed", names: Sequence[str]):
        """``column.storage[rid_tab]`` per named build column over
        *entry*'s universe (depth 2's emit tables), composed once per
        column storage (a column that settles its lane dictionary later
        swaps its storage and is composed again)."""
        with self._aux_lock:
            stale = [
                n
                for n in names
                if n not in entry.col_tabs
                or entry.col_tabs[n][0] is not self.table.columns[n].storage
            ]
            if stale:
                srcs = tuple(self.table.columns[n].storage for n in stale)
                with telemetry.stage("join:compose", entry.size) as out:
                    out["columns"] = len(stale)
                    (got,) = _gather_cols((srcs,), (entry.lower_tab,), vmem=(False,))
                entry.col_tabs.update(zip(stale, zip(srcs, got)))
                self._compositions += 1
            return tuple(entry.col_tabs[n][1] for n in names)

    def probe_slots(self, pc, nrows: int):
        """Depth 2 of the composed probe by the one column *pc*:
        ``(entry, slots, counts)`` where the index is unique and the
        universe has no hole — ``counts`` by a range test, ``slots``
        addressing :meth:`composed_columns` (and, through
        ``_slots_to_rows``, the build rows) — else None: :meth:`probe`
        answers."""
        entry = self._composed_for(pc, nrows)
        if entry is None or not entry.emit_ok:
            return None
        self.offer_build_sample()
        with telemetry.stage("join:probe", nrows) as out:
            out["tier"] = "direct-composed"
            out["depth"] = 2
            out["row_gathers"] = out["vmem_gathers"] = 0
            ans = _probe_range_kernel(pc.storage, entry.base, jnp.int32(entry.size))
            telemetry.barrier(ans)
        return (entry,) + ans

    def probe(
        self, probe_cols: List[StringColumn], nrows: int,
        part_info: "dict | None" = None,
    ) -> "Tuple[jax.Array, jax.Array] | Tuple[np.ndarray, np.ndarray]":
        """(lower, counts) per probe row.

        EVERY tier answers with DEVICE arrays so the fan-out expansion
        and gathers consume them without an O(n) host sync — including
        the partitioned (multi-chip) tier, whose padding, hot-key merge
        and overflow detection run on the mesh with O(1) scalar syncs
        (``parallel/pjoin.py`` device orchestration).

        Fewer probe columns than key columns = a prefix probe matching the
        whole key range under the prefix.

        A probe by ONE column on the direct tier, stream and tables whole
        on one device, whose universe is at most a quarter of *nrows*
        reads its answer from tables composed over that universe (tier
        ``direct-composed``, see ``_composed_for``) instead of walking
        the rows through translate, pack and the two ``cum`` gathers;
        every other shape runs the staged kernels below.

        *part_info* is the multiway join's shared partitioned-tier state
        (``multiway_join`` threads ONE dict through every dimension's
        probe): each dimension's settled exchange capacity and
        skew-routing evidence accumulate into the same dict — see
        ``partitioned_probe_device``'s *info* contract.  Every probe
        counts its own exchange; nothing of one seeds the next.
        """
        assert self.supported
        self.offer_build_sample()
        k = len(probe_cols)
        entry = self._composed_for(probe_cols[0], nrows) if k == 1 else None
        if entry is not None:
            with telemetry.stage("join:probe", nrows) as out:
                out["tier"] = "direct-composed"
                out["depth"] = 1
                out["row_gathers"] = entry.walks
                tabs = (entry.lower_tab,) + (() if entry.cnt_tab is None else (entry.cnt_tab,))
                vmem = vmem_gather_selected(tabs, probe_cols[0].storage)
                out["vmem_gathers"] = len(tabs) if vmem else 0
                ans = _probe_composed_kernel(
                    probe_cols[0].storage, entry.base, entry.lower_tab, entry.cnt_tab,
                    vmem=vmem,  # analysis: allow[RETRACE002] read off shapes and placement: three values
                )
                telemetry.barrier(ans)
            return ans
        with telemetry.stage("join:translate", nrows) as out:
            codes, out["row_gathers"], out["tier"] = self._translated(probe_cols, k)
            telemetry.barrier(codes)
        range_shift = self.shifts[k - 1] if k else 0

        if self.packed_i32 is not None:
            with telemetry.stage("join:pack", nrows):
                if codes:
                    # one fused kernel per execution: the eager
                    # mask/shift/or loop cost ~94ms per key column at 10M
                    # rows vs 8ms fused (r6 warm-join recovery); shifts
                    # are static so the trace count is bounded by
                    # distinct (key-width, shape) pairs
                    qk = _pack_qk_kernel(
                        tuple(codes), tuple(self.shifts[: len(codes)])
                    )
                else:
                    qk = jnp.zeros(nrows, dtype=jnp.int32)
                telemetry.barrier(qk)

            # large build sides probed by a MESH-SHARDED stream: don't
            # replicate — range-partition the key array across the
            # stream's own mesh (respecting device pinning) and shuffle
            # probes over ICI all_to_all.  Full-width probes only; prefix
            # probes and unsharded streams broadcast.
            qk_sh = getattr(qk, "sharding", None)
            from ..parallel.pjoin import partition_tier_selected

            if partition_tier_selected(
                int(self.packed_i32.shape[0]),
                full_width=k == len(self.key_columns),
                stream_sharded=qk_sh is not None
                and len(qk_sh.device_set) > 1
                and hasattr(qk_sh, "mesh"),
                min_keys=self.PARTITION_MIN_KEYS,
            ):
                from ..parallel.pjoin import partitioned_probe_device

                # device-resident end to end: the probe keys, exchange,
                # hot-key merge and answers never leave the mesh; the
                # only host syncs are a bounded hot-key sample with the
                # exchange's route count beside it (and one O(1) flag per
                # attempt the count does not guarantee)
                return partitioned_probe_device(
                    qk_sh.mesh, qk, self._partitioned_for(qk_sh),
                    label=",".join(self.key_columns),
                    info=part_info,
                )

            if self.direct_cum is not None:
                cum = self._lanes_for(qk, "direct_cum")
                with telemetry.stage("join:probe", nrows) as out:
                    out["tier"] = "direct"
                    out["row_gathers"], out["vmem_gathers"] = 2, 0
                    ans = _probe_kernel_direct(
                        cum, qk, jnp.int32(1) << range_shift
                    )
                    telemetry.barrier(ans)
                return ans
            keys = self._keys_for(qk)
            # stays on device: fan-out expansion and gathers consume these
            # directly, so no O(n) host sync happens in the probe
            with telemetry.stage("join:probe", nrows) as out:
                out["tier"] = "broadcast-i32"
                out["row_gathers"] = 2 * _searchsorted_rounds(keys.shape[0])
                out["vmem_gathers"] = 0
                ans = _probe_kernel_i32(keys, qk, jnp.int32(1) << range_shift)
                telemetry.barrier(ans)
            return ans

        # wide keys: dual 31-bit lane probe, fully on device (no x64)
        ok = jnp.ones(nrows, dtype=bool)
        clamped = []
        for c in codes:
            ok = ok & (c >= 0)
            clamped.append(jnp.where(c >= 0, c, 0))
        q_hi, q_lo = pack_lanes(clamped, self.shifts, self.bits)

        # large build sides probed by a mesh-sharded stream go through
        # the partitioned all_to_all path, same policy as the i32 tier
        qk_sh = getattr(q_hi, "sharding", None)
        from ..parallel.pjoin import partition_tier_selected

        if partition_tier_selected(
            int(self.packed_i64.shape[0]),
            full_width=k == len(self.key_columns),
            stream_sharded=qk_sh is not None
            and len(qk_sh.device_set) > 1
            and hasattr(qk_sh, "mesh"),
            min_keys=self.PARTITION_MIN_KEYS,
        ):
            from ..parallel.pjoin import partitioned_probe_device_wide

            # device-resident: invalid probes carry (-1, -1) lanes; no
            # O(n) host sync (the lanes stay on the mesh end to end)
            q_hi_m = jnp.where(ok, q_hi, jnp.int32(-1))
            q_lo_m = jnp.where(ok, q_lo, jnp.int32(-1))
            return partitioned_probe_device_wide(
                qk_sh.mesh, q_hi_m, q_lo_m, self._partitioned_for(qk_sh),
                label=",".join(self.key_columns),
                info=part_info,
            )

        range_size = 1 << range_shift
        keys_hi = self._lanes_for(q_hi, "packed_hi")
        keys_lo = self._lanes_for(q_hi, "packed_lo")
        return _probe_kernel_i32pair(
            keys_hi,
            keys_lo,
            q_hi,
            q_lo,
            jnp.int32(range_size >> 31),
            jnp.int32(range_size & _MASK31),
            ok,
        )


@register_kernel("join.pack_qk", static_argnames=("shifts",))
def _pack_qk_kernel(  # analysis: allow[JIT001] retrace is per join-key ARITY (bounded by the 31-bit pack budget), not per data length
    codes: Tuple[jax.Array, ...], shifts: Tuple[int, ...]
) -> jax.Array:
    """Packed int32 probe key from translated per-column codes; any
    negative code (miss -1 / pad -2) marks the whole row -1."""
    ok = jnp.ones(codes[0].shape, dtype=bool)
    qk = jnp.zeros(codes[0].shape, dtype=jnp.int32)
    for c, s in zip(codes, shifts):
        ok = ok & (c >= 0)
        qk = qk | (jnp.where(c >= 0, c, 0).astype(jnp.int32) << s)
    return jnp.where(ok, qk, jnp.int32(-1))


def expand_matches(
    lower: np.ndarray, counts: np.ndarray
) -> Tuple[np.ndarray, np.ndarray]:
    """Fan-out expansion on host (the partitioned tier, whose probe
    answers are numpy): (probe row ids, build row ids) per match."""
    total = int(counts.sum())
    probe_ids = np.repeat(np.arange(counts.shape[0], dtype=np.int64), counts)
    starts = np.repeat(lower.astype(np.int64), counts)
    # within-group offset: position among this probe row's matches
    ends = np.cumsum(counts)
    group_base = np.repeat(ends - counts, counts)
    offsets = np.arange(total, dtype=np.int64) - group_base
    build_ids = starts + offsets
    return probe_ids, build_ids


@register_kernel("join.expand", static_argnames=("padded_total", "vmem"))
def _expand_kernel(lower, counts, padded_total: int, vmem=False):
    """Device fan-out expansion with a static output size: an exclusive
    prefix sum over counts locates each probe row's output segment, a
    scatter of segment markers + running max inverts it per output slot
    (O(n), unlike a searchsorted inversion whose ~log n sequential
    gather rounds dominate at the 100M-row scale).  Positions past the
    true total produce clipped junk the caller slices off.  *vmem*:
    ``vmem_gather_selected``'s answer for the two probe-length tables
    every output slot reads (``ops/gather.py``)."""
    counts = counts.astype(jnp.int32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    # mark each non-empty segment's first output slot with the probe row
    # id; empty segments scatter out of bounds and drop.  Segment starts
    # are strictly increasing over non-empty segments, so no collisions.
    ids = jnp.arange(counts.shape[0], dtype=jnp.int32)
    mark_pos = jnp.where(counts > 0, starts, padded_total)
    seg = jnp.zeros(padded_total, dtype=jnp.int32)
    seg = seg.at[mark_pos].max(ids, mode="drop")
    probe_ids = jax.lax.cummax(seg)  # fill each segment with its probe id
    out_pos = jnp.arange(padded_total, dtype=jnp.int32)
    # each slot's segment start and first build row: two tables, one index
    group_base, first = take_small((starts, lower.astype(jnp.int32)), probe_ids, vmem=vmem)
    build_ids = first + (out_pos - group_base)
    return probe_ids, build_ids


def expand_matches_device(
    lower, counts, total: "int | None" = None, _exp: "dict | None" = None
) -> Tuple[jax.Array, jax.Array]:
    """Fan-out expansion on device; only the total (sizing the static
    output shape) crosses to host — SURVEY §7's count -> prefix-sum ->
    scatter.  The kernel compiles at the next power of two, so repeated
    joins with varying totals hit O(log n) distinct shapes, not one
    compilation per total.  A caller that already synced the total (e.g.
    join_tables' probe stats) passes it to skip the round trip.  The
    scan's two segment reads go through the VMEM kernel where the probe
    is short enough (``vmem_gather_selected``, read off the inputs: the
    index is born inside the program, where they are); the reads it
    served are counted into *_exp*'s ``vmem_gathers``."""
    if counts.shape[0] == 0:  # empty probe: nothing to expand
        empty = jnp.zeros(0, dtype=jnp.int32)
        return empty, empty
    if total is None:
        total = int(jnp.sum(counts))  # the one O(1) sync
    padded = 1 << max(total - 1, 0).bit_length()
    lower, counts = jnp.asarray(lower), jnp.asarray(counts)
    vmem = vmem_gather_selected((lower, counts))
    if _exp is not None:
        _exp["vmem_gathers"] = 2 if vmem else 0
    padded_ids = _expand_kernel(lower, counts, padded, vmem=vmem)  # analysis: allow[RETRACE002] read off shapes and placement: three values
    # the cut lowers once per total: a slice compiles in milliseconds, and
    # the expansion, which does not, never sees the total
    return _expand_head_kernel(padded_ids, total=total)


def _checked_probe_cols(
    stream: DeviceTable, columns: Sequence[str]
) -> List[StringColumn]:
    """Resolve the stream's key columns, with host-parity errors.

    The host path raises ``missing column`` — wrapped with the row number —
    either when the column is absent from the whole stream or when an
    individual (heterogeneous) row lacks the cell (csvplus.go:556,599 via
    SelectValues).  Columnar absent cells are code -1.  The presence
    check is one cached scalar per column (``has_absent``); the O(n)
    scan happens only on the error path.
    """
    from ..errors import DataSourceError
    from ..row import MissingColumnError

    out = []
    for c in columns:
        if c not in stream.columns:
            raise MissingColumnError(c)
        col = stream.columns[c]
        if col.has_absent:
            bad = jnp.asarray(col.codes) < 0
            raise DataSourceError(int(jnp.argmax(bad)), MissingColumnError(c))
        out.append(col)
    return out


def _aligned_codes(dev_index: "DeviceIndex", name: str, codes, ids):
    """Build-side codes placed compatibly with the gather ids' devices.

    A mesh-sharded probe produces mesh-committed ids; the (small) build
    side is replicated onto that mesh — the broadcast-join layout — and
    cached per device set on the index, like ``_keys_for``.
    """
    ids_sh = getattr(ids, "sharding", None)
    codes_sh = getattr(codes, "sharding", None)
    if ids_sh is None or codes_sh is None:
        return codes
    if codes_sh.device_set == ids_sh.device_set or len(ids_sh.device_set) <= 1:
        return codes
    cache = getattr(dev_index, "_attr_repl_cache", None)
    if cache is None:
        cache = dev_index._attr_repl_cache = {}
    hit = cache.get(name)
    if hit is not None and hit[0] == ids_sh.device_set:
        return hit[1]
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = getattr(ids_sh, "mesh", None)
    if mesh is None:
        # opaque (GSPMD) sharding on the ids (e.g. a jit output whose
        # length doesn't divide the mesh): replicate onto an ad-hoc 1-D
        # mesh over the same device set — eager ops can't mix arrays
        # committed to different device sets
        devs = sorted(ids_sh.device_set, key=lambda d: d.id)
        mesh = Mesh(np.array(devs), ("r",))
    repl = jax.device_put(codes, NamedSharding(mesh, P()))
    cache[name] = (ids_sh.device_set, repl)
    return repl


def _probe_dim(dev_index: "DeviceIndex", probe_cols, nrows: int, part_info=None):
    """One dimension's probe answer ``(lower, counts, entry)``.  With
    *entry* (depth 2 of the composed probe) ``lower`` holds SLOTS of the
    entry's universe: they address ``dev_index.composed_columns`` as
    they stand, and build rows through :func:`_slots_to_rows`."""
    got = dev_index.probe_slots(probe_cols[0], nrows) if len(probe_cols) == 1 else None
    if got is not None:
        entry, slots, counts = got
        return slots, counts, entry
    lower, counts = dev_index.probe(probe_cols, nrows, part_info=part_info)
    return lower, counts, None


def _slots_to_rows(lowers, entries):
    """Depth-2 slots -> build rows (read where the row matched only),
    for the joins that did not match every row once: only the
    all-matched emit reads slots."""
    return tuple(
        lo if e is None else _gather_take(e.lower_tab, lo)
        for lo, e in zip(lowers, entries)
    )


def _slot_gathers(entries) -> int:
    """The gathers :func:`_slots_to_rows` dispatches: one per depth-2
    dimension."""
    return sum(e is not None for e in entries)


def _kept_build_names(dev_index: "DeviceIndex", stream_cols) -> List[str]:
    """The build columns the merge can read.  One whose name is on the
    stream, where the stream's column has no absent cell, is never read
    (``merge_with_fallback`` hands back the stream's column on its one
    cached scalar) — so it is not gathered; :func:`_merge_fold` keeps
    its place in the column order."""
    return [
        n
        for n in dev_index.table.columns
        if not (n in stream_cols and not stream_cols[n].has_absent)
    ]


def _build_sources(dev_index: "DeviceIndex", names, ids, entry):
    """The arrays *ids* gather from, per kept build column: the composed
    column tables where *ids* are depth-2 slots, the build columns'
    storage otherwise.  Kind-agnostic storage (dictionary codes or typed
    value lanes), so a typed payload column is never demoted by the
    join."""
    if entry is not None:
        return dev_index.composed_columns(entry, names)
    return tuple(
        _aligned_codes(dev_index, n, dev_index.table.columns[n].storage, ids)
        for n in names
    )


def _stream_side(cols, names, gathered):
    """The stream's columns as the merge fold's first running result:
    as they stand when no row moved, else over the gathered storage."""
    if gathered is None:
        return dict(cols)
    return {n: cols[n].with_storage(g) for n, g in zip(names, gathered)}


def _merge_fold(cur, sides):
    """Fold the cascade's merge left to right over *sides* of
    ``(dev_index, kept names, gathered storages)``: level d inserts
    build side d's columns first (a column that was not gathered holds
    its place), then overlays the running result with stream-wins /
    absent-cell-fallback semantics — identical column order and values
    to the cascade (elementwise merges commute with the row gathers
    already applied)."""
    for dev_index, names, gathered in sides:
        new = dict.fromkeys(dev_index.table.columns)
        for name, g in zip(names, gathered):
            new[name] = dev_index.table.columns[name].with_storage(g)
        for name, col in cur.items():  # the running result wins on collision...
            if new.get(name) is not None:
                # ...but an absent cell keeps the build side's value
                col = merge_with_fallback(col, new[name])
            new[name] = col
        cur = new
    return cur


def _emit_and_merge(cols, specs, probe_ids, build_ids, entries, runs, sel, n_in: int, device) -> DeviceTable:
    """The tail the three join operators share: name the build columns
    the merge can read, find their sources, move every row through the
    one emit (``ops/gather.py``), fill ``join:merge`` from its record
    and fold the merge.  *cols*: the stream's columns at full length;
    *probe_ids* None: every stream row matched once in every dimension,
    so the stream's rows pass through; *entries*: per dimension its
    depth-2 entry where its *build_ids* are slots of the composed
    columns; *runs*: the binary fan-out's ``(lower, counts, total)``;
    *sel*: the fused join's selection (None: the stream IS *cols*),
    composed into the index once — ``take(take(S, sel), ids) == take(S,
    take(sel, ids))`` — so the stream's lanes move from full-length
    storage in one gather, never materialize-then-gather."""
    kept = [_kept_build_names(di, cols) for di, _ in specs]
    groups = [
        Lanes(_build_sources(di, names, ids, e), ids)
        for (di, _), names, ids, e in zip(specs, kept, build_ids, entries)
    ]
    if runs is not None:  # the fan-out is the binary join's: one build side
        groups[0] = groups[0]._replace(runs=runs)
    stream_names = list(cols)
    n_out = n_in if probe_ids is None else int(probe_ids.shape[0])
    with telemetry.stage("join:merge", n_in) as _mrg:
        idx = probe_ids
        if sel is not None:
            idx = sel if probe_ids is None else emit([Lanes((sel,), probe_ids)]).lanes[0][0]
        if idx is not None:  # else no row of the stream moves
            groups.append(Lanes(tuple(cols[n].storage for n in stream_names), idx))
        done = emit(groups)
        g_build = done.lanes[: len(specs)]
        g_stream = None if idx is None else done.lanes[-1]
        build_lanes, stream_lanes = sum(map(len, g_build)), len(g_stream or ())
        _mrg.update(
            row_gathers=build_lanes + stream_lanes, build_gathers=build_lanes, stream_gathers=stream_lanes,
            vmem_gathers=done.moved("vmem"), run_copies=done.moved("runs"), rows_out=n_out,
        )
        cur = _merge_fold(
            _stream_side(cols, stream_names, g_stream),
            [(di, names, g) for (di, _), names, g in zip(specs, kept, g_build)],
        )
        telemetry.barrier(tuple(c.storage for c in cur.values()))
    return DeviceTable(cur, n_out, device)


def join_tables(
    stream: DeviceTable, dev_index: "DeviceIndex", columns: Sequence[str]
) -> DeviceTable:
    """stream ⋈ index with the reference's merge semantics: result rows
    carry all columns from both sides; on a name collision the stream
    row's value wins, but only for cells the stream row actually has
    (csvplus.go:560, 571-583); stream order preserved, matches emitted in
    index-sorted order (csvplus.go:559)."""
    if stream.nrows == 0:
        # per-row key validation never fires on an empty stream
        # (csvplus.go:553-556): empty result, no error
        empty = np.empty(0, dtype=np.int64)
        out_cols = {
            name: col.gather(empty)
            for name, col in {**dev_index.table.columns, **stream.columns}.items()
        }
        return DeviceTable(out_cols, 0, stream.device)

    probe_cols = _checked_probe_cols(stream, columns)
    lower, counts, entry = _probe_dim(dev_index, probe_cols, stream.nrows)
    probe_ids = build_ids = runs = None
    with telemetry.stage("join:expand", stream.nrows) as _exp:
        _exp["vmem_gathers"] = 0  # the fan-out's two segment reads where the kernel serves them
        if isinstance(lower, jax.Array):
            # (total matches, max run length) in ONE host transfer; a
            # unique build side (max run 1 — the reference's flagship
            # shape) skips the O(n) fan-out expansion entirely
            total, maxc = (
                int(v) for v in np.asarray(_probe_stats(lower, counts))
            )
            telemetry.count_sync(2)  # THE blocking read of the stage
            _exp.update(tier="device", host_sync_elements=2)
            if maxc <= 1 and total == stream.nrows:
                # every stream row matched exactly once: identity on the
                # stream side (columns pass through ungathered, caches
                # intact), build rows addressed by the probe's lower
                # bounds (or, at depth 2, the composed columns by slot)
                build_ids = lower
                _exp.update(path="unique-identity", form="identity", padded=0, row_gathers=0)
            else:
                padded = 1 << max(total - 1, 0).bit_length() if total else 1
                if maxc <= 1:
                    # unique but partial: compact the selection without the
                    # expansion scan; pow2 padding bounds recompiles
                    probe_ids, (build_ids,) = _compact_unique_partial(
                        (lower,), (counts,), (entry,), padded
                    )
                    probe_ids, build_ids = probe_ids[:total], build_ids[:total]
                    _exp.update(
                        path="unique-partial", form=COMPACT_FORM,
                        row_gathers=_slot_gathers((entry,)),
                    )
                else:
                    (lower,) = _slots_to_rows((lower,), (entry,))
                    probe_ids, build_ids = expand_matches_device(lower, counts, total, _exp)
                    runs = (lower, counts, total)  # probe p: build rows lower[p] .. + counts[p]
                    _exp.update(
                        path="fan-out", form="prefix-scatter", probes=stream.nrows, max_run=maxc,
                        row_gathers=2 + _slot_gathers((entry,)),
                    )
                entry = None  # the ids are build rows now, not depth-2 slots
                _exp["padded"] = padded
        else:  # the partitioned (multi-chip) tier answers in numpy
            probe_ids, build_ids = expand_matches(lower, counts)
            total = len(probe_ids)
            _exp.update(
                path="host-expand", tier="host", form="numpy", padded=0,
                row_gathers=0, host_sync_elements=0,
            )
        _exp.update(rows_out=total, emitted=total)
        telemetry.barrier((probe_ids, build_ids))

    return _emit_and_merge(
        stream.columns, [(dev_index, columns)], probe_ids, (build_ids,), (entry,), runs, None,
        stream.nrows, stream.device,
    )


@register_kernel("join.probe_stats")
def _probe_stats(lower, counts):
    """(total matches, max run length) as one device pair — a single
    transfer decides the unique fast paths in :func:`join_tables`."""
    c = counts.astype(jnp.int32)
    return jnp.stack([jnp.sum(c), jnp.max(c) if c.shape[0] else jnp.int32(0)])


# -- single-pass multiway join (ISSUE 17) ----------------------------------
#
# A run of cascaded binary joins over the same stream materializes every
# intermediate table: at the 100M mesh tier the orders×customers
# intermediate alone dominates peak RSS, and every fact row is packed,
# exchanged and gathered once per cascade level.  ``multiway_join``
# replaces the run with ONE pass: every dimension index is probed over
# the ORIGINAL fact rows (a probe answer depends only on the key value,
# so probing the fact row equals probing the intermediate row that
# carries the same key), the cross-product fanout per fact row is
# expanded by one jitted cumsum/scatter kernel, and each dimension's
# build rows are addressed by mixed-radix decomposition of the
# within-row output offset — dimension 0 outermost, exactly the
# cascade's nested emission order.  Row order, column order and merge
# semantics are bitwise-identical to folding ``join_tables`` left to
# right; the rewriter only licenses the fusion when every later join's
# key columns are provably PRESENT on the stream BEFORE the run (then
# the cascade's per-level key checks and stream-wins merges cannot
# observe the intermediate at all — see analysis/rewrite.py).


def _fanout_products(counts):
    """(int32 counts tuple, per-row cross-product fanout) — traceable."""
    cs = tuple(c.astype(jnp.int32) for c in counts)
    prod = cs[0]
    for c in cs[1:]:
        prod = prod * c
    return cs, prod


@register_kernel("join.multiway_stats")
def _multiway_stats(counts):  # analysis: allow[JIT001] retrace is per join ARITY (number of build sides), not per data length
    """(total matches, max fanout, cascade intermediate rows avoided) as
    one stacked device triple — a single transfer decides the multiway
    fast paths AND prices the intermediate the fusion killed."""
    cs = tuple(c.astype(jnp.int32) for c in counts)
    prod = cs[0]
    inter = jnp.int32(0)
    for c in cs[1:]:
        inter = inter + jnp.sum(prod)
        prod = prod * c
    total = jnp.sum(prod)
    maxp = jnp.max(prod) if prod.shape[0] else jnp.int32(0)
    return jnp.stack([total, maxp, inter])


#: ``join:expand``'s ``form`` on the unique-partial paths
COMPACT_FORM = "sort"


@register_kernel("join.compact_partial", static_argnames=("padded",))
def _compact_partial_kernel(lowers, counts, padded: int):  # analysis: allow[JIT001] retrace is per join ARITY, not per data length
    """The unique-but-partial compaction, on the device for every
    placement: every dimension matched <= once, so the surviving fact
    rows are the rows whose every count is set, in order, and each
    dimension's build row (or depth-2 slot) IS its lower bound at those
    rows — no expansion scan.  Returns ``(probe_ids, per-dimension
    build_ids)``, each *padded* long: the ascending survivors, bit for
    bit ``np.flatnonzero``'s, then zeros.

    ONE unstable ``lax.sort``, every dimension's lower bounds riding as
    operands, keyed on the row number with the miss flag in its top bit
    — what a stable sort on the flag compares, as one ``uint32`` with no
    tie and no hidden iota operand (``ops/sort.py``'s ``dedup.compact``)
    — then the first *padded* rows.  Of the forms measured (``PERF.md``
    §5) the one whose time does not grow with the survivors."""
    _, prod = _fanout_products(counts)
    n = prod.shape[0]  # a row number is an int32 everywhere: n < 2**31
    miss = jnp.uint32(1) << 31
    order = jnp.arange(n, dtype=jnp.uint32) | jnp.where(prod > 0, jnp.uint32(0), miss)
    moved = jax.lax.sort(
        (order,) + tuple(lo.astype(jnp.int32) for lo in lowers), num_keys=1, is_stable=False
    )
    head = tuple(m[: min(padded, n)] for m in moved)
    kept = head[0] < miss
    out = tuple(jnp.where(kept, h, 0).astype(jnp.int32) for h in head)
    if padded > n:  # the bucket of a total near a stream length that is no power of two
        out = tuple(jnp.pad(o, (0, padded - n)) for o in out)
    return out[0], out[1:]


def _compact_unique_partial(lowers, counts, entries, padded: int):
    """``(probe_ids, per-dimension build ROW ids)`` of the unique-partial
    shape, *padded* long: the compaction program, then, for a depth-2
    dimension, slots -> build rows over the padded survivors only (never
    over the stream's rows)."""
    probe_ids, build_ids = _compact_partial_kernel(lowers, counts, padded)
    return probe_ids, _slots_to_rows(build_ids, entries)


@register_kernel("join.multiway_expand", static_argnames=("padded_total",))
def _multiway_expand_kernel(lowers, counts, padded_total: int):  # analysis: allow[JIT001] retrace is per join ARITY, not per data length
    """Device cross-product fan-out with a static output size: the
    per-row fanout (product of the dimensions' match counts) drives the
    same exclusive-prefix-sum + scatter-markers + running-max inversion
    as ``_expand_kernel``; the within-row offset then decomposes in
    mixed radix (dimension 0 major, suffix products as the radices) into
    one build-row offset per dimension — the cascade's nested emission
    order without the cascade's intermediate."""
    cs, prod = _fanout_products(counts)
    ends = jnp.cumsum(prod)
    starts = ends - prod
    ids = jnp.arange(prod.shape[0], dtype=jnp.int32)
    mark_pos = jnp.where(prod > 0, starts, padded_total)
    seg = jnp.zeros(padded_total, dtype=jnp.int32)
    seg = seg.at[mark_pos].max(ids, mode="drop")
    probe_ids = jax.lax.cummax(seg)
    out_pos = jnp.arange(padded_total, dtype=jnp.int32)
    r = out_pos - jnp.take(starts, probe_ids, axis=0)
    # suffix products: sufs[d] = prod of counts of dimensions AFTER d
    suffix = jnp.ones(prod.shape[0], dtype=jnp.int32)
    sufs = []
    for c in reversed(cs):
        sufs.append(suffix)
        suffix = suffix * c
    sufs.reverse()
    build_ids = []
    for d, (lo, c, su) in enumerate(zip(lowers, cs, sufs)):
        o = r // jnp.take(jnp.maximum(su, 1), probe_ids, axis=0)
        if d > 0:  # dimension 0 is the major digit: no wrap needed
            o = o % jnp.take(jnp.maximum(c, 1), probe_ids, axis=0)
        build_ids.append(
            jnp.take(lo.astype(jnp.int32), probe_ids, axis=0) + o
        )
    return probe_ids, tuple(build_ids)


def _multiway_expand_host(lowers, counts):
    """Host cross-product fan-out (numpy probe answers): same mixed-radix
    decomposition as the device kernel.  Returns
    (probe_ids, build_ids per dim, total, intermediate rows avoided)."""
    cs = [np.asarray(c).astype(np.int64) for c in counts]
    prod = cs[0].copy()
    inter = 0
    for c in cs[1:]:
        inter += int(prod.sum())
        prod *= c
    total = int(prod.sum())
    probe_ids = np.repeat(np.arange(prod.shape[0], dtype=np.int64), prod)
    ends = np.cumsum(prod)
    r = np.arange(total, dtype=np.int64) - np.repeat(ends - prod, prod)
    suffix = np.ones_like(prod)
    sufs = []
    for c in reversed(cs):
        sufs.append(suffix)
        suffix = suffix * c
    sufs.reverse()
    build_ids = []
    for d, (lo, c, su) in enumerate(zip(lowers, cs, sufs)):
        o = r // np.maximum(su, 1)[probe_ids]
        if d > 0:
            o = o % np.maximum(c, 1)[probe_ids]
        build_ids.append(np.asarray(lo).astype(np.int64)[probe_ids] + o)
    return probe_ids, tuple(build_ids), total, inter


def _multiway_ids(lowers, counts, entries, nrows: int, prefix: str, _exp: dict):
    """The expansion decision shared by the multiway joins: one stats
    sync (total, max fanout, intermediate rows avoided), then the
    unique-identity / unique-partial / fan-out / host-expand ids, and
    the ``join:expand`` extras (``docs/OBSERVABILITY.md``).
    Returns ``(probe_ids, build_ids, entries, inter)``;
    ``probe_ids`` None = every row matched once in EVERY dimension
    (then, and only then, *entries* survive: a depth-2 dimension's
    ``build_ids`` are slots of its composed columns)."""
    dims = len(entries)
    _exp["vmem_gathers"] = 0  # ``…multiway_expand``'s reads are ``jnp.take``
    if all(isinstance(lo, jax.Array) for lo in lowers):
        # (total, max fanout, intermediate rows avoided) in ONE
        # host transfer; unique dimensions skip the expansion scan
        total, maxp, inter = (
            int(v) for v in np.asarray(_multiway_stats(counts))
        )
        telemetry.count_sync(3)  # THE blocking read of the stage
        _exp.update(tier="device", host_sync_elements=3, rows_out=total, emitted=total)
        if maxp <= 1 and total == nrows:
            _exp.update(path=prefix + "-unique-identity", form="identity", padded=0, row_gathers=0)
            return None, lowers, entries, inter
        padded = 1 << max(total - 1, 0).bit_length() if total else 1
        if maxp <= 1:
            probe_ids, build_ids = _compact_unique_partial(
                lowers, counts, entries, padded
            )
            _exp.update(path=prefix + "-unique-partial", form=COMPACT_FORM, row_gathers=0)
        else:
            probe_ids, build_ids = _multiway_expand_kernel(
                _slots_to_rows(lowers, entries), counts, padded
            )
            # the scan's segment starts, then per dimension its radix,
            # its wrap (but the major digit's) and its lower bounds
            _exp.update(path=prefix + "-fan-out", form="prefix-scatter", row_gathers=3 * dims)
        _exp["padded"] = padded
        _exp["row_gathers"] += _slot_gathers(entries)
        probe_ids = probe_ids[:total]
        build_ids = tuple(b[:total] for b in build_ids)
    else:  # a host-answering tier: expand in numpy
        probe_ids, build_ids, total, inter = _multiway_expand_host(
            lowers, counts
        )
        _exp.update(
            path=prefix + "-host-expand", tier="host", form="numpy", padded=0,
            row_gathers=0, host_sync_elements=0, rows_out=total, emitted=total,
        )
    return probe_ids, build_ids, (None,) * dims, inter


def multiway_join(
    stream: DeviceTable,
    specs: "Sequence[Tuple[DeviceIndex, Sequence[str]]]",
) -> DeviceTable:
    """stream ⋈ index_1 ⋈ ... ⋈ index_k in ONE pass over the stream —
    bitwise-identical (row order, column order, values, errors) to
    ``join_tables`` applied left to right, without materializing any
    intermediate table.  *specs* lists the cascade's (DeviceIndex, key
    columns) pairs in cascade order."""
    if len(specs) == 1:  # degenerate run: exactly the binary join
        return join_tables(stream, specs[0][0], specs[0][1])

    if stream.nrows == 0:
        # per-row key validation never fires on an empty stream — fold
        # the cascade's empty early-out per level so column order and
        # kinds match the cascade exactly
        out = stream
        for dev_index, _cols in specs:
            empty = np.empty(0, dtype=np.int64)
            out_cols = {
                name: col.gather(empty)
                for name, col in {
                    **dev_index.table.columns, **out.columns
                }.items()
            }
            out = DeviceTable(out_cols, 0, stream.device)
        return out

    # one pass: every dimension's keys validate and probe over the
    # ORIGINAL stream rows.  The fusion license (rewrite.py) guarantees
    # later dimensions' keys are PRESENT before the run, so validating
    # them here raises exactly what the cascade's per-level checks would.
    return _multiway(
        stream.columns, specs, lambda kcols: _checked_probe_cols(stream, kcols),
        stream.nrows, "multiway", None, stream.device,
    )


def _multiway(cols, specs, probe_cols_of, n_in: int, prefix: str, sel, device) -> DeviceTable:
    """The single pass of ``multiway_join`` and ``multiway_join_selected``
    over *n_in* stream rows: every dimension's probe (*probe_cols_of* a
    spec's key columns: the stream's, validated, or the selection's),
    the shared expansion decision, the shared tail, and the skew
    plane's multiway counter (the staged binary join never ticks it)."""
    from ..obs.joinskew import joinskew

    part_info: dict = {}
    answers = [
        _probe_dim(dev_index, probe_cols_of(kcols), n_in, part_info)
        for dev_index, kcols in specs
    ]
    lowers, counts, entries = (tuple(a[i] for a in answers) for i in range(3))

    with telemetry.stage("join:expand", n_in) as _exp:
        _exp["dims"] = len(specs)
        probe_ids, build_ids, entries, inter = _multiway_ids(
            lowers, counts, entries, n_in, prefix, _exp
        )
        telemetry.barrier((probe_ids,) + tuple(build_ids))

    out = _emit_and_merge(cols, specs, probe_ids, build_ids, entries, None, sel, n_in, device)
    if len(specs) >= 2:
        joinskew.on_multiway(
            "+".join(",".join(di.key_columns) for di, _ in specs),
            len(specs), n_in, out.nrows, inter,
        )
    return out


# -- fused probe pass over a selection (ISSUE 19) ---------------------------
#
# ``multiway_join_selected`` is the probe half of the FusedProbe operator
# (plan.py): the executor keeps the absorbed Filter/Map/projection run
# lazy on its selection view and hands the SELECTION — not a
# materialized table — straight to the probe.  Key columns gather down
# to the selection only for probing (the same arrays the staged path
# would have probed after ``materialize()``, so every probe answer is
# identical); the emit gather then composes the selection into the
# probe ids (``take(take(S, sel), ids) == take(S, take(sel, ids))``),
# so the staged path's pre-join full-width materialize never happens
# while values, row order, column order and merge semantics stay
# bitwise the cascade's.  Unlike ``multiway_join``, a single spec does
# NOT delegate to ``join_tables`` — the multiway kernels subsume the
# binary paths exactly (one dimension's fan-out has suffix product 1,
# so the mixed-radix offset IS ``_expand_kernel``'s run offset), and
# one code path keeps the fused emit uniform over k.
#
# Caller contract: *sel* must be nonempty (the executor falls back to
# the staged join for an empty selection — it hits the cascade's empty
# folds exactly), and every spec's key columns must already be
# validated over the selected rows (the executor's ``_check_key_cells``
# raises the host-parity errors with scan-base-correct row numbers).


def multiway_join_selected(
    cols,
    sel,
    device,
    specs: "Sequence[Tuple[DeviceIndex, Sequence[str]]]",
    identity: bool = False,
) -> DeviceTable:
    """selection(cols, sel) ⋈ index_1 ⋈ ... ⋈ index_k without ever
    materializing the selected stream — bitwise-identical to
    ``multiway_join(gather(cols, sel), specs)`` (and, for one spec, to
    ``join_tables``).  *cols* maps names to FULL-length columns, *sel*
    is the selected row-id array, *identity* asserts sel is the whole
    range in order (then per-column gathers pass through, exactly like
    ``materialize()``'s identity fast path)."""
    # every dimension probes the SELECTED key values: the same arrays a
    # staged materialize would have produced, so probe answers (and the
    # shared partitioned-tier state threading) match the staged run
    return _multiway(
        cols, specs, lambda kcols: [cols[c] if identity else cols[c].gather(sel) for c in kcols],
        int(sel.shape[0]), "fused", None if identity else sel, device,
    )


def except_mask(
    stream: DeviceTable, dev_index: "DeviceIndex", columns: Sequence[str]
) -> "jax.Array | np.ndarray":
    """Boolean keep-mask for the anti-join (csvplus.go:585-608); device
    bool array on the narrow-key tier, numpy on the others."""
    if stream.nrows == 0:
        return np.zeros(0, dtype=bool)
    probe_cols = _checked_probe_cols(stream, columns)
    _, counts = dev_index.probe(probe_cols, stream.nrows)
    return counts == 0
