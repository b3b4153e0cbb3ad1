"""Partitioned lookup join: ICI all-to-all key shuffle inside shard_map.

This is the rebuild's replacement for the reference's per-row host binary
search (csvplus.go:552-568) at multi-chip scale — BASELINE.json config 5:
"8-way sharded orders.csv join across v5e-8 with ICI all-to-all key
shuffle".

Design (SPMD, static shapes throughout — no data-dependent control flow
inside jit):

* the build side of a device index is **range-partitioned over its
  UNIQUE packed keys**: each shard owns a contiguous equal-size slice of
  the distinct keys, and every key carries its precomputed global answer
  (first-match row, run length) as an int32 payload — duplicates never
  travel;
* each shard routes its local probe keys to the owning shard via a
  one-hot running count that ranks rows within their destination group
  (same slot assignment as a stable sort by dest, ~8x cheaper on CPU,
  and answers come back in original row order so no un-permute scatter)
  + a scatter into an ``(N, C)`` slot buffer + ``lax.all_to_all`` (this
  is the ICI shuffle);
* the owner answers every received probe with ``(global lower bound,
  match count)``, and a reverse ``all_to_all`` returns answers through
  the same slots, so no permutation metadata ever crosses the wire.
  Where every shard's slice of unique keys spans at most
  ``2 ** DeviceIndex.DIRECT_MAX_BITS`` values (a dictionary-coded key
  column: every code occurs, so a slice is one contiguous run) the
  owner answers **by position**: ``prepare_partitioned`` lays the
  answers out over ``[first, first + span)`` and a received key reads
  them at ``key - first`` — a range test and two gathers over the
  slots, the one-chip direct tier's idea per owner (``owner_tier``
  ``"positional"``).  A slice too sparse for that (multi-column packed
  keys with wide gaps), and every 62-bit key, keeps the vectorized
  local binary search over its unique keys (``"search"``:
  ``search_rounds`` gather rounds over all ``N * C`` slots);
* capacity ``C`` (slots per destination) is a static compile-time
  parameter, and it is COUNTED: count -> allocate -> fill.  Before the
  exchange a small program (``pjoin.route_count``) routes every probe
  key as the exchange will (:func:`_route_dest`) and returns the
  fullest (source, owner) pair, ``pair_max``; the host reads that one
  int32 in the same ``device_get`` as the hot-key sample, and ``C`` is
  its power-of-two bucket (one program a bucket).  An exchange at a
  capacity that holds ``pair_max`` cannot overflow, so its overflow
  flag is not read.  Overflow detection on device (-1 sentinel) and the
  retry with doubled capacity stay as the net under a capacity the
  count does not guarantee: the sketch's, below, or a caller's.

Skew (ISSUE 15): PROBE-side heavy hitters are detected by a sketch pass
over a bounded strided sample (``_detect_hot``: SpaceSaving count−err
lower bound -> a SOUND heavy predicate, threshold
``CSVPLUS_JOIN_SKEW_THRESHOLD``, default 1/(2·n_shards)) and routed
through a replicated broadcast tier: the few distinct hot keys are
answered once, the answers replicated to every shard, and each shard
resolves its own hot probe rows in place — this IS the JSPIM-style
salted broadcast, with the existing row placement acting as the salt
(a hot key's fact rows stay scattered across shards instead of
collapsing onto the key's range owner) and the positional scatter-back
at emit (``.at[pos].set``) folding the salt out so row order and
checksums stay bitwise-identical to the unsalted path.  The tail rides
the hash-repartition exchange unchanged.  The count is taken before
the hot keys are known and holds their rows too, so with hot keys it
is an upper bound only, and the sketch's hot-share estimate may shrink
the capacity under it (``_skew_capacity``); an undershoot of that
estimate is absorbed by the geometric capacity retry, and
``CSVPLUS_JOIN_SKEW=0`` disables the whole tier (the parity hatch and
skew-naive bench baseline: the capacity is then the count's).
BUILD-side skew is eliminated structurally: because a probe answer is
just ``(global lower bound, run length)`` — the actual match rows are
gathered later by global position — shards never need a heavy key's
duplicate copies at all.
The build side is partitioned over its UNIQUE keys, each carrying a
precomputed (lower, count) payload, so a key that owns 50% of the
build rows costs its owner exactly one slot (a build-side
salt-and-merge stays unnecessary under this answer representation).
"""

from __future__ import annotations

import math
import os
from functools import partial
from typing import NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.recompile import register_kernel
from ..utils.env import env_int, env_str
from .mesh import replicate, row_spec

_SENTINEL = np.int32(np.iinfo(np.int32).max)


def partition_tier_selected(
    n_keys: int, *, full_width: bool = True, stream_sharded: bool = True,
    min_keys: "int | None" = None,
) -> bool:
    """The ONE policy predicate for choosing this module's range-
    partitioned ``all_to_all`` probe tier over broadcast replication:
    a full-width probe of at least ``min_keys`` build keys by a
    mesh-sharded stream.  ``DeviceIndex.probe`` (both key-width tiers)
    and the plan verifier's placement domain both call it, so the
    executor and the static model can never disagree about the
    threshold."""
    if min_keys is None:
        from ..ops.join import DeviceIndex

        min_keys = DeviceIndex.PARTITION_MIN_KEYS
    return bool(full_width and stream_sharded and int(n_keys) >= int(min_keys))


# 62-bit sentinel for wide (int64) keys: packed keys keep headroom below
# it (DeviceIndex._bits_for reserves a slot above every code range)
_SENT62 = np.int64((1 << 62) - 1)


def _sentinel_for(dtype) -> "np.int32 | np.int64":
    return _SENT62 if np.dtype(dtype) == np.int64 else _SENTINEL


def split_lanes(x: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """int64 keys -> two nonnegative 31-bit int32 lanes; -1 -> (-1, -1).

    The 62-bit sentinel maps to (MASK31, MASK31), still the maximum in
    lane order."""
    hi = (x >> 31).astype(np.int32)
    lo = (x & np.int64((1 << 31) - 1)).astype(np.int32)
    neg = x < 0
    if neg.any():
        hi = np.where(neg, np.int32(-1), hi)
        lo = np.where(neg, np.int32(-1), lo)
    return hi, lo


def partition_build_keys(
    keys: np.ndarray, n_shards: int
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Range-partition a sorted build key array (int32 or int64) into
    equal slices of its UNIQUE keys, each key carrying its precomputed
    global answer.

    Returns (uniq_local[(N, k)] padded with the dtype's sentinel,
    lower_local[(N, k)] int32 global first-match row, count_local[(N, k)]
    int32 run length, splits[(N,)] = first unique key per shard).
    Partitioning unique keys makes build-side skew structurally
    impossible: a key's duplicate run contributes one slot regardless of
    its length (see module docstring).
    """
    sent = _sentinel_for(keys.dtype)
    uniq, first, counts = np.unique(keys, return_index=True, return_counts=True)
    u = uniq.shape[0]
    if u == 0:
        return (
            np.full((n_shards, 1), sent, dtype=keys.dtype),
            np.zeros((n_shards, 1), dtype=np.int32),
            np.zeros((n_shards, 1), dtype=np.int32),
            np.full(n_shards, sent, dtype=keys.dtype),
        )
    bounds = (np.arange(n_shards, dtype=np.int64) * u) // n_shards
    ends = np.append(bounds[1:], u)
    sizes = ends - bounds
    k = max(int(sizes.max()), 1)
    local = np.full((n_shards, k), sent, dtype=keys.dtype)
    lower = np.zeros((n_shards, k), dtype=np.int32)
    count = np.zeros((n_shards, k), dtype=np.int32)
    for s in range(n_shards):
        local[s, : sizes[s]] = uniq[bounds[s] : ends[s]]
        lower[s, : sizes[s]] = first[bounds[s] : ends[s]]
        count[s, : sizes[s]] = counts[bounds[s] : ends[s]]
    # splits must be non-decreasing for the routing binary search: an empty
    # shard inherits the NEXT non-empty shard's first key, so equal splits
    # route (via side='right') to the right-most shard — the actual owner.
    splits = np.full(n_shards, sent, dtype=keys.dtype)
    nxt = sent
    for s in range(n_shards - 1, -1, -1):
        if sizes[s] > 0:
            nxt = local[s, 0]
        splits[s] = nxt
    return local, lower, count, splits


def positional_tables(
    local: np.ndarray, lower: np.ndarray, count: np.ndarray, max_span: int
):
    """The positional form of :func:`partition_build_keys`' slices: per
    shard its first key and its answers laid out by ``key - first``.

    Returns ``(span_max, tables)``: *span_max* is the widest shard's
    ``last - first + 1`` over its real (unpadded) unique keys; *tables*
    is ``(first[(N,)], lower_tab[(N, T)], count_tab[(N, T)])`` with
    ``T = max(span_max, 1)`` and ``(-1, 0)`` in every hole and pad, or
    None where *span_max* exceeds *max_span* (or the keys are 62-bit).
    A dense slice of unique keys gives back its own ``lower``/``count``.
    """
    sizes = (local != _sentinel_for(local.dtype)).sum(axis=1)
    rows = np.arange(local.shape[0])
    first = local[:, 0]  # an empty shard's is the sentinel: nothing is in its range
    last = local[rows, np.maximum(sizes, 1) - 1]
    spans = np.where(sizes > 0, last.astype(np.int64) - first + 1, 0)
    span_max = int(spans.max())
    if local.dtype != np.int32 or span_max > max_span:
        return span_max, None
    T = max(span_max, 1)
    lower_tab = np.full((local.shape[0], T), -1, dtype=np.int32)
    count_tab = np.zeros((local.shape[0], T), dtype=np.int32)
    for s, n in enumerate(sizes):
        off = local[s, :n] - first[s]
        lower_tab[s, off] = lower[s, :n]
        count_tab[s, off] = count[s, :n]
    return span_max, (first, lower_tab, count_tab)


class Partitioned(NamedTuple):
    """One index's build side as :func:`prepare_partitioned` leaves it
    on a mesh — what every partitioned probe of that index is handed.

    ``lower``/``count`` are each shard's answers (int32, row-sharded,
    flattened ``(N * k,)``) and ``splits`` the replicated routing keys
    (one int32 lane, or ``(hi, lo)`` for 62-bit keys).  How the owner
    finds a received key's answer is in what else is there:

    * ``first`` set (``(N,)`` row-sharded: a shard's first key) and
      ``uniq`` empty — **positional**: the answers are laid out over
      the slice's span and read at ``key - first``;
    * ``first`` None — **search**: ``uniq`` holds each shard's sorted
      unique keys (sentinel-padded; two lanes for 62-bit keys), the
      answers lie beside them, and the owner binary-searches.
    """

    uniq: Tuple[jax.Array, ...]
    first: "jax.Array | None"
    lower: jax.Array
    count: jax.Array
    splits: Tuple[jax.Array, ...]

    @property
    def wide(self) -> bool:
        return len(self.splits) == 2

    @property
    def positional(self) -> bool:
        return self.first is not None

    @property
    def owner_tier(self) -> str:
        return "positional" if self.positional else "search"

    @property
    def search_rounds(self) -> int:
        """Gather rounds of the owner's search over its slice (0: none)."""
        if self.positional:
            return 0
        from ..ops.join import _searchsorted_rounds

        return _searchsorted_rounds(
            self.uniq[0].shape[0] // self.splits[0].shape[0]
        )

    @property
    def owner(self) -> jax.Array:
        """The narrow shard kernel's owner operand: ``first``, else ``uniq``."""
        return self.first if self.positional else self.uniq[0]


def _route_dest(n_shards: int, q, splits):
    """The owning shard of every probe key — lanes *q*, one int32 lane
    or ``(hi, lo)`` — against the replicated, non-decreasing *splits*:
    how many split keys past the first are <= the key, which is
    ``searchsorted(splits, q, side="right") - 1`` clipped into the mesh,
    in N - 1 compares and no loop.  An invalid probe (``q < 0``: absent,
    or answered by the hot tier) gets *n_shards*: it goes nowhere and
    takes no slot.  The exchange kernels and the count pass
    (:func:`_route_count`) both route by this function, so the capacity
    the count guarantees is the capacity the exchange needs."""
    dest = jnp.zeros(q[0].shape, jnp.int32)
    for i in range(1, n_shards):
        past = True  # split i <= key, lexicographic over the lanes: the last lane first
        for lane, split in zip(reversed(q), reversed(splits)):
            past = (split[i] < lane) | ((split[i] == lane) & past)
        dest = dest + past.astype(jnp.int32)
    return jnp.where(q[0] >= 0, dest, jnp.int32(n_shards))


def _probe_shard_kernel(
    n_shards: int, capacity: int, axes, positional: bool,
    qk, owner, lower_local, count_local, splits,
):
    """Per-shard body (runs under shard_map): route, exchange, probe,
    route back.  All shapes static.  *axes* is the mesh's full axis-name
    tuple: the exchange spans the whole mesh (ICI within a slice, DCN
    across slices on a 2-D mesh).  *owner* is this shard's first key
    (shape ``(1,)``) where *positional*, else its sorted unique keys
    (:class:`Partitioned`)."""
    N, C = n_shards, capacity

    # invalid probes (absent keys / hot-key short-circuited) get dest N:
    # they consume NO exchange slots and answer (−1, 0)
    dest = _route_dest(N, (qk,), (splits,))
    routed = qk >= 0

    # rank of each query within its destination group, in original row
    # order, via a one-hot running count — N is small (mesh size), so
    # this is one O(m·N) prefix-sum pass.  A stable sort by dest gives
    # the identical rank assignment (first occurrence -> slot 0) but
    # costs ~8x more than the cumsum on CPU at mesh-bench scale, and
    # forces an O(m) un-permute scatter on the way out.
    safe_dest = jnp.minimum(dest, N - 1)  # N (invalid) is dropped via ok
    onehot = (dest[:, None] == jnp.arange(N, dtype=jnp.int32)[None, :]).astype(
        jnp.int32
    )
    rank = (
        jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0), safe_dest[:, None], axis=1
        )[:, 0]
        - 1
    )
    ok = routed & (rank < C)  # overflow -> sentinel, caller retries bigger C

    # scatter into (N, C) slot buffer; overflow/invalid drop out of bounds
    buf = jnp.full((N, C), -1, dtype=jnp.int32)
    buf = buf.at[safe_dest, jnp.where(ok, rank, C)].set(qk, mode="drop")

    # ICI shuffle: slot-aligned exchange
    recv = lax.all_to_all(buf, axes, split_axis=0, concat_axis=0, tiled=True)

    # the answer (global lower, run length) is a precomputed payload:
    # found by position where the slice's span has a table (holes and
    # pads hold (-1, 0) there), else by a vectorized local search over
    # this shard's unique keys
    q = recv.reshape(-1)
    if positional:
        off = q - owner[0]  # q >= -1 and first >= 0: no int32 wrap
        found = (q >= 0) & (off >= 0) & (off < lower_local.shape[0])
        idx = jnp.clip(off, 0, lower_local.shape[0] - 1)
    else:
        idx = jnp.searchsorted(owner, q, side="left")
        idx = jnp.minimum(idx, owner.shape[0] - 1).astype(jnp.int32)
        found = (jnp.take(owner, idx, axis=0) == q) & (q >= 0)
    resp_lo = jnp.where(found, jnp.take(lower_local, idx, axis=0), -1)
    resp_ct = jnp.where(found, jnp.take(count_local, idx, axis=0), 0)

    # answers ride home through the same slots
    back_lo = lax.all_to_all(
        resp_lo.reshape(N, C), axes, split_axis=0, concat_axis=0, tiled=True
    )
    back_ct = lax.all_to_all(
        resp_ct.reshape(N, C), axes, split_axis=0, concat_axis=0, tiled=True
    )

    safe_rank = jnp.clip(rank, 0, C - 1)
    # ranks are per original row order already — no un-permute needed
    got_lo = jnp.where(ok, back_lo[safe_dest, safe_rank], -1)
    # invalid probes answer (lo=-1, ct=0); only routed overflow gets -1
    got_ct = jnp.where(
        routed, jnp.where(ok, back_ct[safe_dest, safe_rank], -1), 0
    )
    return got_lo, got_ct


def _probe_shard_kernel2(
    n_shards: int,
    capacity: int,
    axes,
    qh,
    ql,
    uniq_hi,
    uniq_lo,
    lower_local,
    count_local,
    splits_hi,
    splits_lo,
):
    """Dual-lane (62-bit key) variant of :func:`_probe_shard_kernel`:
    identical routing/exchange structure, with the key carried as two
    nonnegative 31-bit int32 lanes and every comparison lexicographic
    over (hi, lo).  Costs one extra (N, C) exchange for the second lane.
    """
    from ..ops.join import _searchsorted2

    N, C = n_shards, capacity

    dest = _route_dest(N, (qh, ql), (splits_hi, splits_lo))
    routed = qh >= 0

    # within-destination rank in original row order via one-hot running
    # count — same slot assignment as the stable sort it replaces, ~8x
    # cheaper at mesh-bench scale (see _probe_shard_kernel)
    safe_dest = jnp.minimum(dest, N - 1)
    onehot = (dest[:, None] == jnp.arange(N, dtype=jnp.int32)[None, :]).astype(
        jnp.int32
    )
    rank = (
        jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0), safe_dest[:, None], axis=1
        )[:, 0]
        - 1
    )
    ok = routed & (rank < C)

    slot = jnp.where(ok, rank, C)
    buf_h = jnp.full((N, C), -1, jnp.int32).at[safe_dest, slot].set(qh, mode="drop")
    buf_l = jnp.full((N, C), -1, jnp.int32).at[safe_dest, slot].set(ql, mode="drop")

    recv_h = lax.all_to_all(buf_h, axes, split_axis=0, concat_axis=0, tiled=True)
    recv_l = lax.all_to_all(buf_l, axes, split_axis=0, concat_axis=0, tiled=True)

    q_h = recv_h.reshape(-1)
    q_l = recv_l.reshape(-1)
    idx = _searchsorted2(uniq_hi, uniq_lo, q_h, q_l, side="left")
    idx = jnp.minimum(idx, uniq_hi.shape[0] - 1).astype(jnp.int32)
    found = (
        (jnp.take(uniq_hi, idx, axis=0) == q_h)
        & (jnp.take(uniq_lo, idx, axis=0) == q_l)
        & (q_h >= 0)
    )
    resp_lo = jnp.where(found, jnp.take(lower_local, idx, axis=0), -1)
    resp_ct = jnp.where(found, jnp.take(count_local, idx, axis=0), 0)

    back_lo = lax.all_to_all(
        resp_lo.reshape(N, C), axes, split_axis=0, concat_axis=0, tiled=True
    )
    back_ct = lax.all_to_all(
        resp_ct.reshape(N, C), axes, split_axis=0, concat_axis=0, tiled=True
    )

    safe_rank = jnp.clip(rank, 0, C - 1)
    # ranks are per original row order already — no un-permute needed
    got_lo = jnp.where(ok, back_lo[safe_dest, safe_rank], -1)
    got_ct = jnp.where(
        routed, jnp.where(ok, back_ct[safe_dest, safe_rank], -1), 0
    )
    return got_lo, got_ct


def _answer_hot_lanes(mesh, kernel, static_tail, k, operands):
    """The hot values' own answers, all of the broadcast tier's device
    work: the *k* replicated hot lanes that lead *operands* (their
    length is the pow2 bucket of the hot count), padded with never-valid
    -1 to whole rows a shard, ride the probes' own per-shard exchange
    *kernel* — its capacity is a shard's whole share of them, so it
    cannot overflow wherever they route — over the rest of *operands*
    (that kernel's build-side arguments), and the bucket's answers come
    back replicated, ready for the main kernel's merge."""
    n_shards = mesh.devices.size
    axes = tuple(mesh.axis_names)
    rows = P(axes)
    n_hot = operands[0].shape[0]
    pad = (-n_hot) % n_shards
    hot = [
        jnp.concatenate([x, jnp.full(pad, -1, x.dtype)]) if pad else x
        for x in operands[:k]
    ]
    f = shard_map(
        partial(kernel, n_shards, (n_hot + pad) // n_shards, axes, *static_tail),
        mesh=mesh,
        in_specs=(rows,) * (2 * k + 2) + (P(),) * k,
        out_specs=(rows, rows),
    )
    lo, ct = f(*hot, *operands[k:])
    repl = NamedSharding(mesh, P())
    return tuple(
        jax.lax.with_sharding_constraint(x[:n_hot], repl) for x in (lo, ct)
    )


@register_kernel("pjoin.hot_answers", static_argnames=("mesh", "positional"))
def _hot_answers_spmd(mesh, positional, hot, owner, lower, count, splits):
    """:func:`_answer_hot_lanes` for narrow keys, one program."""
    return _answer_hot_lanes(
        mesh, _probe_shard_kernel, (positional,), 1,
        (hot, owner, lower, count, splits),
    )


@register_kernel("pjoin.hot_answers2", static_argnames=("mesh",))
def _hot_answers_spmd2(
    mesh, hot_hi, hot_lo, uniq_hi, uniq_lo, lower, count, splits_hi, splits_lo
):
    """:func:`_answer_hot_lanes` for 62-bit keys (dual 31-bit lanes)."""
    return _answer_hot_lanes(
        mesh, _probe_shard_kernel2, (), 2,
        (hot_hi, hot_lo, uniq_hi, uniq_lo, lower, count, splits_hi, splits_lo),
    )


def prepare_partitioned(mesh: Mesh, index_keys_sorted: np.ndarray) -> Partitioned:
    """Range-partition + upload the build keys once; reusable across
    probes (see DeviceIndex._partitioned_for's cache).

    int32 keys whose every slice spans at most
    ``2 ** DeviceIndex.DIRECT_MAX_BITS`` values take the positional form
    of :class:`Partitioned` (what the keys show decides, nothing else);
    sparser slices and int64 (wide, 62-bit) keys the search form, the
    latter with the unique keys and splits as dual 31-bit lanes.
    """
    from ..ops.join import DeviceIndex
    from ..utils.observe import telemetry

    n_shards = mesh.devices.size
    rows = NamedSharding(mesh, row_spec(mesh))
    repl = NamedSharding(mesh, P())
    with telemetry.stage(
        "join:partition", int(index_keys_sorted.shape[0])
    ) as _p:
        _p["n_shards"] = n_shards
        wide = np.dtype(index_keys_sorted.dtype) == np.int64
        lanes = split_lanes if wide else (lambda x: (x,))
        local, lower, count, splits = partition_build_keys(
            index_keys_sorted if wide else index_keys_sorted.astype(np.int32),
            n_shards,
        )
        _p["span_max"], tables = positional_tables(
            local, lower, count, 2 ** DeviceIndex.DIRECT_MAX_BITS
        )
        _p["positional"] = tables is not None
        if tables is not None:
            first, lower, count = tables
            uniq, first = (), jax.device_put(first, rows)
        else:
            uniq = tuple(jax.device_put(x, rows) for x in lanes(local.reshape(-1)))
            first = None
        return telemetry.barrier(
            Partitioned(
                uniq,
                first,
                jax.device_put(lower.reshape(-1), rows),
                jax.device_put(count.reshape(-1), rows),
                tuple(jax.device_put(x, repl) for x in lanes(splits)),
            )
        )


def partitioned_probe(
    mesh: Mesh,
    stream_keys: np.ndarray,
    index_keys_sorted: np.ndarray,
    capacity: "int | None" = None,
    prepared=None,
) -> Tuple[np.ndarray, np.ndarray]:
    """All-to-all partitioned probe: for every stream key, the global
    ``[lower, lower+count)`` match range in the sorted index key array.

    Host-facing numpy shim over the device orchestration
    (:func:`partitioned_probe_device` / ``_wide``), which owns the
    padding, hot-key short circuit, and capacity-retry logic — one
    implementation, two entry points.  Keys are packed keys with -1 for
    invalid probes (absent/unmatched dictionary translation): int32 for
    narrow (<= 31-bit) keys, int64 for wide (<= 62-bit) keys — the wide
    tier exchanges dual 31-bit lanes.  *prepared* short-circuits the
    partition+upload with the result of :func:`prepare_partitioned`.
    """
    wide = np.dtype(stream_keys.dtype) == np.int64
    if prepared is None:
        prepared = prepare_partitioned(mesh, index_keys_sorted)
    assert prepared.wide == wide, "prepared/key dtype mismatch"
    if wide:
        qh, ql = split_lanes(stream_keys)
        lo, ct = partitioned_probe_device_wide(
            mesh, jax.device_put(qh), jax.device_put(ql), prepared, capacity
        )
    else:
        qk = jax.device_put(stream_keys.astype(np.int32))
        lo, ct = partitioned_probe_device(mesh, qk, prepared, capacity)
    return np.asarray(lo), np.asarray(ct)


# -- device-resident orchestration (the executor's multi-chip tier) -------
#
# The host wrapper above (partitioned_probe) syncs the full probe array
# to numpy, pads/samples/uploads on host, and syncs the full counts
# array every capacity retry — O(n) host traffic per probe.  The
# functions below keep the probe keys, answers, hot-key merge, padding,
# and overflow detection ON DEVICE: the only host syncs are a <=4096-
# element hot-key sample with the route count beside it, and one boolean
# overflow scalar per attempt the count does not guarantee.


@register_kernel(
    "pjoin.probe_spmd_dev",
    static_argnames=("mesh", "n_shards", "capacity", "n_hot", "positional"),
)
def _probe_spmd_dev(
    mesh, n_shards, capacity, n_hot, positional, qk, owner, lower, count, splits,
    hot_vals, hot_lo, hot_ct,
):
    """One executable: hot-key mask -> pad -> all_to_all exchange ->
    un-pad -> hot-key merge -> overflow flag.  *n_hot* = 0 compiles the
    variant without the hot path (hot operands are 1-element dummies)
    and returns exactly the historical 3-tuple — the uniform-data
    passthrough contract (same trace, same executable as before the
    skew tier existed).  *n_hot* > 0 additionally returns the number of
    probe rows the broadcast tier answered (the routing-split evidence,
    synced together with the overflow flag — no extra host round)."""
    axes = tuple(mesh.axis_names)
    rows = row_spec(mesh)
    m = qk.shape[0]
    if n_hot:
        idx = jnp.searchsorted(hot_vals, qk, side="left")
        idxc = jnp.minimum(idx, n_hot - 1).astype(jnp.int32)
        hit = (jnp.take(hot_vals, idxc, axis=0) == qk) & (qk >= 0)
        qk_cold = jnp.where(hit, jnp.int32(-1), qk)
    else:
        qk_cold = qk
    pad = (-m) % n_shards
    if pad:
        qk_cold = jnp.concatenate(
            [qk_cold, jnp.full(pad, -1, qk_cold.dtype)]
        )
    qk_cold = jax.lax.with_sharding_constraint(
        qk_cold, NamedSharding(mesh, rows)
    )
    f = shard_map(
        partial(_probe_shard_kernel, n_shards, capacity, axes, positional),
        mesh=mesh,
        in_specs=(rows, rows, rows, rows, P()),
        out_specs=(rows, rows),
    )
    lo, ct = f(qk_cold, owner, lower, count, splits)
    lo, ct = lo[:m], ct[:m]
    if n_hot:
        h_lo = jnp.take(hot_lo, idxc, axis=0)
        h_ct = jnp.take(hot_ct, idxc, axis=0)
        lo = jnp.where(hit, jnp.where(h_ct > 0, h_lo, -1), lo)
        ct = jnp.where(hit, h_ct, ct)
        return lo, ct, jnp.any(ct < 0), jnp.sum(hit)
    return lo, ct, jnp.any(ct < 0)


@register_kernel("pjoin.probe_spmd_dev2", static_argnames=("mesh", "n_shards", "capacity", "n_hot"))
def _probe_spmd_dev2(
    mesh, n_shards, capacity, n_hot, qh, ql,
    uniq_hi, uniq_lo, lower, count, splits_hi, splits_lo,
    hot_hi, hot_lo_lane, hot_ans_lo, hot_ans_ct,
):
    """Wide-key (dual 31-bit lane) variant of :func:`_probe_spmd_dev`."""
    from ..ops.join import _searchsorted2

    axes = tuple(mesh.axis_names)
    rows = row_spec(mesh)
    m = qh.shape[0]
    if n_hot:
        idx = _searchsorted2(hot_hi, hot_lo_lane, qh, ql, side="left")
        idxc = jnp.minimum(idx, n_hot - 1).astype(jnp.int32)
        hit = (
            (jnp.take(hot_hi, idxc, axis=0) == qh)
            & (jnp.take(hot_lo_lane, idxc, axis=0) == ql)
            & (qh >= 0)
        )
        qh_cold = jnp.where(hit, jnp.int32(-1), qh)
        ql_cold = jnp.where(hit, jnp.int32(-1), ql)
    else:
        qh_cold, ql_cold = qh, ql
    pad = (-m) % n_shards
    if pad:
        fill = jnp.full(pad, -1, jnp.int32)
        qh_cold = jnp.concatenate([qh_cold, fill])
        ql_cold = jnp.concatenate([ql_cold, fill])
    sharding = NamedSharding(mesh, rows)
    qh_cold = jax.lax.with_sharding_constraint(qh_cold, sharding)
    ql_cold = jax.lax.with_sharding_constraint(ql_cold, sharding)
    f = shard_map(
        partial(_probe_shard_kernel2, n_shards, capacity, axes),
        mesh=mesh,
        in_specs=(rows, rows, rows, rows, rows, rows, P(), P()),
        out_specs=(rows, rows),
    )
    lo, ct = f(
        qh_cold, ql_cold, uniq_hi, uniq_lo, lower, count, splits_hi, splits_lo
    )
    lo, ct = lo[:m], ct[:m]
    if n_hot:
        h_lo = jnp.take(hot_ans_lo, idxc, axis=0)
        h_ct = jnp.take(hot_ans_ct, idxc, axis=0)
        lo = jnp.where(hit, jnp.where(h_ct > 0, h_lo, -1), lo)
        ct = jnp.where(hit, h_ct, ct)
        return lo, ct, jnp.any(ct < 0), jnp.sum(hit)
    return lo, ct, jnp.any(ct < 0)


def _route_count(mesh, q, splits):
    """``pair_max``: the most probe rows any one shard sends any one
    owner — what the exchange's capacity must hold.  Runs under
    ``shard_map`` over the probe's own row sharding (the same pad of
    never-valid -1 to whole rows a shard, so a shard here holds the rows
    it holds there) and routes by the exchange's own
    :func:`_route_dest`: per shard the rows bound for each owner, their
    maximum over owners and, by ``lax.pmax``, over the mesh.  One int32,
    replicated.  Rows the hot tier will answer in place are still
    counted (the count runs before they are known): an upper bound."""
    n_shards = mesh.devices.size
    axes = tuple(mesh.axis_names)
    rows = row_spec(mesh)
    pad = (-q[0].shape[0]) % n_shards
    if pad:
        q = tuple(jnp.concatenate([x, jnp.full(pad, -1, x.dtype)]) for x in q)
    q = tuple(
        jax.lax.with_sharding_constraint(x, NamedSharding(mesh, rows)) for x in q
    )

    def fullest(q, splits):
        dest = _route_dest(n_shards, q, splits)
        to_owner = jnp.stack(
            [jnp.sum(dest == d, dtype=jnp.int32) for d in range(n_shards)]
        )
        return lax.pmax(jnp.max(to_owner), axes)

    # one spec a tuple of lanes: the probe's rows, the replicated splits
    f = shard_map(fullest, mesh=mesh, in_specs=(rows, P()), out_specs=P())
    return f(q, tuple(splits))


@register_kernel("pjoin.route_count", static_argnames=("mesh",))
def _route_count_spmd(mesh, qk, splits):
    """:func:`_route_count` for narrow keys, one program."""
    return _route_count(mesh, (qk,), (splits,))


@register_kernel("pjoin.route_count2", static_argnames=("mesh",))
def _route_count_spmd2(mesh, qh, ql, splits_hi, splits_lo):
    """:func:`_route_count` for 62-bit keys (dual 31-bit lanes)."""
    return _route_count(mesh, (qh, ql), (splits_hi, splits_lo))


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def _renamed_rows(mesh: Mesh, x: jax.Array) -> jax.Array:
    """Re-commit a jit output to a row NamedSharding: XLA hands results
    back with an opaque GSPMDSharding (no ``.mesh``), but downstream
    consumers (``_aligned_codes``, the executor's replication caches)
    key off the named mesh.  Same layout -> no data movement.  Lengths
    that don't divide the mesh can't carry a row NamedSharding; they
    keep the opaque sharding and downstream falls back to placement-
    agnostic eager gathers."""
    if x.shape[0] % mesh.devices.size == 0:
        return jax.device_put(x, NamedSharding(mesh, row_spec(mesh)))
    return x


def _default_capacity(m: int, n_shards: int) -> int:
    """Twice the mean (source, owner) pair, as a power of two: the clamp
    of :func:`_skew_capacity` and nothing else — the exchange's own
    capacity is counted (:func:`_exchange_capacity`)."""
    m_per_shard = (m + n_shards - 1) // n_shards
    return _pow2(max(64, 2 * ((m_per_shard + n_shards - 1) // n_shards)))


def skew_enabled() -> bool:
    """``CSVPLUS_JOIN_SKEW=0`` disables ALL hot-key handling (the
    parity hatch): no detection, no broadcast tier, the exchange at
    its counted capacity (hot rows and all) — the skew-naive baseline
    the bench gate compares against.  Read per call so one process can
    flip it between passes (the bench measures both modes in the same
    run)."""
    return env_str("CSVPLUS_JOIN_SKEW", "1") != "0"


def skew_threshold(n_shards: int) -> float:
    """Heavy-hitter share threshold τ: a probe key is worth
    broadcasting once its estimated share exceeds τ
    (``CSVPLUS_JOIN_SKEW_THRESHOLD``, default ``1/(2·n_shards)``).
    Rationale for the default — the broadcast-vs-repartition cutoff:
    under hash repartition, one key's rows all land on its owner, so a
    key with share τ adds τ·m rows to one shard on top of the shard's
    m/n fair share; at τ = 1/(2n) that's a 50% overload, the point
    where the (N, C) slot buffer must grow a power of two and every
    shard pays the doubled exchange.  Broadcasting such a key instead
    costs one replicated answer slot — O(1) — so the cutoff sits where
    the repartition cost first becomes super-linear."""
    v = env_str("CSVPLUS_JOIN_SKEW_THRESHOLD")
    if v:
        return max(float(v), 1e-6)
    return 1.0 / (2.0 * max(int(n_shards), 1))


def _skew_sample_cap() -> int:
    """Sample-size cap (``CSVPLUS_JOIN_SKEW_SAMPLE``, default 4096 —
    the bound the sync-accounting tests pin).  Detection resolves key
    shares down to ~16/cap, so benches raise it to see deeper into the
    Zipf tail."""
    return max(env_int("CSVPLUS_JOIN_SKEW_SAMPLE", 4096), 64)


@register_kernel("pjoin.skew_sample")
def _skew_sample(lane, at):
    """The probe keys of one lane at the strided positions *at*: the
    bounded sample the host reads for hot-key detection, taken by a
    named program."""
    return jnp.take(lane, at, axis=0)


def _detect_hot(qk_dev, n_shards: int, wide: bool, count):
    """Sketch-driven heavy-hitter detection over a bounded strided
    device sample — a data-INDEPENDENT host transfer (bounded by the
    sample cap, not the probe length).  *count* is the route count's
    device scalar (:func:`_route_count`): it comes down in the sample's
    own ``device_get`` — the exchange's capacity costs no blocking read
    of its own — and alone where no sample is taken.

    The sample's (value, count) aggregate feeds a :class:`SpaceSaving`
    sketch with ``k = ceil(4/τ)`` tracked keys; a key is classified
    heavy only when its guaranteed lower bound clears the bar::

        count - err >= max(8, τ·sample/2)

    Soundness: SpaceSaving guarantees any key with sample share > 1/k
    is tracked, with ``err <= observed/k <= τ·observed/4`` — so every
    key whose true sample count reaches ``τ·observed`` survives the
    bar (count ≥ τ·observed, err ≤ τ·observed/4), while any key that
    clears it provably holds ≥ τ/2 of the sample.  The absolute floor
    of 8 sample hits guards the small-sample regime where binomial
    noise dominates.  With fewer distinct sampled keys than *k* the
    sketch counts are exact (err 0) and the predicate reduces to the
    plain frequency threshold.

    Returns ``(hot, hot_share, pair_max)``: sorted distinct hot values
    as int64 (wide) / int32 or None, the hot keys' aggregate share of
    the sample — the planner's capacity hint for the tail exchange —
    and *count* as a host int.

    Fused probe passes (ISSUE 19) need no special handling here: the
    sample is drawn from whatever packed key array reaches the
    partitioned probe, and ``multiway_join_selected`` packs keys
    gathered down to the POST-filter selection — so hot-key detection
    and broadcast routing automatically see only the fact rows that
    survived the absorbed filters, exactly the rows the exchange would
    carry."""
    from ..obs.sketch import SpaceSaving
    from ..utils.observe import telemetry

    lanes = qk_dev if wide else (qk_dev,)
    m = int(lanes[0].shape[0])
    if not skew_enabled() or m < 4 * n_shards:
        telemetry.count_sync(1)  # no sample to ride with: the count alone
        return None, 0.0, int(jax.device_get(count))
    tau = skew_threshold(n_shards)
    with telemetry.stage("join:skew-detect", m) as _d:
        cap = _skew_sample_cap()
        step = max(1, -(-m // cap))  # ceil: the sample stays <= cap elements
        at = np.arange(0, m, step, dtype=np.int32)
        # EXPLICIT device_get: the transfer-guard differential test pins
        # that the device path performs no *implicit* device->host
        # transfers
        *sampled, pair_max = jax.device_get(
            (*(_skew_sample(x, at) for x in lanes), count)
        )
        pair_max = int(pair_max)
        synced = sum(x.size for x in sampled) + 1
        telemetry.count_sync(synced)
        _d["host_sync_elements"] = synced
        if wide:
            hi, lo = sampled
            sample = (hi.astype(np.int64) << 31) | np.where(lo >= 0, lo, 0)
            sample = sample[hi >= 0]
        else:
            (sample,) = sampled
            sample = sample[sample >= 0]
        _d["threshold"] = round(tau, 6)
        _d["sample"] = int(sample.size)
        _d["hot_keys"] = 0
        if not sample.size:
            return None, 0.0, pair_max
        vals, cnts = np.unique(sample, return_counts=True)
        sk = SpaceSaving(k=min(max(int(math.ceil(4.0 / tau)), 8), 4096))
        sk.offer_counts(vals, cnts)
        bar = max(8.0, tau * sample.size / 2.0)
        hot_list = [key for key, c, e in sk.topk() if (c - e) >= bar]
        _d["hot_keys"] = len(hot_list)
        if not hot_list:
            return None, 0.0, pair_max
        hot = np.sort(np.asarray(hot_list, dtype=np.int64 if wide else np.int32))
        # hot share from the EXACT sample counts (not the sketch
        # estimates): the tail-capacity hint must never overshoot
        hot_share = float(cnts[np.isin(vals, hot)].sum()) / float(sample.size)
        _d["hot_share"] = round(hot_share, 4)
        return hot, hot_share, pair_max


def _skew_capacity(m: int, n_shards: int, hot_share: float) -> int:
    """Sketch-informed tail capacity: the broadcast tier removes
    ``hot_share`` of the probe rows from the exchange, so the (N, C)
    slot buffer only needs to cover the tail.  1.5x slack over the
    uniform per-(src, dest) expectation absorbs residual tail skew
    (the heaviest un-broadcast key holds < τ of the rows by the
    detection guarantee); an undershoot costs one geometric retry,
    never correctness.  Clamped to the skew-naive default so a bad
    share estimate can only shrink the exchange, and floored like the
    default."""
    tail = max(1.0 - hot_share, 0.0)
    m_per_shard = (m + n_shards - 1) // n_shards
    want = int(math.ceil(1.5 * tail * m_per_shard / n_shards))
    return min(_pow2(max(64, want)), _default_capacity(m, n_shards))


def _note_skew(
    label, m: int, hot_keys: int, rows_broadcast: int, capacity: int,
    threshold: float,
) -> None:
    """The routing-split evidence for one skew-engaged probe: a
    ``join:skew`` row in the span stage table (so a stage-table reader can
    attribute the win) plus the process-global counters
    ``TelemetryPlane`` exports.  ``seconds=0``: this row is an
    accounting record — detection and hot-answer time are already
    attributed to ``join:skew-detect`` / ``join:broadcast`` — so the
    stage table's time shares stay undistorted."""
    from ..obs.joinskew import joinskew
    from ..utils.observe import telemetry

    rows_repartitioned = int(m) - int(rows_broadcast)
    telemetry.add_stage(
        "join:skew", m, m, 0.0,
        hot_keys=int(hot_keys),
        rows_broadcast=int(rows_broadcast),
        rows_repartitioned=rows_repartitioned,
        capacity=int(capacity),
        threshold=round(float(threshold), 6),
    )
    joinskew.on_join(
        label or "packed", int(hot_keys), int(rows_broadcast),
        rows_repartitioned,
    )


def _hot_answers_device(mesh, hot: np.ndarray, prepared: Partitioned):
    """Answer the (few, distinct) hot values themselves: one upload of
    their lanes and one launch of :func:`_hot_answers_spmd` (``…spmd2``
    for 62-bit keys).  Returns device ``(vals..., lo, ct)``, each the
    pow2 bucket of the hot count long and replicated; nothing is read
    back to the host.

    The value lanes — also the main kernel's membership table — are
    sorted and padded by REPEATING the last real value: duplicates at
    the tail keep the array sorted, and searchsorted-left always lands
    on the first (real) slot, whose answer the pads share, so a probe
    key equal to any conceivable pad value can never be answered
    wrongly from a pad slot."""
    pad = _pow2(hot.size) - hot.size
    lanes = split_lanes(hot) if prepared.wide else (hot,)
    vals = replicate(
        mesh, tuple(np.concatenate([x, np.full(pad, x[-1], np.int32)]) for x in lanes)
    )
    if prepared.wide:
        lo, ct = _hot_answers_spmd2(
            mesh, *vals, *prepared.uniq, prepared.lower, prepared.count,
            *prepared.splits,
        )
    else:
        lo, ct = _hot_answers_spmd(
            mesh, prepared.positional, *vals, prepared.owner, prepared.lower,
            prepared.count, *prepared.splits,
        )
    return vals, lo, ct


def _exchange_capacity(
    capacity: "int | None", m: int, n_shards: int, pair_max: int,
    hot_share: "float | None",
) -> Tuple[int, str]:
    """The first attempt's slot capacity and where it is from: the
    caller's if one was given (``caller``); else the power-of-two bucket
    of the counted ``pair_max`` (``count``: a static shape, one program
    a bucket, and a capacity that cannot overflow) — unless hot keys
    were found (*hot_share* not None) and the sketch's tail capacity is
    smaller (``sketch``): the count holds the hot rows too, so there it
    is an upper bound that the sketch may shrink, with the retry as its
    net."""
    if capacity is not None:
        return int(capacity), "caller"
    counted = _pow2(max(64, pair_max))
    if hot_share is not None:
        sketch = _skew_capacity(m, n_shards, hot_share)
        if sketch < counted:
            return sketch, "sketch"
    return counted, "count"


def _retry_probe_device(
    mesh: Mesh, m: int, capacity: int, capacity_from: str, pair_max: int,
    launch, prepared: Partitioned,
):
    """Shared driver of the device wrappers' exchange.  An attempt at a
    capacity that holds the counted ``pair_max`` cannot overflow and its
    overflow flag is not read; that is every attempt whose capacity is
    the count's.  Under a capacity the count does not guarantee — the
    sketch's, a caller's — the geometric retry is the net: ONE overflow
    boolean read per such attempt, the capacity doubled until it holds.
    Results are re-committed to the named mesh.  *prepared* says what
    the stage records of the owner's step (``owner_tier``,
    ``search_rounds``) and how many ``(N, C)`` int32 ``all_to_all``
    rounds one attempt makes (key lanes out, ``lower`` and ``count``
    back): ``bytes_exchanged`` is reckoned from shapes.

    Returns ``((lo, ct), rows_broadcast, capacity)``: when the launch
    carries the hot tier (4-tuple results) the broadcast row count rides
    the same device_get as the overflow flag — at most one host round
    per attempt."""
    from ..utils.observe import telemetry

    n_shards = mesh.devices.size
    exchanges = 4 if prepared.wide else 3
    padded_m = m + ((-m) % n_shards)
    retries = synced = 0
    # the exchange stage covers the whole shard_map launch: all_to_all
    # key shuffle + per-shard local probe + answer return + hot merge
    # (one fused SPMD executable, not separable from outside)
    with telemetry.stage("join:all_to_all", m) as _x:
        _x["pair_max"] = pair_max
        _x["capacity_from"] = capacity_from
        while True:
            res = launch(capacity)
            lo, ct = res[0], res[1]
            guaranteed = pair_max <= capacity
            reads = res[3:] if guaranteed else res[2:]  # (overflow?, hits?)
            if reads:
                reads = jax.device_get(tuple(reads))
                telemetry.count_sync(len(reads))
                synced += len(reads)
            overflowed = not guaranteed and bool(reads[0])
            rows_broadcast = int(reads[-1]) if len(res) > 3 else 0
            if not overflowed:
                slots = n_shards * n_shards * capacity  # of the settled attempt, mesh-wide
                _x["capacity"] = capacity
                _x["retries"] = retries
                _x["attempts"] = retries + 1  # launches of the exchange
                _x["host_sync_elements"] = synced
                _x["slot_fill"] = m / slots
                _x["bytes_exchanged"] = 4 * exchanges * slots
                _x["owner_tier"] = prepared.owner_tier
                _x["search_rounds"] = prepared.search_rounds
                out = _renamed_rows(mesh, lo), _renamed_rows(mesh, ct)
                telemetry.barrier(out)
                return out, rows_broadcast, capacity
            if capacity >= max(padded_m, 1):
                raise RuntimeError(
                    "partitioned probe: capacity overflow at maximum"
                )
            capacity *= 2
            retries += 1


def _note_part_info(info, capacity, hot, rows_broadcast) -> None:
    """Accumulate one partitioned probe's outcome into the multiway
    join's shared *info* dict (the sharded-multiway contract, ISSUE 17):
    ``capacity`` is the max settled exchange capacity so far — a record
    only: every dimension's probe counts its own exchange — and the
    hot-routing tallies sum over dimensions (hot keys of EITHER
    dimension ride the broadcast tier; the tail crosses the exchange
    once per dimension over the original fact rows, never over a
    materialized intermediate)."""
    if info is None:
        return
    info["capacity"] = max(int(capacity), int(info.get("capacity") or 0))
    info["dims"] = info.get("dims", 0) + 1
    info["hot_keys"] = info.get("hot_keys", 0) + (
        int(hot.size) if hot is not None else 0
    )
    info["rows_broadcast"] = info.get("rows_broadcast", 0) + int(rows_broadcast)


def partitioned_probe_device(
    mesh: Mesh, qk: jax.Array, prepared, capacity: "int | None" = None,
    label: "str | None" = None, info: "dict | None" = None,
) -> Tuple[jax.Array, jax.Array]:
    """Device-resident narrow-key partitioned probe: *qk* (int32, -1 =
    invalid) stays on device end to end; answers come back as device
    arrays ready for the device fan-out expansion and fused gathers.

    Host syncs per call: one bounded hot-key sample with the route
    count beside it (one blocking read; the count sets the capacity,
    :func:`_exchange_capacity`), the broadcast tier's hit count where
    hot keys were found, and one overflow boolean per attempt only
    under a capacity the count does not guarantee.  *label*
    names the probed index in the skew-routing evidence
    (``csvplus_join_*`` counters, ``join:skew`` stage row).  *info*
    accumulates this probe's settled capacity and hot-routing split for
    the multiway join's cross-dimension sharing (:func:`_note_part_info`)."""
    n_shards = mesh.devices.size
    m = int(qk.shape[0])

    count = _route_count_spmd(mesh, qk, *prepared.splits)
    hot, hot_share, pair_max = _detect_hot(qk, n_shards, False, count)
    if hot is not None:
        from ..utils.observe import telemetry

        with telemetry.stage("join:broadcast", int(hot.size)) as _b:
            # the static lane width is the pow2 bucket of the hot COUNT
            # (shape-derived, log-bounded distinct values), never the
            # hot values themselves
            n_hot = _pow2(hot.size)
            (hot_vals,), hot_lo, hot_ct = _hot_answers_device(mesh, hot, prepared)
            _b["n_hot"] = n_hot
            telemetry.barrier((hot_vals, hot_lo, hot_ct))
    else:
        z = jnp.zeros(1, jnp.int32)
        hot_vals = hot_lo = hot_ct = z
        n_hot = 0
    capacity, capacity_from = _exchange_capacity(
        capacity, m, n_shards, pair_max, None if hot is None else hot_share
    )

    def launch(cap):
        return _probe_spmd_dev(
            mesh, n_shards, cap, n_hot, prepared.positional,
            qk, prepared.owner, prepared.lower, prepared.count, *prepared.splits,
            hot_vals, hot_lo, hot_ct,
        )

    out, rows_broadcast, cap_used = _retry_probe_device(
        mesh, m, capacity, capacity_from, pair_max, launch, prepared
    )
    if hot is not None:
        _note_skew(
            label, m, int(hot.size), rows_broadcast, cap_used,
            skew_threshold(n_shards),
        )
    _note_part_info(info, cap_used, hot, rows_broadcast)
    return out


def partitioned_probe_device_wide(
    mesh: Mesh,
    q_hi: jax.Array,
    q_lo: jax.Array,
    prepared,
    capacity: "int | None" = None,
    label: "str | None" = None,
    info: "dict | None" = None,
) -> Tuple[jax.Array, jax.Array]:
    """Device-resident wide-key (62-bit dual-lane) partitioned probe.
    Invalid probes carry (-1, -1) lanes."""
    n_shards = mesh.devices.size
    m = int(q_hi.shape[0])

    count = _route_count_spmd2(mesh, q_hi, q_lo, *prepared.splits)
    hot, hot_share, pair_max = _detect_hot((q_hi, q_lo), n_shards, True, count)
    if hot is not None:
        from ..utils.observe import telemetry

        with telemetry.stage("join:broadcast", int(hot.size)) as _b:
            n_hot = _pow2(hot.size)  # pow2 bucket: log-bounded statics
            (hot_hi, hot_lo_lane), hot_ans_lo, hot_ans_ct = (
                _hot_answers_device(mesh, hot, prepared)
            )
            _b["n_hot"] = n_hot
            telemetry.barrier((hot_hi, hot_lo_lane, hot_ans_lo, hot_ans_ct))
    else:
        z = jnp.zeros(1, jnp.int32)
        hot_hi = hot_lo_lane = hot_ans_lo = hot_ans_ct = z
        n_hot = 0
    capacity, capacity_from = _exchange_capacity(
        capacity, m, n_shards, pair_max, None if hot is None else hot_share
    )

    def launch(cap):
        return _probe_spmd_dev2(
            mesh, n_shards, cap, n_hot, q_hi, q_lo,
            *prepared.uniq, prepared.lower, prepared.count, *prepared.splits,
            hot_hi, hot_lo_lane, hot_ans_lo, hot_ans_ct,
        )

    out, rows_broadcast, cap_used = _retry_probe_device(
        mesh, m, capacity, capacity_from, pair_max, launch, prepared
    )
    if hot is not None:
        _note_skew(
            label, m, int(hot.size), rows_broadcast, cap_used,
            skew_threshold(n_shards),
        )
    _note_part_info(info, cap_used, hot, rows_broadcast)
    return out


@jax.jit
def broadcast_probe(index_keys, qk_sharded):
    """Small-build-side fast path: the sorted key array is replicated to
    every shard (the analogue of the reference keeping the whole index in
    memory) and each shard binary-searches its own row slice; XLA
    parallelizes over the row sharding with zero collectives in the probe
    itself."""
    lower = jnp.searchsorted(index_keys, qk_sharded, side="left")
    upper = jnp.searchsorted(index_keys, qk_sharded, side="right")
    counts = jnp.where(qk_sharded >= 0, upper - lower, 0)
    return lower.astype(jnp.int32), counts.astype(jnp.int32)
