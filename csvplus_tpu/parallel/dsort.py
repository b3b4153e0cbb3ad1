"""Distributed sample-sort over a device mesh (explicit, all_to_all).

The single-chip index build sorts code arrays with one ``lax.sort``
(ops/sort.py); over a GSPMD-sharded array XLA lowers that to a gather —
correct, but the whole array lands on every chip.  This module is the
explicit scale-out path (SURVEY §2 "distributed index build"): a classic
sample-sort whose only cross-chip traffic is one slot-aligned
``lax.all_to_all`` per lane, the same exchange shape the partitioned
join uses (pjoin.py).  ``ops/sort.py:sort_table`` routes mesh-sharded
tables through it (packed key codes as the sort key, the row
permutation as payload), so ``IndexOn``/``UniqueIndexOn`` over a
sharded table never replicate the full array.

Algorithm (SPMD under ``shard_map``, static shapes):

1. each shard sorts its local block (``lax.sort``);
2. every shard contributes an evenly-spaced sample of its block; an
   ``all_gather`` + sort of the (tiny) sample pool yields N-1 global
   splitters — the classic equal-depth histogram estimate;
3. each element routes to ``searchsorted(splitters, x)``; a stable sort
   by destination + rank scatter fills an ``(N, C)`` slot buffer that one
   ``all_to_all`` redistributes (payload and validity ride extra lanes);
4. each shard sorts what it received; invalid slots sort to the end.

The result is *range-partitioned and locally sorted*: shard i holds keys
``splitters[i-1] <= k < splitters[i]`` in sorted order — globally sorted
in shard-major read order, and exactly the layout the partitioned join's
build side wants.  A final device compaction (cumsum over the validity
lanes) packs the per-shard valid prefixes into the first ``n`` slots, so
consumers read a dense, globally sorted array without a host stitch.

Key widths mirror the join tiers: narrow keys are one int32 lane; wide
(<= 62-bit packed) keys travel as TWO nonnegative 31-bit lanes with
every comparison lexicographic over (hi, lo) — no x64 anywhere.

Capacity ``C`` is a static parameter; skewed inputs overflow (detected
on device, -1 counts lane) and the orchestrator retries with doubled
capacity after syncing ONE boolean, mirroring ``partitioned_probe``.

Stability: every sort is ``is_stable=True`` and equal keys route to one
destination shard, so the output permutation preserves source order
within equal-key groups — matching the host executor's stable sort.

Differential-tested against ``np.sort`` on the 8-device CPU mesh,
including heavy-skew inputs that exercise the retry and int64 packed
keys through the dual-lane exchange (tests/test_parallel.py).
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.recompile import register_kernel
from .mesh import row_spec

_MASK31 = np.int32((1 << 31) - 1)


def _dsort_shard_kernel(
    n_shards: int, capacity: int, samples: int, n_lanes: int, n_true: int,
    axes, *args
):
    """Per-shard body: local sort, splitter estimate, route, exchange,
    local sort of the received block.  *args* = (*key lanes, payload).

    Validity is tracked explicitly (an extra exchanged lane) rather than
    by a sentinel VALUE, so INT32_MAX is an ordinary sortable key: the
    orchestrator's padding is identified by global row position >=
    *n_true*, and in the final per-shard sort invalid entries order
    after every valid entry regardless of key value.
    """
    from ..ops.join import _searchsorted2

    lanes = args[:n_lanes]
    payload = args[n_lanes]
    m = lanes[0].shape[0]
    N, C, S = n_shards, capacity, samples

    # global positions identify the tail padding; the row dim shards
    # over the axes in mesh-major order (mesh.row_spec)
    flat = jnp.int32(0)
    for ax in axes:
        flat = flat * lax.axis_size(ax) + lax.axis_index(ax)
    my_pos = flat * m + jnp.arange(m, dtype=jnp.int32)
    valid_in = (my_pos < n_true).astype(jnp.int32)

    # 1. local sort (payload + validity ride along; invalid last per key)
    sorted_ops = lax.sort(
        lanes + (1 - valid_in, payload), num_keys=n_lanes + 1, is_stable=True
    )
    lanes_s = sorted_ops[:n_lanes]
    v_s = 1 - sorted_ops[n_lanes]
    p_s = sorted_ops[n_lanes + 1]

    # 2. evenly-spaced local sample -> replicated pool -> global splitters
    step = jnp.maximum(m // S, 1)
    take = jnp.minimum(
        jnp.arange(S, dtype=jnp.int32) * step + step // 2, m - 1
    )
    pools = []
    for lane in lanes_s:
        pool = jnp.take(lane, take, axis=0)
        for ax in axes:
            pool = lax.all_gather(pool, ax, tiled=True)
        pools.append(pool)
    pools = lax.sort(tuple(pools), num_keys=n_lanes, is_stable=True)
    total = pools[0].shape[0]
    # N-1 equal-depth splitters; shard i owns [splitters[i-1], splitters[i])
    cut = jnp.arange(1, N, dtype=jnp.int32) * (total // N)
    splitters = tuple(jnp.take(p, cut, axis=0) for p in pools)

    # 3. route by destination range (invalid rows go nowhere: dest N)
    if n_lanes == 1:
        dest = jnp.searchsorted(splitters[0], lanes_s[0], side="right")
    else:
        dest = _searchsorted2(
            splitters[0], splitters[1], lanes_s[0], lanes_s[1], side="right"
        )
    dest = jnp.where(v_s > 0, dest.astype(jnp.int32), N)
    pos = jnp.arange(m, dtype=jnp.int32)
    routed_ops = lax.sort(
        (dest,) + lanes_s + (p_s,), num_keys=1, is_stable=True
    )
    dest_s = routed_ops[0]
    lanes_r = routed_ops[1 : 1 + n_lanes]
    p_r = routed_ops[1 + n_lanes]
    routed = dest_s < N
    group_start = jnp.searchsorted(
        dest_s, jnp.arange(N + 1, dtype=jnp.int32), side="left"
    )
    rank = pos - group_start[dest_s]
    ok = routed & (rank < C)  # overflow -> counts lane -1, caller retries

    slot = jnp.where(ok, rank, C)
    safe_dest = jnp.minimum(dest_s, N - 1)
    bufs = []
    for lane in lanes_r + (p_r,):
        bufs.append(
            jnp.zeros((N, C), jnp.int32).at[safe_dest, slot].set(lane, mode="drop")
        )
    buf_v = jnp.zeros((N, C), jnp.int32).at[safe_dest, slot].set(1, mode="drop")
    overflow = jnp.any(routed & (rank >= C))

    # 4. one exchange per lane; then sort the received block (validity
    # first in the key: every real element precedes padding regardless
    # of key value — INT32_MAX included)
    recv = [
        lax.all_to_all(b, axes, split_axis=0, concat_axis=0, tiled=True).reshape(-1)
        for b in bufs
    ]
    rv = lax.all_to_all(
        buf_v, axes, split_axis=0, concat_axis=0, tiled=True
    ).reshape(-1)
    final = lax.sort(
        (1 - rv,) + tuple(recv[:n_lanes]) + (recv[n_lanes],),
        num_keys=1 + n_lanes,
        is_stable=True,
    )
    out_v = 1 - final[0]
    out_lanes = final[1 : 1 + n_lanes]
    out_p = final[1 + n_lanes]
    n_here = jnp.sum(rv)
    # all-overflow report rides the counts lane as -1
    n_here = jnp.where(overflow, jnp.int32(-1), n_here)
    return out_lanes + (out_p, out_v, n_here.reshape(1))


@register_kernel(
    "dsort.spmd",
    static_argnames=("mesh", "n_shards", "capacity", "samples", "n_lanes", "n_true"),
)
def _dsort_spmd(  # analysis: allow[JIT001] — arity fixed per pipeline shape
    mesh, n_shards, capacity, samples, n_lanes, n_true, lanes, payload
):
    """Jitted launcher: pad to mesh divisibility ON DEVICE, shard, run
    the SPMD kernel, compact the valid slots to the first *n_true*
    positions with a global cumsum — no host stitch."""
    m = lanes[0].shape[0]
    pad = (-m) % n_shards
    if pad:
        lanes = tuple(
            jnp.concatenate([l, jnp.full(pad, _MASK31, jnp.int32)]) for l in lanes
        )
        payload = jnp.concatenate([payload, jnp.full(pad, -1, jnp.int32)])
    sharding = NamedSharding(mesh, row_spec(mesh))
    lanes = tuple(jax.lax.with_sharding_constraint(l, sharding) for l in lanes)
    payload = jax.lax.with_sharding_constraint(payload, sharding)

    axes = tuple(mesh.axis_names)
    rows = P(axes)
    f = shard_map(
        partial(
            _dsort_shard_kernel, n_shards, capacity, samples, n_lanes, n_true, axes
        ),
        mesh=mesh,
        in_specs=(rows,) * (n_lanes + 1),
        out_specs=(rows,) * (n_lanes + 2) + (rows,),
    )
    out = f(*lanes, payload)
    out_lanes = out[:n_lanes]
    out_p = out[n_lanes]
    out_v = out[n_lanes + 1]
    counts = out[n_lanes + 2]

    # compaction: shard-major valid prefixes -> dense [0, n_true) range
    tgt = jnp.where(out_v > 0, jnp.cumsum(out_v) - 1, n_true)
    dense_lanes = tuple(
        jnp.zeros(n_true, jnp.int32).at[tgt].set(l, mode="drop") for l in out_lanes
    )
    dense_p = jnp.zeros(n_true, jnp.int32).at[tgt].set(out_p, mode="drop")
    return dense_lanes + (dense_p, jnp.any(counts < 0))


def _capacity_plan(n: int, n_shards: int, capacity: "int | None") -> Tuple[int, int, int]:
    """(initial capacity, max capacity, samples) for *n* global rows."""
    padded = n + ((-n) % n_shards)
    m_per_shard = max(padded // n_shards, 1)
    if capacity is None:
        # balanced routing sends ~m_per_shard/N to each destination; the
        # retry doubles toward the guaranteed-sufficient m_per_shard
        capacity = max(64, 4 * ((m_per_shard + n_shards - 1) // n_shards))
    capacity = 1 << (int(capacity) - 1).bit_length()
    cap_max = 1 << (m_per_shard - 1).bit_length()
    capacity = min(capacity, cap_max)
    samples = min(64, max(8, m_per_shard))
    return capacity, cap_max, samples


def distributed_sort_device(
    mesh: Mesh,
    lanes: Tuple[jax.Array, ...],
    payload: jax.Array,
    capacity: "int | None" = None,
) -> Tuple[Tuple[jax.Array, ...], jax.Array]:
    """Device-resident sample-sort: *lanes* (1 narrow int32 lane, or 2
    nonnegative 31-bit lanes in (hi, lo) order) and an int32 *payload*
    stay on device end to end; the only host sync is one overflow
    boolean per capacity retry.  Returns (sorted lanes, permuted
    payload) as dense device arrays of the input length."""
    from ..utils.observe import telemetry

    n_shards = mesh.devices.size
    n = int(lanes[0].shape[0])
    if n == 0:
        return lanes, payload
    capacity, cap_max, samples = _capacity_plan(n, n_shards, capacity)
    while True:
        out = _dsort_spmd(
            mesh, n_shards, capacity, samples, len(lanes), n, tuple(lanes), payload
        )
        telemetry.count_sync(1)
        if not bool(jax.device_get(out[-1])):  # one O(1) scalar sync/attempt
            return out[: len(lanes)], out[len(lanes)]
        if capacity >= cap_max:
            # C = m_per_shard always suffices (a source shard cannot send
            # more rows than it holds), so this is unreachable — guard
            # against a logic regression rather than a data shape
            raise RuntimeError("distributed_sort: capacity overflow at maximum")
        capacity *= 2


def distributed_sort(
    mesh: Mesh,
    values: np.ndarray,
    payload: "np.ndarray | None" = None,
    capacity: "int | None" = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Globally sort an int32 or int64 (<= 62-bit packed) value array
    (with an optional int32 payload permuted alongside) using the
    explicit sample-sort.

    Host-facing wrapper over :func:`distributed_sort_device`: int64
    keys travel as dual 31-bit lanes, exactly like the wide join tier.
    Returns ``(sorted_values, permuted_payload)``; when *payload* is
    None it is the sort permutation (original indices).
    """
    values = np.asarray(values)
    n = values.shape[0]
    if payload is None:
        payload = np.arange(n, dtype=np.int32)
    payload = np.asarray(payload)
    if payload.dtype != np.int32:
        # payloads are row ids; refuse loudly rather than truncate
        raise TypeError(
            f"distributed_sort: int32 payload required, got {payload.dtype}"
        )
    if n == 0:
        return values, payload
    rows = NamedSharding(mesh, row_spec(mesh)) if n % mesh.devices.size == 0 else None

    def put(a):
        return jax.device_put(a, rows) if rows is not None else jax.device_put(a)

    if values.dtype == np.int64:
        if (values < 0).any() or (values >= (1 << 62)).any():
            raise TypeError("distributed_sort: int64 keys must fit 62 bits")
        from .pjoin import split_lanes

        hi, lo = split_lanes(values)
        lanes, pays = distributed_sort_device(
            mesh, (put(hi), put(lo)), put(payload), capacity
        )
        out_hi, out_lo = (np.asarray(l) for l in lanes)
        vals = (out_hi.astype(np.int64) << 31) | out_lo
        return vals, np.asarray(pays)
    if values.dtype != np.int32:
        raise TypeError(
            f"distributed_sort: int32/int64 values required, got {values.dtype}"
        )
    lanes, pays = distributed_sort_device(mesh, (put(values),), put(payload), capacity)
    return np.asarray(lanes[0]), np.asarray(pays)
