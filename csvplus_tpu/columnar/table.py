"""HBM-resident columnar tables.

The reference's execution model is row dicts of strings (csvplus.go:59).
A TPU cannot chase per-row hash maps, so the device representation is
columnar and **dictionary-encoded**: each string column becomes

* ``dictionary`` — the column's unique values, sorted byte-
  lexicographically (host numpy array; UTF-8 byte order == code-point
  order, so this matches Go's ``strings.Compare`` sort semantics,
  csvplus.go:798);
* ``codes`` — ``int32[n]`` device array mapping row -> dictionary slot.
  Because the dictionary is sorted, code order == string order, so
  sorts, range searches and equality tests all run on the MXU/VPU as
  integer ops.  Code ``-1`` marks an absent cell (rows in an Index may
  have heterogeneous schemas after Transform stages).

Predicates, joins and sorts run entirely over the code arrays on device;
strings are only materialized back on the host at the sink boundary.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..obs.recompile import register_kernel
from ..obs.span import tracer
from ..row import Row
from ..utils.env import env_int
from ..utils.observe import telemetry

ABSENT = np.int32(-1)


def default_device(device=None):
    """Resolve a device spec to a jax.Device: ``None`` is the default
    backend's first device, a platform name ("tpu", "cpu") is that
    platform's first device, a jax.Device passes through.  A named
    platform JAX cannot supply raises (jax's own RuntimeError) — a
    caller that asked for a chip never runs on the CPU unannounced."""
    if device is None:
        return jax.devices()[0]
    if isinstance(device, str):
        return jax.devices(device)[0]
    return device  # already a jax.Device


def _to_bytes_array(values) -> np.ndarray:
    """UTF-8 encode a sequence/array of str into an 'S' bytes array."""
    arr = np.asarray(values, dtype=np.str_)
    return np.char.encode(arr, "utf-8")


def encode_strings(values: Sequence[str]) -> "tuple[np.ndarray, np.ndarray]":
    """Dictionary-encode a string column: (sorted unique values, int32 codes).

    Dictionaries are stored as UTF-8 **bytes** ('S' dtype): numpy bytes
    comparison is memcmp, i.e. exactly Go's ``strings.Compare`` byte-
    lexicographic order (csvplus.go:798), and it sidesteps per-entry
    Python string objects on the ingest path.  ``None`` entries (absent
    cells) encode as code -1 and do not enter the dictionary.
    """
    if isinstance(values, np.ndarray) and values.dtype.kind in ("U", "S"):
        # numpy string arrays cannot hold None: skip the per-element scan
        arr_b = values if values.dtype.kind == "S" else np.char.encode(values, "utf-8")
        dictionary, codes = np.unique(arr_b, return_inverse=True)
        return dictionary, codes.astype(np.int32)
    arr = np.asarray(values, dtype=object)
    present = np.array([v is not None for v in arr], dtype=bool)
    if present.all():
        dictionary, codes = np.unique(_to_bytes_array(values), return_inverse=True)
        return dictionary, codes.astype(np.int32)
    codes = np.full(len(arr), ABSENT, dtype=np.int32)
    if present.any():
        present_vals = _to_bytes_array([v for v in arr if v is not None])
        dictionary, inv = np.unique(present_vals, return_inverse=True)
        codes[present] = inv.astype(np.int32)
    else:
        dictionary = np.empty(0, dtype="S1")
    return dictionary, codes


def lookup_code(dictionary: np.ndarray, value: str) -> int:
    """Dictionary slot of *value*, or -1 when absent (host binary search)."""
    if dictionary.size == 0:
        return -1
    key = value.encode("utf-8") if dictionary.dtype.kind == "S" else value
    i = int(np.searchsorted(dictionary, key))
    if i < dictionary.size and dictionary[i] == key:
        return i
    return -1


class _LaneState:
    """Shared mutable state of one device-lane dictionary.

    ``with_codes``/``gather``/``with_sharding`` copies of a column all
    point at the SAME state, so the deferred union sort
    (:meth:`StringColumn._ensure_sorted_lanes`) runs once globally:
    after the first settle, ``trans`` (old slot -> sorted slot) lets
    every other copy remap its codes with one cheap gather instead of
    re-sorting the full dictionary."""

    __slots__ = ("lanes", "sorted", "trans", "host", "lock")

    def __init__(self, lanes: tuple, sorted_: bool):
        self.lanes = lanes
        self.sorted = sorted_
        self.trans = None
        # the settled lanes unpacked on the host, once a copy has read
        # ``.dictionary``: every other copy (a join's gathered result
        # column is a new one per execution) reads the same array
        self.host = None
        # sibling copies may settle concurrently (ingest runs a prefetch
        # producer thread plus encode pools); the union sort + remap must
        # be serialized so it runs once and trans is never read half-set
        self.lock = threading.Lock()


class StringColumn:
    """One dictionary-encoded string column.

    (See :class:`_LaneState` for the shared deferred-sort state of
    device-lane dictionaries.)

    The dictionary normally lives on host (sorted 'S' bytes).  HIGH-
    CARDINALITY columns may instead carry it on DEVICE as sign-flipped
    int32 byte lanes (ops/lanes.py) with ``dictionary=None``: host RSS
    then stays bounded through ingest and through every code-only
    operation (sorts, filters, joins via lane translation).  Reading
    ``.dictionary`` on such a column lazily downloads and unpacks the
    lanes — the sink-boundary cost, paid only when strings are actually
    materialized.
    """

    def __init__(
        self,
        dictionary: "np.ndarray | None",  # sorted 'S' bytes, host (or None)
        codes: jax.Array,  # int32[n] on device; -1 = absent cell
        _has_absent: "bool | None" = None,  # lazy cache: any absent cells?
        _str_dict: "np.ndarray | None" = None,  # lazy cache: decoded dict
        _codes_host: "np.ndarray | None" = None,  # lazy cache: host codes
        dev_dictionary: "tuple | None" = None,  # int32 lanes, device
        dev_dict_sorted: bool = True,  # False: unsorted concat, may hold dups
        _lane_state: "_LaneState | None" = None,  # share with sibling copies
    ):
        assert dictionary is not None or dev_dictionary is not None or (
            _lane_state is not None
        )
        self._dictionary = dictionary
        self._has_absent = _has_absent
        self._str_dict = _str_dict
        self._codes_host = _codes_host
        # streamed ingest defers the global dictionary sort: an UNSORTED
        # lane dictionary (concatenated chunk dictionaries, codes offset
        # per chunk) decodes/gathers/checksums fine, but anything that
        # relies on code order == value order or one-value-one-code
        # (find_code, joins, sorts, host materialization, persistence)
        # must call _ensure_sorted_lanes() first.  The lane state is
        # SHARED between with_codes/gather/with_sharding copies so the
        # global sort runs once; each copy then remaps its own codes
        # with one cheap gather.
        if _lane_state is not None:
            self._lane_state = _lane_state
        elif dev_dictionary is not None:
            self._lane_state = _LaneState(dev_dictionary, dev_dict_sorted)
        else:
            self._lane_state = None
        # (codes, dev_dict_sorted) publish as ONE tuple: the flag is True
        # when the codes index the CURRENT (settled) lane order, and a
        # concurrent reader (with_codes/gather/with_sharding copying a
        # column while a sibling settles on another thread) must never
        # see a remapped codes array paired with a stale flag — a single
        # attribute read is atomic under the GIL, two are not.
        self._codes_state = (
            codes,
            dev_dict_sorted if self._lane_state is not None else True,
        )

    kind = "str"

    @property
    def codes(self) -> jax.Array:
        return self._codes_state[0]

    @property
    def storage(self) -> jax.Array:
        """The kind-agnostic row-indexed device array (shared protocol
        with :class:`~csvplus_tpu.columnar.typed.IntColumn`): dictionary
        codes here, int32 value lanes there.  Row-materializing consumers
        (gathers, sorts' payload permutation, sync) use this so a typed
        payload column is never demoted just to ride along."""
        return self.codes

    def with_storage(self, arr) -> "StringColumn":
        return self.with_codes(arr)

    @property
    def _dev_dict_sorted(self) -> bool:
        return self._codes_state[1]

    @property
    def dev_dictionary(self) -> "tuple | None":
        """The device lane dictionary, coherent with ``self.codes``: if a
        sibling copy already settled the shared state, this column's
        codes remap (cheap gather, no sort) before the lanes are
        exposed."""
        st = self._lane_state
        if st is None:
            return None
        if self._dev_dict_sorted:
            # coherent and FINAL: either the state was born sorted or this
            # copy already remapped; settled lanes never change again
            return st.lanes
        with st.lock:
            # under the lock no sibling can be mid-settle: either the
            # state is still the unsorted concat (coherent with our
            # codes) or it settled completely and we remap before
            # exposing the sorted lanes
            if st.sorted:
                self._settle_locked(st)  # remap-only: the sort already ran
            return st.lanes

    @property
    def dictionary(self) -> np.ndarray:
        """The host dictionary — lazily materialized (download + unpack)
        for device-lane columns, then cached."""
        if self._dictionary is None:
            st = self._lane_state
            if st.host is None:
                from ..ops.lanes import unpack_host

                # once per shared lane state: a milestone of the process
                # journal (``obs/span.py``) — the deferred sort, then the
                # download
                with tracer.milestone(
                    "lane-dict:materialize", entries=int(st.lanes[0].shape[0])
                ):
                    self._ensure_sorted_lanes()
                    with st.lock:  # settled lanes never change again
                        if st.host is None:
                            st.host = unpack_host([np.asarray(l) for l in st.lanes])
            else:  # a sibling paid the download: this copy's codes settle alone
                self._ensure_sorted_lanes()
            self._dictionary = st.host
        return self._dictionary

    def _ensure_sorted_lanes(self) -> None:
        """Sort + dedupe a deferred (unsorted-concat) lane dictionary ON
        DEVICE and remap this column's codes to the dense sorted slots —
        the lazy form of the streamed tier's dictionary union.  The sort
        runs ONCE per shared lane state (copies remap with one gather);
        columns only ever decoded/gathered/checksummed via the shared
        state never pay it (the round-4 northstar profile's dominant
        ingest cost was exactly this sort, paid eagerly for a payload
        column that never needed it)."""
        st = self._lane_state
        if st is None or self._dev_dict_sorted:
            return
        with st.lock:
            self._settle_locked(st)

    def _settle_locked(self, st: "_LaneState") -> None:
        """Settle the shared state (once) and remap this copy's codes.
        Caller must hold ``st.lock``."""
        if self._dev_dict_sorted:  # a sibling settled us meanwhile
            return
        from ..utils.observe import telemetry

        if not st.sorted:
            from ..ops.lanes import union_device

            with telemetry.stage(
                "lane-dict:deferred-sort", int(st.lanes[0].shape[0])
            ):
                union, (trans,) = union_device([st.lanes])
                # st.sorted is the publication flag: assign it LAST so a
                # racing reader can never see sorted lanes before the
                # translation table exists
                st.trans = trans
                st.lanes = union
                st.sorted = True
        trans = st.trans
        sh = getattr(self.codes, "sharding", None)
        if sh is not None and len(getattr(sh, "device_set", ())) > 1:
            # mesh-sharded codes: replicate the translation table onto
            # the codes' mesh so the remap gather is placement-legal
            trans = jax.device_put(
                trans,
                jax.sharding.NamedSharding(
                    sh.mesh, jax.sharding.PartitionSpec()
                ),
            )
        codes = self._codes_state[0]
        remapped = jnp.where(
            codes >= 0,
            jnp.take(trans, jnp.clip(codes, 0), axis=0),
            codes,
        )
        self._codes_host = None  # host mirror (if any) is stale
        # one atomic publication: remapped codes + settled flag together
        self._codes_state = (remapped, True)

    @property
    def dict_size(self) -> int:
        """Dictionary slot count WITHOUT forcing host materialization.
        Equals the distinct-value count once the lane state is settled;
        a DEFERRED (unsorted-concat) lane dictionary may overcount
        (duplicates across chunks) — code-order consumers settle via
        :meth:`_ensure_sorted_lanes` before sizing bit packs from this."""
        if self._dictionary is not None:
            return int(self._dictionary.size)
        return int(self._lane_state.lanes[0].shape[0])

    def find_code(self, value: str) -> int:
        """Dictionary slot of *value* or -1 — the device lane search for
        lane columns (search + verification fused in one jitted kernel,
        ONE scalar sync, no dictionary download), the host binary search
        otherwise."""
        if self._dictionary is not None:
            return lookup_code(self._dictionary, value)
        from ..ops.lanes import (
            MAX_LANE_BYTES,
            lanes_for_width,
            pack_host,
            translate_lanes,
        )

        key = value.encode("utf-8")
        if len(key) > MAX_LANE_BYTES:
            return -1  # wider than any lane-dictionary entry can be
        self._ensure_sorted_lanes()  # the lane search needs sorted order
        n_lanes = len(self.dev_dictionary)
        if lanes_for_width(len(key)) > n_lanes:
            return -1  # longer than every stored entry: cannot match
        q = pack_host(np.array([key], dtype="S"), n_lanes)
        qs = tuple(jnp.asarray(l) for l in q)
        return int(translate_lanes(self.dev_dictionary, qs)[0])

    def find_codes(self, values: Sequence[str]) -> np.ndarray:
        """Vectorized :meth:`find_code` over a batch of probe values —
        int64 codes, -1 where the value is not in the dictionary.

        One ``np.searchsorted`` over the host dictionary (or ONE jitted
        lane translation for device-lane dictionaries), instead of a
        binary search + device dispatch per probe: the per-column half
        of the batched lookup engine (``DeviceIndex.point_bounds_many``).
        """
        m = len(values)
        if m == 0:
            return np.empty(0, dtype=np.int64)
        if self._dictionary is not None:
            d = self._dictionary
            if d.size == 0:
                return np.full(m, -1, dtype=np.int64)
            if d.dtype.kind == "S":
                enc = np.array([v.encode("utf-8") for v in values], dtype="S")
            else:
                enc = np.asarray(values, dtype=d.dtype)
            pos = np.searchsorted(d, enc)
            pos_c = np.clip(pos, 0, d.size - 1)
            ok = d[pos_c] == enc
            return np.where(ok, pos_c, -1).astype(np.int64)
        from ..ops.lanes import (
            MAX_LANE_BYTES,
            lanes_for_width,
            pack_host,
            translate_lanes,
        )

        self._ensure_sorted_lanes()  # the lane search needs sorted order
        n_lanes = len(self.dev_dictionary)
        out = np.full(m, -1, dtype=np.int64)
        keys = [v.encode("utf-8") for v in values]
        # values wider than any stored entry can never match; translate
        # only the rest, in ONE fused device search over all of them
        fit = [
            i
            for i, k in enumerate(keys)
            if len(k) <= MAX_LANE_BYTES and lanes_for_width(len(k)) <= n_lanes
        ]
        if fit:
            sub = np.array([keys[i] for i in fit], dtype="S")
            q = pack_host(sub, n_lanes)
            qs = tuple(jnp.asarray(l) for l in q)
            out[fit] = np.asarray(translate_lanes(self.dev_dictionary, qs))
        return out

    @property
    def has_absent(self) -> bool:
        """True when any cell is absent (one cached scalar device sync).

        Columns parsed from CSV never have absent cells; only tables
        columnarized from heterogeneous rows do, so most paths skip the
        per-cell presence work entirely.
        """
        if self._has_absent is None:
            # absent is exactly -1; sharding pad rows use -2 and must not
            # defeat this fast path
            self._has_absent = bool(jnp.any(self.codes == ABSENT))
        return self._has_absent

    @classmethod
    def from_values(cls, values: Sequence[str], device) -> "StringColumn":
        dictionary, codes = encode_strings(values)
        # The encoder just saw every cell: record absence while it is a
        # free host scan.  A definite ``False`` here is what lets the
        # verifier prove columns PRESENT — the presence obligations the
        # plan rewriter's pushdown proofs consume (analysis/rewrite.py).
        has_absent = bool(codes.size) and bool(codes.min() < 0)
        return cls(
            dictionary, jax.device_put(codes, device), _has_absent=has_absent
        )

    @classmethod
    def constant(cls, value: str, n: int, device) -> "StringColumn":
        return cls(
            np.asarray([value.encode("utf-8")], dtype="S"),
            jax.device_put(np.zeros(n, dtype=np.int32), device),
        )

    def codes_host(self) -> np.ndarray:
        """Host mirror of the code array (one transfer, cached).

        Point-lookup paths (Index.find on a device-lazy index) decode
        matched ranges from this mirror in host numpy: one O(n) transfer
        buys microsecond lookups, instead of a device gather + download
        round trip per find."""
        if self._codes_host is None:
            self._ensure_sorted_lanes()  # mirror must be post-remap
            self._codes_host = np.asarray(self.codes)
        return self._codes_host

    def dictionary_str(self) -> np.ndarray:
        """The dictionary as python-str values (decoded lazily, cached)."""
        if self._str_dict is None:
            d = self.dictionary
            if d.dtype.kind == "S":
                self._str_dict = (
                    np.char.decode(d, "utf-8") if d.size else np.empty(0, np.str_)
                )
            else:
                self._str_dict = d
        return self._str_dict

    def __len__(self) -> int:
        return int(self.codes.shape[0])

    def with_codes(self, codes, dev_dict_sorted: "bool | None" = None) -> "StringColumn":
        """A column over *codes* carrying this column's dictionary and
        caches — the single definition of what survives a row gather:
        the decoded-dictionary cache always, and has_absent only when
        this column is known fully present (a subset of a fully-present
        column is fully present).

        *dev_dict_sorted* must be the flag snapshotted TOGETHER with the
        codes array the caller derived *codes* from (``_codes_state``);
        omitting it reads the current flag, which is only safe when no
        concurrent settle is possible (executor ops on already-settled
        columns — sorts/joins require code order, so their inputs have
        settled before they run)."""
        out = StringColumn(
            self._dictionary,
            codes,
            dev_dict_sorted=(
                self._dev_dict_sorted if dev_dict_sorted is None else dev_dict_sorted
            ),
            _lane_state=self._lane_state,
        )
        out._str_dict = self._str_dict
        if self._has_absent is False:
            out._has_absent = False
        return out

    def gather(self, sel, codes=None) -> "StringColumn":
        """New column of the selected row positions (device gather).

        *codes* substitutes a differently-placed copy of this column's
        codes (e.g. replicated onto the probe's mesh) — the dictionary
        and caches still come from self."""
        if codes is None:
            src, flag = self._codes_state  # one atomic coherent pair
        else:
            src, flag = codes, self._dev_dict_sorted
        idx = jnp.asarray(sel, dtype=jnp.int32)
        return self.with_codes(_gather_take(src, idx), dev_dict_sorted=flag)

    def decode_codes(self, codes: np.ndarray) -> List[Optional[str]]:
        """Decode a host code slice against this column's dictionary;
        absent cells (negative codes, incl. the -2 sharding pad) become
        None.  The single definition of host-side code decoding, shared
        by :meth:`decode` and :meth:`DeviceTable.rows_from_mirror`.

        CALLER CONTRACT: *codes* must be snapshotted AFTER
        ``_ensure_sorted_lanes()`` (``decode``/``codes_host`` do this),
        because the deferred lane-dictionary sort remaps the code space.

        Small slices (point lookups) decode only the matched dictionary
        entries: decoding a 1M-entry dictionary to serve a 10-row
        ``Index.find`` cost ~1.3s of one-time work and was the round-3
        "device find 665 lookups/s" bottleneck."""
        if self.dict_size == 0:
            return [None] * codes.shape[0]
        if self._str_dict is None and codes.shape[0] * 16 < self.dict_size:
            d = self.dictionary
            sel = d[np.clip(codes, 0, d.size - 1)]
            if d.dtype.kind == "S":
                out = [v.decode("utf-8") for v in sel.tolist()]
            else:
                out = sel.tolist()
            if (codes < 0).any():
                out = [None if c < 0 else v for c, v in zip(codes.tolist(), out)]
            return out
        d = self.dictionary_str()
        vals = d[np.clip(codes, 0, d.size - 1)]
        out = vals.tolist()
        if (codes < 0).any():
            out = [None if c < 0 else v for c, v in zip(codes.tolist(), out)]
        return out

    # the kind-agnostic name (shared protocol with IntColumn): decode a
    # host copy of ``storage`` values
    decode_storage = decode_codes

    def decode(self) -> List[Optional[str]]:
        """Materialize values on host; absent cells become None."""
        self._ensure_sorted_lanes()  # BEFORE the code snapshot below
        return self.decode_codes(np.asarray(self.codes))

    def _lanes_narrow(self) -> "tuple":
        """``(lane tuple, original-slot positions | None)`` — this
        dictionary as device lanes, restricted to entries narrow enough
        to lane-pack.  A host dictionary mixed into a lane-column join
        may hold values wider than MAX_LANE_BYTES; those can never equal
        any lane entry, so they are excluded here (positions returned so
        the caller can remap subset slots back to full slots) instead of
        failing the whole join."""
        if self.dev_dictionary is not None:
            self._ensure_sorted_lanes()  # translation assumes sorted lanes
            return self.dev_dictionary, None
        from ..ops.lanes import MAX_LANE_BYTES, lanes_for_width, pack_host

        d = self._dictionary
        width = d.dtype.itemsize if d.size else 1
        lanes = lanes_for_width(width)
        if lanes is not None:
            return tuple(jax.device_put(l) for l in pack_host(d, lanes)), None
        # host dictionaries are always 'S' bytes arrays (encode_strings
        # invariant), so byte lengths come straight from str_len
        keep = np.char.str_len(d) <= MAX_LANE_BYTES
        pos = np.flatnonzero(keep).astype(np.int32)
        sub = d[keep].astype(f"S{MAX_LANE_BYTES}")
        lanes = lanes_for_width(MAX_LANE_BYTES)
        return tuple(jax.device_put(l) for l in pack_host(sub, lanes)), pos

    def code_translation_to(self, other: "StringColumn") -> "jax.Array | None":
        """``trans[code]`` = *other*'s code of the same value, -1 where
        it has none, over this column's dictionary — the device lane
        translation when either side keeps its dictionary on device (no
        host materialization), the host table otherwise.  Host
        dictionaries with entries wider than a lane can hold are handled
        by translating the narrow subset and treating wide values as
        no-match.  None when this column's dictionary is empty (its
        codes are all negative and answer for themselves).  Settles a
        deferred lane dictionary: read ``self.codes`` AFTER this call."""
        if self.dev_dictionary is None and other.dev_dictionary is None:
            return self._host_translation(other.dictionary)
        from ..ops.lanes import translate_lanes

        if self.dict_size == 0:
            return None
        q_lanes, q_pos = self._lanes_narrow()
        b_lanes, b_pos = other._lanes_narrow()
        if b_lanes[0].shape[0] == 0 or q_lanes[0].shape[0] == 0:
            return jnp.full(self.dict_size, ABSENT, jnp.int32)
        trans = translate_lanes(b_lanes, q_lanes)
        if b_pos is not None:
            # subset slots of other -> other's full code space
            trans = jnp.where(
                trans >= 0,
                jnp.take(jnp.asarray(b_pos), jnp.clip(trans, 0), axis=0),
                -1,
            )
        if q_pos is not None:
            # scatter subset results back over self's full dictionary;
            # wide entries stay -1 (no-match)
            trans = (
                jnp.full(self.dict_size, -1, jnp.int32)
                .at[jnp.asarray(q_pos)]
                .set(trans)
            )
        return trans

    def _host_translation(self, other_dictionary: np.ndarray) -> "jax.Array | None":
        """Host form of :meth:`code_translation_to`: one binary search of
        this column's dictionary in *other_dictionary*, uploaded."""
        if self.dictionary.size == 0:
            return None
        pos = np.searchsorted(other_dictionary, self.dictionary)
        pos = np.clip(pos, 0, max(other_dictionary.size - 1, 0))
        ok = (
            other_dictionary[pos] == self.dictionary
            if other_dictionary.size
            else np.zeros(self.dictionary.size, dtype=bool)
        )
        return jnp.asarray(jax.device_put(np.where(ok, pos, -1).astype(np.int32), None))

    def renumbered_to_col(self, other: "StringColumn") -> jax.Array:
        """Translate this column's codes into *other*'s code space (one
        walk over the rows; see :meth:`code_translation_to` for the
        table).  Unmatched becomes -1; negative codes pass through
        unchanged (-1 absent stays -1, -2 sharding pads stay -2)."""
        trans = self.code_translation_to(other)
        if trans is None:
            return self.codes
        return _apply_code_translation(self.codes, trans)

    def renumbered_to(self, other_dictionary: np.ndarray) -> jax.Array:
        """Translate this column's codes into another dictionary's code
        space (host translation table + device gather); unmatched -> -1,
        negative codes unchanged."""
        trans = self._host_translation(other_dictionary)
        if trans is None:
            return self.codes
        return _apply_code_translation(self.codes, trans)


@register_kernel("table.gather_take")
def _gather_take(storage: jax.Array, idx: jax.Array) -> jax.Array:
    """``storage[idx]``: every column gather (a lookup's rows, a sort's
    permutation, a selection) as one named program instead of an eager
    ``jnp.take``, which a device profile shows as ``jit__take``."""
    return jnp.take(storage, idx, axis=0)


@register_kernel("table.gather_take_rows")
def _gather_take_rows(head: jax.Array, lanes: Tuple[jax.Array, ...]) -> jax.Array:  # analysis: allow[JIT001] — arity is the table's column count
    """A lookup batch's rows in one program and one array: *head* is
    ``int32[h, B]`` whose first row holds one row position per query (a
    bounds search's ``lower`` left on the device with its ``upper``
    beneath it, or positions the host formed), *lanes* every column's
    row-indexed ``storage``.  Returns ``int32[h + len(lanes), B]``:
    *head* as it came, then per lane its values at those positions.  A
    position one past the last row (the ``lower`` of a probe beyond the
    last key) reads the last row; the caller drops it by its bounds."""
    rows = head[0]
    return jnp.concatenate(
        [head] + [jnp.take(lane, rows, axis=0, mode="clip")[None] for lane in lanes]
    )


@register_kernel("table.apply_code_translation")
def _apply_code_translation(codes: jax.Array, trans: jax.Array) -> jax.Array:
    """``trans[codes]`` with negative codes passed through unchanged —
    one fused kernel instead of three eager passes (the translation runs
    per probe execution on the warm-join path)."""
    return jnp.where(
        codes >= 0, jnp.take(trans, jnp.clip(codes, 0), axis=0), codes
    )


@register_kernel("table.sync_probe")
def _sync_probe(*code_arrays: jax.Array) -> jax.Array:
    """sum(first element of each array) — a one-scalar dependency on all."""
    return sum(a[0].astype(jnp.int32) for a in code_arrays)


def same_placement(arrays) -> bool:
    """True when every array commits to the same device set (safe to
    pass together into one jitted computation)."""
    first = None
    for a in arrays:
        sh = getattr(a, "sharding", None)
        if sh is None:
            return False
        ds = frozenset(sh.device_set)
        if first is None:
            first = ds
        elif ds != first:
            return False
    return True


def merge_with_fallback(primary: StringColumn, fallback: StringColumn) -> StringColumn:
    """Cell-wise merge: primary's value where present, else fallback's.

    This is the columnar form of the reference's row merge on column-name
    collision (csvplus.go:571-583): the stream (primary) value wins, but a
    stream row *without* the cell keeps the index (fallback) value.
    Both columns are recoded into the union dictionary first.
    """
    if not primary.has_absent:  # one cached scalar sync, no O(n) transfer
        return primary
    union = np.union1d(primary.dictionary, fallback.dictionary)
    p = primary.renumbered_to(union)
    f = fallback.renumbered_to(union)
    return StringColumn(union, jnp.where(p >= 0, p, f))


class DeviceTable:
    """An ordered set of equal-length columns resident on one device.

    ``row_base`` is the source row number of table row 0, in the
    originating source's numbering convention (2 for a Reader ingest of a
    file with a header row, 1 for a headerless one, 0 for in-memory rows
    — matching the host paths' ``DataSourceError`` numbering).  It is
    only meaningful while row i of the table still IS source row i;
    executor stages that reorder or drop rows reset it to 0.
    """

    def __init__(
        self, columns: Dict[str, StringColumn], nrows: int, device, row_base: int = 0
    ):
        self.columns = columns
        self.nrows = nrows
        self.device = device
        self.row_base = row_base
        # serializes the mirror-decode LRU (rows_from_mirror_many): the
        # serving tier made concurrent lookups real, and an OrderedDict
        # being reordered (move_to_end) while another thread inserts or
        # evicts corrupts it — even cache HITS mutate recency order, so
        # every access must hold this
        self._mirror_lock = threading.Lock()

    @classmethod
    def from_pylists(
        cls, data: Dict[str, Sequence[str]], device=None
    ) -> "DeviceTable":
        dev = default_device(device)
        cols = {}
        nrows = 0
        for name, values in data.items():
            cols[name] = StringColumn.from_values(values, dev)
            nrows = len(values)
        return cls(cols, nrows, dev)

    @classmethod
    def from_encoded(
        cls,
        data: "Dict[str, tuple[np.ndarray, np.ndarray]]",
        nrows: int,
        device=None,
    ) -> "DeviceTable":
        """Build from already dictionary-encoded columns
        ((dictionary, codes) pairs, e.g. the native ingest fast path;
        a ready StringColumn — e.g. a device-lane-dictionary column from
        the streamed ingest — passes through unchanged)."""
        from .typed import IntColumn

        dev = default_device(device)
        cols = {}
        for name, value in data.items():
            if isinstance(value, (StringColumn, IntColumn)):
                cols[name] = value
                continue
            if len(value) == 3 and value[0] == "int":
                # typed value lanes from the native/streamed scanners
                _, prefix, vals = value
                cols[name] = IntColumn(
                    prefix,
                    vals
                    if isinstance(vals, jax.Array)
                    else jax.device_put(vals, dev),
                )
                continue
            dictionary, codes = value
            cols[name] = StringColumn(
                dictionary,
                codes if isinstance(codes, jax.Array) else jax.device_put(codes, dev),
            )
        return cls(cols, nrows, dev)

    @classmethod
    def from_rows(cls, rows: Sequence[Row], device=None) -> "DeviceTable":
        """Columnarize possibly-heterogeneous rows; missing cells -> absent."""
        names: List[str] = []
        seen = set()
        for r in rows:
            for k in r:
                if k not in seen:
                    seen.add(k)
                    names.append(k)
        data = {n: [r.get(n) for r in rows] for n in names}
        t = cls.from_pylists(data, device)
        t.nrows = len(rows)
        return t

    def column_names(self) -> List[str]:
        return list(self.columns)

    def with_sharding(self, mesh) -> "DeviceTable":
        """Re-lay every code array row-sharded over *mesh* (GSPMD).

        All executor ops (masks, gathers, sorts, probes) are jnp ops, so
        once the codes carry a ``NamedSharding`` XLA partitions the whole
        pipeline data-parallel and inserts collectives where gathers or
        sorts cross shards — the "pick a mesh, annotate shardings, let
        XLA insert collectives" recipe.  The explicit ``shard_map``
        partitioned join (csvplus_tpu/parallel/pjoin.py) remains the
        hand-optimized path for very large build sides.
        """
        from jax.sharding import NamedSharding
        from ..parallel.mesh import row_spec

        sharding = NamedSharding(mesh, row_spec(mesh))
        n_dev = mesh.devices.size
        pad = (-self.nrows) % n_dev  # NamedSharding needs divisibility
        cols = {}
        from .typed import IntColumn

        for name, col in self.columns.items():
            if isinstance(col, IntColumn):
                from .typed import PAD_VALUE

                vals = np.asarray(col.values)
                if pad:
                    # PAD_VALUE can never be a real cell (the parser
                    # bounds |v| <= INT32_MAX), so pad rows stay
                    # unambiguous through translations and demotion
                    vals = np.concatenate(
                        [vals, np.full(pad, PAD_VALUE, np.int32)]
                    )
                cols[name] = IntColumn(col.prefix, jax.device_put(vals, sharding))
                continue
            src_codes, dict_sorted = col._codes_state  # atomic coherent pair
            codes = np.asarray(src_codes)
            if pad:
                # -2 = padding (never matches; distinct from -1 = absent);
                # padding rows live beyond nrows, outside every selection
                codes = np.concatenate(
                    [codes, np.full(pad, -2, dtype=np.int32)]
                )
            moved = StringColumn(
                col._dictionary,
                jax.device_put(codes, sharding),
                dev_dict_sorted=dict_sorted,
                _lane_state=col._lane_state,
            )
            moved._str_dict = col._str_dict
            moved._has_absent = col._has_absent if not pad else None
            cols[name] = moved
        return DeviceTable(cols, self.nrows, mesh.devices.flat[0], self.row_base)

    def short_desc(self) -> str:
        return f"{self.nrows}x{len(self.columns)}[{','.join(self.columns)}]"

    def sync(self) -> "DeviceTable":
        """Force completion of every column with ONE scalar round trip.

        Per-column ``block_until_ready`` costs one readiness ping per
        buffer.  Instead, dispatch a trivial reduction that depends on
        every code array and sync its single scalar — it cannot complete
        before all inputs have.
        """
        cols = [c.storage for c in self.columns.values()]
        cols = [c for c in cols if c.shape[0]]
        if not cols:
            return self
        if same_placement(cols):
            int(_sync_probe(*cols))
        else:
            # mixed placements (e.g. a join of a single-device build table
            # into a mesh-sharded stream) cannot share one jitted call
            for c in cols:
                c.block_until_ready()
        return self

    def gather(self, sel) -> "DeviceTable":
        cols = {n: c.gather(sel) for n, c in self.columns.items()}
        return DeviceTable(cols, int(len(sel)), self.device)

    def to_rows(self, sel=None) -> List[Row]:
        """Decode (a selection of) the table back into host Rows; absent
        cells are omitted from their row, matching the host path's
        heterogeneous dicts."""
        if sel is None:
            with tracer.span("serve:gather:rows"):
                return self._rows_of(
                    [c.decode() for c in self.columns.values()], self.nrows
                )
        # row positions the host formed (``Index.rows_for_bounds`` past
        # the mirror cap where the bounds had to be read): one upload of
        # the positions, padded to their power-of-two bucket so that a
        # length compiles once a bucket, one program over every column,
        # one blocking read
        k = int(len(sel))
        if k == 0:
            return []
        head = np.zeros((1, 1 << max(k - 1, 0).bit_length()), dtype=np.int32)
        head[0, :k] = sel
        got = self.take_rows(head)
        return self.decode_rows(got[1:, :k])

    def take_rows(self, head) -> np.ndarray:
        """``csvplus.table.gather_take_rows`` over every column at the
        positions in *head*'s first row, read back in ONE blocking read
        (one per device set where a joined table's columns lie on
        several and cannot enter one program).  The read is the batch's
        only one (``one_trip``) where *head* is a search's answer still
        on the device, its second where the host formed *head*."""
        cols = list(self.columns.values())
        with tracer.span("serve:gather:take") as span:
            for c in cols:
                c._ensure_sorted_lanes()  # read the final codes, as decode() does
            lanes = tuple(c.storage for c in cols)
            parts = [lanes] if same_placement(lanes) else [(lane,) for lane in lanes]
            out = _gather_take_rows(head, parts[0])
            rest = [_gather_take_rows(head, part) for part in parts[1:]]
            span["dispatches"] = len(parts)
        with tracer.span("serve:gather:readback") as span:
            got = np.asarray(out)
            if rest:  # each program echoes head above its one lane
                got = np.concatenate([got] + [np.asarray(r)[-1:] for r in rest])
            telemetry.count_sync(int(got.size))
            span["host_syncs"], span["elements"] = len(parts), int(got.size)
            span["one_trip"] = int(isinstance(head, jax.Array) and not rest)
        return got

    def decode_rows(self, lanes_host: np.ndarray) -> List[Row]:
        """Rows from host copies of the columns' ``storage`` values (one
        row of *lanes_host* per column, in the table's column order)."""
        with tracer.span("serve:gather:rows"):
            return self._rows_of(
                [c.decode_storage(v) for c, v in zip(self.columns.values(), lanes_host)],
                lanes_host.shape[1],
            )

    def _rows_of(self, decoded: List[list], n: int) -> List[Row]:
        names = list(self.columns)
        out = []
        for i in range(n):
            row = Row()
            for name, vals in zip(names, decoded):
                v = vals[i]
                if v is not None:
                    row[name] = v
            out.append(row)
        return out

    def rows_from_mirror(self, lower: int, upper: int) -> List[Row]:
        """Decode the row range [lower, upper) from host code mirrors.

        The device-lazy Index's point-lookup decode: each column's codes
        mirror to host once (StringColumn.codes_host), then every find
        is pure numpy — no device dispatch at all."""
        return self.rows_from_mirror_many([(lower, upper)])[0]

    # Decoded mirror blocks are cached per (lower, upper) range up to this
    # many rows; repeated probes of hot keys then skip the decode entirely.
    # Checked per call so tests can tune it via the environment.
    MIRROR_LRU_ROWS_DEFAULT = 65536

    def _mirror_lru_cap(self) -> int:
        return env_int("CSVPLUS_MIRROR_LRU_ROWS", self.MIRROR_LRU_ROWS_DEFAULT)

    def rows_from_mirror_many(
        self, bounds: Sequence[Tuple[int, int]]
    ) -> List[List[Row]]:
        """Batched :meth:`rows_from_mirror`: ONE gather + decode per
        column over the union of all requested ranges, split back into
        per-range row blocks, with a bounded LRU over decoded blocks.

        Returned blocks share Row objects with the cache (and across
        duplicate ranges) — the same sharing contract as the host tier's
        ``rows[lower:upper]`` slices; ``iterate`` clones on delivery.

        Thread-safe: the whole call holds ``_mirror_lock``.  The serving
        tier funnels lookups through ONE dispatcher thread, so the lock
        is normally uncontended — it exists so direct concurrent callers
        (the r08 stress test, user code sharing an Index across threads)
        get serialized decodes instead of a corrupted LRU, with results
        bitwise-equal to the serial order.
        """
        with self._mirror_lock:
            return self._rows_from_mirror_many_locked(bounds)

    def _rows_from_mirror_many_locked(
        self, bounds: Sequence[Tuple[int, int]]
    ) -> List[List[Row]]:
        lru = getattr(self, "_mirror_lru", None)
        if lru is None:
            from collections import OrderedDict

            lru = self._mirror_lru = OrderedDict()
            self._mirror_lru_rows = 0
        out: List[Optional[List[Row]]] = [None] * len(bounds)
        misses: Dict[Tuple[int, int], List[int]] = {}
        for i, (lo, hi) in enumerate(bounds):
            lo, hi = int(lo), int(hi)
            if hi <= lo:
                out[i] = []
                continue
            got = lru.get((lo, hi))
            if got is not None:
                lru.move_to_end((lo, hi))
                out[i] = got
            else:
                misses.setdefault((lo, hi), []).append(i)
        if misses:
            ranges = list(misses)
            starts = np.array([r[0] for r in ranges], dtype=np.int64)
            sizes = np.array([r[1] - r[0] for r in ranges], dtype=np.int64)
            # vectorized concat of aranges: arange(total) re-based per
            # range (an arange + concatenate per range is pure overhead
            # when most matches are single rows)
            offsets = np.concatenate(([0], np.cumsum(sizes)[:-1]))
            idx = (
                np.arange(int(sizes.sum()), dtype=np.int64)
                + np.repeat(starts - offsets, sizes)
            )
            decoded = {}
            for name, col in self.columns.items():
                if col.kind == "int":
                    decoded[name] = col.decode_take(idx)
                else:
                    decoded[name] = col.decode_codes(col.codes_host()[idx])
            names = list(decoded)
            off = 0
            for r in ranges:
                size = r[1] - r[0]
                block = [Row() for _ in range(size)]
                for name in names:
                    vals = decoded[name]
                    for j in range(size):
                        v = vals[off + j]
                        if v is not None:
                            block[j][name] = v
                off += size
                for i in misses[r]:
                    out[i] = block
                lru[r] = block
                self._mirror_lru_rows += size
            cap = self._mirror_lru_cap()
            while self._mirror_lru_rows > cap and len(lru) > 1:
                _, evicted = lru.popitem(last=False)
                self._mirror_lru_rows -= len(evicted)
        return out  # type: ignore[return-value]

    # -- iteration protocol so take(DeviceTable) works ---------------------

    def iterate(self, fn) -> None:
        """Stream decoded rows (the escape hatch for opaque callbacks)."""
        from ..source import iterate

        iterate(self.to_rows(), fn)

    Iterate = iterate

    @property
    def plan(self):
        from ..plan import Scan

        return Scan(self)
