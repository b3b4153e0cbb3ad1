"""Typed numeric value lanes: the affix-int32 column.

SURVEY §7 M2 calls for "typed columns where parseable"; the reference's
typed getters (ValueAsInt, /root/reference/csvplus.go:151-171) are the
spec anchor for which strings count as numeric.  A column qualifies when
every cell is ``prefix + canonical int32 suffix`` — one constant prefix
for the whole column, suffix in canonical decimal form ("0" or
[1-9][0-9]*, sign only with an empty prefix) so that parse -> format
round-trips BITWISE.  This covers pure integers ("42", "-7") and the
ubiquitous prefixed-id shape ("o123", "c45"); leading zeros simply join
the prefix ("o007" = "o00" + 7).

Why: a 100M-unique id column pays the full dictionary-encode machinery
(device sort-rank or host hash/sort per chunk, lane packing, deferred
union) for values that are really just integers.  As an
:class:`IntColumn` the same column is ONE int32 device array: ingest is
a C++ parse + upload, gathers/joins carry 4 bytes/row, and decode is a
C++ itoa.  The round-4 north star spent 88.2s of 109.2s in ingest on
exactly this (VERDICT r4 next #2).

Representation contract:

* ``values``: int32[n] on device — the *storage* array (the typed
  analogue of ``StringColumn.codes``); row order == source order.
* ``prefix``: bytes, constant for the column.
* typed columns NEVER hold absent cells (CSV cells always exist; ops
  that would introduce absence demote first), so ``has_absent`` is
  always False and sharding pads use :data:`PAD_VALUE` (INT32_MIN —
  pad rows live beyond ``nrows``, outside every selection, and the
  sentinel can never collide with a real cell; see its comment).

Anything that needs dictionary semantics (code order == lex order:
sorts, index builds, packed join keys, persistence, point lookups)
triggers :meth:`_demote` — a one-time conversion to an equivalent
``StringColumn`` (device unique over the values, C++ format of the
UNIQUE set only, lex argsort permutation, device code remap).  Demotion
is the explicit slow path and is telemetry-visible; the hot paths
(ingest, equality probes, payload gathers, decode, checksums, CSV/JSON
encode) never demote.
"""

from __future__ import annotations

import threading
from typing import List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..obs.recompile import register_kernel
from .table import _gather_take


# Sharding-pad sentinel for typed value lanes: INT32_MIN can never be a
# real cell (csv_pack_int32 bounds |v| <= INT32_MAX), so pad rows are
# unambiguous — they translate to -2 (the StringColumn pad identity),
# never enter a demoted dictionary, and can't alias a real "prefix+0"
# key the way a 0-pad would (review r5 finding).
PAD_VALUE = np.int32(np.iinfo(np.int32).min)


class IntColumn:
    """One affix-int32 typed column (see module docstring)."""

    kind = "int"

    def __init__(
        self,
        prefix: bytes,
        values: jax.Array,  # int32[n] on device
        _demoted: "Optional[object]" = None,
    ):
        self.prefix = prefix
        self.values = values
        self._demoted = _demoted  # cached StringColumn after demotion
        self._demote_lock = threading.Lock()

    # ---- kind-agnostic storage protocol (shared with StringColumn) ----

    @property
    def storage(self) -> jax.Array:
        """The row-indexed device array (the typed ``codes`` analogue)."""
        return self.values

    def with_storage(self, values: jax.Array) -> "IntColumn":
        return IntColumn(self.prefix, values)

    def gather(self, sel, codes=None) -> "IntColumn":
        src = self.values if codes is None else codes
        idx = jnp.asarray(sel, dtype=jnp.int32)
        return IntColumn(self.prefix, _gather_take(src, idx))

    def __len__(self) -> int:
        return int(self.values.shape[0])

    @property
    def has_absent(self) -> bool:
        return False  # typed columns never hold absent cells (module doc)

    @property
    def dev_dictionary(self):
        return None  # no lane dictionary; value lanes ARE the storage

    def _ensure_sorted_lanes(self) -> None:
        return None  # no deferred lane union to settle

    # ---- decode fast paths (no demotion) ----

    def _prefix_str(self) -> str:
        return self.prefix.decode("utf-8")

    def _format_host(self, values: np.ndarray) -> np.ndarray:
        return format_affix(self.prefix, values)

    def formatted_host(self) -> np.ndarray:
        """All rows formatted to 'S' bytes (sink fast paths)."""
        return self._format_host(np.asarray(self.values))

    def formatted_str(self) -> np.ndarray:
        """All rows formatted as a numpy str array."""
        digits = np.asarray(self.values).astype(np.str_)
        p = self._prefix_str()
        return np.char.add(p, digits) if p else digits

    def decode(self) -> List[Optional[str]]:
        return self.formatted_str().tolist()

    def values_host(self) -> np.ndarray:
        """Host mirror of the value lanes (cached — point-lookup decodes
        then cost zero device dispatches, like codes_host)."""
        got = getattr(self, "_values_host", None)
        if got is None:
            got = self._values_host = np.asarray(self.values)
        return got

    def decode_storage(self, values: np.ndarray) -> List[Optional[str]]:
        """Decode a host copy of ``storage`` values (the kind-agnostic
        name, shared with ``StringColumn``)."""
        digits = values.astype(np.str_)
        p = self._prefix_str()
        return (np.char.add(p, digits) if p else digits).tolist()

    def decode_slice(self, lo: int, hi: int) -> List[Optional[str]]:
        return self.decode_storage(self.values_host()[lo:hi])

    def decode_take(self, idx: np.ndarray) -> List[Optional[str]]:
        """Arbitrary-index decode off the host mirror (the batched
        lookup engine's gather-then-decode path)."""
        return self.decode_storage(self.values_host()[idx])

    def equality_term(self, value: str):
        """The int32 target *value* compares equal to on this column, or
        None when no cell can ever equal it (wrong prefix / non-canonical
        suffix — typed cells only ever hold canonical forms)."""
        try:
            raw = value.encode("utf-8")
        except (UnicodeEncodeError, AttributeError):
            return None
        if not raw.startswith(self.prefix):
            return None
        digits = raw[len(self.prefix) :]
        body = digits[1:] if (not self.prefix and digits[:1] == b"-") else digits
        if not body.isdigit():
            return None
        if body != b"0" and body[:1] == b"0":
            return None  # non-canonical: cells never hold leading zeros
        try:
            v = int(digits)
        except ValueError:
            return None
        if not (-(2**31) < v < 2**31):
            return None
        if digits[:1] == b"-" and v == 0:
            return None  # "-0" never stored
        return v

    # ---- dictionary protocol via demotion (the explicit slow path) ----

    def _demote(self):
        """The equivalent StringColumn (cached; thread-safe).  Cost:
        device unique over the values + host format/argsort of the
        UNIQUE set + one device remap gather."""
        got = self._demoted
        if got is not None:
            return got
        with self._demote_lock:
            if self._demoted is not None:
                return self._demoted
            from ..utils.observe import telemetry
            from .table import StringColumn

            n = int(self.values.shape[0])
            with telemetry.stage("typed:demote", n) as st:
                # the four parts, each a stage: the device's sort + dedup
                # and the download of the unique values; the host's
                # format; its lex argsort; the per-row remap (a search of
                # every row in the unique values, then one gather)
                with telemetry.stage("typed:demote:unique", n) as sub:
                    u = jnp.unique(self.values)  # device sort+dedup
                    uu = np.asarray(u)
                    sub["rows_out"] = int(uu.size)
                # sharding pads (PAD_VALUE sorts first) never enter the
                # dictionary; their rows code as -2 below
                has_pad = bool(uu.size) and uu[0] == PAD_VALUE
                if has_pad:
                    uu = uu[1:]
                    u = u[1:]
                st["entries"] = int(uu.size)
                with telemetry.stage("typed:demote:format", int(uu.size)):
                    strs = self._format_host(uu)
                with telemetry.stage("typed:demote:order", int(uu.size)):
                    order = np.argsort(strs, kind="stable")  # numeric -> lex
                    dictionary = strs[order]
                with telemetry.stage("typed:demote:remap", n):
                    if uu.size == 0:  # empty (or all-pad) column
                        codes = jnp.full(
                            self.values.shape, -2 if has_pad else -1, jnp.int32
                        )
                    else:
                        code_of = np.empty(uu.shape[0], dtype=np.int32)
                        code_of[order] = np.arange(uu.shape[0], dtype=np.int32)
                        # numeric rank per row, then numeric-slot -> lex code
                        pos = jnp.searchsorted(u, self.values)
                        pos = jnp.minimum(pos, int(uu.shape[0]) - 1)
                        codes = jnp.take(jax.device_put(code_of), pos, axis=0)
                        if has_pad:
                            codes = jnp.where(
                                self.values == jnp.int32(PAD_VALUE),
                                jnp.int32(-2),
                                codes,
                            )
                    # collecting, the search's device time lands here and
                    # not under whichever stage first reads the codes
                    telemetry.barrier(codes)
                self._demoted = StringColumn(
                    dictionary, codes, _has_absent=False if not has_pad else None
                )
        return self._demoted

    @property
    def codes(self) -> jax.Array:
        return self._demote().codes

    @property
    def dictionary(self) -> np.ndarray:
        return self._demote().dictionary

    def dictionary_str(self) -> np.ndarray:
        return self._demote().dictionary_str()

    @property
    def dict_size(self) -> int:
        return self._demote().dict_size

    def codes_host(self) -> np.ndarray:
        return self._demote().codes_host()

    def find_code(self, value: str) -> int:
        return self._demote().find_code(value)

    def find_codes(self, values) -> np.ndarray:
        return self._demote().find_codes(values)

    def with_codes(self, codes, dev_dict_sorted=None):
        return self._demote().with_codes(codes, dev_dict_sorted)

    def decode_codes(self, codes: np.ndarray) -> List[Optional[str]]:
        return self._demote().decode_codes(codes)

    # A dense translation table (``table[value - lo] = code``) turns the
    # per-row translation into ONE gather where the sorted pair costs a
    # ~log2(U)-round searchsorted and two more.  It is admitted where it
    # is small (the range at most this multiple of the distinct count,
    # under 2**24 slots), or, whatever its size, where it places no more
    # bytes than the pair it replaces.
    DENSE_RANGE_FACTOR = 16

    @staticmethod
    def _dense_admitted(size: int, lo: int, hi: int) -> bool:
        """Does a build side of *size* distinct values spanning
        ``[lo, hi]`` get the dense table?  A pure function of the three
        (no array is read), so a 20M-key side can be asked for free."""
        if size <= 0:
            return False
        rng = hi - lo + 1
        small = rng <= (1 << 24) and rng <= max(
            size * IntColumn.DENSE_RANGE_FACTOR, 1024
        )
        # one int32 table of rng entries against two of size: never
        # larger than the sorted pair; the slot ``value - lo`` is int32
        # on the device and in the host scatter below
        no_larger = rng <= 2 * size and rng < 2**31
        return small or no_larger

    @staticmethod
    def _build_translation(vals: np.ndarray, cand: np.ndarray):
        """Device translation state from (values, codes) of the build
        side: ('dense', base, table) where :meth:`_dense_admitted` says
        so, else ('sorted', sorted_vals, code_of)."""
        if vals.size == 0:
            return ("sorted", jax.device_put(vals), jax.device_put(cand))
        lo, hi = int(vals.min()), int(vals.max())
        if IntColumn._dense_admitted(vals.size, lo, hi):
            table = np.full(hi - lo + 1, -1, dtype=np.int32)
            table[vals - lo] = cand
            return ("dense", lo, jax.device_put(table))
        order = np.argsort(vals, kind="stable")
        return (
            "sorted",
            jax.device_put(vals[order]),
            jax.device_put(cand[order]),
        )

    def _translate_by_values(self, state) -> jax.Array:
        """Rows translated through a :meth:`_build_translation` state;
        miss -> -1, sharding pads -> -2 (the same negative-code identity
        the StringColumn translation preserves).

        Each variant is ONE jitted kernel (r6 warm-join recovery): the
        translation runs on every probe execution, and the previous
        eager form paid ~6 unfused device passes over the full probe
        length per key column per join — measured 76.6ms vs 10.6ms
        fused at 10M rows.  The dense base offset rides as a traced
        scalar so distinct build sides share one executable."""
        if state[0] == "dense":
            _, lo, table = state
            return _translate_dense_kernel(self.values, jnp.int32(lo), table)
        _, sorted_vals, code_of = state
        if int(sorted_vals.shape[0]) == 0:
            return _translate_empty_kernel(self.values)
        return _translate_sorted_kernel(self.values, sorted_vals, code_of)

    def renumbered_to(self, other_dictionary: np.ndarray) -> jax.Array:
        """Translate rows into *other_dictionary*'s code space without
        demoting SELF: parse the (small) dictionary numerically and
        searchsorted the value lanes against it — O(U) host +
        O(n log U) device, vs. the O(n)-format demotion."""
        cand, vals = parse_affix_dictionary(other_dictionary, self.prefix)
        return self._translate_by_values(self._build_translation(vals, cand))

    def translation_state_to(self, other):
        """The :meth:`_build_translation` state that takes this column's
        values into *other*'s code space.  ``other`` may be a
        StringColumn (its dictionary is parsed numerically — no demotion
        of SELF, the 100M-row probe stays value lanes) or another
        IntColumn (demoted first: build sides are index tables whose key
        columns already hold code semantics).  The parsed state is
        cached on *other* per prefix, so repeated probes of the same
        build side pay the host parse once.  A row-sharded probe gets
        the tables replicated over its mesh, cached per (prefix, device
        set): left on the default device they are uncommitted, and every
        translation call would copy them whole onto each shard again."""
        if isinstance(other, IntColumn):
            other = other._demote()
        cache = getattr(other, "_affix_trans_cache", None)
        if cache is None:
            cache = other._affix_trans_cache = {}
        hit = cache.get(self.prefix)
        if hit is None:
            from ..utils.observe import telemetry

            entries = int(other.dictionary.shape[0])
            with telemetry.stage("typed:parse-dictionary", entries) as st:
                cand, vals = parse_affix_dictionary(other.dictionary, self.prefix)
                st.update(entries=entries, rows_out=int(cand.size))
            with telemetry.stage("typed:build-translation", int(cand.size)) as st:
                hit = cache[self.prefix] = self._build_translation(vals, cand)
                st.update(entries=int(cand.size), tier=hit[0])
        sh = getattr(self.values, "sharding", None)
        mesh = getattr(sh, "mesh", None)
        if mesh is None or len(sh.device_set) <= 1:
            return hit
        key = (self.prefix, frozenset(sh.device_set))
        placed = cache.get(key)
        if placed is None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            repl = NamedSharding(mesh, P())
            placed = cache[key] = tuple(
                jax.device_put(a, repl) if isinstance(a, jax.Array) else a
                for a in hit
            )
        return placed

    def renumbered_to_col(self, other) -> jax.Array:
        """Rows translated into *other*'s code space (the probe-side join
        translation, one walk over the rows per call; the composed probe
        of ``ops/join.py`` folds the same state into its tables
        instead)."""
        return self._translate_by_values(self.translation_state_to(other))


@register_kernel("typed.translate_dense")
def _translate_dense_kernel(values, lo, table):
    is_pad = values == jnp.int32(PAD_VALUE)
    # pads masked BEFORE the subtraction: PAD_VALUE - lo wraps int32 and
    # could land inside the dense range
    safe = jnp.where(is_pad, lo, values)
    idx = safe - lo
    ok = (idx >= 0) & (idx < table.shape[0]) & ~is_pad
    got = jnp.take(table, jnp.clip(idx, 0, table.shape[0] - 1), axis=0)
    return jnp.where(ok, got, jnp.where(is_pad, jnp.int32(-2), jnp.int32(-1)))


@register_kernel("typed.translate_sorted")
def _translate_sorted_kernel(values, sorted_vals, code_of):
    is_pad = values == jnp.int32(PAD_VALUE)
    pos = jnp.searchsorted(sorted_vals, values)
    pos = jnp.minimum(pos, sorted_vals.shape[0] - 1)
    hit = (jnp.take(sorted_vals, pos, axis=0) == values) & ~is_pad
    return jnp.where(
        hit,
        jnp.take(code_of, pos, axis=0),
        jnp.where(is_pad, jnp.int32(-2), jnp.int32(-1)),
    )


@register_kernel("typed.translate_empty")
def _translate_empty_kernel(values):
    return jnp.where(
        values == jnp.int32(PAD_VALUE), jnp.int32(-2), jnp.int32(-1)
    )


def format_affix(prefix: bytes, values: np.ndarray) -> np.ndarray:
    """'S' bytes array of ``prefix + decimal(value)`` per entry — C++
    itoa when available, numpy otherwise; byte-exact either way (the
    inverse of the native csv_pack_int32 parse)."""
    from ..native.scanner import format_i32_native

    values = np.ascontiguousarray(values, dtype=np.int32)
    plen = len(prefix)
    native = format_i32_native(values)
    if native is not None:
        mat, _lens = native
        width = plen + mat.shape[1]
        out = np.zeros((values.shape[0], width), dtype=np.uint8)
        if plen:
            out[:, :plen] = np.frombuffer(prefix, dtype=np.uint8)
        out[:, plen:] = mat
        return np.ascontiguousarray(out).view(f"S{width}").ravel()
    digits = values.astype(np.str_)  # numpy fallback: canonical '%d'
    if plen:
        digits = np.char.add(prefix.decode("utf-8"), digits)
    return np.char.encode(digits, "utf-8")


def parse_affix_dictionary(d: np.ndarray, prefix: bytes):
    """Which entries of the 'S' dictionary *d* have the affix form
    ``prefix + canonical int32``?  Returns (entry indices int32[],
    values int32[]), fully vectorized over the fixed-width byte matrix
    (a Python per-entry loop here would run per join build)."""
    U = d.shape[0]
    plen = len(prefix)
    if U == 0:
        return np.empty(0, np.int32), np.empty(0, np.int32)
    width = d.dtype.itemsize
    lens = np.char.str_len(d).astype(np.int32)
    if width < plen + 1:
        return np.empty(0, np.int32), np.empty(0, np.int32)
    mat = np.frombuffer(
        np.ascontiguousarray(d).tobytes(), dtype=np.uint8
    ).reshape(U, width)
    ok = lens > plen
    if plen:
        pref = np.frombuffer(prefix, dtype=np.uint8)
        ok &= (mat[:, :plen] == pref).all(axis=1)
    # optional sign (empty prefix only)
    neg = np.zeros(U, dtype=bool)
    if plen == 0:
        neg = mat[:, 0] == ord("-")
        ok &= ~neg | (lens > 1)
    digit_start = plen + neg.astype(np.int32)
    sfx_len = lens - digit_start
    ok &= (sfx_len >= 1) & (sfx_len <= 10)
    # suffix region all digits
    colidx = np.arange(width, dtype=np.int32)
    in_sfx = (colidx >= digit_start[:, None]) & (colidx < lens[:, None])
    is_digit = (mat >= ord("0")) & (mat <= ord("9"))
    ok &= np.where(in_sfx, is_digit, True).all(axis=1)
    # canonical: no leading zero unless the suffix IS "0"
    first = mat[np.arange(U), np.minimum(digit_start, width - 1)]
    ok &= (first != ord("0")) | (sfx_len == 1)
    if not ok.any():
        return np.empty(0, np.int32), np.empty(0, np.int32)
    # positional decimal parse over the masked digit region
    exp = (lens[:, None] - 1 - colidx).astype(np.int64)
    w = np.where(in_sfx, 10 ** np.clip(exp, 0, 9), 0)
    vals = ((mat.astype(np.int64) - ord("0")) * w).sum(axis=1)
    vals = np.where(neg, -vals, vals)
    ok &= (vals < 2**31) & (vals > -(2**31)) & ~(neg & (vals == 0))
    cand = np.flatnonzero(ok).astype(np.int32)
    return cand, vals[ok].astype(np.int32)
