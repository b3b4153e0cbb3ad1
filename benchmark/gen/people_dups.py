"""Upstream's people table with repeated ids (BASELINE.json config 4):
people(id, name, surname) at upstream's widths, ids ``c<n>`` over
``0..distinct_id-1``, of which ``doubled_ids`` occur twice (so 10% of
the rows repeat an earlier id), rows in seeded shuffled order.  Names
and surnames go by the ROW's number (upstream's 10 x 12, period 120).

An id's two rows are never adjacent and never carry the same (name,
surname), by construction: one lies on a row = 0 (mod 4), the other on
a row = 2 (mod 4), so their distance is = 2 (mod 4): never 1, and never
a multiple of 120.  A wrong pick (``last`` for ``first``) or an
unstable sort therefore changes the result.

What is the configuration's and what is the seed's, as in
``gen/orders.py`` (whose block writer, digit classes and name tables
this file imports):

- a skeleton drawn from the fixed ``layout_seed`` gives every id slot
  its count of decimal digits, says which slots are doubled and which
  rows hold them: each row's byte length, the file's size, the ingest's
  chunk cuts, the distinct count and the result's length are the same
  for every seed;
- the seed draws the value of every id (a permutation within each digit
  class: which ids are doubled, and in what order the file holds them)
  and re-deals the second copies among their rows within each digit
  class (which rows are copies of each other, and which copy is first).

The reference answers (``first_row`` / ``last_row`` of every id, the ids
in the byte order of their strings) are computed from these arrays
alone; nothing here imports the engine under test.
"""

from __future__ import annotations

import os

import numpy as np

from gen import orders as base


class Data(base.Data):
    """The generated deployment: ``paths``, ``files``, ``n`` (the people
    rows one execution consumes), ``distinct``, ``people_id`` (int32 per
    row) and the reference's arrays.  *rows* overrides ``people.rows``
    (rehearsal), keeping the doubled share."""

    def __init__(self, cfg: dict, seed: int, root: str, files, rows=None):
        people = cfg["tables"]["people"]
        self.files = tuple(files)
        self.paths = {"people": os.path.join(root, "people.csv")}
        n = int(rows) if rows is not None else int(people["rows"])
        doubled = n * int(people["doubled_ids"]) // int(people["rows"])
        self.n = self.n_people = n
        self.distinct = distinct = n - doubled
        layout = int(cfg["layout_seed"])

        def streams(i):
            return np.random.default_rng([layout, i]), np.random.default_rng([seed, i])

        # slot -> id: a seeded permutation of 0..distinct-1 whose digit count
        # per slot is the skeleton's; slots 0..doubled-1 are the doubled ones
        id_of_slot = base._permutation_keeping_lengths(distinct, *streams(0))
        sk, rng = streams(1)
        firsts = sk.choice(np.arange(0, n, 4, dtype=np.int32), doubled, replace=False)
        copies = sk.choice(np.arange(2, n, 4, dtype=np.int32), doubled, replace=False)
        once = np.ones(n, dtype=bool)
        once[firsts] = once[copies] = False
        slot_of_row = np.empty(n, dtype=np.int32)
        slot_of_row[firsts] = sk.permutation(doubled).astype(np.int32)
        slot_of_row[copies] = sk.permutation(doubled).astype(np.int32)
        slot_of_row[once] = doubled + sk.permutation(distinct - doubled).astype(np.int32)
        digits = base._ndigits(id_of_slot, len(str(max(distinct - 1, 1))))[slot_of_row[copies]]
        for k in np.unique(digits):
            # the seed re-deals the copies' slots among the copies' rows of
            # one digit class: lengths stay, which rows are a pair moves
            at = copies[digits == k]
            dealt = slot_of_row[at]
            rng.shuffle(dealt)
            slot_of_row[at] = dealt
        self.people_id = id_of_slot[slot_of_row]
        self._first_row = self._last_row = self._lex_ids = None
        if "people" in self.files:
            self._write_blocks(self.paths["people"], base.PEOPLE_HEAD, n, self._people_block)

    # ---- the reference's arrays (made when a check asks) ----

    @property
    def first_row(self) -> np.ndarray:
        """id -> the first row of the file that carries it.  A fancy
        assignment with repeated indices leaves the last value written,
        so walking the rows backwards leaves each id's first row."""
        if self._first_row is None:
            rows = np.arange(self.n, dtype=np.int32)
            self._first_row = np.empty(self.distinct, dtype=np.int32)
            self._first_row[self.people_id[::-1]] = rows[::-1]
        return self._first_row

    @property
    def last_row(self) -> np.ndarray:
        """id -> the last row of the file that carries it."""
        if self._last_row is None:
            self._last_row = np.empty(self.distinct, dtype=np.int32)
            self._last_row[self.people_id] = np.arange(self.n, dtype=np.int32)
        return self._last_row

    @property
    def lex_ids(self) -> np.ndarray:
        """The ids 0..distinct-1 in the byte order of their strings
        ``c<n>``, by arithmetic (no string is formed).  With W the most
        digits any id has and d the digits of n, ``n * 10**(W - d)`` is
        n's decimal string left-aligned and padded with zeros: two such
        strings that differ at some digit compare, as numbers, by their
        first differing digit, which is the byte order (ASCII digits are
        in numeric order and the prefix ``c`` is common).  They tie only
        where one string is the other followed by zeros (``c1``, ``c10``,
        ``c100``), and there the shorter string, a proper prefix, sorts
        first: ties break by d."""
        if self._lex_ids is None:
            ids = np.arange(self.distinct, dtype=np.int64)
            width = len(str(max(self.distinct - 1, 1)))
            d = base._ndigits(ids, width)
            key = ids * 10 ** (width - d)
            self._lex_ids = np.argsort(key * 16 + d, kind="stable").astype(np.int32)
        return self._lex_ids

    def a_doubled_id(self) -> tuple:
        """(position in the deduplicated result, first row, second row)
        of the first id, in result order, that occurs twice (the control
        swaps the payload of one row for the other's)."""
        ids = self.lex_ids
        first, last = self.first_row[ids], self.last_row[ids]
        pos = int(np.flatnonzero(first != last)[0])
        return pos, int(first[pos]), int(last[pos])
