"""The control of ``star3-selective-resident``, at the cell's own size, on
the chip:

    python benchmark/tests/control_selective.py --workload star3-selective-resident --seed <n> --seconds <s>

runs ``run.py``'s whole path with the inner join broken for one pair of
orders: in the result of every execution one matched order is dropped
and, in its place, one unmatched order is kept (the first order outside
the segment that has a survivor after it takes that survivor's row: its
``cust_id``, ``prod_id``, ``qty`` and ``ts`` stand where the survivor's
stood, beside the survivor's customer and product cells).  The result
keeps its length, so only a comparison of the rows shows it.  It exits 0
only when the run reported ``correct: false``.  The benchmark's own runs
never run it.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import run  # noqa: E402
from control import Tee  # noqa: E402


def _cell(col, text: bytes, number):
    """What *col*'s row-indexed storage holds for the value *text*: the
    number itself in a typed lane, else the value's dictionary code."""
    if getattr(col, "kind", "str") == "int":
        return int(number)
    (at,) = np.flatnonzero(np.asarray(col.dictionary) == text)
    return int(at)


def keep_an_unmatched_order(nth=None):
    """A tamper for ``queries/star3_selective.py``: in every execution's
    result (or only the *nth*) the first unmatched order with a survivor
    after it stands in that survivor's row."""

    def tamper(state):
        inner, calls, cells = state.run_once, [0], {}
        d = state.data
        kept = np.flatnonzero(d.in_segment)  # the survivors, in the result's order
        u = int(np.flatnonzero(~d.in_segment)[0])
        pos = int(np.searchsorted(kept, u))  # the survivor that follows it: its row in the result
        if pos == kept.size:
            raise ValueError("no survivor after the first unmatched order")
        fact = {
            "cust_id": (b"c%d" % d.cust[u], d.cust[u]), "prod_id": (b"p%d" % d.prod[u], d.prod[u]),
            "qty": (b"%d" % d.qty[u], d.qty[u]), "ts": (bytes(d.ts_table[d.ts_idx[u]]), None),
        }

        def broken():
            table = inner()
            calls[0] += 1
            if nth is not None and calls[0] != nth:
                return table
            for column, (text, number) in fact.items():
                col = table.columns[column]
                if column not in cells:
                    cells[column] = _cell(col, text, number)
                table.columns[column] = col.with_storage(col.storage.at[pos].set(cells[column]))
            return table

        state.run_once = broken

    return tamper


def main(argv) -> int:
    out = Tee()
    rc = run.main(argv + ["--trace", "0"], out=out, tamper=keep_an_unmatched_order())
    if rc != 0:
        return rc
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    print(f"control: correct={result['correct']} failed={result['failed']} (must be false)")
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
