"""The control of ``dedup-resident``, at the cell's own size, on the chip:

    python benchmark/tests/control_dedup.py --workload dedup-resident --seed <n> --seconds <s>

runs ``run.py``'s whole path with ``last`` served for ``first`` for one
id: in the result of every execution the row of the first doubled id
keeps its id and carries the name and surname of the id's SECOND row in
file order (the payloads of the id's two rows change places).  It exits
0 only when the run reported ``correct: false``.  The benchmark's own
runs never run it.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import run  # noqa: E402
from control import Tee  # noqa: E402

PAYLOAD_COLUMNS = ("name", "surname")


def keep_the_second_copy(nth=None):
    """A tamper for ``queries/dedup.py``: in every execution's result (or
    only the *nth*), the first id in result order that occurs twice
    carries the payload of its second row, read from the resident people
    table (whose dictionaries the result's columns share)."""

    def tamper(state):
        inner, calls = state.run_once, [0]
        pos, _, second = state.data.a_doubled_id()
        source = state.people.plan.table.columns

        def broken():
            index = inner()
            calls[0] += 1
            if nth is not None and calls[0] != nth:
                return index
            columns = index.device_table.table.columns
            for name in PAYLOAD_COLUMNS:
                col = columns[name]
                columns[name] = col.with_storage(col.storage.at[pos].set(source[name].storage[second]))
            return index

        state.run_once = broken

    return tamper


def main(argv) -> int:
    out = Tee()
    rc = run.main(argv + ["--trace", "0"], out=out, tamper=keep_the_second_copy())
    if rc != 0:
        return rc
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    print(f"control: correct={result['correct']} failed={result['failed']} (must be false)")
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
