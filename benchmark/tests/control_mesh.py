"""The control of a cell on several chips, at the cell's own size:

    python benchmark/tests/control_mesh.py --workload lookupjoin-mesh4 --seed <n> --seconds <s>

runs ``run.py``'s whole path with two answer rows swapped in the result
of every execution (the people-side columns of two orders change places:
each order then carries another customer's row, a wrong answer to an
answered probe) and exits 0 only when the run reported ``correct:
false``.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import run  # noqa: E402
from control import Tee  # noqa: E402

ANSWER_COLUMNS = ("id", "name", "surname")


def swap_two_answer_rows(state) -> None:
    """Every execution's result has the answers of its first row and of
    the first later row with another customer swapped."""
    import numpy as np

    inner = state.run_once

    def broken():
        out = inner()
        ids = np.asarray(out.columns["id"].storage[:4096])
        other = int(np.flatnonzero(ids != ids[0])[0])
        for name in ANSWER_COLUMNS:
            col = out.columns[name]
            v = col.storage
            out.columns[name] = col.with_storage(v.at[0].set(v[other]).at[other].set(v[0]))
        return out

    state.run_once = broken


def main(argv) -> int:
    out = Tee()
    rc = run.main(argv + ["--trace", "0"], out=out, tamper=swap_two_answer_rows)
    if rc != 0:
        return rc
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    print(f"control: correct={result['correct']} failed={result['failed']} (must be false)")
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
