"""The control of a cell, at the cell's own size, on the chip:

    python benchmark/tests/control.py --workload <cell> --seed <n> --seconds <s>

runs ``run.py``'s whole path with the timed path broken underneath (one
guarantee of the configuration broken: see ``tampers.py``) and exits 0
only when the run reported ``correct: false``.  The benchmark's own runs
never run it.
"""

from __future__ import annotations

import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import run  # noqa: E402
import tampers  # noqa: E402


class Tee(io.StringIO):
    def write(self, s):
        sys.stdout.write(s)
        return super().write(s)


def main(argv) -> int:
    cell = run.load_json("workloads", f"{argv[argv.index('--workload') + 1]}.json")
    out = Tee()
    rc = run.main(argv + ["--trace", "0"], out=out, tamper=tampers.CONTROLS[cell["driver"]])
    if rc != 0:
        return rc
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    print(f"control: correct={result['correct']} failed={result['failed']} (must be false)")
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
