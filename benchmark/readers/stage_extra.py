"""Reader beside ``stage_table``: a COUNT the program's stages carry.

A stage of the program's table may say more than its seconds: its
extras hold what the stage itself counted (``telemetry.stage(...) as
out; out[key] = n``).  Since PR 26 the join's stages carry
``row_gathers``: the full-length gathers the stage dispatched, that is,
how many times it walked the stream's rows.

Selector: ``{"stages": [names], "key": name}`` gives the mean over the
executions of the summed ``extra[key]`` of those stages.  None where no
such stage carries the key (a program from before the key existed:
nothing to read) and where there is no stage table at all.
"""

from __future__ import annotations


def read(h, state, samples, selector: dict):
    per_exec = h.evidence.get("stages")
    if not per_exec:
        return None
    names = set(selector["stages"])
    key = selector["key"]
    carried = [
        [r.extra[key] for r in recs if r.stage in names and key in r.extra]
        for recs in per_exec
    ]
    if not any(carried):
        return None
    return sum(sum(c) for c in carried) / len(carried)
