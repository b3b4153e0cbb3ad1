"""Reader beside ``stage_table``: the host's OWN seconds in stages.

With collection on, the program blocks on the device at a stage's end
(``telemetry.barrier``), so a stage's ``seconds`` are mostly device
wait.  Since PR 25 the stage says so: its extras carry ``synced`` and
``wait_s``, the seconds spent inside that block, and ``seconds -
wait_s`` is what the host itself took (dispatching, packing, Python).

Selector: ``{"stages": [names]}`` gives the mean over the executions of
the summed ``seconds - wait_s`` of those stages.  None where no such
stage recorded a wait (a program from before PR 25: nothing to read).
"""

from __future__ import annotations


def read(h, state, samples, selector: dict):
    per_exec = h.evidence.get("stages")
    if not per_exec:
        return None
    names = set(selector["stages"])
    wanted = [[r for r in recs if r.stage in names] for recs in per_exec]
    if not any("wait_s" in r.extra for recs in wanted for r in recs):
        return None
    sums = [sum(r.seconds - r.extra.get("wait_s", 0.0) for r in recs) for recs in wanted]
    return sum(sums) / len(sums)
