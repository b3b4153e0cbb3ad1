"""Reader over the program's stage table (``telemetry.collect()``,
``utils/observe.py``): host-clock seconds and counts per stage, taken in
the traced run, per execution.  Not device time: with collection on, the
program blocks on the device at the end of each stage.

Selector: ``{"stages": [names]}`` or ``{"plan_nodes": true}`` (the
executor's own stages, whose names start with a capital) gives the mean
over the executions of the summed seconds of those stages;
``{"count": "host_sync_elements"}`` gives the mean count per execution.
"""

from __future__ import annotations


def read(h, state, samples, selector: dict):
    per_exec = h.evidence.get("stages")
    if not per_exec:
        return None
    if selector.get("count") == "host_sync_elements":
        syncs = h.evidence["host_sync_elements"]
        return sum(syncs) / len(syncs)
    if selector.get("plan_nodes"):
        def wanted(stage):
            return stage[:1].isupper()
    else:
        names = set(selector["stages"])

        def wanted(stage):
            return stage in names
    sums = [sum(r.seconds for r in recs if wanted(r.stage)) for recs in per_exec]
    if not any(any(wanted(r.stage) for r in recs) for recs in per_exec):
        return None
    return sum(sums) / len(sums)
