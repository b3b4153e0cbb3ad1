"""BASELINE.json config 4 on a resident table, the RESIDENT dedup: per
execution ``Take(people).IndexOn("id")`` -> ``.ResolveDuplicates("first")``
-> ``.sync()``: upstream's index build over a non-unique key, then one
row kept of every id's copies (csvplus.go ``IndexOn`` :707-738,
``Index.ResolveDuplicates`` :643-653).  The result is a device-lazy
``Index`` of one row per distinct id, in the byte order of the ids.

Nothing here selects a path: ``FromFile(...).OnDevice(platform)`` and
the public ``DataSource`` / ``Index`` calls at the program's defaults
(no ``CSVPLUS_*`` variable, no class attribute).

**The set-up refusal.**  This cell is the deployment in which the table,
the index and the result never leave the device.  ``run_once``, on its
first call (the driver's ``first_execution`` phase, where an exception
ends the run), reads its own stage records and refuses a program whose
``ResolveDuplicates`` carries the row set through the host: there must
be a ``dedup:compact`` stage whose ``tier`` is ``device``, and the
``index:*`` / ``dedup:*`` stages' ``host_sync_elements`` must sum to at
most ``HOST_ELEMENTS_ALLOWED``.  Such a program is not a slower run of
this cell, it is no run of it: ``run.py`` exits non-zero in set-up and
prints no result line, as ``drivers/batch_query.py:ingest`` refuses the
Python parser and ``pointserve-closed32`` an index under the mirror cap.
"""

from __future__ import annotations

import reference as ref

POLICY = "first"
STAGES = ("index:", "dedup:")
HOST_ELEMENTS_ALLOWED = 64  # scalars (the kept rows' count); nothing row-proportional


def refuse_host_tier(stages) -> None:
    """Raise ``reference.Mismatch`` unless *stages* (``(name, extra)`` of
    one execution) show the resident dedup."""
    ours = [(s, extra) for s, extra in stages if s.startswith(STAGES)]
    tiers = [extra.get("tier") for s, extra in ours if s == "dedup:compact"]
    crossed = sum(int(extra.get("host_sync_elements", 0)) for _, extra in ours)
    ref.check(
        "device" in tiers and "host" not in tiers and crossed <= HOST_ELEMENTS_ALLOWED,
        "dedup-resident refused in set-up: ResolveDuplicates did not stay on the device "
        f"(dedup:compact tiers recorded {tiers}, want ['device']; index:/dedup: stages read "
        f"{crossed} elements to the host, at most {HOST_ELEMENTS_ALLOWED} allowed; stages "
        f"{[s for s, _ in stages]}): a tree that carries the row set through the host is not a run of this cell",
    )


def build(h, state) -> None:
    from csvplus_tpu import Take
    from csvplus_tpu.utils.observe import telemetry

    with h.phase("ingest"):
        people = state.ingest(h, "people")
    state.people, state.data = people, h.data  # what the control (tests/control_dedup.py) reads
    state.first_stages = None

    def run_once():
        mark = len(telemetry.records)
        with h.annotate("index_on"):
            index = Take(people).IndexOn("id")
        with h.annotate("resolve_duplicates"):
            index.ResolveDuplicates(POLICY)
        with h.annotate("result.sync"):
            index.sync()
        if state.first_stages is None:  # the driver collects stages around the first execution
            state.first_stages = [(r.stage, dict(r.extra)) for r in telemetry.records[mark:]]
            refuse_host_tier(state.first_stages)
            h.say("  first execution's stages " + " ".join(
                f"{s}{extra}" if s.startswith(STAGES) else s for s, extra in state.first_stages
            ))
        return index

    digest = ref.TableDigest()
    state.run_once = run_once
    state.digest = lambda index: digest(index.device_table.table)


def want(d) -> dict:
    """The deduplicated people from the generator's arrays alone: the
    distinct ids in the byte order of ``c<n>`` (``Data.lex_ids``: the
    arithmetic key, which its docstring shows equal to upstream's string
    compare), and of each id's copies the name and surname of the first
    in file order, which go by that row's number."""
    ids = d.lex_ids
    rows = d.first_row[ids]
    return {"id": (b"c", ids), "name": d.people_name(rows), "surname": d.people_surname(rows)}


def verify(h, state, last, digests) -> None:
    """The window's last result equals the reference in full (every
    value of all three columns, one row per distinct id, in key order)
    and sits on the device."""
    d = h.data
    people = h.cfg["tables"]["people"]
    if d.n == int(people["rows"]):
        ref.check(d.distinct == int(people["distinct_id"]), "the generator's distinct ids")
    ref.check(len(last) == d.distinct, f"dedup: {len(last)} rows, expected {d.distinct}")
    ref.check(last.device_table is not None, "dedup: the result has no device table (host fallback)")
    table = last.device_table.table
    ref.placed_on(table, h.platform, "dedup result", 1)
    ref.expect_columns(table, d.distinct, want(d), "dedup")
