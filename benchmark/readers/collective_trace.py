"""Device time in collectives, for a cell on several chips.

A collective shows on a device plane's ``XLA Ops`` line under its HLO
name: ``all-to-all``, ``all-gather``, ``all-reduce``,
``collective-permute``, ``reduce-scatter``, each possibly split into an
asynchronous ``-start`` and ``-done``.  ``reduce_collectives`` gives the
seconds in which such an operation ran: per device plane the union of
those events' intervals, clipped as ``device_trace`` clips
(``bench:window`` less ``bench:digest``), averaged over the planes.  A
``-start``/``-done`` pair is one collective: ``calls`` counts an
operation once (its ``-done`` is not counted again), and the pair's two
intervals enter the one union.

Selector: ``{"what": "collective_s", "per": "execution"}``: those
seconds per execution of the query.  None where the profile holds no
collective at all (one chip: nothing to read).
"""

from __future__ import annotations

import glob
import os
import re

from readers import device_trace as dt
from readers import kernel_trace as kt

# an XLA Ops event is named by its HLO line, "%all_to_all.28 = s32[4,1,4194304]{...} all-to-all(...)":
# jax names the value with underscores, XLA the operation with hyphens.  The line is cut at 160
# characters, so the value's name is tried where the operation's no longer shows.
_KINDS = r"(all[-_]to[-_]all|all[-_]gather|all[-_]reduce|collective[-_]permute|reduce[-_]scatter)"
_BY_OPCODE = re.compile(r"\s" + _KINDS + r"(-start|-done)?\(")
_BY_VALUE = re.compile(r"^%?" + _KINDS + r"(-start|-done)?(?![\w-])")


def collective_of(name: str):
    """(hlo operation, "-start" | "-done" | None) of an XLA Ops event
    that is a collective, else None."""
    m = _BY_OPCODE.search(name) or _BY_VALUE.match(name)
    if m is None:
        return None
    return m.group(1).replace("_", "-"), m.group(2)


def reduce_collectives(ops: dict, host: list):
    """{"seconds", "calls", "by_op": {hlo op: seconds}} over plain event
    lists; None without a window or a device plane."""
    windows = [(s, s + d) for name, s, d in host if name == dt.WINDOW]
    if not windows or not ops:
        return None
    w0, w1 = windows[0]
    own = dt._union(
        [(max(s, w0), min(s + d, w1)) for name, s, d in host if name == dt.OWN and s + d > w0 and s < w1]
    )
    edges = [w0] + [t for iv in own for t in iv] + [w1]
    counted = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    total_ns = 0.0
    calls = 0
    by_op: dict = {}
    for events in ops.values():
        ivs = []
        for name, s, d in events:
            found = collective_of(name)
            if found is None:
                continue
            kind, phase = found
            inside = [(max(s, c0), min(s + d, c1)) for c0, c1 in counted if s + d > c0 and s < c1]
            if not inside:
                continue
            ivs.extend(inside)
            by_op[kind] = by_op.get(kind, 0.0) + sum(e - b for b, e in inside)
            if phase != "-done":
                calls += 1
        total_ns += sum(e - b for b, e in dt._union(ivs))
    n = len(ops)
    return {
        "seconds": total_ns / n / 1e9,
        "calls": calls / n,
        "by_op": {k: ns / n / 1e9 for k, ns in by_op.items()},
    }


def read(h, state, samples, selector: dict):
    if "collective_trace" not in h.evidence:
        found = sorted(
            glob.glob(os.path.join(h.root, "profile", "plugins", "profile", "*", "*.xplane.pb"))
        )
        red = None
        if found:
            ev = kt.load_xplane(found[-1])
            red = reduce_collectives(ev["ops"], ev["host"])
        if red is not None:
            h.say(
                f"collectives: {red['seconds']:.5f}s in {red['calls']:g} calls a device plane "
                f"over {len(ev['ops'])} plane(s); by HLO op: "
                + " ".join(f"{k}={s:.5f}" for k, s in sorted(red["by_op"].items()))
            )
        h.evidence["collective_trace"] = red
    red = h.evidence["collective_trace"]
    if selector["what"] != "collective_s":
        raise ValueError(f"collective_trace: unknown selector {selector!r}")
    per = h.evidence["facts"].get("executions")
    if red is None or not red["calls"] or not per:
        return None
    return red["seconds"] / per
