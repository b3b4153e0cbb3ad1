"""Ways to break a cell's timed path underneath the harness: what the
tests and the on-chip control (``control.py``) hand to ``run.main`` as
*tamper*.  Each breaks one guarantee the configuration states."""

from __future__ import annotations


def swap_one_value(nth=None):
    """batch_query: two cells of ``ts`` change places in the result
    (every execution, or only the *nth*): the result no longer equals the
    reference in values and row order."""

    def tamper(state):
        inner, calls = state.run_once, [0]

        def broken():
            out = inner()
            calls[0] += 1
            if nth is not None and calls[0] != nth:
                return out
            col = out.columns["ts"]
            v = col.storage
            out.columns["ts"] = col.with_storage(v.at[0].set(v[1]).at[1].set(v[0]))
            return out

        state.run_once = broken

    return tamper


def stale_reply(nth=100):
    """closed_loop_lookup: the *nth* reply of each server carries the
    rows of the reply before it: a stale answer to an acknowledged read."""

    def tamper(state):
        make = state.new_server

        def new_server():
            srv = make()
            inner, seen = srv.submit, {"n": 0, "prev": None}

            def submit(probe, *, callback=None, **kw):
                def cb(fut):
                    seen["n"] += 1
                    fresh = fut.value
                    if seen["n"] == nth:
                        fut.value = seen["prev"]
                    seen["prev"] = fresh
                    callback(fut)

                return inner(probe, callback=cb, **kw)

            srv.submit = submit
            return srv

        state.new_server = new_server

    return tamper


CONTROLS = {
    "batch_query": swap_one_value(),
    "closed_loop_lookup": stale_reply(),
}
