"""Traffic: N logical clients against one ``LookupServer``, each with
one point lookup in flight, resubmitting from its completion callback
(``srv.submit(key, callback=...)``): ``bench_serve.py``'s headline loop,
copied.  A closed loop, because the callers are workers that wait for
each reply; resubmission happens on the dispatcher's thread, so the
load comes from one process with no client threads.

One request's latency runs from the call of ``submit()`` to the entry
of its callback.  The window runs from the first ``submit()`` for
``--seconds``; replies that arrive in it are counted, the requests then
in flight are drained and compared but not counted.  Every reply is
compared with the generator's arrays once the window has closed.
"""

from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

import reference as ref

DRAIN_TIMEOUT_S = 60.0


class State:
    def __init__(self):
        self.index = None
        self.server = None
        self.keys = None  # int64[pool]: person ids, some of which do not exist
        self.probes = None  # the same as the strings the clients submit
        self.clients = 0
        self.cursor = 0  # next slot of the pool


def setup(h) -> State:
    from csvplus_tpu import FromFile
    from csvplus_tpu.ops.join import DeviceIndex
    from csvplus_tpu.serve import LookupServer

    state = State()
    d = h.data
    with h.phase("ingest"):
        people = FromFile(d.paths["people"]).OnDevice(h.platform)
        people.plan.table.sync()
        ref.placed_on(people.plan.table, h.platform, "people", 1)
        h.say(f"  people: {people.plan.table.nrows:,} rows {ref.column_kinds(people.plan.table)}")
    with h.phase("index"):
        state.index = people.UniqueIndexOn("id").sync()
    state.mirror_cap = int(DeviceIndex.POINT_MIRROR_MAX_KEYS)
    state.n_keys = len(state.index)
    ref.placed_on(state.index._impl.dev.table, h.platform, "people index", 1)
    with h.phase("keys"):
        state.keys = h.gen.lookup_keys(d, h.seed, int(h.cell["key_pool"]), float(h.cell["miss_share"]))
        state.probes = _probes(state.keys)
    state.clients = int(h.cell["clients"])
    state.new_server = lambda: LookupServer(state.index).start()
    return state


def _probes(keys: np.ndarray) -> list:
    return np.char.add("c", keys.astype(np.str_)).tolist()


def close(h, state: State) -> None:
    if state.server is not None:
        state.server.stop()
        state.server = None


def warm(h, state: State) -> None:
    """The program compiles one searchsorted per batch size and one
    gather per count of hits in a batch, and the window can meet any of
    1..clients (the ramp at its start, the misses): so a closed loop of
    c clients over ids that exist, for every c, then one over ids that do
    not, each until six steady batches have run.  The window then gets a
    server of its own, so its counters are the window's."""
    d = h.data
    hits = _probes(h.gen.lookup_keys(d, h.seed + 1, 8192, 0.0))
    misses = _probes(h.gen.lookup_keys(d, h.seed + 2, 64, 1.0))
    with h.phase("warm"):
        state.server = state.new_server()
        for clients in range(1, state.clients + 1):
            _closed_loop(state, hits, clients, replies=6 * clients)
        _closed_loop(state, misses, 2, replies=12)
        _closed_loop(state, state.probes, state.clients, seconds=0.5)
        close(h, state)
        state.server = state.new_server()
        state.cursor = 0


def measure(h, state: State, seconds: float) -> dict:
    if h.traced:
        from csvplus_tpu.obs.span import tracer

        seconds = float(h.cell["trace"]["seconds"])
        ctx = tracer.capture()  # the window's trace; callbacks adopt it to submit
    else:
        ctx = None
    samples = _closed_loop(
        state, state.probes, state.clients, seconds=seconds, trace_ctx=ctx,
        annotate=lambda: h.annotate("client.on_reply"),
    )
    # the tail of a closed loop that keeps the server busy swings from run
    # to run (PERF.md): a per-layer reading, not an end-to-end metric
    h.evidence["facts"]["lookup_p99_ms"] = _p99_ms(_latencies_in_window(samples))
    batch = samples["snapshot"]["batch"]
    h.evidence["counters"] = dict(
        samples["snapshot"],
        batch_mean=batch["requests"] / batch["batches"] if batch["batches"] else None,
    )
    return samples


def _closed_loop(
    state: State, probes, clients, seconds=None, replies=None, trace_ctx=None,
    annotate=contextlib.nullcontext,
) -> dict:
    """Run the loop over *probes* for *seconds*, or until *replies* have
    come; returns per-request slots, submit and callback-entry times,
    values and errors."""
    from csvplus_tpu.obs.span import tracer

    srv, pool = state.server, len(probes)
    slots, t_sub, t_done, values, errors = [], [], [], [], []
    count = {"in_flight": 0, "replies": 0}
    # the first submissions come from this thread while replies already
    # come on the dispatcher's; afterwards the lock is uncontended
    lock = threading.Lock()
    done = threading.Event()
    clock = time.perf_counter

    def submit_next():
        with lock:
            slot = state.cursor % pool
            state.cursor += 1
            i = len(slots)
            slots.append(slot)
            t_done.append(None)
            values.append(None)
            errors.append(None)
            t_sub.append(0.0)
            count["in_flight"] += 1
        t_sub[i] = clock()
        try:
            srv.submit(probes[slot], callback=lambda fut: on_reply(fut, i))
        except Exception as e:  # shed or refused: the request failed and this client stops
            t_done[i] = clock()
            errors[i] = repr(e)
            with lock:
                count["in_flight"] -= 1
                if count["in_flight"] == 0:
                    done.set()

    def on_reply(fut, i):
        t_done[i] = clock()
        with annotate():  # traced: the client's own host time, in the profiler's trace
            client_turn(fut, i)

    def client_turn(fut, i):
        # kept for the check as tuples of strings, which the collector stops
        # tracking: 100,000 live Row objects would make every full collection
        # a pause of tens of milliseconds inside the window
        values[i] = None if fut.value is None else tuple(tuple(r.items()) for r in fut.value)
        if fut.error is not None:
            errors[i] = repr(fut.error)
        with lock:
            count["in_flight"] -= 1
            count["replies"] += 1
            if replies is None:
                more = t_done[i] < t_end
            else:
                more = count["replies"] + count["in_flight"] < replies
            idle = count["in_flight"] == 0
        if more:
            if trace_ctx is not None:
                with tracer.adopt(trace_ctx):
                    submit_next()
            else:
                submit_next()
        elif idle:
            done.set()

    t0 = clock()
    t_end = t0 + (seconds or 0.0)
    for _ in range(clients):
        submit_next()
    # replies and resubmissions run on the dispatcher's thread from here on
    finished = done.wait((seconds or 0.0) + DRAIN_TIMEOUT_S)
    snap = srv.snapshot()
    return {
        "t0": t0, "t_end": t_end, "slots": slots, "t_sub": t_sub, "t_done": t_done,
        "values": values, "errors": errors, "drained": finished, "snapshot": snap,
    }


def _latencies_in_window(samples: dict) -> np.ndarray:
    """Submit -> callback entry of every request that ended in the
    window; one that was shed or came back with an error is over any
    limit: its latency is +inf."""
    t_end = samples["t_end"]
    return np.array(
        [
            (d - s) if err is None else float("inf")
            for s, d, err in zip(samples["t_sub"], samples["t_done"], samples["errors"])
            if d is not None and d <= t_end
        ]
    )


def _p99_ms(lat: np.ndarray) -> float:
    return 1e3 * float(np.sort(lat)[min(lat.size - 1, int(0.99 * lat.size))]) if lat.size else float("inf")


def end_to_end(h, state: State, samples: dict) -> dict:
    lat = _latencies_in_window(samples)
    window = samples["t_end"] - samples["t0"]
    h.say(
        f"window: {lat.size} replies in {window:.3f}s; latency ms p50={1e3 * float(np.median(lat)):.3f} "
        f"p99={_p99_ms(lat):.3f} max={1e3 * float(lat.max()):.3f}"
    )
    return {"lookups_per_s": (int(np.isfinite(lat).sum()) / window, "lookups/s")}


def check(h, state: State, samples: dict):
    """Every reply equals the person's row, or the empty list for an id
    that does not exist (limit 0 wrong replies); the server degraded,
    failed, retried and shed nothing (limit 0 each); the index is past
    the mirror cap."""
    d = h.data
    keys = state.keys[np.asarray(samples["slots"], dtype=np.int64)]
    exists = keys < d.n_people
    rows = d.row_of[np.where(exists, keys, 0)]
    name = d.people_name(rows).astype(np.str_).tolist()
    surname = d.people_surname(rows).astype(np.str_).tolist()
    wrong = unanswered = errored = 0
    for i, (k, value, err) in enumerate(zip(keys.tolist(), samples["values"], samples["errors"])):
        if err is not None:
            errored += 1
        elif samples["t_done"][i] is None:
            unanswered += 1
        elif exists[i]:
            want = {"id": f"c{k}", "name": name[i], "surname": surname[i]}
            if len(value) != 1 or dict(value[0]) != want:
                wrong += 1
        elif len(value) != 0:
            wrong += 1
    snap = samples["snapshot"]
    counters = {k: int(snap.get(k, 0)) for k in ("degraded", "failed", "retried", "shed", "expired")}
    past_cap = state.n_keys > state.mirror_cap
    attempted = len(keys)
    failed = wrong + unanswered + errored
    misses = int((~exists).sum())
    h.say(
        f"check: {attempted} lookups compared with the generator ({misses} for ids that do not "
        f"exist): wrong={wrong} errored={errored} unanswered={unanswered} (limit 0 each); "
        f"server counters {counters} (limit 0 each); index keys {state.n_keys:,} > mirror cap "
        f"{state.mirror_cap:,}: {past_cap} (required); drained={samples['drained']}; "
        f"batches={snap['batch']['batches']} mean size={snap['batch']['mean']} max={snap['batch']['max']}"
    )
    correct = (
        failed == 0 and attempted > 0 and samples["drained"] and past_cap
        and all(v == 0 for v in counters.values())
    )
    return correct, attempted, failed
