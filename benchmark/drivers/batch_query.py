"""Traffic: one batch query executed over and over, one at a time.

The cell's ``query`` names ``queries/<query>.py``, whose ``build(h,
state)`` does the query's set-up (``state.ingest`` is this file's
``ingest``) and sets ``state.run_once`` (the timed call) and
``state.digest`` (result -> something comparable, taken outside the
span), and whose ``verify(h, state, last, digests)`` holds the window's
last result to the generator's arrays (raising ``reference.Mismatch``).
The window drops each result before the next execution.

The timed span of one execution runs from the call that starts it
(``cache.execute(plan)``, or ``FromFile(...)``) to the return of the
result's ``.sync()`` (or of ``ToCsvFile``).  The digest between
executions, and every comparison, is outside the spans.
"""

from __future__ import annotations

import statistics
import time

import reference as ref

INGEST_TIERS = (
    "ingest:streamed", "ingest:device-parsed", "ingest:native-encoded", "ingest:python",
)


class State:
    def __init__(self):
        self.query = None  # queries/<query>.py: build(h, state), verify(h, state, last, digests)
        self.ingest = ingest
        self.run_once = None  # () -> result; the timed call
        self.digest = None  # result -> comparable digest (outside the span)
        self.first_exec_s = None
        self.warm_spans = []


def ingest(h, key: str):
    """FromFile(path).OnDevice(platform), synced; the tier is printed and
    the Python parser refused."""
    from csvplus_tpu import FromFile
    from csvplus_tpu.utils.observe import telemetry

    with telemetry.collect() as records:
        src = FromFile(h.data.paths[key]).OnDevice(h.platform)
        src.plan.table.sync()
        tiers = [r.stage for r in records if r.stage in INGEST_TIERS]
        workers = [r.extra.get("workers") for r in records if r.stage == "ingest:encode"]
    ref.check(len(tiers) == 1, f"{key}: ingest tiers recorded: {tiers}")
    ref.check(tiers[0] != "ingest:python", f"{key}: ingest fell to the Python parser")
    table = src.plan.table
    ref.placed_on(table, h.platform, key, 1)
    h.say(
        f"  {key}: {table.nrows:,} rows via {tiers[0]} K={workers[-1] if workers else None} "
        f"{ref.column_kinds(table)}"
    )
    return src


def setup(h) -> State:
    state = State()
    state.query = h.load_module("queries", h.cell["query"])
    state.query.build(h, state)
    from csvplus_tpu.utils.observe import telemetry

    with h.phase("first_execution"), telemetry.collect() as records:
        t0 = time.perf_counter()
        result = state.run_once()
        state.first_exec_s = time.perf_counter() - t0
        state.digest(result)  # compiles the digest's programs in set-up
        demotes = [r.seconds for r in records if r.stage == "typed:demote"]
    h.say(
        f"  first execution {state.first_exec_s:.2f}s, of it typed:demote "
        f"{len(demotes)} column(s) {sum(demotes):.2f}s"
    )
    return state


def warm(h, state: State) -> None:
    """One more execution, now that the plan is admitted: every program
    the window drives has run."""
    with h.phase("warm"):
        t0 = time.perf_counter()
        result = state.run_once()
        state.warm_spans.append(time.perf_counter() - t0)
        state.digest(result)


def measure(h, state: State, seconds: float) -> dict:
    from csvplus_tpu.utils.observe import telemetry

    limit = int(h.cell["trace"]["executions"]) if h.traced else None
    spans, digests, stages, syncs, raised = [], [], [], [], []
    result = None
    t_end = time.perf_counter() + seconds
    while True:
        result = None  # drop the previous result before the next execution
        mark, sync0 = len(telemetry.records), telemetry.host_sync_elements
        t0 = time.perf_counter()
        try:
            result = state.run_once()
        except Exception as e:  # an execution that raises has failed; the run goes on
            raised.append(repr(e))
            spans.append(time.perf_counter() - t0)
            digests.append(None)
        else:
            spans.append(time.perf_counter() - t0)
            with h.annotate("digest"):  # the benchmark's own device work: the reducer leaves it out
                digests.append(state.digest(result))
        if h.traced:
            stages.append(list(telemetry.records[mark:]))
            syncs.append(telemetry.host_sync_elements - sync0)
        if len(spans) == limit or (limit is None and time.perf_counter() >= t_end):
            break
        if len(raised) >= 3:
            break
    h.evidence["stages"] = stages
    h.evidence["host_sync_elements"] = syncs
    return {"spans": spans, "digests": digests, "last": result, "raised": raised}


def end_to_end(h, state: State, samples: dict) -> dict:
    spans = samples["spans"]
    rows = h.data.n * len(spans)
    h.say(
        f"window: {len(spans)} executions, span median={statistics.median(spans):.4f}s "
        f"min={min(spans):.4f}s max={max(spans):.4f}s (execution {spans.index(max(spans)) + 1}) sum={sum(spans):.3f}s"
    )
    return {h.cell.get("metric", "rows_per_s"): (rows / sum(spans), "rows/s")}


def check(h, state: State, samples: dict):
    """The last execution's result against the generator in full, every
    other execution against the last by digest; limit 0 differences."""
    spans, digests, last = samples["spans"], samples["digests"], samples["last"]
    for err in samples["raised"]:
        h.say(f"check: an execution raised: {err}")
    facts = h.evidence["facts"]
    facts.update(
        executions=len(spans), first_exec_s=state.first_exec_s,
        warm_median_s=statistics.median(spans),
        first_exec_minus_warm_s=state.first_exec_s - statistics.median(spans),
    )
    h.say(
        f"check: first execution {state.first_exec_s:.2f}s, warm-up {state.warm_spans}, "
        f"window median {facts['warm_median_s']:.4f}s"
    )
    for recs in h.evidence.get("stages", [])[:1]:
        h.say(
            "check: stage table of the first traced execution (host clock, the program "
            "blocks on the device at each stage's end): "
            + " ".join(f"{r.stage}={r.seconds:.4f}s" for r in recs)
        )
    ok_last = False
    if last is not None:
        try:
            state.query.verify(h, state, last, digests)
            ok_last = True
        except ref.Mismatch as e:
            h.say(f"check: {e}")
    same = [
        dg is not None and _same(dg, digests[-1]) for dg in digests
    ]
    differing = len(same) - sum(same)
    failed = len(spans) if not ok_last else differing
    h.say(
        f"check: last execution vs generator: differing=0 required, "
        f"{'equal' if ok_last else 'DIFFERS'}; executions whose digest differs from it: "
        f"{differing} (limit 0) of {len(spans)}"
    )
    return ok_last and differing == 0, len(spans), failed


def _same(a, b) -> bool:
    return a == b if isinstance(a, bytes) else ref.TableDigest.same(a, b)
