"""Observability: per-stage telemetry, now a shim over ``csvplus_tpu.obs``.

The reference has no instrumentation at all (SURVEY.md §5: the only
observability is error line numbers).  This module grew from "row
counts and wall times" into the compatibility surface of a first-class
subsystem (:mod:`csvplus_tpu.obs`, docs/OBSERVABILITY.md): per-stage
wall times and row counts, named counters, host-sync accounting — and,
whenever a span trace is active in the calling context, every stage
recorded here ALSO opens a span in that trace, so the flat table and
the hierarchical per-query view come from the same instrumentation
points:

* :data:`telemetry` — opt-in collector of per-stage statistics from the
  device plan executor, the columnar ingest, the joins, and the serving
  dispatcher; cheap enough to leave on in production pipelines (a few
  host ops per stage, never per row).  Mutation is lock-guarded: ingest
  workers and the serve dispatcher record stages concurrently
  (THREAD001 covers the entry points);
* :func:`profile_to` — context manager around ``jax.profiler.trace`` so
  a whole pipeline run can be captured for XProf/Perfetto; the span
  exporter (:func:`csvplus_tpu.obs.export.export_chrome_trace`) writes
  the host-side trace into the same ``log_dir`` so both open together.
  A stage shows up as a named range inside the device trace whenever a
  span trace is active: the span it opens carries the annotation
  (``obs/span.py`` is the one emitter).
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List

from ..obs.span import tracer

# the innermost open stage's ``out`` dict in this context, while
# collection is on: where ``barrier()`` notes that the stage blocked
_OPEN_STAGE: "contextvars.ContextVar[dict | None]" = contextvars.ContextVar(
    "csvplus_open_stage", default=None
)

# count-shaped stage extras that SUM when records of one stage name
# merge (next to the ``_s``-suffix per-worker second tallies); the skew
# trio lets a multi-join pipeline's ``join:skew`` rows report total
# routed rows, not the last join's
_SUMMED_EXTRAS = frozenset(
    {"chunks", "hot_keys", "rows_broadcast", "rows_repartitioned"}
)


@dataclass
class StageRecord:
    """One executed pipeline stage."""

    stage: str  # e.g. "Filter", "Join", "ingest:native-encoded"
    rows_in: int
    rows_out: int
    seconds: float
    # any other keys the stage body set (e.g. the sharded-ingest
    # assembly's n_shards / max_shard_rows placement evidence)
    extra: dict = field(default_factory=dict)

    def __str__(self) -> str:
        return (
            f"{self.stage:<24} {self.rows_in:>12} -> {self.rows_out:<12}"
            f" {self.seconds * 1e3:9.2f} ms"
        )


@dataclass
class Telemetry:
    """Opt-in pipeline statistics collector (process-global singleton)."""

    enabled: bool = False
    records: List[StageRecord] = field(default_factory=list)
    # elements explicitly synced device->host by the partitioned join's
    # device orchestration (hot-key samples + overflow scalars): the
    # evidence that the multi-chip probe path crosses O(1)-ish data per
    # stage, not O(n) (VERDICT round-2 weak #3's done criterion)
    host_sync_elements: int = 0
    # the blocking device->host reads those elements crossed in (one per
    # ``count_sync`` call): a round trip costs the same for 1 element
    # as for 64, so the serve path is judged on this count
    host_syncs: int = 0
    # generic named counters for subsystems whose evidence is a tally,
    # not a stage timing — e.g. the plan verifier's diagnostics-per-rule
    # counts ("verify.resolution", "verify.divergence-risk", ...)
    counters: Dict[str, int] = field(default_factory=dict)
    # mutation guard: ingest workers and the serve dispatcher call
    # count()/add_stage() concurrently with collecting readers
    _lock: threading.Lock = field(
        default_factory=threading.Lock, repr=False, compare=False
    )

    def reset(self) -> None:
        with self._lock:
            self.records.clear()
            self.host_sync_elements = 0
            self.host_syncs = 0
            self.counters.clear()

    def count_sync(self, n: int) -> None:
        if self.enabled:
            with self._lock:
                self.host_sync_elements += int(n)
                self.host_syncs += 1

    def count(self, name: str, n: int = 1) -> None:
        """Bump a named counter (no-op unless collection is enabled)."""
        if self.enabled:
            with self._lock:
                self.counters[name] = self.counters.get(name, 0) + int(n)

    @contextlib.contextmanager
    def collect(self) -> Iterator[List[StageRecord]]:
        """Enable collection within a scope; yields the record list."""
        prev = self.enabled
        self.enabled = True
        self.reset()
        try:
            yield self.records
        finally:
            self.enabled = prev

    @contextlib.contextmanager
    def stage(self, name: str, rows_in: int) -> Iterator[dict]:
        """Record one stage; the body may set ``out['rows_out']``, or set
        ``out['discard'] = True`` to drop the record (e.g. a fast-path
        tier that declined and handed off to another tier).

        Span shim: when a trace is active in the calling context
        (:data:`csvplus_tpu.obs.span.tracer`), the stage also opens a
        child span there — the hierarchical view needs no new call
        sites — and the span carries the ``csvplus:<stage>`` profiler
        annotation.  An open milestone (``tracer.milestone``: an ingest,
        an index build, a plan's first run) is such a context too, with
        collection off as well as on: the stage then lands in the process
        journal beneath its milestone, and is still no record of the
        table.  With neither a trace nor a milestone open and collection
        off, a stage costs one ``ContextVar.get``.  The span keeps even discarded/failed stages
        (annotated), because a trace records what HAPPENED, while the
        table records what counted.  A stage inside which
        :meth:`barrier` blocked says so: ``synced`` and ``wait_s`` in
        its extras (and span attrs), so ``seconds - wait_s`` is the
        host's own time in the stage."""
        handle = tracer.open_span(name, rows_in=int(rows_in))
        if not self.enabled and handle is None:
            yield {}
            return
        out: dict = {}
        t0 = time.perf_counter()
        token = _OPEN_STAGE.set(out) if self.enabled else None
        try:
            yield out
        except BaseException:
            if handle is not None:
                tracer.close_span(handle, error=True, **out)
                handle = None
            raise
        finally:
            if token is not None:
                _OPEN_STAGE.reset(token)
            if handle is not None:
                tracer.close_span(handle, **out)
        if out.get("discard") or not self.enabled:
            return
        with self._lock:
            self.records.append(
                StageRecord(
                    stage=name,
                    rows_in=rows_in,
                    rows_out=int(out.get("rows_out", rows_in)),
                    seconds=time.perf_counter() - t0,
                    extra={
                        k: v
                        for k, v in out.items()
                        if k not in ("rows_out", "discard")
                    },
                )
            )

    def barrier(self, x):
        """``jax.block_until_ready(x)`` when collecting, so async device
        work lands inside the stage that dispatched it and per-stage
        times are attributable; the open stage records that it blocked
        and for how long (``synced``, ``wait_s``).  A strict no-op (and
        zero dispatch-overlap cost) when collection is off — headline
        timings are measured with telemetry disabled, the per-stage
        table with it enabled."""
        if self.enabled and x is not None:
            import jax

            t0 = time.perf_counter()
            jax.block_until_ready(x)
            out = _OPEN_STAGE.get()
            if out is not None:
                out["synced"] = True
                out["wait_s"] = out.get("wait_s", 0.0) + time.perf_counter() - t0
        return x

    def add_stage(
        self, name: str, rows_in: int, rows_out: int, seconds: float, **extra
    ) -> None:
        """Record a PRE-MEASURED stage — for work accumulated across many
        small slices (e.g. per-chunk producer waits or per-shard seals in
        the streaming ingest) where a contextmanager per slice would
        drown the measurement in bookkeeping.  One record per call; also
        mirrored as a pre-measured span when a trace is active."""
        tracer.add_span(name, float(seconds), rows_in=int(rows_in), **extra)
        if not self.enabled:
            return
        with self._lock:
            self.records.append(
                StageRecord(
                    stage=name,
                    rows_in=int(rows_in),
                    rows_out=int(rows_out),
                    seconds=float(seconds),
                    extra=extra,
                )
            )

    def merged_stages(self) -> List[StageRecord]:
        """Records merged by stage name (first-seen order): seconds and
        row counts summed; ACCUMULABLE extras (keys ending in ``_s`` —
        per-worker second tallies like the staged ingest's ``scan_s`` /
        ``encode_s`` — plus the count-shaped ``chunks`` and the skew
        router's ``hot_keys`` / ``rows_broadcast`` /
        ``rows_repartitioned``) sum too, all other extras taken
        from the last record of the name (configuration-shaped values
        like ``workers`` or ``max_shard_rows`` must not add across
        records).  This is the per-stage table shape the bench artifacts
        carry — a 3-join pipeline records e.g. 'join:translate' once per
        join, but the artifact wants one line per stage kind."""
        with self._lock:
            records = list(self.records)
        order: List[str] = []
        merged: Dict[str, StageRecord] = {}
        for r in records:
            got = merged.get(r.stage)
            if got is None:
                order.append(r.stage)
                merged[r.stage] = StageRecord(
                    r.stage, r.rows_in, r.rows_out, r.seconds, dict(r.extra)
                )
            else:
                got.rows_in += r.rows_in
                got.rows_out += r.rows_out
                got.seconds += r.seconds
                for k, v in r.extra.items():
                    old = got.extra.get(k)
                    if (
                        (k.endswith("_s") or k in _SUMMED_EXTRAS)
                        and isinstance(v, (int, float))
                        and isinstance(old, (int, float))
                    ):
                        got.extra[k] = old + v
                    else:
                        got.extra[k] = v
        return [merged[name] for name in order]

    def to_json(self) -> dict:
        """JSON-safe snapshot: the merged stage table plus counters and
        host-sync accounting — the exact shape the bench artifacts
        embed, so drivers stop hand-rolling it."""
        merged = self.merged_stages()
        with self._lock:
            counters = dict(self.counters)
            host_sync = self.host_sync_elements
            host_syncs = self.host_syncs
        return {
            "stage_table": [
                {
                    "stage": r.stage,
                    "rows_in": r.rows_in,
                    "rows_out": r.rows_out,
                    "seconds": round(r.seconds, 4),
                    **r.extra,
                }
                for r in merged
            ],
            "counters": counters,
            "host_sync_elements": host_sync,
            "host_syncs": host_syncs,
        }

    def report(self) -> str:
        head = f"{'stage':<24} {'rows in':>12}    {'rows out':<12} {'time':>9}"
        with self._lock:
            records = list(self.records)
            counters = dict(self.counters)
            host_sync = self.host_sync_elements
        lines = [head] + [str(r) for r in records]
        if counters:
            lines.append("counters:")
            lines.extend(
                f"  {name:<38} {counters[name]:>12}"
                for name in sorted(counters)
            )
        lines.append(f"host_sync_elements: {host_sync}")
        return "\n".join(lines)


telemetry = Telemetry()


@contextlib.contextmanager
def profile_to(log_dir: str):
    """Capture a JAX device trace of the enclosed pipeline run for
    XProf/Perfetto (``jax.profiler.trace``).  Host-side spans exported
    with :func:`csvplus_tpu.obs.export.export_chrome_trace` into the
    same ``log_dir`` open alongside it."""
    import jax.profiler

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
