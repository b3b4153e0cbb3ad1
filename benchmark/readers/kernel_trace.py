"""Device time by the program's own kernel names.

An ``XLA Ops`` event of a TPU profile carries a name (the HLO line), a
start and a duration and nothing else: no scope, no ``op_name``
(PERF.md §3, looked at on the chip in PR 25).  What carries the
program's name is the device plane's ``XLA Modules`` line, one event per
execution of a compiled program, called ``jit_<function>(<fingerprint>)``;
``csvplus_tpu.obs.recompile.register_kernel`` calls its programs
``jit_csvplus.<kernel>``.  So an operation belongs to the module event
that covers its midpoint, and a kernel's device seconds are the union of
its operations' intervals, clipped to the same window as
``device_trace`` uses (``bench:window`` less ``bench:digest``) and
averaged over the device planes.  Operations of a program the library
did not name (an eager ``jnp`` call shows as ``jit__take``) are the
``unnamed`` share.

``reduce_kernels`` is the reduction over plain event lists (tested on
``fixtures/trace_named_small.json``); ``read`` finds the run's
``.xplane.pb`` under the harness's scratch directory, reduces it once
(kept in ``h.evidence``) and answers by selector:

- ``{"what": "kernel_s", "kernels": [prefixes], "per": "execution" |
  "cycle", "scale": 1 | 1000}``: device seconds of the kernels whose
  name starts with one of the prefixes, per execution of the query
  (``facts.executions``) or per dispatch cycle (the ``csvplus:serve:cycle``
  host annotations that start in the window), times ``scale``;
- ``{"what": "unnamed_busy_pct"}``: busy time under no ``csvplus.`` name,
  as a share of all busy time;
- ``{"what": "kernel_roofline_pct", "kernels": [...], "least_bytes":
  file}``: ``least_bytes/<file>.py``'s bytes at the peak's bytes/s over
  those kernels' device seconds per execution.

None (the metric is left out of the line) where the profile names no
``csvplus.`` kernel at all, as a program from before PR 25 does.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

from readers import device_trace as dt

MODULES_LINE = "XLA Modules"
NAMED = "csvplus."
CYCLE = "csvplus:serve:cycle"
_MODULE = re.compile(r"^jit_(.*?)(\(\d+\))?$")


def kernel_of(module_event_name: str) -> str:
    """``jit_csvplus.join.probe_i32(123)`` -> ``csvplus.join.probe_i32``."""
    m = _MODULE.match(module_event_name)
    return m.group(1) if m else module_event_name


def load_xplane(path: str) -> dict:
    """{"ops": {plane: [(name, start_ns, dur_ns)]}, "modules": {plane:
    [...]}, "host": [...]} from one .xplane.pb."""
    from jax.profiler import ProfileData

    ops: dict = {}
    modules: dict = {}
    host: list = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(dt.DEVICE_PLANE):
            for ln in plane.lines:
                into = {dt.OPS_LINE: ops, MODULES_LINE: modules}.get(ln.name)
                if into is not None:
                    into.setdefault(plane.name, []).extend(
                        (ev.name[: dt.NAME_CHARS], float(ev.start_ns), float(ev.duration_ns))
                        for ev in ln.events
                    )
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(dt.LABELS):
                        host.append((ev.name, float(ev.start_ns), float(ev.duration_ns)))
    return {"ops": ops, "modules": modules, "host": host}


def reduce_kernels(ops: dict, modules: dict, host: list):
    """{"kernels": {name: device seconds}, "calls": {name: module events
    in the window}, "busy_s", "unnamed_s", "cycles"}; None without a
    window, a device plane or a modules line."""
    windows = [(s, s + d) for name, s, d in host if name == dt.WINDOW]
    if not windows or not ops or not any(modules.values()):
        return None
    w0, w1 = windows[0]
    own = dt._union(
        [(max(s, w0), min(s + d, w1)) for name, s, d in host if name == dt.OWN and s + d > w0 and s < w1]
    )
    edges = [w0] + [t for iv in own for t in iv] + [w1]
    counted = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]

    def clipped(events):
        for name, s, d in events:
            for c0, c1 in counted:
                if s + d > c0 and s < c1:
                    yield name, max(s, c0), min(s + d, c1)

    per_kernel: dict = {}
    calls: dict = {}
    busy_ns = 0.0
    for plane, events in ops.items():
        mods = sorted((s, s + d, kernel_of(name)) for name, s, d in modules.get(plane, []))
        starts = [m[0] for m in mods]
        by_kernel: dict = {}
        everything = []
        for _, s, e in clipped(events):
            mid = (s + e) / 2
            i = bisect.bisect_right(starts, mid) - 1
            kernel = mods[i][2] if i >= 0 and mid < mods[i][1] else "(no module)"
            by_kernel.setdefault(kernel, []).append((s, e))
            everything.append((s, e))
        busy_ns += sum(e - s for s, e in dt._union(everything))
        for kernel, ivs in by_kernel.items():
            per_kernel[kernel] = per_kernel.get(kernel, 0.0) + sum(e - s for s, e in dt._union(ivs))
        for s, e, kernel in mods:
            if any(s < c1 and e > c0 for c0, c1 in counted):
                calls[kernel] = calls.get(kernel, 0) + 1
    n = len(ops)
    kernels = {k: ns / n / 1e9 for k, ns in per_kernel.items()}
    return {
        "kernels": kernels,
        "calls": {k: c / n for k, c in calls.items()},
        "busy_s": busy_ns / n / 1e9,
        "unnamed_s": sum(s for k, s in kernels.items() if not k.startswith(NAMED)),
        "cycles": sum(1 for name, s, _ in host if name == CYCLE and any(c0 <= s < c1 for c0, c1 in counted)),
    }


def _reduced(h):
    """The run's profile reduced once; None when there is none."""
    if "kernel_trace" not in h.evidence:
        found = sorted(
            glob.glob(os.path.join(h.root, "profile", "plugins", "profile", "*", "*.xplane.pb"))
        )
        red = None
        if found:
            ev = load_xplane(found[-1])
            red = reduce_kernels(ev["ops"], ev["modules"], ev["host"])
        if red is not None:
            top = sorted(red["kernels"].items(), key=lambda kv: -kv[1])[:16]
            h.say(
                f"kernels: busy {red['busy_s']:.4f}s, under no csvplus. name {red['unnamed_s']:.4f}s, "
                f"{red['cycles']} serve cycles; device seconds (module events) by program: "
                + " ".join(f"{k}={s:.5f}({red['calls'].get(k, 0):g})" for k, s in top)
            )
        h.evidence["kernel_trace"] = red
    return h.evidence["kernel_trace"]


def _kernel_seconds(red, prefixes) -> float:
    return sum(s for k, s in red["kernels"].items() if k.startswith(tuple(prefixes)))


def read(h, state, samples, selector: dict):
    red = _reduced(h)
    if red is None or not any(k.startswith(NAMED) for k in red["kernels"]):
        return None
    what = selector["what"]
    if what == "unnamed_busy_pct":
        return 100.0 * red["unnamed_s"] / red["busy_s"] if red["busy_s"] > 0 else None
    if selector.get("per") == "cycle":
        per = red["cycles"]
    else:
        per = h.evidence["facts"].get("executions")
    seconds = _kernel_seconds(red, selector["kernels"])
    if not per or seconds <= 0:
        return None
    if what == "kernel_s":
        return selector.get("scale", 1) * seconds / per
    if what == "kernel_roofline_pct":
        peaks = h.evidence.get("peaks")
        if not peaks:
            return None
        least_bytes = h.load_module("least_bytes", selector["least_bytes"]).least_bytes
        least_s = least_bytes(h.cfg, h.data.n) / peaks["hbm_bytes_per_s"]
        return 100.0 * least_s / (seconds / per)
    raise ValueError(f"kernel_trace: unknown selector {selector!r}")
