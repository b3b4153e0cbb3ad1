"""The reduction from the JAX profiler's trace to device numbers.

``reduce_profile`` reads the ``.xplane.pb`` the profiler wrote (with
nothing but ``jax.profiler.ProfileData``) into plain event lists, and
``reduce_events`` turns those into:

- ``busy_s``: the seconds in which an operation ran on the device: the
  union of the intervals of the device planes' ``XLA Ops`` events,
  clipped to the window, averaged over the device planes;
- ``window_s``: the length of the ``bench:window`` host annotation that
  ``run.py`` wraps around the measured call (the trace's own clock),
  less the ``bench:digest`` annotations inside it: there the host
  blocks on the benchmark's own digest programs, so those stretches and
  the operations in them belong neither to the program's busy time nor
  to its idle time (a driver calls the digest on a synced result and
  waits for its sums, so no operation of the program runs in them);
- ``breakdown.device_ops``: the ten operations that took most device
  time, by the names the trace prints;
- ``breakdown.idle_gaps``: the device's idle time by what the host was
  doing: each gap between operations is labelled with the innermost
  ``bench:<call>`` or ``csvplus:<stage>`` host annotation that covers
  its midpoint, and the ten labels with most idle time are given.

As a reader (``read``) it gives ``idle_pct`` and ``bytes_roofline_pct``.
"""

from __future__ import annotations

import glob
import os
import shutil

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW = "bench:window"
OWN = "bench:digest"  # the benchmark's own device work between executions
LABELS = ("bench:", "csvplus:")
NAME_CHARS = 160  # the trace prints an operation as its whole HLO line


def load_xplane(path: str) -> dict:
    """{"device": {plane: [(name, start_ns, dur_ns)]}, "host": [...]}
    from one .xplane.pb.  Host events are kept only where their name is
    one of ours (``bench:`` / ``csvplus:``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: dict = {}
    host: list = []
    lines_seen: dict = {}
    for plane in pd.planes:
        lines_seen[plane.name] = [ln.name for ln in plane.lines]
        if plane.name.startswith(DEVICE_PLANE):
            for ln in plane.lines:
                if ln.name == OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        (ev.name[:NAME_CHARS], float(ev.start_ns), float(ev.duration_ns))
                        for ev in ln.events
                    )
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(LABELS):
                        host.append((ev.name, float(ev.start_ns), float(ev.duration_ns)))
    return {"device": device, "host": host, "lines": lines_seen}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_events(device: dict, host: list, top: int = 10):
    """See the module's docstring.  None when there is no window
    annotation or no device plane to read."""
    windows = [(s, s + d) for name, s, d in host if name == WINDOW]
    if not windows or not device:
        return None
    w0, w1 = windows[0]
    own = _union([(max(s, w0), min(s + d, w1)) for name, s, d in host if name == OWN and s + d > w0 and s < w1])
    # the stretches that count: the window less the benchmark's own work
    edges = [w0] + [t for iv in own for t in iv] + [w1]
    counted = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    labels = sorted(
        ((d, s, s + d, name) for name, s, d in host if name not in (WINDOW, OWN)),
        key=lambda t: t[0],
    )  # shortest first: the innermost annotation that covers a point wins

    def label_at(t: float) -> str:
        for _, s, e, name in labels:
            if s <= t < e:
                return name
        return "(no annotation)"

    busy_ns = 0.0
    op_ns: dict = {}
    gap_ns: dict = {}
    for events in device.values():
        for c0, c1 in counted:
            inside = [(name, max(s, c0), min(s + d, c1)) for name, s, d in events if s + d > c0 and s < c1]
            merged = _union([(s, e) for _, s, e in inside])
            busy_ns += sum(e - s for s, e in merged)
            for name, s, e in inside:
                op_ns[name] = op_ns.get(name, 0.0) + (e - s)
            edges = [c0] + [t for iv in merged for t in iv] + [c1]
            for g0, g1 in zip(edges[0::2], edges[1::2]):
                if g1 > g0:
                    lab = label_at((g0 + g1) / 2)
                    gap_ns[lab] = gap_ns.get(lab, 0.0) + (g1 - g0)
    n = len(device)

    def ranked(table: dict) -> list:
        return [
            [name, ns / n / 1e9]
            for name, ns in sorted(table.items(), key=lambda kv: -kv[1])[:top]
        ]

    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": sum(b - a for a, b in counted) / 1e9,
        "n_device_planes": n,
        "breakdown": {"device_ops": ranked(op_ns), "idle_gaps": ranked(gap_ns)},
    }


def reduce_profile(trace_dir: str, host_window_s: float, keep=None):
    """Reduce the newest .xplane.pb under *trace_dir*; *keep* copies it
    to that directory first.  None when the profiler wrote none."""
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return None
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(found[-1], keep)
    ev = load_xplane(found[-1])
    red = reduce_events(ev["device"], ev["host"])
    print(
        f"trace: {os.path.getsize(found[-1]) / 1e6:.1f} MB, planes and lines {ev['lines']}; "
        f"host clock window {host_window_s:.3f}s", flush=True,
    )
    if red is not None:
        print(
            f"trace: window {red['window_s']:.3f}s busy {red['busy_s']:.3f}s over "
            f"{red['n_device_planes']} device plane(s)", flush=True,
        )
    return red


def read(h, state, samples, selector: dict):
    red = h.evidence.get("trace")
    if red is None or red["busy_s"] <= 0:
        return None
    what = selector["what"]
    if what == "idle_pct":
        return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    if what == "bytes_roofline_pct":
        least_bytes = h.load_module("least_bytes", selector["least_bytes"]).least_bytes
        executions = h.evidence["facts"].get("executions")
        peaks = h.evidence.get("peaks")
        if not executions or not peaks:
            return None
        need = least_bytes(h.cfg, h.data.n)
        least_s = need / peaks["hbm_bytes_per_s"]
        return 100.0 * least_s / (red["busy_s"] / executions)
    raise ValueError(f"device_trace: unknown selector {selector!r}")
