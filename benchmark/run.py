"""One process, one cell, once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, kind of traffic or
per-layer metric is a file found by name (see README.md):

    workloads/<cell>.json   configs/<config>.json   gen/<gen>.py
    drivers/<driver>.py   queries/<query>.py   least_bytes/<query>.py
    layer_metrics/<metric>.json   readers/<reader>.py

and a per-layer metric is read in the cells that its entry in
``BENCHMARK.json`` lists (``layer_metrics_for``).

The run places the compile cache, refuses a machine without the TPU
the cell asks for (``--rehearse-cpu``, which only a caller passes, is
the one way onto a CPU), builds the native scanner, generates the data
from ``--seed`` outside the checkout, sets up, warms up, measures for
``--seconds``, checks what the window produced against the generator's
arrays, and prints last one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.

``--trace 0`` reports the cell's end-to-end metrics.  ``--trace 1``
measures a short stretch (the cell's ``trace`` entry) under the JAX
profiler, the stage table and the span tracer, and reports the cell's
per-layer metrics.  The benchmark sets no ``CSVPLUS_*`` variable.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us read it

import argparse
import contextlib
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """workloads/<name>.json, carrying its own name."""
    return dict(load_json("workloads", f"{name}.json"), name=name)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py as a module, found by name."""
    path = os.path.join(HERE, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{name}", path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Compiles:
    """jax's compile seconds and persistent-cache hits and misses
    (``chip_smoke.listening_for_compiles``, copied): how a run counts
    the compilations that fall inside its window."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.hits = 0
        self.misses = 0

    def mark(self) -> tuple:
        return (self.seconds, self.count, self.hits, self.misses)

    def since(self, mark: tuple) -> dict:
        s, c, h, m = mark
        return {
            "compile_s": self.seconds - s, "compiles": self.count - c,
            "cache_hits": self.hits - h, "cache_misses": self.misses - m,
        }

    @contextlib.contextmanager
    def listening(self):
        import jax.monitoring as mon

        def on_duration(event: str, seconds: float, **kw) -> None:
            if event.startswith("/jax/core/compile/"):
                self.seconds += seconds
                if event.endswith("backend_compile_duration"):
                    self.count += 1

        def on_event(event: str, **kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)
        try:
            yield self
        finally:
            mon.unregister_event_duration_listener(on_duration)
            mon.unregister_event_listener(on_event)


class Harness:
    """What a driver and a reader are handed: the cell, its
    configuration, the generated data, and the run's evidence."""

    def __init__(self, cell, cfg, seed, platform, root, traced, compiles, out):
        self.cell = cell
        self.seed = seed
        self.cfg = cfg
        self.platform = platform
        self.root = root  # scratch directory outside the checkout
        self.traced = traced
        self.compiles = compiles
        self.out = out
        self.gen = None  # gen/<the configuration's "gen">.py
        self.data = None
        self.phases: dict = {}  # set-up split: name -> seconds
        # what readers read: "stages" and "host_sync_elements" (per
        # execution), "tracer" (the window's span trace), "counters",
        # "facts", "trace" (the reduced profile), "peaks"
        self.evidence: dict = {"facts": {}, "counters": {}}

    load_module = staticmethod(load_module)

    def say(self, line: str) -> None:
        print(line, file=self.out, flush=True)

    @contextlib.contextmanager
    def phase(self, name: str):
        """One part of set-up, printed with its wall and compile seconds."""
        mark, t0 = self.compiles.mark(), time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        self.phases[name] = self.phases.get(name, 0.0) + wall
        c = self.compiles.since(mark)
        self.say(
            f"setup {name}: wall={wall:.2f}s compile={c['compile_s']:.2f}s "
            f"cache_hits={c['cache_hits']} cache_misses={c['cache_misses']}"
        )

    def annotate(self, name: str):
        """A host span in the profiler's own trace, around a call into a
        layer; nothing when the run is not traced."""
        if not self.traced:
            return contextlib.nullcontext()
        import jax.profiler

        return jax.profiler.TraceAnnotation(f"bench:{name}")


def build_scanner() -> str:
    """Remove any scanner binary lying in the tree and build from
    scanner.cpp (``chip_smoke.build_scanner``, copied); a failure raises,
    so ingest cannot drop to the Python parser unannounced."""
    import csvplus_tpu.native as native_pkg

    here = os.path.dirname(os.path.abspath(native_pkg.__file__))
    for stale in glob.glob(os.path.join(here, "*.so")) + glob.glob(
        os.path.join(here, "*.so.*")
    ):
        os.remove(stale)
    from csvplus_tpu.native import scanner

    so = scanner._build()
    scanner._load()
    return so


def device_facts(devices, peaks: dict, rehearse: bool) -> dict:
    kind = devices[0].device_kind
    if kind not in peaks and not rehearse:
        raise SystemExit(
            f"benchmark: device_kind {kind!r} is not in benchmark/peaks.json; "
            "an unknown device is an error, not a default"
        )
    return {"platform": devices[0].platform, "kind": kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device (0 where the backend does
    not report it, which only a rehearsal on the CPU meets)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def layer_metrics_for(cell: dict) -> list:
    """The per-layer metrics of one cell: the entries of ``BENCHMARK.json``
    ``per_layer`` whose ``workloads`` list it, each with its
    ``layer_metrics/<name>.json`` (``reader``, ``selector``, ``unit``).

    One file is one quantity, read in whichever cells list it.  What
    differs by cell inside a quantity, the least-bytes file of a roofline
    share, is the cell's to name (``"least_bytes": {metric: file}`` in its
    ``workloads/`` file) and the selector's own is the fallback; the
    reader is handed the selector with the file resolved.  A roofline share
    with no file, a file that is not there, and a named metric the cell is
    not listed for end the run here, at start-up."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = [m["name"] for m in json.load(f)["per_layer"] if cell["name"] in m["workloads"]]
    named = cell.get("least_bytes", {})
    stray = sorted(set(named) - set(listed))
    if stray:
        raise SystemExit(
            f"benchmark: workloads/{cell['name']}.json names least bytes for {stray}, "
            "which BENCHMARK.json does not list the cell for"
        )
    found = []
    for name in listed:
        m = load_json("layer_metrics", f"{name}.json")
        m["name"] = name
        selector = m["selector"] = dict(m.get("selector", {}))
        if name in named:
            selector["least_bytes"] = named[name]
        if selector.get("what", "").endswith("roofline_pct"):
            least = selector.get("least_bytes")
            if least is None or not os.path.exists(os.path.join(HERE, "least_bytes", f"{least}.py")):
                raise SystemExit(
                    f"benchmark: {name} in {cell['name']}: no least-bytes file {least!r} "
                    "(the cell's workloads/ file names it, else the metric's selector)"
                )
        found.append(m)
    return found


def main(argv=None, out=sys.stdout, tamper=None) -> int:
    """*tamper*, which only the tests and the control pass, is called
    with the driver's state after set-up and breaks the timed path."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="rehearse on a machine without a chip (never the default)",
    )
    ap.add_argument(
        "--rehearse-rows", type=int, default=None,
        help="rows of the configuration's fact table for a rehearsal (refused without --rehearse-cpu)",
    )
    ap.add_argument(
        "--keep-trace", default=None,
        help="copy the profiler's .xplane.pb into this directory",
    )
    args = ap.parse_args(argv)
    if args.rehearse_rows is not None and not args.rehearse_cpu:
        ap.error("--rehearse-rows is for --rehearse-cpu only")
    traced = bool(args.trace)

    # the benchmark's own modules (reference, gen, readers), then the
    # program under test (csvplus_tpu)
    sys.path[:0] = [p for p in (HERE, ROOT) if p not in sys.path]
    cell = load_cell(args.workload)
    cfg = load_json("configs", f"{cell['config']}.json")
    layer_metrics = layer_metrics_for(cell)
    peaks = load_json("peaks.json")
    driver = load_module("drivers", cell["driver"])

    import jax
    import jaxlib

    from csvplus_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    # where the cache was placed from outside, jax keeps only programs that
    # took a second to compile; the many small ones are most of a warm set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse_cpu:
        print(
            f"benchmark: JAX's default backend is {platform!r}, not a TPU; nothing "
            "was run (pass --rehearse-cpu only to rehearse)", file=sys.stderr,
        )
        return 2
    if len(devices) < int(cfg["chips"]):
        print(
            f"benchmark: {args.workload} needs {cfg['chips']} chip(s), JAX found "
            f"{len(devices)}; nothing was run", file=sys.stderr,
        )
        return 2
    device = device_facts(devices, peaks, args.rehearse_cpu)
    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    print(
        f"benchmark: workload={args.workload} config={cell['config']} seed={args.seed} "
        f"seconds={args.seconds:g} trace={args.trace} platform={platform} "
        f"device_kind={device['kind']} devices={device['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu} host_cpus={os.cpu_count()} "
        f"compile_cache={cache_dir or 'none (CPU backend)'}"
        + (" REHEARSAL-ON-CPU: no number below is a device number" if platform != "tpu" else ""),
        file=out, flush=True,
    )

    compiles = Compiles()
    root = tempfile.mkdtemp(prefix="csvplus_bench_")
    h = Harness(cell, cfg, args.seed, platform, root, traced, compiles, out)
    try:
        with compiles.listening():
            return _run(h, driver, args, devices, device, peaks, layer_metrics, tamper)
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _run(h: Harness, driver, args, devices, device, peaks, layer_metrics, tamper) -> int:
    import jax

    from csvplus_tpu.obs.span import tracer
    from csvplus_tpu.utils.observe import telemetry

    with h.phase("scanner"):
        so = build_scanner()
    h.say(f"  native scanner built from scanner.cpp ({so})")
    with h.phase("generate"):
        h.gen = load_module("gen", h.cfg["gen"])
        h.data = h.gen.Data(h.cfg, args.seed, h.root, h.cell["files"], args.rehearse_rows)
    h.say(
        "  generated " + ", ".join(
            f"{k} {os.path.getsize(h.data.paths[k]) / 1e6:,.1f} MB" for k in h.data.files
        ) + f" under {h.root}"
    )
    state = driver.setup(h)
    try:  # whatever happens, the driver stops what it started (the server's thread)
        if tamper is not None:
            tamper(state)
        driver.warm(h, state)
        setup_compiles = h.compiles.since((0.0, 0, 0, 0))

        # ---- the measured window: nothing compiles in here ----
        # set-up's garbage (the generator's lists, the warm-up's replies) is
        # collected now and what stays is frozen, so that no full collection
        # over the harness's own heap stalls the host inside the window
        gc.collect()
        gc.freeze()
        mark = h.compiles.mark()
        with contextlib.ExitStack() as stack:
            trace_dir = None
            if h.traced:
                stack.enter_context(telemetry.collect())
                h.evidence["tracer"] = stack.enter_context(tracer.trace("bench-window"))
                trace_dir = os.path.join(h.root, "profile")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0  # our annotations are enough; a Python
                opts.enable_hlo_proto = False  # trace of a serving loop is huge
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            setup_s = time.perf_counter() - _T0
            t0 = time.perf_counter()
            try:
                with h.annotate("window"):
                    samples = driver.measure(h, state, args.seconds)
            finally:
                window_s = time.perf_counter() - t0
                if h.traced:
                    jax.profiler.stop_trace()
        in_window = h.compiles.since(mark)
        peak_bytes = memory_peak_bytes(devices)
        h.evidence["facts"]["peak_hbm_bytes"] = peak_bytes
        h.say(
            f"window: {window_s:.2f}s compiles={in_window['compiles']} "
            f"compile_s={in_window['compile_s']:.3f} cache_misses={in_window['cache_misses']} "
            f"(limit 0 each); set-up before it: {setup_s:.2f}s of which "
            + " ".join(f"{k}={v:.2f}s" for k, v in h.phases.items())
            + f"; set-up compiles={setup_compiles['compiles']} compile_s={setup_compiles['compile_s']:.2f} "
            f"cache_hits={setup_compiles['cache_hits']} cache_misses={setup_compiles['cache_misses']}"
        )

        # ---- the reference check, outside set-up and outside the window ----
        t0 = time.perf_counter()
        correct, attempted, failed = driver.check(h, state, samples)
        h.say(f"check: {time.perf_counter() - t0:.2f}s correct={correct} attempted={attempted} failed={failed}")
        quiet = in_window["compiles"] == 0 and in_window["cache_misses"] == 0
        if not quiet:
            h.say("check: a compilation or a compile-cache miss fell inside the window")
        correct = bool(correct and quiet)

        device = dict(device, memory_peak_bytes=peak_bytes)
        result = {"correct": correct, "attempted": int(attempted), "failed": int(failed)}
        if h.traced:
            from readers.device_trace import reduce_profile

            red = reduce_profile(trace_dir, window_s, keep=args.keep_trace)
            h.evidence["trace"] = red
            h.evidence["peaks"] = peaks.get(device["kind"])
            if red is not None:
                device["busy_s"] = red["busy_s"]
                device["window_s"] = red["window_s"]
                result["breakdown"] = red["breakdown"]
            metrics = {}
            for m in layer_metrics:
                reader = load_module("readers", m["reader"])
                value = reader.read(h, state, samples, m["selector"])
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            metrics = {
                name: {"value": value, "unit": unit}
                for name, (value, unit) in driver.end_to_end(h, state, samples).items()
            }
            metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        result["metrics"] = metrics
        result["device"] = device
    finally:
        getattr(driver, "close", lambda h, state: None)(h, state)
    print(json.dumps(result), file=h.out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
