"""CLI for the observability subsystem.

``python -m csvplus_tpu.obs skew ARTIFACT.json [--top N] [--side
probe|build] [--json]``
    Render the heavy-hitter report from an artifact carrying sketch
    snapshots — a flight-recorder dump or any
    JSON embedding a ``skew`` section (``{probe: {index: snapshot},
    build: {...}}``) or a bare sketch ``snapshot()`` dict.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Tuple

from .sketch import skew_report


def _find_sketches(obj: Any) -> List[Tuple[str, Dict[str, Any]]]:
    """Locate sketch snapshots in an arbitrary artifact: a bare
    snapshot (``k``/``observed``/``top`` keys), or a ``skew`` section
    mapping side -> index -> snapshot (the :meth:`TelemetryPlane
    .skew_snapshot` shape), searched one level deep under common
    wrapper keys."""
    out: List[Tuple[str, Dict[str, Any]]] = []
    if not isinstance(obj, dict):
        return out
    if {"k", "observed", "top"} <= set(obj):
        return [("sketch", obj)]
    skew = obj.get("skew") or obj
    for side in ("probe", "build"):
        sides = skew.get(side)
        if isinstance(sides, dict):
            for index, snap in sorted(sides.items()):
                if isinstance(snap, dict) and {"observed", "top"} <= set(snap):
                    out.append((f"{side}:{index}", snap))
    if not out:
        for wrapper in ("context", "obs", "telemetry"):
            inner = obj.get(wrapper)
            if isinstance(inner, dict):
                out.extend(
                    (f"{wrapper}.{name}", snap)
                    for name, snap in _find_sketches(inner)
                )
    return out


def _run_skew(args) -> int:
    with open(args.artifact) as f:
        obj = json.load(f)
    found = _find_sketches(obj)
    if args.side:
        found = [(n, s) for n, s in found if n.startswith(args.side)]
    if not found:
        raise ValueError(
            f"{args.artifact}: no sketch snapshots found"
            " (expected a `skew` section or a {k, observed, top} dict)"
        )
    if args.json:
        print(json.dumps({name: snap for name, snap in found}))
        return 0
    for i, (name, snap) in enumerate(found):
        if i:
            print()
        print(f"[{name}]")
        print(skew_report(snap, top=args.top))
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="python -m csvplus_tpu.obs")
    sub = parser.add_subparsers(dest="cmd", required=True)

    s = sub.add_parser("skew", help="heavy-hitter report from sketch snapshots")
    s.add_argument("artifact")
    s.add_argument("--top", type=int, default=10)
    s.add_argument("--side", choices=("probe", "build"), default=None)
    s.add_argument("--json", action="store_true", help="machine output")

    args = parser.parse_args(argv)
    try:
        return _run_skew(args)
    except (OSError, ValueError) as e:
        print(f"obs {args.cmd}: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    sys.exit(main())
