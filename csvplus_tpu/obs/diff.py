"""Stage-table regression differ: ``python -m csvplus_tpu.obs diff``.

Productizes the r05 -> r06 diagnosis workflow: the warm-join regression
was found by comparing two runs' per-stage tables by hand and noticing
``join:translate`` / ``join:pack`` had grown from noise to dominant.
This module does that comparison mechanically over two artifacts:

* a stage's **time share** (its seconds over the table's total) and its
  **per-row time** (seconds over rows) are both computed per side — the
  per-row metric makes tables from different row tiers comparable (the
  r05 table is a 10M-row run, the r06 record a 100M-row run);
* a stage is **flagged** when either metric moved by more than
  ``--threshold`` (default 2x) in either direction AND the stage is big
  enough to matter on at least one side (``--min-share``, default 0.5%
  of total time) — tiny stages jitter, and a 3x move on 0.1% of the
  run is not a diagnosis;
* stages present on only one side are reported separately (a renamed or
  newly-instrumented stage is signal too, just different signal);
* when both sides carry an ``rss_peak_mb`` extra for a stage (the
  :func:`csvplus_tpu.obs.memory.watch_memory` column), its ratio is
  diffed under the same threshold.

Accepted inputs: any JSON file whose top level is a stage list, or an
artifact dict carrying one under ``stage_table`` / ``stage_table_auto``
/ ``stage_table_serial`` / ``stages`` (first match; override with
``--key``).  Each stage row needs ``stage`` and ``seconds``; ``rows_in``
/ ``rows_out`` enable the per-row metric.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence, Tuple

#: Artifact keys probed, in order, for the embedded stage table.
STAGE_TABLE_KEYS = (
    "stage_table",
    "stage_table_auto",
    "stage_table_serial",
    "stages",
)

DEFAULT_THRESHOLD = 2.0
DEFAULT_MIN_SHARE = 0.005


def load_stage_table(
    path: str, key: Optional[str] = None
) -> List[Dict[str, Any]]:
    """The stage list embedded in *path* (see the module docstring for
    the accepted shapes).  Raises ``ValueError`` with the keys that
    were probed when the artifact carries no stage table."""
    with open(path) as f:
        obj = json.load(f)
    if isinstance(obj, list):
        table = obj
    elif isinstance(obj, dict):
        keys = (key,) if key else STAGE_TABLE_KEYS
        table = next((obj[k] for k in keys if obj.get(k)), None)
        if table is None:
            raise ValueError(
                f"{path}: no stage table under {', '.join(k for k in keys if k)}"
                " — pass --key for a nonstandard artifact"
            )
    else:
        raise ValueError(f"{path}: top level is {type(obj).__name__}")
    out = []
    for row in table:
        if not isinstance(row, dict) or "stage" not in row or "seconds" not in row:
            raise ValueError(f"{path}: stage row missing stage/seconds: {row!r}")
        out.append(row)
    return out


def _stage_facts(table: Sequence[Dict[str, Any]]) -> Dict[str, Dict[str, float]]:
    total = sum(float(r["seconds"]) for r in table) or 1.0
    facts: Dict[str, Dict[str, float]] = {}
    for r in table:
        sec = float(r["seconds"])
        rows = max(int(r.get("rows_in", 0)), int(r.get("rows_out", 0)))
        facts[str(r["stage"])] = {
            "seconds": sec,
            "share": sec / total,
            "ns_per_row": (sec / rows * 1e9) if rows > 0 else None,
            "rss_peak_mb": r.get("rss_peak_mb"),
        }
    return facts


def _ratio(a: Optional[float], b: Optional[float]) -> Optional[float]:
    if a is None or b is None or a <= 0 or b <= 0:
        return None
    return a / b


def diff_stage_tables(
    table_a: Sequence[Dict[str, Any]],
    table_b: Sequence[Dict[str, Any]],
    *,
    threshold: float = DEFAULT_THRESHOLD,
    min_share: float = DEFAULT_MIN_SHARE,
) -> Dict[str, Any]:
    """Compare two stage tables; see the module docstring for the
    flagging rule.  Returns a JSON-safe dict with per-stage ``rows``,
    the ``flagged`` stages (worst movement first, each tagged with the
    side it regressed in), and the one-sided stage lists."""
    fa, fb = _stage_facts(table_a), _stage_facts(table_b)
    rows: List[Dict[str, Any]] = []
    flagged: List[Dict[str, Any]] = []
    for stage in [s for s in fa if s in fb]:
        a, b = fa[stage], fb[stage]
        share_ratio = _ratio(a["share"], b["share"])
        row_ratio = _ratio(a["ns_per_row"], b["ns_per_row"])
        rss_ratio = _ratio(a["rss_peak_mb"], b["rss_peak_mb"])
        # movement = the larger departure from 1.0 among the metrics,
        # measured symmetrically (2.0 and 0.5 are the same movement)
        movement = max(
            (max(r, 1.0 / r) for r in (share_ratio, row_ratio, rss_ratio) if r),
            default=1.0,
        )
        big_enough = max(a["share"], b["share"]) >= min_share
        flag = big_enough and movement >= threshold
        # the side whose cost is HIGHER is the regressed side; per-row
        # time decides when available (scale-invariant), share otherwise
        decider = row_ratio if row_ratio is not None else share_ratio
        regressed_in = None
        if flag and decider is not None:
            regressed_in = "A" if decider > 1.0 else "B"
        row = {
            "stage": stage,
            "share_a": round(a["share"], 4),
            "share_b": round(b["share"], 4),
            "ns_per_row_a": _rnd(a["ns_per_row"]),
            "ns_per_row_b": _rnd(b["ns_per_row"]),
            "movement": round(movement, 2),
            "flagged": flag,
            "regressed_in": regressed_in,
        }
        if rss_ratio is not None:
            row["rss_peak_mb_a"] = a["rss_peak_mb"]
            row["rss_peak_mb_b"] = b["rss_peak_mb"]
        rows.append(row)
        if flag:
            flagged.append(row)
    flagged.sort(key=lambda r: -r["movement"])
    return {
        "threshold": threshold,
        "min_share": min_share,
        "rows": rows,
        "flagged": flagged,
        "only_in_a": [s for s in fa if s not in fb],
        "only_in_b": [s for s in fb if s not in fa],
    }


def _rnd(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(v, 3)


def format_diff(result: Dict[str, Any], label_a: str, label_b: str) -> str:
    """Human-readable report (the CLI's default output)."""
    lines = [
        f"stage-table diff: A={label_a}  B={label_b}",
        f"threshold {result['threshold']}x, min share"
        f" {result['min_share'] * 100:.1f}%",
        "",
        f"{'stage':<24} {'share A':>8} {'share B':>8} {'ns/row A':>10}"
        f" {'ns/row B':>10} {'move':>6}  flag",
    ]
    for r in result["rows"]:
        nra = "-" if r["ns_per_row_a"] is None else f"{r['ns_per_row_a']:.2f}"
        nrb = "-" if r["ns_per_row_b"] is None else f"{r['ns_per_row_b']:.2f}"
        mark = f"REGRESSED in {r['regressed_in']}" if r["flagged"] else ""
        lines.append(
            f"{r['stage']:<24} {r['share_a'] * 100:>7.2f}%"
            f" {r['share_b'] * 100:>7.2f}% {nra:>10} {nrb:>10}"
            f" {r['movement']:>5.2f}x  {mark}"
        )
    for side, stages in (("A", result["only_in_a"]), ("B", result["only_in_b"])):
        if stages:
            lines.append(f"only in {side}: {', '.join(stages)}")
    if result["flagged"]:
        worst = ", ".join(
            f"{r['stage']} ({r['movement']:.1f}x in {r['regressed_in']})"
            for r in result["flagged"]
        )
        lines.append(f"flagged: {worst}")
    else:
        lines.append("flagged: none")
    return "\n".join(lines)


def diff_files(
    path_a: str,
    path_b: str,
    *,
    threshold: float = DEFAULT_THRESHOLD,
    min_share: float = DEFAULT_MIN_SHARE,
    key: Optional[str] = None,
) -> Dict[str, Any]:
    """Load both artifacts and diff their stage tables."""
    return diff_stage_tables(
        load_stage_table(path_a, key),
        load_stage_table(path_b, key),
        threshold=threshold,
        min_share=min_share,
    )
