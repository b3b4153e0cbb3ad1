"""CPU correctness gate (re-execs onto 8 simulated CPU devices before
JAX is touched): the TPC-H-flavored macro-bench — named query chains
through the PlanCache, optimizer-on vs ``CSVPLUS_FUSE=0`` in the SAME
child over identical bytes (ISSUE 19, ROADMAP item 1's open workload).

Five named queries — multi-join star shapes, filters, projection, and
a positional ``Top`` terminal (the plan vocabulary's order-sensitive
tail; there is no sort node) — over uniform AND Zipf(s=1.1) fact keys,
one of them on the hermetic 8-device mesh.  The headline queries join
a REGION-RESTRICTED customer dimension (TPC-H Q5's shape: only ~1/7 of
fact keys find a partner), because that is where fusion's economics
live: the staged leg materializes the full post-filter width before
probing, while the fused leg probes first and gathers the wide columns
only for the rows that matched.

* ``q1_priced_orders``   — Filter→Map→Join(cust∈r1)→Select→Top over
                           the uniform fact, all wide columns live.
* ``q2_priced_skew``     — the same chain over the Zipf(s=1.1) fact.
* ``q3_star``            — Filter→Join(cust∈r1)→Join(part)→Select→Top,
                           uniform: the multiway fuse AND the probe
                           fuse compose on one chain.
* ``q4_star_mesh``       — q3's shape over a Zipf fact sharded across
                           the 8-device mesh (the leg-peak RSS tier).
* ``q5_wide_scan``       — the full-coverage dimension: every selected
                           row matches, the merge is the same
                           full-width gather in both legs, so this
                           pins the fused floor near 1.0x (the pricing
                           rule's break-even shape).

Per query, gates (nonzero exit on any failure):

1. the staged leg (``CSVPLUS_FUSE=0``) runs FIRST — ``peak_rss_mb`` is
   a process-lifetime high watermark, so leg ordering makes the RSS
   comparison honest — then the fused leg over the very same tables;
2. bitwise parity: positional per-column checksums equal across legs;
3. ``RecompileWatch.assert_zero`` across the fused leg's warm reps;
4. every fusible query's fused-leg cache must record ``fused_chains
   >= 1`` (the rewriter fired; not assumed from the env flag);
5. on the mesh query, the fused leg's peak RSS must stay within 10%
   of the staged leg's (the r06 regression guard, measured not priced);
6. at least one fused query must clear the ISSUE 19 acceptance bar:
   >= 1.25x warm throughput over its staged leg;
7. the headline (q1 fused warm rows/s) must stay above HALF the
   checked-in floor (``bench_macro_floor.json``).

Output: ONE JSON line on stdout.  ``CSVPLUS_BENCH_MACRO_OUT`` names
the artifact (per-query speedup, leg-peak RSS, and the per-stage
``obs diff`` attribution tables for both legs).  CSVPLUS_BENCH_MACRO_ROWS
scales the fact tables (default 1M — small row counts are dispatch-
dominated and flatten every leg toward 1.0x).
"""

from __future__ import annotations

import json
import os
import sys
import time


def _hermetic() -> None:
    if os.environ.get("CSVPLUS_MACRO_HERMETIC") == "1":
        return
    env = dict(os.environ)
    env["CSVPLUS_MACRO_HERMETIC"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def main() -> int:
    _hermetic()
    import dataclasses

    import numpy as np

    import csvplus_tpu as cp
    from bench import zipf_probe_values
    from csvplus_tpu import plan as P
    from csvplus_tpu.columnar.exec import execute_plan_view
    from csvplus_tpu.columnar.table import DeviceTable
    from csvplus_tpu.exprs import SetValue
    from csvplus_tpu.obs.diff import diff_stage_tables, format_diff
    from csvplus_tpu.obs.memory import host_header, peak_rss_mb
    from csvplus_tpu.obs.recompile import RecompileWatch
    from csvplus_tpu.parallel.mesh import make_mesh
    from csvplus_tpu.predicates import Like, Not
    from csvplus_tpu.serve import PlanCache
    from csvplus_tpu.utils.checksum import checksum_device_table

    n = int(os.environ.get("CSVPLUS_BENCH_MACRO_ROWS", 1_000_000))
    n_cust, n_part, n_wide, reps = 2_000, 500, 10, 5
    t0_all = time.perf_counter()

    def cust_index(region=None):
        ids = [
            i for i in range(n_cust) if region is None or i % 7 == region
        ]
        return cp.take(DeviceTable.from_pylists(
            {
                "cust_id": [f"c{i}" for i in ids],
                "name": [f"name{i % 997}" for i in ids],
                "region": [f"r{i % 7}" for i in ids],
            },
            device="cpu",
        )).index_on("cust_id").sync()

    # the r1 restriction is TPC-H Q5's dimension shape: the index holds
    # only customers in one region, so ~6/7 of fact rows probe to a
    # miss and the fused leg never pays their wide-column gathers
    cust_r1_idx = cust_index(region=1)
    cust_all_idx = cust_index()
    part_idx = cp.take(DeviceTable.from_pylists(
        {
            "part_id": [f"p{i}" for i in range(n_part)],
            "brand": [f"b{i % 25}" for i in range(n_part)],
        },
        device="cpu",
    )).index_on("part_id").sync()

    def fact(dist):
        rng = np.random.default_rng(7)
        if dist == "zipf":
            cust = zipf_probe_values(np.arange(n_cust), n, s=1.1, seed=7)
            part = zipf_probe_values(np.arange(n_part), n, s=1.1, seed=8)
        else:
            cust = rng.integers(0, n_cust, n)
            part = rng.integers(0, n_part, n)
        arange = np.arange(n)
        cols = {
            "cust_id": np.char.add("c", cust.astype(np.str_)).tolist(),
            "part_id": np.char.add("p", part.astype(np.str_)).tolist(),
            "cat": np.char.add("k", (arange % 16).astype(np.str_)).tolist(),
            "qty": (arange % 100).astype(np.str_).tolist(),
        }
        # every wide column stays LIVE through the final select: the
        # staged leg materializes all of them for every post-filter row,
        # the fused leg only for the ~1/7 that match the r1 dimension
        for w in range(n_wide):
            cols[f"w{w}"] = (
                np.char.add(f"v{w}_", (arange % 89).astype(np.str_))
                .tolist()
            )
        return DeviceTable.from_pylists(cols, device="cpu")

    wide_cols = tuple(f"w{w}" for w in range(n_wide))
    weak_filter = Not(Like({"cat": "k1"}))  # keeps 15/16 of the fact

    def one_join_chain(t):
        return P.Top(
            P.SelectCols(
                P.Join(
                    P.MapExpr(
                        P.Filter(P.Scan(t), weak_filter),
                        SetValue("flag", "y"),
                    ),
                    cust_r1_idx,
                    ("cust_id",),
                ),
                ("cust_id", "name", "qty", "flag") + wide_cols,
            ),
            5_000,
        )

    def star_chain(t):
        return P.Top(
            P.SelectCols(
                P.Join(
                    P.Join(
                        P.Filter(P.Scan(t), weak_filter),
                        cust_r1_idx,
                        ("cust_id",),
                    ),
                    part_idx,
                    ("part_id",),
                ),
                ("cust_id", "name", "brand", "qty") + wide_cols,
            ),
            5_000,
        )

    def wide_chain(t):
        return P.SelectCols(
            P.Join(
                P.Filter(P.Scan(t), weak_filter),
                cust_all_idx,
                ("cust_id",),
            ),
            ("cust_id", "name", "qty") + wide_cols,
        )

    mesh = make_mesh(8)
    facts = {"uniform": fact("uniform"), "zipf": fact("zipf")}
    queries = [
        ("q1_priced_orders", one_join_chain, "uniform", None),
        ("q2_priced_skew", one_join_chain, "zipf", None),
        ("q3_star", star_chain, "uniform", None),
        ("q4_star_mesh", star_chain, "zipf", mesh),
        ("q5_wide_scan", wide_chain, "zipf", None),
    ]

    def timed(cache, pl):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = cache.execute(pl)
            best = min(best, time.perf_counter() - t0)
        return best, out

    def stage_seconds(root):
        """Marginal per-stage seconds via prefix execution (the same
        crude-but-honest attribution ``make bench-opt`` records)."""
        nodes = list(P.linearize(root))
        rows, prev_t, prev_rows = [], 0.0, 0
        for k in range(len(nodes)):
            node = nodes[0]
            for stage in nodes[1 : k + 1]:
                node = dataclasses.replace(stage, child=node)
            best = float("inf")
            for _ in range(2):
                t0 = time.perf_counter()
                out = execute_plan_view(node).materialize()
                best = min(best, time.perf_counter() - t0)
            rows.append(
                {
                    "stage": type(nodes[k]).__name__,
                    "seconds": round(max(best - prev_t, 0.0), 6),
                    "rows_in": prev_rows if k else out.nrows,
                    "rows_out": out.nrows,
                }
            )
            prev_t, prev_rows = best, out.nrows
        return rows

    from csvplus_tpu.analysis.rewrite import apply_recipe

    record: dict = {"rows": n, "queries": {}}
    stage_diff_text: dict = {}
    best_speedup = 0.0
    for name, builder, dist, qmesh in queries:
        t = facts[dist]
        if qmesh is not None:
            t = t.with_sharding(qmesh)
        pl = builder(t)

        # staged leg FIRST: peak_rss_mb is monotonic over the process
        # lifetime, so this ordering lets the fused leg's peak be
        # compared against (not hidden under) the staged leg's
        os.environ["CSVPLUS_FUSE"] = "0"
        try:
            cache_staged = PlanCache(size=4)
            cache_staged.execute(pl)  # cold admit + lower, staged
            t_staged, out_staged = timed(cache_staged, pl)
        finally:
            os.environ.pop("CSVPLUS_FUSE", None)
        rss_staged = peak_rss_mb()

        cache_fused = PlanCache(size=4)
        cache_fused.execute(pl)  # cold admit: pass 5 prices + fuses
        exe = cache_fused.executable_for(pl)
        steps = [s[0] for s in (exe.recipe.steps if exe.recipe else ())]
        if "fuse_chain" not in steps or cache_fused.stats()["fused_chains"] < 1:
            sys.stderr.write(
                f"bench[macro] FAIL({name}): rewriter did not fuse the"
                f" probe run (recipe steps {steps}, stats"
                f" {cache_fused.stats()})\n"
            )
            return 1
        with RecompileWatch() as watch:
            t_fused, out_fused = timed(cache_fused, pl)
        rss_fused = peak_rss_mb()

        # parity AFTER the watch: checksum kernels jit on first use
        if list(out_fused.columns) != list(out_staged.columns) or (
            checksum_device_table(out_fused, positional=True)
            != checksum_device_table(out_staged, positional=True)
        ):
            sys.stderr.write(
                f"bench[macro] FAIL({name}): fused output is not"
                f" bitwise-equal to the CSVPLUS_FUSE=0 leg's\n"
            )
            return 1
        watch.assert_zero(f"warm fused serving ({name})")

        if qmesh is not None and rss_fused > rss_staged * 1.10:
            sys.stderr.write(
                f"bench[macro] FAIL({name}): fused leg peak RSS"
                f" {rss_fused:,.0f}MB exceeds the staged leg's"
                f" {rss_staged:,.0f}MB by more than 10%\n"
            )
            return 1

        speedup = t_staged / t_fused
        best_speedup = max(best_speedup, speedup)
        record["queries"][name] = {
            "fused_rows_per_sec_warm": round(n / t_fused, 1),
            "staged_rows_per_sec_warm": round(n / t_staged, 1),
            "speedup": round(speedup, 3),
            "out_rows": out_fused.nrows,
            "recipe_steps": steps,
            "staged_leg_peak_rss_mb": round(rss_staged, 1),
            "fused_leg_peak_rss_mb": round(rss_fused, 1),
        }
        diff = diff_stage_tables(
            stage_seconds(pl), stage_seconds(apply_recipe(pl, exe.recipe))
        )
        stage_diff_text[name] = format_diff(diff, "staged", "fused")
        sys.stderr.write(
            f"bench[macro] {name}: {speedup:.2f}x"
            f" ({n / t_staged:,.0f} -> {n / t_fused:,.0f} rows/s,"
            f" rss {rss_staged:,.0f} -> {rss_fused:,.0f} MB)\n"
        )

    if best_speedup < 1.25:
        sys.stderr.write(
            f"bench[macro] FAIL: no query cleared the 1.25x fused-vs-"
            f"staged bar (best {best_speedup:.2f}x)\n"
        )
        return 1

    record.update(
        {
            "metric": "macro_fused_rows_per_sec_warm",
            "value": record["queries"]["q1_priced_orders"][
                "fused_rows_per_sec_warm"
            ],
            "unit": "rows/s",
            "best_speedup": round(best_speedup, 3),
            "parity_bitwise": True,
            "warm_recompiles": 0,
            "wall_sec": round(time.perf_counter() - t0_all, 1),
            **host_header(),
        }
    )
    print(json.dumps(record), flush=True)

    out_path = os.environ.get("CSVPLUS_BENCH_MACRO_OUT")
    if out_path:
        artifact = dict(record)
        artifact["stage_diff_text"] = stage_diff_text
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(artifact, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, out_path)
        sys.stderr.write(f"bench[macro] artifact -> {out_path}\n")

    floor = 0.0
    floor_rows = None
    try:
        repo = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(repo, "bench_macro_floor.json")) as f:
            fl = json.load(f)
            floor = float(fl.get("macro_fused_rows_per_sec_warm", 0.0))
            floor_rows = fl.get("rows")
    except (OSError, ValueError):
        pass
    if floor and record["value"] < floor / 2:
        sys.stderr.write(
            f"bench[macro] REGRESSION: q1 fused {record['value']:,.0f}"
            f" rows/s is under half the floor ({floor:,.0f} rows/s at"
            f" {floor_rows or '?'} rows)\n"
        )
        return 1
    lines = ", ".join(
        f"{q} {v['speedup']:.2f}x" for q, v in record["queries"].items()
    )
    sys.stderr.write(
        f"bench[macro] ok: {lines} | bitwise parity all queries, zero"
        f" warm recompiles, floor {floor:,.0f} (n={n},"
        f" {record['wall_sec']}s)\n"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
