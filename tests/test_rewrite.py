"""Differential suite for the verifier-checked plan rewriter (ISSUE 16).

Every rewrite rule — predicate pushdown, filter reordering, projection
pushdown — executes the OPTIMIZED plan and the UNREWRITTEN plan over the
same data and asserts bitwise equality (positional per-column checksums,
so row order counts).  Plus the serving integration: the plan cache
stores the recipe under the original structural key, replays it across
submissions, falls back (correctly, counted) when a submission's leaf
fails the presence obligations, and ``CSVPLUS_OPTIMIZE=0`` restores the
unrewritten behavior byte-identically.
"""

import dataclasses

import pytest

import csvplus_tpu as cp
from csvplus_tpu import plan as P
from csvplus_tpu.analysis.rewrite import (
    PlanRecipe,
    apply_recipe,
    leaf_presence_ok,
    optimize_enabled,
    optimize_plan,
)
from csvplus_tpu.columnar.exec import execute_plan_view
from csvplus_tpu.columnar.table import DeviceTable
from csvplus_tpu.exprs import SetValue
from csvplus_tpu.predicates import Like
from csvplus_tpu.serve import PlanCache
from csvplus_tpu.utils.checksum import checksum_device_table

N = 400


def _fact(n=N, absent_ids=False):
    ids = [None if absent_ids and i % 7 == 0 else str(i % 50)
           for i in range(n)]
    return DeviceTable.from_pylists(
        {
            "id": ids,
            "cat": [f"k{i % 8}" for i in range(n)],
            "pad1": [str(i) for i in range(n)],
            "pad2": ["p"] * n,
        },
        device="cpu",
    )


def _dim(n=50):
    t = DeviceTable.from_pylists(
        {"id": [str(i) for i in range(n)],
         "region": [f"r{i % 5}" for i in range(n)]},
        device="cpu",
    )
    return cp.take(t).index_on("id").sync()


def _run(root):
    return execute_plan_view(root).materialize()


def _bitwise_equal(a, b):
    assert a.nrows == b.nrows
    assert list(a.columns) == list(b.columns)  # dict order is part of it
    assert checksum_device_table(a, positional=True) == checksum_device_table(
        b, positional=True
    )


def _chain_ops(root):
    return [type(n).__name__ for n in P.linearize(root)]


# -- the rules, each bitwise-differential ------------------------------


def test_predicate_pushdown_past_map_and_join_bitwise():
    plan = P.Filter(
        P.Join(
            P.MapExpr(P.Scan(_fact()), SetValue("flag", "x")),
            _dim(),
            ("id",),
        ),
        Like({"cat": "k1"}),
    )
    result = optimize_plan(plan)
    assert any(r.startswith("predicate-pushdown") for r in result.applied)
    # the filter crossed both the Join and the Map, down to the leaf —
    # where pass 5 absorbs the whole Filter->Map->Join run into the
    # probe pass, filter first (i.e. BEFORE the fanout)
    chain = P.linearize(result.root)
    assert _chain_ops(result.root) == ["Scan", "FusedProbe"]
    assert chain[1].ops[0][0] == "filter"
    # crossing the may-error Join consumed a presence fact -> obligation
    assert "id" in result.recipe.require_present
    _bitwise_equal(_run(plan), _run(result.root))


def test_predicate_pushdown_except_mover_bitwise():
    plan = P.Except(
        P.MapExpr(P.Scan(_fact()), SetValue("flag", "x")),
        _dim(10),
        ("id",),
    )
    result = optimize_plan(plan)
    assert any(r.startswith("predicate-pushdown") for r in result.applied)
    assert _chain_ops(result.root)[:2] == ["Scan", "Except"]
    _bitwise_equal(_run(plan), _run(result.root))


def test_filter_reorder_most_selective_first_bitwise():
    # cat has 8 distinct values, id has 50: the id filter is the more
    # selective one and sits later -> it must be hoisted
    plan = P.Filter(
        P.Filter(P.Scan(_fact()), Like({"cat": "k1"})),
        Like({"id": "7"}),
    )
    result = optimize_plan(plan)
    assert any(r.startswith("filter-reorder") for r in result.applied)
    chain = P.linearize(result.root)
    assert chain[1].pred.match == {"id": "7"}
    assert chain[2].pred.match == {"cat": "k1"}
    _bitwise_equal(_run(plan), _run(result.root))


def test_projection_pushdown_drops_dead_leaf_columns_bitwise():
    plan = P.SelectCols(
        P.Join(P.Scan(_fact()), _dim(), ("id",)),
        ("id", "region"),
    )
    result = optimize_plan(plan)
    assert any(r.startswith("projection-pushdown") for r in result.applied)
    drop = P.linearize(result.root)[1]
    assert isinstance(drop, P.DropCols)
    assert sorted(drop.columns) == ["cat", "pad1", "pad2"]
    _bitwise_equal(_run(plan), _run(result.root))


def test_all_three_rules_compose_bitwise():
    plan = P.SelectCols(
        P.Filter(
            P.Filter(
                P.Join(
                    P.MapExpr(P.Scan(_fact()), SetValue("note", "n")),
                    _dim(),
                    ("id",),
                ),
                Like({"cat": "k1"}),
            ),
            Like({"id": "7"}),
        ),
        ("id", "region", "note"),
    )
    result = optimize_plan(plan)
    rules = {r.split(":")[0] for r in result.applied}
    assert {"predicate-pushdown", "filter-reorder",
            "projection-pushdown"} <= rules
    _bitwise_equal(_run(plan), _run(result.root))


def test_blocked_rewrites_carry_typed_diagnostics():
    # Top is positional: a filter may not cross it, and the refusal
    # names the blocking stage
    plan = P.Filter(P.Top(P.Scan(_fact()), 100), Like({"cat": "k1"}))
    result = optimize_plan(plan)
    assert result.recipe is None
    block = [d for d in result.blocked if d.stage.startswith("Top")]
    assert block and "positional" in block[0].message
    assert block[0].rule == "predicate-pushdown"
    # bitwise: the un-applied plan is simply the original
    _bitwise_equal(_run(plan), _run(result.root))

    # Validate aborts mid-stream: same typed refusal (mid-chain
    # Validate is not device-lowerable, so no execution leg here)
    vplan = P.Filter(
        P.Validate(P.Scan(_fact()), Like({"cat": "k1"}), "bad"),
        Like({"id": "7"}),
    )
    vblock = [d for d in optimize_plan(vplan).blocked
              if d.stage.startswith("Validate")]
    assert vblock and "abort" in vblock[0].message


def test_rewrite_is_noop_when_nothing_proves():
    plan = P.Filter(P.Scan(_fact()), Like({"cat": "k1"}))
    result = optimize_plan(plan)
    assert result.recipe is None and result.root is plan
    assert result.report is result.original_report


# -- recipe replay mechanics -------------------------------------------


def test_apply_recipe_refuses_unknown_step():
    with pytest.raises(ValueError, match="unknown recipe step"):
        apply_recipe(P.Scan(_fact()), PlanRecipe((("teleport", ()),)))


def test_leaf_presence_ok_is_metadata_only():
    assert leaf_presence_ok(P.Scan(_fact()), ("id", "cat"))
    assert not leaf_presence_ok(P.Scan(_fact(absent_ids=True)), ("id",))
    assert leaf_presence_ok(P.Scan(_fact(absent_ids=True)), ())
    assert not leaf_presence_ok(P.Scan(_fact()), ("nope",))


# -- serving integration -----------------------------------------------


def _served_shape(table):
    return P.Filter(
        P.Join(table if isinstance(table, P.PlanNode) else P.Scan(table),
               _dim(), ("id",)),
        Like({"cat": "k1"}),
    )


def test_plancache_runs_optimized_under_original_key():
    plan = _served_shape(_fact())
    cache = PlanCache(size=8)
    got = cache.execute(plan)
    st = cache.stats()
    assert st["optimized"] == 1 and st["optimize_failed"] == 0
    # the cached executable replays the recipe...
    exe = cache.executable_for(plan)
    assert exe.recipe is not None and exe.recipe.steps
    # ...and the served result is bitwise the unrewritten plan's
    _bitwise_equal(got, _run(plan))
    # a second submission over DIFFERENT data hits the same entry
    plan2 = _served_shape(_fact(n=300))
    got2 = cache.execute(plan2)
    st = cache.stats()
    assert st["hits"] >= 2 and st["lowered"] == 1 and st["optimized"] == 1
    _bitwise_equal(got2, _run(plan2))


def test_plancache_presence_obligation_fallback():
    cache = PlanCache(size=8)
    plan = _served_shape(_fact())
    cache.execute(plan)
    exe = cache.executable_for(plan)
    assert "id" in exe.recipe.require_present
    # same structural shape over a table whose id presence cache was
    # never seeded (an ingest path without the metadata): the
    # obligation is unprovable, so the shape runs UNREWRITTEN —
    # correct, just not optimized
    unseeded = _fact(n=300)
    unseeded.columns["id"]._has_absent = None
    plan2 = _served_shape(unseeded)
    assert cache.executable_for(plan2) is exe  # same structural key
    before = exe.unoptimized_runs
    got = cache.execute(plan2)
    assert exe.unoptimized_runs == before + 1
    _bitwise_equal(got, _run(plan2))


def test_optimize_disabled_restores_seed_behavior(monkeypatch):
    monkeypatch.setenv("CSVPLUS_OPTIMIZE", "0")
    assert not optimize_enabled()
    plan = _served_shape(_fact())
    cache = PlanCache(size=8)
    got = cache.execute(plan)
    st = cache.stats()
    assert st["optimized"] == 0
    assert cache.executable_for(plan).recipe is None
    _bitwise_equal(got, _run(plan))


def test_plancache_zero_recompiles_on_warm_optimized_path():
    from csvplus_tpu.obs.recompile import RecompileWatch

    cache = PlanCache(size=8)
    tables = [_fact(n=256) for _ in range(3)]
    cache.execute(_served_shape(tables[0]))  # cold: lowers the optimized plan
    with RecompileWatch() as watch:
        for t in tables[1:]:
            cache.execute(_served_shape(t))
    watch.assert_zero("warm optimized serving")
    assert cache.stats()["lowered"] == 1


# -- cost domain: sketch-seeded estimates ------------------------------


def test_estimate_plan_uses_build_side_sketch():
    from csvplus_tpu.analysis.cost import estimate_plan
    from csvplus_tpu.obs.sketch import SpaceSaving

    plan = P.Join(P.Scan(_fact()), _dim(), ("id",))
    uniform = estimate_plan(plan, sketches={})
    sk = SpaceSaving(k=8)
    sk.offer_many(["3"] * 900 + [str(i) for i in range(100)])
    skewed = estimate_plan(plan, sketches={"id": sk})
    assert "no sketch" in uniform[1].note
    assert "sketch" in skewed[1].note and "tracked" in skewed[1].note
    # a heavy-hitter build side predicts MORE matches per probe
    assert skewed[1].rows > uniform[1].rows


def test_rank_join_orders_marks_submitted_and_provable():
    from csvplus_tpu.analysis import verify_plan
    from csvplus_tpu.analysis.cost import rank_join_orders

    plan = P.Except(
        P.Join(P.Scan(_fact()), _dim(), ("id",)),
        _dim(10),
        ("id",),
    )
    report = verify_plan(plan)
    ranked = rank_join_orders(plan, report, sketches={})
    assert ranked and any(c["submitted"] for c in ranked)
    # the anti-join-first order halves the join's input: cheaper AND
    # provable (Except is a narrowing mover with proven key presence)
    best = ranked[0]
    assert best["order"][0].startswith("Except")
    assert best["provable"] and not best["submitted"]


def test_an_expanding_join_never_moves_up_over_an_anti_join():
    """``scan > Except(dim) > Join(dim)`` under sketches that price the
    join as the more selective stage: the join-first order is cheaper
    but NOT provable — only a narrowing stage may move earlier — so the
    rewriter leaves the order alone (``plancert`` refuses a permute
    whose mover is a ``Join``)."""
    from csvplus_tpu.analysis import verify_plan
    from csvplus_tpu.analysis.cost import rank_join_orders
    from csvplus_tpu.obs.sketch import SpaceSaving

    plan = P.Join(
        P.Except(P.Scan(_fact()), _dim(10), ("id",)),
        _dim(),
        ("id",),
    )
    sk = SpaceSaving(k=256)
    sk.offer_many([str(i) for i in range(200)])  # 200 keys, one row each: fanout 0.25
    ranked = rank_join_orders(plan, verify_plan(plan), sketches={"id": sk})
    join_first = next(r for r in ranked if r["order"][0].startswith("Join"))
    assert join_first is ranked[0] and not join_first["submitted"]  # the cheaper order...
    assert join_first["provable"] is False  # ...moves an EXPAND stage earlier
    result = optimize_plan(plan, sketches={"id": sk})
    steps = result.recipe.steps if result.recipe is not None else ()
    assert not any(step[0] == "permute" for step in steps)
    assert not any(r.startswith("join-order") for r in result.applied)
    assert _chain_ops(result.root)[:3] == ["Scan", "Except", "Join"]


# -- the verdict assertion ---------------------------------------------


def test_rewritten_plan_reverified_same_verdict(monkeypatch):
    plan = _served_shape(_fact())
    result = optimize_plan(plan)
    assert result.recipe is not None
    assert result.report.ok == result.original_report.ok
    assert (result.report.predicts_empty
            == result.original_report.predicts_empty)
    # with probe fusion off, the rewritten chain is a permutation + one
    # DropCols insert of the original (no stage invented, none lost)
    monkeypatch.setenv("CSVPLUS_FUSE", "0")
    staged = optimize_plan(plan)
    assert staged.report.ok == staged.original_report.ok
    orig = sorted(_chain_ops(plan))
    new = sorted(_chain_ops(staged.root))
    assert [op for op in new if op != "DropCols"] == orig


# -- ISSUE 17: ranked join orders executed + the multiway fuse ---------


def _cat_dim(n=8):
    t = DeviceTable.from_pylists(
        {"cat": [f"k{i}" for i in range(n)],
         "label": [f"L{i}" for i in range(n)]},
        device="cpu",
    )
    return cp.take(t).index_on("cat").sync()


def _cat_anti(n=2):
    t = DeviceTable.from_pylists(
        {"cat": [f"k{i}" for i in range(n)],
         "tag": ["t"] * n},
        device="cpu",
    )
    return cp.take(t).index_on("cat").sync()


def test_join_order_executes_ranked_permutation_bitwise():
    """The cost domain's best PROVABLE ranked order (anti-join first —
    it halves the probe run's input) is EXECUTED, recorded on the recipe
    as ``join_order`` in original chain slots, and counted by the
    serving cache — all bitwise-differential against the submitted
    order."""
    plan = P.Except(
        P.Join(P.Scan(_fact()), _dim(), ("id",)),
        _cat_anti(),
        ("cat",),
    )
    result = optimize_plan(plan)
    assert any(r.startswith("join-order") for r in result.applied)
    assert result.recipe.join_order == (2, 1)
    assert _chain_ops(result.root) == ["Scan", "Except", "Join"]
    _bitwise_equal(_run(plan), _run(result.root))
    cache = PlanCache(size=8)
    got = cache.execute(plan)
    assert cache.stats()["reordered"] == 1
    _bitwise_equal(got, _run(plan))


def test_multiway_fuse_bitwise_and_counted():
    """A 2-join probe run collapses into ONE MultiwayJoin when the cost
    model prices the fused operator cheaper: the recipe carries the
    ``fuse_joins`` step plus the later dimension's key obligation, the
    fused execution is bitwise the cascade's, and the serving cache
    counts the fuse."""
    plan = P.Join(
        P.Join(P.Scan(_fact()), _dim(), ("id",)),
        _cat_dim(),
        ("cat",),
    )
    result = optimize_plan(plan)
    assert any(r.startswith("multiway-fuse") for r in result.applied)
    assert ("fuse_joins", 1, 2) in result.recipe.steps
    assert _chain_ops(result.root) == ["Scan", "MultiwayJoin"]
    # the fused pass probes the ORIGINAL stream: the later dimension's
    # key column becomes a leaf presence obligation
    assert "cat" in result.recipe.require_present
    _bitwise_equal(_run(plan), _run(result.root))
    cache = PlanCache(size=8)
    got = cache.execute(plan)
    assert cache.stats()["fused"] == 1
    _bitwise_equal(got, _run(plan))


def test_multiway_disabled_hatch(monkeypatch):
    """CSVPLUS_MULTIWAY=0: the same fusible chain keeps its cascade
    shape (no fuse step, both Joins live) and answers identically."""
    monkeypatch.setenv("CSVPLUS_MULTIWAY", "0")
    plan = P.Join(
        P.Join(P.Scan(_fact()), _dim(), ("id",)),
        _cat_dim(),
        ("cat",),
    )
    result = optimize_plan(plan)
    assert not any(r.startswith("multiway-fuse") for r in result.applied)
    steps = result.recipe.steps if result.recipe else ()
    assert not any(s[0] == "fuse_joins" for s in steps)
    assert _chain_ops(result.root).count("Join") == 2
    _bitwise_equal(_run(plan), _run(result.root))


# -- ISSUE 19: filter/map/projection fused into the probe pass ---------


def _zipf_fact(n=N, s=1.1, seed=7):
    import numpy as np

    rng = np.random.default_rng(seed)
    ids = rng.zipf(s, size=n) % 50
    return DeviceTable.from_pylists(
        {"id": [str(int(i)) for i in ids],
         "cat": [f"k{i % 8}" for i in range(n)],
         "pad1": [str(i) for i in range(n)],
         "pad2": ["p"] * n},
        device="cpu",
    )


def _fused_shape(fact):
    """Filter -> Map -> Join over *fact*: the canonical absorbable run."""
    return P.Join(
        P.MapExpr(
            P.Filter(P.Scan(fact), Like({"cat": "k1"})),
            SetValue("flag", "x"),
        ),
        _dim(),
        ("id",),
    )


@pytest.mark.parametrize("fact_fn", [_fact, _zipf_fact],
                         ids=["uniform", "zipf"])
def test_probe_fuse_bitwise(fact_fn):
    """The Filter->Map->Join run lowers into ONE FusedProbe node whose
    execution is bitwise the staged chain's, on uniform AND Zipf-skewed
    key distributions."""
    plan = _fused_shape(fact_fn())
    result = optimize_plan(plan)
    assert any(r.startswith("probe-fuse") for r in result.applied)
    assert any(s[0] == "fuse_chain" for s in result.recipe.steps)
    chain = P.linearize(result.root)
    assert _chain_ops(result.root) == ["Scan", "FusedProbe"]
    assert [k for k, _ in chain[1].ops] == ["filter", "map"]
    _bitwise_equal(_run(plan), _run(result.root))


def test_probe_fuse_partitioned_probe_bitwise(monkeypatch):
    """With the partition threshold floored the fused probe runs through
    the partitioned exchange tier (K=8 shards' worth of keys instead of
    the dense single-shard tier) and stays bitwise-identical."""
    import csvplus_tpu.ops.join as J

    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1)
    plan = _fused_shape(_zipf_fact())
    result = optimize_plan(plan)
    assert _chain_ops(result.root) == ["Scan", "FusedProbe"]
    _bitwise_equal(_run(plan), _run(result.root))


def test_probe_fuse_empty_fact_and_zero_selection():
    """Degenerate selections: an EMPTY fact table, and a filter that
    selects ZERO rows — both take the staged empty-fold path inside the
    fused branch and answer bitwise-identically."""
    empty = DeviceTable.from_pylists(
        {"id": [], "cat": [], "pad1": [], "pad2": []}, device="cpu")
    for fact, pred in ((empty, Like({"cat": "k1"})),
                       (_fact(), Like({"cat": "nope"}))):
        plan = P.Join(P.Filter(P.Scan(fact), pred), _dim(), ("id",))
        result = optimize_plan(plan)
        staged, fused = _run(plan), _run(result.root)
        assert staged.nrows == fused.nrows == 0
        _bitwise_equal(staged, fused)


def _scrape_family_totals(prefix):
    """{family: sum over its series} of a rendered metrics scrape."""
    from csvplus_tpu.obs.metrics import TelemetryPlane

    totals = {}
    for line in TelemetryPlane().registry.render().splitlines():
        if line.startswith(prefix):
            series, value = line.rsplit(" ", 1)
            family = series.split("{", 1)[0]
            totals[family] = totals.get(family, 0.0) + float(value)
    return totals


@pytest.mark.parametrize(
    "prefix, families, stat, build_plan",
    [
        (
            "csvplus_join_multiway_",
            ("total", "rows_in_total", "rows_out_total",
             "intermediate_rows_avoided_total"),
            "fused",
            lambda: P.Join(
                P.Join(P.Scan(_fact()), _dim(), ("id",)), _cat_dim(), ("cat",)
            ),
        ),
        (
            "csvplus_plan_fusion_",
            ("total", "rows_full_total", "rows_selected_total",
             "rows_out_total"),
            "fused_chains",
            lambda: _fused_shape(_fact()),
        ),
    ],
    ids=["multiway", "fusion"],
)
def test_fused_execution_counters_ride_a_scrape(
    prefix, families, stat, build_plan
):
    """One fused execution through the serving cache lands in the
    process-global counters, and a metrics scrape carries the whole
    family: executions up by one, fact rows in up by the stream."""
    before = _scrape_family_totals(prefix)
    cache = PlanCache(size=8)
    cache.execute(build_plan())
    assert cache.stats()[stat] == 1
    after = _scrape_family_totals(prefix)
    assert set(after) >= {prefix + f for f in families}
    assert after[prefix + families[0]] == before.get(prefix + families[0], 0) + 1
    assert after[prefix + families[1]] == before.get(prefix + families[1], 0) + N


def test_probe_fuse_opaque_predicate_refused():
    """An opaque predicate (no static column footprint) bounds the
    absorbable run: the rewriter refuses with a typed probe-fuse
    diagnostic instead of fusing blind."""

    class Opaque:  # not a Like/All/Any/Not tree -> no lowering
        pass

    plan = P.Join(P.Filter(P.Scan(_fact()), Opaque()), _dim(), ("id",))
    result = optimize_plan(plan)
    assert not any(r.startswith("probe-fuse") for r in result.applied)
    block = [d for d in result.blocked if d.rule == "probe-fuse"]
    assert block and "opaque" in block[0].message


def test_probe_fuse_disabled_hatch(monkeypatch):
    """CSVPLUS_FUSE=0: the same chain keeps its staged shape (no
    fuse_chain step, Filter and Join both live) and answers
    byte-identically to the unrewritten plan."""
    monkeypatch.setenv("CSVPLUS_FUSE", "0")
    plan = _fused_shape(_fact())
    result = optimize_plan(plan)
    assert not any(r.startswith("probe-fuse") for r in result.applied)
    steps = result.recipe.steps if result.recipe else ()
    assert not any(s[0] == "fuse_chain" for s in steps)
    assert "FusedProbe" not in _chain_ops(result.root)
    _bitwise_equal(_run(plan), _run(result.root))


def test_probe_fuse_plancache_counted_and_zero_recompiles():
    """The serving cache replays the fuse_chain recipe step under the
    ORIGINAL structural key, counts the fused admission, and the warm
    path recompiles nothing."""
    from csvplus_tpu.obs.recompile import RecompileWatch

    cache = PlanCache(size=8)
    tables = [_fact(n=256) for _ in range(3)]
    got = cache.execute(_fused_shape(tables[0]))
    st = cache.stats()
    assert st["fused_chains"] == 1 and st["fusion_refused"] == 0
    _bitwise_equal(got, _run(_fused_shape(tables[0])))
    with RecompileWatch() as watch:
        for t in tables[1:]:
            cache.execute(_fused_shape(t))
    watch.assert_zero("warm fused serving")
    assert cache.stats()["lowered"] == 1


def test_multiway_fuse_blocked_on_unstable_key():
    """The second dimension keys on a column the FIRST build side
    introduces ("region" is not leaf-PRESENT): fusing would probe a
    column the original stream does not carry, so the rewriter refuses
    with a typed diagnostic and the cascade runs unchanged."""
    region_dim = cp.take(DeviceTable.from_pylists(
        {"region": [f"r{i}" for i in range(5)],
         "zone": [f"z{i}" for i in range(5)]},
        device="cpu",
    )).index_on("region").sync()
    plan = P.Join(
        P.Join(P.Scan(_fact()), _dim(), ("id",)),
        region_dim,
        ("region",),
    )
    result = optimize_plan(plan)
    assert not any(r.startswith("multiway-fuse") for r in result.applied)
    assert any(d.rule == "multiway-fuse" for d in result.blocked)
    steps = result.recipe.steps if result.recipe else ()
    assert not any(s[0] == "fuse_joins" for s in steps)
    _bitwise_equal(_run(plan), _run(result.root))
