"""Unit tests for the ISSUE 20 static certification surface: the
RETRACE002/SYNC001 dataflow lints and their allowlist meta-rules
(analysis/jitlint.py), the ENV001-R registry routing checks, the
exhaustive plan-space certifier (analysis/plancert.py), and the
sketch-aware selectivity pricing (the ROADMAP item-1 closure) with its
pricing-never-changes-results differential."""

import csvplus_tpu as cp
from csvplus_tpu import plan as P
from csvplus_tpu.analysis.astlint import lint_source
from csvplus_tpu.analysis.jitlint import (
    RETRACE002_ALLOWED,
    SYNC001_ALLOWED,
    allowlist_global_findings,
)
from csvplus_tpu.analysis.rewrite import optimize_plan
from csvplus_tpu.analysis.verify import verify_plan
from csvplus_tpu.columnar.exec import execute_plan_view
from csvplus_tpu.columnar.table import DeviceTable
from csvplus_tpu.predicates import Like
from csvplus_tpu.utils.checksum import checksum_device_table

COLD = "csvplus_tpu/utils/zz_fake.py"  # RETRACE002 runs, SYNC001 does not
HOT = "csvplus_tpu/ops/zz_fake.py"  # both run; no allowlist entries match


def _codes(findings):
    return [f.code for f in findings]


# -- RETRACE002: data-derived statics at kernel call sites -------------


RETRACE_DATA = '''
import jax
import jax.numpy as jnp
from functools import partial


@partial(jax.jit, static_argnames=("width",))
def pad_kernel(xs, width):
    return jnp.pad(xs, (0, width - xs.shape[0]))


def bad_call(xs):
    hot = jnp.unique(xs)
    n = int(hot[0])  # host scalar DERIVED from device data
    return pad_kernel(xs, n)
'''


RETRACE_SHAPE = '''
import jax
import jax.numpy as jnp
from functools import partial


@partial(jax.jit, static_argnames=("width",))
def pad_kernel(xs, width):
    return jnp.pad(xs, (0, width - xs.shape[0]))


def good_call(xs):
    n = xs.shape[0]
    width = 1 << max(n - 1, 0).bit_length()  # pow2 bucket of a shape
    return pad_kernel(xs, width)
'''


def test_retrace002_flags_data_derived_static():
    findings = lint_source(RETRACE_DATA, COLD)
    assert "RETRACE002" in _codes(findings)
    f = next(f for f in findings if f.code == "RETRACE002")
    assert "width" in f.message and "pad_kernel" in f.message


def test_retrace002_passes_shape_derived_static():
    assert lint_source(RETRACE_SHAPE, COLD) == []


def test_retrace002_runs_outside_hot_paths_too():
    # the retrace bug class is global; only SYNC001 is hot-path-scoped
    assert "RETRACE002" in _codes(
        lint_source(RETRACE_DATA, "csvplus_tpu/obs/zz_fake.py")
    )


# -- SYNC001: implicit device->host syncs in hot-path modules ----------


_SYNC_FORMS = {
    "np.asarray": "np.asarray(y)",
    "bool": "bool(y)",
    "int": "int(y)",
    "float": "float(y)",
    "len": "len(y)",
    ".item": "y.item()",
    ".tolist": "y.tolist()",
}


def _sync_src(expr):
    return (
        "import jax.numpy as jnp\n"
        "import numpy as np\n\n\n"
        "def f(x):\n"
        "    y = jnp.abs(x)\n"
        f"    return {expr}\n"
    )


def test_sync001_flags_every_banned_form_in_hot_path():
    for name, expr in _SYNC_FORMS.items():
        findings = lint_source(_sync_src(expr), HOT)
        assert _codes(findings) == ["SYNC001"], (name, findings)


def test_sync001_silent_in_cold_modules():
    for expr in _SYNC_FORMS.values():
        assert lint_source(_sync_src(expr), COLD) == []


def test_sync001_silent_on_host_values():
    src = (
        "import numpy as np\n\n\n"
        "def f(rows):\n"
        "    y = [r for r in rows]\n"
        "    return len(y), np.asarray(y)\n"
    )
    assert lint_source(src, HOT) == []


def test_sync001_suppressed_by_count_sync_accounting():
    src = (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "from ..utils.observe import telemetry\n\n\n"
        "def f(x):\n"
        "    y = jnp.abs(x)\n"
        "    out = np.asarray(y)\n"
        "    telemetry.count_sync(out.size)\n"
        "    return out\n"
    )
    assert lint_source(src, HOT) == []


def test_sync001_suppressed_by_allowlist_entry():
    # ops/join.py:probe is a real pinned allowance: the same sync shape
    # under that file/function name lints clean
    src = (
        "import jax.numpy as jnp\n\n\n"
        "def probe(x):\n"
        "    y = jnp.abs(x)\n"
        "    return len(y)\n"
    )
    assert lint_source(src, "csvplus_tpu/ops/join.py") == []


# -- allowlist meta-rules: zero unexplained allowances -----------------


def test_allowlist_empty_citation_is_a_finding(monkeypatch):
    monkeypatch.setitem(SYNC001_ALLOWED, "zz_fake.py:f", "")
    findings = lint_source(_sync_src("int(y)"), HOT)
    assert any("no written accounting citation" in f.message for f in findings)


def test_allowlist_citation_must_name_the_accounting(monkeypatch):
    monkeypatch.setitem(SYNC001_ALLOWED, "zz_fake.py:f", "seems fine to me")
    findings = lint_source(_sync_src("int(y)"), HOT)
    assert any("host_sync_elements" in f.message for f in findings)


def test_allowlist_staleness_is_a_global_check():
    every_key = set(SYNC001_ALLOWED) | set(RETRACE002_ALLOWED)
    assert allowlist_global_findings(every_key) == []
    stale = allowlist_global_findings(set())
    assert len(stale) == len(every_key)
    assert all("stale" in f.message for f in stale)


def test_every_pinned_allowance_carries_its_accounting_token():
    for key, citation in SYNC001_ALLOWED.items():
        assert any(
            tok in citation
            for tok in ("host_sync_elements", "count_sync", "no transfer")
        ), key
    # the pow2 idiom launders every sanctioned retrace case
    assert RETRACE002_ALLOWED == {}


# -- ENV001-R: every env read routes through the registry --------------


def test_env001_flags_unrouted_environ_read():
    src = "import os\n\nFOO = os.environ.get('CSVPLUS_ZZ', '')\n"
    findings = lint_source(src, COLD)
    assert _codes(findings) == ["ENV001-R"]


def test_env001_flags_unregistered_accessor_name():
    src = (
        "from ..utils.env import env_str\n\n"
        "X = env_str('CSVPLUS_ZZ_NOT_REGISTERED', 'x')\n"
    )
    findings = lint_source(src, COLD)
    assert _codes(findings) == ["ENV001-R"]


def test_env_registry_and_docs_in_sync():
    # the whole-tree half: no declared-but-unread entries, and the
    # committed docs/ENV.md matches the rendered registry
    from csvplus_tpu.analysis.astlint import env_global_findings

    assert env_global_findings() == []


def test_every_env_name_in_code_is_registered():
    """Beside the lint of the package's reads: every ``CSVPLUS_*`` name
    a ``*.py`` at the root, under ``csvplus_tpu/`` or under
    ``examples/`` mentions is in the registry, but for the chaos
    driver's own two."""
    import glob
    import os
    import re

    from csvplus_tpu.utils.env import ENV_REGISTRY

    chaos_only = {"CSVPLUS_CHAOS_CASE_TIMEOUT", "CSVPLUS_WAL_CHILD_TEAR"}
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = glob.glob(os.path.join(repo, "*.py"))
    for sub in ("csvplus_tpu", "examples"):
        files += glob.glob(os.path.join(repo, sub, "**", "*.py"), recursive=True)
    named = {}
    for path in files:
        with open(path) as f:
            for name in re.findall(r"CSVPLUS_[A-Z0-9_]*[A-Z0-9]", f.read()):
                named.setdefault(name, os.path.relpath(path, repo))
    assert len(files) > 80 and chaos_only <= set(named)
    unregistered = {
        n: p for n, p in named.items()
        if n not in ENV_REGISTRY and n not in chaos_only
    }
    assert unregistered == {}


# -- plan-space certifier ----------------------------------------------


def test_plancert_leaves_include_lookup():
    from csvplus_tpu.analysis.plancert import _enumerate_plans

    names = [name for name, _ in _enumerate_plans(1)]
    assert names == ["scan", "lookup"]


def test_plancert_size_two_space_certifies():
    from csvplus_tpu.analysis.plancert import certify, summary_json

    s = certify(n=2, budget_s=600.0)
    assert s.ok, s.describe()
    assert s.plans_total == 28  # 2 leaves x (1 + 13 stages)
    assert s.verified_ok == 28
    assert s.rewritten >= 1 and s.executed_pairs == s.rewritten
    j = summary_json(s)
    assert j["ok"] and j["failures"] == []
    assert "budget" not in j  # timing stays out of snapshots


def test_plancert_default_space_certifies_with_rejections():
    # the full default-N sweep: verifier-rejected trees (validate
    # breaks lowerability for downstream stages) are COUNTED, raising
    # plans compare exception types, and every obligation holds
    from csvplus_tpu.analysis.plancert import certify

    s = certify(n=3, budget_s=600.0)
    assert s.ok, s.describe()
    assert s.plans_total == 2 * (1 + 13 + 13 * 13)  # 366
    assert s.verifier_rejected > 0
    assert s.raised_pairs > 0
    assert s.refusals_checked > 0


def test_plancert_handles_empty_projection_schema():
    from csvplus_tpu.analysis.plancert import _corpus, _execute

    leaves, _stages = _corpus()
    root = P.SelectCols(leaves[0][1](), ())
    report = verify_plan(root)
    result = optimize_plan(root, report)
    assert result.report.ok == report.ok
    kind_a, _ = _execute(root)
    kind_b, _ = _execute(result.root)
    assert kind_a == kind_b


def test_plancert_budget_exceeded_fails_the_run():
    from csvplus_tpu.analysis.plancert import certify

    s = certify(n=3, budget_s=0.0)
    assert s.budget_exceeded and not s.ok


# -- sketch-aware selectivity (ROADMAP item 1) -------------------------


def _hot_sketch(values_counts):
    from csvplus_tpu.obs.sketch import SpaceSaving

    sk = SpaceSaving(8)
    sk.offer_counts([v for v, _ in values_counts], [c for _, c in values_counts])
    return sk


def test_selectivity_consults_live_sketch():
    from csvplus_tpu.analysis.cost import predicate_selectivity

    distinct = {"cat": 8}
    static = predicate_selectivity(Like({"cat": "k1"}), distinct)
    assert abs(static - 1.0 / 8) < 1e-9
    sk = _hot_sketch([("k1", 90), ("k0", 5), ("k2", 5)])
    hot = predicate_selectivity(Like({"cat": "k1"}), distinct, {"cat": sk})
    cold = predicate_selectivity(Like({"cat": "k0"}), distinct, {"cat": sk})
    assert abs(hot - 0.9) < 1e-9
    assert abs(cold - 0.05) < 1e-9
    # an empty sketch falls back to the static uniform guess
    from csvplus_tpu.obs.sketch import SpaceSaving

    empty = predicate_selectivity(
        Like({"cat": "k1"}), distinct, {"cat": SpaceSaving(8)}
    )
    assert abs(empty - static) < 1e-9


def test_sketch_pricing_flows_into_choose_fusion():
    from csvplus_tpu.analysis.cost import choose_fusion

    n = 400
    fact = DeviceTable.from_pylists(
        {
            "id": [str(i % 50) for i in range(n)],
            "cat": [f"k{i % 8}" for i in range(n)],
            "pad": [str(i) for i in range(n)],
        },
        device="cpu",
    )
    dim = cp.take(
        DeviceTable.from_pylists(
            {"id": [str(i) for i in range(50)],
             "region": [f"r{i % 5}" for i in range(50)]},
            device="cpu",
        )
    ).index_on("id").sync()
    plan = P.Join(P.Filter(P.Scan(fact), Like({"cat": "k1"})), dim, ("id",))
    base = choose_fusion(plan, sketches={})
    hot = choose_fusion(
        plan, sketches={"cat": _hot_sketch([("k1", 95), ("k0", 5)])}
    )
    assert base is not None and hot is not None
    # the live sketch says k1 dominates: the selected-row estimate rises
    assert hot["est_rows_selected"] > base["est_rows_selected"]


def test_sketch_pricing_never_changes_results_bitwise():
    # the satellite-2 differential: optimize under empty vs hot vs
    # adversarially-wrong sketches — pricing may change the CHOSEN
    # recipe, execution must stay bitwise identical to the unrewritten
    # plan either way
    n = 400
    fact = DeviceTable.from_pylists(
        {
            "id": [str(i % 50) for i in range(n)],
            "cat": [f"k{i % 8}" for i in range(n)],
            "pad": [str(i) for i in range(n)],
        },
        device="cpu",
    )
    dim = cp.take(
        DeviceTable.from_pylists(
            {"id": [str(i) for i in range(50)],
             "region": [f"r{i % 5}" for i in range(50)]},
            device="cpu",
        )
    ).index_on("id").sync()
    plan = P.SelectCols(
        P.Join(P.Filter(P.Scan(fact), Like({"cat": "k1"})), dim, ("id",)),
        ("id", "cat", "region"),
    )
    baseline = execute_plan_view(plan).materialize()
    ref = checksum_device_table(baseline, positional=True)
    sketch_worlds = [
        {},
        {"cat": _hot_sketch([("k1", 95), ("k0", 5)])},
        {"cat": _hot_sketch([("k0", 99), ("k2", 1)])},  # wrong about k1
        {"id": _hot_sketch([("7", 100)])},
    ]
    for sketches in sketch_worlds:
        result = optimize_plan(plan, sketches=sketches)
        out = execute_plan_view(result.root).materialize()
        assert out.nrows == baseline.nrows
        assert list(out.columns) == list(baseline.columns)
        assert checksum_device_table(out, positional=True) == ref
