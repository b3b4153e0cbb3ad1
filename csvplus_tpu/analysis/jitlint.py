"""Dataflow lints over the jit boundary: RETRACE002 and SYNC001.

Both rules run an INTRAPROCEDURAL taint dataflow per function, seeded
from the module's own jitted kernels (``@jax.jit``,
``@partial(jax.jit, static_argnames=...)`` and
``@register_kernel(name, static_argnames=...)``, ``obs/recompile``'s
decorator that jits), and prove facts about how
device values flow — the two load-bearing contracts the benches only
check dynamically (RecompileWatch / ``host_sync_elements``):

* **RETRACE002** — the static-argument boundary (the r06 retrace bug
  class).  For every module-level jitted kernel, each call site's
  STATIC arguments must derive only from shapes/dtypes/constants/
  bounded enums.  A static computed from device DATA (``int(x.sum())``
  passed as ``total_bits``) retraces per distinct value — the exact
  regression r06 measured at 7x.  Sanctioned laundering, which clears
  taint because it maps unbounded data into a log-bounded enum, is the
  repo's pow2-bucket idiom: ``1 << max(total - 1, 0).bit_length()``
  (and ``.shape``/``.ndim``/``.dtype``/``.size``/``len()``/
  comparisons/``bool()`` — all shape-derived or bounded).

* **SYNC001** — the host-sync boundary.  In hot-path modules (``ops/``,
  ``columnar/``, ``parallel/``, ``serve/``), an implicit device->host
  sync — ``np.asarray(x)``/``np.array(x)``/``bool(x)``/``int(x)``/
  ``float(x)``/``x.item()``/``x.tolist()``/``len(x)`` on a provably
  JAX value ``x`` — blocks on the device stream where the caller sees
  only an innocent conversion.  Deliberate syncs are legal ONLY when
  accounted: either the enclosing function calls
  ``telemetry.count_sync(...)`` (the ``host_sync_elements`` ledger —
  visible accounting in the same scope), or the site is pinned in
  :data:`SYNC001_ALLOWED` with a written citation of its accounting.
  Unexplained allowances are themselves findings: a stale or
  citation-free allowlist entry fails lint.

Device taint sources (per function): results of calls rooted at
``jnp``/``jax``/``lax``, results of same-module jitted kernel calls,
names passed positionally to a jnp/lax/kernel call (a kernel argument
IS a device value — upload wrappers ``asarray``/``array``/
``device_put`` excluded, since their argument is the host side), and
``isinstance(x, jax.Array)`` guards.  Data taint additionally follows
device values THROUGH a sync (``int(dev)`` is host data derived from
device data) — that is what RETRACE002 forbids in static positions.

Both analyses are intraprocedural and same-module by design: function
parameters are untainted (callers are checked at their own sites), so
every finding is a provable local derivation, not a may-alias guess.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .astlint import (
    LintFinding,
    _allow_key,
    _enclosing_function,
    _is_jit_decorator,
    _root_name,
)

__all__ = [
    "SYNC001_ALLOWED",
    "RETRACE002_ALLOWED",
    "jitlint_findings",
    "allowlist_global_findings",
]

#: Pinned allowlist for DELIBERATE device->host syncs:
#: ``"<file basename>:<function>" -> citation``.  Every entry MUST cite
#: where its elements land in the ``host_sync_elements`` ledger (or why
#: no transfer happens); an empty citation or an entry matching no
#: current finding is itself a SYNC001 finding — allowances stay
#: explained or they fail lint.
SYNC001_ALLOWED: Dict[str, str] = {
    # -- columnar ------------------------------------------------------
    "exec.py:_exec_stage": (
        "deliberate O(1) scalar control syncs (Validate failure probe, "
        "TakeWhile/DropWhile cut index) — one scalar per stage "
        "execution, no transfer of row data"
    ),
    "exec.py:first_missing_cell": (
        "error path only: scalar row-number syncs while the pipeline "
        "aborts; no transfer in steady state"
    ),
    "ingest.py:_assemble_rows_sharded": (
        "no transfer: len() reads host Python lists of shard segments "
        "(run/pieces), never a device array"
    ),
    "table.py:has_absent": (
        "deliberate cached scalar presence probe, once per column "
        "lifetime; no transfer of cell data"
    ),
    "table.py:take_rows": (
        "THE lookup batch's blocking read: every column's values at the "
        "batch's row positions (and, where the bounds stayed on the "
        "device, the bounds with them) in ONE int32 array of "
        "(head + columns) x bucket elements, counted by count_sync at "
        "the site and as host_syncs/elements/one_trip on "
        "serve:gather:readback; one read more per device set where a "
        "joined table's columns cannot enter one program"
    ),
    "table.py:sync": (
        "THE deliberate completion sync: one scalar round trip "
        "replacing per-buffer readiness pings; no transfer of column "
        "data"
    ),
    "typed.py:_demote": (
        "deliberate dictionary-build transfer of the UNIQUE values "
        "only, accounted as typed:demote stage elements; outside the "
        "host_sync_elements steady-state transfer guard by design"
    ),
    # -- ops -----------------------------------------------------------
    "join.py:_build": (
        "deliberate one-time host int64 key mirror at index BUILD "
        "(serves point_bounds and the partitioned-path preparation); "
        "the probe path does no transfer"
    ),
    "join.py:point_bounds": (
        "serve-tier point read: O(1) scalar bound syncs per lookup ARE "
        "the operation's answer; no transfer of table rows"
    ),
    "join.py:probe": (
        "no transfer: len() reads the host list of key-code arrays, "
        "not a device value"
    ),
    "join.py:expand_matches_device": (
        "deliberate: the one O(1) total sync sizing the static output "
        "shape (see docstring); no transfer of match data"
    ),
    "join.py:_checked_probe_cols": (
        "error path only: one scalar argmax sync while raising "
        "DataSourceError; no transfer on the happy path"
    ),
    "join.py:join_tables": (
        "deliberate stats-sync fast path: (total, max run) in ONE "
        "2-scalar transfer decides the unique fast paths and sizes the "
        "result, counted by count_sync at the site and as join:expand's "
        "host_sync_elements; the len() calls read host tuples and "
        "shapes; no transfer of row data (the unique-partial compaction "
        "is the device program join.compact_partial)"
    ),
    "join.py:_multiway_ids": (
        "deliberate multiway stats sync (multiway_join and the fused "
        "multiway_join_selected): (total, max fanout, rows avoided) in "
        "ONE 3-scalar transfer, counted by count_sync at the site and "
        "as join:expand's host_sync_elements; no transfer of row data"
    ),
    "join.py:_compose": (
        "set-up only: the largest and smallest count over a composed "
        "probe table in ONE 2-scalar transfer, once per (index, probe "
        "prefix or dictionary), deciding rid_tab and depth 2; runs in a "
        "plan's first execution, never in steady state, so it stays "
        "outside the host_sync_elements guard like typed:demote"
    ),
    "lanes.py:union_device": (
        "deliberate: the one scalar union-SIZE sync needed for the "
        "static output slice (see docstring); no transfer of lane data"
    ),
    "lanes.py:translate_lanes": (
        "no transfer: len() reads lane-tuple arity (host tuples), not "
        "a device value"
    ),
    "parse.py:encode_column_device": (
        "deliberate dictionary-build syncs: unique count + first-row "
        "ids so the host touches ONLY unique values; accounted as "
        "ingest stage elements, outside the host_sync_elements "
        "steady-state guard"
    ),
    "sort.py:find_adjacent_duplicate": (
        "deliberate validation scalars (any_dup flag + first index) — "
        "two O(1) syncs per index build; no transfer of key data"
    ),
    "sort.py:run_starts": (
        "the CALLBACK resolver's host bool run-starts mask (it groups "
        "on the host): O(n) transfer, counted by count_sync(nrows); the "
        "named policies never come here (compact_runs: one scalar "
        "host read, count_sync(1))"
    ),
}
#: RETRACE002's allowlist, same key/citation contract as
#: :data:`SYNC001_ALLOWED` — a data-derived static argument is only
#: legal with a written retrace-cost accounting.  Starts (and should
#: stay) empty: the pow2-bucket idiom launders every sanctioned case.
RETRACE002_ALLOWED: Dict[str, str] = {}

_HOT_DIRS = ("ops", "columnar", "parallel", "serve")

# calls whose RESULT is a host value even when the argument is a device
# value — the implicit-sync sinks SYNC001 flags (np.asarray/np.array by
# attribute, the rest by bare name / method)
_SINK_NP_ATTRS = frozenset({"asarray", "array"})
_SINK_BUILTINS = frozenset({"bool", "int", "float", "len"})
_SINK_METHODS = frozenset({"item", "tolist"})

# attribute reads that launder device taint: shape metadata, not data
_META_ATTRS = frozenset({"shape", "ndim", "dtype", "size", "sharding"})

# upload wrappers whose ARGUMENT is host-side: excluded from the
# "positional arg of a jnp call is a device value" evidence rule
_UPLOAD_ATTRS = frozenset({"asarray", "array", "device_put"})

# jax-rooted calls whose RESULT is host metadata, not a device array
_HOST_META_CALLS = frozenset(
    {"devices", "local_devices", "device_count", "local_device_count",
     "default_backend", "process_index", "block_until_ready"}
)

# array CONSTRUCTORS whose arguments are shapes/fill scalars, not device
# values: their result is a device array (dev_expr still says so) but
# their arguments carry no evidence — `jnp.full(k_pad - k, ...)` must
# not mark `k` as a device value.  The *_like variants take an array
# and are deliberately NOT here.
_SHAPE_CTOR_ATTRS = frozenset(
    {"zeros", "ones", "full", "empty", "arange", "linspace", "eye",
     "iota", "identity"}
)


def _is_hot_path(path: str) -> bool:
    return any(d in _HOT_DIRS for d in Path(path).parts[:-1])


def _jit_static_params(
    dec: ast.expr, params: Sequence[str]
) -> Optional[Set[str]]:
    """The static parameter NAMES a jit decorator declares, or None when
    *dec* is not a jit decorator.  Handles ``@jax.jit`` (no statics),
    ``@partial(jax.jit, static_argnames=..., static_argnums=...)`` and
    ``@register_kernel(name, static_argnames=...)``."""
    if not _is_jit_decorator(dec):
        return None
    statics: Set[str] = set()
    if isinstance(dec, ast.Call):
        for kw in dec.keywords:
            if kw.arg == "static_argnames":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and isinstance(n.value, str):
                        statics.add(n.value)
            elif kw.arg == "static_argnums":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and isinstance(n.value, int):
                        if 0 <= n.value < len(params):
                            statics.add(params[n.value])
    return statics


def _params_of(func: ast.AST) -> List[str]:
    a = func.args
    return [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]


def _kernel_table(tree: ast.Module) -> Dict[str, Tuple[List[str], Set[str]]]:
    """``{kernel name: (parameter names, static parameter names)}`` for
    every jitted def in the module (module-level or nested — nested
    kernels are still called by bare name) plus module-level
    ``name = jax.jit(fn, static_argnames=...)`` bindings."""
    out: Dict[str, Tuple[List[str], Set[str]]] = {}
    defs: Dict[str, ast.AST] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs[node.name] = node
            params = _params_of(node)
            statics: Optional[Set[str]] = None
            for dec in node.decorator_list:
                s = _jit_static_params(dec, params)
                if s is not None:
                    statics = (statics or set()) | s
            if statics is not None:
                out[node.name] = (params, statics)
    for stmt in tree.body:
        if not (
            isinstance(stmt, ast.Assign)
            and len(stmt.targets) == 1
            and isinstance(stmt.targets[0], ast.Name)
            and isinstance(stmt.value, ast.Call)
        ):
            continue
        call = stmt.value
        f = call.func
        is_jit = (isinstance(f, ast.Attribute) and f.attr == "jit") or (
            isinstance(f, ast.Name) and f.id == "jit"
        )
        if not is_jit or not call.args:
            continue
        inner = call.args[0]
        params = []
        if isinstance(inner, ast.Name) and inner.id in defs:
            params = _params_of(defs[inner.id])
        statics = set()
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and isinstance(n.value, str):
                        statics.add(n.value)
            elif kw.arg == "static_argnums":
                for n in ast.walk(kw.value):
                    if isinstance(n, ast.Constant) and isinstance(n.value, int):
                        if 0 <= n.value < len(params):
                            statics.add(params[n.value])
        out[stmt.targets[0].id] = (params, statics)
    return out


def _call_root(call: ast.Call) -> Optional[str]:
    return _root_name(call.func)


def _is_device_call(call: ast.Call, kernels: Dict) -> bool:
    """A call whose RESULT is a device value: rooted at jnp/jax/lax, or
    a same-module jitted kernel.  Host-metadata helpers
    (``jax.devices()``, ``jax.default_backend()``, ...) excluded."""
    f = call.func
    if isinstance(f, ast.Name) and f.id in kernels:
        return True
    if isinstance(f, ast.Attribute) and f.attr in _HOST_META_CALLS:
        return False
    root = _call_root(call)
    return root in ("jnp", "jax", "lax")


def _is_meta_expr(e: ast.expr) -> bool:
    """Provably shape-metadata: ``x.shape``, ``x.shape[0]``, constants."""
    if isinstance(e, ast.Constant):
        return True
    if isinstance(e, ast.Attribute):
        return e.attr in _META_ATTRS
    if isinstance(e, ast.Subscript):
        return _is_meta_expr(e.value)
    return False


def _sink_kind(call: ast.Call) -> Optional[Tuple[str, ast.expr]]:
    """``(description, synced argument)`` when *call* is one of the
    implicit-sync forms, else None."""
    f = call.func
    if (
        isinstance(f, ast.Attribute)
        and f.attr in _SINK_NP_ATTRS
        and isinstance(f.value, ast.Name)
        and f.value.id == "np"
        and call.args
    ):
        return (f"np.{f.attr}(...)", call.args[0])
    if isinstance(f, ast.Name) and f.id in _SINK_BUILTINS and len(call.args) == 1:
        return (f"{f.id}(...)", call.args[0])
    if isinstance(f, ast.Attribute) and f.attr in _SINK_METHODS and not call.args:
        return (f".{f.attr}()", f.value)
    return None


class _Taint:
    """Per-function device/data taint over simple assignments, run to a
    fixpoint.  ``dev`` holds names provably bound to JAX values; ``data``
    additionally holds host scalars DERIVED from device values through a
    sync sink (what RETRACE002 forbids in static positions)."""

    def __init__(self, func: ast.AST, kernels: Dict) -> None:
        self.kernels = kernels
        self.dev: Set[str] = set()
        self.data: Set[str] = set()
        self._seed_evidence(func)
        self._fixpoint(func)

    # -- evidence: names the function itself treats as device values ----
    def _seed_evidence(self, func: ast.AST) -> None:
        # names provably bound to shape metadata (`n = keys.shape[0]`)
        # are host ints everywhere — a later appearance inside a device
        # call's arguments (a clip bound, a slice width) is not evidence
        meta_names: Set[str] = set()
        for sub in ast.walk(func):
            if isinstance(sub, ast.Assign) and _is_meta_expr(sub.value):
                for tgt in sub.targets:
                    if isinstance(tgt, ast.Name):
                        meta_names.add(tgt.id)
        for sub in ast.walk(func):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            # isinstance(x, jax.Array) marks x as a device value
            if (
                isinstance(f, ast.Name)
                and f.id == "isinstance"
                and len(sub.args) == 2
                and isinstance(sub.args[0], ast.Name)
                and "jax" in ast.unparse(sub.args[1])
            ):
                self.dev.add(sub.args[0].id)
                continue
            if not _is_device_call(sub, self.kernels):
                continue
            if isinstance(f, ast.Attribute) and f.attr in _UPLOAD_ATTRS:
                continue  # upload wrappers take HOST arguments
            if isinstance(f, ast.Attribute) and f.attr in _SHAPE_CTOR_ATTRS:
                continue  # shape constructors take shapes/fill scalars
            statics: Set[str] = set()
            params: List[str] = []
            if isinstance(f, ast.Name) and f.id in self.kernels:
                params, statics = self.kernels[f.id]
            for i, a in enumerate(sub.args):
                if params and i < len(params) and params[i] in statics:
                    continue
                # only BARE names (incl. inside arithmetic/comparison/
                # starred wrapping) — NOT attribute roots: in
                # `k(self.packed)` the device value is the attribute,
                # not `self`.  Names inside a NESTED shape-ctor/upload
                # call (`concatenate([x, zeros(n - k)])`) are that
                # call's host-side arguments, not device values.
                skip = {
                    id(n.value)
                    for n in ast.walk(a)
                    if isinstance(n, ast.Attribute)
                }
                for n in ast.walk(a):
                    if (
                        isinstance(n, ast.Call)
                        and isinstance(n.func, ast.Attribute)
                        and n.func.attr in (_SHAPE_CTOR_ATTRS | _UPLOAD_ATTRS)
                    ):
                        skip.update(
                            id(m) for m in ast.walk(n)
                            if isinstance(m, ast.Name)
                        )
                for n in ast.walk(a):
                    if (
                        isinstance(n, ast.Name)
                        and id(n) not in skip
                        and n.id not in meta_names
                    ):
                        self.dev.add(n.id)

    # -- expression taint ----------------------------------------------
    def dev_expr(self, e: ast.expr) -> bool:
        if isinstance(e, ast.Name):
            return e.id in self.dev
        if isinstance(e, ast.Constant):
            return False
        if isinstance(e, ast.Attribute):
            if e.attr in _META_ATTRS:
                return False
            return self.dev_expr(e.value)
        if isinstance(e, ast.Call):
            if _sink_kind(e) is not None:
                return False  # the sink's result lives on host
            return _is_device_call(e, self.kernels)
        if isinstance(e, ast.Subscript):
            return self.dev_expr(e.value)
        if isinstance(e, (ast.Tuple, ast.List)):
            return any(self.dev_expr(x) for x in e.elts)
        if isinstance(e, ast.BinOp):
            return self.dev_expr(e.left) or self.dev_expr(e.right)
        if isinstance(e, ast.UnaryOp):
            return self.dev_expr(e.operand)
        if isinstance(e, ast.IfExp):
            return self.dev_expr(e.body) or self.dev_expr(e.orelse)
        if isinstance(e, ast.Starred):
            return self.dev_expr(e.value)
        if isinstance(e, ast.Compare):
            # dev <op> x is itself a device boolean array
            return self.dev_expr(e.left) or any(
                self.dev_expr(c) for c in e.comparators
            )
        return False

    def data_expr(self, e: ast.expr) -> bool:
        """Data-derived (RETRACE002 sense): contains device data or a
        synced derivative, NOT laundered through shape/dtype/bit_length/
        comparison/bool."""
        if isinstance(e, ast.Name):
            return e.id in self.data or e.id in self.dev
        if isinstance(e, ast.Constant):
            return False
        if isinstance(e, ast.Attribute):
            if e.attr in _META_ATTRS:
                return False
            return self.data_expr(e.value)
        if isinstance(e, ast.Call):
            f = e.func
            # laundering calls: shape-derived or bounded-enum results
            if isinstance(f, ast.Attribute) and f.attr == "bit_length":
                return False
            if isinstance(f, ast.Name) and f.id in ("len", "bool"):
                return False
            sink = _sink_kind(e)
            if sink is not None:
                # int(x)/np.asarray(x)/x.item()/... — data survives the
                # hop to host
                return self.data_expr(sink[1])
            if _is_device_call(e, self.kernels):
                return True
            return any(self.data_expr(a) for a in e.args) or any(
                self.data_expr(kw.value) for kw in e.keywords
            )
        if isinstance(e, (ast.Compare, ast.BoolOp)):
            return False  # bounded enum (a bool), the sanctioned class
        if isinstance(e, ast.Subscript):
            return self.data_expr(e.value) or self.data_expr(e.slice)
        if isinstance(e, (ast.Tuple, ast.List, ast.Set)):
            return any(self.data_expr(x) for x in e.elts)
        if isinstance(e, ast.BinOp):
            return self.data_expr(e.left) or self.data_expr(e.right)
        if isinstance(e, ast.UnaryOp):
            return self.data_expr(e.operand)
        if isinstance(e, ast.IfExp):
            return self.data_expr(e.body) or self.data_expr(e.orelse)
        if isinstance(e, ast.Starred):
            return self.data_expr(e.value)
        if isinstance(e, (ast.GeneratorExp, ast.ListComp, ast.SetComp)):
            return self.data_expr(e.elt) or any(
                self.data_expr(g.iter) for g in e.generators
            )
        return False

    # -- assignment fixpoint -------------------------------------------
    def _assign(self, target: ast.expr, is_dev: bool, is_data: bool) -> bool:
        changed = False
        if isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                changed |= self._assign(el, is_dev, is_data)
            return changed
        if isinstance(target, ast.Starred):
            return self._assign(target.value, is_dev, is_data)
        if isinstance(target, ast.Name):
            if is_dev and target.id not in self.dev:
                self.dev.add(target.id)
                changed = True
            if is_data and target.id not in self.data:
                self.data.add(target.id)
                changed = True
        return changed

    def _fixpoint(self, func: ast.AST) -> None:
        for _ in range(8):  # chains are short; 8 rounds is generous
            changed = False
            for sub in ast.walk(func):
                if isinstance(sub, ast.Assign):
                    d, t = self.dev_expr(sub.value), self.data_expr(sub.value)
                    for tgt in sub.targets:
                        changed |= self._assign(tgt, d, t)
                elif isinstance(sub, ast.AugAssign):
                    d, t = self.dev_expr(sub.value), self.data_expr(sub.value)
                    changed |= self._assign(sub.target, d, t)
                elif isinstance(sub, (ast.AnnAssign,)) and sub.value is not None:
                    d, t = self.dev_expr(sub.value), self.data_expr(sub.value)
                    changed |= self._assign(sub.target, d, t)
            if not changed:
                return


def _functions(tree: ast.Module):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _calls_count_sync(func: ast.AST) -> bool:
    for sub in ast.walk(func):
        if (
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Attribute)
            and sub.func.attr == "count_sync"
        ):
            return True
    return False


def _sync_findings(
    tree: ast.Module, path: str, kernels: Dict
) -> Tuple[List[LintFinding], Set[str]]:
    """SYNC001 over one hot-path module.  Returns the findings plus the
    set of allowlist keys actually matched (for staleness checking)."""
    findings: List[LintFinding] = []
    matched: Set[str] = set()
    for func in _functions(tree):
        taint = _Taint(func, kernels)
        accounted = _calls_count_sync(func)
        own_defs = {
            id(s)
            for s in ast.walk(func)
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))
            and s is not func
        }

        def in_nested(node: ast.AST) -> bool:
            for s in ast.walk(func):
                if id(s) in own_defs:
                    end = getattr(s, "end_lineno", s.lineno)
                    if s.lineno <= node.lineno <= end:
                        return True
            return False

        for sub in ast.walk(func):
            if not isinstance(sub, ast.Call) or in_nested(sub):
                continue
            sink = _sink_kind(sub)
            if sink is None:
                continue
            desc, arg = sink
            if not taint.dev_expr(arg):
                continue
            key = _allow_key(path, func)
            if key in SYNC001_ALLOWED:
                matched.add(key)
                continue
            if accounted:
                continue  # count_sync in the same scope IS the ledger
            findings.append(
                LintFinding(
                    "SYNC001",
                    path,
                    sub.lineno,
                    f"implicit device->host sync: {desc} on a JAX value "
                    f"in `{getattr(func, 'name', '?')}` — account it via "
                    "telemetry.count_sync in the same function, or pin "
                    "it in SYNC001_ALLOWED with its host_sync_elements "
                    "citation",
                )
            )
    return findings, matched


def _retrace_findings(
    tree: ast.Module, path: str, kernels: Dict
) -> List[LintFinding]:
    """RETRACE002 over one module: every static argument at every
    same-module kernel call site must be static-safe."""
    findings: List[LintFinding] = []
    statics_by_kernel = {
        name: (params, statics)
        for name, (params, statics) in kernels.items()
        if statics
    }
    if not statics_by_kernel:
        return findings
    for func in _functions(tree):
        taint = _Taint(func, kernels)
        for sub in ast.walk(func):
            if not (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Name)
                and sub.func.id in statics_by_kernel
            ):
                continue
            params, statics = statics_by_kernel[sub.func.id]
            static_args: List[Tuple[str, ast.expr]] = []
            for i, a in enumerate(sub.args):
                if i < len(params) and params[i] in statics:
                    static_args.append((params[i], a))
            for kw in sub.keywords:
                if kw.arg in statics:
                    static_args.append((kw.arg, kw.value))
            for pname, expr in static_args:
                if not taint.data_expr(expr):
                    continue
                key = _allow_key(path, func)
                if key in RETRACE002_ALLOWED and RETRACE002_ALLOWED[key]:
                    continue
                findings.append(
                    LintFinding(
                        "RETRACE002",
                        path,
                        expr.lineno,
                        f"static argument `{pname}` of kernel "
                        f"`{sub.func.id}` derives from device DATA "
                        f"(`{ast.unparse(expr)}`) — every distinct value "
                        "is a fresh trace+compile (the r06 class); "
                        "launder through the pow2 bucket "
                        "(`1 << max(n - 1, 0).bit_length()`) or a "
                        "shape/dtype derivation",
                    )
                )
    return findings


def _allowlist_findings(path: str) -> List[LintFinding]:
    """Per-file meta-rule: every allowlist entry for THIS file must
    carry a non-empty accounting citation — zero unexplained
    allowances.  Staleness (an entry no live sync site matches) is a
    WHOLE-TREE property and lives in
    :func:`allowlist_global_findings` — a single-file lint cannot tell
    a stale entry from one whose site it simply is not looking at."""
    findings: List[LintFinding] = []
    base = Path(path).name
    for table_name, table in (
        ("SYNC001_ALLOWED", SYNC001_ALLOWED),
        ("RETRACE002_ALLOWED", RETRACE002_ALLOWED),
    ):
        code = table_name.split("_")[0]
        for key, citation in table.items():
            if not key.startswith(base + ":"):
                continue
            if not citation.strip():
                findings.append(
                    LintFinding(
                        code,
                        path,
                        1,
                        f"{table_name} entry `{key}` has no written "
                        "accounting citation — unexplained allowances "
                        "fail lint",
                    )
                )
            elif code == "SYNC001" and not any(
                tok in citation
                for tok in ("host_sync_elements", "count_sync", "no transfer")
            ):
                findings.append(
                    LintFinding(
                        code,
                        path,
                        1,
                        f"{table_name} entry `{key}` must cite its "
                        "host_sync_elements / count_sync accounting "
                        "(or state why no transfer happens)",
                    )
                )
    return findings


def allowlist_global_findings(matched: Set[str]) -> List[LintFinding]:
    """Whole-tree meta-rule (the ``global_checks`` lint pass): every
    allowlist entry must have matched a live sync site somewhere in the
    tree — *matched* is the union of matched keys over every linted
    hot-path file.  A key nothing matched is a stale allowance: the
    sync it blessed was removed or renamed, so the entry must go too
    (it would silently bless a FUTURE sync under the same name)."""
    findings: List[LintFinding] = []
    for table_name, table in (
        ("SYNC001_ALLOWED", SYNC001_ALLOWED),
        ("RETRACE002_ALLOWED", RETRACE002_ALLOWED),
    ):
        code = table_name.split("_")[0]
        for key in table:
            if key not in matched:
                findings.append(
                    LintFinding(
                        code,
                        key.split(":", 1)[0],
                        1,
                        f"stale {table_name} entry `{key}`: no current "
                        "sync site matches it — remove the allowance",
                    )
                )
    return findings


def jitlint_findings(
    tree: ast.Module,
    path: str,
    matched_out: Optional[Set[str]] = None,
) -> List[LintFinding]:
    """All RETRACE002/SYNC001 findings for one parsed module.  When
    *matched_out* is given (the whole-tree lint), the allowlist keys
    this file's sync sites matched are accumulated into it for the
    global staleness check."""
    kernels = _kernel_table(tree)
    findings = _retrace_findings(tree, path, kernels)
    if _is_hot_path(path):
        sync, matched = _sync_findings(tree, path, kernels)
        findings.extend(sync)
        findings.extend(_allowlist_findings(path))
        if matched_out is not None:
            matched_out |= matched
    return findings
