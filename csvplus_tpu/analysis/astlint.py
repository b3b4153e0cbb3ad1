"""Repo-specific AST lint: rules generic linters cannot know.

Eight rule classes have bitten this codebase (or its measured history)
and are mechanically checkable from the AST:

* **CTYPES001** — the native scanner boundary.  The C ABI's ``c_char``
  takes EXACTLY one byte; ctypes raises a cryptic ``TypeError`` (or
  silently truncates, for sliced bytes) when a multi-byte encoding of a
  user-supplied delimiter/comment reaches it.  Every ``.encode(...)``
  expression flowing into a ``c_char`` parameter position (positions are
  discovered from the module's own ``lib.X.argtypes = [...]``
  assignments) must be gated in the same function by a
  ``len(<that expression>) == 1`` / ``!= 1`` test or an explicit
  single-byte slice ``[0:1]``.  The round-5 fused-path bug — a
  multi-byte delimiter reaching ``csv_scan_parse_i32`` ungated — is
  exactly this rule.
* **JIT001** — the retrace boundary.  A ``jax.jit``-ed function whose
  body iterates one of its PARAMETERS in a comprehension has a
  tuple-of-arrays signature: every distinct tuple LENGTH is a fresh
  trace + compile (one per chunk-count in the ingest profile).  Such
  kernels should be eager, take a fixed arity, or carry an explicit
  suppression acknowledging the retrace cost.
* **TRACE001** — the trace-churn boundary (the ``_values_concat``
  regression class).  A jit-wrapped callable CONSTRUCTED inside a
  function body is rebuilt — and retraced — on every call; jit
  construction with a non-hashable ``static_argnums``/``static_argnames``
  literal fails at first call.  Sanctioned shapes: module-level jitted
  kernels (``_translate_dense_kernel``), and construction memoized into
  module-owned state (a ``global``-declared name, or a module-level
  cache like ``_JIT_KERNELS.update(...)``) so it happens once.
* **EAGER001** — the unfused-hot-loop boundary (the r06 regression:
  eager per-column translate/pack loops cost 3x the warm sharded join).
  A plain Python ``for`` loop in a HOT module (``ops/``,
  ``columnar/typed.py``, ``columnar/table.py``) issuing two or more
  unfused jnp element-wise transforms per iteration, outside any jit
  context (neither jit-decorated nor called from a same-module jitted
  kernel), dispatches each op eagerly per column per execution.
* **THREAD001** — the worker-purity boundary (the r07 invariant: "all
  cross-chunk state lives in the reassembler").  In a module defining a
  stream worker entry (``_scan_encode_chunk``), no function reachable
  from the worker may mutate module-global state (or the shared context
  argument) — except under a module-level ``threading.Lock``/``RLock``
  ``with`` block (double-checked pool/library init) or into
  ``threading.local()`` storage.
* **LOCK001** — the lock-ordering boundary (ISSUE 16).  Two code paths
  nesting the same pair of locks in opposite orders deadlock under
  contention.  The repo's monitors (serve dispatcher, storage
  writer/compactor, views refresh, obs plane) follow a constant-lock-
  rounds discipline — one lock, bounded work, release — so ANY
  lexically nested acquisition of two recognized locks (module-level
  ``Lock``/``RLock`` names, ``*lock``/``*cv`` attributes) is flagged
  unless the ordered pair appears in the single canonical order table
  ``LOCK001_CANONICAL_ORDER`` (one documented entry: the views refresh
  pass).  The allowance list stays empty — sanctioned nesting is an
  ordering fact, not a per-site waiver.
* **FAULT001** — the silent-swallow boundary (ISSUE 8).  The reference
  error contract says every failure surfaces typed and row-annotated
  (csvplus.go:1229-1238), but a broad ``except``/``except Exception``/
  ``except BaseException`` handler whose body is ONLY ``pass``/
  ``continue`` silently discards whatever went wrong.  Handlers must
  re-raise, wrap via ``map_error``, or record the failure to
  metrics/telemetry/stderr; narrowly-typed best-effort catches
  (``except (OSError, AttributeError):``) remain legal.
* **IO001** — the durability boundary (ISSUE 10).  Under ``storage/``,
  a bare ``open()`` with a write mode in a function that neither
  ``os.fsync``-es nor publishes via ``os.replace``/``os.rename`` can
  ack data that exists only in the page cache — the acked-then-lost
  window the WAL/manifest machinery exists to close.

Each of TRACE001/EAGER001/THREAD001/LOCK001/FAULT001/IO001 carries an explicit
allowance list below (``*_ALLOWED``) that STARTS EMPTY and must stay
empty for the current tree; additions need review.

Suppression: a ``# analysis: allow[CODE]`` comment on the flagged line
or on the enclosing ``def`` line.

Run over the tree with ``python -m csvplus_tpu.analysis`` (no
arguments = the whole installed package tree, so a new module can never
bypass the gate; wired into ``make lint``).
"""

from __future__ import annotations

import ast
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

__all__ = ["LintFinding", "lint_source", "lint_file", "lint_paths"]


@dataclass(frozen=True)
class LintFinding:
    code: str  # "CTYPES001" | "JIT001" | "TRACE001" | "EAGER001" | "THREAD001" | "LOCK001" | "FAULT001" | "IO001"
    path: str
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.code} {self.message}"


def _is_c_char(node: ast.expr) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == "c_char") or (
        isinstance(node, ast.Name) and node.id == "c_char"
    )


def _c_char_positions(tree: ast.Module) -> Dict[str, Tuple[int, ...]]:
    """``{function_name: c_char argument positions}`` from every
    ``<lib>.NAME.argtypes = [...]`` assignment in the module."""
    out: Dict[str, Tuple[int, ...]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        tgt = node.targets[0]
        if not (
            isinstance(tgt, ast.Attribute)
            and tgt.attr == "argtypes"
            and isinstance(tgt.value, ast.Attribute)
        ):
            continue
        if not isinstance(node.value, (ast.List, ast.Tuple)):
            continue
        pos = tuple(
            i for i, el in enumerate(node.value.elts) if _is_c_char(el)
        )
        if pos:
            out[tgt.value.attr] = pos
    return out


def _find_encode(node: ast.expr) -> Optional[ast.Call]:
    """The ``<something>.encode(...)`` call inside *node*, if any."""
    for sub in ast.walk(node):
        if (
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Attribute)
            and sub.func.attr == "encode"
        ):
            return sub
    return None


def _is_single_byte_slice(node: ast.expr) -> bool:
    """``X[0:1]`` — an explicit truncation to at most one byte."""
    if not (isinstance(node, ast.Subscript) and isinstance(node.slice, ast.Slice)):
        return False
    s = node.slice
    return (
        isinstance(s.lower, ast.Constant)
        and s.lower.value == 0
        and isinstance(s.upper, ast.Constant)
        and s.upper.value == 1
        and s.step is None
    )


def _len_one_guards(func: ast.AST) -> Set[str]:
    """Unparsed sources ``X`` for every ``len(X) == 1`` / ``len(X) != 1``
    comparison anywhere in *func* (either operand order)."""
    out: Set[str] = set()

    def record(len_side: ast.expr, const_side: ast.expr) -> None:
        if (
            isinstance(len_side, ast.Call)
            and isinstance(len_side.func, ast.Name)
            and len_side.func.id == "len"
            and len(len_side.args) == 1
            and isinstance(const_side, ast.Constant)
            and const_side.value == 1
        ):
            out.add(ast.unparse(len_side.args[0]))

    for node in ast.walk(func):
        if not isinstance(node, ast.Compare) or len(node.ops) != 1:
            continue
        if not isinstance(node.ops[0], (ast.Eq, ast.NotEq)):
            continue
        record(node.left, node.comparators[0])
        record(node.comparators[0], node.left)
    return out


def _local_assignments(func: ast.AST) -> Dict[str, ast.expr]:
    """Simple single-target ``name = expr`` bindings in *func* (last one
    wins — good enough for the guard-resolution heuristic)."""
    out: Dict[str, ast.expr] = {}
    for node in ast.walk(func):
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
        ):
            out[node.targets[0].id] = node.value
    return out


class _FunctionStack(ast.NodeVisitor):
    """Visitor that tracks the enclosing function for every node."""

    def __init__(self) -> None:
        self.stack: List[ast.AST] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.stack.append(node)
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    @property
    def current(self) -> Optional[ast.AST]:
        return self.stack[-1] if self.stack else None


class _CtypesVisitor(_FunctionStack):
    def __init__(self, positions: Dict[str, Tuple[int, ...]], path: str):
        super().__init__()
        self.positions = positions
        self.path = path
        self.findings: List[LintFinding] = []

    def visit_Call(self, node: ast.Call) -> None:
        self.generic_visit(node)
        fn = node.func
        if not (isinstance(fn, ast.Attribute) and fn.attr in self.positions):
            return
        func = self.current
        guards = _len_one_guards(func) if func is not None else set()
        local = _local_assignments(func) if func is not None else {}
        for pos in self.positions[fn.attr]:
            if pos >= len(node.args):
                continue
            arg = node.args[pos]
            name = None
            if isinstance(arg, ast.Name):
                name = arg.id
                arg = local.get(arg.id, arg)
            enc = _find_encode(arg)
            if enc is None:
                continue
            if _is_single_byte_slice(arg):
                continue
            gate_keys = {ast.unparse(arg), ast.unparse(enc)}
            if name is not None:
                gate_keys.add(name)
            if gate_keys & guards:
                continue
            self.findings.append(
                LintFinding(
                    "CTYPES001",
                    self.path,
                    node.args[pos].lineno,
                    f"{ast.unparse(enc)} flows into c_char parameter "
                    f"{pos} of {fn.attr} without a len(...) == 1 gate "
                    "in the enclosing function",
                )
            )


# decorators that jit: jax's own, and ``obs/recompile.register_kernel``
# (``@register_kernel("join.pack_qk", static_argnames=...)``), which
# jits, names and registers a module-level kernel in one step
_JIT_DECORATOR_NAMES = frozenset({"jit", "register_kernel"})


def _is_jit_decorator(dec: ast.expr) -> bool:
    """``@jax.jit``, ``@jit``, ``@register_kernel(...)``, or any
    decorator CALL mentioning one of them
    (``functools.partial(jax.jit, ...)``)."""
    for node in ast.walk(dec):
        if isinstance(node, ast.Attribute) and node.attr in _JIT_DECORATOR_NAMES:
            return True
        if isinstance(node, ast.Name) and node.id in _JIT_DECORATOR_NAMES:
            return True
    return False


class _JitVisitor(ast.NodeVisitor):
    def __init__(self, path: str):
        self.path = path
        self.findings: List[LintFinding] = []

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.generic_visit(node)
        if not any(_is_jit_decorator(d) for d in node.decorator_list):
            return
        params = {
            a.arg
            for a in (
                node.args.posonlyargs + node.args.args + node.args.kwonlyargs
            )
        }

        def iterates_param(it: ast.expr) -> Optional[str]:
            if isinstance(it, ast.Name) and it.id in params:
                return it.id
            # zip(maps, cks) / enumerate(cks) over parameters
            if isinstance(it, ast.Call):
                for a in it.args:
                    if isinstance(a, ast.Name) and a.id in params:
                        return a.id
            return None

        # one finding per function: the signature is the problem, not
        # each comprehension that exhibits it
        for sub in ast.walk(node):
            if isinstance(
                sub, (ast.ListComp, ast.SetComp, ast.GeneratorExp, ast.For)
            ):
                its = (
                    [g.iter for g in sub.generators]
                    if not isinstance(sub, ast.For)
                    else [sub.iter]
                )
                for it in its:
                    hit = iterates_param(it)
                    if hit is not None:
                        self.findings.append(
                            LintFinding(
                                "JIT001",
                                self.path,
                                sub.lineno,
                                f"jit-compiled `{node.name}` iterates "
                                f"parameter `{hit}`: a tuple-of-arrays "
                                "signature retraces per distinct length",
                            )
                        )
                        return


# ---------------------------------------------------------------------------
# TRACE001 / EAGER001 / THREAD001 — regression-derived rules (ISSUE 5).
# Allowance lists start EMPTY and must stay empty on the current tree:
# entries are "<file basename>:<enclosing function>" and need review.
# ---------------------------------------------------------------------------

TRACE001_ALLOWED: frozenset = frozenset()
EAGER001_ALLOWED: frozenset = frozenset()
THREAD001_ALLOWED: frozenset = frozenset()
FAULT001_ALLOWED: frozenset = frozenset()
IO001_ALLOWED: frozenset = frozenset()
LOCK001_ALLOWED: frozenset = frozenset()

#: LOCK001's canonical lock-order table: the ONLY sanctioned nested
#: acquisitions, as ``(outer identity, inner identity)`` pairs (see
#: ``_lock_identity`` for the identity format: ``Owner.attr`` for
#: attribute locks, ``module_stem.name`` for module-level locks).  The
#: repo's concurrency discipline is CONSTANT LOCK ROUNDS — take one
#: lock, do bounded work, release, then take the next (the r08 metrics
#: cycle, joinskew's registry-then-sketch sequence, the plan cache's
#: verify-outside-the-lock miss path) — so any lexical nesting of two
#: recognized locks is a finding until the pair is reviewed, documented
#: here, and ordered once for the whole repo.  Current entries:
#:
#: * ``MaterializedView._lock -> MaterializedView._qlock`` — the
#:   refresh pass (serialized by ``_lock``) dequeues tier events under
#:   the O(1) queue guard; every other ``_qlock`` use is a leaf (no
#:   lock acquired inside it), so the order is total and deadlock-free.
LOCK001_CANONICAL_ORDER: frozenset = frozenset({
    ("MaterializedView._lock", "MaterializedView._qlock"),
})

# modules whose per-row loops sit on the measured hot path (r06)
_EAGER_HOT_DIRS = ("ops",)
_EAGER_HOT_FILES = ("typed.py", "table.py")

# Cross-thread entry points whose reachable call graph must mutate
# shared state only under locks: the r07 ingest worker, plus the r08
# serving tier's dispatcher loop and its caller-side submission path
# and the serving monitors' mutators (metrics counters/reservoirs, the
# plan-cache map), plus the r09 observability subsystem's entry points
# (telemetry mutators, the tracer's cross-thread recorders, the kernel
# registry, and the memory sampler loop — all called from ingest
# workers, the serve dispatcher, and submitters concurrently).
# Matching is on the bare name, so class METHODS with these names are
# entries too (the lint tracks ``self`` as the shared context).
_WORKER_ENTRY_NAMES = (
    "_scan_encode_chunk",
    "_dispatch_loop",
    "_enqueue",
    "on_tick",
    "on_batch",
    "on_enqueue",
    "on_shed",
    "on_complete_batch",
    "executable_for",
    # csvplus_tpu/obs + utils/observe entry points (r09)
    "add_stage",
    "count",
    "count_sync",
    "add_span",
    "record_span",
    "drain",
    "register_kernel",
    "_sample_loop",
    # csvplus_tpu/resilience entry points (ISSUE 8): the fault plan's
    # hit-counter mutator (armed chaos runs hit it from every worker,
    # dispatcher, and submitter thread), the circuit breaker's
    # route/outcome mutators, and the new serving-metrics counters
    # (retry / degrade / callback-error accounting).
    "fire",
    "route",
    "on_success",
    "on_failure",
    "on_retry",
    "on_degraded",
    "on_callback_error",
    # csvplus_tpu/storage entry points (ISSUE 9): the mutable index's
    # writers (append batches land from caller threads and the serve
    # dispatcher; compact_once races both), the compactor's background
    # loop, and the serving tier's registry/append/per-index-metrics
    # mutators.
    "append_rows",
    "append_table",
    "append_csv",
    "compact_once",
    "_compact_loop",
    "run_once",
    "register",
    "submit_append",
    "on_index_batch",
    "on_compact",
    # csvplus_tpu/storage durability entry points (ISSUE 10): the
    # tombstone writer and leveled-compaction step race appends and the
    # compactor like the r09 writers; the WAL's record/seal/drop
    # mutators are hit from every writer thread AND the compactor's
    # checkpoint; wal_sync is the serve dispatcher's per-cycle fsync
    # barrier; on_recovered lands recovery counts into the serving
    # metrics monitor at registration time.
    "delete",
    "compact_step",
    "wal_sync",
    "append_record",
    "sync_now",
    "seal_active",
    "drop_applied",
    "on_recovered",
    # csvplus_tpu/storage read-pruning entry points (ISSUE 11): the
    # multi-tier probe path itself (serving threads call bounds_many
    # concurrently with writers swapping tier sets — its lazy builds
    # must stay lock-guarded), and the read-amplification tracker's
    # recorder/window mutators (hit from every reader thread and the
    # readamp-policy compactor loop).
    "bounds_many",
    "on_lookup_batch",
    "take_window",
    # csvplus_tpu/views + serve view entry points (ISSUE 12): the
    # tier-swap listener registry mutators and the event-intake
    # callback (fired UNDER the source's writer lock from every writer
    # thread), the refresh pass (serve dispatcher + caller threads) and
    # the lock-free snapshot read path, the server's view registration
    # and delete submission, the per-view metrics mutators, and the
    # lazy pruner/prune-directory builds the probe path races against
    # tier swaps (made lazy in this issue).
    "subscribe",
    "unsubscribe",
    "_on_tier_event",
    "refresh",
    "read",
    "register_view",
    "submit_delete",
    "on_view_refresh",
    "on_view_read",
    "ensure_pruner",
    "prune_directory",
    # csvplus_tpu/obs/joinskew + ops/join skew entry points (ISSUE 15):
    # the partitioned probe's routing-evidence mutators (hit from every
    # pipeline/ingest/serve thread that executes a sharded join) and
    # the index's once-only build-sample offer (first probe or point
    # lookup wins the race under the aux lock).
    "on_join",
    "offer_build",
    "offer_build_sample",
    # csvplus_tpu/obs/joinskew multiway entry point (ISSUE 17): the
    # fused single-pass join's evidence mutator — same concurrency
    # envelope as on_join (any thread executing a multiway join).
    "on_multiway",
)

_EAGER_TRANSFORM_OPS = frozenset(
    {
        "where",
        "take",
        "take_along_axis",
        "clip",
        "searchsorted",
        "minimum",
        "maximum",
        "equal",
        "not_equal",
        "greater",
        "greater_equal",
        "less",
        "less_equal",
        "left_shift",
        "right_shift",
        "bitwise_or",
        "bitwise_and",
        "bitwise_xor",
        "add",
        "subtract",
        "multiply",
        "sum",
        "cumsum",
        "select",
    }
)

_MUTATING_METHODS = frozenset(
    {
        "append",
        "add",
        "update",
        "extend",
        "insert",
        "pop",
        "popitem",
        "remove",
        "discard",
        "clear",
        "setdefault",
        "sort",
        "reverse",
        # deque / OrderedDict mutators the serving tier's queues and
        # LRUs lean on (r08)
        "popleft",
        "appendleft",
        "move_to_end",
    }
)


def _allow_key(path: str, func: Optional[ast.AST]) -> str:
    name = getattr(func, "name", "<module>") if func is not None else "<module>"
    return f"{Path(path).name}:{name}"


def _module_level_names(tree: ast.Module) -> Set[str]:
    """Names bound at module scope (assignments, defs, imports)."""
    out: Set[str] = set()
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        out.add(n.id)
        elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
            if isinstance(stmt.target, ast.Name):
                out.add(stmt.target.id)
        elif isinstance(
            stmt, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
        ):
            out.add(stmt.name)
        elif isinstance(stmt, (ast.Import, ast.ImportFrom)):
            for a in stmt.names:
                out.add((a.asname or a.name).split(".")[0])
    return out


def _jit_construction(call: ast.Call) -> bool:
    """A call whose RESULT is a jit-wrapped callable: ``jax.jit(...)``,
    ``jit(...)``, or ``functools.partial(jax.jit, ...)``."""
    f = call.func
    if isinstance(f, ast.Attribute) and f.attr == "jit":
        return True
    if isinstance(f, ast.Name) and f.id == "jit":
        return True
    if (isinstance(f, ast.Attribute) and f.attr == "partial") or (
        isinstance(f, ast.Name) and f.id == "partial"
    ):
        return bool(call.args) and _is_jit_decorator(call.args[0])
    return False


def _declared_globals(func: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for n in ast.walk(func):
        if isinstance(n, ast.Global):
            out.update(n.names)
    return out


def _root_name(node: ast.expr) -> Optional[str]:
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


class _TraceVisitor(_FunctionStack):
    """TRACE001: jit construction inside a function body (unless stored
    into module-owned state) and non-hashable static-arg literals."""

    def __init__(self, path: str, tree: ast.Module):
        super().__init__()
        self.path = path
        self.module_names = _module_level_names(tree)
        self.findings: List[LintFinding] = []
        # decorator expressions are governed by the FunctionDef branch,
        # not the Call branch (a nested `@partial(jax.jit, ...)` def is
        # one construction, not two)
        self._decorator_nodes = {
            id(sub)
            for f in ast.walk(tree)
            if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef))
            for d in f.decorator_list
            for sub in ast.walk(d)
        }

    def _flag(self, line: int, func: Optional[ast.AST], message: str) -> None:
        if _allow_key(self.path, func) in TRACE001_ALLOWED:
            return
        self.findings.append(LintFinding("TRACE001", self.path, line, message))

    def _stores_to_module_state(self, outer: ast.AST, match) -> bool:
        """True when an assignment in *outer* whose value satisfies
        *match* targets a ``global``-declared name, a module-level name,
        or a subscript/attribute of one — the sanctioned memoization."""
        owned = _declared_globals(outer) | self.module_names
        for n in ast.walk(outer):
            if isinstance(n, ast.Assign) and match(n.value):
                for t in n.targets:
                    if isinstance(t, ast.Name) and t.id in owned:
                        return True
                    if isinstance(t, (ast.Subscript, ast.Attribute)):
                        root = _root_name(t)
                        if root is not None and root in owned:
                            return True
        return False

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        outer = self.current
        if outer is not None and any(
            _is_jit_decorator(d) for d in node.decorator_list
        ):
            escapes = self._stores_to_module_state(
                outer,
                lambda v: any(
                    isinstance(s, ast.Name) and s.id == node.name
                    for s in ast.walk(v)
                ),
            )
            if not escapes:
                self._flag(
                    node.lineno,
                    outer,
                    f"jit-wrapped `{node.name}` is constructed inside "
                    f"`{outer.name}`: retraced on every call — hoist to a "
                    "module-level kernel or memoize into module state",
                )
        self.stack.append(node)
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_Call(self, node: ast.Call) -> None:
        self.generic_visit(node)
        if not _jit_construction(node):
            return
        func = self.current
        for kw in node.keywords:
            if kw.arg in ("static_argnums", "static_argnames") and isinstance(
                kw.value, (ast.Dict, ast.Set, ast.DictComp, ast.SetComp)
            ):
                self._flag(
                    node.lineno,
                    func,
                    f"jit construction passes a non-hashable {kw.arg} "
                    "literal — fails (or cache-misses) at first call",
                )
        if id(node) in self._decorator_nodes:
            return
        if func is None:
            return  # module-level jitted kernels are THE sanctioned shape
        if self._stores_to_module_state(
            func, lambda v: any(s is node for s in ast.walk(v))
        ):
            return
        # a module-cache method call, e.g. _JIT_KERNELS.update(k=jax.jit(f))
        for n in ast.walk(func):
            if (
                isinstance(n, ast.Call)
                and n is not node
                and isinstance(n.func, ast.Attribute)
                and _root_name(n.func) in self.module_names
                and any(s is node for s in ast.walk(n))
            ):
                return
        self._flag(
            node.lineno,
            func,
            f"jit-wrapped callable constructed inside `{func.name}`: "
            "retraced on every call — hoist to a module-level kernel or "
            "memoize into module state",
        )


def _is_hot_module(path: str) -> bool:
    p = Path(path)
    return p.name in _EAGER_HOT_FILES or any(
        d in _EAGER_HOT_DIRS for d in p.parts[:-1]
    )


def _jit_context_names(tree: ast.Module) -> Set[str]:
    """Function names that execute under jit in THIS module: defs with a
    jit decorator, defs passed to a jit construction, and everything
    they transitively call by local name."""
    defs: Dict[str, List[ast.AST]] = {}
    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs.setdefault(n.name, []).append(n)
    roots: Set[str] = set()
    for name, nodes in defs.items():
        if any(
            _is_jit_decorator(dec) for d in nodes for dec in d.decorator_list
        ):
            roots.add(name)
    for n in ast.walk(tree):
        if isinstance(n, ast.Call) and _jit_construction(n) and n.args:
            a = n.args[0]
            if isinstance(a, ast.Name) and a.id in defs:
                roots.add(a.id)
    seen: Set[str] = set()
    work = list(roots)
    while work:
        name = work.pop()
        if name in seen:
            continue
        seen.add(name)
        for d in defs.get(name, []):
            for sub in ast.walk(d):
                if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name):
                    if sub.func.id in defs and sub.func.id not in seen:
                        work.append(sub.func.id)
    return seen


def _eager_counted_call(sub: ast.AST) -> bool:
    if not isinstance(sub, ast.Call) or not isinstance(sub.func, ast.Attribute):
        return False
    f = sub.func
    if f.attr == "astype":
        # only a jnp-dtype astype is a device dispatch; numpy astypes
        # (host packers) are not the r06 shape
        return (
            bool(sub.args)
            and isinstance(sub.args[0], ast.Attribute)
            and isinstance(sub.args[0].value, ast.Name)
            and sub.args[0].value.id == "jnp"
        )
    root = f.value
    while isinstance(root, ast.Attribute):
        root = root.value
    if isinstance(root, ast.Name) and root.id in ("jnp", "jax", "lax"):
        return f.attr in _EAGER_TRANSFORM_OPS
    return False


_EAGER_BINOPS = (
    ast.BitOr,
    ast.BitAnd,
    ast.BitXor,
    ast.LShift,
    ast.RShift,
    ast.Add,
    ast.Sub,
    ast.Mult,
)


def _eager_score(loop: ast.For) -> int:
    """Unfused element-wise device dispatches per loop iteration:
    jnp/lax transform calls, jnp-dtype ``.astype``, and arithmetic/bit
    operators whose operands contain one (each eager ``|``/``<<``/``+``
    over jax arrays is its own dispatch — the r06 pack-loop shape)."""
    count = 0
    for sub in ast.walk(loop):
        if _eager_counted_call(sub):
            count += 1
        elif isinstance(sub, ast.BinOp) and isinstance(sub.op, _EAGER_BINOPS):
            if any(_eager_counted_call(s) for s in ast.walk(sub)):
                count += 1
        elif isinstance(sub, ast.AugAssign) and isinstance(
            sub.op, _EAGER_BINOPS
        ):
            if any(_eager_counted_call(s) for s in ast.walk(sub.value)):
                count += 1
    return count


class _EagerVisitor(_FunctionStack):
    """EAGER001: eager per-column loops in hot modules (r06 shape)."""

    def __init__(self, path: str, tree: ast.Module):
        super().__init__()
        self.path = path
        self.jit_names = _jit_context_names(tree)
        self.findings: List[LintFinding] = []

    def _in_jit_context(self) -> bool:
        for f in self.stack:
            if f.name in self.jit_names or any(
                _is_jit_decorator(d) for d in f.decorator_list
            ):
                return True
        return False

    def visit_For(self, node: ast.For) -> None:
        if not self._in_jit_context():
            score = _eager_score(node)
            if score >= 2 and _allow_key(self.path, self.current) not in (
                EAGER001_ALLOWED
            ):
                self.findings.append(
                    LintFinding(
                        "EAGER001",
                        self.path,
                        node.lineno,
                        f"eager loop issues {score} unfused jnp element-wise "
                        "dispatches per iteration in a hot module — fuse "
                        "into a module-level jitted kernel (r06 regression "
                        "shape)",
                    )
                )
        self.generic_visit(node)


def _lock_names(tree: ast.Module) -> Set[str]:
    out: Set[str] = set()
    for stmt in tree.body:
        if not (isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Call)):
            continue
        f = stmt.value.func
        attr = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else None
        )
        if attr in ("Lock", "RLock"):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
    return out


def _thread_local_names(tree: ast.Module) -> Set[str]:
    out: Set[str] = set()
    for stmt in tree.body:
        if not (isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Call)):
            continue
        f = stmt.value.func
        if isinstance(f, ast.Attribute) and f.attr == "local":
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    out.add(t.id)
    return out


def _thread_findings(tree: ast.Module, path: str) -> List[LintFinding]:
    """THREAD001 over one module, active only when it defines a worker
    entry (:data:`_WORKER_ENTRY_NAMES`; module-level functions AND
    methods of module-level classes match by bare name).  Walks the
    same-module call graph from each entry — through plain calls and
    through ``ctx.method(...)`` calls on a tracked context — propagating
    which parameters alias the SHARED context (the entry's first
    argument; ``self`` for a method entry), and flags any mutation of
    module-global or shared-context state outside a lock's ``with``
    block or ``threading.local()`` storage.  Recognized guards: a
    module-level ``Lock``/``RLock`` name, or an attribute of the
    tracked context / a module global whose terminal name ends in
    ``lock`` or ``cv`` (``with self._lock:``, ``with ctx._cv:`` — a
    Condition's ``with`` acquires its underlying lock)."""
    defs: Dict[str, ast.AST] = {}
    method_index: Dict[str, List[str]] = {}  # bare method name -> "Cls.m" keys
    for stmt in tree.body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            defs[stmt.name] = stmt
        elif isinstance(stmt, ast.ClassDef):
            for sub in stmt.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    q = f"{stmt.name}.{sub.name}"
                    defs[q] = sub
                    method_index.setdefault(sub.name, []).append(q)
    entries = [
        name
        for name in defs
        if name.rsplit(".", 1)[-1] in _WORKER_ENTRY_NAMES
    ]
    if not entries:
        return []
    module_names = _module_level_names(tree)
    locks = _lock_names(tree)
    tlocals = _thread_local_names(tree)

    def params_of(func: ast.AST) -> List[str]:
        a = func.args
        return [p.arg for p in a.posonlyargs + a.args]

    # reachable functions with the set of parameters aliasing the shared
    # context, to a fixpoint (conservative union across call sites)
    tracked: Dict[str, Set[str]] = {}
    for e in entries:
        ps = params_of(defs[e])
        tracked[e] = {ps[0]} if ps else set()

    def propagate(callee: str, passed: Set[str], work: List[str]) -> None:
        prev = tracked.get(callee)
        if prev is None or not passed <= prev:
            tracked[callee] = (prev or set()) | passed
            work.append(callee)

    work = list(entries)
    while work:
        name = work.pop()
        func = defs[name]
        t = tracked.get(name, set())
        for sub in ast.walk(func):
            if not isinstance(sub, ast.Call):
                continue
            callees: List[Tuple[str, int]] = []  # (def key, self offset)
            if isinstance(sub.func, ast.Name) and sub.func.id in defs:
                callees.append((sub.func.id, 0))
            elif (
                isinstance(sub.func, ast.Attribute)
                and isinstance(sub.func.value, ast.Name)
                and sub.func.value.id in t
            ):
                # ctx.method(...): the receiver IS the shared context —
                # resolve to every same-module class method of that name
                # (conservative when classes share a method name)
                callees.extend(
                    (q, 1) for q in method_index.get(sub.func.attr, ())
                )
            for callee, offset in callees:
                callee_params = params_of(defs[callee])
                passed: Set[str] = set()
                if offset and callee_params:
                    passed.add(callee_params[0])  # receiver binds self
                for i, a in enumerate(sub.args):
                    j = i + offset
                    if (
                        isinstance(a, ast.Name)
                        and a.id in t
                        and j < len(callee_params)
                    ):
                        passed.add(callee_params[j])
                for kw in sub.keywords:
                    if (
                        kw.arg is not None
                        and isinstance(kw.value, ast.Name)
                        and kw.value.id in t
                    ):
                        passed.add(kw.arg)
                propagate(callee, passed, work)

    findings: List[LintFinding] = []
    for name, ctx_params in tracked.items():
        func = defs[name]

        def _is_lock_expr(expr: ast.expr) -> bool:
            if isinstance(expr, ast.Name):
                return expr.id in locks
            if isinstance(expr, ast.Attribute):
                root = _root_name(expr)
                tail = expr.attr
                return root is not None and (
                    root in ctx_params or root in module_names
                ) and (tail.endswith("lock") or tail.endswith("cv"))
            return False

        spans = [
            (w.lineno, getattr(w, "end_lineno", w.lineno))
            for w in ast.walk(func)
            if isinstance(w, ast.With)
            and any(_is_lock_expr(item.context_expr) for item in w.items)
        ]
        g = _declared_globals(func)

        def lock_guarded(line: int) -> bool:
            return any(lo <= line <= hi for lo, hi in spans)

        def flag(line: int, what: str) -> None:
            if _allow_key(path, func) in THREAD001_ALLOWED:
                return
            findings.append(
                LintFinding(
                    "THREAD001",
                    path,
                    line,
                    f"`{name}` is reachable from worker entry "
                    f"`{'/'.join(sorted(entries))}` and {what} outside a "
                    "recognized lock — shared mutable state must be "
                    "lock-guarded or owned by one thread (r07/r08 "
                    "invariant)",
                )
            )

        def check_store_target(t: ast.expr, line: int) -> None:
            if isinstance(t, (ast.Tuple, ast.List)):
                for el in t.elts:
                    check_store_target(el, line)
                return
            if isinstance(t, ast.Name):
                if t.id in g and not lock_guarded(line):
                    flag(line, f"stores module global `{t.id}`")
                return
            if isinstance(t, (ast.Attribute, ast.Subscript)):
                root = _root_name(t)
                if root is None or root in tlocals or lock_guarded(line):
                    return
                if root in ctx_params:
                    flag(line, f"mutates the shared context `{root}`")
                elif root in g or (root in module_names and root not in defs):
                    flag(line, f"mutates module-global `{root}`")

        for sub in ast.walk(func):
            if isinstance(sub, ast.Assign):
                for t in sub.targets:
                    check_store_target(t, sub.lineno)
            elif isinstance(sub, (ast.AugAssign, ast.AnnAssign)):
                check_store_target(sub.target, sub.lineno)
            elif (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr in _MUTATING_METHODS
            ):
                root = _root_name(sub.func)
                if (
                    root is not None
                    and root not in tlocals
                    and not lock_guarded(sub.lineno)
                ):
                    if root in ctx_params:
                        flag(
                            sub.lineno,
                            f"calls `{root}.{sub.func.attr}(...)` on the "
                            "shared context",
                        )
                    elif root in module_names and root not in defs:
                        flag(
                            sub.lineno,
                            f"calls `{root}.{sub.func.attr}(...)` on a "
                            "module global",
                        )
    return findings


def _io_findings(tree: ast.Module, path: str) -> List[LintFinding]:
    """IO001, active only under ``storage/``: a bare ``open()`` with a
    write mode (``w``/``a``/``x``/``+``) in a function that neither
    fsyncs nor publishes via atomic rename leaves a durability hole —
    the data may sit in the page cache when the ack goes out, exactly
    the acked-then-lost window the WAL exists to close.  Write through
    the fsync-then-rename idiom (``wal._open_segment``,
    ``manifest.write_manifest``) or fsync in the same function."""
    if "storage" not in Path(path).parts:
        return []
    findings: List[LintFinding] = []
    for node in ast.walk(tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "open"
        ):
            continue
        mode: Optional[str] = None
        if (
            len(node.args) >= 2
            and isinstance(node.args[1], ast.Constant)
            and isinstance(node.args[1].value, str)
        ):
            mode = node.args[1].value
        for kw in node.keywords:
            if (
                kw.arg == "mode"
                and isinstance(kw.value, ast.Constant)
                and isinstance(kw.value.value, str)
            ):
                mode = kw.value.value
        if mode is None or not any(ch in mode for ch in "wax+"):
            continue
        func = _enclosing_function(tree, node.lineno)
        scope = func if func is not None else tree
        durable = any(
            isinstance(sub, ast.Call)
            and isinstance(sub.func, ast.Attribute)
            and sub.func.attr in ("fsync", "replace", "rename")
            for sub in ast.walk(scope)
        )
        if durable:
            continue
        if _allow_key(path, func) in IO001_ALLOWED:
            continue
        findings.append(
            LintFinding(
                "IO001",
                path,
                node.lineno,
                f"open(..., {mode!r}) writes in storage/ without an "
                "fsync or atomic replace/rename in the enclosing "
                "function — an acked write may sit only in the page "
                "cache (use the fsync-then-rename idiom)",
            )
        )
    return findings


def _lock_identity(
    expr: ast.expr, module_locks: Set[str], class_name: Optional[str],
    stem: str
) -> Optional[str]:
    """A stable identity for a lock-like ``with`` context expression,
    or None when the expression is not lock-like.  Recognition matches
    THREAD001's: a module-level ``Lock``/``RLock`` name, or a name/
    attribute whose terminal name ends in ``lock`` or ``cv``.
    Identities are coarse on purpose — ``Owner.attr`` for attribute
    locks (the enclosing class for ``self``/``cls`` receivers),
    ``module_stem.name`` for module-level names — so the canonical
    order table ranks lock *classes*, not instances."""
    if isinstance(expr, ast.Name):
        if expr.id in module_locks or expr.id.endswith(("lock", "cv")):
            return f"{stem}.{expr.id}"
        return None
    if isinstance(expr, ast.Attribute) and expr.attr.endswith(("lock", "cv")):
        root = _root_name(expr)
        if root in ("self", "cls") and class_name is not None:
            return f"{class_name}.{expr.attr}"
        return f"{root or '?'}.{expr.attr}"
    return None


def _lock_findings(tree: ast.Module, path: str) -> List[LintFinding]:
    """LOCK001: lexically nested acquisition of two recognized locks —
    a ``with <lock>`` inside another ``with <lock>`` span (including two
    lock items in ONE ``with``, acquired left to right) — where the
    ordered ``(outer, inner)`` pair is not in
    :data:`LOCK001_CANONICAL_ORDER`.  Two code paths nesting the same
    pair of locks in opposite orders deadlock under contention; the
    repo-wide rule is one documented order or no nesting at all.  Lock
    registry covered: every module-level ``Lock``/``RLock`` plus the
    ``*lock``/``*cv`` attribute convention — the serve dispatcher,
    storage writer/compactor, views refresh, and obs plane monitors all
    follow it.  Nested ``def``/``lambda`` bodies do not execute under
    the enclosing ``with``, so the held-set resets there."""
    module_locks = _lock_names(tree)
    stem = Path(path).stem
    findings: List[LintFinding] = []

    def flag(outer: str, outer_line: int, inner: str, line: int) -> None:
        func = _enclosing_function(tree, line)
        if _allow_key(path, func) in LOCK001_ALLOWED:
            return
        findings.append(
            LintFinding(
                "LOCK001",
                path,
                line,
                f"acquires `{inner}` while holding `{outer}` (taken at "
                f"line {outer_line}) and the pair is not in the "
                "canonical lock order table "
                "(LOCK001_CANONICAL_ORDER) — nested orders must be "
                "documented once repo-wide or restructured into "
                "sequential lock rounds",
            )
        )

    def visit(node: ast.AST, held, class_name: Optional[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                visit(child, [], class_name)
            elif isinstance(child, ast.ClassDef):
                visit(child, held, child.name)
            elif isinstance(child, (ast.With, ast.AsyncWith)):
                now = list(held)
                for item in child.items:
                    ident = _lock_identity(
                        item.context_expr, module_locks, class_name, stem)
                    if ident is None:
                        continue
                    for outer, outer_line in now:
                        if (outer, ident) not in LOCK001_CANONICAL_ORDER:
                            flag(outer, outer_line, ident, child.lineno)
                    now.append((ident, child.lineno))
                visit(child, now, class_name)
            else:
                visit(child, held, class_name)

    visit(tree, [], None)
    return findings


# ---------------------------------------------------------------------------
# ENV001-R — the configuration registry boundary (ISSUE 20).  Every
# ``os.environ`` read routes through utils/env.py's registered
# accessors, every variable they read is declared in ENV_REGISTRY, and
# the generated docs/ENV.md matches the registry byte-for-byte.  A knob
# that exists only at its read site is invisible to operators and to
# the obs-diff lint snapshots; ~25 CSVPLUS_* vars had scattered reads
# before the registry landed.
# ---------------------------------------------------------------------------

_ENV_ACCESSORS = frozenset({"env_int", "env_str", "env_float"})


def _env_registry_names() -> Optional[frozenset]:
    """Registered variable names from the live registry module, or None
    when it cannot be imported (linting outside the package)."""
    try:
        from ..utils.env import ENV_REGISTRY
    except Exception:
        return None
    return frozenset(ENV_REGISTRY)


def _env_findings(tree: ast.Module, path: str) -> List[LintFinding]:
    """ENV001-R per-file half: direct ``os.environ``/``os.getenv`` reads
    outside utils/env.py, and accessor calls naming an unregistered (or
    non-literal) variable."""
    p = Path(path)
    if p.name == "env.py" and "utils" in p.parts:
        return []  # the one sanctioned os.environ reader
    findings: List[LintFinding] = []
    registry = _env_registry_names()
    direct_lines: Set[int] = set()
    for node in ast.walk(tree):
        if (
            isinstance(node, ast.Attribute)
            and node.attr in ("environ", "getenv")
            and isinstance(node.value, ast.Name)
            and node.value.id == "os"
        ):
            if node.lineno not in direct_lines:
                direct_lines.add(node.lineno)
                findings.append(
                    LintFinding(
                        "ENV001-R",
                        path,
                        node.lineno,
                        f"direct os.{node.attr} read — route through the "
                        "utils/env.py accessors (env_str/env_int/"
                        "env_float) so the variable lands in ENV_REGISTRY "
                        "and docs/ENV.md",
                    )
                )
        elif isinstance(node, ast.Call):
            f = node.func
            fname = None
            if isinstance(f, ast.Name):
                fname = f.id.lstrip("_")
            elif isinstance(f, ast.Attribute):
                fname = f.attr.lstrip("_")
            if fname not in _ENV_ACCESSORS or not node.args:
                continue
            first = node.args[0]
            if not (
                isinstance(first, ast.Constant) and isinstance(first.value, str)
            ):
                findings.append(
                    LintFinding(
                        "ENV001-R",
                        path,
                        node.lineno,
                        f"{fname}(...) takes a computed variable name — "
                        "names must be string literals so registration "
                        "is statically checkable",
                    )
                )
            elif registry is not None and first.value not in registry:
                findings.append(
                    LintFinding(
                        "ENV001-R",
                        path,
                        node.lineno,
                        f"{fname}({first.value!r}) reads a variable not "
                        "declared in utils/env.py ENV_REGISTRY — register "
                        "it (name, kind, default, description)",
                    )
                )
    return findings


def env_global_findings() -> List[LintFinding]:
    """ENV001-R whole-tree half, run once per lint invocation over the
    installed package: stale registry entries (declared but read
    nowhere) and generated-doc drift (committed docs/ENV.md differs
    from ``render_env_md()``)."""
    try:
        from ..utils import env as env_mod
    except Exception:
        return []
    pkg = Path(__file__).resolve().parent.parent
    reg_path = pkg / "utils" / "env.py"
    findings: List[LintFinding] = []
    sources = [
        f.read_text(encoding="utf-8")
        for f in sorted(pkg.rglob("*.py"))
        if f != reg_path
    ]
    for name in env_mod.ENV_REGISTRY:
        quoted = (f'"{name}"', f"'{name}'")
        if not any(q in src for src in sources for q in quoted):
            findings.append(
                LintFinding(
                    "ENV001-R",
                    str(reg_path),
                    1,
                    f"ENV_REGISTRY entry {name} is read nowhere in the "
                    "package — registry drift (remove it or wire the "
                    "read through an accessor)",
                )
            )
    docs = pkg.parent / "docs" / "ENV.md"
    if docs.parent.is_dir():
        rendered = env_mod.render_env_md()
        if not docs.exists():
            findings.append(
                LintFinding(
                    "ENV001-R",
                    str(docs),
                    1,
                    "generated docs/ENV.md is missing — write it with "
                    "`python -m csvplus_tpu.analysis env --write "
                    "docs/ENV.md`",
                )
            )
        elif docs.read_text(encoding="utf-8") != rendered:
            findings.append(
                LintFinding(
                    "ENV001-R",
                    str(docs),
                    1,
                    "docs/ENV.md drifted from utils/env.py ENV_REGISTRY "
                    "— regenerate with `python -m csvplus_tpu.analysis "
                    "env --write docs/ENV.md`",
                )
            )
    return findings


_BROAD_EXCEPT_NAMES = frozenset({"Exception", "BaseException"})


def _enclosing_function(tree: ast.Module, line: int) -> Optional[ast.AST]:
    """The innermost function whose span contains *line*, or None."""
    best: Optional[ast.AST] = None
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            end = getattr(node, "end_lineno", node.lineno)
            if node.lineno <= line <= end and (
                best is None or node.lineno > best.lineno
            ):
                best = node
    return best


def _fault_findings(tree: ast.Module, path: str) -> List[LintFinding]:
    """FAULT001: a broad exception handler — bare ``except``,
    ``except Exception``, ``except BaseException`` (alone or inside a
    tuple) — whose body is nothing but ``pass``/``continue``.  The
    failure is silently swallowed; the reference contract (typed,
    row-annotated, surfaced) forbids that.  Handlers that re-raise,
    wrap, return, log, or count are untouched, as are narrowly-typed
    best-effort catches."""

    def is_broad(h: ast.ExceptHandler) -> bool:
        t = h.type
        if t is None:
            return True
        elts = t.elts if isinstance(t, ast.Tuple) else [t]
        for n in elts:
            if isinstance(n, ast.Name) and n.id in _BROAD_EXCEPT_NAMES:
                return True
            if isinstance(n, ast.Attribute) and n.attr in _BROAD_EXCEPT_NAMES:
                return True
        return False

    findings: List[LintFinding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if not is_broad(node):
            continue
        if not all(isinstance(s, (ast.Pass, ast.Continue)) for s in node.body):
            continue
        func = _enclosing_function(tree, node.lineno)
        if _allow_key(path, func) in FAULT001_ALLOWED:
            continue
        findings.append(
            LintFinding(
                "FAULT001",
                path,
                node.lineno,
                "broad except handler silently swallows the error — "
                "re-raise, wrap via map_error, or record it to "
                "metrics/telemetry (the reference contract surfaces "
                "every failure typed and row-annotated)",
            )
        )
    return findings


def _suppressed(finding: LintFinding, lines: List[str], tree: ast.Module) -> bool:
    marker = f"analysis: allow[{finding.code}]"

    def line_has(idx: int) -> bool:
        return 0 < idx <= len(lines) and marker in lines[idx - 1]

    if line_has(finding.line):
        return True
    # any enclosing def line (a flagged closure inherits its outer
    # function's acknowledgment)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            end = getattr(node, "end_lineno", node.lineno)
            if node.lineno <= finding.line <= end and line_has(node.lineno):
                return True
    return False


def lint_source(
    source: str,
    path: str = "<string>",
    matched_out=None,
) -> List[LintFinding]:
    """All unsuppressed findings for one module's source text.
    *matched_out* (a set, whole-tree lint only) accumulates the
    jitlint allowlist keys this file's sync sites matched, feeding the
    global staleness check."""
    tree = ast.parse(source, filename=path)
    findings: List[LintFinding] = []
    positions = _c_char_positions(tree)
    if positions:
        v = _CtypesVisitor(positions, path)
        v.visit(tree)
        findings.extend(v.findings)
    j = _JitVisitor(path)
    j.visit(tree)
    findings.extend(j.findings)
    t = _TraceVisitor(path, tree)
    t.visit(tree)
    findings.extend(t.findings)
    if _is_hot_module(path):
        e = _EagerVisitor(path, tree)
        e.visit(tree)
        findings.extend(e.findings)
    findings.extend(_thread_findings(tree, path))
    findings.extend(_lock_findings(tree, path))
    findings.extend(_fault_findings(tree, path))
    findings.extend(_io_findings(tree, path))
    findings.extend(_env_findings(tree, path))
    from .jitlint import jitlint_findings  # late: jitlint imports us

    findings.extend(jitlint_findings(tree, path, matched_out))
    lines = source.splitlines()
    findings = [f for f in findings if not _suppressed(f, lines, tree)]
    findings.sort(key=lambda f: (f.path, f.line, f.code))
    return findings


def lint_file(path, matched_out=None) -> List[LintFinding]:
    p = Path(path)
    return lint_source(p.read_text(encoding="utf-8"), str(p), matched_out)


def lint_paths(paths: Iterable, global_checks: bool = False) -> List[LintFinding]:
    """Lint every ``.py`` file under each path (file or directory).
    With *global_checks* (the whole-package lint run), the cross-file
    checks run once on top: the ENV001-R registry/doc drift checks and
    the jitlint allowlist staleness check (per-file lints cannot tell
    a stale allowance from a site they are not looking at)."""
    matched: set = set()
    findings: List[LintFinding] = []
    for path in paths:
        p = Path(path)
        files = sorted(p.rglob("*.py")) if p.is_dir() else [p]
        for f in files:
            findings.extend(lint_file(f, matched))
    if global_checks:
        from .jitlint import allowlist_global_findings

        findings.extend(env_global_findings())
        findings.extend(allowlist_global_findings(matched))
        findings.sort(key=lambda f: (f.path, f.line, f.code))
    return findings
