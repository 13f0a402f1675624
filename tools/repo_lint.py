#!/usr/bin/env python
"""Repo-specific AST lint — the rules generic linters cannot know.

Rules (see docs/checking.md for the catalog):

* ``EXPR-EQ`` / ``EXPR-NE`` — Python ``==`` / ``!=`` on expression-AST
  values.  ``Expr.__eq__`` BUILDS an ``EqualsExpr`` comparison node
  (that is the DSL), so boolean comparison of two Expr objects is
  always a bug outside ``compiler/expr.py`` itself — use ``.same()``
  for structural identity or compare ``.skey()`` strings.
* ``EXPR-KEY`` — expression nodes used as dict keys / subscripts.
  With ``__eq__`` overloaded, dict lookup degenerates to identity-ish
  hash behavior; key memo tables by ``.skey()`` instead.
* ``MESH-DIRECT`` — ``Mesh(...)`` construction outside the single
  factory (``yask_tpu/parallel/mesh.py``, ``make_mesh``).  The mesh is
  where the backend becomes config (device list + axis map): scattered
  constructions fork that decision and break the multi-host launch
  path, which hands a ``jax.distributed`` global device list to the
  one factory.
* ``COMPILE-DIRECT`` — a chained ``.lower(...).compile()`` executable
  build, or a ``jax.experimental.serialize_executable`` import,
  outside ``yask_tpu/cache/``.  Every executable must be built through
  the one chokepoint (``yask_tpu.cache.aot_compile``): it owns the
  trace counter, the compile-time accounting, and the persistent
  on-disk cache — a bypassed build silently loses all three.
  Detection is the chain (receiver of ``.compile()`` is itself a
  ``.lower(...)`` call), so ``str.lower()`` and the front-end's
  ``yc_solution.compile(dtype=...)`` never false-positive.
* ``BARE-DEVICE-CALL`` — device WORK (``run_solution`` /
  ``block_until_ready`` / ``compare_data`` / ``run_auto_tuner_now``)
  in a driver artifact (``tools/*.py``) outside any
  resilience guard.  A backend that dies mid-run hangs such a call
  with nothing to kill it; driver tools must route device work through
  ``guarded_call`` / ``run_deadlined``.  Sanctioning is a
  transitive call-graph closure from the functions passed into those
  invokers, so helpers like ``measure`` stay clean without pragmas.
  Library code (``yask_tpu/``) is out of scope — the rule is about
  unattended driver artifacts, not the API.
* ``CKPT-UNGUARDED`` — checkpoint I/O (``save_checkpoint`` /
  ``load_checkpoint`` / ``restore_checkpoint``) in a driver artifact
  outside any resilience guard.  Same mechanics and scope as
  ``BARE-DEVICE-CALL``: a checkpoint save pulls device state to host
  (a device hang can strand it) and its fault-injection sites
  (``ckpt.save`` / ``ckpt.restore``) only classify when the call runs
  under ``guarded_call``; new run-loops that write checkpoints must
  route them through a guard.
* ``TRACE-ID`` — a JSONL append site (a function with an append-mode
  ``open`` plus a ``json.dumps``) that never references
  ``stamp_trace`` / ``trace_id``.  Every journal-style row
  must be joinable against TRACE_EVENTS.jsonl when a trace is active
  (``yask_tpu/obs/tracer.py``); a new appender that forgets the stamp
  silently drops its artifact out of the end-to-end correlation
  spine.  Out of scope in ``tests/`` (fixture writers); the tracer's
  own row writer is pragma'd — it IS the trace.
* ``PHASE-SITE`` — a ``guarded_call``/``fault_point``/``maybe_corrupt``
  site id that falls through ``phase_for_site``'s prefix table to the
  default ``"guard"`` phase.  Guard spans are named after their sites,
  so an unmapped site dumps its time into the catch-all bucket of
  every obs_report phase breakdown instead of the phase it
  belongs to; new device-facing sites must either match an existing
  prefix or extend ``_SITE_PHASES`` (``yask_tpu/obs/tracer.py``) —
  that is the drift this rule pins.  Lexically-resolvable ids only
  (string literals and f-string prefixes); out of scope in ``tests/``
  (throwaway unit-test sites).

* ``CAP-CONST`` — a raw backend-legality literal (lane-tile ``128``,
  a sublane alignment ``% 8`` / ``// 8`` (or 16/32), a
  sublane-by-itemsize dict map, or a constant-MiB VMEM byte value
  ``N * 2**20``) re-appearing in the modules that must read those
  facts from the backend capability table
  (``yask_tpu/backend/capability.py``): VarGeom/lowering, the tile
  planner, the pallas build, and the checker passes.  A re-baked
  constant is exactly the drift the table exists to kill — the static
  checker would keep modeling a rule the runtime no longer enforces
  (or vice versa).  Go through ``get_capability()`` /
  ``tpu_tile_dims`` / ``sublane_count`` / ``vmem_limit_bytes``
  instead.  Dict KEYS are exempt (itemsize→dtype maps key on element
  bytes, which is data, not a layout fact).
* ``LEDGER-WRITE`` — the string ``PERF_LEDGER`` in a source file
  outside ``benchmark/`` and ``tests/``.  ``PERF_LEDGER.jsonl`` is the
  driver's record of speed and has one writer, the driver, from what
  ``benchmark/run.py`` prints; the program emits spans, counters,
  ``get_stats()`` and ``srv.metrics()`` and writes no perf record.  A
  path to that file in the program or its tools is a second writer
  (or a reader that plans from rows the driver did not write) in the
  making.

Detection of "an Expr value" is lexical (this is a linter, not a type
checker): names ``expr``/``lhs``/``rhs``/``eq``, the ``*_expr``
suffix, and attribute access ``.lhs`` / ``.rhs``.  Escape hatch: put
``# lint: <rule>-ok`` on the flagged line (rule tokens: ``expr-eq``,
``expr-key``, ``devices``, ``mesh``, ``compile-direct``,
``bare-device-call``, ``ckpt-unguarded``, ``trace-id``,
``phase-site``, ``cap-const``; ``LEDGER-WRITE`` has none).

Usage: ``python tools/repo_lint.py [paths...]`` — defaults to the
repo root; exit 1 when anything fires.
"""

from __future__ import annotations

import ast
import json
import os
import sys
from typing import List, Optional

# the PHASE-SITE rule imports the REAL phase table (drift check)
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SKIP_DIRS = {".git", "__pycache__", ".claude",
             ".pytest_cache", "build",
             # git-ignored chip-tool scratch: probes, an unpacked
             # `git archive` of the tree, what a chip call brought back
             ".chipwork", "chiprun_out", ".jax_cache"}
# expr.py defines the overloaded operators — == is the DSL there
EXPR_RULE_EXEMPT = {os.path.join("yask_tpu", "compiler", "expr.py")}
# mesh.py hosts make_mesh — THE sanctioned Mesh construction site
MESH_RULE_EXEMPT = {os.path.join("yask_tpu", "parallel", "mesh.py")}
# yask_tpu/cache/ hosts aot_compile — THE sanctioned executable-build
# and executable-(de)serialization site
COMPILE_RULE_EXEMPT_DIR = os.path.join("yask_tpu", "cache") + os.sep

# this file names the string it forbids
LEDGER_RULE_EXEMPT = {os.path.join("tools", "repo_lint.py")}
_LEDGER_NAME = "PERF_LEDGER"

_SUSPECT_NAMES = {"expr", "lhs", "rhs", "eq"}
_SUSPECT_ATTRS = {"lhs", "rhs"}

# ---- BARE-DEVICE-CALL ----------------------------------------------------
#: methods/functions that put work on the device (and therefore hang
#: when the backend dies mid-run)
_DEVICE_WORK = {"run_solution", "block_until_ready", "compare_data",
                "run_auto_tuner_now"}
#: resilience entry points: a function passed (by name, or as a
#: ``factory(...)`` call) into one of these runs under a deadline /
#: classified-fault guard, and so does everything it calls
_GUARD_INVOKERS = {"guarded_call", "run_deadlined"}
#: checkpoint I/O in a driver artifact needs the same guarding as
#: device work: the save pulls device state to host, and the
#: ckpt.save/ckpt.restore injection sites only classify under a guard
_CKPT_WORK = {"save_checkpoint", "load_checkpoint",
              "restore_checkpoint"}


def _device_rule_in_scope(relpath: str) -> bool:
    """Driver artifacts plus the serving layer: the tools/ scripts
    run unattended against the device, and
    yask_tpu/serve/ answers tenants long after any human is watching
    — both must reach device work only through a guard.  Other
    library code is exercised under the callers' guards."""
    return (relpath.startswith("tools" + os.sep)
            or relpath.startswith(
                os.path.join("yask_tpu", "serve") + os.sep))


def _is_expr_operand(node: ast.AST) -> bool:
    if isinstance(node, ast.Name):
        n = node.id
        return n in _SUSPECT_NAMES or n.endswith("_expr")
    if isinstance(node, ast.Attribute):
        return node.attr in _SUSPECT_ATTRS or node.attr.endswith("_expr")
    return False


def _is_compile_chain(node: ast.Call) -> bool:
    """``<anything>.lower(...).compile(...)`` — the receiver of
    ``.compile`` is itself a ``.lower(...)`` call.  Chain detection is
    what keeps ``"x".lower()`` and ``yc_solution.compile(dtype=...)``
    out: neither is both links at once."""
    f = node.func
    return (isinstance(f, ast.Attribute) and f.attr == "compile"
            and isinstance(f.value, ast.Call)
            and isinstance(f.value.func, ast.Attribute)
            and f.value.func.attr == "lower")


def _is_mesh_ctor(node: ast.Call) -> bool:
    """``Mesh(...)`` / ``jax.sharding.Mesh(...)`` — lexical, like every
    rule here; names ending in ``Mesh`` other than the jax class are
    not flagged."""
    f = node.func
    if isinstance(f, ast.Name):
        return f.id == "Mesh"
    if isinstance(f, ast.Attribute):
        return f.attr == "Mesh"
    return False


class _Linter(ast.NodeVisitor):
    def __init__(self, relpath: str, lines: List[str]):
        self.relpath = relpath
        self.lines = lines
        self.findings: List[dict] = []

    def _pragma(self, lineno: int, token: str) -> bool:
        line = self.lines[lineno - 1] if lineno - 1 < len(self.lines) else ""
        return f"# lint: {token}-ok" in line

    def _add(self, rule: str, node: ast.AST, msg: str) -> None:
        self.findings.append({"rule": rule, "path": self.relpath,
                              "line": node.lineno, "message": msg})

    # ---- rules ----------------------------------------------------------
    def visit_Compare(self, node: ast.Compare):
        if self.relpath not in EXPR_RULE_EXEMPT:
            operands = [node.left] + list(node.comparators)
            for op in node.ops:
                if isinstance(op, (ast.Eq, ast.NotEq)):
                    hit = next((o for o in operands
                                if _is_expr_operand(o)), None)
                    if hit is not None and not self._pragma(
                            node.lineno, "expr-eq"):
                        rule = ("EXPR-EQ" if isinstance(op, ast.Eq)
                                else "EXPR-NE")
                        self._add(
                            rule, node,
                            f"Python {'==' if rule == 'EXPR-EQ' else '!='} "
                            "on an expression node builds an AST "
                            "comparison, not a bool — use .same() / "
                            ".skey()")
                        break
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript):
        if (self.relpath not in EXPR_RULE_EXEMPT
                and _is_expr_operand(node.slice)
                and not self._pragma(node.lineno, "expr-key")):
            self._add("EXPR-KEY", node,
                      "expression node used as a dict/table key — "
                      "__eq__ is overloaded; key by .skey()")
        self.generic_visit(node)

    def visit_Dict(self, node: ast.Dict):
        if self.relpath not in EXPR_RULE_EXEMPT:
            for k in node.keys:
                if k is not None and _is_expr_operand(k) \
                        and not self._pragma(k.lineno, "expr-key"):
                    self._add("EXPR-KEY", k,
                              "expression node used as a dict key — "
                              "__eq__ is overloaded; key by .skey()")
        self.generic_visit(node)

    def _import_hits_serialize(self, names) -> bool:
        return any("serialize_executable" in (n or "") for n in names)

    def visit_Import(self, node: ast.Import):
        if (self._import_hits_serialize(a.name for a in node.names)
                and not self.relpath.startswith(COMPILE_RULE_EXEMPT_DIR)
                and not self._pragma(node.lineno, "compile-direct")):
            self._add(
                "COMPILE-DIRECT", node,
                "executable (de)serialization outside yask_tpu/cache/ "
                "— cache entries are written/read only by the "
                "aot_compile chokepoint")
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom):
        names = [node.module or ""] + [a.name for a in node.names]
        if (self._import_hits_serialize(names)
                and not self.relpath.startswith(COMPILE_RULE_EXEMPT_DIR)
                and not self._pragma(node.lineno, "compile-direct")):
            self._add(
                "COMPILE-DIRECT", node,
                "executable (de)serialization outside yask_tpu/cache/ "
                "— cache entries are written/read only by the "
                "aot_compile chokepoint")
        self.generic_visit(node)

    def visit_Call(self, node: ast.Call):
        if (_is_compile_chain(node)
                and not self.relpath.startswith(COMPILE_RULE_EXEMPT_DIR)
                and not self._pragma(node.lineno, "compile-direct")
                and not self._pragma(getattr(node, "end_lineno",
                                             node.lineno),
                                     "compile-direct")):
            self._add(
                "COMPILE-DIRECT", node,
                "chained .lower().compile() executable build outside "
                "yask_tpu/cache/ — route through "
                "yask_tpu.cache.aot_compile (trace counter, compile "
                "accounting, and the persistent cache all live there)")
        if (_is_mesh_ctor(node)
                and self.relpath not in MESH_RULE_EXEMPT
                and not self._pragma(node.lineno, "mesh")):
            self._add(
                "MESH-DIRECT", node,
                "direct Mesh(...) construction outside the "
                "parallel.mesh.make_mesh factory — the mesh is config "
                "(device list + axis map), and forking its construction "
                "breaks the multi-host launch path; call make_mesh")
        self.generic_visit(node)


def _call_name(node: ast.Call) -> Optional[str]:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


class _DeviceCallPass(ast.NodeVisitor):
    """Two-phase BARE-DEVICE-CALL check: collect the module call graph,
    the guard roots (names passed into guard invokers), and every
    device-work call site; then sanction sites whose lexically
    enclosing function is reachable from a root through the call
    graph.  Lexical and name-based — a linter, not a type checker —
    but that is exactly how the driver tools are shaped (nested
    closures handed to ``guarded_call``)."""

    def __init__(self, work=None):
        self.work = work if work is not None else _DEVICE_WORK
        self.calls: dict = {}      # enclosing func name -> called names
        self.roots: set = set()    # names passed into guard invokers
        self.sites: List[tuple] = []   # (node, enclosing-func stack)
        self._stack: List[str] = []

    def visit_FunctionDef(self, node):
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node: ast.Call):
        name = _call_name(node)
        if name:
            if self._stack:
                self.calls.setdefault(self._stack[-1], set()).add(name)
            if name in _GUARD_INVOKERS:
                for a in list(node.args) + [kw.value
                                            for kw in node.keywords]:
                    if isinstance(a, ast.Name):
                        self.roots.add(a.id)
                    elif (isinstance(a, ast.Call)
                          and isinstance(a.func, ast.Name)):
                        # body factory: guarded_call(make_body(...))
                        # — the factory's nested body runs guarded
                        self.roots.add(a.func.id)
            if name in self.work:
                self.sites.append((node, tuple(self._stack)))
        self.generic_visit(node)

    def guarded_funcs(self) -> set:
        guarded = set(self.roots)
        changed = True
        while changed:
            changed = False
            for f in list(guarded):
                for g in self.calls.get(f, ()):
                    if g not in guarded:
                        guarded.add(g)
                        changed = True
        return guarded


def _lint_guarded_work(tree: ast.AST, relpath: str, lines: List[str],
                       work, rule: str, pragma: str,
                       message: str) -> List[dict]:
    """Shared reachability check behind BARE-DEVICE-CALL and
    CKPT-UNGUARDED: flag direct ``work`` invocations whose enclosing
    function is not reachable from any guard root."""
    p = _DeviceCallPass(work=work)
    p.visit(tree)
    guarded = p.guarded_funcs()
    findings = []
    for node, stack in p.sites:
        if any(f in guarded for f in stack):
            continue
        line = (lines[node.lineno - 1]
                if node.lineno - 1 < len(lines) else "")
        if f"# lint: {pragma}-ok" in line:
            continue
        findings.append({
            "rule": rule, "path": relpath, "line": node.lineno,
            "message": f"{message.format(name=_call_name(node))}"})
    return findings


def _lint_device_calls(tree: ast.AST, relpath: str,
                       lines: List[str]) -> List[dict]:
    findings = _lint_guarded_work(
        tree, relpath, lines, _DEVICE_WORK, "BARE-DEVICE-CALL",
        "bare-device-call",
        "device work ({name}) in a driver artifact outside any "
        "resilience guard — a dying backend hangs it with nothing to "
        "kill it; route through guarded_call/run_deadlined, or "
        "pragma a deliberate exception")
    findings.extend(_lint_guarded_work(
        tree, relpath, lines, _CKPT_WORK, "CKPT-UNGUARDED",
        "ckpt-unguarded",
        "checkpoint I/O ({name}) in a driver artifact outside any "
        "resilience guard — the ckpt.save/ckpt.restore fault sites "
        "only classify under guarded_call; route the save/restore "
        "through a guard, or pragma a deliberate exception"))
    return findings


# ---- TRACE-ID ------------------------------------------------------------
_TRACE_REFS = {"stamp_trace", "trace_id"}


def _trace_rule_in_scope(relpath: str) -> bool:
    """Everything but tests/ — test fixtures legitimately write raw
    JSONL; production journal appenders must stamp."""
    return not relpath.startswith("tests" + os.sep)


def _is_append_open(node: ast.Call) -> bool:
    if not (isinstance(node.func, ast.Name) and node.func.id == "open"):
        return False
    mode = None
    if len(node.args) >= 2:
        mode = node.args[1]
    for kw in node.keywords:
        if kw.arg == "mode":
            mode = kw.value
    return (isinstance(mode, ast.Constant)
            and isinstance(mode.value, str)
            and mode.value.startswith("a"))


def _shallow_nodes(scope: ast.AST):
    """The nodes of one function (or module) body WITHOUT descending
    into nested function scopes — each scope answers for its own
    append sites."""
    stack = list(ast.iter_child_nodes(scope))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
            stack.extend(ast.iter_child_nodes(n))


def _lint_trace_id(tree: ast.AST, relpath: str,
                   lines: List[str]) -> List[dict]:
    """Flag JSONL append sites (append-mode ``open`` + ``json.dumps``
    in one scope) with no ``stamp_trace`` / ``trace_id`` reference."""
    findings = []
    scopes = [tree] + [n for n in ast.walk(tree)
                       if isinstance(n, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))]
    for scope in scopes:
        opens: List[ast.Call] = []
        has_dumps = False
        has_ref = False
        for n in _shallow_nodes(scope):
            if isinstance(n, ast.Call):
                if _is_append_open(n):
                    opens.append(n)
                elif _call_name(n) == "dumps":
                    has_dumps = True
                has_ref = has_ref or any(
                    kw.arg in _TRACE_REFS for kw in n.keywords)
            elif isinstance(n, ast.Name) and n.id in _TRACE_REFS:
                has_ref = True
            elif isinstance(n, ast.Attribute) and n.attr in _TRACE_REFS:
                has_ref = True
            elif isinstance(n, ast.Constant) and n.value == "trace_id":
                has_ref = True
            elif isinstance(n, ast.alias) and n.name in _TRACE_REFS:
                has_ref = True
        if not opens or not has_dumps or has_ref:
            continue
        for node in opens:
            line = (lines[node.lineno - 1]
                    if node.lineno - 1 < len(lines) else "")
            if "# lint: trace-id-ok" in line:
                continue
            findings.append({
                "rule": "TRACE-ID", "path": relpath,
                "line": node.lineno,
                "message": "JSONL append site without a stamp_trace/"
                           "trace_id reference — rows written here "
                           "cannot join TRACE_EVENTS.jsonl; call "
                           "yask_tpu.obs.tracer.stamp_trace(row) (or "
                           "pragma a deliberately untraced artifact)"})
    return findings


# ---- PHASE-SITE ----------------------------------------------------------
#: calls whose first positional argument IS a site id
_SITE_CALLS = {"fault_point", "maybe_corrupt"}


def _phase_site_in_scope(relpath: str) -> bool:
    """Everything but tests/ — unit tests mint throwaway site ids;
    production sites must land in a real phase bucket."""
    return not relpath.startswith("tests" + os.sep)


def _site_literal(node: ast.AST) -> Optional[str]:
    """The lexically resolvable site id: a string constant, or the
    leading constant of an f-string (``phase_for_site`` matches on
    prefixes, so the static head of ``f"suite.{name}"`` resolves the
    same as the full id).  None = dynamic, not checkable here."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr) and node.values:
        first = node.values[0]
        if isinstance(first, ast.Constant) \
                and isinstance(first.value, str):
            return first.value
    return None


def _lint_phase_sites(tree: ast.AST, relpath: str,
                      lines: List[str]) -> List[dict]:
    """Flag site ids that resolve to the default "guard" phase — the
    prefix-table drift check (the REAL ``phase_for_site`` is imported,
    so the rule and the runtime can never disagree)."""
    from yask_tpu.obs.tracer import phase_for_site
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        site = None
        if name in _SITE_CALLS and node.args:
            site = _site_literal(node.args[0])
        elif name == "guarded_call":
            for kw in node.keywords:
                if kw.arg == "site":
                    site = _site_literal(kw.value)
        if site is None or phase_for_site(site) != "guard":
            continue
        line = (lines[node.lineno - 1]
                if node.lineno - 1 < len(lines) else "")
        if "# lint: phase-site-ok" in line:
            continue
        findings.append({
            "rule": "PHASE-SITE", "path": relpath, "line": node.lineno,
            "message": f"site {site!r} falls through phase_for_site to "
                       "the default 'guard' phase — its span time lands "
                       "in the catch-all bucket of every breakdown; "
                       "match an existing prefix or extend _SITE_PHASES "
                       "(yask_tpu/obs/tracer.py), or pragma a "
                       "deliberately unphased site"})
    return findings


# ---- CAP-CONST -----------------------------------------------------------
#: the lane-tile extent — unmistakable wherever it appears in scope
_CAP_LANE = 128
#: sublane fold/tile extents by dtype — only flagged in alignment
#: arithmetic (``x % 8`` / ``x // 8``) and itemsize→sublane dict maps,
#: where they are layout facts; a bare ``8`` elsewhere is usually a
#: loop bound or heuristic and stays legal
_CAP_SUBLANES = {8, 16, 32}
_MIB = 2 ** 20


def _cap_const_in_scope(relpath: str) -> bool:
    """The single-source-of-truth perimeter: geometry (VarGeom/
    lowering), the planner, the pallas build, and the checker —
    everything that would let the static model and the runtime drift if
    they each kept a private copy of the probed rules.  The capability
    table itself is the sanctioned home."""
    if relpath.startswith(os.path.join("yask_tpu", "backend") + os.sep):
        return False
    return (relpath in (os.path.join("yask_tpu", "compiler",
                                     "lowering.py"),
                        os.path.join("yask_tpu", "ops",
                                     "tile_planner.py"),
                        os.path.join("yask_tpu", "ops",
                                     "pallas_stencil.py"))
            or relpath.startswith(
                os.path.join("yask_tpu", "checker") + os.sep))


def _is_mib_pow(node: ast.AST) -> bool:
    """``2 ** 20`` or the literal 1048576."""
    if isinstance(node, ast.Constant) and node.value == _MIB:
        return True
    return (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Pow)
            and isinstance(node.left, ast.Constant)
            and node.left.value == 2
            and isinstance(node.right, ast.Constant)
            and node.right.value == 20)


def _lint_cap_consts(tree: ast.AST, relpath: str,
                     lines: List[str]) -> List[dict]:
    findings = []
    # dict KEYS are exempt: itemsize→dtype maps key on element bytes
    dict_keys = set()
    for n in ast.walk(tree):
        if isinstance(n, ast.Dict):
            for k in n.keys:
                if k is not None:
                    dict_keys.add(id(k))

    def _add(node, what: str) -> None:
        line = (lines[node.lineno - 1]
                if node.lineno - 1 < len(lines) else "")
        if "# lint: cap-const-ok" in line:
            return
        findings.append({
            "rule": "CAP-CONST", "path": relpath, "line": node.lineno,
            "message": f"{what} — backend legality facts live in "
                       "yask_tpu/backend/capability.py; read them "
                       "through get_capability()/tpu_tile_dims/"
                       "sublane_count/vmem_limit_bytes (or pragma a "
                       "genuinely backend-independent constant)"})

    for n in ast.walk(tree):
        if (isinstance(n, ast.Constant) and n.value == _CAP_LANE
                and id(n) not in dict_keys):
            _add(n, f"raw lane-tile literal {_CAP_LANE}")
        elif isinstance(n, ast.BinOp):
            if (isinstance(n.op, (ast.Mod, ast.FloorDiv))
                    and isinstance(n.right, ast.Constant)
                    and n.right.value in _CAP_SUBLANES):
                _add(n, f"sublane alignment arithmetic on raw "
                        f"{n.right.value}")
            elif isinstance(n.op, ast.Mult):
                for a, b in ((n.left, n.right), (n.right, n.left)):
                    if (isinstance(a, ast.Constant)
                            and isinstance(a.value, int)
                            and _is_mib_pow(b)):
                        _add(n, f"constant VMEM byte value "
                                f"{a.value} MiB")
                        break
        elif isinstance(n, ast.Dict):
            subs = [v for v in n.values
                    if isinstance(v, ast.Constant)
                    and v.value in _CAP_SUBLANES]
            if len(subs) >= 2:
                _add(n, "itemsize→sublane dict map")
    return findings


# ---- LEDGER-WRITE --------------------------------------------------------
def _ledger_rule_in_scope(relpath: str) -> bool:
    return not (relpath.startswith(("benchmark" + os.sep,
                                    "tests" + os.sep))
                or relpath in LEDGER_RULE_EXEMPT)


def _lint_ledger_name(relpath: str, lines: List[str]) -> List[dict]:
    return [{"rule": "LEDGER-WRITE", "path": relpath, "line": i,
             "message": f"{_LEDGER_NAME} named outside benchmark/ and "
                        "tests/ — the driver's file has one writer; "
                        "emit spans/counters/stats and let "
                        "benchmark/run.py measure"}
            for i, line in enumerate(lines, 1)
            if _LEDGER_NAME in line]


def lint_file(path: str, root: str) -> List[dict]:
    relpath = os.path.relpath(path, root)
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [{"rule": "PARSE-ERROR", "path": relpath,
                 "line": e.lineno or 0, "message": str(e.msg)}]
    lines = src.splitlines()
    linter = _Linter(relpath, lines)
    linter.visit(tree)
    findings = linter.findings
    if _device_rule_in_scope(relpath):
        findings.extend(_lint_device_calls(tree, relpath, lines))
    if _trace_rule_in_scope(relpath):
        findings.extend(_lint_trace_id(tree, relpath, lines))
    if _phase_site_in_scope(relpath):
        findings.extend(_lint_phase_sites(tree, relpath, lines))
    if _cap_const_in_scope(relpath):
        findings.extend(_lint_cap_consts(tree, relpath, lines))
    if _ledger_rule_in_scope(relpath):
        findings.extend(_lint_ledger_name(relpath, lines))
    return findings


def iter_py_files(paths: List[str], root: str):
    for p in paths:
        if os.path.isfile(p):
            if p.endswith(".py"):
                yield p
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [d for d in dirnames if d not in SKIP_DIRS]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def run_lint(paths: Optional[List[str]] = None,
             root: Optional[str] = None) -> List[dict]:
    root = root or os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = paths or [root]
    findings: List[dict] = []
    for path in iter_py_files(paths, root):
        findings.extend(lint_file(path, root))
    return findings


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    as_json = "--json" in argv
    argv = [a for a in argv if a != "--json"]
    findings = run_lint(argv or None)
    if as_json:
        print(json.dumps(findings, indent=2))
    else:
        for f in findings:
            print(f"{f['path']}:{f['line']}: {f['rule']}: {f['message']}")
        print(f"repo_lint: {len(findings)} finding(s)")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
