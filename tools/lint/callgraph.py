"""Repo-wide call graph for lodelint's interprocedural rules.

Two layers:

* **Extraction** (`extract_summary`) — one pass over a module's AST
  producing a JSON-serializable ``ModuleSummary``: every function
  (module-level, methods, nested defs) with its raw call references and
  direct effect set, the import table (aliases and relative imports
  resolved to absolute dotted paths), per-class instance-attribute type
  candidates, and protocol/base-class shape.  Summaries are what the
  mtime-keyed cache stores (see effects.SummaryCache), so an unchanged
  file contributes to the graph without being re-parsed.

* **Resolution** (`Project`) — links summaries into a graph of
  ``module:qualname -> [Edge]``.  Resolution is deliberately static and
  conservative:

    - bare names walk the lexical scope chain (nested defs first), then
      module functions/classes, then the import table;
    - ``self.method()`` dispatches through the enclosing class's MRO
      (base classes resolved across modules);
    - attribute chains (``self.db.block.put``) walk inferred instance
      attribute types class by class;
    - a call on a Protocol-typed value fans out to every concrete
      project class that implements the protocol's full method set —
      this is how ``Repository.put`` reaches both MemoryController and
      SqliteController.

  Anything unresolvable simply contributes no edge: the analysis
  under-approximates reachability, so interprocedural findings are
  backed by a concrete, reportable chain rather than guesswork.
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .core import annotate_parents, dotted_name, enclosing_loop, parse_suppressions, unparse
from .effects import direct_effects, module_effect_context

# --- v3 whole-program vocabulary (rules_program.py consumes these) ---------
# the bucket-quantizer functions of ops/bls12_381/buckets.py: a width that
# flows through one of these is provably an AOT compile rung
QUANT_FUNCS = {"bucket_size", "pool_bucket", "align_down"}
# prometheus metric constructors (canonical, import-resolved)
_PROM_TYPES = {
    f"prometheus_client.{t}" for t in ("Counter", "Gauge", "Histogram", "Summary")
}
_METRIC_OPS = {"inc", "dec", "observe", "set"}
# identifier segments that name a jit-program batch width.  Locals match
# the full set; parameter seeding (rules_program) deliberately uses only
# bucket|width — `size` params are everywhere in SSZ code and are not on
# the dispatch path.
WIDTH_LOCAL_RE = re.compile(r"(?:^|_)(size|bucket|width)(?:_|$)")
WIDTH_PARAM_RE = re.compile(r"(?:^|_)(bucket|width)(?:_|$)")

# --- v5 shard/collective vocabulary (rules_shard.py consumes these) --------
# jax.lax collectives whose axis-name argument must be bound by an
# enclosing shard_map/pmap (collective-axis)
COLLECTIVE_FUNCS = {
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "psum_scatter", "ppermute", "axis_index",
}
# the subset that actually moves data across the axis — an out_specs=P()
# (replicated) shard_map output must derive from one of these
CROSS_AXIS_FUNCS = COLLECTIVE_FUNCS - {"axis_index"}
_MESH_CTORS = {"Mesh", "AbstractMesh", "make_mesh"}
# docstring contract: `@mesh: sp` / `@mesh: dp, tp` names the axis set a
# mesh-parameterized function is written against (the static analogue of
# the Mesh(...) construction the decorator's `mesh=` kwarg can't see)
_MESH_CONTRACT_RE = re.compile(r"@mesh:\s*([A-Za-z_][A-Za-z0-9_]*(?:\s*,\s*[A-Za-z_][A-Za-z0-9_]*)*)")


def parse_mesh_contract(doc: Optional[str]) -> List[str]:
    """Axis names declared by a ``@mesh:`` docstring line, or []."""
    if not doc:
        return []
    m = _MESH_CONTRACT_RE.search(doc)
    if not m:
        return []
    return [a.strip() for a in m.group(1).split(",") if a.strip()]


def _mesh_axes_of(node) -> Optional[List[str]]:
    """Axis names of a ``Mesh(devices, ("sp",))`` / ``make_mesh(...,
    axis_names=...)`` construction when they are static string literals."""
    if not isinstance(node, ast.Call):
        return None
    name = (dotted_name(node.func) or "").rsplit(".", 1)[-1]
    if name not in _MESH_CTORS:
        return None
    axis_arg = node.args[1] if len(node.args) >= 2 else None
    for kw in node.keywords:
        if kw.arg == "axis_names":
            axis_arg = kw.value
    if axis_arg is None:
        return None
    if isinstance(axis_arg, ast.Constant) and isinstance(axis_arg.value, str):
        return [axis_arg.value]
    return _label_list(axis_arg)

# call wrappers that schedule/await the coroutine they are handed — a
# known-async call inside one of these is NOT an unawaited coroutine
_CORO_WRAPPERS = {
    "create_task",
    "ensure_future",
    "gather",
    "wait",
    "wait_for",
    "shield",
    "run",
    "run_until_complete",
    "run_coroutine_threadsafe",
    "as_completed",
    "timeout",
    "Task",
}


def module_name_for(path: str) -> str:
    """Repo-relative path -> dotted module ('a/b/__init__.py' -> 'a.b')."""
    parts = path[:-3].split("/") if path.endswith(".py") else path.split("/")
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(p for p in parts if p)


def _ann_refs(node: Optional[ast.AST]) -> List[str]:
    """Type-reference candidates named by an annotation.  Unwraps
    Optional[X] / X | None; anything fancier contributes nothing."""
    if node is None:
        return []
    if isinstance(node, (ast.Name, ast.Attribute)):
        dn = dotted_name(node)
        return [dn] if dn else []
    if isinstance(node, ast.Subscript):
        base = dotted_name(node.value) or ""
        if base.rsplit(".", 1)[-1] in ("Optional", "Union", "Type", "type"):
            inner = node.slice
            elts = inner.elts if isinstance(inner, ast.Tuple) else [inner]
            out: List[str] = []
            for e in elts:
                out.extend(_ann_refs(e))
            return out
        return []
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.BitOr):
        return _ann_refs(node.left) + _ann_refs(node.right)
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]  # string annotation: 'KvController'
    return []


def _expr_type_refs(
    node: ast.AST, params: Dict[str, List[str]], local_types: Dict[str, List[str]]
) -> List[str]:
    """Candidate type references for an assigned expression: constructor
    calls, annotated params, previously-typed locals; IfExp/BoolOp union
    their branches (``controller if controller else MemoryController()``)."""
    if isinstance(node, ast.Call):
        dn = dotted_name(node.func)
        return [dn] if dn else []
    if isinstance(node, ast.Name):
        return list(params.get(node.id, [])) + list(local_types.get(node.id, []))
    if isinstance(node, ast.IfExp):
        return _expr_type_refs(node.body, params, local_types) + _expr_type_refs(
            node.orelse, params, local_types
        )
    if isinstance(node, ast.BoolOp):
        out: List[str] = []
        for v in node.values:
            out.extend(_expr_type_refs(v, params, local_types))
        return out
    if isinstance(node, ast.Await):
        return _expr_type_refs(node.value, params, local_types)
    return []


# ---------------------------------------------------------------------------
# width/argument provenance tags (retrace-hazard raw material)
# ---------------------------------------------------------------------------
#
# A *tag* is a small JSON value describing where an expression's value
# provably comes from:
#
#   ["quant"]          a bucket-quantizer call (QUANT_FUNCS)
#   ["const", n]       an int literal
#   ["none"]           literal None (callee default applies)
#   ["param", name]    the enclosing function's parameter `name`
#   ["all", [t, ...]]  every branch/operand must satisfy (IfExp/BoolOp/min/max)
#   ["rawlen", detail] a len(...) call — PROVABLY a per-call size, the
#                      canonical retrace storm (one program per distinct
#                      input size); distinguishable from tensor args, so
#                      dispatch sites can judge positional args too
#   ["star"]           a *starred positional (alignment unknown from here on)
#   ["other", detail]  anything else — not provable
#
# rules_program.py closes ["param", ...] over the call graph (every
# resolved caller must pass a quantized value) and judges ["const", n]
# against the rung set parsed from ops/bls12_381/buckets.py.


def _width_tag(node, canon, params, local_tags) -> list:
    if node is None:
        return ["none"]
    if isinstance(node, ast.Constant):
        if node.value is None:
            return ["none"]
        if isinstance(node.value, int) and not isinstance(node.value, bool):
            return ["const", node.value]
        return ["other", repr(node.value)[:60]]
    if isinstance(node, ast.Name):
        if node.id in local_tags:
            return local_tags[node.id]
        if node.id in params:
            return ["param", node.id]
        return ["other", node.id]
    if isinstance(node, ast.Call):
        dn = canon(dotted_name(node.func)) or ""
        last = dn.rsplit(".", 1)[-1]
        if last in QUANT_FUNCS:
            return ["quant"]
        if dn == "len" and len(node.args) == 1:
            # the line of the len() itself rides along: the root site for
            # suppression + binding/dispatch dedup in retrace-hazard
            return ["rawlen", (unparse(node) or "len(...)")[:60], node.lineno]
        if last in ("min", "max") and node.args and not node.keywords:
            return ["all", [_width_tag(a, canon, params, local_tags)
                            for a in node.args]]
        return ["other", (unparse(node) or "call")[:60]]
    if isinstance(node, ast.IfExp):
        return ["all", [_width_tag(node.body, canon, params, local_tags),
                        _width_tag(node.orelse, canon, params, local_tags)]]
    if isinstance(node, ast.BoolOp):
        return ["all", [_width_tag(v, canon, params, local_tags)
                        for v in node.values]]
    if isinstance(node, ast.Await):
        return _width_tag(node.value, canon, params, local_tags)
    return ["other", (unparse(node) or type(node).__name__)[:60]]


def _arg_record(node, canon, params, local_tags) -> dict:
    """Compact provenance record for one call argument: a width tag plus
    the dotted reference when the arg IS a plain name/attribute chain
    (how run_in_executor/Thread callables are recognized)."""
    rec: Dict[str, object] = {"tag": _width_tag(node, canon, params, local_tags)}
    if isinstance(node, (ast.Name, ast.Attribute)):
        ref = dotted_name(node)
        if ref:
            rec["ref"] = ref
    return rec


def _const_str(node, str_env: Dict[str, str]) -> Optional[str]:
    """Statically render a str constant or an f-string whose interpolated
    names are known local str constants (metric-name resolution)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        parts: List[str] = []
        for v in node.values:
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                parts.append(v.value)
            elif (
                isinstance(v, ast.FormattedValue)
                and isinstance(v.value, ast.Name)
                and v.value.id in str_env
            ):
                parts.append(str_env[v.value.id])
            else:
                return None
        return "".join(parts)
    return None


def _label_list(node) -> Optional[List[str]]:
    if isinstance(node, (ast.List, ast.Tuple)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
            else:
                return None
        return out
    return None


def _find_shard_call(dec: ast.AST) -> Optional[Tuple[str, ast.Call]]:
    """(kind, call) when a decorator expresses a shard_map/pmap binding.

    Recognized spellings (the repo uses all three):
      ``@partial(jax.shard_map, mesh=..., ...)``
      ``@lambda f: shard_map(f, mesh=..., ...)``   (and jax.pmap forms)
      ``@shard_map(mesh=..., ...)`` / ``@jax.pmap(...)``
    Any dotted name ENDING in ``shard_map`` matches, so a repo-local
    wrapper binds axes exactly like the jax primitive it wraps.
    """
    def classify(call: ast.Call) -> Optional[Tuple[str, ast.Call]]:
        last = (dotted_name(call.func) or "").rsplit(".", 1)[-1]
        if last.endswith("shard_map"):
            return ("shard_map", call)
        if last == "pmap":
            return ("pmap", call)
        if last == "partial" and call.args:
            inner = (dotted_name(call.args[0]) or "").rsplit(".", 1)[-1]
            if inner.endswith("shard_map"):
                return ("shard_map", call)
            if inner == "pmap":
                return ("pmap", call)
        return None

    if isinstance(dec, ast.Call):
        return classify(dec)
    if isinstance(dec, ast.Lambda):
        for sub in ast.walk(dec.body):
            if isinstance(sub, ast.Call):
                hit = classify(sub)
                if hit:
                    return hit
    return None


def _replicated_spec(node: ast.AST) -> bool:
    """True when an out_specs expression declares a fully-replicated
    output: a bare ``P()`` / ``PartitionSpec()`` call, or a tuple/list
    whose every element is one."""
    if isinstance(node, (ast.Tuple, ast.List)):
        return bool(node.elts) and all(_replicated_spec(e) for e in node.elts)
    return (
        isinstance(node, ast.Call)
        and not node.args
        and not node.keywords
        and (dotted_name(node.func) or "").rsplit(".", 1)[-1]
        in ("P", "PartitionSpec")
    )


def walk_own(func: ast.AST) -> Iterable[ast.AST]:
    """Walk a function's body excluding nested def/lambda subtrees (their
    effects/calls belong to the nested function, which gets its own graph
    node) and excluding the decorator list (runs in the enclosing scope)."""
    stack: List[ast.AST] = list(func.body)  # type: ignore[attr-defined]
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


# -- v4 raw material: fault checkpoints + task lifecycle flows -------------

# fire/inject receivers: `faults.fire("x")` / bare `fire("x")` when the name
# was imported from a faults module (fault-coverage resolves the rest)
_TASK_SPAWNS = {"create_task", "ensure_future"}
# call names whose presence in a statement marks it as settling tasks
_TASK_SETTLERS = {"gather", "wait", "wait_for", "shield", "as_completed"}
# attr names that are machinery, never task containers
_TASK_NOISE = {"cancel", "done", "discard", "add", "append", "pop",
               "add_done_callback", "cancelled", "result", "exception"} | _TASK_SETTLERS


def _fault_call_name(node: ast.Call, imports: Dict[str, str]) -> Optional[str]:
    """'fire' / 'inject' when this call is a fault-checkpoint touch."""
    dn = dotted_name(node.func) or ""
    last = dn.rsplit(".", 1)[-1]
    if last not in ("fire", "inject"):
        return None
    if dn in (last,):  # bare name: must be imported from a faults module
        src = imports.get(last, "")
        return last if src.rsplit(".", 1)[-1].startswith("fault") else None
    # dotted: receiver chain must end in a `faults`-ish name
    recv = dn.rsplit(".", 2)[-2] if "." in dn else ""
    return last if recv.startswith("fault") else None


def _task_flow(own: Sequence[ast.AST], imports: Dict[str, str],
               str_env: Dict[str, str]):
    """(task_binds, task_cancels, fault_fires, fault_injects) for one
    function body.  Binds classify where a create_task/ensure_future
    result lands (self attr / foreign attr / local); cancels are the attr
    names this body settles (cancel/await/gather statements, with local
    aliases like ``tasks = [t for t in self._tasks ...]`` expanded)."""
    stmts = [n for n in own if isinstance(n, ast.stmt)]
    # local name -> attr names its assigned expression mentions
    aliases: Dict[str, Set[str]] = {}
    for n in stmts:
        if (
            isinstance(n, ast.Assign)
            and len(n.targets) == 1
            and isinstance(n.targets[0], ast.Name)
            and n.value is not None
        ):
            attrs = {
                a.attr for a in ast.walk(n.value)
                if isinstance(a, ast.Attribute) and a.attr not in _TASK_NOISE
            }
            if attrs:
                aliases[n.targets[0].id] = attrs

    def _is_settle_stmt(sub: Sequence[ast.AST]) -> bool:
        for c in sub:
            if isinstance(c, ast.Await):
                return True
            if isinstance(c, ast.Call):
                if (
                    isinstance(c.func, ast.Attribute)
                    and c.func.attr == "cancel"
                ):
                    return True
                dn = dotted_name(c.func) or ""
                if dn.rsplit(".", 1)[-1] in _TASK_SETTLERS:
                    return True
        return False

    cancels: Set[str] = set()
    settle_names: Set[str] = set()  # local Names read inside settle stmts
    for n in stmts:
        sub = list(ast.walk(n))
        if not _is_settle_stmt(sub):
            continue
        for c in sub:
            if isinstance(c, ast.Attribute) and c.attr not in _TASK_NOISE:
                cancels.add(c.attr)
            elif isinstance(c, ast.Name):
                settle_names.add(c.id)
                cancels |= aliases.get(c.id, set())

    fires: List[dict] = []
    injects: List[dict] = []
    binds: List[dict] = []
    for node in own:
        if not isinstance(node, ast.Call):
            continue
        kind = _fault_call_name(node, imports)
        if kind is not None and node.args:
            rec = {
                "name": _const_str(node.args[0], str_env),
                "line": node.lineno,
                "col": node.col_offset,
                "expr": (unparse(node.args[0]) or "?")[:60],
            }
            (fires if kind == "fire" else injects).append(rec)
            continue
        dn = dotted_name(node.func) or ""
        if dn.rsplit(".", 1)[-1] not in _TASK_SPAWNS:
            continue
        binds.append(_classify_task_bind(node, stmts, settle_names, aliases))
    return binds, sorted(cancels), fires, injects


def _classify_task_bind(call: ast.Call, stmts, settle_names, aliases) -> dict:
    rec = {"kind": "local", "attr": None, "line": call.lineno,
           "col": call.col_offset, "handled": False}

    def _attr_kind(recv: ast.AST):
        """(kind, attr) for a self.X / obj.X receiver chain, else None."""
        if isinstance(recv, ast.Attribute):
            base = recv.value
            if isinstance(base, ast.Name):
                kind = "self_attr" if base.id == "self" else "obj_attr"
                return kind, recv.attr
            return "obj_attr", recv.attr
        return None

    parent = getattr(call, "_ll_parent", None)
    if isinstance(parent, ast.Await):
        rec.update(kind="local", handled=True)
        return rec
    if isinstance(parent, ast.Assign) and len(parent.targets) == 1:
        t = parent.targets[0]
        if isinstance(t, ast.Attribute):
            hit = _attr_kind(t)
            if hit:
                rec.update(kind=hit[0], attr=hit[1])
                return rec
        if isinstance(t, ast.Name):
            return _classify_local_task(t.id, parent, stmts, settle_names,
                                        aliases, rec)
        rec.update(handled=True)  # tuple/subscript target: assume tracked
        return rec
    if (
        isinstance(parent, ast.Call)
        and isinstance(parent.func, ast.Attribute)
        and parent.func.attr in ("add", "append")
    ):
        hit = _attr_kind(parent.func.value)
        if hit:
            rec.update(kind=hit[0], attr=hit[1])
            return rec
        rec.update(handled=True)
        return rec
    if isinstance(parent, ast.Expr):
        # bare-statement discard is task-no-ref territory, not lifecycle
        rec.update(handled=True)
        return rec
    # return / nested in gather(...) / passed along: ownership transferred
    rec.update(handled=True)
    return rec


def _classify_local_task(name: str, bind_stmt, stmts, settle_names,
                         aliases, rec: dict) -> dict:
    """A locally-named task: stored into an attr collection reclassifies
    the bind; a cancel/await/return use marks it handled; any other use
    (beyond add_done_callback bookkeeping) transfers ownership."""
    escaped = False
    for n in stmts:
        if n is bind_stmt:
            continue
        for c in ast.walk(n):
            if not (isinstance(c, ast.Name) and c.id == name):
                continue
            p = getattr(c, "_ll_parent", None)
            # self._tasks.add(task) / outer._tasks.append(task)
            if (
                isinstance(p, ast.Call)
                and c in p.args
                and isinstance(p.func, ast.Attribute)
                and p.func.attr in ("add", "append")
                and isinstance(p.func.value, ast.Attribute)
                and isinstance(p.func.value.value, ast.Name)
            ):
                kind = ("self_attr" if p.func.value.value.id == "self"
                        else "obj_attr")
                rec.update(kind=kind, attr=p.func.value.attr)
                return rec
            if isinstance(p, ast.Attribute) and p.attr == "add_done_callback":
                continue  # bookkeeping only
            if (
                isinstance(p, ast.Call)
                and isinstance(p.func, ast.Attribute)
                and p.func.attr == "add_done_callback"
            ):
                continue
            escaped = True
    # assignment into an attr / subscript target: self.X = task
    for n in stmts:
        if not isinstance(n, ast.Assign) or not isinstance(n.value, ast.Name):
            continue
        if n.value.id != name:
            continue
        for t in n.targets:
            if isinstance(t, ast.Attribute) and isinstance(t.value, ast.Name):
                kind = "self_attr" if t.value.id == "self" else "obj_attr"
                rec.update(kind=kind, attr=t.attr)
                return rec
            if (
                isinstance(t, ast.Subscript)
                and isinstance(t.value, ast.Attribute)
                and isinstance(t.value.value, ast.Name)
            ):
                kind = ("self_attr" if t.value.value.id == "self"
                        else "obj_attr")
                rec.update(kind=kind, attr=t.value.attr)
                return rec
    if name in settle_names:
        rec.update(handled=True)
        return rec
    for n in stmts:
        if isinstance(n, ast.Return) and n.value is not None and any(
            isinstance(c, ast.Name) and c.id == name
            for c in ast.walk(n.value)
        ):
            rec.update(handled=True)
            return rec
    if escaped:
        rec.update(handled=True)
    return rec


def _interface_marker(func: ast.AST) -> bool:
    """True when a stub body is spelled `...` or raise NotImplementedError
    — the idioms that mark an interface, unlike a plain `pass` stub."""
    for s in getattr(func, "body", []):
        if (
            isinstance(s, ast.Expr)
            and isinstance(s.value, ast.Constant)
            and s.value.value is Ellipsis
        ):
            return True
        if isinstance(s, ast.Raise) and s.exc is not None:
            exc = s.exc.func if isinstance(s.exc, ast.Call) else s.exc
            if (dotted_name(exc) or "").endswith("NotImplementedError"):
                return True
    return False


def _empty_body(func: ast.AST) -> bool:
    body = list(getattr(func, "body", []))
    if body and isinstance(body[0], ast.Expr) and isinstance(
        body[0].value, ast.Constant
    ) and isinstance(body[0].value.value, str):
        body = body[1:]  # docstring
    return all(
        isinstance(s, ast.Pass)
        or (
            isinstance(s, ast.Expr)
            and isinstance(s.value, ast.Constant)
            and s.value.value is Ellipsis
        )
        or (isinstance(s, ast.Raise) and s.cause is None and s.exc is not None
            and (dotted_name(s.exc if not isinstance(s.exc, ast.Call) else s.exc.func)
                 or "").endswith("NotImplementedError"))
        for s in body
    )


class _Extractor(ast.NodeVisitor):
    def __init__(self, module: str, path: str):
        self.module = module
        self.path = path
        self.imports: Dict[str, str] = {}
        self.classes: Dict[str, dict] = {}
        self.functions: List[dict] = []
        self.module_vars: Dict[str, List[str]] = {}
        self.scope: List[Tuple[str, str]] = []  # (kind, name)
        self.ctx = None  # module_effect_context, set in extract_summary
        # v3 whole-program raw material
        self.module_consts: Dict[str, List[int]] = {}  # int / tuple-of-int
        self.module_strs: Dict[str, str] = {}
        self.jit_wrappers: List[str] = []  # names bound to registry.jitted()
        self.metric_defs: List[dict] = []
        self.release_defs: List[str] = []  # stage-release method short names
        # v4 whole-program raw material (fault-coverage / task-lifecycle)
        self.fault_fires: List[dict] = []
        self.fault_injects: List[dict] = []
        # v5 shard/collective raw material (shardcheck)
        self.module_meshes: Dict[str, List[str]] = {}  # name -> Mesh axis names
        self.module_const_lines: Dict[str, int] = {}  # anchor for rung findings
        self.mesh_contract: List[str] = []  # module docstring @mesh: axes
        self._mesh_env: List[Dict[str, List[str]]] = []  # enclosing fn mesh locals
        self._contract_env: List[List[str]] = []  # enclosing fn @mesh: contracts

    # -- imports ------------------------------------------------------

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            self.imports[a.asname or a.name.split(".")[0]] = (
                a.name if a.asname else a.name.split(".")[0]
            )
            if a.asname is None and "." in a.name:
                # `import a.b.c` binds `a`, but the full path is usable
                # through the bound root; record the root mapping only
                self.imports.setdefault(a.name.split(".")[0], a.name.split(".")[0])
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.level:
            parts = self.module.split(".")
            # a package __init__'s `from . import x` is relative to the
            # package itself; a plain module's is relative to its parent
            is_pkg = self.path.endswith("/__init__.py")
            up = node.level - (1 if is_pkg else 0)
            base = parts[: len(parts) - up] if up else parts
            prefix = ".".join(base + ([node.module] if node.module else []))
        else:
            prefix = node.module or ""
        for a in node.names:
            if a.name == "*":
                continue
            self.imports[a.asname or a.name] = (
                f"{prefix}.{a.name}" if prefix else a.name
            )
        self.generic_visit(node)

    # -- classes ------------------------------------------------------

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        qname = ".".join([n for _, n in self.scope] + [node.name])
        bases = [dotted_name(b) for b in node.bases]
        bases = [b for b in bases if b]
        methods = {
            s.name
            for s in node.body
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        method_nodes = [
            s
            for s in node.body
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        # protocol-like: declares the Protocol base, or is an interface
        # sketch (all methods empty, at least one spelled with `...` or
        # NotImplementedError — a pass-only class is just a stub impl)
        is_protocol = any(b.rsplit(".", 1)[-1] == "Protocol" for b in bases) or (
            bool(method_nodes)
            and all(_empty_body(s) for s in method_nodes)
            and any(_interface_marker(s) for s in method_nodes)
        )
        self.classes[qname] = {
            "bases": bases,
            "methods": sorted(methods),
            "protocol": is_protocol,
            "attr_types": {},
        }
        self.scope.append(("class", node.name))
        self.generic_visit(node)
        self.scope.pop()

    # -- functions ----------------------------------------------------

    def _enclosing_class(self) -> Optional[str]:
        for i in range(len(self.scope) - 1, -1, -1):
            if self.scope[i][0] == "class":
                return ".".join(n for _, n in self.scope[: i + 1])
        return None

    def _visit_func(self, node, is_async: bool) -> None:
        qname = ".".join([n for _, n in self.scope] + [node.name])
        cls = self._enclosing_class()
        params: Dict[str, List[str]] = {}
        all_args = list(node.args.posonlyargs) + list(node.args.args) + list(
            node.args.kwonlyargs
        )
        for arg in all_args:
            refs = _ann_refs(arg.annotation)
            if refs:
                params[arg.arg] = refs
        arg_names = [a.arg for a in all_args]
        param_set = set(arg_names)
        canon = self.ctx.canon
        # default-value tags for the trailing positional params + kwonly
        # (a caller that omits a width param gets the default's provenance)
        arg_defaults: Dict[str, list] = {}
        pos_args = list(node.args.posonlyargs) + list(node.args.args)
        for a, d in zip(pos_args[len(pos_args) - len(node.args.defaults):],
                        node.args.defaults):
            arg_defaults[a.arg] = _width_tag(d, canon, param_set, {})
        for a, d in zip(node.args.kwonlyargs, node.args.kw_defaults):
            if d is not None:
                arg_defaults[a.arg] = _width_tag(d, canon, param_set, {})

        local_types: Dict[str, List[str]] = {}
        globals_decl: Set[str] = set()
        local_tags: Dict[str, list] = {}  # last width-provenance per local
        width_locals: List[dict] = []
        str_env: Dict[str, str] = dict(self.module_strs)
        jit_aliases: Set[str] = set()
        local_meshes: Dict[str, List[str]] = {}  # locals bound to Mesh(...)
        own = list(walk_own(node))

        def _jit_ref(value) -> bool:
            if isinstance(value, ast.Name):
                return value.id in self.jit_wrappers or value.id in jit_aliases
            if isinstance(value, ast.IfExp):
                return _jit_ref(value.body) and _jit_ref(value.orelse)
            if isinstance(value, ast.BoolOp):
                return all(_jit_ref(v) for v in value.values)
            return False

        # two passes: types first (assignment order approximation), then
        # calls/effects so `v = Foo(); v.m()` resolves within one body
        for n in sorted(
            (x for x in own if isinstance(x, (ast.Assign, ast.AnnAssign, ast.Global))),
            key=lambda x: (getattr(x, "lineno", 0), getattr(x, "col_offset", 0)),
        ):
            if isinstance(n, ast.Global):
                globals_decl.update(n.names)
                continue
            targets = n.targets if isinstance(n, ast.Assign) else [n.target]
            value = n.value
            refs = (
                _ann_refs(n.annotation)
                if isinstance(n, ast.AnnAssign) and n.annotation is not None
                else []
            )
            if value is not None and not refs:
                refs = _expr_type_refs(value, params, local_types)
            for t in targets:
                if isinstance(t, ast.Name) and refs:
                    local_types.setdefault(t.id, []).extend(
                        r for r in refs if r not in local_types.get(t.id, [])
                    )
                elif (
                    isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                    and cls is not None
                    and refs
                ):
                    at = self.classes.get(cls, {}).get("attr_types")
                    if at is not None:
                        cur = at.setdefault(t.attr, [])
                        cur.extend(r for r in refs if r not in cur)
            if value is None:
                continue
            # v3: width provenance, str consts, jit aliases, metric defs
            for t in targets:
                if isinstance(t, ast.Name):
                    tag = _width_tag(value, canon, param_set, local_tags)
                    local_tags[t.id] = tag
                    if WIDTH_LOCAL_RE.search(t.id):
                        width_locals.append(
                            {"name": t.id, "line": n.lineno,
                             "col": n.col_offset, "tag": tag}
                        )
                    s = _const_str(value, str_env)
                    if s is not None:
                        str_env[t.id] = s
                    if _jit_ref(value):
                        jit_aliases.add(t.id)
                    mesh_axes = _mesh_axes_of(value)
                    if mesh_axes:
                        local_meshes[t.id] = mesh_axes
                elif (
                    isinstance(t, ast.Attribute)
                    and isinstance(t.value, ast.Name)
                    and t.value.id == "self"
                ):
                    self._maybe_metric_def(t.attr, value, str_env)

        own_contract = parse_mesh_contract(ast.get_docstring(node))
        shard_decor = self._shard_decor(node, own_contract)
        if shard_decor is not None and shard_decor.get("out_replicated"):
            shard_decor["untainted_returns"] = self._untainted_returns(own)
        collectives = self._collect_collectives(own, str_env)
        calls = self._collect_calls(own, canon, param_set, local_tags)
        metric_uses = self._collect_metric_uses(own)
        release_calls = self._collect_release_calls(node, own)
        task_binds, task_cancels, fires, injects = _task_flow(
            own, self.imports, str_env
        )
        self.fault_fires.extend(fires)
        self.fault_injects.extend(injects)
        if "release" in node.name and any(
            isinstance(n, ast.Assign)
            and isinstance(n.value, ast.Constant)
            and n.value.value is False
            and any(
                isinstance(t, ast.Attribute)
                and isinstance(t.value, ast.Name)
                and t.value.id == "self"
                for t in n.targets
            )
            for n in own
        ):
            # a stage-release method: flips a self-owned ownership flag
            # off — pool-ownership requires its call sites to be
            # token-guarded (test + clear before the call)
            self.release_defs.append(node.name)
        effects = direct_effects(own, self.ctx, cls=cls, globals_decl=globals_decl)
        self.functions.append(
            {
                "qname": qname,
                "line": node.lineno,
                "col": node.col_offset,
                "is_async": is_async,
                "cls": cls,
                "params": params,
                "arg_names": arg_names,
                "arg_defaults": arg_defaults,
                "locals": local_types,
                "jit_aliases": sorted(jit_aliases),
                "width_locals": width_locals,
                "metric_uses": metric_uses,
                "release_calls": release_calls,
                "task_binds": task_binds,
                "task_cancels": task_cancels,
                "calls": calls,
                "effects": effects,
                "mesh_contract": own_contract,
                "shard_decor": shard_decor,
                "collectives": collectives,
            }
        )
        self.scope.append(("func", node.name))
        self._mesh_env.append(local_meshes)
        self._contract_env.append(own_contract)
        self.generic_visit(node)
        self._contract_env.pop()
        self._mesh_env.pop()
        self.scope.pop()

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._visit_func(node, is_async=False)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._visit_func(node, is_async=True)

    # -- module-level vars --------------------------------------------

    def visit_Assign(self, node: ast.Assign) -> None:
        if not self.scope:
            refs = _expr_type_refs(node.value, {}, {})
            for t in node.targets:
                if isinstance(t, ast.Name) and refs:
                    self.module_vars[t.id] = refs
            self._module_value(node.targets, node.value)
        self.generic_visit(node)

    def visit_AnnAssign(self, node: ast.AnnAssign) -> None:
        if not self.scope and node.value is not None:
            self._module_value([node.target], node.value)
        self.generic_visit(node)

    def _module_value(self, targets, value) -> None:
        """Module-scope constants + jit-wrapper bindings (v3 raw
        material): int/tuple-of-int consts (the bucket rung tables),
        str consts (metric-name prefixes), and names assigned from
        ``registry.jitted(...)`` — the dispatchable program wrappers
        retrace-hazard tracks call sites of."""
        names = [t.id for t in targets if isinstance(t, ast.Name)]
        if not names:
            return
        ints: Optional[List[int]] = None
        if isinstance(value, ast.Constant) and isinstance(value.value, int) \
                and not isinstance(value.value, bool):
            ints = [value.value]
        elif isinstance(value, (ast.Tuple, ast.List)) and value.elts and all(
            isinstance(e, ast.Constant)
            and isinstance(e.value, int)
            and not isinstance(e.value, bool)
            for e in value.elts
        ):
            ints = [e.value for e in value.elts]
        mesh_axes = _mesh_axes_of(value)
        for name in names:
            if ints is not None:
                self.module_consts[name] = ints
                self.module_const_lines[name] = value.lineno
            if mesh_axes:
                self.module_meshes[name] = mesh_axes
            s = _const_str(value, self.module_strs)
            if s is not None:
                self.module_strs[name] = s
            if (
                isinstance(value, ast.Call)
                and (self.ctx.canon(dotted_name(value.func)) or "").rsplit(
                    ".", 1
                )[-1] == "jitted"
            ):
                self.jit_wrappers.append(name)
            self._maybe_metric_def(name, value, self.module_strs)

    def _maybe_metric_def(self, attr: str, value, str_env: Dict[str, str]) -> None:
        """Record a prometheus Counter/Gauge/Histogram/Summary
        construction assigned to ``attr`` (metric-label-drift raw
        material: declared name + label set)."""
        if not isinstance(value, ast.Call):
            return
        if self.ctx.canon(dotted_name(value.func)) not in _PROM_TYPES:
            return
        name = _const_str(value.args[0], str_env) if value.args else None
        # labels: [] == registered label-free; None == a label argument
        # EXISTS but is statically unresolvable (a variable) — the rule
        # must skip label checks then, not treat the metric as unlabeled
        labels: Optional[List[str]] = []
        for kw in value.keywords:
            if kw.arg in ("labelnames", "labels"):
                labels = _label_list(kw.value)
        if labels == [] and len(value.args) >= 3:
            labels = _label_list(value.args[2])
        self.metric_defs.append(
            {
                "attr": attr,
                "name": name,
                "labels": labels,  # None == statically unresolvable
                "line": value.lineno,
                "col": value.col_offset,
            }
        )

    # -- call collection ----------------------------------------------

    def _collect_calls(
        self, own: Sequence[ast.AST], canon, param_set: Set[str],
        local_tags: Dict[str, list],
    ) -> List[dict]:
        out: List[dict] = []
        for node in own:
            if not isinstance(node, ast.Call):
                continue
            target = dotted_name(node.func)
            if not target:
                # `asyncio.get_running_loop().run_in_executor(...)` — the
                # receiver is a call, so no dotted name exists, but the
                # dispatched callable (arg 1) must still reach
                # pool-ownership; record the bare method as the target
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "run_in_executor"
                ):
                    target = "run_in_executor"
                else:
                    continue
            awaited = wrapped = False
            cur: ast.AST = node
            parent = getattr(cur, "_ll_parent", None)
            while parent is not None and not isinstance(parent, ast.stmt):
                if isinstance(parent, ast.Await):
                    awaited = True
                    break
                if isinstance(parent, ast.Call) and parent is not cur:
                    fn = dotted_name(parent.func) or ""
                    if fn.rsplit(".", 1)[-1] in _CORO_WRAPPERS:
                        wrapped = True
                        break
                cur = parent
                parent = getattr(cur, "_ll_parent", None)
            discarded = isinstance(
                getattr(node, "_ll_parent", None), ast.Expr
            )
            args: List[dict] = []
            for a in node.args:
                if isinstance(a, ast.Starred):
                    args.append({"tag": ["star"]})
                else:
                    args.append(_arg_record(a, canon, param_set, local_tags))
            kwargs: Dict[str, dict] = {}
            for kw in node.keywords:
                if kw.arg is not None:  # **expansions contribute nothing
                    kwargs[kw.arg] = _arg_record(
                        kw.value, canon, param_set, local_tags
                    )
            out.append(
                {
                    "target": target,
                    "line": node.lineno,
                    "col": node.col_offset,
                    "awaited": awaited,
                    "wrapped": wrapped,
                    "discarded": discarded,
                    "in_loop": enclosing_loop(node) is not None,
                    "args": args,
                    "kwargs": kwargs,
                }
            )
        return out

    # -- v5 shard/collective raw material -----------------------------

    def _shard_decor(self, node, own_contract: List[str]) -> Optional[dict]:
        """The shard_map/pmap binding a function's decorator list
        declares, with its bound axis names statically resolved.

        Axis resolution order for a ``mesh=`` reference: an inline
        ``Mesh(...)`` construction, a local of an enclosing function
        assigned from ``Mesh(...)``, a module-level ``Mesh(...)``
        binding, then ``@mesh:`` docstring contracts (own, enclosing,
        module).  An unresolvable mesh leaves ``axes`` empty — the
        collective-axis rule treats that as nothing bound, which is the
        forcing function for carrying a ``@mesh:`` contract on
        mesh-parameterized builders."""
        for dec in node.decorator_list:
            hit = _find_shard_call(dec)
            if hit is None:
                continue
            kind, call = hit
            rec: dict = {
                "kind": kind, "line": dec.lineno, "axes": [],
                "mesh_ref": None, "out_replicated": False,
                "out_line": dec.lineno, "check_vma": None,
                "check_vma_line": dec.lineno,
            }
            kwargs = {kw.arg: kw.value for kw in call.keywords if kw.arg}
            if kind == "pmap":
                an = kwargs.get("axis_name")
                if isinstance(an, ast.Constant) and isinstance(an.value, str):
                    rec["axes"] = [an.value]
                return rec
            mesh_arg = kwargs.get("mesh")
            axes: Optional[List[str]] = None
            if mesh_arg is not None:
                ref = dotted_name(mesh_arg)
                rec["mesh_ref"] = ref
                axes = _mesh_axes_of(mesh_arg)
                if axes is None and ref:
                    base = ref.split(".")[0]
                    for env in reversed(self._mesh_env):
                        if base in env:
                            axes = env[base]
                            break
                    if axes is None:
                        axes = self.module_meshes.get(base)
            if axes:
                rec["axes"] = list(axes)
            else:
                for contract in (
                    [own_contract]
                    + list(reversed(self._contract_env))
                    + [self.mesh_contract]
                ):
                    if contract:
                        rec["axes"] = list(contract)
                        break
            out = kwargs.get("out_specs")
            if out is not None:
                rec["out_replicated"] = _replicated_spec(out)
                rec["out_line"] = out.lineno
            if "check_vma" in kwargs:
                v = kwargs["check_vma"]
                rec["check_vma_line"] = v.lineno
                if isinstance(v, ast.Constant) and isinstance(v.value, bool):
                    rec["check_vma"] = v.value
                else:
                    rec["check_vma"] = "dynamic"
            return rec
        return None

    def _collect_collectives(
        self, own: Sequence[ast.AST], str_env: Dict[str, str]
    ) -> List[dict]:
        """Collective call sites with their statically-resolved axis
        names (``axes`` is None when the axis argument is not a string
        literal/const — the rules under-approximate and skip those)."""
        out: List[dict] = []
        for n in own:
            if not isinstance(n, ast.Call):
                continue
            last = (dotted_name(n.func) or "").rsplit(".", 1)[-1]
            if last not in COLLECTIVE_FUNCS:
                continue
            axis_node = None
            for kw in n.keywords:
                if kw.arg == "axis_name":
                    axis_node = kw.value
            if axis_node is None:
                pos = 0 if last == "axis_index" else 1
                if len(n.args) > pos and not isinstance(n.args[pos], ast.Starred):
                    axis_node = n.args[pos]
            axes: Optional[List[str]] = None
            if axis_node is not None:
                s = _const_str(axis_node, str_env)
                axes = [s] if s is not None else _label_list(axis_node)
            out.append(
                {"name": last, "axes": axes, "line": n.lineno, "col": n.col_offset}
            )
        return out

    def _untainted_returns(self, own: Sequence[ast.AST]) -> List[List[int]]:
        """Return sites NOT (transitively, through local names) derived
        from a cross-axis collective — the replicated-escape raw
        material.  Taint is name-level and flow-insensitive (iterated to
        a fixpoint), matching the extractor's assignment-order
        approximation elsewhere."""

        def has_collective(expr: ast.AST) -> bool:
            return any(
                isinstance(sub, ast.Call)
                and (dotted_name(sub.func) or "").rsplit(".", 1)[-1]
                in CROSS_AXIS_FUNCS
                for sub in ast.walk(expr)
            )

        def refs_tainted(expr: ast.AST, tainted: Set[str]) -> bool:
            return any(
                isinstance(sub, ast.Name) and sub.id in tainted
                for sub in ast.walk(expr)
            )

        assigns = [
            n for n in own
            if isinstance(n, (ast.Assign, ast.AugAssign, ast.AnnAssign))
            and n.value is not None
        ]
        tainted: Set[str] = set()
        changed = True
        while changed:
            changed = False
            for st in assigns:
                if not (has_collective(st.value) or refs_tainted(st.value, tainted)):
                    continue
                targets = st.targets if isinstance(st, ast.Assign) else [st.target]
                for t in targets:
                    for sub in ast.walk(t):
                        if isinstance(sub, ast.Name) and sub.id not in tainted:
                            tainted.add(sub.id)
                            changed = True
        return sorted(
            [n.lineno, n.col_offset]
            for n in own
            if isinstance(n, ast.Return)
            and n.value is not None
            and not (has_collective(n.value) or refs_tainted(n.value, tainted))
        )

    def _collect_metric_uses(self, own: Sequence[ast.AST]) -> List[dict]:
        """Sites that touch a metric object: ``<chain>.labels(...)`` and
        bare ``<chain>.inc/dec/observe/set(...)`` where the receiver is
        an attribute chain.  The join key is the receiver's final
        attribute name; metric-label-drift matches it against every
        registered metric slot."""
        out: List[dict] = []
        for node in own:
            if not (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)):
                continue
            op = node.func.attr
            recv = node.func.value
            if op == "labels":
                if isinstance(recv, ast.Attribute):
                    attr = recv.attr
                elif isinstance(recv, ast.Name):
                    attr = recv.id
                else:
                    continue
                out.append(
                    {
                        "attr": attr,
                        "op": "labels",
                        "line": node.lineno,
                        "col": node.col_offset,
                        "kwnames": sorted(
                            kw.arg for kw in node.keywords if kw.arg
                        ),
                        "nargs": len(node.args),
                    }
                )
            elif op in _METRIC_OPS and isinstance(
                recv, (ast.Attribute, ast.Name)
            ):
                # Name receivers included: a module-level metric used
                # bare (JOBS.inc()) drifts exactly like self.m.jobs.inc().
                # The receiver chain rides along so the rule can require
                # a metric-ish receiver for `.set()` — a verb shared with
                # Event/Future-likes, where an attr-name collision with a
                # labeled gauge must not manufacture a finding.
                out.append(
                    {
                        "attr": recv.attr
                        if isinstance(recv, ast.Attribute)
                        else recv.id,
                        "chain": dotted_name(recv) or "",
                        "op": op,
                        "line": node.lineno,
                        "col": node.col_offset,
                        "kwnames": [],
                        "nargs": len(node.args),
                    }
                )
        return out

    def _collect_release_calls(self, func, own: Sequence[ast.AST]) -> List[dict]:
        """Call sites of release-ish methods with their token-guard shape:
        is the call inside an ``if <token>:`` whose body clears the token
        (assigns False to an expression the test reads) BEFORE the call,
        and does any ``await`` sit inside that guarded section?"""
        out: List[dict] = []
        for node in own:
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and "release" in node.func.attr
            ):
                continue
            pos = (node.lineno, node.col_offset)
            # EVERY enclosing `if` body up to the function is a guard
            # candidate: a correct test-and-clear may wrap the release
            # in a further nested condition
            guards: List[ast.If] = []
            cur = node
            while True:
                parent = getattr(cur, "_ll_parent", None)
                if parent is None or parent is func:
                    break
                if (
                    isinstance(parent, ast.If)
                    and getattr(cur, "_ll_field", "") == "body"
                ):
                    guards.append(parent)
                cur = parent
            cleared = False
            await_line: Optional[int] = None
            for guard in guards:
                test_src = unparse(guard.test)
                test_nodes = set(map(id, ast.walk(guard.test)))
                for n in ast.walk(guard):
                    if id(n) in test_nodes:
                        continue
                    npos = (getattr(n, "lineno", 0), getattr(n, "col_offset", 0))
                    if (
                        isinstance(n, ast.Assign)
                        and isinstance(n.value, ast.Constant)
                        and n.value.value is False
                        and npos < pos
                        and any(
                            unparse(t) and unparse(t) in test_src
                            for t in n.targets
                        )
                    ):
                        cleared = True
                    if isinstance(n, ast.Await) and npos < pos:
                        await_line = n.lineno
                if cleared:
                    break
            out.append(
                {
                    "method": node.func.attr,
                    "recv": unparse(node.func.value)[:60],
                    "line": node.lineno,
                    "col": node.col_offset,
                    "guarded": bool(guards),
                    "cleared": cleared,
                    "await_line": await_line,
                }
            )
        return out


# modules whose full source rides in the summary so limb-bounds can
# re-interpret their expression language from cached summaries alone
_BOUNDS_MODULES = ("fp", "tower", "curve", "pairing", "pallas_fp", "limbs")


def bounds_in_scope(path: str, text: str) -> bool:
    """limb-bounds scope: the BLS12-381 kernel modules, plus any source
    that opts in by carrying an ``@bounds:`` annotation (lint fixtures)."""
    base = os.path.basename(path)
    if "ops/bls12_381" in path.replace(os.sep, "/") and base in tuple(
        m + ".py" for m in _BOUNDS_MODULES
    ):
        return True
    return "@bounds:" in text


def extract_summary(
    tree: ast.Module, text: str, path: str, suppressions=None
) -> dict:
    """Build the JSON-serializable ModuleSummary for one parsed file.
    ``annotate_parents`` must already have run on ``tree``."""
    module = module_name_for(path)
    ex = _Extractor(module, path)
    ex.ctx = module_effect_context(tree)
    ex.mesh_contract = parse_mesh_contract(ast.get_docstring(tree))
    ex.visit(tree)
    per_line, per_file = (
        suppressions if suppressions is not None else parse_suppressions(text)
    )
    return {
        "module": module,
        "path": path,
        "imports": ex.imports,
        "classes": ex.classes,
        "module_vars": ex.module_vars,
        "module_consts": ex.module_consts,
        "module_const_lines": ex.module_const_lines,
        "module_meshes": ex.module_meshes,
        "mesh_contract": ex.mesh_contract,
        "jit_wrappers": ex.jit_wrappers,
        "metric_defs": ex.metric_defs,
        "release_defs": sorted(set(ex.release_defs)),
        "functions": ex.functions,
        "fault_fires": ex.fault_fires,
        "fault_injects": ex.fault_injects,
        "bounds_src": text if bounds_in_scope(path, text) else None,
        "suppress_lines": {str(k): sorted(v) for k, v in per_line.items()},
        "suppress_file": sorted(per_file),
    }


def summary_for_source(text: str, path: str) -> Optional[dict]:
    """Parse + extract in one step (tests, check_source); None on a
    syntax error (the parse-error finding is per-file territory)."""
    try:
        tree = ast.parse(text)
    except SyntaxError:
        return None
    annotate_parents(tree)
    return extract_summary(tree, text, path)


# ---------------------------------------------------------------------------
# project resolution
# ---------------------------------------------------------------------------


class Edge:
    __slots__ = ("callee", "line", "col", "awaited", "wrapped", "discarded")

    def __init__(self, callee, line, col, awaited, wrapped, discarded):
        self.callee = callee
        self.line = line
        self.col = col
        self.awaited = awaited
        self.wrapped = wrapped
        self.discarded = discarded


class Func:
    __slots__ = (
        "fq", "module", "qname", "path", "line", "col",
        "is_async", "cls", "effects", "edges",
    )

    def __init__(self, module: str, path: str, fs: dict):
        self.module = module
        self.path = path
        self.qname = fs["qname"]
        self.fq = f"{module}:{self.qname}"
        self.line = fs["line"]
        self.col = fs["col"]
        self.is_async = fs["is_async"]
        self.cls = fs["cls"]
        self.effects = fs["effects"]
        self.edges: List[Edge] = []


class Project:
    """Linked call graph over a set of ModuleSummaries."""

    def __init__(self, summaries: Sequence[dict]):
        self.summaries: Dict[str, dict] = {s["module"]: s for s in summaries}
        self.funcs: Dict[str, Func] = {}
        self._impl_cache: Dict[str, List[Tuple[str, str]]] = {}
        for s in summaries:
            for fs in s["functions"]:
                fn = Func(s["module"], s["path"], fs)
                self.funcs[fn.fq] = fn
        self._resolve_all()
        # filled by effects.propagate()
        self.inherited: Dict[str, Dict[str, Edge]] = {}

    # -- suppressions -------------------------------------------------

    def suppressed(self, path: str, line: int, rule: str) -> bool:
        for s in self.summaries.values():
            if s["path"] != path:
                continue
            if rule in s.get("suppress_file", []):
                return True
            return rule in s.get("suppress_lines", {}).get(str(line), [])
        return False

    # -- type/class helpers -------------------------------------------

    def _find_class(self, module: str, name: str) -> Optional[Tuple[str, str]]:
        s = self.summaries.get(module)
        if s and name in s["classes"]:
            return (module, name)
        return None

    def resolve_type_ref(self, module: str, ref: str) -> Optional[Tuple[str, str]]:
        """'KvController' / 'controller.KvController' (as written in
        ``module``) -> (defining_module, class_qname)."""
        s = self.summaries.get(module)
        if s is None:
            return None
        hit = self._find_class(module, ref)
        if hit:
            return hit
        head, _, rest = ref.partition(".")
        target = s["imports"].get(head)
        if target:
            full = target + ("." + rest if rest else "")
        else:
            full = ref
        # longest module prefix wins: a.b.C / a.b.Outer.Inner
        parts = full.split(".")
        for i in range(len(parts) - 1, 0, -1):
            mod = ".".join(parts[:i])
            if mod in self.summaries:
                cls = ".".join(parts[i:])
                return self._find_class(mod, cls)
        return None

    def _mro_method(
        self, module: str, cls: str, method: str, _seen: Optional[Set] = None
    ) -> Optional[str]:
        seen = _seen or set()
        if (module, cls) in seen:
            return None
        seen.add((module, cls))
        s = self.summaries.get(module)
        info = s["classes"].get(cls) if s else None
        if info is None:
            return None
        if method in info["methods"]:
            return f"{module}:{cls}.{method}"
        for base in info["bases"]:
            loc = self.resolve_type_ref(module, base)
            if loc:
                hit = self._mro_method(loc[0], loc[1], method, seen)
                if hit:
                    return hit
        return None

    def _protocol_impls(self, module: str, cls: str) -> List[Tuple[str, str]]:
        key = f"{module}:{cls}"
        if key in self._impl_cache:
            return self._impl_cache[key]
        info = self.summaries[module]["classes"][cls]
        need = set(info["methods"])
        impls: List[Tuple[str, str]] = []
        if need:
            for m, s in self.summaries.items():
                for cname, cinfo in s["classes"].items():
                    if cinfo["protocol"] or (m, cname) == (module, cls):
                        continue
                    have = set(cinfo["methods"])
                    for base in cinfo["bases"]:
                        loc = self.resolve_type_ref(m, base)
                        if loc:
                            have |= set(
                                self.summaries[loc[0]]["classes"][loc[1]]["methods"]
                            )
                    if need <= have:
                        impls.append((m, cname))
        self._impl_cache[key] = impls
        return impls

    def _method_targets(
        self, module: str, cls: str, method: str
    ) -> List[str]:
        info = self.summaries.get(module, {}).get("classes", {}).get(cls)
        if info is None:
            return []
        if info["protocol"]:
            out = []
            for m, c in self._protocol_impls(module, cls):
                hit = self._mro_method(m, c, method)
                if hit:
                    out.append(hit)
            return out
        hit = self._mro_method(module, cls, method)
        return [hit] if hit else []

    def _attr_type(
        self, module: str, cls: str, attr: str
    ) -> List[Tuple[str, str]]:
        info = self.summaries.get(module, {}).get("classes", {}).get(cls)
        if info is None:
            return []
        out: List[Tuple[str, str]] = []
        for ref in info["attr_types"].get(attr, []):
            loc = self.resolve_type_ref(module, ref)
            if loc and loc not in out:
                out.append(loc)
        return out

    # -- call resolution ----------------------------------------------

    def _resolve_name(self, s: dict, fs: dict, name: str) -> List[str]:
        module = s["module"]
        # lexical scope chain: f.g.name for each ancestor scope of qname,
        # INCLUDING the function's own scope — its nested defs are
        # visible as bare names inside it (run_in_executor(None, nested)
        # must resolve for pool-ownership to judge the callable)
        scope_parts = fs["qname"].split(".")
        for i in range(len(scope_parts), -1, -1):
            cand = ".".join(scope_parts[:i] + [name])
            fq = f"{module}:{cand}"
            if fq in self.funcs:
                # method names aren't visible as bare names inside a
                # method body — skip candidates whose parent is a class
                parent = ".".join(cand.split(".")[:-1])
                if parent and parent in s["classes"]:
                    continue
                return [fq]
        if name in s["classes"]:
            return self._method_targets(module, name, "__init__")
        target = s["imports"].get(name)
        if target:
            return self._resolve_dotted_abs(target)
        return []

    def _resolve_dotted_abs(self, full: str) -> List[str]:
        """Absolute dotted path -> function/class-ctor targets."""
        parts = full.split(".")
        for i in range(len(parts) - 1, 0, -1):
            mod = ".".join(parts[:i])
            s = self.summaries.get(mod)
            if s is None:
                continue
            rest = parts[i:]
            if len(rest) == 1:
                fq = f"{mod}:{rest[0]}"
                if fq in self.funcs:
                    return [fq]
                if rest[0] in s["classes"]:
                    return self._method_targets(mod, rest[0], "__init__")
            else:
                cls = ".".join(rest[:-1])
                if cls in s["classes"]:
                    return self._method_targets(mod, cls, rest[-1])
            return []
        return []

    def _walk_attr_chain(
        self, start: List[Tuple[str, str]], mids: Sequence[str], method: str
    ) -> List[str]:
        cur = start
        for attr in mids:
            nxt: List[Tuple[str, str]] = []
            for mod, cls in cur:
                for loc in self._attr_type(mod, cls, attr):
                    if loc not in nxt:
                        nxt.append(loc)
                # a protocol's attr types aren't declared; widen through
                # implementations so self.db.<proto attr> still chains
                info = self.summaries.get(mod, {}).get("classes", {}).get(cls)
                if info and info["protocol"]:
                    for m2, c2 in self._protocol_impls(mod, cls):
                        for loc in self._attr_type(m2, c2, attr):
                            if loc not in nxt:
                                nxt.append(loc)
            cur = nxt
            if not cur:
                return []
        out: List[str] = []
        for mod, cls in cur:
            for fq in self._method_targets(mod, cls, method):
                if fq not in out:
                    out.append(fq)
        return out

    def _resolve_call(self, s: dict, fs: dict, target: str) -> List[str]:
        module = s["module"]
        if "." not in target:
            return self._resolve_name(s, fs, target)
        parts = target.split(".")
        head, mids, method = parts[0], parts[1:-1], parts[-1]
        if head == "self" and fs["cls"]:
            if not mids:
                return self._method_targets(module, fs["cls"], method)
            start = [(module, fs["cls"])]
            return self._walk_attr_chain(start, mids, method)
        # typed local / param / module var roots
        root_refs = (
            fs.get("locals", {}).get(head, [])
            + fs.get("params", {}).get(head, [])
            + s.get("module_vars", {}).get(head, [])
        )
        start = []
        for ref in root_refs:
            loc = self.resolve_type_ref(module, ref)
            if loc and loc not in start:
                start.append(loc)
        if start:
            return self._walk_attr_chain(start, mids, method)
        # import roots: mod.func / pkg.mod.Class.method / alias.func
        imp = s["imports"].get(head)
        full = (imp + "." + ".".join(parts[1:])) if imp else target
        return self._resolve_dotted_abs(full)

    def _resolve_all(self) -> None:
        for s in self.summaries.values():
            for fs in s["functions"]:
                fn = self.funcs[f"{s['module']}:{fs['qname']}"]
                for c in fs["calls"]:
                    for callee in self._resolve_call(s, fs, c["target"]):
                        if callee == fn.fq:
                            continue  # direct self-recursion adds nothing
                        fn.edges.append(
                            Edge(
                                callee,
                                c["line"],
                                c["col"],
                                c["awaited"],
                                c["wrapped"],
                                c["discarded"],
                            )
                        )

    # -- reporting -----------------------------------------------------

    def graph_lines(self) -> List[str]:
        """Human-readable adjacency dump for ``--graph``."""
        out: List[str] = []
        for fq in sorted(self.funcs):
            fn = self.funcs[fq]
            effs = sorted(
                set(fn.effects) | set(self.inherited.get(fq, {}))
            )
            tag = " async" if fn.is_async else ""
            eff = f" [{','.join(effs)}]" if effs else ""
            out.append(f"{fq}{tag}{eff}  ({fn.path}:{fn.line})")
            seen: Set[str] = set()
            for e in fn.edges:
                if e.callee in seen:
                    continue
                seen.add(e.callee)
                out.append(f"    -> {e.callee}  (line {e.line})")
        return out

    def graph_json(self) -> List[dict]:
        out = []
        for fq in sorted(self.funcs):
            fn = self.funcs[fq]
            out.append(
                {
                    "function": fq,
                    "path": fn.path,
                    "line": fn.line,
                    "async": fn.is_async,
                    "effects": sorted(fn.effects),
                    "inherited_effects": sorted(self.inherited.get(fq, {})),
                    "calls": sorted({e.callee for e in fn.edges}),
                }
            )
        return out


def build_project(summaries: Sequence[dict]) -> Project:
    from . import effects as _eff

    project = Project([s for s in summaries if s is not None])
    _eff.propagate(project)
    return project
