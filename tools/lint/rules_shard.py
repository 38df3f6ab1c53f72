"""Whole-program (v5) "shardcheck" rules: static SPMD/collective safety.

ROADMAP item 3's multi-chip sharded verification has to be debuggable on
real hardware, which means the machine must prove — at lint time, before
a 40-minute XLA compile or a TPU reservation — three invariants the
SURVEY's §2.5/§7 ICI mapping (shard the set axis, reduce the GT
products, one shared final exponentiation) quietly depends on:

* ``collective-axis`` — every ``jax.lax.psum``/``all_gather``/``pmean``/
  ``axis_index`` axis name resolves to an axis bound by an enclosing
  ``shard_map``/``pmap``.  Mesh axis names come from the ``Mesh(...)``
  construction the decorator's ``mesh=`` kwarg references or from a
  ``@mesh:`` docstring contract; binding closes interprocedurally over
  the v2/v3 call graph, so a helper called from inside a shard_map body
  inherits the bound axes, and a collective reachable ONLY from
  unsharded callers is flagged with the witness chain.
* ``replicated-escape`` — a shard_map output declared ``out_specs=P()``
  (replicated) must be produced by a cross-axis collective on every
  return path (the bit-equality-vs-unsharded invariant
  tests/test_mesh_smoke.py checks dynamically, made static), and any
  ``check_vma=False`` needs a reviewed
  root suppression whose comment records WHY inference fails.
* ``shard-divisibility`` — every AOT bucket rung that can feed a
  sharded program must divide evenly over every supported mesh size AND
  shard to a width that is itself a registered rung, so a 2/4/8-chip
  mesh never truncates, pads, or cold-compiles a per-device program
  silently.  Rung tables and mesh sizes are read live from
  ops/bls12_381/buckets.py and ops/bls12_381/sharded.py (the same
  idiom as retrace-hazard's rung parsing).

All three consume the v5 raw material extracted by
tools/lint/callgraph.py (shard_map/pmap decorator bindings, collective
call sites with static axis names, ``Mesh(...)`` axis tables, ``@mesh:``
contracts) and under-approximate: an axis argument that is not a string
literal, or an unresolved caller, contributes nothing — a finding is
always backed by a concrete, reportable failure.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, ProjectRule, register
from .rules_program import _env_for, _DEFAULT_RUNGS

# where the sharded program's mesh geometry lives; parsed from the
# project summaries so the rule updates itself when the tables change
_SHARDED_MODULE = "lodestar_tpu.ops.bls12_381.sharded"
_DEFAULT_MESH_SIZES = (2, 4, 8)
# rung-table names that feed sharded programs: the pool's quantized
# dispatch widths plus the sharded module's own bucket table
_SHARDED_RUNG_TABLES = ("POOL_BUCKETS", "SHARDED_BUCKETS")


def _bound_axes(env) -> Dict[str, Set[str]]:
    """Axis environment per function, closed over the call graph: a
    function's bound axes are its own shard_map/pmap decorator bindings
    UNION the axes of ANY caller (a helper called from inside a sharded
    body inherits them; only a collective with NO sharded caller chain
    is flagged).  Plain worklist fixpoint — monotone, so cycles
    converge."""
    bound: Dict[str, Set[str]] = {}
    for fq, (s, fs) in env.funcs_by_fq.items():
        sd = fs.get("shard_decor")
        axes = set(sd["axes"]) if sd else set()
        # a `@mesh:` docstring contract on the function or its module
        # declares the axes as bound (the ISSUE's contract mechanism)
        axes |= set(fs.get("mesh_contract") or ())
        axes |= set(s.get("mesh_contract") or ())
        bound[fq] = axes
    changed = True
    while changed:
        changed = False
        for fq, callers in env.incoming.items():
            cur = bound.setdefault(fq, set())
            for cs, cfs, _call in callers:
                extra = bound.get(f"{cs['module']}:{cfs['qname']}")
                if extra and not extra <= cur:
                    cur |= extra
                    changed = True
    return bound


def _witness_chain(env, fq: str, axis: str, max_depth: int = 6) -> List[str]:
    """Frames proving the unsharded reachability: walk UP the incoming
    edges from the collective's function until a root caller (no
    callers) — since no caller chain binds ``axis``, any chain is a
    witness; the first/shortest found is reported."""
    frames: List[str] = []
    seen = {fq}
    cur = fq
    for _ in range(max_depth):
        callers = env.incoming.get(cur, ())
        step = None
        for cs, cfs, call in callers:
            caller_fq = f"{cs['module']}:{cfs['qname']}"
            if caller_fq not in seen:
                step = (cs, cfs, call, caller_fq)
                break
        if step is None:
            break
        cs, cfs, call, caller_fq = step
        seen.add(caller_fq)
        frames.append(
            f"{cs['path']}:{call['line']} {cfs['qname']} "
            f"[calls {cur.split(':')[-1].rsplit('.', 1)[-1]}() with no "
            f"{axis!r} binding]"
        )
        cur = caller_fq
    return frames


@register
class CollectiveAxis(ProjectRule):
    id = "collective-axis"
    description = (
        "a jax.lax collective (psum/all_gather/pmean/axis_index/...) "
        "whose axis name is not bound by any enclosing shard_map/pmap "
        "on any caller chain: at runtime this is a NameError-class "
        "trace failure — or worse, a program that only crashes once the "
        "multi-chip path is finally exercised on real hardware.  Mesh "
        "axis names are parsed from the Mesh(...) construction the "
        "decorator references or from a `@mesh:` docstring contract; "
        "binding closes interprocedurally (a helper called from inside "
        "a shard_map body inherits the bound axes).  Axis arguments "
        "that are not string literals contribute nothing "
        "(under-approximation)"
    )

    def check_project(self, project) -> List[Finding]:
        env = _env_for(project)
        bound = _bound_axes(env)
        out: List[Finding] = []
        seen: Set[tuple] = set()
        for s in sorted(project.summaries.values(), key=lambda s: s["path"]):
            path = s["path"]
            for fs in s["functions"]:
                fq = f"{s['module']}:{fs['qname']}"
                have = bound.get(fq, set())
                for c in fs.get("collectives", ()):
                    axes = c.get("axes")
                    if not axes:
                        continue  # non-literal axis: under-approximate
                    for axis in axes:
                        if axis in have:
                            continue
                        key = (path, c["line"], c["col"], axis)
                        if key in seen:
                            continue
                        seen.add(key)
                        chain = _witness_chain(env, fq, axis)
                        if project.suppressed(path, c["line"], self.id):
                            continue
                        if chain:
                            root_line = int(chain[-1].split(":", 2)[1].split(" ")[0])
                            root_path = chain[-1].split(":", 1)[0]
                            if project.suppressed(root_path, root_line, self.id):
                                continue
                        out.append(
                            Finding(
                                path=path, line=c["line"], col=c["col"],
                                rule=self.id,
                                message=(
                                    f"collective {c['name']}(..., {axis!r}) "
                                    f"in {fs['qname']}(): axis {axis!r} is "
                                    "not bound by any enclosing shard_map/"
                                    "pmap on any resolved caller chain — "
                                    "wrap the body in shard_map over a "
                                    f"Mesh binding {axis!r}, or declare the "
                                    "contract with a `@mesh:` docstring "
                                    "line on the builder"
                                ),
                                effects=(f"collective:{c['name']}", f"axis:{axis}"),
                                chain=tuple(chain),
                            )
                        )
        return out


@register
class ReplicatedEscape(ProjectRule):
    id = "replicated-escape"
    description = (
        "a shard_map output declared out_specs=P() (replicated) that is "
        "not produced by a cross-axis collective on every return path — "
        "each device would return its LOCAL value and XLA silently "
        "keeps device 0's copy, the exact bug class "
        "tests/test_mesh_smoke.py's bit-equality check catches "
        "dynamically.  Also flags check_vma=False: disabling JAX's "
        "varying-mesh-axes check requires a reviewed "
        "`# lodelint: disable=replicated-escape` root suppression whose "
        "comment records why inference fails"
    )

    def check_project(self, project) -> List[Finding]:
        out: List[Finding] = []
        for s in sorted(project.summaries.values(), key=lambda s: s["path"]):
            path = s["path"]
            for fs in s["functions"]:
                sd = fs.get("shard_decor")
                if not sd or sd.get("kind") != "shard_map":
                    continue
                cv = sd.get("check_vma")
                if cv is not True and cv is not None:
                    line = sd["check_vma_line"]
                    if not project.suppressed(path, line, self.id):
                        how = (
                            "check_vma=False disables"
                            if cv is False
                            else "a non-literal check_vma value may disable"
                        )
                        out.append(
                            Finding(
                                path=path, line=line, col=0, rule=self.id,
                                message=(
                                    f"{how} JAX's varying-mesh-axes check "
                                    f"on {fs['qname']}(): enable it, or "
                                    "carry a reviewed `# lodelint: "
                                    "disable=replicated-escape` on this "
                                    "line with a comment recording why "
                                    "inference fails"
                                ),
                                effects=(f"check_vma:{cv}", "out_specs:P()"),
                            )
                        )
                if not sd.get("out_replicated"):
                    continue
                for line, col in sd.get("untainted_returns", ()):
                    if project.suppressed(path, line, self.id):
                        continue
                    out.append(
                        Finding(
                            path=path, line=line, col=col, rule=self.id,
                            message=(
                                f"{fs['qname']}() declares out_specs=P() "
                                "(replicated) but this return value is not "
                                "derived from a cross-axis collective "
                                "(psum/all_gather/...): each device would "
                                "return its local shard's value and the "
                                "program silently keeps one copy — reduce "
                                "across the axis before returning, or "
                                "shard the output spec"
                            ),
                            effects=("out_specs:P()",),
                        )
                    )
        return out


@register
class ShardDivisibility(ProjectRule):
    id = "shard-divisibility"
    description = (
        "an AOT bucket rung that can feed a sharded program (the pool's "
        "POOL_BUCKETS and the sharded module's SHARDED_BUCKETS, read "
        "live — the same idiom as retrace-hazard's rung parsing) that "
        "either does not divide evenly over a supported mesh size "
        "(SUPPORTED_MESH_SIZES, default 2/4/8 — the mesh would silently "
        "truncate or pad the batch) or shards to a per-device width "
        "that is not itself a registered rung (each device dispatches a "
        "program shape `aot warm` has never compiled: a cold "
        "multi-minute XLA build at first multi-chip dispatch)"
    )

    def check_project(self, project) -> List[Finding]:
        env = _env_for(project)
        mesh_sizes: List[int] = []
        for s in project.summaries.values():
            mesh_sizes.extend(
                s.get("module_consts", {}).get("SUPPORTED_MESH_SIZES", ())
            )
        if not mesh_sizes:
            mesh_sizes = list(_DEFAULT_MESH_SIZES)
        mesh_sizes = sorted(set(mesh_sizes))
        # the per-device width universe: every registered rung anywhere
        rung_universe = set(env.rungs) | set(_DEFAULT_RUNGS)
        out: List[Finding] = []
        seen: Set[tuple] = set()
        for s in sorted(project.summaries.values(), key=lambda s: s["path"]):
            consts = s.get("module_consts", {})
            lines = s.get("module_const_lines", {})
            for table in _SHARDED_RUNG_TABLES:
                for b in consts.get(table, ()):
                    line = lines.get(table, 1)
                    for m in mesh_sizes:
                        key = (s["path"], table, b, m)
                        if key in seen:
                            continue
                        seen.add(key)
                        if project.suppressed(s["path"], line, self.id):
                            continue
                        if b % m:
                            msg = (
                                f"sharded rung {b} ({table}) is not "
                                f"divisible by mesh size {m}: a {m}-chip "
                                "mesh would silently truncate or pad the "
                                "batch — use a rung divisible by every "
                                "SUPPORTED_MESH_SIZES entry"
                            )
                        elif (b // m) not in rung_universe:
                            msg = (
                                f"sharded rung {b} ({table}) shards to "
                                f"per-device width {b // m} on a {m}-chip "
                                "mesh, which is not a registered AOT rung "
                                "— each device would cold-compile an "
                                "unwarmed program shape; pick a rung whose "
                                "every per-mesh quotient is registered"
                            )
                        else:
                            continue
                        out.append(
                            Finding(
                                path=s["path"], line=line, col=0,
                                rule=self.id, message=msg,
                                effects=(f"rung:{b}", f"mesh:{m}"),
                            )
                        )
        return out
