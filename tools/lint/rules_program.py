"""Whole-program (v3) rules: invariants that live in no single file.

Three defect classes the interprocedural v2 rules cannot see because
they need *repo-global* joins, not just call chains:

* ``retrace-hazard`` — a call into a ``registry.jitted()`` program whose
  batch width is not provably an AOT compile rung.  One unregistered
  shape costs a cold multi-minute XLA compile at runtime (ROADMAP:
  "retrace-safety across jit boundaries"); the proof obligation is
  closed over the call graph, so a raw ``len(sets)`` three calls above
  the dispatch is still caught.
* ``pool-ownership`` — the device-pool lifecycle discipline
  (chain/bls/device_pool.py): state owned by the event loop must not be
  mutated from an executor thread without a threading lock, and a
  stage-release method (the encode-stage token) must be called
  test-and-clear-guarded, with no ``await`` inside the critical section.
* ``metric-label-drift`` — every prometheus metric is registered exactly
  once and every use site passes exactly the declared label set.  Today
  only dashboards are pinned (tests/test_dashboards.py); a drifted call
  site raises ``ValueError`` at runtime on the first scrape-path hit —
  usually inside an error handler, where it shadows the real fault.

All three consume the ModuleSummary raw material extracted by
tools/lint/callgraph.py (width/argument provenance tags, metric
defs/uses, release-guard shapes) and the ``mutates-unlocked`` effect
fixpoint from tools/lint/effects.py.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, ProjectRule, register
from .callgraph import WIDTH_PARAM_RE
from .effects import chain_for, root_site

# where the rung geometry lives; parsed from the project summaries so the
# rule updates itself when the bucket tables change
_BUCKETS_MODULE = "lodestar_tpu.ops.bls12_381.buckets"
# the registry function every served call dispatches a program through
_SERVED_DISPATCH = "lodestar_tpu.aot.registry.call"
# fallback for single-file fixtures that don't include the buckets module
_DEFAULT_RUNGS = frozenset((4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048))
_DEFAULT_STEP = 512


def _in_scope(path: str) -> bool:
    return path.startswith("lodestar_tpu/")


def _jit_connected(s: dict) -> bool:
    """Width vocabulary is only binding in modules actually wired to the
    jit machinery: ones that mint ``registry.jitted()`` wrappers or
    import the bucket-rung module.  The DB layer's keyspace ``Bucket``
    enum and pallas limb ``width`` params reuse the words with entirely
    different meanings — out of scope by construction.  Importing the
    registry whose ``call`` dispatches served programs connects a module
    too."""
    if s.get("jit_wrappers"):
        return True
    registry = _SERVED_DISPATCH.rsplit(".", 1)[0]
    for target in s.get("imports", {}).values():
        if target == _BUCKETS_MODULE or target.startswith(_BUCKETS_MODULE + "."):
            return True
        if target == registry:
            return True
    return False


class _ProgramEnv:
    """Shared joins over a Project the v3 rules all need: function
    summaries by fq name, resolved incoming-call index, the rung set."""

    def __init__(self, project):
        self.project = project
        self.funcs_by_fq: Dict[str, Tuple[dict, dict]] = {}  # fq -> (summary, fs)
        self.incoming: Dict[str, List[Tuple[dict, dict, dict]]] = {}
        for s in project.summaries.values():
            for fs in s["functions"]:
                self.funcs_by_fq[f"{s['module']}:{fs['qname']}"] = (s, fs)
        for s in project.summaries.values():
            for fs in s["functions"]:
                for c in fs.get("calls", ()):
                    for callee in project._resolve_call(s, fs, c["target"]):
                        self.incoming.setdefault(callee, []).append((s, fs, c))
        bks = project.summaries.get(_BUCKETS_MODULE)
        if bks is not None:
            consts = bks.get("module_consts", {})
            rungs = set(consts.get("BUCKETS", ())) | set(
                consts.get("POOL_BUCKETS", ())
            )
            step_vals = consts.get("_STEP", ())
            self.rungs = rungs or set(_DEFAULT_RUNGS)
            self.step = step_vals[0] if step_vals else _DEFAULT_STEP
        else:
            self.rungs = set(_DEFAULT_RUNGS)
            self.step = _DEFAULT_STEP
        self.jit_wrappers: Set[str] = set()
        for s in project.summaries.values():
            self.jit_wrappers.update(s.get("jit_wrappers", ()))


def _env_for(project) -> _ProgramEnv:
    env = getattr(project, "_ll_program_env", None)
    if env is None:
        env = _ProgramEnv(project)
        project._ll_program_env = env
    return env


def _tag_str(tag) -> str:
    kind = tag[0]
    if kind == "const":
        return f"constant {tag[1]}"
    if kind in ("other", "rawlen"):
        return f"`{tag[1]}`" if len(tag) > 1 else "an unprovable expression"
    if kind == "param":
        return f"parameter {tag[1]!r}"
    if kind == "star":
        return "a *starred argument"
    if kind == "all":
        return " / ".join(_tag_str(t) for t in tag[1])
    return kind


# receiver vocabulary that marks a `.set()` receiver as a metric (the
# same judgement silent-except uses for its ambiguous-method whitelist)
_METRICISH = {"metrics", "_metrics", "stats", "m", "beacon", "lodestar"}


def _metricish_chain(chain: str) -> bool:
    return any(
        seg in _METRICISH or "metric" in seg for seg in chain.split(".")
    )


def _rawlen_info(tag) -> Optional[Tuple[str, int]]:
    """(detail, source line) of the first len() in a tag tree, if any."""
    if tag[0] == "rawlen":
        return tag[1], (tag[2] if len(tag) > 2 else 0)
    if tag[0] == "all":
        for t in tag[1]:
            info = _rawlen_info(t)
            if info:
                return info
    return None


@register
class RetraceHazard(ProjectRule):
    id = "retrace-hazard"
    description = (
        "a dispatch into a registry.jitted() program whose batch width "
        "is not provably an AOT bucket rung: the width must flow through "
        "ops/bls12_381/buckets.py (bucket_size/pool_bucket/align_down), "
        "be a registered rung constant, or be a width parameter that "
        "every graph-resolved caller feeds such a value.  A raw "
        "len(sets)-derived width mints one XLA program PER DISTINCT "
        "SIZE at runtime (~15-40 min cold compile each on this host) "
        "that `python -m lodestar_tpu.aot warm` has never heard of — "
        "the interprocedural completion of unregistered-jit.  Unresolved "
        "callers and *args contribute nothing (under-approximation): a "
        "finding is always backed by a concrete provenance failure.  "
        "Local provenance is flow-INsensitive (each name carries its "
        "final binding, matching the extractor's assignment-order "
        "approximation) — reassigning a width name after the dispatch "
        "can shift which site reports; keep one meaning per name"
    )

    # -- provenance judgement -------------------------------------------

    def _tag_ok(self, tag, fq: str, env, memo) -> Tuple[bool, Optional[tuple]]:
        """(quantized?, witness).  A witness is either None (local
        failure — anchor at the binding) or a caller-site tuple
        (path, line, col, detail, callee_fq, param)."""
        kind = tag[0]
        if kind in ("quant", "none"):
            return True, None
        if kind == "const":
            n = tag[1]
            if n in env.rungs or (env.step and n > 0 and n % env.step == 0):
                return True, None
            return False, None
        if kind == "all":
            for t in tag[1]:
                ok, w = self._tag_ok(t, fq, env, memo)
                if not ok:
                    return False, w
            return True, None
        if kind == "param":
            return self._param_ok(fq, tag[1], env, memo)
        if kind == "star":
            return True, None  # alignment unknown: under-approximate
        return False, None  # "other" / "rawlen"

    def _param_ok(self, fq: str, pname: str, env, memo) -> Tuple[bool, Optional[tuple]]:
        key = (fq, pname)
        if key in memo:
            return memo[key]
        memo[key] = (True, None)  # optimistic on cycles (monotone, no churn)
        ent = env.funcs_by_fq.get(fq)
        if ent is None:
            return True, None
        s, fs = ent
        arg_names = fs.get("arg_names", [])
        if pname not in arg_names:
            return True, None
        idx = arg_names.index(pname)
        shift = 1 if (fs.get("cls") and arg_names and arg_names[0] == "self") else 0
        verdict: Tuple[bool, Optional[tuple]] = (True, None)
        for cs, cfs, call in env.incoming.get(fq, ()):
            rec = call.get("kwargs", {}).get(pname)
            if rec is None:
                pos = idx - shift
                args = call.get("args", [])
                if 0 <= pos < len(args):
                    if any(a["tag"][0] == "star" for a in args[: pos + 1]):
                        continue  # positional alignment unknown
                    rec = args[pos]
            if rec is None:
                # caller omits it: the callee default's provenance applies
                d = fs.get("arg_defaults", {}).get(pname)
                if d is None:
                    continue
                ok, w = self._tag_ok(d, fq, env, memo)
                if not ok:
                    verdict = (False, w)
                    break
                continue
            caller_fq = f"{cs['module']}:{cfs['qname']}"
            ok, w = self._tag_ok(rec["tag"], caller_fq, env, memo)
            if not ok:
                if w is None:
                    w = (
                        cs["path"], call["line"], call["col"],
                        _tag_str(rec["tag"]), fq, pname,
                    )
                verdict = (False, w)
                break
        memo[key] = verdict
        return verdict

    # -- the check ------------------------------------------------------

    def _dispatches(self, s: dict, fs: dict, env) -> List[dict]:
        own_wrappers = set(s.get("jit_wrappers", ()))
        aliases = set(fs.get("jit_aliases", ()))
        imports = s.get("imports", {})
        out = []
        for c in fs.get("calls", ()):
            target = c["target"]
            last = target.rsplit(".", 1)[-1]
            head, _, rest = target.partition(".")
            resolved = imports.get(head, head) + ("." + rest if rest else "")
            if resolved == _SERVED_DISPATCH:
                out.append(c)
            elif "." in target:
                if last in env.jit_wrappers:
                    out.append(c)
            elif last in own_wrappers or last in aliases:
                out.append(c)
        return out

    def check_project(self, project) -> List[Finding]:
        env = _env_for(project)
        memo: Dict[tuple, Tuple[bool, Optional[tuple]]] = {}
        out: List[Finding] = []
        seen: Set[tuple] = set()

        def emit(path, line, col, message, chain):
            key = (path, line, col)
            if key in seen or project.suppressed(path, line, self.id):
                return
            seen.add(key)
            out.append(
                Finding(
                    path=path, line=line, col=col, rule=self.id,
                    message=message, effects=("retrace",), chain=tuple(chain),
                )
            )

        for s in sorted(project.summaries.values(), key=lambda s: s["path"]):
            path = s["path"]
            if not _in_scope(path) or path.startswith("lodestar_tpu/aot/"):
                # the aot package IS the registration machinery
                continue
            if not _jit_connected(s):
                continue
            for fs in s["functions"]:
                fq = f"{s['module']}:{fs['qname']}"
                dispatches = self._dispatches(s, fs, env)
                width_params = (
                    []
                    if fs["qname"].endswith("__init__")
                    # a constructor stores dispatch metadata; the padding
                    # happens where tensors are built (reject jobs carry
                    # bucket=0 and never reach the device)
                    else [
                        p for p in fs.get("arg_names", ())
                        if WIDTH_PARAM_RE.search(p)
                    ]
                )
                frames = []
                if dispatches:
                    d = dispatches[0]
                    loop_note = " (inside a loop)" if d.get("in_loop") else ""
                    frames = [
                        f"{path}:{d['line']} {fs['qname']} "
                        f"[dispatches jitted program{loop_note}]"
                    ]
                # each len() root is reported (or suppressed) ONCE per
                # function, whichever pass sees it first — binding,
                # width-kwarg call, or dispatch site
                handled_rawlen: Set[int] = set()

                def rawlen_handled(tag) -> bool:
                    info = _rawlen_info(tag)
                    if info is None:
                        return False
                    if info[1] in handled_rawlen:
                        return True
                    handled_rawlen.add(info[1])
                    # root suppression at the len() line quiets the site
                    return project.suppressed(path, info[1], self.id)

                # 1. width-NAMED locals of seeded functions — but only
                # ones that actually flow onward as a call argument: a
                # byte-count `chunk_size = len(blob)` used for logging
                # in a dispatching function is not a program width
                arg_refs = {
                    rec.get("ref")
                    for c in fs.get("calls", ())
                    for rec in list(c.get("args", ()))
                    + list(c.get("kwargs", {}).values())
                }
                for wl in (
                    fs.get("width_locals", ())
                    if (dispatches or width_params)
                    else ()
                ):
                    if wl["name"] not in arg_refs:
                        continue
                    ok, w = self._tag_ok(wl["tag"], fq, env, memo)
                    if ok:
                        continue
                    if rawlen_handled(wl["tag"]):
                        continue
                    if w is not None:
                        wpath, wline, wcol, detail, callee, pname = w
                        emit(
                            wpath, wline, wcol,
                            f"this call feeds {detail} into width parameter "
                            f"{pname!r} of {callee.split(':')[-1]}() — not "
                            "provably an AOT bucket rung; quantize with "
                            "buckets.bucket_size/pool_bucket before passing",
                            [f"{path}:{wl['line']} {fs['qname']} "
                             f"[width {wl['name']!r} <- param {pname!r}]"]
                            + frames,
                        )
                    else:
                        emit(
                            path, wl["line"], wl["col"],
                            f"width {wl['name']!r} is "
                            f"{_tag_str(wl['tag'])} — not provably an AOT "
                            "bucket rung; derive it via buckets.bucket_size/"
                            "pool_bucket/align_down or a registered rung "
                            "constant so the warm manifest knows the program",
                            frames,
                        )
                # 2. width kwargs at ANY call site in a jit-connected
                # module (e.g. through an untyped self._dv): the kwarg
                # name itself is the contract, no dispatch/width-param
                # seed needed — the value may ride in on a plain param
                for c in fs.get("calls", ()):
                    for kwname, rec in c.get("kwargs", {}).items():
                        if not WIDTH_PARAM_RE.search(kwname):
                            continue
                        ok, w = self._tag_ok(rec["tag"], fq, env, memo)
                        if ok:
                            continue
                        if rawlen_handled(rec["tag"]):
                            continue
                        if w is not None:
                            # the failing value arrives through one of
                            # THIS function's parameters: anchor at the
                            # caller that feeds it (the param need not be
                            # width-named — the kwarg name here is the
                            # contract, so the witness must not be lost)
                            wpath, wline, wcol, detail, callee, pname = w
                            emit(
                                wpath, wline, wcol,
                                f"this call feeds {detail} into parameter "
                                f"{pname!r} of {callee.split(':')[-1]}(), "
                                f"which hands it to a {kwname!r} width "
                                "argument — not provably an AOT bucket "
                                "rung; quantize with buckets.bucket_size/"
                                "pool_bucket before passing",
                                [f"{path}:{c['line']} {fs['qname']} "
                                 f"[{c['target']}(..., {kwname}="
                                 f"{_tag_str(rec['tag'])})]"],
                            )
                            continue
                        emit(
                            path, c["line"], c["col"],
                            f"{c['target']}(..., {kwname}=...) passes "
                            f"{_tag_str(rec['tag'])} — not provably an AOT "
                            "bucket rung; quantize with buckets."
                            "bucket_size/pool_bucket first",
                            [],
                        )
                # 3. arguments AT the dispatch site: a len()-derived
                # value — inline or through a local of any name — is
                # provably a per-call size heading straight into the
                # program's trace key.  (Tensor args are "other"-tagged
                # and stay exempt: only len-provenance is judged here.)
                # A len() already reported — or suppressed — at its
                # binding or a width-kwarg site is not re-reported.
                for d in dispatches:
                    for rec in list(d.get("args", ())) + list(
                        d.get("kwargs", {}).values()
                    ):
                        info = _rawlen_info(rec["tag"])
                        if info is None or rawlen_handled(rec["tag"]):
                            continue
                        loop_note = (
                            " inside a loop" if d.get("in_loop") else ""
                        )
                        emit(
                            path, d["line"], d["col"],
                            f"jitted program dispatched{loop_note} with a "
                            f"len()-derived width (`{info[0]}`): one XLA "
                            "program is minted per distinct input size; "
                            "quantize with buckets.bucket_size/pool_bucket "
                            "first",
                            frames,
                        )
                for p in width_params:
                    ok, w = self._param_ok(fq, p, env, memo)
                    if ok or w is None:
                        continue
                    wpath, wline, wcol, detail, callee, pname = w
                    emit(
                        wpath, wline, wcol,
                        f"this call feeds {detail} into width parameter "
                        f"{pname!r} of {callee.split(':')[-1]}() — not "
                        "provably an AOT bucket rung; quantize with "
                        "buckets.bucket_size/pool_bucket before passing",
                        [f"{env.funcs_by_fq[callee][0]['path']}:"
                         f"{env.funcs_by_fq[callee][1]['line']} "
                         f"{callee.split(':')[-1]} [width parameter {pname!r}]"],
                    )
        return out


@register
class PoolOwnership(ProjectRule):
    id = "pool-ownership"
    description = (
        "device-pool/queue lifecycle discipline: (a) a callable handed "
        "to run_in_executor / threading.Thread that (transitively) "
        "mutates self.*/global state with no threading lock held — the "
        "event loop owns that state and a racing executor thread "
        "corrupts it (asyncio.Lock does not protect cross-thread); "
        "(b) a stage-release method (one that flips a self-owned "
        "ownership flag False, e.g. the encode-stage token) called "
        "without the test-and-clear guard — double-release wakes two "
        "packs into one stage; (c) an await inside the token-guarded "
        "critical section — the stage is neither owned nor released "
        "while the task is suspended"
    )

    def check_project(self, project) -> List[Finding]:
        env = _env_for(project)
        out: List[Finding] = []

        def suppressed(path, line):
            return project.suppressed(path, line, self.id)

        for s in sorted(project.summaries.values(), key=lambda s: s["path"]):
            path = s["path"]
            if not _in_scope(path):
                continue
            release_defs = set(s.get("release_defs", ()))
            for fs in s["functions"]:
                # (a) executor-dispatched callables
                for c in fs.get("calls", ()):
                    last = c["target"].rsplit(".", 1)[-1]
                    rec = None
                    if last == "run_in_executor":
                        args = c.get("args", [])
                        if len(args) >= 2:
                            rec = args[1]
                    elif last == "Thread":
                        rec = c.get("kwargs", {}).get("target")
                    if rec is None or "ref" not in rec:
                        continue
                    for callee in project._resolve_call(s, fs, rec["ref"]):
                        fn = project.funcs.get(callee)
                        if fn is None:
                            continue
                        direct = "mutates-unlocked" in fn.effects
                        inherited = "mutates-unlocked" in project.inherited.get(
                            callee, {}
                        )
                        if not (direct or inherited):
                            continue
                        if suppressed(path, c["line"]):
                            continue
                        root = root_site(project, callee, "mutates-unlocked")
                        if root and project.suppressed(
                            root[0], root[1], self.id
                        ):
                            continue
                        out.append(
                            Finding(
                                path=path, line=c["line"], col=c["col"],
                                rule=self.id,
                                message=(
                                    f"{rec['ref']} runs on an executor "
                                    "thread but mutates loop-owned state "
                                    "with no threading lock — see the "
                                    "chain; move the mutation back to the "
                                    "loop (call_soon_threadsafe) or guard "
                                    "it with a threading.Lock"
                                ),
                                effects=("mutates-unlocked",),
                                chain=tuple(
                                    [f"{path}:{c['line']} {fs['qname']} "
                                     "[dispatches to executor]"]
                                    + chain_for(
                                        project, callee, "mutates-unlocked"
                                    )
                                ),
                            )
                        )
                        break  # one finding per dispatch site
                # (b)+(c) stage-release token discipline
                for rc in fs.get("release_calls", ()):
                    if rc["method"] not in release_defs:
                        continue
                    if fs["qname"].split(".")[-1] == rc["method"]:
                        continue  # the release method's own body
                    if not (rc["guarded"] and rc["cleared"]):
                        if suppressed(path, rc["line"]):
                            continue
                        out.append(
                            Finding(
                                path=path, line=rc["line"], col=rc["col"],
                                rule=self.id,
                                message=(
                                    f"{rc['recv']}.{rc['method']}() without "
                                    "testing-and-clearing the ownership "
                                    "token first — a second caller can "
                                    "release the same stage twice; use "
                                    "`if owner[...]: owner[...] = False; "
                                    f"{rc['method']}()`"
                                ),
                                effects=("ownership",),
                            )
                        )
                    elif rc.get("await_line"):
                        if suppressed(path, rc["line"]):
                            continue
                        out.append(
                            Finding(
                                path=path, line=rc["line"], col=rc["col"],
                                rule=self.id,
                                message=(
                                    "await inside the ownership-release "
                                    f"critical section (line "
                                    f"{rc['await_line']}): between token "
                                    "clear and stage release the stage is "
                                    "neither owned nor released while this "
                                    "task is suspended — keep the guard "
                                    "body await-free"
                                ),
                                effects=("ownership",),
                            )
                        )
        return out


@register
class MetricLabelDrift(ProjectRule):
    id = "metric-label-drift"
    description = (
        "prometheus metric registration/use drift, whole-program: a "
        "metric name registered at more than one construction site "
        "(duplicate time series / ValueError on a shared registry), a "
        "use site whose .labels(...) names don't match the declared "
        "label set, .labels() on an unlabeled metric, or inc/dec/"
        "observe/set directly on a labeled metric (prometheus raises "
        "ValueError at runtime — usually inside the error handler the "
        "metric was meant to make visible).  Dashboards are pinned by "
        "tests/test_dashboards.py; this closes the call-site half"
    )

    def check_project(self, project) -> List[Finding]:
        out: List[Finding] = []
        by_attr: Dict[str, List[Tuple[str, dict]]] = {}
        by_name: Dict[str, List[Tuple[str, dict]]] = {}
        for s in sorted(project.summaries.values(), key=lambda s: s["path"]):
            if not _in_scope(s["path"]):
                continue
            for d in s.get("metric_defs", ()):
                by_attr.setdefault(d["attr"], []).append((s["path"], d))
                if d["name"]:
                    by_name.setdefault(d["name"], []).append((s["path"], d))

        for name, sites in sorted(by_name.items()):
            if len(sites) <= 1:
                continue
            first = sites[0]
            for path, d in sites[1:]:
                if project.suppressed(path, d["line"], self.id):
                    continue
                out.append(
                    Finding(
                        path=path, line=d["line"], col=d["col"], rule=self.id,
                        message=(
                            f"metric {name!r} is registered more than once "
                            f"(first at {first[0]}:{first[1]['line']}); on a "
                            "shared registry the second registration raises "
                            "— every metric has exactly one home"
                        ),
                        effects=("metrics",),
                        chain=(f"{first[0]}:{first[1]['line']} "
                               f"[first registration of {name!r}]",),
                    )
                )

        for s in sorted(project.summaries.values(), key=lambda s: s["path"]):
            path = s["path"]
            if not _in_scope(path):
                continue
            for fs in s["functions"]:
                for use in fs.get("metric_uses", ()):
                    defs = by_attr.get(use["attr"])
                    if not defs:
                        continue
                    labelsets = [
                        d["labels"] for _, d in defs if d["labels"] is not None
                    ]
                    if not labelsets:
                        continue  # statically unresolvable declarations
                    anchor = defs[0]
                    if use["op"] == "labels":
                        if all(ls == [] for ls in labelsets):
                            if project.suppressed(path, use["line"], self.id):
                                continue
                            out.append(
                                Finding(
                                    path=path, line=use["line"],
                                    col=use["col"], rule=self.id,
                                    message=(
                                        f".labels() on {use['attr']!r}, "
                                        "which is registered without "
                                        "labels — prometheus raises at "
                                        "runtime"
                                    ),
                                    effects=("metrics",),
                                    chain=(
                                        f"{anchor[0]}:{anchor[1]['line']} "
                                        f"[{use['attr']} registered here]",
                                    ),
                                )
                            )
                            continue
                        n, kws = use["nargs"], use["kwnames"]
                        matched = any(
                            (
                                sorted(ls) == kws
                                if kws and not n
                                else len(ls) == n
                                if n and not kws
                                else len(ls) == n + len(kws)
                                and set(kws) <= set(ls)
                            )
                            for ls in labelsets
                            if ls
                        )
                        if not matched:
                            if project.suppressed(path, use["line"], self.id):
                                continue
                            declared = next(ls for ls in labelsets if ls)
                            passed = kws if kws else f"{n} positional"
                            out.append(
                                Finding(
                                    path=path, line=use["line"],
                                    col=use["col"], rule=self.id,
                                    message=(
                                        f"{use['attr']}.labels({passed}) "
                                        "does not match the declared label "
                                        f"set {declared} — the series this "
                                        "writes is not the one the "
                                        "dashboard reads"
                                    ),
                                    effects=("metrics",),
                                    chain=(
                                        f"{anchor[0]}:{anchor[1]['line']} "
                                        f"[{use['attr']} declares labels "
                                        f"{declared}]",
                                    ),
                                )
                            )
                    else:  # inc/dec/observe/set directly on the parent
                        if use["op"] == "set" and not _metricish_chain(
                            use.get("chain", "")
                        ):
                            # `.set()` is also an Event/Future verb: an
                            # attr-name collision with a labeled gauge on
                            # a non-metric receiver is not drift
                            continue
                        if all(ls for ls in labelsets):
                            if project.suppressed(path, use["line"], self.id):
                                continue
                            out.append(
                                Finding(
                                    path=path, line=use["line"],
                                    col=use["col"], rule=self.id,
                                    message=(
                                        f".{use['op']}() directly on labeled "
                                        f"metric {use['attr']!r} (labels "
                                        f"{labelsets[0]}) — prometheus "
                                        "raises ValueError; go through "
                                        ".labels(...) first"
                                    ),
                                    effects=("metrics",),
                                    chain=(
                                        f"{anchor[0]}:{anchor[1]['line']} "
                                        f"[{use['attr']} declares labels "
                                        f"{labelsets[0]}]",
                                    ),
                                )
                            )
        return out
