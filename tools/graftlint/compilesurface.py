"""Compile-surface pass: GL15 + GL16 + GL17 and the warmup manifest.

PR 15's NEWVIEW wedge was a COMPILE reachability bug: the first
view-change at a new committee width handed XLA a program shape nobody
had compiled, on the consensus pump thread, and every validator hung
~90s.  The runtime fix (breaker-guarded dispatch) made the wedge
survivable; this pass makes the CLASS statically impossible by treating
the jit surface as an enumerable, machine-checked artifact:

  GL15  bucket derivability — every *program site* (an f-string program
        name flowing into ``device._program_first_use`` or an
        ``aot.resolve/compiled/warm`` lookup) must have each
        placeholder's value set derivable from a pinned bucket registry
        (a module-level int tuple) through declared *bucket functions*
        (``# graftlint: bucket-fn registry=NAME[,NAME]`` — the pass
        VERIFIES every return of such a function stays inside its
        registry; an escaping return is the static generalization of
        committee_bucket's old unbounded overflow tail).  A placeholder
        fed by ``len(...)``, a raw argument, arithmetic or an
        undeclared call is exactly the NEWVIEW class: unbounded shapes
        reachable from serving paths.

  GL16  manifest coverage — the cross product of every derivable
        site's bucket domains IS the warmup manifest
        (tools/artifacts/aot/compile_manifest.json).  Derived programs
        missing from the committed manifest, and committed names no
        longer derivable, both fail the gate;
        ``python -m tools.graftlint --emit-compile-manifest`` emits the
        canonical JSON and CI diffs it against the committed copy.

  GL17  compile locality — ``.lower(args)`` / ``.lower().compile()``
        chains, first-traces of jit-bound callables and bare compile
        heads (jax.jit / pjit / pmap / shard_map / pallas_call) are
        flagged outside the sanctioned device layer
        (device.py, aot.py, ops/, parallel/) unless the enclosing
        function is annotated ``# graftlint: compile-phase=warmup`` (a
        startup precompile) or ``compile-phase=diagnostic`` (an
        armed-profiler-only recompile, never on the serving path).
        Files outside harmony_tpu/ opt in with a module-level
        ``# graftlint: compile-zone=serving`` marker (fixture /
        smoke-tool discipline, mirroring kernelcheck's kernel-module
        opt-in).

Static assumptions, both load-bearing and documented in
docs/ANALYSIS.md: ``kernel_twin_active()`` evaluates False (twin mode
keeps jax unloaded by contract, so twin-only widths are not XLA
programs — aot.warmup marks them separately), and an ``X if t else Y``
placeholder assignment is refined to one branch only when the
consuming sink is itself guarded by a structurally identical test.
"""

from __future__ import annotations

import ast
import itertools
import json
import re
from pathlib import Path

from .interproc import Program, SiteFinding
from .rules import dotted_name
from .threadrole import (
    _Index,
    _own_nodes,
    _role_annotations,
    _spawn_role,
)

REPO_ROOT = Path(__file__).resolve().parents[2]
MANIFEST_RELPATH = "tools/artifacts/aot/compile_manifest.json"
MANIFEST_PATH = REPO_ROOT / MANIFEST_RELPATH

_BUCKET_FN_RE = re.compile(
    r"graftlint:\s*bucket-fn\s+registry=([A-Za-z0-9_,\s]+)")
_PHASE_RE = re.compile(
    r"graftlint:\s*compile-phase=(warmup|diagnostic)")
_ZONE_RE = re.compile(r"graftlint:\s*compile-zone=([A-Za-z0-9_.\-]+)")

# the sanctioned compile layer: the guarded dispatch switch, the AOT
# cache/warmup, the kernel programs and the mesh shardings themselves
_SANCTIONED_FILES = {"harmony_tpu/device.py", "harmony_tpu/aot.py"}
_SANCTIONED_PREFIXES = ("harmony_tpu/ops/", "harmony_tpu/parallel/")

_COMPILE_HEADS = {
    "jax.jit", "jit", "pjit", "jax.pmap", "pmap",
    "jax.shard_map", "shard_map",
}
_AOT_SINK_ATTRS = {"resolve", "compiled", "warm"}

# the thread roles whose cones ARE the serving plane (witness detail
# for findings; program sites in the device layer are always in scope
# — that layer exists to serve these roles)
_SERVING_ROLES = {
    "consensus.pump", "sched.flush", "sidecar.reader", "serving",
}

_NAME_CAP = 4096  # cross-product backstop: beyond this it is unbounded


def _compile_sanctioned(relpath: str) -> bool:
    return (relpath in _SANCTIONED_FILES
            or relpath.startswith(_SANCTIONED_PREFIXES))


def _head_of(expr) -> str | None:
    """The compile-head name of ``expr`` (a call or a bare decorator
    expression), seeing through functools.partial(jax.jit, ...)."""
    if isinstance(expr, ast.Call):
        h = dotted_name(expr.func)
        if h:
            if h in _COMPILE_HEADS or h.split(".")[-1] == "pallas_call":
                return h
            if h.split(".")[-1] == "partial" and expr.args:
                inner = dotted_name(expr.args[0])
                if inner and (inner in _COMPILE_HEADS
                              or inner.split(".")[-1] == "pallas_call"):
                    return inner
        return None
    h = dotted_name(expr)
    if h and (h in _COMPILE_HEADS or h.split(".")[-1] == "pallas_call"):
        return h
    return None


# -- per-module facts --------------------------------------------------------


class _ModFacts:
    """Module-level bucket registries (int tuples), int constants, and
    the annotation line maps the pass keys on."""

    def __init__(self, mi):
        self.registries: dict[str, tuple] = {}
        self.int_consts: dict[str, int] = {}
        for node in mi.tree.body:
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                continue
            name, val = node.targets[0].id, node.value
            if (isinstance(val, ast.Tuple) and val.elts
                    and all(isinstance(e, ast.Constant)
                            and isinstance(e.value, int)
                            for e in val.elts)):
                self.registries[name] = tuple(e.value for e in val.elts)
            elif (isinstance(val, ast.Constant)
                  and isinstance(val.value, int)
                  and not isinstance(val.value, bool)):
                self.int_consts[name] = val.value
        self.bucket_annos: dict[int, list] = {}
        self.phase_annos: dict[int, str] = {}
        self.zone: str | None = None
        for lineno, line in enumerate(mi.source.splitlines(), start=1):
            m = _BUCKET_FN_RE.search(line)
            if m:
                self.bucket_annos[lineno] = [
                    n.strip() for n in m.group(1).split(",") if n.strip()
                ]
            m = _PHASE_RE.search(line)
            if m:
                self.phase_annos[lineno] = m.group(1)
            m = _ZONE_RE.search(line)
            if m and self.zone is None:
                self.zone = m.group(1)


def _def_anno(node, annos: dict):
    """An annotation on the ``def`` line or the line directly above it
    (above any decorators, matching the bucket-fn grammar's examples)."""
    first = node.lineno
    if node.decorator_list:
        first = min(d.lineno for d in node.decorator_list)
    for ln in (node.lineno, first - 1, node.lineno - 1):
        if ln in annos:
            return annos[ln]
    return None


# -- the analysis ------------------------------------------------------------


class _Surface:
    def __init__(self, prog: Program):
        self.prog = prog
        self.idx = _Index(prog)
        self.idx.finalize()
        self.facts = {rel: _ModFacts(mi)
                      for rel, mi in prog.modules.items()}
        self.bucket_fns: dict[str, dict] = {}
        self.violations: list[SiteFinding] = []
        self.sites: list[dict] = []
        self.heads: list[dict] = []
        self.cone: dict[str, str] = {}
        self._collect_bucket_fns()
        self._collect_cone()
        self._collect_sites_and_heads()

    # -- registries / bucket functions ---------------------------------------

    def _registry(self, mi, name):
        """Resolve a registry NAME in module ``mi`` to its int tuple."""
        f = self.facts[mi.relpath]
        if name in f.registries:
            return f.registries[name]
        if name in mi.name_imports:
            modpath, orig = mi.name_imports[name]
            tgt = self.prog.modules.get(modpath)
            if tgt is not None:
                return self.facts[tgt.relpath].registries.get(orig)
        return None

    def _collect_bucket_fns(self):
        annotated = []
        for xf in self.idx.funcs.values():
            names = _def_anno(xf.node, self.facts[xf.relpath].bucket_annos)
            if names is None:
                continue
            mi = self.prog.modules[xf.relpath]
            domain: set = set()
            declared: dict[str, tuple] = {}
            for rname in names:
                reg = self._registry(mi, rname)
                if reg is None:
                    self.violations.append(SiteFinding(
                        xf.relpath, "GL15", xf.node.lineno,
                        xf.node.col_offset,
                        f"bucket-fn declares registry '{rname}' which is "
                        f"not a module-level int-tuple constant",
                        xf.qualname))
                    continue
                declared[rname] = reg
                domain.update(reg)
            self.bucket_fns[xf.fid] = {
                "declared": declared, "domain": domain, "kind": None,
            }
            annotated.append(xf)
        # pass 1: registry-valued fns (return a whole registry tuple)
        for xf in annotated:
            info = self.bucket_fns[xf.fid]
            rets = [n for n in _own_nodes(xf.node)
                    if isinstance(n, ast.Return) and n.value is not None]
            if rets and all(self._is_registry_expr(xf, r.value)
                            for r in rets):
                info["kind"] = "registry"
        # pass 2: verify element-valued returns stay inside the registry
        for xf in annotated:
            info = self.bucket_fns[xf.fid]
            if info["kind"] == "registry":
                continue
            info["kind"] = "element"
            loopvars = self._registry_loopvars(xf)
            for n in _own_nodes(xf.node):
                if not isinstance(n, ast.Return) or n.value is None:
                    continue
                bad = self._escaping_return(xf, n.value, loopvars,
                                            info["domain"])
                if bad:
                    self.violations.append(SiteFinding(
                        xf.relpath, "GL15", n.lineno, n.col_offset,
                        f"bucket-fn return escapes its declared "
                        f"registry: {bad}", xf.qualname))

    def _is_registry_expr(self, xf, expr) -> bool:
        """Is ``expr`` (a return value) a declared-registry tuple?"""
        if isinstance(expr, ast.IfExp):
            return (self._is_registry_expr(xf, expr.body)
                    and self._is_registry_expr(xf, expr.orelse))
        if isinstance(expr, ast.Name):
            info = self.bucket_fns.get(xf.fid, {})
            return expr.id in info.get("declared", {})
        return False

    def _registry_iter(self, xf, it) -> bool:
        """Is ``it`` (a for-loop iterable) registry-backed?"""
        info = self.bucket_fns.get(xf.fid, {})
        if isinstance(it, ast.Name) and it.id in info.get("declared", {}):
            return True
        if isinstance(it, ast.Call):
            mi = self.prog.modules[xf.relpath]
            for fid in self.idx._resolve_call(mi, xf, it):
                tgt = self.bucket_fns.get(fid)
                if tgt is not None and tgt["kind"] == "registry":
                    return True
        return False

    def _registry_loopvars(self, xf) -> set:
        out = set()
        for n in _own_nodes(xf.node):
            if (isinstance(n, ast.For)
                    and isinstance(n.target, ast.Name)
                    and self._registry_iter(xf, n.iter)):
                out.add(n.target.id)
        return out

    def _escaping_return(self, xf, expr, loopvars, domain) -> str | None:
        """None when the return provably stays inside the registry,
        else a short description of the escape."""
        if isinstance(expr, ast.IfExp):
            return (self._escaping_return(xf, expr.body, loopvars, domain)
                    or self._escaping_return(xf, expr.orelse, loopvars,
                                             domain))
        if isinstance(expr, ast.Name):
            if expr.id in loopvars:
                return None
            return f"name '{expr.id}' is not a registry loop variable"
        if isinstance(expr, ast.Constant):
            if isinstance(expr.value, int) and expr.value in domain:
                return None
            return f"constant {expr.value!r} outside the registry"
        if isinstance(expr, ast.Subscript):
            if self._registry_iter(xf, expr.value) or (
                    isinstance(expr.value, ast.Name)
                    and expr.value.id in self.bucket_fns.get(
                        xf.fid, {}).get("declared", {})):
                return None
            return "subscript of a non-registry value"
        if isinstance(expr, ast.Call):
            mi = self.prog.modules[xf.relpath]
            for fid in self.idx._resolve_call(mi, xf, expr):
                if fid in self.bucket_fns:
                    return None
            h = dotted_name(expr.func) or "<call>"
            return f"call to undeclared function {h}()"
        return ast.dump(expr)[:60]

    # -- serving cone --------------------------------------------------------

    def _collect_cone(self):
        roles_by_mod = {
            rel: _role_annotations(mi.source)
            for rel, mi in self.prog.modules.items()
        }
        roots = []
        for xf in self.idx.funcs.values():
            mi = self.prog.modules[xf.relpath]
            for spawn in xf.spawns:
                role = _spawn_role(spawn, roles_by_mod[xf.relpath])
                if role not in _SERVING_ROLES:
                    continue
                tkw = next((k.value for k in spawn.keywords
                            if k.arg == "target"), None)
                tgt = self.idx.resolve_target(mi, xf, tkw) \
                    if tkw is not None else None
                if tgt is not None:
                    roots.append((tgt, role))
        for tgt, role in roots:
            for fid, chain in self.idx.reach(tgt).items():
                label = f"{role}: {chain}" if chain else role
                self.cone.setdefault(fid, label)
        # close over nested defs: a reached dispatcher's closures run on
        # the same thread (the inverse of GL12's passed-not-called trick)
        frontier = list(self.cone)
        while frontier:
            fid = frontier.pop()
            xf = self.idx.funcs.get(fid)
            if xf is None:
                continue
            base = self.cone[fid]
            for nfid in xf.nested.values():
                if nfid in self.cone:
                    continue
                self.cone[nfid] = base
                frontier.append(nfid)
                for rfid, chain in self.idx.reach(nfid).items():
                    if rfid not in self.cone:
                        self.cone[rfid] = (
                            f"{base} -> {chain}" if chain else base)
                        frontier.append(rfid)

    def _in_cone(self, xf) -> str | None:
        p = xf
        while p is not None:
            if p.fid in self.cone:
                return self.cone[p.fid]
            p = p.parent
        return None

    # -- program sites + compile heads ---------------------------------------

    def _site_eligible(self, xf) -> bool:
        if not xf.relpath.startswith("harmony_tpu/"):
            return True  # fixtures / tools opt in by using the sinks
        return (_compile_sanctioned(xf.relpath)
                or self._in_cone(xf) is not None)

    def _collect_sites_and_heads(self):
        by_js: dict[int, dict] = {}  # id(JoinedStr) -> site
        for fid in sorted(self.idx.funcs):
            xf = self.idx.funcs[fid]
            mi = self.prog.modules[xf.relpath]
            self._scan_heads(xf, mi)
            if not self._site_eligible(xf):
                continue
            for node in _own_nodes(xf.node):
                if not isinstance(node, ast.Call):
                    continue
                if not self._is_sink(mi, node):
                    continue
                if not node.args:
                    continue
                arg = node.args[0]
                trues = _guard_tests(xf, node)
                js_list = []
                if isinstance(arg, ast.JoinedStr):
                    js_list = [(arg, xf)]
                elif isinstance(arg, ast.Name):
                    js_list = self._name_joinedstrs(xf, arg.id)
                for js, owner in js_list:
                    site = by_js.get(id(js))
                    if site is None:
                        site = {
                            "js": js, "xf": owner,
                            "relpath": owner.relpath,
                            "line": js.lineno, "col": js.col_offset,
                            "trues": [],
                        }
                        by_js[id(js)] = site
                        self.sites.append(site)
                    site["trues"].append(trues)
        for site in self.sites:
            self._derive_site(site)
        self.sites.sort(key=lambda s: (s["relpath"], s["line"]))

    def _is_sink(self, mi, call: ast.Call) -> bool:
        head = dotted_name(call.func)
        if not head:
            return False
        parts = head.split(".")
        if parts[-1] == "_program_first_use":
            return True
        if parts[-1] in _AOT_SINK_ATTRS and len(parts) > 1:
            root = parts[0]
            if root == "aot":
                return True
            tgt = mi.mod_imports.get(root)
            return isinstance(tgt, str) and tgt.endswith("aot.py")
        return False

    def _name_joinedstrs(self, xf, name):
        """Every JoinedStr assigned to ``name`` in xf's lexical chain."""
        out = []
        p = xf
        while p is not None:
            for n in _own_nodes(p.node):
                if not isinstance(n, ast.Assign):
                    continue
                for tgt in n.targets:
                    if (isinstance(tgt, ast.Name) and tgt.id == name
                            and isinstance(n.value, ast.JoinedStr)):
                        out.append((n.value, p))
            if out:
                return out
            p = p.parent
        return out

    def _scan_heads(self, xf, mi):
        if not xf.relpath.startswith("harmony_tpu/"):
            return
        for dec in getattr(xf.node, "decorator_list", []):
            h = _head_of(dec)
            if h:
                self.heads.append({
                    "path": xf.relpath, "context": xf.qualname,
                    "kind": h, "line": dec.lineno,
                })
        for node in _own_nodes(xf.node):
            if isinstance(node, ast.Call):
                h = _head_of(node)
                if h:
                    self.heads.append({
                        "path": xf.relpath, "context": xf.qualname,
                        "kind": h, "line": node.lineno,
                    })

    # -- bucket-domain derivation --------------------------------------------

    def _derive_site(self, site):
        js, xf = site["js"], site["xf"]
        family_parts, fvs = [], []
        for v in js.values:
            if isinstance(v, ast.Constant):
                family_parts.append(str(v.value))
            elif isinstance(v, ast.FormattedValue):
                family_parts.append("{}")
                fvs.append(v)
        site["family"] = "".join(family_parts)
        domains, reason = [], None
        for fv in fvs:
            dom: set = set()
            why = None
            for trues in site["trues"] or [set()]:
                d, w = self._domain(xf, fv.value, trues, 0)
                if d is None:
                    dom, why = None, w
                    break
                dom.update(d)
            if dom is None:
                reason = why
                site["bad_expr"] = fv
                break
            domains.append(dom)
        if reason is not None:
            site["names"], site["reason"] = None, reason
            return
        total = 1
        for d in domains:
            total *= max(len(d), 1)
        if total > _NAME_CAP:
            site["names"] = None
            site["reason"] = (f"bucket cross-product has {total} members "
                              f"(cap {_NAME_CAP}) — effectively unbounded")
            return
        names = set()
        for combo in itertools.product(
                *[sorted(d) for d in domains]) if domains else [()]:
            out, it = [], iter(combo)
            for part in family_parts:
                out.append(str(next(it)) if part == "{}" else part)
            names.add("".join(out))
        site["names"], site["reason"] = names, None
        site["domains"] = [sorted(d) for d in domains]

    def _domain(self, xf, expr, trues, depth):
        """(value set, None) when derivable, (None, reason) when not."""
        if depth > 8:
            return None, "derivation depth exceeded"
        mi = self.prog.modules[xf.relpath]
        f = self.facts[xf.relpath]
        if isinstance(expr, ast.Constant):
            if isinstance(expr.value, int) \
                    and not isinstance(expr.value, bool):
                return {expr.value}, None
            return None, f"non-int constant {expr.value!r}"
        if isinstance(expr, ast.IfExp):
            cond = _eval_test(expr.test, trues)
            if cond is True:
                return self._domain(xf, expr.body, trues, depth + 1)
            if cond is False:
                return self._domain(xf, expr.orelse, trues, depth + 1)
            a, wa = self._domain(xf, expr.body, trues, depth + 1)
            if a is None:
                return None, wa
            b, wb = self._domain(xf, expr.orelse, trues, depth + 1)
            if b is None:
                return None, wb
            return a | b, None
        if isinstance(expr, ast.Name):
            return self._name_domain(xf, expr.id, trues, depth)
        if isinstance(expr, ast.Call):
            head = dotted_name(expr.func) or "<call>"
            if head.split(".")[-1] == "len":
                return None, "len() of runtime data (unpinned width)"
            for fid in self.idx._resolve_call(mi, xf, expr):
                info = self.bucket_fns.get(fid)
                if info is not None:
                    return set(info["domain"]), None
            return None, f"call to {head}() which is not a declared " \
                         f"bucket-fn"
        if isinstance(expr, ast.Attribute):
            return self._attr_domain(xf, expr, trues, depth)
        if isinstance(expr, ast.BinOp):
            return None, "arithmetic on runtime values"
        return None, f"underivable expression ({type(expr).__name__})"

    def _name_domain(self, xf, name, trues, depth):
        f = self.facts[xf.relpath]
        mi = self.prog.modules[xf.relpath]
        assigns = []
        p = xf
        while p is not None:
            for n in _own_nodes(p.node):
                if isinstance(n, ast.Assign):
                    rhs = _unpack_assign(n, name)
                    if rhs is not None:
                        assigns.append((p, rhs))
                elif (isinstance(n, ast.AnnAssign) and n.value is not None
                      and isinstance(n.target, ast.Name)
                      and n.target.id == name):
                    assigns.append((p, n.value))
                elif (isinstance(n, ast.For)
                      and isinstance(n.target, ast.Name)
                      and n.target.id == name
                      and self._registry_iter(p, n.iter)):
                    dom = set()
                    info = self.bucket_fns.get(p.fid, {})
                    for reg in info.get("declared", {}).values():
                        dom.update(reg)
                    assigns.append((p, dom))
            if assigns:
                break
            p = p.parent
        if assigns:
            out: set = set()
            for owner, rhs in assigns:
                if isinstance(rhs, set):
                    out.update(rhs)
                    continue
                d, why = self._domain(owner, rhs, trues, depth + 1)
                if d is None:
                    return None, why
                out.update(d)
            return out, None
        if name in f.int_consts:
            return {f.int_consts[name]}, None
        if name in f.registries:
            return set(f.registries[name]), None
        if name in mi.name_imports:
            modpath, orig = mi.name_imports[name]
            tgt = self.prog.modules.get(modpath)
            if tgt is not None:
                tf = self.facts[tgt.relpath]
                if orig in tf.int_consts:
                    return {tf.int_consts[orig]}, None
                if orig in tf.registries:
                    return set(tf.registries[orig]), None
        if _is_param(xf, name):
            return None, f"function argument '{name}' with no bucket " \
                         f"derivation"
        return None, f"name '{name}' has no derivable binding"

    def _attr_domain(self, xf, expr, trues, depth):
        if not isinstance(expr.value, ast.Name):
            return None, "chained attribute access on runtime value"
        base, attr = expr.value.id, expr.attr
        mi = self.prog.modules[xf.relpath]
        # module constant through an import alias (DV._VERIFY_BUCKET)
        tgtmod = mi.mod_imports.get(base)
        if isinstance(tgtmod, str) and tgtmod in self.prog.modules:
            tf = self.facts[tgtmod]
            if attr in tf.int_consts:
                return {tf.int_consts[attr]}, None
            if attr in tf.registries:
                return set(tf.registries[attr]), None
        ann = _param_annotation(xf, base)
        if ann is None:
            return None, (f"attribute {base}.{attr} of a value with no "
                          f"class annotation")
        cls_mi, cls = self._resolve_class(mi, ann)
        if cls is None:
            return None, f"annotated class '{ann}' not found in program"
        out: set = set()
        found = False
        for fid in cls["methods"].values():
            mxf = self.idx.funcs.get(fid)
            if mxf is None:
                continue
            for n in _own_nodes(mxf.node):
                if not isinstance(n, ast.Assign):
                    continue
                for tgt in n.targets:
                    if (isinstance(tgt, ast.Attribute)
                            and isinstance(tgt.value, ast.Name)
                            and tgt.value.id == "self"
                            and tgt.attr == attr):
                        found = True
                        d, why = self._domain(mxf, n.value, set(),
                                              depth + 1)
                        if d is None:
                            return None, (f"{ann}.{attr} assignment is "
                                          f"not bucket-derived: {why}")
                        out.update(d)
        if not found:
            return None, f"no 'self.{attr} =' assignment found in {ann}"
        return out, None

    def _resolve_class(self, mi, name):
        if name in mi.classes:
            return mi, mi.classes[name]
        if name in mi.name_imports:
            modpath, orig = mi.name_imports[name]
            tgt = self.prog.modules.get(modpath)
            if tgt is not None and orig in tgt.classes:
                return tgt, tgt.classes[orig]
        return None, None


def _unpack_assign(n: ast.Assign, name):
    """The RHS expr bound to ``name`` by this Assign (tuple-to-tuple
    unpacking resolved positionally), or None."""
    for tgt in n.targets:
        if isinstance(tgt, ast.Name) and tgt.id == name:
            return n.value
        if isinstance(tgt, ast.Tuple) and isinstance(n.value, ast.Tuple) \
                and len(tgt.elts) == len(n.value.elts):
            for t, v in zip(tgt.elts, n.value.elts):
                if isinstance(t, ast.Name) and t.id == name:
                    return v
    return None


def _is_param(xf, name) -> bool:
    p = xf
    while p is not None:
        a = p.node.args
        for arg in (a.posonlyargs + a.args + a.kwonlyargs
                    + ([a.vararg] if a.vararg else [])
                    + ([a.kwarg] if a.kwarg else [])):
            if arg.arg == name:
                return True
        p = p.parent
    return False


def _param_annotation(xf, name) -> str | None:
    p = xf
    while p is not None:
        a = p.node.args
        for arg in a.posonlyargs + a.args + a.kwonlyargs:
            if arg.arg == name and arg.annotation is not None:
                return dotted_name(arg.annotation)
        p = p.parent
    return None


def _add_test(trues: set, test) -> None:
    """A dominating ``A and B`` guard means both conjuncts hold, so a
    placeholder tested on the bare conjunct (``x if fused else ...``
    under ``if fused and not twin():``) still refines."""
    trues.add(ast.dump(test))
    if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
        for v in test.values:
            _add_test(trues, v)


def _guard_tests(xf, target) -> set:
    """ast.dump of every test that dominates ``target`` (IfExp body /
    If body containment within xf's own nodes)."""
    trues = set()
    for n in _own_nodes(xf.node):
        if isinstance(n, ast.IfExp) and _contains(n.body, target):
            _add_test(trues, n.test)
        elif isinstance(n, ast.If) \
                and any(_contains(s, target) for s in n.body):
            _add_test(trues, n.test)
    return trues


def _contains(root, target) -> bool:
    return any(n is target for n in ast.walk(root))


def _eval_test(test, trues):
    """Three-valued static evaluation of a guard under the sink's
    dominating tests.  kernel_twin_active() is statically False: twin
    mode keeps jax unloaded by contract, so twin-only branches are not
    XLA programs (aot.warmup accounts for them separately)."""
    if ast.dump(test) in trues:
        return True
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        inner = _eval_test(test.operand, trues)
        return None if inner is None else not inner
    if isinstance(test, ast.Call):
        h = dotted_name(test.func)
        if h and h.split(".")[-1] == "kernel_twin_active":
            return False
        return None
    if isinstance(test, ast.BoolOp):
        vals = [_eval_test(v, trues) for v in test.values]
        if isinstance(test.op, ast.And):
            if any(v is False for v in vals):
                return False
            if all(v is True for v in vals):
                return True
            return None
        if any(v is True for v in vals):
            return True
        if all(v is False for v in vals):
            return False
    return None


# -- manifest ----------------------------------------------------------------


def load_manifest(path: Path | None = None) -> dict | None:
    path = MANIFEST_PATH if path is None else Path(path)
    try:
        return json.loads(path.read_text(encoding="utf-8"))
    except (OSError, ValueError):
        return None


def manifest_names(manifest: dict | None) -> set:
    if not manifest:
        return set()
    out = set()
    for entry in manifest.get("programs", []):
        out.update(entry.get("names", []))
    return out


def emit_manifest(prog: Program) -> dict:
    """The canonical warmup manifest for ``prog`` — deterministic JSON
    (sorted, no line numbers: it drifts only when the compile surface
    actually changes).  CI diffs this against the committed copy."""
    surf = _Surface(prog)
    fams: dict[str, dict] = {}
    for site in surf.sites:
        if site.get("names") is None:
            continue
        if not site["relpath"].startswith("harmony_tpu/"):
            continue
        fam = fams.setdefault(site["family"], {
            "family": site["family"], "sources": set(), "names": set(),
        })
        fam["sources"].add(f"{site['relpath']}::{site['xf'].qualname}")
        fam["names"].update(site["names"])
    heads = sorted(
        {(h["path"], h["context"], h["kind"]) for h in surf.heads})
    return {
        "version": 1,
        "generated_by":
            "python -m tools.graftlint --emit-compile-manifest",
        "note": ("every XLA program a serving path can request, derived "
                 "statically (GL15/GL16); aot.warmup precompiles this "
                 "set before the node serves"),
        "dtype": "int32",
        "device_counts": [1],
        "heads": [
            {"path": p, "context": c, "kind": k} for p, c, k in heads
        ],
        "programs": [
            {
                "family": fam["family"],
                "sources": sorted(fam["sources"]),
                "names": sorted(fam["names"]),
            }
            for fam in sorted(fams.values(),
                              key=lambda f: f["family"])
        ],
    }


# -- findings ----------------------------------------------------------------


def _phase(xf, facts) -> str | None:
    p = xf
    while p is not None:
        got = _def_anno(p.node, facts[p.relpath].phase_annos)
        if got:
            return got
        p = p.parent
    return None


def _gl17(surf: _Surface) -> list[SiteFinding]:
    out = []
    for fid in sorted(surf.idx.funcs):
        xf = surf.idx.funcs[fid]
        if _compile_sanctioned(xf.relpath):
            continue
        if _phase(xf, surf.facts) is not None:
            continue
        in_zone = (xf.relpath.startswith("harmony_tpu/")
                   or surf.facts[xf.relpath].zone is not None)
        jit_names, lowered_names = set(), set()
        for n in _own_nodes(xf.node):
            if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                    and isinstance(n.targets[0], ast.Name):
                v = n.value
                if _head_of(v):
                    jit_names.add(n.targets[0].id)
                elif (isinstance(v, ast.Call)
                      and isinstance(v.func, ast.Attribute)
                      and v.func.attr == "lower"
                      and (v.args or v.keywords)):
                    lowered_names.add(n.targets[0].id)

        def flag(node, msg):
            out.append(SiteFinding(
                xf.relpath, "GL17", node.lineno, node.col_offset,
                msg, xf.qualname,
                surf._in_cone(xf) or ""))

        if in_zone:
            for dec in getattr(xf.node, "decorator_list", []):
                h = _head_of(dec)
                if h:
                    flag(dec, f"compile head {h} outside the "
                              f"sanctioned device layer")
        for n in _own_nodes(xf.node):
            if not isinstance(n, ast.Call):
                continue
            fn = n.func
            if isinstance(fn, ast.Attribute):
                if fn.attr == "lower" and (n.args or n.keywords):
                    flag(n, "explicit .lower(...) outside the device "
                            "layer / warmup phase")
                    continue
                if fn.attr == "compile" and not n.args:
                    recv = fn.value
                    if (isinstance(recv, ast.Call)
                            and isinstance(recv.func, ast.Attribute)
                            and recv.func.attr == "lower"
                            and not (recv.args or recv.keywords)):
                        flag(n, ".lower().compile() chain outside the "
                                "device layer / warmup phase")
                        continue
                    if isinstance(recv, ast.Name) \
                            and recv.id in lowered_names:
                        flag(n, ".compile() of a lowered program "
                                "outside the device layer / warmup "
                                "phase")
                        continue
            if not in_zone:
                continue
            h = _head_of(n)
            if h:
                flag(n, f"compile head {h} outside the sanctioned "
                        f"device layer")
                continue
            if isinstance(fn, ast.Call) and _head_of(fn):
                flag(n, "immediate first-trace of a fresh compile "
                        "head (jit(f)(args))")
                continue
            if isinstance(fn, ast.Name) and fn.id in jit_names:
                flag(n, f"first-trace of jit-bound callable "
                        f"'{fn.id}' outside the device layer")
    return out


def compilesurface_findings(prog: Program) -> list[SiteFinding]:
    surf = _Surface(prog)
    out = list(surf.violations)
    manifest = load_manifest()
    covered = manifest_names(manifest)
    derived_repo: set = set()
    for site in surf.sites:
        xf = site["xf"]
        witness = surf._in_cone(xf) or ""
        if site.get("names") is None:
            bad = site.get("bad_expr")
            out.append(SiteFinding(
                site["relpath"], "GL15",
                bad.lineno if bad is not None else site["line"],
                bad.col_offset if bad is not None else site["col"],
                f"compile program '{site['family']}' has an "
                f"underivable bucket: {site['reason']}",
                site["family"], witness))
            continue
        if site["relpath"].startswith("harmony_tpu/"):
            derived_repo.update(site["names"])
        missing = sorted(site["names"] - covered)
        if missing:
            ex = ", ".join(missing[:3])
            out.append(SiteFinding(
                site["relpath"], "GL16", site["line"], site["col"],
                f"warmup manifest does not cover {len(missing)} "
                f"derived program(s) ({ex}{', ...' if len(missing) > 3 else ''}) — regenerate with "
                f"--emit-compile-manifest",
                site["family"], witness))
    if "harmony_tpu/device.py" in prog.modules and manifest is not None:
        stale = sorted(covered - derived_repo)
        if stale:
            ex = ", ".join(stale[:4])
            out.append(SiteFinding(
                "harmony_tpu/device.py", "GL16", 1, 0,
                f"{len(stale)} committed manifest name(s) no longer "
                f"derivable from any compile site ({ex}"
                f"{', ...' if len(stale) > 4 else ''}) — regenerate "
                f"with --emit-compile-manifest",
                "compile-manifest"))
    out.extend(_gl17(surf))
    return out
