"""Whole-program analysis: call graph, per-function summaries, and the
interprocedural rule families GL05/GL06/GL07.

The per-file rules (rules.py) see one AST at a time; the failure modes
here span files: a lock acquired in consensus while p2p holds the
reverse pair, a socket recv four calls below a ``with self._lock:``, a
device->host sync buried in a helper that a hot-path loop calls per
item.  This module builds

1. a **Program**: every target file parsed once, plus per-module import
   and class indexes;
2. a **FuncInfo summary** per function: locks acquired (and what was
   held at the time), blocking operations, host syncs, call sites with
   the held-lock set and loop depth at each;
3. a **call graph** over conservative static resolution: bare names,
   ``self.method`` (through single-module inheritance), imported
   modules/functions, and a unique-method fallback for foreign
   attributes (``chain.insert_chain`` resolves because exactly one
   class in the program defines ``insert_chain``);
4. transitive closures (which locks / blocking ops a call can reach)
   feeding three rule families:

GL05 — lock-order: every edge "held L1 while acquiring L2" (directly
or through calls) goes into one digraph; a cycle is a potential
deadlock, a non-reentrant self-edge is a guaranteed one.

GL06 — blocking-under-lock: holding any Lock/RLock/Condition while
(transitively) reaching socket I/O, ``Thread.join``, ``time.sleep``,
or device work (a pairing program dispatch / device->host sync).

GL07 — hot-path host-sync: a device->host sync (``np.asarray``,
``bool()``/``float()``/``int()``, ``.item()``, ...) on a device value
inside a loop, or a per-item loop call into a function that syncs —
the pattern that serializes the TPU where the batched verification
pipeline needs it streaming.

Lock identity is static: ``path::NAME`` for module-global locks,
``path::Class.attr`` for instance locks (the class that assigns the
attribute, resolved through in-program bases).  Distinct instances of
one class share a static lock — the analysis is per lock *site*, which
is what an ordering discipline is about.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from pathlib import Path

from .rules import dotted_name

# ---------------------------------------------------------------------------
# summaries

_FuncDef = (ast.FunctionDef, ast.AsyncFunctionDef)
_LOCK_CTORS = {"Lock": False, "RLock": True, "Condition": False,
               "Semaphore": False, "BoundedSemaphore": False}

# methods too common for the unique-method fallback to trust
_COMMON_METHODS = {
    "get", "put", "add", "pop", "set", "close", "items", "keys",
    "values", "append", "extend", "update", "remove", "discard",
    "clear", "encode", "decode", "read", "write", "send", "start",
    "stop", "run", "join", "wait", "hash", "copy", "insert", "index",
    "count", "sort", "split", "strip", "format", "flush", "seek",
    "tell", "name", "value", "state", "expose", "allow", "drop",
}

_SLEEP_HEADS = {"time.sleep"}
_SOCKET_HEADS = {"socket.create_connection"}
_SOCKET_METHODS = {"sendall", "recv", "recv_into", "accept", "connect",
                   "makefile"}
_SYNC_HEADS = {"jax.device_get", "jax.block_until_ready"}
_NP_SYNC = {"np.asarray", "numpy.asarray", "np.array", "numpy.array"}
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_CAST_SYNCS = {"bool", "float", "int"}

# modules whose functions ARE device programs (calling one dispatches
# device work; its result is a device value).  interop/ref are host-side
# converters and deliberately NOT here.
_DEVICE_MODULES = ("harmony_tpu/ops/bls.py", "harmony_tpu/ops/twin.py")
# device.py factories returning device-program callables
_DEVICE_FACTORIES = {"_get_verify_fn", "_get_agg_verify_fn",
                     "_get_agg_verify_batch_fn"}
_JIT_HEADS = {"jax.jit", "jit", "jax.pmap", "pjit"}


@dataclass(frozen=True)
class CallSite:
    ref: tuple  # ("name", n) | ("self", m) | ("mod", path, n) | ("attr", m)
    line: int
    col: int
    holds: tuple  # lock ids held lexically at the call
    in_loop: bool


@dataclass(frozen=True)
class Op:
    desc: str  # stable human id, e.g. "socket recv", "np.asarray(ok)"
    kind: str  # "sleep" | "join" | "socket" | "device" | "sync"
    line: int
    col: int
    holds: tuple
    in_loop: bool
    on_device_value: bool = False


@dataclass(frozen=True)
class Acquire:
    lock: str
    line: int
    col: int
    holds: tuple  # locks already held when this one is taken


@dataclass
class FuncInfo:
    fid: str
    relpath: str
    qualname: str
    cls: str | None
    calls: list = field(default_factory=list)      # [CallSite]
    acquires: list = field(default_factory=list)   # [Acquire]
    ops: list = field(default_factory=list)        # [Op]
    has_device_call: bool = False


@dataclass
class ModuleInfo:
    relpath: str
    tree: ast.Module
    source: str
    # import name -> target module relpath (in-program only)
    mod_imports: dict = field(default_factory=dict)
    # from-import: local name -> (module relpath, original name)
    name_imports: dict = field(default_factory=dict)
    # module-global lock name -> lock id
    locks: dict = field(default_factory=dict)
    # class name -> {"bases": [...], "methods": {name: fid},
    #                "lock_attrs": {attr: lock_id}}
    classes: dict = field(default_factory=dict)
    # top-level function name -> fid
    functions: dict = field(default_factory=dict)


class Program:
    """All target files parsed + indexed, the call graph, and the
    transitive closures the interprocedural rules consume."""

    def __init__(self):
        self.modules: dict[str, ModuleInfo] = {}
        self.funcs: dict[str, FuncInfo] = {}
        self.lock_reentrant: dict[str, bool] = {}
        # attr name -> [lock ids] across every class (foreign-object
        # resolution: unique attr names resolve, ambiguous ones don't)
        self._lock_attr_index: dict[str, list] = {}
        # method name -> [fids] across every class
        self._method_index: dict[str, list] = {}
        self.call_edges: dict[str, set] = {}  # fid -> {callee fid}
        self.trans_acquires: dict[str, dict] = {}  # fid -> {lock: via}
        self.trans_blocking: dict[str, dict] = {}  # fid -> {desc: via}
        self.trans_syncs: dict[str, dict] = {}     # fid -> {desc: via}

    # -- loading ------------------------------------------------------------

    def add_module(self, relpath: str, source: str, tree: ast.Module):
        mi = ModuleInfo(relpath, tree, source)
        self.modules[relpath] = mi
        self._index_defs(mi)

    def finalize(self):
        # imports resolve against the COMPLETE module set, so indexing
        # them must wait until every file is added
        for mi in self.modules.values():
            self._index_imports(mi)
        self._resolve_inherited_locks()
        for mi in self.modules.values():
            for fid in list(mi.functions.values()):
                self._summarize(mi, fid)
            for cls in mi.classes.values():
                for fid in cls["methods"].values():
                    self._summarize(mi, fid)
        self._build_edges()
        self.trans_acquires = self._closure(
            lambda f: {a.lock: "" for a in f.acquires})
        self.trans_blocking = self._closure(
            lambda f: {o.desc: "" for o in f.ops
                       if o.kind in ("sleep", "join", "socket", "device")})
        self.trans_syncs = self._closure(
            lambda f: {o.desc: "" for o in f.ops
                       if o.kind == "sync" and o.on_device_value})

    # -- indexing -----------------------------------------------------------

    def _module_path_of(self, relpath: str, module: str,
                        level: int) -> str | None:
        """Resolve an import to an in-program module relpath."""
        if level:
            base = Path(relpath).parent
            for _ in range(level - 1):
                base = base.parent
            parts = list(base.parts) + (module.split(".") if module else [])
        else:
            parts = module.split(".")
        cand = "/".join(parts) + ".py"
        if cand in self.modules:
            return cand
        cand = "/".join(parts) + "/__init__.py"
        if cand in self.modules:
            return cand
        if not level:
            # flat absolute import between files linted from one
            # directory (fixture programs outside the repo package)
            sib = (Path(relpath).parent / ("/".join(parts) + ".py"))
            sib = sib.as_posix()
            if sib in self.modules:
                return sib
        return None

    def _index_imports(self, mi: ModuleInfo):
        for node in ast.walk(mi.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    target = self._module_path_of(mi.relpath, a.name, 0)
                    mi.mod_imports[a.asname or a.name.split(".")[0]] = (
                        target or a.name
                    )
            elif isinstance(node, ast.ImportFrom):
                modpath = self._module_path_of(
                    mi.relpath, node.module or "", node.level)
                for a in node.names:
                    local = a.asname or a.name
                    # ``from ..pkg import mod`` binds a MODULE: try the
                    # dotted submodule path before treating it as a name
                    sub = self._module_path_of(
                        mi.relpath,
                        ".".join(p for p in (node.module, a.name) if p),
                        node.level)
                    if sub is not None:
                        mi.mod_imports[local] = sub
                    elif modpath is not None:
                        mi.name_imports[local] = (modpath, a.name)

    def _index_defs(self, mi: ModuleInfo):
        for node in mi.tree.body:
            if isinstance(node, ast.Assign) and _lock_ctor_kind(node.value):
                reentrant = _lock_ctor_kind(node.value) == "RLock"
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        lid = f"{mi.relpath}::{tgt.id}"
                        mi.locks[tgt.id] = lid
                        self.lock_reentrant[lid] = reentrant
            elif isinstance(node, _FuncDef):
                fid = f"{mi.relpath}::{node.name}"
                mi.functions[node.name] = fid
                self.funcs[fid] = FuncInfo(fid, mi.relpath, node.name, None)
                self.funcs[fid].node = node
            elif isinstance(node, ast.ClassDef):
                self._index_class(mi, node)

    def _index_class(self, mi: ModuleInfo, node: ast.ClassDef):
        info = {"bases": [dotted_name(b) for b in node.bases],
                "methods": {}, "lock_attrs": {}}
        mi.classes[node.name] = info
        for item in node.body:
            if not isinstance(item, _FuncDef):
                continue
            fid = f"{mi.relpath}::{node.name}.{item.name}"
            info["methods"][item.name] = fid
            fi = FuncInfo(fid, mi.relpath, f"{node.name}.{item.name}",
                          node.name)
            fi.node = item
            self.funcs[fid] = fi
            self._method_index.setdefault(item.name, []).append(fid)
            for sub in ast.walk(item):
                if isinstance(sub, ast.Assign):
                    kind = _lock_ctor_kind(sub.value)
                    if not kind:
                        continue
                    for tgt in sub.targets:
                        attr = _self_attr(tgt)
                        if attr:
                            lid = f"{mi.relpath}::{node.name}.{attr}"
                            info["lock_attrs"][attr] = lid
                            self.lock_reentrant[lid] = kind == "RLock"
                            self._lock_attr_index.setdefault(
                                attr, []).append(lid)

    def _resolve_inherited_locks(self):
        """A subclass using ``self._lock`` assigned by an in-program
        base shares the base's lock id (Host/TCPHost)."""
        for mi in self.modules.values():
            for cname, info in mi.classes.items():
                for base in info["bases"]:
                    binfo = self._find_class(mi, base)
                    if binfo is None:
                        continue
                    for attr, lid in binfo["lock_attrs"].items():
                        info["lock_attrs"].setdefault(attr, lid)
                    for m, fid in binfo["methods"].items():
                        info["methods"].setdefault(m, fid)

    def _find_class(self, mi: ModuleInfo, name: str | None):
        if not name:
            return None
        name = name.split(".")[-1]
        if name in mi.classes:
            return mi.classes[name]
        for imp, (modpath, orig) in mi.name_imports.items():
            if imp == name and modpath in self.modules:
                return self.modules[modpath].classes.get(orig)
        for other in self.modules.values():
            if name in other.classes:
                return other.classes[name]
        return None

    # -- per-function summary ----------------------------------------------

    def _summarize(self, mi: ModuleInfo, fid: str):
        fi = self.funcs[fid]
        fn = fi.node
        cls = mi.classes.get(fi.cls) if fi.cls else None
        lock_attrs = cls["lock_attrs"] if cls else {}
        device_fns, device_vals, thread_names = _local_dataflow(
            fn, mi, self)

        def lock_of(expr: ast.AST) -> str | None:
            """Static lock id of a with-item / acquire target."""
            if isinstance(expr, ast.Name):
                return mi.locks.get(expr.id)
            attr = _self_attr(expr)
            if attr is not None:
                return lock_attrs.get(attr)
            # foreign object: obj.attr resolves iff the attr names a
            # lock in exactly one in-program class (chain._insert_lock)
            if isinstance(expr, ast.Attribute):
                cands = self._lock_attr_index.get(expr.attr, [])
                if len(cands) == 1:
                    return cands[0]
            return None

        def walk(node: ast.AST, holds: tuple, loop: int):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, _FuncDef):
                    continue  # nested defs run in their own context
                if isinstance(child, ast.With):
                    # items acquire left-to-right: `with A, B:` is an
                    # A->B edge, so each item's Acquire must see the
                    # locks of the items before it, not just the outer
                    # holds
                    cur = holds
                    for item in child.items:
                        g = lock_of(item.context_expr)
                        if g is None:
                            continue
                        fi.acquires.append(Acquire(
                            g, child.lineno, child.col_offset, cur))
                        if g not in cur:
                            cur = cur + (g,)
                    walk(child, cur, loop)
                    continue
                in_loop = loop > 0
                if isinstance(child, ast.Call):
                    self._classify_call(
                        mi, fi, child, holds, in_loop or isinstance(
                            node, (ast.GeneratorExp, ast.ListComp,
                                   ast.SetComp, ast.DictComp)),
                        device_fns, device_vals, thread_names)
                next_loop = loop + (1 if isinstance(
                    child, (ast.For, ast.AsyncFor, ast.While,
                            ast.GeneratorExp, ast.ListComp, ast.SetComp,
                            ast.DictComp)) else 0)
                walk(child, holds, next_loop)

        walk(fn, (), 0)

    def _classify_call(self, mi, fi, node: ast.Call, holds, in_loop,
                       device_fns, device_vals, thread_names):
        head = dotted_name(node.func)
        line, col = node.lineno, node.col_offset

        def op(desc, kind, dev=False):
            fi.ops.append(Op(desc, kind, line, col, holds, in_loop, dev))

        arg_is_device = any(
            isinstance(a, ast.Name) and a.id in device_vals
            or _is_device_call(a, mi, self, device_fns)
            for a in node.args
        )
        # blocking / sync primitives
        if head in _SLEEP_HEADS:
            op("time.sleep", "sleep")
        elif head in _SOCKET_HEADS:
            op("socket connect", "socket")
        elif head in _SYNC_HEADS:
            op(head, "sync", dev=True)
        elif head in _NP_SYNC and arg_is_device:
            op(f"{head} on device value", "sync", dev=True)
        elif head in _CAST_SYNCS and arg_is_device:
            op(f"{head}() on device value", "sync", dev=True)
        elif isinstance(node.func, ast.Attribute):
            meth = node.func.attr
            base = node.func.value
            if meth in _SOCKET_METHODS:
                op(f"socket {meth}", "socket")
            elif meth == "join" and isinstance(base, ast.Name) \
                    and base.id in thread_names:
                op("Thread.join", "join")
            elif meth in _SYNC_METHODS and (
                    isinstance(base, ast.Name) and base.id in device_vals
                    or _is_device_call(base, mi, self, device_fns)):
                op(f".{meth}() on device value", "sync", dev=True)

        # device program dispatch (pairing work: seconds on CPU)
        if _is_device_call(node, mi, self, device_fns):
            fi.has_device_call = True
            op(f"device program {head or '<fn>'}()", "device", dev=True)

        # call-graph edge candidates
        ref = self._call_ref(mi, fi, node)
        if ref is not None:
            fi.calls.append(CallSite(ref, line, col, holds, in_loop))

    def _call_ref(self, mi, fi, node: ast.Call):
        f = node.func
        if isinstance(f, ast.Name):
            return ("name", f.id)
        if isinstance(f, ast.Attribute):
            if _self_attr(f) is not None:
                return ("self", f.attr)
            base = dotted_name(f.value)
            if base and base in mi.mod_imports:
                return ("mod", mi.mod_imports[base], f.attr)
            return ("attr", f.attr)
        return None

    # -- call graph ---------------------------------------------------------

    def resolve(self, fi: FuncInfo, ref: tuple) -> list:
        kind = ref[0]
        mi = self.modules[fi.relpath]
        if kind == "name":
            name = ref[1]
            if name in mi.functions:
                return [mi.functions[name]]
            if name in mi.name_imports:
                modpath, orig = mi.name_imports[name]
                target = self.modules.get(modpath)
                if target and orig in target.functions:
                    return [target.functions[orig]]
            return []
        if kind == "self":
            cls = mi.classes.get(fi.cls) if fi.cls else None
            if cls and ref[1] in cls["methods"]:
                return [cls["methods"][ref[1]]]
            return []
        if kind == "mod":
            target = self.modules.get(ref[1])
            if target:
                if ref[2] in target.functions:
                    return [target.functions[ref[2]]]
            return []
        if kind == "attr":
            meth = ref[1]
            if meth in _COMMON_METHODS or len(meth) <= 3:
                return []
            cands = self._method_index.get(meth, [])
            return cands if len(cands) == 1 else []
        return []

    def _build_edges(self):
        for fid, fi in self.funcs.items():
            out = self.call_edges.setdefault(fid, set())
            for cs in fi.calls:
                out.update(self.resolve(fi, cs.ref))

    def _closure(self, direct) -> dict:
        """fid -> {fact: via-chain}; facts flow callee -> caller.  The
        via-chain names one witness path to the fact.  Iteration is
        fully sorted so the chosen witness is deterministic run-to-run
        (witnesses are display-only, but nondeterministic output churns
        diffs and confuses users)."""
        facts = {fid: dict(direct(fi)) for fid, fi in self.funcs.items()}
        changed = True
        rounds = 0
        while changed and rounds < 50:
            changed = False
            rounds += 1
            for fid in sorted(self.call_edges):
                mine = facts[fid]
                for c in sorted(self.call_edges[fid]):
                    if c == fid:
                        continue
                    for fact, via in sorted(facts.get(c, {}).items()):
                        if fact not in mine:
                            mine[fact] = _short(c) + (
                                f" -> {via}" if via else "")
                            changed = True
        return facts


def _short(fid: str) -> str:
    path, qn = fid.split("::", 1)
    return f"{Path(path).name}:{qn}"


def short_lock(lid: str) -> str:
    path, name = lid.split("::", 1)
    return f"{Path(path).name}:{name}"


def _lock_ctor_kind(node: ast.AST) -> str | None:
    if not isinstance(node, ast.Call):
        return None
    d = dotted_name(node.func)
    if d and d.split(".")[-1] in _LOCK_CTORS:
        return d.split(".")[-1]
    return None


def _self_attr(node: ast.AST) -> str | None:
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def _is_device_head(head: str | None, mi: ModuleInfo,
                    prog: Program) -> bool:
    """Does this dotted call/attr head denote a device program?"""
    if not head:
        return False
    if head in _JIT_HEADS or head in _DEVICE_FACTORIES:
        return True
    root = head.split(".")[0]
    target = mi.mod_imports.get(root)
    if target in _DEVICE_MODULES:
        return True
    if root in mi.name_imports:
        modpath, orig = mi.name_imports[root]
        if modpath in _DEVICE_MODULES:
            return True
        if modpath and modpath.endswith("device.py") \
                and orig in _DEVICE_FACTORIES:
            return True
    return False


def _is_device_call(node: ast.AST, mi: ModuleInfo, prog: Program,
                    device_fns: set) -> bool:
    """A Call that dispatches a device program."""
    if not isinstance(node, ast.Call):
        return False
    head = dotted_name(node.func)
    if head and head in device_fns:
        return True
    return _is_device_head(head, mi, prog)


def _local_dataflow(fn, mi: ModuleInfo, prog: Program):
    """(device_fns, device_vals, thread_names): names bound in this
    function to device callables, device values, and Thread objects."""
    device_fns: set[str] = set()
    device_vals: set[str] = set()
    threads: set[str] = set()

    def value_classes(expr) -> tuple[bool, bool, bool]:
        """(is_device_fn, is_device_val, is_thread) for an RHS."""
        if isinstance(expr, ast.IfExp):
            a = value_classes(expr.body)
            b = value_classes(expr.orelse)
            return tuple(x or y for x, y in zip(a, b))
        if isinstance(expr, ast.Call):
            head = dotted_name(expr.func)
            if head and head.split(".")[-1] == "Thread":
                return (False, False, True)
            if _is_device_head(head, mi, prog):
                # jit()/factory() returns a device callable; a device
                # module op call returns a device value
                root = head.split(".")[0] if head else ""
                factoryish = (head in _JIT_HEADS
                              or head in _DEVICE_FACTORIES
                              or (root in mi.name_imports
                                  and mi.name_imports[root][1]
                                  in _DEVICE_FACTORIES))
                return (factoryish, not factoryish, False)
            if head and head in device_fns:
                return (False, True, False)
            return (False, False, False)
        head = dotted_name(expr) if isinstance(
            expr, (ast.Attribute, ast.Name)) else None
        if head and _is_device_head(head, mi, prog):
            return (True, False, False)  # fn = OB.agg_verify
        if isinstance(expr, ast.Name) and expr.id in device_vals:
            return (False, True, False)
        return (False, False, False)

    # two passes so `fn = ...; ok = fn(...)` resolves regardless of
    # statement order quirks
    for _ in range(2):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            is_fn, is_val, is_thr = value_classes(node.value)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    if is_fn:
                        device_fns.add(tgt.id)
                    if is_val:
                        device_vals.add(tgt.id)
                    if is_thr:
                        threads.add(tgt.id)
    return device_fns, device_vals, threads


# ---------------------------------------------------------------------------
# GL05 — lock-order cycles


@dataclass(frozen=True)
class SiteFinding:
    """An interprocedural finding bound to a file.  ``detail`` carries
    the witness call chain — display-only, never fingerprinted."""
    relpath: str
    rule: str
    line: int
    col: int
    message: str
    context: str
    detail: str = ""


def gl05_findings(prog: Program) -> list[SiteFinding]:
    out = []
    edges: dict[tuple, tuple] = {}
    for fid, fi in prog.funcs.items():
        for a in fi.acquires:
            for held in a.holds:
                edges.setdefault((held, a.lock), (
                    fi.relpath, a.line, a.col, fi.qualname, ""))
        for cs in fi.calls:
            if not cs.holds:
                continue
            for callee in prog.resolve(fi, cs.ref):
                for lock, via in prog.trans_acquires.get(
                        callee, {}).items():
                    for held in cs.holds:
                        chain = _short(callee) + (
                            f" -> {via}" if via else "")
                        edges.setdefault((held, lock), (
                            fi.relpath, cs.line, cs.col, fi.qualname,
                            chain))

    for (a, b), (path, line, col, ctx, via) in sorted(edges.items()):
        if a == b and not prog.lock_reentrant.get(a, False):
            out.append(SiteFinding(
                path, "GL05", line, col,
                f"non-reentrant {short_lock(a)} re-acquired while "
                "held (self-deadlock)", ctx, via))

    adj: dict[str, set] = {}
    for (a, b) in edges:
        if a != b:
            adj.setdefault(a, set()).add(b)

    def reaches(src: str, dst: str) -> bool:
        seen, stack = set(), [src]
        while stack:
            cur = stack.pop()
            if cur == dst:
                return True
            if cur in seen:
                continue
            seen.add(cur)
            stack.extend(adj.get(cur, ()))
        return False

    for (a, b), (path, line, col, ctx, via) in sorted(edges.items()):
        if a == b:
            continue
        if reaches(b, a):
            msg = (f"lock-order cycle: {short_lock(a)} -> "
                   f"{short_lock(b)} closes a reverse path "
                   "(potential deadlock)")
        else:
            # acyclic but UNDECLARED: every nested acquisition must be
            # reviewed once — the committed baseline is the declared
            # lock-order registry, and a cycle can only ever enter the
            # tree through a new edge, so new edges gate the PR
            msg = (f"lock-order edge {short_lock(a)} -> "
                   f"{short_lock(b)} (undeclared nested acquisition: "
                   "shrink the critical section, or pin after review)")
        out.append(SiteFinding(path, "GL05", line, col, msg, ctx, via))
    return out


# ---------------------------------------------------------------------------
# GL06 — blocking work under a lock


def gl06_findings(prog: Program) -> list[SiteFinding]:
    out = []
    for fid, fi in prog.funcs.items():
        for o in fi.ops:
            if o.kind in ("sleep", "join", "socket", "device") \
                    and o.holds:
                lock = short_lock(o.holds[-1])
                out.append(SiteFinding(
                    fi.relpath, "GL06", o.line, o.col,
                    f"{o.desc} while holding {lock}", fi.qualname))
        for cs in fi.calls:
            if not cs.holds:
                continue
            for callee in prog.resolve(fi, cs.ref):
                blocked = prog.trans_blocking.get(callee, {})
                if not blocked:
                    continue
                desc = sorted(blocked)[0]
                lock = short_lock(cs.holds[-1])
                # the witness callee goes in detail ONLY: fingerprints
                # must survive rerouting the same defect through a
                # different first-hop helper
                chain = _short(callee)
                if blocked[desc]:
                    chain += f" -> {blocked[desc]}"
                out.append(SiteFinding(
                    fi.relpath, "GL06", cs.line, cs.col,
                    f"call reaches {desc} while holding {lock}",
                    fi.qualname, chain))
    return out


# ---------------------------------------------------------------------------
# GL07 — hot-path host syncs


def _hot(fi: FuncInfo) -> bool:
    return (fi.relpath == "harmony_tpu/device.py"
            or fi.relpath.startswith("harmony_tpu/ops/")
            or fi.has_device_call)


def gl07_findings(prog: Program) -> list[SiteFinding]:
    out = []
    for fid, fi in prog.funcs.items():
        if not _hot(fi):
            continue
        for o in fi.ops:
            if o.kind == "sync" and o.on_device_value and o.in_loop:
                out.append(SiteFinding(
                    fi.relpath, "GL07", o.line, o.col,
                    f"per-item host sync {o.desc} inside a loop "
                    "(serializes the device pipeline; hoist it)",
                    fi.qualname))
        for cs in fi.calls:
            if not cs.in_loop:
                continue
            for callee in prog.resolve(fi, cs.ref):
                syncs = prog.trans_syncs.get(callee, {})
                if not syncs:
                    continue
                desc = sorted(syncs)[0]
                out.append(SiteFinding(
                    fi.relpath, "GL07", cs.line, cs.col,
                    f"loop calls {_short(callee)} which host-syncs "
                    f"({desc}); batch across iterations",
                    fi.qualname))
    return out


# ---------------------------------------------------------------------------
# GL08 — unbounded blocking calls (no timeout ever set)

_GL08_BLOCKING = {"connect", "recv", "recv_into"}
_GL08_URLOPEN = {"urlopen", "urllib.request.urlopen", "request.urlopen"}


def _is_none_const(node: ast.AST) -> bool:
    return isinstance(node, ast.Constant) and node.value is None


def _gl08_sock_ctor(node: ast.AST) -> str | None:
    """'plain' for socket.socket(...), 'bounded'/'unbounded' for
    create_connection with/without a timeout, else None."""
    if not isinstance(node, ast.Call):
        return None
    head = dotted_name(node.func)
    if head in ("socket.socket", "socket"):
        return "plain"
    if head and head.split(".")[-1] == "create_connection":
        has_timeout = len(node.args) >= 2 or any(
            k.arg == "timeout" and not _is_none_const(k.value)
            for k in node.keywords
        )
        return "bounded" if has_timeout else "unbounded"
    return None


def _gl08_settimeout_target(node: ast.AST) -> ast.AST | None:
    """The receiver of a real ``settimeout`` call (None arg = blocking
    mode, which does NOT count as a timeout)."""
    if (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "settimeout"
            and node.args and not _is_none_const(node.args[0])):
        return node.func.value
    return None


def _gl08_class_attrs(cls_node: ast.ClassDef) -> tuple[set, set]:
    """(created socket attrs, timeout-bounded attrs) for ``self.X``
    sockets, scanned across EVERY method — a timeout set in __init__
    bounds the recv in a sibling method (that cross-method view is why
    this rule lives in the whole-program pass)."""
    created: set[str] = set()
    bounded: set[str] = set()
    for node in ast.walk(cls_node):
        if isinstance(node, ast.Assign):
            kind = _gl08_sock_ctor(node.value)
            if kind:
                for tgt in node.targets:
                    a = _self_attr(tgt)
                    if a:
                        created.add(a)
                        if kind == "bounded":
                            bounded.add(a)
        else:
            tgt = _gl08_settimeout_target(node)
            if tgt is not None:
                a = _self_attr(tgt)
                if a:
                    bounded.add(a)
    return created, bounded


def _gl08_local_sockets(fn: ast.AST) -> tuple[set, set]:
    """(created local socket names, timeout-bounded names) within one
    function body."""
    created: set[str] = set()
    bounded: set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            kind = _gl08_sock_ctor(node.value)
            if kind:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        created.add(tgt.id)
                        if kind == "bounded":
                            bounded.add(tgt.id)
        else:
            tgt = _gl08_settimeout_target(node)
            if isinstance(tgt, ast.Name):
                bounded.add(tgt.id)
    return created, bounded


def _gl08_param_flow(prog: Program) -> tuple[dict, dict]:
    """(blocking params, params list) per fid.  A param index is
    *blocking* when the function recv/connects on it (without setting
    a timeout itself) or passes it positionally into a callee whose
    matching param is blocking — the transitive closure that makes
    ``read_frame(self._sock)`` light up at the call site."""
    params_of: dict[str, list] = {}
    blocking: dict[str, set] = {}
    edges: list[tuple] = []  # (caller fid, caller idx, callee fid, callee idx)
    for fid, fi in prog.funcs.items():
        fn = fi.node
        names = [a.arg for a in (fn.args.posonlyargs + fn.args.args)]
        params_of[fid] = names
        blocking[fid] = set()
    for fid in sorted(prog.funcs):
        fi = prog.funcs[fid]
        fn = fi.node
        names = params_of[fid]
        mi = prog.modules[fi.relpath]
        bounded: set[int] = set()
        for node in ast.walk(fn):
            tgt = _gl08_settimeout_target(node)
            if isinstance(tgt, ast.Name) and tgt.id in names:
                bounded.add(names.index(tgt.id))
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _GL08_BLOCKING
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in names):
                i = names.index(node.func.value.id)
                if i not in bounded:
                    blocking[fid].add(i)
            ref = prog._call_ref(mi, fi, node)
            if ref is None:
                continue
            callees = [c for c in prog.resolve(fi, ref)
                       if c in prog.funcs]
            for callee in callees:
                offset = 1 if prog.funcs[callee].cls else 0
                for ai, arg in enumerate(node.args):
                    if isinstance(arg, ast.Name) and arg.id in names:
                        i = names.index(arg.id)
                        if i not in bounded:
                            edges.append((fid, i, callee, ai + offset))
    changed = True
    rounds = 0
    while changed and rounds < 50:
        changed = False
        rounds += 1
        for caller, ci, callee, pi in edges:
            if pi in blocking.get(callee, ()) \
                    and ci not in blocking[caller]:
                blocking[caller].add(ci)
                changed = True
    return blocking, params_of


def gl08_findings(prog: Program) -> list[SiteFinding]:
    out = []
    param_blocking, params_of = _gl08_param_flow(prog)
    attr_info: dict[tuple, tuple] = {}
    for relpath, mi in prog.modules.items():
        for node in mi.tree.body:
            if isinstance(node, ast.ClassDef):
                attr_info[(relpath, node.name)] = _gl08_class_attrs(node)
    for fid in sorted(prog.funcs):
        fi = prog.funcs[fid]
        fn = fi.node
        mi = prog.modules[fi.relpath]
        created_a, bounded_a = attr_info.get(
            (fi.relpath, fi.cls), (set(), set()))
        created_l, bounded_l = _gl08_local_sockets(fn)

        def render_unbounded(expr: ast.AST) -> str | None:
            a = _self_attr(expr)
            if a is not None:
                if a in created_a and a not in bounded_a:
                    return f"self.{a}"
                return None
            if isinstance(expr, ast.Name):
                if expr.id in created_l and expr.id not in bounded_l:
                    return expr.id
            return None

        def emit(node, message):
            out.append(SiteFinding(
                fi.relpath, "GL08", node.lineno, node.col_offset,
                message, fi.qualname))

        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            head = dotted_name(node.func)
            if head in _GL08_URLOPEN:
                has_timeout = len(node.args) >= 3 or any(
                    k.arg == "timeout" and not _is_none_const(k.value)
                    for k in node.keywords)
                if not has_timeout:
                    emit(node, "urlopen without a timeout (hangs "
                               "forever on a stalled endpoint)")
                continue
            if _gl08_sock_ctor(node) == "unbounded":
                emit(node, "create_connection without a timeout "
                           "(blocking dial can hang forever)")
                continue
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _GL08_BLOCKING):
                name = render_unbounded(node.func.value)
                if name:
                    emit(node, f"socket {node.func.attr} on {name} "
                               "with no timeout ever set")
                continue
            ref = prog._call_ref(mi, fi, node)
            if ref is None:
                continue
            for callee in sorted(prog.resolve(fi, ref)):
                cfi = prog.funcs.get(callee)
                if cfi is None:
                    continue
                offset = 1 if cfi.cls else 0
                blocked = param_blocking.get(callee, set())
                for ai, arg in enumerate(node.args):
                    name = render_unbounded(arg)
                    if name and (ai + offset) in blocked:
                        emit(node, f"timeout-less socket {name} passed "
                                   f"into {_short(callee)} (reaches "
                                   "blocking socket I/O)")
    return out


# ---------------------------------------------------------------------------
# DOT dump


def to_dot(prog: Program) -> str:
    lines = ["digraph graftlint_callgraph {"]
    for fid in sorted(prog.call_edges):
        for callee in sorted(prog.call_edges[fid]):
            lines.append(f'  "{_short(fid)}" -> "{_short(callee)}";')
    lines.append("}")
    return "\n".join(lines) + "\n"


def analyze(sources: dict[str, tuple[str, ast.Module]]) -> Program:
    """Build + finalize a Program from {relpath: (source, tree)}."""
    prog = Program()
    for relpath, (source, tree) in sources.items():
        prog.add_module(relpath, source, tree)
    prog.finalize()
    return prog
