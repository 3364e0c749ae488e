"""Kernel-domain static analysis: GL09 limb value-range abstract
interpretation, GL10 Montgomery-domain typestate, GL11 twin/padding
discipline.

The hot kernels (``harmony_tpu/ops/{fp,fp_pallas,towers,curve,
pairing}.py``) do 381-bit field arithmetic in 32x12-bit int32 limbs.
Every optimization on the roadmap (Karatsuba limb convolution,
MXU-int8 reduction, Karabina compression, precomputed-line Miller)
changes the magnitude of intermediate limb values, and a silent int32
overflow produces a wrong-but-plausible pairing.  This pass makes the
bound a machine-checked precondition:

GL09 — an **interval abstract interpreter** over the jnp/np expression
dataflow.  Each array value carries a proven element bound [lo, hi]
propagated through ``+ - * >> & | where stack concatenate pad einsum/
matmul``-style reductions, the carry-lookahead helpers, ``lax.scan``
(unrolled when the trip count is provably the limb count, widened
fixpoint otherwise) and ``lax.fori_loop``/``while`` (join fixpoint
with power-of-two widening).  Any intermediate whose bound can leave
the module dtype's lanes (int32 by default, parameterized via the
module contract so the int8-plane MXU path is checkable) is flagged.

GL10 — a **Montgomery-domain typestate** rides on the same values:
every field element has an R-degree (value = x * R^d mod p): standard
d=0, Montgomery d=1, the R^2 conversion constant d=2, and "neutral"
for masks/zero/multiples of p.  ``mont_mul`` is the one primitive that
changes degree (d_out = d_a + d_b - 1); add/sub/select require equal
degrees.  Mixing degrees, raw ``*`` products of domain values outside
a primitive, and returns whose degree contradicts the declared
contract are flagged.

GL11 — **twin/padding discipline** for device-dispatched kernels:
every kernel a ``jax.jit`` dispatch site references must have a
bigint twin (same name in the declared twin module), a parity test
under tests/ referencing it, and a provable infinity-sentinel guard
(the kernel transitively reaches an ``is_zero``-style finiteness
check or a reviewed ``padding-safe`` function).

Contracts are declared in-code::

    # graftlint: kernel-module dtype=int32; twin=harmony_tpu/ops/twin.py
    ...
    # graftlint: kernel bounds=(limb, limb) -> limb; domain=(mont, mont) -> mont
    def add(a, b): ...

    ONE_MONT = jnp.asarray(...)  # graftlint: kernel domain=mont

Spec tokens: ``limb`` (canonical digits [0, 2^12-1]), ``bit`` ([0,1]),
``<N``/``<=N`` (explicit bound, N may be ``2**30``), ``any``,
``fieldops`` (a curve.FieldOps-shaped op table).  Domain tokens:
``mont std r2 neutral same any`` plus the whole-signature form
``domain=mul`` marking the Montgomery primitive (degree algebra at
call sites, internal domain checks off).

Like GL05-GL08, findings carry the witness derivation in
``Finding.detail`` (display-only, never fingerprinted) and respect
the baseline/pin workflow.  The pass is assume-guarantee: every
annotated function is verified once against its own contract assuming
its callees' contracts; unannotated helpers are inlined with the
caller's abstract arguments.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field, replace

from .interproc import Program, SiteFinding
from .rules import dotted_name, _enclosing_map

LIMB_BITS = 12
LIMB_MASK = (1 << LIMB_BITS) - 1
N_LIMBS = 32

_DTYPES = {
    "int8": (-(1 << 7), (1 << 7) - 1),
    "int16": (-(1 << 15), (1 << 15) - 1),
    "int32": (-(1 << 31), (1 << 31) - 1),
    "int64": (-(1 << 63), (1 << 63) - 1),
}

# fixpoint knobs: join iterations before widening kicks in, and the
# hard cap after which a non-stabilizing loop carry is flagged
_WIDEN_AFTER = 6
_LOOP_CAP = 48
_UNROLL_CAP = 4096
_INLINE_DEPTH = 24

# ---------------------------------------------------------------------------
# abstract values


DOM_TOP = ("top",)
DOM_NEUTRAL = ("neutral",)


def deg(k: int) -> tuple:
    return ("deg", k)


@dataclass(frozen=True)
class AV:
    """Abstract array value: element interval + Montgomery R-degree.

    ``lo``/``hi`` of None mean unbounded in that direction.  ``prov``
    is a short human derivation note (display-only, excluded from
    equality so fixpoint tests converge)."""

    lo: int | None = None
    hi: int | None = None
    dom: tuple = DOM_TOP
    limbaxis: bool = False     # last axis is the 32-limb axis
    scanlen: int | None = None  # provable lax.scan trip count
    prov: str = field(default="", compare=False)

    @property
    def bounded(self) -> bool:
        return self.lo is not None and self.hi is not None

    def desc(self) -> str:
        lo = "-inf" if self.lo is None else str(self.lo)
        hi = "+inf" if self.hi is None else str(self.hi)
        return f"[{lo}, {hi}]"


TOPV = AV()


@dataclass(frozen=True)
class Conc:
    """A concretely-known host (python) value — int, str, tuple, ..."""
    value: object


UNKNOWN = Conc(object())  # a host value we cannot fold


@dataclass(frozen=True)
class ModRef:
    relpath: str


@dataclass(frozen=True)
class FuncRef:
    relpath: str
    name: str


class Closure:
    """A nested def / lambda with its defining environment."""

    def __init__(self, node, env, relpath):
        self.node = node
        self.env = env
        self.relpath = relpath


class FieldOpsVal:
    """Abstract curve.FieldOps op table: canonical mont ops."""


FIELDOPS = FieldOpsVal()


class AbsTuple(tuple):
    """Abstract tuple/list of abstract values."""


def is_known_conc(v) -> bool:
    return isinstance(v, Conc) and v is not UNKNOWN and v.value is not \
        UNKNOWN.value


def _dom_join(a: tuple, b: tuple) -> tuple:
    if a == b:
        return a
    if a == DOM_NEUTRAL:
        return b
    if b == DOM_NEUTRAL:
        return a
    return DOM_TOP


def _dom_mixes(a: tuple, b: tuple) -> bool:
    """True when two NON-neutral concrete domains disagree — the GL10
    add/sub/select mixing condition."""
    return (a not in (DOM_TOP, DOM_NEUTRAL)
            and b not in (DOM_TOP, DOM_NEUTRAL) and a != b)


def _dom_name(d: tuple) -> str:
    if d == DOM_TOP:
        return "unknown"
    if d == DOM_NEUTRAL:
        return "neutral"
    if d[0] == "deg":
        return {0: "std", 1: "mont", 2: "r2"}.get(d[1], f"R^{d[1]}")
    return f"poly({d[1]})"


def av_join(a, b):
    """Join two abstract values (any kind)."""
    if isinstance(a, AV) or isinstance(b, AV):
        a = to_av(a)
        b = to_av(b)
        lo = None if a.lo is None or b.lo is None else min(a.lo, b.lo)
        hi = None if a.hi is None or b.hi is None else max(a.hi, b.hi)
        return AV(lo, hi, _dom_join(a.dom, b.dom),
                  a.limbaxis and b.limbaxis, None,
                  prov=a.prov or b.prov)
    if isinstance(a, AbsTuple) and isinstance(b, AbsTuple) \
            and len(a) == len(b):
        return AbsTuple(av_join(x, y) for x, y in zip(a, b))
    if is_known_conc(a) and is_known_conc(b) and a.value == b.value \
            and type(a.value) is type(b.value):
        return a
    if isinstance(a, (ModRef, FuncRef, Closure, FieldOpsVal)) and a is b:
        return a
    if isinstance(a, Conc) and isinstance(b, Conc) \
            and isinstance(a.value, (int, bool)) \
            and isinstance(b.value, (int, bool)):
        # diverging host ints (loop counters): promote to unknown host
        return UNKNOWN
    if a is b:
        return a
    return TOPV


def to_av(v) -> AV:
    """View any abstract thing as an array interval (for arithmetic)."""
    if isinstance(v, AV):
        return v
    if is_known_conc(v) and isinstance(v.value, bool):
        return AV(int(v.value), int(v.value), DOM_NEUTRAL)
    if is_known_conc(v) and isinstance(v.value, int):
        return AV(v.value, v.value, DOM_NEUTRAL)
    if isinstance(v, AbsTuple):
        out = None
        for e in v:
            out = to_av(e) if out is None else av_join(out, to_av(e))
        return out if out is not None else TOPV
    return TOPV


def widen(prev: AV, new: AV) -> AV:
    """Power-of-two interval widening to force loop convergence."""
    lo, hi = new.lo, new.hi
    if prev.lo is not None and (lo is None or lo < prev.lo):
        lo = None if lo is None or lo < -(1 << 70) else -_pow2ceil(-lo)
    if prev.hi is not None and (hi is None or hi > prev.hi):
        hi = None if hi is None or hi > (1 << 70) else _pow2ceil(hi + 1) - 1
    return replace(new, lo=lo, hi=hi)


def _pow2ceil(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def widen_any(prev, new):
    if isinstance(prev, AV) and isinstance(new, AV):
        return widen(prev, new)
    if isinstance(prev, AbsTuple) and isinstance(new, AbsTuple) \
            and len(prev) == len(new):
        return AbsTuple(widen_any(p, n) for p, n in zip(prev, new))
    return new


# ---------------------------------------------------------------------------
# contract annotations

_ANNO_RE = re.compile(r"#\s*graftlint:\s*(kernel-module|kernel)\b(.*)$")


@dataclass
class Spec:
    """One parameter/return bound spec."""
    lo: int | None = None
    hi: int | None = None
    limbaxis: bool = False
    fieldops: bool = False
    anyv: bool = False

    def check(self, av) -> str | None:
        """Return a violation description, or None when av satisfies."""
        if self.anyv or self.fieldops:
            return None
        a = to_av(av)
        if not a.bounded:
            return f"unprovable bound {a.desc()}"
        if (self.lo is not None and a.lo < self.lo) or \
                (self.hi is not None and a.hi > self.hi):
            return f"proven {a.desc()} exceeds declared [{self.lo}, {self.hi}]"
        return None

    def seed(self, dom: tuple) -> object:
        if self.fieldops:
            return FIELDOPS
        if self.anyv:
            return AV(None, None, dom)
        return AV(self.lo, self.hi, dom, limbaxis=self.limbaxis)


def _parse_num(tok: str) -> int:
    node = ast.parse(tok, mode="eval").body
    for sub in ast.walk(node):
        if not isinstance(sub, (ast.BinOp, ast.UnaryOp, ast.Constant,
                                ast.Pow, ast.Mult, ast.Add, ast.Sub,
                                ast.LShift, ast.USub, ast.operator,
                                ast.unaryop)):
            raise ValueError(f"bad bound expression {tok!r}")
    return int(eval(compile(ast.Expression(node), "<spec>", "eval")))  # noqa: S307


def parse_spec(tok: str) -> Spec:
    tok = tok.strip()
    if tok == "limb":
        return Spec(0, LIMB_MASK, limbaxis=True)
    if tok == "bit":
        return Spec(0, 1)
    if tok in ("any", "*"):
        return Spec(anyv=True)
    if tok == "fieldops":
        return Spec(fieldops=True)
    if tok.startswith("<="):
        return Spec(0, _parse_num(tok[2:]))
    if tok.startswith("<"):
        return Spec(0, _parse_num(tok[1:]) - 1)
    raise ValueError(f"unknown bound spec {tok!r}")


_DOM_TOKENS = {
    "mont": deg(1), "std": deg(0), "r2": deg(2),
    "neutral": DOM_NEUTRAL, "any": DOM_TOP, "same": ("sym", "S"),
}


def _split_specs(txt: str) -> tuple[list[str], str | None]:
    """'(a, b) -> c' | 'a -> c' | 'a'  ->  ([params], ret|None)."""
    txt = txt.strip()
    ret = None
    if "->" in txt:
        txt, ret = txt.split("->", 1)
        ret = ret.strip()
        txt = txt.strip()
    if txt.startswith("(") and txt.endswith(")"):
        txt = txt[1:-1]
    parts = [p.strip() for p in txt.split(",") if p.strip()] if txt else []
    return parts, ret


def _parse_ret(ret: str, parser):
    ret = ret.strip()
    if ret.startswith("(") and ret.endswith(")"):
        return AbsTuple(parser(p.strip())
                        for p in ret[1:-1].split(",") if p.strip())
    return parser(ret)


@dataclass
class Contract:
    params: list[Spec] = field(default_factory=list)
    ret: object = None                    # Spec | AbsTuple[Spec] | None
    doms: list[tuple] = field(default_factory=list)
    retdom: object = None                 # dom tuple | AbsTuple | None
    primitive: bool = False               # domain=mul: the mont primitive
    padding_safe: bool = False
    trusted: bool = False                 # assume-only: body not verified
    has_bounds: bool = False
    has_domain: bool = False


@dataclass
class ModuleAnno:
    is_kernel_module: bool = False
    dtype: str = "int32"
    twin: str | None = None
    tests: str | None = None
    dispatch: list[str] | None = None


def parse_contract(text: str) -> Contract:
    c = Contract()
    for clause in text.split(";"):
        clause = clause.strip()
        if not clause:
            continue
        if clause == "padding-safe":
            c.padding_safe = True
        elif clause == "trusted":
            c.trusted = True
        elif clause.startswith("bounds="):
            parts, ret = _split_specs(clause[len("bounds="):])
            c.params = [parse_spec(p) for p in parts]
            c.has_bounds = True
            if ret is not None:
                c.ret = _parse_ret(ret, parse_spec)
            elif not parts:
                c.ret = None
            elif len(parts) == 1 and ret is None and "->" not in clause:
                # value annotation: 'bounds=limb' on an assignment
                c.ret = c.params[0]
                c.params = []
        elif clause.startswith("domain="):
            body = clause[len("domain="):].strip()
            if body == "mul":
                c.primitive = True
                c.has_domain = True
                continue
            parts, ret = _split_specs(body)
            c.doms = [_DOM_TOKENS[p] for p in parts]
            c.has_domain = True
            if ret is not None:
                c.retdom = _parse_ret(
                    ret, lambda t: _DOM_TOKENS[t.strip()])
            elif len(parts) == 1 and "->" not in body:
                c.retdom = c.doms[0]
                c.doms = []
    return c


def parse_module_anno(text: str) -> ModuleAnno:
    m = ModuleAnno(is_kernel_module=True)
    for clause in text.split(";"):
        clause = clause.strip()
        if not clause:
            continue
        if clause.startswith("dtype="):
            m.dtype = clause[len("dtype="):].strip()
        elif clause.startswith("twin="):
            m.twin = clause[len("twin="):].strip()
        elif clause.startswith("tests="):
            m.tests = clause[len("tests="):].strip()
        elif clause.startswith("dispatch="):
            m.dispatch = [t.strip() for t in
                          clause[len("dispatch="):].split(",") if t.strip()]
    return m


def collect_annotations(source: str):
    """(module_anno | None, {line: (contract_text, standalone)}).
    ``standalone`` marks a comment-only line (an annotation for the
    def/assign BELOW it); trailing comments annotate their own line."""
    import io
    import tokenize

    mod = None
    lines: dict[int, tuple[str, bool]] = {}
    src_lines = source.splitlines()
    try:
        for tok in tokenize.generate_tokens(io.StringIO(source).readline):
            if tok.type != tokenize.COMMENT:
                continue
            m = _ANNO_RE.search(tok.string)
            if not m:
                continue
            if m.group(1) == "kernel-module":
                mod = parse_module_anno(m.group(2))
            else:
                row, col = tok.start
                standalone = row <= len(src_lines) and \
                    not src_lines[row - 1][:col].strip()
                lines[row] = (m.group(2).strip(), standalone)
    except (tokenize.TokenError, IndentationError, SyntaxError):
        pass
    return mod, lines


def _def_contract_line(node, annos: dict) -> int | None:
    """The annotation line feeding a def/assign: trailing on the node's
    first line, or a standalone annotation line directly above the def
    OR above its decorator stack (both placements are legal)."""
    if node.lineno in annos:
        return node.lineno
    starts = [node.lineno]
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
            and node.decorator_list:
        starts.append(min(d.lineno for d in node.decorator_list))
    for start in starts:
        above = annos.get(start - 1)
        if above is not None and above[1]:
            return start - 1
    return None


# ---------------------------------------------------------------------------
# the fieldops op table (curve.FieldOps abstract methods)

_LIMB_SPEC = Spec(0, LIMB_MASK, limbaxis=True)
_BIT_SPEC = Spec(0, 1)
_ANY_SPEC = Spec(anyv=True)

# method -> (param specs, param doms, ret spec, ret dom); 'join' ret
# means join of args (stack), None params means unchecked varargs
_FIELD_METHODS = {
    "mul": ([_LIMB_SPEC, _LIMB_SPEC], "mul", _LIMB_SPEC, None),
    "sqr": ([_LIMB_SPEC], "mul", _LIMB_SPEC, None),
    "add": ([_LIMB_SPEC, _LIMB_SPEC], "same", _LIMB_SPEC, "same"),
    "sub": ([_LIMB_SPEC, _LIMB_SPEC], "same", _LIMB_SPEC, "same"),
    "neg": ([_LIMB_SPEC], "same", _LIMB_SPEC, "same"),
    "dbl_": ([_LIMB_SPEC], "same", _LIMB_SPEC, "same"),
    "is_zero": ([_ANY_SPEC], None, _BIT_SPEC, DOM_NEUTRAL),
    "select": ([_ANY_SPEC, _LIMB_SPEC, _LIMB_SPEC], "sel",
               _LIMB_SPEC, "same"),
    "one": (None, None, _LIMB_SPEC, deg(1)),
    "zero": (None, None, Spec(0, 0), DOM_NEUTRAL),
    "stack": (None, None, "join", None),
}


class _Analysis:
    """One whole-program kernelcheck run."""

    def __init__(self, prog: Program):
        self.prog = prog
        self.module_annos: dict[str, ModuleAnno] = {}
        self.line_annos: dict[str, dict[int, str]] = {}
        self.contracts: dict[tuple, Contract] = {}  # (relpath, name)
        self.envs: dict[str, dict] = {}
        self._building: set[str] = set()
        self.findings: list[SiteFinding] = []
        self._flagged: set[tuple] = set()  # (relpath, id(node), rule)
        self._memo: dict = {}
        self._enclosing: dict[str, dict] = {}
        self._parity_texts: dict[str, list] = {}
        self._cur_rel: str | None = None
        self._dtype: tuple[int, int] = _DTYPES["int32"]
        self._domain_checks = True
        self._depth = 0

    # -- indexing -----------------------------------------------------------

    def index(self):
        for rel, mi in self.prog.modules.items():
            mod, lines = collect_annotations(mi.source)
            if mod:
                self.module_annos[rel] = mod
            self.line_annos[rel] = lines
            for node in mi.tree.body:
                self._index_def(rel, node, lines)
                if isinstance(node, ast.ClassDef):
                    for item in node.body:
                        self._index_def(rel, item, lines,
                                        prefix=node.name + ".")

    def _index_def(self, rel, node, lines, prefix=""):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return
        ln = _def_contract_line(node, lines)
        if ln is None:
            return
        try:
            c = parse_contract(lines[ln][0])
        except (ValueError, KeyError) as e:
            self.findings.append(SiteFinding(
                rel, "GL09", ln, 0,
                f"unparseable kernel contract: {e}", prefix + node.name))
            return
        self.contracts[(rel, prefix + node.name)] = c

    def enclosing(self, rel: str) -> dict:
        if rel not in self._enclosing:
            self._enclosing[rel] = _enclosing_map(self.prog.modules[rel].tree)
        return self._enclosing[rel]

    # -- findings -----------------------------------------------------------

    def emit(self, rule: str, node, message: str, detail: str = "",
             ctx: str | None = None):
        rel = self._cur_rel
        key = (rel, id(node), rule)
        if key in self._flagged:
            return
        self._flagged.add(key)
        if ctx is None:
            ctx = self.enclosing(rel).get(id(node), "<module>")
            if ctx == "<module>" and isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                ctx = node.name
        self.findings.append(SiteFinding(
            rel, rule, getattr(node, "lineno", 1),
            getattr(node, "col_offset", 0), message, ctx, detail))

    def check_overflow(self, node, av: AV, what: str):
        lo, hi = self._dtype
        if av.lo is not None and av.hi is not None and \
                (av.lo < lo or av.hi > hi):
            self.emit(
                "GL09", node,
                f"proven limb bound {av.desc()} can exceed the module "
                f"dtype lanes [{lo}, {hi}]",
                detail=f"{what}: {av.prov}" if av.prov else what)

    # -- module environments ------------------------------------------------

    def module_env(self, rel: str) -> dict:
        if rel in self.envs:
            return self.envs[rel]
        if rel in self._building or rel not in self.prog.modules:
            return {}
        self._building.add(rel)
        env: dict = {}
        self.envs[rel] = env
        mi = self.prog.modules[rel]
        prev_rel, prev_dtype = self._cur_rel, self._dtype
        self._cur_rel = rel
        anno = self.module_annos.get(rel)
        self._dtype = _DTYPES.get(anno.dtype if anno else "int32",
                                  _DTYPES["int32"])
        interp = Interp(self, rel, env, check=bool(anno))
        try:
            interp.exec_block(mi.tree.body)
        except _AnalysisError as e:
            self.findings.append(SiteFinding(
                rel, "GL09", e.line, 0,
                f"kernelcheck could not analyze module top level: "
                f"{e.msg}", "<module>"))
        finally:
            self._cur_rel, self._dtype = prev_rel, prev_dtype
            self._building.discard(rel)
        return env

    # -- verification roots -------------------------------------------------

    def run(self):
        self.index()
        kernel_mods = sorted(
            rel for rel, a in self.module_annos.items()
            if a.is_kernel_module)
        for rel in kernel_mods:
            self.module_env(rel)
        for rel in kernel_mods:
            mi = self.prog.modules[rel]
            anno = self.module_annos[rel]
            for node in mi.tree.body:
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) and \
                        (rel, node.name) in self.contracts:
                    self.verify_function(rel, node, anno)
        self.gl11()
        return self.findings

    def verify_function(self, rel: str, node, anno: ModuleAnno):
        c = self.contracts[(rel, node.name)]
        if not c.has_bounds or c.trusted:
            return  # value/padding-safe annotations, or host helpers
            # whose contract is asserted rather than derived (documented
            # in docs/ANALYSIS.md; their outputs are test-pinned)
        prev_rel, prev_dtype = self._cur_rel, self._dtype
        prev_dc = self._domain_checks
        self._cur_rel = rel
        self._dtype = _DTYPES.get(anno.dtype, _DTYPES["int32"])
        self._domain_checks = not c.primitive
        try:
            env = dict(self.module_env(rel))
            args = node.args
            names = [a.arg for a in (args.posonlyargs + args.args)]
            doms = list(c.doms)
            if c.primitive:
                doms = [deg(1)] * len(c.params)
            for i, pname in enumerate(names):
                spec = c.params[i] if i < len(c.params) else _ANY_SPEC
                d = doms[i] if i < len(doms) else DOM_TOP
                env[pname] = spec.seed(d)
            for a in args.kwonlyargs:
                env.setdefault(a.arg, TOPV)
            interp = Interp(self, rel, env, check=True)
            try:
                ret = interp.exec_func_body(node)
            except (_AnalysisError, RecursionError) as e:
                self.emit("GL09", node,
                          f"kernelcheck could not analyze "
                          f"{node.name}: {e}")
                return
            self._check_return(node, c, ret)
        finally:
            self._cur_rel, self._dtype = prev_rel, prev_dtype
            self._domain_checks = prev_dc

    def _check_return(self, node, c: Contract, ret):
        if is_known_conc(ret) and ret.value is None:
            # an out-ref kernel (pallas style): the declared return spec
            # bounds the output ref, checked at every store into it
            return
        if c.ret is not None:
            self._check_ret_spec(node, c.ret, ret, "return")
        if c.retdom is not None and not c.primitive:
            self._check_ret_dom(node, c.retdom, ret)

    def _check_ret_spec(self, node, spec, ret, what):
        if isinstance(spec, AbsTuple):
            vals = ret if isinstance(ret, AbsTuple) else \
                AbsTuple([ret] * len(spec))
            for i, s in enumerate(spec):
                v = vals[i] if i < len(vals) else TOPV
                self._check_ret_spec(node, s, v, f"{what}[{i}]")
            return
        bad = spec.check(ret)
        if bad:
            self.emit("GL09", node,
                      f"{what} violates the declared contract: {bad}",
                      detail=to_av(ret).prov)

    def _check_ret_dom(self, node, retdom, ret):
        if isinstance(retdom, AbsTuple):
            vals = ret if isinstance(ret, AbsTuple) else \
                AbsTuple([ret] * len(retdom))
            for d, v in zip(retdom, vals):
                self._check_ret_dom(node, d, v)
            return
        if retdom in (DOM_TOP, DOM_NEUTRAL):
            return
        have = to_av(ret).dom
        if have in (DOM_NEUTRAL,):
            return
        if have != retdom:
            self.emit("GL10", node,
                      f"returns {_dom_name(have)}-domain value where the "
                      f"contract declares {_dom_name(retdom)}")

    # -- GL11 ---------------------------------------------------------------

    def gl11(self):
        for rel in sorted(self.module_annos):
            anno = self.module_annos[rel]
            if anno.twin is None:
                continue
            self._gl11_module(rel, anno)

    def _dispatched(self, rel: str, anno: ModuleAnno) -> list:
        """Kernel def nodes device dispatch references (jax.jit(mod.f)),
        the dispatch= override, or — when neither names any — every
        public top-level def (single-file fixture mode)."""
        mi = self.prog.modules[rel]
        defs = {n.name: n for n in mi.tree.body
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        if anno.dispatch is not None:
            return [defs[n] for n in anno.dispatch if n in defs]
        names: set[str] = set()
        for orel, omi in self.prog.modules.items():
            for node in ast.walk(omi.tree):
                if not isinstance(node, ast.Call):
                    continue
                if dotted_name(node.func) not in (
                        "jax.jit", "jit", "jax.pmap", "pjit"):
                    continue
                for arg in node.args[:1]:
                    d = dotted_name(arg)
                    if not d:
                        continue
                    parts = d.split(".")
                    if len(parts) == 2 and omi.mod_imports.get(
                            parts[0]) == rel:
                        names.add(parts[1])
                    elif len(parts) == 1 and omi.name_imports.get(
                            parts[0], ("", ""))[0] == rel:
                        names.add(omi.name_imports[parts[0]][1])
        if names:
            return [defs[n] for n in sorted(names) if n in defs]
        return [defs[n] for n in sorted(defs) if not n.startswith("_")]

    def _gl11_module(self, rel: str, anno: ModuleAnno):
        self._cur_rel = rel
        twin_mi = self.prog.modules.get(anno.twin)
        twin_defs = set()
        if twin_mi is not None:
            twin_defs = {
                n.name for n in twin_mi.tree.body
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        guard_reach = self._padding_closure()
        for node in self._dispatched(rel, anno):
            name = node.name
            twin_name = name if anno.twin != rel else name + "_twin"
            if twin_name not in twin_defs:
                self.emit(
                    "GL11", node,
                    f"device-dispatched kernel {name} has no twin "
                    f"{twin_name} in {anno.twin}",
                    detail="twin module not in lint scope"
                    if twin_mi is None else "")
            if not self._has_parity_test(name, anno):
                self.emit(
                    "GL11", node,
                    f"device-dispatched kernel {name} has no parity "
                    "test referencing it under tests/")
            fid = f"{rel}::{name}"
            if not guard_reach.get(fid, False):
                self.emit(
                    "GL11", node,
                    f"device-dispatched kernel {name} never reaches an "
                    "infinity-sentinel guard (is_zero / padding-safe) "
                    "for its padding lanes")

    def _padding_closure(self) -> dict[str, bool]:
        """fid -> transitively reaches an is_zero-style guard or a
        padding-safe-annotated function."""
        direct: dict[str, bool] = {}
        for fid, fi in self.prog.funcs.items():
            c = self.contracts.get((fi.relpath, fi.qualname))
            safe = bool(c and c.padding_safe)
            if not safe:
                for node in ast.walk(fi.node):
                    if isinstance(node, ast.Call):
                        d = dotted_name(node.func) or ""
                        leaf = d.split(".")[-1]
                        if leaf.endswith("is_zero") or leaf == "infinity":
                            safe = True
                            break
            direct[fid] = safe
        changed = True
        rounds = 0
        while changed and rounds < 50:
            changed = False
            rounds += 1
            for fid in sorted(self.prog.call_edges):
                if direct.get(fid):
                    continue
                for callee in self.prog.call_edges[fid]:
                    if direct.get(callee):
                        direct[fid] = True
                        changed = True
                        break
        return direct

    def _has_parity_test(self, name: str, anno: ModuleAnno) -> bool:
        """A parity test = a tests/*.py that names the kernel (word-
        boundary) AND names the twin module's stem (word-boundary) —
        'reference'/'prefer' substrings don't count.  The text cache is
        per-run (``self``): a long-lived process re-reads tests/ every
        analysis, matching the engine cache's invalidation key."""
        if anno.tests == "skip":
            return True
        from .engine import REPO_ROOT

        root = REPO_ROOT / (anno.tests or "tests")
        if not root.is_dir():
            return False
        key = str(root)
        if key not in self._parity_texts:
            texts = []
            for p in sorted(root.glob("*.py")):
                try:
                    texts.append(p.read_text(encoding="utf-8"))
                except OSError:
                    continue
            self._parity_texts[key] = texts
        stem = (anno.twin or "twin").rsplit("/", 1)[-1]
        stem = stem[:-3] if stem.endswith(".py") else stem
        name_pat = re.compile(r"\b" + re.escape(name) + r"\b")
        twin_pat = re.compile(r"\b" + re.escape(stem) + r"\b")
        for text in self._parity_texts[key]:
            if name_pat.search(text) and twin_pat.search(text):
                return True
        return False


class _AnalysisError(Exception):
    def __init__(self, msg: str, line: int = 1):
        self.msg = msg
        self.line = line
        super().__init__(msg)


# ---------------------------------------------------------------------------
# the abstract interpreter


def _memokey(v):
    try:
        hash(v)
        return v
    except TypeError:
        return id(v)


class _Dead(Exception):
    """Control left the current path (return/raise)."""


class Interp:
    """Executes one scope (module top level or a function body) over
    the abstract domain."""

    def __init__(self, an: _Analysis, rel: str, env: dict,
                 check: bool):
        self.an = an
        self.rel = rel
        self.env = env
        self.check = check  # GL09/GL10 checks armed (kernel modules)
        self._returns = None

    # -- statements ---------------------------------------------------------

    def exec_func_body(self, node):
        try:
            self.exec_block(node.body)
        except _Dead:
            pass
        return self._returns if self._returns is not None else Conc(None)

    def exec_block(self, stmts):
        for s in stmts:
            self.exec_stmt(s)

    def exec_stmt(self, node):
        m = getattr(self, "_s_" + type(node).__name__, None)
        if m is not None:
            m(node)
        # unknown statement kinds are ignored (assert, global, ...)

    def _s_Expr(self, node):
        self.eval(node.value)

    def _s_Assign(self, node):
        val = self.eval(node.value)
        val = self._apply_line_anno(node, val)
        for tgt in node.targets:
            self._bind(tgt, val, node)

    def _s_AnnAssign(self, node):
        if node.value is not None:
            self._bind(node.target,
                       self._apply_line_anno(node, self.eval(node.value)),
                       node)

    def _s_AugAssign(self, node):
        cur = self.eval(node.target) if isinstance(
            node.target, ast.Name) else UNKNOWN
        val = self._binop(node, cur, node.op, self.eval(node.value))
        self._bind(node.target, val, node)

    def _apply_line_anno(self, node, val):
        """``X = ...  # graftlint: kernel bounds=limb; domain=mont``
        (trailing, or a standalone annotation line right above)."""
        annos = self.an.line_annos.get(self.rel, {})
        ln = _def_contract_line(node, annos)
        if ln is None:
            return val
        try:
            c = parse_contract(annos[ln][0])
        except (ValueError, KeyError) as e:
            self.an.emit("GL09", node,
                         f"unparseable kernel contract: {e}")
            return val
        av = to_av(val)
        if isinstance(c.ret, Spec) and not c.ret.anyv:
            av = replace(av, lo=c.ret.lo, hi=c.ret.hi,
                         limbaxis=c.ret.limbaxis or av.limbaxis)
        if c.retdom is not None and isinstance(c.retdom, tuple):
            av = replace(av, dom=c.retdom)
        return av

    def _bind(self, tgt, val, node):
        if isinstance(tgt, ast.Name):
            self.env[tgt.id] = val
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            elts = tgt.elts
            vals = None
            if isinstance(val, AbsTuple) and len(val) == len(elts):
                vals = list(val)
            elif is_known_conc(val) and isinstance(
                    val.value, (tuple, list)) and \
                    len(val.value) == len(elts):
                vals = [Conc(v) for v in val.value]
            for i, e in enumerate(elts):
                self._bind(e, vals[i] if vals else TOPV, node)
        elif isinstance(tgt, ast.Subscript):
            # store through a ref (pallas out_ref): check against the
            # declared bound of the ref it stores into
            if isinstance(tgt.value, ast.Name):
                ref = self.env.get(tgt.value.id)
                if isinstance(ref, AV) and ref.bounded and self.check:
                    a = to_av(val)
                    if not a.bounded or a.lo < ref.lo or a.hi > ref.hi:
                        self.an.emit(
                            "GL09", node,
                            f"store into {tgt.value.id} of "
                            f"{a.desc()} exceeds its declared bound "
                            f"{ref.desc()}", detail=a.prov)
        elif isinstance(tgt, ast.Starred):
            self._bind(tgt.value, TOPV, node)

    def _s_Return(self, node):
        val = self.eval(node.value) if node.value is not None \
            else Conc(None)
        self._returns = val if self._returns is None \
            else av_join(self._returns, val)
        raise _Dead()

    def _s_Raise(self, node):
        raise _Dead()

    def _s_If(self, node):
        test = self.eval(node.test)
        if is_known_conc(test):
            branch = node.body if test.value else node.orelse
            self.exec_block(branch)
            return
        self._join_branches([node.body, node.orelse])

    def _join_branches(self, branches):
        pre = dict(self.env)
        outs = []
        for body in branches:
            self.env.clear()
            self.env.update(pre)
            try:
                self.exec_block(body)
                outs.append(dict(self.env))
            except _Dead:
                pass  # no fallthrough from this branch
        self.env.clear()
        if not outs:
            self.env.update(pre)
            raise _Dead()
        merged = outs[0]
        for other in outs[1:]:
            keys = set(merged) | set(other)
            merged = {
                k: av_join(merged.get(k, pre.get(k, TOPV)),
                           other.get(k, pre.get(k, TOPV)))
                for k in keys
            }
        self.env.update(merged)

    def _s_With(self, node):
        for item in node.items:
            self.eval(item.context_expr)
        self.exec_block(node.body)

    def _s_Try(self, node):
        pre = dict(self.env)
        try:
            self.exec_block(node.body)
        except _Dead:
            pass
        body_env = dict(self.env)
        for h in node.handlers:
            self.env.clear()
            self.env.update(pre)
            try:
                self.exec_block(h.body)
            except _Dead:
                continue
            keys = set(body_env) | set(self.env)
            body_env = {
                k: av_join(body_env.get(k, pre.get(k, TOPV)),
                           self.env.get(k, pre.get(k, TOPV)))
                for k in keys
            }
        self.env.clear()
        self.env.update(body_env)
        self.exec_block(node.finalbody)

    def _s_FunctionDef(self, node):
        self.env[node.name] = Closure(node, self.env, self.rel)

    _s_AsyncFunctionDef = _s_FunctionDef

    def _s_ClassDef(self, node):
        self.env[node.name] = UNKNOWN

    def _s_Delete(self, node):
        for t in node.targets:
            if isinstance(t, ast.Name):
                self.env.pop(t.id, None)

    def _s_Import(self, node):
        for a in node.names:
            target = self.an.prog._module_path_of(self.rel, a.name, 0)
            name = a.asname or a.name.split(".")[0]
            self.env[name] = ModRef(target) if target else UNKNOWN

    def _s_ImportFrom(self, node):
        prog = self.an.prog
        modpath = prog._module_path_of(
            self.rel, node.module or "", node.level)
        for a in node.names:
            local = a.asname or a.name
            sub = prog._module_path_of(
                self.rel,
                ".".join(p for p in (node.module, a.name) if p),
                node.level)
            if sub is not None:
                self.env[local] = ModRef(sub)
            elif modpath is not None:
                self.env[local] = self._mod_attr(modpath, a.name)
            else:
                self.env[local] = UNKNOWN

    def _mod_attr(self, relpath: str, name: str):
        menv = self.an.module_env(relpath)
        if name in menv:
            return menv[name]
        mi = self.an.prog.modules.get(relpath)
        if mi is not None and name in mi.functions:
            return FuncRef(relpath, name)
        return UNKNOWN

    # -- loops --------------------------------------------------------------

    def _s_For(self, node):
        it = self.eval(node.iter)
        items = None
        if is_known_conc(it) and isinstance(
                it.value, (range, list, tuple, str)):
            items = [Conc(v) if not isinstance(v, (AV, AbsTuple, Conc))
                     else v for v in it.value]
        elif isinstance(it, AbsTuple):
            items = list(it)
        if items is not None and len(items) <= _UNROLL_CAP:
            for v in items:
                self._bind(node.target, v, node)
                self.exec_block(node.body)
            self.exec_block(node.orelse)
            return
        elem = self._elem_of(it)
        self._fix_loop(node, lambda: (self._bind(node.target, elem, node),
                                      self.exec_block(node.body)))
        self.exec_block(node.orelse)

    def _s_While(self, node):
        # concrete spin first: a loop over host ints runs for real
        for _ in range(_UNROLL_CAP):
            test = self.eval(node.test)
            if not is_known_conc(test):
                break
            if not test.value:
                self.exec_block(node.orelse)
                return
            self.exec_block(node.body)
        else:
            self.an.emit("GL09", node,
                         "concrete loop exceeded the unroll cap")
            return
        self._fix_loop(node, lambda: self.exec_block(node.body))
        self.exec_block(node.orelse)

    def _fix_loop(self, node, run_body):
        """Join-fixpoint over a loop body with interval widening."""
        for i in range(_LOOP_CAP):
            pre = dict(self.env)
            try:
                run_body()
            except _Dead:
                pass
            keys = set(pre) | set(self.env)
            nxt = {}
            stable = True
            for k in keys:
                a = pre.get(k, TOPV)
                b = self.env.get(k, pre.get(k, TOPV))
                j = av_join(a, b)
                if i >= _WIDEN_AFTER:
                    j = widen_any(a, j)
                if j != a:
                    stable = False
                nxt[k] = j
            self.env.clear()
            self.env.update(nxt)
            if stable:
                return
        self.an.emit("GL09", node,
                     "loop state does not stabilize under widening "
                     "(no provable bound)")

    def _elem_of(self, it):
        if isinstance(it, AV):
            return replace(it, scanlen=None)
        if isinstance(it, AbsTuple):
            return AbsTuple(self._elem_of(e) for e in it)
        if is_known_conc(it) and isinstance(
                it.value, (range, list, tuple, str)):
            out = None
            for v in it.value:
                c = v if isinstance(v, (AV, AbsTuple, Conc)) else Conc(v)
                out = c if out is None else av_join(out, c)
            return out if out is not None else UNKNOWN
        return TOPV if isinstance(it, AV) else UNKNOWN

    # -- expressions --------------------------------------------------------

    def eval(self, node):
        m = getattr(self, "_e_" + type(node).__name__, None)
        if m is None:
            return UNKNOWN
        return m(node)

    def _e_Constant(self, node):
        return Conc(node.value)

    def _e_Name(self, node):
        if node.id in self.env:
            return self.env[node.id]
        return UNKNOWN

    def _e_Attribute(self, node):
        base = self.eval(node.value)
        if isinstance(base, ModRef):
            return self._mod_attr(base.relpath, node.attr)
        if isinstance(base, AV):
            if node.attr == "T":
                return replace(base, limbaxis=False, scanlen=None)
            return UNKNOWN
        if isinstance(base, FieldOpsVal):
            return ("fieldmeth", node.attr)
        return UNKNOWN

    def _e_Tuple(self, node):
        return self._seq(node.elts)

    _e_List = _e_Tuple

    def _seq(self, elts):
        out = []
        for e in elts:
            if isinstance(e, ast.Starred):
                inner = self.eval(e.value)
                if isinstance(inner, AbsTuple):
                    out.extend(inner)
                elif is_known_conc(inner) and isinstance(
                        inner.value, (tuple, list)):
                    out.extend(Conc(v) for v in inner.value)
                else:
                    out.append(UNKNOWN)
            else:
                out.append(self.eval(e))
        return AbsTuple(out)

    def _e_IfExp(self, node):
        test = self.eval(node.test)
        if is_known_conc(test):
            return self.eval(node.body if test.value else node.orelse)
        return av_join(self.eval(node.body), self.eval(node.orelse))

    def _e_BoolOp(self, node):
        vals = [self.eval(v) for v in node.values]
        if all(is_known_conc(v) for v in vals):
            out = vals[0].value
            for v in vals[1:]:
                out = (out and v.value) if isinstance(node.op, ast.And) \
                    else (out or v.value)
            return Conc(out)
        if any(isinstance(v, AV) for v in vals):
            return AV(0, 1, DOM_NEUTRAL)
        return UNKNOWN

    def _e_Compare(self, node):
        left = self.eval(node.left)
        rights = [self.eval(c) for c in node.comparators]
        if is_known_conc(left) and all(is_known_conc(r) for r in rights):
            try:
                vals = [left.value] + [r.value for r in rights]
                ok = True
                for (a, b), op in zip(zip(vals, vals[1:]), node.ops):
                    ok = ok and _conc_compare(a, b, op)
                return Conc(bool(ok))
            except (TypeError, ValueError):
                return UNKNOWN
        return AV(0, 1, DOM_NEUTRAL)

    def _e_UnaryOp(self, node):
        v = self.eval(node.operand)
        if is_known_conc(v):
            try:
                if isinstance(node.op, ast.USub):
                    return Conc(-v.value)
                if isinstance(node.op, ast.Not):
                    return Conc(not v.value)
                if isinstance(node.op, ast.Invert):
                    return Conc(~v.value)
                return v
            except TypeError:
                return UNKNOWN
        a = to_av(v)
        if isinstance(node.op, ast.USub) and a.bounded:
            return AV(-a.hi, -a.lo, a.dom, prov=a.prov)
        if isinstance(node.op, (ast.Not, ast.Invert)) and \
                isinstance(v, AV):
            return AV(0, 1, DOM_NEUTRAL) if a.bounded and \
                0 <= a.lo and a.hi <= 1 else TOPV
        return TOPV if isinstance(v, AV) else UNKNOWN

    def _e_BinOp(self, node):
        return self._binop(node, self.eval(node.left), node.op,
                           self.eval(node.right))

    def _e_Subscript(self, node):
        base = self.eval(node.value)
        idx = self._eval_index(node.slice)
        if isinstance(base, AbsTuple):
            if is_known_conc(idx) and isinstance(idx.value, int):
                i = idx.value
                return base[i] if -len(base) <= i < len(base) else TOPV
            if is_known_conc(idx) and isinstance(idx.value, slice):
                return AbsTuple(base[idx.value])
            out = None
            for e in base:
                out = e if out is None else av_join(out, e)
            return out if out is not None else TOPV
        if is_known_conc(base):
            if is_known_conc(idx):
                try:
                    return Conc(base.value[idx.value])
                except (TypeError, KeyError, IndexError):
                    return UNKNOWN
            return UNKNOWN
        if isinstance(base, AV):
            # pure indexing/slicing never raises an element bound
            return replace(base, limbaxis=False, scanlen=None)
        return UNKNOWN

    def _eval_index(self, node):
        if isinstance(node, ast.Slice):
            parts = [self.eval(p) if p is not None else Conc(None)
                     for p in (node.lower, node.upper, node.step)]
            if all(is_known_conc(p) for p in parts):
                return Conc(slice(*(p.value for p in parts)))
            return UNKNOWN
        if isinstance(node, ast.Tuple):
            return UNKNOWN  # multi-axis index: bounds unchanged anyway
        return self.eval(node)

    def _e_ListComp(self, node):
        return self._comp(node)

    def _e_GeneratorExp(self, node):
        return self._comp(node)

    def _comp(self, node):
        if len(node.generators) != 1:
            return UNKNOWN
        gen = node.generators[0]
        it = self.eval(gen.iter)
        saved = dict(self.env)
        try:
            if is_known_conc(it) and isinstance(
                    it.value, (range, list, tuple, str)) and \
                    len(it.value) <= _UNROLL_CAP:
                out = []
                for v in it.value:
                    self._bind(gen.target,
                               v if isinstance(v, (AV, AbsTuple, Conc))
                               else Conc(v), node)
                    conds = [self.eval(c) for c in gen.ifs]
                    if any(is_known_conc(c) and not c.value
                           for c in conds):
                        continue
                    out.append(self.eval(node.elt))
                return AbsTuple(out)
            if isinstance(it, AbsTuple) and len(it) <= _UNROLL_CAP:
                out = []
                for v in it:
                    self._bind(gen.target, v, node)
                    out.append(self.eval(node.elt))
                return AbsTuple(out)
            self._bind(gen.target, self._elem_of(it), node)
            return AbsTuple([self.eval(node.elt)])
        finally:
            self.env.clear()
            self.env.update(saved)

    def _e_Lambda(self, node):
        return Closure(node, self.env, self.rel)

    def _e_JoinedStr(self, node):
        return UNKNOWN

    def _e_Starred(self, node):
        return self.eval(node.value)

    # -- arithmetic ---------------------------------------------------------

    def _binop(self, node, left, op, right):
        if is_known_conc(left) and is_known_conc(right):
            try:
                return Conc(_conc_binop(left.value, op, right.value))
            except (TypeError, ValueError, ZeroDivisionError,
                    OverflowError):
                return UNKNOWN
        if not isinstance(left, AV) and not isinstance(right, AV):
            return UNKNOWN
        a, b = to_av(left), to_av(right)
        out = self._interval_op(a, op, b)
        out = self._domain_op(node, a, op, b, out)
        if not isinstance(op, ast.MatMult):
            # elementwise ops keep the limb axis (broadcast included),
            # and a stack's leading axis against an operand of its rank
            out = replace(out, limbaxis=a.limbaxis or b.limbaxis,
                          scanlen=a.scanlen or b.scanlen)
        if self.check:
            self.an.check_overflow(
                node, out,
                f"{_opname(op)} of {a.desc()} and {b.desc()}")
        return out

    def _interval_op(self, a: AV, op, b: AV) -> AV:
        la, ha, lb, hb = a.lo, a.hi, b.lo, b.hi
        prov = ""
        if isinstance(op, ast.Add):
            lo = None if la is None or lb is None else la + lb
            hi = None if ha is None or hb is None else ha + hb
            prov = f"{a.desc()}+{b.desc()}"
        elif isinstance(op, ast.Sub):
            lo = None if la is None or hb is None else la - hb
            hi = None if ha is None or lb is None else ha - lb
            prov = f"{a.desc()}-{b.desc()}"
        elif isinstance(op, ast.Mult):
            if a.bounded and b.bounded:
                prods = [la * lb, la * hb, ha * lb, ha * hb]
                lo, hi = min(prods), max(prods)
            else:
                lo = hi = None
            prov = f"{a.desc()}*{b.desc()}"
        elif isinstance(op, ast.RShift):
            if b.bounded and lb == hb and lb >= 0:
                lo = None if la is None else la >> lb
                hi = None if ha is None else ha >> lb
            else:
                lo, hi = (0, ha) if la is not None and la >= 0 \
                    else (None, None)
            prov = f"{a.desc()}>>{lb if lb == hb else '?'}"
        elif isinstance(op, ast.LShift):
            if b.bounded and lb == hb and lb >= 0 and a.bounded:
                lo, hi = la << lb, ha << lb
            else:
                lo = hi = None
            prov = f"{a.desc()}<<{lb if lb == hb else '?'}"
        elif isinstance(op, ast.BitAnd):
            # masking with a nonneg value lands in [0, mask] regardless
            # of sign (int32 two's complement)
            cands = [x for x in (ha if la is not None and la >= 0
                                 else None,
                                 hb if lb is not None and lb >= 0
                                 else None) if x is not None]
            if hb is not None and lb == hb and hb >= 0:
                lo, hi = 0, hb
            elif ha is not None and la == ha and ha >= 0:
                lo, hi = 0, ha
            elif cands:
                lo, hi = 0, min(cands)
            else:
                lo = hi = None
            prov = f"{a.desc()}&{b.desc()}"
        elif isinstance(op, ast.BitOr):
            if a.bounded and b.bounded and la >= 0 and lb >= 0:
                lo, hi = 0, _pow2ceil(max(ha, hb) + 1) - 1
            else:
                lo = hi = None
            prov = f"{a.desc()}|{b.desc()}"
        elif isinstance(op, ast.BitXor):
            if a.bounded and b.bounded and la >= 0 and lb >= 0:
                lo, hi = 0, _pow2ceil(max(ha, hb) + 1) - 1
            else:
                lo = hi = None
            prov = f"{a.desc()}^{b.desc()}"
        elif isinstance(op, ast.FloorDiv):
            if a.bounded and b.bounded and lb == hb and lb > 0:
                lo, hi = la // lb, ha // lb
            else:
                lo = hi = None
            prov = f"{a.desc()}//{b.desc()}"
        elif isinstance(op, ast.Mod):
            if b.bounded and lb == hb and lb > 0:
                lo, hi = 0, hb - 1
            else:
                lo = hi = None
            prov = f"{a.desc()}%{b.desc()}"
        else:  # Div, Pow, MatMult, ...
            if isinstance(op, ast.MatMult):
                return self._reduction_product(a, b)
            lo = hi = None
            prov = _opname(op)
        return AV(lo, hi, DOM_TOP, prov=prov)

    def _reduction_product(self, a: AV, b: AV,
                           limb_contraction: bool | None = None) -> AV:
        """matmul/einsum-style contraction: elementwise product times
        the contraction length.  Provable ONLY when the contracted
        axis is the limb axis of the left operand (matmul contracts
        a's LAST axis; einsum passes ``limb_contraction`` from its
        parsed spec) — any other contraction length is unproven and
        fails at the next contract, never silently certified."""
        prod = self._interval_op(a, ast.Mult(), b)
        if limb_contraction is None:
            limb_contraction = a.limbaxis  # matmul: contracts a[..., -1]
        if prod.bounded and limb_contraction:
            return AV(min(prod.lo * N_LIMBS, 0), prod.hi * N_LIMBS,
                      DOM_TOP,
                      prov=f"{prod.prov} summed over {N_LIMBS} limbs")
        return AV(None, None, DOM_TOP, prov=prod.prov + " summed over "
                  "an unproven contraction length")

    def _domain_op(self, node, a: AV, op, b: AV, out: AV) -> AV:
        dc = self.check and self.an._domain_checks
        if isinstance(op, (ast.Add, ast.Sub)):
            if dc and _dom_mixes(a.dom, b.dom):
                self.an.emit(
                    "GL10", node,
                    f"{_opname(op)} mixes Montgomery domains "
                    f"{_dom_name(a.dom)} and {_dom_name(b.dom)}")
            return replace(out, dom=_dom_join(a.dom, b.dom))
        if isinstance(op, ast.Mult):
            if a.dom == DOM_NEUTRAL:
                return replace(out, dom=b.dom)
            if b.dom == DOM_NEUTRAL:
                return replace(out, dom=a.dom)
            if dc and a.dom[0] == "deg" and b.dom[0] == "deg":
                self.an.emit(
                    "GL10", node,
                    f"raw * product of {_dom_name(a.dom)}-domain and "
                    f"{_dom_name(b.dom)}-domain values outside the "
                    "mont_mul primitive")
            return replace(out, dom=DOM_TOP)
        if isinstance(op, (ast.RShift, ast.LShift, ast.BitAnd,
                           ast.BitOr, ast.BitXor, ast.Mod,
                           ast.FloorDiv)):
            # carry plumbing keeps the field element's domain
            keep = a.dom if isinstance(op, (ast.RShift, ast.LShift)) \
                else _dom_join(a.dom if a.dom != DOM_TOP else b.dom,
                               b.dom if b.dom != DOM_TOP else a.dom)
            return replace(out, dom=keep if keep != DOM_TOP
                           else _dom_join(a.dom, b.dom))
        return out

    # -- calls --------------------------------------------------------------

    def _eval_args(self, arg_nodes):
        out = []
        for a in arg_nodes:
            if isinstance(a, ast.Starred):
                inner = self.eval(a.value)
                if isinstance(inner, AbsTuple):
                    out.extend(inner)
                elif is_known_conc(inner) and isinstance(
                        inner.value, (tuple, list)):
                    out.extend(Conc(v) for v in inner.value)
                else:
                    out.append(UNKNOWN)
            else:
                out.append(self.eval(a))
        return out

    def _e_Call(self, node):
        dotted = dotted_name(node.func)
        key = _intrinsic_key(dotted)
        if key is not None:
            args = self._eval_args(node.args)
            kwargs = {k.arg: self.eval(k.value)
                      for k in node.keywords if k.arg}
            return _INTRINSICS[key](self, node, args, kwargs)
        args = self._eval_args(node.args)
        kwargs = {k.arg: self.eval(k.value)
                  for k in node.keywords if k.arg}
        if isinstance(node.func, ast.Name) and \
                node.func.id not in self.env:
            return self._builtin(node, node.func.id, args, kwargs)
        if isinstance(node.func, ast.Attribute):
            base = self.eval(node.func.value)
            if isinstance(base, AV):
                return self._av_method(node, base, node.func.attr, args)
            if isinstance(base, FieldOpsVal):
                return self._field_call(node, node.func.attr, args)
            if isinstance(base, ModRef):
                fn = self._mod_attr(base.relpath, node.func.attr)
                return self.call_value(fn, node, args, kwargs)
            return UNKNOWN
        fn = self.eval(node.func)
        return self.call_value(fn, node, args, kwargs)

    def call_value(self, fn, node, args, kwargs=None):
        kwargs = kwargs or {}
        if isinstance(fn, Closure):
            c = self.an.contracts.get((fn.relpath, fn.node.name)) \
                if isinstance(fn.node, (ast.FunctionDef,
                                        ast.AsyncFunctionDef)) else None
            if c is not None and c.has_bounds:
                return self._contract_call(
                    fn.relpath, fn.node.name, c, node, args)
            return self._inline(fn.node, fn.env, fn.relpath, node,
                                args, kwargs, memo=False)
        if isinstance(fn, FuncRef):
            c = self.an.contracts.get((fn.relpath, fn.name))
            if c is not None and c.has_bounds:
                return self._contract_call(fn.relpath, fn.name, c,
                                           node, args)
            fid = f"{fn.relpath}::{fn.name}"
            fi = self.an.prog.funcs.get(fid)
            if fi is None:
                return UNKNOWN
            env = self.an.module_env(fn.relpath)
            return self._inline(fi.node, env, fn.relpath, node, args,
                                kwargs, memo=True)
        if isinstance(fn, tuple) and len(fn) == 2 and \
                fn[0] == "fieldmeth":
            return self._field_call(node, fn[1], args)
        if isinstance(fn, _PallasProg):
            return fn.result(self)
        if isinstance(fn, _Partial):
            return self.call_value(fn.fn, node,
                                   list(fn.args) + list(args),
                                   {**fn.kwargs, **kwargs})
        if any(isinstance(a, AV) for a in args):
            return TOPV
        return UNKNOWN

    def _builtin(self, node, name, args, kwargs):
        if name in ("range", "len", "int", "bin", "hex", "min", "max",
                    "abs", "sum", "bool", "str", "float", "enumerate",
                    "zip", "list", "tuple", "sorted", "reversed",
                    "round", "ord", "chr", "divmod"):
            if all(is_known_conc(a) for a in args) and not kwargs:
                import builtins

                try:
                    v = getattr(builtins, name)(
                        *(a.value for a in args))
                    if name in ("enumerate", "zip", "reversed"):
                        v = list(v)
                    return Conc(v)
                except (TypeError, ValueError, OverflowError):
                    return UNKNOWN
            if name in ("list", "tuple") and args and \
                    isinstance(args[0], AbsTuple):
                return args[0]
            if name in ("len",) and args and \
                    isinstance(args[0], AbsTuple):
                return Conc(len(args[0]))
        return UNKNOWN

    def _av_method(self, node, base, meth, args):
        if meth in ("astype", "copy", "view", "clip", "block_until_ready"):
            return base
        if meth in ("reshape", "transpose", "swapaxes", "ravel",
                    "flatten", "squeeze"):
            return replace(base, limbaxis=False, scanlen=None)
        if meth == "sum":
            return self._reduce_sum(node, base, 0)
        if meth in ("max", "min"):
            return replace(base, limbaxis=False, scanlen=None)
        if meth in ("item", "tolist"):
            return UNKNOWN
        return TOPV

    def _reduce_sum(self, node, x, axis_pos=None):
        a = to_av(x)
        if axis_pos is not None and _names_axis0(node, axis_pos):
            # a sum over a stack's leading axis adds its scanlen terms
            if a.bounded and a.scanlen:
                out = AV(min(a.lo, 0) * a.scanlen, max(a.hi, 0) * a.scanlen,
                         a.dom, prov=f"sum of {a.scanlen} stacked terms "
                                     f"each {a.desc()}")
                if self.check:
                    self.an.check_overflow(node, out, "stacked sum")
                return out
        elif a.bounded and a.limbaxis:
            out = AV(a.lo * N_LIMBS if a.lo < 0 else 0,
                     a.hi * N_LIMBS, a.dom,
                     prov=f"sum of {N_LIMBS} limbs each {a.desc()}")
            if self.check:
                self.an.check_overflow(node, out, "limb-axis sum")
            return out
        return AV(None, None, a.dom,
                  prov=f"sum over an unproven length of {a.desc()}")

    def _field_call(self, node, meth, args):
        info = _FIELD_METHODS.get(meth)
        if info is None:
            return TOPV
        specs, domkind, ret, retdom = info
        if ret == "join":
            out = None
            for e in (args[0] if args and isinstance(args[0], AbsTuple)
                      else args):
                out = e if out is None else av_join(out, e)
            return out if out is not None else TOPV
        if specs is not None and self.check:
            for i, spec in enumerate(specs):
                if i >= len(args):
                    break
                bad = spec.check(args[i])
                if bad:
                    self.an.emit(
                        "GL09", node,
                        f"argument {i} of field op .{meth}(): {bad}",
                        detail=to_av(args[i]).prov)
        dom = retdom if isinstance(retdom, tuple) else DOM_TOP
        if domkind == "mul":
            degs = [to_av(a).dom for a in args]
            if all(d[0] == "deg" for d in degs):
                d = sum(x[1] for x in degs) * (2 if len(degs) == 1
                                               else 1) - 1
                dom = deg(d)
                self._check_deg(node, d, meth)
        elif domkind in ("same", "sel"):
            pick = args[1:] if domkind == "sel" else args
            dom = self._unify(node, [to_av(a).dom for a in pick],
                              f"field op .{meth}()")
        av = AV(ret.lo, ret.hi, dom, limbaxis=ret.limbaxis)
        return av

    def _check_deg(self, node, d, what):
        if self.check and self.an._domain_checks and d not in (0, 1, 2):
            self.an.emit("GL10", node,
                         f"{what} yields Montgomery degree R^{d} "
                         "(outside std/mont/r2 — a missing to_mont/"
                         "from_mont conversion)")

    def _unify(self, node, doms, what) -> tuple:
        uni = None
        all_neutral = True
        for d in doms:
            if d == DOM_NEUTRAL:
                continue
            all_neutral = False
            if d == DOM_TOP:
                continue
            if uni is None:
                uni = d
            elif uni != d:
                if self.check and self.an._domain_checks:
                    self.an.emit(
                        "GL10", node,
                        f"{what} mixes Montgomery domains "
                        f"{_dom_name(uni)} and {_dom_name(d)}")
                return DOM_TOP
        if all_neutral:
            return DOM_NEUTRAL
        return uni if uni is not None else DOM_TOP

    def _contract_call(self, rel, name, c, node, args):
        if c.has_bounds and self.check:
            for i, spec in enumerate(c.params):
                if i >= len(args):
                    break
                bad = spec.check(args[i])
                if bad:
                    self.an.emit(
                        "GL09", node,
                        f"argument {i} of {name}(): {bad}",
                        detail=to_av(args[i]).prov)
        retdom = self._call_retdom(node, name, c, args)
        return self._ret_from_spec(c.ret, retdom, name)

    def _call_retdom(self, node, name, c, args):
        if c.primitive:
            degs = [to_av(a).dom for a in args[:2]]
            if len(degs) == 2 and all(d[0] == "deg" for d in degs):
                d = degs[0][1] + degs[1][1] - 1
                self._check_deg(node, d, f"{name}()")
                return deg(d)
            return DOM_TOP
        doms = c.doms
        sym_doms = [to_av(a).dom for i, a in enumerate(args)
                    if i < len(doms) and doms[i] == ("sym", "S")]
        if self.check and self.an._domain_checks:
            for i, spec_dom in enumerate(doms):
                if i >= len(args) or spec_dom in (
                        DOM_TOP, DOM_NEUTRAL) or spec_dom[0] == "sym":
                    continue
                have = to_av(args[i]).dom
                if have[0] == "deg" and have != spec_dom:
                    self.an.emit(
                        "GL10", node,
                        f"argument {i} of {name}() is "
                        f"{_dom_name(have)}-domain where the contract "
                        f"declares {_dom_name(spec_dom)}")
        unified = self._unify(node, sym_doms, f"{name}()") \
            if sym_doms else DOM_TOP
        return self._resolve_retdom(c.retdom, unified)

    def _resolve_retdom(self, retdom, unified):
        if retdom is None:
            return DOM_TOP
        if isinstance(retdom, AbsTuple):
            return AbsTuple(self._resolve_retdom(d, unified)
                            for d in retdom)
        if retdom == ("sym", "S"):
            return unified
        return retdom

    def _ret_from_spec(self, ret, retdom, name):
        if ret is None:
            return AV(None, None,
                      retdom if isinstance(retdom, tuple) else DOM_TOP)
        if isinstance(ret, AbsTuple):
            doms = retdom if isinstance(retdom, AbsTuple) \
                else AbsTuple([retdom] * len(ret))
            return AbsTuple(self._ret_from_spec(s, d, name)
                            for s, d in zip(ret, doms))
        dom = retdom if isinstance(retdom, tuple) else DOM_TOP
        if ret.fieldops:
            return FIELDOPS
        return AV(ret.lo, ret.hi, dom, limbaxis=ret.limbaxis,
                  prov=f"contract of {name}")

    def _inline(self, fnode, defenv, defrel, node, args, kwargs,
                memo):
        an = self.an
        if an._depth >= _INLINE_DEPTH:
            return TOPV
        key = None
        if memo:
            key = (defrel, id(fnode),
                   tuple(_memokey(a) for a in args),
                   tuple(sorted((k, _memokey(v))
                                for k, v in kwargs.items())))
            if key in an._memo:
                got = an._memo[key]
                return TOPV if got is _INPROGRESS else got
            an._memo[key] = _INPROGRESS
        env = dict(defenv)
        a = fnode.args
        pos = list(a.posonlyargs) + list(a.args)
        bound = set()
        for i, p in enumerate(pos):
            if i < len(args):
                env[p.arg] = args[i]
                bound.add(p.arg)
        for k, v in kwargs.items():
            env[k] = v
            bound.add(k)
        if a.vararg:
            env[a.vararg.arg] = AbsTuple(args[len(pos):])
        if a.kwarg:
            env[a.kwarg.arg] = UNKNOWN
        prev_rel = an._cur_rel
        an._cur_rel = defrel
        an._depth += 1
        child = Interp(an, defrel, env, check=defrel in an.module_annos)
        try:
            ndef = len(a.defaults)
            for j, d in enumerate(a.defaults):
                p = pos[len(pos) - ndef + j]
                if p.arg not in bound:
                    env[p.arg] = child.eval(d)
            for p, d in zip(a.kwonlyargs, a.kw_defaults):
                if p.arg not in bound:
                    env[p.arg] = child.eval(d) if d is not None \
                        else UNKNOWN
            if isinstance(fnode, ast.Lambda):
                ret = child.eval(fnode.body)
            else:
                ret = child.exec_func_body(fnode)
        finally:
            an._cur_rel = prev_rel
            an._depth -= 1
        if memo and key is not None:
            an._memo[key] = ret
        return ret

    # -- lax loop primitives ------------------------------------------------

    def _lax_scan(self, node, args, kwargs):
        if len(args) < 3:
            return TOPV
        f, init, xs = args[0], args[1], args[2]
        xelem = self._elem_of(xs)
        n = xs.scanlen if isinstance(xs, AV) else None
        if n:
            carry = init
            for _ in range(min(n, _UNROLL_CAP)):
                r = self.call_value(f, node, [carry, xelem])
                carry = r[0] if isinstance(r, AbsTuple) and len(r) == 2 \
                    else TOPV
            return AbsTuple([carry, TOPV])
        carry = init
        for i in range(_LOOP_CAP):
            r = self.call_value(f, node, [carry, xelem])
            c2 = r[0] if isinstance(r, AbsTuple) and len(r) == 2 \
                else TOPV
            j = av_join(carry, c2)
            if i >= _WIDEN_AFTER:
                j = widen_any(carry, j)
            if j == carry:
                return AbsTuple([carry, TOPV])
            carry = j
        self.an.emit("GL09", node,
                     "lax.scan carry does not stabilize under widening "
                     "(no provable bound)")
        return AbsTuple([TOPV, TOPV])

    def _lax_fori(self, node, args, kwargs):
        if len(args) < 4:
            return TOPV
        lo, hi, body, init = args[0], args[1], args[2], args[3]
        if is_known_conc(lo) and is_known_conc(hi) and \
                isinstance(lo.value, int) and isinstance(hi.value, int):
            n = hi.value - lo.value
            if 0 <= n <= _UNROLL_CAP:
                carry = init
                for i in range(n):
                    carry = self.call_value(
                        body, node, [Conc(lo.value + i), carry])
                return carry
        carry = init
        for i in range(_LOOP_CAP):
            c2 = self.call_value(body, node, [UNKNOWN, carry])
            j = av_join(carry, c2)
            if i >= _WIDEN_AFTER:
                j = widen_any(carry, j)
            if j == carry:
                return carry
            carry = j
        self.an.emit("GL09", node,
                     "lax.fori_loop carry does not stabilize under "
                     "widening (no provable bound)")
        return TOPV

    def _lax_while(self, node, args, kwargs):
        if len(args) < 3:
            return TOPV
        _cond, body, init = args[0], args[1], args[2]
        carry = init
        for i in range(_LOOP_CAP):
            c2 = self.call_value(body, node, [carry])
            j = av_join(carry, c2)
            if i >= _WIDEN_AFTER:
                j = widen_any(carry, j)
            if j == carry:
                return carry
            carry = j
        self.an.emit("GL09", node,
                     "lax.while_loop carry does not stabilize under "
                     "widening (no provable bound)")
        return TOPV


class _PallasProg:
    """The callable pl.pallas_call returns: its result bound is the
    kernel contract's declared output (the ``->`` spec)."""

    def __init__(self, kernel, an):
        self.kernel = kernel
        self.an = an

    def result(self, interp):
        k = self.kernel
        key = None
        if isinstance(k, Closure) and isinstance(
                k.node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            key = (k.relpath, k.node.name)
        elif isinstance(k, FuncRef):
            key = (k.relpath, k.name)
        c = self.an.contracts.get(key) if key else None
        if c is None or c.ret is None:
            return TOPV
        return interp._ret_from_spec(c.ret, c.retdom or DOM_TOP,
                                     key[1] if key else "pallas kernel")


class _Partial:
    def __init__(self, fn, args, kwargs):
        self.fn = fn
        self.args = args
        self.kwargs = kwargs


_INPROGRESS = object()


def _conc_binop(a, op, b):
    import operator as O

    table = {
        ast.Add: O.add, ast.Sub: O.sub, ast.Mult: O.mul,
        ast.FloorDiv: O.floordiv, ast.Mod: O.mod, ast.Pow: O.pow,
        ast.LShift: O.lshift, ast.RShift: O.rshift,
        ast.BitAnd: O.and_, ast.BitOr: O.or_, ast.BitXor: O.xor,
        ast.Div: O.truediv,
    }
    fn = table.get(type(op))
    if fn is None:
        raise TypeError(type(op).__name__)
    if type(op) is ast.Pow and isinstance(b, int) and b > 4096:
        raise OverflowError("exponent too large to fold")
    return fn(a, b)


def _conc_compare(a, b, op) -> bool:
    import operator as O

    table = {
        ast.Eq: O.eq, ast.NotEq: O.ne, ast.Lt: O.lt, ast.LtE: O.le,
        ast.Gt: O.gt, ast.GtE: O.ge,
        ast.Is: lambda x, y: x is y,
        ast.IsNot: lambda x, y: x is not y,
        ast.In: lambda x, y: x in y,
        ast.NotIn: lambda x, y: x not in y,
    }
    return bool(table[type(op)](a, b))


def _opname(op) -> str:
    return {
        ast.Add: "+", ast.Sub: "-", ast.Mult: "*", ast.RShift: ">>",
        ast.LShift: "<<", ast.BitAnd: "&", ast.BitOr: "|",
        ast.BitXor: "^", ast.FloorDiv: "//", ast.Mod: "%",
        ast.MatMult: "@", ast.Div: "/", ast.Pow: "**",
    }.get(type(op), type(op).__name__)


# ---------------------------------------------------------------------------
# jnp / lax intrinsics


def _arrayify(v):
    if isinstance(v, AV):
        return v
    if isinstance(v, AbsTuple):
        out = None
        for e in v:
            a = _arrayify(e)
            out = a if out is None else av_join(out, to_av(a))
        return to_av(out) if out is not None else AV(0, 0, DOM_NEUTRAL)
    if is_known_conc(v):
        val = v.value
        if isinstance(val, (int, bool)):
            return AV(int(val), int(val), DOM_NEUTRAL)
        if isinstance(val, (list, tuple, range)):
            flat = list(_flatten_conc(val))
            if flat and all(isinstance(x, int) for x in flat):
                return AV(min(flat), max(flat), DOM_NEUTRAL)
    return TOPV


def _flatten_conc(val):
    for x in val:
        if isinstance(x, (list, tuple)):
            yield from _flatten_conc(x)
        elif isinstance(x, bool):
            yield int(x)
        else:
            yield x


def _i_asarray(interp, node, args, kwargs):
    return _arrayify(args[0]) if args else TOPV


def _i_join_seq(interp, node, args, kwargs):
    seq = args[0] if args and isinstance(args[0], AbsTuple) else \
        AbsTuple(args)
    out = None
    doms = []
    for e in seq:
        a = to_av(_arrayify(e) if not isinstance(e, AV) else e)
        doms.append(a.dom)
        out = a if out is None else av_join(out, a)
    if interp.check:
        interp._unify(node, doms, "stack/concatenate")
    return out if out is not None else TOPV


def _i_where(interp, node, args, kwargs):
    if len(args) != 3:
        return TOPV
    a, b = to_av(args[1]), to_av(args[2])
    if interp.check:
        interp._unify(node, [a.dom, b.dom], "jnp.where")
    return av_join(a, b)


def _i_zeros(interp, node, args, kwargs):
    return AV(0, 0, DOM_NEUTRAL)


def _i_ones(interp, node, args, kwargs):
    return AV(1, 1, DOM_NEUTRAL)


def _i_full(interp, node, args, kwargs):
    v = args[1] if len(args) > 1 else kwargs.get("fill_value")
    return to_av(v) if v is not None else TOPV


def _i_pad(interp, node, args, kwargs):
    fill = kwargs.get("constant_values")
    base = to_av(args[0]) if args else TOPV
    return av_join(base, to_av(fill) if fill is not None
                   else AV(0, 0, DOM_NEUTRAL))


def _i_first(interp, node, args, kwargs):
    return args[0] if args else TOPV


def _i_strip(interp, node, args, kwargs):
    a = to_av(args[0]) if args else TOPV
    return replace(a, limbaxis=False, scanlen=None)


def _i_moveaxis(interp, node, args, kwargs):
    if not args:
        return TOPV
    a = to_av(args[0])
    if a.limbaxis and len(args) >= 3 and \
            is_known_conc(args[1]) and args[1].value == -1 and \
            is_known_conc(args[2]) and args[2].value == 0:
        return replace(a, limbaxis=False, scanlen=N_LIMBS)
    return replace(a, limbaxis=False, scanlen=None)


def _i_split(interp, node, args, kwargs):
    a = replace(to_av(args[0]), limbaxis=False, scanlen=None) \
        if args else TOPV
    n = args[1].value if len(args) > 1 and is_known_conc(args[1]) and \
        isinstance(args[1].value, int) else 1
    return AbsTuple([a] * max(1, min(n, 64)))


def _i_bool(interp, node, args, kwargs):
    return AV(0, 1, DOM_NEUTRAL)


def _i_sum(interp, node, args, kwargs):
    return interp._reduce_sum(node, args[0], 1) if args else TOPV


def _names_axis0(node, pos):
    """True iff the sum call ``node`` reduces axis 0, given as
    ``axis=0`` or as its ``pos``-th positional argument."""
    axis = next((k.value for k in node.keywords if k.arg == "axis"), None)
    if axis is None and len(node.args) > pos:
        axis = node.args[pos]
    return isinstance(axis, ast.Constant) and axis.value == 0


def _i_stack(interp, node, args, kwargs):
    """jnp.stack of a sequence of known length k along axis 0: the
    result's leading axis has k entries, which a sum over axis 0 adds."""
    out = _i_join_seq(interp, node, args, kwargs)
    axis = kwargs.get("axis", args[1] if len(args) > 1 else Conc(0))
    if isinstance(out, AV) and args and isinstance(args[0], AbsTuple) \
            and is_known_conc(axis) and axis.value == 0:
        return replace(out, scanlen=len(args[0]))
    return out


def _einsum_contracts_last_axis(spec: str, arrays: list) -> bool:
    """True iff the (single) contracted index is the LAST axis of every
    operand that carries the limb axis — the only contraction whose
    length (N_LIMBS) the analysis can prove.  Anything else — another
    axis, several contracted indices, an unparseable spec — is
    unprovable and must stay unbounded."""
    try:
        inputs, out = spec.replace(" ", "").split("->")
        ins = [s.replace("...", "") for s in inputs.split(",")]
    except ValueError:
        return False  # implicit-output or malformed spec: unprovable
    contracted = {c for s in ins for c in s} - set(out)
    if len(contracted) != 1:
        return False
    (c,) = contracted
    return all(s.endswith(c) for s in ins if s) and \
        all(a.limbaxis for a in arrays)


def _i_einsum(interp, node, args, kwargs):
    arrays = [to_av(a) for a in args if isinstance(a, AV)]
    if not arrays:
        return TOPV
    spec = args[0].value if args and is_known_conc(args[0]) and \
        isinstance(args[0].value, str) else None
    provable = spec is not None and \
        _einsum_contracts_last_axis(spec, arrays)
    out = arrays[0]
    for b in arrays[1:]:
        out = interp._reduction_product(out, b,
                                        limb_contraction=provable)
        if interp.check:
            interp.an.check_overflow(node, out, "einsum contraction")
    if len(arrays) == 1:
        out = interp._reduce_sum(node, out) if provable else \
            AV(None, None, out.dom,
               prov="einsum over an unproven contraction")
    return out


def _i_matmul(interp, node, args, kwargs):
    if len(args) < 2:
        return TOPV
    out = interp._reduction_product(to_av(args[0]), to_av(args[1]))
    if interp.check:
        interp.an.check_overflow(node, out, "matmul contraction")
    return out


def _i_minmax(interp, node, args, kwargs):
    if len(args) >= 2:
        return av_join(to_av(args[0]), to_av(args[1]))
    return to_av(args[0]) if args else TOPV


def _i_abs(interp, node, args, kwargs):
    a = to_av(args[0]) if args else TOPV
    if a.bounded:
        return AV(0, max(abs(a.lo), abs(a.hi)), a.dom)
    return AV(0, None, a.dom)


def _i_scan(interp, node, args, kwargs):
    return interp._lax_scan(node, args, kwargs)


def _i_fori(interp, node, args, kwargs):
    return interp._lax_fori(node, args, kwargs)


def _i_while(interp, node, args, kwargs):
    return interp._lax_while(node, args, kwargs)


def _i_top(interp, node, args, kwargs):
    return TOPV


def _i_unknown(interp, node, args, kwargs):
    return UNKNOWN


def _i_pallas(interp, node, args, kwargs):
    return _PallasProg(args[0] if args else None, interp.an)


def _i_partial(interp, node, args, kwargs):
    if not args:
        return UNKNOWN
    return _Partial(args[0], args[1:], kwargs)


_INTRINSICS = {
    "jnp.asarray": _i_asarray, "jnp.array": _i_asarray,
    "jnp.stack": _i_stack, "jnp.concatenate": _i_join_seq,
    "jnp.hstack": _i_join_seq, "jnp.vstack": _i_join_seq,
    "jnp.where": _i_where,
    "jnp.zeros": _i_zeros, "jnp.zeros_like": _i_zeros,
    "jnp.empty": _i_zeros, "jnp.empty_like": _i_zeros,
    "jnp.ones": _i_ones, "jnp.ones_like": _i_ones,
    "jnp.full": _i_full, "jnp.full_like": _i_full,
    "jnp.pad": _i_pad,
    # broadcasting replicates elements, it never changes their bounds
    "jnp.broadcast_arrays": lambda i, n, a, k: AbsTuple(a),
    "jnp.broadcast_to": _i_first,
    "jnp.reshape": _i_strip, "jnp.squeeze": _i_strip,
    "jnp.transpose": _i_strip, "jnp.swapaxes": _i_strip,
    "jnp.expand_dims": _i_strip, "jnp.ravel": _i_strip,
    "jnp.flip": _i_strip, "jnp.roll": _i_strip,
    "jnp.moveaxis": _i_moveaxis,
    "jnp.split": _i_split,
    "jnp.all": _i_bool, "jnp.any": _i_bool,
    "jnp.logical_and": _i_bool, "jnp.logical_or": _i_bool,
    "jnp.logical_not": _i_bool, "jnp.equal": _i_bool,
    "jnp.sum": _i_sum,
    "jnp.einsum": _i_einsum,
    "jnp.matmul": _i_matmul, "jnp.dot": _i_matmul,
    "jnp.tensordot": _i_matmul,
    "jnp.minimum": _i_minmax, "jnp.maximum": _i_minmax,
    "jnp.abs": _i_abs, "jnp.absolute": _i_abs,
    "jnp.int32": _i_first, "jnp.int8": _i_first,
    "jnp.int16": _i_first, "jnp.int64": _i_first,
    "jnp.uint32": _i_first, "jnp.float32": _i_first,
    "lax.scan": _i_scan, "lax.fori_loop": _i_fori,
    "lax.while_loop": _i_while,
    "lax.associative_scan": _i_top, "lax.select": _i_where,
    "lax.cond": _i_top, "lax.switch": _i_top,
    "lax.dot_general": _i_matmul,
    "jax.jit": _i_first, "jit": _i_first,
    "jax.vmap": _i_first, "vmap": _i_first,
    # a layout constraint moves no value: it fixes only the physical
    # order of the array's dimensions
    "with_layout_constraint": _i_first,
    "jax.ensure_compile_time_eval": _i_unknown,
    "pl.pallas_call": _i_pallas, "pltpu.pallas_call": _i_pallas,
    "pallas_call": _i_pallas,
    "functools.partial": _i_partial, "partial": _i_partial,
}

_NP_PREFIXES = ("jnp.", "np.", "jax.numpy.", "numpy.")


def _intrinsic_key(dotted: str | None) -> str | None:
    if not dotted:
        return None
    for p in _NP_PREFIXES:
        if dotted.startswith(p):
            cand = "jnp." + dotted[len(p):]
            return cand if cand in _INTRINSICS else None
    for p in ("jax.lax.", "lax."):
        if dotted.startswith(p):
            cand = "lax." + dotted[len(p):]
            return cand if cand in _INTRINSICS else None
    if dotted in _INTRINSICS:
        return dotted
    return None


# ---------------------------------------------------------------------------
# public entry


def kernel_findings(prog: Program) -> list[SiteFinding]:
    """Run GL09/GL10/GL11 over an analyzed interproc Program."""
    an = _Analysis(prog)
    try:
        out = an.run()
    except RecursionError:
        out = an.findings + [SiteFinding(
            sorted(prog.modules)[0] if prog.modules else "<unknown>",
            "GL09", 1, 0,
            "kernelcheck internal recursion limit", "<module>")]
    return sorted(out, key=lambda f: (f.relpath, f.line, f.col,
                                      f.rule, f.message))
