"""graftlint tier-1 gate + linter self-tests.

Pure-AST: none of these tests import jax or the linted modules, so the
whole file runs in a few seconds and belongs in tier-1.  Three layers:

1. fixture files under tests/fixtures/graftlint/ assert exact rule ids
   and line numbers per rule family (positive + suppressed cases);
2. baseline machinery (pinning, excess-is-new, fixed detection) on a
   dedicated pinned-cases fixture;
3. THE GATE: harmony_tpu/ linted against the committed baseline — any
   new finding fails tier-1 — plus the CLI exit-code contract.
"""

import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.graftlint import (  # noqa: E402
    DEFAULT_BASELINE_PATH,
    REPO_ROOT,
    Baseline,
    lint_paths,
    lint_source,
    load_baseline,
)
from tools.graftlint.engine import compare  # noqa: E402

FIXTURES = REPO / "tests" / "fixtures" / "graftlint"
_EXPECT_RE = re.compile(r"#\s*expect:\s*(GL\d{2}(?:\s*,\s*GL\d{2})*)")


def _expected(path: Path) -> set:
    out = set()
    for lineno, line in enumerate(
            path.read_text(encoding="utf-8").splitlines(), start=1):
        m = _EXPECT_RE.search(line)
        if m:
            for rule in m.group(1).split(","):
                out.add((lineno, rule.strip()))
    return out


@pytest.mark.parametrize("name", [
    "gl01_cases.py", "gl02_cases.py", "gl03_cases.py", "gl04_cases.py",
    "gl05_cases.py", "gl06_cases.py", "gl07_cases.py", "gl08_cases.py",
    "gl09_cases.py", "gl10_cases.py", "gl11_cases.py",
    "gl12_cases.py", "gl13_cases.py", "gl14_cases.py",
    "gl15_cases.py", "gl16_cases.py", "gl17_cases.py",
])
def test_fixture_exact_lines(name):
    """Each rule family flags exactly the tagged lines — no more, no
    less — and inline suppressions (incl. wrong-rule ones) behave."""
    path = FIXTURES / name
    rel = path.relative_to(REPO_ROOT).as_posix()
    findings = lint_source(path.read_text(encoding="utf-8"), rel)
    actual = {(f.line, f.rule) for f in findings}
    expected = _expected(path)
    assert actual == expected, (
        f"{name}: flagged {sorted(actual - expected)} unexpectedly, "
        f"missed {sorted(expected - actual)}"
    )


def test_fixture_rules_scoped_inside_harmony_tpu():
    """The same weak-where source that fires in a limb module is out of
    scope elsewhere in harmony_tpu/ — scoping is path-based."""
    src = "import jax.numpy as jnp\n\ndef f(x):\n    return jnp.where(x > 0, 1, 0)\n"
    in_scope = lint_source(src, "harmony_tpu/ops/fp.py")
    out_of_scope = lint_source(src, "harmony_tpu/consensus/quorum.py")
    assert [(f.rule, f.line) for f in in_scope] == [("GL02", 4)]
    assert out_of_scope == []


PINNED_SRC = '''\
def racy_one(sig):
    try:
        return sig.check()
    except Exception:
        pass


def racy_two(sig):
    try:
        return sig.check()
    except Exception:
        pass
'''


def test_baseline_pins_and_flags_excess():
    """Pinned findings stay quiet; the same fingerprint appearing MORE
    often than pinned reports exactly the excess sites."""
    rel = "tests/fixtures/graftlint/pinned_virtual.py"
    findings = lint_source(PINNED_SRC, rel)
    assert [(f.rule, f.line) for f in findings] == [
        ("GL04", 4), ("GL04", 11),
    ]
    # distinct contexts -> distinct fingerprints: pin both, gate clean
    full = Baseline.from_findings(findings)
    new, pinned, fixed = compare(findings, full)
    assert new == [] and pinned == 2 and fixed == []

    # same fingerprint twice, only one pinned -> the excess is NEW and
    # it is the LATER line that is reported
    dup_src = PINNED_SRC.replace("racy_two", "racy_one")
    dup = lint_source(dup_src, rel)
    assert len({f.fingerprint for f in dup}) == 1
    half = Baseline({dup[0].fingerprint: 1})
    new, pinned, fixed = compare(dup, half)
    assert pinned == 1 and [f.line for f in new] == [11]

    # a fixed finding is reported so the pin can be shrunk
    new, pinned, fixed = compare([], full)
    assert new == [] and pinned == 0 and len(fixed) == 2


def test_repo_gate_clean_against_committed_baseline():
    """THE tier-1 gate: no new violations in harmony_tpu/.  Runs
    through the content-hash cache — check.sh's CLI stage warms it, so
    this second full-repo pass is ~10x cheaper on an unchanged tree
    (test_result_cache_is_content_correct proves cache == fresh)."""
    result = lint_paths(["harmony_tpu"], use_cache=True)
    assert not result.errors, result.errors
    baseline = load_baseline()
    new, _pinned, fixed = compare(result.findings, baseline)
    assert not new, (
        "new graftlint violations (fix them, or pin deliberate debt "
        "via `python -m tools.graftlint --write-baseline`):\n"
        + "\n".join(f.render() for f in new)
    )
    assert not fixed, (
        "baseline entries no longer fire — shrink the pin file with "
        "`python -m tools.graftlint --write-baseline`:\n"
        + "\n".join(fixed)
    )


def test_baseline_has_no_ops_gl01_gl02_pins():
    """The ops/ hot path must be FIXED, never pinned, for purity and
    dtype discipline (ISSUE 1 acceptance criterion)."""
    baseline = load_baseline()
    offenders = [
        fp for fp in baseline.counts
        if fp.startswith("harmony_tpu/ops/")
        and ("::GL01::" in fp or "::GL02::" in fp)
    ]
    assert offenders == []


def _run_cli(*args, cwd=REPO_ROOT):
    return subprocess.run(
        [sys.executable, "-m", "tools.graftlint", *args],
        cwd=cwd, capture_output=True, text=True, timeout=120,
    )


def test_cli_exit_code_contract(tmp_path):
    """0 clean, 1 new violations, 2 internal error — stable for hooks."""
    clean = tmp_path / "clean.py"
    clean.write_text("def f():\n    return 1\n", encoding="utf-8")
    dirty = tmp_path / "dirty.py"
    dirty.write_text(
        "def f(x):\n    try:\n        return x.check()\n"
        "    except:\n        pass\n",
        encoding="utf-8",
    )
    missing_baseline = tmp_path / "nothing.json"

    r = _run_cli(str(clean), "--baseline", str(missing_baseline))
    assert r.returncode == 0, r.stdout + r.stderr

    r = _run_cli(str(dirty), "--baseline", str(missing_baseline))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "GL04" in r.stdout

    r = _run_cli(str(dirty), "--rules", "GL99")
    assert r.returncode == 2, r.stdout + r.stderr

    # --write-baseline pins the debt; the re-run gates clean on it
    pin = tmp_path / "baseline.json"
    r = _run_cli(str(dirty), "--baseline", str(pin), "--write-baseline")
    assert r.returncode == 0, r.stdout + r.stderr
    data = json.loads(pin.read_text(encoding="utf-8"))
    assert sum(e["count"] for e in data["findings"]) == 1
    r = _run_cli(str(dirty), "--baseline", str(pin))
    assert r.returncode == 0, r.stdout + r.stderr

    # a narrowed run must not clobber the DEFAULT baseline's other pins
    committed = DEFAULT_BASELINE_PATH.read_bytes()
    r = _run_cli(str(dirty), "--write-baseline")
    assert r.returncode == 2, r.stdout + r.stderr
    assert "refusing" in r.stderr
    assert DEFAULT_BASELINE_PATH.read_bytes() == committed

    # a syntactically broken file gates like a violation, not a crash
    broken = tmp_path / "broken.py"
    broken.write_text("def f(:\n", encoding="utf-8")
    r = _run_cli(str(broken), "--baseline", str(missing_baseline))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "SyntaxError" in r.stderr

    # a typo'd path must fail loudly, not lint zero files and pass
    r = _run_cli(str(tmp_path / "no_such_dir"),
                 "--baseline", str(missing_baseline))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "not a .py file or directory" in r.stderr


def test_cli_changed_mode_contract():
    """--changed lints only the git-diff slice.  A bad ref must exit 2
    (fail loudly), never lint zero files and pass; a narrowed --changed
    run must refuse to clobber the default baseline; a valid ref runs
    the gate and reports the changed-slice summary (tree-state agnostic:
    either files changed vs HEAD, or nothing to lint)."""
    r = _run_cli("--changed=definitely-not-a-ref")
    assert r.returncode == 2, r.stdout + r.stderr
    assert "definitely-not-a-ref" in r.stderr

    committed = DEFAULT_BASELINE_PATH.read_bytes()
    r = _run_cli("--changed", "--write-baseline")
    assert r.returncode == 2, r.stdout + r.stderr
    assert "refusing" in r.stderr
    assert DEFAULT_BASELINE_PATH.read_bytes() == committed

    r = _run_cli("--changed=HEAD")
    assert r.returncode in (0, 1), r.stdout + r.stderr
    assert ("changed files vs HEAD" in r.stdout
            or "nothing to lint" in r.stdout), r.stdout


def test_default_baseline_is_committed_and_loads():
    assert DEFAULT_BASELINE_PATH.exists()
    baseline = load_baseline()
    for fp, count in baseline.counts.items():
        assert count >= 1
        path = fp.split("::", 1)[0]
        assert (REPO_ROOT / path).exists(), f"stale baseline path {path}"


# -- interprocedural pass (GL05-GL07) ---------------------------------------


def test_cross_file_program_blocking_under_lock(tmp_path):
    """The call graph crosses FILE boundaries: a.py holds its lock
    while calling into b.py, whose helper sleeps — the finding lands in
    a.py at the call site.  Linting a.py ALONE sees no finding (the
    callee is outside the program), which is exactly the failure mode
    the whole-program pass exists to close."""
    (tmp_path / "b_helpers.py").write_text(
        "import time\n\n\ndef drain():\n    time.sleep(1)\n",
        encoding="utf-8",
    )
    (tmp_path / "a_caller.py").write_text(
        "import threading\n\nfrom b_helpers import drain\n\n"
        "_L = threading.Lock()\n\n\ndef tick():\n    with _L:\n"
        "        drain()\n",
        encoding="utf-8",
    )
    both = lint_paths([tmp_path])
    assert not both.errors
    gl06 = [f for f in both.findings if f.rule == "GL06"]
    assert [(Path(f.path).name, f.line) for f in gl06] == \
        [("a_caller.py", 10)]
    assert "time.sleep" in gl06[0].message
    alone = lint_paths([tmp_path / "a_caller.py"])
    assert [f for f in alone.findings if f.rule == "GL06"] == []


def test_lock_order_cycle_detected_across_classes(tmp_path):
    """Opposite nesting of the same two locks in two classes is a
    GL05 cycle; consistent nesting is only an (un-pinned) edge."""
    (tmp_path / "deadlockable.py").write_text(
        "import threading\n\n\n"
        "class Consensus:\n"
        "    def __init__(self):\n"
        "        self._vc_lock = threading.Lock()\n"
        "        self.net = Gossip()\n\n"
        "    def view_change(self):\n"
        "        with self._vc_lock:\n"
        "            self.net.broadcast_view()\n\n\n"
        "class Gossip:\n"
        "    def __init__(self):\n"
        "        self._mesh_lock = threading.Lock()\n"
        "        self.fbft = None\n\n"
        "    def broadcast_view(self):\n"
        "        with self._mesh_lock:\n"
        "            pass\n\n"
        "    def on_message(self):\n"
        "        with self._mesh_lock:\n"
        "            self.fbft.start_view_change()\n\n\n"
        "class FBFT:\n"
        "    def start_view_change(self):\n"
        "        with self.consensus._vc_lock:\n"
        "            pass\n",
        encoding="utf-8",
    )
    res = lint_paths([tmp_path / "deadlockable.py"])
    cycles = [f for f in res.findings
              if f.rule == "GL05" and "cycle" in f.message]
    assert len(cycles) == 2, [f.render() for f in res.findings]
    assert {f.context for f in cycles} == \
        {"Consensus.view_change", "Gossip.on_message"}


def test_sarif_output_validates_against_schema(tmp_path):
    """--sarif emits SARIF 2.1.0 that validates against the minimal
    schema (the subset GitHub/CI annotators require)."""
    jsonschema = pytest.importorskip("jsonschema")
    dirty = tmp_path / "dirty.py"
    dirty.write_text(
        "import threading\nimport time\n\n_L = threading.Lock()\n\n\n"
        "def f():\n    with _L:\n        time.sleep(1)\n",
        encoding="utf-8",
    )
    r = _run_cli(str(dirty), "--sarif",
                 "--baseline", str(tmp_path / "none.json"))
    assert r.returncode == 1, r.stdout + r.stderr
    doc = json.loads(r.stdout)

    schema = {
        "type": "object",
        "required": ["version", "runs"],
        "properties": {
            "version": {"const": "2.1.0"},
            "runs": {
                "type": "array", "minItems": 1,
                "items": {
                    "type": "object",
                    "required": ["tool", "results"],
                    "properties": {
                        "tool": {
                            "type": "object",
                            "required": ["driver"],
                            "properties": {"driver": {
                                "type": "object",
                                "required": ["name"],
                                "properties": {
                                    "name": {"type": "string"},
                                    "rules": {"type": "array"},
                                },
                            }},
                        },
                        "results": {
                            "type": "array",
                            "items": {
                                "type": "object",
                                "required": ["ruleId", "message",
                                             "locations"],
                                "properties": {
                                    "ruleId": {"type": "string"},
                                    "level": {"enum": [
                                        "none", "note", "warning",
                                        "error"]},
                                    "message": {
                                        "type": "object",
                                        "required": ["text"],
                                    },
                                    "locations": {
                                        "type": "array", "minItems": 1,
                                        "items": {
                                            "type": "object",
                                            "required": [
                                                "physicalLocation"],
                                            "properties": {
                                                "physicalLocation": {
                                                    "type": "object",
                                                    "required": [
                                                        "artifactLocation"],
                                                },
                                            },
                                        },
                                    },
                                },
                            },
                        },
                    },
                },
            },
        },
    }
    jsonschema.validate(doc, schema)
    results = doc["runs"][0]["results"]
    assert {r["ruleId"] for r in results} == {"GL06"}
    region = results[0]["locations"][0]["physicalLocation"]["region"]
    assert region["startLine"] == 9
    fps = results[0]["partialFingerprints"]
    assert "::GL06::" in fps["graftlintFingerprint/v1"]


_DOT_EDGE_RE = re.compile(r'^  "([^"]+)" -> "([^"]+)";$')


def test_dot_output_is_parseable_callgraph():
    r = _run_cli("tests/fixtures/graftlint/gl06_cases.py", "--dot")
    assert r.returncode == 0, r.stdout + r.stderr
    lines = r.stdout.splitlines()
    assert lines[0] == "digraph graftlint_callgraph {"
    assert lines[-1] == "}"
    edges = set()
    for line in lines[1:-1]:
        m = _DOT_EDGE_RE.match(line)
        assert m, f"unparseable DOT line: {line!r}"
        edges.add((m.group(1), m.group(2)))
    assert ("gl06_cases.py:sleepy_via_call",
            "gl06_cases.py:_nap") in edges


def test_whole_program_pass_is_fast():
    """Acceptance: the full-repo whole-program pass runs in < 15 s on
    CPU (measured ~4 s; the bound is generous for a loaded CI box)."""
    import time as _time

    t0 = _time.monotonic()
    result = lint_paths(["harmony_tpu"])
    dt = _time.monotonic() - t0
    assert not result.errors
    assert dt < 15.0, f"whole-program pass took {dt:.1f}s"


# -- kernelcheck (GL09-GL11) ------------------------------------------------


def test_gl09_proves_cios_montmul_and_kernel_modules_clean():
    """ISSUE 10 acceptance: the existing CIOS montmul path — and every
    annotated kernel module — verifies with ZERO unpinned GL09/GL10/
    GL11 findings.  The analysis is non-vacuous (see the seeded-
    overflow and dtype tests below)."""
    result = lint_paths(["harmony_tpu"])
    assert not result.errors, result.errors
    kernel = [f for f in result.findings
              if f.rule in ("GL09", "GL10", "GL11")]
    assert kernel == [], "\n".join(f.render() for f in kernel)


def test_gl09_flags_seeded_karatsuba_overflow_at_exact_line():
    """The sum-of-limbs convolution whose accumulator provably exceeds
    int32 is flagged AT the einsum line, with the proven bound in the
    message; the carry-resolved twin of the same shape is clean."""
    src = (FIXTURES / "gl09_cases.py").read_text(encoding="utf-8")
    rel = "tests/fixtures/graftlint/gl09_cases.py"
    lines = src.splitlines()
    bad_line = next(i for i, ln in enumerate(lines, 1)
                    if "einsum" in ln and "expect: GL09" in ln)
    good_lines = [i for i, ln in enumerate(lines, 1)
                  if "einsum" in ln and "expect" not in ln]
    findings = [f for f in lint_source(src, rel) if f.rule == "GL09"]
    flagged = {f.line for f in findings}
    assert bad_line in flagged
    assert not (flagged & set(good_lines)), (flagged, good_lines)
    kara = next(f for f in findings if f.line == bad_line)
    assert "4829479200" in kara.message  # 12285^2 * 32, the proof
    assert "int" not in kara.fingerprint.split("::")[2]  # ctx is fn name


def test_gl09_bound_is_dtype_parameterized():
    """The same kernel source is provable under int32 lanes and a
    violation under int8 — the knob the MXU int8-plane path needs."""
    src_t = (
        "# graftlint: kernel-module dtype={dtype}\n"
        "import jax.numpy as jnp\n\n"
        "# graftlint: kernel bounds=(<2**4, <2**4) -> any; domain=any\n"
        "def mac(a, b):\n"
        "    return a * b\n"
    )  # 15 * 15 = 225: inside int32 lanes, outside int8's [-128, 127]
    rel = "tests/fixtures/graftlint/virtual_dtype.py"
    ok = lint_source(src_t.format(dtype="int32"), rel)
    assert [f for f in ok if f.rule == "GL09"] == []
    bad = lint_source(src_t.format(dtype="int8"), rel)
    gl09 = [f for f in bad if f.rule == "GL09"]
    assert [f.line for f in gl09] == [6]
    assert "[-128, 127]" in gl09[0].message


def test_gl09_sum_over_a_stack_counts_its_terms():
    """A sum over axis 0 of a jnp.stack of 32 shifted limb arrays times
    limbs (fp._conv's shape) is bounded by 32 products: proven at
    exactly 32 * 4095^2, and flagged against a bound one below."""
    src_t = (
        "# graftlint: kernel-module dtype=int32\n"
        "import jax.numpy as jnp\n\n"
        "# graftlint: kernel bounds=(limb, limb) -> <={hi}; domain=any\n"
        "def conv(a, b):\n"
        "    s = jnp.stack([b[i:] for i in range(32)])\n"
        "    return (a[:, None] * s).sum(axis=0)\n"
    )
    rel = "tests/fixtures/graftlint/virtual_stack_sum.py"
    bound = 32 * 4095 * 4095
    ok = lint_source(src_t.format(hi=bound), rel)
    assert [f for f in ok if f.rule == "GL09"] == []
    bad = [f for f in lint_source(src_t.format(hi=bound - 1), rel)
           if f.rule == "GL09"]
    assert len(bad) == 1 and f"[0, {bound}]" in bad[0].message, \
        [f.render() for f in bad]


def test_gl09_scan_accumulator_bound_is_derived_not_assumed():
    """Tightening normalize's declared input below the derived scan
    bound (~1.078e9) must flag mont_mul's call into it — proof that
    the 32-step CIOS unroll computes a real accumulator bound."""
    import ast as _ast

    from tools.graftlint.engine import _interproc_findings, _suppressions

    fp_src = (REPO / "harmony_tpu/ops/fp.py").read_text(encoding="utf-8")
    assert "bounds=(<2**31) -> limb" in fp_src  # the committed contract
    tightened = fp_src.replace("bounds=(<2**31) -> limb",
                               "bounds=(<2**30) -> limb")
    sources, supps = {}, {}
    for rel, src in (
        ("harmony_tpu/ops/limbs.py",
         (REPO / "harmony_tpu/ops/limbs.py").read_text(encoding="utf-8")),
        ("harmony_tpu/ops/_constants.py",
         (REPO / "harmony_tpu/ops/_constants.py").read_text(
             encoding="utf-8")),
        ("harmony_tpu/ops/fp.py", tightened),
    ):
        sources[rel] = (src, _ast.parse(src))
        supps[rel] = _suppressions(src)
    gl09 = [f for f in _interproc_findings(sources, supps, {"GL09"})
            if "normalize" in f.message]
    assert gl09
    assert any("exceeds declared [0, 1073741823]" in f.message
               for f in gl09), [f.render() for f in gl09]


def test_gl10_typestate_catches_wrong_conversion_inline():
    """from_mont written as a no-op (missing mont_mul by 1) leaves the
    value in the mont domain — caught against the declared std."""
    src = (
        "# graftlint: kernel-module dtype=int32\n"
        "# graftlint: kernel bounds=(limb, limb) -> limb; domain=mul; trusted\n"
        "def mmul(a, b):\n"
        "    return a\n\n"
        "# graftlint: kernel bounds=(limb) -> limb; domain=(mont) -> std\n"
        "def from_mont_broken(a):\n"
        "    return a\n"
    )
    findings = lint_source(src, "tests/fixtures/graftlint/virtual_gl10.py")
    gl10 = [f for f in findings if f.rule == "GL10"]
    assert [f.line for f in gl10] == [7]
    assert "mont" in gl10[0].message and "std" in gl10[0].message


def test_kernel_contract_parse_error_is_a_finding_not_a_crash():
    src = (
        "# graftlint: kernel-module dtype=int32\n"
        "# graftlint: kernel bounds=(wibble) -> limb\n"
        "def f(a):\n"
        "    return a\n"
    )
    findings = lint_source(src, "tests/fixtures/graftlint/virtual_bad.py")
    assert any(f.rule == "GL09" and "unparseable" in f.message
               for f in findings)


def test_gl11_repo_kernels_have_twins_tests_and_guards():
    """The three device-dispatched kernels (verify / agg_verify /
    agg_verify_batch, found via jax.jit sites in device.py) pass all
    three GL11 obligations on the real tree; renaming a twin away
    surfaces exactly that kernel."""
    import ast as _ast

    from tools.graftlint.engine import (_interproc_findings,
                                        _suppressions)

    sources, supps = {}, {}
    for f in sorted((REPO / "harmony_tpu").rglob("*.py")):
        rel = f.relative_to(REPO_ROOT).as_posix()
        src = f.read_text(encoding="utf-8")
        sources[rel] = (src, _ast.parse(src))
        supps[rel] = _suppressions(src)
    assert _interproc_findings(sources, supps, {"GL11"}) == []

    src = sources["harmony_tpu/ops/twin.py"][0].replace(
        "def agg_verify(tbl, bits, h_arr, sig_arr):",
        "def agg_verify_gone(tbl, bits, h_arr, sig_arr):")
    sources["harmony_tpu/ops/twin.py"] = (src, _ast.parse(src))
    broken = _interproc_findings(sources, supps, {"GL11"})
    assert [(f.path, f.context) for f in broken] == \
        [("harmony_tpu/ops/bls.py", "agg_verify")]
    assert "no twin" in broken[0].message


# -- incremental result cache ------------------------------------------------


def test_result_cache_is_content_correct(tmp_path, monkeypatch):
    """Cold == warm == fresh; any byte change re-analyzes; a corrupt
    cache file degrades to a miss, never to wrong results."""
    from tools.graftlint import cache as CA

    monkeypatch.setenv("GRAFTLINT_CACHE", str(tmp_path / "cache.json"))
    target = tmp_path / "mod_under_lint.py"
    target.write_text(
        "def f(x):\n    try:\n        return x.check()\n"
        "    except Exception:\n        pass\n",
        encoding="utf-8",
    )

    def rows(result):
        return [(f.path, f.line, f.rule, f.message) for f in result.findings]

    CA.clear_memory()
    fresh = lint_paths([target])                      # never cached
    cold = lint_paths([target], use_cache=True)       # fills the cache
    CA.clear_memory()                                 # force the disk path
    warm = lint_paths([target], use_cache=True)
    assert rows(fresh) == rows(cold) == rows(warm)
    assert rows(fresh), "fixture must produce findings"

    # a one-byte change must invalidate: the GL04 finding disappears
    target.write_text("def f(x):\n    return x.check()\n",
                      encoding="utf-8")
    CA.clear_memory()
    changed = lint_paths([target], use_cache=True)
    assert rows(changed) == []

    # corrupt cache file: correct results, cache rewritten
    (tmp_path / "cache.json").write_text("{not json", encoding="utf-8")
    CA.clear_memory()
    after = lint_paths([target], use_cache=True)
    assert rows(after) == []

    # linter-source hash keys the entry: a different linter sha misses
    key_now = CA.linter_sha()
    assert isinstance(key_now, str) and len(key_now) == 64

    # GRAFTLINT_CACHE=0 disables persistence entirely
    monkeypatch.setenv("GRAFTLINT_CACHE", "0")
    assert CA.cache_path() is None


def test_cache_aux_regex_covers_module_anno_grammar():
    """The engine's cheap aux-input regex must see every tests= dir the
    kernelcheck annotation parser would hand GL11 — if the grammar
    drifts, the cache could serve stale GL11 results for a changed
    tests tree.  The regex may over-match (spurious invalidation is
    sound); it must never under-match."""
    from tools.graftlint.engine import _TESTS_OVERRIDE_RE
    from tools.graftlint.kernelcheck import collect_annotations

    variants = [
        "# graftlint: kernel-module dtype=int32; tests=tests/kernels\n",
        "# graftlint: kernel-module tests=alt_tests; twin=x.py\n",
        "#  graftlint:  kernel-module  twin=t.py ;  tests=deep/dir\n",
        "# graftlint: kernel-module tests=skip\n",
    ]
    for src in variants:
        anno, _ = collect_annotations(src)
        assert anno is not None
        want = anno.tests
        got = [m.group(1) for m in _TESTS_OVERRIDE_RE.finditer(src)]
        if want is not None:
            assert want in got, (src, want, got)


def test_cli_no_cache_flag(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("def f():\n    return 1\n", encoding="utf-8")
    r = _run_cli(str(clean), "--no-cache",
                 "--baseline", str(tmp_path / "none.json"))
    assert r.returncode == 0, r.stdout + r.stderr


def test_sarif_driver_lists_kernel_rules(tmp_path):
    from tools.graftlint import RULES

    assert {"GL09", "GL10", "GL11"} <= set(RULES)
    dirty = tmp_path / "dirty.py"
    dirty.write_text(
        "# graftlint: kernel-module dtype=int8\n"
        "import jax.numpy as jnp\n\n"
        "# graftlint: kernel bounds=(<2**7, <2**7) -> any; domain=any\n"
        "def mac(a, b):\n"
        "    return a * b\n",
        encoding="utf-8",
    )
    r = _run_cli(str(dirty), "--sarif",
                 "--baseline", str(tmp_path / "none.json"))
    assert r.returncode == 1, r.stdout + r.stderr
    doc = json.loads(r.stdout)
    run = doc["runs"][0]
    rule_ids = {x["id"] for x in run["tool"]["driver"]["rules"]}
    assert {"GL09", "GL10", "GL11"} <= rule_ids
    results = run["results"]
    assert {x["ruleId"] for x in results} == {"GL09"}
    assert results[0]["locations"][0]["physicalLocation"]["region"][
        "startLine"] == 6


def test_interproc_fingerprints_are_line_free_and_stable():
    """GL05/GL06/GL07 fingerprints carry the lock pair / sync site,
    never line numbers or witness chains — pins must survive unrelated
    edits and witness rerouting."""
    result = lint_paths(["harmony_tpu"])
    inter = [f for f in result.findings
             if f.rule in ("GL05", "GL06", "GL07")]
    assert inter, "expected pinned interprocedural findings to exist"
    for f in inter:
        assert str(f.line) not in f.fingerprint.split("::", 2)[2], (
            "line leaked into fingerprint", f.fingerprint)
        if f.detail:
            assert f.detail not in f.fingerprint
