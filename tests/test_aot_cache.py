"""AOT content-addressed executable cache + fallback accounting.

Covers ISSUE 17's satellite contract: cache hit / miss / corrupt-
artifact / version-skew behavior of harmony_tpu.aot, the once-per-
artifact fallback logging with ``harmony_aot_fallback_total{reason}``,
resolve() returning only warmed executables, the cache's place under
the one compile-cache root, concurrent warmup, twin-mode warmup
marking, and the committed compile manifest's shape.  The one real executable these tests
serialize is a scalar add — nothing pairing-shaped ever compiles.
"""

import json
import os
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from harmony_tpu import aot  # noqa: E402


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    aot._reset_for_tests()
    yield
    aot._reset_for_tests()


def _tiny_compiled():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x: x + 1).lower(
        jax.ShapeDtypeStruct((), jnp.int32)).compile()


def _counts(counter, **labels):
    return counter.value(**labels)


def test_cache_store_then_load_hits():
    compiled = _tiny_compiled()
    key = aot.cache_key("sha-tiny", (8,), "cpu")
    hits0 = _counts(aot.CACHE_EVENTS, event="hit")
    stores0 = _counts(aot.CACHE_EVENTS, event="store")
    assert aot.cache_store(key, compiled, {
        "program": "tiny_b8", "bucket": [8],
        "jaxlib": aot.jaxlib_version(), "backend": "cpu",
    })
    assert _counts(aot.CACHE_EVENTS, event="store") == stores0 + 1
    loaded = aot.cache_load(key, "tiny_b8")
    assert loaded is not None
    assert _counts(aot.CACHE_EVENTS, event="hit") == hits0 + 1
    import numpy as np

    assert int(np.asarray(loaded(np.int32(41)))) == 42
    meta = aot.cache_meta(key)
    assert meta["program"] == "tiny_b8" and meta["bucket"] == [8]


def test_cache_miss_counts():
    miss0 = _counts(aot.CACHE_EVENTS, event="miss")
    assert aot.cache_load("0" * 64, "absent_b8") is None
    assert _counts(aot.CACHE_EVENTS, event="miss") == miss0 + 1


def test_corrupt_artifact_unlinked_and_counted():
    key = aot.cache_key("sha-corrupt", (8,), "cpu")
    d = aot.cache_dir()
    os.makedirs(d, exist_ok=True)
    art = os.path.join(d, key + ".aotx")
    with open(art, "wb") as f:
        f.write(b"not a pickled executable")
    corrupt0 = _counts(aot.CACHE_EVENTS, event="corrupt")
    fb0 = _counts(aot.FALLBACKS, reason="corrupt")
    assert aot.cache_load(key, "corrupt_b8") is None
    assert _counts(aot.CACHE_EVENTS, event="corrupt") == corrupt0 + 1
    assert _counts(aot.FALLBACKS, reason="corrupt") == fb0 + 1
    assert not os.path.exists(art), "corrupt artifact must be unlinked"


def test_version_skew_detected_on_miss(monkeypatch):
    """An artifact for the same program under a different jaxlib keys
    differently; the miss sweep must still name the cause."""
    compiled = _tiny_compiled()
    key = aot.cache_key("sha-skew", (8,), "cpu")
    assert aot.cache_store(key, compiled, {
        "program": "skew_b8", "bucket": [8],
        "jaxlib": aot.jaxlib_version(), "backend": "cpu",
    })
    monkeypatch.setattr(aot, "jaxlib_version", lambda: "9.9.9-future")
    new_key = aot.cache_key("sha-skew", (8,), "cpu")
    assert new_key != key, "key must change with jaxlib version"
    skew0 = _counts(aot.CACHE_EVENTS, event="skew")
    fb0 = _counts(aot.FALLBACKS, reason="skew")
    assert aot.cache_load(new_key, "skew_b8") is None
    assert _counts(aot.CACHE_EVENTS, event="skew") == skew0 + 1
    assert _counts(aot.FALLBACKS, reason="skew") == fb0 + 1


def test_cache_dir_inside_compile_cache_root(tmp_path, monkeypatch):
    """One directory set from outside holds JAX's persistent cache and
    the AOT executables; unset, both live at the fixed in-repo path."""
    assert aot.cache_root() == str(tmp_path / "jc")
    assert aot.cache_dir() == os.path.join(str(tmp_path / "jc"), "aot")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert aot.cache_root() == str(REPO / ".jax_cache")


def test_resolve_unwarmed_is_none_and_reads_no_artifact(monkeypatch):
    """A program warmup did not produce resolves to None (the caller
    runs its plain jit) — never to a committed export blob, which would
    run a program traced by another JAX from other code."""
    import builtins

    opened = []
    real_open = builtins.open

    def spy(path, *a, **k):
        opened.append(str(path))
        return real_open(path, *a, **k)

    monkeypatch.setattr(builtins, "open", spy)
    assert aot.resolve("agg_verify_b256") is None
    assert aot.resolve("verify_w8") is None
    assert not [p for p in opened if "artifacts" in p], opened
    art = REPO / "tools" / "artifacts" / "aot"
    assert not list(art.glob("*.jaxexport*"))


def test_warmup_compiles_cold_programs_concurrently_then_hits(
        monkeypatch):
    """The accelerator branch of warmup, with scalar programs standing
    in for the pairing families: a cold pass compiles and stores each
    program (through the pool), a second pass after a restart loads
    every one from the disk cache, and neither counts a fallback."""
    import jax
    import jax.numpy as jnp

    from harmony_tpu import device as DV

    # the accelerator branch needs the real kernels: a test module that
    # imports tools/obs_smoke.py sets the twin flag for its process
    monkeypatch.delenv("HARMONY_KERNEL_TWIN", raising=False)
    monkeypatch.setattr(DV, "_fused", lambda: True)
    monkeypatch.setattr(aot, "program_spec", lambda name: (
        "tiny", (int(name.rsplit("_b", 1)[1]),),
        (jax.ShapeDtypeStruct((), jnp.int32),)))
    monkeypatch.setattr(aot, "_family_fn",
                        lambda fam: jax.jit(lambda x: x * 3 + 1))
    manifest = {"programs": [{"family": "tiny_b{}",
                              "names": ["tiny_b8", "tiny_b16"]}]}
    fb0 = sum(aot.FALLBACKS.value(reason=r) for r in
              ("warmup", "store", "corrupt", "skew"))
    cold = aot.warmup(manifest)
    assert cold["compiled"] == 2 and cold["failed"] == 0
    assert {n: r["outcome"] for n, r in cold["per_program"].items()} == {
        "tiny_b8": "compiled", "tiny_b16": "compiled"}
    import numpy as np

    assert int(np.asarray(aot.resolve("tiny_b8")(np.int32(4)))) == 13
    aot._reset_for_tests()
    warm = aot.warmup(manifest)
    assert warm["cached"] == 2 and warm["compiled"] == 0
    assert int(np.asarray(aot.resolve("tiny_b16")(np.int32(2)))) == 7
    assert fb0 == sum(aot.FALLBACKS.value(reason=r) for r in
                      ("warmup", "store", "corrupt", "skew"))
    assert aot._compile_workers(3) >= 1


def test_resolve_prefers_warmed_executable(monkeypatch):
    sentinel = object()
    with aot._lock:
        aot._compiled["warm_b8"] = sentinel
    assert aot.resolve("warm_b8") is sentinel


def test_warmup_twin_marks_manifest(monkeypatch):
    from harmony_tpu import device as DV

    monkeypatch.setenv("HARMONY_KERNEL_TWIN", "1")
    manifest = {"programs": [
        {"family": "t_b{}", "names": ["t_b8", "t_b16"]},
    ]}
    before = set(DV._SEEN_PROGRAMS)
    stats = aot.warmup(manifest)
    assert stats["mode"] == "twin"
    assert stats["warmed"] == 3  # two names + the verify_w1 hot path
    marked = set(DV._SEEN_PROGRAMS) - before
    assert {"t_b8", "t_b16"} <= set(DV._SEEN_PROGRAMS)
    assert "verify_w1" in DV._SEEN_PROGRAMS
    # warmup marking must not move the JIT first-use counters
    assert marked <= {"t_b8", "t_b16", "verify_w1"}


def test_warmup_without_manifest_degrades():
    stats = aot.warmup(None) if aot.load_manifest() is None else \
        aot.warmup(aot.load_manifest())
    assert stats["programs"] >= 0  # never raises


def test_committed_manifest_shape():
    """The committed manifest is the machine-checked artifact GL16
    diffs against — pin its gross shape so a hand edit stands out."""
    manifest = aot.load_manifest()
    assert manifest is not None, "compile manifest must be committed"
    names = aot.manifest_names(manifest)
    assert len(names) == len(set(names))
    fams = {f["family"] for f in manifest["programs"]}
    assert fams == {"agg_verify_b{}", "agg_verify_batch_b{}x{}",
                    "verify_w{}", "masked_sum_w{}"}
    assert "agg_verify_b8" in names and "agg_verify_b1024" in names
    assert "verify_w8" in names and "masked_sum_w8" in names
    for name in names:
        assert aot.program_spec(name) is not None, (
            f"manifest name {name} matches no warmup program family")


def test_program_spec_shapes():
    fam, dims, specs = aot.program_spec("agg_verify_b8")
    assert fam == "agg_verify" and dims == (8,)
    assert [tuple(s.shape) for s in specs] == [
        (8, 2, 32), (8,), (2, 2, 32), (2, 2, 32)]
    fam, dims, specs = aot.program_spec("agg_verify_batch_b16x64")
    assert fam == "agg_verify_batch" and dims == (16, 64)
    assert [tuple(s.shape) for s in specs] == [
        (16, 2, 32), (64, 16), (64, 2, 2, 32), (64, 2, 2, 32)]
    fam, dims, specs = aot.program_spec("masked_sum_w32")
    assert fam == "masked_sum" and dims == (32,)
    assert [tuple(s.shape) for s in specs] == [(32, 3, 32), (32,)]
    assert aot.program_spec("mystery_b8") is None
