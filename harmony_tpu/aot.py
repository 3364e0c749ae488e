"""AOT program cache: no first-use XLA compile on a serving path.

``resolve(name)`` returns the executable :func:`warmup` produced for a
program name in the compile manifest
(``tools/artifacts/aot/compile_manifest.json``, emitted by ``python -m
tools.graftlint --emit-compile-manifest`` and machine-checked by GL16),
or None — the caller then dispatches its plain ``jax.jit``.  After
warmup every serving-path dispatch in device.py finds its program here
and never traces.

:func:`warmup` is backed by a **content-addressed on-disk executable
cache** keyed on (jaxlib version, program hash, bucket tuple): a node
restart deserializes yesterday's executables instead of re-burning
minutes of XLA time per pairing-shaped program.  It lives in the
``aot`` subdirectory of the compile-cache root (:func:`cache_root`),
the same directory JAX's persistent cache uses, so one directory set
from outside (``JAX_COMPILATION_CACHE_DIR``) holds both.

Failures never take a node down: warmup falls back to plain
``jax.jit`` — but not *silently*.  Each failed program logs once and
counts ``harmony_aot_fallback_total{reason}``; cache traffic counts
``harmony_aot_cache_total{event}`` (hit / miss / store / corrupt /
skew).
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from .log import get_logger
from .metrics import Counter

log = get_logger("aot")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST_PATH = os.path.join(_REPO_ROOT, "tools", "artifacts", "aot",
                             "compile_manifest.json")

FALLBACKS = Counter(
    "harmony_aot_fallback_total",
    "AOT artifact loads that fell back to plain jit, by reason",
)
CACHE_EVENTS = Counter(
    "harmony_aot_cache_total",
    "content-addressed executable-cache events, by event",
)

_compiled: dict = {}  # program name -> warmed executable/callable
_warned: set = set()
_lock = threading.Lock()


def expose() -> str:
    """Prometheus exposition for this module's counter families
    (hooked from metrics.Registry)."""
    return "\n".join((FALLBACKS.expose(), CACHE_EVENTS.expose()))


def _fallback(name: str, reason: str, detail: str) -> None:
    """Record a failed program exactly once per (name, reason): a
    silent fallback turns a corrupt or version-skewed artifact into an
    unexplained minutes-long jit burn."""
    FALLBACKS.inc(reason=reason)
    key = (name, reason)
    with _lock:
        if key in _warned:
            return
        _warned.add(key)
    log.warn("aot artifact unusable — falling back to plain jit",
             artifact=name, reason=reason, detail=detail)


def resolve(name: str):
    """The warmed executable for ``name``, or None when startup warmup
    did not produce one (the caller dispatches its plain jit fn)."""
    with _lock:
        return _compiled.get(name)


def _reset_for_tests() -> None:
    with _lock:
        _compiled.clear()
        _warned.clear()


# ---------------------------------------------------------------------------
# content-addressed executable cache
# ---------------------------------------------------------------------------

def cache_root() -> str:
    """The one compile-cache directory: ``$JAX_COMPILATION_CACHE_DIR``
    when set from outside, else the fixed ``<repo>/.jax_cache`` (a
    fixed path, because the path is part of what a cache hit needs)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_REPO_ROOT, ".jax_cache"))


def cache_dir() -> str:
    """The AOT executable cache, inside :func:`cache_root`."""
    return os.path.join(cache_root(), "aot")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at :func:`cache_root`.
    Every entry point calls this before its first compile.  When
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and no
    cache directory is set in code."""
    root = cache_root()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        jax.config.update("jax_compilation_cache_dir", root)
    return root


def jaxlib_version() -> str:
    try:
        import jaxlib

        return getattr(jaxlib, "__version__", "unknown")
    except Exception:  # noqa: BLE001 — jax-less host (twin mode)
        return "unavailable"


def cache_key(program_sha: str, bucket: tuple, backend: str) -> str:
    """sha256 over (jaxlib version, program hash, bucket tuple,
    backend) — executables are NOT portable across any of these."""
    h = hashlib.sha256()
    for part in (jaxlib_version(), backend, program_sha, repr(bucket)):
        h.update(part.encode())
        h.update(b"\x00")
    return h.hexdigest()


def _paths(key: str) -> tuple:
    d = cache_dir()
    return os.path.join(d, key + ".aotx"), os.path.join(d, key + ".json")


def cache_store(key: str, compiled, meta: dict) -> bool:
    """Serialize ``compiled`` under ``key`` (atomic tmp+rename); meta
    sidecar carries (program, bucket, jaxlib, backend) for the
    version-skew sweep.  Returns False — counted, logged once — on
    any serializer or filesystem failure."""
    art, metapath = _paths(key)
    try:
        from jax.experimental import serialize_executable as se

        payload, in_tree, out_tree = se.serialize(compiled)
        blob = pickle.dumps((payload, in_tree, out_tree))
        os.makedirs(cache_dir(), exist_ok=True)
        tmp = art + f".tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
        os.replace(tmp, art)
        with open(metapath + f".tmp.{os.getpid()}", "w") as f:
            json.dump(meta, f, sort_keys=True)
        os.replace(metapath + f".tmp.{os.getpid()}", metapath)
    except Exception as e:  # noqa: BLE001 — cache is best-effort
        _fallback(meta.get("program", key), "store", repr(e))
        return False
    CACHE_EVENTS.inc(event="store")
    return True


def cache_load(key: str, program: str):
    """Deserialize the executable under ``key``; None on miss.  A
    corrupt artifact is unlinked (the next warmup re-compiles and
    re-stores) and counted ``corrupt``."""
    art, metapath = _paths(key)
    if not os.path.exists(art):
        CACHE_EVENTS.inc(event="miss")
        _note_skew(program, key)
        return None
    try:
        with open(art, "rb") as f:
            payload, in_tree, out_tree = pickle.loads(f.read())
        from jax.experimental import serialize_executable as se

        loaded = se.deserialize_and_load(payload, in_tree, out_tree)
    except Exception as e:  # noqa: BLE001 — stale/foreign/truncated
        CACHE_EVENTS.inc(event="corrupt")
        _fallback(program, "corrupt", f"{art}: {e!r}")
        for p in (art, metapath):
            try:
                os.unlink(p)
            except OSError:
                pass
        return None
    CACHE_EVENTS.inc(event="hit")
    return loaded


def cache_meta(key: str) -> dict | None:
    """The meta sidecar stored with ``key`` (None when absent or
    unreadable) — carries program, bucket, jaxlib, backend and, when
    the writer recorded it, the original compile seconds a later hit
    avoided."""
    try:
        with open(_paths(key)[1]) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _note_skew(program: str, missed_key: str) -> None:
    """On a cache miss, sweep the meta sidecars: an artifact for the
    SAME program under a DIFFERENT jaxlib is version skew — worth a
    counter so operators see 'warm cache, wrong jaxlib' instead of an
    unexplained slow start."""
    ours = jaxlib_version()
    try:
        entries = os.listdir(cache_dir())
    except OSError:
        return
    for fn in entries:
        if not fn.endswith(".json") or fn.startswith(missed_key):
            continue
        try:
            with open(os.path.join(cache_dir(), fn)) as f:
                meta = json.load(f)
        except (OSError, ValueError):
            continue
        if meta.get("program") == program and meta.get("jaxlib") != ours:
            CACHE_EVENTS.inc(event="skew")
            _fallback(program, "skew",
                      f"cached under jaxlib {meta.get('jaxlib')}, "
                      f"running {ours}")
            return


# ---------------------------------------------------------------------------
# manifest + warmup
# ---------------------------------------------------------------------------

def load_manifest(path: str | None = None) -> dict | None:
    path = MANIFEST_PATH if path is None else path
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def manifest_names(manifest: dict | None) -> list:
    if not manifest:
        return []
    names: list = []
    for fam in manifest.get("programs", []):
        names.extend(fam.get("names", []))
    return sorted(set(names))


_FAMILY_RES = (
    (re.compile(r"agg_verify_batch_b(\d+)x(\d+)\Z"), "agg_verify_batch"),
    (re.compile(r"agg_verify_b(\d+)\Z"), "agg_verify"),
    (re.compile(r"verify_w(\d+)\Z"), "verify"),
    (re.compile(r"masked_sum_w(\d+)\Z"), "masked_sum"),
)


def program_spec(name: str):
    """(family, bucket-tuple, arg ShapeDtypeStructs) for a manifest
    program name; None for an unrecognized name.  Shapes are the
    device.py call sites' — int32 limbs throughout."""
    import jax
    import jax.numpy as jnp

    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    for rx, family in _FAMILY_RES:
        m = rx.match(name)
        if not m:
            continue
        dims = tuple(int(g) for g in m.groups())
        if family == "agg_verify":
            n, = dims
            specs = (S((n, 2, 32)), S((n,)), S((2, 2, 32)), S((2, 2, 32)))
        elif family == "agg_verify_batch":
            n, b = dims
            specs = (S((n, 2, 32)), S((b, n)),
                     S((b, 2, 2, 32)), S((b, 2, 2, 32)))
        elif family == "verify":
            w, = dims
            specs = (S((w, 2, 32)), S((w, 2, 2, 32)), S((w, 2, 2, 32)))
        else:  # masked_sum
            n, = dims
            specs = (S((n, 3, 32)), S((n,)))
        return family, dims, specs
    return None


def _family_fn(family: str):
    """The one jitted callable device.py dispatches for ``family``
    (imported lazily: aot must stay importable before device)."""
    from . import device as DV

    return {
        "agg_verify": DV._get_agg_verify_fn,
        "agg_verify_batch": DV._get_agg_verify_batch_fn,
        "verify": DV._get_verify_fn,
        "masked_sum": DV._get_masked_sum_fn,
    }[family]()


# Peak host memory of one pairing-shaped TPU compile, rounded up: the
# off-chip rehearsal of agg_verify_b256 (PR 21) reached 6.4 GB RSS.
_COMPILE_HOST_BYTES = 8 << 30


def _compile_workers(n_cold: int) -> int:
    """Concurrent XLA compiles: one per core, but never more than host
    memory holds at one pairing-sized compile each.  XLA compiles a
    program on about one core and releases the GIL while it does, so
    cold programs compile side by side."""
    try:
        mem = os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError):
        mem = _COMPILE_HOST_BYTES
    return max(1, min(n_cold, os.cpu_count() or 1,
                      mem // _COMPILE_HOST_BYTES))


# graftlint: compile-phase=warmup
def _lower_one(name: str, backend: str):
    """Trace and lower one manifest program and look it up in the disk
    cache.  Returns ("cached", None) after parking a cache hit in
    ``_compiled``, ("cold", (lowered, key, meta)) on a miss, or
    ("failed", None)."""
    spec = program_spec(name)
    if spec is None:
        _fallback(name, "unknown-program",
                  "manifest name matches no program family")
        return "failed", None
    family, dims, arg_specs = spec
    try:
        lowered = _family_fn(family).lower(*arg_specs)
        program_sha = hashlib.sha256(
            lowered.as_text().encode()).hexdigest()
        key = cache_key(program_sha, dims, backend)
        loaded = cache_load(key, name)
    except Exception as e:  # noqa: BLE001 — warmup must not kill boot
        _fallback(name, "warmup", repr(e))
        return "failed", None
    if loaded is not None:
        with _lock:
            _compiled[name] = loaded
        return "cached", None
    return "cold", (lowered, key, {
        "program": name, "bucket": list(dims),
        "jaxlib": jaxlib_version(), "backend": backend,
        "program_sha": program_sha,
    })


# graftlint: compile-phase=warmup
def _compile_one(name: str, lowered, key: str, meta: dict) -> tuple:
    """Compile a cold program, store it, park it for ``resolve``.
    Returns ("compiled"|"failed", seconds-of-XLA-compile)."""
    t0 = time.monotonic()
    try:
        compiled = lowered.compile()
    except Exception as e:  # noqa: BLE001 — warmup must not kill boot
        _fallback(name, "warmup", repr(e))
        return "failed", 0.0
    dt = time.monotonic() - t0
    cache_store(key, compiled, dict(meta, compile_s=round(dt, 3)))
    with _lock:
        _compiled[name] = compiled
    return "compiled", dt


def warmup(manifest: dict | None = None) -> dict:
    """Precompile every manifest program before the node serves, so
    the serving paths (consensus pump, sched lanes, ingress, sync)
    never pay a first-use XLA compile — the PR-15 NEWVIEW wedge class.

    Mode-aware:
      * kernel twin — the twins are plain python callables; every
        manifest program (plus the single-signature ``verify_w1``
        hot path) is marked warm so JIT first-use counters stay flat.
      * XLA:CPU, no twin — device.py dispatches everything eagerly
        (``_fused()`` is False); nothing to compile.
      * accelerator — lower every manifest program in turn (tracing
        holds the GIL), load it from the disk cache or hand it to a
        pool that compiles the cold ones side by side, and park the
        executables for ``resolve``.

    ``stats["per_program"]`` maps each name to its outcome
    (cached / compiled / failed) and its lower and compile seconds.
    """
    from . import device as DV

    if manifest is None:
        manifest = load_manifest()
    names = manifest_names(manifest)
    stats = {"mode": "eager", "programs": len(names), "warmed": 0,
             "cached": 0, "compiled": 0, "failed": 0,
             "compile_s": 0.0, "saved_s": 0.0, "per_program": {}}
    if manifest is None:
        stats["mode"] = "no-manifest"
        log.warn("aot warmup: no compile manifest — serving paths may "
                 "pay first-use compiles", path=MANIFEST_PATH)
        return stats
    if DV.kernel_twin_active():
        stats["mode"] = "twin"
        for name in names + ["verify_w1"]:
            DV.mark_warm(name)
        stats["warmed"] = len(names) + 1
        return stats
    if not DV._fused():
        # XLA:CPU route: device.py runs the ops eagerly, no jitted
        # program is ever dispatched, so there is nothing to warm
        return stats
    import jax

    use_compile_cache()
    backend = jax.default_backend()
    stats["mode"] = backend
    per = stats["per_program"]
    pool = ThreadPoolExecutor(max_workers=_compile_workers(len(names)),
                              thread_name_prefix="aot-compile")
    pending = {}
    try:
        for name in names:
            t0 = time.monotonic()
            outcome, cold = _lower_one(name, backend)
            per[name] = {"outcome": outcome,
                         "lower_s": time.monotonic() - t0,
                         "compile_s": 0.0}
            if outcome == "cold":
                pending[name] = pool.submit(_compile_one, name, *cold)
        for name, fut in pending.items():
            per[name]["outcome"], per[name]["compile_s"] = fut.result()
    finally:
        pool.shutdown(wait=True)
    for name, rec in per.items():
        stats[rec["outcome"]] += 1
        stats["compile_s"] += rec["compile_s"]
        if rec["outcome"] != "failed":
            stats["warmed"] += 1
            DV.mark_warm(name)
    # compile seconds a warm disk cache avoided, estimated from this
    # run's own mean compile time (exact when the cache was cold)
    if stats["compiled"]:
        mean = stats["compile_s"] / stats["compiled"]
        stats["saved_s"] = mean * stats["cached"]
    return stats


def startup_warmup() -> dict | None:
    """cli boot hook: warm the full manifest, log the verdict, never
    raise (a broken warmup degrades to first-use compiles, which the
    JIT counters and GL17 smoke will surface loudly)."""
    try:
        t0 = time.monotonic()
        stats = warmup()
        stats["wall_s"] = round(time.monotonic() - t0, 3)
        log.info(
            "aot warmup done", mode=stats["mode"],
            warmed=stats["warmed"], programs=stats["programs"],
            cached=stats["cached"], compiled=stats["compiled"],
            compile_s=round(stats["compile_s"], 2),
            failed=stats["failed"], wall_s=stats["wall_s"])
        return stats
    except Exception as e:  # noqa: BLE001 — boot must proceed
        log.warn("aot warmup failed — node will pay first-use "
                 "compiles", error=repr(e))
        return None
