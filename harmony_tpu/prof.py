"""Stage profiler: where does a verification's host time go?

``trace.py`` answers "where did round N spend its 800 ms?" at the span
level; this module answers the layer below: which host stage of a
check costs what, and what does XLA itself believe about every
compiled program in ``device.py``'s jit cache (FLOPs, bytes accessed,
peak temp memory, compile wall time).

Three surfaces:

1. **Stage spans** — ``with prof.stage("hash_to_g2"):`` around one
   host stage of the verify path.  The stages there are
   ``header_hash`` (Keccak of a header's RLP), ``sig_decode`` (the
   96-byte G2 decode and checks), ``mask`` (bitmap to signer vector),
   ``quorum_tally`` (the stake-weighted 2/3 check), ``hash_to_g2`` and
   ``device_prep`` (host prep of one device program's inputs, up to the
   program call); they are disjoint, so their sums add up.  Each stage
   records into a per-stage wall-time histogram, opens a
   ``prof.stage`` trace span (so stages nest under the round trace in
   /debug/trace) and, when jax is loaded, opens a
   ``jax.profiler.TraceAnnotation`` named ``stage:<name>``: in any
   ``jax.profiler`` capture the stage lands on the ``/host:`` plane, on
   the same clock as the device ops, nested by thread.  Disabled cost
   is one module-bool comparison (the same discipline as trace.py —
   this sits on the verify path).

2. **Program registry** — ``device.py`` reports every program shape's
   FIRST dispatch here (the one that pays the JIT compile).  The
   registry stores the compile wall time always; when the profiler is
   armed it additionally asks XLA for ``cost_analysis()`` /
   ``memory_analysis()`` of the compiled executable (a ``lower()`` +
   ``compile()`` that hits the in-process executable cache — armed
   deployments only, never the cold path of an unprofiled node).
   Every later dispatch feeds a per-program execute-seconds histogram.
   All of it exposes through ``metrics.Registry`` as the
   ``harmony_prof_*`` families.

3. **Capture hook** — ``HARMONY_TPU_PROFILE_DIR`` arms
   ``jax.profiler.start_trace`` capture: ``with prof.capture():``
   around a device round drops a Perfetto/XProf-loadable trace in that
   directory on the FIRST attempt (the device-hour protocol's step 3;
   no second run to re-instrument).

Stdlib + metrics/trace only at import; jax is touched lazily and only
behind the armed paths, and a stage never imports it: a process that
keeps jax unloaded (the twin kernels) records no annotation.
"""

from __future__ import annotations

import os
import sys
import threading
import time

from . import trace
from .metrics import Histogram

_STAGE_BUCKETS = (1e-5, 1e-4, 5e-4, 1e-3, 5e-3, 0.01, 0.05, 0.1, 0.5,
                  1.0, 5.0)
_EXEC_BUCKETS = (1e-4, 5e-4, 1e-3, 5e-3, 0.01, 0.05, 0.1, 0.5, 1.0,
                 5.0, 30.0)
_COMPILE_BUCKETS = (0.01, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 300.0)

_MAX_LABELS = 64  # program/stage cardinality bound (pinned buckets
# keep the real set ~a dozen; a runaway label namer must not grow the
# exposition without bound)

_enabled = False
_lock = threading.Lock()  # guards the dicts below; never held across
# anything blocking (histogram observes run on the objects' own locks)
_stage_hist: dict[str, Histogram] = {}
_exec_hist: dict[str, Histogram] = {}
_compile_hist: dict[str, Histogram] = {}
_programs: dict[str, dict] = {}  # program -> {compile_s, flops, ...}

_capture_lock = threading.Lock()
_capture_depth = 0  # nested capture() blocks share one jax trace
_capture_active = False  # a jax trace is currently recording


def configure(enabled: bool | None = None) -> None:
    global _enabled
    if enabled is not None:
        _enabled = bool(enabled)


def enabled() -> bool:
    """Armed via ``configure`` or HARMONY_TPU_PROF=1 in the
    environment (checked once at first call after reset)."""
    return _enabled


def arm_from_env() -> bool:
    """Apply HARMONY_TPU_PROF=1 (re-applied at import below, callable
    again after a reset)."""
    if os.environ.get("HARMONY_TPU_PROF") == "1":
        configure(enabled=True)
    return _enabled


def reset() -> None:
    """Disarm and drop all recorded data (test teardown)."""
    global _enabled
    _enabled = False
    with _lock:
        _stage_hist.clear()
        _exec_hist.clear()
        _compile_hist.clear()
        _programs.clear()


def _labeled(store: dict, name: str, family: str, help_: str,
             buckets, label: str) -> Histogram | None:
    with _lock:
        h = store.get(name)
        if h is None:
            if len(store) >= _MAX_LABELS:
                return None  # cardinality bound: drop, never grow
            h = Histogram(family, help_, buckets=buckets,
                          labels={label: name})
            store[name] = h
        return h


# -- stage spans -------------------------------------------------------------


class _NoopStage:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoopStage()


class _Stage:
    __slots__ = ("name", "_t0", "_span", "_ann")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self._span = trace.span("prof.stage", component="prof",
                                stage=name, **attrs)
        # the loaded jax only: importing it here would load jax into a
        # process that keeps it unloaded
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        self._ann = (None if profiler is None
                     else profiler.TraceAnnotation("stage:" + name))
        self._t0 = 0.0

    def __enter__(self):
        self._span.__enter__()
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = time.monotonic() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        h = _labeled(
            _stage_hist, self.name, "harmony_prof_stage_seconds",
            "wall time per host stage",
            _STAGE_BUCKETS, "stage",
        )
        if h is not None:
            h.observe(dt)
        self._span.__exit__(exc_type, exc, tb)
        return False


def stage(name: str, **attrs):
    """``with prof.stage("sig_decode"):`` — one timed host stage,
    recorded as a histogram sample, (when tracing is armed) a
    ``prof.stage`` span nested under the caller's current span, and
    (when jax is loaded) a ``stage:<name>`` profiler annotation.
    Disabled cost: one comparison."""
    if not _enabled:
        return _NOOP
    return _Stage(name, attrs)


def stage_summary() -> dict:
    """{stage: {count, sum_s, p50_s, p99_s}} of everything recorded —
    the bench tools' report surface (no bucket parsing)."""
    with _lock:
        hists = dict(_stage_hist)
    return {name: h.summary() for name, h in hists.items()}


# -- program registry --------------------------------------------------------


def observe_execute(program: str, seconds: float) -> None:
    """One dispatch of a known program shape (post result-sync)."""
    if not _enabled:
        return
    h = _labeled(
        _exec_hist, program, "harmony_prof_execute_seconds",
        "wall time of one dispatch per compiled program shape",
        _EXEC_BUCKETS, "program",
    )
    if h is not None:
        h.observe(seconds)


def on_first_dispatch(program: str, fn, args: tuple,
                      compile_s: float) -> None:
    """device.py's hook at the one dispatch per program shape that
    paid the JIT compile: records the compile wall time, and — when
    the profiler is armed — XLA's own cost/memory analysis of the
    compiled executable.  Never raises into the dispatch path."""
    h = _labeled(
        _compile_hist, program, "harmony_prof_compile_seconds",
        "wall time of the compiling first dispatch per program shape",
        _COMPILE_BUCKETS, "program",
    )
    if h is not None:
        h.observe(compile_s)
    entry = {"compile_s": compile_s}
    if _enabled:
        analysis = _cost_analysis(fn, args)
        if analysis:
            entry.update(analysis)
    with _lock:
        if len(_programs) < _MAX_LABELS or program in _programs:
            _programs.setdefault(program, {}).update(entry)


# graftlint: compile-phase=diagnostic
def _cost_analysis(fn, args: tuple) -> dict:
    """XLA's view of a jitted callable at concrete args: flops, bytes
    accessed, memory footprint.  Twin kernels (plain callables) and
    analysis-less backends yield {} — the registry then carries only
    the wall-clock facts."""
    target = getattr(fn, "_jitted", fn)
    if not hasattr(target, "lower"):
        return {}
    try:
        compiled = target.lower(*args).compile()
    except Exception:  # noqa: BLE001 — profiling must not break dispatch
        return {}
    out: dict = {}
    try:
        ca = compiled.cost_analysis()
        # jax returns a dict on new versions, [dict] on older ones
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            if "flops" in ca:
                out["flops"] = float(ca["flops"])
            if "bytes accessed" in ca:
                out["bytes_accessed"] = float(ca["bytes accessed"])
    except Exception:  # noqa: BLE001 — optional per backend
        pass
    try:
        ma = compiled.memory_analysis()
        for key, attr in (
            ("peak_memory_bytes", "temp_size_in_bytes"),
            ("argument_bytes", "argument_size_in_bytes"),
            ("output_bytes", "output_size_in_bytes"),
            ("generated_code_bytes", "generated_code_size_in_bytes"),
        ):
            v = getattr(ma, attr, None)
            if v is not None:
                out[key] = float(v)
    except Exception:  # noqa: BLE001 — optional per backend
        pass
    return out


def programs() -> dict:
    """Snapshot of the program registry: {program: {compile_s, flops,
    bytes_accessed, peak_memory_bytes, ...}}."""
    with _lock:
        return {k: dict(v) for k, v in _programs.items()}


# -- capture hook ------------------------------------------------------------


def capture_dir() -> str | None:
    return os.environ.get("HARMONY_TPU_PROFILE_DIR") or None


class _Capture:
    __slots__ = ("dir", "_counted")

    def __init__(self, directory: str | None):
        self.dir = directory
        self._counted = False  # this handle is in _capture_depth

    def __enter__(self):
        global _capture_depth, _capture_active
        if self.dir is None:
            return self
        # start_trace runs UNDER the lock: the whole enter is atomic,
        # so a failed start can never strand the depth counter while a
        # sibling thread slips in between count and start (the rare,
        # short setup path of an explicitly-armed capture)
        with _capture_lock:
            if _capture_depth == 0:
                try:
                    import jax

                    os.makedirs(self.dir, exist_ok=True)
                    jax.profiler.start_trace(self.dir)
                    _capture_active = True
                except Exception:  # noqa: BLE001 — capture is
                    # best-effort; the measurement it wraps must
                    # proceed uninstrumented (and uncounted)
                    return self
            _capture_depth += 1
            self._counted = True
        return self

    def __exit__(self, *exc):
        global _capture_depth, _capture_active
        if not self._counted:
            return False
        # the trace stops when the LAST counted handle leaves — never
        # while a sibling capture is still inside (ownership follows
        # the depth counter, not whichever handle happened to start)
        stop = False
        with _capture_lock:
            _capture_depth -= 1
            if _capture_depth == 0 and _capture_active:
                _capture_active = False
                stop = True
        if stop:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:  # noqa: BLE001 — same best-effort contract
                pass
        return False


def capture(directory: str | None = None):
    """``with prof.capture():`` — a jax.profiler trace of the wrapped
    block lands in ``HARMONY_TPU_PROFILE_DIR`` (or ``directory``),
    loadable in Perfetto/XProf.  Without a directory configured the
    block runs uninstrumented; nested captures share the outer trace."""
    return _Capture(directory or capture_dir())


# -- exposition --------------------------------------------------------------

_PROGRAM_GAUGES = (
    ("flops", "harmony_prof_program_flops",
     "XLA cost_analysis flops per compiled program"),
    ("bytes_accessed", "harmony_prof_program_bytes_accessed",
     "XLA cost_analysis bytes accessed per compiled program"),
    ("peak_memory_bytes", "harmony_prof_program_peak_memory_bytes",
     "XLA memory_analysis temp (peak scratch) bytes per program"),
    ("compile_s", "harmony_prof_program_compile_seconds",
     "wall time of the compiling first dispatch per program"),
)


def expose() -> str:
    """The harmony_prof_* Prometheus families (metrics.Registry hook)."""
    with _lock:
        stages = [_stage_hist[k] for k in sorted(_stage_hist)]
        execs = [_exec_hist[k] for k in sorted(_exec_hist)]
        compiles = [_compile_hist[k] for k in sorted(_compile_hist)]
        progs = {k: dict(v) for k, v in sorted(_programs.items())}
    out = []
    for family in (stages, execs, compiles):
        for i, h in enumerate(family):
            lines = h.expose().splitlines()
            out.append("\n".join(lines if i == 0 else lines[2:]))
    for key, name, help_ in _PROGRAM_GAUGES:
        rows = [(p, v[key]) for p, v in progs.items() if key in v]
        if not rows:
            continue
        lines = [f"# HELP {name} {help_}", f"# TYPE {name} gauge"]
        lines.extend(
            f'{name}{{program="{p}"}} {val:g}' for p, val in rows
        )
        out.append("\n".join(lines))
    return "\n".join(x for x in out if x)


# HARMONY_TPU_PROF=1 arms the profiler for the whole process the
# moment any layer imports this module (device.py does at startup) —
# the documented operator path needs no code hook.
arm_from_env()
