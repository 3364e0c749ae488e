"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy and idle, device time per program, the ops
that took most time, and idle gaps attributed to the harness's host
spans.

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Modules``
line holds one event per program execution and ``XLA Ops`` one per op.
Busy time is the union of the op intervals (of the module intervals
where a plane has no op line), clipped to the window and averaged over
the chips.  Host spans are the harness's ``jax.profiler.TraceAnnotation``
events named ``bench:<layer>`` on the host plane; each stretch of a gap
in device work is charged to the innermost such span open over it.
"""

from __future__ import annotations

import re
from collections import defaultdict

SPAN_PREFIX = "bench:"
_SUFFIX = re.compile(r"\(\d+\)$")


def _short(name: str) -> str:
    """``jit_f(12)`` -> ``jit_f``; an op's HLO text -> its name."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0])


def _union(intervals: list) -> list:
    out: list = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def _clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if b > lo and a < hi]


def _gaps(busy: list, lo: float, hi: float) -> list:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def reduce(profile, window_span: str = SPAN_PREFIX + "slice") -> dict:
    """``profile``: a ``jax.profiler.ProfileData``.  The window is the
    host span ``window_span`` (its first occurrence), else the extent of
    the device events."""
    devices, spans = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: list(_events(ln)) for ln in plane.lines}
            devices.append((lines.get("XLA Modules", []),
                            lines.get("XLA Ops", [])))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [ev for ev in _events(ln)
                          if ev[0].startswith(SPAN_PREFIX)]
    if not devices:
        return {}
    win = [s for s in spans if s[0] == window_span]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        ends = [e for mods, ops in devices for e in mods + ops]
        if not ends:
            return {}
        lo, hi = min(e[1] for e in ends), max(e[2] for e in ends)
    programs: dict = defaultdict(float)
    ops: dict = defaultdict(float)
    idle: dict = defaultdict(float)
    busy_ns = 0.0
    inner = [s for s in spans if s[0] != window_span]
    for mods, opev in devices:
        for store, events in ((programs, mods), (ops, opev)):
            for name, a, b in events:
                if b > lo and a < hi:
                    store[name] += (min(b, hi) - max(a, lo)) / 1e9
        busy = _clip(_union([[a, b] for _, a, b in (opev or mods)]), lo, hi)
        busy_ns += sum(b - a for a, b in busy)
        for a, b in _gaps(busy, lo, hi):
            _charge(inner, a, b, idle)
    n = len(devices)
    return {
        "chips": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "programs": _by_name(programs, n),
        "top_ops": sorted(_by_name(ops, n).items(),
                          key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(((k, v / n) for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:10],
    }


def _by_name(store: dict, n: int) -> dict:
    out: dict = defaultdict(float)
    for name, s in store.items():
        out[_short(name)] += s / n
    return dict(out)


def _charge(spans: list, a: float, b: float, idle: dict) -> None:
    """Split the idle stretch [a, b) at every span edge inside it and
    charge each piece, in seconds, to the innermost span open over it."""
    inside = [s for s in spans if s[2] > a and s[1] < b]
    edges = sorted({a, b} | {t for s in inside for t in s[1:] if a < t < b})
    for x, y in zip(edges, edges[1:]):
        mid = (x + y) / 2
        open_ = [s for s in inside if s[1] <= mid < s[2]]
        name = (min(open_, key=lambda s: s[2] - s[1])[0][len(SPAN_PREFIX):]
                if open_ else "no host span")
        idle[name] += (y - x) / 1e9
