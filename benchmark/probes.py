"""What the benchmark reads from the program: the no-hidden-host-path
invariants (chip_smoke.py's Guard), the counters a window moves, and
host spans around the calls into each layer for a traced slice."""

from __future__ import annotations

import contextlib
import gc
import importlib
import time

# Calls into the layers below the entry that a traced slice wraps in a
# host span ``bench:<label>`` (trace_reduce charges device idle gaps to
# them).  The program's own spans and stages are left as they are.
LAYER_SPANS = (
    ("harmony_tpu.ref.hash_to_curve", "hash_to_g2", "hash_to_g2"),
    ("harmony_tpu.sched.scheduler", "VerifyFuture.result", "sched_wait"),
    ("harmony_tpu.device", "_guarded", "device_dispatch"),
)


class Guard:
    """Counts, from warm-up on, every way a check can leave the device
    path or the warmed programs: reference fallbacks, an open breaker,
    scheduler sheds, AOT fallbacks, first uses of an unwarmed program
    and XLA compiles.  Each must read 0."""

    def __init__(self):
        import jax

        from harmony_tpu import aot
        from harmony_tpu import device as DV
        from harmony_tpu.sched.scheduler import SHED

        self._reads = {
            "ref_fallback": lambda: DV.COUNTERS["ref_fallback"],
            "shed": SHED.total,
            "aot_fallback": aot.FALLBACKS.total,
            "unwarmed_program": lambda: DV.JIT["miss"],
        }
        self._base = {k: f() for k, f in self._reads.items()}
        self._breaker = DV.BREAKER
        self._compiles = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, seconds: float, **_) -> None:
        if self._compiles is not None and event.endswith(
                "backend_compile_duration"):
            self._compiles += 1

    def warmed(self) -> None:
        """From here on a first use or a compile is a serving-path one."""
        self._base["unwarmed_program"] = self._reads["unwarmed_program"]()
        self._compiles = 0

    def counts(self) -> dict:
        out = {k: int(f() - self._base[k]) for k, f in self._reads.items()}
        out["compiled_after_warmup"] = self._compiles or 0
        out["breaker_open"] = int(self._breaker.state != "closed")
        return out


class GcPauses:
    """Python's full (generation 2) collections while it is open: how
    many and the longest, for the stderr line of a window (a
    diagnostic of rare multi-second calls, not a metric)."""

    def __init__(self):
        self.count, self.longest_s, self._t0 = 0, 0.0, None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.count += 1
            self.longest_s = max(self.longest_s,
                                 time.perf_counter() - self._t0)
            self._t0 = None

    def close(self) -> str:
        gc.callbacks.remove(self._on_gc)
        return (f"{self.count} full collections, longest "
                f"{1000 * self.longest_s:.1f} ms")


def counters() -> dict:
    """The program's counters that per-layer metrics read, as of now."""
    from harmony_tpu import prof
    from harmony_tpu.sched.scheduler import FILL, FLUSHES, WAIT_SECONDS

    h2g = prof.stage_summary().get("hash_to_g2", {})
    waits = [h.summary() for h in WAIT_SECONDS.values()]
    return {
        "hash_to_g2_n": h2g.get("count", 0),
        "hash_to_g2_s": h2g.get("sum_s", 0.0),
        "sched_wait_n": sum(w["count"] for w in waits),
        "sched_wait_s": sum(w["sum_s"] for w in waits),
        "fill_items": FILL["items"],
        "fill_slots": FILL["slots"],
        "dispatches": FLUSHES.total(),
    }


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


@contextlib.contextmanager
def layer_spans():
    import jax

    patched = []

    def wrap(fn, name):
        def spanned(*args, **kwargs):
            with jax.profiler.TraceAnnotation(name):
                return fn(*args, **kwargs)
        return spanned

    try:
        for mod, path, label in LAYER_SPANS:
            owner = importlib.import_module(mod)
            *parents, attr = path.split(".")
            for p in parents:
                owner = getattr(owner, p)
            orig = getattr(owner, attr)
            setattr(owner, attr, wrap(orig, "bench:" + label))
            patched.append((owner, attr, orig))
        yield
    finally:
        for owner, attr, orig in reversed(patched):
            setattr(owner, attr, orig)
