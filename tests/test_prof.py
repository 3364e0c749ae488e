"""Kernel-stage profiler tier (harmony_tpu/prof.py, ISSUE 6).

Covers the four acceptance edges: stage spans nest under the PR-4
round trace, a compiled program's cost-analysis keys reach /metrics,
the disabled fast path stays micro-benchmark cheap, and the metrics
quantile helper the loadgen/bench report path leans on.
"""

import os
import pathlib
import sys
import time

import numpy as np
import pytest

from harmony_tpu import prof, trace
from harmony_tpu.metrics import Histogram, Registry

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "tools"))

from obs_smoke import validate_prometheus  # noqa: E402


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    # the first-dispatch assertions below need a fresh per-program
    # seen-set: earlier suite files (the chaostest scenarios) dispatch
    # the same bucket-8 programs and would otherwise mark them used
    from harmony_tpu import device as DV

    monkeypatch.setattr(DV, "_SEEN_PROGRAMS", set())
    prof.reset()
    trace.reset()
    yield
    prof.reset()
    trace.reset()


# -- stage spans -------------------------------------------------------------


def test_stage_spans_nest_under_the_round_trace():
    prof.configure(enabled=True)
    trace.configure(enabled=True)
    with trace.span("consensus.round", component="consensus") as root:
        with prof.stage("hash_to_g2"):
            pass
        with prof.stage("miller_loop", batch=8):
            pass
    spans = [s for s in trace.spans() if s.name == "prof.stage"]
    assert len(spans) == 2
    for s in spans:
        assert s.parent_id == root.span_id
        assert s.trace_id == root.trace_id
        assert s.component == "prof"
    assert {s.attrs["stage"] for s in spans} == {"hash_to_g2",
                                                "miller_loop"}


def test_stage_records_histogram_samples():
    prof.configure(enabled=True)
    with prof.stage("montmul"):
        time.sleep(0.002)
    summary = prof.stage_summary()["montmul"]
    assert summary["count"] == 1
    assert summary["sum_s"] >= 0.002


def test_stage_survives_exceptions():
    prof.configure(enabled=True)
    with pytest.raises(ValueError):
        with prof.stage("final_exp"):
            raise ValueError("stage body failed")
    assert prof.stage_summary()["final_exp"]["count"] == 1


def _recording_annotation(events: list):
    """A stand-in for ``jax.profiler.TraceAnnotation`` that logs its
    construction, enter and exit."""

    class Recording:
        def __init__(self, name, **kwargs):
            self.name = name
            events.append(("new", name, kwargs))

        def __enter__(self):
            events.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            events.append(("exit", self.name))
            return False

    return Recording


def test_armed_stage_opens_one_profiler_annotation(monkeypatch):
    import jax

    events: list = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        _recording_annotation(events))
    prof.configure(enabled=True)
    with prof.stage("x", batch=8):
        assert events == [("new", "stage:x", {}), ("enter", "stage:x")]
    assert events == [("new", "stage:x", {}), ("enter", "stage:x"),
                      ("exit", "stage:x")]
    assert prof.stage_summary()["x"]["count"] == 1


def test_disarmed_stage_touches_no_jax(monkeypatch):
    class Untouchable:
        def __getattr__(self, name):
            raise AssertionError(f"jax.{name} touched by a dark stage")

    monkeypatch.setitem(sys.modules, "jax", Untouchable())
    assert not prof.enabled()
    s = prof.stage("x")
    assert s is prof._NOOP
    with s:
        pass
    assert prof.stage_summary() == {}


def test_armed_stage_without_jax_loaded_keeps_it_unloaded(monkeypatch):
    """Twin-kernel nodes keep jax unloaded: an armed stage still
    records its histogram sample and imports nothing."""
    for name in [m for m in sys.modules if m == "jax"
                 or m.startswith("jax.")]:
        monkeypatch.delitem(sys.modules, name)
    prof.configure(enabled=True)
    with prof.stage("x"):
        pass
    assert "jax" not in sys.modules
    assert prof.stage_summary()["x"]["count"] == 1


def test_armed_stage_lands_on_the_host_plane_of_a_capture(tmp_path):
    """A real CPU ``jax.profiler`` capture holds each armed stage as a
    ``stage:<name>`` event on a ``/host:`` plane, nested by thread."""
    import jax
    from jax.profiler import ProfileData

    prof.configure(enabled=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with prof.stage("x"):
            with prof.stage("y"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    [path] = tmp_path.rglob("*.xplane.pb")
    events = {
        e.name: (e.start_ns, e.start_ns + e.duration_ns)
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for e in line.events
        if e.name.startswith("stage:")
    }
    assert set(events) == {"stage:x", "stage:y"}
    (x0, x1), (y0, y1) = events["stage:x"], events["stage:y"]
    assert x0 <= y0 and y1 <= x1
    assert y1 - y0 >= 2_000_000


def test_env_var_arms_the_profiler(monkeypatch):
    """HARMONY_TPU_PROF=1 is the documented operator path; prof.py
    applies it at import and arm_from_env() re-applies after reset."""
    monkeypatch.setenv("HARMONY_TPU_PROF", "1")
    assert not prof.enabled()
    assert prof.arm_from_env() is True
    assert prof.enabled()


def test_batch_dispatch_records_execute_histogram():
    """The replay-critical batch programs feed the execute histogram
    on their non-compiling dispatches (issue->drain latency)."""
    os.environ["HARMONY_KERNEL_TWIN"] = "1"
    try:
        from harmony_tpu import device as DV
        from harmony_tpu.metrics import Registry
        from harmony_tpu.ref import bls as RB
        from harmony_tpu.ref.curve import g2
        from harmony_tpu.ref.hash_to_curve import hash_to_g2

        prof.configure(enabled=True)
        DV.use_device(True)
        sks = [RB.keygen(bytes([31, i])) for i in range(4)]
        table = DV.CommitteeTable([RB.pubkey(sk) for sk in sks])
        h = hash_to_g2(b"batch-exec-histogram-check!!!!!!")
        agg = RB.aggregate_sigs([g2.mul(h, sk) for sk in sks])
        bits = [[1, 1, 1, 1]] * 2
        for _ in range(2):  # first pays "compile", second executes
            assert all(DV.agg_verify_batch_on_device(
                table, bits, [h] * 2, [agg] * 2
            ))
        text = Registry().expose()
        assert ('harmony_prof_execute_seconds_count'
                f'{{program="agg_verify_batch_b{table.size}x8"}} 1'
                in text)
    finally:
        from harmony_tpu import device as DV

        DV.use_device(None)
        os.environ.pop("HARMONY_KERNEL_TWIN", None)


def test_disabled_stage_cost_is_noise():
    """The profiler sits on the verify hot path; disabled it must cost
    one comparison.  10k disabled stages in well under a second is a
    ~50x margin over the measured cost on this box."""
    assert not prof.enabled()
    t0 = time.perf_counter()
    for _ in range(10_000):
        with prof.stage("montmul"):
            pass
    assert time.perf_counter() - t0 < 0.5
    assert prof.stage_summary() == {}  # nothing recorded while dark


# -- program registry / cost analysis ----------------------------------------


def _tiny_jitted():
    import jax

    return jax.jit(lambda x: (x @ x).sum()), np.ones((8, 8), np.float32)


def test_cost_analysis_keys_present_for_a_compiled_program():
    prof.configure(enabled=True)
    fn, x = _tiny_jitted()
    prof.on_first_dispatch("test_prog_w8", fn, (x,), 0.05)
    entry = prof.programs()["test_prog_w8"]
    assert entry["compile_s"] == 0.05
    # XLA's own analysis of the executable, not a model
    assert entry["flops"] > 0
    assert entry["bytes_accessed"] > 0
    assert "peak_memory_bytes" in entry


def test_program_families_reach_the_metrics_exposition():
    prof.configure(enabled=True)
    fn, x = _tiny_jitted()
    prof.on_first_dispatch("test_prog_w8", fn, (x,), 0.05)
    prof.observe_execute("test_prog_w8", 0.004)
    text = Registry().expose()
    assert 'harmony_prof_program_flops{program="test_prog_w8"}' in text
    assert ('harmony_prof_program_bytes_accessed{program="test_prog_w8"}'
            in text)
    assert ('harmony_prof_program_compile_seconds{program="test_prog_w8"}'
            in text)
    assert 'harmony_prof_execute_seconds' in text
    assert validate_prometheus(text) == []


def test_twin_callable_records_walltime_without_analysis():
    """Twin kernels are plain callables: the registry still carries the
    compile wall time, just no XLA analysis."""
    prof.configure(enabled=True)
    prof.on_first_dispatch("agg_verify_b8", lambda *a: True, (), 0.01)
    entry = prof.programs()["agg_verify_b8"]
    assert entry == {"compile_s": 0.01}


def test_device_dispatch_populates_the_registry():
    """The device.py wiring end to end: a twin-kernel dispatch lands
    its program shape in the prof registry and exposition."""
    os.environ["HARMONY_KERNEL_TWIN"] = "1"
    try:
        from harmony_tpu import device as DV
        from harmony_tpu.ref import bls as RB
        from harmony_tpu.ref.curve import g2
        from harmony_tpu.ref.hash_to_curve import hash_to_g2

        prof.configure(enabled=True)
        DV.use_device(True)
        sks = [RB.keygen(bytes([i + 1])) for i in range(4)]
        pks = [RB.pubkey(sk) for sk in sks]
        msg = b"prof-device-dispatch-check!!!!!!"
        h = hash_to_g2(msg)
        agg = RB.aggregate_sigs([g2.mul(h, sk) for sk in sks])
        table = DV.CommitteeTable(pks)
        assert DV.agg_verify_on_device(table, [1, 1, 1, 1], msg, agg)
        progs = prof.programs()
        assert f"agg_verify_b{table.size}" in progs
        assert prof.stage_summary()["hash_to_g2"]["count"] >= 1
    finally:
        from harmony_tpu import device as DV

        DV.use_device(None)
        os.environ.pop("HARMONY_KERNEL_TWIN", None)


# -- capture hook ------------------------------------------------------------


def test_profile_dir_capture_yields_nonempty_trace(tmp_path, monkeypatch):
    """HARMONY_TPU_PROFILE_DIR + one jitted call -> a loadable,
    non-empty profiler trace on CPU (the acceptance edge: the first
    device attempt must produce a trace, not a second run)."""
    d = str(tmp_path / "prof_trace")
    monkeypatch.setenv("HARMONY_TPU_PROFILE_DIR", d)
    prof.configure(enabled=True)
    fn, x = _tiny_jitted()
    import jax

    with prof.capture():
        jax.block_until_ready(fn(x))
    files = [p for p in pathlib.Path(d).rglob("*") if p.is_file()]
    assert files, "profiler capture produced no trace files"


def test_capture_without_dir_is_a_noop(monkeypatch):
    monkeypatch.delenv("HARMONY_TPU_PROFILE_DIR", raising=False)
    with prof.capture():
        pass  # nothing to assert: must simply not touch jax/raise


# -- the metrics quantile helper ---------------------------------------------


def test_histogram_quantile_interpolates():
    h = Histogram("t", "", buckets=(0.01, 0.1, 1.0))
    assert h.quantile(0.5) is None  # empty
    for v in (0.005, 0.05, 0.05, 0.5):
        h.observe(v)
    # rank 2 of 4 falls in the (0.01, 0.1] bucket
    assert 0.01 <= h.quantile(0.5) <= 0.1
    assert 0.1 <= h.quantile(0.99) <= 1.0
    s = h.summary()
    assert s["count"] == 4 and s["p50_s"] <= s["p99_s"]


def test_histogram_quantile_overflow_clamps_to_last_bound():
    h = Histogram("t", "", buckets=(0.01, 0.1))
    h.observe(5.0)  # lands in +Inf
    assert h.quantile(0.99) == 0.1
