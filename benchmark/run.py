#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in BENCHMARK.json; its config
(``benchmark/configs/``), its traffic mix (``benchmark/traffic/<mix>.json``,
data that names its driver in ``benchmark/drivers/``) and its metrics
(``benchmark/metrics/<name>.py``) are found by name, so this file holds
nothing of any one cell: the driver makes the fixtures, drives the
window and judges the decisions; this file times, collects and
compares.

A run, in order:

  set-up   the driver makes the fixtures from the seed (in spawned
           workers) while ``aot.warmup`` warms the cell's programs
           alone (compile cache at ``<checkout>/.jax_cache``); the
           driver's round objects are built, the committee table
           uploaded, one call made;
  window   the driver's loop for ``--seconds``: every call timed,
           every decision kept;
  slice    ``--trace 1`` only: one more call under the profiler;
  judge    device peak memory read, the scheduler stopped, then the
           driver checks every decision of the run with
           ``reference/``.

With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics.  ``correct`` holds when every
number under ``checks`` is within its limit: no decision unlike the
reference's, no call that raised, nothing that left the device path,
the native host BLS library or the warmed programs.  Without a TPU, or
with fewer chips than the cell asks for, the run exits 1 and prints no
result.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmark import probes, trace_reduce  # noqa: E402

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
# Calls in a traced slice: one pairing program's call already makes a
# 162-182 MB trace (my chip runs, PR 22).
TRACE_CALLS = 1


class RunFailure(Exception):
    """The run cannot produce a result; ``code`` is the exit code."""

    def __init__(self, msg: str, code: int = 1):
        super().__init__(msg)
        self.code = code


@dataclass
class Cell:
    bench: dict
    workload: dict
    config: dict
    traffic: dict


@dataclass
class Run:
    """What one run measured; the metric readers take it."""

    programs: list
    setup_s: float = 0.0
    samples: list = field(default_factory=list)   # seconds per call
    items: int = 0                                 # decided in the window
    elapsed: float = 0.0                           # window, start to last
    counters: dict = field(default_factory=dict)   # window deltas
    trace: dict = field(default_factory=dict)      # trace_reduce output
    traced_items: int = 0


def _name(s: str) -> str:
    if not isinstance(s, str) or not NAME.match(s):
        raise RunFailure(f"not a valid name: {s!r}", 2)
    return s


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell's entries and files, found by name."""
    try:
        bench = json.loads((root / "BENCHMARK.json").read_text())
        wl = next(w for w in bench["workloads"] if w["name"] == workload)
        cf = next(c for c in bench["configs"] if c["name"] == wl["config"])
        config = json.loads((root / cf["file"]).read_text())
        traffic = json.loads((root / "benchmark" / "traffic"
                              / f"{_name(wl['traffic'])}.json").read_text())
    except StopIteration:
        raise RunFailure(f"no workload or config named {workload!r} in "
                         "BENCHMARK.json", 2) from None
    except (OSError, ValueError, KeyError) as e:
        raise RunFailure(f"cannot load cell {workload!r}: {e}", 2) from e
    return Cell(bench, wl, dict(config, name=cf["name"]),
                dict(traffic, name=wl["traffic"]))


def driver_module(cell: Cell):
    name = _name(cell.traffic.get("driver", ""))
    try:
        return importlib.import_module(f"benchmark.drivers.{name}")
    except ModuleNotFoundError as e:
        raise RunFailure(f"no driver {name!r}: {e}", 2) from e


def workers() -> int:
    """Worker processes for the fixtures and the reference: the cores
    less one."""
    return max(1, min(12, (os.cpu_count() or 2) - 1))


def metric_names(cell: Cell, trace: bool) -> list:
    """The cell's metrics: end-to-end with --trace 0, per-layer with 1.
    A metric without a ``workloads`` key belongs to every cell that
    reports the metric it moves."""
    name = cell.workload["name"]
    e2e = [m for m in cell.bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not trace:
        return [m["name"] for m in e2e]
    moved = {m["name"] for m in e2e}
    return [m["name"] for m in cell.bench["per_layer"]
            if name in m.get("workloads", [name] if m["moves"] in moved
                             else [])]


def read_metric(name: str, run: Run):
    """benchmark/metrics/<name>.py's ``read(run)``: a number, or None
    when the run holds nothing it can read."""
    path = HERE / "metrics" / f"{_name(name)}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmark.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def require_chip(chips: int) -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise RunFailure(f"no TPU: JAX's first device is {dev.platform!r} "
                         f"({dev.device_kind}); the benchmark runs only on "
                         "the chip")
    if len(devices) < chips:
        raise RunFailure(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def warm(programs: list) -> None:
    from harmony_tpu import aot

    manifest = aot.load_manifest()
    missing = set(programs) - set(aot.manifest_names(manifest or {}))
    if manifest is None or missing:
        raise RunFailure(f"programs not in the compile manifest: {missing}")
    stats = aot.warmup({"programs": [
        dict(fam, names=[n for n in fam["names"] if n in programs])
        for fam in manifest["programs"]]})
    for name, rec in sorted(stats["per_program"].items()):
        print(f"bench: warmup {name}: {rec['outcome']}, lower "
              f"{rec['lower_s']:.1f} s, compile {rec['compile_s']:.1f} s",
              file=sys.stderr, flush=True)
    if stats["failed"] or stats["warmed"] < len(programs):
        raise RunFailure(f"warmup warmed {stats['warmed']} of "
                         f"{len(programs)} programs")


def _traced_slice(driver, calls: int, k: int, decisions: list) -> dict:
    import jax

    tdir = ROOT / ".bench_trace"
    shutil.rmtree(tdir, ignore_errors=True)
    # no Python call tracing and no HLO protos: a whole pairing
    # program's HLO alone made a one-call trace 160 MB (my chip run)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    t0 = time.monotonic()
    jax.profiler.start_trace(str(tdir), profiler_options=opts)
    try:
        with probes.layer_spans(), \
                jax.profiler.TraceAnnotation("bench:slice"):
            for i in range(calls):
                with jax.profiler.TraceAnnotation("bench:entry"):
                    decisions += driver.call(k + i)
    finally:
        jax.profiler.stop_trace()
    try:
        files = sorted(tdir.rglob("*.xplane.pb"))
        if not files:
            return {}
        t1 = time.monotonic()
        out = trace_reduce.reduce(trace_reduce.load(str(files[-1])))
        print(f"bench: trace {files[-1].stat().st_size} bytes, traced "
              f"{t1 - t0:.1f} s, reduced {time.monotonic() - t1:.1f} s",
              file=sys.stderr, flush=True)
        return out
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def _peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def require_native_bls() -> None:
    """The host hash-to-G2 runs in the native library, as a node runs
    it: ``HOST_BLS=native`` (set by ``main``) makes a library that does
    not build or load fail the run instead of the program falling back
    to pure Python."""
    from harmony_tpu.ref import native

    try:
        native.available()
    except Exception as e:  # noqa: BLE001 — no result without it
        raise RunFailure(f"native host BLS library unavailable: {e}") from e


def _spread_line(samples: list) -> str:
    """Per-call quartiles, to tell a run that is slow throughout from
    one with a few stalls."""
    if len(samples) < 2:
        return ""
    q1, q2, q3 = statistics.quantiles(samples, n=4)
    return (f", per call q1 {1000 * q1:.2f} median {1000 * q2:.2f} q3 "
            f"{1000 * q3:.2f} max {1000 * max(samples):.2f} ms")


def run_cell(cell: Cell, seconds: float, trace: bool, pending,
             device: dict) -> dict:
    """Everything after the device check; returns the result object."""
    from harmony_tpu import aot, prof, sched

    drv = driver_module(cell)
    programs = drv.programs(cell.config, cell.traffic)
    require_native_bls()
    aot.use_compile_cache()
    guard = probes.Guard()
    warm(programs)
    guard.warmed()
    fx = pending.result()
    sched.reset()
    sched.configure(enabled=True)
    run = Run(programs=programs)
    decisions: list = []
    failed = 0
    try:
        driver = drv.Driver(cell.config, cell.traffic, fx)
        driver.upload()
        decisions += driver.call(0)
        if trace:
            prof.configure(enabled=True)
        before = probes.counters()
        run.setup_s = time.monotonic() - T0
        pauses = probes.GcPauses()
        w = driver.window(seconds, 1)
        gc_line = pauses.close()
        run.counters = probes.delta(before, probes.counters())
        run.samples, run.items, run.elapsed = w.samples, w.items, w.elapsed
        failed = w.failed
        decisions += w.decisions
        print(f"bench: window {run.elapsed:.3f} s, {len(run.samples)} "
              f"calls{_spread_line(run.samples)}; gc {gc_line}; counters "
              f"{run.counters}", file=sys.stderr)
        if trace:
            run.trace = _traced_slice(driver, TRACE_CALLS, w.next_k,
                                      decisions)
            run.traced_items = TRACE_CALLS * driver.items_per_call
        device = dict(device, memory_peak_bytes=_peak_bytes())
        if trace and run.trace:
            device.update(busy_s=run.trace["busy_s"],
                          window_s=run.trace["window_s"])
    finally:
        sched.reset()
    checks = dict(guard.counts(), failed=failed)
    checks.update(drv.judge(fx, decisions, workers()))
    names = metric_names(cell, trace)
    metrics = {}
    units = {m["name"]: m["unit"]
             for m in cell.bench["end_to_end"] + cell.bench["per_layer"]}
    for name in names:
        value = read_metric(name, run)
        if value is not None:
            metrics[name] = {"value": value, "unit": units[name]}
    out = {"correct": all(v <= 0 for v in checks.values()),
           "attempted": run.items, "failed": failed, "metrics": metrics,
           "device": device}
    if trace and run.trace:
        out["breakdown"] = {"device_ops": run.trace["top_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    pending = None
    try:
        cell = load_cell(args.workload)
        if not (ROOT / "harmony_tpu").is_dir():
            raise RunFailure("no harmony_tpu beside the benchmark: run from "
                             "the root of a checkout", 2)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        os.environ.pop("HARMONY_KERNEL_TWIN", None)  # the real kernels only
        os.environ["HOST_BLS"] = "native"  # never the pure-Python hash
        pending = driver_module(cell).prepare(cell.config, cell.traffic,
                                              args.seed, workers())
        device = require_chip(int(cell.workload["chips"]))
        out = run_cell(cell, args.seconds, bool(args.trace), pending,
                       device)
    except RunFailure as e:
        print(f"bench: FAIL: {e}", file=sys.stderr, flush=True)
        return e.code
    finally:
        if pending is not None:
            pending.close()
    for k, v in out["checks"].items():
        print(f"bench: check {k} = {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # no interpreter teardown: jaxlib's atexit has crashed after
    # thread-heavy runs (chip_smoke.py), after the result printed
    os._exit(code)
