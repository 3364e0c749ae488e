"""The harness: traffic from a seed, statistics over every sample,
lookup by name, and no result without a chip."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import ROOT, SEED, tiny_cell

from benchmark import gen, loops, stats
from benchmark import run as R
from benchmark.metrics import _layers as L

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_traffic_repeats_from_the_seed(workload):
    cell = tiny_cell(workload)
    a = gen.Pending(cell.config, cell.traffic, SEED, 2).result()
    b = gen.Pending(cell.config, cell.traffic, SEED, 3).result()
    c, _ = gen.plan(cell.config, cell.traffic, SEED + 1)
    assert a.pubkeys == b.pubkeys and a.stakes == b.stakes
    assert [(i.header, i.proof, i.invalid) for i in a.items] == \
        [(i.header, i.proof, i.invalid) for i in b.items]
    assert [i.header for i in c.items] != [i.header for i in a.items]
    # every seed makes the same sizes: absent slots, invalid proofs of
    # each kind in turn
    n, pool = cell.config["slots"], cell.traffic["pool"]
    bad = pool // cell.traffic["invalid_every"]
    for fx in (a, c):
        assert len(fx.items) == pool
        assert [i.invalid for i in fx.items if i.invalid] == \
            [gen.INVALID[j % len(gen.INVALID)] for j in range(bad)]
        for it in fx.items:
            if it.invalid == "short_of_quorum":
                continue
            absent = n // cell.traffic["absent_every"]
            assert n - sum(it.bits) == absent + (
                it.invalid == "bitmap_mismatch")


@pytest.mark.parametrize("workload", CELLS)
def test_short_of_quorum_is_short_of_stake_alone(workload):
    """A short proof's signers hold less than 2/3 of the vote by the
    margin; on the full mainnet V3_3 committee they hold more than 2/3
    of the slots, so a tally of slots would accept them."""
    from fractions import Fraction

    cell = R.load_cell(workload)
    cell.traffic["pool"] = 48
    fx, _ = gen.plan(cell.config, cell.traffic, SEED)
    short = [it for it in fx.items if it.invalid == "short_of_quorum"]
    assert short
    for it in short:
        vote = sum(p for p, b in zip(fx.power, it.bits) if b)
        assert vote < Fraction(2, 3) - gen.QUORUM_MARGIN
        assert it.bits == it.signers
        if cell.config["name"] == "mainnet_v3_3_shard":
            assert 3 * sum(it.bits) > 2 * fx.slots


def test_percentiles_take_every_sample():
    xs = [float(i) for i in range(1, 201)]
    assert stats.percentile(xs, 50) == 100.0
    assert stats.percentile(xs, 95) == 190.0
    assert stats.percentile(reversed(xs), 95) == 190.0
    assert stats.percentile([7.0], 95) == 7.0
    # ten samples past the 95th of 200 leave it in place, an eleventh
    # moves it: every sample counts, none is dropped or averaged
    xs[-10:] = [1000.0] * 10
    assert stats.percentile(xs, 95) == 190.0
    xs[0] = 5000.0
    assert stats.percentile(xs, 95) == 1000.0


def test_rate_is_over_the_whole_window():
    run = R.Run(programs=[], samples=[0.5, 0.5, 2.0],
                items=192, elapsed=3.0)
    assert L.items_per_s(run) == 64.0
    assert L.p50_ms(run) == 500.0
    assert L.p95_ms(run) == 2000.0


def test_the_closed_loop_keeps_every_call():
    import time

    seen = []

    def call(k):
        seen.append(k)
        time.sleep(0.01)
        if k == 3:
            raise RuntimeError("planted")
        return [(k, True)] * 4

    w = loops.closed(call, 4, 0.2, 1)
    assert seen == list(range(1, w.next_k)) and len(w.samples) == len(seen)
    assert w.items == 4 * len(w.samples) == len(w.decisions) + 4
    assert w.failed == 4 and w.elapsed >= 0.2
    assert w.elapsed >= sum(w.samples)


def test_cells_configs_traffic_and_metrics_are_found_by_name():
    for w in BENCH["workloads"]:
        cell = R.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["name"] == w["traffic"]
        mod = R.driver_module(cell)
        assert mod.programs(cell.config, cell.traffic)
        for attr in ("prepare", "Driver", "judge"):
            assert callable(getattr(mod, attr))
        for trace in (False, True):
            for name in R.metric_names(cell, trace):
                assert (ROOT / "benchmark" / "metrics"
                        / f"{name}.py").is_file()
        assert "setup_s" in R.metric_names(cell, False)
        assert R.metric_names(cell, True)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
    with pytest.raises(R.RunFailure) as e:
        R.load_cell("no_such_cell")
    assert e.value.code == 2
    cell = R.load_cell(CELLS[0])
    cell.traffic["driver"] = "no_such_driver"
    with pytest.raises(R.RunFailure) as e:
        R.driver_module(cell)
    assert e.value.code == 2


def test_readers_say_nothing_when_there_is_nothing_to_read():
    run = R.Run(programs=["agg_verify_b256"])
    for m in BENCH["per_layer"]:
        assert R.read_metric(m["name"], run) is None
    run.trace = {"window_s": 2.0, "busy_s": 1.5,
                 "programs": {"jit_agg_verify": 1.0, "jit_other": 9.0}}
    run.traced_items = 5
    assert R.read_metric("kernel_ms.proof", run) == 200.0
    assert R.read_metric("device_idle_share.proof", run) == 25.0


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", str(SEED), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_fails_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_fails_with_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert not (Path(tmp_path) / ".jax_cache").exists()
