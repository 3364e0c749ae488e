"""The readers of the program's host stages: ms of a stage per item
the run decided, and nothing where the stage was never recorded."""

import json

import pytest

from conftest import ROOT

from benchmark import run as R

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
STAGES = ("header_hash", "sig_decode", "mask", "quorum_tally",
          "device_prep")
CELLS = {"proof": "v5_quorum_proof", "replay": "v3_3_replay_window"}
# metric -> (stage, cell); a quorum-proof check hashes no header
STAGE_METRICS = {f"{s}_ms.{c}": (s, cell) for s in STAGES
                 for c, cell in CELLS.items()
                 if (s, c) != ("header_hash", "proof")}


def test_each_stage_metric_has_its_reader_and_one_cell():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name, (stage, cell) in STAGE_METRICS.items():
        m = entries[name]
        assert (ROOT / "benchmark" / "metrics" / f"{name}.py").is_file()
        assert m["source"] == "program_span" and m["unit"] == "ms"
        assert m["workloads"] == [cell]
        assert m["layer"] == ("device dispatch" if stage == "device_prep"
                              else "entry")


@pytest.fixture
def summary(monkeypatch):
    from harmony_tpu import prof

    filled: dict = {}
    monkeypatch.setattr(prof, "stage_summary", lambda: filled)
    return filled


@pytest.mark.parametrize("name", sorted(STAGE_METRICS))
def test_reader_is_ms_of_its_stage_per_item(name, summary):
    stage = STAGE_METRICS[name][0]
    run = R.Run(programs=[], items=192, traced_items=64)
    assert R.read_metric(name, run) is None  # never recorded
    for i, other in enumerate(STAGES):
        summary[other] = {"count": 7, "sum_s": 0.5 * (i + 1)}
    want = 1000 * summary[stage]["sum_s"] / (192 + 64)
    assert R.read_metric(name, run) == pytest.approx(want)
    assert R.read_metric(name, R.Run(programs=[])) is None  # no items
    summary[stage] = {"count": 0, "sum_s": 0.0}
    assert R.read_metric(name, run) is None


def test_reader_reads_what_an_armed_stage_recorded():
    from harmony_tpu import prof

    prof.reset()
    prof.configure(enabled=True)
    try:
        for _ in range(3):
            with prof.stage("mask"):
                pass
        run = R.Run(programs=[], items=2, traced_items=1)
        got = R.read_metric("mask_ms.proof", run)
        assert got == 1000 * prof.stage_summary()["mask"]["sum_s"] / 3
        assert R.read_metric("sig_decode_ms.proof", run) is None
    finally:
        prof.reset()
