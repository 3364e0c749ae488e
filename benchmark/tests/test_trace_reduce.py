"""The trace reduction, on a small trace recorded on the chip
(``tools/record_trace.py``: three calls of a small program, each after
20 ms of host work in a ``bench:hash_to_g2`` span) and on hand-made
intervals."""

from collections import defaultdict
from pathlib import Path

import pytest

from benchmark import trace_reduce as T

TRACE = Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return T.reduce(T.load(str(TRACE)))


def test_one_chip_and_its_window(reduced):
    assert reduced["chips"] == 1
    # the bench:slice span: three calls of ~20 ms host work plus compute
    assert 0.06 < reduced["window_s"] < 1.0
    assert 0 < reduced["busy_s"] < reduced["window_s"]


def test_program_and_op_time(reduced):
    progs = reduced["programs"]
    assert set(progs) == {"jit_step"}
    assert 0 < progs["jit_step"] <= reduced["window_s"]
    ops = dict(reduced["top_ops"])
    assert ops and len(reduced["top_ops"]) <= 10
    assert sum(ops.values()) >= reduced["busy_s"] * 0.5


def test_idle_gaps_are_charged_to_host_spans(reduced):
    gaps = dict(reduced["idle_gaps"])
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    # 3 x 20 ms of host work sat in bench:hash_to_g2 with the device idle
    assert 0.055 < gaps["hash_to_g2"] < 0.2
    assert max(gaps, key=gaps.get) == "hash_to_g2"


def test_intervals():
    assert T._union([[5, 6], [0, 2], [1, 3]]) == [[0, 3], [5, 6]]
    assert T._gaps([[0, 3], [5, 6]], -1, 8) == [(-1, 0), (3, 5), (6, 8)]
    s = 1e9  # ns
    spans = [("bench:entry", 0, 100 * s), ("bench:hash_to_g2", 10 * s, 20 * s),
             ("bench:sched_wait", 40 * s, 90 * s)]
    idle = defaultdict(float)
    T._charge(spans, -10 * s, 200 * s, idle)
    assert dict(idle) == pytest.approx({
        "no host span": 110.0, "entry": 40.0, "hash_to_g2": 10.0,
        "sched_wait": 50.0})
