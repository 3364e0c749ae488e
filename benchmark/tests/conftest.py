"""The benchmark's own tests: on the CPU, with the program's twin
kernels where a test drives a run, at sizes a test run holds.

    python3 -m pytest benchmark/tests -q
"""

import os
import sys
from pathlib import Path

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

SEED = 2**33 + 12345  # past 32 signed bits, as the driver's are

TINY = {  # a cell's config and traffic cut to what a test run holds
    "config": {"slots": 16, "harmony_slots": 2, "committee_bucket": 16},
    "traffic": {"pool": 16, "invalid_every": 4},
}


def tiny_cell(workload: str):
    from benchmark import run as R

    cell = R.load_cell(workload)
    cell.config.update(TINY["config"])
    cell.traffic.update(TINY["traffic"])
    if "batch" in cell.traffic:
        cell.traffic["batch"] = 8
    return cell


@pytest.fixture
def twin(monkeypatch):
    """The program's device path with its twin kernels on the CPU."""
    from harmony_tpu import device as DV

    monkeypatch.setenv("HARMONY_KERNEL_TWIN", "1")
    DV.use_device(True)
    yield
    DV.use_device(None)


def drive(workload: str, seconds: float = 2.0, trace: bool = False,
          seed: int = SEED) -> dict:
    """A whole run of a tiny cell past the look for a chip."""
    from benchmark import run as R

    cell = tiny_cell(workload)
    pending = R.driver_module(cell).prepare(cell.config, cell.traffic,
                                            seed, 2)
    try:
        return R.run_cell(cell, seconds, trace, pending,
                          {"platform": "cpu", "kind": "cpu", "count": 1})
    finally:
        pending.close()
