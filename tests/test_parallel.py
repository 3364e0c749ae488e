"""Multi-chip sharding validation: run dryrun_multichip in a subprocess
with 8 virtual CPU devices (see conftest.py for why not in-process).

CPU-virtualized dryruns compile the SHARDED collective half and decide
the pairing with the bigint reference (see __graft_entry__ docstring —
measured 253 s from scratch on the 1-core CI box), so the budget here
is the driver-shaped 600 s, not the old 3600 s.
"""

import os
import pathlib
import subprocess
import sys


def test_dryrun_multichip_8_devices():
    root = pathlib.Path(__file__).parent.parent
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = str(root)
    proc = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        cwd=root,
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "dryrun_multichip OK" in proc.stdout
