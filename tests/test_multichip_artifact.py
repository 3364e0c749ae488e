"""CI diff of the fused multichip lowering artifact (VERDICT r3 #7).

Regenerates the StableHLO summary of ``sharded_agg_verify`` lowered for
an 8-virtual-device mesh — its entry signature and its collectives —
and diffs it against the checked-in artifact: a mesh, sharding or
collective regression in parallel/mesh.py fails here WITHOUT executing
the program, which no box below a real 8-chip mesh can afford.  Lowering is
tracing + StableHLO emission only (no LLVM): ~2-3 min on the 1-core
box.  Set MULTICHIP_ARTIFACT=0 to skip locally.
"""

import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).parent.parent

if os.environ.get("MULTICHIP_ARTIFACT") == "0":
    pytest.skip("MULTICHIP_ARTIFACT=0", allow_module_level=True)


def test_fused_lowering_matches_checked_in_artifact():
    env = dict(os.environ)
    # a clean child: the conftest's CPU pinning must not leak, and the
    # script pins the platform itself
    env.pop("PYTEST_CURRENT_TEST", None)
    proc = subprocess.run(
        [sys.executable, "tools/lower_multichip.py", "--check"],
        cwd=ROOT,
        capture_output=True,
        text=True,
        timeout=1200,
    )
    assert proc.returncode == 0, (
        f"multichip lowering artifact drifted:\n{proc.stdout[-3000:]}"
        f"\n{proc.stderr[-500:]}"
    )
