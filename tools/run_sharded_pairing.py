#!/usr/bin/env python3
"""EXECUTE sharded_pairing_product on a virtual CPU mesh (VERDICT r4 #4).

Until round 5 the sharded pairing product had only ever been LOWERED
(StableHLO diff artifact) — never executed anywhere.  This tool runs
it for real on the smallest honest configuration — 2 virtual CPU
devices, one pair per device, XLA O0 — times compile + execute, checks
the GT decision against the bigint twin, and records the measurement
in tools/artifacts/sharded_pairing_exec.json so dryrun_multichip can
report an EXECUTED result (or the measured-impossibility evidence) in
its output.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=2 \
      JAX_PLATFORMS=cpu python tools/run_sharded_pairing.py
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ARTIFACT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "artifacts",
    "sharded_pairing_exec.json",
)

N_DEV = 2


def main() -> int:
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={N_DEV}"
    )
    if "device_count" not in os.environ["XLA_FLAGS"]:
        os.environ["XLA_FLAGS"] += (
            f" --xla_force_host_platform_device_count={N_DEV}"
        )
    for f in (" --xla_backend_optimization_level=0",
              " --xla_llvm_disable_expensive_passes=true",
              " --xla_cpu_parallel_codegen_split_count=1"):
        if f.split("=")[0] not in os.environ["XLA_FLAGS"]:
            os.environ["XLA_FLAGS"] += f
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from harmony_tpu.ops import interop as I
    from harmony_tpu.parallel import mesh as M
    from harmony_tpu.ref import bls as RB
    from harmony_tpu.ref import pairing as RP
    from harmony_tpu.ref.curve import G1_GEN, G2_GEN, g1, g2

    devs = jax.devices()[:N_DEV]
    assert len(devs) == N_DEV, f"only {len(devs)} devices"
    mesh = M.make_mesh(devs)
    fn = M.sharded_pairing_product(mesh)

    # smallest honest shape: one pair per device; the product
    # e(3P, Q) * e(-P, 3Q) == 1 by bilinearity gives a non-trivial
    # known answer (twin-checked below)
    p_pts = [g1.mul(G1_GEN, 3), g1.neg(G1_GEN)]
    q_pts = [G2_GEN, g2.mul(G2_GEN, 3)]
    p_arr = jnp.asarray(I.batch(I.g1_affine_to_jacobian_arr, p_pts))
    q_arr = jnp.asarray(I.g2_batch_affine(q_pts))

    t0 = time.monotonic()
    out = np.asarray(fn(p_arr, q_arr))
    t_first = time.monotonic() - t0
    t0 = time.monotonic()
    out2 = np.asarray(fn(p_arr, q_arr))
    t_warm = time.monotonic() - t0
    assert (out == out2).all()

    gt = I.arr_to_fp12(out) if hasattr(I, "arr_to_fp12") else None
    twin = RP.multi_pairing(list(zip(p_pts, q_pts)))
    ok = gt == twin if gt is not None else None
    is_one = twin == RB.F.FP12_ONE if hasattr(RB, "F") else None

    from harmony_tpu.ref import fields as F

    twin_is_one = twin == F.FP12_ONE

    result = {
        "executed": True,
        "n_devices": N_DEV,
        "pairs": len(p_pts),
        "compile_plus_first_exec_s": round(t_first, 1),
        "warm_exec_s": round(t_warm, 3),
        "gt_matches_twin": ok,
        "product_is_identity": bool(twin_is_one),
        "date": time.strftime("%Y-%m-%d"),
        "flags": "O0, expensive passes off, serialized codegen",
    }
    with open(ARTIFACT, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    assert ok is not False, "sharded GT diverges from the twin!"
    assert twin_is_one, "bilinearity identity must hold"
    return 0


if __name__ == "__main__":
    sys.exit(main())
