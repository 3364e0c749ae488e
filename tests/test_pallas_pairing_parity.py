"""Full-pairing GT parity: FP_BACKEND scan vs pallas (VERDICT r3 #2).

Opt-in (OPS_PALLAS_PAIRING=1): a full pairing program costs 20+ minutes
of XLA:CPU compile on the 1-core box, and interpret-
mode Pallas multiplies that further.  The fast tier already proves the
two backends bit-identical at every composable tier (mont_mul incl.
lane padding, Fp2/Fp12 towers, the group law — tests/test_fp_backend.py);
since fp.mont_mul is the ONLY primitive the flag swaps, identical
mont_mul on all shapes implies identical GT elements.  This test checks
that implication end-to-end when the budget allows.
"""

import os

import numpy as np
import pytest

if not os.environ.get("OPS_PALLAS_PAIRING"):
    pytest.skip(
        "full-pairing backend parity is opt-in: OPS_PALLAS_PAIRING=1 "
        "(20+ min of XLA:CPU compile on this box)",
        allow_module_level=True,
    )


def test_pairing_gt_identical_across_backends():
    import jax

    from harmony_tpu.ops import fp
    from harmony_tpu.ops import interop as I
    from harmony_tpu.ops import pairing as OP
    from harmony_tpu.ref.curve import G1_GEN, G2_GEN, g1, g2

    ps = I.g1_batch_affine([G1_GEN, g1.dbl(G1_GEN)])
    qs = I.g2_batch_affine([G2_GEN, g2.dbl(G2_GEN)])

    fp.set_backend("scan")
    want = np.asarray(jax.jit(OP.pairing)(ps, qs))

    backend = (
        "pallas" if jax.default_backend() != "cpu" else "pallas-interpret"
    )
    fp.set_backend(backend)
    try:
        # fresh python callable => fresh trace under the new backend
        got = np.asarray(jax.jit(lambda p, q: OP.pairing(p, q))(ps, qs))
    finally:
        fp.set_backend("scan")
    np.testing.assert_array_equal(want, got)
