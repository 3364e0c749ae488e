"""Fast-tier EXECUTION of the Miller-loop step kernels (VERDICT r3 #5).

The full pairing program cannot compile inside the fast tier on this
box (20+ min of XLA:CPU), which left a hole: an edit
breaking ops/pairing.py math kept the quick suite green.  Three layers
now close it:

1. HERE — the factored Miller step kernels (_dbl_step, _add_step) are
   small programs that compile in seconds; their point halves are
   checked against the bigint ref group law (formula-independent: the
   jax kernels use twist-Jacobian dbl-2009-l / madd-2007-bl, the ref
   uses affine chord-tangent).
2. tests/test_fp_backend.py — mont_mul/towers/group-law executed and
   cross-checked on every run.
3. tests/test_multichip_artifact.py — the lowering digest of the FULL
   fused program (Miller loop, final exponentiation, line assembly
   included): any structural/math edit flips the artifact and fails CI,
   forcing the isolated heavy parity tier before re-pinning.

The line-coefficient VALUES and the final exponentiation stay covered
by the heavy tier (test_ops_pairing_bls via test_ops_heavy_isolated) —
they have no cheap independent oracle below a full pairing.
"""

import jax
import numpy as np
import pytest

from harmony_tpu.ops import fp
from harmony_tpu.ops import interop as I
from harmony_tpu.ops import pairing as OP
from harmony_tpu.ref.curve import G2_GEN, g2
from harmony_tpu.ref import fields as F


def _g2_jac_from_affine(pt):
    arr = I.g2_affine_to_arr(pt)  # (2, 2, 32) x/y affine
    one = I.fp2_to_arr((1, 0))
    return arr[0], arr[1], one


def _g2_affine_from_jac(x, y, z):
    xi = I.arr_to_fp2(np.asarray(x))
    yi = I.arr_to_fp2(np.asarray(y))
    zi = I.arr_to_fp2(np.asarray(z))
    z_inv = F.fp2_inv(zi)
    z2 = F.fp2_sqr(z_inv)
    return (
        F.fp2_mul(xi, z2),
        F.fp2_mul(yi, F.fp2_mul(z2, z_inv)),
    )


@pytest.fixture(scope="module")
def base_points():
    t = g2.mul(G2_GEN, 7)
    q = g2.mul(G2_GEN, 11)
    return t, q


def test_dbl_step_point_half_matches_group_law(base_points):
    t, _ = base_points
    x, y, z = _g2_jac_from_affine(t)
    xp3 = fp.to_mont(np.zeros(32, dtype=np.int32))  # line inputs: any
    yp2 = xp3  # valid Fp residues; the point half ignores them

    @jax.jit
    def step(x, y, z, a, b):
        (x3, y3, z3), _ = OP._dbl_step(x, y, z, a, b)
        return x3, y3, z3

    x3, y3, z3 = step(x, y, z, xp3, yp2)
    assert _g2_affine_from_jac(x3, y3, z3) == g2.dbl(t)


def test_add_step_point_half_matches_group_law(base_points):
    t, q = base_points
    x, y, z = _g2_jac_from_affine(t)
    qx = I.fp2_to_arr(q[0])
    qy = I.fp2_to_arr(q[1])
    dummy = fp.to_mont(np.zeros(32, dtype=np.int32))

    @jax.jit
    def step(x, y, z, qx, qy, a, b):
        (x3, y3, z3), _ = OP._add_step(x, y, z, qx, qy, a, b)
        return x3, y3, z3

    x3, y3, z3 = step(x, y, z, qx, qy, dummy, dummy)
    assert _g2_affine_from_jac(x3, y3, z3) == g2.add(t, q)


def test_dbl_chain_stays_on_curve_and_consistent(base_points):
    """Three chained doublings through the jitted kernel must track the
    bigint group law exactly (catches accumulated coordinate-scaling
    errors a single step could mask)."""
    t, _ = base_points
    x, y, z = _g2_jac_from_affine(t)
    dummy = fp.to_mont(np.zeros(32, dtype=np.int32))

    @jax.jit
    def chain(x, y, z, a, b):
        for _ in range(3):
            (x, y, z), _ = OP._dbl_step(x, y, z, a, b)
        return x, y, z

    x3, y3, z3 = chain(x, y, z, dummy, dummy)
    want = g2.dbl(g2.dbl(g2.dbl(t)))
    assert _g2_affine_from_jac(x3, y3, z3) == want
