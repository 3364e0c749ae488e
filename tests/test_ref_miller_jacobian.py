"""The bigint twin's Miller loop with P in Jacobian coordinates.

The TPU kernel (ops/pairing.py) takes the aggregate key as the masked G1
sum leaves it, (X, Y, Z), and scales every line by Z^3 instead of
inverting Z.  Its twin, ``ref.pairing.miller_loop_projective`` with
``zp``, mirrors those lines; these checks hold the twin to the pairing.
Pure Python: a few bigint Miller loops, one final exponentiation pair.
"""

import random

import pytest

from harmony_tpu.ref import fields as F
from harmony_tpu.ref import pairing as PR
from harmony_tpu.ref.curve import G1_GEN, G2_GEN, g1, g2
from harmony_tpu.ref.params import P, R_ORDER

rng = random.Random(0x2A24)


def _with_z(pt, zp):
    """(X, Y) of the affine point ``pt`` written with Z coordinate zp."""
    return (pt[0] * zp * zp % P, pt[1] * pow(zp, 3, P) % P)


def test_jacobian_p_gives_the_affine_pairing():
    # the lines scaled by Z^3 give the affine P's pairing after the
    # final exponentiation
    p = g1.mul(G1_GEN, rng.randrange(1, R_ORDER))
    q = g2.mul(G2_GEN, rng.randrange(1, R_ORDER))
    zp = rng.randrange(2, P)
    f = PR.miller_loop_projective(_with_z(p, zp), q, zp)
    assert PR.final_exponentiation(f) == PR.pairing(p, q)


@pytest.mark.parametrize("zp", [1, 0x5DEECE66D << 300],
                         ids=["z1", "z_wide"])
def test_jacobian_p_scales_the_value_by_z3(zp):
    # exactly f(affine P) * zp^(3 e): each line carries zp^3 and the
    # loop's squarings raise the k-th line's factor to 2^(squarings
    # after it); at zp = 1 the twin's value is the affine one
    e = 0
    for bit in PR._ABS_X_BITS[1:]:
        e = 2 * e + 1  # square, then the doubling line
        if bit == "1":
            e += 1  # the addition line
    p, q = g1.mul(G1_GEN, 5), g2.dbl(G2_GEN)
    f_aff = PR.miller_loop_projective(p, q)
    f_jac = PR.miller_loop_projective(_with_z(p, zp), q, zp)
    assert f_jac == F.fp12_mul(f_aff, F.fp_to_fp12(pow(zp, 3 * e, P)))
