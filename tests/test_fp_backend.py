"""FP_BACKEND=pallas wiring parity (VERDICT r3 #2).

ops/fp.py::mont_mul is the single chokepoint every Fp product in the
framework flows through — tower muls, curve adds, the Miller loop, the
final exponentiation.  These tests flip the backend to the Pallas
kernel (interpret mode on CPU) and assert bit-identical results against
the scan path at each tier the fast suite can afford on this box:
raw mont_mul (incl. the lane-padding path), the Fp2/Fp12 towers, and a
G1 point-double.  The full-pairing GT comparison lives in the isolated
heavy tier (test_ops_heavy_isolated.py) because any pairing-shaped
program costs 20+ min of XLA:CPU compile here.
"""

import numpy as np
import pytest

from harmony_tpu.ops import fp
from harmony_tpu.ops import _constants as C
from harmony_tpu.ops.limbs import int_to_limbs, limbs_to_int

P = C.P_INT
rng = np.random.default_rng(42)


def _rand_fp(shape=()):
    flat = [rng.integers(0, 2**63, size=7) for _ in range(int(np.prod(shape)) or 1)]
    vals = [int.from_bytes(np.asarray(f, dtype=np.uint64).tobytes(), "little") % P
            for f in flat]
    arr = np.stack([int_to_limbs(v) for v in vals])
    return arr.reshape(*shape, arr.shape[-1]) if shape else arr[0], vals


@pytest.fixture
def pallas_backend():
    fp.set_backend("pallas-interpret")
    yield
    fp.set_backend("scan")


def _both_backends(fn):
    fp.set_backend("scan")
    want = np.asarray(fn())
    fp.set_backend("pallas-interpret")
    try:
        got = np.asarray(fn())
    finally:
        fp.set_backend("scan")
    return want, got


def test_mont_mul_parity_small_batch():
    a, _ = _rand_fp((5,))
    b, _ = _rand_fp((5,))
    want, got = _both_backends(lambda: fp.mont_mul(a, b))
    np.testing.assert_array_equal(want, got)


def test_mont_mul_parity_lane_padding():
    # 131 rows: exercises the pad-to-128 path and a 2-tile grid
    a, _ = _rand_fp((131,))
    b, _ = _rand_fp((131,))
    want, got = _both_backends(lambda: fp.mont_mul(a, b))
    np.testing.assert_array_equal(want, got)


def test_mont_mul_pallas_is_correct_vs_bigint(pallas_backend):
    a, av = _rand_fp((3,))
    b, bv = _rand_fp((3,))
    out = np.asarray(fp.mont_mul(a, b))
    r_inv = pow(1 << 384, P - 2, P)
    for row, x, y in zip(out, av, bv):
        assert limbs_to_int(row) == x * y * r_inv % P


def test_tower_mul_parity():
    from harmony_tpu.ops import towers as T

    a, _ = _rand_fp((2, 2))  # one Fp2 element batch of 2: (2, 2, 32)
    b, _ = _rand_fp((2, 2))
    want, got = _both_backends(lambda: T.fp2_mul(a, b))
    np.testing.assert_array_equal(want, got)


def test_fp12_mul_parity():
    from harmony_tpu.ops import towers as T

    a, _ = _rand_fp((2, 3, 2))  # one Fp12 element (2, 3, 2, 32)
    b, _ = _rand_fp((2, 3, 2))
    want, got = _both_backends(lambda: T.fp12_mul(a, b))
    np.testing.assert_array_equal(want, got)


def test_g1_double_parity():
    from harmony_tpu.ops import curve as CV
    from harmony_tpu.ops import interop as I
    from harmony_tpu.ref.curve import G1_GEN

    pt = I.g1_affine_to_jacobian_arr(G1_GEN)

    def run():
        x, y, z = CV.dbl(pt, CV.FP_OPS)
        return np.stack([np.asarray(x), np.asarray(y), np.asarray(z)])

    want, got = _both_backends(run)
    np.testing.assert_array_equal(want, got)
