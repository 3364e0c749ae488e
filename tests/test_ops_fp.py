"""Limb-arithmetic tests: JAX Fp ops vs Python bigints."""

import random

import jax.numpy as jnp
import numpy as np
import pytest

from harmony_tpu.ops import fp
from harmony_tpu.ops.limbs import ints_to_limbs, limbs_to_int
from harmony_tpu.ref.params import P

rng = random.Random(0xF9)
R = 1 << 384

XS = [rng.randrange(P) for _ in range(16)]
YS = [rng.randrange(P) for _ in range(16)]
A = jnp.asarray(ints_to_limbs(XS))
B = jnp.asarray(ints_to_limbs(YS))


def _ints(arr):
    return [limbs_to_int(np.array(row)) for row in np.asarray(arr)]


def test_add_sub_neg():
    assert _ints(fp.add(A, B)) == [(x + y) % P for x, y in zip(XS, YS)]
    assert _ints(fp.sub(A, B)) == [(x - y) % P for x, y in zip(XS, YS)]
    assert _ints(fp.neg(A)) == [(-x) % P for x in XS]


# below p with every limb but the top one at 4095: the widest canonical
# digits, so the convolutions' sums reach their bounds
ALL_4095 = (P >> 372 << 372) - 1
EDGE = [0, 1, P - 1, ALL_4095]


def _rand(shape):
    return [rng.randrange(P) for _ in range(int(np.prod(shape)))]


def _limbs(xs, shape):
    return jnp.asarray(ints_to_limbs(xs).reshape(shape + (32,)))


# leading shapes: one row (padded to a sublane tile inside mont_mul), a
# stacked Fp12 product's 54 rows, the tower-shaped (3, 6, 3), and 16
# rows, a whole number of tiles
@pytest.mark.parametrize("shape", [(16,), (1,), (54,), (3, 6, 3)])
def test_mont_mul_matches_bigint(shape):
    xs, ys = _rand(shape), _rand(shape)
    got = fp.mont_mul(_limbs(xs, shape), _limbs(ys, shape))
    assert got.shape == shape + (32,)
    assert _ints(got.reshape(-1, 32)) == [
        x * y * pow(R, -1, P) % P for x, y in zip(xs, ys)]


def test_mont_mul_broadcasts_operands():
    """(3, 1, 32) times (1, 4, 32): the stacked-by-broadcast call the
    towers make, against the bigint product of every pair."""
    xs, ys = _rand((3,)), _rand((4,))
    got = fp.mont_mul(_limbs(xs, (3, 1)), _limbs(ys, (1, 4)))
    assert got.shape == (3, 4, 32)
    rinv = pow(R, -1, P)
    assert _ints(got.reshape(-1, 32)) == [
        x * y * rinv % P for x in xs for y in ys]


@pytest.mark.parametrize("xs", [XS, EDGE], ids=["random", "edge"])
def test_mont_domain_roundtrip(xs):
    a = jnp.asarray(ints_to_limbs(xs))
    m = fp.to_mont(a)
    assert _ints(m) == [x * R % P for x in xs]
    assert _ints(fp.from_mont(m)) == xs


def test_inverse():
    am = jnp.asarray(ints_to_limbs([x * R % P for x in XS]))
    prod = fp.mont_mul(fp.inv(am), am)
    assert _ints(prod) == [R % P] * 16  # Montgomery form of 1


def test_edge_values():
    e = jnp.asarray(ints_to_limbs([0, 1, P - 1, P - 1]))
    f2 = jnp.asarray(ints_to_limbs([0, P - 1, P - 1, 1]))
    assert _ints(fp.add(e, f2)) == [0, 0, P - 2, 0]
    assert _ints(fp.neg(e)) == [0, P - 1, 1, 1]
    assert list(np.asarray(fp.is_zero(e))) == [True, False, False, False]


@pytest.mark.parametrize("pair", [
    (P - 1, P - 1), (ALL_4095, ALL_4095), (ALL_4095, P - 1), (P - 1, 1),
    (0, P - 1), (1, 1), ((P - 1) * R % P, (P - 1) * R % P),
], ids=["p-1^2", "4095s^2", "4095s*(p-1)", "(p-1)*1", "0*(p-1)", "1*1",
        "mont(p-1)^2"])
def test_mul_worst_case_carries(pair):
    # raw digits, not Montgomery forms: the operands' limbs themselves
    # are the extreme ones
    x, y = pair
    got = _ints(fp.mont_mul(jnp.asarray(ints_to_limbs([x])),
                            jnp.asarray(ints_to_limbs([y]))))
    assert got == [x * y * pow(R, -1, P) % P]
