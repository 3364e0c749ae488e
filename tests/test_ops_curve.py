"""Batched Jacobian group-law tests vs the affine bigint reference."""

import random

import jax.numpy as jnp
import numpy as np

from harmony_tpu.ops import curve as CV
from harmony_tpu.ops import interop as I
from harmony_tpu.ref import curve as RC
from harmony_tpu.ref.params import R_ORDER

rng = random.Random(0xC4)

KS = [rng.randrange(1, R_ORDER) for _ in range(4)]
G1_REF = [RC.g1.mul(RC.G1_GEN, k) for k in KS]
G1_PTS = jnp.asarray(np.stack([I.g1_affine_to_jacobian_arr(p) for p in G1_REF]))


def test_g1_dbl():
    out = CV.dbl(G1_PTS, CV.FP_OPS)
    for i in range(4):
        assert I.arr_to_g1_affine(np.array(out[i])) == RC.g1.dbl(G1_REF[i])


def test_g1_add_including_special_cases():
    p0, p1 = G1_REF[0], G1_REF[1]
    cases = [
        (p0, p1),
        (p0, p0),  # doubling path
        (p0, RC.g1.neg(p0)),  # inverse -> infinity
        (None, p1),
        (p0, None),
        (None, None),
    ]
    a = jnp.asarray(np.stack([I.g1_affine_to_jacobian_arr(x) for x, _ in cases]))
    b = jnp.asarray(np.stack([I.g1_affine_to_jacobian_arr(y) for _, y in cases]))
    out = CV.add(a, b, CV.FP_OPS)
    for i, (x, y) in enumerate(cases):
        assert I.arr_to_g1_affine(np.array(out[i])) == RC.g1.add(x, y), i


def test_g2_dbl_add():
    ref2 = [RC.g2.mul(RC.G2_GEN, k) for k in KS[:2]]
    pts2 = jnp.asarray(np.stack([I.g2_affine_to_jacobian_arr(p) for p in ref2]))
    out = CV.dbl(pts2, CV.FP2_OPS)
    for i in range(2):
        assert I.arr_to_g2_affine(np.array(out[i])) == RC.g2.dbl(ref2[i])
    cases = [
        (ref2[0], ref2[1]),
        (ref2[0], ref2[0]),
        (ref2[0], RC.g2.neg(ref2[0])),
        (None, ref2[1]),
    ]
    a = jnp.asarray(np.stack([I.g2_affine_to_jacobian_arr(x) for x, _ in cases]))
    b = jnp.asarray(np.stack([I.g2_affine_to_jacobian_arr(y) for _, y in cases]))
    out = CV.add(a, b, CV.FP2_OPS)
    for i, (x, y) in enumerate(cases):
        assert I.arr_to_g2_affine(np.array(out[i])) == RC.g2.add(x, y), i


def test_scalar_mul_per_element():
    ks = [rng.randrange(1, 1 << 64) for _ in range(4)]
    bits = jnp.asarray(
        [[(k >> (63 - j)) & 1 for j in range(64)] for k in ks], dtype=jnp.int32
    )
    out = CV.scalar_mul(G1_PTS, bits, CV.FP_OPS)
    for i in range(4):
        assert I.arr_to_g1_affine(np.array(out[i])) == RC.g1.mul(
            G1_REF[i], ks[i]
        )


def test_masked_sum_matches_mask_aggregate():
    # the Mask.AggregatePublic behavior (reference: crypto/bls/mask.go)
    mask = [1, 0, 1, 1]
    expect = None
    for i, m in enumerate(mask):
        if m:
            expect = RC.g1.add(expect, G1_REF[i])
    out = CV.masked_sum(G1_PTS, jnp.asarray(mask), CV.FP_OPS)
    assert I.arr_to_g1_affine(np.array(out)) == expect
    # empty mask -> infinity
    out = CV.masked_sum(G1_PTS, jnp.asarray([0, 0, 0, 0]), CV.FP_OPS)
    assert I.arr_to_g1_affine(np.array(out)) is None


def test_masked_sum_duplicate_points():
    # duplicate keys exercise the doubling path inside the tree reduction
    dup = jnp.asarray(
        np.stack([I.g1_affine_to_jacobian_arr(G1_REF[0])] * 2)
    )
    out = CV.masked_sum(dup, jnp.asarray([1, 1]), CV.FP_OPS)
    assert I.arr_to_g1_affine(np.array(out)) == RC.g1.dbl(G1_REF[0])

