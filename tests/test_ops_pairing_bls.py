"""TPU pairing + BLS op-surface tests vs the bigint reference.

These carry the heaviest one-time XLA:CPU compiles in the suite (cached in
.jax_cache; shapes here deliberately match across tests to share cache
entries).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from harmony_tpu.ops import bls as OB
from harmony_tpu.ops import interop as I
from harmony_tpu.ops import pairing as OP
from harmony_tpu.ref import bls as RB
from harmony_tpu.ref import pairing as RP
from harmony_tpu.ref.curve import G1_GEN, G2_GEN, g1, g2
from harmony_tpu.ref.hash_to_curve import hash_to_g2, map_to_twist

MSG = b"0123456789abcdef0123456789abcdef"


def _g1_aff(p):
    return np.stack([I.fp_to_arr(p[0]), I.fp_to_arr(p[1])])


def _g1_jac(p, zp=1):
    """The affine point p as a (3, 32) Jacobian tensor with Z = zp."""
    x = p[0] * zp * zp % RP.P
    y = p[1] * pow(zp, 3, RP.P) % RP.P
    return np.stack([I.fp_to_arr(x), I.fp_to_arr(y), I.fp_to_arr(zp)])


def _g2_aff(q):
    return np.stack([I.fp2_to_arr(q[0]), I.fp2_to_arr(q[1])])


@pytest.fixture(scope="module")
def keys():
    sks = [RB.keygen(bytes([i])) for i in range(4)]
    pks = [RB.pubkey(sk) for sk in sks]
    sigs = [RB.sign(sk, MSG) for sk in sks]
    return sks, pks, sigs


@pytest.fixture(scope="module")
def h_point():
    return hash_to_g2(MSG)


def test_miller_loop_matches_bigint_twin():
    ps = [G1_GEN, g1.mul(G1_GEN, 123456789)]
    qs = [G2_GEN, g2.mul(G2_GEN, 987654321)]
    p_arr = jnp.asarray(np.stack([_g1_jac(p) for p in ps]))
    q_arr = jnp.asarray(np.stack([_g2_aff(q) for q in qs]))
    f = OP.miller_loop(p_arr, q_arr)
    for i in range(2):
        assert I.arr_to_fp12(np.array(f[i])) == RP.miller_loop_projective(
            ps[i], qs[i]
        )


def test_miller_loop_jacobian_p_matches_bigint_twin():
    # P with Z != 1, as the masked G1 sum hands it to the pairing: the
    # kernel's Z^3-scaled lines equal the twin's bit for bit
    ps = [G1_GEN, g1.mul(G1_GEN, 123456789)]
    qs = [G2_GEN, g2.mul(G2_GEN, 987654321)]
    zs = [0x1F2E3D4C5B6A7988, RP.P - 3]
    p_arr = jnp.asarray(np.stack([_g1_jac(p, z) for p, z in zip(ps, zs)]))
    q_arr = jnp.asarray(np.stack([_g2_aff(q) for q in qs]))
    f = OP.miller_loop(p_arr, q_arr)
    for i in range(2):
        assert I.arr_to_fp12(np.array(f[i])) == RP.miller_loop_projective(
            (ps[i][0] * zs[i] ** 2 % RP.P, ps[i][1] * zs[i] ** 3 % RP.P),
            qs[i],
            zs[i],
        )


def test_pairing_matches_reference_gt():
    ps = [G1_GEN, g1.mul(G1_GEN, 123456789)]
    qs = [G2_GEN, g2.mul(G2_GEN, 987654321)]
    p_arr = jnp.asarray(np.stack([_g1_jac(p) for p in ps]))
    q_arr = jnp.asarray(np.stack([_g2_aff(q) for q in qs]))
    e = OP.pairing(p_arr, q_arr)
    for i in range(2):
        assert I.arr_to_fp12(np.array(e[i])) == RP.pairing(ps[i], qs[i])


def test_pairing_product_cancellation():
    # e(-G1, 2 G2) * e(2 G1, G2) == 1
    pp = [g1.neg(G1_GEN), g1.dbl(G1_GEN)]
    qq = [g2.dbl(G2_GEN), G2_GEN]
    p_arr = jnp.asarray(np.stack([_g1_jac(p) for p in pp]))
    q_arr = jnp.asarray(np.stack([_g2_aff(q) for q in qq]))
    assert bool(OP.is_one(OP.pairing_product(p_arr, q_arr)))


def test_bls_verify_batch(keys, h_point):
    _, pks, sigs = keys
    pk = jnp.asarray(np.stack([_g1_aff(p) for p in pks]))
    sg = jnp.asarray(np.stack([_g2_aff(s) for s in sigs]))
    hh = jnp.broadcast_to(jnp.asarray(_g2_aff(h_point)), (4, 2, 2, 32))
    ok = OB.verify(pk, hh, sg)
    assert all(np.array(ok))
    bad = OB.verify(pk, hh, jnp.roll(sg, 1, axis=0))
    assert not any(np.array(bad))


def test_bls_agg_verify_bitmap(keys, h_point):
    _, pks, sigs = keys
    pk = jnp.asarray(np.stack([_g1_aff(p) for p in pks]))
    h_arr = jnp.asarray(_g2_aff(h_point))
    agg = RB.aggregate_sigs([sigs[0], sigs[2], sigs[3]])
    ag = jnp.asarray(_g2_aff(agg))
    assert bool(OB.agg_verify(pk, jnp.asarray([1, 0, 1, 1]), h_arr, ag))
    assert not bool(OB.agg_verify(pk, jnp.asarray([1, 1, 1, 1]), h_arr, ag))


def test_bls_agg_verify_rejects_aggregate_key_at_infinity(keys, h_point):
    # a key and its negation sum to infinity (Z = 0): no signature,
    # however formed, passes for it
    _, pks, sigs = keys
    pk = jnp.asarray(np.stack([_g1_aff(p) for p in
                               [pks[0], g1.neg(pks[0]), pks[1], pks[2]]]))
    h_arr = jnp.asarray(_g2_aff(h_point))
    ag = jnp.asarray(_g2_aff(sigs[0]))
    assert not bool(OB.agg_verify(pk, jnp.asarray([1, 1, 0, 0]), h_arr, ag))


def test_device_sign_matches_reference(keys, h_point):
    sks, _, sigs = keys
    skb = jnp.asarray(OB.sk_to_bits(sks[:2]))
    h_jac = jnp.asarray(
        np.stack([I.g2_affine_to_jacobian_arr(h_point)] * 2)
    )
    out = OB.sign(h_jac, skb)
    for i in range(2):
        assert I.arr_to_g2_affine(np.array(out[i])) == sigs[i]


def test_device_pubkey_derivation(keys):
    sks, pks, _ = keys
    skb = jnp.asarray(OB.sk_to_bits(sks[:2]))
    out = OB.derive_pubkeys(skb)
    for i in range(2):
        assert I.arr_to_g1_affine(np.array(out[i])) == pks[i]


def test_device_cofactor_clearing(h_point):
    tw = map_to_twist(MSG)
    arr = jnp.asarray(np.stack([I.g2_affine_to_jacobian_arr(tw)]))
    out = OB.clear_cofactor_g2(arr)
    assert I.arr_to_g2_affine(np.array(out[0])) == h_point
