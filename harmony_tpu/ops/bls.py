"""Batched BLS signature ops on TPU — the kernel-side replacement for every
herumi call the reference makes through cgo (SURVEY.md §2.1):

    reference cgo op                      TPU op here
    --------------------------------------------------------------------
    SecretKey.SignHash                    sign (batched scalar-mul on G2)
    Sign.VerifyHash                       verify (batched 2-pairing check)
    aggregate verify vs Mask              agg_verify (masked G1 sum +
      (validator.go:228, engine.go:640)     one 2-pairing product)
    Sign.Add / PublicKey.Add              curve.masked_sum / curve.add
    hashAndMapToG2 (cofactor part)        clear_cofactor_g2 (batched)

Conventions: secret keys are MSB-first bit tensors (B, 255); points are
affine limb tensors in the Montgomery domain (G1 (B, 2, 32), G2
(B, 2, 2, 32)), except that a G1 point handed to the pairing is
Jacobian (B, 3, 32), so an aggregate key goes in without an inversion;
hashed messages arrive as twist points produced by the
host-side map-to-field (ref/hash_to_curve.py — branchy SHA work stays on
host per SURVEY.md §7.2).  All functions are jittable with static shapes.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import _constants as C
from . import curve as CV
from . import fp
from . import pairing as PR
from . import towers as T

# graftlint: kernel-module dtype=int32; twin=harmony_tpu/ops/twin.py

SK_BITS = 255  # ceil(log2 r)

_H2_BITS = jnp.asarray([int(b) for b in bin(C.H2)[2:]], dtype=jnp.int32)

_NEG_G1_GEN = None  # lazily built (x, -y, 1) of the G1 generator


def _neg_g1_gen():
    global _NEG_G1_GEN
    if _NEG_G1_GEN is None:
        # force concrete evaluation: a first call from INSIDE a trace
        # (e.g. under shard_map) must not cache a tracer into the
        # module global — that leaks into every later program
        with jax.ensure_compile_time_eval():
            _NEG_G1_GEN = CV.neg(CV.G1_GEN, CV.FP_OPS)
    return _NEG_G1_GEN


def sk_to_bits(sk_ints) -> np.ndarray:
    """Host helper: list of scalar ints -> (B, 255) MSB-first bit matrix."""
    out = np.zeros((len(sk_ints), SK_BITS), dtype=np.int32)
    for row, sk in enumerate(sk_ints):
        for j in range(SK_BITS):
            out[row, j] = (sk >> (SK_BITS - 1 - j)) & 1
    return out


def derive_pubkeys(sk_bits):
    """pk = sk * G1 for a batch of secret keys; returns Jacobian (B, 3, 32)."""
    base = jnp.broadcast_to(
        CV.G1_GEN, (sk_bits.shape[0],) + CV.G1_GEN.shape
    )
    return CV.scalar_mul(base, sk_bits, CV.FP_OPS)


def clear_cofactor_g2(pts):
    """Multiply twist points (B, 3, 2, 32) Jacobian by the G2 cofactor —
    the device half of hash-to-G2 (host does map-to-twist)."""
    return CV.scalar_mul(pts, _H2_BITS, CV.FP2_OPS)


def sign(h_points, sk_bits):
    """sig = sk * H(m): batched SignHash.  h_points are Jacobian G2
    (B, 3, 2, 32) hashed-message points; returns Jacobian signatures."""
    return CV.scalar_mul(h_points, sk_bits, CV.FP2_OPS)


def verify(pk_aff, h_aff, sig_aff):
    """Batched single verify: e(-G1, sig) * e(pk, H(m)) == 1.

    All inputs affine: pk (B, 2, 32), h and sig (B, 2, 2, 32).
    Returns a (B,) boolean mask.  Infinity is encoded as (0, 0) and
    rejected (matches the reference treating identity elements as
    invalid in verification).
    """
    return verify_jacobian(_affine_to_jacobian_g1(pk_aff), h_aff, sig_aff)


def verify_jacobian(pk_jac, h_aff, sig_aff):
    """``verify`` with pk in Jacobian coordinates (B, 3, 32), as the
    masked G1 sum leaves it: the Miller loop takes P with any Z, so no
    inversion runs before the pairing.  Z = 0 (infinity) is rejected."""
    neg_g1 = jnp.broadcast_to(_neg_g1_gen(), pk_jac.shape)
    ps = jnp.stack([neg_g1, pk_jac])  # (2, B, 3, 32)
    qs = jnp.stack([sig_aff, h_aff])  # (2, B, 2, 2, 32)
    gt = PR.pairing_product(ps, qs)
    ok = PR.is_one(gt)
    pk_finite = ~fp.is_zero(pk_jac[..., 2, :])
    sig_finite = ~T.fp2_is_zero(sig_aff[..., 1, :, :])
    return ok & pk_finite & sig_finite


def agg_verify(pk_affs, bitmap, h_aff, agg_sig_aff):
    """The FBFT quorum check: aggregate the bitmap-selected public keys in
    G1 and verify the aggregate signature with ONE pairing product.

    Replaces the reference's hot sequence DecodeSigBitmap -> mask
    aggregate (G1 adds per set bit) -> aggSig.VerifyHash (reference:
    internal/chain/sig.go:37-50 + engine.go:619-642).

    pk_affs: (N, 2, 32) committee pubkeys (affine), bitmap: (N,),
    h_aff / agg_sig_aff: single affine points (2, 2, 32).
    Returns a scalar bool.  The Jacobian aggregate key goes to the
    pairing as it is; one at infinity (no signer, or keys that cancel)
    is rejected.
    """
    jac = _affine_to_jacobian_g1(pk_affs)
    agg_pk = CV.masked_sum(jac, bitmap, CV.FP_OPS)  # (3, 32)
    return verify_jacobian(agg_pk[None], h_aff[None], agg_sig_aff[None])[0]


def agg_verify_batch(pk_affs, bitmaps, h_affs, agg_sig_affs):
    """Batched quorum checks against ONE committee table: B headers,
    each with its own participation bitmap, hashed payload, and
    aggregate signature — the block-replay throughput shape (reference
    call stack SURVEY.md §3.3: Engine.VerifyHeaderSignature per block).

    pk_affs: (N, 2, 32) committee pubkeys; bitmaps: (B, N);
    h_affs / agg_sig_affs: (B, 2, 2, 32).  Returns (B,) bools.

    One compiled program does ALL the masked G1 tree-sums and ALL the
    pairing checks — no host round-trip between aggregation and verify
    (the r2 live path paid one per header).
    """
    jac = _affine_to_jacobian_g1(pk_affs)  # (N, 3, 32)
    agg = jax.vmap(lambda bm: CV.masked_sum(jac, bm, CV.FP_OPS))(bitmaps)
    return verify_jacobian(agg, h_affs, agg_sig_affs)  # agg (B, 3, 32)


def aggregate_sigs(sig_affs, bitmap=None):
    """Sign.Add analog: sum signatures (N, 2, 2, 32) in G2, optionally
    bitmap-masked; returns a Jacobian point (3, 2, 32)."""
    n = sig_affs.shape[0]
    jac = _affine_to_jacobian_g2(sig_affs)
    if bitmap is None:
        bitmap = jnp.ones((n,), dtype=jnp.int32)
    return CV.masked_sum(jac, bitmap, CV.FP2_OPS)


def aggregate_pubkeys(pk_affs, bitmap):
    """Mask.AggregatePublic analog: bitmap-masked G1 sum (Jacobian out)."""
    return CV.masked_sum(_affine_to_jacobian_g1(pk_affs), bitmap, CV.FP_OPS)


def _affine_to_jacobian_g1(aff):
    x = aff[..., 0, :]
    y = aff[..., 1, :]
    finite = ~(fp.is_zero(x) & fp.is_zero(y))
    one = jnp.broadcast_to(fp.ONE_MONT, x.shape)
    z = jnp.where(finite[..., None], one, jnp.zeros_like(one))
    return jnp.stack([x, y, z], axis=-2)


def _affine_to_jacobian_g2(aff):
    x = aff[..., 0, :, :]
    y = aff[..., 1, :, :]
    finite = ~(T.fp2_is_zero(x) & T.fp2_is_zero(y))
    one = T.fp2_one(x.shape[:-2])
    z = jnp.where(finite[..., None, None], one, jnp.zeros_like(one))
    return jnp.stack([x, y, z], axis=-3)
