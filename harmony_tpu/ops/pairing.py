"""Batched optimal ate pairing on TPU: Miller loop + final exponentiation.

This is the op the whole framework exists for: the reference burns one
pairing check per FBFT vote (reference: consensus/leader.go:173) and per
block replay (reference: internal/chain/engine.go:640) inside herumi's C++
library; here it is a batched, jittable JAX program.

Algorithm (bit-for-bit the bigint twin in ref/pairing.py
miller_loop_projective, which the tests pin against the affine ground
truth):

- Miller loop over |x|'s static square-and-multiply schedule: one
  outer lax.scan over its 6 segments, each a fori_loop of double-steps
  plus one masked add-step (see miller_loop).
- Twist-Jacobian line construction with denominator elimination; lines
  live in the sparse Fp12 basis {v^2, w, w v}.  P itself is Jacobian:
  every line is scaled by Z_P^3, which lies in Fp* and which the final
  exponentiation sends to 1, so no inversion precedes the loop.
- Final exponentiation: easy part via conjugate / inverse / Frobenius^2;
  hard part by the x-addition chain with cyclotomic squarings (see
  final_exponentiation).

Batching: points are batched over leading axes; products of pairings
(the aggregate-verify shape) share one final exponentiation.
"""

import jax
import jax.numpy as jnp

from . import _constants as C
from . import fp
from . import towers as T

# graftlint: kernel-module dtype=int32

# graftlint: kernel bounds=(any) -> (<64, bit); domain=any; trusted
def _schedule(e: int):
    """Square-and-multiply schedule of a STATIC exponent as two equal-
    length arrays: per segment, the number of squarings, then whether a
    multiply follows.  BLS |x| has hamming weight 6, so the schedule is
    6 segments — the loops pay 63 squarings + 5 multiplies instead of
    the 63 multiply-and-select steps a uniform bit scan costs.

    Compiled shape: ONE outer lax.scan over segments whose body runs a
    dynamic-length lax.fori_loop of squarings plus one (masked)
    multiply — every loop body compiles exactly once.  (The fully
    unrolled variant of this schedule compiled 5-20x slower: dozens of
    inlined Fp12 multiplies explode the top-level XLA graph.)
    """
    bits = bin(e)[2:]
    runs, zeros = [], 0
    for ch in bits[1:]:
        if ch == "0":
            zeros += 1
        else:
            runs.append(zeros + 1)
            zeros = 0
    n_sqr = list(runs)
    do_mul = [1] * len(runs)
    if zeros:
        n_sqr.append(zeros)
        do_mul.append(0)
    return (
        jnp.asarray(n_sqr, dtype=jnp.int32),
        jnp.asarray(do_mul, dtype=jnp.int32),
    )


_ABS_X = -C.BLS_X  # 0xd201000000010000
_X_SCHED = _schedule(_ABS_X)
_XM1_SCHED = _schedule(_ABS_X + 1)  # |x - 1| = |x| + 1 (x < 0)


def _fp2_scale_fp(a, s):
    """Multiply an Fp2 element (..., 2, 32) by an Fp scalar (..., 32)."""
    return fp.mont_mul(a, s[..., None, :])


def _small(a, k):
    """Multiply by a tiny integer constant via doubling chains."""
    if k == 2:
        return fp.add(a, a)
    if k == 3:
        return fp.add(fp.add(a, a), a)
    if k == 8:
        t2 = fp.add(a, a)
        t4 = fp.add(t2, t2)
        return fp.add(t4, t4)
    raise ValueError(k)


def _sparse_line_to_fp12(c_v2, c_w, c_wv):
    """Assemble c_v2*v^2 + c_w*w + c_wv*(w v) into a dense Fp12 tensor."""
    z = jnp.zeros_like(c_v2)
    c0 = jnp.stack([z, z, c_v2], axis=-3)  # coefficients of 1, v, v^2
    c1 = jnp.stack([c_w, c_wv, z], axis=-3)  # w, w v, w v^2
    return jnp.stack([c0, c1], axis=-4)


def _dbl_step(x, y, z, p_lin):
    """Twist-Jacobian doubling + tangent line at P, the line scaled by
    Z_P^3 (p_lin = stacked (Y_P, 3 X_P Z_P, Z_P^3), see miller_loop)."""
    sq = T.fp2_sqr(jnp.stack([x, y, z]))
    xsq, ysq, zsq = sq[0], sq[1], sq[2]
    m = T.fp2_mul(jnp.stack([zsq, xsq]), jnp.stack([z, x]))
    z3p, x3p = m[0], m[1]  # Z^3, X^3
    m = T.fp2_mul(
        jnp.stack([T.fp2_add(y, y), xsq]),
        jnp.stack([z3p, zsq]),
    )
    lin = _fp2_scale_fp(
        jnp.stack([m[0], m[1], fp.sub(_small(x3p, 3), _small(ysq, 2))]),
        p_lin,
    )
    c_v2 = lin[0]  # 2 Y Z^3 * Y_P
    c_wv = fp.neg(lin[1])  # -3 X^2 Z^2 * X_P Z_P
    c_w = lin[2]  # (3 X^3 - 2 Y^2) * Z_P^3
    # dbl-2009-l
    b = ysq
    csq = T.fp2_sqr(jnp.stack([b, T.fp2_add(x, b)]))
    c, t = csq[0], csq[1]
    d = _small(fp.sub(fp.sub(t, xsq), c), 2)
    e = _small(xsq, 3)
    m = T.fp2_mul(jnp.stack([e, y]), jnp.stack([e, z]))
    f_, yz = m[0], m[1]
    x3 = fp.sub(f_, _small(d, 2))
    y3 = fp.sub(T.fp2_mul(e, fp.sub(d, x3)), _small(c, 8))
    z3 = _small(yz, 2)
    return (x3, y3, z3), (c_v2, c_w, c_wv)


def _add_step(x, y, z, xq, yq, q_z3, p_lin):
    """Twist-Jacobian mixed addition of the affine base Q + chord line,
    the line scaled by Z_P^3 (q_z3 = stacked (xq, yq) Z_P^3, p_lin =
    stacked (Y_P, X_P Z_P))."""
    zsq = T.fp2_sqr(z)
    z3p = T.fp2_mul(zsq, z)
    m = T.fp2_mul(jnp.stack([yq, xq]), jnp.stack([z3p, zsq]))
    s2, u2 = m[0], m[1]
    num = fp.sub(y, s2)  # Y - yq Z^3
    h = fp.sub(u2, x)
    den = T.fp2_mul(z, fp.neg(h))  # Z (X - xq Z^2) = -Z*H
    lin = _fp2_scale_fp(jnp.stack([den, num]), p_lin)
    c_v2 = lin[0]  # den * Y_P
    c_wv = fp.neg(lin[1])  # -num * X_P Z_P
    m = T.fp2_mul(q_z3, jnp.stack([num, den]))
    c_w = fp.sub(m[0], m[1])  # (xq num - yq den) * Z_P^3
    # madd-2007-bl (Z2 = 1)
    r = _small(fp.sub(s2, y), 2)
    sq = T.fp2_sqr(jnp.stack([_small(h, 2), r, T.fp2_add(z, h)]))
    i, rsq, zh = sq[0], sq[1], sq[2]
    m = T.fp2_mul(jnp.stack([h, x]), jnp.stack([i, i]))
    j, v = m[0], m[1]
    x3 = fp.sub(fp.sub(rsq, j), _small(v, 2))
    m = T.fp2_mul(jnp.stack([r, y]), jnp.stack([fp.sub(v, x3), j]))
    y3 = fp.sub(m[0], _small(m[1], 2))
    z3 = fp.sub(fp.sub(zh, zsq), T.fp2_sqr(h))
    return (x3, y3, z3), (c_v2, c_w, c_wv)


# graftlint: kernel bounds=(limb, limb) -> limb; domain=(mont, mont) -> mont
def miller_loop(p_jac, q_aff):
    """f_{|x|,Q}(P), conjugated for x < 0, up to a factor in Fp* that
    the final exponentiation sends to 1.  p_jac (..., 3, 32): P in
    Jacobian coordinates (X, Y, Z) over Fp, Z != 0 (affine callers pass
    Z = 1); q_aff (..., 2, 2, 32): finite affine Q over Fp2.

    P enters the lines only through xp = X/Z^2 and yp = Y/Z^3, so every
    line is scaled by Z^3: its coefficients take Y, X Z and Z^3 where
    they took yp, xp and 1 — no inversion of Z.

    The loop follows |x|'s STATIC bit schedule (_schedule): an outer
    scan over the 6 segments; each runs its double-steps in a dynamic-
    length fori_loop and applies one masked add-step.  The uniform
    per-bit variant paid a full add-step + dense Fp12 multiply on all
    63 iterations for the 5 that use them."""
    xp = p_jac[..., 0, :]
    yp = p_jac[..., 1, :]
    zp = p_jac[..., 2, :]
    xq = q_aff[..., 0, :, :]
    yq = q_aff[..., 1, :, :]
    m = fp.mont_mul(jnp.stack([xp, zp]), zp)
    xz, zz = m[0], m[1]  # X Z, Z^2
    z3 = fp.mont_mul(zz, zp)
    q_z3 = _fp2_scale_fp(jnp.stack([xq, yq]), z3)  # (xq, yq) Z^3
    dbl_lin = jnp.stack([yp, _small(xz, 3), z3])
    add_lin = jnp.stack([yp, xz])
    batch = xp.shape[:-1]
    one2 = T.fp2_one(batch)

    def dbl_once(_, carry):
        f, x, y, z = carry
        (x, y, z), (c_v2, c_w, c_wv) = _dbl_step(x, y, z, dbl_lin)
        f = T.fp12_mul(T.fp12_sqr(f), _sparse_line_to_fp12(c_v2, c_w, c_wv))
        return (f, x, y, z)

    def segment(carry, seg):
        n, do_add = seg
        carry = jax.lax.fori_loop(0, n, dbl_once, carry)
        f, x, y, z = carry
        (xa, ya, za), (a_v2, a_w, a_wv) = _add_step(
            x, y, z, xq, yq, q_z3, add_lin
        )
        fa = T.fp12_mul(f, _sparse_line_to_fp12(a_v2, a_w, a_wv))
        take = do_add == 1
        f = jnp.where(take, fa, f)
        x = jnp.where(take, xa, x)
        y = jnp.where(take, ya, y)
        z = jnp.where(take, za, z)
        return (f, x, y, z), None

    f0 = T.fp12_one(batch)
    carry, _ = jax.lax.scan(segment, (f0, xq, yq, one2), _X_SCHED)
    return T.fp12_conj(carry[0])


# graftlint: kernel bounds=(limb, any) -> limb; domain=(mont, any) -> mont
def _cyclo_pow_abs(a, sched):
    """a^e for a STATIC positive exponent given as its square-and-
    multiply schedule, with Granger-Scott cyclotomic squarings — valid
    only for unitary a (everything after the easy part).  63 squarings
    at half cost + 5 multiplies replace the 64 select-masked generic
    squaring+multiply steps; one outer scan + one fori_loop keep the
    compiled graph the size of two loop bodies."""

    def sqr_once(_, acc):
        return T.fp12_cyclo_sqr(acc)

    def segment(acc, seg):
        n, do_mul = seg
        acc = jax.lax.fori_loop(0, n, sqr_once, acc)
        return T.fp12_select(do_mul == 1, T.fp12_mul(acc, a), acc), None

    acc, _ = jax.lax.scan(segment, a, sched)
    return acc


# graftlint: kernel bounds=(limb) -> limb; domain=(mont) -> mont
def final_exponentiation(f):
    """f^(3 (p^12-1)/r): easy part exactly, hard part by the x-chain.

    Hard part uses 3 lambda = (x-1)^2 (x+p)(x^2+p^2-1) + 3 (identity
    verified against bigints in the tests; the cubed pairing is the
    framework's canonical pairing — see ref/pairing.py).  Four 64-bit
    x-powers replace a 1509-bit generic exponentiation: ~7x less work.
    Inversions after the easy part are conjugations (unitary elements),
    squarings are cyclotomic, and the x-powers follow |x|'s static bit
    schedule (_segments).
    """
    f1 = T.fp12_mul(T.fp12_conj(f), T.fp12_inv(f))  # ^(p^6 - 1)
    f2 = T.fp12_mul(T.fp12_frobenius(f1, 2), f1)  # ^(p^2 + 1), unitary now
    m1 = T.fp12_conj(_cyclo_pow_abs(f2, _XM1_SCHED))  # f2^(x-1)
    m2 = T.fp12_conj(_cyclo_pow_abs(m1, _XM1_SCHED))  # ^(x-1)^2
    m3 = T.fp12_mul(
        T.fp12_conj(_cyclo_pow_abs(m2, _X_SCHED)),  # m2^x
        T.fp12_frobenius(m2, 1),  # m2^p
    )
    m3_x2 = _cyclo_pow_abs(
        _cyclo_pow_abs(m3, _X_SCHED), _X_SCHED
    )  # m3^(x^2) — two |x| powers; the two conjugations cancel
    m4 = T.fp12_mul(
        T.fp12_mul(m3_x2, T.fp12_frobenius(m3, 2)),
        T.fp12_conj(m3),  # m3^-1 (unitary)
    )
    return T.fp12_mul(m4, T.fp12_mul(T.fp12_sqr(f2), f2))  # * f2^3


# graftlint: kernel bounds=(limb, limb) -> limb; domain=(mont, mont) -> mont
def pairing(p_jac, q_aff):
    """Batched full pairing e(P, Q); P Jacobian, Q affine (miller_loop)."""
    return final_exponentiation(miller_loop(p_jac, q_aff))


# graftlint: kernel bounds=(limb, limb) -> limb; domain=(mont, mont) -> mont
def pairing_product(p_jac, q_aff):
    """prod_k e(P_k, Q_k) over the FIRST axis, one shared final
    exponentiation — the aggregate-verify shape (reference:
    internal/chain/engine.go:619-642 does exactly two such pairings per
    block; batch replay does many).  P Jacobian, Q affine."""
    fs = miller_loop(p_jac, q_aff)  # (K, ..., fp12)
    return final_exponentiation(fp12_tree_reduce(fs))


# graftlint: kernel bounds=(limb) -> limb; domain=(mont) -> mont
def fp12_tree_reduce(fs):
    """Log-depth product of Fp12 elements over the first axis."""
    while fs.shape[0] > 1:
        k = fs.shape[0]
        half = k // 2
        merged = T.fp12_mul(fs[:half], fs[half : 2 * half])
        fs = (
            jnp.concatenate([merged, fs[2 * half :]], axis=0)
            if k % 2
            else merged
        )
    return fs[0]


# graftlint: kernel bounds=(limb) -> bit; domain=(any) -> neutral
def is_one(gt):
    """Boolean mask: GT element == 1 (canonical Montgomery digits)."""
    one = T.fp12_one(gt.shape[:-4])
    return jnp.all(gt == one, axis=(-1, -2, -3, -4))
