"""Batched Jacobian group law on G1 (over Fp) and G2 (over the Fp2 twist).

TPU replacement for herumi's G1/G2 ops crossing the reference's cgo
boundary: PublicKey.Add/Sub for mask aggregation (reference:
crypto/bls/mask.go:113-153), Sign.Add for vote aggregation (reference:
consensus/quorum/quorum.go:164-196), and the scalar multiplications inside
SignHash / keygen / cofactor clearing.

Design:
- Jacobian coordinates (X, Y, Z), infinity encoded as Z = 0 — the group
  law is branchless: both the add and double results are computed and the
  special cases (either operand at infinity, P + P, P + (-P)) are fixed up
  with vectorized selects, so one fused program serves the whole batch.
- a = 0 short-Weierstrass formulas (dbl-2009-l / add-2007-bl structure),
  with independent products stacked into shared mont_mul scans (4 stacked
  calls per double, 6 per add instead of 7/16 sequential muls).
- Generic over the coordinate field via a small op table; G1 and G2 share
  all the code.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import _constants as C
from . import fp
from . import towers as T

# graftlint: kernel-module dtype=int32


class FieldOps:
    """Vectorized field-op table the generic group law is written against."""

    def __init__(self, *, mul, sqr, add, sub, neg, is_zero, select,
                 one, zero, coord_axes):
        self.mul, self.sqr = mul, sqr
        self.add, self.sub, self.neg = add, sub, neg
        self.is_zero, self.select = is_zero, select
        self.one, self.zero = one, zero
        # number of trailing axes of one field element (1 for Fp, 2 for Fp2)
        self.coord_axes = coord_axes

    def dbl_(self, a):
        return self.add(a, a)

    def stack(self, items):
        return jnp.stack(items, axis=0)


FP_OPS = FieldOps(
    mul=fp.mont_mul,
    sqr=fp.sqr,
    add=fp.add,
    sub=fp.sub,
    neg=fp.neg,
    is_zero=fp.is_zero,
    select=fp.select,
    one=lambda shape=(): jnp.broadcast_to(fp.ONE_MONT, (*shape, fp.N_LIMBS)),
    zero=lambda shape=(): jnp.zeros((*shape, fp.N_LIMBS), dtype=jnp.int32),
    coord_axes=1,
)

FP2_OPS = FieldOps(
    mul=T.fp2_mul,
    sqr=T.fp2_sqr,
    add=T.fp2_add,
    sub=T.fp2_sub,
    neg=T.fp2_neg,
    is_zero=T.fp2_is_zero,
    select=T.fp2_select,
    one=T.fp2_one,
    zero=T.fp2_zero,
    coord_axes=2,
)


def _coords(pt, ops):
    """Split a point tensor (..., 3, <field>) into X, Y, Z."""
    axis = -(ops.coord_axes + 1)
    x, y, z = jnp.split(pt, 3, axis=axis)
    return (jnp.squeeze(x, axis), jnp.squeeze(y, axis), jnp.squeeze(z, axis))


def _point(x, y, z, ops):
    return jnp.stack([x, y, z], axis=-(ops.coord_axes + 1))


# graftlint: kernel bounds=(fieldops, any) -> limb; domain=(any, any) -> mont
def infinity(ops, batch_shape=()):
    """Canonical infinity (1, 1, 0)."""
    one = ops.one(batch_shape)
    return _point(one, one, ops.zero(batch_shape), ops)


def _select_point(mask, a, b, ops):
    return jnp.where(
        mask[(...,) + (None,) * (ops.coord_axes + 1)], a, b
    )


# graftlint: kernel bounds=(limb, fieldops) -> limb; domain=(mont, any) -> mont
def dbl(pt, ops):
    """Jacobian doubling, a = 0 (dbl-2009-l).  Handles infinity (Z3 = 0
    follows from Z = 0 automatically)."""
    x, y, z = _coords(pt, ops)
    s1 = ops.sqr(ops.stack([x, y]))
    a, b = s1[0], s1[1]  # X^2, Y^2
    s2 = ops.sqr(ops.stack([b, ops.add(x, b)]))
    c, t = s2[0], s2[1]  # Y^4, (X + Y^2)^2
    d = ops.dbl_(ops.sub(ops.sub(t, a), c))  # 2((X+B)^2 - A - C)
    e = ops.add(ops.dbl_(a), a)  # 3 X^2
    m = ops.mul(ops.stack([e, y]), ops.stack([e, z]))
    f, yz = m[0], m[1]  # E^2, Y Z
    x3 = ops.sub(f, ops.dbl_(d))
    y3_part = ops.mul(e, ops.sub(d, x3))
    c8 = ops.dbl_(ops.dbl_(ops.dbl_(c)))
    y3 = ops.sub(y3_part, c8)
    z3 = ops.dbl_(yz)
    return _point(x3, y3, z3, ops)


# graftlint: kernel bounds=(limb, limb, fieldops) -> limb; domain=(mont, mont, any) -> mont
def add(p1, p2, ops, handle_equal=True):
    """Branchless Jacobian addition (add-2007-bl structure) with select-based
    handling of infinity / equal / opposite inputs.

    ``handle_equal=False`` drops the embedded doubling graph for callers
    that can prove p1 != p2 for finite inputs — the doubling subgraph is
    ~40% of the op's compile and runtime cost.  Double-and-add scalar
    multiplication qualifies up to the standard incomplete-addition
    caveat: an add step sees acc == pt only when the scalar's bit-prefix
    equals (ord(pt)+1)/2 exactly, a 2^-254 event for uniform signing
    scalars and impossible for the fixed cofactor scalars (2*prefix stays
    below the twist group order).
    """
    x1, y1, z1 = _coords(p1, ops)
    x2, y2, z2 = _coords(p2, ops)

    s = ops.sqr(ops.stack([z1, z2]))
    z1z1, z2z2 = s[0], s[1]
    m = ops.mul(
        ops.stack([x1, x2, z2, z1]),
        ops.stack([z2z2, z1z1, z2z2, z1z1]),
    )
    u1, u2, t1, t2 = m[0], m[1], m[2], m[3]
    m = ops.mul(ops.stack([y1, y2]), ops.stack([t1, t2]))
    s1, s2 = m[0], m[1]

    h = ops.sub(u2, u1)
    r = ops.dbl_(ops.sub(s2, s1))
    s = ops.sqr(ops.stack([ops.dbl_(h), r, ops.add(z1, z2)]))
    i, rsq, zz = s[0], s[1], s[2]
    m = ops.mul(ops.stack([h, u1]), ops.stack([i, i]))
    j, v = m[0], m[1]
    x3 = ops.sub(ops.sub(rsq, j), ops.dbl_(v))
    m = ops.mul(
        ops.stack([r, s1, ops.sub(ops.sub(zz, z1z1), z2z2)]),
        ops.stack([ops.sub(v, x3), j, h]),
    )
    y3 = ops.sub(m[0], ops.dbl_(m[1]))
    z3 = m[2]
    added = _point(x3, y3, z3, ops)

    p1_inf = ops.is_zero(z1)
    p2_inf = ops.is_zero(z2)
    both_finite = ~p1_inf & ~p2_inf
    same_x = ops.is_zero(h) & both_finite
    same_y = ops.is_zero(r)

    out = added
    if handle_equal:
        out = _select_point(same_x & same_y, dbl(p1, ops), out, ops)
    out = _select_point(
        same_x & ~same_y, infinity(ops, _batch_shape(p1, ops)), out, ops
    )
    out = _select_point(p1_inf, p2, out, ops)
    out = _select_point(p2_inf & ~p1_inf, p1, out, ops)
    return out


def _batch_shape(pt, ops):
    return pt.shape[: pt.ndim - (ops.coord_axes + 1)]


# graftlint: kernel bounds=(limb, fieldops) -> limb; domain=(mont, any) -> mont
def neg(pt, ops):
    x, y, z = _coords(pt, ops)
    return _point(x, ops.neg(y), z, ops)


# graftlint: kernel bounds=(limb, bit, fieldops) -> limb; domain=(mont, any, any) -> mont
def scalar_mul(pt, bits, ops):
    """Double-and-add over an MSB-first bit tensor.

    ``bits`` is either a static (L,) array (same scalar for the whole
    batch, e.g. cofactor clearing) or (..., L) per-element scalars (e.g.
    signing).  Constant-shape scan; per-element bit selection is
    branchless.
    """
    bits = jnp.asarray(bits, dtype=jnp.int32)
    xs = jnp.moveaxis(bits, -1, 0) if bits.ndim > 1 else bits

    def step(acc, bit):
        acc = dbl(acc, ops)
        # acc = k'*pt with k' != 1 at every add step (see add docstring),
        # so the equal-points doubling fallback is dead weight here.
        with_add = add(acc, pt, ops, handle_equal=False)
        acc = _select_point(bit == 1, with_add, acc, ops)
        return acc, None

    acc0 = infinity(ops, _batch_shape(pt, ops))
    acc, _ = jax.lax.scan(step, acc0, xs)
    return acc


# graftlint: kernel bounds=(limb, any, fieldops) -> limb; domain=(mont, any, any) -> mont
def masked_sum(points, mask, ops):
    """Sum of points[i] where mask[i] == 1, via log-depth tree reduction.

    The TPU analog of the reference's incremental Mask.AggregatePublic
    (reference: crypto/bls/mask.go:113-153) and AggregateVotes
    (reference: consensus/quorum/quorum.go:164-196): instead of serial
    G1/G2 adds per bit flip, one batched reduction over the whole
    committee.  ``points`` has the batch axis FIRST: (N, 3, <field>).
    """
    n = points.shape[0]
    pts = _select_point(
        jnp.asarray(mask, dtype=jnp.int32) == 1,
        points,
        infinity(ops, (n,)),
        ops,
    )
    # pad to a power of two with infinity
    size = 1
    while size < n:
        size *= 2
    if size != n:
        pad = infinity(ops, (size - n,))
        pts = jnp.concatenate([pts, pad], axis=0)
    while size > 1:
        half = size // 2
        pts = add(pts[:half], pts[half:size], ops)
        size = half
    return pts[0]


# --- generators ------------------------------------------------------------

# graftlint: kernel bounds=limb; domain=mont
G1_GEN = jnp.asarray(
    np.stack(
        [
            np.array(C.G1_GEN_MONT[0], dtype=np.int32),
            np.array(C.G1_GEN_MONT[1], dtype=np.int32),
            np.array(C.ONE_MONT, dtype=np.int32),
        ]
    )
)

# graftlint: kernel bounds=limb; domain=mont
G2_GEN = jnp.asarray(
    np.stack(
        [
            np.array(C.G2_GEN_X_MONT, dtype=np.int32),
            np.array(C.G2_GEN_Y_MONT, dtype=np.int32),
            np.stack(
                [np.array(C.ONE_MONT, dtype=np.int32),
                 np.zeros(fp.N_LIMBS, dtype=np.int32)]
            ),
        ]
    )
)
