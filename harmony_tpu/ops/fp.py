"""Batched 381-bit Fp arithmetic in JAX: the hot substrate of the framework.

Replaces mcl's x86 Montgomery assembly (reference: herumi mcl via
go.mod:27) with a TPU-shaped design:

- 32 limbs x 12 bits in int32 (see ops/limbs.py): every partial product
  stays < 2^24 and every lazy limb < 2^31 (graftlint GL09 proves a
  limb convolution <= 32 * 4095^2 ~ 5.37e8 and the reduction's sum
  <= 1.078e9), so nothing needs the 64-bit multiplies TPUs lack.
- Montgomery multiplication has no sequential loop: the product a*b is
  one limb convolution (32 shifted copies of b, stacked, times the limbs
  of a, summed over the stack), and the reduction is one-shot with
  R = 2^384: q = (a*b mod R)(-p^-1) mod R by a second, truncated
  convolution, then (a*b + q*p) / R, which is the high half of a third.
  Its loop-free dataflow lets XLA fuse each stage into a few wide
  kernels, with no device trip per limb.
- Inside a product the limbs lie along axis 0 and the operands' rows
  along the vector lanes, so taking one limb of every row is a cheap
  slice.
- Carry/borrow propagation is O(log n) via carry-lookahead
  (generate/propagate pairs, Kogge-Stone), never an n-step ripple.

All functions are shape-polymorphic over leading batch axes; tower fields
(ops/towers.py) exploit this by stacking their independent sub-products
into one call (54 Fp muls per Fp12 mul in a single mont_mul).
"""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from . import _constants as C
from .limbs import LIMB_BITS, LIMB_MASK, N_LIMBS, int_to_limbs

# graftlint: kernel-module dtype=int32

P_LIMBS = jnp.asarray(int_to_limbs(C.P_INT))  # graftlint: kernel domain=neutral
ONE_MONT = jnp.asarray(np.array(C.ONE_MONT, dtype=np.int32))  # graftlint: kernel domain=mont
R2 = jnp.asarray(np.array(C.R2_LIMBS, dtype=np.int32))  # graftlint: kernel domain=r2
ZERO = jnp.zeros(N_LIMBS, dtype=jnp.int32)
_ONE_RAW = jnp.asarray(int_to_limbs(1))  # graftlint: kernel domain=std

# limb-major columns for mont_mul's convolutions: -p^-1 mod R, and p
# padded to the 64 limbs of a double-width product
_P_INV_NEG_COL = jnp.asarray(C.P_INV_NEG_LIMBS, dtype=jnp.int32).reshape(N_LIMBS, 1)  # graftlint: kernel domain=neutral
_P_COL64 = jnp.pad(P_LIMBS, (0, N_LIMBS)).reshape(2 * N_LIMBS, 1)  # graftlint: kernel domain=neutral

# mont_mul's operands as (limbs, rows) with the rows along the vector
# lanes.  XLA left to itself keeps the rows on the sublanes of the
# callers' (..., rows, 32) arrays and gathers each limb of every row
# across the lanes: on the v5e a stacked product of 54 rows, shaped
# (3, 6, 3, 1, 32), took 14.7 us that way and 3.7 us with this layout
# (measured with the convolution as 32 separate multiply-adds).
_LIMB_MAJOR = Layout(major_to_minor=(0, 1))
# Rows are padded to whole sublane tiles: a one-row product took 147 us
# on the v5e as a (32, 1) array and 4.4 us padded to (32, 8) (same form).
_ROW_TILE = 8

# exponent bit arrays (MSB first) for fixed-exponent powering
_P_MINUS_2_BITS = jnp.asarray(
    [int(b) for b in bin(C.P_INT - 2)[2:]], dtype=jnp.int32
)


def _shift_in_zeros(x, d):
    """x shifted up by d along the last axis, zeros shifted in at the front."""
    pad = [(0, 0)] * (x.ndim - 1) + [(d, 0)]
    return jnp.pad(x, pad)[..., :-d]


def _lookahead(gen, prop):
    """Exclusive prefix carries along the last axis from per-limb
    (generate, propagate) descriptors — manual Kogge-Stone.

    Hand-rolled instead of jax.lax.associative_scan: the flat pad/slice
    pattern CSEs across the hundreds of instances a pairing emits, where
    associative_scan's recursive lowering cost ~0.4 s of XLA compile time
    PER INSTANCE (measured: 4 chained adds compiled 10x faster this way).
    """
    g, p = gen, prop
    for k in range((gen.shape[-1] - 1).bit_length()):  # d = 1, 2, 4, ...
        d = 1 << k
        g = g | (p & _shift_in_zeros(g, d))
        p = p & _shift_in_zeros(p, d)
    return _shift_in_zeros(g, 1)


# graftlint: kernel bounds=(<2**13) -> limb; domain=(same) -> same
def resolve_carries(s):
    """Exact digit normalization for limbs in [0, 2^13 - 1]: one
    carry-lookahead pass (carries are binary in this range)."""
    gen = s >> LIMB_BITS
    prop = jnp.where((s & LIMB_MASK) == LIMB_MASK, 1, 0).astype(s.dtype)
    carry_in = _lookahead(gen, prop)
    return (s + carry_in) & LIMB_MASK


# graftlint: kernel bounds=(<2**31) -> limb; domain=(same) -> same
def normalize(t):
    """Exact digits from lazy nonneg limbs < 2^31 (the value must fit
    the limbs: < 2^384 for 32, < 2^768 for mont_mul's 64).

    Three value-halving rounds shrink carries to binary, then one
    lookahead pass finishes exactly.
    """
    for _ in range(3):
        q = t >> LIMB_BITS
        rem = t & LIMB_MASK
        t = rem + jnp.concatenate(
            [jnp.zeros_like(q[..., :1]), q[..., :-1]], axis=-1
        )
    return resolve_carries(t)


# graftlint: kernel bounds=(limb, limb) -> (limb, bit); domain=(same, same) -> (same, neutral)
def _sub_exact(x, y):
    """(x - y) as exact digits plus the final borrow (1 iff x < y).

    x, y must be canonical digit arrays.
    """
    d = x - y
    gen = jnp.where(d < 0, 1, 0).astype(d.dtype)
    prop = jnp.where(d == 0, 1, 0).astype(d.dtype)
    borrow_in = _lookahead(gen, prop)
    out = (d - borrow_in) & LIMB_MASK
    last = d[..., -1] - borrow_in[..., -1]
    borrow_out = jnp.where(last < 0, 1, 0).astype(d.dtype)
    return out, borrow_out


# graftlint: kernel bounds=(limb) -> limb; domain=(same) -> same
def cond_sub_p(a):
    """Map canonical digits with value in [0, 2p) to [0, p)."""
    diff, borrow = _sub_exact(a, P_LIMBS)
    return jnp.where(borrow[..., None] == 1, a, diff)


# graftlint: kernel bounds=(limb, limb) -> limb; domain=(same, same) -> same
def add(a, b):
    """Canonical modular addition."""
    return cond_sub_p(resolve_carries(a + b))


# graftlint: kernel bounds=(limb) -> limb; domain=(same) -> same
def neg(a):
    """Canonical modular negation (p - a, with -0 = 0)."""
    diff, _ = _sub_exact(P_LIMBS, a)
    return cond_sub_p(diff)


# graftlint: kernel bounds=(limb, limb) -> limb; domain=(same, same) -> same
def sub(a, b):
    """Canonical modular subtraction."""
    return add(a, neg(b))


# mont_mul backend selection (VERDICT r3 #2): "scan", the default, is
# the jnp product below (the name is the switch's value, kept as it
# is); "pallas" routes every Fp product in the framework — towers,
# curve, Miller loop, final exponentiation — through the VMEM-resident
# Pallas kernel (ops/fp_pallas.py).  "pallas-interpret" runs the same
# kernel under the Pallas interpreter for CPU parity tests
# (tests/test_fp_backend.py).
_BACKEND = os.environ.get("FP_BACKEND", "scan")


def set_backend(name: str):
    """Select the Fp multiply backend: scan | pallas | pallas-interpret.

    Takes effect at TRACE time — callers must not mix backends inside
    one jitted program (jax caches traces per python callable, and the
    backend is read when tracing)."""
    global _BACKEND
    if name not in ("scan", "pallas", "pallas-interpret"):
        raise ValueError(f"unknown fp backend {name!r}")
    _BACKEND = name


def get_backend() -> str:
    return _BACKEND


# graftlint: kernel bounds=(limb) -> limb; domain=(same) -> same
def _limb_major(x):
    """(..., 32) digits as a (32, rows) array, rows padded with zeros to
    whole sublane tiles and laid out along the lanes."""
    x = x.reshape(-1, N_LIMBS)
    x = jnp.pad(x, ((0, -x.shape[0] % _ROW_TILE), (0, 0)))
    return with_layout_constraint(x.T, _LIMB_MAJOR)


# graftlint: kernel bounds=(<=4128, limb) -> <=540933120; domain=(any, any) -> any
def _conv(a, b):
    """Lazy limbs of a * b, limb-major (32, rows) by (n, rows or 1),
    truncated to n limbs: out[k] = sum over i of a[i] * b[k - i].
    The 32 copies of b shifted up by i limbs are stacked, so the whole
    sum is one broadcast multiply and one reduction over the stack; each
    sum is at most 32 * 4128 * 4095."""
    n = b.shape[0]
    b = jnp.pad(b, ((N_LIMBS, 0), (0, 0)))
    shifted = jnp.stack([b[N_LIMBS - i:N_LIMBS - i + n] for i in range(N_LIMBS)])
    return (a[:, None] * shifted).sum(axis=0)


# graftlint: kernel bounds=(<=540933120) -> <=4128; domain=(same) -> same
def _two_carry_rounds(t):
    """Two carry rounds on limb-major lazy limbs: each limb keeps its low
    12 bits and gains the carry of the limb below.  The top limb's carry
    is dropped, so the value is kept modulo beta^limbs."""
    for _ in range(2):
        t = (t & LIMB_MASK) + jnp.pad(t >> LIMB_BITS, ((1, 0), (0, 0)))[:-1]
    return t


# graftlint: kernel bounds=(limb, limb) -> limb; domain=mul
def mont_mul(a, b):
    """Montgomery product (a b R^-1 mod p) of canonical-digit operands,
    R = 2^384, in one reduction.

    T = a b as 64 lazy limbs.  q = (T mod R)(-p^-1) mod R makes T + q p
    divisible by R, so (T + q p) / R is the high half of its digits,
    and is below 2p; one conditional subtract canonicalizes.  q needs
    only value mod R: two carry rounds bring T's low half (limbs
    <= 5.37e8) and then q (a truncated convolution) to limbs <= 4128,
    small enough to multiply again within int32, with q < 1.01 R; so
    (T + q p) / R < p^2 / R + 1.01 p < 1.12 p.

    Dispatches on the module backend (see set_backend).
    """
    if _BACKEND != "scan":
        from . import fp_pallas

        return fp_pallas.mont_mul_pallas(
            a, b, interpret=_BACKEND == "pallas-interpret"
        )
    return _mont_mul_jnp(*jnp.broadcast_arrays(a, b))


# Traced once per operand shape: a pairing program multiplies at a few
# shapes but at a hundred-odd sites, and re-tracing the product's ~400
# operations at each would dominate tracing and lowering the program.
@jax.jit
# graftlint: kernel bounds=(limb, limb) -> limb; domain=mul
def _mont_mul_jnp(a, b):
    shape = a.shape
    a, b = _limb_major(a), _limb_major(b)
    t = _conv(a, jnp.pad(b, ((0, N_LIMBS), (0, 0))))
    q = _two_carry_rounds(_conv(_two_carry_rounds(t[:N_LIMBS]), _P_INV_NEG_COL))
    t = normalize((t + _conv(q, _P_COL64)).T)
    rows = math.prod(shape[:-1])
    return cond_sub_p(t[:rows, N_LIMBS:]).reshape(shape)


# graftlint: kernel bounds=(limb) -> limb; domain=(mont) -> mont
def sqr(a):
    return mont_mul(a, a)


# graftlint: kernel bounds=(limb) -> limb; domain=(std) -> mont
def to_mont(a):
    """Enter the Montgomery domain: a -> a R mod p."""
    return mont_mul(a, R2)


# graftlint: kernel bounds=(limb) -> limb; domain=(mont) -> std
def from_mont(a):
    """Leave the Montgomery domain: a R -> a."""
    return mont_mul(a, _ONE_RAW)


# graftlint: kernel bounds=(limb, bit) -> limb; domain=(mont, any) -> mont
def pow_fixed(a, exponent_bits):
    """a^e in the Montgomery domain, e given as a static MSB-first bit
    array; used for inversion and sqrt-style fixed exponents."""
    bits = jnp.asarray(exponent_bits, dtype=jnp.int32)

    def step(acc, bit):
        acc = mont_mul(acc, acc)
        with_mul = mont_mul(acc, a)
        acc = jnp.where(bit == 1, with_mul, acc)
        return acc, None

    one = jnp.broadcast_to(ONE_MONT, a.shape)
    acc, _ = jax.lax.scan(step, one, bits)
    return acc


# graftlint: kernel bounds=(limb) -> limb; domain=(mont) -> mont
def inv(a):
    """Modular inverse via Fermat: a^(p-2).  inv(0) = 0 (callers guard)."""
    return pow_fixed(a, _P_MINUS_2_BITS)


# graftlint: kernel bounds=(limb) -> bit; domain=(any) -> neutral
def is_zero(a):
    """Boolean (...,) mask: element == 0 (canonical digits assumed)."""
    return jnp.all(a == 0, axis=-1)


# graftlint: kernel bounds=(any, limb, limb) -> limb; domain=(any, same, same) -> same
def select(mask, x, y):
    """Branchless per-element select; mask shape (...,), operands (..., 32)."""
    return jnp.where(mask[..., None], x, y)
