"""Pallas TPU kernel for batched Montgomery multiplication.

This kernel runs CIOS with the whole accumulator in VMEM/registers and
the loop unrolled, so HBM traffic is reading A, B and writing the
result once per tile.

Layout: limbs live on the SUBLANE axis, batch on the LANE axis —
a (32, 128) int32 tile is exactly one VPU-shaped block (32 sublanes x
128 lanes), so every CIOS step is a broadcast-multiply-accumulate across
the full tile.  The public wrapper transposes from the framework's
(..., 32) limbs-last convention at the boundary.

Used on real TPUs; interpret mode covers CPU tests.  The jnp path
(ops/fp.py mont_mul) is the default backend.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import _constants as C
from .limbs import LIMB_BITS, LIMB_MASK, N_LIMBS, int_to_limbs

# graftlint: kernel-module dtype=int32

_LANES = 128
_P_COL = int_to_limbs(C.P_INT).reshape(N_LIMBS, 1)  # (32, 1) np array
_P_INV_NEG = C.P_INV_NEG


# graftlint: kernel bounds=(limb, limb, limb, limb) -> limb; domain=mul
def _mont_mul_kernel(a_ref, b_ref, p_ref, out_ref):
    """One (32, LANES) tile: full CIOS, unrolled, accumulator in VMEM."""
    a = a_ref[:, :]
    b = b_ref[:, :]
    p_col = p_ref[:, :]
    t = jnp.zeros_like(b)
    for _ in range(N_LIMBS):
        # process digit i of A: thanks to the one-limb shift each step,
        # the current digit is always row 0 of the rolling view of a
        a_i = a[0:1, :]
        a = jnp.concatenate([a[1:, :], jnp.zeros_like(a[0:1, :])], axis=0)
        t = t + a_i * b
        m = ((t[0:1, :] & LIMB_MASK) * _P_INV_NEG) & LIMB_MASK
        t = t + m * p_col
        carry0 = t[0:1, :] >> LIMB_BITS
        t = jnp.concatenate(
            [t[1:2, :] + carry0, t[2:, :], jnp.zeros_like(t[0:1, :])],
            axis=0,
        )
    # normalize: three value rounds then exact binary carry resolution
    for _ in range(3):
        q = t >> LIMB_BITS
        rem = t & LIMB_MASK
        t = rem + jnp.concatenate(
            [jnp.zeros_like(q[0:1, :]), q[:-1, :]], axis=0
        )
    t = _resolve_binary_carries(t)
    # conditional subtract p (value < 2p here); p_col reread for clarity
    d = t - p_ref[:, :]
    borrow = _borrow_out(d)
    out_ref[:, :] = jnp.where(borrow > 0, t, _apply_borrows(d))


def _shift_down_sublanes(x, dist, fill=0):
    pad = jnp.full_like(x[0:dist, :], fill)
    return jnp.concatenate([pad, x[:-dist, :]], axis=0)


def _resolve_binary_carries(s):
    """Kogge-Stone carry lookahead along the sublane (limb) axis for
    limbs <= 2^13 - 1."""
    g = s >> LIMB_BITS
    p = jnp.where((s & LIMB_MASK) == LIMB_MASK, 1, 0).astype(s.dtype)
    for d in (1, 2, 4, 8, 16):
        g = g | (p & _shift_down_sublanes(g, d))
        p = p & _shift_down_sublanes(p, d)
    carry_in = _shift_down_sublanes(g, 1)
    return (s + carry_in) & LIMB_MASK


def _borrow_lookahead(d):
    g = jnp.where(d < 0, 1, 0).astype(d.dtype)
    p = jnp.where(d == 0, 1, 0).astype(d.dtype)
    for dist in (1, 2, 4, 8, 16):
        g = g | (p & _shift_down_sublanes(g, dist))
        p = p & _shift_down_sublanes(p, dist)
    return g  # inclusive: borrow OUT of each prefix


def _borrow_out(d):
    """1 where subtraction underflowed (t < p), per lane: (1, LANES)."""
    return _borrow_lookahead(d)[N_LIMBS - 1 : N_LIMBS, :]


def _apply_borrows(d):
    borrow_in = _shift_down_sublanes(_borrow_lookahead(d), 1)
    return (d - borrow_in) & LIMB_MASK


@functools.partial(jax.jit, static_argnames=("interpret",))
# graftlint: kernel bounds=(limb, limb, any) -> limb; domain=mul
def mont_mul_pallas(a, b, interpret: bool = False):
    """Montgomery product over the framework layout (..., 32).

    Flattens leading axes onto lanes, pads to a LANES multiple, runs the
    tiled kernel, and restores the shape.  interpret=True runs the
    kernel in the Pallas interpreter (CPU tests).
    """
    a, b = jnp.broadcast_arrays(a, b)
    shape = a.shape
    rows = 1
    for s in shape[:-1]:
        rows *= s
    a2 = a.reshape(rows, N_LIMBS).T  # (32, rows): limbs on sublanes
    b2 = b.reshape(rows, N_LIMBS).T
    padded = (rows + _LANES - 1) // _LANES * _LANES
    if padded != rows:
        a2 = jnp.pad(a2, ((0, 0), (0, padded - rows)))
        b2 = jnp.pad(b2, ((0, 0), (0, padded - rows)))
    grid = padded // _LANES
    out = pl.pallas_call(
        _mont_mul_kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((N_LIMBS, _LANES), lambda i: (0, i)),
            pl.BlockSpec((N_LIMBS, _LANES), lambda i: (0, i)),
            pl.BlockSpec((N_LIMBS, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((N_LIMBS, _LANES), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((N_LIMBS, padded), jnp.int32),
        interpret=interpret,
    )(a2, b2, jnp.asarray(_P_COL))
    return out[:, :rows].T.reshape(shape)
