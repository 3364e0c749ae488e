"""Structure of the quorum-check program: one Fermat inversion.

The aggregate key reaches the Miller loop in Jacobian coordinates, so
the only field inversion left in ``ops.bls.agg_verify`` is the one in
the final exponentiation's easy part (``fp12_inv``).  An inversion is
``fp.pow_fixed`` over the bits of p - 2: one scan of that length, whose
trips run one after another on the device whatever the batch.
"""

import jax
import jax.numpy as jnp

from harmony_tpu.ops import _constants as C
from harmony_tpu.ops import bls as OB

INV_SCAN = (C.P_INT - 2).bit_length()  # 381 trips of pow_fixed


def _scan_lengths(jaxpr):
    """Lengths of every scan in a jaxpr, nested bodies included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["length"])
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out += _scan_lengths(inner)
    return out


def test_agg_verify_runs_one_inversion():
    n = 8
    closed = jax.make_jaxpr(OB.agg_verify)(
        jnp.zeros((n, 2, 32), jnp.int32),
        jnp.zeros((n,), jnp.int32),
        jnp.zeros((2, 2, 32), jnp.int32),
        jnp.zeros((2, 2, 32), jnp.int32),
    )
    lengths = _scan_lengths(closed.jaxpr)
    assert INV_SCAN == 381
    assert lengths.count(INV_SCAN) == 1, sorted(set(lengths))
