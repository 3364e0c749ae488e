"""Mesh construction and sharded BLS computations (pjit / shard_map).

The reference has no NCCL/MPI analog — its "distributed backend" is
libp2p gossip between hosts (SURVEY.md §2.5); the intra-node scaling story
for the TPU framework is XLA collectives over ICI, expressed here.
"""

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..ops import bls as OB
from ..ops import curve as CV
from ..ops import pairing as OP

BATCH_AXIS = "batch"


def make_mesh(devices=None, axis=BATCH_AXIS) -> Mesh:
    import numpy as np

    devices = devices if devices is not None else jax.devices()
    return Mesh(np.array(devices), (axis,))


def sharded_verify(mesh: Mesh):
    """Batch-data-parallel verify: inputs sharded over the batch axis.

    Each element is an independent 2-pairing check; XLA partitions the
    whole program with zero collectives.
    """
    spec = NamedSharding(mesh, P(BATCH_AXIS))

    @partial(
        jax.jit,
        in_shardings=(spec, spec, spec),
        out_shardings=spec,
    )
    def fn(pk_aff, h_aff, sig_aff):
        return OB.verify(pk_aff, h_aff, sig_aff)

    return fn


def sharded_masked_sum(mesh: Mesh):
    """Committee-sharded mask aggregation: each device tree-sums its local
    chunk of (pubkey, bit) pairs, partial sums are all_gathered over ICI
    and merged in a log-depth tail on every device (replicated output).

    This is the multi-chip version of Mask.AggregatePublic (reference:
    crypto/bls/mask.go:113-153) for committees too large for one chip.
    """

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(BATCH_AXIS), P(BATCH_AXIS)),
        out_specs=P(),
        # the all_gather + identical merge on every device IS replicated,
        # but the static varying-axes checker cannot infer that
        check_vma=False,
    )
    def fn(pk_jac_chunk, bitmap_chunk):
        local = CV.masked_sum(pk_jac_chunk, bitmap_chunk, CV.FP_OPS)
        partials = jax.lax.all_gather(local, BATCH_AXIS)  # (d, 3, 32)
        total = CV.masked_sum(
            partials,
            jnp.ones(partials.shape[0], dtype=jnp.int32),
            CV.FP_OPS,
        )
        return total

    return fn


def sharded_pairing_product(mesh: Mesh):
    """prod_k e(P_k, Q_k) with the pair axis sharded: local Miller loops
    and local Fp12 products per device, one all_gather, then a replicated
    merge + final exponentiation.  P Jacobian (K, 3, 32), Q affine."""

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(BATCH_AXIS), P(BATCH_AXIS)),
        out_specs=P(),
        check_vma=False,  # replicated by construction (see above)
    )
    def fn(p_chunk, q_chunk):
        fs = OP.miller_loop(p_chunk, q_chunk)
        local = OP.fp12_tree_reduce(fs)
        partials = jax.lax.all_gather(local, BATCH_AXIS)  # (d, fp12)
        return OP.final_exponentiation(OP.fp12_tree_reduce(partials))

    return fn


def sharded_agg_verify(mesh: Mesh):
    """The full multi-chip FBFT quorum check: committee pubkeys + bitmap
    sharded across devices, aggregate built with one all_gather, the
    2-pairing verify replicated (it is latency-bound, not compute-bound,
    at this point)."""
    masked = sharded_masked_sum(mesh)

    @jax.jit
    def fn(pk_jac, bitmap, h_aff, agg_sig_aff):
        agg = masked(pk_jac, bitmap)
        return OB.verify_jacobian(agg[None], h_aff[None],
                                  agg_sig_aff[None])[0]

    return fn
