"""Bigint-backed twins of the jitted BLS kernels (ops/bls.py).

Same signatures, same padded array layouts, REAL verify decisions —
but the innermost pairing runs on the host crypto path (native C++
when loaded, bigint otherwise) instead of XLA.  Two consumers:

* ``HARMONY_KERNEL_TWIN=1`` swaps these in behind device.py's kernel
  switch, so a LIVE node can exercise every device-path layer —
  CommitteeTable padding, bitmap routing, counters, batch chunking —
  on a box where executing the pairing through XLA:CPU is measured in
  minutes.  The kernel math itself is covered by
  the ops parity tier; this preserves the layer split of
  tests/test_device_path.py for live runs (VERDICT r4 #3).
* tests, as hermetic stand-ins with call accounting.

Wrong padding, table layout, or result slicing fails loudly — the
twins convert the exact arrays the kernels would receive.
"""

from __future__ import annotations

import numpy as np

from ..ref import bls as RB
from ..ref.curve import g1
from . import interop as I

CALLS = {"verify": 0, "agg_verify": 0, "agg_verify_batch": 0}


def _aff_g1(arr):
    return (I.arr_to_fp(arr[0]), I.arr_to_fp(arr[1]))


def _aff_g2(arr):
    return (I.arr_to_fp2(arr[0]), I.arr_to_fp2(arr[1]))


def _masked_agg(tbl: np.ndarray, bits: np.ndarray):
    agg = None
    pts = []
    for i, bit in enumerate(np.asarray(bits)):
        if bit:
            pts.append(_aff_g1(np.asarray(tbl)[i]))
    agg = RB.aggregate_pubkeys(pts) if pts else None
    return agg


def agg_verify(tbl, bits, h_arr, sig_arr):
    """Twin of ops/bls.agg_verify: one masked quorum check."""
    CALLS["agg_verify"] += 1
    agg = _masked_agg(np.asarray(tbl), np.asarray(bits))
    if agg is None:
        return np.asarray(False)
    ok = RB.verify_hashed(
        agg, _aff_g2(np.asarray(h_arr)), _aff_g2(np.asarray(sig_arr))
    )
    return np.asarray(bool(ok))


def agg_verify_batch(tbl, bitmaps, h_arrs, sig_arrs):
    """Twin of ops/bls.agg_verify_batch: B masked checks, one table."""
    CALLS["agg_verify_batch"] += 1
    tbl = np.asarray(tbl)
    out = []
    for bits, h, s in zip(np.asarray(bitmaps), np.asarray(h_arrs),
                          np.asarray(sig_arrs)):
        agg = _masked_agg(tbl, bits)
        if agg is None:
            out.append(False)
            continue
        out.append(bool(RB.verify_hashed(agg, _aff_g2(h), _aff_g2(s))))
    return np.asarray(out)


def verify(pk_arrs, h_arrs, sig_arrs):
    """Twin of ops/bls.verify: lane-wise single checks."""
    CALLS["verify"] += 1
    out = []
    for pk, h, s in zip(np.asarray(pk_arrs), np.asarray(h_arrs),
                        np.asarray(sig_arrs)):
        pk_pt = _aff_g1(pk)
        if pk_pt == (0, 0):
            out.append(False)
            continue
        out.append(bool(RB.verify_hashed(pk_pt, _aff_g2(h), _aff_g2(s))))
    return np.asarray(out)
