"""CI coverage for the DEVICE verification path (VERDICT r2 weak #3).

Forces ``device.use_device(True)`` so the engine's and FBFT's device
branches — CommitteeTable padding, the fused agg_verify route, the
batched replay grouping, COUNTERS — execute in CI and are
bitwise-compared against the host bigint path.

The innermost jitted kernels (ops/bls.agg_verify + friends) are
swapped for BIGINT-BACKED TWINS here: on this 1-core CI box ANY
execution of the pairing through XLA — jit compile OR eager — costs
8+ minutes (measured 2026-07-29), so the
kernel math is covered by the ops parity tier while THIS module covers
every layer above it: the twins receive exactly the padded device
arrays the real kernels would, convert them back, and make REAL
verify decisions in bigint — wrong padding, bitmap routing, table
layout, or result slicing fails loudly.
"""

import numpy as np
import pytest

from harmony_tpu import bls as B
from harmony_tpu import device as DV
from harmony_tpu.chain.engine import Engine, EpochContext
from harmony_tpu.chain.header import Header
from harmony_tpu.consensus.mask import Mask
from harmony_tpu.consensus.signature import construct_commit_payload
from harmony_tpu.ops import bls as OB
from harmony_tpu.ops import interop as I
from harmony_tpu.ref import bls as RB
from harmony_tpu.ref.curve import g1

N_KEYS = 4

KERNEL_CALLS = {"agg_verify": 0, "agg_verify_batch": 0, "verify": 0}


def _aff_g1(arr):
    return (I.arr_to_fp(arr[0]), I.arr_to_fp(arr[1]))


def _aff_g2(arr):
    return (I.arr_to_fp2(arr[0]), I.arr_to_fp2(arr[1]))


def _twin_agg_verify(pk_affs, bitmap, h_aff, agg_sig_aff):
    """Bigint twin of ops/bls.agg_verify: same signature, same padded
    array layout, decisions from the reference implementation."""
    KERNEL_CALLS["agg_verify"] += 1
    tbl = np.asarray(pk_affs)
    bits = np.asarray(bitmap)
    assert tbl.shape[0] == bits.shape[0], "table/bitmap width mismatch"
    agg = None
    for i, bit in enumerate(bits):
        if bit:
            agg = g1.add(agg, _aff_g1(tbl[i]))
    if agg is None:
        return np.asarray(False)
    h_pt = _aff_g2(np.asarray(h_aff))
    sig_pt = _aff_g2(np.asarray(agg_sig_aff))
    return np.asarray(RB.verify_hashed(agg, h_pt, sig_pt))


def _twin_agg_verify_batch(pk_affs, bitmaps, h_affs, agg_sig_affs):
    KERNEL_CALLS["agg_verify_batch"] += 1
    out = [
        bool(_twin_agg_verify(pk_affs, bm, h, s))
        for bm, h, s in zip(
            np.asarray(bitmaps), np.asarray(h_affs),
            np.asarray(agg_sig_affs),
        )
    ]
    KERNEL_CALLS["agg_verify"] -= len(out)  # inner calls don't count
    return np.asarray(out)


def _twin_verify(pk_affs, h_affs, sig_affs):
    KERNEL_CALLS["verify"] += 1
    out = []
    for pk, h, s in zip(
        np.asarray(pk_affs), np.asarray(h_affs), np.asarray(sig_affs)
    ):
        out.append(RB.verify_hashed(_aff_g1(pk), _aff_g2(h), _aff_g2(s)))
    return np.asarray(out)


@pytest.fixture(scope="module", autouse=True)
def force_device_with_twin_kernels():
    DV.use_device(True)
    saved = (OB.agg_verify, OB.agg_verify_batch, OB.verify)
    OB.agg_verify = _twin_agg_verify
    OB.agg_verify_batch = _twin_agg_verify_batch
    OB.verify = _twin_verify
    yield
    OB.agg_verify, OB.agg_verify_batch, OB.verify = saved
    DV.use_device(None)


@pytest.fixture(scope="module")
def committee():
    keys = [B.PrivateKey.generate(bytes([60 + i])) for i in range(N_KEYS)]
    serialized = [k.pub.bytes for k in keys]
    return keys, serialized


def _provider(serialized):
    def provide(shard_id, epoch):
        return EpochContext(serialized)

    return provide


def _sign_header(header, keys, signer_idx):
    payload = construct_commit_payload(
        header.hash(), header.block_num, header.view_id, True
    )
    sigs = [keys[i].sign_hash(payload) for i in signer_idx]
    agg = B.aggregate_sigs(sigs)
    mask = Mask([k.pub.point for k in keys])
    for i in signer_idx:
        mask.set_bit(i, True)
    return agg.bytes, mask.mask_bytes()


def test_device_enabled_is_forced():
    assert DV.device_enabled()


def test_committee_table_padding():
    keys = [B.PrivateKey.generate(bytes([80 + i])) for i in range(3)]
    tbl = DV.CommitteeTable([k.pub.point for k in keys])
    assert tbl.n == 3 and tbl.size == 8  # padded to the smallest bucket
    bits = tbl.pad_bits([1, 0, 1])
    assert list(bits) == [1, 0, 1, 0, 0, 0, 0, 0]


def test_engine_device_verify_matches_host(committee):
    """The fused device quorum check and the host bigint check must
    agree bitwise on accept AND reject (VERDICT r2 next-steps #3)."""
    keys, serialized = committee
    before = DV.COUNTERS["agg_verify"]
    dev = Engine(_provider(serialized), device=True)
    host = Engine(_provider(serialized), device=False)
    h = Header(shard_id=0, block_num=10, epoch=2, view_id=10)
    cases = []
    sig, bitmap = _sign_header(h, keys, [0, 1, 2, 3])
    cases.append((h, sig, bitmap))
    sig2, bitmap2 = _sign_header(h, keys, [0, 1, 2])
    cases.append((h, sig2, bitmap2))
    # mismatched: 3-signer sig against the full bitmap
    cases.append((h, sig2, bitmap))
    # insufficient quorum (2 of 4)
    sig3, bitmap3 = _sign_header(h, keys, [0, 3])
    cases.append((h, sig3, bitmap3))
    for hdr, s, bm in cases:
        assert dev.verify_header_signature(hdr, s, bm) == \
            host.verify_header_signature(hdr, s, bm)
    assert DV.COUNTERS["agg_verify"] > before  # device branch really ran


def test_engine_device_batch_replay_matches_host(committee):
    keys, serialized = committee
    before = DV.COUNTERS["batch_verify"]
    dev = Engine(_provider(serialized), device=True)
    host = Engine(_provider(serialized), device=False)
    headers = []
    prev_hash = bytes(32)
    for n in range(12):
        h = Header(
            shard_id=0, block_num=200 + n, epoch=3, view_id=200 + n,
            parent_hash=prev_hash,
        )
        signers = [0, 1, 2, 3] if n % 3 else [0, 1, 2]
        sig, bitmap = _sign_header(h, keys, signers)
        headers.append((h, sig, bitmap))
        prev_hash = h.hash()
    items = list(headers)
    # corrupt two entries: swapped sig, truncated quorum
    items[4] = (items[4][0], items[3][1], items[4][2])
    bad_sig, bad_bm = _sign_header(items[7][0], keys, [1])
    items[7] = (items[7][0], bad_sig, bad_bm)
    got = dev.verify_headers_batch(items)
    want = host.verify_headers_batch(items)
    assert got == want
    assert got[4] is False and got[7] is False
    assert sum(got) == 10
    assert DV.COUNTERS["batch_verify"] > before


def test_fbft_validator_device_branch(committee):
    """Validator._verify_proof device branch: committee table built
    lazily, fused agg_verify consulted, decision matches host."""
    from harmony_tpu.consensus import fbft as FB
    from harmony_tpu.consensus import quorum as Q
    from harmony_tpu.multibls import PrivateKeys

    keys, serialized = committee
    payload = b"fbft-device-branch-payload-32byt"
    sigs = [k.sign_hash(payload) for k in keys[:3]]
    agg = B.aggregate_sigs(sigs)
    mask = Mask([k.pub.point for k in keys])
    for i in range(3):
        mask.set_bit(i, True)
    proof = agg.bytes + mask.mask_bytes()
    cfg = FB.RoundConfig(committee=serialized, block_num=1, view_id=0)

    def mk_validator():
        return FB.Validator(
            PrivateKeys.from_keys([keys[0]]), cfg,
            Q.Decider(Q.Policy.UNIFORM, serialized),
        )

    def mk_msg(pl):
        return FB.FBFTMessage(
            msg_type=FB.MsgType.PREPARED, view_id=0, block_num=1,
            block_hash=b"\xab" * 32, sender_pubkeys=[serialized[0]],
            payload=pl,
        )

    before = DV.COUNTERS["agg_verify"]
    v = mk_validator()
    assert v._verify_proof(mk_msg(proof), payload)
    assert DV.COUNTERS["agg_verify"] > before
    # flipped bitmap bit -> aggregate mismatch -> reject
    bad = bytearray(proof)
    bad[-1] ^= 0x08
    assert not v._verify_proof(mk_msg(bytes(bad)), payload)
    DV.use_device(False)
    try:
        v2 = mk_validator()
        assert v2._verify_proof(mk_msg(proof), payload)
        assert not v2._verify_proof(mk_msg(bytes(bad)), payload)
    finally:
        DV.use_device(True)
