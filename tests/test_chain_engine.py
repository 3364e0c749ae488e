"""Header model + engine verification tests, including a mini chain
replay through the batched device path (BASELINE config #5 in miniature).

Engine runs host-mode (device=False) here: this image's XLA persistent cache aborts deserializing the big pairing executables (see tests/conftest.py); the device path's correctness is covered by the ops parity suite and runs on real TPU via bench/__graft_entry__."""

import pytest

from harmony_tpu import bls as B
from harmony_tpu.chain.engine import Engine, EpochContext
from harmony_tpu.chain.header import Header
from harmony_tpu.consensus.mask import Mask
from harmony_tpu.consensus.signature import construct_commit_payload
from harmony_tpu.multibls import PrivateKeys

N_KEYS = 4


@pytest.fixture(scope="module")
def committee():
    keys = [B.PrivateKey.generate(bytes([30 + i])) for i in range(N_KEYS)]
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


def test_header_hash_includes_carried_commit_proof():
    """Reference semantics (block/v3/header.go:67-68): the PARENT's
    commit sig/bitmap are ordinary header fields, fixed at proposal —
    the signed hash commits to them."""
    h = Header(shard_id=0, block_num=5, epoch=1, view_id=5)
    base = h.hash()
    h.last_commit_sig = b"x" * 96
    h.last_commit_bitmap = b"\x0f"
    assert h.hash() != base  # proof is part of the hashed fields
    h2 = Header(shard_id=0, block_num=6, epoch=1, view_id=5)
    assert h2.hash() != base


def test_header_versions_hash_distinctly():
    kw = dict(shard_id=1, block_num=7, epoch=2, view_id=7)
    hashes = {Header(version=v, **kw).hash() for v in ("v0", "v1", "v2", "v3")}
    assert len(hashes) == 4  # tagged envelope separates versions
    import pytest

    with pytest.raises(ValueError):
        Header(version="v9", **kw).hash()


def test_header_rawdb_roundtrip_all_versions():
    from harmony_tpu.core import rawdb

    for v in ("v0", "v1", "v2", "v3"):
        h = Header(
            shard_id=2, block_num=9, epoch=1, view_id=9,
            parent_hash=b"\x01" * 32, root=b"\x02" * 32,
            last_commit_sig=b"s" * 96, last_commit_bitmap=b"\x0f",
            vrf=b"vrf-bytes", shard_state=b"ss", cross_links=b"cl",
            slashes=b"sl", version=v,
        )
        back = rawdb.decode_header(rawdb.encode_header(h))
        assert back == h
        assert back.hash() == h.hash()


def test_verify_header_signature_and_cache(committee):
    keys, serialized = committee
    eng = Engine(_provider(serialized), device=False)
    h = Header(shard_id=0, block_num=10, epoch=2, view_id=10)
    sig, bitmap = _sign_header(h, keys, [0, 1, 2, 3])
    assert eng.verify_header_signature(h, sig, bitmap)
    # cached second call (host-only fast path)
    assert eng.verify_header_signature(h, sig, bitmap)
    # insufficient quorum: only 2 of 4 (threshold 2*4//3+1 = 3)
    sig2, bitmap2 = _sign_header(h, keys, [0, 1])
    assert not eng.verify_header_signature(h, sig2, bitmap2)
    # signature/bitmap mismatch
    sig3, _ = _sign_header(h, keys, [0, 1, 2])
    assert not eng.verify_header_signature(h, sig3, bitmap)


def test_verify_seal_via_child(committee):
    keys, serialized = committee
    eng = Engine(_provider(serialized), device=False)
    parent = Header(shard_id=0, block_num=20, epoch=2, view_id=20)
    sig, bitmap = _sign_header(parent, keys, [0, 1, 2])
    child = Header(
        shard_id=0,
        block_num=21,
        epoch=2,
        view_id=21,
        parent_hash=parent.hash(),
        last_commit_sig=sig,
        last_commit_bitmap=bitmap,
    )
    assert eng.verify_seal(parent, child)
    assert not eng.verify_seal(child, child)  # proof is for the parent


def test_batched_replay(committee):
    keys, serialized = committee
    eng = Engine(_provider(serialized), device=False)
    headers = []
    prev_hash = bytes(32)
    for n in range(5):
        h = Header(
            shard_id=0, block_num=100 + n, epoch=3, view_id=100 + n,
            parent_hash=prev_hash,
        )
        sig, bitmap = _sign_header(h, keys, [0, 1, 2, 3])
        headers.append((h, sig, bitmap))
        prev_hash = h.hash()
    # corrupt one: replace block 102's sig with block 101's
    items = list(headers)
    items[2] = (items[2][0], items[1][1], items[2][2])
    results = eng.verify_headers_batch(items)
    assert results == [True, True, False, True, True]
    # second replay: everything good is cache-hit (no device work needed)
    results2 = eng.verify_headers_batch(
        [headers[0], headers[1], headers[3], headers[4]]
    )
    assert results2 == [True] * 4


@pytest.fixture
def twin_device(monkeypatch):
    """The engine's device branch on the twin kernels (no XLA), with
    the stage profiler dark until a test arms it."""
    from harmony_tpu import device as DV
    from harmony_tpu import prof

    monkeypatch.setenv("HARMONY_KERNEL_TWIN", "1")
    DV.use_device(True)
    prof.reset()
    yield DV
    prof.reset()
    DV.use_device(None)


def _stage_counts():
    from harmony_tpu import prof

    return {k: v["count"] for k, v in prof.stage_summary().items()}


def _dispatches(DV):
    return DV.COUNTERS["batch_verify"] + DV.COUNTERS["agg_verify"]


def test_batched_replay_records_every_stage_when_armed(committee,
                                                        twin_device):
    """Armed, each decoded header records sig_decode, mask and
    quorum_tally once; each header past the tally one hash_to_g2; each
    device program one device_prep; header_hash is taken at the cache
    key, the commit payload and the verified-cache insert.  The
    decisions are the same armed and dark."""
    from harmony_tpu import prof

    keys, serialized = committee
    headers = [Header(shard_id=0, block_num=300 + n, epoch=4,
                      view_id=300 + n) for n in range(5)]
    items = [(h, *_sign_header(h, keys, [0, 1, 2, 3])) for h in headers]
    items[1] = (headers[1], *_sign_header(headers[1], keys, [0, 1]))
    items[3] = (headers[3], items[2][1], items[3][2])  # forged
    want = [True, False, True, False, True]
    dark = Engine(_provider(serialized), device=True)
    assert dark.verify_headers_batch(items) == want
    assert _stage_counts() == {}
    prof.configure(enabled=True)
    before = _dispatches(twin_device)
    armed = Engine(_provider(serialized), device=True)
    assert armed.verify_headers_batch(items) == want
    dispatches = _dispatches(twin_device) - before
    assert dispatches >= 1
    assert _stage_counts() == {
        "header_hash": 5 + 4 + 3, "sig_decode": 5, "mask": 5,
        "quorum_tally": 5, "hash_to_g2": 4, "device_prep": dispatches,
    }


def test_single_seal_check_records_every_stage_when_armed(committee,
                                                          twin_device):
    from harmony_tpu import prof

    keys, serialized = committee
    h = Header(shard_id=0, block_num=400, epoch=4, view_id=400)
    sig, bitmap = _sign_header(h, keys, [0, 1, 2])
    short_sig, short_bitmap = _sign_header(h, keys, [0, 3])
    cases = [(sig, bitmap), (short_sig, short_bitmap), (short_sig, bitmap)]
    want = [True, False, False]
    dark = Engine(_provider(serialized), device=True)
    assert [dark.verify_header_signature(h, s, b) for s, b in cases] == want
    prof.configure(enabled=True)
    armed = Engine(_provider(serialized), device=True)
    before = _dispatches(twin_device)
    assert armed.verify_header_signature(h, sig, bitmap)
    assert _dispatches(twin_device) - before == 1
    assert _stage_counts() == {
        "header_hash": 2, "sig_decode": 1, "mask": 1, "quorum_tally": 1,
        "hash_to_g2": 1, "device_prep": 1,
    }
    armed = Engine(_provider(serialized), device=True)
    assert [armed.verify_header_signature(h, s, b)
            for s, b in cases] == want
