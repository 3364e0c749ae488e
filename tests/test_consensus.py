"""Host-side consensus-layer tests: Dec, mask, payloads, votepower, quorum."""

import pytest

from harmony_tpu.consensus import quorum as Q
from harmony_tpu.consensus import signature as SIG
from harmony_tpu.consensus import votepower as VP
from harmony_tpu.consensus.mask import Mask
from harmony_tpu.numeric import Dec, new_dec, one_dec, zero_dec
from harmony_tpu.ref import bls as RB
from harmony_tpu.ref import curve as RC


# --- Dec -------------------------------------------------------------------


def test_dec_basics():
    a = Dec.from_str("1.5")
    b = Dec.from_str("2.5")
    assert a.add(b).equal(new_dec(4))
    assert b.sub(a).equal(one_dec())
    assert a.mul(b).equal(Dec.from_str("3.75"))
    assert new_dec(1).quo(new_dec(3)).raw == 333333333333333333
    assert new_dec(2).quo(new_dec(3)).raw == 666666666666666667


def test_dec_bankers_rounding():
    # 0.5 ulp cases round to even
    x = Dec(5)  # 5e-18
    tenth = Dec.from_str("0.1")
    # 5e-18 * 0.1 = 5e-19 -> half of an ulp -> rounds to 0 (even)
    assert x.mul(tenth).raw == 0
    y = Dec(15)
    # 1.5e-18 ulp product -> rounds to 2 (even)
    assert y.mul(tenth).raw == 2


def test_dec_negative_and_truncate():
    a = Dec.from_str("-1.7")
    assert a.truncate_int() == -1
    assert a.round_int() == -2
    assert a.neg().equal(Dec.from_str("1.7"))
    assert Dec.from_str("5.0").quo_truncate(new_dec(3)).raw == 1666666666666666666


# --- payloads --------------------------------------------------------------


def test_commit_payload_layout():
    h = bytes(range(32))
    p = SIG.construct_commit_payload(h, 0x1122334455667788, 0x99, True)
    assert p[:8] == bytes.fromhex("8877665544332211")  # LE block number
    assert p[8:40] == h
    assert p[40:48] == (0x99).to_bytes(8, "little")
    assert len(p) == 48
    p2 = SIG.construct_commit_payload(h, 1, 2, False)
    assert len(p2) == 40  # pre-staking: no view id
    with pytest.raises(ValueError):
        SIG.construct_commit_payload(b"short", 1, 2, True)


# --- mask ------------------------------------------------------------------


@pytest.fixture(scope="module")
def committee():
    sks = [RB.keygen(bytes([i])) for i in range(10)]
    return [RB.pubkey(sk) for sk in sks]


def test_mask_bit_semantics(committee):
    m = Mask(committee)
    assert m.bytes_len() == 2  # 10 keys -> 2 bytes
    m.set_bit(0, True)
    m.set_bit(7, True)
    m.set_bit(8, True)
    # little-endian: bit i -> byte i>>3, bit (i & 7)
    assert m.mask_bytes() == bytes([0b10000001, 0b00000001])
    assert m.count_enabled() == 3
    m.set_bit(7, False)
    assert m.mask_bytes() == bytes([0b00000001, 0b00000001])
    with pytest.raises(IndexError):
        m.set_bit(10, True)


def test_mask_set_mask_length_check(committee):
    m = Mask(committee)
    with pytest.raises(ValueError):
        m.set_mask(b"\x01")  # wrong length
    m.set_mask(bytes([0xFF, 0x03]))
    assert m.count_enabled() == 10


def test_mask_set_key_and_signers(committee):
    m = Mask(committee)
    m.set_key(RB.pubkey_to_bytes(committee[3]), True)
    assert m.index_enabled() == [3]
    assert m.get_signed_pubkeys() == [committee[3]]


def test_mask_aggregate_host_matches_reference(committee):
    m = Mask(committee)
    for i in (0, 2, 5, 9):
        m.set_bit(i, True)
    expect = None
    for i in (0, 2, 5, 9):
        expect = RC.g1.add(expect, committee[i])
    assert m.aggregate_public(device=False) == expect


# --- votepower -------------------------------------------------------------


def _slots():
    # 2 harmony slots + 3 stakers with stakes 100, 200, 700
    slots = [
        VP.Slot("hmy1", b"k0", None),
        VP.Slot("hmy2", b"k1", None),
        VP.Slot("s1", b"k2", new_dec(100)),
        VP.Slot("s2", b"k3", new_dec(200)),
        VP.Slot("s3", b"k4", new_dec(700)),
    ]
    return slots


def test_roster_sums_to_one():
    r = VP.compute_roster(
        _slots(), Dec.from_str("0.49"), Dec.from_str("0.51")
    )
    total = r.our_voting_power.add(r.their_voting_power)
    assert total.equal(one_dec())
    assert r.harmony_slot_count == 2
    # harmony nodes split 0.49 equally
    assert r.voters[b"k0"].overall_percent.equal(Dec.from_str("0.245"))
    # staker with 70% of stake gets 0.7 * 0.51 plus the rounding residue
    v = r.voters[b"k4"]
    assert v.overall_percent.sub(Dec.from_str("0.357")).raw in (0, 1, -1)


def test_roster_all_harmony_sums_to_one():
    # no external stakers: the residue lands on the last Harmony voter
    # and the invariant must still hold exactly
    slots = [VP.Slot(f"h{i}", bytes([i]), None) for i in range(3)]
    r = VP.compute_roster(slots, one_dec(), zero_dec())
    assert r.our_voting_power.add(r.their_voting_power).equal(one_dec())
    # the last slot absorbed the 1e-18 residue
    assert r.voters[bytes([2])].overall_percent.gt(
        r.voters[bytes([0])].overall_percent
    )


def test_roster_residue_to_last_staker():
    # 3 stakers with equal stake: 1/3 each cannot sum exactly; the residue
    # lands on the last one
    slots = [
        VP.Slot("a", b"a", new_dec(1)),
        VP.Slot("b", b"b", new_dec(1)),
        VP.Slot("c", b"c", new_dec(1)),
    ]
    r = VP.compute_roster(slots, zero_dec(), one_dec())
    assert r.our_voting_power.add(r.their_voting_power).equal(one_dec())
    assert r.voters[b"c"].overall_percent.gt(r.voters[b"a"].overall_percent)


# --- quorum ----------------------------------------------------------------


def test_uniform_quorum():
    keys = [bytes([i]) for i in range(10)]
    d = Q.Decider(Q.Policy.UNIFORM, keys)
    # threshold = 2*10//3 + 1 = 7
    for i in range(6):
        d.submit_vote(
            Q.Phase.PREPARE, Q.Ballot(keys[i], b"h", b"s", 1, 0)
        )
    assert not d.is_quorum_achieved(Q.Phase.PREPARE)
    d.submit_vote(Q.Phase.PREPARE, Q.Ballot(keys[6], b"h", b"s", 1, 0))
    assert d.is_quorum_achieved(Q.Phase.PREPARE)
    # duplicate ballots are rejected
    assert not d.submit_vote(
        Q.Phase.PREPARE, Q.Ballot(keys[6], b"h", b"s", 1, 0)
    )
    assert d.count(Q.Phase.PREPARE) == 7
    # mask-based check agrees with the ballot path at exact quorum
    assert not d.is_quorum_achieved_by_mask([1] * 6 + [0] * 4)
    assert d.is_quorum_achieved_by_mask([1] * 7 + [0] * 3)


def test_staked_quorum():
    slots = [
        VP.Slot("h", b"k0", None),
        VP.Slot("a", b"k1", new_dec(400)),
        VP.Slot("b", b"k2", new_dec(600)),
    ]
    roster = VP.compute_roster(
        slots, Dec.from_str("0.30"), Dec.from_str("0.70")
    )
    keys = [b"k0", b"k1", b"k2"]
    d = Q.Decider(Q.Policy.STAKED, keys, roster)
    # k2 alone: 0.6*0.7 = 0.42 < 2/3
    d.submit_vote(Q.Phase.COMMIT, Q.Ballot(b"k2", b"h", b"s", 1, 0))
    assert not d.is_quorum_achieved(Q.Phase.COMMIT)
    # + harmony 0.30 => 0.72 > 2/3
    d.submit_vote(Q.Phase.COMMIT, Q.Ballot(b"k0", b"h", b"s", 1, 0))
    assert d.is_quorum_achieved(Q.Phase.COMMIT)
    assert d.is_quorum_achieved_by_mask([1, 0, 1])
    assert d.is_quorum_achieved_by_mask([0, 1, 1])  # 0.28 + 0.42 = 0.70
    assert not d.is_quorum_achieved_by_mask([1, 1, 0])  # 0.30 + 0.28 = 0.58


def test_staked_quorum_exact_boundary():
    # power exactly 2/3 must NOT reach quorum (strictly greater)
    slots = [
        VP.Slot("a", b"a", new_dec(2)),
        VP.Slot("b", b"b", new_dec(1)),
    ]
    roster = VP.compute_roster(slots, zero_dec(), one_dec())
    d = Q.Decider(Q.Policy.STAKED, [b"a", b"b"], roster)
    d.submit_vote(Q.Phase.COMMIT, Q.Ballot(b"a", b"h", b"s", 1, 0))
    # a's power: 2/3 rounded = 0.666666666666666667 > 2/3's Dec value
    # (0.666666666666666667) -> equal, not greater
    assert not d.is_quorum_achieved(Q.Phase.COMMIT)


# --- stages of the validator's proof check ---------------------------------


def test_on_committed_records_every_stage_when_armed(monkeypatch):
    """Armed, one COMMITTED proof check on the device branch (twin
    kernels, no XLA) records each host stage once; the decisions on a
    good, a forged and a short proof are the same armed and dark."""
    from harmony_tpu import bls as B
    from harmony_tpu import device as DV
    from harmony_tpu import prof
    from harmony_tpu.consensus import fbft as FB
    from harmony_tpu.multibls import PrivateKeys

    monkeypatch.setenv("HARMONY_KERNEL_TWIN", "1")
    DV.use_device(True)
    prof.reset()
    try:
        keys = [B.PrivateKey.generate(bytes([90 + i])) for i in range(4)]
        serialized = [k.pub.bytes for k in keys]
        cfg = FB.RoundConfig(committee=serialized, block_num=7, view_id=3)
        block_hash = b"\xcd" * 32

        def proof(signers, block=block_hash):
            payload = SIG.construct_commit_payload(block, 7, 3, True)
            agg = B.aggregate_sigs([keys[i].sign_hash(payload)
                                    for i in signers])
            mask = Mask([k.pub.point for k in keys])
            for i in signers:
                mask.set_bit(i, True)
            return agg.bytes + mask.mask_bytes()

        def check(pl):
            v = FB.Validator(PrivateKeys.from_keys([]), cfg,
                             Q.Decider(Q.Policy.UNIFORM, serialized))
            return v.on_committed(FB.FBFTMessage(
                msg_type=FB.MsgType.COMMITTED, view_id=3, block_num=7,
                block_hash=block_hash, sender_pubkeys=[serialized[0]],
                payload=pl))

        proofs = [proof([0, 1, 2]), proof([0, 1, 2], b"\xee" * 32),
                  proof([0, 1])]
        dark = [check(p) for p in proofs]
        assert dark == [True, False, False]
        assert prof.stage_summary() == {}
        prof.configure(enabled=True)
        before = DV.COUNTERS["agg_verify"]
        assert check(proofs[0])
        assert DV.COUNTERS["agg_verify"] - before == 1
        assert {k: v["count"] for k, v in prof.stage_summary().items()} \
            == {"mask": 1, "quorum_tally": 1, "sig_decode": 1,
                "hash_to_g2": 1, "device_prep": 1}
        assert [check(p) for p in proofs] == dark
    finally:
        prof.reset()
        DV.use_device(None)
