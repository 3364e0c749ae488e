"""The quorum-proof semantics a Harmony validator enforces, written
plainly (reference: harmony-one/harmony consensus/validator.go onCommitted,
internal/chain/engine.go VerifyHeaderSignature, internal/chain/sig.go).

A proof is ``sig(96 B) || bitmap``.  It is accepted exactly when the
bitmap has the committee's length, the signature decodes to a G2
point of the r-torsion subgroup, the signers (bit i = bit i&7 of byte
i>>3) hold more than 2/3 of the voting power, and
e(-G1, sig) * e(sum of the signers' keys, H(payload)) == 1.
"""

from __future__ import annotations

import struct
from fractions import Fraction

from . import fields as F
from .curve import G1_GEN, g1
from .hash_to_curve import hash_to_g2
from .keccak import keccak256
from .pairing import multi_pairing
from .serialize import g2_decompress

SIG_BYTES = 96
TWO_THIRDS = Fraction(2, 3)


def commit_payload(block_hash: bytes, block_num: int, view_id: int,
                   is_staking: bool = True) -> bytes:
    """LE64(number) || hash || LE64(view) (consensus/signature/signature.go);
    the view is left out before the staking era."""
    out = struct.pack("<Q", block_num) + block_hash
    return out + struct.pack("<Q", view_id) if is_staking else out


def voting_power(stakes: list, harmony_share: Fraction) -> list:
    """Each slot's share of the vote (consensus/votepower/roster.go):
    Harmony-operated slots (stake None) split ``harmony_share``
    equally, the others split the rest by effective stake."""
    n_hmy = sum(s is None for s in stakes)
    total = sum(s for s in stakes if s is not None)
    ext = 1 - harmony_share
    return [harmony_share / n_hmy if s is None else ext * Fraction(s, total)
            for s in stakes]


def bits(bitmap: bytes, n: int) -> list:
    return [(bitmap[i >> 3] >> (i & 7)) & 1 for i in range(n)]


def check_proof(committee: list, power: list, payload: bytes,
                proof: bytes, pairing: bool = True) -> bool:
    """``committee``: the slots' G1 keys (affine tuples), in slot order;
    ``power``: their voting power (Fractions summing to 1).
    ``pairing=False`` leaves the signature check out: the benchmark's
    control, never a reference answer."""
    n = len(committee)
    sig_bytes, bitmap = proof[:SIG_BYTES], proof[SIG_BYTES:]
    if len(sig_bytes) != SIG_BYTES or len(bitmap) != (n + 7) // 8:
        return False
    try:
        sig = g2_decompress(sig_bytes)
    except ValueError:
        return False
    if sig is None:
        return False
    signed = bits(bitmap, n)
    if sum(p for p, b in zip(power, signed) if b) <= TWO_THIRDS:
        return False
    apk = None
    for pk, b in zip(committee, signed):
        if b:
            apk = g1.add(apk, pk)
    if apk is None:
        return False
    if not pairing:
        return True
    h = hash_to_g2(payload)
    return multi_pairing([(g1.neg(G1_GEN), sig), (apk, h)]) == F.FP12_ONE


# -- block headers ------------------------------------------------------------

def rlp(item) -> bytes:
    """Canonical RLP of bytes / non-negative ints / lists."""
    if isinstance(item, list):
        body = b"".join(rlp(x) for x in item)
        return _prefix(len(body), 0xC0) + body
    if isinstance(item, int):
        item = item.to_bytes((item.bit_length() + 7) // 8, "big")
    if len(item) == 1 and item[0] < 0x80:
        return item
    return _prefix(len(item), 0x80) + item


def _prefix(length: int, base: int) -> bytes:
    if length <= 55:
        return bytes([base + length])
    lb = length.to_bytes((length.bit_length() + 7) // 8, "big")
    return bytes([base + 55 + len(lb)]) + lb


# v3 header field order (block/v3/header.go), wrapped in the taggedrlp
# envelope [b"HmnyTgd", b"v3", fields]; the hash is keccak-256 of it.
HEADER_V3_FIELDS = (
    "parent_hash", "root", "tx_root", "receipt_root", "out_cx_root",
    "block_num", "timestamp", "extra", "view_id", "epoch", "shard_id",
    "last_commit_sig", "last_commit_bitmap", "shard_state", "vrf", "vdf",
    "cross_links", "slashes",
)


def header_hash(fields: dict) -> bytes:
    body = [fields[k] for k in HEADER_V3_FIELDS]
    return keccak256(rlp([b"HmnyTgd", b"v3", body]))
